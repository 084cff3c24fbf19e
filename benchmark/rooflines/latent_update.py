"""The update's latent attention proper (T = unroll + 1 queries an env
through a ring of latent rows and the call's own rows, forward and
backward once each, no rematerialized forward): least work from shapes,
the same whatever implements the pass.

A layer, B envs: query ``t`` of a head scores the ``live`` rows of its
episode (the ring's and the call's own before it) and sums their
values; the backward gives the queries' gradient, the own rows' and
Wkvb's, the ring none.  Two forms, both counted a pass, the lesser
taken (``rooflines/latent_decode.py`` has them): ``absorbed``, ``heads
x (2 rank + rope)`` MACs a (query, live row); ``up_projected``, each
row some query of its env sees up-projected once a pass (``rank x heads
x (nope + v)``) and ``heads x (nope + rope + v)`` a (query, live row).
The backward is counted as one more pass of the form (d weights and d
query, as deep as scores and values; what d Wkvb costs the up-projected
form beyond that is left out: a lower count, a lower share).

Bytes, each pass: the ring's rows some query of the env sees once (key
and value are the same bytes), the queries, the own rows, the output
(d output), float32.

``live`` is worked out for the updates the trace caught whole
(``rooflines/latent_decode.py traced_updates``, ``live_rows``), not
taken from the steady ``mean_context``.

The work is marked by scope: ops under ``attention/latent/attend`` and
not under ``rollout``.
"""

import numpy as np

from benchmark.lib import readers

decode = readers.roofline_module("latent_decode")


def in_update(op_name) -> bool:
    return bool(op_name and decode._SCOPE.search(op_name)
                and not decode._ROLLOUT.search(op_name))


def least_at(ctx, update: int):
    """``rooflines/latent_decode.py least_at`` for the update's pass."""
    cfg, flags = ctx.config, ctx.flags
    heads, rank, nope, rope, v_dim, item = decode.sizes(cfg)
    queries = int(flags["unroll_length"]) + 1
    live = decode.live_rows(ctx, update, np.arange(queries))  # [B, T]
    envs, pairs = live.shape[0], float(live.sum())
    # the rows of an env some query sees: the most any does, and the own
    seen = float(np.maximum(live.max(axis=1), queries).sum())
    absorbed = pairs * heads * (2 * rank + rope)
    up_projected = (seen * rank * heads * (nope + v_dim)
                    + pairs * heads * (nope + rope + v_dim))
    layers, passes = cfg["num_hidden_layers"], 2.0
    per_pass = (item * (rank + rope) * seen
                + envs * queries * (item * heads * (rank + rope)
                                    + 4.0 * heads * min(rank, v_dim)))
    return ({"flops": 2.0 * layers * passes * min(absorbed, up_projected),
             "bytes": layers * passes * per_pass},
            pairs / (envs * queries), absorbed, up_projected)


def least(ctx):
    """{"flops", "bytes"} of one step's update attention; None for a
    configuration with no latent cache or a program without the
    counter."""
    return decode.mean_over(ctx, "latent update", least_at)
