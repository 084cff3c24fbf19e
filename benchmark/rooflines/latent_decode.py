"""The decode's latent attention proper (one query an env through a ring
of latent rows, ``unroll`` times a step in every layer): least work from
shapes, the same whatever implements the pass.

A decode step of a layer, B envs: a query head's scores against the
``live`` rows its episode has left in the ring, and the weighted sum.
Two forms of the same attention, both counted, the lesser taken:

* ``absorbed``: the up-projection taken into the query and out of the
  weighted sum, so a row is the key (``kv_lora_rank + rope`` deep) and
  its first ``kv_lora_rank`` columns the value: ``heads x (2 rank +
  rope)`` MACs a live row;
* ``up_projected``: a live row's whole keys and values made first
  (``rank x heads x (nope + v)`` MACs a row, for ONE query an env), then
  ``heads x (nope + rope + v)`` a row.

Bytes: the live rows once (key and value are the same bytes), the
queries, the own row, the output in float32.

``live`` is not the configuration's steady ``mean_context``: the
window opens while the rings still fill (envs staggered through their
first episode by ``episode / B``, every ring empty at launch), so it is
worked out for the updates the trace caught whole (``traced_updates``:
the program's count of dispatched updates, the harness's own in-flight
depth, the trace's count of whole runs), from the world's shapes, and
the work is the mean over them, as the measured time is.

The work is marked by scope: ops under ``attention/latent/attend`` and
under ``rollout``.
"""

import re

import numpy as np

from benchmark.lib import readers, whole_runs
from benchmark.lib.probe import FUSED_INFLIGHT

_SCOPE = re.compile(r"(?<![A-Za-z0-9_])attention/latent/attend"
                    r"(?![A-Za-z0-9_])")
_ROLLOUT = re.compile(r"(?<![A-Za-z0-9_])rollout(?![A-Za-z0-9_])")


def in_update(op_name) -> bool:
    """(``scope_roofline``'s name for the matcher.)  The decode's."""
    return bool(op_name and _SCOPE.search(op_name)
                and _ROLLOUT.search(op_name))


def traced_updates(ctx):
    """The indices, from 0, of the updates whose step runs lie whole
    inside the trace, oldest first; None on a program without the
    counter.

    The harness stops the profiler inside the retire that closes the
    window (``probe.Probe.on_retire``), and that retire asks for the
    program's preemption drain, which the fused loop takes at the same
    iteration's decision point: nothing is dispatched after it.  So of
    the N updates the program counts (``devtel/learner/updates_total``,
    fetched at the drain), the newest ``FUSED_INFLIGHT - 1`` were still
    in flight when the trace stopped and are cut by its end; the one
    before them, the closing retire's, is the newest whole run; and the
    whole runs the trace holds (``whole_runs.runs``) are it and the
    updates before it, one each."""
    try:
        from scalable_agent_tpu.obs import get_registry
    except ImportError:
        return None
    done = get_registry().snapshot().get("devtel/learner/updates_total")
    if not done:
        return None
    planes = readers.planes(ctx)
    whole = len(whole_runs.runs(ctx, planes[0])) if planes else 1
    newest = int(done) - 1 - (FUSED_INFLIGHT - 1)
    return [max(0, newest - back) for back in range(whole)][::-1]


def live_rows(ctx, update: int, offsets) -> np.ndarray:
    """[B, len(offsets)]: the rows of its own episode an env's query at
    token ``update * unroll + offset`` finds before it (ring and own
    alike), envs staggered as the world staggers them, rings empty at
    launch."""
    flags, world = ctx.flags, ctx.traffic["world"]
    envs = int(flags["batch_size"]) // int(getattr(ctx, "chips", 1))
    length = int(world["episode_length"])
    begun = (np.arange(envs) * (length // envs)) % length
    token = update * int(flags["unroll_length"]) + np.asarray(offsets)
    reached = begun[:, None] + token[None, :]
    # an episode under way at launch has only the tokens since launch
    return np.where(reached >= length, reached % length, token[None, :])


def sizes(cfg):
    return (cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"],
            2 if cfg.get("compute_dtype", "float32") == "bfloat16" else 4)


def least_at(ctx, update: int):
    """({"flops", "bytes"} of the decode attention of the step of update
    ``update``, the live rows a query, MACs a layer in each form)."""
    cfg, flags = ctx.config, ctx.flags
    heads, rank, nope, rope, v_dim, item = sizes(cfg)
    unroll = int(flags["unroll_length"])
    live = live_rows(ctx, update, np.arange(unroll))     # [B, unroll]
    envs, rows = live.shape[0], float(live.sum())
    absorbed = rows * heads * (2 * rank + rope)
    up_projected = rows * (rank * heads * (nope + v_dim)
                           + heads * (nope + rope + v_dim))
    layers = cfg["num_hidden_layers"]
    per_query = envs * unroll * (
        item * heads * (rank + rope)        # the query, absorbed or not
        + item * (rank + rope)              # the own row
        + 4.0 * heads * min(rank, v_dim))   # the output
    return ({"flops": 2.0 * layers * min(absorbed, up_projected),
             "bytes": layers * (item * (rank + rope) * rows + per_query)},
            rows / (envs * unroll), absorbed, up_projected)


def mean_over(ctx, what: str, least_of):
    """The mean of ``least_of(ctx, update)`` over the traced updates,
    said in the run's notes; None for a configuration with no latent
    cache or a program without the counter."""
    updates = traced_updates(ctx)
    if "kv_lora_rank" not in ctx.config or not updates:
        return None
    each = [least_of(ctx, update) for update in updates]
    counts = {key: float(np.mean([one[0][key] for one in each]))
              for key in ("flops", "bytes")}
    live, absorbed, up_projected = (np.mean([one[i] for one in each])
                                    for i in (1, 2, 3))
    ctx.notes.append(
        f"{what} at update(s) {updates}: {live:.0f} live rows a query "
        f"(steady {ctx.config.get('mean_context')}); MACs a layer"
        f"{'' if what.endswith('decode') else ' a pass'} absorbed "
        f"{absorbed:.4g}, up-projected {up_projected:.4g}")
    return counts


def least(ctx):
    """{"flops", "bytes"} of one step's decode attention (``unroll``
    decode steps x the layers)."""
    return mean_over(ctx, "latent decode", least_at)
