"""The update's delta-rule scans (a Gated-DeltaNet layer's recurrence over
the unroll, forward and backward once each, no rematerialized forward):
least work, the same whatever implements the pass.

A delta-rule layer, B envs x T = unroll + 1 tokens, H heads with keys of
K and values of V numbers, chunks of C tokens (the configuration's
``chunk_size``): the forward reads q and k ``[B, T, H, K]``, v ``[B, T,
H, V]``, the decay and the write strength ``[B, T, H]`` and the state it
starts from ``[B, H, V, K]``, and writes o and the last state; the
backward reads q, k, v, the two gates and d o, and writes d q, d k, d v,
the gates' gradients and d state.  A chunked scan cannot keep a matrix
state a head on the chip from the forward to the backward (2.1 MiB an
env a layer), so the state each WHOLE chunk starts from is counted once
a pass: written by the forward, read by the backward; the states between
tokens are never in HBM and none is counted.  All float32 (the
configuration states the recurrence so).

Arithmetic: the recurrence's own three products a token a head, ``2 V
K`` each (the state read against the key, the rank-one write, the
read-out against the query), NOT any chunk size's: a chunk's triangular
solve and its ``[C, C]`` products are an implementation's, and a faster
one may do without them.  The backward is counted as two passes of the
forward's, as a matrix product's is.  The greater of the two bounds is
the least time.

The work is marked by scope: ops under ``gdn/scan`` and not under
``rollout`` (the rollout's one-token steps are the decode's:
``rooflines/gdn_decode.py``).
"""

import re

_SCOPE = re.compile(r"(?<![A-Za-z0-9_])gdn/scan(?![A-Za-z0-9_])")
_ROLLOUT = re.compile(r"(?<![A-Za-z0-9_])rollout(?![A-Za-z0-9_])")


def in_update(op_name) -> bool:
    return bool(op_name and _SCOPE.search(op_name)
                and not _ROLLOUT.search(op_name))


def sizes(ctx):
    """(delta-rule layers, envs a chip, heads, a key's numbers, a
    value's, tokens a chunk); None for a configuration with no
    delta-rule layer."""
    cfg, flags = ctx.config, ctx.flags
    kinds = cfg.get("layer_types") or ()
    layers = list(kinds).count("linear_attention")
    if not layers or not cfg.get("linear_num_key_heads"):
        return None
    envs = int(flags["batch_size"]) // int(getattr(ctx, "chips", 1))
    return (layers, envs, cfg["linear_num_key_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["chunk_size"])


def least(ctx):
    """{"flops", "bytes"} of one step's update scans, from shapes."""
    found = sizes(ctx)
    if found is None:
        return None
    layers, envs, heads, keys, values, chunk = found
    tokens = int(ctx.flags["unroll_length"]) + 1
    chunks = max(1, tokens // chunk)             # whole chunks
    per_key = envs * tokens * heads * keys       # q, k and their gradients
    per_value = envs * tokens * heads * values   # v, o, d o, d v
    per_head = envs * tokens * heads             # the two gates, and theirs
    state = envs * heads * values * keys
    forward = 4.0 * (2 * per_key + 2 * per_value + 2 * per_head
                     + (2 + chunks) * state)
    backward = 4.0 * (4 * per_key + 3 * per_value + 4 * per_head
                      + (2 + chunks) * state)
    a_pass = envs * tokens * heads * 3 * 2.0 * values * keys
    return {"flops": layers * 3.0 * a_pass,
            "bytes": layers * (forward + backward)}
