"""The update's Mamba-2 (SSD) scans (a layer's recurrence over the
unroll, forward and backward once each, no rematerialized forward):
least work, the same whatever implements the pass.

A scan layer, B envs x T = unroll + 1 tokens, H heads of P channels, G
groups of N states, chunks of Q tokens (the source's ``chunk_size``):
the forward reads x ``[B, T, H, P]``, delta ``[B, T, H]``, B_t and C_t
``[B, T, G, N]`` and the state it starts from ``[B, H, P, N]``, and
writes y and the last state; the backward reads x, delta, B_t, C_t and
d y, and writes d x, d delta, d B_t, d C_t and d state.  A chunked scan
cannot keep a matrix state a head on the chip from the forward to the
backward (2 MiB an env a layer), so the state each chunk starts from is
counted once a pass: written by the forward, read by the backward; the
states between tokens are never in HBM and none is counted.  All
float32 (the configuration states the recurrence so).

Arithmetic, the chunked form's matrix products a (env, chunk, head):
``C B^T`` (``2 Q Q N``, shared by the H / G heads of a group), ``((C
B^T) o L) U`` (``2 Q Q P``), ``C S`` and the state's update (``2 Q N
P`` each); the backward is counted as two passes of the forward's, as
a matrix product's is.  The greater of the two bounds is the least
time.

The work is marked by scope: ops under ``ssd/scan`` and not under
``rollout`` (the rollout's one-token steps are the decode's:
``rooflines/ssd_decode.py``).
"""

import re

_SCOPE = re.compile(r"(?<![A-Za-z0-9_])ssd/scan(?![A-Za-z0-9_])")
_ROLLOUT = re.compile(r"(?<![A-Za-z0-9_])rollout(?![A-Za-z0-9_])")


def in_update(op_name) -> bool:
    return bool(op_name and _SCOPE.search(op_name)
                and not _ROLLOUT.search(op_name))


def sizes(ctx):
    """(scan layers, envs a chip, heads, channels a head, groups,
    states, tokens a chunk); None for a configuration with no Mamba-2
    layer."""
    cfg, flags = ctx.config, ctx.flags
    layers = str(cfg.get("hybrid_override_pattern", "")).count("M")
    if not layers or not cfg.get("mamba_num_heads"):
        return None
    envs = int(flags["batch_size"]) // int(getattr(ctx, "chips", 1))
    return (layers, envs, cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"])


def least(ctx):
    """{"flops", "bytes"} of one step's update scans, from shapes."""
    found = sizes(ctx)
    if found is None:
        return None
    layers, envs, heads, dim, groups, states, chunk = found
    tokens = int(ctx.flags["unroll_length"]) + 1
    chunks = -(-tokens // chunk)
    per_token = envs * tokens * heads * dim      # x, y, d y, d x
    per_head = envs * tokens * heads             # delta and its gradient
    per_group = envs * tokens * groups * states  # B_t, C_t and theirs
    state = envs * heads * dim * states
    forward = 4.0 * (2 * per_token + per_head + 2 * per_group
                     + (2 + chunks) * state)
    backward = 4.0 * (3 * per_token + 2 * per_head + 4 * per_group
                      + (2 + chunks) * state)
    a_pass = envs * tokens * heads * (
        2.0 * chunk * dim + 4.0 * states * dim
        + 2.0 * chunk * states * groups / heads)
    return {"flops": layers * 3.0 * a_pass,
            "bytes": layers * (forward + backward)}
