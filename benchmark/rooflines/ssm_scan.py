"""The update's selective scans (a state-space layer's recurrence over
the unroll, forward and backward once each, no rematerialized forward):
least work.

A scan layer, B envs x T = unroll + 1 tokens, D channels (``mamba_expand
* hidden_size``), N states: the forward reads x and delta ``[B, T, D]``,
B_t and C_t ``[B, T, N]``, A ``[N, D]``, the skip and the state it
starts from ``[B, N, D]``, and writes y ``[B, T, D]`` and the last
state; the backward reads x, delta and d y again with B_t and C_t, and
writes d x, d delta, d B_t, d C_t, d A, d skip and d state.  All float32
(the configuration states the scan's state so).  The states between
tokens are the implementation's business: an algorithm that keeps them
on the chip moves none, so none is counted.  Elementwise work a (token,
channel, state): 7 forward (exp, two products into it, the decay, the
input's product and add, the output's product and add), 19 backward (the
state again, and every operand's gradient); no matrix product computes
it, so against the chip's matrix peak the scan is bound by its bytes.

The work is marked by scope: ops under ``ssm/scan`` and not under
``rollout`` (the rollout's one-token steps are the decode's, not a
scan's).
"""

import re

_SCOPE = re.compile(r"(?<![A-Za-z0-9_])ssm/scan(?![A-Za-z0-9_])")
_ROLLOUT = re.compile(r"(?<![A-Za-z0-9_])rollout(?![A-Za-z0-9_])")
STATE_SPACE = "state_space"


def in_update(op_name) -> bool:
    return bool(op_name and _SCOPE.search(op_name)
                and not _ROLLOUT.search(op_name))


def least(ctx):
    """{"flops", "bytes"} of one step's update scans, from shapes; None
    for a configuration with no scan layer."""
    cfg, flags = ctx.config, ctx.flags
    layers = sum(1 for entry in cfg.get("layer_kinds", ())
                 if entry["kind"] == STATE_SPACE)
    if not layers:
        return None
    envs = int(flags["batch_size"]) // int(getattr(ctx, "chips", 1))
    tokens = int(flags["unroll_length"]) + 1
    width = cfg["mamba_expand"] * cfg["hidden_size"]
    states = cfg["mamba_d_state"]
    per_token = envs * tokens * width            # x, delta, y, d y, ...
    per_column = envs * tokens * states          # B_t, C_t and theirs
    small = states * width + width               # A and the skip
    state = envs * states * width
    forward = 4.0 * (3 * per_token + 2 * per_column + small + 2 * state)
    backward = 4.0 * (5 * per_token + 4 * per_column + 2 * small
                      + 2 * state)
    flops = (7.0 + 19.0) * envs * tokens * width * states
    return {"flops": layers * flops, "bytes": layers * (forward + backward)}
