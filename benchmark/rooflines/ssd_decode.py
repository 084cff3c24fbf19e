"""The acting steps' Mamba-2 state updates (one token an env a step,
``unroll`` steps a fused step): least work from shapes.

A scan layer, B envs: a token reads the env's matrix state ``[H, P, N]``
float32 (2 MiB at the published widths), decays it, adds the token's
outer product and writes it back, and reads it out against C_t: the
state crosses HBM once each way a token a layer, which nothing that
carries a state between steps of a rollout can avoid; x, delta, B_t,
C_t and y of one token are a thousandth of it and are counted too.  Two
multiply-adds a (channel, state) (the update and the read-out): against
4 bytes moved each way the step is bound by its bytes.

The work is marked by scope: ops under ``ssd/scan`` that are under
``rollout``.
"""

from benchmark.lib import readers

scan = readers.roofline_module("ssd_scan")


def in_update(op_name) -> bool:
    """(``scope_roofline``'s name for the matcher.)  The decode's."""
    return bool(op_name and scan._SCOPE.search(op_name)
                and scan._ROLLOUT.search(op_name))


def least(ctx):
    """{"flops", "bytes"} of one step's acting state updates."""
    found = scan.sizes(ctx)
    if found is None:
        return None
    layers, envs, heads, dim, groups, states, _ = found
    steps = int(ctx.flags["unroll_length"])
    state = envs * heads * dim * states
    small = envs * (2 * heads * dim + heads + 2 * groups * states)
    return {"flops": layers * steps * 4.0 * state,
            "bytes": layers * steps * 4.0 * (2 * state + small)}
