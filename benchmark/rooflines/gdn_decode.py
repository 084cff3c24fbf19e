"""The acting steps' delta-rule state updates (one token an env a step,
``unroll`` steps a fused step): least work from shapes.

A delta-rule layer, B envs: a token reads the env's matrix state ``[H, V,
K]`` float32 (2.1 MiB at the published widths), decays it, reads it
against the token's key, writes the corrected value's outer product and
reads it out against the query: the state crosses HBM once each way a
token a layer, which nothing that carries a state between steps of a
rollout can avoid; q, k, v, the two gates and o of one token are a
thousandth of it and are counted too.  Three multiply-adds a (value,
key) (the read against the key, the write, the read-out): against 4
bytes moved each way the step is bound by its bytes.

The work is marked by scope: ops under ``gdn/scan`` that are under
``rollout``.
"""

from benchmark.lib import readers

scan = readers.roofline_module("gdn_scan")


def in_update(op_name) -> bool:
    """(``scope_roofline``'s name for the matcher.)  The decode's."""
    return bool(op_name and scan._SCOPE.search(op_name)
                and scan._ROLLOUT.search(op_name))


def least(ctx):
    """{"flops", "bytes"} of one step's acting state updates."""
    found = scan.sizes(ctx)
    if found is None:
        return None
    layers, envs, heads, keys, values, _ = found
    steps = int(ctx.flags["unroll_length"])
    state = envs * heads * values * keys
    small = envs * heads * (2 * keys + 2 * values + 2)
    return {"flops": layers * steps * 6.0 * state,
            "bytes": layers * steps * 4.0 * (2 * state + small)}
