"""gdn_decode_roofline.fused: the least time the chip could take for the acting steps' delta-rule state updates (each env's matrix state read and written once a token a layer, unroll tokens a step, from shapes: benchmark/rooflines/gdn_decode.py) over the device self time a step spends in the ops under scope gdn/scan that are under rollout, over the step runs that lie whole inside the trace, mean over chips (benchmark/lib/scope_roofline.py). None on a program with no such scope."""

from benchmark.lib import scope_roofline


def read(ctx):
    return scope_roofline.share(ctx, "gdn_decode",
                                "decode delta-rule state updates")
