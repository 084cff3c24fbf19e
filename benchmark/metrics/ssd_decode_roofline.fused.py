"""ssd_decode_roofline.fused: the least time the chip could take for the acting steps' Mamba-2 state updates (each env's matrix state read and written once a token a layer, unroll tokens a step, from shapes: benchmark/rooflines/ssd_decode.py) over the device self time a step spends in the ops under scope ssd/scan that are under rollout, over the step runs that lie whole inside the trace, mean over chips (benchmark/lib/scope_roofline.py). None on a program with no such scope."""

from benchmark.lib import scope_roofline


def read(ctx):
    return scope_roofline.share(ctx, "ssd_decode", "decode SSD state updates")
