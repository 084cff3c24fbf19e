"""top_op_share.fused: largest device op's self time over device busy time, first device."""

from benchmark.lib import readers


def read(ctx):
    return readers.top_op_share(ctx)
