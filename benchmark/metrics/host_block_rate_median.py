"""host_block_rate_median: median rate of consecutive blocks of 8 updates, completion to completion: the loop's pace with a disturbed block left out, beside the whole-window rate that keeps it in."""

from benchmark.lib import readers


def read(ctx):
    return readers.block_median_rate(ctx)
