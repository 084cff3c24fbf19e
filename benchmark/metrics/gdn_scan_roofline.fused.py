"""gdn_scan_roofline.fused: the least time the chip could take for the update's delta-rule scans (forward and backward once each, from shapes: benchmark/rooflines/gdn_scan.py) over the device self time a step spends in the ops under scope gdn/scan that are not under rollout (a rematerialized forward among them), over the step runs that lie whole inside the trace, mean over chips (benchmark/lib/scope_roofline.py). None on a program with no such scope."""

from benchmark.lib import scope_roofline


def read(ctx):
    return scope_roofline.share(ctx, "gdn_scan", "update delta-rule scans")
