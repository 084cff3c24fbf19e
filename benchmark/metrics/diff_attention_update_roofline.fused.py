"""diff_attention_update_roofline.fused: the least time the chip could take for the update's differential attention proper (two score streams a pair of heads through the cache and the weighted sum, the cross layers' reads among them, forward and backward once each, from shapes: benchmark/rooflines/diff_attention_update.py) over the device self time a step spends in the ops under scopes attention/window, attention/full and attention/cross that are not under rollout, over the step runs that lie whole inside the trace, mean over chips (benchmark/lib/scope_roofline.py). None on a program with no such scope."""

from benchmark.lib import scope_roofline


def read(ctx):
    return scope_roofline.share(ctx, "diff_attention_update", "update differential attention")
