"""loss_heads_device_share.fused: device self time of the update's ops under vtrace_loss, policy_logits or baseline (the 25,024-way head, its log-softmax, the value head, V-trace) over the device time of the step runs that lie whole inside the trace (benchmark/lib/whole_runs.py), mean over chips."""

from benchmark.lib import whole_runs


def read(ctx):
    return whole_runs.share(ctx, "update.loss_heads")
