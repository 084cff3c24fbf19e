"""latent_attention_device_share.fused: device self time of ops under scope attention/latent (the whole latent mixer: Wq, Wkva, the norm, the rotation, Wkvb absorbed and up-projected under project; the cache's read, the kernels and the ring's write under attend; the output projection; rollout and update, forward, rematerialized forward and backward) over the device time of the step runs that lie whole inside the trace (benchmark/lib/whole_runs.py), mean over chips. None on a program with no such scope."""

from benchmark.lib import whole_runs


def read(ctx):
    return whole_runs.share_where(ctx, r"\battention/latent\b")
