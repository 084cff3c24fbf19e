"""latent_update_roofline.fused: the least time the chip could take for the update's latent attention proper (unroll + 1 queries an env against the live rows of every layer's ring and the call's own, forward and backward once each, the lesser of the absorbed and the up-projected form's arithmetic, from shapes and the updates the trace caught whole: benchmark/rooflines/latent_update.py) over the device self time a step spends in the ops under scope attention/latent/attend that are not under rollout (a rematerialized forward among them), over the step runs that lie whole inside the trace, mean over chips (benchmark/lib/scope_roofline.py). None on a program with no such scope."""

from benchmark.lib import scope_roofline


def read(ctx):
    return scope_roofline.share(ctx, "latent_update", "update latent attention")
