"""latent_decode_roofline.fused: the least time the chip could take for the decode's latent attention proper (one query an env against the live rows of every layer's ring, unroll times a step, from shapes and the updates the trace caught whole: benchmark/rooflines/latent_decode.py) over the device self time a step spends in the ops under scope attention/latent/attend that are under rollout, over the step runs that lie whole inside the trace, mean over chips (benchmark/lib/scope_roofline.py). None on a program with no such scope."""

from benchmark.lib import scope_roofline


def read(ctx):
    return scope_roofline.share(ctx, "latent_decode", "decode latent attention")
