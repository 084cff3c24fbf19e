"""Stem conv weight gradient (8x8 stride 4 over the frames): least work.

``dW[kh,kw,c,f] = sum_n,i,j x[n, 4i+kh, 4j+kw, c] * dy[n,i,j,f]``: one
multiply-add per (image, output position, tap, channel pair).  Bytes:
the normalized frames and the output cotangent read once each in
bfloat16 (the type the activations move in), unpadded; dW written once
in float32.
"""

from benchmark.lib import trace_reduce

CALLS_PER_STEP = 1


def matcher(ctx):
    """By the kernel's own name — the event's OWN name: the long names
    of the ops that consume its result mention it too."""
    def match(name: str) -> bool:
        return trace_reduce.short_name(name).startswith(
            "pallas_conv0_gradw")

    return match


def least(ctx):
    cfg, flags = ctx.config, ctx.flags
    features, k, stride = cfg["conv_layers"][0]
    n = ((int(flags["unroll_length"]) + 1) * int(flags["batch_size"])
         // int(getattr(ctx, "chips", 1)))
    h, w, c = cfg["frame_height"], cfg["frame_width"], cfg["frame_channels"]
    oh, ow = -(-h // stride), -(-w // stride)
    flops = 2.0 * n * oh * ow * features * k * k * c
    reads = 2.0 * n * (h * w * c + oh * ow * features)
    writes = 4.0 * k * k * c * features
    return {"flops": flops, "bytes": reads + writes}
