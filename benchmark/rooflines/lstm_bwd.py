"""LSTM backward (BPTT over the unroll): least work.

Per step four matmuls of the forward's size: dx and dh from the gate
gradients, and the two weight-gradient accumulations — twice the
forward's FLOPs.  Bytes: dys, x and the previous carry (c, h) of every
step read once in float32 (from them the gates can be had again), the
done flags, the weights once per call; dx and the weight, bias and
initial-carry gradients written once.

Told apart from the other ``core.<n>`` Mosaic calls by what it returns:
dx ``f32[T+1, B, D]`` and the input-weight gradient ``f32[D, 4H]``.
"""

from benchmark.lib import readers

CALLS_PER_STEP = 1


def matcher(ctx):
    fwd = readers.roofline_module("lstm_fwd")
    s = fwd.shapes(ctx)
    dx = f"f32[{s['T']},{s['B']},{s['D']}]"
    dwi = f"f32[{s['D']},{4 * s['H']}]"

    def match(name: str) -> bool:
        outputs = fwd.outputs_of(name)
        return outputs is not None and dx in outputs and dwi in outputs

    return match


def least(ctx):
    s = readers.roofline_module("lstm_fwd").shapes(ctx)
    t, b, d, h = s["T"], s["B"], s["D"], s["H"]
    flops = 4.0 * t * b * (d + h) * 4 * h
    weights = (d + h) * 4 * h
    reads = 4.0 * (t * b * (h + d + 2 * h) + t * b + weights + 2 * b * h)
    writes = 4.0 * (t * b * d + weights + 4 * h + 2 * b * h)
    return {"flops": flops, "bytes": reads + writes}
