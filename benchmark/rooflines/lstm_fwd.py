"""LSTM forward (the update's residual-producing unroll): least work.

One call unrolls T+1 steps over B rows.  The algorithm needs, per
call: the gate matmuls ``[B, D+H] x [D+H, 4H]`` at every step; x
``[T+1, B, D]`` read once in float32 (the kernel really takes float32
x), the done flags, the initial carry, the weights ONCE per call (not
once per step: they stay in fast memory across the grid), and ys
``[T+1, B, H]`` and the final carry written once.  The residuals the
kernel stashes for the backward pass are its own choice and are not
counted.

The Mosaic calls of the LSTM carry no name of their own: all three show
in the trace under the flax scope's name, ``core.<n>`` (the update's
forward, its backward, and the T=1 inference forward that outnumbers
them a hundred to one).  They are told apart by what they return: only
the update's forward returns the gates ``f32[T+1, B, 4H]``.
"""

CALLS_PER_STEP = 1


def shapes(ctx):
    cfg, flags = ctx.config, ctx.flags
    return {"T": int(flags["unroll_length"]) + 1,
            "B": int(flags["batch_size"]) // int(getattr(ctx, "chips", 1)),
            "D": cfg["fc_size"] + 1 + cfg["num_actions"],
            "H": cfg["lstm_size"]}


def outputs_of(name: str):
    """The result signature of a custom-call event, or None."""
    head, call, _ = name.partition(" custom-call(")
    return head if call else None


def matcher(ctx):
    s = shapes(ctx)
    gates = f"f32[{s['T']},{s['B']},{4 * s['H']}]"

    def match(name: str) -> bool:
        outputs = outputs_of(name)
        return outputs is not None and gates in outputs

    return match


def least(ctx):
    s = shapes(ctx)
    t, b, d, h = s["T"], s["B"], s["D"], s["H"]
    flops = 2.0 * t * b * (d + h) * 4 * h
    reads = 4.0 * (t * b * d + t * b + 2 * b * h + (d + h) * 4 * h + 4 * h)
    writes = 4.0 * (t * b * h + 2 * b * h)
    return {"flops": flops, "bytes": reads + writes}
