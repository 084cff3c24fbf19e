"""first_update_s: harness clock, launch to the first update retired."""


def read(ctx):
    if ctx.t_first_update is None:
        return None
    return ctx.t_first_update - ctx.t_launch
