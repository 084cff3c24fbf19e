"""compile_events_s: JAX compile-event seconds totalled before the window opens."""


def read(ctx):
    return ctx.compile_s_before_window
