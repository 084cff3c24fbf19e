"""update_interval_p95_ms: harness clock, retire-to-retire gaps in the window, p95."""

from benchmark.lib import readers


def read(ctx):
    return readers.interval_p95_ms(ctx)
