"""setup_before_main_s: launch to the start of the program's first span (driver.main's first line): imports and backend start-up."""

from benchmark.lib import timeline


def read(ctx):
    return timeline.setup_part(ctx, "before_main")
