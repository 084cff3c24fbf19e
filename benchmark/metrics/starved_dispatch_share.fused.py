"""starved_dispatch_share.fused: share of the window's fused/enqueue spans that began with in_flight 0, the chip with nothing queued."""

from benchmark.lib import dispatch_spans


def read(ctx):
    spans = dispatch_spans.enqueues(ctx)
    if not spans:
        return None
    starved = [e["args"].get("update") for e in spans
               if e["args"]["in_flight"] == 0]
    ctx.notes.append(
        f"starved dispatches: {len(starved)} of {len(spans)} in the "
        f"window, updates {starved[:16]}")
    return 100.0 * len(starved) / len(spans)
