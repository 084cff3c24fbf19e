"""transport_batch_ms: transport/pack + transport/upload + transport/unpack spans, per uploaded batch."""

from benchmark.lib import readers


def read(ctx):
    uploads = readers.span_durations(ctx, "transport/upload")
    if not uploads:
        return None
    total = sum(sum(readers.span_durations(ctx, name)) for name in
                ("transport/pack", "transport/upload", "transport/unpack"))
    return total / len(uploads) * 1e3
