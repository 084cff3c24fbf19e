"""allreduce_exposed_ms: all-reduce time during which no other op runs on that device, per step run, mean over chips."""

from benchmark.lib import readers


def read(ctx):
    import statistics
    from benchmark.lib import trace_reduce
    per_plane = []
    for plane in readers.planes(ctx):
        runs = readers.step_runs(ctx, plane)
        if not runs:
            continue
        exposed = trace_reduce.exposed_seconds(
            ctx.events, plane, lambda name: "all-reduce" in name
            or "all_reduce" in name)
        per_plane.append(exposed / len(runs))
    return statistics.mean(per_plane) * 1e3 if per_plane else None
