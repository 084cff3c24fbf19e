"""frame_age_p95_s: frame age at consumption (trajectory birth to the
retire of the update that consumed it), p95 over the trajectories
retired inside the window, from the program's pipeline-ledger stamps
(the data behind its ledger/staleness_s histogram, which cannot leave
the warm-up out)."""

from benchmark.lib import window


def read(ctx):
    births, retires = {}, {}
    for stamp in ctx.ledger_ring:
        if stamp["stage"] == "birth":
            births[stamp["tid"]] = stamp["ts_us"] * 1e-6
        elif stamp["stage"] == "retire":
            retires[stamp["tid"]] = stamp["ts_us"] * 1e-6
    ages = [t - births[tid] for tid, t in retires.items()
            if tid in births and ctx.t_open <= t <= ctx.t_close]
    if not ages:
        return None
    ctx.notes.append(f"frame_age_p95_s over {len(ages)} trajectories")
    return window.percentile(ages, 95)
