"""moe_compact_share: the share of the expert layers' update passes whose landed pairs fit the first chunk of the grouped path's walk (ops/moe.py compact_rows), mean over the expert layers and over every update the program published (its devtel/learn histogram's mean; the gauge of the last update where that is not there). 1.0 is every pass done in its one straight-line chunk; a pass at 0.0 walked on through further chunks. None on a program without the counter."""


def read(ctx):
    try:
        from scalable_agent_tpu.obs import get_registry
    except ImportError:
        return None
    seen = get_registry().snapshot()
    mean = "devtel/learn/moe_compact_share/mean"
    if seen.get(mean.replace("/mean", "/count")):
        return seen[mean]
    return seen.get("moe/compact_share")
