"""expert_load_max_over_mean: tokens on the fullest held expert over the mean of the held experts, mean over the expert layers and over every update the program published (its devtel/learn histogram's mean; the gauge of the last update where that is not there). 1.0 is an even load. None on a program without the counter."""


def read(ctx):
    try:
        from scalable_agent_tpu.obs import get_registry
    except ImportError:
        return None
    seen = get_registry().snapshot()
    mean = "devtel/learn/moe_expert_load_max_over_mean/mean"
    if seen.get(mean.replace("/mean", "/count")):
        return seen[mean]
    return seen.get("moe/expert_load_max_over_mean")
