"""attention_update_roofline.fused: the least time the chip could take for the update's attention proper (scores through the cache and the weighted sum, forward and backward once each, no rematerialized forward) over the device self time a step spends in it: the ops under the scopes `window` / `full` of the token policy's attention that are not under `rollout`, over the step runs that lie whole inside the trace (benchmark/lib/whole_runs.py), mean over chips. The same work whatever implements it: the scopes are the program's, the count is from shapes. Least work a layer: queries x `mean_context` keys (the configuration's own count, as `train_flops_per_env_frame` has it; the window where that is shorter) x head_dim x 2 products x 2 FLOPs a MAC forward and twice that backward; bytes: the queries, the call's own keys and values, the ring once and the output, each pass. None on a program with no such scope."""

import re
import statistics

from benchmark.lib import readers, scopes, whole_runs

_SCOPE = re.compile(r"(?<![A-Za-z0-9_])attention/(?:window|full)"
                    r"(?![A-Za-z0-9_])")
_ROLLOUT = re.compile(r"(?<![A-Za-z0-9_])rollout(?![A-Za-z0-9_])")


def in_update_attention(op_name) -> bool:
    return bool(op_name and _SCOPE.search(op_name)
                and not _ROLLOUT.search(op_name))


def least(ctx):
    """{"flops", "bytes"} of one step's update attention, from shapes."""
    cfg, flags = ctx.config, ctx.flags
    envs = int(flags["batch_size"]) // int(getattr(ctx, "chips", 1))
    unroll = int(flags["unroll_length"])
    queries = unroll + 1
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, window = cfg["head_dim"], cfg["sliding_window"]
    context = float(cfg.get("mean_context", window))
    episode = int(ctx.traffic["world"]["episode_length"])
    item = 2 if cfg.get("compute_dtype", "float32") == "bfloat16" else 4
    flops = bytes_moved = 0.0
    for kind in cfg["layer_types"]:
        sliding = kind == "sliding_attention"
        keys = min(context, window) if sliding else context
        slots = (window if sliding else episode) + unroll
        forward = 2.0 * 2.0 * envs * queries * heads * dim * keys
        flops += 3.0 * forward
        bytes_moved += 2.0 * (
            item * envs * queries * dim * (heads + 2 * kv)   # q, own k, v
            + item * envs * slots * dim * 2 * kv             # the ring
            + 4.0 * envs * queries * heads * dim)            # out / d out
    return {"flops": flops, "bytes": bytes_moved}


def read(ctx):
    table = scopes.table(ctx)
    if table is None or ctx.peak is None:
        return None
    per_plane = []
    for plane in readers.planes(ctx):
        ops, _ = whole_runs._ops(ctx, plane)
        runs = len(whole_runs.runs(ctx, plane))
        if runs:
            per_plane.append(sum(
                self_s for name, self_s in ops
                if in_update_attention(table.get(name))) / runs)
    if not per_plane or statistics.mean(per_plane) <= 0:
        return None
    measured = statistics.mean(per_plane)
    counts = least(ctx)
    least_s, bound = readers.least_seconds(counts["flops"], counts["bytes"],
                                           ctx.peak)
    ctx.notes.append(
        f"update attention: {measured * 1e3:.2f} ms a step measured, least "
        f"{least_s * 1e3:.2f} ms ({bound}-bound: {counts['flops']:.4g} flop, "
        f"{counts['bytes']:.4g} B)")
    return 100.0 * least_s / measured
