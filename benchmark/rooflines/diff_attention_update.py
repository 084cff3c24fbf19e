"""The update's differential attention proper (scores through the cache
and the weighted sum, forward and backward once each, no rematerialized
forward): least work, counted as ``attention_update_roofline.fused``
counts plain attention's, with two score streams a pair of heads and
the cross layers' reads.

A layer, B envs x T = unroll + 1 queries over ``keys`` keys a query (the
configuration's ``mean_context``; the window where that is shorter):
the two streams' scores are the published heads' products (``heads x
head_dim`` a key) and the weighted value one product a pair of twice
the width, as many as plain attention of these heads: 2 products x 2
FLOPs a MAC forward, twice that backward.  Bytes, each pass: the
queries, the call's own keys and values (a cross layer reads the full
layer's), the ring once (a cross layer reads the full layer's ring
again: it is another pass over it) and the output, float32.

The work is marked by scope: ops under ``attention/window``,
``attention/full`` or ``attention/cross`` and not under ``rollout``.
"""

import re

_SCOPE = re.compile(r"(?<![A-Za-z0-9_])attention/(?:window|full|cross)"
                    r"(?![A-Za-z0-9_])")
_ROLLOUT = re.compile(r"(?<![A-Za-z0-9_])rollout(?![A-Za-z0-9_])")
ATTENTION = ("sliding_attention", "full_attention", "cross_attention")


def in_update(op_name) -> bool:
    return bool(op_name and _SCOPE.search(op_name)
                and not _ROLLOUT.search(op_name))


def least(ctx):
    """{"flops", "bytes"} of one step's update attention, from shapes;
    None for a configuration with no ``layer_kinds``."""
    cfg, flags = ctx.config, ctx.flags
    kinds = [entry["kind"] for entry in cfg.get("layer_kinds", ())
             if entry["kind"] in ATTENTION]
    if not kinds:
        return None
    envs = int(flags["batch_size"]) // int(getattr(ctx, "chips", 1))
    unroll = int(flags["unroll_length"])
    queries = unroll + 1
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg.get("head_dim", cfg["hidden_size"] // heads)
    window = cfg["sliding_window"]
    context = float(cfg.get("mean_context", window))
    episode = int(ctx.traffic["world"]["episode_length"])
    item = 2 if cfg.get("compute_dtype", "float32") == "bfloat16" else 4
    flops = bytes_moved = 0.0
    for kind in kinds:
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
