"""latent_cache_bytes_per_token: bytes a token a layer the attention cache's rings hold where attention is latent (the program's gauge cache/latent_bytes_per_token, set where the policy is built from the bytes of the state's own ring arrays over envs x slots x layers, so anything kept beside the compressed row counts: 1,152 at kv_lora_rank 512 + 64 rotated numbers in bfloat16; whole keys and values of the same model would be 20,480). None on a program without the gauge or whose rings hold whole keys and values."""


def read(ctx):
    try:
        from scalable_agent_tpu.obs import get_registry
    except ImportError:
        return None
    return get_registry().snapshot().get("cache/latent_bytes_per_token") or None
