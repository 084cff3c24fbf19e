"""ssd_state_bytes_per_env: bytes an env of the Mamba-2 layers' matrix states and convolution tails, over the layers (the program's gauge ssd/state_bytes_per_env, set where the policy is built from the bytes of the state's own arrays, so a state kept a token or a second copy beside the carried one would show: 4 x (2,097,152 + 73,728) = 8,683,520 at 64 heads x 64 x 128 float32 and a 3 x 6,144 tail over four layers). None on a program without the gauge or with no Mamba-2 layer."""


def read(ctx):
    try:
        from scalable_agent_tpu.obs import get_registry
    except ImportError:
        return None
    return get_registry().snapshot().get("ssd/state_bytes_per_env") or None
