"""gdn_state_bytes_per_env: bytes an env of the delta-rule layers' matrix states and convolution tails, over the layers (the program's gauge gdn/state_bytes_per_env, set where the policy is built from the bytes of the state's own arrays, so a state kept a token or a second copy beside the carried one would show: 3 x (2,211,840 + 138,240) = 7,050,240 at 30 heads x 192 x 96 float32 and a 3 x 11,520 tail over three layers). None on a program without the gauge or with no delta-rule layer."""


def read(ctx):
    try:
        from scalable_agent_tpu.obs import get_registry
    except ImportError:
        return None
    return get_registry().snapshot().get("gdn/state_bytes_per_env") or None
