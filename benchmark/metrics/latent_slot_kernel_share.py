"""latent_slot_kernel_share: of the one-token writes into a latent ring that the program traced (one a latent layer in the acting step), the share that move the slot's one lane tile in place in a Mosaic kernel (ops/attention.py _latent_slot_write) and not XLA's element-wise slice update of a column: the program's trace-time gauge attention/latent_slot_kernel_share, set wherever latent_ring_write takes or leaves the kernel path. 1.0 where every latent ring's shape fits the kernel; under 1.0 some layer's write fell back (slots not whole lane tiles, rows not whole sublane tiles). None on a program without the gauge or with no latent ring."""


def read(ctx):
    try:
        from scalable_agent_tpu.obs import get_registry
    except ImportError:
        return None
    return get_registry().snapshot().get("attention/latent_slot_kernel_share")
