"""Link-adaptive actor configuration: measure the host↔device link,
pick the fused-co-dispatch shard count from a throughput model.

The ``accum_fused`` inference mode exists for accelerator attachments
where the host link dominates (a remote or degraded link): its lockstep
drivers collapse per-step link cost to ~1 RTT, and splitting the fleet
into shards lets one shard's frame upload + env stepping overlap
another's action-fetch round trip.  The right shard count depends
entirely on the measured link:

- co-located chip (sub-ms RTT, >10 GB/s): 1 shard — extra lockstep
  threads add handoff overhead with no RTT to hide;
- bandwidth-collapsed remote link (r4: 24-104 MB/s, 67-91 ms RTT): 2
  shards measured 14.4k fps where 1 measured 8-9.3k, and 3 regressed
  to 12.6k (host thread contention + uneven 2/2/1 split).

A static default cannot serve both deployments (round-4 ADVICE), so
``accum_fused_shards=0`` (the config default) probes the link at pool
startup and picks the predicted-best count.  The model below is the
round-4 RTT-floor model (``predicted_steps_per_s``), validated
against the r4 shard sweep; ``tests/test_linktune.py`` checks the
choice against an independent discrete-event simulation of the sharded
pipeline across link profiles.

No reference equivalent: the reference's actors talk to a co-located
GPU over gRPC and never face this trade (reference:
experiment.py:497-512).
"""

import time
from typing import NamedTuple

import numpy as np


class LinkProfile(NamedTuple):
    """The two link numbers the shard model needs."""

    rtt_s: float
    h2d_bytes_per_s: float


# RTT-jitter guards for the bandwidth estimate: the measured upload
# window includes one fetch round trip, so the RTT is subtracted before
# dividing — but RTT jitter can make ``upload_s - rtt_s`` collapse to
# (or below) zero, and an unclamped division then reports ~8e15 B/s,
# falsely clearing any bandwidth gate (bench.py's 300 MB/s e2e retry
# threshold).  The transfer window is therefore floored at this fraction
# of the whole upload window (an RTT-dominated measurement can still
# only certify ~1/frac x the naive bytes/window estimate)...
MIN_TRANSFER_FRAC = 0.1
# ...and the reported bandwidth is capped outright: no host link this
# probe runs over moves more than this, so anything above it is jitter,
# not wire.
MAX_H2D_BYTES_PER_S = 64e9

# Env stepping cost per group-step: ~9 ms measured for the bench fleet
# on the 1-core host (the r3 link characterization).  It enters
# the model additively and identically for every shard count, so the
# CHOICE is insensitive to it; a constant beats a costly startup
# calibration.
DEFAULT_ENV_STEP_S = 0.010
# Per-extra-shard throughput penalty for lockstep-driver thread
# contention, fitted to the r4 sweep (3 shards at 12.6k vs 2 at 14.4k
# where the pure link model says they tie): each shard past the first
# costs ~10% on a host with few spare cores.
SHARD_CONTENTION_FRAC = 0.10


def probe_link(device=None, upload_bytes: int = 8 << 20) -> LinkProfile:
    """Measure RTT (min of 3 tiny round trips) and flat H2D bandwidth
    (one ``upload_bytes`` upload) against ``device``.

    Synchronization is by VALUE FETCH (bench.py ``_fetch_scalar``):
    holding the bytes waits on any backend.  The upload window
    includes one fetch round
    trip, so the measured RTT is SUBTRACTED before dividing — without
    that, a 67 ms-RTT link reads at most upload_bytes/RTT (~250 MB/s
    for 16 MB) no matter how fast the wire is, and any
    bandwidth-threshold consumer silently saturates below its gate.
    The subtraction is clamped (``MIN_TRANSFER_FRAC``/
    ``MAX_H2D_BYTES_PER_S``): RTT jitter between the RTT probes and the
    upload window can otherwise drive the denominator to the float
    floor and report physically impossible bandwidth.
    Cost: ~2x RTT-bound seconds on a degraded link, ~ms co-located.
    """
    import jax

    device = device or jax.local_devices()[0]
    tiny = np.zeros((8,), np.float32)
    float(np.asarray(jax.device_put(tiny, device)[0]))  # warm the path
    rtts = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(np.asarray(jax.device_put(tiny, device)[0]))
        rtts.append(time.perf_counter() - t0)
    rtt_s = min(rtts)
    big = np.zeros((upload_bytes,), np.uint8)
    t0 = time.perf_counter()
    float(np.asarray(jax.device_put(big, device)[0]))
    upload_s = time.perf_counter() - t0
    return LinkProfile(
        rtt_s=rtt_s,
        h2d_bytes_per_s=_clamped_bandwidth(upload_bytes, upload_s,
                                           rtt_s),
    )


def _clamped_bandwidth(upload_bytes: int, upload_s: float,
                       rtt_s: float) -> float:
    """RTT-corrected H2D bandwidth with jitter guards: the transfer
    window never shrinks below ``MIN_TRANSFER_FRAC`` of the measured
    upload window, and the result never exceeds
    ``MAX_H2D_BYTES_PER_S``."""
    transfer_s = max(upload_s - rtt_s, MIN_TRANSFER_FRAC * upload_s,
                     1e-9)
    return min(upload_bytes / transfer_s, MAX_H2D_BYTES_PER_S)


def predicted_fused_fps(
    shards: int,
    num_groups: int,
    group_size: int,
    frame_bytes: int,
    link: LinkProfile,
    env_step_s: float = DEFAULT_ENV_STEP_S,
) -> float:
    """Steady-state agent-steps/s of the sharded lockstep pipeline
    under the RTT-floor model.

    Shards run concurrently; each shard's cycle is one action-fetch RTT
    + env stepping + its own groups' frame upload, but all uploads
    serialize on the one link — so throughput is the lesser of the
    link-bandwidth bound and the sum of per-shard rates, discounted by
    the measured per-extra-shard host contention.  (The action-repeat
    multiplier scales every shard count equally and is omitted.)
    """
    if shards < 1 or shards > num_groups:
        return 0.0
    upload_total_s = (num_groups * group_size * frame_bytes
                      / link.h2d_bytes_per_s)
    steps_per_fleet_step = num_groups * group_size
    bw_bound = steps_per_fleet_step / max(upload_total_s, 1e-9)
    # Actual split (ActorPool's divmod): uneven splits hurt via the
    # larger shards' longer cycles, which is how the r4 2/2/1
    # regression enters the model.
    base, extra = divmod(num_groups, shards)
    sizes = [base + (1 if s < extra else 0) for s in range(shards)]
    overlap_rate = 0.0
    for g in sizes:
        cycle = (link.rtt_s + env_step_s
                 + g * group_size * frame_bytes / link.h2d_bytes_per_s)
        overlap_rate += g * group_size / cycle
    contention = max(0.0, 1.0 - SHARD_CONTENTION_FRAC * (shards - 1))
    return min(bw_bound, overlap_rate) * contention


def choose_fused_shards(
    num_groups: int,
    group_size: int,
    frame_bytes: int,
    link: LinkProfile,
    env_step_s: float = DEFAULT_ENV_STEP_S,
    max_shards: int = 4,
) -> int:
    """The predicted-best shard count; ties break toward FEWER shards
    (fewer threads, even splits)."""
    best_s, best_fps = 1, -1.0
    for s in range(1, min(max_shards, num_groups) + 1):
        fps = predicted_fused_fps(
            s, num_groups, group_size, frame_bytes, link, env_step_s)
        if fps > best_fps * 1.02:  # >2% gain to justify another thread
            best_s, best_fps = s, fps
    return best_s


def resolve_fused_shards(
    fused_shards: int,
    num_groups: int,
    group_size: int,
    frame_bytes: int,
    device=None,
    probe=None,
) -> tuple:
    """ActorPool entry point: 0 = auto (probe + choose); explicit
    values pass through.  Returns ``(shards, LinkProfile | None)`` so
    callers can log what the choice was based on."""
    if fused_shards:
        return max(1, min(fused_shards, num_groups)), None
    link = (probe or probe_link)(device)
    shards = choose_fused_shards(
        num_groups, group_size, frame_bytes, link)
    return shards, link
