"""Link-adaptive fused-shard selection (runtime/linktune.py).

The chooser's RTT-floor model is validated two ways:

1. Against an INDEPENDENT discrete-event simulation of the sharded
   lockstep pipeline (shards as loops serializing their uploads on one
   link): across link profiles spanning co-located chips to collapsed
   remote links, the chosen shard count must land within 10% of the
   simulation's sweep optimum.
2. Against the round-4 measured sweep facts: 2 shards beat 1 and 3 on
   the degraded link; 1 shard wins co-located (round-4 ADVICE: a
   static default of 2 regresses co-located deployments).
"""

import numpy as np
import pytest

from scalable_agent_tpu.runtime.linktune import (
    DEFAULT_ENV_STEP_S,
    SHARD_CONTENTION_FRAC,
    LinkProfile,
    choose_fused_shards,
    predicted_fused_fps,
    resolve_fused_shards,
)

# The bench fleet: 5 groups x 256 envs, 72x96x3 uint8 frames.
GROUPS, GROUP_SIZE, FRAME_BYTES = 5, 256, 72 * 96 * 3

REMOTE_R4 = LinkProfile(rtt_s=0.085, h2d_bytes_per_s=95e6)
REMOTE_COLLAPSED = LinkProfile(rtt_s=0.09, h2d_bytes_per_s=30e6)
REMOTE_R3 = LinkProfile(rtt_s=0.10, h2d_bytes_per_s=800e6)
COLOCATED = LinkProfile(rtt_s=0.0002, h2d_bytes_per_s=20e9)
ALL_PROFILES = [REMOTE_R4, REMOTE_COLLAPSED, REMOTE_R3, COLOCATED]


def simulate_fps(shards, num_groups, group_size, frame_bytes, link,
                 env_step_s=DEFAULT_ENV_STEP_S, horizon=300):
    """Discrete-event simulation of the sharded pipeline, independent
    of the analytic model: each shard loops (upload -> RTT+env), with
    uploads serialized on the single link resource.  The measured
    per-extra-shard host contention is applied as in production (it is
    a host property no link model can derive)."""
    base, extra = divmod(num_groups, shards)
    sizes = [base + (1 if s < extra else 0) for s in range(shards)]
    t = [0.0] * shards  # each shard's next-ready time
    link_free = 0.0
    agent_steps = 0
    for _ in range(horizon * shards):
        i = int(np.argmin(t))
        start = max(t[i], link_free)
        upload = sizes[i] * group_size * frame_bytes / link.h2d_bytes_per_s
        link_free = start + upload
        t[i] = link_free + link.rtt_s + env_step_s
        agent_steps += sizes[i] * group_size
    fps = agent_steps / max(t)
    return fps * max(0.0, 1.0 - SHARD_CONTENTION_FRAC * (shards - 1))


class TestChooserVsSimulation:
    @pytest.mark.parametrize("link", ALL_PROFILES)
    def test_choice_within_10pct_of_sim_optimum(self, link):
        chosen = choose_fused_shards(
            GROUPS, GROUP_SIZE, FRAME_BYTES, link)
        sims = {s: simulate_fps(s, GROUPS, GROUP_SIZE, FRAME_BYTES, link)
                for s in range(1, 5)}
        best = max(sims.values())
        assert sims[chosen] >= 0.9 * best, (
            f"chose {chosen} shards ({sims[chosen]:.0f} steps/s) but "
            f"sweep optimum is {best:.0f}: {sims}")

    @pytest.mark.parametrize("groups,link", [
        (2, REMOTE_R4), (3, REMOTE_R4), (8, REMOTE_R3),
        (4, COLOCATED),
    ])
    def test_other_fleet_shapes(self, groups, link):
        chosen = choose_fused_shards(
            groups, GROUP_SIZE, FRAME_BYTES, link)
        sims = {s: simulate_fps(s, groups, GROUP_SIZE, FRAME_BYTES, link)
                for s in range(1, min(4, groups) + 1)}
        assert sims[chosen] >= 0.9 * max(sims.values())


class TestMeasuredFacts:
    """The r4 sweep's qualitative facts must hold in the model."""

    def test_two_shards_beat_one_on_degraded_link(self):
        one = predicted_fused_fps(
            1, GROUPS, GROUP_SIZE, FRAME_BYTES, REMOTE_R4)
        two = predicted_fused_fps(
            2, GROUPS, GROUP_SIZE, FRAME_BYTES, REMOTE_R4)
        assert two > 1.1 * one

    def test_three_shards_do_not_beat_two(self):
        two = predicted_fused_fps(
            2, GROUPS, GROUP_SIZE, FRAME_BYTES, REMOTE_R4)
        three = predicted_fused_fps(
            3, GROUPS, GROUP_SIZE, FRAME_BYTES, REMOTE_R4)
        assert three <= two

    def test_colocated_picks_one_shard(self):
        assert choose_fused_shards(
            GROUPS, GROUP_SIZE, FRAME_BYTES, COLOCATED) == 1

    def test_degraded_link_picks_two(self):
        assert choose_fused_shards(
            GROUPS, GROUP_SIZE, FRAME_BYTES, REMOTE_R4) == 2


class TestResolve:
    def test_explicit_value_passes_through_without_probe(self):
        def exploding_probe(device):
            raise AssertionError("probe must not run for explicit value")

        shards, link = resolve_fused_shards(
            2, GROUPS, GROUP_SIZE, FRAME_BYTES, probe=exploding_probe)
        assert shards == 2 and link is None

    def test_explicit_value_clamped_to_group_count(self):
        shards, _ = resolve_fused_shards(
            7, 3, GROUP_SIZE, FRAME_BYTES, probe=lambda d: None)
        assert shards == 3

    def test_auto_probes_and_chooses(self):
        shards, link = resolve_fused_shards(
            0, GROUPS, GROUP_SIZE, FRAME_BYTES,
            probe=lambda device: REMOTE_R4)
        assert shards == 2
        assert link == REMOTE_R4

    def test_actor_pool_auto_resolves_from_probe(self, monkeypatch):
        """ActorPool(accum_fused, fused_shards=0) probes the link and
        builds the chosen number of lockstep drivers."""
        import functools

        import jax

        import scalable_agent_tpu.runtime.linktune as linktune
        from scalable_agent_tpu.envs import MultiEnv, make_impala_stream
        from scalable_agent_tpu.envs.spec import TensorSpec
        from scalable_agent_tpu.models import ImpalaAgent
        from scalable_agent_tpu.runtime import ActorPool

        probed = []
        monkeypatch.setattr(
            linktune, "probe_link",
            lambda device=None, **kw: probed.append(1) or REMOTE_R4)
        # Pin the wiring, not the model (tiny test fleets are legitimately
        # RTT-bound -> 1 shard): force a 2-shard choice and check the
        # pool builds exactly that many lockstep drivers.
        monkeypatch.setattr(
            linktune, "choose_fused_shards", lambda *a, **k: 2)
        frame = TensorSpec((16, 16, 3), np.uint8, "frame")
        groups = [
            MultiEnv(
                [functools.partial(make_impala_stream, "fake_small",
                                   seed=g * 10 + i)
                 for i in range(2)],
                frame, num_workers=1)
            for g in range(2)
        ]
        agent = ImpalaAgent(num_actions=9)
        pool = ActorPool(agent, groups, unroll_length=3,
                         inference_mode="accum_fused", fused_shards=0)
        try:
            assert probed, "auto mode must probe the link"
            assert pool.fused_shards == 2
            assert len(pool._actors) == 2
        finally:
            pool.stop()

    def test_probe_measures_real_device(self):
        """The probe returns sane numbers against the test backend."""
        from scalable_agent_tpu.runtime.linktune import probe_link

        link = probe_link(upload_bytes=1 << 20)
        assert 0.0 < link.rtt_s < 5.0
        assert link.h2d_bytes_per_s > 1e5


class TestBandwidthClamp:
    """RTT jitter must not let the probe report impossible bandwidth
    (ADVICE r5): ``upload_s - rtt_s`` hitting the 1e-9 floor used to
    yield ~8e15 B/s, falsely clearing bench.py's 300 MB/s e2e retry
    gate."""

    def test_jitter_inflated_rtt_is_clamped(self):
        from scalable_agent_tpu.runtime.linktune import (
            MAX_H2D_BYTES_PER_S,
            MIN_TRANSFER_FRAC,
            _clamped_bandwidth,
        )

        # A jitter spike made the RTT probes read LONGER than the whole
        # upload window: the naive subtraction would divide by 1e-9.
        bw = _clamped_bandwidth(16 << 20, upload_s=0.060, rtt_s=0.067)
        assert bw <= MAX_H2D_BYTES_PER_S
        # The transfer window floors at MIN_TRANSFER_FRAC of the upload
        # window, so the report is bounded by 1/frac x bytes/window.
        assert bw == pytest.approx(
            (16 << 20) / (MIN_TRANSFER_FRAC * 0.060))
        assert bw < 8e15  # the r5 artifact this guards against

    def test_clean_measurement_unchanged(self):
        from scalable_agent_tpu.runtime.linktune import _clamped_bandwidth

        # Healthy window: RTT well below the upload time — the clamp
        # must not perturb the honest estimate.
        bw = _clamped_bandwidth(16 << 20, upload_s=0.200, rtt_s=0.010)
        assert bw == pytest.approx((16 << 20) / 0.190)

    def test_absolute_cap(self):
        from scalable_agent_tpu.runtime.linktune import (
            MAX_H2D_BYTES_PER_S,
            _clamped_bandwidth,
        )

        # Even a plausible-looking subtraction cannot report above the
        # physical cap.
        bw = _clamped_bandwidth(1 << 30, upload_s=0.0101, rtt_s=0.010)
        assert bw == MAX_H2D_BYTES_PER_S
