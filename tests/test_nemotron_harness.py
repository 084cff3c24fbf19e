"""The token policy's fourth family (``nemotron_h``) through the system
around it, at tests/test_nemotron_policy.py's tiny preset:
``TestHarness`` is the suite every family inherits
(tests/family_suite.py ``HarnessConformance``: the driver and the
state's gauges, what the policy refuses, the world of the cell,
``token_recall_14k``, the configuration file and the cell's entry, and
the benchmark's harness at the tiny preset: ``run.py --rehearse 1``,
``seeds_big.py --rehearse 1`` and the cell's planted fault through
``correct.follow`` on one checkout; benchmark/tests/test_ssd_cell.py
holds the chip's rows of the fault) with this family's own assertions.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from family_suite import HarnessConformance  # noqa: E402
from scalable_agent_tpu.models import token_policy  # noqa: E402
from test_nemotron_policy import (  # noqa: E402
    BATCH,
    EPISODE,
    PRESET,
    UNROLL,
    ref,
)


class TestHarness(HarnessConformance):
    preset = PRESET

    def check_run(self, final, gauge):
        assert 0.0 < final["moe/pairs_here_share"] < 1.0
        assert final["moe/tokens_per_expert_mean"] > 0.0
        assert final["moe/expert_load_max_over_mean"] >= 1.0
        per_env = 2 * 4 * (4 * 8 * 16 + 3 * 96)
        assert gauge("ssd/state_bytes_per_env").value == per_env
        assert gauge("ssm/state_bytes").value == BATCH * per_env
        assert gauge("cache/latent_bytes_per_token").value == 0
        assert gauge("cache/bytes").value == (
            BATCH * (EPISODE + UNROLL) * 2 * 2 * 8 * 4)

    def check_configuration(self, cfg, differs, model):
        published = PRESET.published
        assert set(cfg["reduced"]) == differs | {"experts_held"}
        assert published["hybrid_override_pattern"].startswith(
            cfg["hybrid_override_pattern"])
        assert (cfg["num_hidden_layers"], cfg["experts_held"]) == (9, 8)
        for told in ("positions", "gated_norm", "router_bias", "time_step",
                     "rescale_prenorm_residual", "scan_precision",
                     "value_head", "weights", "optimizer"):
            assert told in cfg["assumed"], told
        assert "16 chips" in cfg["deployment"]
        kanana = json.load(open(os.path.join(
            ROOT, "benchmark/configs/kanana2_30b_ep8.json")))
        assert cfg["loss"] == kanana["loss"]
        assert cfg["optimizer"] == kanana["optimizer"]
        assert "".join({token_policy.MAMBA2: "M", token_policy.EXPERTS: "E",
                        token_policy.FULL: "*"}[k]
                       for k in model.layer_types) == "MEMEM*EME"
        assert (model.d_inner, model.conv_width,
                model.shared_expert_width) == (4096, 6144, 3712)
        shapes = ref.weight_shapes(cfg)
        count = sum(int(np.prod(s)) for s in shapes.values())
        assert 660e6 < count < 675e6, count
        assert ref.train_flops_per_env_frame(cfg) == pytest.approx(
            4 * ref.forward_flops_per_token(cfg, 7168.0))
        # one attention layer: a key more is 32 heads' score and value
        assert (ref.forward_flops_per_token(cfg, 7169.0)
                - ref.forward_flops_per_token(cfg, 7168.0)) == pytest.approx(
                    2.0 * 2.0 * 32 * 128)

    def test_the_cells_stagger_puts_episode_ends_mid_unroll_and_mid_chunk(
            self):
        """At the cell's 32 envs the stagger puts episode ends at
        offsets 0, 192, 128 and 64 of an unroll: mid-unroll, and
        mid-chunk at 64."""
        world = json.load(open(PRESET.traffic_path))["world"]
        stagger = world["episode_length"] // 32
        ends = {(world["episode_length"] - env_ * stagger) % 256
                for env_ in range(32)}
        assert stagger == 448 and ends == {0, 64, 128, 192}
        assert {end % 128 for end in ends} == {0, 64}
