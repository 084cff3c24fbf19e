"""The token policy's fifth family (``olmo_hybrid``) through the system
around it, at tests/test_olmo_hybrid_policy.py's tiny preset:
``TestHarness`` is the suite every family inherits
(tests/family_suite.py ``HarnessConformance``: the driver and the
state's gauges, what the policy refuses, the world of the cell,
``token_recall_8k``, the configuration file and the cell's entry, and
the benchmark's harness at the tiny preset: ``run.py --rehearse 1``,
``seeds_big.py --rehearse 1`` and the cell's planted fault through
``correct.follow`` on one checkout; benchmark/tests/test_gdn_cell.py
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
from test_olmo_hybrid_policy import (  # noqa: E402
    BATCH,
    EPISODE,
    PERIOD,
    PRESET,
    UNROLL,
    ref,
)


class TestHarness(HarnessConformance):
    preset = PRESET

    def check_run(self, final, gauge):
        per_env = 3 * 4 * (2 * 16 * 8 + 3 * 64)
        assert gauge("gdn/state_bytes_per_env").value == per_env
        assert gauge("ssm/state_bytes").value == BATCH * per_env
        assert gauge("ssd/state_bytes_per_env").value == 0
        assert gauge("cache/latent_bytes_per_token").value == 0
        # one ring, as many key heads as query heads: 4 of 16 numbers
        assert gauge("cache/bytes").value == (
            BATCH * (EPISODE + UNROLL) * 2 * 4 * 16 * 4)
        assert gauge("cache/ring_readers").value == 1

    def check_configuration(self, cfg, differs, model):
        assert set(cfg["reduced"]) == differs
        assert cfg["layer_types"] == PERIOD
        assert PRESET.published["layer_types"][:4] == cfg["layer_types"]
        assert cfg["num_hidden_layers"] == 4
        for told in ("norm_placement", "qk_norm", "positions", "convolution",
                     "l2_norm", "gates", "gated_norm", "chunk_size",
                     "scan_precision", "value_head", "weights", "optimizer"):
            assert told in cfg["assumed"], told
        assert "8 chips" in cfg["deployment"]
        nemotron = json.load(open(os.path.join(
            ROOT, "benchmark/configs/nemotron3_nano_ep16.json")))
        assert cfg["loss"] == nemotron["loss"]
        assert cfg["optimizer"] == nemotron["optimizer"]
        assert model.layer_types == (token_policy.LINEAR,) * 3 + (
            token_policy.FULL,)
        assert (model.head_dim, model.linear_widths, model.chunk_size) == (
            128, (2880, 5760), 128)
        shapes = ref.weight_shapes(cfg)
        count = sum(int(np.prod(s)) for s in shapes.values())
        # a linear layer 215.5M, a full layer 185.8M, an eighth of the
        # untied vocabulary 96.3M (ISSUE 46's count)
        assert 928e6 < count < 930e6, count
        linear = sum(int(np.prod(s)) for path, s in shapes.items()
                     if path[0] == "layer_0")
        full = sum(int(np.prod(s)) for path, s in shapes.items()
                   if path[0] == "layer_3")
        assert 215.4e6 < linear < 215.6e6 and 185.7e6 < full < 185.9e6
        assert ref.train_flops_per_env_frame(cfg) == pytest.approx(
            4 * ref.forward_flops_per_token(cfg, 3968.0))
        # one attention layer: a key more is 30 heads' score and value
        assert (ref.forward_flops_per_token(cfg, 3969.0)
                - ref.forward_flops_per_token(cfg, 3968.0)) == pytest.approx(
                    2.0 * 2.0 * 30 * 128)

    def test_the_cells_stagger_puts_episode_ends_mid_unroll_and_mid_chunk(
            self):
        """At the cell's 8 envs the stagger of 992 puts episode ends at
        offsets 0, 32, ..., 224 of an unroll, the same every episode:
        seven of eight mid-unroll, six inside a 128-token chunk; the
        ring of 7,936 + 256 slots is whole blocks of 512."""
        cell = json.load(open(PRESET.config_path))
        world = json.load(open(PRESET.traffic_path))["world"]
        envs = cell["sizing"]["fused_env_batch_1chip"]
        stagger = world["episode_length"] // envs
        ends = {(world["episode_length"] - env_ * stagger) % 256
                for env_ in range(envs)}
        assert envs == 8 and stagger == 992
        assert ends == set(range(0, 256, 32))
        assert sum(1 for end in ends if end % 128) == 6
        assert (world["episode_length"] + 256) % 512 == 0
        assert world["episode_length"] // 256 == 31

    def test_the_traffic_gives_the_closing_drain_its_time(self):
        """The drain after the window writes 7.43 GB of checkpoint: the
        traffic file passes ``preemption_grace_s`` 120, and says why."""
        traffic = json.load(open(PRESET.traffic_path))
        assert traffic["flags"]["preemption_grace_s"] == 120
        assert "7.43 GB" in traffic["flags_why"]["preemption_grace_s"]
