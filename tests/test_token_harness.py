"""The token policy's first family (``afmoe``) through the system around
it, at tests/test_token_policy.py's tiny preset: ``TestHarness`` is the
suite every family inherits (tests/family_suite.py
``HarnessConformance``: the driver, what the policy refuses, the world
of the cell, ``token_recall``, the configuration file and the cell's
entry, and the benchmark's harness at the tiny preset) with this
family's own assertions.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from family_suite import HarnessConformance  # noqa: E402
from scalable_agent_tpu.models.token_policy import TokenPolicy  # noqa: E402
from test_token_policy import PRESET  # noqa: E402


class TestHarness(HarnessConformance):
    preset = PRESET
    # this family's cell plants no fault of its own beside the harness's
    # two, which ``seeds_big`` reads
    test_the_cells_own_fault_reads_far_off_through_follow = None

    def check_run(self, final, gauge):
        for name in TokenPolicy.STATS:
            assert np.isfinite(final[name]), name
        assert 0.0 < final["moe/pairs_here_share"] < 1.0
        assert gauge("attention/key_blocks_visited_share").value == (
            pytest.approx(final["attention/key_blocks_visited_share"]))

    def check_configuration(self, cfg, differs, model):
        assert set(cfg["reduced"]) == differs | {"experts_held"}
        assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
                cfg["experts_held"]) == (5, 1, 16)
        period = self.preset.published["layer_types"][:4]
        assert cfg["layer_types"] == period[:1] + period
        assert "8 chips" in cfg["deployment"]
        assert [model.is_expert_layer(layer) for layer in range(5)] == [
            False, True, True, True, True]

    def check_rehearsal(self, line, lines, root):
        assert set(line["compared"]) == {"loss1_gap", "loss_gap",
                                         "grad_median_gap", "delta_norm_gap"}
        (device,) = [l for l in lines if l.startswith("device:")]
        for attribute in ("core_impl", "conv_backend", "torso_type"):
            assert f"'{attribute}': None" in device
