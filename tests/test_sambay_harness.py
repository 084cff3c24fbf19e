"""The token policy's second family (``phi4flash``) through the system
around it, at tests/test_sambay_policy.py's tiny preset:
``TestHarness`` is the suite every family inherits
(tests/family_suite.py ``HarnessConformance``: the driver, what the
policy refuses, the world of the cell, ``token_recall_long``, the
configuration file and the cell's entry, and the benchmark's harness at
the tiny preset) with this family's own assertions; beside it, a layer
with nothing to read is refused.  (The planted fault is held at the
loss, tests/test_sambay_policy.py; it never went through
``correct.follow`` here.)
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmark.lib import manifest  # noqa: E402
from family_suite import HarnessConformance  # noqa: E402
from scalable_agent_tpu.models.token_policy import (  # noqa: E402
    TokenModelConfig,
)
from test_sambay_policy import (  # noqa: E402
    BATCH,
    EPISODE,
    PRESET,
    TINY,
    UNROLL,
    ref,
)


class TestHarness(HarnessConformance):
    preset = PRESET
    test_the_cells_own_fault_reads_far_off_through_follow = None

    def check_run(self, final, gauge):
        from scalable_agent_tpu import driver

        assert "devtel/learn/grad_norm_experts" not in (
            driver.get_registry().snapshot())
        assert gauge("ssm/state_bytes").value == BATCH * 4 * 128 * 2 * (8 + 3)
        assert gauge("cache/ring_readers").value == 2
        assert gauge("cache/bytes").value == (
            BATCH * 2 * 2 * 16 * 4 * ((8 + UNROLL) + (EPISODE + UNROLL)))

    def check_configuration(self, cfg, differs, model):
        assert set(cfg["reduced"]) <= differs | {"layer_kinds"}
        assert (model.d_inner, model.mamba_dt_rank) == (5120, 2560 // 16)
        assert model.memory_from == 2 and model.ring_of(5) == 3
        shapes = ref.weight_shapes(cfg)
        assert sum(int(np.prod(s)) for s in shapes.values()) == 697_076_353

    def check_rehearsal(self, line, lines, root):
        cell = manifest.load_cell("phi4flash.ingraph", root=str(root))
        assert {"ssm_device_share.fused", "ssm_scan_roofline.fused",
                "gmu_device_share.fused", "diff_attention_device_share.fused",
                "diff_attention_update_roofline.fused", "device_mfu.fused",
                "rollout_device_share.fused"} <= {
                    m.name for m in cell.per_layer}


@pytest.mark.parametrize("kinds, names", [
    (["cross_attention"], "no full_attention layer"),
    (["memory_unit"], "no state_space layer"),
    (["linear_attention"], "layer_kinds must name"),
])
def test_a_layer_with_nothing_to_read_is_refused(kinds, names):
    raw = dict(TINY, num_hidden_layers=len(kinds), layer_kinds=[
        {"kind": kind, "published_index": i}
        for i, kind in enumerate(kinds)])
    with pytest.raises(ValueError, match=names):
        TokenModelConfig.from_dict(raw)
