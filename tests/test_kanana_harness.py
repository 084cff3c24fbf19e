"""The token policy's third family (``deepseek_v3``) through the system
around it, at tests/test_kanana_policy.py's tiny preset:
``TestHarness`` is the suite every family inherits
(tests/family_suite.py ``HarnessConformance``: the driver, what the
policy refuses, the world of the cell, ``token_recall_10k``, the
configuration file and the cell's entry, and the benchmark's harness at
the tiny preset, the cell's own planted fault through
``correct.follow``) with this family's own assertions; beside it, what
this family's file may not ask for.
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
from scalable_agent_tpu.models.token_policy import (  # noqa: E402
    TokenModelConfig,
)
from test_kanana_policy import (  # noqa: E402
    BATCH,
    EPISODE,
    PRESET,
    ROW,
    TINY,
    UNROLL,
    ref,
)


class TestHarness(HarnessConformance):
    preset = PRESET

    def check_run(self, final, gauge):
        assert 0.0 < final["attention/decode_key_blocks_visited_share"] <= 1.0
        assert 0.0 < final["moe/pairs_here_share"] < 1.0
        assert gauge("cache/latent_bytes_per_token").value == 4 * ROW
        assert gauge("cache/ring_bytes").value == (
            BATCH * (EPISODE + UNROLL) * 4 * ROW)
        assert gauge("cache/bytes").value == (
            3 * gauge("cache/ring_bytes").value)
        assert gauge("cache/ring_readers").value == 1
        assert gauge("cache/full_slots").value == EPISODE + UNROLL

    def check_configuration(self, cfg, differs, model):
        assert set(cfg["reduced"]) == differs | {"experts_held"}
        assert (cfg["num_hidden_layers"], cfg["experts_held"]) == (5, 16)
        for told in ("e_score_correction_bias", "group_limit", "positions",
                     "value_head", "weights", "optimizer"):
            assert told in cfg["assumed"], told
        assert "8 chips" in cfg["deployment"]
        trinity = json.load(open(os.path.join(
            ROOT, "benchmark/configs/trinity_mini_ep8.json")))
        assert cfg["loss"] == trinity["loss"]
        assert cfg["optimizer"] == trinity["optimizer"]
        assert (model.num_experts, model.num_experts_per_tok,
                model.num_shared_experts, model.num_dense_layers) == (
                    128, 6, 2, 1)
        assert (model.route_scale, model.route_norm) == (2.448, True)
        assert model.latent_dim == 576
        assert [model.is_expert_layer(layer) for layer in range(5)] == [
            False, True, True, True, True]
        shapes = ref.weight_shapes(cfg)
        assert sum(int(np.prod(s)) for s in shapes.values()) == 575_957_505
        # attention as the model states it, at the cell's mean context
        assert ref.train_flops_per_env_frame(cfg) == pytest.approx(
            4 * ref.forward_flops_per_token(cfg, 5120.0))
        per_key = 2.0 * 32 * (192 + 128)
        assert (ref.forward_flops_per_token(cfg, 5121.0)
                - ref.forward_flops_per_token(cfg, 5120.0)) == pytest.approx(
                    5 * per_key)


@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4),
    ("scoring_func", "softmax"), ("tie_word_embeddings", True),
    ("rope_scaling", {"type": "yarn"}), ("moe_layer_freq", 2)])
def test_what_the_family_is_not_built_for_is_refused_by_its_key(key, value):
    with pytest.raises(ValueError, match=f"{key}=.*not built"):
        TokenModelConfig.from_dict(dict(TINY, **{key: value}))


def test_experts_outside_the_routers_are_refused():
    with pytest.raises(ValueError, match=r"experts \[7, 9\)"):
        TokenModelConfig.from_dict(dict(TINY, first_expert=7))
