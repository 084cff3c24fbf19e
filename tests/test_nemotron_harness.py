"""The token policy's fourth family (``nemotron_h``) through the system
around it, at tests/test_nemotron_policy.py's tiny preset:

(f) the fused step trains through ``driver.main`` and sets the state's
    gauges; what the policy is not built for is refused by name for
    this family too;
(g) the world of the cell (``token_recall_14k``) is the reference's;
    the configuration file is the catalog's but for what it lists;
(h) the benchmark's harness (``run.py --rehearse 1``) drives the cell
    at the tiny preset, and in float32 the program is the reference;
    (``benchmark/seeds_big.py --rehearse 1`` and the cell's planted
    fault through ``correct.follow`` on the same checkout were run by
    hand before the chip calls, PR 42, and are not kept: the suite runs
    within 8% of its time limit; tests/test_nemotron_policy.py holds
    the fault at the loss, benchmark/tests/test_ssd_cell.py the chip's
    rows of it.)
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmark.lib import manifest  # noqa: E402
from scalable_agent_tpu.models import token_policy  # noqa: E402
from scalable_agent_tpu.models.token_policy import (  # noqa: E402
    TokenModelConfig,
)
from test_nemotron_policy import (  # noqa: E402
    BATCH,
    EPISODE,
    TINY,
    UNROLL,
    VOCAB,
    ref,
)

CONFIG_FILE = os.path.join(ROOT, "benchmark/configs/nemotron3_nano_ep16.json")
TRAFFIC_FILE = os.path.join(
    ROOT, "benchmark/traffic/fused_token_recall_u256_e14336.json")


def driver_argv(tmp_path, cfg, *more):
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(cfg))
    return [
        "--mode=train", f"--logdir={tmp_path / 'run'}",
        f"--model_config={path}", "--level_name=token_recall_small",
        "--train_backend=ingraph", f"--batch_size={BATCH}",
        f"--unroll_length={UNROLL}", "--num_action_repeats=1",
        "--compute_dtype=float32", "--mesh_data=1",
        f"--total_environment_frames={3 * BATCH * UNROLL}",
        "--log_interval_s=0.2", *more]


def test_three_updates_through_the_driver(tmp_path, monkeypatch):
    from scalable_agent_tpu import driver
    from scalable_agent_tpu.obs import registry

    # a registry of this run's own: the process's one outlives the test,
    # and a later file's test reads what groups it holds
    monkeypatch.setattr(registry, "_registry", registry.MetricsRegistry())
    said = []
    info = driver.log.info
    monkeypatch.setattr(
        driver.log, "info",
        lambda message, *args: (said.append(message % args),
                                info(message, *args)))
    final = driver.main(driver_argv(tmp_path, TINY))
    assert final["env_frames"] == 3 * BATCH * UNROLL
    assert np.isfinite(final["total_loss"])
    assert final["nonfinite_skips"] == 0
    assert 0.0 < final["attention/key_blocks_visited_share"] <= 1.0
    assert 0.0 < final["moe/pairs_here_share"] < 1.0
    assert final["moe/tokens_per_expert_mean"] > 0.0
    assert final["moe/expert_load_max_over_mean"] >= 1.0
    snapshot = driver.get_registry().snapshot()
    for group in ("embedding", "attention", "ssd", "experts", "mlp", "norms",
                  "heads"):
        assert f"devtel/learn/grad_norm_{group}" in snapshot, group
    gauge = driver.get_registry().gauge
    per_env = 2 * 4 * (4 * 8 * 16 + 3 * 96)
    assert gauge("ssd/state_bytes_per_env").value == per_env
    assert gauge("ssm/state_bytes").value == BATCH * per_env
    assert gauge("cache/latent_bytes_per_token").value == 0
    assert gauge("cache/bytes").value == (
        BATCH * (EPISODE + UNROLL) * 2 * 2 * 8 * 4)
    assert gauge("policy/vocab_slice").value == VOCAB
    (line,) = [m for m in said if m.startswith("kernel policy")]
    assert "family=nemotron_h" in line and "policy=token" in line
    assert "2 mamba2, 2 experts, 1 full_attention" in line
    assert "experts_held=8/128" in line


def test_a_family_the_policy_does_not_build_is_refused_with_the_list(
        tmp_path):
    from scalable_agent_tpu import driver

    with pytest.raises(
            ValueError,
            match="afmoe.*phi4flash.*deepseek_v3.*nemotron_h"):
        driver.main(driver_argv(tmp_path, dict(TINY, model_type="llama")))
    assert token_policy.FAMILIES[3] == "nemotron_h"


def test_the_host_loop_is_refused_for_this_family_by_name(tmp_path):
    from scalable_agent_tpu import driver

    argv = [a for a in driver_argv(tmp_path, TINY)
            if not a.startswith("--train_backend")]
    with pytest.raises(ValueError, match="family nemotron_h.*train_backend"):
        driver.main(argv + ["--train_backend=host"])


def test_the_references_world_emits_the_14k_worlds_tokens():
    from scalable_agent_tpu.envs.device import make_device_env

    world = json.load(open(TRAFFIC_FILE))["world"]
    assert (world["vocab_size"], world["episode_length"],
            world["period"]) == (16384, 14336, 8192)
    env = make_device_env("token_recall_14k")
    assert (env.num_actions, env.episode_length, env.period) == (
        world["vocab_size"], world["episode_length"], world["period"])
    seeds = np.arange(BATCH, dtype=np.int32) + 1
    actions = jnp.asarray(np.random.default_rng(4).integers(
        0, world["vocab_size"], (20, BATCH)), jnp.int32)
    state, first = env.initial(seeds)
    _, outs = jax.lax.scan(lambda s, a: env.step(s, a), state, actions)
    held, (_, done, token) = ref.world_initial(world, seeds)
    np.testing.assert_array_equal(first.observation.frame, token)
    np.testing.assert_array_equal(first.done, done)
    for t in range(actions.shape[0]):
        held, (reward, done, token) = ref.world_step(world, held, actions[t])
        np.testing.assert_array_equal(outs.observation.frame[t], token)
        np.testing.assert_array_equal(outs.reward[t], reward)
    # at the cell's 32 envs the stagger puts episode ends at offsets 0,
    # 192, 128 and 64 of an unroll: mid-unroll, and mid-chunk at 64
    stagger = world["episode_length"] // 32
    ends = {(world["episode_length"] - env_ * stagger) % 256
            for env_ in range(32)}
    assert stagger == 448 and ends == {0, 64, 128, 192}
    assert {end % 128 for end in ends} == {0, 64}


def test_the_configuration_file_is_the_catalogs_but_for_what_it_lists():
    """Every number of the published configuration under its own key,
    but for the keys the file lists as reduced, each with what it was;
    every width as published."""
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 52, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072}
    cfg = json.load(open(CONFIG_FILE))
    differs = {key for key, value in published.items() if cfg[key] != value}
    assert differs == {"num_hidden_layers", "hybrid_override_pattern",
                       "vocab_size"}
    assert set(cfg["reduced"]) == differs | {"experts_held"}
    assert set(cfg["reduced_from"]) == set(cfg["reduced"])
    assert published["hybrid_override_pattern"].startswith(
        cfg["hybrid_override_pattern"])
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["experts_held"]) == (9, 8)
    for told in ("positions", "gated_norm", "router_bias", "time_step",
                 "rescale_prenorm_residual", "scan_precision", "value_head",
                 "weights", "optimizer"):
        assert told in cfg["assumed"], told
    assert "16 chips" in cfg["deployment"]
    kanana = json.load(open(os.path.join(
        ROOT, "benchmark/configs/kanana2_30b_ep8.json")))
    assert cfg["loss"] == kanana["loss"]
    assert cfg["optimizer"] == kanana["optimizer"]
    model = TokenModelConfig.from_dict(cfg)
    assert "".join({token_policy.MAMBA2: "M", token_policy.EXPERTS: "E",
                    token_policy.FULL: "*"}[k]
                   for k in model.layer_types) == "MEMEM*EME"
    assert (model.d_inner, model.conv_width, model.shared_expert_width) == (
        4096, 6144, 3712)
    shapes = ref.weight_shapes(cfg)
    count = sum(int(np.prod(s)) for s in shapes.values())
    assert 660e6 < count < 675e6, count
    assert ref.train_flops_per_env_frame(cfg) == pytest.approx(
        4 * ref.forward_flops_per_token(cfg, 7168.0))
    # one attention layer: a key more is 32 heads' score and value
    assert (ref.forward_flops_per_token(cfg, 7169.0)
            - ref.forward_flops_per_token(cfg, 7168.0)) == pytest.approx(
                2.0 * 2.0 * 32 * 128)


def test_the_cells_entry_names_its_traffic_and_its_metrics():
    bench = manifest.load_benchmark()
    (entry,) = [w for w in bench["workloads"]
                if w["name"] == "nemotron3.ingraph"]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "nemotron3_nano_ep16", "fused_token_recall_u256_e14336", 1)
    assert "384" in entry["why"] and "16x" in entry["why"]
    cell = manifest.load_cell("nemotron3.ingraph")
    flags = manifest.driver_flags(cell)
    assert (flags["batch_size"], flags["unroll_length"],
            flags["level_name"]) == (
                cell.config["sizing"]["fused_env_batch_1chip"], 256,
                "token_recall_14k")
    mine = {m.name: m.entry for m in cell.per_layer
            if m.entry.get("workloads") == ["nemotron3.ingraph"]}
    assert sorted(mine) == [
        "ssd_decode_roofline.fused", "ssd_device_share.fused",
        "ssd_scan_roofline.fused", "ssd_state_bytes_per_env"]
    assert all(e["moves"] == "fused_env_frames_per_s"
               for e in mine.values())
    assert {"device_mfu.fused", "fused_step_device_ms"} <= {
        m.name for m in cell.per_layer}


def _tiny_checkout(tmp_path, compute_dtype="float32"):
    """A copy of the benchmark whose ``nemotron3.ingraph`` files hold the
    tiny preset (the harness hands a cell's reference the configuration
    file whole, so the preset has to BE the file)."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "scalable_agent_tpu"),
               root / "scalable_agent_tpu")
    config_path = root / "benchmark/configs/nemotron3_nano_ep16.json"
    config = json.loads(config_path.read_text())
    config.update(TINY)
    config["flags"].update(
        unroll_length=UNROLL, compute_dtype=compute_dtype, mesh_data=1,
        learning_rate=TINY["optimizer"]["learning_rate"])
    config["sizing"]["fused_env_batch_1chip"] = BATCH
    config_path.write_text(json.dumps(config))
    traffic_path = (root / "benchmark/traffic"
                    / "fused_token_recall_u256_e14336.json")
    traffic = json.loads(traffic_path.read_text())
    traffic["flags"]["level_name"] = "token_recall_small"
    traffic["world"].update(vocab_size=VOCAB, episode_length=EPISODE,
                            period=10)
    traffic_path.write_text(json.dumps(traffic))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    return root, env, config, traffic


def test_the_cell_rehearses_through_the_harness_at_the_tiny_preset(tmp_path):
    """``benchmark/run.py --rehearse 1`` on a copy of the benchmark whose
    ``nemotron3.ingraph`` files hold the tiny preset: the probe's
    patches, the seeded weights into the policy's own tree, the three
    checked steps against the reference's own rollout of the world
    (episodes of 16 under an unroll of 6 and chunks of 4: an episode's
    end inside every unroll, inside a chunk and at its edge), the
    readers.  In float32 the program IS the reference: every compared
    number under 1e-4."""
    root, env, _, _ = _tiny_checkout(tmp_path)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "nemotron3.ingraph", "--rehearse", "1", "--seed", "3000000007",
         "--seconds", "2", "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["checks_failed"] == {}
    assert line["attempted"] > 0 and line["failed"] == 0
    for name, row in line["compared"].items():
        assert row["value"] < 1e-4, (name, row)
    would = line["rehearsal"]["metrics_that_would_print"]
    assert "first_update_s" in would
    assert "ssd_state_bytes_per_env" in would
    assert "latent_cache_bytes_per_token" not in would
