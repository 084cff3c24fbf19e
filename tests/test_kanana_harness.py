"""The token policy's third family (``deepseek_v3``) through the system
around it, at tests/test_kanana_policy.py's tiny preset:

(f) the fused step trains through ``driver.main``; what a token policy
    is not built for is refused by name for this family too, and a file
    that lacks a size or asks for what is not built is refused;
(g) the world of the cell (``token_recall_10k``) is the reference's;
    the configuration file is the catalog's but for what it lists;
(h) the benchmark's harness (``run.py``, ``seeds_big.py``) drives the
    cell at the tiny preset, and in float32 the program is the
    reference; the cell's own planted fault reads far off through
    ``correct.follow``.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmark.lib import correct, manifest  # noqa: E402
from scalable_agent_tpu import driver  # noqa: E402
from scalable_agent_tpu.models import token_policy  # noqa: E402
from scalable_agent_tpu.models.token_policy import (  # noqa: E402
    TokenModelConfig,
)
from test_kanana_policy import (  # noqa: E402
    BATCH,
    EPISODE,
    ROW,
    TINY,
    UNROLL,
    VOCAB,
    ref,
)

CONFIG_FILE = os.path.join(ROOT, "benchmark/configs/kanana2_30b_ep8.json")
TRAFFIC_FILE = os.path.join(
    ROOT, "benchmark/traffic/fused_token_recall_u256_e10240.json")


# -- (f) through the driver ---------------------------------------------------

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


def test_three_updates_through_the_driver(tmp_path):
    final = driver.main(driver_argv(tmp_path, TINY))
    assert final["env_frames"] == 3 * BATCH * UNROLL
    assert np.isfinite(final["total_loss"])
    assert final["nonfinite_skips"] == 0
    assert 0.0 < final["attention/key_blocks_visited_share"] <= 1.0
    assert 0.0 < final["attention/decode_key_blocks_visited_share"] <= 1.0
    assert 0.0 < final["moe/pairs_here_share"] < 1.0
    snapshot = driver.get_registry().snapshot()
    for group in ("embedding", "attention", "experts", "mlp", "norms",
                  "heads"):
        assert f"devtel/learn/grad_norm_{group}" in snapshot, group
    gauge = driver.get_registry().gauge
    assert gauge("cache/latent_bytes_per_token").value == 4 * ROW
    assert gauge("cache/ring_bytes").value == (
        BATCH * (EPISODE + UNROLL) * 4 * ROW)
    assert gauge("cache/bytes").value == 3 * gauge("cache/ring_bytes").value
    assert gauge("cache/ring_readers").value == 1
    assert gauge("cache/full_slots").value == EPISODE + UNROLL
    assert gauge("policy/vocab_slice").value == VOCAB


def test_the_kernel_policy_line_names_the_family(tmp_path, monkeypatch):
    from scalable_agent_tpu.config import Config

    said = []
    monkeypatch.setattr(
        driver.log, "info",
        lambda message, *args: said.append(message % args))
    config = Config.from_argv(driver_argv(tmp_path, TINY))
    _, action_space, _ = driver.probe_env(config)
    agent = driver.build_agent(config, action_space, ())
    assert agent.model.model_type == "deepseek_v3"
    (line,) = [m for m in said if m.startswith("kernel policy")]
    assert "family=deepseek_v3" in line and "policy=token" in line
    assert "3 latent_attention" in line
    assert f"latent_bytes_per_token={4 * ROW}" in line
    assert "experts_held=2/8" in line


def test_a_family_the_policy_does_not_build_is_refused_with_the_list(
        tmp_path):
    argv = driver_argv(tmp_path, dict(TINY, model_type="llama"))
    with pytest.raises(ValueError,
                       match="afmoe.*phi4flash.*deepseek_v3"):
        driver.main(argv)
    # later families come after these three, which keep their places
    assert token_policy.FAMILIES[:3] == ("afmoe", "phi4flash", "deepseek_v3")


@pytest.mark.parametrize("flags, names", [
    (["--train_backend=host"], "host loop"),
    (["--loss=impact"], "--loss=impact"),
    (["--replay_ratio=1"], "--replay_ratio=1"),
    (["--sentinel_interval=2"], "--sentinel_interval=2"),
    (["--mesh_data=4"], "a mesh of 4 devices"),
])
def test_what_a_token_policy_is_not_built_for_is_refused_by_name(
        tmp_path, flags, names):
    argv = [a for a in driver_argv(tmp_path, TINY)
            if a.split("=")[0] not in {f.split("=")[0] for f in flags}]
    with pytest.raises(ValueError, match=f"family deepseek_v3.*{names}"):
        driver.main(argv + flags)


def test_a_world_that_is_no_token_world_is_refused_by_name(tmp_path):
    argv = [a for a in driver_argv(tmp_path, TINY)
            if not a.startswith("--level_name")]
    with pytest.raises(ValueError, match="family deepseek_v3.*token world"):
        driver.main(argv + ["--level_name=fake_small"])


@pytest.mark.parametrize("lacking", [
    "kv_lora_rank", "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
    "first_k_dense_replace", "routed_scaling_factor", "rope_interleave",
    "experts_held"])
def test_a_file_that_lacks_a_size_is_refused_by_its_name(lacking):
    raw = {k: v for k, v in TINY.items() if k != lacking}
    with pytest.raises(ValueError, match=lacking):
        TokenModelConfig.from_dict(raw)


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


# -- (g) the world and the configuration file ---------------------------------

def test_the_references_world_emits_the_10k_worlds_tokens():
    from scalable_agent_tpu.envs.device import make_device_env

    world = json.load(open(TRAFFIC_FILE))["world"]
    assert (world["vocab_size"], world["episode_length"],
            world["period"]) == (16032, 10240, 6144)
    env = make_device_env("token_recall_10k")
    assert (env.num_actions, env.episode_length, env.period) == (
        world["vocab_size"], world["episode_length"], world["period"])
    seeds = np.arange(BATCH, dtype=np.int32) + 1
    actions = jnp.asarray(np.random.default_rng(4).integers(
        0, world["vocab_size"], (20, BATCH)), jnp.int32)
    state, first = env.initial(seeds)

    def step(state, action):
        state, out = env.step(state, action)
        return state, out

    _, outs = jax.lax.scan(step, state, actions)
    held, (_, done, token) = ref.world_initial(world, seeds)
    np.testing.assert_array_equal(first.observation.frame, token)
    np.testing.assert_array_equal(first.done, done)
    for t in range(actions.shape[0]):
        held, (reward, done, token) = ref.world_step(world, held, actions[t])
        np.testing.assert_array_equal(outs.observation.frame[t], token)
        np.testing.assert_array_equal(outs.reward[t], reward)


def test_the_configuration_file_is_the_catalogs_but_for_what_it_lists():
    """Every number of the published configuration under its own key,
    but for the keys the file lists as reduced, each with what it was;
    every width as published."""
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "kv_lora_rank": 512,
        "max_position_embeddings": 32768, "model_type": "deepseek_v3",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 128, "n_shared_experts": 2,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 48,
        "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True,
        "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
    cfg = json.load(open(CONFIG_FILE))
    differs = {key for key, value in published.items() if cfg[key] != value}
    assert differs == {"num_hidden_layers", "vocab_size"}
    assert differs <= set(cfg["reduced"]) == differs | {"experts_held"}
    assert set(cfg["reduced_from"]) == set(cfg["reduced"])
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["experts_held"]) == (5, 16)
    for told in ("e_score_correction_bias", "group_limit", "positions",
                 "value_head", "weights", "optimizer"):
        assert told in cfg["assumed"], told
    assert "8 chips" in cfg["deployment"]
    trinity = json.load(open(os.path.join(
        ROOT, "benchmark/configs/trinity_mini_ep8.json")))
    assert cfg["loss"] == trinity["loss"]
    assert cfg["optimizer"] == trinity["optimizer"]
    model = TokenModelConfig.from_dict(cfg)
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


def test_the_cells_entry_names_its_traffic_and_its_metrics():
    bench = manifest.load_benchmark()
    (entry,) = [w for w in bench["workloads"]
                if w["name"] == "kanana2.ingraph"]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "kanana2_30b_ep8", "fused_token_recall_u256_e10240", 1)
    assert "384" in entry["why"] and "8x" in entry["why"]
    cell = manifest.load_cell("kanana2.ingraph")
    flags = manifest.driver_flags(cell)
    assert (flags["batch_size"], flags["unroll_length"],
            flags["level_name"]) == (
                cell.config["sizing"]["fused_env_batch_1chip"], 256,
                "token_recall_10k")
    mine = {m.name: m.entry for m in cell.per_layer
            if m.entry.get("workloads") == ["kanana2.ingraph"]}
    assert sorted(mine) == [
        "latent_attention_device_share.fused",
        "latent_cache_bytes_per_token", "latent_decode_roofline.fused",
        "latent_slot_kernel_share", "latent_update_roofline.fused"]
    assert all(e["moves"] == "fused_env_frames_per_s"
               for e in mine.values())
    assert {"device_mfu.fused", "fused_step_device_ms"} <= {
        m.name for m in cell.per_layer}


# -- (h) the harness at the tiny preset ---------------------------------------

def _tiny_checkout(tmp_path, compute_dtype="float32"):
    """A copy of the benchmark whose ``kanana2.ingraph`` files hold the
    tiny preset (the harness hands a cell's reference the configuration
    file whole, so the preset has to BE the file)."""
    import shutil

    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "scalable_agent_tpu"),
               root / "scalable_agent_tpu")
    config_path = root / "benchmark/configs/kanana2_30b_ep8.json"
    config = json.loads(config_path.read_text())
    config.update(TINY)
    config["flags"].update(
        unroll_length=UNROLL, compute_dtype=compute_dtype, mesh_data=1,
        learning_rate=TINY["optimizer"]["learning_rate"])
    config["sizing"]["fused_env_batch_1chip"] = BATCH
    config_path.write_text(json.dumps(config))
    traffic_path = (root / "benchmark/traffic"
                    / "fused_token_recall_u256_e10240.json")
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
    ``kanana2.ingraph`` files hold the tiny preset: the probe's patches,
    the seeded weights into the policy's own tree, the three checked
    steps against the reference's own rollout of the world (episodes of
    16 under an unroll of 6: an episode's end inside every unroll), the
    readers.  In float32 the program IS the reference: every compared
    number under 1e-4."""
    import subprocess

    root, env, _, _ = _tiny_checkout(tmp_path)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "kanana2.ingraph", "--rehearse", "1", "--seed", "3000000007",
         "--seconds", "2", "--trace", "1", "--control", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["checks_failed"] == {}
    assert line["attempted"] > 0 and line["failed"] == 0
    for name, row in line["compared"].items():
        assert row["value"] < 1e-4, (name, row)
    would = line["rehearsal"]["metrics_that_would_print"]
    assert "first_update_s" in would
    assert "latent_cache_bytes_per_token" in would
    assert "expert_load_max_over_mean" not in would


def test_seeds_big_reads_the_cells_seeds_with_one_state(tmp_path):
    """``benchmark/seeds_big.py`` (what reads the limits file's rows on
    the chip) at the tiny preset: it re-seeds this policy's tree in
    place, a leaf at a time, through the reference's
    ``make_weight_on_device``; in float32 each seed's three steps are
    the reference's, and both planted faults read far off."""
    import subprocess

    root, env, _, _ = _tiny_checkout(tmp_path)
    done = subprocess.run(
        [sys.executable, "benchmark/seeds_big.py", "--workload",
         "kanana2.ingraph", "--rehearse", "1", "--seeds",
         "3000000007,11", "--faults", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    rows = [json.loads(line.split(" ", 1)[1])
            for line in done.stdout.splitlines()
            if line.startswith("seed ")]
    sound = [row for row in rows if row["kind"] == "sound"]
    assert [row["seed"] for row in sound] == [3000000007, 11]
    for row in sound:
        for name, value in row["compared"].items():
            # a leaf's first gradient is read out of a float32 mean
            # square that starts at 1: (0.99 + 0.01 g * g) - 0.99 keeps
            # three digits of a small one
            bound = 1e-3 if name == "grad_norm_gap" else 1e-4
            assert value < bound, (row["seed"], name, value)
    planted = {row["kind"]: row["compared"] for row in rows
               if row["kind"] != "sound"}
    assert planted["half_batch"]["loss1_gap"] > 0.1
    assert planted["control_fp8"]["loss_gap"] > 0.02


def test_the_cells_own_fault_reads_far_off_through_follow(tmp_path):
    """The shared key left unrotated (``quant="no_rope_on_shared_key"``),
    read as the limits file's row is read on the chip: the reference
    with the fault against the reference without, through
    ``correct.follow``.  At this size every query's position differs
    from its keys', and the first loss already moves."""
    _, _, config, traffic = _tiny_checkout(tmp_path)
    fused = {"world": traffic["world"], "batch": BATCH,
             "unroll_length": UNROLL, "program_seed": 5}
    frames = float(BATCH * UNROLL)
    sound = correct.follow(config, 11, frames, fused=fused, reference=ref)
    planted = correct.follow(config, 11, frames, fused=fused,
                             quant=ref.NO_ROPE_ON_SHARED_KEY, reference=ref)
    gaps = correct.compare(planted, sound)
    assert gaps["loss1_gap"] > 1e-4 and gaps["delta_norm_gap"] > 1e-4
    again = correct.follow(config, 11, frames, fused=fused, reference=ref)
    assert correct.compare(again, sound)["loss_gap"] == 0.0
