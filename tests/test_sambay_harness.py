"""The token policy's second family (``phi4flash``) through the system
around it, at tests/test_sambay_policy.py's tiny preset:

(f) the fused step trains through ``driver.main``; what a token policy
    is not built for is refused by name whichever the family, a file
    that lacks a size or names a layer with nothing to read is refused,
    and the first family builds and steps as it did;
(g) the world of the cell (``token_recall_long``) is the reference's;
    the configuration file is the catalog's but for what it lists;
(h) the benchmark's harness (``run.py``, ``seeds_big.py``) drives the
    cell at the tiny preset, and in float32 the program is the
    reference.
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

from benchmark.lib import manifest  # noqa: E402
from scalable_agent_tpu import driver  # noqa: E402
from scalable_agent_tpu.models import token_policy  # noqa: E402
from scalable_agent_tpu.models.token_policy import (  # noqa: E402
    TokenModelConfig,
    TokenPolicy,
)
from test_sambay_policy import (  # noqa: E402
    BATCH,
    EPISODE,
    TINY,
    UNROLL,
    VOCAB,
    env_outputs,
    policy,
    ref,
)


# -- (f) through the driver ---------------------------------------------------

AFMOE_TINY = {
    "model_type": "afmoe", "hidden_act": "silu", "score_func": "sigmoid",
    "rope_scaling": None, "vocab_size": VOCAB, "hidden_size": 64,
    "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "num_shared_experts": 1,
    "num_hidden_layers": 2, "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "full_attention"],
    "sliding_window": 8, "route_scale": 2.826, "route_norm": True,
    "rope_theta": 10000, "rms_norm_eps": 1e-05, "mup_enabled": True,
    "experts_held": 2, "first_expert": 0,
}
FAMILIES = {"phi4flash": TINY, "afmoe": AFMOE_TINY}


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
    from scalable_agent_tpu.obs import registry

    # a registry of this run's own: the process's one holds whatever an
    # earlier file's driver run in the same worker left (an expert
    # family's groups), and a group is asserted ABSENT below
    monkeypatch.setattr(registry, "_registry", registry.MetricsRegistry())
    final = driver.main(driver_argv(tmp_path, TINY))
    assert final["env_frames"] == 3 * BATCH * UNROLL
    assert np.isfinite(final["total_loss"])
    assert final["nonfinite_skips"] == 0
    assert 0.0 < final["attention/key_blocks_visited_share"] <= 1.0
    snapshot = driver.get_registry().snapshot()
    for group in ("embedding", "attention", "ssm", "gmu", "mlp", "norms",
                  "heads"):
        assert f"devtel/learn/grad_norm_{group}" in snapshot, group
    assert "devtel/learn/grad_norm_experts" not in snapshot
    gauge = driver.get_registry().gauge
    assert gauge("ssm/state_bytes").value == BATCH * 4 * 128 * 2 * (8 + 3)
    assert gauge("cache/ring_readers").value == 2
    assert gauge("policy/vocab_slice").value == VOCAB
    assert gauge("cache/bytes").value == (
        BATCH * 2 * 2 * 16 * 4 * ((8 + UNROLL) + (EPISODE + UNROLL)))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_kernel_policy_line_names_the_family(
        tmp_path, family, monkeypatch):
    from scalable_agent_tpu.config import Config

    said = []
    monkeypatch.setattr(
        driver.log, "info",
        lambda message, *args: said.append(message % args))
    config = Config.from_argv(driver_argv(tmp_path, FAMILIES[family]))
    _, action_space, _ = driver.probe_env(config)
    agent = driver.build_agent(config, action_space, ())
    assert agent.model.model_type == family
    (line,) = [m for m in said if m.startswith("kernel policy")]
    assert f"family={family}" in line and "policy=token" in line


def test_a_family_the_policy_does_not_build_is_refused_with_the_list(
        tmp_path):
    argv = driver_argv(tmp_path, dict(TINY, model_type="llama"))
    with pytest.raises(ValueError, match="afmoe.*phi4flash"):
        driver.main(argv)


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("flags, names", [
    (["--train_backend=host"], "host loop"),
    (["--loss=impact"], "--loss=impact"),
    (["--replay_ratio=1"], "--replay_ratio=1"),
    (["--mesh_data=4"], "a mesh of 4 devices"),
])
def test_what_a_token_policy_is_not_built_for_is_refused_by_name(
        tmp_path, family, flags, names):
    argv = [a for a in driver_argv(tmp_path, FAMILIES[family])
            if a.split("=")[0] not in {f.split("=")[0] for f in flags}]
    with pytest.raises(ValueError, match=f"family {family}.*{names}"):
        driver.main(argv + flags)


@pytest.mark.parametrize("lacking", ["layer_kinds", "mamba_d_state",
                                     "layer_norm_eps", "sliding_window"])
def test_a_file_that_lacks_a_size_is_refused_by_its_name(lacking):
    raw = {k: v for k, v in TINY.items() if k != lacking}
    with pytest.raises(ValueError, match=lacking):
        TokenModelConfig.from_dict(raw)


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


def test_the_first_family_builds_and_steps_as_before():
    """Its state holds no scan's, its parameter groups and statistics
    are the ones it had, and a step of it runs."""
    model = TokenModelConfig.from_dict(AFMOE_TINY)
    agent = policy(model=model)
    state = agent.initial_state(BATCH)
    assert state.ssm_state == () and state.conv_tail == ()
    assert len(state.keys) == 2
    assert agent.layer_groups == TokenPolicy.layer_groups
    assert agent.STATS == TokenPolicy.STATS
    tokens = jnp.zeros((1, BATCH), jnp.int32)
    outputs = env_outputs(tokens, jnp.ones((1, BATCH), bool))
    params = agent.init(jax.random.key(0), tokens, outputs, state)
    (logits, _), new = agent.apply(params, tokens, outputs, state)
    assert logits.shape == (1, BATCH, VOCAB)
    assert int(new.written) == 1
    # later families come after these two, which keep their places
    assert token_policy.FAMILIES[:2] == ("afmoe", "phi4flash")


# -- the world, and the harness at the tiny preset ----------------------------

def test_the_references_world_emits_the_long_worlds_tokens():
    from scalable_agent_tpu.envs.device import make_device_env

    world = json.load(open(os.path.join(
        ROOT, "benchmark/traffic/fused_token_recall_u256_e6144.json")))[
            "world"]
    env = make_device_env("token_recall_long")
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
    but for the keys the file lists as reduced, each with what it was."""
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark/configs/phi4_mini_flash_vp8.json")))
    differs = {key for key, value in published.items() if cfg[key] != value}
    assert differs == {"num_hidden_layers", "vocab_size"}
    assert differs <= set(cfg["reduced"]) <= differs | {"layer_kinds"}
    assert set(cfg["reduced_from"]) == set(cfg["reduced"])
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    model = TokenModelConfig.from_dict(cfg)
    assert (model.d_inner, model.mamba_dt_rank) == (5120, 2560 // 16)
    assert model.memory_from == 2 and model.ring_of(5) == 3
    shapes = ref.weight_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 697_076_353


def _tiny_checkout(tmp_path, compute_dtype="float32"):
    """A copy of the benchmark whose ``phi4flash.ingraph`` files hold
    the tiny preset (the harness hands a cell's reference the
    configuration file whole, so the preset has to BE the file)."""
    import shutil

    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "scalable_agent_tpu"),
               root / "scalable_agent_tpu")
    config_path = root / "benchmark/configs/phi4_mini_flash_vp8.json"
    config = json.loads(config_path.read_text())
    config.update(TINY)
    config.pop("head_dim")
    config["flags"].update(
        unroll_length=UNROLL, compute_dtype=compute_dtype, mesh_data=1,
        learning_rate=TINY["optimizer"]["learning_rate"])
    config["sizing"]["fused_env_batch_1chip"] = BATCH
    config["mean_context"] = 8
    config_path.write_text(json.dumps(config))
    traffic_path = (root / "benchmark/traffic"
                    / "fused_token_recall_u256_e6144.json")
    traffic = json.loads(traffic_path.read_text())
    traffic["flags"]["level_name"] = "token_recall_small"
    traffic["world"].update(vocab_size=VOCAB, episode_length=EPISODE,
                            period=10)
    traffic_path.write_text(json.dumps(traffic))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    return root, env


def test_the_cell_rehearses_through_the_harness_at_the_tiny_preset(tmp_path):
    """``benchmark/run.py --rehearse 1`` on a copy of the benchmark whose
    ``phi4flash.ingraph`` files hold the tiny preset: the probe's
    patches, the seeded weights into the policy's own tree, the three
    checked steps against the reference's own rollout of the world
    (episodes of 16 under an unroll of 6: resets inside every unroll),
    the readers.  In float32 the program IS the reference: every
    compared number under 1e-4."""
    import subprocess

    root, env = _tiny_checkout(tmp_path)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "phi4flash.ingraph", "--rehearse", "1", "--seed", "3000000007",
         "--seconds", "2", "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["checks_failed"] == {}
    assert line["attempted"] > 0 and line["failed"] == 0
    for name, row in line["compared"].items():
        assert row["value"] < 1e-4, (name, row)
    # a dry run prints what needs no device; the expert layer's counter
    # is another cell's
    would = line["rehearsal"]["metrics_that_would_print"]
    assert "first_update_s" in would
    assert "expert_load_max_over_mean" not in would
    cell = manifest.load_cell("phi4flash.ingraph", root=str(root))
    assert {"ssm_device_share.fused", "ssm_scan_roofline.fused",
            "gmu_device_share.fused", "diff_attention_device_share.fused",
            "diff_attention_update_roofline.fused", "device_mfu.fused",
            "rollout_device_share.fused"} <= {m.name for m in cell.per_layer}


def test_seeds_big_reads_the_cells_seeds_with_one_state(tmp_path):
    """``benchmark/seeds_big.py`` (what reads the limits file's rows on
    the chip) at the tiny preset: it re-seeds this policy's tree in
    place, a leaf at a time, through the reference's
    ``make_weight_on_device``; in float32 each seed's three steps are
    the reference's, and both planted faults read far off."""
    import subprocess

    root, env = _tiny_checkout(tmp_path)
    done = subprocess.run(
        [sys.executable, "benchmark/seeds_big.py", "--workload",
         "phi4flash.ingraph", "--rehearse", "1", "--seeds",
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
            # the widest leaf's gap is a lambda vector's, whose gradient
            # this tool reads out of a float32 mean square that starts
            # at 1: (0.99 + 0.01 g * g) - 0.99 keeps three digits of it
            bound = 1e-3 if name == "grad_norm_gap" else 1e-4
            assert value < bound, (row["seed"], name, value)
    planted = {row["kind"]: row["compared"] for row in rows
               if row["kind"] != "sound"}
    assert planted["half_batch"]["loss1_gap"] > 0.1
    assert planted["control_fp8"]["loss_gap"] > 0.05
