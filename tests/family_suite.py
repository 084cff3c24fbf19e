"""The suite every decoder family of the token policy
(models/token_policy.py ``FAMILIES``) inherits, written once against a
**preset** (``Preset``): the family's tiny configuration file, its plain
reference under benchmark/references/, its cell of the benchmark and
the bounds its numbers are held to.  Not collected itself: a family's
two files hold its preset and

    class TestPolicy(PolicyConformance):    preset = PRESET
    class TestHarness(HarnessConformance):  preset = PRESET

beside the tests of the mechanisms only that family has.  The driver
runs tier-1 with ``--dist loadfile``: a file is one worker's, so a
family keeps files of its own and the suite is what they share.

``PolicyConformance`` holds the program to the reference: one
T = unroll forward, the loss and every leaf's gradient in float32
(1e-5), the parameter tree, a bfloat16 band an fp8 cast falls out of,
the reference's planted fault, acting a token at a time against the
reference's whole forward and against forwards a few tokens at a time,
and ``unroll_state``.  ``HarnessConformance`` takes the family through
the system around it: ``driver.main``, what the policy refuses, the
cell's world, configuration file and entry, and the benchmark's harness
(``run.py``, ``seeds_big.py``, ``correct.follow``) at the tiny preset,
on ONE copy of the benchmark tree a family whose children share one
compile cache.

Where a family's copy asserted something the others did not, it is a
method of that family's subclass (``check_run``, ``check_rehearsal``,
``check_configuration``, or a test of its own); a test a family never
had and cannot have is set to None in its subclass, with the reason.
A case list that follows the family (a gradient leaf a case) comes from
the preset through ``per_preset`` (tests/conftest.py has the hook).
"""

import dataclasses
import functools
import json
import os
import shutil
import subprocess
import sys
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _path in (ROOT, os.path.join(ROOT, "tests")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from benchmark.lib import correct, manifest  # noqa: E402
from scalable_agent_tpu import driver  # noqa: E402
from scalable_agent_tpu.models import token_policy  # noqa: E402
from scalable_agent_tpu.models.token_policy import (  # noqa: E402
    TokenModelConfig,
    TokenPolicy,
)
from scalable_agent_tpu.parallel import MeshSpec, make_mesh  # noqa: E402
from scalable_agent_tpu.runtime.learner import (  # noqa: E402
    Learner,
    LearnerHyperparams,
    Trajectory,
)
from scalable_agent_tpu.types import (  # noqa: E402
    AgentOutput,
    Observation,
    StepOutput,
    StepOutputInfo,
)

LOSS = {"name": "vtrace", "entropy_cost": 0.00025, "baseline_cost": 0.5,
        "discounting": 0.99, "reward_clipping": "abs_one",
        "clip_rho_threshold": 1.0, "clip_pg_rho_threshold": 1.0}
OPTIMIZER = {"name": "rmsprop", "learning_rate": 0.00048,
             "rmsprop_decay": 0.99, "rmsprop_momentum": 0.0,
             "rmsprop_epsilon": 0.1, "initial_mean_square": 1.0,
             "total_environment_frames": 1e9}
# the world every tiny preset acts in (level ``token_recall_small``)
SMALL_WORLD = {"name": "token_recall_small", "vocab_size": 64,
               "episode_length": 16, "period": 10}


def env_outputs(tokens, done, reward=None):
    zeros = jnp.zeros(tokens.shape, jnp.float32)
    return StepOutput(
        reward=zeros if reward is None else reward,
        info=StepOutputInfo(zeros, jnp.zeros(tokens.shape, jnp.int32)),
        done=done, observation=Observation(frame=tokens))


def learner_of(agent, frames_per_update, **more):
    mesh = make_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    return Learner(agent, LearnerHyperparams(), mesh,
                   frames_per_update=frames_per_update, **more)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def per_preset(argname: str, field: str):
    """Parametrize ``argname`` by the class's ``preset.<field>``."""
    def mark(fn):
        fn.per_preset = getattr(fn, "per_preset", ()) + ((argname, field),)
        return fn
    return mark


@dataclasses.dataclass(frozen=True)
class Preset:
    """A family at the suite's size: vocabulary 64, unroll 6, episodes
    of 16, 4 envs, seeded weights."""

    tiny: Dict[str, Any]            # the family's configuration file, tiny
    reference: str                  # the module under benchmark/references/
    # the family's cell of the benchmark: its name, its configuration and
    # traffic files' names, its world's level and (vocabulary, episode,
    # period), and what ``BENCHMARK.json`` says of it
    cell: str
    config_file: str
    traffic_file: str
    level: str
    world: Tuple[int, int, int]
    why_says: Tuple[str, ...]
    own_metrics: Tuple[str, ...]    # the per-layer metrics that are its alone
    # the learner's parameter groups, and what the kernel-policy line
    # says beside the family's name
    groups: Tuple[str, ...]
    kernel_policy_says: Tuple[str, ...]
    lacking: Tuple[str, ...]        # keys a file may not lack, a case each
    published: Dict[str, Any]       # the catalog's configuration
    reduced_numbers: Tuple[str, ...]    # its keys the cell's file cuts
    # a dry run's line says it would print these, and not those
    prints: Tuple[str, ...]
    does_not_print: Tuple[str, ...]
    # (step, env): the episode ends inside the hand-made unroll beside
    # every env's at step 0
    ends_inside: Tuple[Tuple[int, int], ...] = ((3, 1), (5, 2))
    # every call through ``jax.jit`` (an eager interpret-mode kernel is
    # ten times slower) or op by op
    jitted: bool = False
    # a leaf's gradient is held to 1e-5 of the largest leaf's, or (a
    # number here) of its own where that is more than this share of the
    # largest's: a leaf whose gradient is tiny beside the largest is
    # then held to the float32 sum's own noise, not to its own size
    leaf_floor: Optional[float] = None
    every_leaf_has_a_gradient: bool = True
    # what two float32 sums of the same terms in another order may differ
    # by, as a share of the largest number compared (a family whose
    # layers amplify a rounding, layer on layer, states its own with the
    # reference's own distance from its float64 self)
    float32_gap: float = 1e-5
    # and what the harness's compared numbers may read in float32, three
    # steps on (each step starts from the last one's weights)
    rehearsal_gap: float = 1e-4
    # the bfloat16 loss against the float32 reference's lies inside, an
    # fp8 cast's outside
    bfloat16_band: float = 0.02
    # the reference's planted fault (its ``quant``), and the share of
    # the loss it must move
    fault: Optional[str] = None
    fault_moves: float = 1e-4
    # forty steps: env e's episodes end ``stagger`` steps after env
    # e - 1's
    stagger: int = 4
    unrolls_from: Tuple[str, ...] = ("forward", "rings")
    # the harness: ``run.py``'s extra flags, ``seeds_big.py``'s seeds,
    # keys of the cell's file that the tiny one must not inherit, the
    # bound on ``grad_norm_gap`` and what the planted faults must read
    rehearse_flags: Tuple[str, ...] = ()
    seeds: Tuple[int, ...] = (3000000007, 11)
    not_in_tiny: Tuple[str, ...] = ()
    # a leaf's first gradient is read out of a float32 mean square that
    # starts at 1: (0.99 + 0.01 g * g) - 0.99 keeps three digits of a
    # small one
    grad_norm_bound: float = 1e-3
    half_batch_moves: float = 0.1
    fp8_moves: float = 0.05
    unroll: int = 6
    episode: int = 16
    batch: int = 4
    vocab: int = 64

    @functools.cached_property
    def ref(self):
        return manifest.load_module(
            os.path.join(ROOT, "benchmark", "references",
                         self.reference + ".py"),
            f"reference_{self.reference}_tests")

    @functools.cached_property
    def model(self) -> TokenModelConfig:
        return TokenModelConfig.from_dict(self.tiny)

    @property
    def family(self) -> str:
        return self.tiny["model_type"]

    @property
    def config_path(self) -> str:
        return os.path.join(ROOT, "benchmark", "configs",
                            self.config_file + ".json")

    @property
    def traffic_path(self) -> str:
        return os.path.join(ROOT, "benchmark", "traffic",
                            self.traffic_file + ".json")

    @property
    def leaves(self):
        return sorted("/".join(path)
                      for path in self.ref.weight_shapes(self.tiny))

    def policy(self, dtype=jnp.float32, model=None, episode=None):
        return TokenPolicy(
            model=self.model if model is None else model,
            unroll_length=self.unroll,
            episode_length=self.episode if episode is None else episode,
            compute_dtype=dtype)

    def weights(self, seed=5, cfg=None):
        cfg = self.tiny if cfg is None else cfg
        return {"params": self.ref.to_tree(self.ref.make_weights(cfg, seed))}

    def learner(self, agent, **more):
        return learner_of(agent, self.batch * self.unroll, **more)

    def call(self, fn):
        return jax.jit(fn) if self.jitted else fn

    def unroll_stream(self, seed):
        """(tokens, done, the generator) of one hand-made unroll."""
        rng = np.random.default_rng(seed)
        tokens = jnp.asarray(
            rng.integers(0, self.vocab, (self.unroll + 1, self.batch)),
            jnp.int32)
        done = np.zeros((self.unroll + 1, self.batch), bool)
        done[0] = True
        for step, env in self.ends_inside:
            done[step, env] = True
        return tokens, jnp.asarray(done), rng

    def trajectory(self, agent, params, seed=3):
        """One unroll as the fused rollout lays it out, made by hand:
        T+1 entries, an episode's end inside it for some of the envs,
        behaviour log-probabilities from the policy's own logits moved a
        little off, so that the importance ratios are not 1."""
        steps, batch = self.unroll + 1, self.batch
        tokens, done, rng = self.unroll_stream(seed)
        actions = jnp.asarray(rng.integers(0, self.vocab, (steps, batch)),
                              jnp.int32)
        reward = jnp.asarray(rng.integers(0, 2, (steps, batch)),
                             jnp.float32)
        state = agent.initial_state(batch)
        (logits, _), _ = self.call(agent.apply)(
            params, actions, env_outputs(tokens, done, reward), state)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        taken = jnp.take_along_axis(logp[:-1], actions[1:, :, None],
                                    -1)[..., 0]
        noise = jnp.asarray(rng.normal(0, 0.2, taken.shape), jnp.float32)
        behaviour = jnp.concatenate([jnp.zeros((1, batch)), taken + noise])
        traj = Trajectory(
            agent_state=state,
            env_outputs=env_outputs(tokens, done, reward),
            agent_outputs=AgentOutput(
                action=actions, policy_logits=behaviour[..., None],
                baseline=jnp.zeros((steps, batch))))
        batch = self.ref.Batch(actions, behaviour, reward, done, tokens,
                               self.ref.empty_history(self.tiny, batch))
        return traj, batch

    def driver_argv(self, tmp_path, cfg=None, *more):
        path = tmp_path / "tiny.json"
        path.write_text(json.dumps(self.tiny if cfg is None else cfg))
        return [
            "--mode=train", f"--logdir={tmp_path / 'run'}",
            f"--model_config={path}", "--level_name=token_recall_small",
            "--train_backend=ingraph", f"--batch_size={self.batch}",
            f"--unroll_length={self.unroll}", "--num_action_repeats=1",
            "--compute_dtype=float32", "--mesh_data=1",
            f"--total_environment_frames={3 * self.batch * self.unroll}",
            "--log_interval_s=0.2", *more]

    def tiny_checkout(self, root, compute_dtype="float32"):
        """A copy of the benchmark under ``root`` whose files of this
        family's cell hold the tiny preset (the harness hands a cell's
        reference the configuration file whole, so the preset has to BE
        the file) -> (the copy's root, the environment of a child run
        there, the configuration, the traffic)."""
        root = root / "checkout"
        shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                        ignore=shutil.ignore_patterns("__pycache__", "tests"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
        os.symlink(os.path.join(ROOT, "scalable_agent_tpu"),
                   root / "scalable_agent_tpu")
        config_path = (root / "benchmark/configs"
                       / (self.config_file + ".json"))
        config = json.loads(config_path.read_text())
        config.update(self.tiny)
        for key in self.not_in_tiny:
            config.pop(key)
        config["flags"].update(
            unroll_length=self.unroll, compute_dtype=compute_dtype,
            mesh_data=1,
            learning_rate=self.tiny["optimizer"]["learning_rate"])
        config["sizing"]["fused_env_batch_1chip"] = self.batch
        config["mean_context"] = 8
        config_path.write_text(json.dumps(config))
        traffic_path = (root / "benchmark/traffic"
                        / (self.traffic_file + ".json"))
        traffic = json.loads(traffic_path.read_text())
        traffic["flags"]["level_name"] = SMALL_WORLD["name"]
        traffic["world"].update(SMALL_WORLD)
        traffic_path.write_text(json.dumps(traffic))
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        return root, env, config, traffic


# -- the program against its reference ----------------------------------------

class PolicyConformance:
    preset: Preset

    # -- (a) forward, loss and gradients against the reference

    @pytest.fixture(scope="class")
    def float32_pair(self):
        p = self.preset
        ref, tiny = p.ref, p.tiny
        agent, params = p.policy(), p.weights()
        traj, batch = p.trajectory(agent, params)
        learner = p.learner(agent)
        (loss, _), grads = p.call(jax.value_and_grad(
            lambda prm, t: learner._loss(prm, t, None), has_aux=True))(
                params, traj)
        ref_loss, ref_grads = p.call(jax.value_and_grad(
            lambda prm, b: ref.loss(tiny, prm, b)))(params["params"], batch)
        (logits, baseline), _ = p.call(agent.apply)(
            params, traj.agent_outputs.action, traj.env_outputs,
            traj.agent_state)
        ref_logits, ref_baseline, _ = p.call(
            lambda prm, b: ref.forward(tiny, prm, b.token, b.done,
                                       b.history))(params["params"], batch)
        return dict(loss=(loss, ref_loss), logits=(logits, ref_logits),
                    baseline=(baseline, ref_baseline), batch=batch,
                    params=params,
                    grads=(ref.from_tree(grads["params"]),
                           ref.from_tree(ref_grads)))

    @pytest.mark.parametrize("what", ["logits", "baseline", "loss"])
    def test_float32_forward_and_loss_are_the_references(
            self, float32_pair, what):
        """1e-5: both are float32 sums of the same terms in another
        order."""
        got, want = float32_pair[what]
        assert rel(got, want) < self.preset.float32_gap

    @per_preset("leaf", "leaves")
    def test_float32_gradient_is_the_references(self, float32_pair, leaf):
        p = self.preset
        got, want = float32_pair["grads"]
        path = tuple(leaf.split("/"))
        scale = max(float(np.max(np.abs(v))) for v in want.values())
        own = float(np.max(np.abs(want[path])))
        gap = float(np.max(np.abs(np.asarray(got[path], np.float64)
                                  - np.asarray(want[path], np.float64))))
        if p.leaf_floor is None:
            assert gap <= p.float32_gap * scale, (leaf, gap, scale)
        else:
            assert gap < p.float32_gap * max(own, p.leaf_floor * scale), (
                leaf, gap, own, scale)
        assert own > 0.0 or not p.every_leaf_has_a_gradient, leaf

    def test_the_program_has_the_references_leaves_and_no_other(self):
        p = self.preset
        agent = p.policy()
        one = jnp.zeros((1, p.batch), jnp.int32)
        made = jax.eval_shape(
            lambda: agent.init(
                jax.random.key(0), one,
                env_outputs(one, jnp.ones((1, p.batch), bool)),
                agent.initial_state(p.batch)))["params"]
        assert ({path: leaf.shape for path, leaf
                 in p.ref.from_tree(made).items()}
                == {path: tuple(shape) for path, shape
                    in p.ref.weight_shapes(p.tiny).items()})

    def test_bfloat16_loss_is_inside_a_band_fp8_falls_out_of(self):
        p = self.preset
        params = p.weights()
        agent = p.policy(jnp.bfloat16)
        traj, batch = p.trajectory(p.policy(), params)
        traj = traj._replace(agent_state=agent.initial_state(p.batch))
        learner = p.learner(agent)
        loss, _ = p.call(lambda prm, t: learner._loss(prm, t, None))(
            params, traj)
        want = float(p.ref.loss(p.tiny, params["params"], batch))
        fp8 = float(p.ref.loss(p.tiny, params["params"], batch,
                               quant="fp8"))
        assert abs(float(loss) - want) / abs(want) < p.bfloat16_band
        assert abs(fp8 - want) / abs(want) > p.bfloat16_band

    def test_the_references_planted_fault_moves_its_loss(self, float32_pair):
        """The limits file's own fault of the cell, at the loss: it moves
        by far more than float32's rounding."""
        p = self.preset
        batch, params = float32_pair["batch"], float32_pair["params"]
        want = float(p.ref.loss(p.tiny, params["params"], batch))
        planted = float(p.ref.loss(p.tiny, params["params"], batch,
                                   quant=p.fault))
        assert abs(planted - want) > p.fault_moves * abs(want)

    # -- (b) acting through the cache is the whole forward

    @pytest.fixture(scope="class")
    def forty_steps(self):
        """40 steps of 4 envs in episodes of 16, staggered: every env
        crosses two episode ends, a window ring (8 + 6 slots) wraps
        twice and a full ring (16 + 6) once.  -> (agent, params, tokens,
        done, the logits and the baselines a token at a time, the last
        state, the step)."""
        p = self.preset
        steps = 40
        rng = np.random.default_rng(11)
        tokens = jnp.asarray(rng.integers(0, p.vocab, (steps, p.batch)),
                             jnp.int32)
        offset = np.arange(p.batch) * p.stagger
        done = (np.arange(steps)[:, None] + offset[None, :]) % p.episode == 0
        done[0] = True
        done = jnp.asarray(done)
        agent, params = p.policy(), p.weights(9)
        step = jax.jit(lambda prm, e, s: agent.apply(
            prm, jnp.zeros(e.done.shape, jnp.int32), e, s))
        state, logits, values = agent.initial_state(p.batch), [], []
        for t in range(steps):
            (row, value), state = step(
                params, env_outputs(tokens[t:t + 1], done[t:t + 1]), state)
            logits.append(row[0])
            values.append(value[0])
        return (agent, params, tokens, done, jnp.stack(logits),
                jnp.stack(values), state, step)

    @pytest.mark.parametrize("what", ["logits", "baseline"])
    def test_stepwise_outputs_are_the_references_whole_forward(
            self, forty_steps, what):
        p = self.preset
        _, params, tokens, done, logits, values, _, _ = forty_steps
        whole, baseline, _ = p.call(lambda prm: p.ref.forward(
            p.tiny, prm, tokens, done,
            p.ref.empty_history(p.tiny, p.batch)))(params["params"])
        got, want = ((logits, whole) if what == "logits"
                     else (values, baseline))
        assert rel(got, want) < p.float32_gap

    @pytest.mark.parametrize("chunk", [2, 5, 7])
    def test_stepwise_logits_are_the_chunked_forwards(self, forty_steps,
                                                      chunk):
        batch = self.preset.batch
        agent, params, tokens, done, stepwise, _, last, _ = forty_steps
        state, rows = agent.initial_state(batch), []
        for t in range(0, tokens.shape[0], chunk):
            (logits, _), state = agent.apply(
                params, jnp.zeros((chunk, batch), jnp.int32),
                env_outputs(tokens[t:t + chunk], done[t:t + chunk]), state)
            rows.append(logits)
        got = jnp.concatenate(rows)
        assert rel(got, stepwise[:got.shape[0]]) < 1e-5
        if got.shape[0] == stepwise.shape[0]:
            for a, b in zip(jax.tree_util.tree_leaves(state),
                            jax.tree_util.tree_leaves(last)):
                np.testing.assert_allclose(np.asarray(a, np.float32),
                                           np.asarray(b, np.float32),
                                           atol=1e-5)

    # -- (c) what the update unrolls from

    @per_preset("what", "unrolls_from")
    def test_the_update_unrolls_from_the_rollouts_own_rings(
            self, forty_steps, what):
        """``unroll_state``: the rings as a rollout LEFT them, under the
        counters (and the scans' states and tails) of its start, give
        the forward that the start's own rings give."""
        p = self.preset
        unroll, batch = p.unroll, p.batch
        agent, params, tokens, done, *_ = forty_steps
        state = agent.initial_state(batch)
        zeros = jnp.zeros((unroll, batch), jnp.int32)
        for t in range(0, 30, unroll):
            start = state
            (_, _), state = agent.apply(
                params, zeros, env_outputs(tokens[t:t + unroll],
                                           done[t:t + unroll]), state)
        handed = agent.unroll_state(start, state)
        if what == "rings":
            for got, want in zip(handed.keys + handed.values,
                                 state.keys + state.values):
                assert got is want
            assert handed.written is start.written
            assert handed.episode_start is start.episode_start
        elif what == "recurrent":
            for got, want in zip(handed.ssm_state + handed.conv_tail,
                                 start.ssm_state + start.conv_tail):
                assert got is want
            assert float(jnp.max(jnp.abs(
                state.ssm_state[0] - start.ssm_state[0]))) > 0.0
        else:
            t = 30 - unroll
            again = env_outputs(tokens[t:t + unroll + 1],
                                done[t:t + unroll + 1])
            actions = jnp.zeros((unroll + 1, batch), jnp.int32)
            (want, _), _ = agent.apply(params, actions, again, start)
            (got, _), _ = agent.apply(params, actions, again, handed)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- the family through the system around it ----------------------------------

class HarnessConformance:
    preset: Preset

    # the family's own assertions on a run of the driver, on the cell's
    # file and on a rehearsal's line
    def check_run(self, final, gauge):
        pass

    def check_configuration(self, cfg, differs, model):
        pass

    def check_rehearsal(self, line, lines, root):
        pass

    @pytest.fixture
    def own_registry(self, monkeypatch):
        """A registry of this test's own: the process's one outlives a
        test, and another file's test in the same worker reads what
        groups and gauges it holds."""
        from scalable_agent_tpu.obs import registry

        monkeypatch.setattr(registry, "_registry", registry.MetricsRegistry())

    # -- (f) through the driver

    def test_three_updates_through_the_driver(self, tmp_path, own_registry):
        p = self.preset
        final = driver.main(p.driver_argv(tmp_path))
        assert final["env_frames"] == 3 * p.batch * p.unroll
        assert np.isfinite(final["total_loss"])
        assert final["nonfinite_skips"] == 0
        # the update's attention says how many key blocks it visited,
        # and the number is a gauge like the expert layers' (ISSUE 33)
        assert 0.0 < final["attention/key_blocks_visited_share"] <= 1.0
        snapshot = driver.get_registry().snapshot()
        for group in p.groups:
            assert f"devtel/learn/grad_norm_{group}" in snapshot, group
        gauge = driver.get_registry().gauge
        assert gauge("policy/vocab_slice").value == p.vocab
        self.check_run(final, gauge)

    def test_the_kernel_policy_line_names_the_family(
            self, tmp_path, monkeypatch, own_registry):
        from scalable_agent_tpu.config import Config

        p = self.preset
        said = []
        monkeypatch.setattr(
            driver.log, "info",
            lambda message, *args: said.append(message % args))
        config = Config.from_argv(p.driver_argv(tmp_path))
        _, action_space, _ = driver.probe_env(config)
        agent = driver.build_agent(config, action_space, ())
        assert agent.model.model_type == p.family
        (line,) = [m for m in said if m.startswith("kernel policy")]
        assert f"family={p.family}" in line and "policy=token" in line
        for text in p.kernel_policy_says:
            assert text in line, text

    def test_a_family_the_policy_does_not_build_is_refused_with_the_list(
            self, tmp_path):
        """With the families as they stand: the next one edits no
        earlier family's tests."""
        p = self.preset
        assert p.family in token_policy.FAMILIES
        argv = p.driver_argv(tmp_path, dict(p.tiny, model_type="llama"))
        with pytest.raises(ValueError,
                           match=".*".join(token_policy.FAMILIES)):
            driver.main(argv)

    @pytest.mark.parametrize("flags, names", [
        (["--train_backend=host"], "host loop"),
        (["--loss=impact"], "--loss=impact"),
        (["--replay_ratio=1"], "--replay_ratio=1"),
        (["--mesh_data=4"], "a mesh of 4 devices"),
        (["--sentinel_interval=5"], "--sentinel_interval=5"),
        (["--level_name=fake_small"], "token world"),
    ])
    def test_what_a_token_policy_is_not_built_for_is_refused_by_name(
            self, tmp_path, flags, names):
        p = self.preset
        argv = [a for a in p.driver_argv(tmp_path)
                if a.split("=")[0] not in {f.split("=")[0] for f in flags}]
        with pytest.raises(ValueError,
                           match=f"family {p.family}.*{names}"):
            driver.main(argv + flags)

    @per_preset("lacking", "lacking")
    def test_a_file_that_lacks_a_size_is_refused_by_its_name(self, lacking):
        raw = {k: v for k, v in self.preset.tiny.items() if k != lacking}
        with pytest.raises(ValueError, match=lacking):
            TokenModelConfig.from_dict(raw)

    # -- (g) the world and the configuration file

    def test_the_references_world_emits_the_cells_worlds_tokens(self):
        from scalable_agent_tpu.envs.device import make_device_env

        p = self.preset
        world = json.load(open(p.traffic_path))["world"]
        assert (world["vocab_size"], world["episode_length"],
                world["period"]) == p.world
        env = make_device_env(p.level)
        assert (env.num_actions, env.episode_length, env.period) == p.world
        seeds = np.arange(p.batch, dtype=np.int32) + 1
        actions = jnp.asarray(np.random.default_rng(4).integers(
            0, world["vocab_size"], (40, p.batch)), jnp.int32)
        state, first = env.initial(seeds)
        _, outs = jax.lax.scan(env.step, state, actions)
        held, (_, done, token) = p.ref.world_initial(world, seeds)
        np.testing.assert_array_equal(first.observation.frame, token)
        np.testing.assert_array_equal(first.done, done)
        for t in range(actions.shape[0]):
            held, (reward, done, token) = p.ref.world_step(
                world, held, actions[t])
            np.testing.assert_array_equal(outs.observation.frame[t], token)
            np.testing.assert_array_equal(outs.reward[t], reward)
            np.testing.assert_array_equal(outs.done[t], done)

    def test_the_configuration_file_is_the_catalogs_but_for_what_it_lists(
            self):
        """Every number of the published configuration under its own
        key, but for the keys the file lists as reduced, each with what
        it was; every width as published."""
        p = self.preset
        cfg = json.load(open(p.config_path))
        differs = {key for key, value in p.published.items()
                   if cfg[key] != value}
        assert differs == set(p.reduced_numbers)
        assert differs <= set(cfg["reduced"])
        assert set(cfg["reduced_from"]) == set(cfg["reduced"])
        assert cfg["vocab_size"] * 8 == p.published["vocab_size"]
        self.check_configuration(cfg, differs,
                                 TokenModelConfig.from_dict(cfg))

    def test_the_cells_entry_names_its_traffic_and_its_metrics(self):
        p = self.preset
        bench = manifest.load_benchmark()
        (entry,) = [w for w in bench["workloads"] if w["name"] == p.cell]
        assert (entry["config"], entry["traffic"], entry["chips"]) == (
            p.config_file, p.traffic_file, 1)
        for text in p.why_says:
            assert text in entry["why"], text
        cell = manifest.load_cell(p.cell)
        flags = manifest.driver_flags(cell)
        assert (flags["batch_size"], flags["unroll_length"],
                flags["level_name"]) == (
                    cell.config["sizing"]["fused_env_batch_1chip"], 256,
                    p.level)
        mine = {m.name: m.entry for m in cell.per_layer
                if m.entry.get("workloads") == [p.cell]}
        assert sorted(mine) == sorted(p.own_metrics)
        assert all(e["moves"] == "fused_env_frames_per_s"
                   for e in mine.values())
        assert {"device_mfu.fused", "fused_step_device_ms"} <= {
            m.name for m in cell.per_layer}

    # -- (h) the benchmark's harness at the tiny preset

    @pytest.fixture(scope="class")
    def checkout(self, tmp_path_factory):
        """ONE copy of the benchmark tree for the family's rehearsal,
        ``seeds_big`` and follow, and one compile cache for the children
        that run there: the second child finds the step's and the
        reference's programs the first compiled.  Both live and die with
        pytest's tmp: tests/conftest.py turns the cache off so that no
        test writes into the checkout or inherits an earlier run's
        entries, and a directory of the class's own keeps both."""
        base = tmp_path_factory.mktemp(self.preset.family)
        root, env, config, traffic = self.preset.tiny_checkout(base)
        env.update(JAX_ENABLE_COMPILATION_CACHE="true",
                   JAX_COMPILATION_CACHE_DIR=str(base / "compile_cache"))
        return root, env, config, traffic

    def _child(self, checkout, *argv):
        root, env, _, _ = checkout
        done = subprocess.run(
            [sys.executable, *argv], cwd=root, env=env, capture_output=True,
            text=True, timeout=900)
        assert done.returncode == 0, done.stderr[-2000:]
        return done.stdout

    def test_the_cell_rehearses_through_the_harness_at_the_tiny_preset(
            self, checkout):
        """``benchmark/run.py --rehearse 1`` on a copy of the benchmark
        whose files of the cell hold the tiny preset: the probe's
        patches, the seeded weights into the policy's own tree, the
        three checked steps against the reference's own rollout of the
        world (episodes of 16 under an unroll of 6: an episode's end
        inside every unroll), the readers.  In float32 the program IS
        the reference: every compared number under 1e-4."""
        p = self.preset
        lines = self._child(
            checkout, "benchmark/run.py", "--workload", p.cell,
            "--rehearse", "1", "--seed", "3000000007", "--seconds", "2",
            "--trace", "1", *p.rehearse_flags).strip().splitlines()
        line = json.loads(lines[-1])
        assert line["correct"] and line["checks_failed"] == {}
        assert line["attempted"] > 0 and line["failed"] == 0
        for name, row in line["compared"].items():
            assert row["value"] < p.rehearsal_gap, (name, row)
        # a dry run prints what needs no device; another cell's counters
        # are not this one's
        would = line["rehearsal"]["metrics_that_would_print"]
        assert "first_update_s" in would
        for name in p.prints:
            assert name in would, name
        for name in p.does_not_print:
            assert name not in would, name
        self.check_rehearsal(line, lines, checkout[0])

    def test_seeds_big_reads_the_cells_seeds_with_one_state(self, checkout):
        """``benchmark/seeds_big.py`` (what reads the limits file's rows
        on the chip) at the tiny preset: every third dispatch starts
        from the next seed's weights, the optimizer's leaves and the
        carry re-made in place, a leaf at a time, through the
        reference's ``make_weight_on_device``; in float32 each seed's
        three steps are the reference's (a seed that inherited anything
        of the last one's would not be), and both planted faults read
        far off."""
        p = self.preset
        out = self._child(
            checkout, "benchmark/seeds_big.py", "--workload", p.cell,
            "--rehearse", "1", "--seeds", ",".join(map(str, p.seeds)),
            "--faults", "1")
        rows = [json.loads(line.split(" ", 1)[1])
                for line in out.splitlines() if line.startswith("seed ")]
        sound = [row for row in rows if row["kind"] == "sound"]
        assert [row["seed"] for row in sound] == list(p.seeds)
        for row in sound:
            for name, value in row["compared"].items():
                bound = (p.grad_norm_bound if name == "grad_norm_gap"
                         else p.rehearsal_gap)
                assert value < bound, (row["seed"], name, value)
        planted = {row["kind"]: row["compared"] for row in rows
                   if row["kind"] != "sound"}
        assert set(planted) == {"control_fp8", "half_batch"}
        assert planted["half_batch"]["loss1_gap"] > p.half_batch_moves
        assert planted["control_fp8"]["loss_gap"] > p.fp8_moves

    def test_the_cells_own_fault_reads_far_off_through_follow(self, checkout):
        """The cell's own planted fault, read as the limits file's row
        is read on the chip: the reference with the fault against the
        reference without, through ``correct.follow``."""
        p = self.preset
        _, _, config, traffic = checkout
        fused = {"world": traffic["world"], "batch": p.batch,
                 "unroll_length": p.unroll, "program_seed": 5}
        frames = float(p.batch * p.unroll)
        sound = correct.follow(config, 11, frames, fused=fused,
                               reference=p.ref)
        planted = correct.follow(config, 11, frames, fused=fused,
                                 quant=p.fault, reference=p.ref)
        gaps = correct.compare(planted, sound)
        assert gaps["loss1_gap"] > 1e-4 and gaps["delta_norm_gap"] > 1e-4
        again = correct.follow(config, 11, frames, fused=fused,
                               reference=p.ref)
        assert correct.compare(again, sound)["loss_gap"] == 0.0
