"""The learner's unroll is data-parallel in fact (ISSUE 26).

On a ``data=4`` mesh each device must compute its own quarter of the
envs' torso, heads and backward, and the only cross-device traffic of
an update is the gradient all-reduce (plus scalar metrics).  Before
PR 26 the agent merged ``[T, B] -> [T*B]`` time-major while B was the
sharded axis: the SPMD partitioner gathered the frames and every device
ran every convolution over the whole global batch — four chips at 1.09x
one chip.

Everything here is a verdict of the CPU rig's partitioner on compiled
text (4 of conftest's 8 host devices), plus a numeric comparison with
the single-device program.  Sizes are chosen so that the global batch
(28), its merges with time (6 x 28 = 168 rows in the unroll, 5 x 28 =
140 in the loss) and a device's share of the unroll's (42) are dims
nothing else in the program has, a device's own merges (42, 35)
included.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_agent_tpu.models import ImpalaAgent
from scalable_agent_tpu.models.agent import initial_state
from scalable_agent_tpu.obs import kernels as kernels_lib
from scalable_agent_tpu.parallel import (
    MeshSpec,
    batch_sharding,
    make_mesh,
    replicated_sharding,
)
from scalable_agent_tpu.types import Observation, StepOutput

DEVICES = 4
T, B = 6, 28                    # the learner's T+1 rows, global envs
H, W = 24, 32
NUM_ACTIONS = 9
GLOBAL = {B, T * B, (T - 1) * B}    # dims only a global-batch array has
MERGED_LOCAL = T * B // DEVICES

_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<shape>\(.*?\)|\S+)\s+(?P<op>[\w\-]+)\((?P<rest>.*)$")
_DIMS_RE = re.compile(r"[a-z]+[0-9a-z]*\[([0-9,]*)\]")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def _dims(text):
    return [[int(d) for d in dims.split(",") if d]
            for dims in _DIMS_RE.findall(text)]


def _instructions(hlo_text):
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m:
            scope = _OP_NAME_RE.search(line)
            yield (m.group("name"), m.group("op"), _dims(m.group("shape")),
                   line, scope.group(1) if scope else "")


def _is_scalar_metric(dims_list, scope):
    """At most one scalar per step per env, and only the obs plane
    reads it (``telemetry`` scope): V-trace's exact p95 of log-rho
    sorts the whole [T, B] and so gathers 4 bytes a step — a scalar
    metric's input, not a layer's activations."""
    return "telemetry" in scope and all(
        int(np.prod(d)) <= T * B for d in dims_list)


def global_batch_carriers(hlo_text):
    """Instructions of a partitioned module whose per-device result
    still has a global-batch dim, scalar metrics aside.  (A nested
    computation's ``parameter`` carries no scope and is some listed
    instruction's operand.)"""
    return [
        f"{name} = {op} {dims_list}"
        for name, op, dims_list, _, scope in _instructions(hlo_text)
        if op != "parameter" and any(GLOBAL & set(d) for d in dims_list)
        and not _is_scalar_metric(dims_list, scope)]


def convolution_batches(hlo_text, scope_word=""):
    """For every convolution (under ``scope_word``): the set of dims of
    its result and operands — the batch is one of them whatever the
    dim_labels (a weight gradient contracts over it)."""
    shapes = {name: dims_list
              for name, _, dims_list, _, _ in _instructions(hlo_text)}
    out = []
    for name, op, dims_list, line, scope in _instructions(hlo_text):
        if op != "convolution" or scope_word not in scope:
            continue
        args = line.split(" convolution(", 1)[1].split(")", 1)[0]
        operands = _dims(args)          # printed with their shapes, or
        for operand in re.findall(r"%([\w.\-]+)", args):   # by name
            operands += shapes.get(operand, [])
        out.append((name, set(sum(dims_list + operands, []))))
    return out


def assert_data_parallel(hlo_text, conv_scope=""):
    rows = kernels_lib.collectives(hlo_text)
    assert rows, "a data=4 update has at least the gradient all-reduce"
    moved = [r for r in rows
             if any(GLOBAL & set(d) for d in r["dims"])
             and not _is_scalar_metric(r["dims"], r["op_name"] or "")]
    assert not moved, (
        "collectives move global-batch arrays: "
        + "; ".join(f"{r['name']} {r['dims']}" for r in moved))
    assert not [r for r in rows if r["kind"] == "other"], rows
    carriers = global_batch_carriers(hlo_text)
    assert not carriers, carriers[:12]
    convs = convolution_batches(hlo_text, conv_scope)
    assert convs
    for name, dims in convs:
        assert MERGED_LOCAL in dims and not (GLOBAL & dims), (name, dims)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(MeshSpec(data=DEVICES), devices=jax.devices()[:DEVICES])


def _inputs(seed=0):
    rng = np.random.default_rng(seed)
    actions = jnp.asarray(rng.integers(0, NUM_ACTIONS, (T, B)), jnp.int32)
    env_outputs = StepOutput(
        reward=jnp.asarray(rng.normal(size=(T, B)), jnp.float32),
        info=None,
        done=jnp.asarray(rng.random((T, B)) < 0.2),
        observation=Observation(
            frame=jnp.asarray(rng.integers(0, 256, (T, B, H, W, 3)),
                              jnp.uint8),
            instruction=None))
    return actions, env_outputs, initial_state(B)


def _place(mesh, actions, env_outputs, core_state):
    tb, b = batch_sharding(mesh, 1), batch_sharding(mesh, 0)
    put = lambda tree, s: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.device_put(x, s), tree)
    return put(actions, tb), put(env_outputs, tb), put(core_state, b)


def _loss(agent):
    def loss(params, actions, env_outputs, core_state):
        (logits, baseline), state = agent.apply(
            params, actions, env_outputs, core_state)
        return (jnp.mean(jnp.square(logits)) + jnp.mean(baseline * baseline)
                + jnp.mean(state.h))
    return loss


@pytest.mark.parametrize("torso", ["shallow", "resnet"])
def test_agent_gradient_is_sharded_over_the_batch(mesh, torso):
    """Shard-major merge on data=4: no gather, every convolution over a
    quarter of the merged batch, and the loss and gradients of the
    plain time-major merge on one device."""
    kwargs = dict(num_actions=NUM_ACTIONS, torso_type=torso, core_impl="xla")
    actions, env_outputs, core_state = _inputs()
    one_device = ImpalaAgent(**kwargs)
    params = one_device.init(
        jax.random.key(1), actions, env_outputs, core_state)
    want_loss, want_grads = jax.jit(jax.value_and_grad(_loss(one_device)))(
        params, actions, env_outputs, core_state)

    step = jax.jit(jax.value_and_grad(
        _loss(ImpalaAgent(batch_shards=DEVICES, **kwargs))))
    sharded = _place(mesh, actions, env_outputs, core_state)
    placed = jax.device_put(params, replicated_sharding(mesh))
    assert_data_parallel(
        step.lower(placed, *sharded).compile().as_text())
    got_loss, got_grads = step(placed, *sharded)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    for got, want in zip(jax.tree_util.tree_leaves(got_grads),
                         jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(
            got, want, rtol=1e-4, atol=1e-6 + 1e-5 * np.abs(want).max())


def test_time_major_merge_of_a_sharded_batch_is_what_replicates(mesh):
    """The control: the same gradient with the merge left time-major
    (``batch_shards=1``, every program before PR 26) on the same data=4
    placement gathers the frames, the reward, the actions, the core's
    output and the torso's cotangent, and convolves the global batch
    on every device."""
    agent = ImpalaAgent(num_actions=NUM_ACTIONS, core_impl="xla")
    actions, env_outputs, core_state = _inputs()
    params = agent.init(jax.random.key(1), actions, env_outputs, core_state)
    text = jax.jit(jax.value_and_grad(_loss(agent))).lower(
        jax.device_put(params, replicated_sharding(mesh)),
        *_place(mesh, actions, env_outputs, core_state)).compile().as_text()
    gathered = [r["dims"][0] for r in kernels_lib.collectives(text)
                if r["kind"] == "all_gather"]
    assert [T, B, H, W, 3] in gathered and [T, B, 256] in gathered
    with pytest.raises(AssertionError, match="global-batch arrays"):
        assert_data_parallel(text)
    assert any(T * B in dims for _, dims in convolution_batches(text))


def test_one_shard_lowers_the_plain_reshape():
    """On one device nothing is reordered: the unroll lowers without a
    single transpose (the program of every PR before 26), and a shard
    count the batch does not divide, or T = 1, falls back to it."""
    actions, env_outputs, core_state = _inputs()
    plain = ImpalaAgent(num_actions=NUM_ACTIONS, core_impl="xla")
    params = plain.init(jax.random.key(1), actions, env_outputs, core_state)

    def lowered(agent, *args):
        return jax.jit(agent.apply).lower(params, *args).as_text()

    text = lowered(plain, actions, env_outputs, core_state)
    assert "stablehlo.transpose" not in text
    assert "stablehlo.transpose" in lowered(
        ImpalaAgent(num_actions=NUM_ACTIONS, core_impl="xla",
                    batch_shards=DEVICES),
        actions, env_outputs, core_state)
    odd = ImpalaAgent(num_actions=NUM_ACTIONS, core_impl="xla",
                      batch_shards=3)          # 28 % 3 != 0
    assert lowered(odd, actions, env_outputs, core_state) == text
    acting = jax.tree_util.tree_map(
        lambda x: x[:1], (actions, env_outputs)) + (core_state,)
    assert (lowered(ImpalaAgent(num_actions=NUM_ACTIONS, core_impl="xla",
                                batch_shards=DEVICES), *acting)
            == lowered(plain, *acting))


def test_the_learner_points_the_agent_at_its_mesh(mesh):
    from scalable_agent_tpu.parallel import batch_shards
    from scalable_agent_tpu.runtime import Learner, LearnerHyperparams

    agent = ImpalaAgent(num_actions=NUM_ACTIONS)
    hp = LearnerHyperparams(total_environment_frames=1e6)
    assert batch_shards(mesh.shape) == DEVICES
    assert batch_shards({"data": 2, "seq": 2, "model": 2}) == 4
    assert Learner(agent, hp, mesh, 1)._agent.batch_shards == DEVICES
    one = make_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    assert Learner(agent, hp, one, 1)._agent is agent


def _trainer(devices):
    from scalable_agent_tpu.envs.device import DeviceFakeEnv
    from scalable_agent_tpu.runtime import (
        InGraphTrainer,
        Learner,
        LearnerHyperparams,
    )

    agent = ImpalaAgent(num_actions=NUM_ACTIONS, core_impl="xla")
    mesh = make_mesh(MeshSpec(data=devices), devices=jax.devices()[:devices])
    learner = Learner(agent, LearnerHyperparams(
        total_environment_frames=1e6), mesh,
        frames_per_update=(T - 1) * B)
    env = DeviceFakeEnv(height=H, width=W, num_actions=NUM_ACTIONS,
                        episode_length=7)
    trainer = InGraphTrainer(agent, learner, env, T - 1, B, seed=5)
    state, carry = trainer.init(jax.random.key(0))
    return trainer, state, carry


def test_fused_step_is_sharded_over_the_batch():
    """The whole fused step (rollout scan + update + telemetry) on
    data=4: nothing but the all-reduce and scalar metrics crosses
    devices, the update's convolutions see a quarter of the merged
    batch, and three steps give the single-device run's loss and
    gradient norm."""
    trainer, state, carry = _trainer(DEVICES)
    text = trainer.train_step.lower(
        state, carry, np.int32(0)).compile().as_text()
    assert_data_parallel(text, conv_scope="learner_update")
    totals = kernels_lib.collective_bytes(kernels_lib.collectives(text))
    # the gathers left are V-trace's p95 inputs: a few [T, B] scalars
    assert totals["all_gather"] <= 8 * 4 * T * B, totals
    assert totals["all_reduce"] > 1e6, totals     # the torso's gradient

    one, one_state, one_carry = _trainer(1)
    for i in range(3):
        state, carry, got = trainer.train_step(state, carry, np.int32(i))
        one_state, one_carry, want = one.train_step(
            one_state, one_carry, np.int32(i))
        for key in ("total_loss", "grad_norm"):
            np.testing.assert_allclose(got[key], want[key], rtol=2e-4,
                                       err_msg=f"{key} at step {i}")
