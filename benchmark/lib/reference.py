"""The plain reference: IMPALA agent, V-trace loss, RMSProp, fake world.

Straightforward ``jax.numpy`` in float32 at ``precision=HIGHEST``: conv
or ResNet torso, LSTM(256) with done-reset, policy and baseline heads,
V-trace targets, the three loss terms (sums over time and batch, as
the source has them), TF-style RMSProp, and the ``fake_benchmark``
world with the sampling that drives a fused rollout.  It imports
nothing of the program and takes nothing the program made: sizes come
from the configuration file, weights from the seed
(``make_weights``), and the only inputs are trajectories (host loop)
or the seed (fused loop).

Sources followed: Espeholt et al. 2018 (arXiv:1802.01561) section 4 and
Fig. 3; deepmind/scalable_agent ``experiment.py`` (``_torso``,
``_head``, ``unroll``, ``compute_*_loss``, ``build_learner``) and
``vtrace.py`` (``from_importance_weights``).  Departures: the source
samples inside ``_head``; here sampling is a separate step with an
explicit key (JAX has no implicit RNG), keyed as the fused loop keys
it — ``fold_in(fold_in(key(seed), update), t)``, component 0.

``quant`` lowers the precision of every matmul/conv operand (used by
the control only): ``None`` float32, or ``"fp8"`` (float8_e4m3fn with a
per-tensor scale, straight-through backward) — the nearest precision
below the configurations' bfloat16.
"""

import zlib
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
CONV_DIMS = ("NHWC", "HWIO", "NHWC")


class _Static:
    """Hashable wrapper so a dict of sizes can be a static argument."""

    def __init__(self, value):
        self.value = value
        self._key = repr(sorted(value.items(), key=repr))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return self._key == other._key


# -- sizes and weights --------------------------------------------------------

def _same_out(size: int, stride: int) -> int:
    return -(-size // stride)


def weight_shapes(cfg: Dict[str, Any]) -> Dict[Tuple[str, ...], Tuple]:
    """Path -> shape of every parameter, from the configuration file."""
    h, w, c = cfg["frame_height"], cfg["frame_width"], cfg["frame_channels"]
    shapes: Dict[Tuple[str, ...], Tuple] = {}

    def conv(path, k, cin, cout):
        shapes[path + ("kernel",)] = (k, k, cin, cout)
        shapes[path + ("bias",)] = (cout,)

    def dense(path, fin, fout, bias=True):
        shapes[path + ("kernel",)] = (fin, fout)
        if bias:
            shapes[path + ("bias",)] = (fout,)

    if cfg["torso"] == "shallow":
        cin = c
        for i, (cout, k, s) in enumerate(cfg["conv_layers"]):
            conv(("convnet", f"conv_{i}"), k, cin, cout)
            h, w, cin = _same_out(h, s), _same_out(w, s), cout
    elif cfg["torso"] == "resnet":
        cin = c
        for i, (cout, blocks) in enumerate(cfg["resnet_sections"]):
            conv(("convnet", f"downscale_{i}"), 3, cin, cout)
            h, w, cin = _same_out(h, 2), _same_out(w, 2), cout
            for j in range(blocks):
                for n in (0, 1):
                    conv(("convnet", f"residual_{i}_{j}", f"conv_{n}"),
                         3, cout, cout)
    else:
        raise ValueError(f"unknown torso {cfg['torso']!r}")
    fc, hid, acts = cfg["fc_size"], cfg["lstm_size"], cfg["num_actions"]
    dense(("convnet", "fc"), h * w * cin, fc)
    core_in = fc + 1 + acts
    for gate in "ifgo":
        dense(("core", "lstm", f"i{gate}"), core_in, hid, bias=False)
        dense(("core", "lstm", f"h{gate}"), hid, hid)
    dense(("policy_logits",), hid, acts)
    dense(("baseline",), hid, 1)
    return shapes


def seed_key(seed: int):
    """A key from any whole number up to 2**63 (the driver's seeds pass
    2**31, which ``jax.random.key`` alone refuses)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key((seed >> 24) & 0x7FFFFFFF), seed & 0xFFFFFF)


def make_weights(cfg: Dict[str, Any], seed: int) -> Dict[Tuple[str, ...], Any]:
    """All weights in ONE jitted call on the device, float32 (the type
    the program keeps its parameters in): kernels normal with variance
    1/fan_in, biases normal at 0.02."""
    shapes = weight_shapes(cfg)
    paths = sorted(shapes)

    @jax.jit
    def build(key):
        out = {}
        for path in paths:
            shape = shapes[path]
            k = jax.random.fold_in(
                key, zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF)
            x = jax.random.normal(k, shape, jnp.float32)
            if path[-1] == "kernel":
                x = x * (1.0 / np.sqrt(np.prod(shape[:-1])))
            else:
                x = x * 0.02
            out[path] = x
        return out

    return build(seed_key(seed))


def to_tree(flat: Dict[Tuple[str, ...], Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for path, value in flat.items():
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = value
    return tree


def from_tree(tree, prefix=()) -> Dict[Tuple[str, ...], Any]:
    flat = {}
    for key, value in tree.items():
        if isinstance(value, dict) or hasattr(value, "items"):
            flat.update(from_tree(value, prefix + (key,)))
        else:
            flat[prefix + (key,)] = value
    return flat


# -- precision of the control -------------------------------------------------

def _quantizer(quant: Optional[str]):
    if quant is None:
        return lambda x: x
    if quant == "fp8":
        def q(x):
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0
            y = (x / scale).astype(jnp.float8_e4m3fn).astype(
                jnp.float32) * scale
            return x + lax.stop_gradient(y - x)
        return q
    raise ValueError(f"unknown quant {quant!r}")


# -- the agent ----------------------------------------------------------------

def _conv(x, p, stride, q):
    y = lax.conv_general_dilated(
        q(x), q(p["kernel"]), (stride, stride), "SAME",
        dimension_numbers=CONV_DIMS, precision=HIGHEST)
    return y + p["bias"]


def _dense(x, p, q):
    y = jnp.dot(q(x), q(p["kernel"]), precision=HIGHEST)
    return y + p["bias"] if "bias" in p else y


def torso(cfg, p, frame, q):
    """uint8 [N,H,W,C] -> [N, fc_size]."""
    x = frame.astype(jnp.float32) / 255.0
    if cfg["torso"] == "shallow":
        for i, (_, _, stride) in enumerate(cfg["conv_layers"]):
            x = jax.nn.relu(_conv(x, p[f"conv_{i}"], stride, q))
    else:
        for i, (_, blocks) in enumerate(cfg["resnet_sections"]):
            x = _conv(x, p[f"downscale_{i}"], 1, q)
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), "SAME")
            for j in range(blocks):
                r = p[f"residual_{i}_{j}"]
                y = _conv(jax.nn.relu(x), r["conv_0"], 1, q)
                y = _conv(jax.nn.relu(y), r["conv_1"], 1, q)
                x = x + y
        x = jax.nn.relu(x)
    x = x.reshape((x.shape[0], -1))
    return jax.nn.relu(_dense(x, p["fc"], q))


def lstm_step(p, c, h, x, done, q):
    """One LSTM step; a done flag zeroes the carry BEFORE the step."""
    keep = (1.0 - done)[:, None]
    c, h = c * keep, h * keep
    gate = lambda g: _dense(x, p[f"i{g}"], q) + _dense(h, p[f"h{g}"], q)
    i, f = jax.nn.sigmoid(gate("i")), jax.nn.sigmoid(gate("f"))
    g, o = jnp.tanh(gate("g")), jax.nn.sigmoid(gate("o"))
    c = f * c + i * g
    h = o * jnp.tanh(c)
    return c, h


def core_inputs(cfg, params, last_action, reward, frame, q):
    """[N,...] -> [N, fc+1+A]: torso, clipped reward, one-hot action."""
    feat = torso(cfg, params["convnet"], frame, q)
    clipped = jnp.clip(reward.astype(jnp.float32), -1.0, 1.0)[:, None]
    one_hot = jax.nn.one_hot(last_action, cfg["num_actions"],
                             dtype=jnp.float32)
    return jnp.concatenate([feat, clipped, one_hot], axis=-1)


def heads(params, h, q):
    logits = _dense(h, params["policy_logits"], q)
    baseline = _dense(h, params["baseline"], q)[..., 0]
    return logits, baseline


def unroll(cfg, params, actions, reward, done, frame, c0, h0, quant=None):
    """Time-major unroll: [T,B,...] -> logits [T,B,A], baseline [T,B]."""
    q = _quantizer(quant)
    t, b = actions.shape
    flat = lambda x: x.reshape((t * b,) + x.shape[2:])
    x = core_inputs(cfg, params, flat(actions), flat(reward), flat(frame),
                    q).reshape((t, b, -1))
    lstm = params["core"]["lstm"]

    def step(carry, xs):
        c, h = lstm_step(lstm, carry[0], carry[1], xs[0], xs[1], q)
        return (c, h), h

    _, hs = lax.scan(step, (c0, h0), (x, done.astype(jnp.float32)))
    logits, baseline = heads(params, hs.reshape((t * b, -1)), q)
    return logits.reshape((t, b, -1)), baseline.reshape((t, b))


# -- V-trace and the loss -----------------------------------------------------

def vtrace(log_rhos, discounts, rewards, values, bootstrap):
    """vs and policy-gradient advantages, rho-bar = c-bar = 1."""
    rhos = jnp.exp(log_rhos)
    clipped = jnp.minimum(1.0, rhos)
    cs = jnp.minimum(1.0, rhos)
    next_values = jnp.concatenate([values[1:], bootstrap[None]], axis=0)
    deltas = clipped * (rewards + discounts * next_values - values)

    def back(acc, xs):
        delta, discount, c = xs
        acc = delta + discount * c * acc
        return acc, acc

    _, diff = lax.scan(back, jnp.zeros_like(bootstrap),
                       (deltas, discounts, cs), reverse=True)
    vs = diff + values
    next_vs = jnp.concatenate([vs[1:], bootstrap[None]], axis=0)
    adv = clipped * (rewards + discounts * next_vs - values)
    return lax.stop_gradient(vs), lax.stop_gradient(adv)


class Batch(NamedTuple):
    """A trajectory batch, time-major, T+1 entries (the overlap
    layout): entry i holds the env output seen at step i and the agent
    output (action, logits) that LED to it."""

    action: Any        # i32 [T+1,B]
    logits: Any        # f32 [T+1,B,A]  behaviour
    reward: Any        # f32 [T+1,B]
    done: Any          # bool [T+1,B]
    frame: Any         # u8  [T+1,B,H,W,C]
    c0: Any            # f32 [B,hid]
    h0: Any


def loss(cfg, params, batch: Batch, quant=None):
    """The IMPALA loss as a SUM over time and batch (source:
    experiment.py build_learner): pg + 0.5*baseline + entropy_cost*ent."""
    hp = cfg["loss"]
    logits, baseline = unroll(cfg, params, batch.action, batch.reward,
                              batch.done, batch.frame, batch.c0, batch.h0,
                              quant)
    bootstrap = baseline[-1]
    logits, baseline = logits[:-1], baseline[:-1]
    actions = batch.action[1:]
    rewards = jnp.clip(batch.reward[1:], -1.0, 1.0)
    discounts = jnp.where(batch.done[1:], 0.0, hp["discounting"])
    logp = jax.nn.log_softmax(logits)
    logp_b = jax.nn.log_softmax(batch.logits[1:])
    take = lambda lp: jnp.take_along_axis(
        lp, actions[..., None], axis=-1)[..., 0]
    log_rhos = lax.stop_gradient(take(logp) - take(logp_b))
    vs, adv = vtrace(log_rhos, discounts, rewards,
                     lax.stop_gradient(baseline),
                     lax.stop_gradient(bootstrap))
    pg = jnp.sum(-take(logp) * adv)
    base = 0.5 * jnp.sum(jnp.square(vs - baseline))
    ent = jnp.sum(jnp.sum(jnp.exp(logp) * logp, axis=-1))
    return pg + hp["baseline_cost"] * base + hp["entropy_cost"] * ent


_LOSS_GRAD_FNS: Dict[Any, Any] = {}


def _loss_grad_fn(cfg_key, quant):
    """One jitted value-and-grad per (sizes, precision)."""
    key = (cfg_key, quant)
    if key not in _LOSS_GRAD_FNS:
        _LOSS_GRAD_FNS[key] = jax.jit(jax.value_and_grad(
            partial(loss, cfg_key.value, quant=quant)))
    return _LOSS_GRAD_FNS[key]


def loss_and_grads(cfg, params, batch: Batch, block: int, quant=None):
    """Loss and gradients over the whole batch, in blocks of ``block``
    batch columns (columns are independent and the loss is a sum, so
    the blocks add): what keeps the float32 reference inside the
    chip's memory at the cell's own batch."""
    fn = _loss_grad_fn(_Static(cfg), quant)
    total, grads = None, None
    width = batch.action.shape[1]
    for start in range(0, width, block):
        cols = slice(start, min(width, start + block))
        part = Batch(*(x[:, cols] for x in batch[:5]),
                     batch.c0[cols], batch.h0[cols])
        value, g = fn(params, part)
        total = value if total is None else total + value
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    return total, grads


# -- the optimizer ------------------------------------------------------------

def rmsprop_init(params):
    """TF's RMSProp starts the mean square at ONE."""
    return jax.tree_util.tree_map(jnp.ones_like, params)


@partial(jax.jit, static_argnums=(0,))
def _rmsprop(hp_key, params, nu, grads, lr):
    decay, eps = hp_key
    nu = jax.tree_util.tree_map(
        lambda n, g: decay * n + (1.0 - decay) * g * g, nu, grads)
    params = jax.tree_util.tree_map(
        lambda p, n, g: p - lr * g * lax.rsqrt(n + eps), params, nu, grads)
    return params, nu


def rmsprop_step(cfg, params, nu, grads, env_frames: float):
    """One step; the rate decays linearly to 0 over the total frames."""
    opt = cfg["optimizer"]
    lr = opt["learning_rate"] * max(
        0.0, 1.0 - env_frames / opt["total_environment_frames"])
    return _rmsprop((opt["rmsprop_decay"], opt["rmsprop_epsilon"]),
                    params, nu, grads, jnp.float32(lr))


# -- the fake world and the fused rollout -------------------------------------

class World(NamedTuple):
    seed: Any
    episode: Any
    step: Any
    ret: Any
    agent_step: Any


def world_frame(cfg, seed, episode, step, action):
    base = ((seed % 251) * 131 % 251 + (episode % 251) * 17
            + (step % 251) * 7) % 251
    b = base.shape[0]
    frame = jnp.broadcast_to(
        base.astype(jnp.uint8)[:, None, None, None],
        (b, cfg["frame_height"], cfg["frame_width"],
         cfg["frame_channels"]))
    frame = frame.at[:, 0, 0, 0].set((episode % 256).astype(jnp.uint8))
    frame = frame.at[:, 0, 1, 0].set((step % 256).astype(jnp.uint8))
    return frame.at[:, 0, 2, 0].set((action % 256).astype(jnp.uint8))


def world_initial(cfg, world_cfg, seeds):
    seeds = jnp.asarray(seeds, jnp.int32)
    zi = jnp.zeros_like(seeds)
    zf = jnp.zeros(seeds.shape, jnp.float32)
    world = World(seeds, zi, zi, zf, zi)
    frame = world_frame(cfg, seeds, zi, zi, zi)
    return world, (zf, jnp.ones(seeds.shape, bool), frame)


def world_step(cfg, world_cfg, world: World, action):
    """One agent step = ``num_action_repeats`` simulator steps: reward
    0.1*(step%3) each plus 1 at the episode's end, then auto-reset."""
    step, reward = world.step, jnp.zeros_like(world.ret)
    done = jnp.zeros(step.shape, bool)
    for _ in range(cfg["num_action_repeats"]):
        active = ~done
        step = step + active.astype(jnp.int32)
        sub_done = active & (step >= world_cfg["episode_length"])
        reward = reward + jnp.where(
            active, 0.1 * (step % 3).astype(jnp.float32), 0.0)
        reward = reward + jnp.where(sub_done, 1.0, 0.0)
        done = done | sub_done
    episode = world.episode + done.astype(jnp.int32)
    step = jnp.where(done, 0, step)
    new = World(world.seed, episode, step,
                jnp.where(done, 0.0, world.ret + reward),
                jnp.where(done, 0, world.agent_step + 1))
    frame = world_frame(cfg, world.seed, episode, step,
                        jnp.where(done, 0, action))
    return new, (reward, done, frame)


class RolloutCarry(NamedTuple):
    world: World
    reward: Any
    done: Any
    frame: Any
    action: Any
    logits: Any
    c: Any
    h: Any


def rollout_initial(cfg, world_cfg, batch: int, program_seed: int):
    world, (reward, done, frame) = world_initial(
        cfg, world_cfg, np.arange(batch, dtype=np.int32) + program_seed)
    hid = cfg["lstm_size"]
    return RolloutCarry(
        world, reward, done, frame,
        jnp.zeros((batch,), jnp.int32),
        jnp.zeros((batch, cfg["num_actions"]), jnp.float32),
        jnp.zeros((batch, hid), jnp.float32),
        jnp.zeros((batch, hid), jnp.float32))


@partial(jax.jit, static_argnums=(0, 1, 5, 6))
def _rollout(cfg_key, world_key, params, carry, rng, unroll_length, quant):
    cfg, world_cfg = cfg_key.value, world_key.value
    q = _quantizer(quant)

    def step(c: RolloutCarry, t):
        x = core_inputs(cfg, params, c.action, c.reward, c.frame, q)
        cc, hh = lstm_step(params["core"]["lstm"], c.c, c.h, x,
                           c.done.astype(jnp.float32), q)
        logits, _ = heads(params, hh, q)
        key = jax.random.fold_in(jax.random.fold_in(rng, t), 0)
        action = jax.random.categorical(key, logits, axis=-1).astype(
            jnp.int32)
        world, (reward, done, frame) = world_step(
            cfg, world_cfg, c.world, action)
        new = RolloutCarry(world, reward, done, frame, action, logits,
                           cc, hh)
        return new, (action, logits, reward, done, frame)

    new, seq = lax.scan(step, carry, jnp.arange(unroll_length))
    first = (carry.action, carry.logits, carry.reward, carry.done,
             carry.frame)
    stacked = [jnp.concatenate([f[None], s], axis=0)
               for f, s in zip(first, seq)]
    return Batch(*stacked, carry.c, carry.h), new


def rollout(cfg, world_cfg, params, carry: RolloutCarry, program_seed: int,
            update_index: int, unroll_length: int, quant=None):
    """One fused-loop unroll under ``params``: T steps of (T=1
    inference, sample, world step), keyed as the fused loop keys it."""
    rng = jax.random.fold_in(jax.random.key(program_seed), update_index)
    return _rollout(_Static(cfg), _Static(world_cfg), params, carry, rng,
                    unroll_length, quant)
