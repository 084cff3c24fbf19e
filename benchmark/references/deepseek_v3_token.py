"""The plain reference of a token policy of the ``deepseek_v3`` family:
one expert-parallel chip's share of a latent-attention (MLA)
mixture-of-experts decoder (``q_lora_rank`` null: a whole query
projection; keys and values up-projected from one compressed row a
token; shared experts and sigmoid routing over all experts, of which
this chip holds a slice, behind leading dense layers), a
vocabulary-sized action head and the system's value head, trained by
V-trace and TF-style RMSProp in the ``token_recall`` world.

Straightforward ``jax.numpy`` in float32 at ``precision=HIGHEST``, no
kernel, no cache ring, no grouped matrix product and no absorption:
every token's whole keys and values are up-projected from its row, in
every forward, and attention is one masked softmax over them.  The
history an unroll attends back into is its own: a list of the rows
``[c | r]`` (the normalised compression and the rotated shared key)
in the order they were made, in a buffer with room for three unrolls
(made under whatever parameters were current when their tokens were
acted on, as a cache holds them; their keys and values are
up-projections under the parameters of the forward that reads them,
which is what the model's own cache gives).  Every held expert runs
over every token and is weighted by what the router gave it (0 where
the token was routed elsewhere).  It imports nothing of the program and
takes nothing the program made: sizes come from the configuration file,
weights from the seed, the world from the traffic file's ``world``
block and the program's seed; the pieces no architecture changes come
from the harness's ``benchmark/lib/reference.py``.

The layer, for token ids ``x`` (``p`` a token's index in its
episode)::

    h = E[x]
    a = RMSNorm_in(h)
    q = a Wq [heads, nope + rope] = [q_nope | q_rope]
    [c | r] = a Wkva [kv_lora_rank | rope];  c = RMSNorm_kv(c)
    q_rope, r = RoPE(q_rope, r; theta, p)    interleaved pairs; one r for all heads
    [k_nope | v] = c Wkvb [heads, nope | v_head_dim]
    s_ij = (q_nope_i . k_nope_j + q_rope_i . r_j) / sqrt(nope + rope)   j <= i, same episode
    h = h + concat_heads(softmax(s) v) Wo
    m = RMSNorm_post(h)
    layer < first_k_dense_replace:  f = (silu(m W1) * (m W3)) W2
    expert layer: p = sigmoid(m Wr);  S = top_k(p + b), b = 0
                  w_e = routed_scaling_factor * p_e / (sum_{e in S} p_e + 1e-20)
                  f = shared(m) + sum_{e in S, e held here} w_e expert_e(m)
    h = h + f

and after the last layer ``z = RMSNorm_f(h)``, ``policy_logits = z
W_head``, ``baseline = z w_b + c``.  What the source's configuration
has no key for is listed in the configuration file's ``assumed``.

``quant`` lowers the precision of every matmul operand (the control
only): ``None`` float32, or ``"fp8"`` (float8_e4m3fn with a per-tensor
scale, straight-through backward), the nearest precision below the
configuration's bfloat16.  It may also name the cell's planted fault,
``"no_rope_on_shared_key"``: the shared key ``r`` is kept as projected,
never rotated (what a cache written before the rotation holds), in
float32.
"""

import math
import time
import zlib
from functools import partial, wraps
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# What no architecture changes is the harness's own: the hashable
# wrapper, the key of a large seed, the tree of path tuples, the
# control's quantizer, V-trace.
from benchmark.lib.reference import (  # noqa: F401  (the harness asks
    _quantizer,                        #  this module for the two trees)
    _Static,
    from_tree,
    seed_key,
    to_tree,
    vtrace,
)

HIGHEST = lax.Precision.HIGHEST
# Each program here runs a few times and is compiled once, in set-up (a
# leaf of the weights) or after the window, in a run that has a time
# limit.  The compiler's search for a faster program is most of that
# compile when nothing is cached: for a v5e, ahead of time, the loss and
# gradient's program takes 94.8 s with it and 9.6 s without, the
# rollout's 34.3 and 3.9 s, in the same bytes (PR 38).  The arithmetic
# is what the program's text says either way.
QUICK_COMPILE = {"exec_time_optimization_effort": -1.0}
WORLD_KEY = 20483          # the world's base key, as the program has it
REWARD_CLASSES = 16
HISTORY_UNROLLS = 3        # unrolls a fused rollout's history has room for
NO_KEY = -(2 ** 30)        # the index of a history slot that holds nothing
NO_ROPE_ON_SHARED_KEY = "no_rope_on_shared_key"   # the planted fault

_CLOCK = [time.perf_counter()]


def _timed(fn):
    """A run of the cell has a time limit and this module is half of
    what follows the window: every call the harness makes says how long
    it took and how long the harness took since the last one returned
    (its own transfers and norms)."""
    @wraps(fn)
    def call(*args, **kwargs):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
        t1 = time.perf_counter()
        print(f"reference: {fn.__name__} {t1 - t0:.1f}s "
              f"(the caller {t0 - _CLOCK[0]:.1f}s before it)", flush=True)
        _CLOCK[0] = t1
        return out
    return call


# -- sizes and weights --------------------------------------------------------

def is_expert_layer(cfg, layer: int) -> bool:
    return layer >= cfg["first_k_dense_replace"]


def latent_dim(cfg) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def weight_shapes(cfg: Dict[str, Any]) -> Dict[Tuple[str, ...], Tuple]:
    """Path -> shape of every parameter, from the configuration file."""
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, turned = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, v_dim = cfg["kv_lora_rank"], cfg["v_head_dim"]
    shapes: Dict[Tuple[str, ...], Tuple] = {
        ("embed", "embedding"): (cfg["vocab_size"], hidden),
        ("final_norm", "scale"): (hidden,),
        ("policy_logits", "kernel"): (hidden, cfg["vocab_size"]),
        ("baseline", "kernel"): (hidden, 1),
        ("baseline", "bias"): (1,),
    }

    def mlp(path, width):
        shapes[path + ("gate_proj", "kernel")] = (hidden, width)
        shapes[path + ("up_proj", "kernel")] = (hidden, width)
        shapes[path + ("down_proj", "kernel")] = (width, hidden)

    for layer in range(cfg["num_hidden_layers"]):
        at = (f"layer_{layer}",)
        for norm in ("input_norm", "pre_mlp_norm"):
            shapes[at + (norm, "scale")] = (hidden,)
        attn = at + ("attention",)
        shapes[attn + ("q_proj", "kernel")] = (hidden,
                                               heads * (nope + turned))
        shapes[attn + ("kv_a_proj", "kernel")] = (hidden, rank + turned)
        shapes[attn + ("kv_a_norm", "scale")] = (rank,)
        shapes[attn + ("kv_b_proj", "kernel")] = (rank,
                                                  heads * (nope + v_dim))
        shapes[attn + ("o_proj", "kernel")] = (heads * v_dim, hidden)
        if is_expert_layer(cfg, layer):
            moe = at + ("moe",)
            held, width = cfg["experts_held"], cfg["moe_intermediate_size"]
            shapes[moe + ("router", "kernel")] = (hidden,
                                                  cfg["n_routed_experts"])
            shapes[moe + ("experts", "gate_proj")] = (held, hidden, width)
            shapes[moe + ("experts", "up_proj")] = (held, hidden, width)
            shapes[moe + ("experts", "down_proj")] = (held, width, hidden)
            mlp(moe + ("shared",), width * cfg["n_shared_experts"])
        else:
            mlp(at + ("mlp",), cfg["intermediate_size"])
    return shapes


@partial(jax.jit, static_argnums=(2, 3), compiler_options=QUICK_COMPILE)
def _seeded_leaf(key, salt, kind, shape):
    """One leaf, float32, on the device: a matrix normal with variance
    1/fan_in (the embedding's rows 1/hidden), a norm's weight 1, the
    one bias normal at 0.02.  One compiled program a kind and shape."""
    if kind == "scale":
        return jnp.ones(shape, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, salt), shape, jnp.float32)
    if kind == "bias":
        fan_in = 2500.0                     # 0.02
    elif kind == "embedding":
        fan_in = shape[-1]
    else:
        fan_in = shape[-2]
    return x * (1.0 / math.sqrt(fan_in))


def _seeded(key, path, shape):
    kind = path[-1] if path[-1] in ("scale", "bias", "embedding") else "w"
    return _seeded_leaf(
        key, np.int32(zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF),
        kind, tuple(shape))


def make_weight_on_device(cfg: Dict[str, Any], seed: int,
                          path: Tuple[str, ...]):
    """One leaf of ``make_weights``, left on the device
    (``benchmark/seeds_big.py`` re-seeds a program in place, a leaf at a
    time, where two sets of weights do not fit the chip)."""
    return _seeded(seed_key(seed), path, weight_shapes(cfg)[path])


# The last start made, on the host.  The harness asks for a seed's
# weights three times in a run (the program's own start, the program's
# numbers, the follow), and 2.3 GB cross to the host at well under a
# GB/s (my chip runs, PR 32: 10-15 s each at trinity_mini_ep8's 2.8 GB).
_START: Dict[str, Any] = {}


@_timed
def make_weights(cfg: Dict[str, Any], seed: int) -> Dict[Tuple[str, ...], Any]:
    """All weights, made on the device and handed back on the HOST: the
    harness keeps the start beside the three steps it follows (for the
    parameters' change), and 2.3 GB of float32 kept on the chip beside
    parameters, mean square, gradient and a block's gradient would not
    fit it.  The leaves are the caller's to read, not to write."""
    shapes = weight_shapes(cfg)
    made = (int(seed), tuple(sorted(shapes.items())))
    if _START.get("made") != made:
        _START.clear()
        key = seed_key(seed)
        _START.update(made=made, flat=jax.device_get({
            path: _seeded(key, path, shape)
            for path, shape in sorted(shapes.items())}))
    return dict(_START["flat"])


# The parameters a step was last given from the host, and their copy on
# the chip: a step reads them three times (rollout, loss, optimizer).
_ON_CHIP: list = []


def _on_chip(params):
    """``params`` on the device.  The harness hands the start over from
    the host; where it is the start this module made last, the programs
    that made it make it again (the same bits, and 2.3 GB that do not
    cross from the host).  ``rmsprop_step`` lets the copy go."""
    leaves = jax.tree_util.tree_leaves(params)
    if all(isinstance(leaf, jax.Array) for leaf in leaves):
        return params
    if _ON_CHIP and _ON_CHIP[0] is params:
        return _ON_CHIP[1]
    flat, held = from_tree(params), _START.get("flat", {})
    if len(flat) == len(held) and all(
            leaf is held.get(path) for path, leaf in flat.items()):
        key = seed_key(_START["made"][0])
        tree = to_tree({path: _seeded(key, path, leaf.shape)
                        for path, leaf in flat.items()})
    else:
        tree = jax.device_put(params)
    _ON_CHIP[:] = [params, tree]
    return tree


# -- the layers ---------------------------------------------------------------

def _mm(x, w, q):
    return jnp.dot(q(x), q(w), precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * scale


def rope(x, position, theta, interleave: bool):
    """``x`` [B, T, heads, D], ``position`` [B, T]: the rotation at
    ``theta ** (-2i / D)`` of the pairs (2i, 2i + 1) (``rope_interleave``)
    or (i, i + D / 2)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = position.astype(jnp.float32)[..., None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def gated_mlp(p, x, q):
    return _mm(jax.nn.silu(_mm(x, p["gate_proj"]["kernel"], q))
               * _mm(x, p["up_proj"]["kernel"], q),
               p["down_proj"]["kernel"], q)


def route(cfg, p, m):
    """[N, hidden] -> (weights [N, num_experts], 0 off the chosen
    ``num_experts_per_tok``; the chosen ids [N, k]).  Float32, never
    quantized: the router is a hundredth of a layer's work and decides
    which work is done."""
    scores = jax.nn.sigmoid(jnp.dot(m, p["router"]["kernel"],
                                    precision=HIGHEST))
    # the buffer e_score_correction_bias; one group, so no group limit
    bias = jnp.zeros((cfg["n_routed_experts"],), jnp.float32)
    _, chosen = lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    picked = picked * cfg["routed_scaling_factor"]
    rows = jnp.arange(m.shape[0])[:, None]
    weights = jnp.zeros_like(scores).at[rows, chosen].set(picked)
    return weights, chosen


def expert_layer(cfg, p, m, q, experts=None):
    """The shared experts (one gated MLP of their summed width) plus
    this chip's experts' part of the routed sum.  ``experts``: (first, held) to run another share than the
    configuration's (the test that the shares add up); the weights under
    ``p["experts"]`` are then that share's."""
    first, held = experts or (cfg["first_expert"], cfg["experts_held"])
    weights, _ = route(cfg, p, m)
    mine = lax.dynamic_slice_in_dim(weights, first, held, axis=1)
    stack = p["experts"]

    @jax.checkpoint
    def one(total, xs):
        gate, up, down, w = xs
        y = _mm(jax.nn.silu(_mm(m, gate, q)) * _mm(m, up, q), down, q)
        return total + w[:, None] * y, None

    routed, _ = lax.scan(one, jnp.zeros_like(m),
                         (stack["gate_proj"], stack["up_proj"],
                          stack["down_proj"], mine.T))
    return gated_mlp(p["shared"], m, q) + routed


class History(NamedTuple):
    """What an unroll attends back into: per layer the rows ``[c | r]``
    of the tokens acted on before it, as they were made, in the order
    they were made; their index in the env's stream (``NO_KEY`` past the
    last); where each env's episode began; the stream's length.  The
    room is fixed (``empty_history``'s ``capacity``), so that every
    unroll is one compiled program."""

    rows: Tuple[Any, ...]       # per layer f32 [B, capacity, rank + rope]
    index: Any                  # i32 [capacity]
    episode_start: Any          # i32 [B]
    written: Any                # i32 []


def attention(cfg, p, a, position, index, start, held, key_index, q,
              rotate_shared_key: bool = True):
    """``a`` [B, T, hidden] against itself and the rows ``held`` [B, S,
    rank + rope] of indices ``key_index`` [S] -> ([B, T, hidden], the
    unroll's rows)."""
    b, t, _ = a.shape
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, turned = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    v_dim = cfg["v_head_dim"]
    theta, pairs = float(cfg["rope_theta"]), bool(cfg["rope_interleave"])
    flat = a.reshape(b * t, -1)
    query = _mm(flat, p["q_proj"]["kernel"], q).reshape(
        b, t, heads, nope + turned)
    q_nope = query[..., :nope]
    q_rope = rope(query[..., nope:], position, theta, pairs)
    row = _mm(flat, p["kv_a_proj"]["kernel"], q).reshape(b, t, rank + turned)
    shared = row[..., None, rank:]
    if rotate_shared_key:
        shared = rope(shared, position, theta, pairs)
    made = jnp.concatenate([
        rms_norm(row[..., :rank], p["kv_a_norm"]["scale"],
                 cfg["rms_norm_eps"]), shared[..., 0, :]], axis=-1)
    rows = jnp.concatenate([held, made], axis=1)              # [B, S', .]
    key_index = jnp.concatenate([key_index, index])
    # every token's whole keys and values, from its row
    up = _mm(rows[..., :rank].reshape(-1, rank), p["kv_b_proj"]["kernel"],
             q).reshape(b, rows.shape[1], heads, nope + v_dim)
    k_nope, value = up[..., :nope], up[..., nope:]
    # an empty slot's index lies below every episode's start
    seen = ((key_index[None, None, :] <= index[None, :, None])
            & (key_index[None, None, :] >= start[:, :, None]))
    scores = (jnp.einsum("bthd,bshd->bhts", q(q_nope), q(k_nope),
                         precision=HIGHEST)
              + jnp.einsum("bthd,bsd->bhts", q(q_rope), q(rows[..., rank:]),
                           precision=HIGHEST)) / math.sqrt(nope + turned)
    scores = jnp.where(seen[:, None], scores, -jnp.inf)
    out = jnp.einsum("bhts,bshd->bthd", q(jax.nn.softmax(scores, -1)),
                     q(value), precision=HIGHEST)
    return (_mm(out.reshape(b * t, -1), p["o_proj"]["kernel"], q).reshape(
        b, t, -1), made)


def forward(cfg, params, tokens, done, history: History, quant=None):
    """``tokens``, ``done`` [T, B] -> (policy logits [T, B, vocab],
    baseline [T, B], the history with the unroll's tokens behind it).
    A token whose ``done`` is set starts its env's episode."""
    rotate = quant != NO_ROPE_ON_SHARED_KEY
    q = _quantizer(quant if rotate else None)
    eps = cfg["rms_norm_eps"]
    t, b = tokens.shape
    index = history.written + jnp.arange(t, dtype=jnp.int32)
    marks = jnp.where(done.T, index[None, :], -1)
    start = jnp.maximum(lax.cummax(marks, axis=1),
                        history.episode_start[:, None])        # [B, T]
    position = index[None, :] - start
    h = params["embed"]["embedding"][tokens.T]
    new_rows = []
    for layer in range(cfg["num_hidden_layers"]):
        p = params[f"layer_{layer}"]
        a = rms_norm(h, p["input_norm"]["scale"], eps)
        attn, made = attention(cfg, p["attention"], a, position, index,
                               start, history.rows[layer], history.index, q,
                               rotate)
        new_rows.append(_append(history.rows[layer], made, history.written))
        h = h + attn
        m = rms_norm(h, p["pre_mlp_norm"]["scale"], eps)
        flat = m.reshape(b * t, -1)
        if is_expert_layer(cfg, layer):
            f = expert_layer(cfg, p["moe"], flat, q)
        else:
            f = gated_mlp(p["mlp"], flat, q)
        h = h + f.reshape(b, t, -1)
    z = rms_norm(h, params["final_norm"]["scale"], eps)
    z = jnp.swapaxes(z, 0, 1).reshape(t * b, -1)
    logits = _mm(z, params["policy_logits"]["kernel"], q)
    baseline = (_mm(z, params["baseline"]["kernel"], q)
                + params["baseline"]["bias"])[:, 0]
    return (logits.reshape(t, b, -1), baseline.reshape(t, b), History(
        tuple(new_rows),
        _append(history.index[None], index[None], history.written)[0],
        start[:, -1], history.written + t))


def _append(held, new, written):
    """``held`` [B, capacity, ...] with ``new`` [B, T, ...] from slot
    ``written`` on; a history with no room (a single forward's) stays
    as it is."""
    if held.shape[1] == 0:
        return held
    return lax.dynamic_update_slice_in_dim(held, new, written, axis=1)


def empty_history(cfg, batch: int, capacity: int = 0) -> History:
    shape = (batch, capacity, latent_dim(cfg))
    return History(
        tuple(jnp.zeros(shape, jnp.float32)
              for _ in range(cfg["num_hidden_layers"])),
        jnp.full((capacity,), NO_KEY, jnp.int32),
        jnp.zeros((batch,), jnp.int32), jnp.zeros((), jnp.int32))


# -- V-trace and the loss -----------------------------------------------------

class Batch(NamedTuple):
    """A trajectory batch, time-major, T+1 entries (the overlap layout):
    entry i holds the env output seen at step i and the agent output
    that LED to it; ``log_prob`` is the behaviour policy's of the action
    taken (a vocabulary of logits an entry is not kept)."""

    action: Any        # i32 [T+1, B]
    log_prob: Any      # f32 [T+1, B]
    reward: Any        # f32 [T+1, B]
    done: Any          # bool [T+1, B]
    token: Any         # i32 [T+1, B]
    history: History   # at the unroll's start


def loss(cfg, params, batch: Batch, quant=None):
    """The IMPALA loss as a SUM over time and batch:
    pg + baseline_cost * baseline + entropy_cost * (-entropy)."""
    hp = cfg["loss"]
    logits, baseline, _ = forward(cfg, params, batch.token, batch.done,
                                  batch.history, quant)
    bootstrap = baseline[-1]
    logits, baseline = logits[:-1], baseline[:-1]
    actions = batch.action[1:]
    rewards = jnp.clip(batch.reward[1:], -1.0, 1.0)
    discounts = jnp.where(batch.done[1:], 0.0, hp["discounting"])
    logp = jax.nn.log_softmax(logits)
    taken = jnp.take_along_axis(logp, actions[..., None], axis=-1)[..., 0]
    log_rhos = lax.stop_gradient(taken - batch.log_prob[1:])
    vs, adv = vtrace(log_rhos, discounts, rewards,
                     lax.stop_gradient(baseline),
                     lax.stop_gradient(bootstrap))
    pg = jnp.sum(-taken * adv)
    base = 0.5 * jnp.sum(jnp.square(vs - baseline))
    ent = jnp.sum(jnp.sum(jnp.exp(logp) * logp, axis=-1))
    return pg + hp["baseline_cost"] * base + hp["entropy_cost"] * ent


_LOSS_GRAD_FNS: Dict[Any, Any] = {}


def _columns(batch: Batch, cols: slice) -> Batch:
    history = batch.history
    return Batch(
        *(x[:, cols] for x in batch[:5]),
        History(tuple(rows[cols] for rows in history.rows),
                history.index, history.episode_start[cols],
                history.written))


@_timed
def loss_and_grads(cfg, params, batch: Batch, block: int, quant=None):
    """Loss and gradients over the whole batch, in blocks of ``block``
    batch columns (columns are independent and the loss is a sum, so
    the blocks add): what keeps the float32 reference inside the chip's
    memory at the cell's own batch.  A block's program adds its
    gradient into the sum it is given (donated), leaf by leaf as it
    makes them: one gradient is held, not a sum and a block's beside it
    (1.2 GB less at the cell's sizes, AOT for a v5e, PR 32)."""
    key = (_Static(cfg), quant)
    if key not in _LOSS_GRAD_FNS:
        value_and_grad = jax.value_and_grad(partial(loss, cfg, quant=quant))

        def add_block(params, columns, total, grads):
            value, g = value_and_grad(params, columns)
            return total + value, jax.tree_util.tree_map(jnp.add, grads, g)

        _LOSS_GRAD_FNS[key] = jax.jit(add_block, donate_argnums=(2, 3),
                                      compiler_options=QUICK_COMPILE)
    fn = _LOSS_GRAD_FNS[key]
    params = _on_chip(params)              # the start comes from the host
    total = jnp.zeros((), jnp.float32)
    grads = jax.tree_util.tree_map(jnp.zeros_like, params)
    width = batch.action.shape[1]
    for begin in range(0, width, block):
        total, grads = fn(params, _columns(
            batch, slice(begin, min(width, begin + block))), total, grads)
    return total, grads


# -- the optimizer ------------------------------------------------------------

def rmsprop_init(params):
    """TF's RMSProp starts the mean square at ONE: a scalar a leaf
    until the first step gives it its leaf's shape (2.3 GB of ones need
    neither be made nor cross from the host)."""
    return jax.tree_util.tree_map(lambda p: np.float32(1.0), params)


# The gradient is the caller's no longer (the harness reads the first
# gradient before the step), nor is a mean square a step has made; the
# parameters may be the start the harness keeps.
@partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3))
def _rmsprop(hp_key, params, nu, grads, lr):
    decay, eps = hp_key
    nu = jax.tree_util.tree_map(
        lambda n, g: decay * n + (1.0 - decay) * g * g, nu, grads)
    params = jax.tree_util.tree_map(
        lambda p, n, g: p - lr * g * lax.rsqrt(n + eps), params, nu, grads)
    return params, nu


@_timed
def rmsprop_step(cfg, params, nu, grads, env_frames: float):
    """One step; the rate decays linearly to 0 over the total frames.
    The mean square stays on the chip between steps: through the host
    it is 2.3 GB each way, each step, of a run that has a time limit."""
    opt = cfg["optimizer"]
    lr = opt["learning_rate"] * max(
        0.0, 1.0 - env_frames / opt["total_environment_frames"])
    # the first step's scalars take their leaves' shapes on the chip
    nu = jax.tree_util.tree_map(
        lambda n, g: n if np.ndim(n) else jnp.full_like(g, n), nu, grads)
    params, nu = _rmsprop(
        (opt["rmsprop_decay"], opt["rmsprop_epsilon"]), _on_chip(params),
        nu, grads, jnp.float32(lr))
    _ON_CHIP.clear()
    return params, nu


@_timed
def first_gradient_norms(cfg, paths, nu1) -> Dict[tuple, float]:
    """Leaf norms of the first gradient out of RMSProp's mean square
    after step one, ``nu1 = decay + (1 - decay) * g**2`` in float32: the
    decay is taken as float32 holds it, so that an element no token
    reached (most of an embedding's, of a head's) reads exactly 0 and
    not the 1e-6 that 0.99 rounds by, 51 million times."""
    _keep_freed_memory()       # the harness's first call after the window
    decay = np.float32(cfg["optimizer"]["rmsprop_decay"])
    rest = np.float64(np.float32(1.0) - decay)
    out = {}
    for path, nu in zip(paths, nu1):
        # float32 less float32 (exact while nu <= 2 * decay), summed in
        # float64: no float64 copy of a leaf (576M elements in all)
        above = np.asarray(nu, np.float32) - decay
        np.maximum(above, 0.0, out=above)
        out[path] = float(np.sqrt(np.sum(above, dtype=np.float64) / rest))
    return out


# -- the world and the fused rollout ------------------------------------------

class World(NamedTuple):
    seed: Any          # i32 [B]
    episode: Any
    position: Any      # of the token the agent now sees


def world_token(world_cfg, seed, episode, position):
    """The token an env shows at ``position`` of ``episode``: a
    log-uniform (Zipf, exponent 1) draw over the vocabulary, keyed by
    the position modulo ``period``, so position p >= period repeats
    position p - period."""
    vocab = world_cfg["vocab_size"]

    def one(seed, episode, position):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
            jax.random.key(WORLD_KEY), seed), episode),
            position % world_cfg["period"])
        u = jax.random.uniform(key, (), jnp.float32)
        rank = jnp.floor(jnp.exp(u * math.log(vocab + 1.0))) - 1.0
        return jnp.clip(rank.astype(jnp.int32), 0, vocab - 1)

    return jax.vmap(one)(seed, episode, position)


def world_initial(world_cfg, seeds):
    """Envs staggered through their first episode by length / batch."""
    seeds = jnp.asarray(seeds, jnp.int32)
    batch = seeds.shape[0]
    length = world_cfg["episode_length"]
    position = (jnp.arange(batch, dtype=jnp.int32)
                * (length // batch)) % length
    episode = jnp.zeros_like(seeds)
    world = World(seeds, episode, position)
    return world, (jnp.zeros(seeds.shape, jnp.float32),
                   jnp.ones(seeds.shape, bool),
                   world_token(world_cfg, seeds, episode, position))


def world_step(world_cfg, world: World, action):
    """The next token; reward 1 where the action names its class."""
    position = world.position + 1
    done = position >= world_cfg["episode_length"]
    episode = world.episode + done.astype(jnp.int32)
    position = jnp.where(done, 0, position)
    token = world_token(world_cfg, world.seed, episode, position)
    reward = (action % REWARD_CLASSES
              == token % REWARD_CLASSES).astype(jnp.float32)
    return World(world.seed, episode, position), (reward, done, token)


class RolloutCarry(NamedTuple):
    world: World
    reward: Any
    done: Any
    token: Any
    action: Any
    log_prob: Any
    history: History


_MADE_ROOM = []
_KEPT = []


def _keep_freed_memory():
    """Once a process, at the reference's first call after the window,
    on the chip's machine: freed host memory stays with the process.
    The harness's norms make float64 copies of 576M elements leaf by
    leaf, five times over; glibc maps and unmaps each, and on a machine
    without transparent hugepages a fresh page costs what computing on
    it does (my chip runs, PR 32: a 51M-element leaf's norm 1.0 s,
    0.08 s on memory the heap kept; the difference 1.4 -> 0.46 s).
    Nothing that is timed is running by now."""
    if _KEPT or jax.default_backend() != "tpu":
        return
    _KEPT.append(True)
    try:
        import ctypes

        libc = ctypes.CDLL("libc.so.6")
        libc.mallopt(-4, 0)                # M_MMAP_MAX: the heap serves all
        libc.mallopt(-1, 2 ** 31 - 1)      # M_TRIM_THRESHOLD: and keeps it
    except (OSError, AttributeError):
        pass                               # another libc: slower, not wrong


def _make_room():
    """Once a process, before the reference's first array: what the
    timed program left on the chip goes (its state waits in reference
    cycles for the collector, its executables keep their scratch while
    the jit caches hold them), or 11 GB of float32 reference does not
    fit beside it."""
    _keep_freed_memory()
    if not _MADE_ROOM:
        import gc

        gc.collect()
        jax.clear_caches()
        if jax.default_backend() == "tpu":
            # The harness has read everything it reads of the program
            # (losses, optimizer leaves and parameters are on the host)
            # before it first asks the reference for anything, and the
            # start and the mean square of this module live on the host:
            # no array on the chip is anyone's at this point.
            for array in jax.live_arrays():
                array.delete()
        _MADE_ROOM.append(True)


@_timed
def rollout_initial(cfg, world_cfg, batch: int, program_seed: int):
    _make_room()
    world, (reward, done, token) = world_initial(
        world_cfg, np.arange(batch, dtype=np.int32) + program_seed)
    return RolloutCarry(
        world, reward, done, token, jnp.zeros((batch,), jnp.int32),
        jnp.zeros((batch,), jnp.float32), empty_history(cfg, batch))


@partial(jax.jit, static_argnums=(0, 1, 5, 6),
         compiler_options=QUICK_COMPILE)
def _rollout(cfg_key, world_key, params, carry, rng, unroll_length, quant):
    """The stream does not depend on the action, so the unroll's tokens
    are made first, one forward over them gives every step's logits
    (attention is causal: what acting step by step through a cache
    computes), and every step's action is drawn from its own logits
    under its own key."""
    cfg, world_cfg = cfg_key.value, world_key.value

    def advance(world, _):
        # the action-free part of ``world_step``: done and the token
        world, (_, done, token) = world_step(
            world_cfg, world, jnp.zeros_like(world.seed))
        return world, (done, token)

    world, (dones, tokens) = lax.scan(advance, carry.world, None,
                                      length=unroll_length)
    seen_token = jnp.concatenate([carry.token[None], tokens])     # T+1
    seen_done = jnp.concatenate([carry.done[None], dones])
    width = carry.token.shape[0]
    block = min(width, int(cfg["reference_block"]))
    if width % block:
        raise ValueError(f"reference_block {block} does not divide the "
                         f"batch of {width}")
    held = carry.history

    def columns(x, begin, axis=0):
        return lax.dynamic_slice_in_dim(x, begin, block, axis)

    def one_block(begin):
        # one compiled forward, whatever the number of blocks
        logits, _, grown = forward(
            cfg, params, columns(seen_token[:-1], begin, 1),
            columns(seen_done[:-1], begin, 1),
            History(tuple(columns(rows, begin) for rows in held.rows),
                    held.index, columns(held.episode_start, begin),
                    held.written), quant)

        def draw(t, row):
            # jax.random.categorical is argmax(gumbel(key, shape) +
            # logits) with one key for the whole batch: a block takes
            # its columns of the whole batch's noise
            key = jax.random.fold_in(jax.random.fold_in(rng, t), 0)
            noise = columns(jax.random.gumbel(
                key, (width, row.shape[-1]), jnp.float32), begin)
            return jnp.argmax(noise + row, axis=-1).astype(jnp.int32)

        action = jax.vmap(draw)(jnp.arange(unroll_length), logits)
        logp = jnp.take_along_axis(
            jax.nn.log_softmax(logits), action[..., None], -1)[..., 0]
        return action, logp, grown

    action, log_prob, grown = lax.map(
        one_block, jnp.arange(0, width, block, dtype=jnp.int32))

    def whole(x):                      # [blocks, block, ...] -> [B, ...]
        return x.reshape((width,) + x.shape[2:])

    action = jnp.moveaxis(action, 0, 1).reshape(unroll_length, width)
    log_prob = jnp.moveaxis(log_prob, 0, 1).reshape(unroll_length, width)
    history = History(
        tuple(whole(rows) for rows in grown.rows),
        grown.index[0], whole(grown.episode_start), grown.written[0])
    reward = (action % REWARD_CLASSES
              == tokens % REWARD_CLASSES).astype(jnp.float32)
    batch = Batch(
        jnp.concatenate([carry.action[None], action]),
        jnp.concatenate([carry.log_prob[None], log_prob]),
        jnp.concatenate([carry.reward[None], reward]),
        seen_done, seen_token, carry.history)
    new = RolloutCarry(world, reward[-1], dones[-1], tokens[-1],
                       action[-1], log_prob[-1], history)
    return batch, new


@_timed
def rollout(cfg, world_cfg, params, carry: RolloutCarry, program_seed: int,
            update_index: int, unroll_length: int, quant=None):
    """One fused-loop unroll under ``params``, keyed as the fused loop
    keys it: ``fold_in(fold_in(fold_in(key(seed), update), t), 0)``."""
    if update_index >= HISTORY_UNROLLS:
        raise ValueError(
            f"the reference's history holds {HISTORY_UNROLLS} unrolls")
    if carry.history.index.shape[0] == 0:      # the first unroll: its room
        carry = carry._replace(history=empty_history(
            cfg, carry.token.shape[0], HISTORY_UNROLLS * unroll_length))
    rng = jax.random.fold_in(jax.random.key(program_seed), update_index)
    return _rollout(_Static(cfg), _Static(world_cfg), _on_chip(params),
                    carry, rng, unroll_length, quant)


# -- model work, from shapes --------------------------------------------------

def forward_flops_per_token(cfg, context: float) -> float:
    """Multiply-add FLOPs (2 per MAC) of one token through the held
    share, attention AS THE MODEL STATES IT: the projections (Wq, Wkva,
    one Wkvb product a token, Wo), scores ``nope + rope`` deep and
    values ``v_head_dim`` deep a head over ``context`` keys (a pass that
    absorbs Wkvb and scores ``kv_lora_rank + rope`` deep does more
    arithmetic and gets no credit for it), the dense MLP or the shared
    experts plus the routed experts' share that falls on this chip when
    routing is even (k * held / experts), the router, the head and the
    value head."""
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, turned = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, v_dim = cfg["kv_lora_rank"], cfg["v_head_dim"]
    total = 0.0
    for layer in range(cfg["num_hidden_layers"]):
        total += 2.0 * hidden * (heads * (nope + turned) + rank + turned)
        total += 2.0 * rank * heads * (nope + v_dim)          # Wkvb, once
        total += 2.0 * heads * v_dim * hidden                 # Wo
        total += 2.0 * heads * (nope + turned + v_dim) * context
        if is_expert_layer(cfg, layer):
            width = cfg["moe_intermediate_size"]
            here = (cfg["num_experts_per_tok"] * cfg["experts_held"]
                    / cfg["n_routed_experts"])
            total += 2.0 * hidden * cfg["n_routed_experts"]
            total += 2.0 * 3 * hidden * width * (
                cfg["n_shared_experts"] + here)
        else:
            total += 2.0 * 3 * hidden * cfg["intermediate_size"]
    return total + 2.0 * hidden * (cfg["vocab_size"] + 1)


def train_flops_per_env_frame(cfg) -> float:
    """Acting forward + learning forward + backward (2 x forward) per
    token, attention at the mean context of an episode (half its
    length).  Rematerialized forwards are not counted."""
    return 4.0 * forward_flops_per_token(cfg, float(cfg["mean_context"]))
