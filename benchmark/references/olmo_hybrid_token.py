"""The plain reference of a token policy of the ``olmo_hybrid`` family:
one chip's share of a dense hybrid decoder whose layers come in periods
of four, three Gated-DeltaNet layers (``linear_attention``: a delta-rule
matrix state a head behind a short convolution) to one full-attention
layer (as many key/value heads as query heads, the query and the key
normed over the whole projection, no rotation), every layer followed by
a gated silu MLP, each branch's RESULT normed and its input not; a
vocabulary-sized action head and the system's value head, trained by
V-trace and TF-style RMSProp in the ``token_recall`` world.

Straightforward ``jax.numpy`` in float32 at ``precision=HIGHEST``, no
kernel, no cache ring, no chunk and no triangular solve: the delta rule
is a ``lax.scan`` over tokens with the ``[B, heads, value_dim,
key_dim]`` state (under ``jax.checkpoint`` a layer, so that one layer's
states a token are alive at a time in the backward: 0.57 GB an env a
layer at the published widths), and attention is one masked softmax
over a list of keys and values in the order they were made, in a buffer
with room for three unrolls (made under whatever parameters were current
when their tokens were acted on, as a cache holds them).  It imports
nothing of the program and takes nothing the program made: sizes come
from the configuration file, weights from the seed, the world from the
traffic file's ``world`` block and the program's seed; the pieces no
architecture changes come from the harness's ``benchmark/lib/reference.py``.

The layers, for token ids ``x`` (what the source's ``config.json`` has
no key for is in the configuration file's ``assumed``)::

    h = E[x]                                    no scale, no position encoding anywhere
    every layer:  h = h + RMSNorm(Mixer(h));  h = h + RMSNorm(MLP(h))     eps rms_norm_eps
    MLP(m) = (silu(m W_gate) * (m W_up)) W_down

    linear_attention, H heads, keys of K and values of V numbers:
        [q | k | v | z | a | b] = h W_in        H K | H K | H V | H V | H | H
        [q | k | v] = silu(causal depthwise conv_taps([q | k | v]))     no bias; taps before the episode dropped
        q = q / |q| / sqrt(K);  k = k / |k|     a head; |x| = sqrt(sum x^2 + 1e-6)
        b_t = 2 sigmoid(b)  (sigmoid(b) where linear_allow_neg_eigval is false)
        a_t = exp(-exp(A_log) softplus(a + dt_bias))
        S_t = a_t keep_t S_(t-1) + b_t (v_t - a_t keep_t S_(t-1) k_t) k_t^T     [H, V, K]
        o_t = S_t q_t                           keep_t = 0 at an episode's first token
        out = (RMSNorm_V(o_t) w * silu(z_t)) W_out      the norm over a head's V outputs, one weight of V
    full_attention:
        q = RMSNorm(h Wq);  k = RMSNorm(h Wk);  v = h Wv        the norms over the whole projection
        out = softmax(q k / sqrt(D)) v Wo       a head; causal, keys of the own episode only

and after the last layer ``z = RMSNorm_f(h)``, ``policy_logits = z
W_head``, ``baseline = z w_b + c``.

``quant`` lowers the precision of every matmul operand (the control
only): ``None`` float32, or ``"fp8"`` (float8_e4m3fn with a per-tensor
scale, straight-through backward), the nearest precision below the
configuration's bfloat16.  It may also name this architecture's planted
fault, ``"no_delta"``: the UPDATE's scans (the loss's forward, not the
rollout's) drop the correction, ``S_t = a_t keep_t S_(t-1) + b_t v_t
k_t^T``: the state is never read against the key before it is written,
which is what a linear-attention scan with a scalar decay computes; in
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
NO_DELTA = "no_delta"      # the planted fault

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

LINEAR, ATTENTION = "linear_attention", "full_attention"


def kinds(cfg):
    return list(cfg["layer_types"])


def head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def linear_widths(cfg):
    """(a delta-rule layer's keys' numbers a token, its values')."""
    return (cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"],
            cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])


def conv_width(cfg) -> int:
    keys, values = linear_widths(cfg)
    return 2 * keys + values


def layers_of(cfg, kind):
    return [layer for layer, k in enumerate(kinds(cfg)) if k == kind]


def weight_shapes(cfg: Dict[str, Any]) -> Dict[Tuple[str, ...], Tuple]:
    """Path -> shape of every parameter, from the configuration file."""
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    kv, dim = cfg["num_key_value_heads"], head_dim(cfg)
    keys, values = linear_widths(cfg)
    scans = cfg["linear_num_key_heads"]
    shapes: Dict[Tuple[str, ...], Tuple] = {
        ("embed", "embedding"): (cfg["vocab_size"], hidden),
        ("final_norm", "scale"): (hidden,),
        ("policy_logits", "kernel"): (hidden, cfg["vocab_size"]),
        ("baseline", "kernel"): (hidden, 1),
        ("baseline", "bias"): (1,),
    }
    for layer, kind in enumerate(kinds(cfg)):
        at = (f"layer_{layer}",)
        for norm in ("post_attn_norm", "post_mlp_norm"):
            shapes[at + (norm, "scale")] = (hidden,)
        for name in ("gate_proj", "up_proj"):
            shapes[at + ("mlp", name, "kernel")] = (
                hidden, cfg["intermediate_size"])
        shapes[at + ("mlp", "down_proj", "kernel")] = (
            cfg["intermediate_size"], hidden)
        if kind == LINEAR:
            gdn = at + ("gdn",)
            shapes[gdn + ("in_proj", "kernel")] = (
                hidden, 2 * keys + 2 * values + 2 * scans)
            shapes[gdn + ("conv_kernel",)] = (cfg["linear_conv_kernel_dim"],
                                              conv_width(cfg))
            for name in ("dt_bias", "A_log"):
                shapes[gdn + (name,)] = (scans,)
            shapes[gdn + ("norm_scale",)] = (cfg["linear_value_head_dim"],)
            shapes[gdn + ("out_proj", "kernel")] = (values, hidden)
        elif kind == ATTENTION:
            attn = at + ("attention",)
            shapes[attn + ("q_proj", "kernel")] = (hidden, heads * dim)
            shapes[attn + ("q_norm", "scale")] = (heads * dim,)
            shapes[attn + ("k_proj", "kernel")] = (hidden, kv * dim)
            shapes[attn + ("k_norm", "scale")] = (kv * dim,)
            shapes[attn + ("v_proj", "kernel")] = (hidden, kv * dim)
            shapes[attn + ("o_proj", "kernel")] = (heads * dim, hidden)
        else:
            raise ValueError(f"layer_types names {kind!r}, which is not "
                             f"built")
    return shapes


@partial(jax.jit, static_argnums=(2, 3), compiler_options=QUICK_COMPILE)
def _seeded_leaf(key, salt, kind, shape):
    """One leaf, float32, on the device: a matrix normal with variance
    1/fan_in (the embedding's rows 1/hidden, the convolution's taps
    1/linear_conv_kernel_dim), a norm's weight 1, the one bias normal at
    0.02, and as the family starts them: ``A_log`` the log of a uniform
    in (0, 16] a head, ``dt_bias`` the inverse softplus of a step
    log-uniform in [1e-3, 1e-1].  One compiled program a kind and
    shape."""
    if kind == "scale":
        return jnp.ones(shape, jnp.float32)
    key = jax.random.fold_in(key, salt)
    if kind == "A_log":
        return jnp.log(16.0 - jax.random.uniform(key, shape, jnp.float32,
                                                 0.0, 16.0))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "bias":
        fan_in = 2500.0                     # 0.02
    elif kind == "embedding":
        fan_in = shape[-1]
    else:
        fan_in = shape[-2]
    return x * (1.0 / math.sqrt(fan_in))


def _kind_of(path) -> str:
    last = path[-1]
    if last in ("embedding", "A_log", "dt_bias"):
        return last
    if last.endswith("scale"):
        return "scale"
    return "bias" if last.endswith("bias") else "w"

def _seeded(key, path, shape):
    return _seeded_leaf(
        key, np.int32(zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF),
        _kind_of(path), tuple(shape))


def make_weight_on_device(cfg: Dict[str, Any], seed: int,
                          path: Tuple[str, ...]):
    """One leaf of ``make_weights``, left on the device
    (``benchmark/seeds_big.py`` re-seeds a program in place, a leaf at a
    time, where two sets of weights do not fit the chip)."""
    return _seeded(seed_key(seed), path, weight_shapes(cfg)[path])


# The last start made, on the host.  The harness asks for a seed's
# weights three times in a run (the program's own start, the program's
# numbers, the follow), and 2.7 GB cross to the host at well under a
# GB/s (my chip runs, PR 32: 10-15 s each at trinity_mini_ep8's 2.8 GB).
_START: Dict[str, Any] = {}


@_timed
def make_weights(cfg: Dict[str, Any], seed: int) -> Dict[Tuple[str, ...], Any]:
    """All weights, made on the device and handed back on the HOST: the
    harness keeps the start beside the three steps it follows (for the
    parameters' change), and 2.7 GB of float32 kept on the chip beside
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
    that made it make it again (the same bits, and 2.7 GB that do not
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


def mlp(p, x, q):
    return _mm(jax.nn.silu(_mm(x, p["gate_proj"]["kernel"], q))
               * _mm(x, p["up_proj"]["kernel"], q),
               p["down_proj"]["kernel"], q)


class History(NamedTuple):
    """What an unroll continues from: per attention layer the keys and
    values of the tokens acted on before it, as they were made, in the
    order they were made; their index in the env's stream (``NO_KEY``
    past the last); where each env's episode began; the stream's length;
    per delta-rule layer its state after the last token and that
    token's last ``linear_conv_kernel_dim - 1`` convolution inputs.  The
    room is fixed (``empty_history``'s ``capacity``), so that every
    unroll is one compiled program."""

    keys: Tuple[Any, ...]       # per attention layer f32 [B, capacity, kv, D]
    values: Tuple[Any, ...]
    index: Any                  # i32 [capacity]
    episode_start: Any          # i32 [B]
    written: Any                # i32 []
    state: Tuple[Any, ...]      # per delta-rule layer f32 [B, H, V, K]
    tail: Tuple[Any, ...]       # per delta-rule layer f32 [B, taps - 1, 2 H K + H V]


def delta_rule(q_t, k_t, v_t, write, decay, first, state, correct=True):
    """The recurrence, a token at a time: q_t, k_t [B, T, H, K]; v_t [B,
    T, H, V]; write, decay [B, T, H]; first bool [B, T]; state [B, H, V,
    K] -> (o [B, T, H, V], the state after the last token).  ``correct``
    false is the planted fault: the state is not read against the key."""
    # under ``jax.checkpoint``: the backward keeps the state a token (the
    # carry) and makes the step's other residuals again, not three states
    # a token (3.4 GB a layer at two envs, AOT for a v5e, PR 46)
    @jax.checkpoint
    def step(s, inputs):
        q, k, v, b, a, first = inputs          # [B, H, .], [B, H], [B]
        s = jnp.where(first[:, None, None, None], 0.0, s)
        s = a[..., None, None] * s
        if correct:
            v = v - jnp.sum(s * k[:, :, None, :], axis=-1)
        s = s + (b[..., None] * v)[..., None] * k[:, :, None, :]
        return s, jnp.sum(s * q[:, :, None, :], axis=-1)

    state, o = lax.scan(step, state, tuple(
        jnp.swapaxes(x, 0, 1) for x in (q_t, k_t, v_t, write, decay, first)))
    return jnp.swapaxes(o, 0, 1), state


def gated_delta_net(cfg, p, h, position, state, tail, q, correct=True):
    """``h`` [B, T, hidden] -> ([B, T, hidden], the state and the tail
    after the last token)."""
    b, t, _ = h.shape
    heads = cfg["linear_num_key_heads"]
    key_dim, value_dim = (cfg["linear_key_head_dim"],
                          cfg["linear_value_head_dim"])
    keys, values = linear_widths(cfg)
    mixed, taps = conv_width(cfg), cfg["linear_conv_kernel_dim"]
    proj = _mm(h.reshape(b * t, -1), p["in_proj"]["kernel"], q).reshape(
        b, t, -1)
    z = proj[..., mixed:mixed + values]
    decay, write = (proj[..., mixed + values:mixed + values + heads],
                    proj[..., mixed + values + heads:])
    seen = jnp.concatenate([tail, proj[..., :mixed]], axis=1)
    conv = 0.0
    for back in range(taps):      # tap ``back`` reaches that many tokens back
        at = taps - 1 - back
        conv = conv + (p["conv_kernel"][at] * seen[:, at:at + t]
                       * (position >= back)[..., None])
    conv = jax.nn.silu(conv)

    def unit(x):
        x = x.reshape(b, t, heads, key_dim)
        return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)

    q_t = unit(conv[..., :keys]) / math.sqrt(key_dim)
    k_t = unit(conv[..., keys:2 * keys])
    v_t = conv[..., 2 * keys:].reshape(b, t, heads, value_dim)
    write = jax.nn.sigmoid(write) * (
        2.0 if cfg["linear_allow_neg_eigval"] else 1.0)
    decay = jnp.exp(-jnp.exp(p["A_log"])
                    * jax.nn.softplus(decay + p["dt_bias"]))
    o, state = delta_rule(q_t, k_t, v_t, write, decay, position == 0, state,
                          correct)
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                      + cfg["rms_norm_eps"]) * p["norm_scale"]
    gated = o.reshape(b * t, values) * jax.nn.silu(z).reshape(b * t, values)
    out = _mm(gated, p["out_proj"]["kernel"], q)
    return out.reshape(b, t, -1), state, seen[:, t:]


def attention(cfg, p, a, index, start, keys, values, key_index, q):
    """``a`` [B, T, hidden] against itself and the history's ``keys`` /
    ``values`` [B, S, kv, D] of indices ``key_index`` [S] -> ([B, T,
    hidden], the unroll's keys, its values)."""
    b, t, _ = a.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, eps = head_dim(cfg), cfg["rms_norm_eps"]
    flat = a.reshape(b * t, -1)
    query = rms_norm(_mm(flat, p["q_proj"]["kernel"], q),
                     p["q_norm"]["scale"], eps).reshape(
                         b, t, kv, heads // kv, dim)
    key = rms_norm(_mm(flat, p["k_proj"]["kernel"], q),
                   p["k_norm"]["scale"], eps).reshape(b, t, kv, dim)
    value = _mm(flat, p["v_proj"]["kernel"], q).reshape(b, t, kv, dim)
    all_keys = jnp.concatenate([keys, key], axis=1)           # [B, S', ..]
    all_values = jnp.concatenate([values, value], axis=1)
    key_index = jnp.concatenate([key_index, index])
    # an empty slot's index lies below every episode's start
    seen = ((key_index[None, None, :] <= index[None, :, None])
            & (key_index[None, None, :] >= start[:, :, None]))
    scores = jnp.einsum("btkgd,bskd->bkgts", q(query), q(all_keys),
                        precision=HIGHEST) / math.sqrt(dim)
    scores = jnp.where(seen[:, None, None], scores, -jnp.inf)
    out = jnp.einsum("bkgts,bskd->btkgd", q(jax.nn.softmax(scores, -1)),
                     q(all_values), precision=HIGHEST)
    return (_mm(out.reshape(b * t, -1), p["o_proj"]["kernel"], q).reshape(
        b, t, -1), key, value)


def forward(cfg, params, tokens, done, history: History, quant=None,
            correct: bool = True):
    """``tokens``, ``done`` [T, B] -> (policy logits [T, B, vocab],
    baseline [T, B], the history with the unroll's tokens behind it).
    A token whose ``done`` is set starts its env's episode.  ``correct``
    false: the planted fault."""
    q = _quantizer(None if quant == NO_DELTA else quant)
    eps = cfg["rms_norm_eps"]
    t, b = tokens.shape
    index = history.written + jnp.arange(t, dtype=jnp.int32)
    marks = jnp.where(done.T, index[None, :], -1)
    start = jnp.maximum(lax.cummax(marks, axis=1),
                        history.episode_start[:, None])        # [B, T]
    position = index[None, :] - start
    h = params["embed"]["embedding"][tokens.T]
    new_keys, new_values = list(history.keys), list(history.values)
    states, tails = list(history.state), list(history.tail)
    rings, scans = layers_of(cfg, ATTENTION), layers_of(cfg, LINEAR)
    for layer, kind in enumerate(kinds(cfg)):
        p = params[f"layer_{layer}"]
        if kind == LINEAR:
            at = scans.index(layer)
            mixed, states[at], tails[at] = jax.checkpoint(
                partial(gated_delta_net, cfg, q=q, correct=correct))(
                    p["gdn"], h, position, history.state[at],
                    history.tail[at])
        else:
            at = rings.index(layer)
            mixed, key, value = attention(
                cfg, p["attention"], h, index, start, history.keys[at],
                history.values[at], history.index, q)
            new_keys[at] = _append(history.keys[at], key, history.written)
            new_values[at] = _append(history.values[at], value,
                                     history.written)
        h = h + rms_norm(mixed, p["post_attn_norm"]["scale"], eps)
        f = mlp(p["mlp"], h.reshape(b * t, -1), q).reshape(b, t, -1)
        h = h + rms_norm(f, p["post_mlp_norm"]["scale"], eps)
    z = rms_norm(h, params["final_norm"]["scale"], eps)
    z = jnp.swapaxes(z, 0, 1).reshape(t * b, -1)
    logits = _mm(z, params["policy_logits"]["kernel"], q)
    baseline = (_mm(z, params["baseline"]["kernel"], q)
                + params["baseline"]["bias"])[:, 0]
    return (logits.reshape(t, b, -1), baseline.reshape(t, b), History(
        tuple(new_keys), tuple(new_values),
        _append(history.index[None], index[None], history.written)[0],
        start[:, -1], history.written + t, tuple(states), tuple(tails)))


def _append(held, new, written):
    """``held`` [B, capacity, ...] with ``new`` [B, T, ...] from slot
    ``written`` on; a history with no room (a single forward's) stays
    as it is."""
    if held.shape[1] == 0:
        return held
    return lax.dynamic_update_slice_in_dim(held, new, written, axis=1)


def empty_history(cfg, batch: int, capacity: int = 0) -> History:
    shape = (batch, capacity, cfg["num_key_value_heads"], head_dim(cfg))
    rings = len(layers_of(cfg, ATTENTION))
    scans = len(layers_of(cfg, LINEAR))
    return History(
        tuple(jnp.zeros(shape, jnp.float32) for _ in range(rings)),
        tuple(jnp.zeros(shape, jnp.float32) for _ in range(rings)),
        jnp.full((capacity,), NO_KEY, jnp.int32),
        jnp.zeros((batch,), jnp.int32), jnp.zeros((), jnp.int32),
        tuple(jnp.zeros((batch, cfg["linear_num_value_heads"],
                         cfg["linear_value_head_dim"],
                         cfg["linear_key_head_dim"]), jnp.float32)
              for _ in range(scans)),
        tuple(jnp.zeros((batch, cfg["linear_conv_kernel_dim"] - 1,
                         conv_width(cfg)), jnp.float32)
              for _ in range(scans)))


def _history_columns(history: History, take) -> History:
    """``take`` of every field that has a batch axis (its first)."""
    return History(
        tuple(take(k) for k in history.keys),
        tuple(take(v) for v in history.values), history.index,
        take(history.episode_start), history.written,
        tuple(take(s) for s in history.state),
        tuple(take(x) for x in history.tail))


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
    # the planted fault is the UPDATE's: this forward's scans, not the
    # rollout's
    logits, baseline, _ = forward(
        cfg, params, batch.token, batch.done, batch.history, quant,
        correct=quant != NO_DELTA)
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
    return Batch(*(x[:, cols] for x in batch[:5]),
                 _history_columns(batch.history, lambda x: x[cols]))



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
    until the first step gives it its leaf's shape (2.7 GB of ones need
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
    it is 2.7 GB each way, each step, of a run that has a time limit."""
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
        # float64: no float64 copy of a leaf (667M elements in all)
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
    The harness's norms make float64 copies of 667M elements leaf by
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
            _history_columns(held, lambda x: columns(x, begin)), quant)

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
    history = _history_columns(grown, whole)._replace(
        index=grown.index[0], written=grown.written[0])
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
    share, the layers as run: every layer's gated MLP (three matrices);
    a delta-rule layer's two projections, its convolution and the
    recurrence's own three products a (head, value, key) (the state read
    against the key, the rank-one write, the read-out against the query;
    the decay is elementwise and not counted, nor is any chunk's solve,
    which is an implementation's); the attention layer's projections and
    its scores and values over ``context`` keys; the head and the value
    head."""
    hidden = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = head_dim(cfg)
    keys, values = linear_widths(cfg)
    scans = cfg["linear_num_key_heads"]
    total = 0.0
    for kind in kinds(cfg):
        total += 2.0 * 3 * hidden * cfg["intermediate_size"]
        if kind == LINEAR:
            total += 2.0 * hidden * (2 * keys + 2 * values + 2 * scans)
            total += 2.0 * cfg["linear_conv_kernel_dim"] * conv_width(cfg)
            total += 2.0 * 3.0 * values * cfg["linear_key_head_dim"]
            total += 2.0 * values * hidden
        else:
            total += 2.0 * hidden * dim * (2 * heads + 2 * kv)
            total += 2.0 * 2.0 * heads * dim * context        # qk and pv
    return total + 2.0 * hidden * (cfg["vocab_size"] + 1)


def train_flops_per_env_frame(cfg) -> float:
    """Acting forward + learning forward + backward (2 x forward) per
    token, attention at the mean context of an episode (half its
    length).  Rematerialized forwards are not counted."""
    return 4.0 * forward_flops_per_token(cfg, float(cfg["mean_context"]))
