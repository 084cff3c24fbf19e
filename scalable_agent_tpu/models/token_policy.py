"""A token policy: a decoder of the ``afmoe`` family (window and full
attention mixed, a mixture of experts with a shared expert) behind the
agent's calling contract.

``__call__(actions, env_outputs, state) -> ((policy_logits, baseline),
state)`` over time-major ``[T, B]`` inputs, as ``ImpalaAgent`` has it:
T = 1 is acting and T = unroll is learning, from one definition.  The
observation is a token id (``observation.frame``, int32), the action a
token of the same vocabulary, and the agent's state is its attention
cache (``TokenCache``): per layer a ring of keys and values, each
slot's index in the env's token stream beside it, and where each env's
episode began.  An episode's end clears nothing: a query sees a key of
its own episode only (ops/attention.py), so ``done`` moves
``episode_start`` and the stale slots fall out of every mask.

The layer, for token ids ``x`` (sizes under the source's key names,
``TokenModelConfig``; benchmark/references/afmoe_token.py is the plain
float32 statement of the same equations)::

    h = E[x] * sqrt(hidden)                                  (mup_enabled)
    a = RMSNorm_in(h)
    q = RMSNorm_q(a Wq) [heads, head_dim];  k = RMSNorm_k(a Wk);  v = a Wv
    sliding layer: q, k = RoPE(q, k; theta, position in episode)
    attn = (softmax(q k / sqrt(head_dim)) v * sigmoid(a Wg)) Wo
    h = h + RMSNorm_post_attn(attn);  m = RMSNorm_pre_mlp(h)
    dense layer:  f = (silu(m W1) * (m W3)) W2
    expert layer: f = shared(m) + the held experts' part (ops/moe.py)
    h = h + RMSNorm_post_mlp(f)

One chip holds ``experts_held`` of ``num_experts`` experts and routes
over all of them.

The rings are sized so that ONE buffer serves the rollout and the
update: ``window + unroll`` slots (``episode_length + unroll`` on a full
layer) still hold, when an unroll ends, everything its first query may
see, so the update attends into the cache as the rollout left it and
masks the unroll's own slots by their index (``unroll_state``); no copy
of the cache is kept from the unroll's start.
"""

import dataclasses
import json
import math
import os
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from scalable_agent_tpu.ops import attention as attention_lib
from scalable_agent_tpu.ops import distributions, moe
from scalable_agent_tpu.types import StepOutput

SLIDING = "sliding_attention"
FULL = "full_attention"


@dataclasses.dataclass(frozen=True)
class TokenModelConfig:
    """The sizes of the model as it is run, under the source's own key
    names, from one JSON file (``from_file``); keys it does not name are
    the file's own business (loss, optimizer, flags)."""

    vocab_size: int
    hidden_size: int
    head_dim: int
    num_attention_heads: int
    num_key_value_heads: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_shared_experts: int
    num_hidden_layers: int
    num_dense_layers: int
    layer_types: Tuple[str, ...]
    sliding_window: int
    route_scale: float
    route_norm: bool
    rope_theta: float
    rms_norm_eps: float
    mup_enabled: bool
    experts_held: int
    first_expert: int = 0

    @classmethod
    def from_dict(cls, raw: Dict[str, Any]) -> "TokenModelConfig":
        for key, want in (("model_type", "afmoe"), ("hidden_act", "silu"),
                          ("score_func", "sigmoid"), ("rope_scaling", None)):
            if raw.get(key, want) != want:
                raise ValueError(
                    f"token policy: {key}={raw[key]!r} is not built "
                    f"(only {want!r})")
        names = [f.name for f in dataclasses.fields(cls)]
        missing = [n for n in names if n not in raw and n != "first_expert"]
        if missing:
            raise ValueError(
                f"token policy: the model configuration lacks {missing}")
        values = {n: raw[n] for n in names if n in raw}
        values["layer_types"] = tuple(values["layer_types"])
        model = cls(**values)
        if len(model.layer_types) != model.num_hidden_layers or any(
                kind not in (SLIDING, FULL) for kind in model.layer_types):
            raise ValueError(
                "token policy: layer_types must name sliding_attention or "
                "full_attention for each of num_hidden_layers")
        if not (0 <= model.first_expert and model.first_expert
                + model.experts_held <= model.num_experts):
            raise ValueError(
                f"token policy: experts [{model.first_expert}, "
                f"{model.first_expert + model.experts_held}) are not "
                f"among {model.num_experts}")
        return model

    @classmethod
    def from_file(cls, path: str) -> "TokenModelConfig":
        if not os.path.isabs(path) and not os.path.exists(path):
            # a path as the repository's files give it
            root = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
            path = os.path.join(root, path)
        with open(path) as f:
            return cls.from_dict(json.load(f))


class TokenCache(NamedTuple):
    """The token policy's state: what the next query attends back into."""

    keys: Tuple[Any, ...]       # per layer [B, slots, kv_heads, head_dim]
    values: Tuple[Any, ...]
    window_index: Any           # i32 [window slots]: stream index by slot
    full_index: Any             # i32 [full slots]
    written: Any                # i32 []: tokens in every env's stream
    episode_start: Any          # i32 [B]: index at which the episode began


class _Linear(nn.Module):
    """Operands rounded to ``dtype``, the product accumulated and handed
    back in float32.  Every rounding to the compute dtype in this model
    is such an explicit rounding of a matmul operand (or of a key or
    value on its way into the cache; ``ops/attention.py round_to``, which
    the compiler may not drop); everything between matmuls is float32.
    Acting (T = 1) and learning (T = unroll) are two compiled programs
    that fuse differently, and a value that may be kept in bfloat16
    between ops is rounded in one and not in the other: the on-policy
    importance ratios then scatter round 1 by 1e-2 (my chip run, PR
    32).  With every rounding explicit the two still differ in the last
    bits of a float32 sum, and a rounding to bfloat16 turns a difference
    of 1e-6 into one of 3e-3 on the elements it flips, so a few layers
    on the two are as far apart as independent roundings leave them
    (4.5e-3 of a log-probability on the chip): the learner is told the
    fused loop is on policy and does not read the ratio
    (``ops/vtrace.py from_behaviour_log_probs``)."""

    features: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features))
        return jnp.dot(attention_lib.round_to(x, self.dtype),
                       kernel.astype(self.dtype),
                       preferred_element_type=jnp.float32)


class _RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones_init(),
                           (x.shape[-1],))
        x = x.astype(jnp.float32)
        y = x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps)
        return y * scale


class _GatedMLP(nn.Module):
    width: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        hidden = x.shape[-1]
        gate = _Linear(self.width, self.dtype, name="gate_proj")(x)
        up = _Linear(self.width, self.dtype, name="up_proj")(x)
        return _Linear(hidden, self.dtype, name="down_proj")(
            jax.nn.silu(gate) * up)


class _Experts(nn.Module):
    """The held experts' stacked weights."""

    held: int
    width: int

    @nn.compact
    def __call__(self, hidden: int):
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        return (self.param("gate_proj", init,
                           (self.held, hidden, self.width)),
                self.param("up_proj", init,
                           (self.held, hidden, self.width)),
                self.param("down_proj", init,
                           (self.held, self.width, hidden)))


class _MoE(nn.Module):
    model: TokenModelConfig
    dtype: Any

    @nn.compact
    def __call__(self, x, decode: bool = False):
        model = self.model
        hidden = x.shape[-1]
        with jax.named_scope("router"):
            kernel = _RouterKernel(model.num_experts, name="router")(hidden)
            # ``load_balance_coeff`` moves this bias by a rule the source
            # does not publish: it is held at 0 (a buffer, not a weight).
            routing = moe.route(
                x, kernel, jnp.zeros((model.num_experts,), jnp.float32),
                model.num_experts_per_tok, model.route_scale,
                model.route_norm)
        gate_proj, up_proj, down_proj = _Experts(
            model.experts_held, model.moe_intermediate_size,
            name="experts")(hidden)
        routed, stats = moe.held_experts(
            x, routing, gate_proj, up_proj, down_proj, model.first_expert,
            self.dtype,
            every_expert=decode and x.shape[0] <= moe.EVERY_EXPERT_MAX_ROWS)
        with jax.named_scope("shared"):
            shared = _GatedMLP(
                model.moe_intermediate_size * model.num_shared_experts,
                self.dtype, name="shared")(x)
        return shared + routed, stats


class _RouterKernel(nn.Module):
    experts: int

    @nn.compact
    def __call__(self, hidden: int):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          (hidden, self.experts))


def rope(x, position, theta: float):
    """``x`` [B, T, heads, D], ``position`` [B, T]: the half-split
    rotation at ``theta ** (-2i / D)``, in float32."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = position.astype(jnp.float32)[..., None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x = x.astype(jnp.float32)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


class _Attention(nn.Module):
    model: TokenModelConfig
    sliding: bool
    dtype: Any

    @nn.compact
    def __call__(self, a, position, index, episode_start, ring_keys,
                 ring_values, ring_index, written):
        model, dtype = self.model, self.dtype
        batch, count, _ = a.shape
        heads, kv = model.num_attention_heads, model.num_key_value_heads
        dim = model.head_dim

        def heads_of(x, n):
            return x.reshape(batch, count, n, dim)

        query = _RMSNorm(model.rms_norm_eps, name="q_norm")(
            heads_of(_Linear(heads * dim, dtype, name="q_proj")(a), heads))
        key = _RMSNorm(model.rms_norm_eps, name="k_norm")(
            heads_of(_Linear(kv * dim, dtype, name="k_proj")(a), kv))
        value = attention_lib.round_to(
            heads_of(_Linear(kv * dim, dtype, name="v_proj")(a), kv), dtype)
        gate = _Linear(heads * dim, dtype, name="gate_proj")(a)
        if self.sliding:
            query = rope(query, position, model.rope_theta)
            key = rope(key, position, model.rope_theta)
        query = attention_lib.round_to(query, dtype)
        key = attention_lib.round_to(key, dtype)
        with jax.named_scope("window" if self.sliding else "full"):
            out, stats = attention_lib.cached_attention(
                query, key, value, ring_keys, ring_values, ring_index,
                index, episode_start,
                window=model.sliding_window if self.sliding else None)
            ring_keys = attention_lib.ring_write(ring_keys, key, written)
            ring_values = attention_lib.ring_write(ring_values, value,
                                                   written)
        out = out * jax.nn.sigmoid(gate)
        return (_Linear(model.hidden_size, dtype, name="o_proj")(out),
                ring_keys, ring_values, stats)


class _Layer(nn.Module):
    model: TokenModelConfig
    sliding: bool
    expert: bool
    dtype: Any

    @nn.compact
    def __call__(self, h, position, index, episode_start, ring_keys,
                 ring_values, ring_index, written):
        model, dtype = self.model, self.dtype

        def norm(name):
            return _RMSNorm(model.rms_norm_eps, name=name)

        attn, ring_keys, ring_values, seen = _Attention(
            model, self.sliding, dtype, name="attention")(
                norm("input_norm")(h), position, index, episode_start,
                ring_keys, ring_values, ring_index, written)
        stats = {f"attention/{name}": x for name, x in seen.items()}
        h = h + norm("post_attn_norm")(attn)
        m = norm("pre_mlp_norm")(h)
        flat = m.reshape(-1, m.shape[-1])
        if self.expert:
            # one token an env: a decode step
            f, routed = _MoE(model, dtype, name="moe")(
                flat, decode=m.shape[1] == 1)
            stats.update({f"moe/{name}": x for name, x in routed.items()})
        else:
            f = _GatedMLP(model.intermediate_size, dtype, name="mlp")(flat)
        h = h + norm("post_mlp_norm")(f.reshape(m.shape))
        return h, ring_keys, ring_values, stats


class _Baseline(nn.Module):
    """The value head: ``z w_b + c``."""

    dtype: Any

    @nn.compact
    def __call__(self, z):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (z.shape[-1], 1))
        bias = self.param("bias", nn.initializers.zeros_init(), (1,))
        return (jnp.dot(attention_lib.round_to(z, self.dtype),
                        kernel.astype(self.dtype),
                        preferred_element_type=jnp.float32) + bias)[..., 0]


class _Embed(nn.Module):
    vocab: int
    hidden: int

    @nn.compact
    def __call__(self, tokens):
        table = self.param(
            "embedding", nn.initializers.normal(1.0 / math.sqrt(self.hidden)),
            (self.vocab, self.hidden))
        return table[tokens]


class TokenPolicy(nn.Module):
    """See the module docstring.  ``unroll_length`` and
    ``episode_length`` size the rings; the rest is ``model``."""

    model: TokenModelConfig
    unroll_length: int
    episode_length: int
    compute_dtype: Any = jnp.float32
    # what ``Learner`` sets on its copy of any agent (parallel/mesh.py
    # batch_shards); this policy runs on one chip and merges nothing
    batch_shards: int = 1

    # what the kernel-policy line, the benchmark's probe and its AOT
    # sizing read off any agent: none of the conv agents' choices apply
    core_impl = None
    core_matmul_dtype = None
    conv_backend = None
    remat_torso = None
    torso_type = None
    # what the boundary still buys: the expert layer's sorted pairs'
    # buffers and every float32 activation between matmuls, one layer
    # at a time; attention keeps no score in HBM either way
    # (ops/attention.py: its backward kernel recomputes them in VMEM)
    remat_placement = "each layer"
    # learning-dynamics telemetry (runtime/learner.py): the parameter
    # groups, no module whose dead units are read, and the collection
    # the forward pass leaves its own numbers in
    layer_groups = ("embedding", "attention", "experts", "mlp", "norms",
                    "heads")
    dead_unit_module = None
    stats_collection = "stats"
    # ``Learner.init``: one jitted program, not ~400 eager ones
    init_in_one_program = True
    STATS = ("moe/pairs_here_share", "moe/tokens_per_expert_mean",
             "moe/expert_load_max_over_mean",
             "attention/key_blocks_visited_share")

    @staticmethod
    def layer_group(path) -> str:
        keys = [str(getattr(entry, "key", entry)) for entry in path]
        if "policy_logits" in keys or "baseline" in keys:
            return "heads"
        if "embed" in keys:
            return "embedding"
        if keys[-1] == "scale":
            return "norms"
        if "attention" in keys:
            return "attention"
        if "experts" in keys or "router" in keys:
            return "experts"
        return "mlp"

    # -- the action distribution --------------------------------------------

    @property
    def dist_spec(self) -> distributions.DistributionSpec:
        return distributions.DistributionSpec(
            sizes=(self.model.vocab_size,), vocabulary=True)

    @property
    def num_logits(self) -> int:
        return self.model.vocab_size

    @property
    def num_action_components(self) -> int:
        return 1

    def zero_actions(self, batch: int) -> jnp.ndarray:
        return jnp.zeros((batch,), jnp.int32)

    # -- the state -----------------------------------------------------------

    @property
    def window_slots(self) -> int:
        return self.model.sliding_window + self.unroll_length

    @property
    def full_slots(self) -> int:
        return self.episode_length + self.unroll_length

    def _slots(self, layer: int) -> int:
        return (self.window_slots
                if self.model.layer_types[layer] == SLIDING
                else self.full_slots)

    def initial_state(self, batch: int) -> TokenCache:
        model = self.model

        def ring(layer):
            return jnp.zeros((batch, self._slots(layer),
                              model.num_key_value_heads, model.head_dim),
                             self.compute_dtype)

        layers = range(model.num_hidden_layers)
        return TokenCache(
            keys=tuple(ring(layer) for layer in layers),
            values=tuple(ring(layer) for layer in layers),
            window_index=jnp.full((self.window_slots,),
                                  attention_lib.NO_KEY, jnp.int32),
            full_index=jnp.full((self.full_slots,),
                                attention_lib.NO_KEY, jnp.int32),
            written=jnp.zeros((), jnp.int32),
            episode_start=jnp.zeros((batch,), jnp.int32))

    def unroll_state(self, start: TokenCache, end: TokenCache) -> TokenCache:
        """The state the update unrolls from, without a copy of the
        rings from the unroll's start: the rings as the rollout left
        them, under the start's counters.  ``__call__`` masks every slot
        not written before ``written``, which hides the unroll's own,
        and the rings are long enough that the unroll overwrote nothing
        its queries may see."""
        return end._replace(written=start.written,
                            episode_start=start.episode_start)

    def cache_bytes(self, batch: int) -> int:
        model = self.model
        per_slot = (2 * model.num_key_value_heads * model.head_dim
                    * jnp.dtype(self.compute_dtype).itemsize)
        return batch * per_slot * sum(
            self._slots(layer) for layer in range(model.num_hidden_layers))

    def acting_params(self, params):
        """The parameters as acting reads them: cast once to the compute
        dtype before the rollout's scan, not at every step of it (the
        router stays float32: its product is float32)."""
        def cast(path, leaf):
            keys = [str(getattr(entry, "key", entry)) for entry in path]
            # matrices only: a norm's scale and the one bias are read
            # in float32 by both passes
            if "router" in keys or leaf.ndim < 2:
                return leaf
            return leaf.astype(self.compute_dtype)

        return jax.tree_util.tree_map_with_path(cast, params)

    # -- the forward pass ----------------------------------------------------

    @nn.compact
    def __call__(self, actions, env_outputs: StepOutput, state: TokenCache):
        del actions            # the last action is in no input of this model
        model, dtype = self.model, self.compute_dtype
        tokens = env_outputs.observation.frame           # i32 [T, B]
        count, batch = tokens.shape
        if count > self.unroll_length + 1:
            raise ValueError(
                f"token policy: {count} steps in one call, the rings are "
                f"sized for unroll_length + 1 = {self.unroll_length + 1}")
        written = state.written
        index = written + jnp.arange(count, dtype=jnp.int32)
        # a token whose ``done`` is set begins its env's episode
        marks = jnp.where(env_outputs.done.T, index[None, :], -1)
        start = jnp.maximum(jax.lax.cummax(marks, axis=1),
                            state.episode_start[:, None])      # [B, T]
        position = index[None, :] - start

        # rounded to the compute dtype first: acting reads a table cast
        # beforehand (``acting_params``), learning the float32 one
        h = attention_lib.round_to(
            _Embed(model.vocab_size, model.hidden_size, name="embed")(
                tokens.T), dtype).astype(jnp.float32)
        if model.mup_enabled:
            h = h * math.sqrt(model.hidden_size)

        def before(ring_index):     # hide what was not written before
            return jnp.where(ring_index < written, ring_index,
                             attention_lib.NO_KEY)

        ring_index = {SLIDING: before(state.window_index),
                      FULL: before(state.full_index)}
        # Learning keeps one layer's residuals at a time: the sorted
        # pairs' buffers of an expert layer are 1 GB at 8,224 tokens,
        # and the float32 activations between matmuls 67 MB apiece.
        # Attention's scores are not among them (its kernel writes none
        # and its backward recomputes a block's in VMEM), so the
        # boundary costs attention one more forward kernel, no more.
        layer_cls = nn.remat(_Layer) if count > 1 else _Layer
        keys, values, stats = [], [], []
        for layer, kind in enumerate(model.layer_types):
            h, ring_keys, ring_values, layer_stats = layer_cls(
                model, kind == SLIDING, layer >= model.num_dense_layers,
                dtype, name=f"layer_{layer}")(
                    h, position, index, start, state.keys[layer],
                    state.values[layer], ring_index[kind], written)
            keys.append(ring_keys)
            values.append(ring_values)
            stats.append(layer_stats)
        # each number's mean over the layers that say it (acting says
        # none of the attention's: one query an env visits every slot)
        for name in self.STATS:
            said = [s[name] for s in stats if name in s]
            if said:
                self.sow(self.stats_collection, name,
                         jnp.mean(jnp.stack(said)),
                         init_fn=lambda: 0.0, reduce_fn=lambda _, new: new)

        z = _RMSNorm(model.rms_norm_eps, name="final_norm")(h)
        z = jnp.swapaxes(z, 0, 1)                         # [T, B, hidden]
        policy_logits = _Linear(model.vocab_size, dtype,
                                name="policy_logits")(z)
        baseline = _Baseline(dtype, name="baseline")(z)
        new_state = TokenCache(
            keys=tuple(keys), values=tuple(values),
            window_index=attention_lib.index_write(
                state.window_index, written, count),
            full_index=attention_lib.index_write(
                state.full_index, written, count),
            written=written + count,
            episode_start=start[:, -1])
        return (policy_logits, baseline), new_state
