"""A token policy: a decoder behind the agent's calling contract, its
layers a mixer (attention through a ring of its own, attention into
another layer's ring, a state-space scan, a delta-rule scan, a gated
memory unit, a mixture of experts) and, where the family has one, an MLP
(dense or experts) behind it, picked by the configuration file.  What a
family is made of is declared once, in its record of ``_FAMILY`` (the keys its
file must have, where its layers' kinds are written, which module a
layer of each kind is, its norms, its head, its telemetry); nothing
else in the program asks a family's name, and the record is private,
not an extension point: a family is added by adding a record, the
mechanisms it lacks, a preset for the family suite
(tests/family_suite.py) and the benchmark's files.  Five families
(``FAMILIES``): ``afmoe`` (window and full attention mixed, a mixture of
experts with a shared expert), ``phi4flash`` (the decoder-hybrid-
decoder: state-space and window layers, one full-attention layer whose
cache every later cross layer reads, memory units gated by the last
state-space layer's output), ``deepseek_v3`` (latent attention: the
cache holds one compressed row a token, which every head's key and
value are up-projections of; a mixture of experts with shared experts
behind leading dense layers), ``nemotron_h`` (every layer ONE mixer
alone, by the file's ``hybrid_override_pattern``: a Mamba-2 scan with a
matrix state a head, a mixture of experts whose experts have no gate,
or plain grouped-query attention) and ``olmo_hybrid`` (Gated-DeltaNet
layers, a matrix state a head that a token CORRECTS before it writes,
to one full-attention layer in four, a dense MLP behind each; a
branch's result normed, its input not).

``__call__(actions, env_outputs, state) -> ((policy_logits, baseline),
state)`` over time-major ``[T, B]`` inputs, as ``ImpalaAgent`` has it:
T = 1 is acting and T = unroll is learning, from one definition.  The
observation is a token id (``observation.frame``, int32), the action a
token of the same vocabulary, and the agent's state is its attention
cache (``TokenCache``): per layer that makes keys a ring of keys and
values (of latent rows where the family's attention is latent), each
slot's index in the env's token stream beside it, where
each env's episode began, and per scan layer (Mamba-1's, Mamba-2's or
a delta-rule layer's) its recurrent state and the last inputs of its
short convolution.  An episode's end clears
no ring: a query sees a key of its own episode only (ops/attention.py),
so ``done`` moves ``episode_start`` and the stale slots fall out of
every mask; a recurrence cannot be masked after the fact, so the scan
zeroes its state at an episode's first token (ops/ssm.py, ops/ssd.py,
ops/gated_delta.py) and the convolution drops the taps that reach
before it.

The ``afmoe`` layer, for token ids ``x`` (sizes under the source's key names,
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

The ``phi4flash`` layers (benchmark/references/sambay_token.py states
them in float32; no position encoding anywhere, LayerNorm with bias, a
tied head)::

    h = E[x];  h = h + Mixer(LN_in(h));  h = h + MLP(LN_post(h))
    state space: [x, z] = a W_in;  x = silu(conv_4(x) + b)
                 [d, B, C] = x W_x;  delta = softplus(d W_dt + b_dt)
                 y = scan(x, delta, -exp(A_log), B, C) + D x   (ops/ssm.py)
                 memory = y;  out = (y * silu(z)) W_out
    memory unit: out = (silu(a W_in) * memory) W_out      the same token's
    differential attention (heads in adjacent pairs, v of a pair 2 x wide):
                 o = (softmax(q1 k1) - lambda softmax(q2 k2)) v
                 lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l)
                 out = concat(RMSNorm(o) * (1 - lambda_init)) W_o
    a cross layer projects q only: k, v and the ring are the full layer's

The ``deepseek_v3`` layer (benchmark/references/deepseek_v3_token.py;
``q_lora_rank`` null; plain pre-norm residuals, no embedding scale)::

    a = RMSNorm_in(h);  q = a Wq [heads, nope | rope]
    [c | r] = a Wkva [kv_lora_rank | rope];  c = RMSNorm_kv(c)
    q_rope, r = RoPE(q_rope, r; theta, position in episode)  one r for all heads
    [k_nope | v] = c Wkvb [heads, nope | v_head_dim]
    attn_h = softmax((q_nope_h . k_nope_h + q_rope_h . r) / sqrt(nope + rope)) v_h
    h = h + concat(attn) Wo;  h = h + MLP(RMSNorm_post(h))   dense, then experts

The ring of such a layer holds ``[c | r]`` and nothing else.  Neither
pass makes a past token's ``k_nope`` or ``v``: ``q_nope_h . k_nope_hj =
(q_nope_h Wkvb_k,h^T) . c_j`` and ``sum_j p_j v_hj = (sum_j p_j c_j)
Wkvb_v,h``, so the query takes the up-projection in before the cache is
read and the weighted sum of rows takes it after
(``ops/attention.py latent_attention``).

The ``nemotron_h`` layer (benchmark/references/nemotron_h_token.py;
no embedding scale, no position encoding anywhere, RMSNorm)::

    every layer:  h = h + Mixer(RMSNorm_in(h))      one sub-block, no MLP
    M (Mamba-2):  [z | xBC | dt] = a W_in;  xBC = silu(conv_4(xBC) + b)
                  [x | B | C] = xBC     x [heads, head_dim]; B, C [groups, N]
                  delta = softplus(dt + dt_bias);  A = -exp(A_log)    a head
                  y = scan(x, delta, A, B, C) + D x           (ops/ssd.py)
                  out = (RMSNorm_group(y * silu(z)) * w) W_out
    * (attention): softmax(q k / sqrt(head_dim)) v Wo   no rotation or gate
    E (experts):  shared(a) + the held experts' part, an expert
                  relu(a Wu)^2 Wd: two matrices, no gate       (ops/moe.py)

The ``olmo_hybrid`` layers (benchmark/references/olmo_hybrid_token.py;
no embedding scale, no position encoding anywhere, RMSNorm; the Olmo 2/3
order: a branch's RESULT is normed, its input is not)::

    every layer:  h = h + RMSNorm(Mixer(h));  h = h + RMSNorm(MLP(h))
    linear_attention (Gated DeltaNet), H heads, keys of K, values of V:
        [q | k | v | z | a | b] = h W_in
        [q | k | v] = silu(conv_4([q | k | v]))               no bias
        q = q / |q| / sqrt(K);  k = k / |k|                       a head
        b_t = 2 sigmoid(b);  a_t = exp(-exp(A_log) softplus(a + dt_bias))
        S_t = a_t S_(t-1) + b_t (v_t - a_t S_(t-1) k_t) k_t^T      [H, V, K]
        o_t = S_t q_t                                (ops/gated_delta.py)
        out = (RMSNorm_V(o_t) w * silu(z_t)) W_out    the norm BEFORE the gate
    full_attention: q = RMSNorm(h Wq), k = RMSNorm(h Wk) over the WHOLE
        projection, before the heads are split; as many key/value heads
        as query heads; softmax(q k / sqrt(D)) v Wo, no rotation or gate

The rings are sized so that ONE buffer serves the rollout and the
update: ``window + unroll`` slots (``episode_length + unroll`` on a full
layer) still hold, when an unroll ends, everything its first query may
see, so the update attends into the cache as the rollout left it and
masks the unroll's own slots by their index (``unroll_state``); no copy
of the rings is kept from the unroll's start.  The recurrent state and
the convolution's tail are kept from the start (0.8 MB an env at
``phi4flash``'s published widths, 8.7 MB at ``nemotron_h``'s and 7.1 MB
at ``olmo_hybrid``'s, whose states are a matrix a head): the update
scans again from them.
"""

import dataclasses
import functools
import json
import math
import os
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from scalable_agent_tpu.ops import attention as attention_lib
from scalable_agent_tpu.ops import distributions, gated_delta, moe, ssd, ssm
from scalable_agent_tpu.types import StepOutput

SLIDING = "sliding_attention"
FULL = "full_attention"
CROSS = "cross_attention"       # queries only, into the last full layer's ring
STATE_SPACE = "state_space"
MEMORY_UNIT = "memory_unit"     # gated by the last state-space layer's output
LATENT = "latent_attention"     # full attention through a ring of latent rows
MAMBA2 = "mamba2"               # a matrix-state scan (ops/ssd.py)
EXPERTS = "experts"             # the expert layer as the layer's one mixer
LINEAR = "linear_attention"     # a delta-rule scan (ops/gated_delta.py)
_SCANS = (STATE_SPACE, MAMBA2, LINEAR)
# ``hybrid_override_pattern``'s letters ("-", a dense MLP alone, is not
# built)
_PATTERN = {"M": MAMBA2, "E": EXPERTS, "*": FULL}
# an expert's form by the file's activation (ops/moe.py): a silu expert
# is gated (three matrices), a relu2 one is not (two)
_GATED = {"silu": True, "relu2": False}
_OWN_RING = (SLIDING, FULL, LATENT)
# the keys every family's file must have (the rest of the fields default;
# ``_Family.required`` has a family's own)
_ALWAYS = ("vocab_size", "hidden_size", "num_attention_heads",
           "intermediate_size", "num_hidden_layers")
_WINDOWED = _ALWAYS + ("num_key_value_heads", "sliding_window")


@dataclasses.dataclass(frozen=True)
class TokenModelConfig:
    """The sizes of the model as it is run, under the source's own key
    names, from one JSON file (``from_file``); keys it does not name are
    the file's own business (loss, optimizer, flags).  ``model_type``
    says which family's keys the file has; the other families' stay at
    their defaults and nothing reads them."""

    vocab_size: int
    hidden_size: int
    head_dim: int
    num_attention_heads: int
    num_key_value_heads: int
    intermediate_size: int
    num_hidden_layers: int
    layer_types: Tuple[str, ...]        # each layer's mixer
    sliding_window: int
    model_type: str = "afmoe"
    # afmoe, and deepseek_v3 under its own names (``_Family.said_as``)
    moe_intermediate_size: int = 0
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    num_dense_layers: int = 0
    route_scale: float = 1.0
    route_norm: bool = True
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = False
    experts_held: int = 0
    first_expert: int = 0
    # phi4flash
    layer_index: Tuple[int, ...] = ()   # each layer's published index
    layer_norm_eps: float = 1e-5
    mamba_d_state: int = 0
    mamba_d_conv: int = 0
    mamba_expand: int = 0
    mamba_dt_rank: int = 0
    # deepseek_v3
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    # nemotron_h
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    n_groups: int = 0
    conv_kernel: int = 0
    chunk_size: int = 0
    moe_shared_expert_intermediate_size: int = 0
    mlp_hidden_act: str = "silu"        # the experts' (``_GATED``)
    # olmo_hybrid (its convolution's taps are ``conv_kernel``)
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_allow_neg_eigval: bool = True    # b in (0, 2), not (0, 1)

    @property
    def d_inner(self) -> int:
        if self.mamba_num_heads:
            return self.mamba_num_heads * self.mamba_head_dim
        return self.mamba_expand * self.hidden_size

    @property
    def conv_width(self) -> int:
        """Channels a scan layer's short convolution runs over: Mamba-1's
        ``x``, Mamba-2's ``x | B | C``."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def linear_widths(self) -> Tuple[int, int]:
        """Numbers a token in a delta-rule layer's keys (its queries
        too) and in its values, over the heads."""
        return (self.linear_num_key_heads * self.linear_key_head_dim,
                self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def conv_taps(self) -> int:
        return self.conv_kernel or self.mamba_d_conv

    @property
    def mixer_alone(self) -> bool:
        """Every layer is its mixer and nothing else: no MLP behind it
        (an expert layer is then a MIXER, by the file's pattern)."""
        return _FAMILY[self.model_type].mixer_alone

    @property
    def shared_expert_width(self) -> int:
        return self.num_shared_experts * (
            self.moe_shared_expert_intermediate_size
            or self.moe_intermediate_size)

    @property
    def latent_dim(self) -> int:
        """Numbers a token a layer in a latent ring: the normalised
        compression and the one rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def is_expert_layer(self, layer: int) -> bool:
        if self.mixer_alone:
            return self.layer_types[layer] == EXPERTS
        return self.num_experts > 0 and layer >= self.num_dense_layers

    def ring_of(self, layer: int) -> Optional[int]:
        """The layer whose ring ``layer`` attends into: its own, the last
        full layer before it (a cross layer), none."""
        kind = self.layer_types[layer]
        if kind in _OWN_RING:
            return layer
        if kind == CROSS:
            return max(at for at in range(layer)
                       if self.layer_types[at] == FULL)
        return None

    @property
    def memory_from(self) -> Optional[int]:
        """The state-space layer whose output gates the memory units:
        the last one before the first of them."""
        if MEMORY_UNIT not in self.layer_types:
            return None
        first = self.layer_types.index(MEMORY_UNIT)
        return max(at for at in range(first)
                   if self.layer_types[at] == STATE_SPACE)

    @classmethod
    def from_dict(cls, raw: Dict[str, Any]) -> "TokenModelConfig":
        name = raw.get("model_type", cls.model_type)
        if name not in _FAMILY:
            raise ValueError(
                f"token policy: model_type={name!r} is not built "
                f"(only {', '.join(map(repr, FAMILIES))})")
        family = _FAMILY[name]
        for key, want in family.only:
            if raw.get(key, want) != want:
                raise ValueError(
                    f"token policy: {key}={raw[key]!r} is not built "
                    f"(only {want!r})")
        missing = [n for n in family.required if n not in raw]
        if missing:
            raise ValueError(
                f"token policy: the model configuration lacks {missing}")
        names = [f.name for f in dataclasses.fields(cls)]
        values = {n: raw[n] for n in names if n in raw}
        values["model_type"] = name
        values.update((field, raw[said]) for said, field in family.said_as)
        values.update(family.layers(raw))
        values["layer_types"] = tuple(values["layer_types"])
        model = cls(**values)
        if len(model.layer_types) != model.num_hidden_layers or any(
                kind not in family.mixers for kind in model.layer_types):
            raise ValueError(family.layers_refusal)
        if model.num_experts and not (
                0 <= model.first_expert and model.first_expert
                + model.experts_held <= model.num_experts):
            raise ValueError(
                f"token policy: experts [{model.first_expert}, "
                f"{model.first_expert + model.experts_held}) are not "
                f"among {model.num_experts}")
        for layer, kind in enumerate(model.layer_types):
            before = model.layer_types[:layer]
            if ((kind == CROSS and FULL not in before)
                    or (kind == MEMORY_UNIT and STATE_SPACE not in before)):
                raise ValueError(
                    f"token policy: layer {layer} ({kind}) has no "
                    f"{FULL if kind == CROSS else STATE_SPACE} layer "
                    f"before it to read")
        refused = family.check and family.check(model)
        if refused:
            raise ValueError(refused)
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
    """The token policy's state: what the next query attends back into
    and what the next token's recurrences continue from."""

    # per layer that makes keys, in order: [B, slots, kv_heads, head_dim]
    # each; under latent attention ``keys`` holds the one ring, [B,
    # latent_dim, full slots], a column a token that is every head's key
    # and value, compressed (ops/attention.py has why a token is a
    # column), and ``values`` is empty
    keys: Tuple[Any, ...]
    values: Tuple[Any, ...]
    window_index: Any           # i32 [window slots]: stream index by slot
    full_index: Any             # i32 [full slots]
    written: Any                # i32 []: tokens in every env's stream
    episode_start: Any          # i32 [B]: index at which the episode began
    # per state-space layer, in order.  Mamba-1 (ops/ssm.py has why
    # states lie down the sublanes): f32 [B, d_state, d_inner], [B,
    # d_conv - 1, d_inner].  Mamba-2 (ops/ssd.py), a matrix a head: f32
    # [B, heads, head_dim, d_state], and the tail over ``x | B | C``:
    # [B, conv_kernel - 1, d_inner + 2 * n_groups * d_state].  A
    # delta-rule layer (ops/gated_delta.py), a matrix a head too: f32
    # [B, heads, value_dim, key_dim], and the tail over ``q | k | v``:
    # [B, conv_kernel - 1, 2 * heads * key_dim + heads * value_dim].
    # (``_Family.scan_state`` is where a family states the two.)
    ssm_state: Tuple[Any, ...] = ()
    conv_tail: Tuple[Any, ...] = ()


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


class _LayerNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones_init(),
                           (x.shape[-1],))
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (x.shape[-1],))
        x = x.astype(jnp.float32)
        x = x - jnp.mean(x, axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps)
        return y * scale + bias


class _MLP(nn.Module):
    """``(act(x Wg) * (x Wu)) Wd``, or without a gate ``act(x Wu) Wd``
    (``ops/moe.py`` has the two forms)."""

    width: int
    dtype: Any
    act: str = "silu"

    @nn.compact
    def __call__(self, x):
        hidden = x.shape[-1]
        projected = [_Linear(self.width, self.dtype, name=name)(x)
                     for name in (("gate_proj", "up_proj")
                                  if _GATED[self.act] else ("up_proj",))]
        return _Linear(hidden, self.dtype, name="down_proj")(
            moe.activate(projected, self.act))


class _Experts(nn.Module):
    """The held experts' stacked weights; no ``gate_proj`` (None) where
    an expert has no gate."""

    held: int
    width: int
    gated: bool = True

    @nn.compact
    def __call__(self, hidden: int):
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        return (self.param("gate_proj", init,
                           (self.held, hidden, self.width))
                if self.gated else None,
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
            kernel = _Kernel(model.num_experts, name="router")(hidden)
            # ``load_balance_coeff`` (``topk_method: noaux_tc``) moves
            # this bias by a rule the source does not publish: it is
            # held at 0 (a buffer, not a weight).
            routing = moe.route(
                x, kernel, jnp.zeros((model.num_experts,), jnp.float32),
                model.num_experts_per_tok, model.route_scale,
                model.route_norm)
        act = model.mlp_hidden_act
        gate_proj, up_proj, down_proj = _Experts(
            model.experts_held, model.moe_intermediate_size, _GATED[act],
            name="experts")(hidden)
        routed, stats = moe.held_experts(
            x, routing, gate_proj, up_proj, down_proj, model.first_expert,
            model.num_experts, self.dtype,
            every_expert=decode and x.shape[0] <= moe.EVERY_EXPERT_MAX_ROWS,
            act=act)
        with jax.named_scope("shared"):
            shared = _MLP(model.shared_expert_width, self.dtype, act,
                          name="shared")(x)
        return shared + routed, stats


class _Kernel(nn.Module):
    """A matrix its caller multiplies by in a way of its own (the router
    in float32; latent attention, a head's columns at a time)."""

    features: int

    @nn.compact
    def __call__(self, inputs: int):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          (inputs, self.features))


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


def rope_interleaved(x, position, theta: float):
    """``rope`` over adjacent pairs: numbers 2i and 2i + 1 turn by the
    angle ``rope`` gives numbers i and i + D / 2 (``rope_interleave``;
    the same rotation of a permuted vector, so a model's scores are the
    half-split ones' under the matching permutation of its weights'
    columns)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = position.astype(jnp.float32)[..., None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


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


class _PlainAttention(nn.Module):
    """Grouped-query attention over the whole episode and nothing else:
    no rotation, no bias, no gate, no head norm (``nemotron_h``).
    ``whole_norms`` (``olmo_hybrid``): the query and key projections are
    normed over their WHOLE width, every head's numbers in one mean
    square, before the heads are split."""

    model: TokenModelConfig
    dtype: Any
    whole_norms: bool = False

    @nn.compact
    def __call__(self, a, index, episode_start, ring_keys, ring_values,
                 ring_index, written):
        model, dtype = self.model, self.dtype
        batch, count, _ = a.shape
        dim = model.head_dim

        def project(name, heads, norm=None):
            x = _Linear(heads * dim, dtype, name=name)(a)
            if norm and self.whole_norms:
                x = _RMSNorm(model.rms_norm_eps, name=norm)(x)
            return attention_lib.round_to(
                x.reshape(batch, count, heads, dim), dtype)

        query = project("q_proj", model.num_attention_heads, "q_norm")
        key = project("k_proj", model.num_key_value_heads, "k_norm")
        value = project("v_proj", model.num_key_value_heads)
        with jax.named_scope("full"):
            out, stats = attention_lib.cached_attention(
                query, key, value, ring_keys, ring_values, ring_index,
                index, episode_start, window=None)
            ring_keys = attention_lib.ring_write(ring_keys, key, written)
            ring_values = attention_lib.ring_write(ring_values, value,
                                                   written)
        return (_Linear(model.hidden_size, dtype, name="o_proj")(out),
                ring_keys, ring_values, stats)


class _LatentAttention(nn.Module):
    """Latent attention with the up-projection absorbed (the module's
    docstring): what the ring holds, and what this call hands the
    kernels as its own keys and values, is the row ``[c | r]``."""

    model: TokenModelConfig
    dtype: Any

    @nn.compact
    def __call__(self, a, position, index, episode_start, ring, ring_index,
                 written):
        model, dtype = self.model, self.dtype
        batch, count, _ = a.shape
        heads, rank = model.num_attention_heads, model.kv_lora_rank
        nope, turned = model.qk_nope_head_dim, model.qk_rope_head_dim
        rotate = rope_interleaved if model.rope_interleave else rope
        with jax.named_scope("latent"):
            with jax.named_scope("project"):
                query = _Linear(heads * (nope + turned), dtype,
                                name="q_proj")(a).reshape(
                                    batch, count, heads, nope + turned)
                row = _Linear(rank + turned, dtype, name="kv_a_proj")(a)
                latent = attention_lib.round_to(jnp.concatenate([
                    _RMSNorm(model.rms_norm_eps, name="kv_a_norm")(
                        row[..., :rank]),
                    rotate(row[..., None, rank:], position,
                           model.rope_theta)[..., 0, :]], axis=-1), dtype)
                up = _Kernel(heads * (nope + model.v_head_dim),
                             name="kv_b_proj")(rank).astype(dtype).reshape(
                                 rank, heads, nope + model.v_head_dim)
                # a head's scores against c_j through its own columns of
                # Wkvb, taken in before the cache is read
                query = attention_lib.round_to(jnp.concatenate([
                    jnp.einsum(
                        "bthd,rhd->bthr",
                        attention_lib.round_to(query[..., :nope], dtype),
                        up[..., :nope], preferred_element_type=jnp.float32),
                    rotate(query[..., nope:], position, model.rope_theta)],
                    axis=-1), dtype)
            with jax.named_scope("attend"):
                out, stats = attention_lib.latent_attention(
                    query, latent, ring, ring_index, index, episode_start,
                    rank, 1.0 / math.sqrt(nope + turned))
                ring = attention_lib.latent_ring_write(ring, latent, written)
            with jax.named_scope("project"):
                # the weighted sum of rows, up-projected a head at a time
                out = jnp.einsum(
                    "bthr,rhd->bthd", attention_lib.round_to(out, dtype),
                    up[..., nope:], preferred_element_type=jnp.float32)
            return (_Linear(model.hidden_size, dtype, name="o_proj")(
                out.reshape(batch, count, heads * model.v_head_dim)),
                ring, stats)


class _Handed(NamedTuple):
    """What a layer leaves for the later layers of the same call: the
    gating state-space layer's output, and the full layer's own keys and
    values of this call beside its ring as this call found it."""

    memory: Any = None          # f32 [B, T, d_inner]
    key: Any = None             # [B, T, kv pairs, 2 * head_dim]
    value: Any = None
    ring_keys: Any = None
    ring_values: Any = None


def lambda_init(published_index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * published_index)


class _DifferentialAttention(nn.Module):
    """Heads in adjacent pairs: a pair's two queries score its two keys
    in two softmaxes over the one value of twice the width
    (``ops/attention.py`` ``streams``).  A cross layer projects queries
    only and reads the full layer's keys, values and ring."""

    model: TokenModelConfig
    kind: str
    published_index: int
    dtype: Any

    @nn.compact
    def __call__(self, a, index, episode_start, ring_keys, ring_values,
                 ring_index, written, handed: _Handed):
        model, dtype = self.model, self.dtype
        batch, count, _ = a.shape
        pairs, kv = (model.num_attention_heads // 2,
                     model.num_key_value_heads // 2)
        dim = 2 * model.head_dim

        def project(name, heads):
            return attention_lib.round_to(
                _Linear(heads * dim, dtype, name=name)(a).reshape(
                    batch, count, heads, dim), dtype)

        query = project("q_proj", pairs)
        if self.kind == CROSS:
            key, value = handed.key, handed.value
            ring_keys, ring_values = handed.ring_keys, handed.ring_values
        else:
            key, value = project("k_proj", kv), project("v_proj", kv)
        scope = {SLIDING: "window", FULL: "full", CROSS: "cross"}[self.kind]
        with jax.named_scope(scope):
            out, stats = attention_lib.cached_attention(
                query, key, value, ring_keys, ring_values, ring_index,
                index, episode_start,
                window=model.sliding_window if self.kind == SLIDING else None,
                streams=2)
            if self.kind == FULL:
                handed = handed._replace(
                    key=key, value=value, ring_keys=ring_keys,
                    ring_values=ring_values)
            if self.kind != CROSS:
                ring_keys = attention_lib.ring_write(ring_keys, key, written)
                ring_values = attention_lib.ring_write(ring_values, value,
                                                       written)
        out = out.reshape(batch, count, pairs, 2, dim)
        small = nn.initializers.normal(0.1)
        lq1, lk1, lq2, lk2 = (
            self.param(name, small, (model.head_dim,))
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
        start = lambda_init(self.published_index)
        lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + start
        out = _RMSNorm(model.layer_norm_eps, name="pair_norm")(
            out[..., 0, :] - lam * out[..., 1, :]) * (1.0 - start)
        return (_Linear(model.hidden_size, dtype, name="o_proj")(
            out.reshape(batch, count, pairs * dim)),
            ring_keys, ring_values, handed, stats)


def _family_dt_bias(key, shape, dtype=jnp.float32):
    """The family's start of the step's bias: softplus(bias) log-uniform
    in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    return dt + jnp.log(-jnp.expm1(-dt))


def _short_conv(module: nn.Module, x, position, tail, taps: int,
                biased: bool = True):
    """``silu(conv_taps(x) + b)``, depthwise and causal, of ``module``'s
    ``conv_kernel`` and (where ``biased``) ``conv_bias``: tap k reaches k
    tokens back (into ``tail``, the call before's last inputs) and not
    before the episode.  -> (the result, the tail the next call
    continues from)."""
    count, width = x.shape[1], x.shape[2]
    with jax.named_scope("conv"):
        kernel = module.param(
            "conv_kernel", nn.initializers.normal(1.0 / math.sqrt(taps)),
            (taps, width))
        bias = module.param("conv_bias", nn.initializers.zeros_init(),
                            (width,)) if biased else 0.0
        seen = jnp.concatenate([tail, x], axis=1)
        x = bias + sum(
            kernel[taps - 1 - back]
            * seen[:, taps - 1 - back:taps - 1 - back + count]
            * (position >= back)[..., None] for back in range(taps))
        return jax.nn.silu(x), seen[:, count:]


class _StateSpace(nn.Module):
    """The selective state-space mixer: a short causal convolution and
    the scan of ``ops/ssm.py``, both of which start afresh at an
    episode's first token."""

    model: TokenModelConfig
    dtype: Any

    @nn.compact
    def __call__(self, a, position, state, tail):
        model, dtype = self.model, self.dtype
        width, states = model.d_inner, model.mamba_d_state
        rank = model.mamba_dt_rank
        x, z = jnp.split(_Linear(2 * width, dtype, name="in_proj")(a), 2,
                         axis=-1)
        x, tail = _short_conv(self, x, position, tail, model.mamba_d_conv)
        chosen = _Linear(rank + 2 * states, dtype, name="x_proj")(x)
        delta = jax.nn.softplus(
            _Linear(width, dtype, name="dt_proj")(chosen[..., :rank])
            + self.param("dt_bias", _family_dt_bias, (width,)))
        a_log = self.param(
            "A_log", lambda key, shape: jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[1] + 1, dtype=jnp.float32)), shape), (width, states))
        skip = self.param("D", nn.initializers.ones_init(), (width,))
        with jax.named_scope("scan"):
            y, state = ssm.selective_scan(
                x, delta, -jnp.exp(a_log).T, skip,
                chosen[..., rank:rank + states], chosen[..., rank + states:],
                position == 0, state)
        with jax.named_scope("gate"):
            gated = y * jax.nn.silu(z)
        return (_Linear(model.hidden_size, dtype, name="out_proj")(gated), y,
                state, tail)


class _Mamba2(nn.Module):
    """The Mamba-2 mixer: one projection into gate, ``x | B | C`` and a
    step a head, the short convolution over ``x | B | C``, the scan of
    ``ops/ssd.py`` (a matrix state a head, one decay a head a token; both
    start afresh at an episode's first token), and a norm over each
    group's channels AFTER the gate."""

    model: TokenModelConfig
    dtype: Any

    @nn.compact
    def __call__(self, a, position, state, tail):
        model, dtype = self.model, self.dtype
        batch, count, _ = a.shape
        heads, dim = model.mamba_num_heads, model.mamba_head_dim
        groups, states = model.n_groups, model.ssm_state_size
        width = model.d_inner
        z, mixed, dt = jnp.split(
            _Linear(width + model.conv_width + heads, dtype,
                    name="in_proj")(a),
            [width, width + model.conv_width], axis=-1)
        mixed, tail = _short_conv(self, mixed, position, tail,
                                  model.conv_kernel)
        x, b, c = jnp.split(mixed, [width, width + groups * states], axis=-1)
        delta = jax.nn.softplus(
            dt + self.param("dt_bias", _family_dt_bias, (heads,)))
        # the family's start: A uniform in [1, 16], one a head
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                key, shape, jnp.float32, 1.0, 16.0)), (heads,))
        skip = self.param("D", nn.initializers.ones_init(), (heads,))
        with jax.named_scope("scan"):
            y, state = ssd.ssd_scan(
                x.reshape(batch, count, heads, dim), delta, -jnp.exp(a_log),
                skip, b.reshape(batch, count, groups, states),
                c.reshape(batch, count, groups, states), position == 0,
                state, chunk=model.chunk_size, dtype=dtype)
        with jax.named_scope("norm_gate"):
            gated = (y.reshape(batch, count, width)
                     * jax.nn.silu(z)).reshape(batch, count, groups, -1)
            gated = gated * jax.lax.rsqrt(
                jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
                + model.rms_norm_eps)
            gated = gated.reshape(batch, count, width) * self.param(
                "norm_scale", nn.initializers.ones_init(), (width,))
        return (_Linear(model.hidden_size, dtype, name="out_proj")(gated),
                state, tail)


class _GatedDeltaNet(nn.Module):
    """The Gated-DeltaNet mixer: one projection into ``q | k | v``, the
    output's gate and a decay and a write strength a head; the short
    convolution over ``q | k | v`` (no bias); queries and keys of unit
    length a head; the delta-rule scan of ``ops/gated_delta.py`` (a
    matrix state a head; it and the convolution start afresh at an
    episode's first token); a norm over each head's values BEFORE the
    gate."""

    model: TokenModelConfig
    dtype: Any

    @nn.compact
    def __call__(self, a, position, state, tail):
        model, dtype = self.model, self.dtype
        batch, count, _ = a.shape
        heads = model.linear_num_key_heads
        key_dim, value_dim = (model.linear_key_head_dim,
                              model.linear_value_head_dim)
        keys, values = model.linear_widths
        mixed, z, decay, write = jnp.split(
            _Linear(2 * keys + 2 * values + 2 * heads, dtype,
                    name="in_proj")(a),
            [2 * keys + values, 2 * keys + 2 * values,
             2 * keys + 2 * values + heads], axis=-1)
        mixed, tail = _short_conv(self, mixed, position, tail,
                                  model.conv_kernel, biased=False)

        def unit(x):        # [B, T, heads * key_dim] -> unit length a head
            x = x.reshape(batch, count, heads, key_dim)
            return x * jax.lax.rsqrt(
                jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

        query = unit(mixed[..., :keys]) * (1.0 / math.sqrt(key_dim))
        key = unit(mixed[..., keys:2 * keys])
        value = mixed[..., 2 * keys:].reshape(batch, count, heads, value_dim)
        # b in (0, 2) lets a transition's eigenvalue along its key be
        # negative (``linear_allow_neg_eigval``); in (0, 1) it cannot
        write = jax.nn.sigmoid(write) * (
            2.0 if model.linear_allow_neg_eigval else 1.0)
        # the family's start: A uniform in (0, 16], one a head
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(16.0 - jax.random.uniform(
                key, shape, jnp.float32, 0.0, 16.0)), (heads,))
        log_decay = -jnp.exp(a_log) * jax.nn.softplus(
            decay + self.param("dt_bias", _family_dt_bias, (heads,)))
        with jax.named_scope("scan"):
            out, state = gated_delta.gated_delta_scan(
                query, key, value, write, log_decay, position == 0, state,
                chunk=model.chunk_size, dtype=dtype)
        with jax.named_scope("norm_gate"):
            out = out * jax.lax.rsqrt(
                jnp.mean(jnp.square(out), axis=-1, keepdims=True)
                + model.rms_norm_eps)
            out = out * self.param("norm_scale", nn.initializers.ones_init(),
                                   (value_dim,))
            gated = out.reshape(batch, count, values) * jax.nn.silu(z)
        return (_Linear(model.hidden_size, dtype, name="out_proj")(gated),
                state, tail)


class _MemoryUnit(nn.Module):
    model: TokenModelConfig
    dtype: Any

    @nn.compact
    def __call__(self, a, memory):
        gate = _Linear(self.model.d_inner, self.dtype, name="in_proj")(a)
        return _Linear(self.model.hidden_size, self.dtype, name="out_proj")(
            jax.nn.silu(gate) * memory)


class _Held(NamedTuple):
    """What a layer's mixer may read, and hands back with what it
    changed.  ``ring_*``: the ring it attends into (None where it attends
    into none); ``state`` / ``tail``: a scan's; ``handed``: what earlier
    layers of this call left."""

    position: Any
    index: Any
    episode_start: Any
    ring_keys: Any
    ring_values: Any
    ring_index: Any
    written: Any
    handed: _Handed
    state: Any
    tail: Any


def _expert_layer(layer: "_Layer", m):
    """-> (the expert layer's result, what it says of itself)."""
    flat = m.reshape(-1, m.shape[-1])
    # one token an env: a decode step
    f, routed = _MoE(layer.model, layer.dtype, name="moe")(
        flat, decode=m.shape[1] == 1)
    return f.reshape(m.shape), {f"moe/{name}": x
                                for name, x in routed.items()}


def _attends(held: _Held, ring_keys, ring_values, seen, **more):
    """An attention mixer's rings written, and what the pass says of
    itself."""
    return (held._replace(ring_keys=ring_keys, ring_values=ring_values,
                          **more),
            {f"attention/{name}": x for name, x in seen.items()})


# A mixer by ``_Family.mixers``: (the layer, the normed residual, what it
# may read) -> (its result, what it hands back, its numbers).  Each maps
# ``_Layer``'s arguments onto a module that keeps a signature of its own,
# under the module's name in the parameter tree.

def _experts_mixer(layer, a, held):
    mixed, stats = _expert_layer(layer, a)
    return mixed, held, stats


def _mamba2_mixer(layer, a, held):
    mixed, state, tail = _Mamba2(layer.model, layer.dtype, name="ssd")(
        a, held.position, held.state, held.tail)
    return mixed, held._replace(state=state, tail=tail), {}


def _gated_delta_mixer(layer, a, held):
    mixed, state, tail = _GatedDeltaNet(layer.model, layer.dtype, name="gdn")(
        a, held.position, held.state, held.tail)
    return mixed, held._replace(state=state, tail=tail), {}


def _state_space_mixer(layer, a, held):
    mixed, memory, state, tail = _StateSpace(
        layer.model, layer.dtype, name="ssm")(
            a, held.position, held.state, held.tail)
    handed = held.handed
    if layer.layer == layer.model.memory_from:
        handed = handed._replace(memory=memory)
    return mixed, held._replace(handed=handed, state=state, tail=tail), {}


def _memory_unit_mixer(layer, a, held):
    return (_MemoryUnit(layer.model, layer.dtype, name="gmu")(
        a, held.handed.memory), held, {})


def _plain_attention_mixer(layer, a, held, whole_norms=False):
    mixed, ring_keys, ring_values, seen = _PlainAttention(
        layer.model, layer.dtype, whole_norms, name="attention")(
            a, held.index, held.episode_start, held.ring_keys,
            held.ring_values, held.ring_index, held.written)
    return (mixed,) + _attends(held, ring_keys, ring_values, seen)


def _gated_attention_mixer(layer, a, held):
    sliding = layer.model.layer_types[layer.layer] == SLIDING
    mixed, ring_keys, ring_values, seen = _Attention(
        layer.model, sliding, layer.dtype, name="attention")(
            a, held.position, held.index, held.episode_start,
            held.ring_keys, held.ring_values, held.ring_index, held.written)
    return (mixed,) + _attends(held, ring_keys, ring_values, seen)


def _latent_attention_mixer(layer, a, held):
    mixed, ring, seen = _LatentAttention(
        layer.model, layer.dtype, name="attention")(
            a, held.position, held.index, held.episode_start,
            held.ring_keys, held.ring_index, held.written)
    return (mixed,) + _attends(held, ring, held.ring_values, seen)


def _differential_attention_mixer(layer, a, held):
    model = layer.model
    mixed, ring_keys, ring_values, handed, seen = _DifferentialAttention(
        model, model.layer_types[layer.layer],
        model.layer_index[layer.layer], layer.dtype, name="attention")(
            a, held.index, held.episode_start, held.ring_keys,
            held.ring_values, held.ring_index, held.written, held.handed)
    return (mixed,) + _attends(held, ring_keys, ring_values, seen,
                               handed=handed)


class _Layer(nn.Module):
    """A mixer, by the layer's kind in the file's pattern
    (``_Family.mixers``), and (unless the model's layers are a mixer
    alone) an MLP, between residual adds, as the family places its
    norms.  The arguments after ``h`` are ``_Held``'s, and what comes back
    after ``h`` is what of them a mixer may change, then the layer's
    numbers."""

    model: TokenModelConfig
    layer: int
    dtype: Any

    @nn.compact
    def __call__(self, h, *held):
        model, dtype = self.model, self.dtype
        family = _FAMILY[model.model_type]

        def norm(name):
            return family.norm(getattr(model, family.norm_eps), name=name)

        def result(name, x):
            return norm(name)(x) if family.result_norms else x

        def entering(name, x):
            return norm(name)(x) if family.input_norms else x

        a = entering("input_norm", h)
        mixed, held, stats = family.mixers[model.layer_types[self.layer]](
            self, a, _Held(*held))
        h = h + result("post_attn_norm", mixed)
        if not family.mixer_alone:
            m = entering("pre_mlp_norm", h)
            if model.is_expert_layer(self.layer):
                f, said = _expert_layer(self, m)
                stats = dict(stats, **said)
            else:
                f = _MLP(model.intermediate_size, dtype, name="mlp")(
                    m.reshape(-1, m.shape[-1])).reshape(m.shape)
            h = h + result("post_mlp_norm", f)
        return (h, held.ring_keys, held.ring_values, held.handed,
                held.state, held.tail, stats)


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
    """The table: the caller looks tokens up in it and, where the head
    is tied, multiplies by it."""

    vocab: int
    hidden: int

    @nn.compact
    def __call__(self):
        return self.param(
            "embedding", nn.initializers.normal(1.0 / math.sqrt(self.hidden)),
            (self.vocab, self.hidden))


def tied_logits(z, table, dtype):
    """``z`` [T, B, hidden] against the rows of the embedding ``table``
    [vocab, hidden] this chip holds: its slice of the logits."""
    return jax.lax.dot_general(
        attention_lib.round_to(z, dtype), table.astype(dtype),
        (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32)


@dataclasses.dataclass(frozen=True)
class _Family:
    """What a decoder family is made of: ``_FAMILY`` holds one record a
    ``model_type`` and is the one place that knows a family by its name.
    ``TokenModelConfig.from_dict`` reads the file by the first block of
    fields, ``_Layer`` and ``TokenPolicy`` build by the second."""

    # -- the file
    required: Tuple[str, ...]           # keys the file must have
    # (key, value): what the file may not say otherwise
    only: Tuple[Tuple[str, Any], ...]
    # raw -> the fields the file does not state under their own names:
    # ``layer_types`` from wherever the family writes its layers, and
    # what it overrides beside them
    layers: Callable[[Dict[str, Any]], Dict[str, Any]]
    # kind -> the mixer a layer of that kind is (the ``_*_mixer``
    # functions); its keys are the kinds the family's layers may be
    mixers: Dict[str, Callable]
    # what a file whose layers are of another kind, or not one a layer,
    # is told
    layers_refusal: str
    # learning-dynamics telemetry's parameter groups, and the numbers the
    # forward pass leaves in the ``stats`` collection
    groups: Tuple[str, ...]
    stats: Tuple[str, ...]
    # (the source's key, the field): what another family's file calls
    # otherwise; the expert layer is one (ops/moe.py), and reads one set
    # of names
    said_as: Tuple[Tuple[str, str], ...] = ()
    # model -> the sentence that refuses it (the family's own
    # divisibility rule), or None
    check: Optional[Callable[["TokenModelConfig"], Optional[str]]] = None
    # -- the layers
    norm: Any = _RMSNorm                # every norm but a mixer's own
    norm_eps: str = "rms_norm_eps"      # the field that holds its epsilon
    result_norms: bool = False          # a branch's RESULT is normed too
    input_norms: bool = True            # a branch's INPUT is normed
    # model -> (the shape an env of a scan layer's state, the width of
    # its convolution's tail); None where no layer is a scan
    scan_state: Optional[Callable[["TokenModelConfig"],
                                  Tuple[Tuple[int, ...], int]]] = None
    mixer_alone: bool = False           # no MLP behind the mixer
    paired_heads: bool = False          # a ring head is a pair, twice as wide
    tied_head: bool = False             # the head is the embedding's table


def _verbatim_layers(raw):
    """The file's ``layer_types`` are the layers' kinds."""
    return {}


def _layer_kinds(raw):
    """``phi4flash``'s file names each layer's kind beside its index in
    the published model (the start of a differential layer's lambda
    follows it); a head's width is the hidden size's share."""
    return dict(
        layer_types=[k["kind"] for k in raw["layer_kinds"]],
        layer_index=tuple(int(k["published_index"])
                          for k in raw["layer_kinds"]),
        head_dim=raw.get("head_dim", raw["hidden_size"]
                         // raw["num_attention_heads"]))


def _latent_layers(raw):
    """Every layer attends through its own latent ring, over the whole
    episode; whole keys and values exist nowhere, so their head count
    and width size nothing."""
    return dict(layer_types=[LATENT] * raw["num_hidden_layers"],
                sliding_window=0, num_key_value_heads=0, head_dim=0)


def _pattern_layers(raw):
    """A layer a letter of ``hybrid_override_pattern``."""
    unknown = sorted(set(raw["hybrid_override_pattern"]) - set(_PATTERN))
    if unknown:
        raise ValueError(
            f"token policy: hybrid_override_pattern letters "
            f"{unknown} are not built (only "
            f"{', '.join(map(repr, _PATTERN))})")
    return dict(
        layer_types=[_PATTERN[letter]
                     for letter in raw["hybrid_override_pattern"]],
        sliding_window=0)


def _linear_layers(raw):
    """``olmo_hybrid``'s file names each layer's kind in ``layer_types``;
    a head's width is the hidden size's share, and no layer has a
    window."""
    return dict(
        head_dim=raw.get("head_dim", raw["hidden_size"]
                         // raw["num_attention_heads"]),
        sliding_window=0)


def _mamba1_state(model):
    return (model.mamba_d_state, model.d_inner), model.d_inner


def _mamba2_state(model):
    return ((model.mamba_num_heads, model.mamba_head_dim,
             model.ssm_state_size), model.conv_width)


def _delta_state(model):
    keys, values = model.linear_widths
    return ((model.linear_num_value_heads, model.linear_value_head_dim,
             model.linear_key_head_dim), 2 * keys + values)


def _heads_in_pairs(model):
    if (model.num_attention_heads % 2 or model.num_key_value_heads % 2
            or (model.num_attention_heads // 2)
            % (model.num_key_value_heads // 2)):
        return ("token policy: differential attention takes heads in "
                "pairs, the query pairs a multiple of the key pairs")
    return None


def _equal_groups(model):
    if (model.mamba_num_heads % model.n_groups
            or model.d_inner % model.n_groups
            or model.num_attention_heads % model.num_key_value_heads):
        return ("token policy: the scan's heads and channels come in "
                "n_groups equal groups, the query heads in "
                "num_key_value_heads")
    return None


def _a_key_head_a_value_head(model):
    if (model.linear_num_key_heads != model.linear_num_value_heads
            or model.num_attention_heads % model.num_key_value_heads
            or model.hidden_size % model.num_attention_heads):
        return ("token policy: a delta-rule layer has a key head a value "
                "head, and the query heads come in num_key_value_heads "
                "equal groups that divide hidden_size")
    return None


_EXPERT_STATS = (
    "moe/pairs_here_share", "moe/tokens_per_expert_mean",
    "moe/expert_load_max_over_mean", "moe/compact_share",
    "attention/key_blocks_visited_share",
    "attention/decode_key_blocks_visited_share")
# the source's keys of the DeepSeek-V3 line for what the expert layer
# (ops/moe.py) reads under the first family's names
_ROUTED_SAID_AS = (("n_routed_experts", "num_experts"),
                   ("n_shared_experts", "num_shared_experts"),
                   ("routed_scaling_factor", "route_scale"),
                   ("norm_topk_prob", "route_norm"))
_DIFFERENTIAL = {
    SLIDING: _differential_attention_mixer,
    FULL: _differential_attention_mixer,
    CROSS: _differential_attention_mixer,
    STATE_SPACE: _state_space_mixer, MEMORY_UNIT: _memory_unit_mixer}
# A family is added by adding a record here, the mechanisms it lacks (a
# mixer module and its ``_*_mixer``, an op), a preset for the family suite
# (tests/family_suite.py) and the benchmark's files.
_FAMILY: Dict[str, _Family] = {
    "afmoe": _Family(
        required=_WINDOWED + (
            "head_dim", "layer_types", "moe_intermediate_size",
            "num_experts", "num_experts_per_tok", "num_shared_experts",
            "num_dense_layers", "route_scale", "route_norm", "rope_theta",
            "rms_norm_eps", "mup_enabled", "experts_held"),
        only=(("hidden_act", "silu"), ("score_func", "sigmoid"),
              ("rope_scaling", None)),
        layers=_verbatim_layers,
        mixers={SLIDING: _gated_attention_mixer,
                FULL: _gated_attention_mixer},
        layers_refusal=(
            "token policy: layer_types must name sliding_attention "
            "or full_attention for each of num_hidden_layers"),
        groups=("embedding", "attention", "experts", "mlp", "norms",
                "heads"),
        stats=_EXPERT_STATS,
        result_norms=True),
    "phi4flash": _Family(
        required=_WINDOWED + (
            "layer_kinds", "layer_norm_eps", "mamba_d_state",
            "mamba_d_conv", "mamba_expand", "mamba_dt_rank"),
        only=(("hidden_act", "silu"), ("tie_word_embeddings", True),
              ("mlp_bias", False), ("lm_head_bias", False)),
        layers=_layer_kinds,
        mixers=_DIFFERENTIAL,
        layers_refusal=(
            f"token policy: layer_kinds must name one of "
            f"{tuple(_DIFFERENTIAL)} for each of num_hidden_layers"),
        # the tied table is the head too, and is counted as the embedding
        groups=("embedding", "attention", "ssm", "gmu", "mlp", "norms",
                "heads"),
        stats=("attention/key_blocks_visited_share",
               "attention/decode_key_blocks_visited_share"),
        check=_heads_in_pairs,
        norm=_LayerNorm, norm_eps="layer_norm_eps",
        paired_heads=True, tied_head=True, scan_state=_mamba1_state),
    "deepseek_v3": _Family(
        required=_ALWAYS + (
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "moe_intermediate_size", "n_routed_experts",
            "n_shared_experts", "num_experts_per_tok",
            "first_k_dense_replace", "routed_scaling_factor",
            "norm_topk_prob", "rope_interleave", "rope_theta",
            "rms_norm_eps", "experts_held"),
        # the group limit of the choice is a no-op at one group, which is
        # all that is built; a compressed query (q_lora_rank) is not
        only=(("hidden_act", "silu"), ("scoring_func", "sigmoid"),
              ("rope_scaling", None), ("q_lora_rank", None),
              ("tie_word_embeddings", False), ("attention_bias", False),
              ("n_group", 1), ("topk_group", 1), ("moe_layer_freq", 1)),
        layers=_latent_layers,
        mixers={LATENT: _latent_attention_mixer},
        layers_refusal=(
            f"token policy: layer_kinds must name one of {(LATENT,)} for "
            f"each of num_hidden_layers"),
        groups=("embedding", "attention", "experts", "mlp", "norms",
                "heads"),
        stats=_EXPERT_STATS,
        said_as=_ROUTED_SAID_AS + (
            ("first_k_dense_replace", "num_dense_layers"),)),
    "nemotron_h": _Family(
        required=_ALWAYS + (
            "num_key_value_heads", "head_dim", "hybrid_override_pattern",
            "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
            "n_groups", "conv_kernel", "chunk_size",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size",
            "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "mlp_hidden_act",
            "layer_norm_epsilon", "experts_held"),
        # experts of two matrices under relu2, one group to choose from,
        # no bias but the convolution's, no window
        only=(("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"),
              ("n_group", 1), ("topk_group", 1),
              ("tie_word_embeddings", False), ("attention_bias", False),
              ("mamba_proj_bias", False), ("mlp_bias", False),
              ("use_bias", False), ("use_conv_bias", True),
              ("sliding_window", None)),
        layers=_pattern_layers,
        mixers={MAMBA2: _mamba2_mixer, EXPERTS: _experts_mixer,
                FULL: _plain_attention_mixer},
        layers_refusal=(
            "token policy: hybrid_override_pattern must have a "
            "letter for each of num_hidden_layers"),
        # a shared expert's matrices count as the MLP, as in the first
        # family
        groups=("embedding", "attention", "ssd", "experts", "mlp", "norms",
                "heads"),
        stats=_EXPERT_STATS,
        said_as=_ROUTED_SAID_AS + (
            ("layer_norm_epsilon", "rms_norm_eps"),),
        mixer_alone=True, scan_state=_mamba2_state),
    "olmo_hybrid": _Family(
        required=_ALWAYS + (
            "num_key_value_heads", "layer_types", "linear_num_key_heads",
            "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim",
            "linear_allow_neg_eigval", "rms_norm_eps", "chunk_size"),
        # a gated silu MLP, no bias, an untied head, and no rotation: the
        # scans and the convolutions carry order
        only=(("hidden_act", "silu"), ("attention_bias", False),
              ("tie_word_embeddings", False),
              ("rope_parameters", {"rope_theta": None}),
              ("sliding_window", None)),
        layers=_linear_layers,
        mixers={LINEAR: _gated_delta_mixer,
                FULL: functools.partial(_plain_attention_mixer,
                                        whole_norms=True)},
        layers_refusal=(
            f"token policy: layer_types must name one of "
            f"{(LINEAR, FULL)} for each of num_hidden_layers"),
        groups=("embedding", "attention", "gdn", "mlp", "norms", "heads"),
        stats=("attention/key_blocks_visited_share",
               "attention/decode_key_blocks_visited_share"),
        said_as=(("linear_conv_kernel_dim", "conv_kernel"),),
        check=_a_key_head_a_value_head,
        # the Olmo 2/3 order: a branch's result is normed, its input not
        result_norms=True, input_norms=False, scan_state=_delta_state),
}
FAMILIES = tuple(_FAMILY)
_FIRST = _FAMILY[TokenModelConfig.model_type]


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
    # an acting step hands the update nothing but the cache itself
    # (``unroll_state``): the update's passes are the rematerialized
    # layers', over keys the decode never held together
    handover_collection = None
    # learning-dynamics telemetry (runtime/learner.py): the parameter
    # groups, no module whose dead units are read, and the collection
    # the forward pass leaves its own numbers in
    dead_unit_module = None
    stats_collection = "stats"
    # ``Learner.init``: one jitted program, not ~400 eager ones, of one
    # token an env (no parameter's shape follows the unroll's length, and
    # the whole unroll's forward pass, kernels and all, took 23 s to
    # compile for the compiler to drop it; my chip run, PR 34)
    init_in_one_program = True
    init_steps = 1

    # the parameter groups and what the forward pass says of itself
    # (``_Family.groups``, ``.stats``): the default family's here, the
    # model's own once the policy is made
    layer_groups: Tuple[str, ...] = _FIRST.groups
    STATS: Tuple[str, ...] = _FIRST.stats

    def __post_init__(self):
        family = _FAMILY[self.model.model_type]
        object.__setattr__(self, "layer_groups", family.groups)
        object.__setattr__(self, "STATS", family.stats)
        super().__post_init__()

    @staticmethod
    def layer_group(path) -> str:
        keys = [str(getattr(entry, "key", entry)) for entry in path]
        if "policy_logits" in keys or "baseline" in keys:
            return "heads"
        if "embed" in keys:
            return "embedding"
        if keys[-1].endswith("scale") or keys[-2].endswith("norm"):
            return "norms"
        for group in ("attention", "ssm", "ssd", "gdn", "gmu"):
            if group in keys:
                return group
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
        slots = self.episode_length + self.unroll_length
        if self._latent_row_bytes:
            # whole blocks of the decode's own size
            return attention_lib.latent_ring_slots(
                slots, self._latent_row_bytes)
        return slots

    @property
    def _latent_row_bytes(self) -> int:
        """Bytes of one compressed row; 0 where attention is not latent."""
        return (self.model.latent_dim
                * jnp.dtype(self.compute_dtype).itemsize)

    @property
    def latent_bytes_per_token(self) -> int:
        """Bytes a token a layer the rings hold where attention is
        latent, read off the state's own arrays (every ring, keys and
        values alike, over envs x slots x layers): whatever a later
        change keeps beside the compressed row counts.  0 where the
        rings hold whole keys and values."""
        if not self._latent_row_bytes:
            return 0
        state = jax.eval_shape(lambda: self.initial_state(1))
        held = sum(ring.size * ring.dtype.itemsize
                   for ring in state.keys + state.values)
        return held // (self.full_slots * len(self._ring_layers))

    def _slots(self, layer: int) -> int:
        return (self.window_slots
                if self.model.layer_types[layer] == SLIDING
                else self.full_slots)

    @property
    def _ring_layers(self) -> Tuple[int, ...]:
        """The layers that make keys, in order: ``TokenCache.keys[i]`` is
        the ring of the i-th of them."""
        return tuple(layer for layer, kind
                     in enumerate(self.model.layer_types)
                     if kind in _OWN_RING)

    @property
    def _scan_layers(self) -> Tuple[int, ...]:
        return tuple(layer for layer, kind
                     in enumerate(self.model.layer_types)
                     if kind in _SCANS)

    @property
    def ring_readers(self) -> int:
        """The most layers that read one ring (its own layer and the
        cross layers into it)."""
        model = self.model
        read = [model.ring_of(layer)
                for layer in range(model.num_hidden_layers)]
        return max(read.count(layer) for layer in self._ring_layers)

    def initial_state(self, batch: int) -> TokenCache:
        model = self.model
        latent = bool(model.latent_dim)
        heads, dim = model.num_key_value_heads, model.head_dim
        if _FAMILY[model.model_type].paired_heads:
            # a pair of heads is one of twice the width
            heads, dim = heads // 2, 2 * dim

        def ring(layer):
            slots = self._slots(layer)
            return jnp.zeros(
                (batch, model.latent_dim, slots) if latent
                else (batch, slots, heads, dim), self.compute_dtype)

        rings = tuple(ring(layer) for layer in self._ring_layers)

        def per_scan(*shape):
            return tuple(jnp.zeros((batch,) + shape, jnp.float32)
                         for _ in self._scan_layers)

        scan_state, tail_width = (), 0
        if self._scan_layers:
            scan_state, tail_width = _FAMILY[model.model_type].scan_state(
                model)

        return TokenCache(
            keys=rings,
            values=() if latent else tuple(
                ring(layer) for layer in self._ring_layers),
            window_index=jnp.full((self.window_slots,),
                                  attention_lib.NO_KEY, jnp.int32),
            full_index=jnp.full((self.full_slots,),
                                attention_lib.NO_KEY, jnp.int32),
            written=jnp.zeros((), jnp.int32),
            episode_start=jnp.zeros((batch,), jnp.int32),
            ssm_state=per_scan(*scan_state),
            conv_tail=per_scan(model.conv_taps - 1, tail_width))

    def unroll_state(self, start: TokenCache, end: TokenCache) -> TokenCache:
        """The state the update unrolls from, without a copy of the
        rings from the unroll's start: the rings as the rollout left
        them, under the start's counters.  ``__call__`` masks every slot
        not written before ``written``, which hides the unroll's own,
        and the rings are long enough that the unroll overwrote nothing
        its queries may see.  A recurrence cannot be masked after the
        fact: the scans' states and the convolutions' tails are the
        start's."""
        return end._replace(written=start.written,
                            episode_start=start.episode_start,
                            ssm_state=start.ssm_state,
                            conv_tail=start.conv_tail)

    def cache_bytes(self, batch: int) -> int:
        return sum(self.ring_bytes(batch, layer)
                   for layer in self._ring_layers)

    def ring_bytes(self, batch: int, layer: Optional[int] = None) -> int:
        """Bytes of ``layer``'s ring (of the largest ring, for none)."""
        if layer is None:
            return max(self.ring_bytes(batch, layer)
                       for layer in self._ring_layers)
        model = self.model
        per_slot = self._latent_row_bytes or (
            2 * model.num_key_value_heads * model.head_dim
            * jnp.dtype(self.compute_dtype).itemsize)
        return batch * per_slot * self._slots(layer)

    def ssm_state_bytes(self, batch: int) -> int:
        """The scans' states and the convolutions' tails, read off the
        state's own arrays (over the scan layers): a state kept a token
        or a second copy beside the carried one would show."""
        state = jax.eval_shape(lambda: self.initial_state(batch))
        return sum(x.size * x.dtype.itemsize
                   for x in state.ssm_state + state.conv_tail)

    def gauges(self, batch: int):
        """(name, value, help text) of what the driver sets once a run
        (``driver.build_token_policy``): the sizes of the state the
        rollout carries at ``batch`` envs."""
        return (
            ("cache/bytes", self.cache_bytes(batch),
             "bytes of the attention cache the rollout carries"),
            ("cache/window_slots", self.window_slots,
             "slots of a window layer's ring (window + unroll)"),
            ("cache/full_slots", self.full_slots,
             "slots of a full layer's ring (episode + unroll)"),
            ("cache/ring_readers", self.ring_readers,
             "the most layers that read one ring: its own layer and the "
             "cross layers into it"),
            ("cache/ring_bytes", self.ring_bytes(batch),
             "bytes of the largest one layer's ring"),
            ("cache/latent_bytes_per_token", self.latent_bytes_per_token,
             "bytes a token a layer the rings hold where attention is "
             "latent (one compressed row, every head's key and value); "
             "0 where they hold whole keys and values"),
            ("ssm/state_bytes", self.ssm_state_bytes(batch),
             "bytes of the state-space layers' recurrent states and "
             "convolution tails the rollout carries (float32)"),
            ("ssd/state_bytes_per_env",
             self.ssm_state_bytes(1)
             if MAMBA2 in self.model.layer_types else 0,
             "bytes an env of the Mamba-2 layers' matrix states and "
             "convolution tails, over the layers, read off the state's "
             "own arrays; 0 where no layer is a Mamba-2 scan"),
            ("gdn/state_bytes_per_env",
             self.ssm_state_bytes(1)
             if LINEAR in self.model.layer_types else 0,
             "bytes an env of the delta-rule layers' matrix states and "
             "convolution tails, over the layers, read off the state's "
             "own arrays; 0 where no layer is a delta-rule layer"),
            ("policy/vocab_slice", self.model.vocab_size,
             "tokens of the vocabulary this chip's head and embedding "
             "hold"))

    def acting_params(self, params):
        """The parameters as acting reads them: cast once to the compute
        dtype before the rollout's scan, not at every step of it (the
        router stays float32: its product is float32)."""
        def cast(path, leaf):
            keys = [str(getattr(entry, "key", entry)) for entry in path]
            # matrix products' operands only: a norm's scale, the one
            # bias and what a scan or its convolution reads are read in
            # float32 by both passes
            if ("router" in keys or leaf.ndim < 2
                    or keys[-1] in ("A_log", "conv_kernel")):
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
        table = _Embed(model.vocab_size, model.hidden_size, name="embed")()
        h = attention_lib.round_to(table[tokens.T], dtype).astype(jnp.float32)
        if model.mup_enabled:
            h = h * math.sqrt(model.hidden_size)

        def before(ring_index):     # hide what was not written before
            return jnp.where(ring_index < written, ring_index,
                             attention_lib.NO_KEY)

        ring_index = {SLIDING: before(state.window_index),
                      FULL: before(state.full_index)}
        ring_index[LATENT] = ring_index[FULL]
        # Learning keeps one layer's residuals at a time: the sorted
        # pairs' buffers of an expert layer are 1 GB at 8,224 tokens,
        # and the float32 activations between matmuls 67 MB apiece.
        # Attention's scores are not among them (its kernel writes none
        # and its backward recomputes a block's in VMEM), nor a scan's
        # states, so the boundary costs each one more forward kernel.
        layer_cls = nn.remat(_Layer) if count > 1 else _Layer
        keys, values, stats = list(state.keys), list(state.values), []
        ssm_state, conv_tail = list(state.ssm_state), list(state.conv_tail)
        handed = _Handed()
        for layer, kind in enumerate(model.layer_types):
            # the ring the layer attends into, and the slot of its own
            reads = model.ring_of(layer)
            ring = (self._ring_layers.index(reads)
                    if reads is not None else None)
            scan = (self._scan_layers.index(layer)
                    if kind in _SCANS else None)
            h, ring_keys, ring_values, handed, scanned, tail, layer_stats = (
                layer_cls(model, layer, dtype, name=f"layer_{layer}")(
                    h, position, index, start,
                    None if ring is None else state.keys[ring],
                    None if ring is None or kind == LATENT
                    else state.values[ring],
                    None if reads is None
                    else ring_index[model.layer_types[reads]],
                    written, handed,
                    None if scan is None else ssm_state[scan],
                    None if scan is None else conv_tail[scan]))
            if kind in _OWN_RING:
                keys[ring] = ring_keys
                if kind != LATENT:
                    values[ring] = ring_values
            if scan is not None:
                ssm_state[scan], conv_tail[scan] = scanned, tail
            stats.append(layer_stats)
        # each number's mean over the layers that say it (acting says
        # none of the attention's: the update's pass counts the blocks
        # the unroll's decode steps visited, by the decode's own rule)
        for name in self.STATS:
            said = [s[name] for s in stats if name in s]
            if said:
                self.sow(self.stats_collection, name,
                         jnp.mean(jnp.stack(said)),
                         init_fn=lambda: 0.0, reduce_fn=lambda _, new: new)

        family = _FAMILY[model.model_type]
        z = family.norm(getattr(model, family.norm_eps),
                        name="final_norm")(h)
        z = jnp.swapaxes(z, 0, 1)                         # [T, B, hidden]
        if family.tied_head:
            with jax.named_scope("policy_logits"):
                policy_logits = tied_logits(z, table, dtype)
        else:
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
            episode_start=start[:, -1],
            ssm_state=tuple(ssm_state), conv_tail=tuple(conv_tail))
        return (policy_logits, baseline), new_state
