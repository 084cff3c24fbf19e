"""``ops/attention.py latent_attention`` (a cache of one latent row a
token: every head's key and, in its first columns, its value), T = 1
through the decode kernel and T > 1 through the blockwise pair, in the
interpreter, against whole keys and values up-projected from every row
in ``jax.numpy``:

    k_nope_h, v_h = c W_kvb  (a head's columns);  the rotated key r is shared
    out_h = softmax_j((q_nope_h . k_nope_hj + q_rope_h . r_j) * scale) v_hj

The program never makes ``k_nope`` or ``v`` of a past token: it scores
``[q_nope_h W_k,h^T | q_rope_h]`` against the row and up-projects the
weighted sum of rows (``absorbed`` below, what the policy's mixer does
around the call).
"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from scalable_agent_tpu.ops import attention as A  # noqa: E402

HEADS, NOPE, ROPE, VALUE, RANK = 4, 8, 4, 8, 16      # a row is 16 + 4 wide
SCALE = 1.0 / math.sqrt(NOPE + ROPE)


def case(queries, slots=12, written=20, done_at=None, empty=False, seed=0,
         dtype=jnp.float32):
    """A ring of ``slots`` holding the tokens before ``written`` (it has
    wrapped where ``written > slots``; none where ``empty``), then
    ``queries`` own tokens; env 1's episode began 5 tokens before
    ``written``, and ``done_at`` begins another inside the call for env
    0."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    ring_index = np.full((slots,), A.NO_KEY, np.int32)
    if not empty:
        for index in range(max(0, written - slots), written):
            ring_index[index % slots] = index
    index = written + np.arange(queries, dtype=np.int32)
    start = np.zeros((2, queries), np.int32)
    start[1] = written - 5
    if done_at is not None:
        start[0, done_at:] = written + done_at
    return dict(
        q_nope=normal(2, queries, HEADS, NOPE),
        q_rope=normal(2, queries, HEADS, ROPE),
        latent=normal(2, queries, RANK + ROPE).astype(dtype),
        ring=normal(2, RANK + ROPE, slots).astype(dtype),   # a token a column
        w_k=normal(RANK, HEADS, NOPE) / 4, w_v=normal(RANK, HEADS, VALUE) / 4,
        ring_index=jnp.asarray(ring_index), index=jnp.asarray(index),
        episode_start=jnp.asarray(start))


def up_projected(c):
    """Whole keys and values of every row, one masked softmax."""
    rows = jnp.concatenate([jnp.swapaxes(c["ring"], 1, 2), c["latent"]],
                           axis=1).astype(jnp.float32)
    key_index = jnp.concatenate([c["ring_index"], c["index"]])
    seen = ((key_index[None, None, :] <= c["index"][None, :, None])
            & (key_index[None, None, :] >= c["episode_start"][:, :, None]))
    k_nope = jnp.einsum("bsr,rhd->bshd", rows[..., :RANK], c["w_k"],
                        precision="highest")
    value = jnp.einsum("bsr,rhd->bshd", rows[..., :RANK], c["w_v"],
                       precision="highest")
    scores = (jnp.einsum("bthd,bshd->bhts", c["q_nope"], k_nope,
                         precision="highest")
              + jnp.einsum("bthd,bsd->bhts", c["q_rope"], rows[..., RANK:],
                           precision="highest")) * SCALE
    weights = jax.nn.softmax(jnp.where(seen[:, None], scores, -jnp.inf), -1)
    return jnp.einsum("bhts,bshd->bthd", weights, value, precision="highest")


def absorbed(c):
    query = jnp.concatenate(
        [jnp.einsum("bthd,rhd->bthr", c["q_nope"], c["w_k"],
                    precision="highest"), c["q_rope"]], axis=-1)
    out, stats = A.latent_attention(
        query.astype(c["ring"].dtype), c["latent"], c["ring"],
        c["ring_index"], c["index"], c["episode_start"], RANK, SCALE)
    return jnp.einsum("bthr,rhd->bthd", out, c["w_v"],
                      precision="highest"), stats


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


CASES = {
    "a ring partly full": dict(slots=12, written=7),
    "a ring that has wrapped": dict(slots=12, written=20),
    "an empty ring": dict(slots=12, written=20, empty=True),
    "an episode begins mid-call": dict(slots=12, written=20, done_at=3),
    "three blocks of lane tiles": dict(slots=384, written=500, done_at=2),
    "a block no query sees": dict(slots=384, written=130),
}


@pytest.mark.parametrize("queries", [1, 7])
@pytest.mark.parametrize("name", sorted(CASES))
def test_absorbed_is_up_projected(name, queries):
    """One query an env is the decode kernel, seven the blockwise pair;
    a row 20 wide is no whole number of lanes."""
    spec = dict(CASES[name])
    if queries == 1:
        spec.pop("done_at", None)
    c = case(queries, **spec)
    got, stats = absorbed(c)
    assert rel(got, up_projected(c)) < 1e-5
    assert set(stats) == (set() if queries == 1 else {
        "key_blocks_visited_share", "decode_key_blocks_visited_share"})


@pytest.fixture
def small_decode_blocks():
    """Decode blocks of one lane tile, so that a ring of three is three
    grid steps an env."""
    real = A._DECODE_BLOCK_BYTES
    A._DECODE_BLOCK_BYTES = 128 * (RANK + ROPE) * 4
    jax.clear_caches()
    yield
    A._DECODE_BLOCK_BYTES = real
    jax.clear_caches()


@pytest.mark.parametrize("written", [130, 300, 500])
def test_the_decode_walks_the_live_blocks_of_a_latent_ring(
        small_decode_blocks, written):
    c = case(1, slots=384, written=written, seed=written)
    got, _ = absorbed(c)
    assert rel(got, up_projected(c)) < 1e-5


@pytest.mark.parametrize("operand", ["latent", "q_nope", "q_rope", "w_k",
                                     "w_v"])
@pytest.mark.parametrize("name", ["a ring that has wrapped",
                                  "an episode begins mid-call",
                                  "three blocks of lane tiles"])
def test_the_gradient_is_the_up_projected_ones(name, operand):
    """The own rows collect the cotangent of both their uses (key and
    value) in one array; W_kvb's two halves get theirs through the
    absorption and the up-projection of the weighted sum."""
    c = case(7, seed=1, **CASES[name])
    weigh = jnp.cos(jnp.arange(VALUE, dtype=jnp.float32))

    def total(fn):
        return lambda x: jnp.sum(fn(dict(c, **{operand: x})) * weigh)

    got = jax.grad(total(lambda c: absorbed(c)[0]))(c[operand])
    want = jax.grad(total(up_projected))(c[operand])
    assert float(jnp.max(jnp.abs(want))) > 0.0
    assert rel(got, want) < 1e-5


def test_the_ring_gets_no_cotangent():
    c = case(7, seed=2)
    got = jax.grad(lambda ring: jnp.sum(absorbed(dict(c, ring=ring))[0]))(
        c["ring"])
    assert float(jnp.max(jnp.abs(got))) == 0.0


@pytest.mark.parametrize("heads,queries,want", [
    (32, 257, 8), (32, 7, 32), (4, 7, 4), (32, 2305, 1)])
def test_the_head_tile_keeps_a_steps_lanes_in_bounds(heads, queries, want):
    assert A._head_tile(heads, queries) == want


def test_a_latent_rings_slots_are_whole_decode_blocks():
    """1,152 bytes a row: 7 lane tiles in a MiB, 10,496 needed -> 12
    blocks of 896, which blocks of 512 divide too."""
    slots = A.latent_ring_slots(10240 + 256, 1152)
    assert slots == 10752 and slots % 896 == 0
    assert A._decode_block(slots, 1152) == 896
    assert A._key_block(slots) == 512
    # a ring one step brings whole stays as it is
    assert A.latent_ring_slots(22, 80) == 22


def test_bfloat16_rows_stay_within_their_rounding():
    c32 = case(7, slots=384, written=500, seed=3)
    c16 = dict(c32, latent=c32["latent"].astype(jnp.bfloat16),
               ring=c32["ring"].astype(jnp.bfloat16))
    want = up_projected(c32)
    for queries in (7, 1):
        a = {k: (v[:, :queries] if k in ("q_nope", "q_rope", "latent")
                 else v) for k, v in c16.items()}
        a["index"] = c16["index"][:queries]
        a["episode_start"] = c16["episode_start"][:, :queries]
        b = {k: (v[:, :queries] if k in ("q_nope", "q_rope", "latent")
                 else v) for k, v in c32.items()}
        b["index"], b["episode_start"] = a["index"], a["episode_start"]
        got, _ = absorbed(a)
        assert rel(got, up_projected(b)) < 0.03
    del want


# -- the ring's write: one token moves the slot's lane tile -------------------

SLOTS, ROWS = 256, 32           # two lane tiles; whole sublane tiles of both


def slice_update(ring, rows, written):
    """What ``latent_ring_write`` was before the kernel: XLA's slice
    update of one column, a scatter of more."""
    slots, count = ring.shape[2], rows.shape[1]
    columns = jnp.swapaxes(A.round_to(rows, ring.dtype), 1, 2)
    if count == 1:
        return jax.lax.dynamic_update_slice_in_dim(
            ring, columns, written % slots, axis=2)
    at = (written + jnp.arange(count, dtype=jnp.int32)) % slots
    return ring.at[:, :, at].set(columns)



WRITES = {
    **{f"{dtype} written {written}": dict(dtype=dtype, written=written)
       for dtype in ("bfloat16", "float32")
       for written in (0, 127, 128, SLOTS - 1, SLOTS, SLOTS + 129)},
    "two writes in a row": dict(written=127, writes=2),
    "under scan, the ring in the carry": dict(written=250, writes=9,
                                              scan=True),
    "four grid steps of two envs": dict(written=131, envs=8, block_envs=2),
    "envs no block divides go one a step": dict(written=5, envs=3,
                                                block_envs=2),
    "more tokens than one keep the scatter": dict(
        written=250, tokens=7, kernel=None),
    "slots that are no whole lane tiles keep the slice update": dict(
        written=13, slots=12, kernel=False),
    "rows that are no whole sublane tiles keep the slice update": dict(
        written=129, rows=RANK + ROPE, kernel=False),
    "bfloat16 rows of 8 are half a sublane tile": dict(
        written=129, rows=8, kernel=False),
}


@pytest.mark.parametrize("name", sorted(WRITES))
def test_the_slot_write_is_the_slice_update_bit_for_bit(name, monkeypatch):
    """``latent_ring_write``: one token into a ring of whole tiles goes
    through ``_latent_slot_write`` (the lane tile that holds the slot
    brought, one lane replaced, written back in place) and gives the
    slice update's bytes, wrapped slots and all; any other shape keeps
    the slice update or the scatter.  ``kernel``: what the trace-time
    gauge has to say of the case (None: it counts one-token writes
    only)."""
    from scalable_agent_tpu.obs import registry

    monkeypatch.setattr(registry, "_registry", registry.MetricsRegistry())
    monkeypatch.setattr(A, "_slot_writes", [0, 0])
    spec = dict(dtype="bfloat16", writes=1, scan=False, envs=2, tokens=1,
                slots=SLOTS, rows=ROWS, kernel=True, block_envs=None)
    spec.update(WRITES[name])
    dtype = jnp.dtype(spec["dtype"])
    if spec["block_envs"]:      # read where the call is traced: a shape
        monkeypatch.setattr(    # of the case's own, so nothing cached
            A, "_SLOT_BLOCK_BYTES",
            spec["block_envs"] * spec["rows"] * 128 * dtype.itemsize)
    rng = np.random.default_rng(len(name))
    ring = jnp.asarray(rng.normal(size=(spec["envs"], spec["rows"],
                                        spec["slots"])), dtype)
    rows = jnp.asarray(rng.normal(size=(
        spec["writes"], spec["envs"], spec["tokens"], spec["rows"])),
        jnp.float32)
    written = jnp.int32(spec["written"])

    def both(write):
        if spec["scan"]:
            def step(carry, new):
                ring, at = carry
                return (write(ring, new, at), at + 1), ()
            return jax.jit(lambda: jax.lax.scan(
                step, (ring, written), rows)[0][0])()
        out = ring
        for i in range(spec["writes"]):
            out = write(out, rows[i], written + i)
        return out

    got, want = both(A.latent_ring_write), both(slice_update)
    assert got.dtype == want.dtype == dtype and got.shape == ring.shape
    width = np.uint16 if dtype.itemsize == 2 else np.uint32
    np.testing.assert_array_equal(np.asarray(got).view(width),
                                  np.asarray(want).view(width))
    assert not np.array_equal(np.asarray(got).view(width),
                              np.asarray(ring).view(width))
    share = registry.get_registry().snapshot().get(
        "attention/latent_slot_kernel_share")
    assert share == (None if spec["kernel"] is None
                     else float(spec["kernel"]))


def test_the_gauge_is_the_share_of_the_one_token_writes_traced(monkeypatch):
    from scalable_agent_tpu.obs import registry

    monkeypatch.setattr(registry, "_registry", registry.MetricsRegistry())
    monkeypatch.setattr(A, "_slot_writes", [0, 0])
    whole = jnp.zeros((2, ROWS, SLOTS), jnp.bfloat16)
    ragged = jnp.zeros((2, ROWS, 12), jnp.bfloat16)
    row = jnp.ones((2, 1, ROWS), jnp.float32)
    for ring in (whole, whole, ragged, whole):
        A.latent_ring_write(ring, row, jnp.int32(3))
    A.latent_ring_write(whole, jnp.ones((2, 5, ROWS)), jnp.int32(3))
    assert registry.get_registry().snapshot()[
        "attention/latent_slot_kernel_share"] == 0.75
