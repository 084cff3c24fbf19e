"""Pallas grad-W stem kernel (ops/conv_pallas.py): parity against
XLA's own derivative across geometry edges, the K % S refusal, the
bf16 MXU-operand mode, the ragged last batch tile, and checkpoint
interchangeability of the agent-facing PallasStemConv module.

All CPU runs go through the Pallas interpreter (the same kernel body
TPU compiles), so tier-1 exercises the real code path — the
ops/lstm_pallas.py testing contract.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_agent_tpu.ops import conv_pallas

from scalable_agent_tpu.parallel.mesh import pallas_interpret

_INTERPRET = pallas_interpret()


def _conv(x, w, s):
    return jax.lax.conv_general_dilated(
        x, w, (s, s), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _reference_gradw(x, cot, k, s):
    """XLA's own d/dW of the SAME conv under cotangent ``cot`` — the
    derivative the Pallas kernel must reproduce."""
    w0 = jnp.zeros((k, k, x.shape[-1], cot.shape[-1]), jnp.float32)
    return jax.grad(lambda w: jnp.sum(_conv(x, w, s) * cot))(w0)


def _all_eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (pjit, custom_vjp, the
    pallas_call's kernel) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _all_eqns(inner)


def _random_case(seed, n, h, w, c, f, s):
    kx, kg = jax.random.split(jax.random.key(seed))
    out_h, out_w = -(-h // s), -(-w // s)
    x = jax.random.normal(kx, (n, h, w, c), jnp.float32)
    g = jax.random.normal(kg, (n, out_h, out_w, f), jnp.float32)
    return x, g


# (h, w, k, s): the stem aspect at reduced size, odd spatial extents
# (asymmetric SAME padding on both axes), a smaller stem, stride ==
# kernel (depth-1 tiles, no overlap), and the 1x1 degenerate case.
GEOMETRIES = (
    (24, 32, 8, 4),
    (17, 23, 8, 4),
    (9, 11, 4, 2),
    (8, 8, 2, 2),
    (5, 5, 1, 1),
)


class TestGradWParity:
    @pytest.mark.parametrize("h,w,k,s", GEOMETRIES)
    def test_f32_matches_xla_derivative(self, h, w, k, s):
        x, g = _random_case(k * 100 + s, 3, h, w, 3, 8, s)
        dw = conv_pallas.conv_gradw(x, g, k, s, interpret=_INTERPRET)
        ref = _reference_gradw(x, g, k, s)
        assert dw.dtype == jnp.float32
        np.testing.assert_allclose(dw, ref, rtol=2e-5, atol=2e-5)

    def test_bf16_operands_f32_accumulation(self):
        """bf16 MXU operands with the f32 scratch accumulator: the
        documented tolerance is bf16's ~8-bit mantissa on the operands,
        NOT a bf16 accumulation error (which would grow with N*OH*OW
        and blow far past 3e-2 at this size)."""
        x, g = _random_case(7, 4, 24, 32, 3, 8, 4)
        dw = conv_pallas.conv_gradw(x, g, 8, 4, interpret=_INTERPRET,
                                    matmul_dtype="bfloat16")
        ref = _reference_gradw(x, g, 8, 4)
        assert dw.dtype == jnp.float32
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(dw, ref, rtol=3e-2,
                                   atol=3e-2 * scale)

    def test_k_not_multiple_of_stride_is_refused_not_rerouted(self):
        """K % S != 0 breaks the space-to-depth tap lattice.  The
        kernel does not take it — and says so: the support predicate
        answers 0, and conv_gradw raises instead of quietly handing
        XLA the derivative (which geometry runs where is the kernel
        policy's decision, made once, in the open)."""
        x, g = _random_case(11, 3, 10, 13, 3, 8, 2)
        assert conv_pallas.gradw_batch_tile(
            x.shape, 8, 3, 2, x.dtype) == 0
        with pytest.raises(ValueError, match="does not take"):
            conv_pallas.conv_gradw(x, g, 3, 2, interpret=_INTERPRET)

    @pytest.mark.parametrize("matmul_dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("n,tile_cap,tile", [
        (13, 2, 2),        # a ragged last step of one image
        (16, None, 16),    # the whole batch in one step
        (6 * 101, None, 128),   # the real cap: 5 steps, 34 lanes masked
        (521, None, 128),  # a prime above the cap: no tile divides it
    ])
    def test_no_batch_tile_pads_in_hbm(self, monkeypatch, n, tile_cap,
                                       tile, matmul_dtype):
        """N not divisible by the batch tile: the kernel masks the
        lanes past N in its last grid step — it does NOT ``jnp.pad`` x
        and g up to a whole number of tiles (two whole-array copies,
        19.6 ms of a 158 ms step on the v5e, ledger PR 24).  The answer
        must equal the untiled one, and the traced program must hold no
        pad that grows a 4-D operand's batch dim."""
        if tile_cap:
            monkeypatch.setattr(conv_pallas, "_MAX_BATCH_TILE", tile_cap)
        x, g = _random_case(13, n, 16, 16, 3, 8, 4)
        assert conv_pallas.gradw_batch_tile(
            x.shape, 8, 8, 4, x.dtype) == tile

        def gradw(x, g):
            return conv_pallas.conv_gradw(
                x, g, 8, 4, interpret=_INTERPRET,
                matmul_dtype=matmul_dtype)

        ref = _reference_gradw(x, g, 8, 4)
        tol = 2e-5 if matmul_dtype == "float32" else 3e-2
        np.testing.assert_allclose(
            gradw(x, g), ref, rtol=tol,
            atol=tol * float(jnp.max(jnp.abs(ref))))
        for eqn in _all_eqns(jax.make_jaxpr(gradw)(x, g).jaxpr):
            if eqn.primitive.name != "pad":
                continue
            operand, out = eqn.invars[0].aval, eqn.outvars[0].aval
            assert not (operand.ndim == 4
                        and out.shape[0] != operand.shape[0]), eqn

    @pytest.mark.parametrize("n,divides", [(256 * 101, True),
                                           (64 * 101, False)])
    def test_batch_tile_at_the_cells_shapes(self, n, divides):
        """The fused cell's 25,856 images and the host loop's 6,464 at
        72x96x3 in bf16.  The images are the operands' lane dim, so a
        tile is a multiple of 128: one divides 25,856 (no ragged step,
        nothing masked); none divides 6,464 = 2^6 x 101, and the tile
        is the one that masks the fewest images."""
        tile = conv_pallas.gradw_batch_tile(
            (n, 72, 96, 3), 32, 8, 4, "bfloat16")
        assert tile and tile % 128 == 0
        masked = conv_pallas.gradw_padded_images(n, tile)
        assert (n % tile == 0) == divides
        assert masked == (0 if divides else 64)

    @pytest.mark.parametrize("features,k,s,why", [
        (16, 3, 1, "the ResNet stem: 128 images' blocks pass VMEM"),
        (32, 6, 4, "K % S != 0"),
    ])
    def test_unsupported_stems_still_give_no_tile(self, features, k, s,
                                                  why):
        assert conv_pallas.gradw_batch_tile(
            (256 * 101, 72, 96, 3), features, k, s, "bfloat16") == 0, why
        assert conv_pallas.gradw_padded_images(256 * 101, 0) == 0


class TestStemConvVjp:
    def test_forward_is_xla_conv(self):
        x, _ = _random_case(17, 2, 17, 23, 3, 8, 4)
        w = jax.random.normal(jax.random.key(3), (8, 8, 3, 8),
                              jnp.float32) * 0.05
        out = conv_pallas.stem_conv(x, w, 4, _INTERPRET, "float32")
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(_conv(x, w, 4)))

    def test_value_and_grad_under_jit(self):
        """The full custom_vjp in a jitted value_and_grad over BOTH
        inputs: dx is XLA's transposed conv (exact), dw the Pallas
        kernel (tight f32 tolerance)."""
        x, _ = _random_case(19, 2, 16, 16, 3, 8, 4)
        w = jax.random.normal(jax.random.key(5), (8, 8, 3, 8),
                              jnp.float32) * 0.05

        def loss(op):
            return lambda xx, ww: jnp.sum(op(xx, ww) ** 2)

        pallas_loss = jax.jit(jax.value_and_grad(
            loss(lambda xx, ww: conv_pallas.stem_conv(
                xx, ww, 4, _INTERPRET, "float32")), argnums=(0, 1)))
        xla_loss = jax.jit(jax.value_and_grad(
            loss(lambda xx, ww: _conv(xx, ww, 4)), argnums=(0, 1)))
        val_p, (dx_p, dw_p) = pallas_loss(x, w)
        val_x, (dx_x, dw_x) = xla_loss(x, w)
        np.testing.assert_allclose(val_p, val_x, rtol=1e-6)
        np.testing.assert_allclose(dx_p, dx_x, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(dw_p, dw_x, rtol=2e-5, atol=2e-5)


class TestRawFrameEntry:
    """``stem_conv(frame, w, ..., normalize)``: the torso hands the op
    the uint8 frame and its own normalisation."""

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_every_byte_reaches_the_kernel_as_the_forward_sees_it(
            self, dtype):
        """All 256 byte values, several times over.  The kernel's
        operand is normalised AFTER the pad (so that XLA fuses the two)
        and the forward conv's before it; both must hold
        ``_normalize_frame``'s value of every pixel bit for bit — so
        the output and the weight gradient equal the float entry's on
        the frame normalised beforehand, exactly, in either dtype."""
        import functools

        from scalable_agent_tpu.models.networks import _normalize_frame

        normalize = functools.partial(_normalize_frame, dtype=dtype)
        frame = jnp.arange(3 * 16 * 16 * 3, dtype=jnp.int32).reshape(
            3, 16, 16, 3).astype(jnp.uint8)
        assert set(np.unique(frame)) == set(range(256))
        w = (jax.random.normal(jax.random.key(2), (8, 8, 3, 8),
                               jnp.float32) * 0.05).astype(dtype)
        cot = jax.random.normal(jax.random.key(4), (3, 4, 4, 8),
                                jnp.float32).astype(dtype)

        def through(x, norm):
            out, vjp = jax.vjp(
                lambda ww: conv_pallas.stem_conv(
                    x, ww, 4, _INTERPRET, dtype, norm), w)
            return out, vjp(cot)[0]

        raw_out, raw_dw = through(frame, normalize)
        out, dw = through(normalize(frame), None)
        np.testing.assert_array_equal(
            np.asarray(raw_out, np.float32), np.asarray(out, np.float32))
        np.testing.assert_array_equal(
            np.asarray(raw_dw, np.float32), np.asarray(dw, np.float32))

    def test_a_uint8_frame_takes_no_gradient_a_float_one_does(self):
        import functools

        from scalable_agent_tpu.models.networks import _normalize_frame

        normalize = functools.partial(_normalize_frame,
                                      dtype=jnp.float32)
        x, _ = _random_case(31, 2, 16, 16, 3, 8, 4)
        w = jax.random.normal(jax.random.key(6), (8, 8, 3, 8),
                              jnp.float32) * 0.05

        def loss(op):
            return lambda xx, ww: jnp.sum(op(xx, ww) ** 2)

        dx_p, dw_p = jax.grad(loss(lambda xx, ww: conv_pallas.stem_conv(
            xx, ww, 4, _INTERPRET, "float32", normalize)),
            argnums=(0, 1))(x, w)
        dx_x, dw_x = jax.grad(loss(
            lambda xx, ww: _conv(normalize(xx), ww, 4)),
            argnums=(0, 1))(x, w)
        np.testing.assert_allclose(dx_p, dx_x, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(dw_p, dw_x, rtol=2e-5, atol=2e-7)
        frame = jnp.zeros((2, 16, 16, 3), jnp.uint8)
        dw = jax.grad(loss(lambda ww, xx: conv_pallas.stem_conv(
            xx, ww, 4, _INTERPRET, "float32", normalize)))(w, frame)
        np.testing.assert_array_equal(dw, jnp.zeros_like(dw))


class TestPallasStemConvModule:
    def _frame(self, seed=23):
        return jax.random.randint(
            jax.random.key(seed), (2, 24, 32, 3), 0, 255, jnp.int32
        ).astype(jnp.uint8)

    def test_checkpoint_interchangeable_with_nn_conv(self):
        """Same param tree (kernel [K,K,C,F] + bias under the module
        name) and the same function of those params — a torso
        checkpoint written by either backend restores into the other
        (the _SpaceToDepthFirstConv contract)."""
        from scalable_agent_tpu.models import networks

        xla = networks.ShallowConvTorso(conv_backend="xla")
        pallas = networks.ShallowConvTorso(conv_backend="pallas")
        frame = self._frame()
        params = xla.init(jax.random.key(0), frame)
        params_p = pallas.init(jax.random.key(0), frame)
        assert (jax.tree_util.tree_structure(params)
                == jax.tree_util.tree_structure(params_p))
        assert (jax.tree_util.tree_map(jnp.shape, params)
                == jax.tree_util.tree_map(jnp.shape, params_p))
        out_x = xla.apply(params, frame)
        out_p = pallas.apply(params, frame)  # the XLA checkpoint
        np.testing.assert_allclose(out_x, out_p, rtol=1e-6, atol=1e-6)

    def test_torso_grads_match_xla_backend(self):
        """End-to-end through the torso: the two backends are the same
        mathematical function, so loss gradients agree to f32 kernel
        tolerance."""
        from scalable_agent_tpu.models import networks

        frame = self._frame(29)
        xla = networks.ShallowConvTorso(conv_backend="xla")
        pallas = networks.ShallowConvTorso(conv_backend="pallas")
        params = xla.init(jax.random.key(1), frame)

        def grads(torso):
            return jax.grad(
                lambda p: jnp.sum(torso.apply(p, frame) ** 2))(params)

        gx, gp = grads(xla), grads(pallas)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                a, b, rtol=5e-5, atol=5e-5), gx, gp)

    def test_unknown_backend_rejected(self):
        from scalable_agent_tpu.models import networks

        with pytest.raises(ValueError, match="conv_backend"):
            networks.ShallowConvTorso(conv_backend="tensorrt").init(
                jax.random.key(0), self._frame())


class TestStemHandOver:
    """ISSUE 37: the stem's activation, computed once by a call under
    the same parameters, stands in for the stem conv of a later call's
    forward — and every gradient is the one the conv's own call has."""

    @pytest.mark.parametrize("x_dtype", (jnp.uint8, jnp.float32))
    def test_op_has_the_gradients_of_the_conv_it_stands_for(self, x_dtype):
        kx, kw, kb, kc = jax.random.split(jax.random.key(41), 4)
        x = jax.random.randint(kx, (3, 16, 24, 3), 0, 255).astype(x_dtype)
        w = jax.random.normal(kw, (8, 8, 3, 32), jnp.float32) * 0.1
        b = jax.random.normal(kb, (32,), jnp.float32) * 0.1
        cot = jax.random.normal(kc, (3, 4, 6, 32), jnp.float32)
        normalize = lambda frame: jnp.asarray(frame, jnp.float32) / 255.0
        args = (4, _INTERPRET, "float32", normalize)

        def computed(x, w, b):
            return jax.nn.relu(conv_pallas.stem_conv(x, w, *args) + b)

        activation = computed(x, w, b)
        assert float((activation > 0).mean()) not in (0.0, 1.0)

        def handed(x, w, b, activation):
            return conv_pallas.stem_conv_handed(x, w, b, activation, *args)

        np.testing.assert_array_equal(handed(x, w, b, activation),
                                      activation)
        wrt = (0, 1, 2) if x_dtype == jnp.float32 else (1, 2)
        want = jax.grad(lambda *a: jnp.sum(computed(*a) * cot),
                        argnums=wrt)(x, w, b)
        got = jax.grad(lambda *a: jnp.sum(handed(*a) * cot),
                       argnums=wrt + (3,))(x, w, b, activation)
        for have, need in zip(got, want):
            np.testing.assert_allclose(have, need, rtol=1e-6, atol=1e-6)
        # the handed tensor gets no cotangent: whoever computed it
        # differentiates nothing through it
        np.testing.assert_array_equal(got[-1], jnp.zeros_like(activation))

    @pytest.mark.parametrize("dtype,tolerance", [
        (jnp.float32, 1e-6), (jnp.bfloat16, 1e-6)],
        ids=("float32", "bfloat16"))
    def test_torso_given_its_stem_activation_is_the_torso_that_computes_it(
            self, dtype, tolerance):
        from scalable_agent_tpu.models import networks

        torso = networks.ShallowConvTorso(conv_backend="pallas",
                                          dtype=dtype)
        frame = jax.random.randint(
            jax.random.key(43), (5, 24, 32, 3), 0, 255).astype(jnp.uint8)
        params = torso.init(jax.random.key(2), frame)
        # nothing of the hand-over is a variable of the model
        assert set(params) == {"params"}
        # a bias off zero, or its gradient's path would go untested
        params = jax.tree_util.tree_map(
            lambda p: p + 0.05 * jnp.cos(jnp.arange(p.size, dtype=p.dtype)
                                          ).reshape(p.shape), params)
        out, sown = torso.apply(params, frame,
                                mutable=[networks.HANDOVER])
        stem = sown[networks.HANDOVER]["stem"]
        assert stem.shape == (5, 6, 8, 32) and stem.dtype == dtype
        # an immutable collection: the sow is a no-op
        np.testing.assert_array_equal(torso.apply(params, frame), out)
        np.testing.assert_array_equal(torso.apply(params, frame, stem),
                                      out)
        weights = jax.random.normal(jax.random.key(3), out.shape)

        def grads(*stem):
            return jax.grad(lambda p: jnp.sum(
                jnp.asarray(torso.apply(p, frame, *stem), jnp.float32)
                * weights))(params)

        want, got = grads(), grads(stem)
        assert (jax.tree_util.tree_structure(want)
                == jax.tree_util.tree_structure(got))
        for path, need in jax.tree_util.tree_leaves_with_path(want):
            have = got
            for entry in path:
                have = have[entry.key]
            assert float(jnp.abs(need).max()) > 0, path
            np.testing.assert_allclose(
                have, need, rtol=tolerance,
                atol=tolerance * float(jnp.abs(need).max()),
                err_msg=jax.tree_util.keystr(path))

    def test_only_the_checkpoint_free_pallas_torso_hands_anything(self):
        from scalable_agent_tpu.models import networks

        frame = jnp.zeros((2, 16, 16, 3), jnp.uint8)
        hands = {
            (name, backend, remat): networks.TORSOS[name](
                conv_backend=backend, remat=remat).hands_stem
            for name in networks.TORSOS
            for backend in networks.CONV_BACKENDS
            for remat in (False, True)}
        assert {key for key, value in hands.items() if value} == {
            ("shallow", "pallas", False), ("shallow", "pallas", True)}
        xla = networks.ShallowConvTorso(conv_backend="xla")
        params = xla.init(jax.random.key(0), frame)
        _, sown = xla.apply(params, frame, mutable=[networks.HANDOVER])
        assert not sown
        with pytest.raises(ValueError, match="takes no handed"):
            xla.apply(params, frame, jnp.zeros((2, 4, 4, 32)))
