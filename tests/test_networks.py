"""Torso equivalence: the space-to-depth stem conv is the SAME linear
map as the direct 8x8/stride-4 nn.Conv it can replace.

The s2d form (models/networks.py _SpaceToDepthFirstConv) is an MXU
layout experiment — measured SLOWER for this torso and off by default
(the stem input needs no gradient; see the module docstring) — but
whenever it is enabled, any
numerical divergence beyond contraction-order noise would silently
change the model.  Both forms share one parameter tree, so a single
init drives both and checkpoints must be interchangeable both ways.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_agent_tpu.models.networks import ShallowConvTorso


def _frames(shape, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(0, 256, shape, np.uint8))


# Shapes the framework actually runs: dmlab/fake 72x96, test fakes
# 16x16, atari 84x84, plus an odd non-multiple-of-4 size.
SHAPES = [(72, 96), (16, 16), (84, 84), (10, 13)]


class TestSpaceToDepthEquivalence:
    @pytest.mark.parametrize("hw", SHAPES)
    def test_forward_matches_direct_conv(self, hw):
        x = _frames((4,) + hw + (3,))
        s2d = ShallowConvTorso(space_to_depth=True)
        direct = ShallowConvTorso(space_to_depth=False)
        params = s2d.init(jax.random.key(0), x)
        # One param tree drives BOTH implementations (checkpoint
        # interchangeability is part of the contract).
        out_s2d = s2d.apply(params, x)
        out_direct = direct.apply(params, x)
        assert out_s2d.shape == out_direct.shape
        np.testing.assert_allclose(
            np.asarray(out_s2d), np.asarray(out_direct),
            rtol=1e-4, atol=1e-4)

    def test_param_trees_identical(self):
        x = _frames((2, 72, 96, 3))
        p_s2d = ShallowConvTorso(space_to_depth=True).init(
            jax.random.key(3), x)
        p_direct = ShallowConvTorso(space_to_depth=False).init(
            jax.random.key(3), x)
        flat_a = jax.tree_util.tree_map(lambda l: l.shape, p_s2d)
        flat_b = jax.tree_util.tree_map(lambda l: l.shape, p_direct)
        assert flat_a == flat_b
        # Same init distribution too: identical keys give identical
        # leaves.
        for a, b in zip(jax.tree_util.tree_leaves(p_s2d),
                        jax.tree_util.tree_leaves(p_direct)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_gradients_match(self):
        x = _frames((3, 72, 96, 3), seed=1)
        s2d = ShallowConvTorso(space_to_depth=True)
        direct = ShallowConvTorso(space_to_depth=False)
        params = s2d.init(jax.random.key(1), x)

        def loss(module, p):
            return jnp.sum(module.apply(p, x) ** 2)

        g_s2d = jax.grad(lambda p: loss(s2d, p))(params)
        g_direct = jax.grad(lambda p: loss(direct, p))(params)
        for a, b in zip(jax.tree_util.tree_leaves(g_s2d),
                        jax.tree_util.tree_leaves(g_direct)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


# -- where a torso rematerializes (ISSUE 27) ---------------------------------

# (torso, stem path) -> where ``remat=True`` puts the checkpoint
REMAT_CASES = {
    ("resnet", "xla"): "stem",
    ("shallow", "xla"): "torso",
    ("shallow", "pallas"): "none",
}


def _torso(case, remat):
    from scalable_agent_tpu.models.networks import TORSOS

    torso_type, conv_backend = case
    return TORSOS[torso_type](conv_backend=conv_backend, remat=remat)


def _torso_loss(torso, frames):
    return lambda params: jnp.sum(torso.apply(params, frames) ** 2)


@pytest.mark.parametrize("case", sorted(REMAT_CASES), ids="-".join)
class TestRematPlacement:
    """``remat`` changes WHEN a value is computed and where the
    boundary sits — each torso's own choice — never a value, a
    parameter path or a shape."""

    def test_outputs_and_gradients_equal_to_the_bit(self, case):
        frames = _frames((5, 24, 32, 3))
        plain, remat = _torso(case, False), _torso(case, True)
        params = plain.init(jax.random.key(0), frames)
        np.testing.assert_array_equal(plain.apply(params, frames),
                                      remat.apply(params, frames))
        g_plain = jax.grad(_torso_loss(plain, frames))(params)
        g_remat = jax.grad(_torso_loss(remat, frames))(params)
        assert (jax.tree_util.tree_structure(g_plain)
                == jax.tree_util.tree_structure(g_remat))
        jax.tree_util.tree_map(np.testing.assert_array_equal,
                               g_plain, g_remat)

    def test_parameter_tree_is_identical(self, case):
        frames = _frames((2, 24, 32, 3))
        shapes = [
            {jax.tree_util.keystr(path): (leaf.shape, leaf.dtype)
             for path, leaf in jax.tree_util.tree_leaves_with_path(
                 _torso(case, remat).init(jax.random.key(0), frames))}
            for remat in (False, True)]
        assert shapes[0] == shapes[1]
        stem = "downscale_0" if case[0] == "resnet" else "conv_0"
        assert f"['params']['{stem}']['kernel']" in shapes[1]

    def test_the_boundary_is_where_the_torso_says(self, case):
        """The conv counts of the whole update's gradient are pinned in
        tests/test_learner_fused.py; here, that a checkpoint exists
        exactly where the torso says it placed one."""
        from scalable_agent_tpu.models.networks import REMAT_PLACEMENTS

        placement = REMAT_CASES[case]
        assert placement in REMAT_PLACEMENTS
        frames = _frames((2, 24, 32, 3))
        assert _torso(case, False).remat_placement == "none"
        assert _torso(case, True).remat_placement == placement
        for remat in (False, True):
            torso = _torso(case, remat)
            params = torso.init(jax.random.key(0), frames)
            text = str(jax.make_jaxpr(
                jax.grad(_torso_loss(torso, frames)))(params))
            # jax.checkpoint's primitive prints as ``remat2``
            assert ("remat2[" in text) == (
                remat and placement != "none")
