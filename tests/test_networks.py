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
