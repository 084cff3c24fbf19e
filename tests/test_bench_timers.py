"""Regression tests for the bench's pipelined micro-timer.

`bench._timed_us_pipelined` carries three subtle correctness
properties that broke (silently, each producing plausible-looking
numbers) during round 4; each is locked in here structurally by
inspecting the lowered program rather than by comparing wall times —
timing comparisons are meaningless on a 1-core CI host and were the
original trap on the r4 rig's remote link (four microbench-methodology
bugs, each fixed in `_timed_us_pipelined`).

1. DCE-proofing: the scan carry must keep EVERY output leaf live, or
   XLA dead-code-eliminates e.g. the whole backward pass of a
   value_and_grad stage (round-4 bug: "grad" timings measured
   forward-only).
2. LICM-proofing: EVERY input leaf must be perturbed by the carry, or
   input-exclusive subcomputation (uint8 frame preprocessing) hoists
   out of the loop.
3. Value-exactness: the perturbations must not change what the stage
   computes (floats get +carry*1e-30, ints +(carry != carry), bools
   ^(carry != carry) — all runtime zero).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench


def _lowered_scan_text(fn, args, iters=3):
    """Run _timed_us_pipelined while capturing the lowered text of the
    one jitted program it builds."""
    captured = {}
    orig_jit = jax.jit

    def spy(f, *a, **k):
        j = orig_jit(f, *a, **k)

        class Wrap:
            def __call__(self, *ca, **ck):
                if "txt" not in captured:
                    captured["txt"] = j.lower(*ca, **ck).as_text()
                return j(*ca, **ck)

        return Wrap()

    jax.jit = spy
    try:
        bench._timed_us_pipelined(fn, args, iters=iters)
    finally:
        jax.jit = orig_jit
    return captured["txt"]


@pytest.mark.smoke
class TestPipelinedTimerLiveness:
    def test_backward_pass_stays_live(self):
        """value_and_grad over both operands must keep the backward
        dot_generals (1 forward + 2 backward) live in the compiled scan
        body.  Bounds + a forward-only negative control rather than an
        exact count: printer dialects change across JAX releases."""
        x = jnp.asarray(np.random.randn(32, 32).astype(np.float32))
        w = jnp.asarray(np.random.randn(32, 32).astype(np.float32))
        vg = jax.value_and_grad(
            lambda a, b: jnp.sum((a @ b) ** 2), argnums=(0, 1))
        txt = _lowered_scan_text(vg, (x, w))
        fwd_txt = _lowered_scan_text(
            lambda a, b: jnp.sum((a @ b) ** 2), (x, w))
        n_vg = len(re.findall(r"dot_general", txt))
        n_fwd = len(re.findall(r"dot_general", fwd_txt))
        assert n_fwd >= 1
        assert n_vg >= n_fwd + 2, (
            f"backward matmuls missing: {n_vg} dot_generals in "
            f"value_and_grad vs {n_fwd} forward-only")

    def test_unseeded_arg_preprocessing_stays_in_loop(self):
        """uint8 'frames' whose preprocessing depends on no float input
        must still be perturbed (anti-LICM): the integer NE-perturbation
        and the frame->float divide must both appear, and the frames
        arg must be consumed through an add (the perturb), not raw."""
        frames = jnp.asarray(
            np.random.randint(0, 255, (4, 8, 8), np.uint8))
        w = jnp.asarray(np.random.randn(64, 16).astype(np.float32))

        def stage(fr, wt):
            xx = fr.astype(jnp.float32).reshape(4, 64) / 255.0
            return jax.value_and_grad(
                lambda q: jnp.sum((xx @ q) ** 2))(wt)

        txt = _lowered_scan_text(stage, (frames, w))
        # carry != carry (runtime zero); whitespace/dialect-tolerant
        assert re.search(r"compare\s+NE", txt)
        assert re.search(r"\bui?8\b|ui8", txt) and "divide" in txt
        # the perturb add on the uint8 leaf exists inside the program
        assert any(re.search(r"\badd", line)
                   and re.search(r"ui?8", line)
                   for line in txt.splitlines())

    def test_bool_leaves_perturbed(self):
        """bool inputs get the xor-perturbation so a bool-only 'done'
        mask cannot be hoisted (round-4 review finding)."""
        done = jnp.asarray(np.random.rand(16) < 0.5)
        f = jnp.asarray(np.random.randn(16).astype(np.float32))
        txt = _lowered_scan_text(
            lambda d, x: jnp.where(d, x, -x).sum(), (done, f))
        assert any(("xor" in line and re.search(r"i1\b", line))
                   for line in txt.splitlines())

    def test_perturbation_is_value_exact(self):
        """The timed program computes the same value as a direct call
        for float, int, and bool inputs."""
        done = jnp.asarray(np.random.rand(16) < 0.5)
        idx = jnp.asarray(np.random.randint(0, 9, (16,), np.int32))
        f = jnp.asarray(np.random.randn(16, 9).astype(np.float32))

        def stage(d, i, x):
            picked = jnp.take_along_axis(x, i[:, None], axis=1)[:, 0]
            return jnp.where(d, picked, 0.0).sum()

        direct = float(stage(done, idx, f))
        got = {}
        orig_jit = jax.jit

        def spy(fn, *a, **k):
            j = orig_jit(fn, *a, **k)

            def run(*ca, **ck):
                out = j(*ca, **ck)
                got["final_carry"] = out
                return out

            return run

        jax.jit = spy
        try:
            bench._timed_us_pipelined(stage, (done, idx, f), iters=4)
        finally:
            jax.jit = orig_jit
        # every iteration's output feeds the carry; the final carry is
        # the last iteration's value — identical to the direct result.
        assert float(np.asarray(got["final_carry"])) == pytest.approx(
            direct, rel=1e-6)

    def test_timer_returns_nonnegative(self):
        x = jnp.ones((64, 64))
        us, floor_us = bench._timed_us_pipelined(
            lambda a: jnp.tanh(a).sum(), (x,), iters=5)
        assert us >= 0.0
        assert floor_us >= 0.0

    def test_integer_only_outputs_stay_live(self):
        """A stage whose compute feeds ONLY integer outputs (argmax
        actions) must still keep its matmul live — integer leaves fold
        into the carry too (round-4 ADVICE)."""
        x = jnp.asarray(np.random.randn(16, 16).astype(np.float32))
        w = jnp.asarray(np.random.randn(16, 16).astype(np.float32))
        txt = _lowered_scan_text(
            lambda a, b: jnp.argmax(a @ b, axis=-1), (x, w))
        assert re.search(r"dot_general", txt), (
            "integer-only stage was dead-code-eliminated")

    def test_record_timed_clamps_to_floor(self):
        """Sub-resolution readings are published as the floor with an
        explanatory note, never as 0.0 (round-4 VERDICT item 7)."""
        diag = {}
        orig = bench._timed_us_pipelined
        bench._timed_us_pipelined = lambda *a, **k: (0.0, 3.7)
        try:
            bench._record_timed(diag, "kernel_x_us", None, (), 5)
        finally:
            bench._timed_us_pipelined = orig
        assert diag["kernel_x_us"] == 3.7
        assert "below timer resolution" in diag["kernel_x_us_note"]
