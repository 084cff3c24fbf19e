"""The Mamba-2 scan (ops/ssd.py: a matrix state a head, one decay a head
a token) under the Pallas interpreter against the token-by-token
recurrence (``ssd_step`` in a ``lax.scan``), float32: the forward and
the gradient of every operand, at chunk sizes that do and do not divide
the unroll, episode ends scattered through it, and one token as a step
with no kernel.  The interpreter proves the arithmetic and the custom
VJP's plumbing; tests/test_chip_bringup.py compiles both kernels for a
v5e at the cell's widths; tests/test_nemotron_policy.py holds the
family's whole policy, these kernels in it, to its reference.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_agent_tpu.ops import ssd


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def recurrence(x, delta, a, d, b, c, reset, state):
    """``ssd_scan`` a token at a time: the step in a ``lax.scan``."""
    def step(s, inputs):
        xt, dt, bt, ct, rt = inputs
        y, s = ssd.ssd_step(xt, dt, a, d, bt, ct, rt, s)
        return s, y

    def time_major(v):
        return jnp.swapaxes(v, 0, 1)

    state, y = jax.lax.scan(
        step, state, tuple(map(time_major, (x, delta, b, c, reset))))
    return time_major(y), state


def scan_operands(steps, seed=0):
    batch, heads, dim, groups, states = 2, 4, 8, 2, 16
    keys = jax.random.split(jax.random.key(seed), 8)
    return dict(
        x=jax.random.normal(keys[0], (batch, steps, heads, dim)),
        delta=jax.nn.softplus(
            jax.random.normal(keys[1], (batch, steps, heads)) - 1.0),
        a=-jnp.exp(2.0 * jax.random.uniform(keys[2], (heads,))),
        d=jax.random.normal(keys[3], (heads,)),
        b=jax.random.normal(keys[4], (batch, steps, groups, states)),
        c=jax.random.normal(keys[5], (batch, steps, groups, states)),
        state=jax.random.normal(keys[6], (batch, heads, dim, states)),
        reset=jax.random.uniform(keys[7], (batch, steps)) < 0.15)


_DIFFERENTIABLE = ("x", "delta", "a", "d", "b", "c", "state")


@functools.lru_cache(maxsize=None)
def scanned(steps, chunk):
    """(outputs, gradients) of the kernels and of the recurrence."""
    ops = scan_operands(steps)

    def run(fn):
        def loss(*values):
            y, last = fn(*values[:6], ops["reset"], values[6])
            return jnp.sum(y * jnp.cos(y)) + jnp.sum(last * last)

        values = [ops[name] for name in _DIFFERENTIABLE]
        return jax.jit(lambda *values: (
            fn(*values[:6], ops["reset"], values[6]),
            jax.grad(loss, argnums=tuple(range(7)))(*values)))(*values)

    return run(lambda *v: ssd.ssd_scan(*v, chunk=chunk)), run(recurrence)


# 17 tokens: chunks of 8 leave one over; 16: whole chunks of 8; 9 in one
# chunk of 16
_SHAPES = [(17, 8), (16, 8), (9, 16)]


@pytest.mark.parametrize("steps,chunk", _SHAPES)
@pytest.mark.parametrize("what", ["y", "state"])
def test_the_scans_kernels_are_the_recurrence(steps, chunk, what):
    (got, _), (want, _) = scanned(steps, chunk)
    at = ("y", "state").index(what)
    assert rel(got[at], want[at]) < 1e-5


@pytest.mark.parametrize("steps,chunk", _SHAPES)
@pytest.mark.parametrize("operand", _DIFFERENTIABLE)
def test_the_scans_backward_kernel_is_the_recurrences(steps, chunk, operand):
    (_, got), (_, want) = scanned(steps, chunk)
    at = _DIFFERENTIABLE.index(operand)
    assert rel(got[at], want[at]) < 2e-5


def test_one_token_is_a_step_and_no_kernel():
    ops = scan_operands(1)
    text = jax.jit(lambda **o: ssd.ssd_scan(
        o["x"], o["delta"], o["a"], o["d"], o["b"], o["c"], o["reset"],
        o["state"])).lower(**ops).as_text()
    assert "pallas" not in text and "custom_call" not in text
