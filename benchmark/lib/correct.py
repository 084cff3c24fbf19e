"""The comparison that decides ``correct``: the timed object's first
steps against the plain reference's.

The program's numbers come from the window's own compiled step and its
state, driven from the seed through its first three steps before the
window opens (``probe.py`` records them): each step's loss, the first
gradient as the optimizer got it (worked out of the RMSProp mean square
after one step), and the parameters' change after the three.  The
reference follows the same three steps from the same seeded weights:
on the very batches the host loop's update consumed, or — for the
fused loop, whose batch never leaves the program — on its own rollout
of the same world under the same keys.

Norms are compared by the worst leaf: the gap between the program's
norm and the reference's, against the reference's norm of that leaf or
of the median leaf, whichever is larger.

The reference is the cell's (``manifest.reference_module(cell)``): a
module with ``make_weights``, ``to_tree``, ``from_tree``,
``rmsprop_init``, ``rmsprop_step``, ``rollout_initial``, ``rollout``,
``loss_and_grads`` and ``Batch``, and — where its optimizer is not
RMSProp — ``first_gradient_norms``.  None: ``benchmark/lib/reference.py``.
"""

import statistics
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from benchmark.lib import reference as default_reference

STEPS = 3
# what ``compare`` gives; a cell's limits file holds those it is held to
COMPARED = ("loss1_gap", "loss_gap", "grad_norm_gap", "grad_median_gap",
            "delta_norm_gap")


def _leaf_norms(flat: Dict[tuple, Any]) -> Dict[tuple, float]:
    return {path: float(np.sqrt(np.sum(np.square(
        np.asarray(value, np.float64))))) for path, value in flat.items()}


def leaf_gaps(program: Dict[tuple, float],
              ref: Dict[tuple, float]) -> Dict[tuple, float]:
    """Leaf by leaf, the gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    floor = statistics.median(ref.values())
    return {path: abs(program[path] - ref[path])
            / max(ref[path], floor, 1e-30) for path in ref}


def worst_leaf_gap(program: Dict[tuple, float],
                   ref: Dict[tuple, float]) -> float:
    return max(leaf_gaps(program, ref).values())


def first_gradient_norms(cfg: Dict[str, Any], paths: Sequence[tuple],
                         nu1: Sequence[Any]) -> Dict[tuple, float]:
    """Leaf norms of the first gradient as RMSProp got it, out of its
    mean square after step one (leaves in parameter order):
    ``nu1 = decay * 1 + (1 - decay) * g**2``.  A reference module whose
    optimizer keeps another state brings its own under this name."""
    decay = cfg["optimizer"]["rmsprop_decay"]
    grad = {}
    for path, nu in zip(paths, nu1):
        g2 = (np.asarray(nu, np.float64) - decay) / (1.0 - decay)
        grad[path] = float(np.sqrt(np.sum(np.maximum(g2, 0.0))))
    return grad


def program_numbers(cfg: Dict[str, Any], seed: int, paths: Sequence[tuple],
                    losses: Sequence[float], nu1: Sequence[Any],
                    params_after: Any, reference=None) -> Dict[str, Any]:
    """Losses, first-gradient leaf norms and parameter-change leaf
    norms of the program, from what the probe recorded.  ``nu1`` are
    the optimizer-state leaves after step one, in parameter order."""
    reference = reference or default_reference
    grad = getattr(reference, "first_gradient_norms",
                   first_gradient_norms)(cfg, paths, nu1)
    start = reference.make_weights(cfg, seed)
    after = reference.from_tree(
        params_after["params"] if "params" in params_after
        else params_after)
    delta = {path: np.asarray(after[path], np.float64)
             - np.asarray(start[path], np.float64) for path in start}
    return {"losses": [float(x) for x in losses],
            "grad_norms": grad, "delta_norms": _leaf_norms(delta)}


def follow(cfg: Dict[str, Any], seed: int, frames_per_update: float,
           batches: Optional[List[Any]] = None,
           fused: Optional[Dict[str, Any]] = None,
           quant: Optional[str] = None, reference=None) -> Dict[str, Any]:
    """The reference's three steps.  ``batches``: the host loop's own
    (one per step).  ``fused``: {world, batch, unroll_length,
    program_seed} — the reference rolls its own world out."""
    import jax
    import jax.numpy as jnp

    reference = reference or default_reference
    start = reference.make_weights(cfg, seed)
    params = reference.to_tree(start)
    nu = reference.rmsprop_init(params)
    block = int(cfg["reference_block"])
    carry = None
    if fused is not None:
        carry = reference.rollout_initial(
            cfg, fused["world"], fused["batch"], fused["program_seed"])
    losses, grad_norms = [], None
    for k in range(STEPS):
        if fused is not None:
            batch, carry = reference.rollout(
                cfg, fused["world"], params, carry,
                fused["program_seed"], k, fused["unroll_length"], quant)
        else:
            batch = reference.Batch(*(jnp.asarray(x) for x in batches[k]))
        value, grads = reference.loss_and_grads(
            cfg, params, batch, block, quant)
        del batch
        losses.append(float(value))
        if k == 0:
            grad_norms = _leaf_norms(
                reference.from_tree(jax.device_get(grads)))
        params, nu = reference.rmsprop_step(
            cfg, params, nu, grads, k * frames_per_update)
    after = reference.from_tree(jax.device_get(params))
    delta = {path: np.asarray(after[path], np.float64)
             - np.asarray(start[path], np.float64) for path in start}
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": _leaf_norms(delta)}


def compare(program: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers held to limits."""
    gaps = [abs(p - r) / max(abs(r), 1e-30)
            for p, r in zip(program["losses"], ref["losses"])]
    grad = leaf_gaps(program["grad_norms"], ref["grad_norms"])
    return {
        # Step one starts from identical weights: precision and a
        # missing part of the batch show here.  Steps two and three
        # start from weights that already differ, and at seeded weights
        # the loss falls thirtyfold in one step, so their gap is wide
        # by nature and is held loosely.
        "loss1_gap": gaps[0],
        "loss_gap": max(gaps),
        # The worst leaf is as a rule the first conv's kernel, whose
        # gradient over the fake world's constant frames is a
        # near-cancelling sum behind a ReLU: on a few seeds in a
        # hundred rounding moves it by 7-15% (PERF.md, PR 31).  The
        # median leaf is steady from seed to seed, and a gradient off
        # in scale (part of the batch, an exchange left out) moves it
        # as far as it moves any leaf.
        "grad_norm_gap": max(grad.values()),
        "grad_median_gap": statistics.median(grad.values()),
        "delta_norm_gap": worst_leaf_gap(program["delta_norms"],
                                         ref["delta_norms"]),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """[(name, value, limit, ok)], one row per number compared."""
    return [(name, numbers[name], limit,
             bool(numbers[name] <= limit and np.isfinite(numbers[name])))
            for name, limit in sorted(limits.items())]
