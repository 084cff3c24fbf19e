"""The seam a decoder family is declared behind
(models/token_policy.py ``_FAMILY``: one record a ``model_type``), held
from both sides:

(a) a fifth family is a record: registered under a name of its own for
    the length of a test, the first family's record builds a policy
    whose forward is the first family's bit for bit (no arm of
    ``from_dict``, ``_Layer``, the head or the telemetry asks its
    name);
(b) a static guard of the ``test_hotpath_lint.py`` kind: in
    ``models/token_policy.py``, ``driver.py`` and ``config.py`` nothing
    compares a ``model_type`` (or a variable bound from one) with a
    string literal, and a family's name is a string literal only as its
    key of the table (and as the dataclass's default): what a family
    is made of stays in its record.
"""

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import scalable_agent_tpu
from scalable_agent_tpu.models import token_policy
from scalable_agent_tpu.models.token_policy import TokenModelConfig

PKG_DIR = os.path.dirname(os.path.abspath(scalable_agent_tpu.__file__))
GUARDED = (os.path.join("models", "token_policy.py"), "driver.py",
           "config.py")


# -- (a) a fifth family is a record -------------------------------------------

def test_a_fifth_family_is_a_record(monkeypatch):
    from family_suite import env_outputs
    from test_token_policy import PRESET

    first = PRESET.family
    table = dict(token_policy._FAMILY, fifth=token_policy._FAMILY[first])
    with pytest.raises(ValueError, match="'fifth' is not built"):
        TokenModelConfig.from_dict(dict(PRESET.tiny, model_type="fifth"))
    monkeypatch.setattr(token_policy, "_FAMILY", table)
    monkeypatch.setattr(token_policy, "FAMILIES", tuple(table))
    model = TokenModelConfig.from_dict(dict(PRESET.tiny, model_type="fifth"))
    assert model.model_type == "fifth"
    params = PRESET.weights()
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, PRESET.vocab, (PRESET.unroll, PRESET.batch)), jnp.int32)
    outputs = env_outputs(
        tokens, jnp.zeros(tokens.shape, bool).at[0].set(True))
    results = []
    for agent in (PRESET.policy(), PRESET.policy(model=model)):
        assert agent.layer_groups == PRESET.groups
        (logits, baseline), state = agent.apply(
            params, tokens, outputs, agent.initial_state(PRESET.batch))
        results.append(jax.tree_util.tree_leaves(
            (logits, baseline, state)))
    assert float(jnp.max(jnp.abs(results[0][0]))) > 0.0
    for mine, theirs in zip(*results):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))


# -- (b) nothing outside the table asks a family's name -----------------------

def _mentions_model_type(node, bound) -> bool:
    """``x.model_type``, the key ``"model_type"`` or a name bound from
    either, anywhere in ``node``."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr == "model_type":
            return True
        if isinstance(sub, ast.Constant) and sub.value == "model_type":
            return True
        if isinstance(sub, ast.Name) and sub.id in bound:
            return True
    return False


def _is_text(node) -> bool:
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        return bool(node.elts) and all(_is_text(e) for e in node.elts)
    return isinstance(node, ast.Constant) and isinstance(node.value, str)


def family_name_tests(source: str, families):
    """(line, what) of every comparison of a ``model_type`` with a
    string literal, and of every string literal that is a family's name
    outside the table's keys and the dataclass's default."""
    tree = ast.parse(source)
    bound = set()
    for _ in range(2):          # a name bound from a bound name
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and _mentions_model_type(
                    node.value, bound):
                bound.update(t.id for t in node.targets
                             if isinstance(t, ast.Name))
    allowed = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.AnnAssign)
                and isinstance(node.target, ast.Name)):
            continue
        if node.target.id == "_FAMILY" and isinstance(node.value, ast.Dict):
            allowed.update(id(key) for key in node.value.keys)
        if node.target.id == "model_type":
            allowed.add(id(node.value))
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Compare):
            sides = [node.left] + node.comparators
            if (any(_is_text(side) for side in sides)
                    and any(_mentions_model_type(side, bound)
                            for side in sides)):
                found.append((node.lineno, ast.unparse(node)))
        if (isinstance(node, ast.Constant) and node.value in families
                and id(node) not in allowed):
            found.append((node.lineno, repr(node.value)))
    return sorted(set(found))


def test_nothing_outside_the_table_asks_a_familys_name():
    for relative in GUARDED:
        with open(os.path.join(PKG_DIR, relative)) as f:
            found = family_name_tests(f.read(), token_policy.FAMILIES)
        assert not found, (
            f"{relative} asks a family's name outside its record of "
            f"token_policy._FAMILY: {found}")
    # the guard sees what it guards against
    planted = family_name_tests(
        'family = raw.get("model_type", "afmoe")\n'
        'if family == "nemotron_h": pass\n'
        'tied = model.model_type in ("phi4flash",)\n'
        'kinds = {"afmoe": 1}\n', token_policy.FAMILIES)
    assert [line for line, _ in planted] == [1, 2, 2, 3, 3, 4]
