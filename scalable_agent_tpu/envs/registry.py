"""Env construction registry with name-prefix dispatch.

The reference dispatches on name prefixes — ``doom_*``/``atari_*``/
``dmlab_*`` (reference: envs/create_env.py:1-19).  Here families register
themselves; heavyweight simulator families are imported lazily so a missing
pip package only fails when that family is actually requested.
"""

from typing import Callable, Dict, Optional, Tuple

from scalable_agent_tpu.envs.core import Environment

_FACTORIES: Dict[str, Tuple[Callable[..., Environment], bool]] = {}


def register_family(prefix: str, factory: Callable[..., Environment],
                    consumes_action_repeats: bool = False):
    """Register ``factory(full_name, **kwargs)`` for env names ``prefix*``.

    ``consumes_action_repeats``: the family applies action repeats
    natively (simulator-side, like DMLab's ``num_steps`` or Atari's
    skip pipeline) and accepts a ``num_action_repeats`` kwarg.  Families
    without it are wrapped by ``make_impala_stream`` instead and never
    see the kwarg — so third-party factories need no boilerplate.
    """
    _FACTORIES[prefix] = (factory, consumes_action_repeats)


def _lookup(full_env_name: str):
    for prefix, entry in sorted(
            _FACTORIES.items(), key=lambda kv: -len(kv[0])):
        if full_env_name.startswith(prefix):
            return entry
    raise ValueError(
        f"unknown env name {full_env_name!r}; registered prefixes: "
        f"{sorted(_FACTORIES)}")


def family_consumes_repeats(full_env_name: str) -> bool:
    return _lookup(full_env_name)[1]


def create_env(full_env_name: str, **kwargs) -> Environment:
    """Instantiate an env by prefix-dispatched name.

    (reference: envs/create_env.py:1-19)
    """
    return _lookup(full_env_name)[0](full_env_name, **kwargs)


def _make_fake(full_env_name: str, **kwargs) -> Environment:
    from scalable_agent_tpu.envs.fake import FakeEnv

    # Fake levels with a device twin read their parameters from the
    # DEVICE_LEVELS registry entry (envs/device/fake.py) — ONE copy of
    # the defaults, so probe_env's host spec and make_device_env can
    # never skew.  (Import is lazy: env worker subprocesses import this
    # module and must not pull the jax-importing device package until a
    # device level is actually requested — fake levels only touch it on
    # construction, in the parent.)
    from scalable_agent_tpu.envs.device.protocol import DEVICE_LEVELS

    entry = DEVICE_LEVELS.get(full_env_name)
    if entry is not None:
        for key, value in entry.defaults.items():
            kwargs.setdefault(key, value)
    elif full_env_name == "fake_tuple":
        # Composite action space: Tuple(Discrete, Discretized) — the
        # hermetic stand-in for Doom's composite spaces
        # (reference: envs/doom/action_space.py:13-138).  Host-only: no
        # device twin, so its defaults live here.
        from scalable_agent_tpu.envs.spaces import (
            Discrete, Discretized, TupleSpace)

        kwargs.setdefault("height", 16)
        kwargs.setdefault("width", 16)
        kwargs.setdefault("episode_length", 10)
        kwargs.setdefault("action_space", TupleSpace(
            [Discrete(3), Discretized(5, -1.0, 1.0)]))
    return FakeEnv(**kwargs)


def _lazy_family(family: str, module: str, attr: str):
    """Factory that imports its simulator module on first use and turns a
    missing module/pip package into a clear error instead of a raw
    ModuleNotFoundError deep inside an env worker."""

    def factory(full_env_name: str, **kwargs) -> Environment:
        import importlib

        try:
            mod = importlib.import_module(module)
        except ImportError as exc:
            raise ValueError(
                f"env family {family!r} is not available here: importing "
                f"{module} failed ({exc}).  Its simulator package is an "
                f"optional dependency.") from exc
        return getattr(mod, attr)(full_env_name, **kwargs)

    return factory


# Device-native levels (device_grid_*, device_minatar_* — the
# DEVICE_LEVELS registry, envs/device/protocol.py): the host twin is
# the HostDeviceEnv adapter driving the same XLA transition function
# with batch 1, so probe_env/eval and the device env agree by
# construction.  Lazy like the simulator families — the adapter jits,
# so it imports jax.
_make_device = _lazy_family(
    "device_", "scalable_agent_tpu.envs.device.host",
    "make_host_device_env")
_make_doom = _lazy_family(
    "doom_", "scalable_agent_tpu.envs.doom.factory", "make_doom_env")
_make_atari = _lazy_family(
    "atari_", "scalable_agent_tpu.envs.atari", "make_atari_env")
_make_dmlab = _lazy_family(
    "dmlab_", "scalable_agent_tpu.envs.dmlab", "make_dmlab_env")
_make_gym = _lazy_family(
    "gym_", "scalable_agent_tpu.envs.gym_adapter", "make_gym_env")


register_family("fake_", _make_fake, consumes_action_repeats=True)
register_family("device_", _make_device, consumes_action_repeats=True)
# token worlds (envs/device/token_recall.py) are device-native too
register_family("token_", _make_device, consumes_action_repeats=True)
register_family("doom_", _make_doom, consumes_action_repeats=True)
register_family("atari_", _make_atari, consumes_action_repeats=True)
register_family("dmlab_", _make_dmlab, consumes_action_repeats=True)
register_family("gym_", _make_gym)
