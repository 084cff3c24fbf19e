"""In-graph (on-device) environments: envs as pure XLA functions.

The reference steps its environments *inside* the TF graph through
``tf.py_func`` pipes to subprocesses (reference: py_process.py:97-112,
environments.py:149-233) — the graph stalls on the host every step.  The
TPU-native inversion: an environment whose transition function is
expressible in XLA runs ON the accelerator, vectorized over the batch,
inside the same jitted program as agent inference — an entire unroll (or
the whole train step) becomes ONE device launch with zero per-step
host↔device traffic.  This is the standard JAX-RL architecture
(gymnax/Brax-style) and is what lets the framework saturate a chip whose
host link is slow (e.g. a remote TPU attachment).

Package layout (docs/environments.md is the narrative version):

- ``protocol``: the DeviceEnv contract + the DEVICE_LEVELS registry +
  ``make_device_env`` — the single source of level defaults that
  envs/registry.py's host twins and the driver's ingraph validation
  also consult.  JAX-FREE: env worker subprocesses read it.
- ``fake``: ``DeviceFakeEnv``, the bit-exact mirror of envs/fake.py
  (zero-simulator-cost benchmark + hermetic test backend).
- ``world``: the shared chassis for hand-written worlds (vmapping,
  action repeats, auto-reset, accounting, hashed randomness).
- ``gridworld`` / ``minatar``: the real XLA worlds —
  ``device_grid_*`` (procedural key-door) and ``device_minatar_*``
  (Atari-lite object-channel games).
- ``token_recall``: ``DeviceTokenRecall``, the world that emits tokens
  (``token_recall*``: seeded Zipf streams that repeat past an attention
  window; the observation is an int32 token id).
- ``host``: ``HostDeviceEnv``, the gym-like adapter that makes any
  device level a host ``Environment`` (probe_env/eval/registry).
- ``conformance``: the protocol checks every registered level must
  pass (tests/test_device_conformance.py runs the full matrix).
- ``accounting``: the ``devtel/env/*`` episode telemetry every device
  env shares (obs/device_telemetry.py instruments).

Attribute access is lazy (PEP 562): importing this package — which
envs/registry.py's jax-free worker path does to read the level-defaults
table — pulls in NO jax-importing module until a world class or the
telemetry helpers are actually touched.
"""

import importlib

_EXPORTS = {
    "DEVICE_LEVELS": "protocol",
    "DeviceEnvSpec": "protocol",
    "DeviceLevel": "protocol",
    "device_level_names": "protocol",
    "make_device_env": "protocol",
    "register_device_level": "protocol",
    "DeviceEnvState": "fake",
    "DeviceFakeEnv": "fake",
    "DeviceGridState": "gridworld",
    "DeviceGridWorld": "gridworld",
    "DeviceAsterix": "minatar",
    "DeviceBreakout": "minatar",
    "DeviceTokenRecall": "token_recall",
    "TokenRecallState": "token_recall",
    "DeviceWorld": "world",
    "HostDeviceEnv": "host",
    "make_host_device_env": "host",
    "env_telemetry_spec": "accounting",
    "record_episode_telemetry": "accounting",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    module_name = _EXPORTS.get(name)
    if module_name is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}")
    module = importlib.import_module(f"{__name__}.{module_name}")
    value = getattr(module, name)
    globals()[name] = value  # cache: subsequent accesses skip this hook
    return value


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
