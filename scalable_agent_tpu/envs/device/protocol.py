"""The DeviceEnv protocol + the DEVICE_LEVELS registry.

A *device environment* is an environment whose transition function IS an
XLA program: ``initial``/``step`` are pure jnp functions over ``[B]``
batched state, usable under ``jit``/``scan``/``vmap``, so an entire
unroll (or the whole fused train step, runtime/ingraph.py) compiles into
ONE device launch with zero per-step host↔device traffic.  This module
is the contract every such world implements and the single registry
every consumer — ``make_device_env``, ``envs/registry.py``'s host twin
family, the driver's ``--train_backend=ingraph`` validation, the
conformance harness, and ``bench_device_env`` — consults.

The protocol (enforced mechanically by envs/device/conformance.py on
every registered level):

- ``spec`` describes shapes/dtypes/action space; outputs must match it
  for ANY seed (seeds select content, never structure).
- ``initial(seeds) -> (state, StepOutput[B])`` resets all envs.  The
  emitted output has ``done=True`` ("start of episode", the reference's
  FlowEnvironment.initial), reward 0, and zeroed episode info.
- ``step(state, action) -> (state, StepOutput[B])`` advances one agent
  step (= ``num_action_repeats`` simulator sub-steps, rewards summed,
  early stop on termination) and AUTO-RESETS: when ``done``, the
  emitted observation is already the NEXT episode's first frame (the
  StreamAdapter contract, envs/core.py), so the T+1-overlap trajectory
  layout needs no host-side reset step.
- Episode accounting is emitted-vs-carried (ImpalaStream): the emitted
  ``info`` INCLUDES the final step (``episode_step >= 1`` after
  initial, ``episode_return`` sums the whole episode), while the
  carried state resets to zero on done.  Finished-episode detection is
  ``done & (info.episode_step > 0)`` — initial's done=True rows carry
  step 0 and never count.
- Determinism: the trajectory is a pure function of (seeds, actions) —
  bit-identical across jit/scan boundaries and env re-instantiation.
- Donation safety: every array leaf of ``(state, output)`` is a
  DISTINCT buffer (no aliasing), so the fused trainer can donate the
  full carry without "donate the same buffer twice".
- Zero host syncs: nothing in ``initial``/``step`` may materialize a
  device value or call back into the host (the hot-path lint,
  tests/test_hotpath_lint.py, covers this package).

See docs/environments.md for the worked walkthrough.
"""

from typing import Callable, Dict, NamedTuple, Tuple, Union

from scalable_agent_tpu.envs.spaces import Discrete
from scalable_agent_tpu.types import Observation

# NOTE: this module is JAX-FREE by design, and its registrations below
# name their world classes as lazy "module:attr" strings: env worker
# subprocesses reach the level-defaults table through
# envs/registry.py's fake family without importing jax (spawn latency,
# and the TPU runtime must never initialize in children).  The world
# modules only load when an env is actually constructed.

__all__ = [
    "DEVICE_LEVELS",
    "DeviceEnvSpec",
    "DeviceLevel",
    "device_level_names",
    "make_device_env",
    "register_device_level",
]


class DeviceEnvSpec(NamedTuple):
    """Seed-independent structure of a device env's interface."""

    observation_spec: Observation  # pytree of TensorSpec
    action_space: Discrete
    num_actions: int


class DeviceLevel(NamedTuple):
    """One registered device level.

    ``defaults`` are the level's constructor parameters — the ONE copy
    both ``make_device_env`` and the host-twin factories in
    envs/registry.py read, so the device env and ``probe_env``'s host
    spec can never skew.  ``factory`` is the world class/callable, or a
    lazy ``"module:attr"`` string resolved on first construction.
    ``accepts`` names the config-level override knobs (``height``/
    ``width``/``num_actions``) this level honors; overrides outside it
    are ignored — a gridworld's frame geometry is fixed by its
    dynamics, not by ``--height``.
    """

    name: str
    factory: Union[str, Callable[..., object]]
    defaults: Dict[str, object]
    accepts: Tuple[str, ...]
    description: str

    def build(self, **params):
        factory = self.factory
        if isinstance(factory, str):
            import importlib

            module, _, attr = factory.partition(":")
            factory = getattr(importlib.import_module(module), attr)
        return factory(**params)


DEVICE_LEVELS: Dict[str, DeviceLevel] = {}


def register_device_level(name: str,
                          factory: Union[str, Callable[..., object]],
                          defaults: Dict[str, object],
                          accepts: Tuple[str, ...] = (),
                          description: str = "") -> None:
    """Register a device level.  Double registration raises — a level's
    defaults must have exactly one home."""
    if name in DEVICE_LEVELS:
        raise ValueError(f"device level {name!r} already registered")
    DEVICE_LEVELS[name] = DeviceLevel(
        name=name, factory=factory, defaults=dict(defaults),
        accepts=tuple(accepts), description=description)


def device_level_names() -> Tuple[str, ...]:
    return tuple(sorted(DEVICE_LEVELS))


def make_device_env(level_name: str, height: int = 0, width: int = 0,
                    num_actions: int = 0, num_action_repeats: int = 1,
                    with_instruction: bool = False,
                    **kwargs):
    """Device-env factory for levels expressible as pure XLA functions
    (the in-graph training backend, runtime/ingraph.py + driver
    --train_backend=ingraph).

    Level parameters come from the DEVICE_LEVELS entry — the same
    defaults envs/registry.py's host twins consult.  ``height``/
    ``width``/``num_actions`` of 0 mean "use the level default"; a
    nonzero override is honored only when the level's registry entry
    ``accepts`` that knob (the driver passes its config values for
    every level, and a world with dynamics-fixed geometry must not be
    silently resized into nonsense).  Explicit ``**kwargs`` always win
    — they address the constructor directly, for tests and benches.

    Levels whose simulators live in external processes (doom_/dmlab_/
    atari_) cannot run in-graph; asking for one is a clear error, not a
    silent fallback.
    """
    if with_instruction:
        raise ValueError(
            "device envs do not emit instruction observations")
    entry = DEVICE_LEVELS.get(level_name)
    if entry is None:
        raise ValueError(
            f"level {level_name!r} has no device (in-graph) "
            f"implementation; device-expressible levels: "
            f"{sorted(DEVICE_LEVELS)}")
    params = dict(entry.defaults)
    for knob, value in (("height", height), ("width", width),
                        ("num_actions", num_actions)):
        if value and knob in entry.accepts:
            params[knob] = value
    params.update(kwargs)
    return entry.build(num_action_repeats=num_action_repeats, **params)


# -- the registry --------------------------------------------------------

# The fake family (envs/device/fake.py — bit-exact mirrors of
# envs/fake.py; their host twins in envs/registry.py read THESE
# defaults).
register_device_level(
    "fake_benchmark", "scalable_agent_tpu.envs.device.fake:DeviceFakeEnv",
    dict(height=72, width=96, episode_length=1000, num_actions=9),
    accepts=("height", "width", "num_actions"),
    description="zero-simulator-cost throughput benchmark fake")
register_device_level(
    "fake_small", "scalable_agent_tpu.envs.device.fake:DeviceFakeEnv",
    dict(height=16, width=16, episode_length=10, num_actions=9),
    accepts=("height", "width", "num_actions"),
    description="small deterministic fake for smoke tests")
register_device_level(
    "fake_bandit", "scalable_agent_tpu.envs.device.fake:DeviceFakeEnv",
    dict(height=16, width=16, episode_length=16, num_actions=4,
         reward_mode="bandit"),
    accepts=("height", "width", "num_actions"),
    description="learnable contextual bandit (learning-proof level)")
register_device_level(
    "fake_memory", "scalable_agent_tpu.envs.device.fake:DeviceFakeEnv",
    dict(height=16, width=16, episode_length=8, num_actions=4,
         reward_mode="memory"),
    accepts=("height", "width", "num_actions"),
    description="first-frame-cue memory task (LSTM done-reset proof)")

# The real worlds (device-native; their host twins are the
# envs/device/host.py adapter driving the same transition function).
register_device_level(
    "device_grid_small",
    "scalable_agent_tpu.envs.device.gridworld:DeviceGridWorld",
    dict(grid_size=5, view=5, cell_px=3, episode_length=24),
    description="5x5 key-door gridworld, near-full observability — the "
                "short-run learnability level")
register_device_level(
    "device_grid_large",
    "scalable_agent_tpu.envs.device.gridworld:DeviceGridWorld",
    dict(grid_size=11, view=5, cell_px=3, episode_length=96),
    description="11x11 key-door gridworld, partial observation window")
register_device_level(
    "device_minatar_breakout",
    "scalable_agent_tpu.envs.device.minatar:DeviceBreakout",
    dict(episode_length=128, sticky_prob=0.0),
    description="MinAtar-style breakout: object-channel 10x10 frames, "
                "pure-lax dynamics")
register_device_level(
    "device_minatar_asterix",
    "scalable_agent_tpu.envs.device.minatar:DeviceAsterix",
    dict(episode_length=128, sticky_prob=0.0),
    description="MinAtar-style asterix: streaming enemies/gold, "
                "hash-spawned")

# Token worlds (envs/device/token_recall.py): the observation is a token
# id and the action a token of the same vocabulary slice, so the slice
# is ``num_actions`` and nothing else of the geometry knobs applies.
register_device_level(
    "token_recall",
    "scalable_agent_tpu.envs.device.token_recall:DeviceTokenRecall",
    dict(num_actions=25024, episode_length=4096, period=2560),
    description="seeded Zipf token streams that repeat after 2,560 "
                "positions: the token to predict lies past a 2,048 "
                "window, inside a 4,096-token episode")
register_device_level(
    "token_recall_long",
    "scalable_agent_tpu.envs.device.token_recall:DeviceTokenRecall",
    dict(num_actions=25008, episode_length=6144, period=3584),
    description="the same world for a policy with windows of 512 and "
                "one full cache: episodes of 6,144 tokens that repeat "
                "after 3,584 positions, seven windows back, from an "
                "eighth of a 200,064-token vocabulary")
register_device_level(
    "token_recall_10k",
    "scalable_agent_tpu.envs.device.token_recall:DeviceTokenRecall",
    dict(num_actions=16032, episode_length=10240, period=6144),
    description="the same world for a policy whose every layer keeps "
                "the whole episode (a latent cache): episodes of 10,240 "
                "tokens that repeat after 6,144 positions, from an "
                "eighth of a 128,256-token vocabulary")
register_device_level(
    "token_recall_14k",
    "scalable_agent_tpu.envs.device.token_recall:DeviceTokenRecall",
    dict(num_actions=16384, episode_length=14336, period=8192),
    description="the same world for a policy that keeps the whole "
                "episode in one full-attention ring among scans: "
                "episodes of 14,336 tokens that repeat after 8,192 "
                "positions, from an eighth of a 131,072-token vocabulary")
register_device_level(
    "token_recall_8k",
    "scalable_agent_tpu.envs.device.token_recall:DeviceTokenRecall",
    dict(num_actions=12544, episode_length=7936, period=4096),
    description="the same world for a policy that keeps the whole "
                "episode in one full-attention ring among delta-rule "
                "scans: episodes of 7,936 tokens that repeat after 4,096 "
                "positions, from an eighth of a 100,352-token vocabulary")
register_device_level(
    "token_recall_small",
    "scalable_agent_tpu.envs.device.token_recall:DeviceTokenRecall",
    dict(num_actions=64, episode_length=16, period=10),
    description="the same world at test size: 64 tokens, episodes of "
                "16, period 10 (past a window of 8)")
