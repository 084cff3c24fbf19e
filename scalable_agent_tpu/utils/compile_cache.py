"""The ONE place JAX's persistent compilation cache is armed.

Used by the driver (train, ingraph, test), ``bench.py`` and
``chip_smoke.py``.  The directory is placed from OUTSIDE the program:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
  module names no directory in code — child processes (elastic
  relaunches, bench suites) inherit the variable, which is how a
  relaunch finds the parent epoch's compiles.
- unset: a FIXED path inside the checkout, ``<repo>/.jax_cache``
  (git-ignored).  The path is part of how a later process finds the
  cache, so it is never a temporary name, a pid or a timestamp.
"""

import os

import jax

from scalable_agent_tpu.utils.misc import log

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<repo>/.jax_cache``: the checkout root is the directory that
    holds the ``scalable_agent_tpu`` package."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def setup_compile_cache() -> str:
    """Arm the persistent compilation cache and return the directory in
    use.  The size/time floors are zeroed so every program caches — an
    elastic relaunch's recovery time is its first compile
    (docs/robustness.md), and the small CPU-rig programs must hit too.
    Idempotent; never raises (an unwritable default directory logs a
    warning and the run compiles uncached)."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = default_cache_dir()
        try:
            os.makedirs(path, exist_ok=True)
        except OSError as exc:
            log.warning("compile cache disabled: cannot create %s (%s); "
                        "set %s to a writable directory", path, exc,
                        ENV_VAR)
            return ""
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
