"""The compile cache of every rafiki-tpu process.

Service entrypoints call :func:`apply_platform_env` first, before any
jax backend initializes. The platform itself is JAX's own business:
``JAX_PLATFORMS`` in the environment (the ServicesManager sets it on
every child — ``parallel.mesh.submesh_env_vars``).
"""

from __future__ import annotations

import logging
import os
import sys

#: JAX's own variable for its persistent compilation cache. Set from
#: outside, it is THE cache directory and this module sets no other;
#: unset, every process of a checkout shares ``<checkout>/.jax_cache``.
#: (``JAX_ENABLE_COMPILATION_CACHE=false``, JAX's own switch, turns the
#: cache off.)
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_path() -> str:
    """The persistent XLA-executable cache directory shared by every
    process of this checkout. Trials are separate processes but
    overwhelmingly compile the SAME programs (same template, same
    shape-relevant knobs across rungs/replicas), so a disk cache turns
    every repeat compile into a load. The path is part of the cache
    key, so it is FIXED: ``$JAX_COMPILATION_CACHE_DIR`` when the
    environment sets one, else ``.jax_cache`` at the root of the
    checkout — never a home, temporary, pid- or time-derived name."""
    return os.environ.get(CACHE_DIR_ENV) or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def apply_platform_env() -> None:
    """Place the compile cache before jax backends init.

    Never imports jax itself: numpy-only services (the predictor) call
    this too and must not pay a jax import for nothing.
    """
    cache = compile_cache_path()
    try:
        os.makedirs(cache, exist_ok=True)
    except OSError as e:
        logging.getLogger(__name__).warning(
            "compile cache directory %s cannot be created (%s): every "
            "program of this process compiles from scratch", cache, e)
    # through the environment: a later ``import jax`` reads it, and so
    # does every child this process spawns
    os.environ[CACHE_DIR_ENV] = cache
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                          "0.3")
    jax = sys.modules.get("jax")
    if jax is not None:  # imported before this call: its config read
        # the environment then, so hand it the same values directly
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]))


def log_devices(service: str) -> None:
    """One line on stdout (a service's log file) naming the devices
    this process came up on — the proof, per worker, of which backend
    its trials and replicas actually ran on. Initializes the backend:
    worker mains only, never the admin."""
    import jax

    print(f"{service}: platform={jax.default_backend()} "
          f"devices={[str(d) for d in jax.devices()]}", flush=True)
