"""Process-entry JAX set-up: where compiled programs are cached, and which
platform a serving process may run on.

Every process entry that compiles (worker, cli run, encode worker,
tests/conftest.py) calls :func:`configure_compile_cache` once, before its
first compile. No other code sets ``jax_compilation_cache_dir``
(tests/test_jax_env.py greps for it). The path is fixed because a
cache that moves never hits: a second start of the same worker is served
from the first one's compiles only if both name the same directory.

jax is imported inside the functions so parents that must stay off the
chip (chip_smoke.py) can resolve the directory for their children without
initialising a backend.
"""

from __future__ import annotations

import os

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# One threshold for every process. Zero: the CPU suite is dominated by
# thousands of sub-second compiles (tests/test_models.py alone makes 641,
# every one under the 0.5 s this used to be, so every run recompiled them
# all — a warm run of that file took 49 s at 0.5 and 19 s at 0), and on the
# chip the small entries cost nothing next to the minute-long ones.
MIN_COMPILE_TIME_SECS = 0.0

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.realpath(__file__)
)))


def compile_cache_dir() -> str:
    """The one compile-cache location: ``JAX_COMPILATION_CACHE_DIR`` when
    set (the operator or the chip tool placed it), otherwise the real path
    of ``.jax_cache`` in the checkout."""
    return os.environ.get(COMPILE_CACHE_ENV) or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def configure_compile_cache() -> str:
    """Point this process's persistent compile cache at
    :func:`compile_cache_dir`. With the variable set nothing about the
    directory is set in code — JAX reads the variable itself."""
    import jax

    if not os.environ.get(COMPILE_CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", MIN_COMPILE_TIME_SECS
    )
    return compile_cache_dir()


def require_serving_platform() -> str:
    """Fail at start unless JAX picked the TPU, or the caller asked for
    the CPU by name. With ``JAX_PLATFORMS`` unset JAX warns and takes the
    CPU when it finds no chip; a worker that starts that way serves
    tokens at CPU speed under a TPU worker's name. Tests and rehearsals
    set ``JAX_PLATFORMS=cpu`` explicitly. Returns the platform."""
    import jax

    platform = jax.default_backend()
    if platform == "tpu":
        return platform
    asked = [
        p.strip().lower()
        for p in os.environ.get("JAX_PLATFORMS", "").split(",")
    ]
    if platform == "cpu" and "cpu" in asked:
        return platform
    raise RuntimeError(
        f"JAX selected platform {platform!r} (JAX_PLATFORMS="
        f"{os.environ.get('JAX_PLATFORMS')!r}); this process serves from a "
        "TPU. Set JAX_PLATFORMS=cpu explicitly to run on the CPU."
    )
