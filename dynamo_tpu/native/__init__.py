"""Native (C++) runtime components with build-on-demand + Python fallback.

Reference parity: the reference's runtime hot paths are Rust/C++ (the
kv-router indexer, tokens crate, runtime core); the compute path here is
JAX/XLA, and these extensions cover the non-device hot paths. Each native
component has a pure-Python reference implementation that remains the
fallback (and the oracle in tests), so the framework never hard-requires a
toolchain at runtime.

Build model: g++ compiles the .cpp into a shared library under
``native/_build`` on first use (~1s). The artifact's name carries a hash
of the source text and the compile flags, so a binary built from other
source — e.g. one that rode along in a copy of the tree — can never load;
set ``DYN_TPU_NATIVE=0`` to force the Python fallbacks.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional

from dynamo_tpu import config
from dynamo_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# Declared in the canonical registry (config.py).
NATIVE = config.NATIVE

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_HERE, "_build")
_LOAD_CACHE: dict = {}


def _build_and_load(
    name: str, source: str, extra_flags: tuple = ()
) -> Optional[ctypes.CDLL]:
    """Compile ``source`` (under native/) to a cached .so and dlopen it."""
    if name in _LOAD_CACHE:
        return _LOAD_CACHE[name]
    lib = None
    if NATIVE.get():
        src = os.path.join(_HERE, source)
        flags = ["-O2", "-shared", "-fPIC", "-std=c++17"]
        try:
            with open(src, "rb") as f:
                digest = hashlib.sha256(
                    f.read() + " ".join([*flags, *extra_flags]).encode()
                ).hexdigest()[:12]
            out = os.path.join(_BUILD_DIR, f"lib{name}-{digest}.so")
            if not os.path.exists(out):
                os.makedirs(_BUILD_DIR, exist_ok=True)
                # Build to a private name, then rename: a concurrent
                # process never dlopens a half-written file.
                tmp = f"{out}.{os.getpid()}.tmp"
                subprocess.run(
                    ["g++", *flags, src, "-o", tmp, *extra_flags],
                    check=True, capture_output=True, timeout=120,
                )
                os.replace(tmp, out)
                logger.info("built native component %s", name)
            lib = ctypes.CDLL(out)
        except (OSError, subprocess.SubprocessError) as exc:
            logger.warning(
                "native component %s unavailable (%s); using Python fallback",
                name, exc,
            )
            lib = None
    _LOAD_CACHE[name] = lib
    return lib


def load_radix_lib() -> Optional[ctypes.CDLL]:
    lib = _build_and_load("dynradix", "radix_index.cpp")
    if lib is None:
        return None
    u64p = ctypes.POINTER(ctypes.c_uint64)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    lib.radix_new.restype = ctypes.c_void_p
    lib.radix_free.argtypes = [ctypes.c_void_p]
    lib.radix_store.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint64, ctypes.c_int,
        u64p, ctypes.c_size_t,
    ]
    lib.radix_remove.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, u64p, ctypes.c_size_t
    ]
    lib.radix_remove_worker.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.radix_num_blocks.argtypes = [ctypes.c_void_p]
    lib.radix_num_blocks.restype = ctypes.c_size_t
    lib.radix_worker_block_count.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.radix_worker_block_count.restype = ctypes.c_size_t
    lib.radix_find_matches.argtypes = [
        ctypes.c_void_p, u64p, ctypes.c_size_t, u32p, u32p, ctypes.c_size_t,
        u32p,
    ]
    lib.radix_find_matches.restype = ctypes.c_size_t
    return lib
