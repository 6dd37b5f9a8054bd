"""Which device the program runs on, and how its kernels run there.

The Pallas kernels are compiled on a TPU.  On the CPU, where the tests
run, they run in the Pallas interpreter (and ``window_pack`` falls back
to its jnp reference).  Any other platform is refused: a TPU that failed
to initialise makes JAX fall back to the CPU with only a warning, and the
kernels must not switch to the interpreter behind such a fallback — an
entry point that needs the chip checks ``device_info()`` first.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax


def interpret_kernels() -> bool:
    """True on the CPU (Pallas interpreter), False on a TPU (compiled).

    Raises on any other platform instead of interpreting there.
    """
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {platform!r}")


def device_info() -> dict:
    """Platform, device kind and device count, as JAX reports them."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def configure_compile_cache(default_dir: Path) -> str:
    """Turn on JAX's persistent compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is overridden.  Otherwise the cache lives at the fixed
    ``default_dir`` (the path is part of the cache key, so it must not
    move between runs).  Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(default_dir).resolve())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
