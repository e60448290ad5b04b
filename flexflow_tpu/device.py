"""Which device this process has, and where its compiled programs are kept.

The one place that answers both. Kernel dispatch asks :func:`on_tpu`;
anything that reports a device number (``chip_smoke.py``,
``benchmark/run.py``) calls :func:`require_tpu` and fails without a
chip instead of measuring the CPU; every launcher calls
:func:`enable_compile_cache` before its first jit.
"""
from __future__ import annotations

import os
import pathlib

import jax

from .obs.steptrace import GLOBAL_STARTUP

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed for a checkout: a directory that moves between runs never hits
_DEFAULT_COMPILE_CACHE = pathlib.Path(__file__).resolve().parent.parent / ".jax_cache"


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def require_tpu() -> jax.Device:
    """The first device, which must be a TPU. JAX falls back to the CPU
    with a warning when libtpu finds no chip, so the platform is checked
    rather than inferred from ``JAX_PLATFORMS`` being unset. A
    launcher's first call is the process's first listing of devices, so
    the TPU runtime's own start is the span ``ff.startup.backend``."""
    with GLOBAL_STARTUP.span("backend"):
        dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"no TPU: JAX's first device is platform={dev.platform!r} "
            f"kind={dev.device_kind!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}); this path reports device "
            f"numbers and does not run on anything else"
        )
    return dev


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and nothing is set here; otherwise the cache lives at
    ``<checkout>/.jax_cache`` (gitignored)."""
    env = os.environ.get(COMPILE_CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_COMPILE_CACHE))
    return str(_DEFAULT_COMPILE_CACHE)
