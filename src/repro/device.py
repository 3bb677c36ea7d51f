"""Device set-up shared by the programs that run on the chip.

``chip_smoke.py`` (and any benchmark that measures on the chip) calls
:func:`require_tpu` and :func:`enable_compile_cache` before its first
compile.  Neither falls back: a process that finds no TPU stops, and the
compile cache lives at one fixed path, since the path is part of the key
under which compiled programs are found again.
"""

from __future__ import annotations

import os
from pathlib import Path

#: JAX reads this variable itself; where it is set, nothing here overrides it.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: The cache's one fixed place when the variable is unset: inside the
#: checkout, git-ignored.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


class NoTPUError(RuntimeError):
    """JAX found no TPU; a chip measurement must not run elsewhere."""


def compile_cache_dir() -> str:
    """Where the persistent compile cache lives: ``$JAX_COMPILATION_CACHE_DIR``
    when set, otherwise :data:`DEFAULT_CACHE_DIR`."""
    return os.environ.get(CACHE_ENV) or str(DEFAULT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at :func:`compile_cache_dir` and
    return that path.  Sets the directory in code only when the variable is
    unset; call before the first compile."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_tpu():
    """The local devices, or :class:`NoTPUError` when they are not TPUs.
    Backend-initialization errors propagate unchanged."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoTPUError(f"no TPU found: JAX's devices are "
                         f"{devices[0].platform} ({len(devices)} of them)")
    return devices


def device_line(devices) -> dict:
    """The device as JAX reports it, in the form every chip result names."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
