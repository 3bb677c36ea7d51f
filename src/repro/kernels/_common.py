"""Shared kernel tiling constants + backend/interpret resolution (one source
of truth — the propagate kernels' chunking must stay in sync with each other,
and every Pallas entry point must resolve ``interpret`` the same way).

Backend resolution lives HERE (not ops.py) so the kernel modules themselves
can default ``interpret=None`` and auto-detect without importing the dispatch
layer (kernels stay leaf; ops re-exports these names for its callers).

``REPRO_FORCE_INTERPRET=1`` forces interpret-mode Pallas on CPU — CI's
forced-interpret lane uses it to exercise the real kernel code paths on
CPU-only runners (where production dispatch would otherwise route to the jnp
reference forms and the kernels would never run).  On a TPU it is an error:
emulating kernels there would silently run the slow interpreter in place of
the device code.

Neither probe hides the device: a backend that fails to initialize raises
from :func:`on_tpu` instead of answering "not a TPU" and sending traffic to
the CPU forms.
"""

from __future__ import annotations

import os

DEFAULT_BR = 256        # rows per block (sublane-dim multiple of 8)
DEFAULT_WC = 1 << 19    # weight-chunk length (f32 => 2 MB VMEM per chunk)
DEFAULT_FC = 128        # file-axis block for vector-payload ELL (lane dim)

FORCE_INTERPRET_ENV = "REPRO_FORCE_INTERPRET"


def round_up_pow2(x: int) -> int:
    """Smallest power of two >= max(x, 1).  Kept semantically identical to
    core.grammar.pow2_bucket (no cross-layer import: kernels stay leaf)."""
    return 1 << max(0, (max(int(x), 1) - 1).bit_length())


_BACKEND_CACHE: dict = {}


def on_tpu() -> bool:
    """Cached backend probe.  NOT an lru_cache: tests monkeypatch the jax
    backend, and a process-lifetime cache would leak the first answer
    across them — reset_backend_cache() makes the memo revocable.

    Backend-initialization errors propagate: a TPU that fails to start
    must fail the caller, not demote it to the CPU forms."""
    if "on_tpu" not in _BACKEND_CACHE:
        import jax
        _BACKEND_CACHE["on_tpu"] = jax.devices()[0].platform == "tpu"
    return _BACKEND_CACHE["on_tpu"]


def reset_backend_cache() -> None:
    """Drop the memoized backend probe (call after changing jax backends).

    Caveat: routing decisions are made at trace time, so programs that are
    already jit-compiled keep whatever branch they baked in — also call
    ``jax.clear_caches()`` if compiled routing must change too."""
    _BACKEND_CACHE.clear()


def force_interpret() -> bool:
    """True when the forced-interpret CI lane is active (re-read each call:
    tests toggle the env var at runtime).  Raises on a TPU, where the lane
    would replace the compiled kernels with their emulation."""
    if os.environ.get(FORCE_INTERPRET_ENV, "") in ("", "0"):
        return False
    if on_tpu():
        raise RuntimeError(
            f"{FORCE_INTERPRET_ENV} is set on a TPU backend; the "
            f"forced-interpret lane is for CPU runners only — unset it")
    return True


def resolve_interpret(interpret: bool | None) -> bool:
    """The one ``interpret`` policy for every Pallas entry point.

    None => auto: real lowering on TPU, interpret mode elsewhere (the
    forced-interpret lane pins True on CPU and raises on TPU).  Explicit
    True/False is always honored — True is the validation-oracle mode,
    False asserts real lowering."""
    if interpret is None:
        return force_interpret() or not on_tpu()
    return bool(interpret)
