"""Production mesh factory.

Single pod: (data=16, model=16) = 256 chips (TPU v5e-256 topology).
Multi-pod: (pod=2, data=16, model=16) = 512 chips across two pods — the
"pod" axis is the DCN boundary; cross-pod collectives are gradient
all-reduces (and optional cross-pod FSDP), everything else stays inside a
pod's ICI.

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the LM stack places arrays with
    pjit-style sharding rules, and ``make_mesh`` now defaults to Explicit
    axes, under which sharding is part of every array's type (a token
    gather from an FSDP-sharded embedding then types as a duplicate spec)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int = None):
    """Mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    data = data or (n // model)
    assert data * model == n, (data, model, n)
    return auto_mesh((data, model), ("data", "model"))
