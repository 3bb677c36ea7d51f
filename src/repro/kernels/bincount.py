"""Pallas TPU kernel: weighted histogram (the paper's global result update).

G-TADOC resolves thousands of threads atomically updating one global hash
table with a lock buffer + atomicAdd (§IV-C, Fig. 5).  TPUs have no atomics;
the idiomatic replacement (DESIGN.md §2) turns the scatter into dense MXU
work: for a tile of (id, value) pairs and a block of histogram bins, build
the one-hot matrix ``ids == bin`` and accumulate ``vals @ onehot`` on the
MXU.  Conflict-free and deterministic by construction — every (tile, bin
block) contribution is a 128-aligned matmul.

Layout:
  ids   [NT, 1, TN] int32   (flattened input padded/reshaped by ops.py)
  vals  [NT, 1, TN] float32
  out   [1, V]      float32 (V padded to a multiple of BV)

The unit middle axis makes each input block's last two dims ``(1, TN)``
equal the array's, which Mosaic requires of a block that is not (8, 128)
aligned; TN and BV are multiples of 128.  The one-hot tile is built
transposed, ``[BV, TN]`` with bins on sublanes, so the ids row broadcasts
down the sublanes instead of being relaid out as a column, and the MXU
contracts the TN axis of both operands.  The contraction runs at fp32
precision (``Precision.HIGHEST``): at the TPU default an f32 matmul rounds
its operands to bfloat16, and counts above 256 would lose exactness.

Grid = (V // BV, NT): for a fixed bin block i we sweep all input tiles j,
accumulating into the same VMEM-resident output block (revisiting grid
dimension; out block depends only on i).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import resolve_interpret


DEFAULT_TN = 512   # input tile (multiple of 128 for the MXU contraction dim)
DEFAULT_BV = 512   # bin block   (multiple of 128, lane dim)


def _kernel(ids_ref, vals_ref, out_ref, *, bv: int):
    j = pl.program_id(1)                       # input-tile index

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    i = pl.program_id(0)                       # bin-block index
    ids = ids_ref[0]                           # [1, TN]
    vals = vals_ref[0]                         # [1, TN]
    bins = i * bv + jax.lax.broadcasted_iota(jnp.int32, (bv, ids.shape[1]), 0)
    onehot_t = (bins == ids).astype(jnp.float32)                  # [BV, TN]
    # [1, TN] x [BV, TN]^T -> [1, BV] on the MXU
    out_ref[...] += jax.lax.dot_general(
        vals, onehot_t, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def weighted_bincount_pallas(ids: jnp.ndarray, vals: jnp.ndarray, nbins: int,
                             tn: int = DEFAULT_TN, bv: int = DEFAULT_BV,
                             interpret: bool | None = None) -> jnp.ndarray:
    """out[b] = sum(vals[ids == b]) for b in [0, nbins).

    ids outside [0, nbins) are ignored (ops.py uses id == -1 as padding).
    ``interpret=None`` auto-resolves outside jit (_common.resolve_interpret).
    """
    return _weighted_bincount_jit(ids, vals, nbins, tn, bv,
                                  resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("nbins", "tn", "bv", "interpret"))
def _weighted_bincount_jit(ids, vals, nbins: int, tn: int, bv: int,
                           interpret: bool) -> jnp.ndarray:
    n = ids.shape[0]
    n_pad = (-n) % tn
    ids_p = jnp.pad(ids.astype(jnp.int32), (0, n_pad), constant_values=-1)
    vals_p = jnp.pad(vals.astype(jnp.float32), (0, n_pad))
    nt = ids_p.shape[0] // tn
    ids3 = ids_p.reshape(nt, 1, tn)
    vals3 = vals_p.reshape(nt, 1, tn)
    v_pad = (-nbins) % bv
    vtot = nbins + v_pad

    out = pl.pallas_call(
        functools.partial(_kernel, bv=bv),
        grid=(vtot // bv, nt),
        in_specs=[
            pl.BlockSpec((1, 1, tn), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((1, 1, tn), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bv), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, vtot), jnp.float32),
        interpret=interpret,
    )(ids3, vals3)
    return out[0, :nbins]
