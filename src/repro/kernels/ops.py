"""Jit'd public wrappers + dispatch layer for the Pallas kernels.

``interpret`` defaults to auto: the platform picks the execution path per
kernel (DESIGN note below).  Wrappers fall back to the jnp
reference for degenerate shapes where a kernel launch is pure overhead; the
dispatch predicates are exposed (``bincount_use_ref`` / ``ell_use_ref`` /
``ell_batched_use_ref`` / ``bincount_batch_rows``) so tests can assert the
routing without allocating the big inputs that trigger it.

DESIGN — ELL vs segment_sum dispatch: the batched traversal engine
(core/batch.py) asks ``ell_batched_use_ref`` whether a round should run on
the ELL edge plan (gather form, no scatter — see propagate_batched.py) or
stay on the COO segment_sum path.  The predicate is an occupancy model over
(edge count, the plan's slot count, its widest row K, batch width N): very
sparse or very wide plans waste slot-proportional work, tiny batches never
amortize a launch.  The batched engines run the *split* plan
(``GrammarBatch.ell_split_plan``): hub rows hand their in-edges to overflow
rows, so its slot count follows the in-degree distribution, not N*R*K_max,
and the gates judge that count (``slots``).  The split round is a jnp
program on every platform (``exec:ell_split``); the Pallas kernels below
are the oracles of the dense plan, which the split plan equals bit for bit.

DESIGN — execution path per kernel (:func:`exec_path`, a static decision
from the platform; counted as
``repro_kernel_dispatch_total{decision="exec:*"}``
with path ``pallas_compiled`` / ``pallas_interpret`` / ``jnp_ref``):

* TPU lowers the bincount kernel.  The four ELL kernels gather
  ``weight[src]`` from VMEM by arbitrary index, and Mosaic lowers a gather
  only within one source vreg ("Multiple source vregs along gather
  dimension"), so on TPU they run as the jnp form of the same plan (an XLA
  gather).  No compile error is caught at run time: the route is chosen
  before anything is traced (tests/test_tpu_compile.py holds both facts).
* CPU production takes the jnp form of the ELL plans (interpret-mode
  emulation is pure overhead); bincount and row sums keep their
  interpret-mode kernels there.  ``interpret=True`` remains the validation
  oracle everywhere, and the forced-interpret CI lane pushes every CPU call
  through the kernels (it raises on a TPU — _common.force_interpret).

Weight-vector size routes nothing: both ELL kernels stream the weight
vector through VMEM in grid-blocked chunks (propagate.py DESIGN note), so
arbitrarily large rule counts run through the same kernels — dispatch
decisions here are about occupancy and platform only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs import global_registry

from . import autotune, ref
from ._common import (DEFAULT_BR, DEFAULT_WC, force_interpret,
                      on_tpu as _on_tpu, reset_backend_cache)
from .bincount import weighted_bincount_pallas
from .propagate import ell_row_sums_pallas
from .propagate_batched import ell_propagate_batched_pallas
from .propagate_fused import ell_frontier_fused_pallas
from .propagate_vector import ell_propagate_vector_pallas

__all__ = [
    "weighted_bincount", "weighted_bincount_batched", "masked_top_k",
    "ell_row_sums", "ell_propagate_batched", "ell_propagate_vector",
    "ell_frontier_fused", "bincount_use_ref", "bincount_batch_rows",
    "ell_use_ref", "ell_batched_use_ref", "ell_fused_use_kernel",
    "ell_vector_plan_ok", "count_ell_split", "reset_backend_cache",
    "force_interpret",
    "exec_path", "KERNELS",
]

# Below these sizes a kernel launch is pure overhead.
BINCOUNT_MIN_N = 64
BINCOUNT_MIN_BINS = 8
ELL_MIN_ROWS = 64
# weighted_bincount_batched flattens [N, T] ids into N*nbins disjoint bins;
# above this flat-bin count the batch is chunked instead (huge vocabularies
# would otherwise allocate N*V scratch bins for one [N, V] result).
BINCOUNT_BATCH_FLAT_LIMIT = 1 << 22
# Batched ELL-plan occupancy gates (see module docstring).
ELL_BATCH_MIN_ROWS = 64
ELL_BATCH_MAX_WIDTH = 2048
ELL_BATCH_MIN_FILL = 1.0 / 256.0
# Absolute dense-plan budget (N * rows * K entries, ~1 GB of src+freq at the
# limit): the safety valve for *explicit* ELL requests — a huge sparse
# grammar with one moderate hub rule passes the width gate yet would
# allocate an O(R * K) plan far beyond its COO size.
ELL_PLAN_MAX_ENTRIES = 1 << 27
# The fused multi-round kernel keeps the WHOLE frontier state (six [R_pad]
# float32 vectors) VMEM-resident per corpus — ~24 B/rule.  Above this rule
# count the engines fall back to the per-round streaming path.
ELL_FUSED_MAX_RULES = 1 << 18


def _count_dispatch(decision: str, path: str) -> None:
    """Meter one dispatch decision on the process registry.  These fire at
    trace/plan time (host side), so steady-state jitted traffic does NOT
    re-count per call — the counters answer "which engine did this shape
    compile onto", which is the question the 31x campaign needs."""
    global_registry().counter(
        "repro_kernel_dispatch_total",
        "kernel dispatch decisions at trace/plan time",
        ("decision", "path")).labels(decision, path).inc()


def _count_tuned(kind: str, result: str) -> None:
    global_registry().counter(
        "repro_kernel_tuned_table_total",
        "autotune tuned-table lookups by result",
        ("kind", "result")).labels(kind, result).inc()


#: Every Pallas kernel behind a wrapper here.
KERNELS = ("bincount", "ell_row_sums", "ell_batched", "ell_vector",
           "ell_fused")
#: Kernels Mosaic lowers for TPU (module DESIGN note); every other kernel
#: runs its jnp form there under auto routing.
TPU_LOWERED_KERNELS = frozenset({"bincount"})
#: Kernels whose CPU production form is the jnp reference.
CPU_JNP_KERNELS = frozenset({"ell_batched", "ell_vector", "ell_fused"})


def exec_path(kernel: str, interpret=None) -> str:
    """Which of the three execution modes ``kernel`` takes:
    ``pallas_compiled``, ``pallas_interpret`` or ``jnp_ref``.  Explicit
    ``interpret`` is always honored; ``None`` routes by platform (module
    DESIGN note)."""
    if interpret is not None:
        return "pallas_interpret" if interpret else "pallas_compiled"
    if force_interpret():                   # CPU lane; raises on a TPU
        return "pallas_interpret"
    if _on_tpu():
        return ("pallas_compiled" if kernel in TPU_LOWERED_KERNELS
                else "jnp_ref")
    return "jnp_ref" if kernel in CPU_JNP_KERNELS else "pallas_interpret"


def _dispatch(kernel: str, interpret) -> str:
    """:func:`exec_path`, counted on the dispatch metric."""
    path = exec_path(kernel, interpret)
    _count_dispatch(f"exec:{kernel}", path)
    return path


def bincount_use_ref(n: int, nbins: int) -> bool:
    """True when weighted_bincount should route to the jnp reference."""
    return n < BINCOUNT_MIN_N or nbins < BINCOUNT_MIN_BINS


def bincount_batch_rows(n: int, nbins: int) -> int:
    """Rows per flattened chunk for weighted_bincount_batched.

    == n (no chunking) while n*nbins stays under BINCOUNT_BATCH_FLAT_LIMIT;
    above it, the largest row count whose flat bin range fits the limit
    (>= 1 — a single row degenerates to the per-row kernel)."""
    if n * nbins <= BINCOUNT_BATCH_FLAT_LIMIT:
        return n
    return max(1, BINCOUNT_BATCH_FLAT_LIMIT // nbins)


def ell_use_ref(num_weights: int, rows: int) -> bool:
    """True when ell_row_sums should route to the jnp reference.

    Only tiny row counts route away now; ``num_weights`` is kept for API
    compatibility but no longer matters — the blocked kernel streams weight
    vectors of any size through VMEM chunks (propagate.py)."""
    del num_weights
    return rows < ELL_MIN_ROWS


def ell_batched_use_ref(num_edges: int, n: int, rows: int, k: int,
                        shards: int = 1, slots: int | None = None) -> bool:
    """True when a batched propagation round should stay on segment_sum.

    Occupancy dispatch for the ELL plan of ``slots`` entries (default the
    dense ``n * rows * k``; widest row ``k``): reject tiny batches (launch
    overhead), very wide plans (K beyond any realistic in-degree bucket),
    and plans so sparse that the padded gather does >256x the real edge
    work.  ``shards`` > 1 evaluates the launch-overhead
    gate per device — a corpus-sharded pack (core/batch.py DESIGN note)
    launches one program per shard over N/shards rows, so that is the width
    the launch must amortize.  Fill is a ratio and shard-invariant.

    A tuned table entry (kernels/autotune.py, kind ``ell_vs_seg`` — both
    engine paths actually timed on this machine at this shape bucket)
    overrides all of the static heuristics."""
    shards = max(int(shards), 1)
    tuned = autotune.tuned_use_ref(
        "ell_vs_seg", autotune.shape_bucket(max(n // shards, 1), rows, k))
    if tuned is not None:
        _count_tuned("ell_vs_seg", "hit")
        use_ref = tuned
    else:
        _count_tuned("ell_vs_seg", "miss")
        use_ref = ((n // shards) * rows < ELL_BATCH_MIN_ROWS
                   or k > ELL_BATCH_MAX_WIDTH
                   or num_edges / max(_slots(n, rows, k, slots), 1)
                   < ELL_BATCH_MIN_FILL)
    _count_dispatch("ell_vs_seg", "segment_sum" if use_ref else "ell")
    return use_ref


def _slots(n: int, rows: int, k: int, slots: int | None) -> int:
    return n * rows * k if slots is None else int(slots)


def count_ell_split(split: bool) -> None:
    """Meter which ELL plan a pack built: ``split`` (hub rows overflow) or
    ``dense`` (no row exceeds the main width)."""
    _count_dispatch("ell_split", "split" if split else "dense")


def _split_route(interpret) -> None:
    """The split round has no Pallas kernel: it runs its jnp form on every
    platform, so an explicit kernel mode cannot be honoured."""
    if interpret is not None:
        raise ValueError("the split ELL plan has no Pallas kernel; "
                         "run it with interpret=None")
    _count_dispatch("exec:ell_split", "jnp_ref")


def ell_fused_use_kernel(rows: int) -> bool:
    """True when the fused multi-round traversal may run device-resident:
    the whole frontier state must fit VMEM (see ELL_FUSED_MAX_RULES).
    Engines that get False fall back to the per-round frontier path —
    identical results, per-round dispatch cost."""
    fused = rows <= ELL_FUSED_MAX_RULES
    _count_dispatch("fused_vs_per_round", "fused" if fused else "per_round")
    return fused


def ell_vector_plan_ok(n: int, rows: int, k: int, f: int,
                       slots: int | None = None) -> bool:
    """True when the vector-payload round over a plan of ``slots`` entries
    (default the dense ``n * rows * k``) fits the plan budget: the gather
    materializes ``slots * F`` contributions."""
    ok = _slots(n, rows, k, slots) * max(f, 1) <= ELL_PLAN_MAX_ENTRIES
    _count_dispatch("vector_plan", "ell" if ok else "segment_sum")
    return ok


def _blocks(kind: str, bucket, defaults: dict) -> dict:
    """Merge tuned block sizes (autotune table) over the shipped defaults."""
    merged = dict(defaults)
    tuned = autotune.tuned_blocks(kind, bucket)
    _count_tuned(kind, "hit" if tuned else "miss")
    for key, val in tuned.items():
        if key in merged:
            merged[key] = val
    return merged


def weighted_bincount(ids: jnp.ndarray, vals: jnp.ndarray, nbins: int,
                      interpret: bool | None = None) -> jnp.ndarray:
    """MXU histogram: out[b] = sum(vals[ids == b]).  See bincount.py."""
    if ids.shape[0] == 0:
        return jnp.zeros(nbins, jnp.float32)
    if bincount_use_ref(ids.shape[0], nbins):
        return ref.weighted_bincount_ref(ids, vals, nbins)
    path = _dispatch("bincount", interpret)
    return weighted_bincount_pallas(ids, vals, nbins,
                                    interpret=path == "pallas_interpret")


def weighted_bincount_batched(ids: jnp.ndarray, vals: jnp.ndarray,
                              nbins: int,
                              interpret: bool | None = None) -> jnp.ndarray:
    """Batched histogram: out[i, b] = sum(vals[i][ids[i] == b]).

    The batched analytics engine's global-reduction entry point: rows are
    fused into ONE kernel launch by offsetting row i's ids into the
    disjoint bin range ``[i * nbins, (i+1) * nbins)`` and histogramming the
    flattened stream (same trick as packing corpora side by side in the
    pre-planned pool).  Ids outside ``[0, nbins)`` are treated as padding
    and ignored, exactly like the unbatched wrapper.

    Huge vocabularies would make the flat bin range N*nbins blow up, so the
    batch is processed in row chunks of ``bincount_batch_rows(n, nbins)``
    (each chunk's flat range stays under BINCOUNT_BATCH_FLAT_LIMIT; a
    single-row chunk degenerates to the per-row kernel).
    """
    if ids.ndim != 2 or vals.shape != ids.shape:
        raise ValueError(f"expected matching [N, T] inputs, got "
                         f"{ids.shape} / {vals.shape}")
    n, t = ids.shape
    if n == 0 or t == 0:
        return jnp.zeros((n, nbins), jnp.float32)

    def flat_chunk(ids_c: jnp.ndarray, vals_c: jnp.ndarray) -> jnp.ndarray:
        rows = ids_c.shape[0]
        valid = (ids_c >= 0) & (ids_c < nbins)
        offs = (jnp.arange(rows, dtype=jnp.int32) * nbins)[:, None]
        flat_ids = jnp.where(valid, ids_c + offs, -1).reshape(-1)
        flat = weighted_bincount(flat_ids, vals_c.reshape(-1), rows * nbins,
                                 interpret=interpret)
        return flat.reshape(rows, nbins)

    rows = bincount_batch_rows(n, nbins)
    _count_dispatch("bincount_batch", "flat" if rows >= n else "chunked")
    if rows >= n:
        return flat_chunk(ids, vals)
    return jnp.concatenate(
        [flat_chunk(ids[s: s + rows], vals[s: s + rows])
         for s in range(0, n, rows)], axis=0)


def masked_top_k(scores: jnp.ndarray, valid: jnp.ndarray, k: int):
    """Top-k over the trailing axis with invalid slots masked out.

    The search subsystem's ranking primitive: ``scores [..., M]`` and a
    ``valid`` mask of the same shape; masked slots become ``-inf`` so any
    finite real score outranks them.  Returns ``(values, indices)`` of the
    ``k`` largest per row, values descending; ``jax.lax.top_k`` resolves
    equal values toward the LOWER index, which is exactly the
    deterministic file-id tie-break the retrieval layer promises (and the
    numpy oracle's stable argsort reproduces).  ``k`` is static and must
    not exceed the trailing dimension.
    """
    k = int(k)
    if k < 1:
        raise ValueError(f"masked_top_k needs k >= 1, got {k}")
    if k > scores.shape[-1]:
        raise ValueError(f"k={k} exceeds the candidate axis "
                         f"({scores.shape[-1]})")
    masked = jnp.where(valid, scores, -jnp.inf)
    vals, idx = jax.lax.top_k(masked, k)
    return vals, idx                # a real tuple (shard_map out_specs)


def ell_row_sums(weights: jnp.ndarray, src: jnp.ndarray, freq: jnp.ndarray,
                 interpret: bool | None = None) -> jnp.ndarray:
    """ELL gather row sums: the frontier-propagation hot loop."""
    if src.shape[0] == 0:
        return jnp.zeros(0, jnp.float32)
    if ell_use_ref(weights.shape[0], src.shape[0]):
        return ref.ell_row_sums_ref(weights, src, freq)
    path = _dispatch("ell_row_sums", interpret)
    if path == "jnp_ref":
        return ref.ell_row_sums_ref(weights, src, freq)
    return ell_row_sums_pallas(weights, src, freq,
                               interpret=path == "pallas_interpret")


def ell_propagate_batched(weights: jnp.ndarray, active: jnp.ndarray,
                          src: jnp.ndarray, freq: jnp.ndarray,
                          interpret: bool | None = None, ovf: tuple = ()):
    """One fused propagation round over the [N, rows, K] ELL plan.

    Returns ``(delta, seen)`` — both [N, rows] float32; see
    propagate_batched.py for the exact semantics.  Routing (interpret=None,
    module DESIGN note): the jnp form of the same plan on TPU and CPU;
    interpret=True runs the interpret-mode kernel as the validation oracle.
    ``ovf`` — ``(ovf_src, ovf_freq)`` of a split plan — runs the split
    round (ref.ell_propagate_split_ref) instead.
    """
    if src.ndim != 3 or freq.shape != src.shape:
        raise ValueError(f"expected matching [N, rows, K] plans, got "
                         f"{src.shape} / {freq.shape}")
    n, rows, k = src.shape
    if n == 0 or rows == 0 or k == 0:
        z = jnp.zeros((n, rows), jnp.float32)
        return z, z
    if ovf:
        _split_route(interpret)
        return ref.ell_propagate_split_ref(weights, active, src, freq, *ovf)
    path = _dispatch("ell_batched", interpret)
    if path == "jnp_ref":
        return ref.ell_propagate_batched_ref(weights, active, src, freq)
    blocks = _blocks("ell_batched", autotune.shape_bucket(n, rows, k),
                     {"br": DEFAULT_BR, "wc": DEFAULT_WC})
    return ell_propagate_batched_pallas(weights, active, src, freq,
                                        interpret=path == "pallas_interpret",
                                        **blocks)


def ell_propagate_vector(W: jnp.ndarray, active: jnp.ndarray,
                         src: jnp.ndarray, freq: jnp.ndarray,
                         interpret: bool | None = None, ovf: tuple = ()):
    """One vector-payload propagation round over the [N, rows, K] plan.

    W: [N, R, F] per-file payload; returns ``(delta [N, rows, F],
    seen [N, rows])`` — the per-file traversals' ELL round (see
    propagate_vector.py).  Routing mirrors ``ell_propagate_batched``: the
    jnp gather form in production, interpret=True (or the forced-interpret
    lane) runs the interpret-mode kernel as the validation oracle.  ``ovf``
    runs the split round, as in ``ell_propagate_batched``.
    """
    if src.ndim != 3 or freq.shape != src.shape:
        raise ValueError(f"expected matching [N, rows, K] plans, got "
                         f"{src.shape} / {freq.shape}")
    if W.ndim != 3:
        raise ValueError(f"expected [N, R, F] payload, got {W.shape}")
    n, rows, k = src.shape
    if n == 0 or rows == 0 or k == 0:
        return (jnp.zeros((n, rows, W.shape[-1]), jnp.float32),
                jnp.zeros((n, rows), jnp.float32))
    if ovf:
        _split_route(interpret)
        return ref.ell_propagate_split_ref(W, active, src, freq, *ovf)
    path = _dispatch("ell_vector", interpret)
    if path == "jnp_ref":
        return ref.ell_propagate_vector_ref(W, active, src, freq)
    from .propagate_vector import DEFAULT_BRV, DEFAULT_WCV
    from ._common import DEFAULT_FC
    blocks = _blocks(
        "ell_vector", autotune.shape_bucket(n, rows, k, W.shape[-1]),
        {"br": DEFAULT_BRV, "wc": DEFAULT_WCV, "fc": DEFAULT_FC})
    return ell_propagate_vector_pallas(W, active, src, freq,
                                       interpret=path == "pallas_interpret",
                                       **blocks)


def ell_frontier_fused(weights0: jnp.ndarray, in_deg: jnp.ndarray,
                       src: jnp.ndarray, freq: jnp.ndarray,
                       max_rounds: int, with_rounds: bool = False,
                       interpret: bool | None = None, ovf: tuple = ()):
    """The WHOLE frontier traversal in one dispatch (see propagate_fused.py).

    weights0/in_deg: [N, R]; src/freq: [N, R, K]; ``max_rounds`` must bound
    the frontier round count (the DAG's ``num_levels`` is exact).  Returns
    weights [N, R] — or ``(weights, rounds [N])`` when ``with_rounds``.
    Callers must pre-gate with ``ell_fused_use_kernel(R)`` (VMEM state
    residency); routing follows ``ell_propagate_batched``: production on
    TPU and CPU runs the jitted fori_loop reference (one dispatch, no
    per-round convergence test — the same structural-tax win in jnp form),
    the interpret lanes run the Pallas kernel.  ``ovf`` runs every round
    over the split plan (jnp form only, as in ``ell_propagate_batched``).
    """
    if src.ndim != 3 or freq.shape != src.shape:
        raise ValueError(f"expected matching [N, rows, K] plans, got "
                         f"{src.shape} / {freq.shape}")
    n, rows, k = src.shape
    if n == 0 or rows == 0 or k == 0:
        w = weights0.astype(jnp.float32)
        return (w, jnp.zeros(n, jnp.int32)) if with_rounds else w
    if ovf:
        _split_route(interpret)
        return ref.ell_frontier_fused_ref(weights0, in_deg, src, freq,
                                          max_rounds, with_rounds=with_rounds,
                                          ovf=ovf)
    path = _dispatch("ell_fused", interpret)
    if path == "jnp_ref":
        return ref.ell_frontier_fused_ref(weights0, in_deg, src, freq,
                                          max_rounds,
                                          with_rounds=with_rounds)
    blocks = _blocks("ell_fused",
                     autotune.shape_bucket(n, rows, k, max_rounds),
                     {"br": DEFAULT_BR})
    w, rounds = ell_frontier_fused_pallas(weights0, in_deg, src, freq,
                                          max_rounds,
                                          interpret=path == "pallas_interpret",
                                          **blocks)
    return (w, rounds) if with_rounds else w
