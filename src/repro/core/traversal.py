"""G-TADOC DAG traversals in JAX (the paper's §IV-B execution engine).

The paper's fine-grained GPU scheduling assigns one thread per rule with a
per-rule ``mask``, in/out-edge counters, and a host loop that relaunches the
kernel until a ``stopFlag`` says the DAG is exhausted (Algorithms 1 and 2).

TPU adaptation (DESIGN.md §2): a "thread" becomes a vector lane.  Each
relaunch round becomes one dense gather + segment-reduce over *all* edges,
gated by the mask — identical schedule, identical results, but expressed as
SpMV-shaped ops the VPU/MXU like.  The host relaunch loop becomes
``jax.lax.while_loop`` (the stop flag is ``mask.any()``).

Two engines are provided:

* ``frontier``  — paper-faithful masked rounds (Algorithm 1/2 semantics).
* ``leveled``   — beyond-paper optimization: topological levels are known
  statically (host precomputes them in grammar.py), so each edge is touched
  exactly once, in level order, with zero mask bookkeeping.  This removes
  the O(E) per-round re-scan the masked design pays (see EXPERIMENTS.md
  §Perf/core).

Both produce bit-identical results (tests/test_traversal.py).
"""

from __future__ import annotations

import functools
import weakref
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .grammar import GrammarArrays, pow2_bucket as _pow2_bucket


# ----------------------------------------------------------------------- #
# Top-down: rule weights (occurrence counts of each rule in the corpus).   #
# ----------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("num_rules",))
def _top_down_frontier(edge_parent: jnp.ndarray, edge_child: jnp.ndarray,
                       edge_freq: jnp.ndarray, in_deg: jnp.ndarray,
                       num_rules: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Masked top-down rounds (paper Algorithm 1). Returns (weights, rounds)."""
    R = num_rules
    dtype = jnp.float32

    def cond(state):
        _, _, mask, _, _ = state
        return jnp.any(mask)

    def body(state):
        weight, cur_in, mask, ever, rounds = state
        active_e = mask[edge_parent]
        contrib = jnp.where(active_e, edge_freq.astype(dtype) * weight[edge_parent], 0.0)
        delta = jax.ops.segment_sum(contrib, edge_child, num_segments=R)
        seen = jax.ops.segment_sum(active_e.astype(jnp.int32), edge_child,
                                   num_segments=R)
        weight = weight + delta
        cur_in = cur_in + seen
        new_ready = (cur_in == in_deg) & (~ever)
        return weight, cur_in, new_ready, ever | new_ready, rounds + 1

    weight0 = jnp.zeros(R, dtype).at[0].set(1.0)
    cur0 = jnp.zeros(R, jnp.int32)
    mask0 = (in_deg == 0)                      # root (and only root)
    state = (weight0, cur0, mask0, mask0, jnp.int32(0))
    weight, _, _, _, rounds = jax.lax.while_loop(cond, body, state)
    return weight, rounds


def top_down_weights(ga: GrammarArrays, method: str = "frontier") -> jnp.ndarray:
    """weights[r] == number of times rule r's expansion occurs in the corpus."""
    if method in ("frontier", "top_down", "bottom_up"):
        # Direction selection affects the *analytics* data flow; the weight
        # pass itself is always top-down (weights are defined root-down).
        w, _ = _top_down_frontier(
            jnp.asarray(ga.edge_parent), jnp.asarray(ga.edge_child),
            jnp.asarray(ga.edge_freq), jnp.asarray(ga.in_deg), ga.num_rules)
        return w
    if method in ("leveled", "leveled_ell"):
        # single-corpus leveled: the per-level segments are already gathers
        # over tiny slices; the dense ELL replay only pays off batched.
        return _top_down_leveled(ga)
    if method == "frontier_ell":
        return _top_down_frontier_ell(ga)
    if method == "frontier_fused":
        return _top_down_frontier_fused(ga)
    raise ValueError(f"unknown traversal method {method!r}")


def resolve_single_method(ga: GrammarArrays, method: str,
                          per_file: bool = False) -> str:
    """Predict the single-corpus engine's routing for ``method`` — the N=1
    analogue of :func:`repro.core.batch.resolve_batch_method`, so the
    serving layer can count ELL→segment_sum downgrades on the per-corpus
    path too.  Mirrors the actual dispatch: scalar ``leveled_ell`` always
    runs the N=1 leveled replay (see :func:`top_down_weights`), everything
    else goes through the shared shape gates."""
    if method not in ("frontier_ell", "leveled_ell", "frontier_fused"):
        return method
    if not per_file and method == "leveled_ell":
        return "leveled"
    from .batch import resolve_traversal_method
    K = _pow2_bucket(int(ga.in_deg.max(initial=0)))
    return resolve_traversal_method(
        method, n=1, rows=ga.num_rules, k=K, edges=len(ga.edge_parent),
        per_file=per_file, f=ga.num_files)


def _top_down_frontier_ell(ga: GrammarArrays) -> jnp.ndarray:
    """Masked frontier rounds over the dense per-rule ELL plan.

    The N=1 case of core/batch.py's ``_frontier_weights_batched_ell`` —
    the jitted loop (and its compilation cache) is shared with the batched
    engine; each round is ONE fused ``kernels.ops.ell_propagate_batched``
    call with no scatter (row index == destination rule).  The blocked
    kernels stream weight vectors of any size through VMEM in grid-blocked
    chunks, so there is no rule-count cliff.  Skewed grammars
    whose plan width would exceed ELL_BATCH_MAX_WIDTH take the COO
    frontier instead (the dense plan is O(R * K) memory).
    """
    from repro.kernels import ops as kops
    from .batch import _frontier_weights_batched_ell

    K = _pow2_bucket(int(ga.in_deg.max(initial=0)))
    if (K > kops.ELL_BATCH_MAX_WIDTH
            or ga.num_rules * K > kops.ELL_PLAN_MAX_ENTRIES):
        w, _ = _top_down_frontier(
            jnp.asarray(ga.edge_parent), jnp.asarray(ga.edge_child),
            jnp.asarray(ga.edge_freq), jnp.asarray(ga.in_deg), ga.num_rules)
        return w

    srcj, freqj, in_deg = _ell_plan_single(ga)
    return _frontier_weights_batched_ell(srcj, freqj, in_deg)[0]


def _ell_plan_single(ga: GrammarArrays):
    """Memoized N=1 dense ELL plan (src, freq, in_deg), shared by the
    per-round and fused single-corpus engines (same eviction discipline as
    the other _ENGINE_CACHE entries)."""
    key = ("ell", id(ga))
    entry = _ENGINE_CACHE.get(key)
    if entry is None:
        src, freq = ga.in_edges_ell_dense()
        entry = (jnp.asarray(src)[None],           # [1, R, K]
                 jnp.asarray(freq)[None],
                 jnp.asarray(ga.in_deg)[None])     # [1, R]
        _ENGINE_CACHE[key] = entry
        weakref.finalize(ga, _ENGINE_CACHE.pop, key, None)
    return entry


def _top_down_frontier_fused(ga: GrammarArrays) -> jnp.ndarray:
    """The whole frontier loop in ONE dispatch over the N=1 ELL plan.

    ``ga.num_levels`` is the exact round count the while_loop form needs
    (level-L rules activate in round L+1), so the fused form loses nothing
    to its static bound.  Gates mirror the batched engine: plans too wide /
    too big for the dense layout take the COO frontier; rule counts beyond
    the fused kernel's VMEM state residency take the per-round ELL path.
    """
    from repro.kernels import ops as kops

    K = _pow2_bucket(int(ga.in_deg.max(initial=0)))
    if (K > kops.ELL_BATCH_MAX_WIDTH
            or ga.num_rules * K > kops.ELL_PLAN_MAX_ENTRIES):
        w, _ = _top_down_frontier(
            jnp.asarray(ga.edge_parent), jnp.asarray(ga.edge_child),
            jnp.asarray(ga.edge_freq), jnp.asarray(ga.in_deg), ga.num_rules)
        return w
    if not kops.ell_fused_use_kernel(ga.num_rules):
        return _top_down_frontier_ell(ga)
    from .batch import _frontier_fused_batched

    srcj, freqj, in_deg = _ell_plan_single(ga)
    return _frontier_fused_batched(srcj, freqj, in_deg,
                                   num_levels=ga.num_levels)[0]


_ENGINE_CACHE: Dict = {}


def _top_down_leveled(ga: GrammarArrays) -> jnp.ndarray:
    """Leveled top-down: each edge processed exactly once (static schedule)."""
    key = ("leveled", id(ga))
    if key in _ENGINE_CACHE:
        run, args = _ENGINE_CACHE[key]
        return run(*args)
    (slices, order) = ga.level_edge_slices()
    ep = jnp.asarray(ga.edge_parent[order])
    ec = jnp.asarray(ga.edge_child[order])
    ef = jnp.asarray(ga.edge_freq[order].astype(np.float32))
    R = ga.num_rules

    @jax.jit
    def run(ep, ec, ef):
        weight = jnp.zeros(R, jnp.float32).at[0].set(1.0)
        for (s, e) in slices:          # static python loop: levels are static
            if s == e:
                continue
            contrib = ef[s:e] * weight[ep[s:e]]
            weight = weight + jax.ops.segment_sum(contrib, ec[s:e],
                                                  num_segments=R)
        return weight

    _ENGINE_CACHE[key] = (run, (ep, ec, ef))
    # evict when ga dies: id() values are recycled, and a same-id key must
    # never serve another grammar's schedule (same scheme as frontier_ell)
    weakref.finalize(ga, _ENGINE_CACHE.pop, key, None)
    return run(ep, ec, ef)


# ----------------------------------------------------------------------- #
# Per-file top-down (batched): weights of each rule w.r.t. each file.      #
# ----------------------------------------------------------------------- #
def per_file_weights(ga: GrammarArrays, method: str = "frontier") -> jnp.ndarray:
    """Wf[r, f] == occurrences of rule r inside file f. Shape [R, F].

    The root's processing is replaced by per-file initialization from the
    root-segment edge lists (splitters partition the root body).  The mask
    schedule is *identical* to the global traversal — topology does not
    depend on the propagated payload — so the paper's Algorithm 1 carries
    over with a batched weight vector.

    The ELL methods run the vector-payload [R, F] rounds over the N=1
    dense edge plan (kernels/propagate_vector.py) — the historical silent
    remap to the segment_sum bases is gone; only shape-gate-ineligible
    plans degrade (same valves as the batched engine).  ``frontier_fused``
    runs its per-round ELL base (the fused kernel is scalar-payload).
    """
    if method in ("frontier_ell", "leveled_ell", "frontier_fused"):
        from .batch import resolve_traversal_method
        K = _pow2_bucket(int(ga.in_deg.max(initial=0)))
        method = resolve_traversal_method(
            method, n=1, rows=ga.num_rules, k=K, edges=len(ga.edge_parent),
            per_file=True, f=ga.num_files)
    if method in ("frontier_ell", "leveled_ell"):
        return _per_file_weights_ell(ga, method)
    R, F = ga.num_rules, ga.num_files
    ep = jnp.asarray(ga.edge_parent)
    ec = jnp.asarray(ga.edge_child)
    ef = jnp.asarray(ga.edge_freq)
    in_deg = jnp.asarray(ga.in_deg)

    W0 = jnp.zeros((R, F), jnp.float32)
    W0 = W0.at[ga.fedge_child, ga.fedge_file].add(
        ga.fedge_freq.astype(np.float32))
    # in-edges from the root are consumed by the init above
    root_seen = jnp.asarray(
        np.bincount(ga.edge_child[ga.edge_parent == 0],
                    minlength=ga.num_rules).astype(np.int32))

    if method == "leveled":
        (slices, order) = ga.level_edge_slices()
        epo, eco = ep[jnp.asarray(order)], ec[jnp.asarray(order)]
        efo = ef[jnp.asarray(order)].astype(jnp.float32)

        @jax.jit
        def run(W):
            for (s, e) in slices:
                if s == e:
                    continue
                keep = ga.edge_parent[order][s:e] != 0   # host bool, static
                if not keep.any():
                    continue
                contrib = efo[s:e, None] * W[epo[s:e], :]
                contrib = contrib * jnp.asarray(keep, jnp.float32)[:, None]
                W = W + jax.ops.segment_sum(contrib, eco[s:e], num_segments=R)
            return W

        return run(W0)

    @jax.jit
    def run(W):
        def cond(state):
            _, _, mask, _ = state
            return jnp.any(mask)

        def body(state):
            W, cur_in, mask, ever = state
            active_e = mask[ep] & (ep != 0)
            gathered = W[ep, :] * ef.astype(jnp.float32)[:, None]
            gathered = jnp.where(active_e[:, None], gathered, 0.0)
            delta = jax.ops.segment_sum(gathered, ec, num_segments=R)
            seen = jax.ops.segment_sum(active_e.astype(jnp.int32), ec,
                                       num_segments=R)
            W = W + delta
            cur_in = cur_in + seen
            new_ready = (cur_in == in_deg) & (~ever)
            return W, cur_in, new_ready, ever | new_ready

        mask0 = (root_seen == in_deg) & (in_deg > 0)
        state = (W, root_seen, mask0, mask0 | (in_deg == 0))
        W, _, _, _ = jax.lax.while_loop(cond, body, state)
        return W

    return run(W0)


def _per_file_weights_ell(ga: GrammarArrays, method: str) -> jnp.ndarray:
    """Per-file traversal over the N=1 dense ELL plan with vector-payload
    rounds — the single-corpus case of core/batch.py's per-file ELL
    engines (shared jitted loops + compile cache).  Plan arrays are
    memoized per grammar with the same id-keyed weakref eviction as the
    scalar plan."""
    from .batch import _per_file_ell_batched, _per_file_leveled_ell_batched

    srcj, freqj, in_deg = _ell_plan_single(ga)
    key = ("ell_pf", id(ga))
    entry = _ENGINE_CACHE.get(key)
    if entry is None:
        root_seen = np.bincount(ga.edge_child[ga.edge_parent == 0],
                                minlength=ga.num_rules).astype(np.int32)
        entry = (jnp.asarray(root_seen)[None],     # [1, R]
                 jnp.asarray(ga.fedge_child)[None],
                 jnp.asarray(ga.fedge_file)[None],
                 jnp.asarray(ga.fedge_freq.astype(np.float32))[None],
                 jnp.asarray(ga.level)[None])      # [1, R]
        _ENGINE_CACHE[key] = entry
        # evict when ga dies: id() values are recycled, and a same-id key
        # must never serve another grammar's plan
        weakref.finalize(ga, _ENGINE_CACHE.pop, key, None)
    root_seen, fc, ff, fq, level = entry
    if method == "frontier_ell":
        return _per_file_ell_batched(srcj, freqj, in_deg, root_seen,
                                     fc, ff, fq, F=ga.num_files)[0]
    return _per_file_leveled_ell_batched(srcj, freqj, level, fc, ff, fq,
                                         num_levels=ga.num_levels,
                                         F=ga.num_files)[0]


# ----------------------------------------------------------------------- #
# Bottom-up: local word tables merged leaves -> root (paper Algorithm 2).  #
# ----------------------------------------------------------------------- #
def bottom_up_tables(ga: GrammarArrays) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense local tables C[r, v] = word counts of rule r's full expansion,
    plus the merged global result (the paper's ``reduceResultKernel``:
    root's own words + level-2 children scaled by their root frequencies).

    Dense [R, V] — used for validation and small/medium corpora; the
    production word-count path is the top-down weights + weighted bincount
    (mathematically identical, O(R+T) memory instead of O(R*V)).
    """
    R, V = ga.num_rules, ga.vocab_size
    ep = jnp.asarray(ga.edge_parent)
    ec = jnp.asarray(ga.edge_child)
    ef = jnp.asarray(ga.edge_freq)
    out_deg = jnp.asarray(ga.out_deg)

    C0 = jnp.zeros((R, V), jnp.float32).at[ga.tw_rule, ga.tw_word].add(
        ga.tw_cnt.astype(np.float32))

    @jax.jit
    def run(C):
        def cond(state):
            _, _, mask, _ = state
            return jnp.any(mask)

        def body(state):
            C, cur_out, mask, ever = state
            # Edges whose *child* is active push tables upward.  The paper
            # does NOT accumulate into the root ("the root contains file
            # information", §IV-B bottom-up): the root-level merge happens in
            # reduceResultKernel below.
            active_e = mask[ec] & (ep != 0)
            gathered = C[ec, :] * ef.astype(jnp.float32)[:, None]
            gathered = jnp.where(active_e[:, None], gathered, 0.0)
            delta = jax.ops.segment_sum(gathered, ep, num_segments=R)
            seen = jax.ops.segment_sum(active_e.astype(jnp.int32), ep,
                                       num_segments=R)
            C = C + delta
            cur_out = cur_out + seen
            new_ready = (cur_out == out_deg) & (~ever)
            return C, cur_out, new_ready, ever | new_ready

        mask0 = (out_deg == 0)                     # leaves
        state = (C, jnp.zeros(R, jnp.int32), mask0, mask0)
        C, _, _, _ = jax.lax.while_loop(cond, body, state)
        return C

    C = run(C0)
    # reduceResultKernel: root own words + direct children x root freqs
    root_mask = np.asarray(ga.edge_parent == 0)
    lvl2 = jnp.asarray(ga.edge_child[root_mask])
    lvl2_f = jnp.asarray(ga.edge_freq[root_mask].astype(np.float32))
    result = C[0] + (C[lvl2] * lvl2_f[:, None]).sum(axis=0)
    return C, result


def bottom_up_bounds(ga: GrammarArrays) -> jnp.ndarray:
    """The paper's ``genLocTblBoundKernel``: upper bound on each rule's local
    table size — own unique words + sum of children's bounds (merging can
    only dedup).  Used by the memory planner (core/memory.py).
    """
    R = ga.num_rules
    own = np.bincount(ga.tw_rule, minlength=R).astype(np.float32)
    ep = jnp.asarray(ga.edge_parent)
    ec = jnp.asarray(ga.edge_child)
    out_deg = jnp.asarray(ga.out_deg)

    @jax.jit
    def run(bound):
        def cond(state):
            _, _, mask, _ = state
            return jnp.any(mask)

        def body(state):
            bound, cur_out, mask, ever = state
            active_e = mask[ec]
            contrib = jnp.where(active_e, bound[ec], 0.0)
            delta = jax.ops.segment_sum(contrib, ep, num_segments=R)
            seen = jax.ops.segment_sum(active_e.astype(jnp.int32), ep,
                                       num_segments=R)
            bound = bound + delta
            cur_out = cur_out + seen
            new_ready = (cur_out == out_deg) & (~ever)
            return bound, cur_out, new_ready, ever | new_ready

        mask0 = (out_deg == 0)
        state = (bound, jnp.zeros(R, jnp.int32), mask0, mask0)
        bound, _, _, _ = jax.lax.while_loop(cond, body, state)
        return bound

    return run(jnp.asarray(own))


def traversal_rounds(ga: GrammarArrays) -> int:
    """Number of masked rounds the frontier engine needs (== DAG depth+1)."""
    _, rounds = _top_down_frontier(
        jnp.asarray(ga.edge_parent), jnp.asarray(ga.edge_child),
        jnp.asarray(ga.edge_freq), jnp.asarray(ga.in_deg), ga.num_rules)
    return int(rounds)
