"""Jitted execution of the query operators over :class:`GrammarBatch`.

One call evaluates an operator for every corpus in a pack, in ONE
program, entirely in the compressed domain:

* **filter / aggregate** draw per-file term counts from
  :func:`repro.search.engine.batch_search_stats` — the memoized batched
  per-file traversal the search subsystem already pays for, keyed on the
  pack's plan cache.  Recurring query traffic against a cached pack (the
  serving layer's case) never re-traverses.
* **filter** gathers every predicate leaf's tf column in one
  ``take_along_axis``, compares against the per-leaf thresholds, and
  folds the AND/OR tree (a hashable jit static — one compiled program
  per (pack signature, predicate structure)) with jnp logical ops.
* **aggregate** accumulates the gathered columns with a ``fori_loop``
  over term slots (sum) or a running ``maximum`` (max) — the loop over a
  materialized contribution tensor keeps each add an exactly-specified
  IEEE op, the same discipline as the search scorer.
* **phrase** reuses the pack's memoized sequence plans
  (``core.batch._padded_sequence_plans`` → ``core/sequence.py``
  ``plan_head_tail``/``plan_stream``): window tokens are gathered exactly
  like ``batched_sequence_count``'s counting program, matched against
  the phrase, and the matching windows' rule weights are summed.  The
  paper's §IV-D sequence support — no decompression anywhere.

Sharded packs (``gb.mesh``) run the same programs through ``shard_map``
(:func:`repro.core.batch._sharded_program`): each device evaluates its
own corpus rows, nothing crosses shards, and the host slice drops shard
padding via ``real_gas`` — bit-identical to the unsharded program.

Everything is integer-valued float32 (< 2**24), so every reduce is exact
in any order and each path is bit-equal to the decompress-then-scan
numpy oracle (``tests/_oracle.py``), the repo's standing discipline.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import analytics as _analytics
from repro.core.batch import (GrammarBatch, _padded_sequence_plans,
                              _sharded_program, batched_top_down_weights)
from repro.core.grammar import pow2_bucket
from repro.core.sequence import _K_HEAD, _K_LIT, _K_TAIL
from repro.obs.tracing import device_scope as _device_scope
from repro.obs.tracing import readback as _readback
from repro.search.engine import batch_search_stats
from repro.search.index import base_method

from .ops import (normalize_agg, normalize_phrase, normalize_predicate,
                  predicate_leaves, predicate_mask, predicate_structure)

__all__ = ["QUERY_KINDS", "batched_filter", "batched_agg", "batched_phrase",
           "filter_corpus", "agg_corpus", "phrase_corpus",
           "run_batched_query", "query_corpus"]

# Serving kinds of the query tier (see serving/analytics_server.py):
#   filter_count — predicate filter, per-corpus matching file ids
#   agg_terms    — per-file + cross-corpus sum/max over a term set
#   phrase_count — exact phrase occurrences via sequence plans
QUERY_KINDS = ("filter_count", "agg_terms", "phrase_count")


# ----------------------------------------------------------------------- #
# The jitted programs (shard_map-compatible: batch-only leading axes)       #
# ----------------------------------------------------------------------- #
@_device_scope("query")
def _filter_impl(tv, fvalid, terms, tvalid, thresh, structure=None):
    """bool [n, F] file mask.  ``terms [n, P]`` are pre-clipped leaf term
    ids, ``tvalid`` zeroes counts of out-of-range leaves, ``thresh`` the
    per-leaf minimum counts; ``structure`` is the AND/OR tree over leaf
    slots (static)."""
    cnt = jnp.take_along_axis(tv, terms[:, None, :], axis=2) \
        * tvalid[:, None, :]                                # [n, F, P]
    leaf = cnt >= thresh[:, None, :]

    def fold(node):
        if node[0] == "leaf":
            return leaf[:, :, node[1]]
        kids = [fold(c) for c in node[1]]
        out = kids[0]
        for k in kids[1:]:
            out = (out & k) if node[0] == "and" else (out | k)
        return out

    return fold(structure) & fvalid


_filter = jax.jit(_filter_impl, static_argnames=("structure",))


@_device_scope("query")
def _agg_impl(tv, fvalid, terms, tvalid, op=None):
    """(per_file [n, F], total [n]) float32 aggregates of the term set.

    Padded term slots contribute exactly +0.0 (sum) or never win (max —
    all counts are >= 0); padded files are zeroed before the cross-corpus
    reduce, which is exact for integer-valued float32 in any order.
    """
    cnt = jnp.take_along_axis(tv, terms[:, None, :], axis=2) \
        * tvalid[:, None, :]                                # [n, F, P]
    contrib = jnp.moveaxis(cnt, 2, 0)                       # [P, n, F]
    zeros = jnp.zeros(tv.shape[:2], jnp.float32)
    if op == "sum":
        pf = jax.lax.fori_loop(0, contrib.shape[0],
                               lambda j, s: s + contrib[j], zeros)
        pf = jnp.where(fvalid, pf, 0.0)
        total = jnp.sum(pf, axis=1)
    else:  # "max"
        pf = jax.lax.fori_loop(0, contrib.shape[0],
                               lambda j, s: jnp.maximum(s, contrib[j]),
                               zeros)
        pf = jnp.where(fvalid, pf, 0.0)
        total = jnp.max(pf, axis=1)
    return pf, total


_agg = jax.jit(_agg_impl, static_argnames=("op",))


@_device_scope("query")
def _phrase_impl(head, tail, weights, st_kind, st_lit, st_src, st_idx,
                 st_symj, win_start, win_rule, win_valid, phrase, l=None):
    """float32 [n] exact phrase counts from the pack's sequence plans.

    Window token gather + validity are op-for-op the counting program of
    ``core.batch._count_windows_batched``; instead of the distinct-gram
    segment reduce, matching windows' rule weights are summed directly.
    """
    def one(head, tail, w, kind, lit, src, idx, symj, ws, wr, wv, ph):
        tok = jnp.where(kind == _K_LIT, lit,
                        jnp.where(kind == _K_HEAD, head[src, idx],
                                  jnp.where(kind == _K_TAIL,
                                            tail[src, idx], lit)))
        pos = ws[:, None] + jnp.arange(l)[None, :]
        wtok = tok[pos]                                   # [Nw, l]
        wsym = symj[pos]
        valid = (wtok >= 0).all(axis=1) & (wsym[:, 0] != wsym[:, -1]) & wv
        match = valid & (wtok == ph[None, :]).all(axis=1)
        return jnp.sum(jnp.where(match, w[wr], jnp.float32(0.0)))

    return jax.vmap(one)(head, tail, weights, st_kind, st_lit, st_src,
                         st_idx, st_symj, win_start, win_rule, win_valid,
                         phrase)


_phrase = jax.jit(_phrase_impl, static_argnames=("l",))


# ----------------------------------------------------------------------- #
# Host prep                                                                 #
# ----------------------------------------------------------------------- #
def _leaf_arrays(leaves: Sequence[Tuple[int, int]], vocab: int
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """pow2-padded clipped leaf term ids [P], validity mask [P] float32,
    thresholds [P] float32.  Out-of-vocab leaves gather a padded column
    masked to 0 — their comparison sees the true count of 0."""
    P = pow2_bucket(max(len(leaves), 1))
    t = np.full(P, -1, np.int64)
    th = np.zeros(P, np.float32)
    for j, (term, min_count) in enumerate(leaves):
        t[j] = term
        th[j] = min_count
    ok = (t >= 0) & (t < vocab)
    t_clip = np.clip(t, 0, max(vocab - 1, 0)).astype(np.int32)
    return t_clip, ok.astype(np.float32), th


def _tile(gb: GrammarBatch, row: np.ndarray) -> jnp.ndarray:
    """Broadcast one host row to every pack row, with pack placement."""
    return gb._place(np.tile(row[None, :], (gb.n, 1)))


# ----------------------------------------------------------------------- #
# Batched entry points                                                      #
# ----------------------------------------------------------------------- #
def batched_filter(gb: GrammarBatch, predicate,
                   method: str = "frontier") -> List[np.ndarray]:
    """Per real corpus: ascending int32 file ids satisfying the predicate."""
    pred = normalize_predicate(predicate)
    st = batch_search_stats(gb, method)
    t_clip, ok, th = _leaf_arrays(predicate_leaves(pred), gb.V_pad)
    structure = predicate_structure(pred)
    args = (st.tv, st.fvalid, _tile(gb, t_clip), _tile(gb, ok),
            _tile(gb, th))
    if gb.mesh is not None:
        mask = _sharded_program(_filter_impl, gb.mesh, (3, 2, 2, 2, 2), 2,
                                static=(("structure", structure),))(*args)
    else:
        mask = _filter(*args, structure)
    with _readback():
        mask_h = np.asarray(mask)
    return [np.flatnonzero(mask_h[i, : ga.num_files]).astype(np.int32)
            for i, ga in enumerate(gb.real_gas)]


def batched_agg(gb: GrammarBatch, terms: Sequence[int], op: str = "sum",
                method: str = "frontier"
                ) -> List[Tuple[np.ndarray, np.float32]]:
    """Per real corpus: (per_file [num_files] float32, total float32)."""
    op = normalize_agg(op)
    leaves = [(int(t), 0) for t in terms]
    if not leaves:
        raise ValueError("agg queries need a non-empty terms sequence")
    if any(t < 0 for t, _ in leaves):
        raise ValueError(f"negative term ids are invalid: {tuple(terms)}")
    st = batch_search_stats(gb, method)
    t_clip, ok, _ = _leaf_arrays(leaves, gb.V_pad)
    args = (st.tv, st.fvalid, _tile(gb, t_clip), _tile(gb, ok))
    if gb.mesh is not None:
        pf, total = _sharded_program(_agg_impl, gb.mesh, (3, 2, 2, 2),
                                     (2, 1), static=(("op", op),))(*args)
    else:
        pf, total = _agg(*args, op)
    with _readback():
        pf_h = np.asarray(pf)
        total_h = np.asarray(total)
    return [(pf_h[i, : ga.num_files], np.float32(total_h[i]))
            for i, ga in enumerate(gb.real_gas)]


def batched_phrase(gb: GrammarBatch, phrase: Sequence[int],
                   method: str = "frontier") -> List[np.float32]:
    """Per real corpus: exact float32 occurrence count of the phrase."""
    phrase = normalize_phrase(phrase)
    l = len(phrase)
    weights = batched_top_down_weights(gb, method=method)
    head, tail, stream = _padded_sequence_plans(gb, l)
    ph = gb._place(np.tile(np.asarray(phrase, np.int32)[None, :],
                           (gb.n, 1)))
    if gb.mesh is not None:
        counts = _sharded_program(
            _phrase_impl, gb.mesh,
            (3, 3, 2, 2, 2, 2, 2, 2, 2, 2, 2, 2), 1,
            static=(("l", l),))(head, tail, weights, *stream, ph)
    else:
        counts = _phrase(head, tail, weights, *stream, ph, l)
    with _readback():
        counts_h = np.asarray(counts)
    return [np.float32(counts_h[i]) for i in range(gb.real)]


# ----------------------------------------------------------------------- #
# Single-corpus entry points                                                #
# ----------------------------------------------------------------------- #
def _corpus_tv(source, method: str) -> Tuple[np.ndarray, object]:
    """Dense [F, V] float32 term vector of one corpus; ``source`` is a
    ``GrammarArrays`` or a ``CompressedCorpus`` — the latter's memoized
    per-file traversal weights are reused (same memo the search index
    shares)."""
    m = base_method(method)
    if hasattr(source, "per_file_weights"):
        ga = source.ga
        fw = source.per_file_weights(m)
        tv = _analytics.term_vector(ga, method=m, file_weights=fw)
    else:
        ga = source
        tv = _analytics.term_vector(ga, method=m)
    with _readback():
        return np.asarray(tv, np.float32), ga


def filter_corpus(source, predicate,
                  method: str = "frontier") -> np.ndarray:
    """Ascending int32 file ids of one corpus satisfying the predicate —
    bit-identical to the corpus's row in a batched pack."""
    pred = normalize_predicate(predicate)
    tv, _ = _corpus_tv(source, method)
    return np.flatnonzero(predicate_mask(pred, tv)).astype(np.int32)


def agg_corpus(source, terms: Sequence[int], op: str = "sum",
               method: str = "frontier") -> Tuple[np.ndarray, np.float32]:
    """(per_file [num_files] float32, total float32) for one corpus."""
    op = normalize_agg(op)
    terms = tuple(int(t) for t in terms)
    if not terms:
        raise ValueError("agg queries need a non-empty terms sequence")
    if any(t < 0 for t in terms):
        raise ValueError(f"negative term ids are invalid: {terms}")
    tv, ga = _corpus_tv(source, method)
    F, V = tv.shape
    pf = np.zeros(F, np.float32)
    # mirror the device fori_loop: sequential accumulation over term
    # slots in query order (exact for integer-valued float32 regardless)
    for t in terms:
        cnt = tv[:, t] if t < V else np.zeros(F, np.float32)
        pf = pf + cnt if op == "sum" else np.maximum(pf, cnt)
    if op == "sum":
        total = np.float32(pf.sum(dtype=np.float32))
    else:
        total = np.float32(pf.max()) if F else np.float32(0.0)
    return pf, total


def phrase_corpus(source, phrase: Sequence[int],
                  method: str = "frontier") -> np.float32:
    """Exact float32 phrase count of one corpus, via the single-corpus
    sequence plans (``core/sequence.py``) — reusing the store-memoized
    top-down traversal weights when ``source`` is a CompressedCorpus."""
    phrase = normalize_phrase(phrase)
    l = len(phrase)
    if hasattr(source, "top_down_weights"):
        ga = source.ga
        w = source.top_down_weights(method)
    else:
        ga = source
        w = None
    grams, cnts = _analytics.sequence_count(ga, l=l, method=method,
                                            weights=w)
    grams = np.asarray(grams)
    cnts = np.asarray(cnts, np.float32)
    if grams.size:
        hit = np.nonzero((grams == np.asarray(phrase, grams.dtype))
                         .all(axis=1))[0]
        if hit.size:
            return np.float32(cnts[hit[0]])
    return np.float32(0.0)


# ----------------------------------------------------------------------- #
# Kind dispatchers (serving + distributed layers)                           #
# ----------------------------------------------------------------------- #
def run_batched_query(gb: GrammarBatch, kind: str, predicate=None,
                      terms=None, agg=None,
                      method: str = "frontier") -> List:
    """Dispatch one query kind over the whole pack; per-corpus results
    shaped exactly like the single-corpus functions."""
    if kind == "filter_count":
        return batched_filter(gb, predicate, method=method)
    if kind == "agg_terms":
        return batched_agg(gb, terms, op=normalize_agg(agg), method=method)
    if kind == "phrase_count":
        return batched_phrase(gb, terms, method=method)
    raise ValueError(f"unknown query kind {kind!r}; "
                     f"expected one of {QUERY_KINDS}")


def query_corpus(source, kind: str, predicate=None, terms=None, agg=None,
                 method: str = "frontier"):
    """Single-corpus dispatch, mirroring :func:`run_batched_query`."""
    if kind == "filter_count":
        return filter_corpus(source, predicate, method=method)
    if kind == "agg_terms":
        return agg_corpus(source, terms, op=normalize_agg(agg),
                          method=method)
    if kind == "phrase_count":
        return phrase_corpus(source, terms, method=method)
    raise ValueError(f"unknown query kind {kind!r}; "
                     f"expected one of {QUERY_KINDS}")
