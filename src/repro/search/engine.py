"""Jitted batched BM25/TF-IDF scoring + top-k over :class:`GrammarBatch`.

One call ranks every file of every corpus in a pack against a multi-term
query — the "ranked inverted index" application family of the TADOC
journal paper promoted to a serving workload, still entirely in the
compressed domain:

1. **Statistics** come from the batched per-file traversal
   (:func:`repro.core.batch.batched_term_vector`): ``tv [N, F, V]`` term
   frequencies, ``dl = tv.sum(V)`` doc lengths, ``df = (tv > 0).sum(F)``
   document frequencies.  They are memoized per (pack, traversal base) on
   the pack's plan cache — recurring search traffic against a cached pack
   pays the traversal once, like the ELL and sequence plans.
2. **Transcendental prep** (idf tables, the BM25 length normalizer) runs
   on host in numpy float32 (:mod:`repro.search.scoring` DESIGN note:
   ``log`` is not bit-stable across libms, so it never runs on device).
3. **Scoring + top-k** is ONE jitted program per pack signature:
   vocab-gather of the query terms' tf columns, the per-(doc, term)
   contribution, a ``fori_loop`` accumulation over term slots, and
   ``kernels.ops.masked_top_k`` (``jax.lax.top_k``: ties resolve to the
   lower file id — deterministic rankings).  The accumulation is a
   ``fori_loop`` over a *materialized* contribution tensor on purpose:
   an unrolled ``score += idf * quot`` lets XLA contract the mul+add into
   an FMA and the result stops being bit-identical to the numpy oracle;
   the loop-carried add keeps every operation an exactly-specified IEEE
   elementwise op (tests/test_differential.py asserts bit equality of
   both rankings and scores).  Division is the exception: TPU's float32
   divide is close to the quotient but not correctly rounded, so the BM25
   quotient goes through :func:`div_rn`, which rounds it as IEEE division
   does on every backend.
4. **Sharded packs** (``gb.mesh``) run the same scoring program through
   ``shard_map`` (:func:`repro.core.batch._sharded_program`): each device
   ranks its own corpus rows — per-shard top-k, no cross-device traffic —
   and the host merge slices per-corpus results exactly like ``unbatch``.

Padding is inert end to end: padded files are masked to ``-inf`` before
top-k (and sliced off by ``min(k, num_files)``), padded/out-of-vocab term
slots contribute exactly ``+0.0`` (zero idf or zero tf), and padded
corpus rows are dropped by ``real_gas``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batch import GrammarBatch, _sharded_program, \
    batched_term_vector
from repro.core.grammar import pow2_bucket
from repro.distributed.shard_batch import shard_batch
from repro.kernels import ops as kops
from repro.obs import plan_stage as _plan_stage
from repro.obs.tracing import device_scope as _device_scope
from repro.obs.tracing import readback as _readback

from .index import SearchIndex, base_method, build_search_index
from .scoring import (DEFAULT_TOP_K, K1P1, SCHEMES, avg_doc_len, bm25_norm,
                      idf, normalize_terms)

__all__ = ["batched_search", "search_corpus", "search_index_topk",
           "search_sharded", "batch_search_stats"]


# ----------------------------------------------------------------------- #
# The jitted scoring + top-k program                                       #
# ----------------------------------------------------------------------- #
def _two_prod(x, y):
    """``(p, e)`` with ``p + e == x * y`` exactly (Dekker's product with
    Veltkamp splitting: plain float32 multiplies and adds, no FMA)."""
    def split(v):
        c = v * jnp.float32(4097.0)
        hi = c - (c - v)
        return hi, v - hi

    p = x * y
    xh, xl = split(x)
    yh, yl = split(y)
    return p, ((xh * yh - p) + xh * yl + xl * yh) + xl * yl


def _residual(a, b, q):
    """``a - q*b`` exactly, for ``q`` within a factor of two of ``a / b``
    (then ``a - p`` is exact by Sterbenz's lemma)."""
    p, e = _two_prod(q, b)
    return (a - p) - e


def round_quotient(a, b, q):
    """The float32 nearest ``a / b`` (ties to even), given an approximate
    quotient ``q``: one Newton step on the exact residual brings ``q``
    within an ulp, then of ``q`` and its two neighbours the one with the
    smallest ``|a - q*b|`` wins.  ``a >= 0``, ``b > 0``, normal
    magnitudes."""
    def even(c):
        return (jax.lax.bitcast_convert_type(c, jnp.int32) & 1) == 0

    best = q + _residual(a, b, q) / b
    r_best = jnp.abs(_residual(a, b, best))
    for c in (jnp.nextafter(best, jnp.float32(-jnp.inf)),
              jnp.nextafter(best, jnp.float32(jnp.inf))):
        r = jnp.abs(_residual(a, b, c))
        take = (r < r_best) | ((r == r_best) & even(c) & ~even(best))
        best = jnp.where(take, c, best)
        r_best = jnp.where(take, r, r_best)
    return best


def div_rn(a, b):
    """``a / b`` rounded as IEEE float32 division rounds it, on any
    backend (module DESIGN note, step 3): on a TPU v5e, about 27% of
    random BM25-shaped float32 quotients come out one or more ulp away
    from numpy's."""
    return round_quotient(a, b, a / b)


@_device_scope("search")
def _score_topk_impl(tv, terms, idf_q, norm, fvalid, k=None, scheme=None):
    """Score ``[n, F]`` docs against ``[n, Q]`` term slots and rank top-k.

    ``terms`` are pre-clipped vocab indices (host prep), ``idf_q`` is 0.0
    on invalid/padded slots, ``norm`` the host-computed BM25 length
    normalizer.  Every op is an exactly-specified IEEE float32 elementwise
    op in a fixed order (module DESIGN note) — the numpy oracle mirrors it
    bit for bit.  shard_map-compatible: batch-only leading axes, no
    cross-row communication.
    """
    tf_q = jnp.take_along_axis(tv, terms[:, None, :], axis=2)   # [n, F, Q]
    if scheme == "bm25":
        quot = div_rn(tf_q * jnp.float32(K1P1), tf_q + norm[:, :, None])
    elif scheme == "tfidf":
        quot = tf_q
    else:
        raise ValueError(f"unknown scoring scheme {scheme!r}; "
                         f"expected one of {SCHEMES}")
    contrib = jnp.moveaxis(idf_q[:, None, :] * quot, 2, 0)      # [Q, n, F]
    # fori over the materialized contribs: keeps adds un-contractible
    score = jax.lax.fori_loop(
        0, contrib.shape[0], lambda j, s: s + contrib[j],
        jnp.zeros(tv.shape[:2], jnp.float32))
    return kops.masked_top_k(score, fvalid, k)


_score_topk = jax.jit(_score_topk_impl, static_argnames=("k", "scheme"))


# ----------------------------------------------------------------------- #
# Pack-level retrieval statistics (memoized like the ELL/sequence plans)   #
# ----------------------------------------------------------------------- #
@dataclass(frozen=True)
class _BatchSearchStats:
    tv: jnp.ndarray       # [N, F_pad, V_pad] device (pack placement)
    norm: jnp.ndarray     # [N, F_pad] device, bm25_norm per corpus
    fvalid: jnp.ndarray   # [N, F_pad] bool device (file < num_files)
    df: np.ndarray        # [N, V_pad] float32 host document frequencies
    nf: np.ndarray        # [N] int64 host true file counts


def batch_search_stats(gb: GrammarBatch,
                       method: str = "frontier") -> _BatchSearchStats:
    """Doc lengths / document frequencies / tf lookups for a whole pack,
    derived from the batched per-file traversal and memoized on the pack
    (key: traversal base) — sharded packs keep the device arrays with the
    pack's placement."""
    m = base_method(method)
    key = ("search", m)
    if key not in gb._plan_cache:
        with _plan_stage("search_stats"):
            tv = batched_term_vector(gb, method=m)
            # dl/df are integer-valued (exact in float32 in any reduce
            # order)
            dl_dev = jnp.sum(tv, axis=2)                        # [N, F_pad]
            with _readback():
                dl = np.asarray(dl_dev, np.float32)
            df_dev = jnp.sum(tv > 0, axis=1)
            with _readback():
                df = np.asarray(df_dev).astype(np.float32)
            nf = gb.num_files.astype(np.int64)
            norm = np.stack([
                bm25_norm(dl[i], avg_doc_len(dl[i], int(nf[i])))
                for i in range(gb.n)]).astype(np.float32)
            fvalid = np.arange(gb.F_pad)[None, :] < nf[:, None]
            gb._plan_cache[key] = _BatchSearchStats(
                tv=tv, norm=gb._place(norm), fvalid=gb._place(fvalid),
                df=df, nf=nf)
    return gb._plan_cache[key]


def _query_arrays(df: np.ndarray, nf: np.ndarray, vocab: int,
                  terms: Tuple[int, ...], scheme: str
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Host query prep: pow2-padded clipped term indices [Qp] and the
    per-corpus idf table [N, Qp] (0.0 on padded / out-of-range slots —
    their contribution must be exactly +0.0)."""
    qp = pow2_bucket(len(terms))
    t = np.full(qp, -1, np.int64)
    t[: len(terms)] = terms
    ok = (t >= 0) & (t < vocab)
    t_clip = np.clip(t, 0, max(vocab - 1, 0)).astype(np.int32)
    df_q = np.where(ok[None, :], df[:, t_clip], np.float32(0.0))
    idf_q = idf(df_q, nf[:, None], scheme)
    idf_q = np.where(ok[None, :], idf_q, np.float32(0.0)).astype(np.float32)
    return t_clip, idf_q


def _check_query(k: int, scheme: str) -> int:
    if scheme not in SCHEMES:
        raise ValueError(f"unknown scoring scheme {scheme!r}; "
                         f"expected one of {SCHEMES}")
    k = int(k)
    if k < 1:
        raise ValueError(f"top-k needs k >= 1, got {k}")
    return k


# ----------------------------------------------------------------------- #
# Entry points                                                             #
# ----------------------------------------------------------------------- #
def batched_search(gb: GrammarBatch, terms: Sequence[int],
                   k: int = DEFAULT_TOP_K, scheme: str = "bm25",
                   method: str = "frontier"
                   ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Rank every corpus in the pack against one query, in ONE program.

    Returns per real corpus ``(doc_ids [k_i], scores [k_i])`` with
    ``k_i = min(k, num_files)``, scores descending, ties broken toward the
    lower file id.  Sharded packs rank per shard and merge on host.
    """
    terms = normalize_terms(terms)
    k = _check_query(k, scheme)
    st = batch_search_stats(gb, method)
    t_clip, idf_q = _query_arrays(st.df, st.nf, gb.V_pad, terms, scheme)
    terms_dev = gb._place(np.tile(t_clip[None, :], (gb.n, 1)))
    idf_dev = gb._place(idf_q)
    # k bucketed to pow2 (<= F_pad) so nearby k values share the compiled
    # program; the per-corpus slice below restores the exact ask
    k_run = min(pow2_bucket(k), gb.F_pad)
    if gb.mesh is not None:
        vals, idx = _sharded_program(
            _score_topk_impl, gb.mesh, (3, 2, 2, 2, 2), (2, 2),
            static=(("k", k_run), ("scheme", scheme)))(
            st.tv, terms_dev, idf_dev, st.norm, st.fvalid)
    else:
        vals, idx = _score_topk(st.tv, terms_dev, idf_dev, st.norm,
                                st.fvalid, k_run, scheme)
    with _readback():
        vals_h = np.asarray(vals)
        idx_h = np.asarray(idx)
    out: List[Tuple[np.ndarray, np.ndarray]] = []
    for i, ga in enumerate(gb.real_gas):
        k_eff = min(k, ga.num_files)
        out.append((idx_h[i, :k_eff].astype(np.int32), vals_h[i, :k_eff]))
    return out


def _index_device_arrays(si: SearchIndex):
    """Device copies of an index's tf/norm/valid, memoized on the index:
    repeat single-corpus traffic pays the [F, V] upload once, like the
    batched path's pack-resident statistics."""
    if "arrays" not in si._device_cache:
        si._device_cache["arrays"] = (
            jnp.asarray(si.tf)[None], jnp.asarray(si.norm)[None],
            jnp.ones((1, si.n_docs), bool))
    return si._device_cache["arrays"]


def search_index_topk(si: SearchIndex, terms: Sequence[int],
                      k: int = DEFAULT_TOP_K, scheme: str = "bm25"
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Rank one corpus through its memoized :class:`SearchIndex` — the
    same jitted scoring program (and the same host query prep) as the
    batched path, at N == 1: results bit-identical to the corpus's row in
    a batched pack."""
    terms = normalize_terms(terms)
    k = _check_query(k, scheme)
    if si.n_docs == 0:
        return np.zeros(0, np.int32), np.zeros(0, np.float32)
    t_clip, idf_q = _query_arrays(si.df[None, :],
                                  np.array([si.n_docs], np.int64),
                                  si.vocab_size, terms, scheme)
    tf_dev, norm_dev, valid_dev = _index_device_arrays(si)
    k_run = min(pow2_bucket(k), si.n_docs)
    vals, idx = _score_topk(
        tf_dev, jnp.asarray(t_clip)[None], jnp.asarray(idf_q),
        norm_dev, valid_dev, k_run, scheme)
    k_eff = min(k, si.n_docs)
    with _readback():
        return (np.asarray(idx)[0, :k_eff].astype(np.int32),
                np.asarray(vals)[0, :k_eff])


def search_corpus(source, terms: Sequence[int], k: int = DEFAULT_TOP_K,
                  scheme: str = "bm25", method: str = "frontier"
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Single-corpus retrieval.  ``source`` is a ``GrammarArrays`` or a
    ``CompressedCorpus`` — the latter's memoized index (and per-file
    traversal weights) are reused across queries."""
    si = (source.search_index(base_method(method))
          if hasattr(source, "search_index")
          else build_search_index(source, method=method))
    return search_index_topk(si, terms, k=k, scheme=scheme)


def search_sharded(gas: Sequence, terms: Sequence[int],
                   k: int = DEFAULT_TOP_K, scheme: str = "bm25",
                   mesh=None, method: str = "frontier", bucket: bool = True
                   ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """One-call device-sharded retrieval: pad + pack + shard + rank (see
    :func:`repro.distributed.shard_batch.shard_batch`); bit-identical to
    :func:`batched_search` on a single device.  Recurring traffic should
    keep the pack (serving layer) instead of re-packing per query."""
    gb = shard_batch(gas, mesh=mesh, bucket=bucket)
    return batched_search(gb, terms, k=k, scheme=scheme, method=method)
