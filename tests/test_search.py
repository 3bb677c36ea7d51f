"""Compressed search subsystem: index statistics, ranking determinism,
serving integration, and parameter normalization.

Bit-level correctness against the decompress-then-scan oracle lives in
tests/test_differential.py (single / batched / sharded paths); this module
covers the subsystem's own contracts: SearchIndex statistics, the masked
top-k primitive's tie-breaking, memoization on the store and the pack,
query validation, and the serving-layer group-key normalization for the
new search parameters (the regression family next to the l-normalization
tests).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import GrammarBatch, compress_files, flatten
from repro.data import CompressedCorpus
from repro.kernels.ops import masked_top_k
from repro.search import (DEFAULT_TOP_K, SEARCH_KINDS, batch_search_stats,
                          batched_search, build_search_index,
                          normalize_terms, search_corpus, search_index_topk)
from repro.serving import (AnalyticsServer, AsyncAnalyticsServer, Query,
                           SERVED_KINDS)
from _hypothesis_compat import given, settings, st
from _oracle import oracle_search
from conftest import make_repetitive_files


def _mk(rng, vocab=None, n_files=None):
    vocab = vocab or int(rng.integers(10, 50))
    files = make_repetitive_files(rng, vocab,
                                  n_files=n_files or int(rng.integers(1, 5)))
    g, nf = compress_files(files, vocab)
    return flatten(g, vocab, nf), files


# ----------------------------------------------------------------- index --
def test_search_index_statistics_match_raw_files(seeded_rng):
    ga, files = _mk(seeded_rng)
    si = build_search_index(ga)
    assert si.n_docs == len(files) and si.vocab_size == ga.vocab_size
    tv = np.stack([np.bincount(f, minlength=ga.vocab_size)
                   for f in files]).astype(np.float32)
    np.testing.assert_array_equal(si.tf, tv)
    np.testing.assert_array_equal(si.dl,
                                  np.array([len(f) for f in files],
                                           np.float32))
    np.testing.assert_array_equal(si.df, (tv > 0).sum(0).astype(np.float32))
    assert si.avgdl > 0 and si.norm.shape == (len(files),)
    assert (si.norm > 0).all()


def test_search_index_memoized_on_store(seeded_rng):
    _, files = _mk(seeded_rng, vocab=20, n_files=3)
    cc = CompressedCorpus.build(files, vocab_size=20)
    si = cc.search_index()
    assert cc.search_index() is si                       # memoized
    assert ("search_index", "frontier") in cc.cached_weight_keys()
    # the index build shares the memoized per-file traversal
    assert ("per_file", "frontier") in cc.cached_weight_keys()
    # "auto" still collapses onto the frontier base; the ELL methods now
    # run their own vector-payload per-file traversal — a distinct memo
    # entry with bit-identical statistics (frontier_fused shares the
    # frontier_ell base: the fused kernel is scalar-payload)
    assert cc.search_index("auto") is si
    si_ell = cc.search_index("frontier_ell")
    assert si_ell is not si
    assert cc.search_index("frontier_ell") is si_ell     # memoized too
    assert cc.search_index("frontier_fused") is si_ell
    np.testing.assert_array_equal(si_ell.tf, si.tf)
    np.testing.assert_array_equal(si_ell.df, si.df)
    cc.clear_weight_cache()
    assert cc.cached_weight_keys() == ()


def test_batch_search_stats_memoized_on_pack(seeded_rng):
    gas = [_mk(seeded_rng)[0] for _ in range(3)]
    gb = GrammarBatch.build(gas)
    st = batch_search_stats(gb)
    assert batch_search_stats(gb) is st                  # memoized
    assert batch_search_stats(gb, "auto") is st          # same base
    # ELL methods keep their own (bit-identical) stats entry now that the
    # per-file traversal runs on the vector-payload ELL engines
    st_ell = batch_search_stats(gb, "frontier_ell")
    assert st_ell is not st
    assert batch_search_stats(gb, "frontier_fused") is st_ell
    np.testing.assert_array_equal(np.asarray(st_ell.tv), np.asarray(st.tv))
    for i, ga in enumerate(gas):
        si = build_search_index(ga)
        np.testing.assert_array_equal(st.df[i, : ga.vocab_size], si.df)
        assert int(st.nf[i]) == ga.num_files
        np.testing.assert_array_equal(
            np.asarray(st.norm)[i, : ga.num_files], si.norm)
        assert np.asarray(st.fvalid)[i].sum() == ga.num_files


# ---------------------------------------------------------- masked top-k --
def test_masked_top_k_ties_break_toward_lower_index():
    scores = jnp.asarray(np.array([[1.0, 3.0, 3.0, 0.5, 3.0]], np.float32))
    valid = jnp.ones((1, 5), bool)
    vals, idx = masked_top_k(scores, valid, 4)
    np.testing.assert_array_equal(np.asarray(idx)[0], [1, 2, 4, 0])
    np.testing.assert_array_equal(np.asarray(vals)[0], [3, 3, 3, 1])
    # masked slots lose to every finite score
    valid = jnp.asarray(np.array([[True, False, True, True, True]]))
    vals, idx = masked_top_k(scores, valid, 4)
    np.testing.assert_array_equal(np.asarray(idx)[0], [2, 4, 0, 3])
    with pytest.raises(ValueError):
        masked_top_k(scores, valid, 0)
    with pytest.raises(ValueError):
        masked_top_k(scores, valid, 6)


def test_masked_top_k_k_exceeds_valid_count():
    """k larger than the number of VALID slots is legal (only k > M is an
    error): the tail of the row is filled with -inf values whose indices
    walk the masked slots in ascending order (lax.top_k's lower-index
    tie-break over equal -inf)."""
    scores = jnp.asarray(np.array([[2.0, 7.0, 1.0, 5.0]], np.float32))
    valid = jnp.asarray(np.array([[False, True, False, True]]))
    vals, idx = masked_top_k(scores, valid, 4)
    np.testing.assert_array_equal(np.asarray(idx)[0], [1, 3, 0, 2])
    np.testing.assert_array_equal(np.asarray(vals)[0],
                                  [7.0, 5.0, -np.inf, -np.inf])
    # the retrieval layer's contract: everything past the valid count is
    # exactly -inf, so callers can trim on finiteness alone
    assert np.isfinite(np.asarray(vals)[0, :2]).all()


def test_masked_top_k_all_invalid_rows():
    """A row with zero valid slots must yield all--inf values (never a
    stale score) with the deterministic 0..k-1 index walk, and must not
    poison sibling rows in the same batch."""
    scores = jnp.asarray(np.array([[3.0, 1.0, 2.0],
                                   [9.0, 8.0, 7.0]], np.float32))
    valid = jnp.asarray(np.array([[False, False, False],
                                  [True, True, True]]))
    vals, idx = masked_top_k(scores, valid, 2)
    np.testing.assert_array_equal(np.asarray(vals)[0], [-np.inf, -np.inf])
    np.testing.assert_array_equal(np.asarray(idx)[0], [0, 1])
    np.testing.assert_array_equal(np.asarray(vals)[1], [9.0, 8.0])
    np.testing.assert_array_equal(np.asarray(idx)[1], [0, 1])


@given(st.lists(st.integers(0, 5), min_size=1, max_size=12),
       st.integers(1, 12))
@settings(deadline=None, max_examples=25)
def test_masked_top_k_tie_break_deterministic_under_permutation(ints, k):
    """Property: ties resolve toward the LOWER index, so sorting by
    (-value, index) is a complete oracle — including duplicated scores and
    any k up to the axis length."""
    m = len(ints)
    k = min(k, m)
    scores = np.asarray(ints, np.float32)[None]
    valid = (scores >= 1.0)          # 0-scores double as invalid slots
    vals, idx = masked_top_k(jnp.asarray(scores), jnp.asarray(valid), k)
    masked = np.where(valid[0], scores[0], -np.inf)
    order = np.lexsort((np.arange(m), -masked))[:k]
    np.testing.assert_array_equal(np.asarray(idx)[0], order)
    np.testing.assert_array_equal(np.asarray(vals)[0], masked[order])


# ------------------------------------------------------ ranking contracts --
def test_single_and_batched_rankings_bit_identical(seeded_rng):
    gas = [_mk(seeded_rng)[0] for _ in range(4)]
    gb = GrammarBatch.build(gas)
    terms = (1, 5, 5, 2, 10_000)        # duplicate + out-of-vocab
    for scheme in ("bm25", "tfidf"):
        got = batched_search(gb, terms, k=3, scheme=scheme)
        assert len(got) == 4
        for ga, (ids, sc) in zip(gas, got):
            s_ids, s_sc = search_corpus(ga, terms, k=3, scheme=scheme)
            np.testing.assert_array_equal(ids, s_ids)
            np.testing.assert_array_equal(sc, s_sc)
            assert len(ids) == min(3, ga.num_files)
            assert (np.diff(sc) <= 0).all()              # descending


def test_k_clamps_to_file_count_and_buckets_share_programs(seeded_rng):
    ga, files = _mk(seeded_rng, vocab=25, n_files=3)
    ids, sc = search_corpus(ga, (1, 2), k=50)
    assert len(ids) == 3 == len(sc)
    # k=50 ranks every file: the full ordering matches the oracle's
    want_ids, want_sc = oracle_search(ga, (1, 2), k=50)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(sc, want_sc)
    # nearby k values are a prefix of the same ranking
    ids1, sc1 = search_corpus(ga, (1, 2), k=2)
    np.testing.assert_array_equal(ids1, ids[:2])
    np.testing.assert_array_equal(sc1, sc[:2])


def test_zero_file_corpus_returns_empty_ranking():
    """A corpus with no files must rank to empty arrays on both the single
    and batched paths (regression: the single path used to ask top-k for
    one candidate out of a zero-length file axis and crash)."""
    g0, n0 = compress_files([], 10)
    ga0 = flatten(g0, 10, n0)
    assert ga0.num_files == 0
    ids, sc = search_corpus(ga0, (1, 2), k=3)
    assert ids.shape == (0,) and sc.shape == (0,)
    got = batched_search(GrammarBatch.build([ga0]), (1, 2), k=3)
    assert got[0][0].shape == (0,) and got[0][1].shape == (0,)


def test_out_of_vocab_terms_contribute_nothing(seeded_rng):
    ga, _ = _mk(seeded_rng, vocab=15)
    base = search_corpus(ga, (1, 2), k=4)
    with_oov = search_corpus(ga, (1, 2, 999, 10_000), k=4)
    np.testing.assert_array_equal(base[0], with_oov[0])
    np.testing.assert_array_equal(base[1], with_oov[1])


def test_term_validation():
    with pytest.raises(ValueError):
        normalize_terms(None)
    with pytest.raises(ValueError):
        normalize_terms(())
    with pytest.raises(ValueError):
        normalize_terms((1, -2))
    assert normalize_terms([3, 1, 1]) == (3, 1, 1)       # order + dups kept


def test_search_rejects_bad_k_and_scheme(seeded_rng):
    ga, _ = _mk(seeded_rng)
    with pytest.raises(ValueError):
        search_corpus(ga, (1,), k=0)
    with pytest.raises(ValueError):
        search_corpus(ga, (1,), scheme="nope")
    si = build_search_index(ga)
    with pytest.raises(ValueError):
        search_index_topk(si, (1,), scheme="bm42")


# ------------------------------------------------- serving normalization --
def test_group_key_normalizes_terms_and_k():
    """The l-normalization contract, extended to the search parameters:
    terms/k are inert off the search kinds; distinct searches can never
    share a group; omitted k means DEFAULT_TOP_K."""
    assert (Query("a", "word_count", terms=(1, 2), k=5).group_key()
            == Query("a", "word_count").group_key())
    assert Query("a", "word_count", terms=(1, 2)).effective_terms() is None
    assert Query("a", "word_count", k=5).effective_k() is None
    assert (Query("a", "search_bm25", terms=(1, 2)).group_key()
            == Query("a", "search_bm25", terms=(1, 2),
                     k=DEFAULT_TOP_K).group_key())
    assert (Query("a", "search_bm25", terms=(1, 2)).group_key()
            != Query("a", "search_bm25", terms=(2, 1)).group_key())
    assert (Query("a", "search_bm25", terms=(1, 2)).group_key()
            != Query("a", "search_bm25", terms=(1, 2), k=3).group_key())
    assert (Query("a", "search_bm25", terms=(1, 2)).group_key()
            != Query("a", "search_tfidf", terms=(1, 2)).group_key())
    # list terms normalize to a hashable tuple
    assert Query("a", "search_bm25", terms=[1, 2]).terms == (1, 2)


def test_distinct_searches_never_share_a_chunk(seeded_rng):
    """Regression alongside test_word_count_l_variants_share_one_group:
    same-terms searches share ONE batched call; different terms/k/scheme
    split into separate groups and never mis-share results."""
    srv = AnalyticsServer(max_batch=8, mesh=None)
    gas = {}
    for i in range(4):
        ga, _ = _mk(seeded_rng, vocab=30)
        srv.register(f"c{i}", ga)
        gas[f"c{i}"] = ga
    before = srv.stats.batched_calls
    res = srv.run([Query(f"c{i}", "search_bm25", terms=(1, 2), k=4)
                   for i in range(4)])
    assert srv.stats.batched_calls == before + 1         # one group, 1 chunk
    for i in range(4):
        want = search_corpus(gas[f"c{i}"], (1, 2), k=4, scheme="bm25")
        np.testing.assert_array_equal(res[i][0], want[0])
        np.testing.assert_array_equal(res[i][1], want[1])

    before_g = srv.stats.groups
    res = srv.run([Query("c0", "search_bm25", terms=(1, 2), k=4),
                   Query("c0", "search_bm25", terms=(2, 1), k=4),
                   Query("c0", "search_bm25", terms=(1, 2), k=2),
                   Query("c0", "search_tfidf", terms=(1, 2), k=4)])
    assert srv.stats.groups == before_g + 4              # all distinct
    for (ids, sc), (terms, k, scheme) in zip(
            res, [((1, 2), 4, "bm25"), ((2, 1), 4, "bm25"),
                  ((1, 2), 2, "bm25"), ((1, 2), 4, "tfidf")]):
        want = search_corpus(gas["c0"], terms, k=k, scheme=scheme)
        np.testing.assert_array_equal(ids, want[0])
        np.testing.assert_array_equal(sc, want[1])


def test_server_validates_search_queries(seeded_rng):
    srv = AnalyticsServer()
    ga, _ = _mk(seeded_rng)
    srv.register("c", ga)
    assert set(SEARCH_KINDS) < set(SERVED_KINDS)
    with pytest.raises(ValueError):                      # no terms
        srv.run([Query("c", "search_bm25")])
    with pytest.raises(ValueError):                      # empty terms
        srv.run([Query("c", "search_bm25", terms=())])
    with pytest.raises(ValueError):                      # negative term
        srv.run([Query("c", "search_bm25", terms=(1, -3))])
    with pytest.raises(ValueError):                      # bad k
        srv.run([Query("c", "search_bm25", terms=(1,), k=0)])
    with pytest.raises(KeyError):
        srv.run([Query("nope", "search_bm25", terms=(1,))])


def test_execute_chunk_enforces_search_normalization(seeded_rng):
    srv = AnalyticsServer(mesh=None)
    ga, _ = _mk(seeded_rng)
    srv.register("c", ga)
    with pytest.raises(ValueError):                      # stray terms
        srv.execute_chunk("word_count", ["c"], terms=(1, 2))
    with pytest.raises(ValueError):                      # stray k
        srv.execute_chunk("word_count", ["c"], k=5)
    with pytest.raises(ValueError):                      # missing terms
        srv.execute_chunk("search_bm25", ["c"], k=5)
    with pytest.raises(ValueError):                      # missing k
        srv.execute_chunk("search_bm25", ["c"], terms=(1,))


def test_store_single_path_uses_memoized_index(seeded_rng):
    _, files = _mk(seeded_rng, vocab=18, n_files=3)
    cc = CompressedCorpus.build(files, vocab_size=18)
    srv = AnalyticsServer()
    srv.register("solo", cc)
    r1 = srv.run([Query("solo", "search_bm25", terms=(1, 4), k=2)])[0]
    assert ("search_index", "frontier") in cc.cached_weight_keys()
    assert srv.stats.single_calls == 1
    r2 = srv.run([Query("solo", "search_bm25", terms=(1, 4), k=2)])[0]
    np.testing.assert_array_equal(r1[0], r2[0])
    np.testing.assert_array_equal(r1[1], r2[1])
    want = oracle_search(cc.ga, (1, 4), k=2, scheme="bm25")
    np.testing.assert_array_equal(r1[0], want[0])
    np.testing.assert_array_equal(r1[1], want[1])


def test_async_queue_search_matches_sync(seeded_rng):
    srv = AnalyticsServer(max_batch=4, mesh=None)
    for i in range(3):
        ga, _ = _mk(seeded_rng, vocab=25)
        srv.register(f"c{i}", ga)
    clk = [0.0]
    aq = AsyncAnalyticsServer(srv, idle_timeout=100.0, clock=lambda: clk[0])
    queries = ([Query(f"c{i}", "search_bm25", terms=(1, 3), k=3)
                for i in range(3)]
               + [Query("c0", "search_tfidf", terms=(2,), k=2),
                  Query("c1", "word_count")])
    futs = [aq.submit(q) for q in queries]
    aq.drain()
    want = srv.run(queries)
    for f, w, q in zip(futs, want, queries):
        got = f.result(timeout=10)
        if isinstance(w, tuple):
            for a, b in zip(got, w):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(w))
    # flush events carry the normalized search params
    ev = [e for e in aq.flush_log if e.kind == "search_bm25"]
    assert ev and ev[0].terms == (1, 3) and ev[0].k == 3


# ------------------------------------------- correctly rounded quotient --
def test_div_rn_matches_ieee_division(seeded_rng):
    """div_rn equals numpy's (IEEE) float32 division bit for bit, and
    round_quotient pulls a quotient that is a few ulp off — as TPU's divide
    returns for about a quarter of inputs — back to the IEEE one."""
    from repro.search.engine import div_rn, round_quotient

    n = 50_000
    tf = seeded_rng.integers(0, 5000, n).astype(np.float32)
    norm = (seeded_rng.random(n) * 3 + 0.1).astype(np.float32)
    a = tf * np.float32(2.2)
    b = tf + norm
    want = a / b
    np.testing.assert_array_equal(np.asarray(div_rn(a, b)), want)
    pos = a > 0                   # an ulp error only exists off zero
    bits = want[pos].view(np.int32)
    for ulps in (-17, -3, -1, 1, 2, 5):
        off = (bits + ulps).view(np.float32)
        np.testing.assert_array_equal(
            np.asarray(round_quotient(a[pos], b[pos], off)), want[pos])
