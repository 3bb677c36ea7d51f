"""Batched ELL propagation: kernel == oracle, ELL engines == segment_sum.

Covers the ISSUE-2 acceptance surface: the fused [N, R, K] kernel against
the jnp reference (interpret mode), the frontier_ell / leveled_ell batched
traversals and all six analytics bit-identical to the segment_sum path on
ragged / empty / size-1 batches, weight vectors straddling the old VMEM
limit, and the occupancy dispatch predicates.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (GrammarBatch, batched_per_file_weights,
                        batched_top_down_weights, compress_files, flatten,
                        run_batched, top_down_weights)
from repro.kernels import ops, ref
from repro.kernels.propagate_batched import ell_propagate_batched_pallas


def _build_corpus(rng, vocab, n_files, size):
    phrase = rng.integers(0, vocab, int(rng.integers(3, 9)))
    files = []
    for _ in range(n_files):
        parts, total = [], 0
        while total < size:
            p = (phrase if rng.random() < 0.5
                 else rng.integers(0, vocab, int(rng.integers(2, 12))))
            parts.append(p)
            total += len(p)
        files.append(np.concatenate(parts)[:size] if parts
                     else np.zeros(0, np.int64))
    g, nf = compress_files(files, vocab)
    return flatten(g, vocab, nf)


@pytest.fixture(scope="module")
def ragged_gb():
    """>= 4 corpora with wildly different R / V / F, incl. an empty one."""
    rng = np.random.default_rng(1234)
    specs = [(7, 1, 40), (50, 4, 300), (400, 6, 900), (15, 2, 120),
             (30, 3, 0)]                       # last corpus: empty files
    gas = [_build_corpus(rng, *s) for s in specs]
    return GrammarBatch.build(gas), gas


# --------------------------------------------------------------- kernel --
@pytest.mark.parametrize("n,rows,k,R", [(1, 64, 1, 10), (3, 100, 4, 50),
                                        (2, 300, 16, 333), (4, 257, 3, 129)])
def test_kernel_matches_ref(n, rows, k, R, rng):
    src = jnp.asarray(rng.integers(0, R, (n, rows, k)).astype(np.int32))
    freq = jnp.asarray(rng.integers(0, 3, (n, rows, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(n, R)).astype(np.float32))
    a = jnp.asarray((rng.random((n, R)) < 0.5).astype(np.float32))
    d, s = ell_propagate_batched_pallas(w, a, src, freq, br=64)
    d_ref, s_ref = ref.ell_propagate_batched_ref(w, a, src, freq)
    np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("wc", [32, 64, 512])
def test_kernel_weight_chunking(wc, rng):
    """Streaming the weight vector through small VMEM chunks must not
    change the result (every source falls in exactly one chunk)."""
    n, rows, k, R = 2, 130, 5, 777
    src = jnp.asarray(rng.integers(0, R, (n, rows, k)).astype(np.int32))
    freq = jnp.asarray(rng.integers(0, 4, (n, rows, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(n, R)).astype(np.float32))
    a = jnp.asarray((rng.random((n, R)) < 0.7).astype(np.float32))
    d, s = ell_propagate_batched_pallas(w, a, src, freq, br=64, wc=wc)
    d_ref, s_ref = ref.ell_propagate_batched_ref(w, a, src, freq)
    np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-4)


def test_kernel_seen_counts_valid_entries_only(rng):
    """seen must count (freq > 0) entries whose source is active — padding
    (src=0, freq=0) never counts even when the root is active."""
    src = jnp.asarray(np.array([[[1, 0], [0, 0], [1, 2]]], np.int32))
    freq = jnp.asarray(np.array([[[2, 0], [0, 0], [1, 3]]], np.float32))
    w = jnp.asarray(np.array([[1.0, 5.0, 7.0]], np.float32))
    a = jnp.asarray(np.array([[1.0, 1.0, 0.0]], np.float32))  # rule 2 off
    d, s = ops.ell_propagate_batched(w, a, src, freq)
    np.testing.assert_allclose(np.asarray(d)[0], [10.0, 0.0, 5.0])
    np.testing.assert_allclose(np.asarray(s)[0], [1.0, 0.0, 1.0])


@pytest.mark.slow
def test_kernel_weights_straddle_old_vmem_limit(rng):
    """[N, R] weights with R > the old 3.5M-rule limit run through the
    blocked batched kernel in interpret mode (no fallback left to hide it)."""
    R = (3 << 20) + 2048
    rows, k = 96, 2
    w = np.zeros((1, R), np.float32)
    hot = rng.integers(0, R, rows * k)
    w[0, hot] = rng.normal(size=rows * k).astype(np.float32)
    src = jnp.asarray(hot.reshape(1, rows, k).astype(np.int32))
    freq = jnp.asarray(rng.integers(1, 4, (1, rows, k)).astype(np.float32))
    a = jnp.asarray((np.arange(R) % 2 == 0).astype(np.float32)[None, :])
    wj = jnp.asarray(w)
    d, s = ell_propagate_batched_pallas(wj, a, src, freq, interpret=True)
    d_ref, s_ref = ref.ell_propagate_batched_ref(wj, a, src, freq)
    np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-4)


def test_wrapper_validation_and_empty():
    with pytest.raises(ValueError):
        ops.ell_propagate_batched(jnp.zeros((2, 4)), jnp.zeros((2, 4)),
                                  jnp.zeros((2, 4, 3), jnp.int32),
                                  jnp.zeros((2, 4, 2)))
    d, s = ops.ell_propagate_batched(jnp.zeros((2, 4)), jnp.zeros((2, 4)),
                                     jnp.zeros((2, 0, 3), jnp.int32),
                                     jnp.zeros((2, 0, 3)))
    assert d.shape == (2, 0) and s.shape == (2, 0)


# ------------------------------------------------------------- dispatch --
def test_ell_batched_dispatch_predicate():
    # tiny batches never amortize a launch
    assert ops.ell_batched_use_ref(100, 1, 32, 4)
    # absurd plan width
    assert ops.ell_batched_use_ref(10_000, 4, 256,
                                   ops.ELL_BATCH_MAX_WIDTH + 1)
    # pathological sparsity: K-padded work >256x the real edges
    assert ops.ell_batched_use_ref(10, 16, 1024, 512)
    # the bench shape (16 corpora, R_pad 256, K 64, ~3k edges) must take ELL
    assert not ops.ell_batched_use_ref(3000, 16, 256, 64)


def test_auto_method_matches_frontier(ragged_gb):
    gb, _ = ragged_gb
    w_auto = np.asarray(batched_top_down_weights(gb, method="auto"))
    w_frontier = np.asarray(batched_top_down_weights(gb, method="frontier"))
    np.testing.assert_array_equal(w_auto, w_frontier)


def test_ell_plan_layout(ragged_gb):
    gb, gas = ragged_gb
    src, freq, level, num_levels = gb.ell_plan()
    K = gb.ell_plan_width()
    assert src.shape == (gb.n, gb.R_pad, K) and freq.shape == src.shape
    assert (K & (K - 1)) == 0                       # power of two
    assert gb.ell_plan() is gb._plan_cache[("ell",)]   # memoized
    srcn, freqn, leveln = (np.asarray(src), np.asarray(freq),
                           np.asarray(level))
    for i, ga in enumerate(gas):
        # per-rule entry counts == in-degrees; padding is freq 0
        np.testing.assert_array_equal(
            (freqn[i, : ga.num_rules] > 0).sum(axis=1), ga.in_deg)
        assert (freqn[i, ga.num_rules:] == 0).all()
        np.testing.assert_array_equal(leveln[i, : ga.num_rules], ga.level)
        assert (leveln[i, ga.num_rules:] == -1).all()
        # edge multiset round-trips: (parent, child, freq) recoverable
        rows, cols = np.nonzero(freqn[i, : ga.num_rules])
        got = sorted(zip(srcn[i][rows, cols].tolist(), rows.tolist(),
                         freqn[i][rows, cols].astype(int).tolist()))
        want = sorted(zip(ga.edge_parent.tolist(), ga.edge_child.tolist(),
                          ga.edge_freq.tolist()))
        assert got == want
    assert num_levels == max(ga.num_levels for ga in gas)


def test_wide_plan_falls_back_to_segment_sum(monkeypatch):
    """Explicit ELL methods must not build an O(R*K) dense plan when a hub
    rule's in-degree exceeds the width gate — they take the segment_sum
    base (identical results) instead."""
    rng = np.random.default_rng(99)
    gas = [_build_corpus(rng, 40, 2, 250), _build_corpus(rng, 30, 2, 200)]
    gb = GrammarBatch.build(gas)
    want = np.asarray(batched_top_down_weights(gb, method="frontier"))
    for gate in ("ELL_BATCH_MAX_WIDTH", "ELL_PLAN_MAX_ENTRIES"):
        monkeypatch.setattr(ops, gate, 0)
        for method in ("frontier_ell", "leveled_ell"):
            got = np.asarray(batched_top_down_weights(gb, method=method))
            np.testing.assert_array_equal(got, want,
                                          err_msg=f"{gate}/{method}")
        assert ("ell",) not in gb._plan_cache       # plan never built
        got_single = np.asarray(top_down_weights(gas[0], "frontier_ell"))
        np.testing.assert_allclose(
            got_single, np.asarray(top_down_weights(gas[0], "frontier")),
            rtol=1e-6)
        monkeypatch.undo()


def test_single_corpus_ell_cache_evicted_on_gc():
    """The id(ga)-keyed plan memo must die with the grammar: a recycled id
    must never serve another grammar's plan."""
    import gc

    from repro.core import traversal

    rng = np.random.default_rng(101)
    ga = _build_corpus(rng, 35, 2, 200)
    w = np.asarray(top_down_weights(ga, "frontier_ell"))
    np.testing.assert_allclose(
        w, np.asarray(top_down_weights(ga, "frontier")), rtol=1e-6)
    key = ("ell", id(ga))
    assert key in traversal._ENGINE_CACHE
    del ga
    gc.collect()
    assert key not in traversal._ENGINE_CACHE


# -------------------------------------------------- engine equivalence --
def test_ell_engines_match_segment_sum_ragged(ragged_gb):
    gb, gas = ragged_gb
    want = np.asarray(batched_top_down_weights(gb, method="frontier"))
    for method in ("frontier_ell", "leveled_ell", "frontier_fused"):
        got = np.asarray(batched_top_down_weights(gb, method=method))
        np.testing.assert_array_equal(got, want, err_msg=method)
    # and against the single-corpus oracle on true sizes
    for i, ga in enumerate(gas):
        np.testing.assert_allclose(
            want[i, : ga.num_rules],
            np.asarray(top_down_weights(ga, "frontier")), rtol=1e-6)


def test_ell_engines_size1_batch():
    rng = np.random.default_rng(77)
    ga = _build_corpus(rng, 60, 3, 400)
    gb = GrammarBatch.build([ga])
    want = np.asarray(batched_top_down_weights(gb, method="frontier"))
    for method in ("frontier_ell", "leveled_ell", "frontier_fused", "auto"):
        got = np.asarray(batched_top_down_weights(gb, method=method))
        np.testing.assert_array_equal(got, want, err_msg=method)


def test_ell_engines_empty_corpus_batch():
    rng = np.random.default_rng(78)
    gas = [_build_corpus(rng, 20, 2, 0), _build_corpus(rng, 25, 2, 150)]
    gb = GrammarBatch.build(gas)
    want = np.asarray(batched_top_down_weights(gb, method="frontier"))
    for method in ("frontier_ell", "leveled_ell", "frontier_fused"):
        got = np.asarray(batched_top_down_weights(gb, method=method))
        np.testing.assert_array_equal(got, want, err_msg=method)


def test_per_file_ell_engines_match_segment_sum(ragged_gb):
    """The per-file ELL methods run REAL vector-payload [R, F] rounds now
    (kernels/propagate_vector.py) — the historical silent remap to the
    segment_sum bases is gone — and stay bit-identical to them.
    ``frontier_fused`` takes its per-round ELL base per-file (the fused
    kernel is scalar-payload)."""
    gb, _ = ragged_gb
    want = np.asarray(batched_per_file_weights(gb, method="frontier"))
    for method in ("frontier_ell", "frontier_fused"):
        got = np.asarray(batched_per_file_weights(gb, method=method))
        np.testing.assert_array_equal(got, want, err_msg=method)
    want_lv = np.asarray(batched_per_file_weights(gb, method="leveled"))
    got_lv = np.asarray(batched_per_file_weights(gb, method="leveled_ell"))
    np.testing.assert_array_equal(got_lv, want_lv)


@pytest.mark.parametrize("kind", ("word_count", "sort", "inverted_index",
                                  "term_vector", "sequence_count",
                                  "ranked_inverted_index"))
def test_all_six_analytics_ell_vs_segment_sum(ragged_gb, kind):
    gb, _ = ragged_gb
    want = run_batched(gb, kind, method="frontier")
    got = run_batched(gb, kind, method="frontier_ell")
    assert len(got) == len(want)
    for w, g in zip(want, got):
        if isinstance(w, tuple):
            for wi, gi in zip(w, g):
                np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
        else:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ------------------------------------------------------ split ELL plan --
# The engines run the split plan (core/batch.py DESIGN note): hub rows hand
# their in-edges to overflow rows.  It must equal the dense plan, the COO
# segment_sum path and the decompress-then-scan oracle bit for bit.
def _phrase_corpus(seed, tokens, vocab, phrase_rate, n_files=2):
    """Zipf text with repeated phrases: its grammar has hub rules used by
    tens of other rules (the shape of Table II's corpora, scaled down)."""
    from repro.data import synthetic

    spec = synthetic.CorpusSpec("hub", n_files=n_files,
                                tokens_per_file=tokens, vocab=vocab,
                                phrase_rate=phrase_rate, n_phrases=60,
                                phrase_len=10, seed=seed)
    files = synthetic.make_corpus(spec)
    g, nf = compress_files(files, vocab)
    return flatten(g, vocab, nf)


def _flat_corpus(n_phrases=12):
    """A grammar whose every rule has in-degree 1 (each phrase rule is
    used only by the root), so no plan can split a row."""
    toks = []
    for i in range(n_phrases):
        p = [10 + 2 * i, 1, 2, 11 + 2 * i]
        toks += p + [3] + p + [4]
    vocab = 10 + 2 * n_phrases
    g, nf = compress_files([np.array(toks)], vocab)
    return flatten(g, vocab, nf)


@pytest.fixture(scope="module")
def hub_gb():
    """Corpora whose hubs overflow unevenly: in-degrees up to 51, 30 and
    43, and one corpus with no row past any K0 the chooser can pick."""
    gas = [_phrase_corpus(3, 8000, 100000, 0.6),
           _phrase_corpus(3, 4000, 100000, 0.6),
           _phrase_corpus(3, 6000, 50, 0.0),
           _flat_corpus()]
    gb = GrammarBatch.build(gas)
    k0, k1, o_pad = gb.ell_split_shape()
    assert o_pad > 0 and k0 < gb.ell_plan_width()
    return gb, gas


def _hub_rows(ga, k0, k1):
    deg = ga.in_deg.astype(np.int64)
    return int(np.where(deg > k0, -(-deg // k1), 0).sum())


def test_split_plan_layout(hub_gb):
    gb, gas = hub_gb
    k0, k1, o_pad = gb.ell_split_shape()
    src, freq, ovf, level, num_levels = gb.ell_split_plan()
    assert gb.ell_split_plan() is gb._plan_cache[("ell_split",)]
    (osrc, ofreq), = ovf
    assert src.shape == (gb.n, gb.R_pad, k0) and freq.shape == src.shape
    assert osrc.shape == (gb.n, o_pad, k1) and ofreq.shape == osrc.shape
    assert gb.ell_plan_slots() == gb.n * (gb.R_pad * k0 + o_pad * k1)
    assert k0 * k1 >= max(int(ga.in_deg.max()) for ga in gas)
    srcn, freqn = np.asarray(src), np.asarray(freq)
    osrcn, ofreqn = np.asarray(osrc), np.asarray(ofreq)
    rows = [_hub_rows(ga, k0, k1) for ga in gas]
    assert max(rows) <= o_pad and len(set(rows)) > 2      # uneven overflow
    for i, ga in enumerate(gas):
        R = ga.num_rules
        ptr = srcn[i] >= gb.R_pad
        # pointer slots: freq 1, one per overflow chunk, on hub rows only
        assert (freqn[i][ptr] == 1).all()
        deg = ga.in_deg.astype(np.int64)
        np.testing.assert_array_equal(
            ptr[:R].sum(axis=1), np.where(deg > k0, -(-deg // k1), 0))
        assert sorted(srcn[i][ptr] - gb.R_pad) == list(range(rows[i]))
        assert (freqn[i, R:] == 0).all() and (ofreqn[i, rows[i]:] == 0).all()
        # every real edge appears once: in its own row, or in an overflow
        # row that its row points to
        owner = np.full(o_pad, -1)
        r_idx, c_idx = np.nonzero(ptr)
        owner[srcn[i][r_idx, c_idx] - gb.R_pad] = r_idx
        got = []
        rr, cc = np.nonzero((freqn[i] > 0) & ~ptr)
        got += zip(srcn[i][rr, cc].tolist(), rr.tolist(),
                   freqn[i][rr, cc].astype(int).tolist())
        oo, kk = np.nonzero(ofreqn[i] > 0)
        got += zip(osrcn[i][oo, kk].tolist(), owner[oo].tolist(),
                   ofreqn[i][oo, kk].astype(int).tolist())
        want = zip(ga.edge_parent.tolist(), ga.edge_child.tolist(),
                   ga.edge_freq.tolist())
        assert sorted(got) == sorted(want)
    np.testing.assert_array_equal(np.asarray(level),
                                  np.asarray(gb.ell_plan()[2]))
    assert num_levels == max(ga.num_levels for ga in gas)


def test_split_rejects_too_many_chunks():
    ga = _phrase_corpus(3, 4000, 100000, 0.6)
    kmax = int(ga.in_deg.max())
    with pytest.raises(ValueError, match="overflow rows"):
        ga.in_edges_ell_split(2, 1, 1024)
    s, f, os_, of = ga.in_edges_ell_split(2, kmax, 1024)
    assert s.shape == (ga.num_rules, 2) and of.shape[1] == kmax


@pytest.mark.parametrize("payload", ["scalar", "per_file"])
def test_split_round_matches_dense_kernels(hub_gb, payload, seeded_rng):
    """One round over the split plan == the interpret-mode Pallas kernel
    (and the jnp reference) over the dense plan, on integer payloads."""
    from repro.kernels.propagate_vector import ell_propagate_vector_pallas

    gb, _ = hub_gb
    src, freq, _, _ = gb.ell_plan()
    s0, f0, ovf, _, _ = gb.ell_split_plan()
    shape = (gb.n, gb.R_pad) + ((gb.F_pad,) if payload == "per_file" else ())
    w = jnp.asarray(seeded_rng.integers(0, 50, shape).astype(np.float32))
    a = jnp.asarray((seeded_rng.random((gb.n, gb.R_pad)) < 0.5)
                    .astype(np.float32))
    if payload == "scalar":
        got = ops.ell_propagate_batched(w, a, s0, f0, ovf=ovf[0])
        kern = ell_propagate_batched_pallas(w, a, src, freq, interpret=True)
        jref = ref.ell_propagate_batched_ref(w, a, src, freq)
    else:
        got = ops.ell_propagate_vector(w, a, s0, f0, ovf=ovf[0])
        kern = ell_propagate_vector_pallas(w, a, src, freq, interpret=True)
        jref = ref.ell_propagate_vector_ref(w, a, src, freq)
    for want in (kern, jref):
        for g_, w_ in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_))
    with pytest.raises(ValueError, match="no Pallas kernel"):
        ops.ell_propagate_batched(w, a, s0, f0, interpret=True, ovf=ovf[0])


@pytest.mark.parametrize("method", ["frontier_ell", "leveled_ell",
                                    "frontier_fused"])
@pytest.mark.parametrize("payload", ["scalar", "per_file"])
def test_split_engines_match_dense_and_coo(hub_gb, method, payload):
    """Every ELL engine on the split plan == its program on the dense plan
    == the COO segment_sum base, bit for bit."""
    from repro.core import batch as B

    gb, _ = hub_gb
    src, freq, level, L = gb.ell_plan()
    base = B.SEGMENT_SUM_BASES[method]
    if payload == "scalar":
        got = batched_top_down_weights(gb, method=method)
        coo = batched_top_down_weights(gb, method=base)
        dense = {"frontier_ell": lambda: B._frontier_weights_batched_ell(
                     src, freq, gb.in_deg),
                 "leveled_ell": lambda: B._leveled_weights_batched_ell(
                     src, freq, level, num_levels=L),
                 "frontier_fused": lambda: B._frontier_fused_batched(
                     src, freq, gb.in_deg, num_levels=L)}[method]()
    else:
        got = batched_per_file_weights(gb, method=method)
        coo = batched_per_file_weights(gb, method=base)
        fargs = (gb.fedge_child, gb.fedge_file, gb.fedge_freq)
        dense = (B._per_file_leveled_ell_batched(
                     src, freq, level, *fargs, num_levels=L, F=gb.F_pad)
                 if method == "leveled_ell" else B._per_file_ell_batched(
                     src, freq, gb.in_deg, gb.root_seen, *fargs,
                     F=gb.F_pad))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(dense))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(coo))


def test_split_fused_matches_dense_fused_kernel(hub_gb):
    """The fused split traversal == the interpret-mode fused Pallas kernel
    on the dense plan (its oracle)."""
    from repro.kernels.propagate_fused import ell_frontier_fused_pallas

    gb, _ = hub_gb
    src, freq, _, L = gb.ell_plan()
    w0 = jnp.zeros((gb.n, gb.R_pad), jnp.float32).at[:, 0].set(1.0)
    want, _ = ell_frontier_fused_pallas(
        w0, gb.in_deg.astype(jnp.float32), src, freq, L, interpret=True)
    got = batched_top_down_weights(gb, method="frontier_fused")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kind", ("word_count", "sort", "inverted_index",
                                  "term_vector", "sequence_count",
                                  "ranked_inverted_index"))
def test_split_analytics_match_oracle(hub_gb, kind):
    from _oracle import assert_result_equal, oracle

    gb, gas = hub_gb
    got = run_batched(gb, kind, method="auto")
    for i, (g, ga) in enumerate(zip(got, gas)):
        assert_result_equal(g, oracle(ga, kind), kind, f"(corpus {i})")


@pytest.mark.parametrize("method", ["frontier_ell", "leveled_ell",
                                    "frontier_fused"])
def test_split_giant_hub_widens_k1(method):
    """A hub too wide for K0 chunks of the cheapest K1 (in-degree 100 >
    K0 * K1 / 2) widens K1; every ELL engine on that plan still equals the
    COO base and the oracle bit for bit."""
    from _oracle import assert_result_equal, oracle
    from repro.core import batch as B

    gas = [_phrase_corpus(3, 30000, 100000, 0.6), _flat_corpus()]
    gb = GrammarBatch.build(gas)
    k0, k1, o_pad = gb.ell_split_shape()
    kmax = max(int(ga.in_deg.max()) for ga in gas)
    assert o_pad and k0 * k1 >= kmax > k0 * (k1 // 2), (kmax, k0, k1)
    base = B.SEGMENT_SUM_BASES[method]
    np.testing.assert_array_equal(
        np.asarray(batched_top_down_weights(gb, method=method)),
        np.asarray(batched_top_down_weights(gb, method=base)))
    np.testing.assert_array_equal(
        np.asarray(batched_per_file_weights(gb, method=method)),
        np.asarray(batched_per_file_weights(gb, method=base)))
    for kind in ("word_count", "term_vector"):
        got = run_batched(gb, kind, method=method)
        for i, (g, ga) in enumerate(zip(got, gas)):
            assert_result_equal(g, oracle(ga, kind), kind, f"(corpus {i})")


def _shape_reference(hists, rows):
    """Brute force over every power-of-two (K0, K1) pair, written out with
    loops over rules: the reference for ``ell_split_shape``."""
    degs = [[d for d, c in enumerate(h) for _ in range(int(c))]
            for h in hists]
    kmax = max(max(d, default=0) for d in degs)
    dense = 1
    while dense < kmax:
        dense *= 2
    best = (rows * dense, dense, dense, 0)
    for k0 in (1 << e for e in range(12)):
        for k1 in (1 << e for e in range(12)):
            if k0 >= dense or k1 > dense:
                continue
            if any(-(-d // k1) > k0 for ds in degs for d in ds if d > k0):
                continue
            need = max(sum(-(-d // k1) for d in ds if d > k0) for ds in degs)
            o_pad = 0
            if need:
                o_pad = 1
                while o_pad < need:
                    o_pad *= 2
            cost = rows * k0 + o_pad * k1
            if cost < best[0]:
                best = (cost, k0, k1, o_pad)
    return best[1:]


def _b_like_hists(seed, n=8):
    """In-degree histograms shaped like Table II dataset B's pack: about
    2,700 rules a corpus, mean about 2.2, p99 about 21, max up to 169."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        deg = np.minimum(rng.zipf(2.4, 2700), 40)
        deg[rng.choice(2700, 6, replace=False)] = [169 - 11 * i, 120, 102,
                                                   90, 75, 64]
        out.append(np.bincount(deg))
    return out


def test_ell_split_shape_is_pure_and_optimal():
    from repro.core.batch import ell_split_shape

    hists = _b_like_hists(7)
    deg = np.concatenate([np.repeat(np.arange(len(h)), h) for h in hists])
    assert 2.0 < deg.mean() < 2.8 and 15 <= np.percentile(deg, 99) <= 25
    got = ell_split_shape(hists, 4096)
    assert got == ell_split_shape([h.copy() for h in hists], 4096)
    assert got == ell_split_shape(hists[::-1], 4096)      # order-free
    assert got == _shape_reference(hists, 4096)
    k0, k1, o_pad = got
    for v in got:
        assert v & (v - 1) == 0
    dense = 8 * 4096 * 256
    split = 8 * (4096 * k0 + o_pad * k1)
    assert dense / split >= 16, (got, dense / split)
    # a lone giant hub widens K1 until it fits in K0 pointer slots
    hub = np.bincount(np.r_[np.ones(3000, int), 2 * np.ones(400, int),
                            [1000]])
    k0, k1, o_pad = ell_split_shape([hub], 4096)
    assert o_pad and -(-1000 // k1) <= k0
    assert (k0, k1, o_pad) == _shape_reference([hub], 4096)


@pytest.mark.parametrize("degrees,rows,want", [
    ([1, 1, 1], 64, (1, 1, 0)),            # nothing can split
    ([4] * 60, 64, (4, 4, 0)),             # every row at the max: dense
    ([0], 8, (1, 1, 0)),                   # no edges at all
])
def test_ell_split_shape_keeps_dense(degrees, rows, want):
    from repro.core.batch import ell_split_shape

    assert ell_split_shape([np.bincount(degrees)], rows) == want


def test_dense_choice_keeps_plan_and_program(monkeypatch):
    """With max in-degree <= K0 the engines build the dense plan's own
    arrays and hand its program exactly the dense arguments."""
    from repro.core import batch as B

    gb = GrammarBatch.build([_flat_corpus(), _flat_corpus(5)])
    K = gb.ell_plan_width()
    assert gb.ell_split_shape() == (K, K, 0)
    src, freq, ovf, level, L = gb.ell_split_plan()
    dsrc, dfreq, dlevel, dL = gb.ell_plan()
    assert ovf == () and (src, freq, level, L) == (dsrc, dfreq, dlevel, dL)
    seen = []
    real = B._frontier_fused_batched

    def spy(*args, **kw):
        seen.append((args, kw))
        return real(*args, **kw)

    monkeypatch.setattr(B, "_frontier_fused_batched", spy)
    batched_top_down_weights(gb, method="frontier_fused")
    (args, kw), = seen
    assert len(args) == 3 and args[0] is dsrc and args[1] is dfreq
    assert kw == {"num_levels": dL}
    assert (real.lower(*args, **kw).as_text()
            == real.lower(dsrc, dfreq, gb.in_deg, num_levels=dL).as_text())


def test_ell_split_counter_and_gauge(hub_gb):
    from repro.obs import global_registry

    fam = global_registry().counter("repro_kernel_dispatch_total", "",
                                    ("decision", "path"))

    def count(path):
        return sum(c.value for k, c in fam.children()
                   if k == ("ell_split", path))

    before = count("split"), count("dense")
    gb = GrammarBatch.build(hub_gb[1])             # fresh pack, no memo
    gb.ell_split_plan()
    gb.ell_split_plan()                            # memoized: no recount
    assert (count("split"), count("dense")) == (before[0] + 1, before[1])
    gauge = global_registry().gauge("repro_ell_plan_slots_per_edge")
    assert gauge.labels().value == pytest.approx(
        gb.ell_plan_slots() / gb.total_edges)
    dense = GrammarBatch.build([_flat_corpus()])
    dense.ell_split_plan()
    assert count("dense") == before[1] + 1


def test_gates_read_split_slot_count(hub_gb, monkeypatch):
    """The occupancy and budget gates judge the split plan's slots, not
    N * R_pad * K_max: a budget or fill between the two admits the pack."""
    from repro.core.batch import resolve_batch_method

    gb, _ = hub_gb
    split = gb.ell_plan_slots()
    dense = gb.n * gb.R_pad * gb.ell_plan_width()
    assert split < dense
    monkeypatch.setattr(ops, "ELL_PLAN_MAX_ENTRIES", split * gb.F_pad)
    for method in ("frontier_ell", "leveled_ell", "frontier_fused"):
        assert resolve_batch_method(gb, method) != "frontier"
        assert resolve_batch_method(gb, method, per_file=True) in (
            "frontier_ell", "leveled_ell")
    monkeypatch.setattr(ops, "ELL_PLAN_MAX_ENTRIES", split - 1)
    assert resolve_batch_method(gb, "frontier_ell") == "frontier"
    monkeypatch.undo()
    fill = gb.total_edges / split
    monkeypatch.setattr(ops, "ELL_BATCH_MIN_FILL",
                        (fill + gb.total_edges / dense) / 2)
    assert resolve_batch_method(gb, "auto") != "frontier"
    monkeypatch.setattr(ops, "ELL_BATCH_MIN_FILL", fill * 1.01)
    assert resolve_batch_method(gb, "auto") == "frontier"
