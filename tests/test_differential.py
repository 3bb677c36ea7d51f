"""Differential suite: every analytic vs the decompress-then-scan oracle.

TADOC's validation discipline: whatever the compressed-domain engine
computes must equal a plain scan of the decompressed text.  The oracle
(tests/_oracle.py) expands the grammar via ``Grammar.expand`` /
``expand_range`` and recomputes all six ANALYTICS_KINDS with numpy; these
tests assert bit-exact agreement on randomized grammars across the engine's
execution paths:

* single-corpus (``core.analytics``, frontier + leveled traversals);
* batched segment_sum (``run_batched`` method ``frontier`` / ``leveled``);
* batched ELL (``frontier_ell`` / ``leveled_ell`` — the split edge plan);
* device-sharded batched (``distributed.shard_batch.run_sharded``) when
  more than one device is visible — CI's multidevice lane forces 8 CPU
  host devices; tests/_shard_worker.py covers it on single-device hosts
  via a subprocess.

The search subsystem (``repro.search``) is held to the same discipline:
BM25/TF-IDF top-k rankings — document ids AND float32 scores — must be
bit-equal to a numpy recomputation from the decompressed stream, on the
single-corpus, batched, and device-sharded paths (the engine keeps its
transcendental prep on host and its device accumulation FMA-free exactly
so this bar is meetable; see repro/search/engine.py).

Runs without hypothesis via tests/_hypothesis_compat (fixed seeded
examples); the ``slow``-marked test rescales the same check to larger
grammars (CI's scheduled lane; ``DIFF_SCALE`` env var controls size).
"""

import os

import numpy as np
import pytest

import jax

from repro.core import (ANALYTICS_KINDS, Grammar, GrammarBatch,
                        compress_files, expand_range, flatten,
                        inverted_index, ranked_inverted_index, run_batched,
                        sequence_count, sort_words, term_vector, word_count)
from repro.distributed.shard_batch import corpus_mesh, run_sharded
from repro.query import query_corpus, run_batched_query
from repro.search import batched_search, search_corpus
from _hypothesis_compat import given, settings, st
from _oracle import (assert_result_equal, full_stream, oracle, oracle_batch,
                     oracle_query, oracle_search, stream_segments)
from conftest import make_repetitive_files

BATCHED_METHODS = ("frontier", "leveled", "frontier_ell", "leveled_ell",
                   "frontier_fused")
SEARCH_SCHEMES = ("bm25", "tfidf")


def _query_terms(rng, gas):
    """Random multi-term query: mostly in-vocab, some duplicated, one
    guaranteed out-of-vocab id (must contribute exactly nothing)."""
    vmax = max(ga.vocab_size for ga in gas)
    nt = int(rng.integers(1, 7))
    terms = [int(t) for t in rng.integers(0, vmax, nt)]
    terms.append(terms[0])                   # duplicate term
    terms.append(vmax + 17)                  # out-of-vocab
    return tuple(terms)


def _random_predicate(rng, gas, depth: int = 0):
    """Random AND/OR tree over term predicates: mostly in-vocab leaves,
    one guaranteed out-of-vocab leaf at the root (count 0 everywhere —
    must behave exactly like the oracle's zero column)."""
    vmax = max(ga.vocab_size for ga in gas)

    def node(d):
        if d >= 2 or rng.random() < 0.4:
            return ("term", int(rng.integers(0, vmax)),
                    int(rng.integers(0, 4)))
        op = "and" if rng.random() < 0.5 else "or"
        return (op, tuple(node(d + 1)
                          for _ in range(int(rng.integers(1, 4)))))

    return ("or", (node(0), ("term", vmax + 23, 1)))


def _random_phrase(rng, gas, streams):
    """Half the time a window actually present in some corpus (nonzero
    counts), half the time a random token tuple (usually count 0)."""
    l = int(rng.integers(2, 5))
    if rng.random() < 0.5:
        ga = gas[0]
        segs = [s for s in stream_segments(ga, streams[0]) if len(s) >= l]
        if segs:
            seg = segs[int(rng.integers(0, len(segs)))]
            start = int(rng.integers(0, len(seg) - l + 1))
            return tuple(int(x) for x in seg[start: start + l])
    vmax = max(ga.vocab_size for ga in gas)
    return tuple(int(t) for t in rng.integers(0, vmax + 3, l))


def _query_cases(rng, gas, streams):
    """One randomized case per query-operator family (agg gets both ops);
    the kwargs feed the engine dispatchers and ``oracle_query`` alike."""
    return [
        ("filter_count", dict(predicate=_random_predicate(rng, gas))),
        ("agg_terms", dict(terms=_query_terms(rng, gas), agg="sum")),
        ("agg_terms", dict(terms=_query_terms(rng, gas), agg="max")),
        ("phrase_count", dict(terms=_random_phrase(rng, gas, streams))),
    ]


def _random_grammar(rng, scale: int = 1):
    vocab = int(rng.integers(8, 30 * scale + 10))
    n_files = int(rng.integers(1, 3 + scale))
    files = make_repetitive_files(rng, vocab, n_files=n_files)
    g, nf = compress_files(files, vocab)
    return flatten(g, vocab, nf), g, files


def _single(ga, kind, l=3, method="frontier"):
    if kind == "word_count":
        return np.asarray(word_count(ga, method=method))
    if kind == "sort":
        o, c = sort_words(ga, method=method)
        return (np.asarray(o), np.asarray(c))
    if kind == "term_vector":
        return np.asarray(term_vector(ga, method=method))
    if kind == "inverted_index":
        return np.asarray(inverted_index(ga, method=method))
    if kind == "ranked_inverted_index":
        r, c = ranked_inverted_index(ga, method=method)
        return (np.asarray(r), np.asarray(c))
    if kind == "sequence_count":
        return sequence_count(ga, l=l, method=method)
    raise ValueError(kind)


def test_expansion_matches_original_corpus(seeded_rng):
    """The oracle's input is itself differential: the decompressed stream
    must reproduce the raw files (words + per-file splitters) and the two
    expansion APIs must agree."""
    ga, g, files = _random_grammar(seeded_rng)
    parts = []
    for i, f in enumerate(files):
        parts.append(np.asarray(f, np.int64))
        parts.append(np.array([ga.vocab_size + i], np.int64))
    raw = np.concatenate(parts)
    np.testing.assert_array_equal(g.expand(0), raw)
    np.testing.assert_array_equal(full_stream(ga), raw)
    # windowed random access agrees with the full expansion
    lo = len(raw) // 3
    np.testing.assert_array_equal(expand_range(ga, lo, len(raw) // 2),
                                  raw[lo: lo + len(raw) // 2])


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 100_000))
def test_single_corpus_paths_match_oracle(seed):
    rng = np.random.default_rng(seed)
    ga, _, _ = _random_grammar(rng)
    stream = full_stream(ga)
    for kind in ANALYTICS_KINDS:
        want = oracle(ga, kind, stream=stream)
        for method in ("frontier", "leveled"):
            assert_result_equal(_single(ga, kind, method=method), want,
                                kind, f"(single, {method}, seed={seed})")


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 100_000))
def test_batched_paths_match_oracle(seed):
    """All six analytics, four batched execution paths (segment_sum COO and
    dense ELL, frontier and leveled), ragged 3-corpus packs."""
    rng = np.random.default_rng(seed)
    gas = [_random_grammar(rng)[0] for _ in range(3)]
    gb = GrammarBatch.build(gas)
    streams = [full_stream(ga) for ga in gas]
    for kind in ANALYTICS_KINDS:
        wants = [oracle(ga, kind, stream=s) for ga, s in zip(gas, streams)]
        for method in BATCHED_METHODS:
            got = run_batched(gb, kind, method=method, l=3)
            for i, (g_i, w_i) in enumerate(zip(got, wants)):
                assert_result_equal(
                    g_i, w_i, kind,
                    f"(batched {method}, corpus {i}, seed={seed})")


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs a multi-device mesh (CI multidevice lane "
                           "forces 8 CPU host devices)")
@settings(max_examples=3, deadline=None)
@given(st.integers(0, 100_000))
def test_sharded_paths_match_oracle(seed):
    """All six analytics through the device-sharded pack — ragged N=5 so
    shard padding (N < devices or N % devices != 0) is always exercised —
    bit-equal to the decompress-then-scan oracle."""
    rng = np.random.default_rng(seed)
    gas = [_random_grammar(rng)[0] for _ in range(5)]
    mesh = corpus_mesh()
    for kind in ANALYTICS_KINDS:
        wants = oracle_batch(gas, kind)
        for method in ("frontier", "leveled_ell", "frontier_fused"):
            got = run_sharded(gas, kind, mesh=mesh, method=method, l=3)
            for i, (g_i, w_i) in enumerate(zip(got, wants)):
                assert_result_equal(
                    g_i, w_i, kind,
                    f"(sharded {method}, corpus {i}, seed={seed})")


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 100_000))
def test_search_rankings_match_oracle(seed):
    """BM25/TF-IDF top-k rankings — doc ids AND float32 scores — bit-equal
    to the numpy decompress-then-scan oracle on the single-corpus and
    batched paths, for random multi-term queries with duplicates and
    out-of-vocab terms, across traversal methods."""
    rng = np.random.default_rng(seed)
    gas = [_random_grammar(rng)[0] for _ in range(3)]
    gb = GrammarBatch.build(gas)
    terms = _query_terms(rng, gas)
    k = int(rng.integers(1, 9))
    for scheme in SEARCH_SCHEMES:
        wants = [oracle_search(ga, terms, k=k, scheme=scheme) for ga in gas]
        for ga, want in zip(gas, wants):
            assert_result_equal(
                search_corpus(ga, terms, k=k, scheme=scheme), want,
                f"search_{scheme}", f"(single, seed={seed})")
        for method in ("frontier", "leveled", "frontier_ell"):
            got = batched_search(gb, terms, k=k, scheme=scheme,
                                 method=method)
            for i, (g_i, w_i) in enumerate(zip(got, wants)):
                assert_result_equal(
                    g_i, w_i, f"search_{scheme}",
                    f"(batched {method}, corpus {i}, seed={seed}, "
                    f"terms={terms}, k={k})")


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs a multi-device mesh (CI multidevice lane "
                           "forces 8 CPU host devices)")
@settings(max_examples=3, deadline=None)
@given(st.integers(0, 100_000))
def test_sharded_search_rankings_match_oracle(seed):
    """Search through the device-sharded pack (per-shard scoring + top-k,
    host merge) — ragged N=5 exercises shard padding — bit-equal to the
    oracle and to the single-device batched path."""
    rng = np.random.default_rng(seed)
    gas = [_random_grammar(rng)[0] for _ in range(5)]
    gb1 = GrammarBatch.build(gas)
    mesh = corpus_mesh()
    terms = _query_terms(rng, gas)
    k = int(rng.integers(1, 6))
    for kind, scheme in (("search_bm25", "bm25"), ("search_tfidf", "tfidf")):
        wants = [oracle_search(ga, terms, k=k, scheme=scheme) for ga in gas]
        got = run_sharded(gas, kind, mesh=mesh, terms=terms, k=k)
        single = batched_search(gb1, terms, k=k, scheme=scheme)
        for i, (g_i, w_i, s_i) in enumerate(zip(got, wants, single)):
            assert_result_equal(g_i, w_i, kind,
                                f"(sharded, corpus {i}, seed={seed})")
            assert_result_equal(g_i, s_i, kind,
                                f"(sharded vs single-device, corpus {i})")


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 100_000))
def test_query_operators_match_oracle(seed):
    """The composable query tier (filter / aggregate / phrase) bit-equal
    to the decompress-then-scan oracle — file-id sets, per-file and total
    float32 aggregates, float32 phrase counts — on the single-corpus and
    batched paths, across traversal methods.  The phrase path runs the
    sequence-support plans (core/sequence.py), never decompression."""
    rng = np.random.default_rng(seed)
    gas = [_random_grammar(rng)[0] for _ in range(3)]
    streams = [full_stream(ga) for ga in gas]
    gb = GrammarBatch.build(gas)
    for kind, kw in _query_cases(rng, gas, streams):
        wants = [oracle_query(ga, kind, stream=s, **kw)
                 for ga, s in zip(gas, streams)]
        for ga, want in zip(gas, wants):
            assert_result_equal(query_corpus(ga, kind, **kw), want, kind,
                                f"(single, seed={seed}, {kw})")
        for method in ("frontier", "leveled", "frontier_ell"):
            got = run_batched_query(gb, kind, method=method, **kw)
            for i, (g_i, w_i) in enumerate(zip(got, wants)):
                assert_result_equal(
                    g_i, w_i, kind,
                    f"(batched {method}, corpus {i}, seed={seed}, {kw})")


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs a multi-device mesh (CI multidevice lane "
                           "forces 8 CPU host devices)")
@settings(max_examples=3, deadline=None)
@given(st.integers(0, 100_000))
def test_sharded_query_operators_match_oracle(seed):
    """Query operators through the device-sharded pack — ragged N=5 so
    shard padding is always exercised — bit-equal to the oracle and to
    the single-device batched path."""
    rng = np.random.default_rng(seed)
    gas = [_random_grammar(rng)[0] for _ in range(5)]
    streams = [full_stream(ga) for ga in gas]
    gb1 = GrammarBatch.build(gas)
    mesh = corpus_mesh()
    for kind, kw in _query_cases(rng, gas, streams):
        wants = [oracle_query(ga, kind, stream=s, **kw)
                 for ga, s in zip(gas, streams)]
        got = run_sharded(gas, kind, mesh=mesh, **kw)
        single = run_batched_query(gb1, kind, **kw)
        for i, (g_i, w_i, s_i) in enumerate(zip(got, wants, single)):
            assert_result_equal(g_i, w_i, kind,
                                f"(sharded, corpus {i}, seed={seed}, {kw})")
            assert_result_equal(g_i, s_i, kind,
                                f"(sharded vs single-device, corpus {i})")


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 100_000))
def test_appended_corpus_matches_rebuilt_and_oracle(seed):
    """Streaming-ingest differential lane: a corpus grown by
    ``append_files`` vs a from-scratch build of the concatenated file
    list.  The grammars are bit-identical (tests/test_ingest.py), and
    here the *engine outputs* are held to the same bar: all six analytics
    and both search rankings (float32 scores included) bit-equal to the
    rebuilt corpus AND to the decompress-then-scan oracle, on the
    single-corpus and batched paths.  Packing appended + rebuilt into one
    GrammarBatch also proves the appended arrays are first-class pack
    citizens (identical padded rows, identical plans)."""
    from repro.data import CompressedCorpus

    rng = np.random.default_rng(seed)
    vocab = int(rng.integers(8, 40))
    base = make_repetitive_files(rng, vocab,
                                 n_files=int(rng.integers(1, 4)))
    tail = make_repetitive_files(rng, vocab,
                                 n_files=int(rng.integers(1, 4)))
    appended = CompressedCorpus.build(base, vocab).append_files(tail)
    rebuilt = CompressedCorpus.build(base + tail, vocab)
    ga_a, ga_r = appended.ga, rebuilt.ga
    stream = full_stream(ga_a)
    gb = GrammarBatch.build([ga_a, ga_r])
    for kind in ANALYTICS_KINDS:
        want = oracle(ga_r, kind, stream=stream)
        assert_result_equal(_single(ga_a, kind), want, kind,
                            f"(appended single, seed={seed})")
        for method in ("frontier", "frontier_ell"):
            got = run_batched(gb, kind, method=method, l=3)
            assert_result_equal(got[0], want, kind,
                                f"(appended batched {method}, seed={seed})")
            assert_result_equal(got[1], want, kind,
                                f"(rebuilt batched {method}, seed={seed})")
    terms = _query_terms(rng, [ga_a])
    k = int(rng.integers(1, 7))
    for scheme in SEARCH_SCHEMES:
        want = oracle_search(ga_r, terms, k=k, scheme=scheme,
                             stream=stream)
        assert_result_equal(
            search_corpus(appended, terms, k=k, scheme=scheme), want,
            f"search_{scheme}", f"(appended single, seed={seed})")
        got = batched_search(gb, terms, k=k, scheme=scheme)
        for i, g_i in enumerate(got):
            assert_result_equal(g_i, want, f"search_{scheme}",
                                f"(appended batched, row {i}, seed={seed})")


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs a multi-device mesh (CI multidevice lane "
                           "forces 8 CPU host devices)")
@settings(max_examples=2, deadline=None)
@given(st.integers(0, 100_000))
def test_appended_corpus_sharded_matches_oracle(seed):
    """The appended corpus through the device-sharded path: a ragged pack
    of appended corpora (N=3 over an 8-way mesh exercises padding) stays
    bit-equal to the oracle on analytics and search."""
    from repro.data import CompressedCorpus

    rng = np.random.default_rng(seed)
    corpora = []
    for _ in range(3):
        vocab = int(rng.integers(8, 30))
        base = make_repetitive_files(rng, vocab, n_files=2)
        tail = make_repetitive_files(rng, vocab,
                                     n_files=int(rng.integers(1, 3)))
        corpora.append(
            CompressedCorpus.build(base, vocab).append_files(tail))
    gas = [c.ga for c in corpora]
    mesh = corpus_mesh()
    for kind in ("word_count", "term_vector"):
        wants = oracle_batch(gas, kind)
        got = run_sharded(gas, kind, mesh=mesh)
        for i, (g_i, w_i) in enumerate(zip(got, wants)):
            assert_result_equal(g_i, w_i, kind,
                                f"(appended sharded, corpus {i})")
    terms = _query_terms(rng, gas)
    for kind, scheme in (("search_bm25", "bm25"), ("search_tfidf", "tfidf")):
        wants = [oracle_search(ga, terms, k=4, scheme=scheme) for ga in gas]
        got = run_sharded(gas, kind, mesh=mesh, terms=terms, k=4)
        for i, (g_i, w_i) in enumerate(zip(got, wants)):
            assert_result_equal(g_i, w_i, kind,
                                f"(appended sharded, corpus {i})")


@settings(max_examples=4, deadline=None)
@given(st.integers(2, 5), st.integers(0, 100_000))
def test_sequence_count_window_lengths_match_oracle(l, seed):
    rng = np.random.default_rng(seed)
    gas = [_random_grammar(rng)[0] for _ in range(2)]
    wants = [oracle(ga, "sequence_count", l=l) for ga in gas]
    for ga, want in zip(gas, wants):
        assert_result_equal(sequence_count(ga, l=l, method="frontier"),
                            want, "sequence_count", f"(single, l={l})")
    gb = GrammarBatch.build(gas)
    for method in ("frontier", "frontier_ell"):
        got = run_batched(gb, "sequence_count", method=method, l=l)
        for g_i, w_i in zip(got, wants):
            assert_result_equal(g_i, w_i, "sequence_count",
                                f"(batched {method}, l={l}, seed={seed})")


@pytest.mark.slow
def test_differential_slow_larger_grammars(seeded_rng):
    """Same oracle check at larger grammar sizes (scheduled CI lane);
    ``DIFF_SCALE`` scales corpus size, default 3."""
    from repro.data.synthetic import CorpusSpec, make_corpus

    scale = int(os.environ.get("DIFF_SCALE", "3"))
    gas = []
    for i in range(3):
        spec = CorpusSpec(f"diff{i}", n_files=2 + scale,
                          tokens_per_file=400 * scale, vocab=120 * scale,
                          phrase_rate=0.55, n_phrases=30, phrase_len=7,
                          seed=int(seeded_rng.integers(1 << 31)))
        files = make_corpus(spec)
        g, nf = compress_files(files, spec.vocab)
        gas.append(flatten(g, spec.vocab, nf))
    gb = GrammarBatch.build(gas)
    streams = [full_stream(ga) for ga in gas]
    for kind in ANALYTICS_KINDS:
        wants = [oracle(ga, kind, stream=s) for ga, s in zip(gas, streams)]
        for ga, want in zip(gas, wants):
            assert_result_equal(_single(ga, kind), want, kind,
                                "(single, slow)")
        for method in ("frontier", "frontier_ell", "leveled_ell"):
            got = run_batched(gb, kind, method=method, l=3)
            for g_i, w_i in zip(got, wants):
                assert_result_equal(g_i, w_i, kind,
                                    f"(batched {method}, slow)")
    terms = _query_terms(seeded_rng, gas)
    for scheme in SEARCH_SCHEMES:
        wants = [oracle_search(ga, terms, k=10, scheme=scheme, stream=s)
                 for ga, s in zip(gas, streams)]
        got = batched_search(gb, terms, k=10, scheme=scheme)
        for ga, w_i, g_i in zip(gas, wants, got):
            assert_result_equal(g_i, w_i, f"search_{scheme}",
                                "(batched, slow)")
            assert_result_equal(
                search_corpus(ga, terms, k=10, scheme=scheme), w_i,
                f"search_{scheme}", "(single, slow)")
    for kind, kw in _query_cases(seeded_rng, gas, streams):
        wants = [oracle_query(ga, kind, stream=s, **kw)
                 for ga, s in zip(gas, streams)]
        got = run_batched_query(gb, kind, **kw)
        for ga, w_i, g_i in zip(gas, wants, got):
            assert_result_equal(g_i, w_i, kind, f"(batched, slow, {kw})")
            assert_result_equal(query_corpus(ga, kind, **kw), w_i, kind,
                                f"(single, slow, {kw})")
