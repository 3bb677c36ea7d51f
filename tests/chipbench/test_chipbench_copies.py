"""The benchmark's copies of the data generator, the arrival generator and
the decompress-then-scan reference agree with the originals they were
copied from."""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

# the benchmark is a package at the root of the checkout
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import corpus, reference, traffic

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def originals():
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "tests"))
    import _oracle
    from benchmarks import _load_trace
    from repro.data import synthetic
    return synthetic, _load_trace, _oracle


@pytest.mark.parametrize("history", [0.0, 0.05])
def test_corpus_copy_matches_synthetic(originals, history):
    synthetic = originals[0]
    kw = dict(name="x", n_files=5, tokens_per_file=300, vocab=3000,
              phrase_rate=0.55, n_phrases=12, phrase_len=6, seed=11,
              history_rate=history, revisions=6, redraw_tail=True)
    mine = corpus.make_corpus(corpus.CorpusSpec(**kw))
    theirs = synthetic.make_corpus(synthetic.CorpusSpec(**kw))
    assert len(mine) == len(theirs)
    for a, b in zip(mine, theirs):
        np.testing.assert_array_equal(a, b)
    assert [f.name for f in dataclasses.fields(corpus.CorpusSpec)] == \
        [f.name for f in dataclasses.fields(synthetic.CorpusSpec)]


def test_client_trace_copy_matches_load_trace(originals):
    lt = originals[1]
    args = (7, 20.0, 15.0, 30, 1.1, 0.5, 0.1, 0.4, 4.0, 0.25, 2.0)
    assert traffic.client_trace(args) == lt.client_trace(args)
    np.testing.assert_array_equal(traffic.zipf_popularity(30, 1.1),
                                  lt.zipf_popularity(30, 1.1))


def test_reference_copy_matches_oracle(originals):
    _oracle = originals[2]
    from repro.data.store import CompressedCorpus

    vocab = 400
    files = corpus.make_corpus(corpus.CorpusSpec(
        "r", n_files=4, tokens_per_file=250, vocab=vocab, phrase_rate=0.5,
        n_phrases=8, phrase_len=5, seed=3, history_rate=0.05, revisions=5,
        redraw_tail=True))
    ga = CompressedCorpus.build(files, vocab).ga
    meta = reference.Meta(vocab, len(files))
    stream = reference.raw_stream(files, vocab)
    phrase = tuple(int(t) for t in files[1][10:13])
    pred = ("and", (("term", 1, 2), ("or", (("term", 2, 1),
                                            ("term", 3, 2)))))
    cases = [("word_count", {}), ("sort", {}), ("term_vector", {}),
             ("inverted_index", {}), ("ranked_inverted_index", {}),
             ("sequence_count", {"l": 3}),
             ("search_bm25", {"terms": (1, 4, 9), "k": 5}),
             ("search_tfidf", {"terms": (2, 7), "k": 10}),
             ("filter_count", {"predicate": pred}),
             ("agg_terms", {"terms": (1, 2, 5), "agg": "sum"}),
             ("agg_terms", {"terms": (1, 2, 5), "agg": "max"}),
             ("phrase_count", {"terms": phrase})]
    for kind, p in cases:
        if kind in ("search_bm25", "search_tfidf"):
            want = _oracle.oracle_search_kind(ga, kind, p["terms"], k=p["k"])
        elif kind in ("filter_count", "agg_terms", "phrase_count"):
            want = _oracle.oracle_query(ga, kind,
                                        predicate=p.get("predicate"),
                                        terms=p.get("terms"),
                                        agg=p.get("agg", "sum"))
        else:
            want = _oracle.oracle(ga, kind, l=p.get("l", 3))
        got = reference.answer(meta, stream, kind, p)
        _oracle.assert_result_equal(got, want, kind)
        assert reference.digest(got) == reference.digest(want), kind
