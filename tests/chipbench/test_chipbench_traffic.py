"""Generated inputs depend on the seed alone."""

from __future__ import annotations

import sys
from pathlib import Path

import itertools

import numpy as np
import pytest

# the benchmark is a package at the root of the checkout
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import corpus, spec, traffic

CFG = {"corpora": 5, "files_per_corpus": [2, 4], "tokens_per_file": 120,
       "vocab": 300, "phrase_rate": 0.5, "n_phrases": 6, "phrase_len": 5,
       "history_rate": 0.02, "revisions": 4, "popularity_zipf_s": 1.1}
SEED = 2 ** 31 + 12345          # larger than 32 signed bits hold

#: an open-loop mix (no cell runs one yet): all eleven served kinds
OPEN_MIX = {
    "loop": "open", "rate_qps": 20.0,
    "arrivals": {"burst_factor": 4, "burst_frac": 0.25, "mean_phase_s": 2.0},
    "deadline_frac": 0.5, "deadline_s": 2.0,
    "kinds": {"search_bm25": 0.20, "search_tfidf": 0.10,
              "filter_count": 0.15, "agg_terms": 0.15, "word_count": 0.10,
              "term_vector": 0.05, "inverted_index": 0.05, "sort": 0.05,
              "ranked_inverted_index": 0.05, "sequence_count": 0.05,
              "phrase_count": 0.05},
    "params": {"pool": 64, "pool_zipf_s": 1.1, "head_terms": 256,
               "sequence_l": 3, "phrase_min": 2, "phrase_max": 4},
}


def _files(seed):
    return {s.name: corpus.make_corpus(s)
            for s in corpus.corpus_specs(CFG, seed)}


def test_corpora_per_seed():
    a, b, c = _files(SEED), _files(SEED), _files(SEED + 1)
    assert list(a) == list(b)
    for name in a:
        for x, y in zip(a[name], b[name]):
            np.testing.assert_array_equal(x, y)
    assert any(len(a[n]) != len(c[n]) or not all(
        np.array_equal(x, y) for x, y in zip(a[n], c[n])) for n in a)


def test_text_seed_fixes_the_grammar_shape():
    cfg = dict(CFG, vocab=400, text_seed=7, server={})
    a, b = corpus.build(cfg, SEED, workers=0), corpus.build(cfg, SEED + 1,
                                                           workers=0)
    assert list(a.files) == list(b.files)
    for name in a.files:
        ga, gb = a.stores[name].ga, b.stores[name].ga
        assert (ga.num_rules, ga.num_levels) == (gb.num_rules, gb.num_levels)
        x, y = np.concatenate(a.files[name]), np.concatenate(b.files[name])
        assert not np.array_equal(x, y)
        # one to one: the same words repeat at the same places
        assert len(set(zip(x.tolist(), y.tolist()))) == len(set(x.tolist()))
        assert len(set(x.tolist())) == len(set(y.tolist()))


def test_open_schedule_per_seed():
    mix = OPEN_MIX
    files = _files(SEED)
    s1 = traffic.open_schedule(mix, files, 1.1, SEED, 5.0)
    s2 = traffic.open_schedule(mix, files, 1.1, SEED, 5.0)
    s3 = traffic.open_schedule(mix, files, 1.1, SEED + 1, 5.0)
    assert s1 == s2 and s1 != s3
    assert {k for _, (_, k, _), _ in s1} <= set(mix["kinds"])
    assert all(0 <= t < 5.0 for t, _, _ in s1)
    dl = [d for _, _, d in s1 if d is not None]
    assert dl and set(dl) == {mix["deadline_s"]}


def test_closed_streams_per_seed():
    mix = spec.traffic("analytics-closed")
    files = _files(SEED)

    def take(seed):
        return [list(itertools.islice(s, 20))
                for s in traffic.closed_streams(mix, files, seed)]

    a, b, c = take(SEED), take(SEED), take(SEED + 1)
    assert a == b and a != c
    assert len(a) == len(mix["clients"])
    # each client sends its own kinds, and no kind comes from two clients
    sent = [{k for req in stream for _, k, _ in req} for stream in a]
    assert all(s <= set(own) for s, own in zip(sent, mix["clients"]))
    assert sum(len(s) for s in sent) == len(set().union(*sent)) \
        == len(mix["kinds"])
    # a job is one kind and parameter set over every corpus
    for stream in a:
        for req in stream:
            assert [c for c, _, _ in req] == list(files)
            assert len({(k, str(p)) for _, k, p in req}) == 1
    # a seed changes the parameters, never the kinds or their order
    assert [[r[0][1] for r in s] for s in a] == \
        [[r[0][1] for r in s] for s in c]


def test_clients_split_the_kinds_once():
    mix = dict(spec.traffic("analytics-closed"))
    files = _files(SEED)
    for clients in ([["word_count"]],
                    [list(mix["kinds"]), ["word_count"]]):
        with pytest.raises(ValueError):
            traffic.closed_streams(dict(mix, clients=clients), files, SEED)


def test_warmup_covers_every_kind_and_shape():
    mix = spec.traffic("analytics-closed")
    files = _files(SEED)
    jobs = traffic.warmup_jobs(mix, files, SEED)
    assert jobs == traffic.warmup_jobs(mix, files, SEED)
    kinds = [job[0][1] for job in jobs]
    assert set(kinds) == set(mix["kinds"])
    p = mix["params"]
    lengths = sorted(len(job[0][2]["terms"]) for job in jobs
                     if job[0][1] == "phrase_count")
    assert lengths == list(range(p["phrase_min"], p["phrase_max"] + 1))
    assert all(kinds.count(k) == 1 for k in kinds if k != "phrase_count")
    for job in jobs:
        assert [c for c, _, _ in job] == list(files)
    # every parameter set the window draws has a shape the warm-up sent
    shapes = {(k, repr(sorted((n, traffic._shape(v)) for n, v in q.items())))
              for job in jobs for _, k, q in job[:1]}
    for stream in traffic.closed_streams(mix, files, SEED):
        for job in itertools.islice(stream, 40):
            _, k, q = job[0]
            key = repr(sorted((n, traffic._shape(v)) for n, v in q.items()))
            assert (k, key) in shapes


def test_rotation_holds_the_weights():
    mix = spec.traffic("analytics-closed")
    order = traffic.rotation(mix["kinds"])
    n = len(order)
    for kind, w in mix["kinds"].items():
        assert order.count(kind) == round(w * n)
    # spread out: no kind twice in a row
    assert all(order[i] != order[i + 1] for i in range(n - 1))
    assert traffic.rotation({"a": 0.4, "b": 0.3, "c": 0.3}).count("a") == 4


def test_phrases_come_from_the_corpora():
    mix = OPEN_MIX
    files = _files(SEED)
    pools = traffic.param_pools(mix, files, SEED)
    text = [tuple(f) for fs in files.values() for f in fs]
    for p in pools["phrase_count"]:
        ph = p["terms"]
        assert any(any(f[i:i + len(ph)] == ph for i in range(len(f)))
                   for f in text)
