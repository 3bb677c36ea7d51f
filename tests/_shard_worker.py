"""Worker run in a subprocess with 8 fake host devices: device-sharded
batch execution must be bit-identical to the single-device engine AND to
the decompress-then-scan oracle.

Asserts (exit code is the test result):
  1. run_sharded == oracle == single-device run_batched for all six
     analytics, on ragged shard counts: N=5 (< devices), N=11 (not a
     multiple of 8) — frontier and leveled_ell methods;
  2. pack signatures: two sharded packs of different (same-bucket) corpus
     compositions share a signature (compile-cache reuse across traffic);
  3. server: sharded execution (shard_min_corpora) == mesh=None server,
     sharded_calls counted; a single-corpus query arriving in sharded
     mode (shard_min_corpora=1) is bit-equal too;
  4. queue: target_shards > 1 raises the fill condition to
     chunk_capacity and drains bit-equal to the sync path;
  5. search: BM25/TF-IDF top-k through the sharded pack (per-shard
     scoring + top-k, host merge) bit-equal to the decompress-then-scan
     oracle and the single-device batched path on the same ragged shard
     counts, including the sharded server mode;
  6. ingest: corpora grown by CompressedCorpus.append_files, run through
     the sharded pack (epoch stamps padded across shard-padding rows),
     bit-equal to from-scratch rebuilds of the concatenated files AND a
     sharded server serves post-append data after a mid-traffic append;
  7. query operators: filter_count / agg_terms / phrase_count through the
     sharded pack (per-shard predicate eval, aggregation, sequence-plan
     phrase matching) bit-equal to the decompress-then-scan oracle and
     the single-device batched path on the same ragged shard counts,
     including the sharded server mode;
  8. split ELL plan: a sharded pack whose hub rows overflow unevenly
     across shards (one shared O_pad, so one program per shard) runs every
     ELL method bit-equal to the oracle and the single-device pack.
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))

import numpy as np

import jax

from repro.core import (ANALYTICS_KINDS, GrammarBatch, compress_files,
                        flatten, run_batched)
from repro.distributed.shard_batch import (corpus_mesh, mesh_size,
                                           shard_batch, run_sharded)
from repro.query import run_batched_query
from repro.search import batched_search
from repro.serving.analytics_server import AnalyticsServer, Query
from repro.serving.queue import AsyncAnalyticsServer

from _oracle import (assert_result_equal, full_stream, oracle, oracle_query,
                     oracle_search)

rng = np.random.default_rng(20260801)


def mk(vocab, nf, size):
    files = [rng.integers(0, vocab, size) for _ in range(nf)]
    g, n = compress_files(files, vocab)
    return flatten(g, vocab, n)


def make_corpora(n):
    return [mk(int(rng.integers(25, 80)), int(rng.integers(1, 4)),
               int(rng.integers(80, 300))) for _ in range(n)]


def results_equal(a, b, kind, ctx):
    aa = a if isinstance(a, tuple) else (a,)
    bb = b if isinstance(b, tuple) else (b,)
    assert len(aa) == len(bb), (kind, ctx)
    for x, y in zip(aa, bb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{kind} {ctx}")


def test_sharded_matches_oracle_and_single_device():
    mesh = corpus_mesh()
    assert mesh is not None and mesh_size(mesh) == 8, jax.devices()
    for n in (5, 11):            # N < devices; N not divisible by devices
        gas = make_corpora(n)
        gb1 = GrammarBatch.build(gas)
        streams = [full_stream(ga) for ga in gas]
        for kind in ANALYTICS_KINDS:
            wants = [oracle(ga, kind, stream=s)
                     for ga, s in zip(gas, streams)]
            for method in ("frontier", "leveled_ell", "frontier_fused"):
                got = run_sharded(gas, kind, mesh=mesh, method=method)
                single = run_batched(gb1, kind, method=method)
                assert len(got) == n
                for i, (g_i, w_i, s_i) in enumerate(
                        zip(got, wants, single)):
                    assert_result_equal(
                        g_i, w_i, kind,
                        f"(sharded {method}, N={n}, corpus {i})")
                    results_equal(g_i, s_i, kind,
                                  f"(vs single-device, N={n}, corpus {i})")
    print("sharded == oracle == single-device (ragged N) OK")


def test_shard_signature_reuse():
    mesh = corpus_mesh()
    a = shard_batch(make_corpora(5), mesh)
    b = shard_batch(make_corpora(5), mesh)
    assert a.shards == b.shards == 8
    assert a.signature[-1] == 8
    # same bucketed dims -> same signature -> same compiled programs
    if a.signature == b.signature:
        print("shard signature reuse OK (equal signatures)")
    else:
        # random corpora may land in different buckets; the invariant that
        # MUST hold is padding-to-mesh keeps N a multiple of the shards
        assert a.n % 8 == 0 and b.n % 8 == 0
        print("shard signature reuse OK (different buckets, padded N)")


def test_server_sharded_equals_unsharded():
    gas = {f"c{i}": ga for i, ga in enumerate(make_corpora(18))}
    srv_s = AnalyticsServer(max_batch=4, shard_min_corpora=2)
    srv_1 = AnalyticsServer(max_batch=4, mesh=None)
    for name, ga in gas.items():
        srv_s.register(name, ga)
        srv_1.register(name, ga)
    qs = [Query(f"c{i}", kind) for i in range(18)
          for kind in ("word_count", "term_vector", "sequence_count")]
    for got, want, q in zip(srv_s.run(qs), srv_1.run(qs), qs):
        results_equal(got, want, q.kind, f"(server sharded, {q.corpus})")
    assert srv_s.stats.sharded_calls > 0, srv_s.stats
    assert srv_1.stats.sharded_calls == 0, srv_1.stats

    # a single-corpus query arriving in sharded mode
    srv_one = AnalyticsServer(max_batch=4, shard_min_corpora=1)
    srv_one.register("c0", gas["c0"])
    got = srv_one.run([Query("c0", "word_count")])[0]
    want = srv_1.run([Query("c0", "word_count")])[0]
    results_equal(got, want, "word_count", "(single corpus, sharded mode)")
    assert srv_one.stats.sharded_calls == 1, srv_one.stats
    print("server sharded == unsharded OK "
          f"(sharded_calls={srv_s.stats.sharded_calls})")


def test_queue_target_shards():
    gas = {f"c{i}": ga for i, ga in enumerate(make_corpora(16))}
    srv = AnalyticsServer(max_batch=4, shard_min_corpora=2)
    srv_sync = AnalyticsServer(max_batch=4, mesh=None)
    for name, ga in gas.items():
        srv.register(name, ga)
        srv_sync.register(name, ga)
    assert srv.chunk_capacity(4) == 16
    t = [0.0]
    q = AsyncAnalyticsServer(srv, clock=lambda: t[0], target_shards=4)
    queries = [Query(f"c{i}", "word_count") for i in range(16)]
    futs = [q.submit(qq) for qq in queries]
    q.drain()
    wants = srv_sync.run(queries)
    for f, want, qq in zip(futs, wants, queries):
        results_equal(f.result(timeout=10), want, "word_count",
                      f"(queue target_shards, {qq.corpus})")
    assert srv.stats.sharded_calls > 0, srv.stats
    print("queue target_shards OK "
          f"(flushes={dict(srv.stats.flushes)})")


def test_sharded_search_matches_oracle_and_single_device():
    mesh = corpus_mesh()
    terms = (1, 7, 7, 23, 5000)          # duplicate + out-of-vocab term
    for n in (5, 11):
        gas = make_corpora(n)
        gb1 = GrammarBatch.build(gas)
        streams = [full_stream(ga) for ga in gas]
        for kind, scheme in (("search_bm25", "bm25"),
                             ("search_tfidf", "tfidf")):
            wants = [oracle_search(ga, terms, k=4, scheme=scheme, stream=s)
                     for ga, s in zip(gas, streams)]
            got = run_sharded(gas, kind, mesh=mesh, terms=terms, k=4)
            single = batched_search(gb1, terms, k=4, scheme=scheme)
            assert len(got) == n
            for i, (g_i, w_i, s_i) in enumerate(zip(got, wants, single)):
                assert_result_equal(g_i, w_i, kind,
                                    f"(sharded search, N={n}, corpus {i})")
                results_equal(g_i, s_i, kind,
                              f"(search vs single-device, N={n}, "
                              f"corpus {i})")
    # sharded server mode serves search bit-equal to the unsharded server
    gas = {f"s{i}": ga for i, ga in enumerate(make_corpora(12))}
    srv_s = AnalyticsServer(max_batch=4, shard_min_corpora=2)
    srv_1 = AnalyticsServer(max_batch=4, mesh=None)
    for name, ga in gas.items():
        srv_s.register(name, ga)
        srv_1.register(name, ga)
    qs = [Query(f"s{i}", "search_bm25", terms=terms, k=3)
          for i in range(12)]
    for got, want, q in zip(srv_s.run(qs), srv_1.run(qs), qs):
        results_equal(got, want, q.kind, f"(server sharded search, "
                                         f"{q.corpus})")
    assert srv_s.stats.sharded_calls > 0, srv_s.stats
    print("sharded search == oracle == single-device OK")


def test_sharded_query_operators_match_oracle_and_single_device():
    mesh = corpus_mesh()
    pred = ("or", (("and", (("term", 3, 1), ("term", 7, 2))),
                   ("term", 11, 3), ("term", 5000, 1)))
    cases = [
        ("filter_count", dict(predicate=pred)),
        ("agg_terms", dict(terms=(3, 7, 7, 11, 5000), agg="sum")),
        ("agg_terms", dict(terms=(3, 7, 11), agg="max")),
    ]
    for n in (5, 11):
        gas = make_corpora(n)
        gb1 = GrammarBatch.build(gas)
        streams = [full_stream(ga) for ga in gas]
        # a phrase actually present in corpus 0 (nonzero count somewhere)
        seg0 = streams[0][streams[0] < gas[0].vocab_size]
        phrase = tuple(int(x) for x in seg0[:2])
        for kind, kw in cases + [("phrase_count", dict(terms=phrase))]:
            wants = [oracle_query(ga, kind, stream=s, **kw)
                     for ga, s in zip(gas, streams)]
            got = run_sharded(gas, kind, mesh=mesh, **kw)
            single = run_batched_query(gb1, kind, **kw)
            assert len(got) == n
            for i, (g_i, w_i, s_i) in enumerate(zip(got, wants, single)):
                assert_result_equal(g_i, w_i, kind,
                                    f"(sharded query, N={n}, corpus {i})")
                results_equal(g_i, s_i, kind,
                              f"(query vs single-device, N={n}, "
                              f"corpus {i})")
    # sharded server mode serves query kinds bit-equal to the unsharded
    gas = {f"q{i}": ga for i, ga in enumerate(make_corpora(12))}
    srv_s = AnalyticsServer(max_batch=4, shard_min_corpora=2)
    srv_1 = AnalyticsServer(max_batch=4, mesh=None)
    for name, ga in gas.items():
        srv_s.register(name, ga)
        srv_1.register(name, ga)
    qs = [Query(f"q{i}", kind, **qkw) for i in range(12)
          for kind, qkw in (("filter_count", dict(predicate=pred)),
                            ("agg_terms", dict(terms=(3, 7), agg="max")),
                            ("phrase_count", dict(terms=(3, 7))))]
    for got, want, q in zip(srv_s.run(qs), srv_1.run(qs), qs):
        results_equal(got, want, q.kind,
                      f"(server sharded query, {q.corpus})")
    assert srv_s.stats.sharded_calls > 0, srv_s.stats
    print("sharded query operators == oracle == single-device OK")


def test_sharded_ingest_appended_equals_rebuilt():
    from repro.data import CompressedCorpus

    mesh = corpus_mesh()
    stores, rebuilt = [], []
    for _ in range(5):                   # N=5 < 8 devices: padding + epochs
        vocab = int(rng.integers(25, 60))
        base = [rng.integers(0, vocab, int(rng.integers(60, 150)))
                for _ in range(2)]
        tail = [rng.integers(0, vocab, int(rng.integers(60, 150)))
                for _ in range(int(rng.integers(1, 3)))]
        stores.append(CompressedCorpus.build(base, vocab).append_files(tail))
        rebuilt.append(CompressedCorpus.build(base + tail, vocab))
    gas_a = [c.ga for c in stores]
    gas_r = [c.ga for c in rebuilt]
    # the epoch stamp survives shard padding (pad rows inherit their
    # source row's epoch) and passes against the real-row prefix
    gb = shard_batch(gas_a, mesh, epochs=[c.epoch for c in stores])
    gb.check_epochs([c.epoch for c in stores])
    for kind in ("word_count", "term_vector", "sequence_count"):
        got = run_sharded(gas_a, kind, mesh=mesh)
        want = run_sharded(gas_r, kind, mesh=mesh)
        for i, (g_i, w_i) in enumerate(zip(got, want)):
            results_equal(g_i, w_i, kind,
                          f"(sharded appended vs rebuilt, corpus {i})")

    # sharded server: append mid-traffic, the next sharded flush must
    # serve post-append data (refresh + re-pack on the sharded path too)
    srv = AnalyticsServer(max_batch=4, shard_min_corpora=2)
    srv_ref = AnalyticsServer(max_batch=4, shard_min_corpora=2)
    for i, (s, r) in enumerate(zip(stores, rebuilt)):
        srv.register(f"i{i}", s)
        srv_ref.register(f"i{i}", r)
    qs = [Query(f"i{i}", "word_count") for i in range(5)]
    srv.run(qs)                          # warm the sharded pack cache
    extra = [rng.integers(0, stores[0].ga.vocab_size, 40)]
    stores[0].append_files(extra)
    rebuilt0 = CompressedCorpus.build(
        [stores[0].window(f, 0, int(stores[0].file_lens[f]))
         for f in range(len(stores[0].file_lens))],
        int(stores[0].ga.vocab_size))
    srv_ref.register("i0", rebuilt0)
    got = srv.run(qs)
    want = srv_ref.run(qs)
    for g_i, w_i, q in zip(got, want, qs):
        results_equal(g_i, w_i, q.kind,
                      f"(sharded server post-append, {q.corpus})")
    assert srv.stats.epoch_invalidations >= 1, srv.stats
    print("sharded ingest: appended == rebuilt, post-append serving OK")


def test_sharded_split_plan_matches_oracle():
    from repro.data import synthetic

    mesh = corpus_mesh()
    gas = []
    for seed, tokens, vocab, rate in ((3, 4000, 100000, 0.6),
                                      (5, 3000, 50, 0.0),
                                      (7, 2000, 100000, 0.6)):
        spec = synthetic.CorpusSpec("hub", n_files=2, tokens_per_file=tokens,
                                    vocab=vocab, phrase_rate=rate,
                                    n_phrases=60, phrase_len=10, seed=seed)
        g, nf = compress_files(synthetic.make_corpus(spec), vocab)
        gas.append(flatten(g, vocab, nf))
    gas += make_corpora(2)                 # N=5: three shards hold a hub
    gb = shard_batch(gas, mesh)
    assert gb.ell_split_shape()[2] > 0, gb.ell_split_shape()
    gb1 = GrammarBatch.build(gas)
    streams = [full_stream(ga) for ga in gas]
    for kind in ("word_count", "term_vector", "sequence_count"):
        wants = [oracle(ga, kind, stream=s) for ga, s in zip(gas, streams)]
        for method in ("frontier_ell", "leveled_ell", "frontier_fused"):
            got = run_sharded(gas, kind, mesh=mesh, method=method)
            single = run_batched(gb1, kind, method=method)
            for i, (g_i, w_i, s_i) in enumerate(zip(got, wants, single)):
                assert_result_equal(g_i, w_i, kind,
                                    f"(sharded split {method}, corpus {i})")
                results_equal(g_i, s_i, kind,
                              f"(split vs single-device, corpus {i})")
    print("sharded split ELL plan == oracle == single-device OK")


if __name__ == "__main__":
    test_sharded_matches_oracle_and_single_device()
    test_shard_signature_reuse()
    test_server_sharded_equals_unsharded()
    test_queue_target_shards()
    test_sharded_search_matches_oracle_and_single_device()
    test_sharded_query_operators_match_oracle_and_single_device()
    test_sharded_ingest_appended_equals_rebuilt()
    test_sharded_split_plan_matches_oracle()
    print("SHARDED ALL OK")
