#!/usr/bin/env python3
"""Drive the served compressed-analytics path once on a TPU and check it.

Run from the repository root on a host with a TPU:

    python3 chip_smoke.py              # one chip: both deployments, every kind
    python3 chip_smoke.py --chips 4    # the corpus-sharded tier on four chips

Two deployments in the shapes of G-TADOC's Table II (``data/synthetic.py``
``TABLE2``) are generated from ``--seed`` and compressed here, in a pool of
worker processes that never start a JAX backend:

* **A** — many small files spread over many corpora, queried with zipf
  popularity across the corpora;
* **B** — a few large files per corpus, holding revision histories (as a
  wiki's page-history dump does), which make the rule DAG deep.

One-chip mode registers both with an ``AnalyticsServer`` (``method="auto"``,
so the dispatch layer picks the traversal engine per pack) and sends a few
queries of every kind in ``SERVED_KINDS`` through ``AnalyticsServer.run``,
then again through ``AsyncAnalyticsServer.submit`` with deadlines, reading
every future.  It also runs the batched word count on the Pallas bincount
kernel.  Every answer is compared bit for bit with the numpy
decompress-then-scan oracle (``tests/_oracle.py``) computed from the raw
token files, and the device's BM25 quotient with numpy's division.

``--chips 4`` runs only the corpus-sharded tier: deployment A through a
server whose corpus mesh spans all four devices, and the same queries on an
unsharded server; the two must agree bit for bit (and with the oracle).

Every line but the last is a JSON object describing one step: the device,
the cuts from the paper's scale, tokens, rules and device bytes per
deployment, build seconds, first-call and warm seconds per kind, peak device
memory and the kernel dispatch counts.  The last line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The script exits non-zero, and prints no such line, when JAX finds no TPU,
when an answer differs from the oracle, or when any phase raises.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import multiprocessing
import os
import sys
import time
from collections import Counter
from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

#: Deployment shapes.  Per-corpus shape follows TABLE2's A and B, at a
#: vocabulary of real-text order (the Table II corpora are NSF abstracts and
#: Wikipedia) with the zipf tail redrawn, not clipped.  B's files carry
#: revision histories (a wiki page-history dump), which make its rule DAG
#: deep.  Scale is the corpus count and file length, cut so that both
#: together hold ~8M tokens (the host builds them with pure-Python Sequitur
#: inside the run).
DEPLOYMENTS = {
    "A": dict(corpora=192, files=(48, 144), tokens_per_file=220,
              vocab=120_000, phrase_rate=0.55, n_phrases=40, phrase_len=8,
              popularity="zipf"),
    "B": dict(corpora=8, files=(4, 4), tokens_per_file=128_000,
              vocab=1_000_000, phrase_rate=0.6, n_phrases=60, phrase_len=10,
              history_rate=0.0005, revisions=48, popularity="uniform"),
}
#: Queries of each kind per deployment (corpora drawn by popularity).
QUERIES_PER_KIND = 12
#: Zipf exponent of corpus popularity in deployment A.
ZIPF_S = 1.1
#: Relative deadline of every async query; generous, so that a query is
#: shed only if the server stalls.
DEADLINE_S = 600.0
#: Seconds to wait for one async result before the run fails.
RESULT_TIMEOUT_S = 900.0
BUILD_WORKERS = 16
MAX_BATCH = 16
#: Packs the server keeps resident.  One A pack's search state is a dense
#: [N, F_pad, V_pad] float32 term-vector tensor, ~1.6-2.1 GB at A's
#: vocabulary, so the cache is bounded to fit the chip's HBM.
MAX_CACHED_PACKS = 3
CUTS = (
    "scale: corpus count and file length cut from the paper's GB-sized "
    "Table II datasets to the sizes above (~8M tokens in all), since "
    "Sequitur runs on the host inside the run (ROADMAP Reach 4)",
    "vocabulary: A 120,000 and B 1,000,000 words, where the full NSF and "
    "Wikipedia dictionaries run to millions; A's dense [N, F_pad, V_pad] "
    "per-file term vectors must fit one chip's 16 GB HBM per pack",
)


def emit(**fields) -> None:
    print(json.dumps(fields, default=_jsonable), flush=True)


def _jsonable(x):
    if isinstance(x, np.generic):
        return x.item()
    return str(x)


class SmokeFailure(RuntimeError):
    """An answer differed from the oracle, or a run invariant broke."""


# --------------------------------------------------------------- corpora --
@dataclass
class Deployment:
    name: str
    vocab: int
    files: Dict[str, List[np.ndarray]]       # corpus name -> raw token files
    popularity: np.ndarray                   # per-corpus query probability
    stores: Dict[str, object] = None         # corpus name -> CompressedCorpus
    build_s: float = 0.0


def make_files(name: str, seed: int) -> Deployment:
    from repro.data.synthetic import CorpusSpec, make_corpus

    cfg = DEPLOYMENTS[name]
    rng = np.random.default_rng([seed, ord(name)])
    files = {}
    for c in range(cfg["corpora"]):
        lo, hi = cfg["files"]
        spec = CorpusSpec(f"{name}{c}", n_files=int(rng.integers(lo, hi + 1)),
                          tokens_per_file=cfg["tokens_per_file"],
                          vocab=cfg["vocab"], phrase_rate=cfg["phrase_rate"],
                          n_phrases=cfg["n_phrases"],
                          phrase_len=cfg["phrase_len"],
                          seed=int(rng.integers(2 ** 31)),
                          history_rate=cfg.get("history_rate", 0.0),
                          revisions=cfg.get("revisions", 0),
                          redraw_tail=True)
        files[spec.name] = make_corpus(spec)
    n = cfg["corpora"]
    if cfg["popularity"] == "zipf":
        p = 1.0 / np.arange(1, n + 1) ** ZIPF_S
    else:
        p = np.ones(n)
    return Deployment(name, cfg["vocab"], files, p / p.sum())


def _build_one(job):
    """Pool worker: compress one corpus (no JAX backend is started here)."""
    from repro.data.store import CompressedCorpus

    name, files, vocab = job
    cc = CompressedCorpus.build(files, vocab)
    return name, cc.ga, cc.file_starts, cc.file_lens


@contextlib.contextmanager
def _env(**values):
    old = {k: os.environ.get(k) for k in values}
    os.environ.update(values)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def build_stores(deps: Sequence[Deployment]) -> None:
    """Compress every corpus in a spawn pool.  The workers see
    ``JAX_PLATFORMS=cpu``: only this process may hold the chip."""
    from repro.data.store import CompressedCorpus

    jobs = [(c, f, d.vocab) for d in deps for c, f in d.files.items()]
    # largest first, so the long builds start before the pool drains
    jobs.sort(key=lambda j: -sum(len(f) for f in j[1]))
    workers = max(1, min(BUILD_WORKERS, os.cpu_count() or 1, len(jobs)))
    t0 = time.perf_counter()
    with _env(JAX_PLATFORMS="cpu"):
        pool = multiprocessing.get_context("spawn").Pool(workers)
    with pool:
        built = {name: (ga, starts, lens) for name, ga, starts, lens
                 in pool.imap_unordered(_build_one, jobs)}
    elapsed = time.perf_counter() - t0
    for d in deps:
        d.stores = {c: CompressedCorpus(ga=built[c][0],
                                        file_starts=built[c][1],
                                        file_lens=built[c][2])
                    for c in d.files}
        d.build_s = elapsed
    emit(step="build", workers=workers, corpora=len(jobs),
         tokens=int(sum(len(f) for j in jobs for f in j[1])),
         seconds=elapsed,
         tokens_per_s=sum(len(f) for j in jobs for f in j[1]) / elapsed)


def describe(d: Deployment) -> None:
    cfg = DEPLOYMENTS[d.name]
    gas = [s.ga for s in d.stores.values()]
    emit(step="deployment", deployment=d.name, shape=cfg,
         source=f"G-TADOC Table II dataset {d.name} shape "
                f"(repro.data.synthetic.TABLE2['{d.name}'])",
         cuts=CUTS,
         corpora=len(gas),
         files=int(sum(g.num_files for g in gas)),
         tokens=int(sum(s.total_tokens for s in d.stores.values())),
         rules=int(sum(g.num_rules for g in gas)),
         max_dag_depth=int(max(g.num_levels for g in gas)),
         build_s=d.build_s)


# --------------------------------------------------------------- queries --
def make_queries(d: Deployment, seed: int):
    """A few queries of every served kind; corpora drawn by popularity.
    Parameterized kinds share their parameters across corpora, so each
    group batches like real traffic."""
    from repro.serving import Query
    from repro.serving.analytics_server import SERVED_KINDS

    rng = np.random.default_rng([seed, ord(d.name), 1])
    names = list(d.files)
    hot = names[int(np.argmax(d.popularity))]
    hot_file = max(d.files[hot], key=len)

    def corpora():
        k = min(QUERIES_PER_KIND, len(names))
        return list(rng.choice(names, size=k, replace=False,
                               p=d.popularity))

    def terms(n):
        return tuple(int(t) for t in rng.integers(0, 64, n))

    queries = []
    for kind in SERVED_KINDS:
        params = [{}]
        if kind == "sequence_count":
            params = [{"l": 3}]
        elif kind in ("search_bm25", "search_tfidf"):
            params = [{"terms": terms(3), "k": 10}, {"terms": terms(2),
                                                     "k": 5}]
        elif kind == "filter_count":
            a, b, c = terms(3)
            params = [{"predicate": ("and", (("term", a, 2),
                                             ("or", (("term", b, 1),
                                                     ("term", c, 3)))))}]
        elif kind == "agg_terms":
            params = [{"terms": terms(4), "agg": "sum"},
                      {"terms": terms(3), "agg": "max"}]
        elif kind == "phrase_count":
            at = int(rng.integers(0, len(hot_file) - 3))
            params = [{"terms": tuple(int(t) for t in hot_file[at:at + 3])}]
        for p in params:
            queries += [Query(c, kind, **p) for c in corpora()]
    return queries


def raw_stream(files: Sequence[np.ndarray], vocab: int) -> np.ndarray:
    """The terminal stream as the engine defines it: each file followed by
    its own splitter symbol ``vocab + i`` — built from the raw files."""
    parts = []
    for i, f in enumerate(files):
        parts += [np.asarray(f, np.int64), np.array([vocab + i], np.int64)]
    return np.concatenate(parts)


class Oracle:
    """Decompress-then-scan answers from the raw token files (memoized)."""

    def __init__(self, deps: Sequence[Deployment]):
        sys.path.insert(0, os.path.join(ROOT, "tests"))
        import _oracle
        self.o = _oracle
        self.files = {c: (f, d.vocab) for d in deps for c, f in d.files.items()}
        self.gas = {c: s.ga for d in deps for c, s in d.stores.items()}
        self._streams: Dict[str, np.ndarray] = {}
        self._memo: Dict = {}

    def stream(self, corpus: str) -> np.ndarray:
        if corpus not in self._streams:
            self._streams[corpus] = raw_stream(*self.files[corpus])
        return self._streams[corpus]

    def answer(self, q):
        key = (q.corpus, q.group_key())
        if key not in self._memo:
            self._memo[key] = self._compute(q)
        return self._memo[key]

    def _compute(self, q):
        o, ga, st = self.o, self.gas[q.corpus], self.stream(q.corpus)
        if q.kind in ("search_bm25", "search_tfidf"):
            return o.oracle_search_kind(ga, q.kind, q.terms, k=q.k, stream=st)
        if q.kind in ("filter_count", "agg_terms", "phrase_count"):
            return o.oracle_query(ga, q.kind, predicate=q.predicate,
                                  terms=q.terms, agg=q.agg or "sum",
                                  stream=st)
        return o.oracle(ga, q.kind, l=q.l, stream=st)

    def check(self, queries, results, label: str) -> Counter:
        """Bit-exact comparison; returns mismatches per kind (and emits the
        first few with their difference)."""
        bad: Counter = Counter()
        for q, got in zip(queries, results):
            try:
                self.o.assert_result_equal(got, self.answer(q), q.kind,
                                           f"({label}, {q.corpus})")
            except AssertionError as e:
                bad[q.kind] += 1
                if sum(bad.values()) <= 5:
                    emit(step="mismatch", where=label, corpus=q.corpus,
                         kind=q.kind, detail=str(e)[:2000])
        return bad


# ----------------------------------------------------------------- timing --
def timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def by_kind(queries):
    groups: Dict[str, list] = {}
    for q in queries:
        groups.setdefault(q.kind, []).append(q)
    return groups


def bytes_in_use() -> int:
    """Device bytes held now (the first device; None where the backend
    does not report memory)."""
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")


# ----------------------------------------------------------------- phases --
def serve_sync(server, d: Deployment, queries, oracle: Oracle) -> Counter:
    """Load every corpus once (word count), then each kind cold and warm."""
    from repro.serving import Query

    load = [Query(c, "word_count") for c in d.files]
    res, dt = timed(lambda: server.run(load))
    bad = oracle.check(load, res, f"{d.name}/load")
    # cumulative: the server keeps every deployment's packs resident
    emit(step="load", deployment=d.name, corpora=len(load), seconds=dt,
         device_bytes_in_use=bytes_in_use())
    for kind, qs in by_kind(queries).items():
        first, t_first = timed(lambda: server.run(qs))
        warm, t_warm = timed(lambda: server.run(qs))
        bad += oracle.check(qs, first, f"{d.name}/sync")
        bad += oracle.check(qs, warm, f"{d.name}/sync-warm")
        emit(step="sync", deployment=d.name, kind=kind, queries=len(qs),
             first_s=t_first, warm_s=t_warm)
    return bad


def serve_async(server, d: Deployment, queries, oracle: Oracle) -> Counter:
    from repro.serving import AsyncAnalyticsServer

    shed0 = server.stats.shed
    t0 = time.perf_counter()
    with AsyncAnalyticsServer(server, idle_timeout=0.02,
                              max_wait=0.5) as aq:
        futures = [aq.submit(q, deadline=server.clock() + DEADLINE_S)
                   for q in queries]
        results = [f.result(timeout=RESULT_TIMEOUT_S) for f in futures]
    dt = time.perf_counter() - t0
    if server.stats.shed != shed0:
        raise SmokeFailure(f"{server.stats.shed - shed0} async queries "
                           f"were shed")
    emit(step="async", deployment=d.name, queries=len(queries), seconds=dt)
    return oracle.check(queries, results, f"{d.name}/async")


def pallas_word_count(d: Deployment, oracle: Oracle) -> Counter:
    """The batched word count on the Pallas bincount kernel (the one kernel
    Mosaic lowers for TPU), through the library's batched entry point.  At
    B's vocabulary the flat [N * V_pad] bin range passes
    ``BINCOUNT_BATCH_FLAT_LIMIT`` and the batch runs in row chunks."""
    from repro.core.batch import GrammarBatch, run_batched
    from repro.kernels import ops
    from repro.serving import Query

    names = list(d.files)[:MAX_BATCH]
    gb = GrammarBatch.build([d.stores[c].ga for c in names])
    res, dt = timed(lambda: run_batched(gb, "word_count", method="auto",
                                        backend="pallas"))
    emit(step="pallas_word_count", deployment=d.name, corpora=len(names),
         v_pad=gb.V_pad, chunk_rows=ops.bincount_batch_rows(gb.n, gb.V_pad),
         seconds=dt)
    return oracle.check([Query(c, "word_count") for c in names], res,
                        f"{d.name}/pallas")


def quotient_exactness(seed: int) -> Counter:
    """BM25 divides on the device, and TPU's float32 divide is not IEEE
    rounded; ``search.engine.div_rn`` rounds it as numpy does.  Check that
    on 2^20 quotients of the BM25 shape, bit for bit."""
    import jax
    import jax.numpy as jnp
    from repro.search.engine import div_rn
    from repro.search.scoring import K1P1

    rng = np.random.default_rng([seed, 2])
    n = 1 << 20
    tf = rng.integers(0, 1 << 16, n).astype(np.float32)
    norm = (rng.random(n) * 12 + 0.3).astype(np.float32)
    a, b = tf * np.float32(K1P1), tf + norm
    want = a / b
    off = int((np.asarray(jax.jit(div_rn)(a, b)) != want).sum())
    raw = int((np.asarray(jax.jit(jnp.divide)(a, b)) != want).sum())
    emit(step="quotient_exactness", quotients=n, div_rn_off=off,
         device_divide_off=raw)
    return Counter({"bm25_quotient": off}) if off else Counter()


def dispatch_counts() -> Dict[str, float]:
    from repro.obs import global_registry

    fam = global_registry().counter("repro_kernel_dispatch_total", "",
                                    ("decision", "path"))
    return {f"{dec}={path}": child.value
            for (dec, path), child in fam.children()}


def one_chip(deps, seed: int) -> Counter:
    from repro.serving import AnalyticsServer

    oracle = Oracle(deps)
    bad: Counter = Counter()
    server = AnalyticsServer(max_batch=MAX_BATCH, method="auto",
                             max_cached_batches=MAX_CACHED_PACKS)
    for d in deps:
        for c, store in d.stores.items():
            server.register(c, store)
    for d in deps:
        queries = make_queries(d, seed)
        bad += serve_sync(server, d, queries, oracle)
        bad += serve_async(server, d, queries, oracle)
    for d in deps:
        bad += pallas_word_count(d, oracle)
    bad += quotient_exactness(seed)
    return bad


def four_chips(deps, seed: int) -> Counter:
    from repro.distributed.shard_batch import corpus_mesh, mesh_size
    from repro.serving import AnalyticsServer

    (d,) = deps
    oracle = Oracle(deps)
    mesh = corpus_mesh()
    if mesh_size(mesh) != 4:
        raise SmokeFailure(f"corpus mesh spans {mesh_size(mesh)} devices, "
                           f"not 4")
    sharded = AnalyticsServer(max_batch=MAX_BATCH, method="auto", mesh=mesh,
                              max_cached_batches=MAX_CACHED_PACKS)
    single = AnalyticsServer(max_batch=MAX_BATCH, method="auto", mesh=None,
                             max_cached_batches=MAX_CACHED_PACKS)
    for c, store in d.stores.items():
        sharded.register(c, store)
        single.register(c, store)
    queries = make_queries(d, seed)
    bad: Counter = Counter()
    for kind, qs in by_kind(queries).items():
        got, t_sharded = timed(lambda: sharded.run(qs))
        want, t_single = timed(lambda: single.run(qs))
        for q, g, w in zip(qs, got, want):
            try:
                oracle.o.assert_result_equal(g, w, kind,
                                             f"(sharded vs single, "
                                             f"{q.corpus})")
            except AssertionError as e:
                bad[f"{kind}:sharded_vs_single"] += 1
                emit(step="mismatch", where="sharded_vs_single",
                     corpus=q.corpus, kind=kind, detail=str(e)[:2000])
        bad += oracle.check(qs, got, f"{d.name}/sharded")
        emit(step="sharded", deployment=d.name, kind=kind, queries=len(qs),
             sharded_first_s=t_sharded, single_first_s=t_single)
    if sharded.stats.sharded_calls == 0:
        raise SmokeFailure("no chunk ran sharded across the mesh")
    emit(step="mesh", mesh_size=mesh_size(mesh),
         sharded_calls=sharded.stats.sharded_calls,
         batched_calls=sharded.stats.batched_calls)
    return bad


# ------------------------------------------------------------------- main --
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.device import (NoTPUError, device_line,
                                  enable_compile_cache, require_tpu)
    except ImportError as e:
        print(f"chip_smoke: the repository's sources are not importable "
              f"next to this script ({e})", file=sys.stderr)
        return 2
    try:
        devices = require_tpu()
    except NoTPUError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 1
    device = device_line(devices)
    emit(step="device", **device, cache_dir=enable_compile_cache(),
         mode=f"chips={args.chips}")
    from repro.kernels import ops
    emit(step="kernel_paths",
         paths={k: ops.exec_path(k) for k in ops.KERNELS})

    names = ["A"] if args.chips == 4 else ["A", "B"]
    deps = [make_files(n, args.seed) for n in names]
    build_stores(deps)
    for d in deps:
        describe(d)
    run = four_chips if args.chips == 4 else one_chip
    bad = run(deps, args.seed)

    stats = devices[0].memory_stats() or {}
    emit(step="memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_in_use=stats.get("bytes_in_use"),
         bytes_limit=stats.get("bytes_limit"))
    emit(step="dispatch", counts=dispatch_counts())
    if bad:
        print(f"chip_smoke: answers differ from the oracle: {dict(bad)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
