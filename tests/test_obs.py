"""Observability layer: registry semantics, exposition formats, thread
safety, and span-tree completeness across both serving paths.

The acceptance bar this suite holds (docs/observability.md):

* the registry is exact under concurrency — N threads hammering one
  counter/histogram lose nothing, and a threaded storm of async submits
  satisfies ``completed + shed + rejected == offered`` on the registry's
  own counters;
* ``render_prometheus()`` output parses under a strict text-format
  grammar, histogram buckets are cumulative-monotone and the ``+Inf``
  bucket equals ``_count``;
* every query through the sync server or the async queue produces a
  complete span tree — no stage gaps (``span_problems`` is the checker);
* the ``enabled`` flag's asymmetry: counters/gauges always record,
  histograms and span construction go dark when disabled.
"""

import json
import math
import re
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compress_files, flatten
from repro.data.store import CompressedCorpus
from repro.kernels import ops as kops
from repro.obs import (BoundedLog, MetricsRegistry, global_registry,
                       span_problems)
from repro.serving import (AnalyticsServer, AsyncAnalyticsServer,
                           DeadlineExceeded, Query, QueueFull)
from conftest import make_repetitive_files

MAX_BATCH = 3


class SimClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _build_engine(n_corpora=MAX_BATCH, seed=321, **kw):
    rng = np.random.default_rng(seed)
    eng = AnalyticsServer(max_batch=MAX_BATCH, **kw)
    for i in range(n_corpora):
        vocab = int(rng.integers(8, 20))
        files = make_repetitive_files(rng, vocab, n_files=2)
        g, nf = compress_files(files, vocab)
        eng.register(f"c{i}", flatten(g, vocab, nf))
    return eng


_ENGINE = None


def _shared_engine():
    """One warmed engine for the exposition/accounting tests (packs and
    compiled programs are reused; per-test registries are NOT needed here
    because these tests only ever read deltas or parse formats)."""
    global _ENGINE
    if _ENGINE is None:
        _ENGINE = _build_engine()
        _ENGINE.run([Query(f"c{i}", "word_count") for i in range(MAX_BATCH)])
    return _ENGINE


# ------------------------------------------------------- registry units --
def test_counter_semantics():
    reg = MetricsRegistry()
    c = reg.counter("repro_t_total", "help text")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    c.set(10.0)                         # forward set OK (the thin views)
    with pytest.raises(ValueError):
        c.set(5.0)                      # backwards never


def test_gauge_semantics():
    reg = MetricsRegistry()
    g = reg.gauge("repro_t_depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3.0


def test_labels_fanout_and_remove():
    reg = MetricsRegistry()
    fam = reg.counter("repro_t_labeled_total", "", ("reason",))
    fam.labels("idle").inc()
    fam.labels("idle").inc()
    fam.labels("drain").inc()
    assert fam.labels("idle").value == 2.0
    assert dict((k, c.value) for k, c in fam.children()) == {
        ("drain",): 1.0, ("idle",): 2.0}
    with pytest.raises(ValueError):
        fam.inc()                       # labeled family has no bare child
    with pytest.raises(ValueError):
        fam.labels("a", "b")            # wrong arity
    fam.remove("drain")
    assert [k for k, _ in fam.children()] == [("idle",)]


def test_registration_validation():
    reg = MetricsRegistry()
    fam = reg.counter("repro_t_x_total", "first", ("a",))
    # idempotent re-registration returns the same family
    assert reg.counter("repro_t_x_total", "other help", ("a",)) is fam
    # conflicting kind or labelnames is refused loudly
    with pytest.raises(ValueError):
        reg.gauge("repro_t_x_total")
    with pytest.raises(ValueError):
        reg.counter("repro_t_x_total", "", ("b",))
    with pytest.raises(ValueError):
        reg.counter("0bad_name")
    with pytest.raises(ValueError):
        reg.counter("repro_t_y_total", "", ("bad-label",))


def test_histogram_bucket_validation():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.histogram("repro_t_h1_seconds", buckets=(1.0, 1.0, 2.0))
    with pytest.raises(ValueError):
        reg.histogram("repro_t_h2_seconds", buckets=(2.0, 1.0))
    h = reg.histogram("repro_t_h3_seconds", buckets=(1.0, 2.0))
    assert math.isinf(h.buckets[-1])    # +Inf auto-appended


def test_histogram_percentiles_interpolate():
    reg = MetricsRegistry()
    h = reg.histogram("repro_t_lat_seconds", buckets=(1.0, 2.0, 4.0))
    assert math.isnan(h.percentile(50))          # empty
    for v in (0.5, 1.5, 1.5, 3.0):
        h.observe(v)
    assert h.count == 4 and h.sum == pytest.approx(6.5)
    # rank 2 of 4 lands in the (1, 2] bucket; linear interpolation
    assert 1.0 <= h.percentile(50) <= 2.0
    assert 2.0 <= h.percentile(99) <= 4.0
    h.observe(100.0)                             # +Inf bucket
    assert h.percentile(99.9) == 4.0             # open-ended: lower bound


def test_disabled_registry_asymmetry():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("repro_t_total")
    h = reg.histogram("repro_t_seconds")
    c.inc()
    h.observe(1.0)
    assert c.value == 1.0               # counters ALWAYS record (policy)
    assert h.count == 0                 # histograms go dark


def test_reset_zeroes_in_place():
    reg = MetricsRegistry()
    fam = reg.counter("repro_t_total", "", ("x",))
    child = fam.labels("a")
    child.inc(5)
    h = reg.histogram("repro_t_seconds")
    h.observe(0.5)
    reg.reset()
    assert child.value == 0.0           # same handle, zeroed
    assert h.count == 0 and h.sum == 0.0


# ---------------------------------------------------------- exposition --
_HELP_RE = re.compile(r"^# HELP [a-zA-Z_:][a-zA-Z0-9_:]* .*$")
_TYPE_RE = re.compile(
    r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) (counter|gauge|histogram)$")
_LABEL_RE = (r'[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\\\|\\"|\\n)*"')
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(" + _LABEL_RE + r"(?:," + _LABEL_RE + r")*)\})?"
    r" ([+-]?(?:Inf|NaN|\d+(?:\.\d+)?(?:[eE][+-]?\d+)?))$")


def _parse_prometheus(text: str) -> dict:
    """Strict line-by-line parse of the 0.0.4 text format; returns
    {family: {"type": ..., "samples": [(name, {label: value}, float)]}}."""
    families, current = {}, None
    assert text.endswith("\n")
    for line in text.splitlines():
        m = _TYPE_RE.match(line)
        if m:
            current = families.setdefault(
                m.group(1), {"type": m.group(2), "samples": []})
            continue
        if _HELP_RE.match(line):
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        labels = {}
        if m.group(2):
            for part in re.findall(_LABEL_RE, m.group(2)):
                k, v = part.split("=", 1)
                labels[k] = v[1:-1]
        assert current is not None, f"sample before any # TYPE: {line!r}"
        current["samples"].append((m.group(1), labels, float(m.group(3))))
    return families


def _check_histogram_series(fam_name: str, fam: dict) -> None:
    """Cumulative buckets monotone, +Inf bucket == _count, per label set."""
    by_key = {}
    for name, labels, value in fam["samples"]:
        key = tuple(sorted((k, v) for k, v in labels.items() if k != "le"))
        entry = by_key.setdefault(key, {"buckets": [], "count": None})
        if name == fam_name + "_bucket":
            entry["buckets"].append((labels["le"], value))
        elif name == fam_name + "_count":
            entry["count"] = value
    for key, entry in by_key.items():
        counts = [v for _, v in entry["buckets"]]
        assert counts == sorted(counts), \
            f"{fam_name}{key}: buckets not cumulative-monotone: {counts}"
        assert entry["buckets"][-1][0] == "+Inf"
        assert entry["buckets"][-1][1] == entry["count"], \
            f"{fam_name}{key}: +Inf bucket != _count"


def test_prometheus_exposition_parses():
    eng = _shared_engine()
    eng.run([Query("c0", "word_count"), Query("c1", "term_vector")])
    for reg in (eng.registry, global_registry()):
        families = _parse_prometheus(reg.render_prometheus())
        assert families, "exposition rendered no families"
        for name, fam in families.items():
            if fam["type"] == "histogram":
                _check_histogram_series(name, fam)
            else:
                for sname, _, _ in fam["samples"]:
                    assert sname == name
    assert "repro_server_queries_total" in _parse_prometheus(
        eng.registry.render_prometheus())


def test_snapshot_is_json_safe_and_consistent():
    eng = _shared_engine()
    snap = eng.registry.snapshot()
    json.dumps(snap)                    # must not raise
    stage = snap["repro_server_stage_seconds"]
    assert stage["type"] == "histogram"
    for s in stage["samples"]:
        # cumulative table's last row is the +Inf bucket == count
        assert s["buckets"][-1][0] == "+Inf"
        assert s["buckets"][-1][1] == s["count"]
    json.dumps(global_registry().snapshot())


def test_label_escaping():
    reg = MetricsRegistry()
    reg.counter("repro_t_esc_total", "", ("v",)).labels('a"b\\c\nd').inc()
    text = reg.render_prometheus()
    assert r'v="a\"b\\c\nd"' in text
    _parse_prometheus(text)             # still parses


# -------------------------------------------------------- thread safety --
def test_registry_concurrent_updates_exact():
    reg = MetricsRegistry()
    c = reg.counter("repro_t_total")
    h = reg.histogram("repro_t_seconds", buckets=(0.5, 1.0))
    n_threads, n_iter = 8, 500

    def work():
        for i in range(n_iter):
            c.inc()
            h.observe((i % 3) * 0.4)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_iter
    assert h.count == n_threads * n_iter
    # sum of per-bucket increments == count (no lost bucket update)
    assert sum(h.labels()._counts) == h.count


def test_concurrent_submit_exact_accounting():
    """Threaded submit storm against a small bounded queue: every offered
    query resolves exactly one way, and the registry's own counters agree
    with the observed outcomes."""
    eng = _shared_engine()
    sub0, rej0, shed0 = (eng.stats.submitted, eng.stats.rejected,
                         eng.stats.shed)
    outcomes = {"completed": 0, "shed": 0, "rejected": 0, "errors": 0}
    lock = threading.Lock()
    futs = []
    n_threads, per_thread = 6, 20

    with AsyncAnalyticsServer(eng, idle_timeout=0.002, poll_interval=0.001,
                              max_pending=16) as aq:
        def client(tid: int):
            rng = np.random.default_rng(tid)
            for j in range(per_thread):
                q = Query(f"c{int(rng.integers(MAX_BATCH))}", "word_count")
                # ~1 in 4 queries carries an already-hopeless deadline
                dl = (time.monotonic() - 1.0
                      if rng.random() < 0.25 else None)
                try:
                    f = aq.submit(q, deadline=dl)
                except QueueFull:
                    with lock:
                        outcomes["rejected"] += 1
                    continue
                with lock:
                    futs.append(f)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        aq.drain()

    for f in futs:
        try:
            f.result(timeout=60)
            outcomes["completed"] += 1
        except DeadlineExceeded:
            outcomes["shed"] += 1
        except Exception:
            outcomes["errors"] += 1

    offered = n_threads * per_thread
    assert outcomes["errors"] == 0
    assert (outcomes["completed"] + outcomes["shed"]
            + outcomes["rejected"]) == offered
    # the registry counted the same story
    assert eng.stats.submitted - sub0 == offered - outcomes["rejected"]
    assert eng.stats.rejected - rej0 == outcomes["rejected"]
    assert eng.stats.shed - shed0 == outcomes["shed"]
    # stage histograms stayed internally consistent under the storm
    for _, child in eng.stats.stage_seconds.children():
        assert sum(child._counts) == child.count


# ------------------------------------------------------------ span trees --
def test_sync_span_tree_complete():
    eng = _build_engine(seed=99)
    qs = [Query(f"c{i}", "word_count") for i in range(2)]
    eng.run(qs)                                  # cold: pays the compile
    for q in qs:
        root = q.trace
        assert root is not None and root.attrs["path"] == "sync"
        assert span_problems(
            root, require=("run_group", "chunk", "pack_build")) == []
        assert root.find("compile"), "first call must trace as compile"
    # the shared chunk subtree IS the batching: both roots hold it
    assert qs[0].trace.find("chunk")[0] is qs[1].trace.find("chunk")[0]

    warm = [Query(f"c{i}", "word_count") for i in range(2)]
    eng.run(warm)
    root = warm[0].trace
    assert span_problems(
        root, require=("run_group", "chunk", "pack_build", "execute")) == []
    assert not root.find("compile")              # warm: no compile stage
    chunk = root.find("chunk")[0]
    assert chunk.attrs["cache_hit"] is True
    assert len(eng.trace_log) == 4               # every root was logged


def test_async_span_tree_simclock():
    """One injectable clock through server, queue, registry, spans: the
    tree's durations are exact simulated time, and the flush subtree is
    shared by every query it answered."""
    clk = SimClock()
    # one grammar under three names: identical size buckets, so the three
    # submits share one pending group and the third fills it (max_batch)
    rng = np.random.default_rng(77)
    files = make_repetitive_files(rng, 12, n_files=2)
    g, nf = compress_files(files, 12)
    ga = flatten(g, 12, nf)
    eng = AnalyticsServer(max_batch=MAX_BATCH, clock=clk)
    for i in range(MAX_BATCH):
        eng.register(f"c{i}", ga)
    aq = AsyncAnalyticsServer(eng, idle_timeout=100.0)   # inherits clk
    qs = [Query(f"c{i}", "word_count") for i in range(MAX_BATCH)]
    futs = []
    for q in qs:
        futs.append(aq.submit(q))
        clk.t += 0.5                    # advance between submits
    assert all(f.done() for f in futs)  # max_batch flushed on last submit
    for q in qs:
        root = q.trace
        assert root.attrs["path"] == "async"
        assert root.attrs["outcome"] == "ok"
        assert span_problems(
            root, require=("queue_wait", "flush", "chunk",
                           "pack_build")) == []
    # queue_wait measured in pure simulated time: q0 waited two ticks
    waits = [q.trace.find("queue_wait")[0].duration for q in qs]
    assert waits == pytest.approx([1.0, 0.5, 0.0])
    # one flush span, shared under all three roots
    fspans = {id(q.trace.find("flush")[0]) for q in qs}
    assert len(fspans) == 1
    ev = aq.flush_log[-1]
    assert ev.span is qs[0].trace.find("flush")[0]
    assert ev.span.attrs["reason"] == "max_batch"
    aq.close()


def test_async_shed_span_outcome():
    clk = SimClock()
    eng = _build_engine(n_corpora=1, seed=55, clock=clk)
    aq = AsyncAnalyticsServer(eng, idle_timeout=100.0, default_latency=0.01)
    clk.t = 10.0
    q = Query("c0", "word_count")
    fut = aq.submit(q, deadline=9.0)    # already hopeless
    clk.t += 1.0
    aq.poll()
    with pytest.raises(DeadlineExceeded):
        fut.result(timeout=0)
    root = q.trace
    assert root.attrs["outcome"] == "shed" and root.finished
    assert root in list(eng.trace_log)
    aq.close()


def test_disabled_registry_skips_spans_not_counters():
    eng = _build_engine(n_corpora=1, seed=44,
                        registry=MetricsRegistry(enabled=False))
    q = Query("c0", "word_count")
    eng.run([q])
    assert q.trace is None              # no span tree built
    assert eng.stats.queries == 1       # policy counters still count
    assert eng.stats.stage_seconds.labels("execute").count == 0
    assert len(eng.trace_log) == 0


# ------------------------------------------------- serving stat views --
def test_stats_thin_views_are_registry_backed():
    eng = _shared_engine()
    assert eng.stats.queries == int(eng.registry.counter(
        "repro_server_queries_total").value)
    # dict-shaped views behave like dicts (the pre-registry call sites)
    flushes = eng.stats.flushes
    assert flushes == dict(flushes)
    assert flushes.get("no_such_reason", 0) == 0
    assert repr(flushes) == repr(dict(flushes))
    with pytest.raises(KeyError):
        flushes["no_such_reason"]
    sig_fam = eng.registry.counter(
        "repro_server_pack_signatures_total", "", ("signature",))
    assert len(sig_fam.children()) == len(eng.stats.signatures)


# ------------------------------------------------------- bounded logs --
def test_bounded_log_counts_drops():
    reg = MetricsRegistry()
    g = reg.gauge("repro_t_dropped")
    log = BoundedLog(2, gauge=g)
    for i in range(5):
        log.append(i)
    assert list(log) == [3, 4]
    assert log.dropped == 3 and g.value == 3.0
    assert log.maxlen == 2 and len(log) == 2 and log[-1] == 4
    with pytest.raises(ValueError):
        BoundedLog(0)


def test_flush_log_drop_gauge_wired():
    eng = _shared_engine()
    aq = AsyncAnalyticsServer(eng, idle_timeout=100.0, clock=SimClock())
    assert isinstance(aq.flush_log, BoundedLog)
    assert aq.flush_log._gauge is not None
    aq.close()


# ------------------------------------------------- library-layer metrics --
def _global_value(name: str, *labelvalues, labelnames=()) -> float:
    fam = global_registry().counter(name, "", labelnames)
    return fam.labels(*labelvalues).value if labelvalues else fam.value


def test_store_memo_and_ingest_counters():
    rng = np.random.default_rng(5)
    files = [rng.integers(0, 12, 40) for _ in range(3)]
    miss0 = _global_value("repro_store_memo_lookups_total", "miss",
                          labelnames=("result",))
    hit0 = _global_value("repro_store_memo_lookups_total", "hit",
                         labelnames=("result",))
    files0 = _global_value("repro_ingest_files_total")
    corpus = CompressedCorpus.build(files, vocab_size=12)
    assert _global_value("repro_ingest_files_total") - files0 == 3
    corpus.top_down_weights()
    corpus.top_down_weights()
    assert _global_value("repro_store_memo_lookups_total", "miss",
                         labelnames=("result",)) - miss0 == 1
    assert _global_value("repro_store_memo_lookups_total", "hit",
                         labelnames=("result",)) - hit0 == 1
    appends0 = _global_value("repro_store_appends_total")
    corpus.append_files([rng.integers(0, 12, 20)])
    assert _global_value("repro_store_appends_total") - appends0 == 1


def test_kernel_dispatch_counters():
    fam = global_registry().counter("repro_kernel_dispatch_total", "",
                                    ("decision", "path"))
    before = sum(c.value for k, c in fam.children() if k[0] == "ell_vs_seg")
    kops.ell_batched_use_ref(num_edges=64, n=2, rows=8, k=4)
    kops.ell_fused_use_kernel(rows=8)
    after = sum(c.value for k, c in fam.children() if k[0] == "ell_vs_seg")
    assert after - before == 1
    fused = {k[1]: c.value for k, c in fam.children()
             if k[0] == "fused_vs_per_round"}
    assert fused.get("fused", 0) >= 1


def test_vocabulary_gate_dispatch_counters(monkeypatch):
    """The batched bincount's flat-vs-chunked decision and the per-file
    vector-plan gate are counted, so a run shows which path its widths
    took."""
    fam = global_registry().counter("repro_kernel_dispatch_total", "",
                                    ("decision", "path"))

    def count(decision):
        return {k[1]: c.value for k, c in fam.children() if k[0] == decision}

    before = count("bincount_batch")
    monkeypatch.setattr(kops, "BINCOUNT_BATCH_FLAT_LIMIT", 256)
    ids = jnp.asarray(np.arange(32).reshape(4, 8) % 128, jnp.int32)
    vals = jnp.ones((4, 8), jnp.float32)
    chunked = kops.weighted_bincount_batched(ids, vals, 128)   # 2 rows/chunk
    flat = kops.weighted_bincount_batched(ids[:2], vals[:2], 128)
    np.testing.assert_array_equal(np.asarray(chunked[:2]), np.asarray(flat))
    after = count("bincount_batch")
    assert after.get("chunked", 0) - before.get("chunked", 0) == 1
    assert after.get("flat", 0) - before.get("flat", 0) == 1

    before = count("vector_plan")
    assert kops.ell_vector_plan_ok(1, 1024, 8, 16)
    assert not kops.ell_vector_plan_ok(64, 1 << 18, 64, 1024)
    after = count("vector_plan")
    assert after.get("ell", 0) - before.get("ell", 0) == 1
    assert after.get("segment_sum", 0) - before.get("segment_sum", 0) == 1


def test_trace_annotation_env_gate(monkeypatch):
    from contextlib import nullcontext

    from repro.kernels import autotune

    monkeypatch.delenv(autotune.ANNOTATE_ENV, raising=False)
    assert not autotune.annotations_enabled()
    assert isinstance(autotune.trace_annotation("x"), nullcontext)
    monkeypatch.setenv(autotune.ANNOTATE_ENV, "0")
    assert not autotune.annotations_enabled()
    monkeypatch.setenv(autotune.ANNOTATE_ENV, "1")
    assert autotune.annotations_enabled()
    with autotune.trace_annotation("obs-test"):   # real annotation works
        pass
