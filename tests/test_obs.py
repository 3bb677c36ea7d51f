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
  histograms and span construction go dark when disabled;
* the spans are on the profiler's clock (``repro.*`` annotations in a
  captured trace, nested as the tree is), and ``compile`` spans and
  counts are JAX's own backend compile events.
"""

import contextlib
import glob
import json
import math
import os
import re
import threading
import time

import jax
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


@contextlib.contextmanager
def _backend_compiles():
    """JAX's backend compile events on this thread, seen by a listener of
    the test's own (not the program's)."""
    seen = []
    me = threading.get_ident()

    def listen(event, duration, **_):
        if (event == "/jax/core/compile/backend_compile_duration"
                and threading.get_ident() == me):
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def _host_events(tmp_path, fn):
    """``repro.*`` host events ``(name, start_ns, end_ns)`` of a profiler
    trace captured on the CPU around ``fn()``."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


# ------------------------------------------------------------ span trees --
def test_sync_span_tree_complete():
    eng = _build_engine(seed=99)
    qs = [Query(f"c{i}", "word_count") for i in range(2)]
    jax.clear_caches()                           # cold whatever ran before
    with _backend_compiles() as seen:
        eng.run(qs)                              # cold: pays the compiles
    assert seen
    for q in qs:
        root = q.trace
        assert root is not None and root.attrs["path"] == "sync"
        assert span_problems(
            root, require=("run_group", "chunk", "pack_build", "execute",
                           "compile")) == []
        # one compile span per backend compile event, none guessed
        assert len(root.find("compile")) == len(seen)
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


def test_compile_events_counted():
    """A cold call on a fresh shape adds as many ``compile`` spans, stage
    observations and ``stats.compiles`` as JAX had backend compile events;
    a warm call adds none."""
    eng = _build_engine(n_corpora=2, seed=123)
    jax.clear_caches()
    with _backend_compiles() as seen:
        eng.run([Query(f"c{i}", "sort") for i in range(2)])
    assert seen and eng.stats.compiles == len(seen)
    stage = eng.stats.stage_seconds.labels("compile")
    assert stage.count == len(seen)
    assert stage.sum == pytest.approx(sum(seen))
    # the chunk's tree says which programs compiled
    compiled = eng.trace_log[-1].find("chunk")[0].find("compile")
    assert len(compiled) == len(seen)
    assert all(s.attrs["fun_name"] for s in compiled)

    with _backend_compiles() as seen_warm:
        warm = [Query(f"c{i}", "sort") for i in range(2)]
        eng.run(warm)
    assert seen_warm == [] and eng.stats.compiles == len(seen)
    assert not warm[0].trace.find("compile")
    assert stage.count == len(seen)


def test_readback_stage_inside_execute():
    """Every execution observes ``execute`` and ``readback``; the
    device→host reads are ``readback`` spans inside ``execute``."""
    eng = _build_engine(n_corpora=2, seed=31)
    for kind in ("word_count", "sort", "term_vector"):
        q = Query("c0", kind)
        eng.run([q, Query("c1", kind)])
        ex = q.trace.find("execute")[0]
        reads = ex.find("readback")
        assert reads and all(r.t0 >= ex.t0 and r.t1 <= ex.t1 for r in reads)
    h = eng.stats.stage_seconds
    assert h.labels("readback").count == h.labels("execute").count == 3
    assert 0 < h.labels("readback").sum <= h.labels("execute").sum


def test_spans_on_the_profiler_clock(tmp_path):
    """A profiler trace captured on the CPU around one sync ``run()``
    holds the program's spans as ``repro.*`` host events, nested as the
    span tree is."""
    eng = _build_engine(n_corpora=2, seed=47)
    qs = [Query(f"c{i}", "word_count") for i in range(2)]
    eng.run(qs)                                  # compile outside the trace
    events = _host_events(tmp_path, lambda: eng.run(qs))
    by_name = {}
    for name, t0, t1 in events:
        by_name.setdefault(name, []).append((t0, t1))
    for name in ("repro.run_group", "repro.chunk", "repro.pack_build",
                 "repro.execute", "repro.readback"):
        assert name in by_name, sorted(by_name)

    def inside(child, parent):
        return all(any(p0 <= c0 and c1 <= p1 for p0, p1 in by_name[parent])
                   for c0, c1 in by_name[child])

    assert inside("repro.chunk", "repro.run_group")
    assert inside("repro.pack_build", "repro.chunk")
    assert inside("repro.execute", "repro.chunk")
    assert inside("repro.readback", "repro.execute")
    # the same counts as the span tree of the traced call
    tree = qs[0].trace
    assert len(by_name["repro.readback"]) == len(tree.find("readback"))
    assert len(by_name["repro.chunk"]) == len(tree.find("chunk"))


def test_serve_loop_idle_annotation(tmp_path):
    """The serve thread's wait for work is a ``repro.queue.idle`` event."""
    eng = _build_engine(n_corpora=1, seed=53)

    def serve_idle():
        with AsyncAnalyticsServer(eng, poll_interval=0.001):
            time.sleep(0.05)

    names = {name for name, _, _ in _host_events(tmp_path, serve_idle)}
    assert "repro.queue.idle" in names


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


def test_trace_annotation_env_gate(tmp_path):
    """No switch gates the autotuner's annotations: a timed candidate is a
    ``repro.autotune:<candidate>`` event in a CPU trace."""
    from repro.kernels import autotune

    x = jnp.arange(8.0)
    names = {name for name, _, _ in _host_events(
        tmp_path, lambda: autotune._time_call(lambda: x * 2, repeat=1,
                                              warmup=0,
                                              name="autotune:obs-test"))}
    assert "repro.autotune:obs-test" in names
