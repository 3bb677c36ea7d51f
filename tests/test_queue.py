"""Async deadline-aware queue: flush policy properties + sync equivalence.

The invariants fuzzed here (tests run without hypothesis via
_hypothesis_compat):

* no submitted query ever starves — every future resolves (with a result
  or :class:`DeadlineExceeded`), and a query that resolves with a result
  under a deadline did so no later than ``deadline + one poll interval``
  of simulated time;
* a query whose deadline has already passed at flush time is shed: its
  future raises ``DeadlineExceeded``, the engine is never asked for it,
  and ``stats.shed`` / ``FlushEvent.n_shed`` account for every shed
  exactly once;
* no flush ever packs more than ``max_batch`` distinct corpora, and every
  flush carries exactly one (kind, l) group;
* the async path is bit-identical to a one-shot synchronous
  ``AnalyticsServer.run`` of the same queries for every non-shed result,
  whatever the arrival order, deadlines, duplicates, shed mix, and flush
  interleaving.

Time is fully simulated (``clock=`` injection): the trace loop drives
:meth:`AsyncAnalyticsServer.poll` on a fixed tick grid, so runs reproduce
exactly from the conftest-logged seed.
"""

import threading

import numpy as np
import pytest

from repro.core import compress_files, flatten, word_count
from repro.serving import (AnalyticsServer, AsyncAnalyticsServer,
                           DeadlineExceeded, Query, QueueFull)
from _hypothesis_compat import given, settings, st
from _oracle import assert_result_equal
from conftest import make_repetitive_files

MAX_BATCH = 3
POLL_DT = 0.005


class SimClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _build_engine(n_corpora=6, max_batch=MAX_BATCH, seed=1234):
    rng = np.random.default_rng(seed)
    eng = AnalyticsServer(max_batch=max_batch)
    for i in range(n_corpora):
        vocab = int(rng.integers(8, 28))
        files = make_repetitive_files(rng, vocab,
                                      n_files=int(rng.integers(1, 4)))
        g, nf = compress_files(files, vocab)
        eng.register(f"c{i}", flatten(g, vocab, nf))
    return eng


_ENGINE = None


def _shared_engine():
    """One engine for the whole module: packs/compilations are reused, and
    @given-wrapped tests cannot take fixtures under the no-hypothesis
    fallback."""
    global _ENGINE
    if _ENGINE is None:
        _ENGINE = _build_engine()
    return _ENGINE


def _assert_same(got, want):
    assert_result_equal(got, want, "async-vs-sync")


# --------------------------------------------------------------- policy --
def test_submit_validates_before_queueing():
    eng = _shared_engine()
    aq = AsyncAnalyticsServer(eng, clock=SimClock())
    with pytest.raises(KeyError):
        aq.submit(Query("nope", "word_count"))
    with pytest.raises(ValueError):
        aq.submit(Query("c0", "nope"))
    assert aq.queue_depth == 0


def test_full_group_flushes_on_submit():
    eng = _shared_engine()
    clk = SimClock()
    aq = AsyncAnalyticsServer(eng, idle_timeout=100.0, clock=clk)
    futs = [aq.submit(Query(f"c{i}", "word_count")) for i in range(MAX_BATCH)]
    assert all(f.done() for f in futs)          # no poll needed
    assert aq.queue_depth == 0
    ev = aq.flush_log[-1]
    assert ev.reason == "max_batch" and ev.n_corpora == MAX_BATCH
    for i, f in enumerate(futs):
        _assert_same(f.result(),
                     np.asarray(word_count(eng._corpora[f"c{i}"],
                                           method="frontier")))


def test_deadline_flush_fires_within_one_estimated_latency():
    eng = _build_engine(n_corpora=2, seed=7)    # fresh: empty latency EWMA
    clk = SimClock()
    aq = AsyncAnalyticsServer(eng, idle_timeout=100.0, default_latency=0.05,
                              clock=clk)
    fut = aq.submit(Query("c0", "word_count"), deadline=1.0)
    aq.poll()
    assert not fut.done()                       # 1.0 - 0.0 > 0.05
    clk.t = 0.9
    aq.poll()
    assert not fut.done()                       # 0.1 > 0.05
    clk.t = 0.96
    aq.poll()                                   # 0.04 <= estimate: due now
    assert fut.done()
    assert aq.flush_log[-1].reason == "deadline"


def test_expired_deadline_is_shed_not_executed():
    """A query whose deadline passed before its flush gets DeadlineExceeded
    and never reaches the engine; an expired singleton group therefore
    costs zero engine calls (but still logs its flush)."""
    eng = _build_engine(n_corpora=2, seed=41)
    clk = SimClock()
    aq = AsyncAnalyticsServer(eng, idle_timeout=100.0, clock=clk)
    fut = aq.submit(Query("c0", "word_count"), deadline=0.5)
    clk.t = 1.0                                 # deadline long gone
    calls_before = eng.stats.batched_calls + eng.stats.single_calls
    aq.poll()                                   # deadline condition fires
    assert fut.done()
    with pytest.raises(DeadlineExceeded):
        fut.result()
    assert eng.stats.batched_calls + eng.stats.single_calls == calls_before
    assert eng.stats.shed == 1
    ev = aq.flush_log[-1]
    assert ev.n_shed == 1 and ev.n_queries == 0 and ev.n_corpora == 0
    assert ev.reason == "deadline"


def test_partial_shed_keeps_live_results_bit_identical():
    """Shedding one group member must not disturb the others: live members
    execute and stay bit-identical to the sync path."""
    eng = _build_engine(n_corpora=3, seed=43)
    clk = SimClock()
    aq = AsyncAnalyticsServer(eng, idle_timeout=100.0, clock=clk)
    f_dead = aq.submit(Query("c0", "word_count"), deadline=0.1)
    f_live = aq.submit(Query("c1", "word_count"))
    f_dup = aq.submit(Query("c1", "word_count"))    # duplicate rides along
    clk.t = 0.5
    aq.drain()
    with pytest.raises(DeadlineExceeded):
        f_dead.result()
    want = eng.run([Query("c1", "word_count")])[0]
    _assert_same(f_live.result(), want)
    _assert_same(f_dup.result(), want)
    assert eng.stats.shed == 1
    ev = aq.flush_log[-1]
    assert ev.reason == "drain" and ev.n_shed == 1
    assert ev.n_queries == 2 and ev.n_corpora == 1


def test_deadline_exactly_at_flush_time_is_not_shed():
    """now == deadline is the boundary: only strictly-passed deadlines are
    shed (the contract is 'already expired', not 'about to expire')."""
    eng = _build_engine(n_corpora=2, seed=47)
    clk = SimClock()
    aq = AsyncAnalyticsServer(eng, idle_timeout=100.0, clock=clk)
    fut = aq.submit(Query("c0", "word_count"), deadline=0.5)
    clk.t = 0.5
    aq.drain()
    _assert_same(fut.result(),
                 eng.run([Query("c0", "word_count")])[0])
    assert eng.stats.shed == 0


def test_idle_flush_after_timeout():
    eng = _shared_engine()
    clk = SimClock()
    aq = AsyncAnalyticsServer(eng, idle_timeout=0.5, clock=clk)
    fut = aq.submit(Query("c0", "word_count"))
    clk.t = 0.4
    aq.poll()
    assert not fut.done()
    clk.t = 0.3                                 # new arrival resets idleness
    f2 = aq.submit(Query("c0", "sort"))
    clk.t = 0.55
    aq.poll()
    assert fut.done()                           # word_count group: idle
    assert aq.flush_log[-1].reason == "idle"
    clk.t = 0.85
    aq.poll()
    assert f2.done()


def test_engine_wait_splits_queue_wait():
    """A stub engine holds the first flush for ``hold`` s of simulated
    time.  The second group was due 2 ms after the first flush fired, so
    its ``engine_wait`` is ``hold`` less those 2 ms, and its ``queue_wait``
    is its batching wait (``idle_timeout``) plus ``engine_wait``."""
    eng = _build_engine(n_corpora=2, seed=21)
    clk = SimClock()
    hold = 2.0

    def run_group(kind, names, **_):            # the engine, busy hold s
        clk.t += hold
        return {n: kind for n in names}

    eng.run_group = run_group
    aq = AsyncAnalyticsServer(eng, idle_timeout=0.005, max_wait=0.05,
                              clock=clk)
    qa, qb = Query("c0", "word_count"), Query("c1", "sort")
    fa = aq.submit(qa)                          # group A at 0, due 0.005
    clk.t = 0.002
    fb = aq.submit(qb)                          # group B, due 0.007
    clk.t = 0.005
    aq.poll()                                   # A flushes, engine busy
    assert fa.result(timeout=0) == "word_count" and not fb.done()
    aq.poll()                                   # at 2.005 B's flush claims
    assert fb.result(timeout=0) == "sort"

    wait_b = qb.trace.find("queue_wait")[0]
    (engine_b,) = wait_b.children
    assert engine_b.name == "engine_wait"
    assert engine_b.duration == pytest.approx(hold - 0.002)
    assert wait_b.duration == pytest.approx(0.005 + engine_b.duration)
    assert qa.trace.find("engine_wait")[0].duration == pytest.approx(0.0)
    h = eng.stats.stage_seconds
    assert h.labels("engine_wait").count == 2
    assert h.labels("engine_wait").sum == pytest.approx(hold - 0.002)
    assert h.labels("queue_wait").sum == pytest.approx(
        0.005 + 0.005 + h.labels("engine_wait").sum)
    aq.close()


def test_sustained_stream_bounded_by_max_wait():
    """A same-corpus stream resets idleness on every arrival and never
    fills a pack; the oldest query must still flush within max_wait."""
    eng = _build_engine(n_corpora=2, seed=15)
    clk = SimClock()
    aq = AsyncAnalyticsServer(eng, idle_timeout=0.05, max_wait=0.2,
                              clock=clk)
    first = aq.submit(Query("c0", "word_count"))
    t = 0.0
    while t < 0.13:                             # arrivals every 0.04 < idle
        t += 0.04
        clk.t = t
        aq.poll()
        aq.submit(Query("c0", "word_count"))
        assert not first.done()
    clk.t = 0.20                                # idle not yet due; age is
    aq.poll()
    assert first.done()
    assert aq.flush_log[-1].reason == "max_wait"
    aq.close()


def test_cancelled_future_does_not_break_its_flush():
    """A caller cancelling a pending future must not starve the rest of
    the group or raise out of the flush path."""
    eng = _shared_engine()
    aq = AsyncAnalyticsServer(eng, idle_timeout=100.0, clock=SimClock())
    f_cancel = aq.submit(Query("c0", "word_count"))
    f_keep = aq.submit(Query("c1", "word_count"))
    assert f_cancel.cancel()
    aq.drain()                                  # must not raise
    assert f_keep.done() and not f_keep.cancelled()
    _assert_same(f_keep.result(),
                 np.asarray(word_count(eng._corpora["c1"],
                                       method="frontier")))
    assert f_cancel.cancelled()
    ev = aq.flush_log[-1]
    assert ev.n_queries == 1 and ev.n_corpora == 1
    # a fully-cancelled group flushes without touching the engine
    aq2 = AsyncAnalyticsServer(eng, idle_timeout=100.0, clock=SimClock())
    f_only = aq2.submit(Query("c0", "sort"))
    assert f_only.cancel()
    calls_before = eng.stats.batched_calls + eng.stats.single_calls
    aq2.drain()
    assert eng.stats.batched_calls + eng.stats.single_calls == calls_before
    assert aq2.flush_log[-1].n_queries == 0


def test_backpressure_rejects_when_full():
    """max_pending bounds the queue depth: overflowing submits raise
    QueueFull (counted on stats.rejected), space freed by a flush admits
    new traffic, and the high-water mark is recorded."""
    eng = _build_engine(n_corpora=4, seed=23)
    clk = SimClock()
    aq = AsyncAnalyticsServer(eng, idle_timeout=0.5, clock=clk,
                              max_pending=2)
    # distinct kinds -> distinct groups: nothing fills max_batch
    f1 = aq.submit(Query("c0", "word_count"))
    f2 = aq.submit(Query("c1", "sort"))
    assert aq.queue_depth == 2
    with pytest.raises(QueueFull):
        aq.submit(Query("c2", "term_vector"))
    assert eng.stats.rejected == 1
    assert not f1.done() and not f2.done()      # rejection flushed nothing
    clk.t = 1.0
    aq.poll()                                   # idle flush frees the queue
    assert f1.done() and f2.done()
    f3 = aq.submit(Query("c2", "term_vector"))  # space again
    aq.drain()
    assert f3.done()
    assert eng.stats.max_queue_depth >= 2
    with pytest.raises(ValueError):
        AsyncAnalyticsServer(eng, max_pending=0)


def test_backpressure_block_waits_for_space():
    """submit(block=True) parks instead of raising and resumes as soon as
    a flush (driven elsewhere) frees queue depth."""
    eng = _build_engine(n_corpora=4, seed=29)
    aq = AsyncAnalyticsServer(eng, idle_timeout=100.0, clock=SimClock(),
                              max_pending=1)
    aq.submit(Query("c0", "word_count"))
    entered = threading.Event()
    futs = []

    def blocked_submit():
        entered.set()
        futs.append(aq.submit(Query("c1", "sort"), block=True))

    t = threading.Thread(target=blocked_submit)
    t.start()
    entered.wait(5)
    assert t.is_alive()                         # parked on the full queue
    aq.drain()                                  # frees space -> unblocks
    t.join(timeout=10)
    assert not t.is_alive() and len(futs) == 1
    aq.drain()
    assert futs[0].done()
    _assert_same(futs[0].result(),
                 eng.run([Query("c1", "sort")])[0])
    assert eng.stats.rejected == 0              # block never rejects


def test_backpressure_blocked_submit_raises_on_close():
    eng = _build_engine(n_corpora=2, seed=31)
    aq = AsyncAnalyticsServer(eng, idle_timeout=100.0, clock=SimClock(),
                              max_pending=1)
    aq.submit(Query("c0", "word_count"))
    raised = threading.Event()

    def blocked_submit():
        try:
            aq.submit(Query("c1", "word_count"), block=True)
        except RuntimeError:
            raised.set()

    t = threading.Thread(target=blocked_submit)
    t.start()
    # close() must wake the blocked submit and fail it, never hang it
    import time as _time
    _time.sleep(0.05)
    aq.close()
    t.join(timeout=10)
    assert raised.is_set()


def test_close_races_many_blocked_submits_under_running_thread():
    """Lifecycle race: several submits parked on max_pending while the
    background thread is live and another thread calls close().  Every
    blocked submit must resolve — either admitted-and-drained by close()
    or failed with RuntimeError — and nothing may hang."""
    eng = _build_engine(n_corpora=4, seed=37)
    # idle_timeout generous: blocked submits wait on close(), not a flush
    aq = AsyncAnalyticsServer(eng, idle_timeout=60.0, poll_interval=0.001,
                              max_pending=1).start()
    aq.submit(Query("c0", "word_count"))
    outcomes = []
    started = threading.Barrier(4)

    def blocked_submit(i):
        started.wait(5)
        try:
            outcomes.append(("ok", aq.submit(Query(f"c{i}", "sort"),
                                             block=True)))
        except RuntimeError:
            outcomes.append(("raised", None))

    threads = [threading.Thread(target=blocked_submit, args=(i,))
               for i in range(1, 4)]
    for t in threads:
        t.start()
    started.wait(5)
    import time as _time
    _time.sleep(0.05)                   # let them reach the wait
    aq.close()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive(), "blocked submit hung across close()"
    assert len(outcomes) == 3
    # admitted submits were drained by close(); the rest raised
    for tag, fut in outcomes:
        assert tag == "raised" or fut.done()


def test_fully_cancelled_group_logs_flush_without_engine_call():
    """A flush whose every future was cancel()ed must not call the engine
    but must still log the flush (the observability ring stays complete) —
    here via the poll path, not drain."""
    eng = _build_engine(n_corpora=3, seed=53)
    clk = SimClock()
    aq = AsyncAnalyticsServer(eng, idle_timeout=0.5, clock=clk)
    f1 = aq.submit(Query("c0", "word_count"))
    f2 = aq.submit(Query("c0", "word_count"))   # same group, same corpus
    assert f1.cancel() and f2.cancel()
    calls_before = eng.stats.batched_calls + eng.stats.single_calls
    log_before = len(aq.flush_log)
    clk.t = 1.0
    aq.poll()                                   # idle flush of a dead group
    assert eng.stats.batched_calls + eng.stats.single_calls == calls_before
    assert len(aq.flush_log) == log_before + 1
    ev = aq.flush_log[-1]
    assert ev.reason == "idle" and ev.n_queries == 0 and ev.n_corpora == 0
    assert aq.queue_depth == 0


def test_submit_after_close_raises_instead_of_hanging():
    eng = _shared_engine()
    aq = AsyncAnalyticsServer(eng, idle_timeout=100.0, clock=SimClock())
    fut = aq.submit(Query("c0", "word_count"))
    aq.close()
    assert fut.done()                           # close drains
    with pytest.raises(RuntimeError):
        aq.submit(Query("c0", "word_count"))
    with pytest.raises(RuntimeError):
        aq.start()
    aq.close()                                  # idempotent


def test_poll_returns_next_trigger_time():
    eng = _build_engine(n_corpora=2, seed=9)
    clk = SimClock()
    aq = AsyncAnalyticsServer(eng, idle_timeout=1.0, default_latency=0.1,
                              clock=clk)
    assert aq.poll() is None
    aq.submit(Query("c0", "word_count"))        # idle trigger at 1.0
    nxt = aq.poll()
    assert nxt == pytest.approx(1.0)
    aq.submit(Query("c1", "word_count"), deadline=0.5)
    nxt = aq.poll()                             # deadline - estimate = 0.4
    assert nxt == pytest.approx(0.4)


def test_drain_and_close_leave_nothing_pending():
    eng = _shared_engine()
    aq = AsyncAnalyticsServer(eng, idle_timeout=100.0, clock=SimClock())
    futs = [aq.submit(Query("c0", "word_count")),
            aq.submit(Query("c1", "sequence_count", l=2))]
    assert aq.queue_depth == 2
    aq.close()                                  # no thread started: drains
    assert aq.queue_depth == 0
    assert all(f.done() for f in futs)
    assert aq.stats.flushes.get("drain", 0) >= 1


def test_queue_counters():
    eng = _build_engine(n_corpora=3, seed=11)
    aq = AsyncAnalyticsServer(eng, idle_timeout=100.0, clock=SimClock())
    aq.submit(Query("c0", "word_count"))
    aq.submit(Query("c1", "word_count"))
    assert eng.stats.submitted == 2
    assert eng.stats.max_queue_depth >= 2
    aq.drain()
    assert sum(eng.stats.flushes.values()) >= 1


# ------------------------------------------------------ sync equivalence --
def _mixed_queries(rng, eng, n):
    kinds = ("word_count", "sort", "term_vector", "inverted_index",
             "ranked_inverted_index", "sequence_count")
    names = eng.corpora()
    out = []
    for _ in range(n):
        kind = kinds[int(rng.integers(len(kinds)))]
        out.append(Query(names[int(rng.integers(len(names)))], kind,
                         l=int(rng.integers(2, 5))))
    return out


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 100_000))
def test_fuzz_policy_never_starves_and_matches_sync(seed):
    rng = np.random.default_rng(seed)
    eng = _shared_engine()
    clk = SimClock()
    aq = AsyncAnalyticsServer(eng, idle_timeout=4 * POLL_DT,
                              default_latency=POLL_DT, clock=clk)
    shed_before = eng.stats.shed
    queries = _mixed_queries(rng, eng, n=int(rng.integers(6, 16)))
    arrivals = np.cumsum(rng.exponential(POLL_DT, len(queries)))
    # deadline mix: none / feasible / already expired at submission (the
    # expired ones MUST be shed — they grow the shed path's fuzz coverage)
    deadlines = []
    for at in arrivals:
        r = rng.random()
        if r < 0.4:
            deadlines.append(None)
        elif r < 0.8:
            deadlines.append(float(at) + float(rng.uniform(POLL_DT,
                                                           10 * POLL_DT)))
        else:
            deadlines.append(float(at) - float(rng.uniform(0.1 * POLL_DT,
                                                           5 * POLL_DT)))

    futs = [None] * len(queries)
    done_at = {}
    i = 0
    tick = 0.0
    horizon = float(arrivals[-1]) + 100 * POLL_DT
    while len(done_at) < len(queries):
        next_tick = tick + POLL_DT
        if i < len(queries) and arrivals[i] <= next_tick:
            clk.t = float(arrivals[i])
            futs[i] = aq.submit(queries[i], deadline=deadlines[i])
            i += 1
        else:
            tick = next_tick
            clk.t = tick
            aq.poll()
        for j, f in enumerate(futs):
            if f is not None and j not in done_at and f.done():
                done_at[j] = clk.t
        assert clk.t <= horizon, "queries starved past the horizon"

    shed = [j for j, f in enumerate(futs)
            if f.exception() is not None]
    # (1) nothing starves: every future resolved; every query that
    # resolved WITH a result under a deadline met it within one tick
    for j, dl in enumerate(deadlines):
        if dl is not None and j not in shed:
            assert done_at[j] <= dl + POLL_DT + 1e-9, (
                f"query {j} finished {done_at[j]:.4f}, "
                f"deadline {dl:.4f} + tick {POLL_DT}")
    # (2) sheds are genuine and fully accounted: only deadline-carrying
    # queries shed, expired-at-submit deadlines always shed, exceptions
    # are DeadlineExceeded, and the counters agree with the futures
    for j in shed:
        assert deadlines[j] is not None
        assert isinstance(futs[j].exception(), DeadlineExceeded)
    for j, (at, dl) in enumerate(zip(arrivals, deadlines)):
        if dl is not None and dl < float(at):
            assert j in shed, f"expired-at-submit query {j} not shed"
    assert eng.stats.shed - shed_before == len(shed)
    assert sum(ev.n_shed for ev in aq.flush_log) == len(shed)
    # (3) flushes respect max_batch and are single-group
    for ev in aq.flush_log:
        assert ev.n_corpora <= eng.max_batch
        assert ev.kind in ("word_count", "sort", "term_vector",
                           "inverted_index", "ranked_inverted_index",
                           "sequence_count")
        assert (ev.l is None) == (ev.kind != "sequence_count")
    # (4) every non-shed result is bit-identical to the one-shot sync run
    # of the same query list (differential equivalence under shedding)
    want = eng.run(queries)
    for j, (f, w) in enumerate(zip(futs, want)):
        if j not in shed:
            _assert_same(f.result(), w)


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 100_000))
def test_fuzz_burst_submission_then_drain_matches_sync(seed):
    """Degenerate arrival pattern: everything at t=0, no polls, then drain
    (covers pure max_batch + drain flushing)."""
    rng = np.random.default_rng(seed)
    eng = _shared_engine()
    aq = AsyncAnalyticsServer(eng, idle_timeout=100.0, clock=SimClock())
    queries = _mixed_queries(rng, eng, n=int(rng.integers(4, 12)))
    futs = [aq.submit(q) for q in queries]
    aq.drain()
    assert all(f.done() for f in futs)
    for ev in aq.flush_log:
        assert ev.n_corpora <= eng.max_batch
    want = eng.run(queries)
    for f, w in zip(futs, want):
        _assert_same(f.result(), w)


def test_flush_groups_by_size_bucket():
    """Corpora in different grammar-size buckets never share a flush (the
    pack would pad everyone to the biggest member)."""
    rng = np.random.default_rng(3)
    eng = AnalyticsServer(max_batch=4)
    small = make_repetitive_files(rng, 10, n_files=1)
    g, nf = compress_files(small, 10)
    eng.register("small", flatten(g, 10, nf))
    from repro.data.synthetic import CorpusSpec, make_corpus
    big_files = make_corpus(CorpusSpec("big", n_files=4, tokens_per_file=900,
                                       vocab=300, phrase_rate=0.5,
                                       n_phrases=25, phrase_len=7, seed=5))
    g2, nf2 = compress_files(big_files, 300)
    eng.register("big", flatten(g2, 300, nf2))
    assert eng.size_bucket("small") != eng.size_bucket("big")
    aq = AsyncAnalyticsServer(eng, idle_timeout=100.0, clock=SimClock())
    fa = aq.submit(Query("small", "word_count"))
    fb = aq.submit(Query("big", "word_count"))
    aq.drain()
    assert fa.done() and fb.done()
    assert len(aq.flush_log) == 2               # one flush per size bucket
    assert {ev.n_corpora for ev in aq.flush_log} == {1}


def test_threaded_serving_smoke():
    """Real clock + background thread: submissions resolve without manual
    polling and close() drains."""
    eng = _shared_engine()
    with AsyncAnalyticsServer(eng, idle_timeout=0.01,
                              poll_interval=0.002) as aq:
        f1 = aq.submit(Query("c0", "word_count"))
        f2 = aq.submit(Query("c1", "sequence_count", l=3))
        r1 = f1.result(timeout=60)
        r2 = f2.result(timeout=60)
    _assert_same(r1, eng.run([Query("c0", "word_count")])[0])
    _assert_same(r2, eng.run([Query("c1", "sequence_count", l=3)])[0])
    with pytest.raises(RuntimeError):
        aq2 = AsyncAnalyticsServer(eng)
        aq2.start()
        try:
            aq2.start()                          # double start
        finally:
            aq2.close()
