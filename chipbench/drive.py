"""Drive the queue: an open loop on a schedule, or a closed loop of
clients, recording for every query when it was due, when it was sent,
when its answer came and a digest of that answer.

Both loops talk to the program through one call,
``submit(corpus, kind, params, deadline) -> Future``.  Answers are reduced
to digests on a thread of their own as they arrive and then dropped, so a
window of dense answers never piles up in host memory.
"""

from __future__ import annotations

import json
import queue
import threading
import time
from concurrent.futures import TimeoutError as FutureTimeout
from concurrent.futures import wait
from dataclasses import dataclass
from typing import Callable, List, Optional

import numpy as np

from chipbench import reference

#: How far ahead of its first query a loop takes its start time.
LEAD_S = 0.05


@dataclass
class Outcome:
    """One query: its identity, and when it was due, sent and answered."""
    corpus: str
    kind: str
    params: dict
    due: float
    deadline: Optional[float] = None     # absolute, open loop only
    sent: float = float("nan")
    done: Optional[float] = None
    digest: Optional[str] = None
    error: Optional[str] = None          # exception type name

    def params_key(self) -> str:
        return json.dumps(self.params, sort_keys=True)


@dataclass
class Window:
    outcomes: List[Outcome]
    start: float                          # first query due
    stop: float                           # the schedule's or the loop's end
    end: float                            # last answer (or give-up) time


#: Threads that digest answers (hashing releases the interpreter lock).
DIGEST_THREADS = 4


class _Digester:
    """Reduces answers to digests off the sending threads."""

    def __init__(self, keep: bool, hook: Optional[Callable] = None):
        self.keep, self.hook = keep, hook
        self.q: queue.SimpleQueue = queue.SimpleQueue()
        self.threads = [threading.Thread(target=self._run, daemon=True,
                                         name=f"chipbench-digest-{i}")
                        for i in range(DIGEST_THREADS)]
        for t in self.threads:
            t.start()

    def put(self, outcome: Outcome, result) -> None:
        self.q.put((outcome, result if self.keep else None))

    def _run(self) -> None:
        while True:
            item = self.q.get()
            if item is None:
                return
            o, result = item
            if self.keep:
                if self.hook is not None:
                    result = self.hook(result)
                o.digest = reference.digest(result)

    def close(self) -> None:
        for _ in self.threads:
            self.q.put(None)
        for t in self.threads:
            t.join()


def run_open(submit, schedule, seconds: float, clock=time.monotonic,
             keep: bool = True, wait_s: float = 60.0,
             hook: Optional[Callable] = None) -> Window:
    """Send each ``(at_s, (corpus, kind, params), rel_deadline)`` of the
    schedule when it is due, whatever is still outstanding, then wait up to
    ``wait_s`` past the schedule's end for the answers."""
    dig = _Digester(keep, hook)
    outs: List[Outcome] = []
    futs = []
    t0 = clock() + LEAD_S
    waiting = threading.Event()
    waiting.set()

    def on_done(o: Outcome, fut) -> None:
        if not waiting.is_set():           # came after the wait: unanswered
            return
        o.done = clock()
        exc = fut.exception()
        if exc is not None:
            o.error = type(exc).__name__
        else:
            dig.put(o, fut.result())

    for at, (corpus, kind, params), rel in schedule:
        due = t0 + at
        o = Outcome(corpus, kind, params, due,
                    deadline=None if rel is None else due + rel)
        outs.append(o)
        delay = due - clock()
        if delay > 0:
            time.sleep(delay)
        o.sent = clock()
        try:
            fut = submit(corpus, kind, params, o.deadline)
        except Exception as e:                  # noqa: BLE001 — recorded
            o.error, o.done = type(e).__name__, None
            continue
        futs.append(fut)
        fut.add_done_callback(lambda f, o=o: on_done(o, f))
    stop = t0 + seconds
    wait(futs, timeout=max(0.0, stop + wait_s - clock()))
    waiting.clear()
    end = clock()
    dig.close()
    return Window(outs, t0, stop, end)


def run_closed(submit, streams, seconds: float, clock=time.monotonic,
               keep: bool = True, wait_s: float = 60.0,
               hook: Optional[Callable] = None) -> Window:
    """Each stream is one client: it sends its next request (a list of
    queries, sent together) as soon as the answers to its last one have
    arrived, until ``seconds`` have passed."""
    dig = _Digester(keep, hook)
    outs: List[Outcome] = []
    lock = threading.Lock()
    t0 = clock() + LEAD_S
    stop = t0 + seconds

    def client(stream) -> None:
        delay = t0 - clock()
        if delay > 0:
            time.sleep(delay)
        while True:
            now = clock()
            if now >= stop:
                return
            sent = []
            for corpus, kind, params in next(stream):
                o = Outcome(corpus, kind, params, now, sent=now)
                with lock:
                    outs.append(o)
                try:
                    sent.append((o, submit(corpus, kind, params, None)))
                except Exception as e:          # noqa: BLE001 — recorded
                    o.error = type(e).__name__
            for o, fut in sent:
                try:
                    result = fut.result(
                        timeout=max(0.0, stop + wait_s - clock()))
                except FutureTimeout:
                    o.error = "unanswered"
                    return
                except Exception as e:          # noqa: BLE001 — recorded
                    o.error = type(e).__name__
                    continue
                o.done = clock()
                dig.put(o, result)
                del result

    threads = [threading.Thread(target=client, args=(s,), daemon=True,
                                name=f"chipbench-client-{i}")
               for i, s in enumerate(streams)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    end = clock()
    dig.close()
    return Window(outs, t0, stop, end)


# ------------------------------------------------------------- reading --
def latencies_ms(w: Window) -> np.ndarray:
    """Per query, answer time minus due time; a query shed, failed or never
    answered counts as infinitely late."""
    return np.array([(o.done - o.due) * 1e3
                     if o.done is not None and o.error is None
                     else np.inf for o in w.outcomes])


def percentile(values: np.ndarray, q: float) -> float:
    """The q-th percentile as a sample of ``values`` (no interpolation, so
    an infinitely late query stays infinitely late)."""
    return float(np.percentile(values, q, method="inverted_cdf"))


def answered_by(w: Window, t: float) -> int:
    return sum(1 for o in w.outcomes
               if o.done is not None and o.error is None and o.done <= t)


def sender_lag_ms(w: Window) -> np.ndarray:
    """How late the open loop sent each query after it was due."""
    return np.array([(o.sent - o.due) * 1e3 for o in w.outcomes])


def deadline_met_share(w: Window) -> Optional[float]:
    """Share of the deadline-carrying queries answered by their
    deadline."""
    dl = [o for o in w.outcomes if o.deadline is not None]
    if not dl:
        return None
    return sum(o.done is not None and o.error is None
               and o.done <= o.deadline for o in dl) / len(dl)


def by_kind_ms(w: Window) -> dict:
    """Per kind: queries, and the median and 95th percentile of their
    latency (ms, a query not answered counting as infinitely late)."""
    lat = latencies_ms(w)
    out = {}
    for kind in sorted({o.kind for o in w.outcomes}):
        v = lat[[o.kind == kind for o in w.outcomes]]
        out[kind] = [int(len(v)), percentile(v, 50), percentile(v, 95)]
    return out


def backlog_trend(w: Window) -> Optional[float]:
    """Median latency of the schedule's last third over its first third: a
    backlog that grows through the window reads well above 1."""
    lat = latencies_ms(w)
    n = len(lat) // 3
    if n < 5:
        return None
    return percentile(lat[-n:], 50) / percentile(lat[:n], 50)
