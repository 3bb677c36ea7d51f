#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, on the TPU of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``configs/``) under a traffic mix (``traffic/``).  One run:

1. generates the configuration's corpora from ``--seed`` and compresses
   them in a spawn pool that never starts a JAX backend;
2. registers them with the program's ``AnalyticsServer``, wrapped in its
   ``AsyncAnalyticsServer`` (the queue's library defaults unless the
   configuration sets them);
3. warms up: a closed loop sends at once one job of each kind and
   parameter shape its clients can send (``traffic.warmup_jobs``; every
   chunk of the window holds one such job, so these are its programs); an
   open loop replays the window's own schedule once;
4. measures ``--seconds`` of that traffic, through
   ``AsyncAnalyticsServer.submit`` and the future's result, digesting every
   answer as it arrives.  A closed loop's clients send nothing new once
   the time is up and wait for what they sent: its rate is every answer
   over the time to the last one;
5. frees the program's state and compares every answer with the plain
   decompress-then-scan reference (``reference.py``);
6. prints one JSON line: ``correct``, ``attempted``, ``failed``, the
   cell's end-to-end metrics (``--trace 0``) or per-layer metrics
   (``--trace 1``, with a profiler trace of the window), ``device``, and,
   last, ``checks``: each number compared with its limit.  The checks are
   also the last lines on standard error.

Set-up time runs from the start of the process to the end of the warm-up.
JAX's persistent compile cache lives in ``.chipbench_cache/jax`` inside the
checkout, so only a cell's first run there compiles.  The run exits with 1
and prints no result when JAX finds no TPU, fewer chips than the cell asks
for, or a chip whose peaks are unknown; with 2 when a name does not
resolve or the program is not beside the benchmark.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import corpus, drive, peaks, reference, spec  # noqa: E402
from chipbench import trace as tracing  # noqa: E402
from chipbench.record import Record  # noqa: E402

CACHE_DIR = ROOT / ".chipbench_cache"
#: Seconds past the window's close that answers due in it are awaited.
WAIT_AFTER_S = 60.0
#: Seconds past the warm-up's close that its answers are awaited: the
#: window starts on an empty queue.
WARMUP_WAIT_S = 900.0
#: The harness's spans around calls into the program (traced runs only).
SPANS = {"_flush_group": "chipbench.flush",
         "_get_batch": "chipbench.pack_build",
         "_execute_batched": "chipbench.execute",
         "_run_single": "chipbench.execute"}
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """No TPU, too few chips, or a chip the peaks table does not know."""


def log(**fields) -> None:
    print(json.dumps(fields), file=sys.stderr, flush=True)


# ------------------------------------------------------------------ jax --
def open_chips(chips: int):
    """The chips this run uses; JAX's compile cache set to the checkout."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX's devices are "
                     f"{devices[0].platform} ({len(devices)} of them)")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    try:
        peaks.peaks(devices[0].device_kind)
    except peaks.UnknownDevice as e:
        raise NoChip(str(e)) from None
    return devices[:chips]


class Compiles:
    """Counts JAX's compile events (a compile, or a load from the
    persistent cache) and persistent-cache hits."""

    def __init__(self):
        import jax

        self.n, self.seconds, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def state(self):
        return self.n, self.seconds, self.hits


def _annotate(obj, spans) -> None:
    """Open a profiler span around each named method of ``obj`` (on the
    instance, so nothing else in the process changes)."""
    import jax

    for attr, name in spans.items():
        if hasattr(obj, attr):
            fn = getattr(obj, attr)

            def wrapped(*a, _fn=fn, _name=name, **kw):
                with jax.profiler.TraceAnnotation(_name):
                    return _fn(*a, **kw)
            setattr(obj, attr, wrapped)


def _counters(server) -> dict:
    st = server.stats
    out = {a: float(getattr(st, a)) for a in type(st)._SCALARS}
    # corpora summed over batched chunks: a pack signature starts with
    # its corpus count
    out["pack_corpora"] = float(sum(sig[0] * n for sig, n
                                    in dict(st.signatures).items()))
    out["stages"] = {s: (st.stage_seconds.labels(s).count,
                         st.stage_seconds.labels(s).sum)
                     for s in ("queue_wait", "pack_build", "execute",
                               "compile")}
    return out


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after if k != "stages"}


# ----------------------------------------------------------------- cell --
def run_cell(cell: dict, cfg: dict, mix: dict, metrics: list, seed: int,
             seconds: float, trace: bool, require_chip: bool = True,
             hook: Optional[Callable] = None, workers: Optional[int] = None,
             t_start: Optional[float] = None) -> dict:
    """One run of ``cell`` reporting ``metrics`` (entries of
    ``BENCHMARK.json``); returns the result line as a dict.  ``hook`` (the
    control) transforms each answer before it is digested."""
    t_start = _PROCESS_START if t_start is None else t_start
    import jax

    from repro.serving import AnalyticsServer, AsyncAnalyticsServer, Query
    from chipbench import traffic

    if require_chip:
        devices = open_chips(cell["chips"])
    else:
        devices = jax.devices()[: cell["chips"]]
    compiles = Compiles()

    t = time.perf_counter()
    dep = corpus.build(cfg, seed, **({} if workers is None
                                     else {"workers": workers}))
    t_built = time.perf_counter()
    server = AnalyticsServer(**cfg["server"])
    for name, store in dep.stores.items():
        server.register(name, store)
    aq = AsyncAnalyticsServer(server, **cfg.get("queue", {}))
    if trace:
        _annotate(server, SPANS)
        _annotate(aq, SPANS)
    aq.start()

    def submit(corpus_name, kind, params, deadline):
        return aq.submit(Query(corpus_name, kind, **params),
                         deadline=deadline)

    zipf_s = cfg["popularity_zipf_s"]
    if mix["loop"] == "open":
        schedule = traffic.open_schedule(mix, dep.files, zipf_s, seed,
                                         seconds)

        def loop(keep, wait_s, hook=None):
            return drive.run_open(submit, schedule, seconds,
                                  clock=server.clock, keep=keep,
                                  wait_s=wait_s, hook=hook)
        warmup, warm_seconds = schedule, seconds
    elif mix["loop"] == "closed":
        def loop(keep, wait_s, hook=None):
            streams = traffic.closed_streams(mix, dep.files, seed)
            return drive.run_closed(submit, streams, seconds,
                                    clock=server.clock, keep=keep,
                                    wait_s=wait_s, hook=hook)
        warmup = [(0.0, q, None)
                  for job in traffic.warmup_jobs(mix, dep.files, seed)
                  for q in job]
        warm_seconds = 0.0
    else:
        raise spec.SpecError(f"unknown loop {mix['loop']!r}")

    t_warm = time.perf_counter()
    c_setup = compiles.state()
    warm = drive.run_open(submit, warmup, warm_seconds, clock=server.clock,
                          keep=False, wait_s=WARMUP_WAIT_S)
    setup_s = time.perf_counter() - t_start
    c0 = compiles.state()
    setup = {"total_s": setup_s,
             "before_build_s": t - t_start,
             "generate_s": dep.seconds["generate"],
             "sequitur_s": dep.seconds["sequitur"],
             "build_workers": dep.seconds["workers"],
             "register_s": t_warm - t_built,
             "warmup_s": t_start + setup_s - t_warm,
             "compiles": c0[0], "compile_or_load_s": c0[1],
             "cache_hits": c0[2],
             "compiles_in_warmup": c0[0] - c_setup[0],
             "warmup_queries": len(warm.outcomes),
             "warmup_unanswered": sum(o.done is None for o in warm.outcomes)}
    log(setup=setup)

    before = _counters(server)
    trace_dir = str(CACHE_DIR / "trace")
    if trace:
        tracing.start(trace_dir)
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        w = loop(keep=True, wait_s=WAIT_AFTER_S, hook=hook)
    if trace:
        tracing.stop()
    after = _counters(server)
    c1 = compiles.state()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    aq.close()
    del loop, submit, aq, server, dep.stores
    gc.collect()

    t_check = time.perf_counter()
    checks = check(w, dep)
    check_s = time.perf_counter() - t_check

    lat = drive.latencies_ms(w)
    shed = sum(o.error == "DeadlineExceeded" for o in w.outcomes)
    failed = int(sum(o.error is not None or o.done is None
                     for o in w.outcomes))
    answered_in_window = drive.answered_by(w, w.stop)
    window = {"queries": len(w.outcomes), "answered": int(sum(
              o.done is not None and o.error is None for o in w.outcomes)),
              "answered_in_window": answered_in_window, "shed": shed,
              "failed": failed, "seconds": w.stop - w.start,
              "drained_s": w.end - w.stop, "measured_s": w.end - w.start,
              "compiles": c1[0] - c0[0],
              "compile_or_load_s": c1[1] - c0[1], "check_s": check_s}
    if mix["loop"] == "open":
        lag = drive.sender_lag_ms(w)
        window.update(sender_lag_p95_ms=drive.percentile(lag, 95),
                      sender_lag_max_ms=float(lag.max(initial=0.0)),
                      deadline_met_share=drive.deadline_met_share(w),
                      backlog_trend=drive.backlog_trend(w))
    window["by_kind_ms"] = drive.by_kind_ms(w)
    log(window=window)

    values = {}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {}
    if not trace:
        read = {
            "p50_ms": lambda: drive.percentile(lat, 50),
            "p95_ms": lambda: drive.percentile(lat, 95),
            "queries_per_s": lambda: window["answered"] / (w.end - w.start),
            "setup_s": lambda: setup_s,
        }
        for m in metrics:
            values[m["name"]] = {"value": read[m["name"]](),
                                 "unit": m["unit"]}
    else:
        summary = tracing.summarize(*tracing.read_dir(trace_dir),
                                    chips=len(devices))
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        d = _delta(after, before)
        rec = Record(cell=cell, window_s=w.end - w.start,
                     answered=window["answered"], compiles=c1[0] - c0[0],
                     counters=d,
                     chunk_corpora=d["pack_corpora"] + d["single_calls"],
                     stages={s: (after["stages"][s][0]
                                 - before["stages"][s][0],
                                 after["stages"][s][1]
                                 - before["stages"][s][1])
                             for s in after["stages"]},
                     trace=summary)
        for m in metrics:
            v = spec.reader(m["name"])(rec)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = tracing.breakdown(summary)
    correct = all(c["ok"] for c in checks.values())
    result = {"correct": correct, "attempted": len(w.outcomes),
              "failed": failed, "metrics": values, "device": device}
    result.update(out)
    result["setup_parts"] = setup
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"],
                            "is": c["is"]} for k, c in checks.items()}
    return result


def check(w: drive.Window, dep: corpus.Deployment) -> dict:
    """Compare every answer of the window with the reference, corpus by
    corpus (one scan of a corpus serves all its answers)."""
    by_corpus: dict = {}
    for o in w.outcomes:
        if o.digest is not None:
            k = (o.kind, o.params_key())
            slot = by_corpus.setdefault(o.corpus, {}).setdefault(
                k, (o.params, []))
            slot[1].append(o.digest)
    compared = mismatched = 0
    for name, answers in by_corpus.items():
        files = dep.files[name]
        meta = reference.Meta(dep.vocab, len(files))
        stream = reference.raw_stream(files, dep.vocab)
        tv = (reference.oracle_term_vector(meta, stream)
              if any(k in reference.TV_KINDS for k, _ in answers) else None)
        for (kind, _), (params, digests) in answers.items():
            want = reference.digest(reference.answer(meta, stream, kind,
                                                     params, tv))
            bad = sum(d != want for d in digests)
            compared += len(digests)
            mismatched += bad
            if bad and mismatched - bad < 5:
                log(mismatch={"corpus": name, "kind": kind,
                              "params": params, "answers": len(digests),
                              "differ": bad})
        del tv
    # an answer that never came, or that raised anything but the queue's
    # deadline shedding, is for correctness; a shed query is only failed
    unanswered = sum(1 for o in w.outcomes
                     if o.error not in (None, "DeadlineExceeded")
                     or (o.error is None and o.digest is None))
    return {
        "mismatched": {"value": mismatched, "limit": 0, "is": "at most",
                       "ok": mismatched <= 0},
        "unanswered": {"value": unanswered, "limit": 0, "is": "at most",
                       "ok": unanswered <= 0},
        "compared": {"value": compared, "limit": 1, "is": "at least",
                     "ok": compared >= 1},
    }


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']} ({c['is']} {c['limit']})",
              file=sys.stderr, flush=True)


# ----------------------------------------------------------------- main --
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = spec.load()
        cell = spec.workload(bench, args.workload)
        cfg = spec.config(bench, cell["config"])
        mix = spec.traffic(cell["traffic"])
        metrics = spec.metrics_for(bench, cell["name"], bool(args.trace))
        if args.trace:
            for m in metrics:
                spec.reader(m["name"])
    except (OSError, spec.SpecError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro.serving  # noqa: F401
    except ImportError as e:
        print(f"chipbench: the program is not importable beside the "
              f"benchmark ({e})", file=sys.stderr)
        return 2
    try:
        result = run_cell(cell, cfg, mix, metrics, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
