"""Observability demo: metrics exposition + per-query span trees.

Registers a few Table-II-analogue corpora, serves a mixed burst through
both the sync server and the async queue, and then shows what the
observability layer captured (docs/observability.md):

* the span tree of one query's whole lifecycle — submit, queue wait,
  flush, pack build, execute with its readback and compile parts — read
  off ``query.trace``;
* the server's metrics registry rendered two ways: the JSON ``snapshot``
  (what BENCH uploads) and the Prometheus text exposition (what a
  scrape endpoint would serve);
* a slice of the process-global registry — kernel dispatch decisions and
  store memo traffic recorded by the library layers below the server.

    PYTHONPATH=src python examples/metrics.py
"""

import json
import time

from repro.core import compress_files, flatten
from repro.data.synthetic import TABLE2, make_table2_corpus
from repro.obs import global_registry
from repro.serving import AnalyticsServer, AsyncAnalyticsServer, Query


def _print_span(span, depth: int = 0) -> None:
    pad = "  " * depth
    attrs = ", ".join(f"{k}={v}" for k, v in span.attrs.items())
    print(f"{pad}{span.name:<12} {span.duration * 1e3:8.3f} ms"
          + (f"   [{attrs}]" if attrs else ""))
    for child in span.children:
        _print_span(child, depth + 1)


def main() -> None:
    engine = AnalyticsServer(max_batch=4, method="auto")
    for name in ("A", "B", "D"):
        files = make_table2_corpus(name)
        g, nf = compress_files(files, TABLE2[name].vocab)
        engine.register(name, flatten(g, TABLE2[name].vocab, nf))

    # ---- sync path: every run() query gets a root span --------------------
    queries = [Query(n, "word_count") for n in ("A", "B", "D")]
    engine.run(queries)                      # cold: pack build + compile
    queries = [Query(n, "word_count") for n in ("A", "B", "D")]
    engine.run(queries)                      # warm: cache hit + execute

    print("warm sync query span tree (shared run_group/chunk subtree is")
    print("the batching — three queries, one engine call):")
    _print_span(queries[0].trace)

    # ---- async path: spans grow queue_wait and flush stages ---------------
    with AsyncAnalyticsServer(engine, idle_timeout=0.01,
                              poll_interval=0.002) as queue:
        q = Query("A", "sequence_count", l=3)
        fut = queue.submit(q, deadline=time.monotonic() + 1.0)
        fut.result(timeout=60)
    print("\nasync query span tree (queue_wait + flush around the chunk):")
    _print_span(q.trace)

    # ---- exposition -------------------------------------------------------
    snap = engine.registry.snapshot()
    stage = snap["repro_server_stage_seconds"]["samples"]
    print("\nJSON snapshot, stage-latency excerpt:")
    for s in stage:
        print(f"  stage={s['labels']['stage']:<12} n={s['count']:<3} "
              f"p99={s['p99'] * 1e3:.3f} ms")

    print("\nPrometheus exposition (server registry, first 20 lines):")
    for line in engine.registry.render_prometheus().splitlines()[:20]:
        print(f"  {line}")

    print("\nprocess-global library metrics (dispatch / memo / plans):")
    gsnap = global_registry().snapshot()
    for name in sorted(gsnap):
        for s in gsnap[name]["samples"]:
            labels = ",".join(f"{k}={v}" for k, v in s["labels"].items())
            value = s.get("value", s.get("count"))
            print(f"  {name}{{{labels}}} = {value}")

    # the snapshot is JSON-safe end to end (what CI uploads as an artifact)
    json.dumps({"server": snap, "global": gsnap})
    print("\nsnapshot serializes cleanly; "
          f"trace log holds {len(engine.trace_log)} root spans")


if __name__ == "__main__":
    main()
