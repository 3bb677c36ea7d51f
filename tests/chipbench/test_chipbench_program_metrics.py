"""Per-layer metrics the program counts itself: the reader on a hand-built
record, and the harness's counter snapshot finding the program's counter
on a real server."""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

# the benchmark is a package at the root of the checkout
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import run, spec
from chipbench.record import Record


def _record(counters):
    return Record(cell={"name": "B-analytics-closed"}, window_s=47.0,
                  answered=64, compiles=0, counters=counters,
                  chunk_corpora=64.0)


def test_program_compiles_reads_the_counter():
    read = spec.reader("program_compiles.closed")
    assert read(_record({"compiles": 3.0, "batched_calls": 8.0})) == 3.0
    assert read(_record({"compiles": 0.0})) == 0.0
    # a program without the counter: the metric is left out, no error
    assert read(_record({"batched_calls": 8.0})) is None


def test_harness_snapshot_holds_program_compiles():
    import jax

    from repro.core import compress_files, flatten
    from repro.serving import AnalyticsServer, Query

    rng = np.random.default_rng(9)
    server = AnalyticsServer(max_batch=4)
    for i in range(2):
        files = [list(rng.integers(0, 12, 40)) for _ in range(2)]
        g, nf = compress_files(files, 12)
        server.register(f"c{i}", flatten(g, 12, nf))
    before = run._counters(server)
    jax.clear_caches()
    server.run([Query(f"c{i}", "word_count") for i in range(2)])
    d = run._delta(run._counters(server), before)
    assert d["compiles"] == server.stats.compiles > 0
