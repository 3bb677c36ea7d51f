"""The harness end to end on the CPU at a tiny size: it refuses to run
without a TPU or without the program, a sound run comes out correct, and a
run whose timed path is broken underneath, or whose answers are the
control's, comes out not correct.

The look for a chip is skipped by calling ``run_cell`` with
``require_chip=False``; everything after it runs as on the chip.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

# the benchmark is a package at the root of the checkout
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import control, run, spec

ROOT = Path(__file__).resolve().parents[2]
CFG = {"name": "tiny", "corpora": 3, "files_per_corpus": [2, 3],
       "tokens_per_file": 150, "vocab": 300, "phrase_rate": 0.5,
       "n_phrases": 6, "phrase_len": 5, "history_rate": 0.02,
       "revisions": 4, "popularity_zipf_s": 1.1,
       "server": {"max_batch": 16, "method": "auto",
                  "max_cached_batches": 3},
       "queue": {"idle_timeout": 0.005, "max_wait": 0.05}}
KINDS = {"word_count": 0.4, "search_bm25": 0.3, "filter_count": 0.3}
SEED = 2 ** 31 + 77

#: an open-loop mix (no cell runs one yet): all eleven served kinds
OPEN_MIX = {
    "loop": "open",
    "arrivals": {"burst_factor": 4, "burst_frac": 0.25, "mean_phase_s": 2.0},
    "deadline_frac": 0.5, "deadline_s": 30.0,
    "kinds": {"search_bm25": 0.20, "search_tfidf": 0.10,
              "filter_count": 0.15, "agg_terms": 0.15, "word_count": 0.10,
              "term_vector": 0.05, "inverted_index": 0.05, "sort": 0.05,
              "ranked_inverted_index": 0.05, "sequence_count": 0.05,
              "phrase_count": 0.05},
    "params": {"pool": 64, "pool_zipf_s": 1.1, "head_terms": 256,
               "sequence_l": 3, "phrase_min": 2, "phrase_max": 4},
}


def _mix(loop, **kw):
    base = (dict(OPEN_MIX, rate_qps=300.0) if loop == "open"
            else spec.traffic("analytics-closed"))
    base["kinds"] = KINDS
    if loop == "closed":
        base.update(clients=[[k] for k in KINDS])
    base.update(kw)
    return base


OPEN_METRICS = [{"name": "p50_ms", "unit": "ms"},
                {"name": "p95_ms", "unit": "ms"},
                {"name": "setup_s", "unit": "s"}]


def _run(loop="open", hook=None, **mix):
    cell = {"name": "B-analytics-closed", "chips": 1}
    metrics = (OPEN_METRICS if loop == "open"
               else spec.metrics_for(spec.load(), cell["name"], False))
    return run.run_cell(cell, CFG, _mix(loop, **mix), metrics, SEED, 0.4,
                        False, require_chip=False, hook=hook, workers=0)


def _result_lines(stdout):
    return [line for line in stdout.splitlines() if line.startswith("{")]


def _cli(cwd, args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


ARGS = ["--workload", "B-analytics-closed", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def test_refuses_without_tpu():
    out = _cli(ROOT, ARGS)
    assert out.returncode == 1, out.stderr[-2000:]
    assert "no TPU found" in out.stderr
    assert not _result_lines(out.stdout)


def test_refuses_without_the_program(tmp_path):
    bench = spec.load()
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(tmp_path, ARGS)
    assert out.returncode != 0
    assert not _result_lines(out.stdout)


def test_unknown_workload_fails():
    out = _cli(ROOT, ["--workload", "nope", "--seed", "1", "--seconds", "1",
                      "--trace", "0"])
    assert out.returncode == 2 and not _result_lines(out.stdout)


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_sound_run_is_correct(loop):
    r = _run(loop)
    assert r["correct"], r["checks"]
    assert r["checks"]["compared"]["value"] > 0
    assert r["checks"]["mismatched"]["value"] == 0
    assert list(r)[-1] == "checks"
    names = set(r["metrics"])
    assert names == ({"p50_ms", "p95_ms", "setup_s"} if loop == "open"
                     else {"queries_per_s", "setup_s"})
    assert all(v["value"] > 0 for v in r["metrics"].values())
    json.dumps(r)


def _alter(x):
    if isinstance(x, tuple):
        return (_alter(x[0]),) + x[1:]
    a = np.array(x, copy=True)
    if a.size == 0:
        return np.ones(1, np.float32)
    a.reshape(-1)[0] = (not a.reshape(-1)[0]) if a.dtype == bool \
        else a.reshape(-1)[0] + 1
    return a


def test_altered_answer_is_caught(monkeypatch):
    from repro.serving.analytics_server import AnalyticsServer

    orig = AnalyticsServer.execute_chunk

    def broken(self, kind, chunk, **kw):
        out = orig(self, kind, chunk, **kw)
        first = next(iter(out))
        out[first] = _alter(out[first])
        return out

    monkeypatch.setattr(AnalyticsServer, "execute_chunk", broken)
    r = _run()
    assert not r["correct"]
    assert r["checks"]["mismatched"]["value"] > 0


def test_half_the_chunk_left_out_is_caught(monkeypatch):
    from repro.serving.analytics_server import AnalyticsServer

    orig = AnalyticsServer.execute_chunk
    halved = []

    def broken(self, kind, chunk, **kw):
        half = max(1, len(chunk) // 2)
        out = orig(self, kind, chunk[:half], **kw)
        halved.append(len(chunk) > 1)
        return {c: out[chunk[i % half]] for i, c in enumerate(chunk)}

    monkeypatch.setattr(AnalyticsServer, "execute_chunk", broken)
    # one size bucket, so that a flush holds several corpora
    monkeypatch.setattr(AnalyticsServer, "size_bucket", lambda self, n: 0)
    r = _run(kinds={"word_count": 1.0}, rate_qps=2000.0)
    assert any(halved)
    assert not r["correct"]
    assert r["checks"]["mismatched"]["value"] > 0


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_answer_that_never_comes_is_caught(monkeypatch, loop):
    from repro.serving.queue import AsyncAnalyticsServer

    orig = AsyncAnalyticsServer._flush_group

    def broken(self, g, reason, now):
        g.items = g.items[1:]            # the first query is never answered
        return orig(self, g, reason, now)

    monkeypatch.setattr(AsyncAnalyticsServer, "_flush_group", broken)
    monkeypatch.setattr(run, "WAIT_AFTER_S", 0.5)
    monkeypatch.setattr(run, "WARMUP_WAIT_S", 0.5)
    r = _run(loop)
    assert not r["correct"]
    assert r["checks"]["unanswered"]["value"] > 0


def test_control_is_not_correct():
    r = _run(hook=control.bfloat16_answers)
    assert not r["correct"]
    assert r["checks"]["mismatched"]["value"] > 0
