"""Every name in BENCHMARK.json resolves to its file, an unknown name
fails, and the file keeps to the benchmark's contract."""

from __future__ import annotations

import sys
from pathlib import Path

import json
import re

import pytest

# the benchmark is a package at the root of the checkout
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import peaks, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load()


def test_every_name_resolves(bench):
    for c in bench["configs"]:
        cfg = spec.config(bench, c["name"])
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg)
    for w in bench["workloads"]:
        assert spec.workload(bench, w["name"]) is w
        mix = spec.traffic(w["traffic"])
        assert mix["loop"] in ("open", "closed")
        spec.config(bench, w["config"])
    for m in bench["per_layer"]:
        assert callable(spec.reader(m["name"]))


def test_unknown_names_fail(bench):
    with pytest.raises(spec.SpecError):
        spec.workload(bench, "no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.config(bench, "no-such-config")
    with pytest.raises(spec.SpecError):
        spec.traffic("no-such-mix")
    with pytest.raises(spec.SpecError):
        spec.reader("no_such_metric.open")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v0")


def test_contract_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    cells = {w["name"] for w in bench["workloads"]}
    for entries in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[entries]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    assert {w["config"] for w in bench["workloads"]} == \
        {c["name"] for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", [cell])
    for cell in cells:
        reported = spec.metrics_for(bench, cell, False)
        assert "setup_s" in {m["name"] for m in reported}
        assert len(reported) >= 2
        assert spec.metrics_for(bench, cell, True)
    assert len(json.dumps(bench)) < 64 * 1024
