"""Resolve the names in ``BENCHMARK.json`` to the files that define them.

A cell names a configuration and a traffic mix; each is a file of its own
(``configs/<config>.json`` as the configuration's ``file`` says,
``traffic/<mix>.json``), and each per-layer metric is a reader
``metrics/<metric>.py``.  A metric split by the end-to-end metric it moves
(``queue_wait_ms.open``, ``queue_wait_ms.closed``) may share one reader,
``metrics/queue_wait_ms.py``, when it has no file of its own.  Adding a
cell, a mix or a metric therefore takes new files and new entries only.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class SpecError(LookupError):
    """A name in ``BENCHMARK.json`` resolves to nothing."""


def load(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    entry = _named(bench["configs"], name, "config")
    with open(root / entry["file"]) as f:
        return json.load(f)


def traffic(name: str, here: Path = HERE) -> dict:
    path = here / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no traffic mix file {path.name} for {name!r}")
    with open(path) as f:
        return json.load(f)


def metrics_for(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metric entries a run of ``cell`` reports: the end-to-end ones
    untraced, the per-layer ones traced.  An entry without ``workloads``
    belongs to every cell."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]


def reader(name: str, here: Path = HERE) -> Callable:
    """The ``read(record)`` function of a per-layer metric."""
    for stem in (name, name.split(".")[0]):
        path = here / "metrics" / f"{stem}.py"
        if path.is_file():
            mod_name = "chipbench_metric_" + stem.replace(".", "_")
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise SpecError(f"no metric reader metrics/{name}.py for {name!r}")
