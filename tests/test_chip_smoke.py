"""chip_smoke.py: its refusals, and its phases rehearsed on the CPU.

The script itself refuses to run anywhere but on a TPU; the rehearsal
steers it in-process (the platform check and the deployment sizes are
patched here, never through an option of the program) so that every phase
and every oracle comparison runs on tiny corpora.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]


def _run_script(path: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(path)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _has_result_line(stdout: str) -> bool:
    return any(line.startswith('{"ok"') for line in stdout.splitlines())


def test_refuses_without_tpu():
    out = _run_script(REPO / "chip_smoke.py", REPO)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert not _has_result_line(out.stdout)


def test_fails_without_the_repository(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run_script(tmp_path / "chip_smoke.py", tmp_path)
    assert out.returncode != 0
    assert not _has_result_line(out.stdout)


def test_cpu_rehearsal_checks_every_kind(monkeypatch, capsys, tmp_path):
    monkeypatch.syspath_prepend(str(REPO))
    import chip_smoke
    from repro import device
    from repro.kernels import ops
    from repro.serving.analytics_server import SERVED_KINDS

    monkeypatch.setattr(device, "require_tpu", jax.devices)
    monkeypatch.setattr(chip_smoke, "DEPLOYMENTS", {
        "A": dict(chip_smoke.DEPLOYMENTS["A"], corpora=5, files=(3, 6),
                  vocab=400),
        "B": dict(chip_smoke.DEPLOYMENTS["B"], corpora=2,
                  tokens_per_file=600, vocab=2500, revisions=6,
                  history_rate=0.05),
    })
    # B's two corpora pass the flat-bin limit, as B's full vocabulary does
    # on the chip, so its word count runs in row chunks; A's stays flat
    monkeypatch.setattr(ops, "BINCOUNT_BATCH_FLAT_LIMIT", 4096)
    monkeypatch.setattr(chip_smoke, "QUERIES_PER_KIND", 3)
    monkeypatch.setattr(chip_smoke, "BUILD_WORKERS", 2)
    # set: the script leaves JAX's cache configuration alone
    monkeypatch.setenv(device.CACHE_ENV, str(tmp_path))
    assert chip_smoke.main(["--seed", "3"]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert lines[-1] == {"ok": True, "device": device.device_line(
        jax.devices())}
    steps = [line.get("step") for line in lines[:-1]]
    assert "mismatch" not in steps
    for d in ("A", "B"):
        kinds = {line["kind"] for line in lines
                 if line.get("step") == "sync" and line["deployment"] == d}
        assert kinds == set(SERVED_KINDS), d
        assert any(line.get("step") == "async" and line["deployment"] == d
                   for line in lines)
    dispatch = next(line for line in lines if line.get("step") == "dispatch")
    assert dispatch["counts"]["exec:bincount=pallas_interpret"] >= 1
    assert dispatch["counts"]["bincount_batch=chunked"] >= 1
    assert dispatch["counts"]["bincount_batch=flat"] >= 1
    depth = {line["deployment"]: line["max_dag_depth"] for line in lines
             if line.get("step") == "deployment"}
    assert depth["B"] > depth["A"]
    paths = next(line for line in lines if line.get("step") == "kernel_paths")
    assert paths["paths"]["ell_fused"] == "jnp_ref"
