"""The device set-up shared by the chip programs (repro/device.py)."""

from __future__ import annotations

import os
from pathlib import Path

import jax
import pytest

from repro import device


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(device.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert device.compile_cache_dir() == str(tmp_path)
    assert device.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; no other directory is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv(device.CACHE_ENV, raising=False)
    repo = Path(__file__).resolve().parents[1]
    first, second = device.compile_cache_dir(), device.compile_cache_dir()
    assert first == second == str(repo / ".jax_cache")
    assert str(os.getpid()) not in first
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
    before = jax.config.jax_compilation_cache_dir
    try:
        assert device.enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_require_tpu_refuses_cpu():
    with pytest.raises(device.NoTPUError, match="no TPU found"):
        device.require_tpu()


def test_device_line_names_the_device():
    line = device.device_line(jax.devices())
    assert line == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                    "count": len(jax.devices())}
