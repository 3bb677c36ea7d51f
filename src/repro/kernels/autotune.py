"""Block-size / XLA-flag autotuner for the Pallas kernel dispatch layer.

The dispatch predicates in ops.py (``ell_batched_use_ref`` and friends) and
the kernel block sizes (row-block ``br``, weight-chunk ``wc``, the vector
kernel's F-block ``fc``) ship with static defaults.  This module measures
the real machine instead:

* ``tune_ell_batched`` / ``tune_ell_fused`` / ``tune_ell_vector`` sweep
  candidate block shapes (the jnp reference form is itself a candidate, so
  the sweep also answers the ref-vs-kernel routing question) and return a
  winner entry;
* winners land in an in-process table keyed ``(backend, kind,
  shape-bucket)`` — shape buckets are the same pow2 rounding the packing
  layer uses, so one tuning run covers every pack that compiles to the
  same program;
* ops.py consults the in-process table first (``tuned_use_ref`` /
  ``tuned_blocks``) and falls back to its shipped defaults and static
  heuristics on a miss.  The table holds only this process's own sweeps;
  no file steers dispatch.  A table can never change results, only speed;
* ``sweep_xla_flags`` times a workload under named XLA flag sets in fresh
  subprocesses (flags are process-global, so in-process sweeping is
  impossible) — the flag-set dictionary follows saxml's
  ``llm_xla_flags.py`` shape: named, per-backend, composable.  Failures
  (unknown flag on this jax build) score ``inf`` and lose, never crash.
  Its children initialize JAX, so it needs the accelerator free: never run
  it from a process that holds the chip;
* ``hlo_profile`` revives utils/hlo_analysis.py + launch/roofline.py as
  measurement instrumentation: op histogram, FLOP/byte estimates and
  roofline classification for any jitted workload.

benchmarks/bench_batch.py runs the sweeps on its corpus set and records
``autotune/*`` rows into BENCH_batch.json.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Any, Callable, Dict, Iterable, Optional, Sequence, Tuple

from repro.obs.tracing import annotation

from ._common import DEFAULT_BR, DEFAULT_FC, DEFAULT_WC, round_up_pow2

# Candidate grids.  Small on purpose: each candidate is a fresh compile.
BR_CANDIDATES = (128, 256, 512)
WC_CANDIDATES = (1 << 16, 1 << 19)
BRV_CANDIDATES = (32, 64, 128)
FC_CANDIDATES = (64, 128, 256)

# Named XLA flag sets per backend (saxml llm_xla_flags.py idiom: a flat
# name -> {flag: value} table; "default" is the empty set and always a
# candidate, so the sweep's winner can never be slower than shipping
# defaults).  TPU sets are carried for when a TPU runner executes the
# sweep; the CPU sets are conservative, widely-available flags.
XLA_FLAG_SETS: Dict[str, Dict[str, Dict[str, str]]] = {
    "cpu": {
        "default": {},
        "fast_min_max": {"xla_cpu_enable_fast_min_max": "true"},
        "no_fast_min_max": {"xla_cpu_enable_fast_min_max": "false"},
    },
    "tpu": {
        "default": {},
        "latency_hiding": {
            "xla_tpu_enable_latency_hiding_scheduler": "true",
        },
        "async_collectives": {
            "xla_enable_async_all_gather": "true",
            "xla_enable_async_collective_permute": "true",
        },
    },
}


def backend_name() -> str:
    import jax
    return jax.default_backend()


def shape_bucket(*dims: int) -> Tuple[int, ...]:
    """Pow2-bucketed shape key — the same rounding the packing layer uses,
    so every pack that shares a compiled program shares a tuning entry."""
    return tuple(round_up_pow2(int(d)) for d in dims)


def _key(kind: str, bucket: Sequence[int], backend: Optional[str]) -> str:
    b = backend if backend is not None else backend_name()
    return "|".join([b, kind, "x".join(str(int(d)) for d in bucket)])


# ----------------------------------------------------------------------- #
# The tuned table                                                          #
# ----------------------------------------------------------------------- #
# The in-process table dispatch reads (module docstring), filled by the
# sweeps below.
_TABLE: Dict[str, Any] = {}


def reset_table() -> None:
    """Empty the in-process table (tests isolate their entries with it)."""
    _TABLE.clear()


def put_entry(kind: str, bucket: Sequence[int], entry: Dict[str, Any],
              backend: Optional[str] = None) -> None:
    _TABLE[_key(kind, bucket, backend)] = entry


def get_entry(kind: str, bucket: Sequence[int],
              backend: Optional[str] = None) -> Optional[Dict[str, Any]]:
    return _TABLE.get(_key(kind, bucket, backend))


def tuned_use_ref(kind: str, bucket: Sequence[int],
                  backend: Optional[str] = None) -> Optional[bool]:
    """Tuned ref-vs-kernel routing; None on a table miss (callers fall back
    to the static heuristics in ops.py)."""
    e = get_entry(kind, bucket, backend)
    if e is None or "use_ref" not in e:
        return None
    return bool(e["use_ref"])


def tuned_blocks(kind: str, bucket: Sequence[int],
                 backend: Optional[str] = None) -> Dict[str, int]:
    """Tuned block sizes ({} on a miss; callers merge over defaults)."""
    e = get_entry(kind, bucket, backend)
    if e is None:
        return {}
    return {k: int(v) for k, v in e.get("blocks", {}).items()}


# ----------------------------------------------------------------------- #
# In-process block-size sweeps                                             #
# ----------------------------------------------------------------------- #
def _time_call(fn: Callable[[], Any], repeat: int = 3,
               warmup: int = 1, name: str = "autotune") -> float:
    """Median seconds per call, steady-state (results block_until_ready).
    ``name`` labels the timed region in profiler traces
    (``repro.<name>``, :func:`repro.obs.tracing.annotation`)."""
    import jax

    def run() -> None:
        with annotation(name):
            jax.block_until_ready(fn())

    for _ in range(warmup):
        run()
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        run()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def _sweep(candidates: Iterable[Tuple[str, Dict[str, int],
                                      Callable[[], Any]]],
           repeat: int, warmup: int) -> Dict[str, Any]:
    """Time every (name, blocks, thunk) candidate; return the winner entry
    (a failing candidate — e.g. a block shape the backend rejects — scores
    inf and loses)."""
    table: Dict[str, float] = {}
    best: Optional[Tuple[str, Dict[str, int]]] = None
    for name, blocks, thunk in candidates:
        try:
            t = _time_call(thunk, repeat=repeat, warmup=warmup,
                           name=f"autotune:{name}")
        except Exception:
            t = float("inf")
        table[name] = t
        if best is None or t < table[best[0]]:
            best = (name, blocks)
    assert best is not None, "no candidates"
    name, blocks = best
    return {
        "winner": name,
        "blocks": blocks,
        "use_ref": name == "ref",
        "us": table[name] * 1e6,
        "default_us": table.get("default", float("inf")) * 1e6,
        "table_us": {k: v * 1e6 for k, v in table.items()},
    }


def tune_ell_batched(weights, active, src, freq,
                     brs: Sequence[int] = BR_CANDIDATES,
                     wcs: Sequence[int] = WC_CANDIDATES,
                     repeat: int = 3, warmup: int = 1) -> Dict[str, Any]:
    """Sweep the scalar batched ELL kernel on a real plan; record the
    winner."""
    from . import ref
    from .propagate_batched import ell_propagate_batched_pallas

    n, rows, k = src.shape
    cands: list = [
        ("ref", {},
         lambda: ref.ell_propagate_batched_ref(weights, active, src, freq)),
        ("default", {"br": DEFAULT_BR, "wc": DEFAULT_WC},
         lambda: ell_propagate_batched_pallas(weights, active, src, freq)),
    ]
    for br in brs:
        for wc in wcs:
            if br == DEFAULT_BR and wc == DEFAULT_WC:
                continue
            cands.append((
                f"br{br}_wc{wc}", {"br": br, "wc": wc},
                lambda br=br, wc=wc: ell_propagate_batched_pallas(
                    weights, active, src, freq, br=br, wc=wc)))
    entry = _sweep(cands, repeat, warmup)
    put_entry("ell_batched", shape_bucket(n, rows, k), entry)
    return entry


def tune_ell_fused(weights0, in_deg, src, freq, max_rounds: int,
                   brs: Sequence[int] = BR_CANDIDATES,
                   repeat: int = 3, warmup: int = 1) -> Dict[str, Any]:
    """Sweep the fused multi-round traversal (ref fori form vs kernel)."""
    from . import ref
    from .propagate_fused import ell_frontier_fused_pallas

    n, rows, k = src.shape
    cands: list = [
        ("ref", {},
         lambda: ref.ell_frontier_fused_ref(weights0, in_deg, src, freq,
                                            max_rounds)),
        ("default", {"br": DEFAULT_BR},
         lambda: ell_frontier_fused_pallas(weights0, in_deg, src, freq,
                                           max_rounds)),
    ]
    for br in brs:
        if br == DEFAULT_BR:
            continue
        cands.append((
            f"br{br}", {"br": br},
            lambda br=br: ell_frontier_fused_pallas(
                weights0, in_deg, src, freq, max_rounds, br=br)))
    entry = _sweep(cands, repeat, warmup)
    put_entry("ell_fused", shape_bucket(n, rows, k, max_rounds), entry)
    return entry


def tune_ell_vector(W, active, src, freq,
                    brs: Sequence[int] = BRV_CANDIDATES,
                    fcs: Sequence[int] = FC_CANDIDATES,
                    repeat: int = 3, warmup: int = 1) -> Dict[str, Any]:
    """Sweep the vector-payload kernel's (row-block, F-block) shape."""
    from . import ref
    from .propagate_vector import (DEFAULT_BRV, DEFAULT_WCV,
                                   ell_propagate_vector_pallas)

    n, rows, k = src.shape
    F = W.shape[-1]
    cands: list = [
        ("ref", {},
         lambda: ref.ell_propagate_vector_ref(W, active, src, freq)),
        ("default", {"br": DEFAULT_BRV, "wc": DEFAULT_WCV, "fc": DEFAULT_FC},
         lambda: ell_propagate_vector_pallas(W, active, src, freq)),
    ]
    for br in brs:
        for fc in fcs:
            if br == DEFAULT_BRV and fc == DEFAULT_FC:
                continue
            cands.append((
                f"br{br}_fc{fc}", {"br": br, "wc": DEFAULT_WCV, "fc": fc},
                lambda br=br, fc=fc: ell_propagate_vector_pallas(
                    W, active, src, freq, br=br, fc=fc)))
    entry = _sweep(cands, repeat, warmup)
    put_entry("ell_vector", shape_bucket(n, rows, k, F), entry)
    return entry


# ----------------------------------------------------------------------- #
# XLA flag sweep (fresh subprocess per flag set — flags are process-global)#
# ----------------------------------------------------------------------- #
def _flags_to_env(flags: Dict[str, str]) -> str:
    return " ".join(f"--{k}={v}" for k, v in flags.items())


def _default_runner(workload: str, xla_flags: str) -> float:
    """Run ``workload`` (python source printing one float: seconds/call) in
    a fresh interpreter under XLA_FLAGS; inf on any failure.  The child
    takes the accelerator, so the caller must not hold it."""
    env = dict(os.environ)
    if xla_flags:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + xla_flags).strip()
    src_dir = os.path.join(os.path.dirname(__file__), "..", "..")
    env["PYTHONPATH"] = (os.path.abspath(src_dir) + os.pathsep
                         + env.get("PYTHONPATH", ""))
    try:
        out = subprocess.run([sys.executable, "-c", workload], env=env,
                             capture_output=True, text=True, timeout=600)
        if out.returncode != 0:
            return float("inf")
        return float(out.stdout.strip().splitlines()[-1])
    except (OSError, ValueError, IndexError, subprocess.TimeoutExpired):
        return float("inf")


def sweep_xla_flags(workload: str,
                    backend: Optional[str] = None,
                    flag_sets: Optional[Dict[str, Dict[str, str]]] = None,
                    runner: Optional[Callable[[str, str], float]] = None
                    ) -> Dict[str, Any]:
    """Time ``workload`` under every named flag set for ``backend``.

    ``runner(workload, xla_flags) -> seconds`` is injectable for tests; the
    default spawns a fresh interpreter per set (XLA flags are read once per
    process).  Those interpreters initialize JAX on the default backend, so
    the sweep needs the accelerator free: a chip belongs to one process,
    and a child started by a process that holds it fails or hangs (and
    scores ``inf`` here).  The winner is recorded under kind "xla_flags" keyed
    by a hash bucket of the workload source, and "default" is always a candidate so
    the tuned flags can never lose to shipping none.
    """
    b = backend or backend_name()
    sets = flag_sets if flag_sets is not None else XLA_FLAG_SETS.get(b, {})
    if "default" not in sets:
        sets = {"default": {}, **sets}
    run = runner or _default_runner
    table: Dict[str, float] = {}
    for name, flags in sets.items():
        table[name] = run(workload, _flags_to_env(flags))
    winner = min(table, key=lambda k: table[k])
    entry = {
        "winner": winner,
        "flags": sets[winner],
        "us": table[winner] * 1e6,
        "default_us": table.get("default", float("inf")) * 1e6,
        "table_us": {k: v * 1e6 for k, v in table.items()},
    }
    import zlib
    bucket = (zlib.crc32(workload.encode()) & 0xffff,)
    put_entry("xla_flags", bucket, entry, backend=b)
    return entry


# ----------------------------------------------------------------------- #
# HLO instrumentation (utils/hlo_analysis + launch/roofline revived)       #
# ----------------------------------------------------------------------- #
def hlo_profile(fn: Callable[..., Any], *args: Any,
                **static: Any) -> Dict[str, Any]:
    """Compile ``fn(*args)`` and report what the autotuner is moving.

    Returns the compiled op histogram (utils.hlo_analysis.op_histogram),
    collective traffic, XLA's own FLOP/byte cost analysis, and the
    roofline classification (compute- vs bandwidth-bound against the
    launch/roofline.py machine model) — the instrumentation behind the
    autotune BENCH rows.
    """
    import jax

    from repro.launch import roofline
    from repro.utils import hlo_analysis

    lowered = jax.jit(fn, static_argnames=tuple(static) or None).lower(
        *args, **static)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    out: Dict[str, Any] = {
        "ops": hlo_analysis.op_histogram(hlo),
        "collective_bytes": hlo_analysis.total_collective_bytes(hlo),
    }
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0))
        bytes_ = float(cost.get("bytes accessed", 0.0))
        out["flops"] = flops
        out["bytes"] = bytes_
        if bytes_ > 0:
            intensity = flops / bytes_
            ridge = roofline.PEAK_FLOPS / roofline.HBM_BW
            out["intensity"] = intensity
            out["bound"] = "compute" if intensity >= ridge else "bandwidth"
    except Exception:  # pragma: no cover - cost analysis is best-effort
        pass
    return out
