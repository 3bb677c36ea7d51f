"""Fused multi-round + vector-payload ELL kernels and the autotune table.

Covers the ISSUE-7 kernel surface directly against independent numpy
oracles (topologically-ordered DAG replay), the interpret-mode Pallas
lanes, the dispatch discipline (CPU production -> jnp reference, forced
interpret -> kernels, TPU -> real lowering WITHOUT interpret emulation),
and the autotune table's round-trip / override semantics.  The engine- and
analytics-level equivalence of the same paths lives in test_ell_batched.py
and test_differential.py.
"""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import autotune, ops, ref
from repro.kernels._common import (FORCE_INTERPRET_ENV, force_interpret,
                                   resolve_interpret)
from repro.kernels.propagate_fused import ell_frontier_fused_pallas
from repro.kernels.propagate_vector import ell_propagate_vector_pallas


# --------------------------------------------------------------- helpers --
def _random_dag(rng, R, max_deg):
    """A random rule DAG in ELL form: rule indices are a topological order
    (parents of r come from [0, r)), so a direct numpy replay in index
    order is an exact oracle for the frontier fixpoint."""
    src = np.zeros((R, max_deg), np.int32)
    freq = np.zeros((R, max_deg), np.float32)
    in_deg = np.zeros(R, np.int32)
    w = np.zeros(R, np.float64)
    lvl = np.zeros(R, np.int64)
    w[0] = 1.0
    for r in range(1, R):
        d = int(rng.integers(1, min(max_deg, r) + 1))
        ps = rng.choice(r, size=d, replace=False)
        fs = rng.integers(1, 4, size=d)
        if float((fs * w[ps]).sum()) > (1 << 22):
            # keep every weight an integer < 2^23: exact in float32 under
            # ANY summation order, so the oracle compare stays bit-level
            # (mirrors the production invariant — counts < 2^24)
            ps, fs, d = np.array([0]), np.array([1]), 1
        src[r, :d] = ps
        freq[r, :d] = fs
        in_deg[r] = d
        w[r] = float((fs * w[ps]).sum())
        lvl[r] = 1 + int(lvl[ps].max())
    depth = int(lvl.max())
    return src, freq, in_deg, w.astype(np.float32), depth


def _batch_dags(rng, R, max_deg, n):
    """n independent DAGs padded onto one [n, R, K] plan."""
    parts = [_random_dag(rng, R, max_deg) for _ in range(n)]
    src = np.stack([p[0] for p in parts])
    freq = np.stack([p[1] for p in parts])
    ind = np.stack([p[2] for p in parts])
    want = np.stack([p[3] for p in parts])
    depths = np.array([p[4] for p in parts])
    w0 = np.zeros((n, R), np.float32)
    w0[:, 0] = 1.0
    return (jnp.asarray(src), jnp.asarray(freq),
            jnp.asarray(ind.astype(np.float32)), jnp.asarray(w0),
            want, depths)


# ------------------------------------------------------ fused multi-round --
@pytest.mark.parametrize("R,max_deg,n", [(40, 3, 1), (130, 5, 3),
                                         (500, 4, 2), (257, 2, 4)])
def test_fused_matches_dag_oracle(R, max_deg, n, rng):
    src, freq, ind, w0, want, depths = _batch_dags(rng, R, max_deg, n)
    max_rounds = int(depths.max()) + 1          # == num_levels
    got_ref = ref.ell_frontier_fused_ref(w0, ind, src, freq, max_rounds)
    np.testing.assert_array_equal(np.asarray(got_ref), want)
    got_k, rounds = ell_frontier_fused_pallas(w0, ind, src, freq,
                                              max_rounds, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_k), want)
    # each corpus converges after exactly depth+1 frontier rounds
    np.testing.assert_array_equal(np.asarray(rounds), depths + 1)


def test_fused_rounds_match_ref_counter(rng):
    src, freq, ind, w0, _, depths = _batch_dags(rng, 120, 4, 3)
    max_rounds = int(depths.max()) + 1
    _, r_ref = ref.ell_frontier_fused_ref(w0, ind, src, freq, max_rounds,
                                          with_rounds=True)
    _, r_k = ell_frontier_fused_pallas(w0, ind, src, freq, max_rounds,
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_k))


def test_fused_extra_rounds_are_exact_noops(rng):
    """Rounds past convergence must be bit-exact no-ops (the SMEM done
    flag skips them in the kernel; the ref adds literal 0.0)."""
    src, freq, ind, w0, want, depths = _batch_dags(rng, 90, 3, 2)
    exact = int(depths.max()) + 1
    for extra in (0, 3, 10):
        got = np.asarray(ref.ell_frontier_fused_ref(
            w0, ind, src, freq, exact + extra))
        np.testing.assert_array_equal(got, want)
        got_k, rounds = ell_frontier_fused_pallas(
            w0, ind, src, freq, exact + extra, interpret=True)
        np.testing.assert_array_equal(np.asarray(got_k), want)
        # converged corpora never bump the round counter
        np.testing.assert_array_equal(np.asarray(rounds), depths + 1)


@pytest.mark.parametrize("br", [8, 32, 256])
def test_fused_row_block_alignment(br, rng):
    """R not a multiple of br: alignment-padded rows get in_deg = -1 and
    must stay off every frontier (in_deg == 0 would seed them)."""
    src, freq, ind, w0, want, depths = _batch_dags(rng, 101, 3, 2)
    got, _ = ell_frontier_fused_pallas(w0, ind, src, freq,
                                       int(depths.max()) + 1, br=br,
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_fused_ops_dispatch_and_empty():
    w0 = jnp.zeros((0, 5), jnp.float32)
    ind = jnp.zeros((0, 5), jnp.float32)
    plan = jnp.zeros((0, 5, 2), jnp.float32)
    out = ops.ell_frontier_fused(w0, ind, plan.astype(jnp.int32), plan, 3)
    assert out.shape == (0, 5)
    out, rounds = ops.ell_frontier_fused(w0, ind, plan.astype(jnp.int32),
                                         plan, 3, with_rounds=True)
    assert rounds.shape == (0,)
    assert ops.ell_fused_use_kernel(ops.ELL_FUSED_MAX_RULES)
    assert not ops.ell_fused_use_kernel(ops.ELL_FUSED_MAX_RULES + 1)


def test_fused_ops_ref_and_kernel_agree(rng):
    """ops-level: the CPU production (jnp fori) path and the interpret
    kernel path return identical weights."""
    src, freq, ind, w0, want, depths = _batch_dags(rng, 150, 4, 2)
    mr = int(depths.max()) + 1
    got_prod = ops.ell_frontier_fused(w0, ind, src, freq, mr)
    got_kern = ops.ell_frontier_fused(w0, ind, src, freq, mr,
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(got_prod), want)
    np.testing.assert_array_equal(np.asarray(got_kern), want)


# ----------------------------------------------------- vector payload ELL --
def _vector_oracle(W, active, src, freq):
    n, rows, k = src.shape
    F = W.shape[-1]
    delta = np.zeros((n, rows, F), np.float32)
    seen = np.zeros((n, rows), np.float32)
    for c in range(n):
        for r in range(rows):
            for j in range(k):
                s = src[c, r, j]
                if freq[c, r, j] > 0:
                    seen[c, r] += active[c, s]
                delta[c, r] += freq[c, r, j] * active[c, s] * W[c, s]
    return delta, seen


@pytest.mark.parametrize("R,K,F,n", [(64, 3, 4, 1), (130, 5, 17, 2),
                                     (300, 2, 129, 1)])
def test_vector_matches_oracle(R, K, F, n, rng):
    W = rng.integers(0, 4, (n, R, F)).astype(np.float32)
    active = (rng.random((n, R)) < 0.4).astype(np.float32)
    src = rng.integers(0, R, (n, R, K)).astype(np.int32)
    freq = rng.integers(0, 3, (n, R, K)).astype(np.float32)
    want_d, want_s = _vector_oracle(W, active, src, freq)
    for got_d, got_s in (
            ref.ell_propagate_vector_ref(jnp.asarray(W), jnp.asarray(active),
                                         jnp.asarray(src), jnp.asarray(freq)),
            ell_propagate_vector_pallas(jnp.asarray(W), jnp.asarray(active),
                                        jnp.asarray(src), jnp.asarray(freq),
                                        interpret=True)):
        np.testing.assert_array_equal(np.asarray(got_d), want_d)
        np.testing.assert_array_equal(np.asarray(got_s), want_s)


@pytest.mark.parametrize("br,wc,fc", [(8, 32, 4), (16, 64, 8), (64, 128, 64)])
def test_vector_block_streaming(br, wc, fc, rng):
    """Multi-chunk streaming on every axis (rule chunks, F-blocks, row
    blocks with ragged sizes) == jnp reference, bit-exact."""
    n, R, K, F = 2, 100, 4, 19
    W = jnp.asarray(rng.integers(0, 4, (n, R, F)).astype(np.float32))
    active = jnp.asarray((rng.random((n, R)) < 0.5).astype(np.float32))
    src = jnp.asarray(rng.integers(0, R, (n, R, K)).astype(np.int32))
    freq = jnp.asarray(rng.integers(0, 3, (n, R, K)).astype(np.float32))
    want_d, want_s = ref.ell_propagate_vector_ref(W, active, src, freq)
    got_d, got_s = ell_propagate_vector_pallas(W, active, src, freq,
                                               br=br, wc=wc, fc=fc,
                                               interpret=True)
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


def test_vector_ops_validation_and_empty():
    with pytest.raises(ValueError):
        ops.ell_propagate_vector(jnp.zeros((2, 3), jnp.float32),
                                 jnp.zeros((2, 3), jnp.float32),
                                 jnp.zeros((2, 3, 1), jnp.int32),
                                 jnp.zeros((2, 3, 1), jnp.float32))
    d, s = ops.ell_propagate_vector(jnp.zeros((2, 3, 4), jnp.float32),
                                    jnp.zeros((2, 3), jnp.float32),
                                    jnp.zeros((2, 0, 1), jnp.int32),
                                    jnp.zeros((2, 0, 1), jnp.float32))
    assert d.shape == (2, 0, 4) and s.shape == (2, 0)
    assert ops.ell_vector_plan_ok(1, 1024, 8, 16)
    assert not ops.ell_vector_plan_ok(64, 1 << 18, 64, 1024)


# ----------------------------------------------- dispatch discipline (S1) --
def test_forced_interpret_lane_routes_to_kernels(rng, monkeypatch):
    """REPRO_FORCE_INTERPRET=1 must push production-shaped calls through
    the interpret-mode Pallas kernels instead of the jnp reference."""
    monkeypatch.delenv(FORCE_INTERPRET_ENV, raising=False)
    assert not force_interpret()
    assert resolve_interpret(None) is True        # CPU auto => interpret
    assert ops.exec_path("ell_batched", None) == "jnp_ref"  # ...prod: jnp

    monkeypatch.setenv(FORCE_INTERPRET_ENV, "1")
    assert force_interpret()
    assert ops.exec_path("ell_batched", None) == "pallas_interpret"
    calls = []
    real = ops.ell_propagate_batched_pallas

    def spy(*a, **kw):
        calls.append(kw.get("interpret"))
        return real(*a, **kw)

    monkeypatch.setattr(ops, "ell_propagate_batched_pallas", spy)
    w = jnp.asarray(rng.normal(size=(1, 70)).astype(np.float32))
    act = jnp.ones((1, 70), jnp.float32)
    src = jnp.asarray(rng.integers(0, 70, (1, 70, 2)).astype(np.int32))
    frq = jnp.asarray(rng.integers(0, 3, (1, 70, 2)).astype(np.float32))
    got = ops.ell_propagate_batched(w, act, src, frq)
    assert calls == [True]                        # interpret-mode kernel
    want = ref.ell_propagate_batched_ref(w, act, src, frq)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))


def test_tpu_production_never_runs_interpret(rng, monkeypatch):
    """Satellite regression: on TPU, production traffic (interpret=None)
    never emulates a kernel — the old ``interpret: bool = True`` jit
    default silently did.  The bincount kernel reaches its Pallas entry
    with interpret=False; the ELL kernels, which Mosaic cannot lower, take
    the jnp form of the same plan and never reach the Pallas entry.
    Backend is faked via the revocable probe (reset_backend_cache)."""

    class _Dev:
        platform = "tpu"

    captured = {}

    def fake_pallas(w, a, s, f, br=0, wc=0, interpret=None):
        captured["ell"] = interpret
        return ref.ell_propagate_batched_ref(w, a, s, f)

    def fake_bincount(ids, vals, nbins, interpret=None):
        captured["bincount"] = interpret
        return ref.weighted_bincount_ref(ids, vals, nbins)

    monkeypatch.delenv(FORCE_INTERPRET_ENV, raising=False)
    monkeypatch.setattr(ops, "ell_propagate_batched_pallas", fake_pallas)
    monkeypatch.setattr(ops, "weighted_bincount_pallas", fake_bincount)
    monkeypatch.setattr(ops.jax, "devices", lambda: [_Dev()])
    ops.reset_backend_cache()
    try:
        assert ops._on_tpu() is True
        assert resolve_interpret(None) is False   # real lowering
        w = jnp.ones((1, 70), jnp.float32)
        src = jnp.zeros((1, 70, 2), jnp.int32)
        frq = jnp.ones((1, 70, 2), jnp.float32)
        got = ops.ell_propagate_batched(w, w, src, frq)
        want = ref.ell_propagate_batched_ref(w, w, src, frq)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))
        ops.weighted_bincount(jnp.zeros(100, jnp.int32),
                              jnp.ones(100, jnp.float32), 16)
        assert captured == {"bincount": False}
    finally:
        monkeypatch.undo()
        ops.reset_backend_cache()
        assert ops._on_tpu() is False


# ------------------------------------------------------------ autotune --
@pytest.fixture
def tuned_table():
    """Isolated autotune table: empty on both entry and exit."""
    autotune.reset_table()
    yield
    autotune.reset_table()


def test_autotune_table_roundtrip(tuned_table):
    bucket = autotune.shape_bucket(3, 100, 5)
    assert bucket == (4, 128, 8)                 # pow2 rounding
    assert autotune.get_entry("ell_batched", bucket) is None
    assert autotune.tuned_use_ref("ell_batched", bucket) is None
    assert autotune.tuned_blocks("ell_batched", bucket) == {}
    entry = {"winner": "br128_wc65536", "use_ref": False,
             "blocks": {"br": 128, "wc": 1 << 16}, "us": 10.0}
    autotune.put_entry("ell_batched", bucket, entry)
    got = autotune.get_entry("ell_batched", bucket)
    assert got["winner"] == "br128_wc65536"
    assert autotune.tuned_use_ref("ell_batched", bucket) is False
    assert autotune.tuned_blocks("ell_batched", bucket) == \
        {"br": 128, "wc": 1 << 16}
    # entries are per backend: a table tuned elsewhere never applies here
    autotune.put_entry("ell_fused", bucket, entry, backend="other")
    assert autotune.get_entry("ell_fused", bucket) is None
    autotune.reset_table()
    assert autotune.get_entry("ell_batched", bucket) is None


def test_dispatch_ignores_files_in_the_checkout(tuned_table, tmp_path,
                                                monkeypatch):
    """Block sizes come from the shipped defaults and this process's own
    sweeps only: a tuned-table file lying in the working directory (as an
    older autotuner wrote one) never steers dispatch."""
    bucket = autotune.shape_bucket(2, 100, 3)
    key = f"{autotune.backend_name()}|ell_batched|2x128x4"
    (tmp_path / "AUTOTUNE_cache.json").write_text(json.dumps(
        {"version": 1, "entries": {key: {"blocks": {"br": 8}}}}))
    monkeypatch.chdir(tmp_path)
    assert autotune.tuned_blocks("ell_batched", bucket) == {}
    assert ops._blocks("ell_batched", bucket, {"br": 256}) == {"br": 256}


def test_tuned_use_ref_overrides_heuristics(tuned_table):
    """An ``ell_vs_seg`` entry must override the static occupancy gates in
    BOTH directions (the tuned table timed the real engines)."""
    # tiny batch: static heuristics say ref...
    assert ops.ell_batched_use_ref(10, 1, 8, 2)
    autotune.put_entry("ell_vs_seg", autotune.shape_bucket(1, 8, 2),
                       {"use_ref": False})
    assert not ops.ell_batched_use_ref(10, 1, 8, 2)
    # healthy shape: static heuristics say kernel...
    assert not ops.ell_batched_use_ref(4000, 4, 1000, 4)
    autotune.put_entry("ell_vs_seg", autotune.shape_bucket(4, 1000, 4),
                       {"use_ref": True})
    assert ops.ell_batched_use_ref(4000, 4, 1000, 4)
    # sharded gate evaluates per-device width under the same override
    autotune.put_entry("ell_vs_seg", autotune.shape_bucket(2, 1000, 4),
                       {"use_ref": False})
    assert not ops.ell_batched_use_ref(4000, 4, 1000, 4, shards=2)


def test_tuned_blocks_feed_kernel_dispatch(tuned_table, rng, monkeypatch):
    """ops.ell_propagate_batched must launch with the TUNED block shape
    for the pack's bucket, falling back to defaults elsewhere."""
    n, R, K = 2, 100, 3
    autotune.put_entry("ell_batched", autotune.shape_bucket(n, R, K),
                       {"blocks": {"br": 128, "wc": 1 << 16,
                                   "bogus": 7}})   # unknown keys dropped
    seen = {}

    def spy(w, a, s, f, br=None, wc=None, interpret=None):
        seen.update(br=br, wc=wc)
        return ref.ell_propagate_batched_ref(w, a, s, f)

    monkeypatch.setattr(ops, "ell_propagate_batched_pallas", spy)
    w = jnp.ones((n, R), jnp.float32)
    src = jnp.asarray(rng.integers(0, R, (n, R, K)).astype(np.int32))
    frq = jnp.ones((n, R, K), jnp.float32)
    ops.ell_propagate_batched(w, w, src, frq, interpret=True)
    assert seen == {"br": 128, "wc": 1 << 16}


def test_tune_sweeps_record_and_persist(tuned_table, rng):
    """The three kernel sweeps run real candidates (interpret mode on CPU)
    and record winner entries the dispatch layer reads back."""
    n, R, K, F = 1, 70, 2, 3
    src = jnp.asarray(rng.integers(0, R, (n, R, K)).astype(np.int32))
    frq = jnp.asarray(rng.integers(0, 3, (n, R, K)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(n, R)).astype(np.float32))
    act = jnp.ones((n, R), jnp.float32)
    e1 = autotune.tune_ell_batched(w, act, src, frq, brs=(8,), wcs=(64,),
                                   repeat=1, warmup=0)
    w0 = jnp.zeros((n, R), jnp.float32).at[:, 0].set(1.0)
    ind = jnp.asarray(
        (frq > 0).sum(axis=-1).astype(np.float32))   # consistent in-degrees
    e2 = autotune.tune_ell_fused(w0, ind, src, frq, 4, brs=(8,),
                                 repeat=1, warmup=0)
    W = jnp.asarray(rng.integers(0, 3, (n, R, F)).astype(np.float32))
    e3 = autotune.tune_ell_vector(W, act, src, frq, brs=(8,), fcs=(4,),
                                  repeat=1, warmup=0)
    for e in (e1, e2, e3):
        assert {"winner", "blocks", "use_ref", "us", "table_us"} <= set(e)
        assert e["us"] <= min(e["table_us"].values()) + 1e-9
    assert autotune.get_entry(
        "ell_batched", autotune.shape_bucket(n, R, K)) is not None
    assert autotune.get_entry(
        "ell_fused", autotune.shape_bucket(n, R, K, 4)) is not None
    assert autotune.get_entry(
        "ell_vector", autotune.shape_bucket(n, R, K, F)) is not None


def test_sweep_xla_flags_injected_runner(tuned_table):
    """Flag-set sweep with an injected runner: 'default' is always a
    candidate, failures score inf and lose, the winner persists."""
    times = {"default": 2.0, "fast": 1.0, "broken": float("inf")}

    def runner(workload, flags):
        if "broken" in flags:
            return float("inf")
        return 1.0 if "fast" in flags else 2.0

    entry = autotune.sweep_xla_flags(
        "print(0.001)", backend="cpu",
        flag_sets={"fast": {"xla_fast": "true"},
                   "broken": {"xla_broken": "true"}},
        runner=runner)
    assert entry["winner"] == "fast" and entry["flags"] == \
        {"xla_fast": "true"}
    assert entry["table_us"]["broken"] == float("inf")
    assert entry["default_us"] == pytest.approx(times["default"] * 1e6)


def test_sweep_xla_flags_subprocess_runner(tuned_table):
    """The real subprocess runner on a trivial workload (no jax import:
    keeps it fast) — and inf on a failing workload."""
    entry = autotune.sweep_xla_flags("print(0.000001)", backend="cpu",
                                     flag_sets={})
    assert entry["winner"] == "default"
    assert entry["us"] == pytest.approx(1.0)
    bad = autotune._default_runner("raise SystemExit(3)", "")
    assert bad == float("inf")


def test_hlo_profile_reports_roofline(rng):
    """hlo_profile revives the HLO histogram + roofline instrumentation
    for any jitted workload."""
    x = jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))
    out = autotune.hlo_profile(lambda a: a @ a, x)
    assert isinstance(out["ops"], dict) and out["ops"]
    assert out.get("collective_bytes", 0) == 0
    if "intensity" in out:
        assert out["bound"] in ("compute", "bandwidth")
