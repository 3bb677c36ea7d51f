"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.bincount import weighted_bincount_pallas
from repro.kernels.propagate import ell_row_sums_pallas


@pytest.mark.parametrize("n,v", [(64, 8), (513, 129), (1000, 777),
                                 (5000, 2000), (4096, 512), (100_000, 30_000)])
def test_bincount_shapes(n, v, rng):
    ids = jnp.asarray(rng.integers(0, v, n).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=n).astype(np.float32))
    got = ops.weighted_bincount(ids, vals, v)
    want = ref.weighted_bincount_ref(ids, vals, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("tn,bv", [(128, 128), (512, 512), (256, 1024)])
def test_bincount_block_shapes(tn, bv, rng):
    ids = jnp.asarray(rng.integers(0, 300, 1500).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=1500).astype(np.float32))
    got = weighted_bincount_pallas(ids, vals, 300, tn=tn, bv=bv)
    want = ref.weighted_bincount_ref(ids, vals, 300)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_bincount_negative_ids_ignored(rng):
    ids = jnp.asarray(np.array([-1, 0, 1, -1, 1] * 40, np.int32))
    vals = jnp.ones(200, jnp.float32)
    got = np.asarray(ops.weighted_bincount(ids, vals, 4))
    assert got[0] == 40 and got[1] == 80 and got[2] == 0


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_bincount_val_dtypes(dtype, rng):
    ids = jnp.asarray(rng.integers(0, 50, 600).astype(np.int32))
    vals = jnp.asarray(rng.integers(0, 5, 600).astype(dtype))
    got = ops.weighted_bincount(ids, vals, 50)
    want = ref.weighted_bincount_ref(ids, vals.astype(jnp.float32), 50)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("rows,w,R", [(64, 1, 10), (100, 4, 50),
                                      (1000, 16, 333), (5000, 8, 4000),
                                      (257, 3, 129)])
def test_ell_row_sums_shapes(rows, w, R, rng):
    src = jnp.asarray(rng.integers(0, R, (rows, w)).astype(np.int32))
    freq = jnp.asarray(rng.integers(0, 5, (rows, w)).astype(np.float32))
    wts = jnp.asarray(rng.normal(size=R).astype(np.float32))
    got = ops.ell_row_sums(wts, src, freq)
    want = ref.ell_row_sums_ref(wts, src, freq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("br", [8, 64, 256])
def test_ell_block_shapes(br, rng):
    src = jnp.asarray(rng.integers(0, 77, (300, 5)).astype(np.int32))
    freq = jnp.asarray(rng.integers(0, 3, (300, 5)).astype(np.float32))
    wts = jnp.asarray(rng.normal(size=77).astype(np.float32))
    got = ell_row_sums_pallas(wts, src, freq, br=br)
    want = ref.ell_row_sums_ref(wts, src, freq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# ------------------------------------------------------ fallback branches --
def test_bincount_empty_input():
    got = ops.weighted_bincount(jnp.zeros(0, jnp.int32),
                                jnp.zeros(0, jnp.float32), 7)
    assert got.shape == (7,) and (np.asarray(got) == 0).all()


def test_ell_empty_input():
    got = ops.ell_row_sums(jnp.ones(5, jnp.float32),
                           jnp.zeros((0, 3), jnp.int32),
                           jnp.zeros((0, 3), jnp.float32))
    assert got.shape == (0,)


@pytest.mark.parametrize("n,v", [(1, 100), (63, 100), (200, 7), (5, 3)])
def test_bincount_small_shape_fallback(n, v, rng):
    """< 64 elements or < 8 bins must route to (and agree with) the ref."""
    assert ops.bincount_use_ref(n, v)
    ids = jnp.asarray(rng.integers(0, v, n).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=n).astype(np.float32))
    got = ops.weighted_bincount(ids, vals, v)
    want = ref.weighted_bincount_ref(ids, vals, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("rows", [1, 63])
def test_ell_small_shape_fallback(rows, rng):
    assert ops.ell_use_ref(50, rows)
    src = jnp.asarray(rng.integers(0, 50, (rows, 4)).astype(np.int32))
    freq = jnp.asarray(rng.integers(0, 3, (rows, 4)).astype(np.float32))
    wts = jnp.asarray(rng.normal(size=50).astype(np.float32))
    got = ops.ell_row_sums(wts, src, freq)
    want = ref.ell_row_sums_ref(wts, src, freq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_ell_no_vmem_cliff():
    """The old ELL_VMEM_WEIGHT_LIMIT hard fallback is gone: weight size no
    longer routes to the ref — the blocked kernel streams chunks."""
    assert not hasattr(ops, "ELL_VMEM_WEIGHT_LIMIT")
    assert not ops.ell_use_ref((3 << 20) + 1, 1000)
    assert not ops.ell_use_ref(100 * (3 << 20), 1 << 20)
    assert not ops.ell_use_ref(1000, 1000)
    assert ops.ell_use_ref(1000, ops.ELL_MIN_ROWS - 1)   # rows floor stays


@pytest.mark.parametrize("wc", [64, 128, 1024])
def test_ell_blocked_weight_chunks(wc, rng):
    """Multi-chunk weight streaming == single-chunk == jnp ref."""
    R = 1000
    src = jnp.asarray(rng.integers(0, R, (300, 5)).astype(np.int32))
    freq = jnp.asarray(rng.integers(0, 3, (300, 5)).astype(np.float32))
    wts = jnp.asarray(rng.normal(size=R).astype(np.float32))
    got = ell_row_sums_pallas(wts, src, freq, br=64, wc=wc)
    want = ref.ell_row_sums_ref(wts, src, freq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.slow
def test_ell_weights_straddle_old_vmem_limit(rng):
    """> 3.5M-rule weight vector through the ops wrapper in interpret mode:
    the blocked kernel must handle it (the fallback used to hide it)."""
    R = (3 << 20) + 4096
    wts = np.zeros(R, np.float32)
    hot = rng.integers(0, R, 512)
    wts[hot] = rng.normal(size=512).astype(np.float32)
    src = jnp.asarray(np.concatenate(
        [hot[:128], rng.integers(0, R, 128)]).reshape(128, 2).astype(np.int32))
    freq = jnp.asarray(rng.integers(1, 4, (128, 2)).astype(np.float32))
    wtsj = jnp.asarray(wts)
    got = ops.ell_row_sums(wtsj, src, freq, interpret=True)
    want = ref.ell_row_sums_ref(wtsj, src, freq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_bincount_batched_matches_per_row(rng):
    ids = rng.integers(0, 40, (5, 300)).astype(np.int32)
    ids[2, 10:20] = -1                            # padding entries ignored
    vals = rng.normal(size=(5, 300)).astype(np.float32)
    got = np.asarray(ops.weighted_bincount_batched(
        jnp.asarray(ids), jnp.asarray(vals), 40))
    assert got.shape == (5, 40)
    for i in range(5):
        want = np.asarray(ref.weighted_bincount_ref(
            jnp.asarray(ids[i]), jnp.asarray(vals[i]), 40))
        np.testing.assert_allclose(got[i], want, rtol=1e-4, atol=1e-4)


def test_bincount_batched_chunking_crossover(rng, monkeypatch):
    """Above the flat-bin limit the batch is chunked; results must match
    the unchunked path exactly, including the single-row degenerate."""
    ids = rng.integers(0, 40, (7, 300)).astype(np.int32)
    ids[3, 5:25] = -1
    vals = rng.normal(size=(7, 300)).astype(np.float32)
    want = np.asarray(ops.weighted_bincount_batched(
        jnp.asarray(ids), jnp.asarray(vals), 40))
    for limit, rows in ((120, 3), (40, 1), (80, 2)):
        monkeypatch.setattr(ops, "BINCOUNT_BATCH_FLAT_LIMIT", limit)
        assert ops.bincount_batch_rows(7, 40) == rows
        got = np.asarray(ops.weighted_bincount_batched(
            jnp.asarray(ids), jnp.asarray(vals), 40))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_bincount_batch_rows_predicate():
    limit = ops.BINCOUNT_BATCH_FLAT_LIMIT
    assert ops.bincount_batch_rows(16, limit // 16) == 16       # fits: whole
    assert ops.bincount_batch_rows(16, limit) == 1              # huge vocab
    assert ops.bincount_batch_rows(16, 10 * limit) == 1         # per-row
    assert ops.bincount_batch_rows(1000, limit // 100) == 100   # chunked


def test_on_tpu_cache_resettable(monkeypatch):
    """The backend probe must not leak across monkeypatched backends (the
    old functools.lru_cache did)."""

    class _Dev:
        platform = "tpu"

    assert ops._on_tpu() is False                 # CPU test environment
    try:
        monkeypatch.setattr(ops.jax, "devices", lambda: [_Dev()])
        assert ops._on_tpu() is False             # memo still holds
        ops.reset_backend_cache()
        assert ops._on_tpu() is True              # re-probed after reset
    finally:
        monkeypatch.undo()
        ops.reset_backend_cache()
        assert ops._on_tpu() is False


def test_on_tpu_propagates_backend_errors(monkeypatch):
    """A backend that fails to start must fail the caller, not answer
    "not a TPU" and send traffic to the CPU forms."""

    def broken():
        raise RuntimeError("TPU backend failed to initialize")

    try:
        monkeypatch.setattr(ops.jax, "devices", broken)
        ops.reset_backend_cache()
        with pytest.raises(RuntimeError, match="failed to initialize"):
            ops._on_tpu()
    finally:
        monkeypatch.undo()
        ops.reset_backend_cache()
        assert ops._on_tpu() is False


def test_force_interpret_raises_on_tpu(monkeypatch):
    """REPRO_FORCE_INTERPRET is a CPU lane: on a TPU it is an error, never
    a silent switch to kernel emulation."""
    from repro.kernels._common import (FORCE_INTERPRET_ENV, force_interpret,
                                       resolve_interpret)

    class _Dev:
        platform = "tpu"

    try:
        monkeypatch.setattr(ops.jax, "devices", lambda: [_Dev()])
        monkeypatch.setenv(FORCE_INTERPRET_ENV, "1")
        ops.reset_backend_cache()
        with pytest.raises(RuntimeError, match=FORCE_INTERPRET_ENV):
            force_interpret()
        with pytest.raises(RuntimeError, match=FORCE_INTERPRET_ENV):
            resolve_interpret(None)
        with pytest.raises(RuntimeError, match=FORCE_INTERPRET_ENV):
            ops.weighted_bincount(jnp.zeros(100, jnp.int32),
                                  jnp.ones(100, jnp.float32), 16)
        monkeypatch.setenv(FORCE_INTERPRET_ENV, "0")
        assert force_interpret() is False          # unset lane: no error
    finally:
        monkeypatch.undo()
        ops.reset_backend_cache()
        assert ops._on_tpu() is False


def test_bincount_batched_empty_and_bad_shapes():
    assert ops.weighted_bincount_batched(
        jnp.zeros((3, 0), jnp.int32), jnp.zeros((3, 0), jnp.float32),
        5).shape == (3, 5)
    with pytest.raises(ValueError):
        ops.weighted_bincount_batched(jnp.zeros((3, 4), jnp.int32),
                                      jnp.zeros((3, 5), jnp.float32), 5)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_property_bincount(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(64, 2000))
    v = int(rng.integers(8, 500))
    ids = jnp.asarray(rng.integers(0, v, n).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=n).astype(np.float32))
    got = np.asarray(ops.weighted_bincount(ids, vals, v))
    want = np.zeros(v, np.float32)
    np.add.at(want, np.asarray(ids), np.asarray(vals))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    # conservation: total mass preserved
    np.testing.assert_allclose(got.sum(), float(vals.sum()), rtol=1e-4,
                               atol=1e-3)
