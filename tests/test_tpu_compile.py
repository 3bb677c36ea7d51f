"""Compile the TPU path's kernels for a described v5e chip, with no chip.

The TPU compiler ships with the installed libtpu and compiles for a chip
that is described rather than attached, so a kernel the chip's compiler
refuses fails here instead of on the chip.  This is the only file that
describes the chip: the topology is built inside a module-scoped fixture,
never while a module is imported (only one process may load libtpu, and
the suite runs under several pytest-xdist workers), and every test that
needs it skips where it cannot be described.

What runs on the chip under auto routing (kernels/ops.py DESIGN note):

* the bincount kernel lowers through Mosaic — compiled here at the widths
  the batched word count hands it;
* the ELL kernels gather from VMEM by arbitrary index, which Mosaic lowers
  only within one source vreg — on TPU they take the jnp form of the same
  plan, whose XLA programs are compiled here at real widths, and the
  routing is asserted with the platform probe monkeypatched.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, ref
from repro.kernels._common import FORCE_INTERPRET_ENV
from repro.kernels.bincount import weighted_bincount_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described chip's compile is written to the persistent cache but
    cannot be read back without the chip; keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


@pytest.mark.parametrize("n_ids,nbins", [
    (16 * (1 << 14), 16 * 2048),      # batched word count: N=16 packs
    (1 << 20, 4096),                  # one large corpus
    (4 * (1 << 15), 4 * (1 << 20)),   # a 4-row chunk at a 2^20 vocabulary
])
def test_bincount_kernel_compiles_for_v5e(one_chip, no_persistent_cache,
                                          n_ids, nbins):
    compiled = _compile(
        lambda i, v: weighted_bincount_pallas(i, v, nbins, interpret=False),
        _spec(one_chip, (n_ids,), jnp.int32),
        _spec(one_chip, (n_ids,), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()    # the Mosaic kernel


def test_mosaic_refuses_multi_vreg_gather(one_chip, no_persistent_cache):
    """The platform fact behind routing the ELL kernels to jnp: a VMEM
    gather whose source spans more than one vreg (256 lanes here) does not
    lower.  If a later Mosaic accepts it, revisit ops.TPU_LOWERED_KERNELS."""
    def kernel(x_ref, i_ref, o_ref):
        o_ref[...] = jnp.take_along_axis(x_ref[...], i_ref[...], axis=1)

    def gather(x, i):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((8, 256), jnp.float32))(
                x, i)

    with pytest.raises(Exception, match="Multiple source vregs"):
        _compile(gather, _spec(one_chip, (8, 256), jnp.float32),
                 _spec(one_chip, (8, 256), jnp.int32))


@pytest.mark.parametrize("kind", ["ell_batched", "ell_fused", "ell_vector"])
def test_ell_jnp_forms_compile_for_v5e(one_chip, no_persistent_cache, kind):
    """The programs the TPU path runs for the ELL plans, at the widths of a
    served pack of deployment A (chip_smoke.py): 16 corpora of up to 2048
    rules, in-degree bucket 16, 128 files.  Each compiles in seconds at
    this width; XLA's compile time for these gathers grows steeply with
    width (~100 s at 2^16 rules on a CPU host)."""
    n, r, k, f = 16, 2048, 16, 128
    vec = _spec(one_chip, (n, r), jnp.float32)
    src = _spec(one_chip, (n, r, k), jnp.int32)
    frq = _spec(one_chip, (n, r, k), jnp.float32)
    if kind == "ell_batched":
        _compile(ref.ell_propagate_batched_ref, vec, vec, src, frq)
    elif kind == "ell_fused":
        _compile(lambda w, d, s, q: ref.ell_frontier_fused_ref(w, d, s, q,
                                                               32),
                 vec, vec, src, frq)
    else:
        _compile(ref.ell_propagate_vector_ref,
                 _spec(one_chip, (n, r, f), jnp.float32), vec, src, frq)


@pytest.mark.parametrize("kind", ["split", "split_fused", "split_vector"])
def test_split_jnp_forms_compile_for_v5e(one_chip, no_persistent_cache,
                                         kind):
    """The split ELL plan's programs at the widths of deployment B's pack
    (chipbench's tadoc-B-large-files): 8 corpora of up to 4096 rules, main
    plan K0 = 4, 256 overflow rows of K1 = 64, 4 files, 51 rounds."""
    n, r, k0, o, k1, f = 8, 4096, 4, 256, 64, 4
    vec = _spec(one_chip, (n, r), jnp.float32)
    plan = (_spec(one_chip, (n, r, k0), jnp.int32),
            _spec(one_chip, (n, r, k0), jnp.float32),
            _spec(one_chip, (n, o, k1), jnp.int32),
            _spec(one_chip, (n, o, k1), jnp.float32))
    if kind == "split":
        _compile(ref.ell_propagate_split_ref, vec, vec, *plan)
    elif kind == "split_fused":
        _compile(lambda w, d, s, q, os_, oq: ref.ell_frontier_fused_ref(
            w, d, s, q, 51, ovf=(os_, oq)), vec, vec, *plan)
    else:
        _compile(ref.ell_propagate_split_ref,
                 _spec(one_chip, (n, r, f), jnp.float32), vec, *plan)


def test_tpu_routing_is_static(monkeypatch):
    """On TPU, auto routing compiles bincount and sends every gather kernel
    to its jnp form — decided from the platform, before any tracing."""
    monkeypatch.delenv(FORCE_INTERPRET_ENV, raising=False)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert ops.exec_path("bincount", None) == "pallas_compiled"
    for kernel in set(ops.KERNELS) - {"bincount"}:
        assert ops.exec_path(kernel, None) == "jnp_ref", kernel
    assert ops.TPU_LOWERED_KERNELS == frozenset({"bincount"})
