"""Worker run in a subprocess with 8 fake host devices.

Asserts (exit code is the test result):
  1. sharded (2x4 mesh) pjit train step == single-device train step;
  2. gpipe forward == sequential stage composition;
  3. elastic restart: checkpoint from dp=4 resumes on dp=2 with identical
     loss trajectory (same global batch, re-partitioned).
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.data import BatchPipeline, CompressedCorpus, synthetic
from repro.distributed import (batch_shardings, default_rules,
                               param_shardings, reshard_tree)
from repro.launch.mesh import auto_mesh
from repro.models import init_lm, reduced, unbox
from repro.training import AdamW, make_train_step


def tiny():
    cfg = reduced(get_config("yi_9b"), dtype="float32", num_layers=2,
                  d_model=32, num_heads=4, num_kv_heads=2, head_dim=8,
                  d_ff=64, vocab_size=400)
    boxed = init_lm(jax.random.PRNGKey(0), cfg)
    params, axes = unbox(boxed)
    files = synthetic.make_table2_corpus("D")
    cc = CompressedCorpus.build(files, vocab_size=400)
    return cfg, params, axes, cc


def test_sharded_equals_single():
    cfg, params, axes, cc = tiny()
    pl = BatchPipeline(cc, global_batch=8, seq_len=16, seed=0, prefetch=0)
    x, y = pl.batch_at(0)
    batch = {"tokens": jnp.asarray(x), "labels": jnp.asarray(y)}
    opt = AdamW(lr=1e-2)
    step = make_train_step(cfg, opt)

    # single device
    p1, _, m1 = jax.jit(step)(params, opt.init(params), batch)

    # 2x4 mesh
    mesh = auto_mesh((2, 4), ("data", "model"))
    rules = default_rules(mesh)
    psh = param_shardings(axes, params, mesh, rules)
    params_s = jax.tree.map(jax.device_put, params, psh)
    batch_s = jax.tree.map(jax.device_put, batch,
                           batch_shardings(batch, mesh, rules))
    with mesh:
        p2, _, m2 = jax.jit(step)(params_s, opt.init(params_s), batch_s)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4, \
        (float(m1["loss"]), float(m2["loss"]))
    d = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), p1, p2)))
    assert d < 5e-3, d
    print("sharded==single OK", float(m1["loss"]))


def test_gpipe():
    from repro.distributed.pipeline import gpipe, make_pp_mesh
    mesh = make_pp_mesh(4)
    rng = np.random.default_rng(0)
    ws = jnp.asarray(rng.normal(size=(4, 16, 16)).astype(np.float32)) * 0.5
    mb = jnp.asarray(rng.normal(size=(6, 3, 16)).astype(np.float32))

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    out = gpipe(stage_fn, mesh, 4)(ws, mb)
    ref = mb
    for i in range(4):
        ref = jnp.tanh(ref @ ws[i])
    assert float(jnp.abs(out - ref).max()) < 1e-5
    print("gpipe OK")


def test_elastic():
    import tempfile
    from repro.checkpoint import save_checkpoint, restore_checkpoint
    from repro.distributed.elastic import elastic_pipeline
    cfg, params, axes, cc = tiny()
    opt = AdamW(lr=1e-2)
    step = jax.jit(make_train_step(cfg, opt))

    def run(mesh_shape, start, stop, params, opt_state, losses):
        mesh = auto_mesh(mesh_shape, ("data", "model"))
        rules = default_rules(mesh)
        params = reshard_tree(params, axes, mesh, rules)
        opt_state = type(opt_state)(
            count=opt_state.count,
            mu=reshard_tree(opt_state.mu, axes, mesh, rules),
            nu=reshard_tree(opt_state.nu, axes, mesh, rules))
        with mesh:
            for s in range(start, stop):
                pl = elastic_pipeline(cc, global_batch=8, seq_len=16, seed=0,
                                      resume_step=s, shard=0, num_shards=1)
                x, y = pl.batch_at(s)
                batch = {"tokens": jnp.asarray(x), "labels": jnp.asarray(y)}
                params, opt_state, m = step(params, opt_state, batch)
                losses.append(float(m["loss"]))
        return params, opt_state

    # continuous run on 4x2
    l_ref = []
    p, o = run((4, 2), 0, 6, params, opt.init(params), l_ref)

    # run 0-3 on 4x2, checkpoint, resume 3-6 on 2x4 (elastic shrink of dp)
    l_el = []
    p1, o1 = run((4, 2), 0, 3, params, opt.init(params), l_el)
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 3, {"p": p1, "o": o1})
        tree, st, _ = restore_checkpoint(d, {"p": p1, "o": o1})
    p2, o2 = run((2, 4), 3, 6, tree["p"], tree["o"], l_el)
    np.testing.assert_allclose(l_ref, l_el, rtol=1e-4)
    print("elastic OK", l_ref)


if __name__ == "__main__":
    test_sharded_equals_single()
    test_gpipe()
    test_elastic()
    print("MULTIDEVICE ALL OK")
