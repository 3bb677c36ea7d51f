"""Production training launcher.

On a real fleet this runs once per host::

    python -m repro.launch.train --arch qwen2-0.5b --corpus corpus.npz \
        --coordinator $COORD:1234 --num-hosts 64 --host-id $ID \
        --mesh 16x16 --steps 10000 --ckpt-dir gs://...

`jax.distributed.initialize` wires the hosts together; the mesh spans all
devices; every host feeds its own data-parallel shard from the same
deterministic compressed-corpus stream (restart- and topology-exact).  On
this container it degrades gracefully to the local device count — the same
code path the multi-device subprocess tests exercise.
"""

from __future__ import annotations

import argparse
import os

import jax
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU containers)")
    ap.add_argument("--corpus", default=None,
                    help=".npz compressed corpus (default: synthetic E)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--mesh", default=None,
                    help="DxM data x model (default: all devices x 1)")
    # multi-host wiring
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--num-hosts", type=int, default=1)
    ap.add_argument("--host-id", type=int, default=0)
    args = ap.parse_args()

    if args.coordinator:
        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_hosts, process_id=args.host_id)

    from repro.launch.mesh import auto_mesh
    from repro.configs import get_config
    from repro.data import BatchPipeline, CompressedCorpus, synthetic
    from repro.distributed import (batch_shardings, default_rules,
                                   param_shardings)
    from repro.models import init_lm, reduced, unbox
    from repro.training import AdamW, StragglerWatchdog, make_train_step, \
        train

    if args.corpus:
        cc = CompressedCorpus.load(args.corpus)
    else:
        spec = synthetic.TABLE2["E"]
        cc = CompressedCorpus.build(synthetic.make_table2_corpus("E"),
                                    vocab_size=spec.vocab)
    print(f"[train] corpus: {cc.stats()}")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg, vocab_size=max(cc.ga.vocab_size + 1, 257),
                      dtype="float32")

    # mesh + shardings
    n_dev = len(jax.devices())
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
    else:
        d, m = n_dev, 1
    mesh = auto_mesh((d, m), ("data", "model"))
    rules = default_rules(mesh)

    boxed = init_lm(jax.random.PRNGKey(0), cfg)
    params, axes = unbox(boxed)
    params = jax.tree.map(jax.device_put, params,
                          param_shardings(axes, params, mesh, rules))

    opt = AdamW(lr=args.lr, warmup_steps=min(100, args.steps // 10 + 1),
                schedule="cosine", total_steps=args.steps)
    step_fn = jax.jit(make_train_step(cfg, opt,
                                      microbatches=args.microbatches),
                      donate_argnums=(0, 1))

    shard_id = jax.process_index()
    pipeline = BatchPipeline(cc, global_batch=args.global_batch,
                             seq_len=args.seq_len, seed=0,
                             shard=shard_id,
                             num_shards=jax.process_count(), prefetch=2)
    wd = StragglerWatchdog(on_straggler=lambda s, dt, ema: print(
        f"[watchdog] host {shard_id}: step {s} {dt:.2f}s vs ema {ema:.2f}s"))
    with mesh:
        out = train(cfg, params, opt, pipeline, steps=args.steps,
                    ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                    train_step=step_fn, watchdog=wd)
    print(f"[train] done: loss {out['history'][0]:.3f} -> "
          f"{out['history'][-1]:.3f}, stragglers {out['straggler_events']}")
    pipeline.close()


if __name__ == "__main__":
    main()
