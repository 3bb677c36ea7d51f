"""GPipe pipeline parallelism over a "stage" mesh axis.

shard_map + lax.ppermute implementation: each device along the stage axis
holds one stage's parameters; microbatches stream through with the classic
(M + S - 1)-tick schedule; activations hop stages via collective_permute
(point-to-point on the ICI torus, overlappable with compute by XLA's async
collective pass).

This is the optional PP mode of DESIGN.md §4: the assigned models fit on
the 256-chip pod with DP x TP x FSDP, so the 40-cell dry-run does not use
PP; the module exists for deeper-than-memory models and is exercised by a
multi-device subprocess test (tests/test_distributed.py) on 8 host devices.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def gpipe(stage_fn: Callable, mesh: Mesh, n_stages: int,
          stage_axis: str = "stage") -> Callable:
    """Build a pipelined forward.

    ``stage_fn(params_slice, x) -> y`` is one stage's compute; all stages
    must share input/output activation shape (classic GPipe).

    Returns ``run(stacked_params, microbatches)`` where ``stacked_params``
    leaves have leading dim ``n_stages`` and ``microbatches`` is
    [M, mb, ...]; output is [M, mb, ...] after the last stage.
    """

    def run(stacked_params, microbatches):
        M = microbatches.shape[0]

        def per_device(params, mb):
            # params: [1, ...] my stage's slice; mb: [M, ...] (replicated in)
            params = jax.tree.map(lambda x: x[0], params)
            idx = jax.lax.axis_index(stage_axis)
            S = jax.lax.axis_size(stage_axis)
            ticks = M + S - 1

            def tick(carry, t):
                buf, outs = carry
                # stage 0 injects microbatch t (if in range); others use buf
                inject = jnp.where(t < M, t, M - 1)
                x0 = mb[inject]
                x = jnp.where(idx == 0, x0, buf)
                y = stage_fn(params, x)
                # shift y to the next stage; last stage's y is the output
                nxt = jax.lax.ppermute(
                    y, stage_axis,
                    perm=[(i, i + 1) for i in range(S - 1)])
                out_t = t - (S - 1)
                take = (idx == S - 1) & (out_t >= 0) & (out_t < M)
                outs = jnp.where(
                    take,
                    jax.lax.dynamic_update_index_in_dim(
                        outs, y, jnp.clip(out_t, 0, M - 1), 0),
                    outs)
                return (nxt, outs), None

            # carries are stage-varying; the initial values come from the
            # replicated microbatch buffer -> promote explicitly
            # (varying-manual-axes typing)
            buf0 = jax.lax.pcast(jnp.zeros_like(mb[0]), stage_axis,
                                 to="varying")
            outs0 = jax.lax.pcast(jnp.zeros_like(mb), stage_axis,
                                  to="varying")
            (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                        jnp.arange(ticks))
            return outs[None]      # re-add the stage dim for the out spec

        fn = shard_map(
            per_device, mesh=mesh,
            in_specs=(P(stage_axis), P()),
            out_specs=P(stage_axis))
        outs = fn(stacked_params, microbatches)
        # every stage produced an [M,...] buffer; only the last is real
        return outs[-1]

    return run


def make_pp_mesh(n_stages: int):
    devs = jax.devices()[:n_stages]
    import numpy as np
    return Mesh(np.array(devs), ("stage",))
