"""Pure-jnp oracles for the Pallas kernels (the allclose references)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def weighted_bincount_ref(ids: jnp.ndarray, vals: jnp.ndarray,
                          nbins: int) -> jnp.ndarray:
    """out[b] = sum(vals[ids == b]); ids outside [0, nbins) ignored."""
    ids = ids.astype(jnp.int32)
    valid = (ids >= 0) & (ids < nbins)
    safe = jnp.where(valid, ids, 0)
    v = jnp.where(valid, vals.astype(jnp.float32), 0.0)
    return jax.ops.segment_sum(v, safe, num_segments=nbins)


def ell_row_sums_ref(weights: jnp.ndarray, src: jnp.ndarray,
                     freq: jnp.ndarray) -> jnp.ndarray:
    """row_sums[r] = sum_k freq[r, k] * weights[src[r, k]]."""
    return (weights.astype(jnp.float32)[src] *
            freq.astype(jnp.float32)).sum(axis=1)


def ell_propagate_batched_ref(weights: jnp.ndarray, active: jnp.ndarray,
                              src: jnp.ndarray, freq: jnp.ndarray):
    """(delta, seen) of one fused round over the [N, R, K] edge plan.

    delta[n, r] = sum_k freq[n,r,k] * weights[n, src[n,r,k]]
                                    * active[n, src[n,r,k]]
    seen[n, r]  = sum_k [freq[n,r,k] > 0] * active[n, src[n,r,k]]

    This gather form doubles as the fast CPU production path: it touches
    each plan entry once with no scatter (the segment_sum path runs two
    scatters per round), which is also why the ELL plan wins on CPU.
    """
    n = src.shape[0]
    flat = src.reshape(n, -1).astype(jnp.int32)
    w = weights.astype(jnp.float32)
    a = active.astype(jnp.float32)
    f = freq.astype(jnp.float32)
    gw = jnp.take_along_axis(w, flat, axis=1).reshape(src.shape)
    ga = jnp.take_along_axis(a, flat, axis=1).reshape(src.shape)
    delta = (f * gw * ga).sum(axis=-1)
    seen = jnp.where(f > 0, ga, 0.0).sum(axis=-1)
    return delta, seen


def ell_propagate_vector_ref(W: jnp.ndarray, active: jnp.ndarray,
                             src: jnp.ndarray, freq: jnp.ndarray):
    """(delta, seen) of one vector-payload round over the [N, R, K] plan.

    delta[n, r, f] = sum_k freq[n,r,k] * W[n, src[n,r,k], f]
                                       * active[n, src[n,r,k]]
    seen[n, r]     = sum_k [freq[n,r,k] > 0] * active[n, src[n,r,k]]

    Gather form, the CPU production path for the per-file ELL traversals
    (propagate_vector.py is the TPU lowering of the same plan).  The
    gathered intermediate is [N, rows*K, F] — ops.py gates plan sizes so
    this stays within the dense-plan budget.
    """
    n, rows, k = src.shape
    flat = src.reshape(n, -1).astype(jnp.int32)
    w = W.astype(jnp.float32)
    a = active.astype(jnp.float32)
    f = freq.astype(jnp.float32)
    gw = jnp.take_along_axis(w, flat[:, :, None], axis=1)
    gw = gw.reshape(src.shape + (W.shape[-1],))            # [N, rows, K, F]
    ga = jnp.take_along_axis(a, flat, axis=1).reshape(src.shape)
    delta = ((f * ga)[..., None] * gw).sum(axis=2)         # [N, rows, F]
    seen = jnp.where(f > 0, ga, 0.0).sum(axis=-1)
    return delta, seen


def _plan_sums(v: jnp.ndarray, s: jnp.ndarray, src: jnp.ndarray,
               freq: jnp.ndarray):
    """``(sum_k freq * v[src], sum_k [freq > 0] * s[src])`` over an
    ``[N, rows, K]`` plan; ``v`` is ``[N, R]`` or ``[N, R, F]``."""
    n = src.shape[0]
    flat = src.reshape(n, -1).astype(jnp.int32)
    f = freq.astype(jnp.float32)
    gs = jnp.take_along_axis(s, flat, axis=1).reshape(src.shape)
    if v.ndim == 2:
        gv = jnp.take_along_axis(v, flat, axis=1).reshape(src.shape)
        delta = (f * gv).sum(axis=-1)
    else:
        gv = jnp.take_along_axis(v, flat[:, :, None], axis=1)
        gv = gv.reshape(src.shape + (v.shape[-1],))
        delta = (f[..., None] * gv).sum(axis=2)
    return delta, jnp.where(f > 0, gs, 0.0).sum(axis=-1)


def ell_propagate_split_ref(weights: jnp.ndarray, active: jnp.ndarray,
                            src: jnp.ndarray, freq: jnp.ndarray,
                            ovf_src: jnp.ndarray, ovf_freq: jnp.ndarray):
    """(delta, seen) of one round over the split plan (core/batch.py
    DESIGN note): the main plan ``[N, R, K0]`` and the overflow plan
    ``[N, O, K1]`` that holds the in-edges of hub rows in chunks.

    The overflow rows' partial sums come first, from ``weights * active``
    and ``active``.  The main rows then gather from ``[weights * active ;
    overflow delta]`` and ``[active ; overflow seen]``: a hub's pointer
    slot (src ``R + o``, freq 1) adds overflow row o's partial delta and
    its partial seen count.  Payloads are ``[N, R]`` or ``[N, R, F]``.
    With integer-valued float32 counts below 2**24 this equals the dense
    round bit for bit (any summation order is exact).
    """
    a = active.astype(jnp.float32)
    w = weights.astype(jnp.float32)
    wa = w * (a if w.ndim == 2 else a[..., None])
    odelta, oseen = _plan_sums(wa, a, ovf_src, ovf_freq)
    return _plan_sums(jnp.concatenate([wa, odelta], axis=1),
                      jnp.concatenate([a, oseen], axis=1), src, freq)


def ell_frontier_fused_ref(weights0: jnp.ndarray, in_deg: jnp.ndarray,
                           src: jnp.ndarray, freq: jnp.ndarray,
                           max_rounds: int, with_rounds: bool = False,
                           ovf: tuple = ()):
    """Whole frontier loop over the ELL plan as ONE jitted fori_loop.

    The jnp production form of propagate_fused.py: a static ``max_rounds``
    trip count (num_levels is exact — see the kernel docstring) with no
    per-round convergence test, so the per-round host round-trip AND the
    while_loop's cond evaluation both disappear.  Converged extra rounds
    are exact no-ops (delta == 0.0 and ``x + 0.0 == x`` on non-negative
    float32 counts).  Returns weights [N, R] — or ``(weights, rounds)``
    with the per-corpus non-converged round count when ``with_rounds``
    (rounds costs a per-round reduction, so production leaves it off).
    ``ovf`` — ``(ovf_src, ovf_freq)`` of a split plan — runs every round
    as :func:`ell_propagate_split_ref`; empty, the dense round.
    """
    return _ell_frontier_fused_ref_jit(weights0, in_deg, src, freq,
                                       int(max_rounds), bool(with_rounds),
                                       tuple(ovf))


@functools.partial(jax.jit, static_argnames=("max_rounds", "with_rounds"))
def _ell_frontier_fused_ref_jit(weights0, in_deg, src, freq,
                                max_rounds: int, with_rounds: bool,
                                ovf=()):
    n = src.shape[0]
    w0 = weights0.astype(jnp.float32)
    ind = in_deg.astype(jnp.int32)
    mask0 = (ind == 0).astype(jnp.float32)
    rounds0 = jnp.zeros(n, jnp.int32)

    def body(_, state):
        w, cur, mask, ever, rounds = state
        if with_rounds:
            rounds = rounds + jnp.any(mask > 0, axis=1).astype(jnp.int32)
        if ovf:
            delta, seen = ell_propagate_split_ref(w, mask, src, freq, *ovf)
        else:
            delta, seen = ell_propagate_batched_ref(w, mask, src, freq)
        w = w + delta
        cur = cur + seen.astype(jnp.int32)
        ready = ((cur == ind) & (ever == 0.0)).astype(jnp.float32)
        return w, cur, ready, ever + ready, rounds

    state = (w0, jnp.zeros_like(ind), mask0, mask0, rounds0)
    w, _, _, _, rounds = jax.lax.fori_loop(0, max(max_rounds, 1), body, state)
    return (w, rounds) if with_rounds else w
