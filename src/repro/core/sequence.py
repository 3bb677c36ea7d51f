"""Sequence support (paper §IV-D): head/tail buffers + cross-rule l-grams.

G-TADOC's insight: a word sequence (l-gram) either lies entirely inside one
rule's expansion — counted *once* by that rule and scaled by the rule's
occurrence weight — or it crosses a junction between adjacent symbols of
some rule's body, in which case the *parent* counts it by looking only at
the head/tail buffers of its children (no recursive descent).

Each rule r stores:
  head[r] = first  min(len(r), l-1) tokens of its expansion
  tail[r] = last   min(len(r), l-1) tokens of its expansion

Phase 1 (paper Fig. 7): fill head/tail with masked iterative rounds — a rule
resolves once the sub-rules in its body prefix/suffix have resolved.

Phase 2 (paper Fig. 8): per rule, scan the "junction stream" — the body with
each sub-rule occurrence replaced by ``head ++ GAP ++ tail`` (or its full
expansion when it is short enough to be covered by head+tail) — and count
every window of l tokens that (a) contains no GAP and no file splitter, and
(b) spans at least two body symbols (windows inside a single symbol are the
sub-rule's own business).  Window counts are scaled by the rule's top-down
weight.  The paper's lock+atomic hash-table merge becomes a sort+segment
reduction (DESIGN.md §2: no TPU atomics; deterministic by construction).

The *layout* of all gathers is static given the grammar (expansion lengths
are known host-side), so the device phases are pure dense gathers/reduces —
this is the TPU analogue of the paper's pre-planned memory pool.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.tracing import readback as _readback

from .grammar import GrammarArrays
from .traversal import top_down_weights

_GAP = -1
_BREAK = -2

_K_LIT, _K_HEAD, _K_TAIL, _K_GAP, _K_BREAK = 0, 1, 2, 3, 4


# ----------------------------------------------------------------------- #
# Host-side static planning                                                #
# ----------------------------------------------------------------------- #
@dataclass(frozen=True)
class HeadTailPlan:
    """Static gather plan for resolving head/tail buffers on device."""
    h: int
    # head gather: head[r, t] = lit[r,t] if is_lit else head_src's buffer
    head_is_lit: np.ndarray   # [R, h] bool
    head_lit: np.ndarray      # [R, h] int32 (token or -1 pad)
    head_src: np.ndarray      # [R, h] int32 source rule
    head_idx: np.ndarray      # [R, h] int32 index into source head buffer
    head_dep: np.ndarray      # [R, Kd] int32 rules that must resolve first (pad -1)
    tail_is_lit: np.ndarray
    tail_lit: np.ndarray
    tail_src: np.ndarray
    tail_idx: np.ndarray
    tail_dep: np.ndarray
    head_len: np.ndarray      # [R] int32 = min(len, h)
    tail_len: np.ndarray


def plan_head_tail(ga: GrammarArrays, l: int) -> HeadTailPlan:
    h = l - 1
    R = ga.num_rules
    nt = ga.num_terminals
    lens = ga.exp_len

    head_is_lit = np.zeros((R, h), bool)
    head_lit = np.full((R, h), -1, np.int32)
    head_src = np.zeros((R, h), np.int32)
    head_idx = np.zeros((R, h), np.int32)
    tail_is_lit = np.zeros((R, h), bool)
    tail_lit = np.full((R, h), -1, np.int32)
    tail_src = np.zeros((R, h), np.int32)
    tail_idx = np.zeros((R, h), np.int32)
    head_dep: List[List[int]] = [[] for _ in range(R)]
    tail_dep: List[List[int]] = [[] for _ in range(R)]

    for r in range(R):
        b = ga.rule_body(r)
        # ---- head: walk prefix until h tokens are covered
        off = 0
        for s in b:
            if off >= h:
                break
            s = int(s)
            if s < nt:
                head_is_lit[r, off] = True
                head_lit[r, off] = s
                off += 1
            else:
                sub = s - nt
                c = int(min(lens[sub], h - off))
                head_is_lit[r, off: off + c] = False
                head_src[r, off: off + c] = sub
                head_idx[r, off: off + c] = np.arange(c)
                head_dep[r].append(sub)
                off += c
        # ---- tail: walk suffix backwards
        off = 0  # tokens collected from the end
        for s in b[::-1]:
            if off >= h:
                break
            s = int(s)
            if s < nt:
                tail_is_lit[r, h - 1 - off] = True
                tail_lit[r, h - 1 - off] = s
                off += 1
            else:
                sub = s - nt
                tl = int(min(lens[sub], h))      # sub's tail buffer length
                c = int(min(lens[sub], h - off))
                # we need the last c tokens of sub == tail[sub][tl-c : tl]
                # (sub tail buffer is left-aligned with tl valid entries)
                dst = slice(h - off - c, h - off)
                tail_is_lit[r, dst] = False
                tail_src[r, dst] = sub
                tail_idx[r, dst] = np.arange(tl - c, tl)
                tail_dep[r].append(sub)
                off += c
        # tail stored left-aligned: shift so valid tokens occupy [0, tlen)
        tlen = int(min(lens[r], h))
        shift = h - off
        if shift > 0 and off > 0:
            tail_is_lit[r, :off] = tail_is_lit[r, shift: shift + off]
            tail_lit[r, :off] = tail_lit[r, shift: shift + off]
            tail_src[r, :off] = tail_src[r, shift: shift + off]
            tail_idx[r, :off] = tail_idx[r, shift: shift + off]
            tail_is_lit[r, off:] = False
            tail_lit[r, off:] = -1

    Kd = max(1, max((len(d) for d in head_dep + tail_dep), default=1))

    def _pad_dep(dep):
        out = np.full((R, Kd), -1, np.int32)
        for r, d in enumerate(dep):
            u = sorted(set(d))[:Kd]
            out[r, :len(u)] = u
        return out

    return HeadTailPlan(
        h=h,
        head_is_lit=head_is_lit, head_lit=head_lit,
        head_src=head_src, head_idx=head_idx, head_dep=_pad_dep(head_dep),
        tail_is_lit=tail_is_lit, tail_lit=tail_lit,
        tail_src=tail_src, tail_idx=tail_idx, tail_dep=_pad_dep(tail_dep),
        head_len=np.minimum(lens, h).astype(np.int32),
        tail_len=np.minimum(lens, h).astype(np.int32),
    )


@dataclass(frozen=True)
class StreamPlan:
    """Static junction-stream layout + window index for one grammar."""
    l: int
    st_kind: np.ndarray    # [S] int8
    st_lit: np.ndarray     # [S] int32
    st_src: np.ndarray     # [S] int32
    st_idx: np.ndarray     # [S] int32
    st_symj: np.ndarray    # [S] int32 body-symbol ordinal within owner rule
    win_start: np.ndarray  # [Nw] int32 stream positions where a window fits
    win_rule: np.ndarray   # [Nw] int32 owner rule of each window


def plan_stream(ga: GrammarArrays, l: int) -> StreamPlan:
    h = l - 1
    nt = ga.num_terminals
    V = ga.vocab_size
    lens = ga.exp_len
    kinds: List[int] = []
    lits: List[int] = []
    srcs: List[int] = []
    idxs: List[int] = []
    symjs: List[int] = []
    win_start: List[int] = []
    win_rule: List[int] = []

    for r in range(ga.num_rules):
        b = ga.rule_body(r)
        seg_start = len(kinds)
        for j, s in enumerate(b):
            s = int(s)
            if s < V:                                   # word literal
                kinds.append(_K_LIT); lits.append(s)
                srcs.append(0); idxs.append(0); symjs.append(j)
            elif s < nt:                                # file splitter
                kinds.append(_K_BREAK); lits.append(_BREAK)
                srcs.append(0); idxs.append(0); symjs.append(j)
            else:
                sub = s - nt
                L = int(lens[sub])
                if L <= 2 * h:
                    # full expansion reconstructible from head ++ tail tail-end
                    hl = int(min(L, h))
                    for t in range(hl):
                        kinds.append(_K_HEAD); lits.append(-1)
                        srcs.append(sub); idxs.append(t); symjs.append(j)
                    rem = L - hl
                    tl = int(min(L, h))
                    for t in range(tl - rem, tl):
                        kinds.append(_K_TAIL); lits.append(-1)
                        srcs.append(sub); idxs.append(t); symjs.append(j)
                else:
                    for t in range(h):
                        kinds.append(_K_HEAD); lits.append(-1)
                        srcs.append(sub); idxs.append(t); symjs.append(j)
                    kinds.append(_K_GAP); lits.append(_GAP)
                    srcs.append(0); idxs.append(0); symjs.append(j)
                    for t in range(h):
                        kinds.append(_K_TAIL); lits.append(-1)
                        srcs.append(sub); idxs.append(t); symjs.append(j)
        # windows inside this rule's stream segment
        seg_len = len(kinds) - seg_start
        for p in range(seg_len - l + 1):
            win_start.append(seg_start + p)
            win_rule.append(r)

    return StreamPlan(
        l=l,
        st_kind=np.array(kinds, np.int8), st_lit=np.array(lits, np.int32),
        st_src=np.array(srcs, np.int32), st_idx=np.array(idxs, np.int32),
        st_symj=np.array(symjs, np.int32),
        win_start=np.array(win_start, np.int32),
        win_rule=np.array(win_rule, np.int32),
    )


# ----------------------------------------------------------------------- #
# Device phase 1: resolve head/tail (paper Fig. 7, masked rounds)          #
# ----------------------------------------------------------------------- #
def resolve_head_tail(ga: GrammarArrays, plan: HeadTailPlan
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    R, h = ga.num_rules, plan.h

    def _resolve(is_lit, lit, src, idx, dep):
        is_lit = jnp.asarray(is_lit)
        lit = jnp.asarray(lit)
        src = jnp.asarray(src)
        idx = jnp.asarray(idx)
        dep = jnp.asarray(dep)          # [R, Kd], -1 pad
        leaf = (dep < 0).all(axis=1)

        @jax.jit
        def run():
            buf0 = jnp.where(is_lit, lit, -1)
            ready0 = leaf

            def cond(state):
                _, ready, prev = state
                return jnp.any(ready != prev)

            def body(state):
                buf, ready, _ = state
                dep_ok = jnp.where(dep < 0, True,
                                   ready[jnp.clip(dep, 0, R - 1)]).all(axis=1)
                newly = dep_ok & (~ready)
                gathered = jnp.where(is_lit, lit, buf[src, idx])
                buf = jnp.where(newly[:, None], gathered, buf)
                return buf, ready | newly, ready

            buf, ready, _ = jax.lax.while_loop(
                cond, body, (buf0, ready0, jnp.zeros(R, bool)))
            return buf

        return run()

    head = _resolve(plan.head_is_lit, plan.head_lit, plan.head_src,
                    plan.head_idx, plan.head_dep)
    tail = _resolve(plan.tail_is_lit, plan.tail_lit, plan.tail_src,
                    plan.tail_idx, plan.tail_dep)
    return head, tail


# ----------------------------------------------------------------------- #
# Device phase 2: gather streams, count windows (paper Fig. 8)             #
# ----------------------------------------------------------------------- #
def sequence_count(ga: GrammarArrays, l: int = 3, method: str = "frontier",
                   weights: jnp.ndarray | None = None
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Count all l-grams of the corpus directly on the grammar.

    Returns (grams [U, l], counts [U]) for the U distinct l-grams, sorted
    lexicographically.  File splitters break windows (sequences never span
    files), matching per-file direct counting.  ``weights`` lets callers
    reuse a memoized traversal (must equal ``top_down_weights(ga)``).
    """
    if l < 2:
        raise ValueError("sequence_count needs l >= 2")
    htp = plan_head_tail(ga, l)
    sp = plan_stream(ga, l)
    head, tail = resolve_head_tail(ga, htp)
    if weights is None:
        weights = top_down_weights(ga, method=method)

    if sp.win_start.shape[0] == 0:
        return np.zeros((0, l), np.int32), np.zeros((0,), np.float32)

    st_kind = jnp.asarray(sp.st_kind)
    st_lit = jnp.asarray(sp.st_lit)
    st_src = jnp.asarray(sp.st_src)
    st_idx = jnp.asarray(sp.st_idx)
    st_symj = jnp.asarray(sp.st_symj)
    win_start = jnp.asarray(sp.win_start)
    win_rule = jnp.asarray(sp.win_rule)

    @jax.jit
    def count(head, tail, weights):
        tok = jnp.where(st_kind == _K_LIT, st_lit,
                        jnp.where(st_kind == _K_HEAD, head[st_src, st_idx],
                                  jnp.where(st_kind == _K_TAIL,
                                            tail[st_src, st_idx], st_lit)))
        # windows: [Nw, l] gather
        pos = win_start[:, None] + jnp.arange(l)[None, :]
        wtok = tok[pos]                                   # [Nw, l]
        wsym = st_symj[pos]
        valid = (wtok >= 0).all(axis=1) & (wsym[:, 0] != wsym[:, -1])
        wweight = jnp.where(valid, weights[win_rule], 0.0)

        # sort windows lexicographically by token tuple (primary = col 0)
        order = jnp.lexsort(tuple(wtok[:, c] for c in range(l - 1, -1, -1)))
        stok = wtok[order]
        sw = wweight[order]
        newseg = jnp.concatenate([
            jnp.array([True]),
            (stok[1:] != stok[:-1]).any(axis=1)])
        seg = jnp.cumsum(newseg) - 1
        counts = jax.ops.segment_sum(sw, seg, num_segments=stok.shape[0])
        return stok, seg, counts

    stok, seg, counts = count(head, tail, weights)
    with _readback():
        stok = np.asarray(stok)
        counts = np.asarray(counts)
        n_seg = int(np.asarray(seg)[-1]) + 1
        seg = np.asarray(seg)
    # representative token tuple of each segment = first row of the segment
    first_idx = np.searchsorted(seg, np.arange(n_seg), "left")
    grams = stok[first_idx]
    cnts = counts[:n_seg]
    keep = cnts > 0
    return grams[keep].astype(np.int32), cnts[keep]
