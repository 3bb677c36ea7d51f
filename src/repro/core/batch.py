"""Batched multi-corpus analytics: pack N grammars, traverse them together.

The single-corpus engine (traversal.py / analytics.py) runs one compressed
corpus per jitted call.  Under serving load ("heavy traffic" — many corpora,
many queries) that wastes the device: every corpus gets its own dispatch,
its own while_loop, its own compilation (shapes differ corpus to corpus).

This module is the TPU analogue of batching many compressed segments into
one GPU program: a :class:`GrammarBatch` packs N :class:`GrammarArrays`
into padded, bucketed ``[N, ...]`` device arrays (the pre-planned memory
pool of paper §IV-C, extended across corpora), and every analytic runs as
ONE jitted program over the whole batch:

* ``frontier`` traversal — vmap of the masked-rounds engine over the packed
  batch, sharing a single ``while_loop`` whose stop flag is ``mask.any()``
  across *all* corpora (finished corpora idle harmlessly: their masks are
  empty, so extra rounds are no-ops).
* ``leveled`` traversal — per-level edge segments are padded to a common
  width across corpora, so the level schedule is shared and each real edge
  is still touched exactly once.
* all six analytics (word count, sort, inverted index, term vector,
  sequence count, ranked inverted index) — bit-identical to running the
  single-corpus functions in a Python loop (tests/test_batch.py).

Padding convention: padded edges carry ``freq == 0`` and are additionally
masked by ``edge_valid``; padded rule slots have ``in_deg == out_deg == 0``
(they become "ready" in round 0 with weight 0 and never contribute).
Dimensions are bucketed (rounded up to powers of two) so batches of similar
size hit the same compiled program — the dispatch layer
(serving/analytics_server.py) groups queries by this signature.

DESIGN — the ELL edge plan (methods ``frontier_ell`` / ``leveled_ell`` /
``frontier_fused``): :meth:`GrammarBatch.ell_plan` converts each corpus's
COO in-edges to a dense ``src/freq [N, R_pad, K]`` layout (row r = rule
r's parents, K = max in-degree across the batch bucketed to a power of
two, padding src=0 / freq=0).  Because the row index IS the destination
rule, one propagation round needs no scatter: ``kernels.ops.
ell_propagate_batched`` fuses the gather, mask-gate, multiply and row-sum —
and emits the ``seen`` frontier bookkeeping — in a single launch (two
segment_sum scatters per round on the COO path).  Plans are built lazily
and memoized per batch; method ``auto`` asks ``kernels.ops.
ell_batched_use_ref`` (occupancy over edge count, the plan's slot count,
batch width N) whether the plan pays off.  The leveled variants replay the
same plan once per level with the mask ``level[parent] == lv`` — each real
edge still contributes exactly once, at its parent's level.  Per-file
traversals run the same plan with an ``[R, F]`` payload per rule.

DESIGN — hub rows are split (:meth:`GrammarBatch.ell_split_plan`, what the
ELL engines run).  In-degrees are skewed: a few hub rules are used by
hundreds of rules, most by two or three, and a dense plan gathers K_max
slots for every row in every round.  The split plan keeps a main plan
``[N, R_pad, K0]`` and moves each hub's in-edges (in-degree > K0) into
chunks of K1 on overflow rows ``[N, O_pad, K1]``; the hub's own row holds
one pointer slot per chunk (src ``R_pad + o``, freq 1).  A round first
sums the overflow rows over ``weight * active`` (their delta and seen
partials), then the main rows over ``[weight * active ; overflow delta]``
and ``[active ; overflow seen]`` — still scatter-free, and no extra round,
where turning chunks into virtual rules would add rounds to the critical
path.  ``(K0, K1, O_pad)`` are powers of two chosen by
:func:`ell_split_shape` from the pack's in-degree histograms alone, to
minimise the slot count ``N * (R_pad * K0 + O_pad * K1)``; O_pad is the
largest corpus's need, so every shard of a sharded pack runs one program.
When no row exceeds the chosen K0 the split plan IS the dense plan (same
arrays, same program).  Counts are integer-valued float32 below 2**24, so
the split sums equal the dense ones bit for bit.

DESIGN — device-sharded batches (:meth:`GrammarBatch.shard`): the corpus
axis N is embarrassingly parallel (every traversal above is a vmap over
it), so a pack placed with ``NamedSharding(mesh, P(CORPUS_AXIS, ...))``
splits row-wise across a 1-D device mesh and the same analytics run as one
jitted program spanning all devices.  The frontier engines (a
``while_loop`` whose stop flag is ``mask.any()``) are wrapped in
``shard_map`` so each shard's loop stops when *its own* corpora finish —
no per-round cross-device all-reduce, and each shard executes exactly the
single-device program on its ``[N/D, ...]`` slice, which keeps results
bit-identical to the unsharded path (all counts are integer-valued and far
below 2**24, so float32 arithmetic is exact in any summation order).  The
leveled engines (static schedule, no loop) shard by placement alone.
Sharding requires N to be a multiple of the mesh size;
:mod:`repro.distributed.shard_batch` pads a corpus list to that multiple
(``n_real`` tracks how many rows are real — finalization and
:func:`unbatch` never surface padding rows).  Per-shard pack signatures
are identical by construction (same padded dims on every shard), so
recurring sharded traffic reuses compiled programs exactly like the
single-device pack cache.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from dataclasses import field as dataclass_field
from typing import Any, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.obs import global_registry
from repro.obs import plan_stage as _plan_stage
from repro.obs.tracing import device_scope as _device_scope
from repro.obs.tracing import readback as _readback

from .grammar import GrammarArrays, StaleGrammarError, pow2_bucket
from . import sequence as _sequence
from .sequence import _K_HEAD, _K_LIT, _K_TAIL


# ----------------------------------------------------------------------- #
# Packed layout                                                            #
# ----------------------------------------------------------------------- #
#: Mesh axis name the corpus dimension N shards over (1-D device mesh,
#: built by repro.distributed.shard_batch.corpus_mesh).
CORPUS_AXIS = "corpus"


def _round_up_pow2(x: int, minimum: int = 8) -> int:
    if x <= minimum:
        return minimum
    return 1 << (int(x) - 1).bit_length()


def ell_split_shape(hists: Sequence[np.ndarray],
                    rows: int) -> Tuple[int, int, int]:
    """``(K0, K1, O_pad)`` of the split ELL plan (module DESIGN note).

    ``hists[i][d]`` is the number of corpus i's rules with in-degree d and
    ``rows`` the pack's padded rule count.  Every power-of-two K0 below the
    dense width, and every power-of-two K1 wide enough that the widest hub
    needs at most K0 overflow rows, is priced at ``rows * K0 + O_pad * K1``
    slots per corpus (O_pad: the largest corpus's overflow rows, bucketed);
    the cheapest wins, the dense plan ``(K, K, 0)`` on ties.  A pure
    function of the histograms and ``rows``.
    """
    kmax = max((len(h) - 1 for h in hists), default=0)
    dense = pow2_bucket(kmax)
    degs = [(np.flatnonzero(h), h[np.flatnonzero(h)]) for h in hists]
    best = (rows * dense, dense, dense, 0)
    k0 = 1
    while k0 < dense:
        k1 = pow2_bucket(-(-kmax // k0))
        while k1 <= dense:
            need = max(int((c * -(-d // k1))[d > k0].sum()) for d, c in degs)
            o_pad = pow2_bucket(need) if need else 0
            cost = rows * k0 + o_pad * k1
            if cost < best[0]:
                best = (cost, k0, k1, o_pad)
            k1 *= 2
        k0 *= 2
    return best[1:]


def _pad_stack(arrs: Sequence[np.ndarray], width: int, fill=0,
               dtype=np.int32) -> np.ndarray:
    out = np.full((len(arrs), width), fill, dtype)
    for i, a in enumerate(arrs):
        out[i, : len(a)] = a
    return out


@dataclass(frozen=True, eq=False)   # eq over jnp fields would raise; identity
class GrammarBatch:
    """N grammars packed into padded ``[N, ...]`` device arrays."""

    gas: Tuple[GrammarArrays, ...]      # originals (host, for finalization)

    # padded dims (bucketed)
    R_pad: int
    E_pad: int
    T_pad: int
    F_pad: int
    V_pad: int
    Tf_pad: int

    # per-corpus true sizes (host)
    num_rules: np.ndarray               # [N]
    vocab_sizes: np.ndarray             # [N]
    num_files: np.ndarray               # [N]

    # packed DAG (device)
    edge_parent: jnp.ndarray            # [N, E_pad] int32
    edge_child: jnp.ndarray             # [N, E_pad] int32
    edge_freq: jnp.ndarray              # [N, E_pad] float32 (0 on padding)
    edge_valid: jnp.ndarray             # [N, E_pad] bool
    in_deg: jnp.ndarray                 # [N, R_pad] int32
    root_seen: jnp.ndarray              # [N, R_pad] int32 (in-edges from root)

    # packed local word tables (device)
    tw_rule: jnp.ndarray                # [N, T_pad] int32
    tw_word: jnp.ndarray                # [N, T_pad] int32
    tw_cnt: jnp.ndarray                 # [N, T_pad] float32 (0 on padding)

    # packed per-file root segments (device)
    fedge_file: jnp.ndarray             # [N, Ef_pad] int32
    fedge_child: jnp.ndarray            # [N, Ef_pad] int32
    fedge_freq: jnp.ndarray             # [N, Ef_pad] float32
    fword_file: jnp.ndarray             # [N, Tf_pad] int32
    fword_word: jnp.ndarray             # [N, Tf_pad] int32
    fword_cnt: jnp.ndarray              # [N, Tf_pad] float32

    # leveled schedule: per-level segments padded to shared widths
    lv_parent: jnp.ndarray              # [N, EL] int32
    lv_child: jnp.ndarray               # [N, EL] int32
    lv_freq: jnp.ndarray                # [N, EL] float32 (0 on padding)
    lv_slices: Tuple[Tuple[int, int], ...]   # shared (start, end) per level

    # device-sharded execution (module DESIGN note): a 1-D jax Mesh whose
    # CORPUS_AXIS splits the N axis row-wise, and the count of *real* rows
    # when the pack was padded up to a mesh multiple (None: all rows real)
    mesh: Any = None
    n_real: Optional[int] = None

    # ingest-tier staleness guard: the source-corpus epoch of each packed
    # row at pack time (None when the pack was built from bare immutable
    # GrammarArrays with no mutable store behind them).  A pack snapshots
    # its gas, so the pack itself stays internally consistent forever —
    # including every lazy plan below, which derives from those snapshot
    # arrays — but serving it for a corpus whose store has since absorbed
    # appended files would answer with pre-append data.  check_epochs is
    # the loud guard against that.
    epochs: Optional[Tuple[int, ...]] = None

    # per-batch memo for host-side sequence plans (mutable contents are
    # fine on a frozen dataclass; keyed by window length l)
    _plan_cache: dict = dataclass_field(default_factory=dict, repr=False,
                                        compare=False)

    @property
    def n(self) -> int:
        return len(self.gas)

    @property
    def real(self) -> int:
        """Rows that correspond to real corpora (the rest is shard padding;
        their results are computed and discarded, never surfaced)."""
        return self.n if self.n_real is None else self.n_real

    @property
    def real_gas(self) -> Tuple[GrammarArrays, ...]:
        return self.gas[: self.real]

    @property
    def shards(self) -> int:
        """Device count the pack spans (1 when unsharded)."""
        return 1 if self.mesh is None else int(self.mesh.size)

    @property
    def signature(self) -> Tuple[int, ...]:
        """Compilation signature: batches with equal signatures (and equal
        ``lv_slices`` for the leveled engine) reuse jitted programs.  The
        trailing element is the shard count — a sharded pack compiles a
        different (partitioned) program than a single-device pack of the
        same shape."""
        return (self.n, self.R_pad, self.E_pad, self.T_pad, self.F_pad,
                self.V_pad, int(self.fedge_file.shape[1]), self.Tf_pad,
                self.shards)

    # ------------------------------------------------------------- shard --
    def _placement(self, ndim: int):
        """NamedSharding splitting the leading (corpus) axis, or None when
        the pack is unsharded."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(CORPUS_AXIS, *([None] * (ndim - 1))))

    def _place(self, arr) -> jnp.ndarray:
        """Put one [N, ...] array where the pack lives: sharded row-wise
        across ``mesh`` (lazy plan arrays must land with the same placement
        as the packed arrays, or every sharded call pays a reshard)."""
        sh = self._placement(np.ndim(arr))
        a = jnp.asarray(arr)
        return a if sh is None else jax.device_put(a, sh)

    def shard(self, mesh, n_real: Optional[int] = None) -> "GrammarBatch":
        """Re-place every packed device array row-sharded over ``mesh``.

        ``mesh`` must be a 1-D mesh over axis ``CORPUS_AXIS`` whose size
        divides N (use :func:`repro.distributed.shard_batch.shard_batch` to
        pad an arbitrary corpus list up to the multiple).  Returns a new
        :class:`GrammarBatch`; lazy plans (ELL, sequence) are rebuilt on
        demand with the sharded placement.
        """
        if tuple(mesh.axis_names) != (CORPUS_AXIS,):
            raise ValueError(f"mesh must be 1-D over axis {CORPUS_AXIS!r}, "
                             f"got axes {tuple(mesh.axis_names)}")
        d = int(mesh.shape[CORPUS_AXIS])
        if self.n % d:
            raise ValueError(
                f"batch of {self.n} corpora does not divide across {d} "
                f"devices; pad first (distributed.shard_batch.shard_batch)")
        if n_real is not None and not (0 < n_real <= self.n):
            raise ValueError(f"n_real={n_real} out of range for N={self.n}")
        sharded = dataclasses.replace(
            self, mesh=mesh,
            n_real=self.n_real if n_real is None else n_real,
            _plan_cache={})
        # re-place the packed [N, ...] device arrays row-sharded
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, jnp.ndarray):
                object.__setattr__(sharded, f.name, sharded._place(v))
        return sharded

    def check_epochs(self, current: Sequence[int]) -> None:
        """Raise :class:`StaleGrammarError` if any source corpus has moved
        past the epoch this pack (and every lazy plan memoized on it) was
        built from.

        ``current`` is the live epoch per *real* row, in pack order (shard
        padding rows duplicate a real grammar and are never surfaced, so
        only the real prefix is compared).  Packs without epoch stamps
        (``epochs is None`` — built from bare immutable arrays) pass
        trivially.  The serving layer re-packs instead of raising; this is
        the backstop for any caller that skips that refresh.
        """
        if self.epochs is None:
            return
        cur = tuple(int(e) for e in current)
        if len(cur) > len(self.epochs):
            raise StaleGrammarError(
                f"epoch check over {len(cur)} corpora against a pack "
                f"stamped with {len(self.epochs)}")
        for i, (have, now) in enumerate(zip(self.epochs, cur)):
            if have != now:
                raise StaleGrammarError(
                    f"pack row {i} was built at corpus epoch {have} but "
                    f"the corpus is now at epoch {now} — re-pack before "
                    f"serving (the corpus absorbed appended files)")

    @property
    def total_edges(self) -> int:
        """True (unpadded) edge count across the batch (memoized: the
        dispatch runs per batched call on cached packs)."""
        if ("edges",) not in self._plan_cache:
            self._plan_cache[("edges",)] = sum(ga.num_edges
                                               for ga in self.gas)
        return self._plan_cache[("edges",)]

    def ell_plan_width(self) -> int:
        """K of the dense ELL plan (max in-degree across the batch, bucketed
        to a power of two) — host-only and memoized: lets the auto dispatch
        reject the plan before building it, on every call, for free."""
        if ("ell_width",) not in self._plan_cache:
            kmax = max((int(ga.in_deg.max(initial=0)) for ga in self.gas),
                       default=0)
            self._plan_cache[("ell_width",)] = pow2_bucket(kmax)
        return self._plan_cache[("ell_width",)]

    def ell_plan(self) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, int]:
        """Dense [N, R_pad, K] in-edge plan + per-rule levels (memoized).

        Returns ``(src, freq, level, num_levels)``: src/freq are the padded
        per-corpus :meth:`GrammarArrays.in_edges_ell_dense` plans stacked to
        a shared K, ``level[i, r]`` is corpus i's rule level (-1 on padded
        rule slots — never active in the leveled replay), and num_levels the
        shared (max) level count.  Built lazily: the engines run
        :meth:`ell_split_plan`, which builds this plan only when it splits
        no row.
        """
        key = ("ell",)
        if key not in self._plan_cache:
            with _plan_stage("ell"):
                K = self.ell_plan_width()
                src = np.zeros((self.n, self.R_pad, K), np.int32)
                freq = np.zeros((self.n, self.R_pad, K), np.float32)
                for i, ga in enumerate(self.gas):
                    s, f = ga.in_edges_ell_dense(k=K)
                    src[i, : ga.num_rules] = s
                    freq[i, : ga.num_rules] = f
                self._plan_cache[key] = (
                    self._place(src), self._place(freq),
                    *self._levels())
        return self._plan_cache[key]

    def _levels(self) -> Tuple[jnp.ndarray, int]:
        """Per-rule levels ``[N, R_pad]`` (-1 on padded rule slots) and the
        shared (max) level count, for the ELL plans."""
        level = np.full((self.n, self.R_pad), -1, np.int32)
        for i, ga in enumerate(self.gas):
            level[i, : ga.num_rules] = ga.level
        return (self._place(level),
                max(ga.num_levels for ga in self.gas))

    def ell_split_shape(self) -> Tuple[int, int, int]:
        """``(K0, K1, O_pad)`` of the split plan (:func:`ell_split_shape`
        over the pack's in-degree histograms) — host-only and memoized, so
        the dispatch gates read it on every call without building a plan.
        ``O_pad == 0``: no row is split and K0 is the dense width."""
        if ("ell_split_shape",) not in self._plan_cache:
            self._plan_cache[("ell_split_shape",)] = ell_split_shape(
                [np.bincount(ga.in_deg, minlength=1) for ga in self.gas],
                self.R_pad)
        return self._plan_cache[("ell_split_shape",)]

    def ell_plan_slots(self) -> int:
        """Slots of the plan the ELL engines run: ``N * (R_pad * K0 +
        O_pad * K1)`` (the dense ``N * R_pad * K`` when nothing splits)."""
        k0, k1, o_pad = self.ell_split_shape()
        return self.n * (self.R_pad * k0 + o_pad * k1)

    def ell_split_plan(self) -> Tuple[jnp.ndarray, jnp.ndarray, tuple,
                                      jnp.ndarray, int]:
        """The plan the ELL engines run (module DESIGN note), memoized.

        Returns ``(src, freq, ovf, level, num_levels)``: the main plan
        ``[N, R_pad, K0]``, ``ovf`` the trailing arguments the ELL programs
        take — ``()`` when no row is split (then src/freq/level are
        :meth:`ell_plan`'s own arrays), else ``((ovf_src, ovf_freq),)``
        with the overflow plan ``[N, O_pad, K1]`` — and the levels as in
        :meth:`ell_plan`.  Counted on ``repro_kernel_dispatch_total
        {decision="ell_split"}``, and its slots per real edge set the gauge
        ``repro_ell_plan_slots_per_edge``.
        """
        from repro.kernels import ops as kops

        key = ("ell_split",)
        if key in self._plan_cache:
            return self._plan_cache[key]
        k0, k1, o_pad = self.ell_split_shape()
        if not o_pad:
            src, freq, level, num_levels = self.ell_plan()
            ovf = ()
        else:
            with _plan_stage("ell"):
                src = np.zeros((self.n, self.R_pad, k0), np.int32)
                freq = np.zeros((self.n, self.R_pad, k0), np.float32)
                osrc = np.zeros((self.n, o_pad, k1), np.int32)
                ofreq = np.zeros((self.n, o_pad, k1), np.float32)
                for i, ga in enumerate(self.gas):
                    s, f, os_, of = ga.in_edges_ell_split(k0, k1, self.R_pad)
                    src[i, : ga.num_rules] = s
                    freq[i, : ga.num_rules] = f
                    osrc[i, : len(os_)] = os_
                    ofreq[i, : len(of)] = of
                src, freq = self._place(src), self._place(freq)
                ovf = ((self._place(osrc), self._place(ofreq)),)
                level, num_levels = self._levels()
        kops.count_ell_split(bool(o_pad))
        global_registry().gauge(
            "repro_ell_plan_slots_per_edge",
            "slots of the last ELL plan built per real in-edge").set(
                self.ell_plan_slots() / max(self.total_edges, 1))
        self._plan_cache[key] = (src, freq, ovf, level, num_levels)
        return self._plan_cache[key]

    # ------------------------------------------------------------ build --
    @classmethod
    def build(cls, gas: Sequence[GrammarArrays],
              bucket: bool = True,
              epochs: Optional[Sequence[int]] = None) -> "GrammarBatch":
        if not gas:
            raise ValueError("GrammarBatch needs at least one corpus")
        gas = tuple(gas)
        if epochs is not None:
            epochs = tuple(int(e) for e in epochs)
            if len(epochs) != len(gas):
                raise ValueError(f"epochs stamps {len(epochs)} corpora but "
                                 f"the pack holds {len(gas)}")
        rnd = _round_up_pow2 if bucket else (lambda x, minimum=1:
                                             max(int(x), minimum))
        R_pad = rnd(max(ga.num_rules for ga in gas))
        E_pad = rnd(max(ga.num_edges for ga in gas))
        T_pad = rnd(max(len(ga.tw_rule) for ga in gas))
        F_pad = rnd(max(ga.num_files for ga in gas), 1)
        V_pad = rnd(max(ga.vocab_size for ga in gas))
        Ef_pad = rnd(max(len(ga.fedge_file) for ga in gas), 1)
        Tf_pad = rnd(max(len(ga.fword_file) for ga in gas), 1)

        in_deg = _pad_stack([ga.in_deg for ga in gas], R_pad)
        root_seen = _pad_stack(
            [np.bincount(ga.edge_child[ga.edge_parent == 0],
                         minlength=ga.num_rules).astype(np.int32)
             for ga in gas], R_pad)
        valid = np.zeros((len(gas), E_pad), bool)
        for i, ga in enumerate(gas):
            valid[i, : ga.num_edges] = True

        # leveled schedule: align per-level segments across corpora
        n_levels = max(ga.num_levels for ga in gas)
        per_corpus = []
        for ga in gas:
            slices, order = ga.level_edge_slices()
            per_corpus.append((slices, order))
        widths = []
        for lv in range(n_levels):
            w = 0
            for (slices, _) in per_corpus:
                if lv < len(slices):
                    s, e = slices[lv]
                    w = max(w, e - s)
            widths.append(w)
        EL = sum(widths)
        lv_parent = np.zeros((len(gas), EL), np.int32)
        lv_child = np.zeros((len(gas), EL), np.int32)
        lv_freq = np.zeros((len(gas), EL), np.float32)
        lv_slices: List[Tuple[int, int]] = []
        off = 0
        for lv, w in enumerate(widths):
            lv_slices.append((off, off + w))
            for i, (ga, (slices, order)) in enumerate(zip(gas, per_corpus)):
                if lv >= len(slices):
                    continue
                s, e = slices[lv]
                sel = order[s:e]
                lv_parent[i, off: off + (e - s)] = ga.edge_parent[sel]
                lv_child[i, off: off + (e - s)] = ga.edge_child[sel]
                lv_freq[i, off: off + (e - s)] = ga.edge_freq[sel]
            off += w

        return cls(
            gas=gas,
            epochs=epochs,
            R_pad=R_pad, E_pad=E_pad, T_pad=T_pad, F_pad=F_pad,
            V_pad=V_pad, Tf_pad=Tf_pad,
            num_rules=np.array([ga.num_rules for ga in gas]),
            vocab_sizes=np.array([ga.vocab_size for ga in gas]),
            num_files=np.array([ga.num_files for ga in gas]),
            edge_parent=jnp.asarray(
                _pad_stack([ga.edge_parent for ga in gas], E_pad)),
            edge_child=jnp.asarray(
                _pad_stack([ga.edge_child for ga in gas], E_pad)),
            edge_freq=jnp.asarray(
                _pad_stack([ga.edge_freq for ga in gas], E_pad,
                           dtype=np.float32)),
            edge_valid=jnp.asarray(valid),
            in_deg=jnp.asarray(in_deg),
            root_seen=jnp.asarray(root_seen),
            tw_rule=jnp.asarray(_pad_stack([ga.tw_rule for ga in gas], T_pad)),
            tw_word=jnp.asarray(_pad_stack([ga.tw_word for ga in gas], T_pad)),
            tw_cnt=jnp.asarray(
                _pad_stack([ga.tw_cnt for ga in gas], T_pad,
                           dtype=np.float32)),
            fedge_file=jnp.asarray(
                _pad_stack([ga.fedge_file for ga in gas], Ef_pad)),
            fedge_child=jnp.asarray(
                _pad_stack([ga.fedge_child for ga in gas], Ef_pad)),
            fedge_freq=jnp.asarray(
                _pad_stack([ga.fedge_freq for ga in gas], Ef_pad,
                           dtype=np.float32)),
            fword_file=jnp.asarray(
                _pad_stack([ga.fword_file for ga in gas], Tf_pad)),
            fword_word=jnp.asarray(
                _pad_stack([ga.fword_word for ga in gas], Tf_pad)),
            fword_cnt=jnp.asarray(
                _pad_stack([ga.fword_cnt for ga in gas], Tf_pad,
                           dtype=np.float32)),
            lv_parent=jnp.asarray(lv_parent),
            lv_child=jnp.asarray(lv_child),
            lv_freq=jnp.asarray(lv_freq),
            lv_slices=tuple(lv_slices),
        )


# ----------------------------------------------------------------------- #
# Batched traversals                                                       #
# ----------------------------------------------------------------------- #
@functools.lru_cache(maxsize=None)
def _sharded_program(fn, mesh, in_ndims: Tuple[int, ...],
                     out_ndim: Union[int, Tuple[int, ...]],
                     static: Tuple[Tuple[str, Any], ...] = ()):
    """``jit(shard_map(fn))`` splitting every array's leading corpus axis.

    Each shard runs ``fn`` — the exact single-device program — on its
    ``[N/D, ...]`` row slice; nothing crosses shards, so a frontier
    ``while_loop`` inside ``fn`` stops as soon as the shard's own corpora
    finish instead of spinning until the globally slowest one does.
    Memoized per (fn, mesh, shapes, statics) so recurring sharded calls
    reach jit's compile cache instead of rebuilding a fresh (cache-missing)
    wrapper each time; ``static`` binds hashable keyword args (level
    schedules, padded dims) before wrapping.  ``out_ndim`` may be a tuple
    of ranks for functions returning several row-sharded arrays (the
    search scorer returns top-k values + indices).
    """
    bound = functools.partial(fn, **dict(static)) if static else fn

    def spec(nd: int) -> P:
        return P(CORPUS_AXIS, *([None] * (nd - 1)))

    out_specs = (tuple(spec(nd) for nd in out_ndim)
                 if isinstance(out_ndim, tuple) else spec(out_ndim))
    sm = shard_map(bound, mesh=mesh,
                   in_specs=tuple(spec(nd) for nd in in_ndims),
                   out_specs=out_specs, check_vma=False)
    return jax.jit(sm)


@_device_scope("traversal")
def _frontier_weights_impl(ep, ec, ef, valid, in_deg):
    """vmap of the masked frontier rounds; one shared while_loop.

    The vmapped ``while_loop`` runs until every corpus's mask is empty;
    corpora that finish early keep executing no-op rounds (their ``mask``
    is all-False, so delta and seen are zero and the state is a fixpoint).
    """
    R = in_deg.shape[1]

    def one(ep, ec, ef, valid, in_deg):
        def cond(state):
            _, _, mask, _ = state
            return jnp.any(mask)

        def body(state):
            weight, cur_in, mask, ever = state
            active_e = mask[ep] & valid
            contrib = jnp.where(active_e, ef * weight[ep], 0.0)
            delta = jax.ops.segment_sum(contrib, ec, num_segments=R)
            seen = jax.ops.segment_sum(active_e.astype(jnp.int32), ec,
                                       num_segments=R)
            weight = weight + delta
            cur_in = cur_in + seen
            new_ready = (cur_in == in_deg) & (~ever)
            return weight, cur_in, new_ready, ever | new_ready

        weight0 = jnp.zeros(R, jnp.float32).at[0].set(1.0)
        mask0 = (in_deg == 0)
        state = (weight0, jnp.zeros(R, jnp.int32), mask0, mask0)
        weight, _, _, _ = jax.lax.while_loop(cond, body, state)
        return weight

    return jax.vmap(one)(ep, ec, ef, valid, in_deg)


_frontier_weights_batched = jax.jit(_frontier_weights_impl)


@_device_scope("traversal")
def _leveled_weights_impl(ep, ec, ef, slices, R):
    """Shared static level schedule; each real edge touched exactly once
    (padded slots have freq 0)."""
    N = ep.shape[0]
    w = jnp.zeros((N, R), jnp.float32).at[:, 0].set(1.0)
    seg = jax.vmap(lambda c, i: jax.ops.segment_sum(c, i, num_segments=R))
    for (s, e) in slices:
        if s == e:
            continue
        contrib = ef[:, s:e] * jnp.take_along_axis(w, ep[:, s:e], axis=1)
        w = w + seg(contrib, ec[:, s:e])
    return w


_leveled_weights_batched = jax.jit(_leveled_weights_impl,
                                   static_argnames=("slices", "R"))


# Methods that run on the ELL plan, and the segment_sum bases an
# ineligible request degrades to.  ``resolve_traversal_method`` is the ONE
# place the gates live: the engines dispatch on its answer and the serving
# layer compares requested-vs-resolved to count downgrades
# (ServerStats.method_fallbacks) instead of remapping silently.
ELL_METHODS = ("frontier_ell", "leveled_ell", "frontier_fused")
SEGMENT_SUM_BASES = {"frontier_ell": "frontier", "frontier_fused": "frontier",
                     "leveled_ell": "leveled"}
# Kinds whose traversal carries the [R, F] per-file payload (the rest use
# the scalar weight vector).  Search kinds feed from batched_term_vector,
# so they are per-file too (serving/analytics_server.py extends this set).
PER_FILE_KINDS = ("term_vector", "inverted_index", "ranked_inverted_index")


def resolve_traversal_method(method: str, *, n: int, rows: int, k: int,
                             edges: int, shards: int = 1,
                             per_file: bool = False, f: int = 1,
                             slots: Optional[int] = None) -> str:
    """Resolve a requested traversal method against the pack's shape gates.

    Pure over dimensions (n/rows are the pack's N and R_pad, k the widest
    row of the ELL plan that would run and ``slots`` its slot count —
    default the dense ``n * rows * k``; ``f`` is F_pad for per-file
    traversals) so the serving layer can predict the engine's routing
    without building a plan.  Rules:

    * ``auto`` — occupancy dispatch (kernels.ops.ell_batched_use_ref, per
      shard), then the fused path when the scalar state fits VMEM;
    * explicit ELL methods degrade to their segment_sum base when the dense
      plan itself is ineligible (width / absolute-entry safety valves, and
      the vector-payload budget for per-file traversals);
    * ``frontier_fused`` degrades to ``frontier_ell`` (still an ELL base —
      NOT a fallback) when the fused state exceeds VMEM residency or the
      traversal is per-file (the fused kernel is scalar-payload).
    """
    from repro.kernels import ops as kops

    if method == "auto":
        if kops.ell_batched_use_ref(edges, n, rows, k, shards=shards,
                                    slots=slots):
            return "frontier"
        if per_file:
            if not kops.ell_vector_plan_ok(n, rows, k, f, slots=slots):
                return "frontier"
            return "frontier_ell"
        if kops.ell_fused_use_kernel(rows):
            return "frontier_fused"
        return "frontier_ell"
    if method in ELL_METHODS:
        # safety valves even when ELL is requested explicitly: a skewed
        # grammar (a row wider than any realistic bucket) or a huge one
        # (a plan of more slots than the budget) falls back to the
        # segment_sum base (identical results).
        if (k > kops.ELL_BATCH_MAX_WIDTH
                or (n * rows * k if slots is None else slots)
                > kops.ELL_PLAN_MAX_ENTRIES):
            return SEGMENT_SUM_BASES[method]
        if per_file:
            if not kops.ell_vector_plan_ok(n, rows, k, f, slots=slots):
                return SEGMENT_SUM_BASES[method]
            if method == "frontier_fused":
                return "frontier_ell"
        elif method == "frontier_fused":
            if not kops.ell_fused_use_kernel(rows):
                return "frontier_ell"
    return method


def is_segment_sum_fallback(requested: str, resolved: str) -> bool:
    """True when an explicitly-requested ELL-family method landed on a
    segment_sum base (the downgrade ServerStats.method_fallbacks counts)."""
    return requested in ELL_METHODS and resolved in ("frontier", "leveled")


def resolve_batch_method(gb: "GrammarBatch", method: str,
                         per_file: bool = False) -> str:
    """`resolve_traversal_method` with the dims read off a built pack: the
    gates judge the split plan the ELL engines would run."""
    if method != "auto" and method not in ELL_METHODS:
        return method
    k0, k1, _ = gb.ell_split_shape()
    return resolve_traversal_method(
        method, n=gb.n, rows=gb.R_pad, k=max(k0, k1),
        edges=gb.total_edges, shards=gb.shards, per_file=per_file,
        f=gb.F_pad, slots=gb.ell_plan_slots())


@_device_scope("traversal")
def _frontier_ell_impl(ell_src, ell_freq, in_deg, ovf=()):
    """Masked frontier rounds over the ELL plan: every round is ONE fused
    gather + row-sum (no scatter), with delta and the seen-counter emitted
    by the same kernels.ops.ell_propagate_batched call.  ``ovf`` is the
    overflow plan of a split plan (module DESIGN note), empty for a dense
    one — then the program is the dense plan's."""
    from repro.kernels import ops as kops

    N, R = in_deg.shape

    def cond(state):
        _, _, mask, _ = state
        return jnp.any(mask)

    def body(state):
        weight, cur_in, mask, ever = state
        delta, seen = kops.ell_propagate_batched(
            weight, mask.astype(jnp.float32), ell_src, ell_freq, ovf=ovf)
        weight = weight + delta
        cur_in = cur_in + seen.astype(jnp.int32)
        new_ready = (cur_in == in_deg) & (~ever)
        return weight, cur_in, new_ready, ever | new_ready

    weight0 = jnp.zeros((N, R), jnp.float32).at[:, 0].set(1.0)
    mask0 = (in_deg == 0)
    state = (weight0, jnp.zeros((N, R), jnp.int32), mask0, mask0)
    weight, _, _, _ = jax.lax.while_loop(cond, body, state)
    return weight


_frontier_weights_batched_ell = jax.jit(_frontier_ell_impl)


@_device_scope("traversal")
def _leveled_ell_impl(ell_src, ell_freq, level, ovf=(), *, num_levels):
    """Static level schedule over the ELL plan (``ovf`` as in
    ``_frontier_ell_impl``): level lv's round activates exactly the
    parents at that level, so each real edge contributes once, at its
    parent's level (padded slots: level -1)."""
    from repro.kernels import ops as kops

    N, R = level.shape
    w = jnp.zeros((N, R), jnp.float32).at[:, 0].set(1.0)
    for lv in range(num_levels):
        active = (level == lv).astype(jnp.float32)
        delta, _ = kops.ell_propagate_batched(w, active, ell_src, ell_freq,
                                              ovf=ovf)
        w = w + delta
    return w


_leveled_weights_batched_ell = jax.jit(_leveled_ell_impl,
                                       static_argnames=("num_levels",))


@_device_scope("traversal")
def _frontier_fused_impl(ell_src, ell_freq, in_deg, ovf=(), *, num_levels):
    """The whole frontier loop in ONE dispatch (kernels.ops runs the jitted
    fori_loop form on TPU and CPU; the fused Pallas kernel is its
    interpret-mode oracle for the dense plan — kernels/ops.py DESIGN note;
    ``ovf`` as in ``_frontier_ell_impl``).

    ``num_levels`` — the pack's max DAG depth — is the exact round count
    the while_loop form executes (level-L rules activate in round L+1), so
    the static bound loses nothing; corpora shallower than the deepest one
    converge early and their remaining rounds are exact no-ops.  This
    replaces the per-round while_loop -> kernel -> XLA round-trip
    ("the structural tax"): one launch instead of num_levels launches.
    """
    from repro.kernels import ops as kops

    N, R = in_deg.shape
    w0 = jnp.zeros((N, R), jnp.float32).at[:, 0].set(1.0)
    return kops.ell_frontier_fused(w0, in_deg.astype(jnp.float32),
                                   ell_src, ell_freq, num_levels, ovf=ovf)


_frontier_fused_batched = jax.jit(_frontier_fused_impl,
                                  static_argnames=("num_levels",))


def batched_top_down_weights(gb: GrammarBatch,
                             method: str = "frontier") -> jnp.ndarray:
    """weights[i, r] == occurrences of corpus i's rule r. Shape [N, R_pad].

    Methods: ``frontier`` / ``leveled`` (COO + segment_sum),
    ``frontier_ell`` / ``leveled_ell`` (the split ELL plan, scatter-free,
    per-round), ``frontier_fused`` (the ELL frontier loop in ONE dispatch —
    kernels/propagate_fused.py), and ``auto`` (occupancy dispatch via
    ``resolve_traversal_method``: ELL when the plan is dense enough, fused
    when the state fits VMEM).  Sharded packs (``gb.mesh``) run the same
    methods through ``shard_map`` — each device traverses its own corpus
    rows (module DESIGN note), results bit-identical to the unsharded
    program.
    """
    method = resolve_batch_method(gb, method)
    if method in ("frontier", "top_down", "bottom_up"):
        if gb.mesh is not None:
            return _sharded_program(_frontier_weights_impl, gb.mesh,
                                    (2, 2, 2, 2, 2), 2)(
                gb.edge_parent, gb.edge_child, gb.edge_freq, gb.edge_valid,
                gb.in_deg)
        return _frontier_weights_batched(
            gb.edge_parent, gb.edge_child, gb.edge_freq, gb.edge_valid,
            gb.in_deg)
    if method == "leveled":
        if gb.mesh is not None:
            return _sharded_program(
                _leveled_weights_impl, gb.mesh, (2, 2, 2), 2,
                static=(("slices", gb.lv_slices), ("R", gb.R_pad)))(
                gb.lv_parent, gb.lv_child, gb.lv_freq)
        return _leveled_weights_batched(
            gb.lv_parent, gb.lv_child, gb.lv_freq, gb.lv_slices, gb.R_pad)
    if method == "frontier_ell":
        src, freq, ovf, _, _ = gb.ell_split_plan()
        if gb.mesh is not None:
            return _sharded_program(_frontier_ell_impl, gb.mesh,
                                    (3, 3, 2) + (3,) * len(ovf), 2)(
                src, freq, gb.in_deg, *ovf)
        return _frontier_weights_batched_ell(src, freq, gb.in_deg, *ovf)
    if method == "leveled_ell":
        src, freq, ovf, level, num_levels = gb.ell_split_plan()
        if gb.mesh is not None:
            return _sharded_program(
                _leveled_ell_impl, gb.mesh, (3, 3, 2) + (3,) * len(ovf), 2,
                static=(("num_levels", num_levels),))(src, freq, level, *ovf)
        return _leveled_weights_batched_ell(src, freq, level, *ovf,
                                            num_levels=num_levels)
    if method == "frontier_fused":
        src, freq, ovf, _, num_levels = gb.ell_split_plan()
        if gb.mesh is not None:
            return _sharded_program(
                _frontier_fused_impl, gb.mesh, (3, 3, 2) + (3,) * len(ovf),
                2, static=(("num_levels", num_levels),))(
                src, freq, gb.in_deg, *ovf)
        return _frontier_fused_batched(src, freq, gb.in_deg, *ovf,
                                       num_levels=num_levels)
    raise ValueError(f"unknown batched traversal method {method!r}")


@_device_scope("traversal")
def _per_file_frontier_impl(ep, ec, ef, valid, in_deg, root_seen,
                            fedge_child, fedge_file, fedge_freq, F):
    R = in_deg.shape[1]

    def one(ep, ec, ef, valid, in_deg, root_seen, fc, ff, fq):
        W0 = jnp.zeros((R, F), jnp.float32).at[fc, ff].add(fq)

        def cond(state):
            _, _, mask, _ = state
            return jnp.any(mask)

        def body(state):
            W, cur_in, mask, ever = state
            active_e = mask[ep] & valid & (ep != 0)
            gathered = W[ep, :] * ef[:, None]
            gathered = jnp.where(active_e[:, None], gathered, 0.0)
            delta = jax.ops.segment_sum(gathered, ec, num_segments=R)
            seen = jax.ops.segment_sum(active_e.astype(jnp.int32), ec,
                                       num_segments=R)
            W = W + delta
            cur_in = cur_in + seen
            new_ready = (cur_in == in_deg) & (~ever)
            return W, cur_in, new_ready, ever | new_ready

        mask0 = (root_seen == in_deg) & (in_deg > 0)
        state = (W0, root_seen, mask0, mask0 | (in_deg == 0))
        W, _, _, _ = jax.lax.while_loop(cond, body, state)
        return W

    return jax.vmap(one)(ep, ec, ef, valid, in_deg, root_seen,
                         fedge_child, fedge_file, fedge_freq)


_per_file_weights_batched = jax.jit(_per_file_frontier_impl,
                                    static_argnames=("F",))


@_device_scope("traversal")
def _per_file_leveled_impl(ep, ec, ef, fedge_child, fedge_file,
                           fedge_freq, slices, R, F):
    """Leveled per-file traversal: root edges are consumed by the per-file
    init (splitter segments), so every non-root edge is touched once.
    Padded slots have ``parent == 0`` and are excluded by the same gate."""
    N = ep.shape[0]
    W = jax.vmap(
        lambda fc, ff, fq: jnp.zeros((R, F), jnp.float32).at[fc, ff].add(fq)
    )(fedge_child, fedge_file, fedge_freq)
    seg = jax.vmap(lambda c, i: jax.ops.segment_sum(c, i, num_segments=R))
    for (s, e) in slices:
        if s == e:
            continue
        keep = (ep[:, s:e] != 0).astype(jnp.float32)
        gathered = jnp.take_along_axis(W, ep[:, s:e, None], axis=1)  # [N,w,F]
        contrib = gathered * (ef[:, s:e] * keep)[:, :, None]
        W = W + seg(contrib, ec[:, s:e])
    return W


_per_file_leveled_batched = jax.jit(_per_file_leveled_impl,
                                    static_argnames=("slices", "R", "F"))


@_device_scope("traversal")
def _per_file_frontier_ell_impl(ell_src, ell_freq, in_deg, root_seen,
                                fedge_child, fedge_file, fedge_freq, ovf=(),
                                *, F):
    """Per-file frontier rounds over the ELL plan (``ovf`` as in
    ``_frontier_ell_impl``) with the VECTOR payload round
    (kernels.ops.ell_propagate_vector).  Root-edge exclusion
    is structural: the root has in_deg == 0 so it enters ``ever`` at init
    and its mask entry is never 1 — plan entries with src == 0 contribute
    nothing, exactly the ``ep != 0`` gate of the COO form (root edges are
    consumed by the per-file init and pre-counted in ``root_seen``)."""
    from repro.kernels import ops as kops

    R = in_deg.shape[1]

    def cond(state):
        _, _, mask, _ = state
        return jnp.any(mask)

    def body(state):
        W, cur_in, mask, ever = state
        delta, seen = kops.ell_propagate_vector(
            W, mask.astype(jnp.float32), ell_src, ell_freq, ovf=ovf)
        W = W + delta
        cur_in = cur_in + seen.astype(jnp.int32)
        new_ready = (cur_in == in_deg) & (~ever)
        return W, cur_in, new_ready, ever | new_ready

    W0 = jax.vmap(
        lambda fc, ff, fq: jnp.zeros((R, F), jnp.float32).at[fc, ff].add(fq)
    )(fedge_child, fedge_file, fedge_freq.astype(jnp.float32))
    mask0 = (root_seen == in_deg) & (in_deg > 0)
    state = (W0, root_seen, mask0, mask0 | (in_deg == 0))
    W, _, _, _ = jax.lax.while_loop(cond, body, state)
    return W


_per_file_ell_batched = jax.jit(_per_file_frontier_ell_impl,
                                static_argnames=("F",))


@_device_scope("traversal")
def _per_file_leveled_ell_impl(ell_src, ell_freq, level, fedge_child,
                               fedge_file, fedge_freq, ovf=(), *,
                               num_levels, F):
    """Leveled per-file traversal over the ELL plan (``ovf`` as in
    ``_frontier_ell_impl``): level lv's vector round activates exactly
    the parents at that level.  The root
    (rule 0, level 0) is masked out — its edges are consumed by the
    per-file init, like the COO form's ``parent != 0`` gate."""
    from repro.kernels import ops as kops

    R = level.shape[1]
    W = jax.vmap(
        lambda fc, ff, fq: jnp.zeros((R, F), jnp.float32).at[fc, ff].add(fq)
    )(fedge_child, fedge_file, fedge_freq.astype(jnp.float32))
    nonroot = (jnp.arange(R) > 0)[None, :]
    for lv in range(num_levels):
        active = ((level == lv) & nonroot).astype(jnp.float32)
        delta, _ = kops.ell_propagate_vector(W, active, ell_src, ell_freq,
                                             ovf=ovf)
        W = W + delta
    return W


_per_file_leveled_ell_batched = jax.jit(_per_file_leveled_ell_impl,
                                        static_argnames=("num_levels", "F"))


def batched_per_file_weights(gb: GrammarBatch,
                             method: str = "frontier") -> jnp.ndarray:
    """Wf[i, r, f] == occurrences of rule r inside file f of corpus i.

    The ELL methods run the vector-payload [R, F] rounds
    (kernels/propagate_vector.py) over the same split edge plan as the
    scalar traversals — no more silent remap to the segment_sum bases
    (``resolve_batch_method`` still degrades ineligible plans, and the
    serving layer counts those downgrades).  ``frontier_fused`` runs its
    per-round ELL base here (the fused kernel is scalar-payload).  Sharded
    packs run through ``shard_map`` like the scalar traversals.
    """
    method = resolve_batch_method(gb, method, per_file=True)
    if method in ("frontier", "top_down", "bottom_up"):
        if gb.mesh is not None:
            return _sharded_program(
                _per_file_frontier_impl, gb.mesh,
                (2, 2, 2, 2, 2, 2, 2, 2, 2), 3,
                static=(("F", gb.F_pad),))(
                gb.edge_parent, gb.edge_child, gb.edge_freq, gb.edge_valid,
                gb.in_deg, gb.root_seen, gb.fedge_child, gb.fedge_file,
                gb.fedge_freq)
        return _per_file_weights_batched(
            gb.edge_parent, gb.edge_child, gb.edge_freq, gb.edge_valid,
            gb.in_deg, gb.root_seen, gb.fedge_child, gb.fedge_file,
            gb.fedge_freq, gb.F_pad)
    if method == "leveled":
        if gb.mesh is not None:
            return _sharded_program(
                _per_file_leveled_impl, gb.mesh, (2, 2, 2, 2, 2, 2), 3,
                static=(("slices", gb.lv_slices), ("R", gb.R_pad),
                        ("F", gb.F_pad)))(
                gb.lv_parent, gb.lv_child, gb.lv_freq, gb.fedge_child,
                gb.fedge_file, gb.fedge_freq)
        return _per_file_leveled_batched(
            gb.lv_parent, gb.lv_child, gb.lv_freq, gb.fedge_child,
            gb.fedge_file, gb.fedge_freq, gb.lv_slices, gb.R_pad, gb.F_pad)
    if method == "frontier_ell":
        src, freq, ovf, _, _ = gb.ell_split_plan()
        args = (src, freq, gb.in_deg, gb.root_seen, gb.fedge_child,
                gb.fedge_file, gb.fedge_freq, *ovf)
        if gb.mesh is not None:
            return _sharded_program(
                _per_file_frontier_ell_impl, gb.mesh,
                (3, 3, 2, 2, 2, 2, 2) + (3,) * len(ovf), 3,
                static=(("F", gb.F_pad),))(*args)
        return _per_file_ell_batched(*args, F=gb.F_pad)
    if method == "leveled_ell":
        src, freq, ovf, level, num_levels = gb.ell_split_plan()
        args = (src, freq, level, gb.fedge_child, gb.fedge_file,
                gb.fedge_freq, *ovf)
        if gb.mesh is not None:
            return _sharded_program(
                _per_file_leveled_ell_impl, gb.mesh,
                (3, 3, 2, 2, 2, 2) + (3,) * len(ovf), 3,
                static=(("num_levels", num_levels), ("F", gb.F_pad)))(*args)
        return _per_file_leveled_ell_batched(*args, num_levels=num_levels,
                                             F=gb.F_pad)
    raise ValueError(f"unknown batched traversal method {method!r}")


# ----------------------------------------------------------------------- #
# Batched analytics (the six CompressDirect apps)                          #
# ----------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("V",))
@_device_scope("analytics")
def _word_count_from_weights(w, tw_rule, tw_word, tw_cnt, V):
    vals = tw_cnt * jnp.take_along_axis(w, tw_rule, axis=1)
    return jax.vmap(
        lambda i, v: jax.ops.segment_sum(v, i, num_segments=V))(tw_word, vals)


def batched_word_count(gb: GrammarBatch, method: str = "frontier",
                       backend: str = "jnp") -> jnp.ndarray:
    """counts[i, v] for every corpus in one jitted call. Shape [N, V_pad]."""
    w = batched_top_down_weights(gb, method=method)
    if backend == "pallas":
        from repro.kernels import ops as kops
        vals = gb.tw_cnt * jnp.take_along_axis(w, gb.tw_rule, axis=1)
        return kops.weighted_bincount_batched(gb.tw_word, vals, gb.V_pad)
    return _word_count_from_weights(w, gb.tw_rule, gb.tw_word, gb.tw_cnt,
                                    gb.V_pad)


def batched_sort_words(gb: GrammarBatch, method: str = "frontier",
                       backend: str = "jnp"
                       ) -> List[Tuple[jnp.ndarray, jnp.ndarray]]:
    """Per corpus (word_ids, counts) sorted by frequency desc — the heavy
    reduction is batched; the final per-corpus argsort runs on true sizes so
    results match :func:`repro.core.analytics.sort_words` exactly."""
    wc = batched_word_count(gb, method=method, backend=backend)
    out = []
    for i, ga in enumerate(gb.real_gas):
        counts = wc[i, : ga.vocab_size]
        order = jnp.argsort(-counts, stable=True)
        out.append((order, counts[order]))
    return out


@functools.partial(jax.jit, static_argnames=("V",))
@_device_scope("analytics")
def _term_vector_from_weights(Wf, tw_rule, tw_word, tw_cnt,
                              fword_file, fword_word, fword_cnt, V):
    def one(Wf, tr, twd, tc, ff, fw, fc):
        contrib = Wf[tr, :] * tc[:, None]                       # [T, F]
        tv = jax.ops.segment_sum(contrib, twd, num_segments=V)  # [V, F]
        tv = tv.T
        return tv.at[ff, fw].add(fc)

    return jax.vmap(one)(Wf, tw_rule, tw_word, tw_cnt,
                         fword_file, fword_word, fword_cnt)


def batched_term_vector(gb: GrammarBatch,
                        method: str = "frontier") -> jnp.ndarray:
    """tv[i, f, v] — dense per-file counts, all corpora in one call."""
    Wf = batched_per_file_weights(gb, method=method)
    return _term_vector_from_weights(
        Wf, gb.tw_rule, gb.tw_word, gb.tw_cnt,
        gb.fword_file, gb.fword_word, gb.fword_cnt, gb.V_pad)


def batched_inverted_index(gb: GrammarBatch,
                           method: str = "frontier") -> jnp.ndarray:
    return batched_term_vector(gb, method=method) > 0


def batched_ranked_inverted_index(gb: GrammarBatch, method: str = "frontier"
                                  ) -> List[Tuple[jnp.ndarray, jnp.ndarray]]:
    """Per corpus (ranking [V, F], counts [V, F]) — batched traversal, true
    per-corpus shapes out (matches the single-corpus function exactly)."""
    tv = batched_term_vector(gb, method=method)
    out = []
    for i, ga in enumerate(gb.real_gas):
        tvi = tv[i, : ga.num_files, : ga.vocab_size]
        order = jnp.argsort(-tvi, axis=0, stable=True)
        ranked = jnp.take_along_axis(tvi, order, axis=0)
        out.append((order.T, ranked.T))
    return out


def unbatch(gb: GrammarBatch, packed: jnp.ndarray,
            kind: str = "word_count") -> List[np.ndarray]:
    """Slice a packed ``[N, ...]`` result back to per-corpus true shapes
    (shard-padding rows, if any, are dropped)."""
    out = []
    for i, ga in enumerate(gb.real_gas):
        if kind == "word_count":
            part = packed[i, : ga.vocab_size]
        elif kind in ("term_vector", "inverted_index"):
            part = packed[i, : ga.num_files, : ga.vocab_size]
        else:
            raise ValueError(f"cannot unbatch kind {kind!r}")
        with _readback():
            out.append(np.asarray(part))
    return out


# ----------------------------------------------------------------------- #
# Batched sequence count (paper §IV-D across corpora)                      #
# ----------------------------------------------------------------------- #
@jax.jit
@_device_scope("analytics")
def _resolve_buffers_batched(is_lit, lit, src, idx, dep):
    R = is_lit.shape[1]

    def one(is_lit, lit, src, idx, dep):
        leaf = (dep < 0).all(axis=1)
        buf0 = jnp.where(is_lit, lit, -1)

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

        buf, _, _ = jax.lax.while_loop(
            cond, body, (buf0, leaf, jnp.zeros(R, bool)))
        return buf

    return jax.vmap(one)(is_lit, lit, src, idx, dep)


@functools.partial(jax.jit, static_argnames=("l",))
@_device_scope("analytics")
def _count_windows_batched(head, tail, weights, st_kind, st_lit, st_src,
                           st_idx, st_symj, win_start, win_rule, win_valid,
                           l):
    def one(head, tail, w, kind, lit, src, idx, symj, ws, wr, wv):
        tok = jnp.where(kind == _K_LIT, lit,
                        jnp.where(kind == _K_HEAD, head[src, idx],
                                  jnp.where(kind == _K_TAIL,
                                            tail[src, idx], lit)))
        pos = ws[:, None] + jnp.arange(l)[None, :]
        wtok = tok[pos]                                   # [Nw, l]
        wsym = symj[pos]
        valid = (wtok >= 0).all(axis=1) & (wsym[:, 0] != wsym[:, -1]) & wv
        wweight = jnp.where(valid, w[wr], 0.0)
        order = jnp.lexsort(tuple(wtok[:, c] for c in range(l - 1, -1, -1)))
        stok = wtok[order]
        sw = wweight[order]
        newseg = jnp.concatenate([
            jnp.array([True]),
            (stok[1:] != stok[:-1]).any(axis=1)])
        seg = jnp.cumsum(newseg) - 1
        counts = jax.ops.segment_sum(sw, seg, num_segments=stok.shape[0])
        return stok, seg, counts

    return jax.vmap(one)(head, tail, weights, st_kind, st_lit, st_src,
                         st_idx, st_symj, win_start, win_rule, win_valid)


def _padded_sequence_plans(gb: GrammarBatch, l: int):
    """Host-side planning + padding + resolved head/tail buffers, memoized
    per (batch, l): the serving layer reuses packed batches across query
    groups, so repeat sequence_count traffic pays the planning once."""
    if l in gb._plan_cache:
        return gb._plan_cache[l]
    with _plan_stage("sequence"):
        gb._plan_cache[l] = _build_sequence_plans(gb, l)
    return gb._plan_cache[l]


def _build_sequence_plans(gb: GrammarBatch, l: int):
    N = gb.n
    h = l - 1
    htps = [_sequence.plan_head_tail(ga, l) for ga in gb.gas]
    sps = [_sequence.plan_stream(ga, l) for ga in gb.gas]

    R_pad = gb.R_pad
    # bucket the data-dependent plan widths like the pack dims: packs with
    # equal signatures then reuse the jitted resolve/count programs across
    # corpus compositions instead of compiling per exact max-width
    Kd = _round_up_pow2(
        max(max(p.head_dep.shape[1], p.tail_dep.shape[1]) for p in htps), 1)

    def _stack_plan(get_arr, fill, dtype, width2):
        out = np.full((N, R_pad, width2), fill, dtype)
        for i, p in enumerate(htps):
            a = get_arr(p)
            out[i, : a.shape[0], : a.shape[1]] = a
        return gb._place(out)

    def _resolve(side: str) -> jnp.ndarray:
        return _resolve_buffers_batched(
            _stack_plan(lambda p: getattr(p, f"{side}_is_lit"), False, bool, h),
            _stack_plan(lambda p: getattr(p, f"{side}_lit"), -1, np.int32, h),
            _stack_plan(lambda p: getattr(p, f"{side}_src"), 0, np.int32, h),
            _stack_plan(lambda p: getattr(p, f"{side}_idx"), 0, np.int32, h),
            _stack_plan(lambda p: getattr(p, f"{side}_dep"), -1, np.int32, Kd))

    head = _resolve("head")
    tail = _resolve("tail")

    S_pad = _round_up_pow2(max(max(len(p.st_kind) for p in sps), l), 1)
    W_pad = _round_up_pow2(max(max(len(p.win_start) for p in sps), 1), 1)
    win_valid = np.zeros((N, W_pad), bool)
    for i, p in enumerate(sps):
        win_valid[i, : len(p.win_start)] = True
    stream = (
        gb._place(_pad_stack([p.st_kind for p in sps], S_pad,
                             fill=_sequence._K_BREAK, dtype=np.int8)),
        gb._place(_pad_stack([p.st_lit for p in sps], S_pad,
                             fill=_sequence._BREAK)),
        gb._place(_pad_stack([p.st_src for p in sps], S_pad)),
        gb._place(_pad_stack([p.st_idx for p in sps], S_pad)),
        gb._place(_pad_stack([p.st_symj for p in sps], S_pad)),
        gb._place(_pad_stack([p.win_start for p in sps], W_pad)),
        gb._place(_pad_stack([p.win_rule for p in sps], W_pad)),
        gb._place(win_valid))
    return (head, tail, stream)


def batched_sequence_count(gb: GrammarBatch, l: int = 3,
                           method: str = "frontier"
                           ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Per corpus (grams [U, l], counts [U]) — head/tail resolution, stream
    gathers, window sorting and segment reduction all run batched; only the
    final distinct-gram extraction is per corpus (ragged output)."""
    if l < 2:
        raise ValueError("sequence_count needs l >= 2")
    weights = batched_top_down_weights(gb, method=method)
    head, tail, stream = _padded_sequence_plans(gb, l)
    stok, seg, counts = _count_windows_batched(head, tail, weights,
                                               *stream, l)

    with _readback():
        stok_h = np.asarray(stok)
        seg_h = np.asarray(seg)
        counts_h = np.asarray(counts)
    out: List[Tuple[np.ndarray, np.ndarray]] = []
    for i in range(gb.real):
        n_seg = int(seg_h[i, -1]) + 1
        first_idx = np.searchsorted(seg_h[i], np.arange(n_seg), "left")
        grams = stok_h[i][first_idx]
        cnts = counts_h[i, :n_seg]
        keep = cnts > 0           # padded / invalid windows carry zero weight
        out.append((grams[keep].astype(np.int32), cnts[keep]))
    return out


# ----------------------------------------------------------------------- #
# Convenience: run any of the six analytics batched, per-corpus results    #
# ----------------------------------------------------------------------- #
ANALYTICS_KINDS = ("word_count", "sort", "inverted_index", "term_vector",
                   "sequence_count", "ranked_inverted_index")


def run_batched(gb: GrammarBatch, kind: str, method: str = "frontier",
                backend: str = "jnp", l: int = 3) -> List:
    """Dispatch one analytics kind over the whole batch; returns a list of
    per-corpus results shaped exactly like the single-corpus functions."""
    if kind == "word_count":
        return unbatch(gb, batched_word_count(gb, method=method,
                                              backend=backend), "word_count")
    if kind == "sort":
        pairs = batched_sort_words(gb, method=method, backend=backend)
        with _readback():
            return [(np.asarray(o), np.asarray(c)) for o, c in pairs]
    if kind == "term_vector":
        return unbatch(gb, batched_term_vector(gb, method=method),
                       "term_vector")
    if kind == "inverted_index":
        return unbatch(gb, batched_inverted_index(gb, method=method),
                       "inverted_index")
    if kind == "ranked_inverted_index":
        pairs = batched_ranked_inverted_index(gb, method=method)
        with _readback():
            return [(np.asarray(r), np.asarray(c)) for r, c in pairs]
    if kind == "sequence_count":
        return batched_sequence_count(gb, l=l, method=method)
    raise ValueError(f"unknown analytics kind {kind!r}; "
                     f"expected one of {ANALYTICS_KINDS}")
