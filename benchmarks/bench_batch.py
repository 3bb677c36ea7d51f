"""Batched multi-corpus engine vs sequential per-corpus loop.

Emits ``batch/<app>/<mode>`` rows (us per full sweep over the batch) plus a
``batch/<app>/speedup`` row.  The sequential mode is the pre-batching
serving story: one jitted call per corpus (each with its own shapes, its
own dispatch).  The batched mode packs all corpora into one
:class:`GrammarBatch` and runs ONE program.  Steady-state timing (both
modes fully warmed/compiled before measurement).

Also emits ``batch/traversal/{segment_sum,ell,ell_speedup}``: the batched
frontier rounds on the COO segment_sum path vs the dense ELL edge plan
(scatter-free gather form — core/batch.py DESIGN note); and
``batch/traversal/{fused,fused_speedup}``: the fused multi-round traversal
(kernels/propagate_fused.py — the whole frontier loop in ONE dispatch)
against the per-round while_loop ELL path it replaces.  The fused floor
(docs/benchmarks.md) binds on this row: one dispatch must never lose to
num_levels dispatches.

``autotune/*`` rows run the kernels/autotune.py block-size sweeps on the
pack's real ELL plan (the jnp reference form is itself a candidate, so the
sweep also answers ref-vs-kernel routing), record each kind's winner and
its winner-vs-default ratio, and seed an ``ell_vs_seg`` routing entry from
the segment_sum/ELL timings above.  The tuned table lives in the process
and in BENCH_batch.json's ``autotune`` field; no file is written for it.

``search/<scheme>/{sequential,batched,speedup}`` rows time compressed
BM25/TF-IDF top-k ranking (repro/search): one jitted per-corpus scoring
call per corpus (prebuilt SearchIndex each) vs ONE batched program over
the whole pack — the retrieval analogue of the batched-vs-sequential
analytics story (index builds excluded; both sides warmed).

``query/<op>/{sequential,batched,speedup}`` rows time the composable
query operators (repro/query): an AND/OR predicate filter, a term-set
sum aggregation and a sequence-plan phrase count, each as one
single-corpus engine call per corpus vs ONE jitted program over the
pack (whose per-file stats and sequence plans are memoized on the pack,
like recurring serving traffic).

``shard/*`` rows time the device-sharded pack (distributed/shard_batch.py)
against the single-device pack on the same corpora: ``shard/<app>/single``
vs ``shard/<app>/sharded`` plus a ``speedup`` row, and the ``devices``
field records how many devices the mesh actually spanned (1 = no mesh
visible, rows then measure the transparent fallback and speedup ~1).  Run
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to exercise a
real 8-way mesh on CPU — CI's multidevice lane does.

``ingest/*`` rows time the streaming ingestion tier (data/store.py
``append_files``): appending a tail of files onto an existing corpus's
live Sequitur state vs recompressing the whole concatenated file list
from scratch.  ``ingest/append`` and ``ingest/rebuild`` are seconds per
ingest of the same tail, ``ingest/speedup`` is rebuild/append (the floor
in docs/benchmarks.md binds on it: incremental must beat recompression),
and ``ingest/append_tokens_per_s`` is the tail-token throughput of the
incremental path.  Append mutates the corpus, so each repetition clones a
fresh base corpus outside the timed region.

``run`` returns the full timing dict; ``benchmarks.run`` serializes it to
BENCH_batch.json so CI tracks the perf trajectory across PRs.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (GrammarArrays, GrammarBatch, batched_term_vector,
                        batched_top_down_weights, batched_word_count,
                        compress_files, flatten, term_vector, word_count)
from repro.distributed.shard_batch import corpus_mesh, mesh_size, shard_batch
from repro.query import (agg_corpus, batched_agg, batched_filter,
                         batched_phrase, filter_corpus, phrase_corpus)
from repro.search import (batched_search, build_search_index,
                          search_index_topk)

from repro.data import CompressedCorpus

from .common import emit, timeit


def make_ragged_corpora(n: int, seed: int = 7) -> List[GrammarArrays]:
    """n small corpora of deliberately different R/V/F (ragged batch)."""
    rng = np.random.default_rng(seed)
    gas = []
    for i in range(n):
        vocab = int(rng.integers(40, 400))
        n_files = int(rng.integers(1, 7))
        size = int(rng.integers(150, 900))
        phrase = rng.integers(0, vocab, int(rng.integers(4, 9)))
        files = []
        for _ in range(n_files):
            parts, total = [], 0
            while total < size:
                p = (phrase if rng.random() < 0.5
                     else rng.integers(0, vocab, int(rng.integers(3, 12))))
                parts.append(p)
                total += len(p)
            files.append(np.concatenate(parts)[:size])
        g, nf = compress_files(files, vocab)
        gas.append(flatten(g, vocab, nf))
    return gas


def _autotune_rows(gb: GrammarBatch, n: int, t_seg: float, t_ell: float,
                   smoke: bool) -> dict:
    """Run the block-size sweeps on the pack's real ELL plan and emit
    ``autotune/<kind>/{winner,winner_speedup}`` rows.

    Candidate grids shrink at smoke scale (every candidate is a fresh
    compile; the smoke lane only checks the harness executes end to end).
    The ``ell_vs_seg`` routing entry is seeded from the segment_sum/ELL
    timings the traversal section already measured — both engine paths
    actually timed on this machine."""
    from repro.kernels import autotune

    src, freq, _, num_levels = gb.ell_plan()
    in_deg = gb.in_deg
    w0 = jnp.zeros(in_deg.shape, jnp.float32).at[:, 0].set(1.0)
    active0 = (in_deg == 0).astype(jnp.float32)
    Wv = jnp.zeros((*in_deg.shape, gb.F_pad),
                   jnp.float32).at[:, 0, 0].set(1.0)
    if smoke:
        kw = dict(repeat=1, warmup=0)
        entries = {
            "ell_batched": autotune.tune_ell_batched(
                w0, active0, src, freq, brs=(128,), wcs=(1 << 16,), **kw),
            "ell_fused": autotune.tune_ell_fused(
                w0, in_deg.astype(jnp.float32), src, freq, num_levels,
                brs=(128,), **kw),
            "ell_vector": autotune.tune_ell_vector(
                Wv, active0, src, freq, brs=(32,), fcs=(64,), **kw),
        }
    else:
        entries = {
            "ell_batched": autotune.tune_ell_batched(w0, active0, src, freq),
            "ell_fused": autotune.tune_ell_fused(
                w0, in_deg.astype(jnp.float32), src, freq, num_levels),
            "ell_vector": autotune.tune_ell_vector(Wv, active0, src, freq),
        }
    autotune.put_entry(
        "ell_vs_seg",
        autotune.shape_bucket(n, gb.R_pad, gb.ell_plan_width()),
        {"use_ref": bool(t_ell > t_seg), "us": min(t_seg, t_ell) * 1e6,
         "default_us": t_seg * 1e6})
    out = {"kinds": {}}
    for kind, e in entries.items():
        ratio = e["default_us"] / max(e["us"], 1e-9)
        emit(f"autotune/{kind}/winner", e["us"] / 1e6, e["winner"])
        emit(f"autotune/{kind}/winner_speedup", 0.0, f"{ratio:.2f}x")
        out["kinds"][kind] = {
            "winner": e["winner"], "winner_us": e["us"],
            "default_us": e["default_us"], "winner_vs_default": ratio}
    return out


def _ingest_rows(smoke: bool) -> dict:
    """Time the streaming ingestion tier: incremental ``append_files`` of a
    tail onto an existing corpus vs recompressing the concatenation from
    scratch.  Appending mutates the corpus, so a fresh base is built
    (untimed) for every timed repetition; the base's compressor state is
    live, so the append measures exactly the marginal Sequitur work plus
    one re-export — the cost an online ingest pipeline actually pays."""
    rng = np.random.default_rng(23)
    vocab = 120
    n_base, n_tail = (4, 2) if smoke else (16, 4)
    phrase = rng.integers(0, vocab, 8)

    def mk_file(size: int) -> np.ndarray:
        parts, total = [], 0
        while total < size:
            p = (phrase if rng.random() < 0.5
                 else rng.integers(0, vocab, int(rng.integers(3, 12))))
            parts.append(p)
            total += len(p)
        return np.concatenate(parts)[:size]

    base = [mk_file(400) for _ in range(n_base)]
    tail = [mk_file(400) for _ in range(n_tail)]
    repeat, warmup = (2, 1) if smoke else (5, 1)

    fresh = iter([CompressedCorpus.build(base, vocab)
                  for _ in range(repeat + warmup)])
    t_append = timeit(lambda: next(fresh).append_files(tail),
                      repeat=repeat, warmup=warmup)
    t_rebuild = timeit(lambda: CompressedCorpus.build(base + tail, vocab),
                       repeat=repeat, warmup=warmup)
    speedup = t_rebuild / max(t_append, 1e-12)
    tail_tokens = int(sum(len(f) for f in tail))
    tok_per_s = tail_tokens / max(t_append, 1e-12)
    emit("ingest/append", t_append, f"base={n_base};tail={n_tail}")
    emit("ingest/rebuild", t_rebuild, f"files={n_base + n_tail}")
    emit("ingest/speedup", 0.0, f"{speedup:.2f}x")
    emit("ingest/append_tokens_per_s", 0.0, f"{tok_per_s:.0f}")
    return {"base_files": n_base, "tail_files": n_tail,
            "tail_tokens": tail_tokens,
            "append_us": t_append * 1e6, "rebuild_us": t_rebuild * 1e6,
            "speedup": speedup, "append_tokens_per_s": tok_per_s}


def run(smoke: bool = False) -> dict:
    n = 4 if smoke else 16
    gas = make_ragged_corpora(n)
    gb = GrammarBatch.build(gas)

    def seq_word_count():
        for ga in gas:
            jax.block_until_ready(word_count(ga, method="frontier"))

    def bat_word_count():
        jax.block_until_ready(batched_word_count(gb))

    def seq_term_vector():
        for ga in gas:
            jax.block_until_ready(term_vector(ga, method="frontier"))

    def bat_term_vector():
        jax.block_until_ready(batched_term_vector(gb))

    out = {"n": n, "batched_vs_sequential": {}, "ell_vs_segment_sum": {}}
    for app, seq, bat in (("word_count", seq_word_count, bat_word_count),
                          ("term_vector", seq_term_vector, bat_term_vector)):
        t_seq = timeit(seq, repeat=3, warmup=1)
        t_bat = timeit(bat, repeat=3, warmup=1)
        speedup = t_seq / max(t_bat, 1e-12)
        emit(f"batch/{app}/sequential", t_seq, f"n={n}")
        emit(f"batch/{app}/batched", t_bat, f"n={n}")
        emit(f"batch/{app}/speedup", 0.0, f"{speedup:.2f}x")
        out["batched_vs_sequential"][app] = {
            "sequential_us": t_seq * 1e6, "batched_us": t_bat * 1e6,
            "speedup": speedup}

    def trav_seg():
        jax.block_until_ready(batched_top_down_weights(gb, method="frontier"))

    def trav_ell():
        jax.block_until_ready(
            batched_top_down_weights(gb, method="frontier_ell"))

    def trav_fused():
        jax.block_until_ready(
            batched_top_down_weights(gb, method="frontier_fused"))

    t_seg = timeit(trav_seg, repeat=3, warmup=1)
    t_ell = timeit(trav_ell, repeat=3, warmup=1)
    t_fused = timeit(trav_fused, repeat=5, warmup=2)
    ell_speedup = t_seg / max(t_ell, 1e-12)
    fused_speedup = t_ell / max(t_fused, 1e-12)
    emit("batch/traversal/segment_sum", t_seg, f"n={n}")
    emit("batch/traversal/ell", t_ell, f"n={n}")
    emit("batch/traversal/ell_speedup", 0.0, f"{ell_speedup:.2f}x")
    emit("batch/traversal/fused", t_fused, f"n={n}")
    emit("batch/traversal/fused_speedup", 0.0, f"{fused_speedup:.2f}x")
    out["ell_vs_segment_sum"] = {
        "segment_sum_us": t_seg * 1e6, "ell_us": t_ell * 1e6,
        "speedup": ell_speedup}
    out["traversal_fused"] = {
        "ell_us": t_ell * 1e6, "fused_us": t_fused * 1e6,
        "speedup": fused_speedup,
        "vs_segment_sum": t_seg / max(t_fused, 1e-12)}

    out["autotune"] = _autotune_rows(gb, n, t_seg, t_ell, smoke)

    # ----- compressed search: batched vs per-corpus sequential ranking ---
    # sequential = the pre-batching retrieval story: one jitted scoring
    # call per corpus against its (prebuilt, memoized) SearchIndex;
    # batched = one program ranking every corpus in the pack (pack-level
    # statistics memoized, like recurring serving traffic).  Index builds
    # are excluded from both sides — this times the ranking hot path.
    terms = tuple(int(t) for t in
                  np.random.default_rng(11).integers(0, 40, 8))
    indexes = [build_search_index(ga) for ga in gas]
    out["search"] = {"n": n, "terms": len(terms), "schemes": {}}
    for scheme in ("bm25", "tfidf"):
        def seq_search(scheme=scheme):
            for si in indexes:
                search_index_topk(si, terms, k=10, scheme=scheme)

        def bat_search(scheme=scheme):
            batched_search(gb, terms, k=10, scheme=scheme)

        t_seq = timeit(seq_search, repeat=3, warmup=1)
        t_bat = timeit(bat_search, repeat=3, warmup=1)
        s_speedup = t_seq / max(t_bat, 1e-12)
        emit(f"search/{scheme}/sequential", t_seq, f"n={n}")
        emit(f"search/{scheme}/batched", t_bat, f"n={n}")
        emit(f"search/{scheme}/speedup", 0.0, f"{s_speedup:.2f}x")
        out["search"]["schemes"][scheme] = {
            "sequential_us": t_seq * 1e6, "batched_us": t_bat * 1e6,
            "speedup": s_speedup}

    # ----- query operators: batched vs per-corpus sequential -------------
    # sequential = the pre-batching story again: one single-corpus engine
    # call per corpus, each re-traversing for its own stats; batched = ONE
    # jitted program over the pack, whose per-file stats / sequence plans
    # are memoized on the pack like recurring serving traffic.
    qrng = np.random.default_rng(13)
    pred = ("or", (("and", (("term", 3, 1), ("term", 7, 2))),
                   ("term", 11, 1)))
    agg_terms = tuple(int(t) for t in qrng.integers(0, 40, 6))
    phrase = tuple(int(t) for t in qrng.integers(0, 40, 3))
    out["query"] = {"n": n, "ops": {}}
    for op, seq_fn, bat_fn in (
            ("filter",
             lambda: [filter_corpus(ga, pred) for ga in gas],
             lambda: batched_filter(gb, pred)),
            ("agg",
             lambda: [agg_corpus(ga, agg_terms, "sum") for ga in gas],
             lambda: batched_agg(gb, agg_terms, "sum")),
            ("phrase",
             lambda: [phrase_corpus(ga, phrase) for ga in gas],
             lambda: batched_phrase(gb, phrase))):
        t_seq = timeit(seq_fn, repeat=3, warmup=1)
        t_bat = timeit(bat_fn, repeat=3, warmup=1)
        q_speedup = t_seq / max(t_bat, 1e-12)
        emit(f"query/{op}/sequential", t_seq, f"n={n}")
        emit(f"query/{op}/batched", t_bat, f"n={n}")
        emit(f"query/{op}/speedup", 0.0, f"{q_speedup:.2f}x")
        out["query"]["ops"][op] = {
            "sequential_us": t_seq * 1e6, "batched_us": t_bat * 1e6,
            "speedup": q_speedup}

    # ----- device-sharded pack vs single-device pack (same corpora) -----
    mesh = corpus_mesh()
    devices = mesh_size(mesh)
    gb_sh = shard_batch(gas, mesh)      # == gb placement when mesh is None
    out["sharded"] = {"devices": devices, "n": n, "apps": {}}
    for app, one_fn, sh_fn in (
            ("word_count",
             lambda: jax.block_until_ready(batched_word_count(gb)),
             lambda: jax.block_until_ready(batched_word_count(gb_sh))),
            ("traversal",
             lambda: jax.block_until_ready(
                 batched_top_down_weights(gb, method="frontier")),
             lambda: jax.block_until_ready(
                 batched_top_down_weights(gb_sh, method="frontier"))),
            ("term_vector",
             lambda: jax.block_until_ready(batched_term_vector(gb)),
             lambda: jax.block_until_ready(batched_term_vector(gb_sh)))):
        t_one = timeit(one_fn, repeat=3, warmup=1)
        t_sh = timeit(sh_fn, repeat=3, warmup=1)
        sh_speedup = t_one / max(t_sh, 1e-12)
        emit(f"shard/{app}/single", t_one, f"n={n}")
        emit(f"shard/{app}/sharded", t_sh, f"n={n};devices={devices}")
        emit(f"shard/{app}/speedup", 0.0, f"{sh_speedup:.2f}x")
        out["sharded"]["apps"][app] = {
            "single_us": t_one * 1e6, "sharded_us": t_sh * 1e6,
            "speedup": sh_speedup}

    out["ingest"] = _ingest_rows(smoke)
    return out


if __name__ == "__main__":
    import sys
    run(smoke="--smoke" in sys.argv)
