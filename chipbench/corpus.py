"""Deployments: corpora generated from the seed and compressed in a pool.

The generator below is a copy of ``repro.data.synthetic`` (``CorpusSpec``,
``zipf_words``, ``revision_history``, ``make_corpus``) as it stood when the
benchmark was defined, so that a change to the program cannot move the data
under the yardstick.  ``tests/chipbench/test_chipbench_copies.py`` checks
that the copy and the original still agree.

Corpora are compressed by the program's own ``CompressedCorpus.build`` in a
spawn pool whose workers see ``JAX_PLATFORMS=cpu``: only the parent process
may hold the chip.
"""

from __future__ import annotations

import contextlib
import multiprocessing
import os
import time
from dataclasses import dataclass
from typing import Dict, List

import numpy as np

#: Upper bound on the processes that compress corpora.
BUILD_WORKERS = 16


# ------------------------------------------------ copy of data/synthetic --
@dataclass(frozen=True)
class CorpusSpec:
    name: str
    n_files: int
    tokens_per_file: int
    vocab: int
    phrase_rate: float        # fraction of text drawn from repeated phrases
    n_phrases: int
    phrase_len: int
    seed: int = 0
    # fraction of draws that emit one article's revision history: the
    # article, then ``revisions - 1`` revisions, each the previous one with
    # an edit appended.  Each revision nests the one before, so the rule
    # DAG is about ``revisions`` deep.
    history_rate: float = 0.0
    revisions: int = 0
    # zipf draws beyond the vocabulary are redrawn (True) or clipped onto
    # the last word id (False)
    redraw_tail: bool = False


def zipf_words(rng: np.random.Generator, n: int, vocab: int,
               a: float = 1.3, redraw_tail: bool = False) -> np.ndarray:
    """Zipf-distributed word ids, clipped to the vocab or, with
    ``redraw_tail``, drawn again until they fall inside it."""
    w = rng.zipf(a, size=n) - 1
    if not redraw_tail:
        return np.minimum(w, vocab - 1).astype(np.int64)
    out = w >= vocab
    while out.any():
        w[out] = rng.zipf(a, size=int(out.sum())) - 1
        out = w >= vocab
    return w.astype(np.int64)


def revision_history(rng: np.random.Generator, revisions: int, vocab: int,
                     redraw_tail: bool = False) -> np.ndarray:
    """One article followed by its later revisions, each the previous
    revision with a short edit appended."""
    text = zipf_words(rng, int(rng.integers(20, 60)), vocab,
                      redraw_tail=redraw_tail)
    out = [text]
    for _ in range(revisions - 1):
        edit = zipf_words(rng, int(rng.integers(3, 15)), vocab,
                          redraw_tail=redraw_tail)
        text = np.concatenate([text, edit])
        out.append(text)
    return np.concatenate(out)


def make_corpus(spec: CorpusSpec) -> List[np.ndarray]:
    rng = np.random.default_rng(spec.seed)
    tail = spec.redraw_tail
    phrases = [zipf_words(rng, spec.phrase_len, spec.vocab, redraw_tail=tail)
               for _ in range(spec.n_phrases)]
    files: List[np.ndarray] = []
    for _ in range(spec.n_files):
        parts: List[np.ndarray] = []
        total = 0
        while total < spec.tokens_per_file:
            r = rng.random()
            if r < spec.history_rate:
                p = revision_history(rng, spec.revisions, spec.vocab, tail)
            elif r < spec.history_rate + spec.phrase_rate:
                p = phrases[int(rng.integers(spec.n_phrases))]
                # occasionally a multi-phrase motif (nested repetition)
                if rng.random() < 0.3:
                    p = np.concatenate(
                        [p, phrases[int(rng.integers(spec.n_phrases))]])
            else:
                p = zipf_words(rng, int(rng.integers(3, 15)), spec.vocab,
                               redraw_tail=tail)
            parts.append(p)
            total += len(p)
        files.append(np.concatenate(parts)[: spec.tokens_per_file])
    return files


# ------------------------------------------------------------ deployment --
@dataclass
class Deployment:
    """A configuration's corpora: raw token files (the reference reads
    these), query popularity, and the program's compressed stores."""
    vocab: int
    files: Dict[str, List[np.ndarray]]
    popularity: np.ndarray                  # per corpus, in ``files`` order
    stores: Dict[str, object]
    seconds: Dict[str, float]               # generation / build wall time


def corpus_specs(cfg: dict, seed: int) -> List[CorpusSpec]:
    """One spec per corpus.  Every seed gets the same set of corpus sizes
    (file counts spread evenly over the configured range), in an order
    drawn from the seed, so that the seed changes the text and which
    corpora are popular but not the amount of work.  A configuration with
    a ``text_seed`` takes the specs from that seed instead, the same for
    every run; the run's seed then only relabels the words
    (``relabel``)."""
    rng = np.random.default_rng([cfg.get("text_seed", seed) % 2 ** 63, 0xC0])
    lo, hi = cfg["files_per_corpus"]
    n = cfg["corpora"]
    sizes = np.rint(np.linspace(lo, hi, n)).astype(int)
    return [CorpusSpec(f"c{i}", n_files=int(f),
                       tokens_per_file=cfg["tokens_per_file"],
                       vocab=cfg["vocab"], phrase_rate=cfg["phrase_rate"],
                       n_phrases=cfg["n_phrases"],
                       phrase_len=cfg["phrase_len"],
                       seed=int(rng.integers(2 ** 63)),
                       history_rate=cfg["history_rate"],
                       revisions=cfg["revisions"], redraw_tail=True)
            for i, f in enumerate(rng.permutation(sizes))]


def popularity(n: int, zipf_s: float) -> np.ndarray:
    """Rank-zipf query popularity over ``n`` corpora (uniform at s = 0)."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** zipf_s
    return w / w.sum()


def relabel(vocab: int, seed: int) -> np.ndarray:
    """The seed's one-to-one map of word ids onto word ids.  Text relabeled
    by it has the same repeats, so the same grammar shape (rule count, DAG
    depth) and the same work, with other words in every answer."""
    return np.random.default_rng([seed % 2 ** 63, 0x1AB]).permutation(vocab)


def _build_one(job):
    """Pool worker: generate one corpus, relabel its words where the
    configuration fixes the text (``job = (spec, seed or None)``), and
    compress it (no JAX backend is started here)."""
    from repro.data.store import CompressedCorpus

    spec, relabel_seed = job
    t0 = time.perf_counter()
    files = make_corpus(spec)
    if relabel_seed is not None:
        words = relabel(spec.vocab, relabel_seed)
        files = [words[f] for f in files]
    t1 = time.perf_counter()
    cc = CompressedCorpus.build(files, spec.vocab)
    return (spec.name, files, cc.ga, cc.file_starts, cc.file_lens,
            t1 - t0, time.perf_counter() - t1)


@contextlib.contextmanager
def _env(**values):
    old = {k: os.environ.get(k) for k in values}
    os.environ.update(values)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def build(cfg: dict, seed: int, workers: int = BUILD_WORKERS) -> Deployment:
    """Generate and compress every corpus of ``cfg`` in a spawn pool; the
    pool's workers see ``JAX_PLATFORMS=cpu``.  ``workers=0`` builds in this
    process instead (small test deployments)."""
    from repro.data.store import CompressedCorpus

    specs = corpus_specs(cfg, seed)
    relabel_seed = seed if "text_seed" in cfg else None
    # largest first, so the long builds start before the pool drains
    jobs = [(s, relabel_seed) for s in
            sorted(specs, key=lambda s: -s.n_files * s.tokens_per_file)]
    n = max(0, min(workers, os.cpu_count() or 1, len(jobs)))
    t0 = time.perf_counter()
    if n:
        with _env(JAX_PLATFORMS="cpu"):
            pool = multiprocessing.get_context("spawn").Pool(n)
        with pool:
            built = {r[0]: r for r in pool.imap_unordered(_build_one, jobs)}
    else:
        built = {r[0]: r for r in map(_build_one, jobs)}
    wall = time.perf_counter() - t0
    names = [s.name for s in specs]
    stores = {c: CompressedCorpus(ga=built[c][2], file_starts=built[c][3],
                                  file_lens=built[c][4]) for c in names}
    gen = sum(built[c][5] for c in names)
    comp = sum(built[c][6] for c in names)
    return Deployment(
        vocab=cfg["vocab"], files={c: built[c][1] for c in names},
        popularity=popularity(len(names), cfg["popularity_zipf_s"]),
        stores=stores,
        # the pool's wall time, split by the workers' share of each part
        seconds={"generate": wall * gen / max(gen + comp, 1e-9),
                 "sequitur": wall * comp / max(gen + comp, 1e-9),
                 "workers": n})
