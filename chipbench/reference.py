"""The plain reference: decompress-then-scan answers from the raw files.

A copy of ``tests/_oracle.py`` as it stood when the benchmark was defined,
cut loose from the program: it reads the raw token files the generator
made, never a grammar, weights or tables the program built.  The stream is
each file followed by its own splitter symbol ``vocab + i``, as the
program defines it.  Functions that scan the per-file term vector take it
precomputed (``tv=``), so one corpus's many answers share one scan.
``tests/chipbench/test_chipbench_copies.py`` checks that the copy and the
original still agree.

:func:`digest` reduces an answer to a hash of its values, so the harness
compares every answer of a window without holding the dense ones in host
memory.
"""

from __future__ import annotations

import hashlib
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np


class Meta(NamedTuple):
    """What the reference needs to know of a corpus besides its stream."""
    vocab_size: int
    num_files: int

    @property
    def num_terminals(self) -> int:
        return self.vocab_size + self.num_files


def raw_stream(files: Sequence[np.ndarray], vocab: int) -> np.ndarray:
    """The terminal stream: each file followed by its splitter."""
    parts = []
    for i, f in enumerate(files):
        parts += [np.asarray(f, np.int64), np.array([vocab + i], np.int64)]
    return np.concatenate(parts)


def stream_segments(ga: Meta, stream: np.ndarray) -> List[np.ndarray]:
    """Word segments between file splitters, in stream order.

    Segment i (for i < F) is terminated by file i's splitter; a trailing
    segment (no terminator) may follow and belongs to the last file.
    """
    is_split = (stream >= ga.vocab_size) & (stream < ga.num_terminals)
    cuts = np.flatnonzero(is_split)
    bounds = np.concatenate([[-1], cuts, [len(stream)]])
    segs = [stream[bounds[i] + 1: bounds[i + 1]]
            for i in range(len(bounds) - 1)]
    if len(segs) and len(segs[-1]) == 0 and len(cuts) == ga.num_files:
        segs.pop()                      # empty trailing pseudo-segment
    return segs


def _seg_file(ga: Meta, seg_idx: int) -> int:
    return min(seg_idx, max(ga.num_files - 1, 0))


def oracle_word_count(ga: Meta, stream: np.ndarray) -> np.ndarray:
    words = stream[stream < ga.vocab_size]
    return np.bincount(words, minlength=ga.vocab_size).astype(np.float32)


def oracle_sort(ga: Meta, stream: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray]:
    counts = oracle_word_count(ga, stream)
    order = np.argsort(-counts, kind="stable")
    return order, counts[order]


def oracle_term_vector(ga: Meta, stream: np.ndarray) -> np.ndarray:
    tv = np.zeros((ga.num_files, ga.vocab_size), np.float32)
    for i, seg in enumerate(stream_segments(ga, stream)):
        tv[_seg_file(ga, i)] += np.bincount(seg,
                                            minlength=ga.vocab_size)
    return tv


def oracle_inverted_index(tv: np.ndarray) -> np.ndarray:
    return tv > 0


def oracle_ranked_inverted_index(tv: np.ndarray
                                 ) -> Tuple[np.ndarray, np.ndarray]:
    order = np.argsort(-tv, axis=0, kind="stable")      # [F, V]
    ranked = np.take_along_axis(tv, order, axis=0)
    return order.T, ranked.T


# ------------------------------------------------------------- search --
# float32 constants and expression order mirror the program's scoring op
# for op, so scores and rankings compare bit for bit.
_K1 = np.float32(1.2)
_B = np.float32(0.75)
_ONE = np.float32(1.0)
_HALF = np.float32(0.5)
_K1P1 = _K1 + _ONE


def oracle_search(tv: np.ndarray, terms, k: int = 10, scheme: str = "bm25"
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """BM25 / TF-IDF top-k ranking from the scanned term vector: scoring
    in sequential-term float32, stable argsort for the ranking (ties ->
    lower file id)."""
    F, V = tv.shape
    dl = tv.sum(axis=1, dtype=np.float32)
    df = (tv > 0).sum(axis=0).astype(np.float32)
    n = np.float32(F)
    avgdl = np.float32(dl.sum(dtype=np.float32)) / np.float32(max(F, 1))
    if not avgdl > 0:
        avgdl = _ONE
    norm = (_K1 * (_ONE - _B + _B * (dl / np.float32(avgdl)))).astype(
        np.float32)
    t = np.asarray(terms, np.int64)
    ok = (t >= 0) & (t < V)
    tf_q = np.zeros((F, len(t)), np.float32)
    tf_q[:, ok] = tv[:, t[ok]]
    df_q = np.zeros(len(t), np.float32)
    df_q[ok] = df[t[ok]]
    if scheme == "bm25":
        idf = np.log(_ONE + (n - df_q + _HALF) / (df_q + _HALF)).astype(
            np.float32)
        quot = (tf_q * _K1P1) / (tf_q + norm[:, None])
    elif scheme == "tfidf":
        idf = (np.log((n + _ONE) / (df_q + _ONE)) + _ONE).astype(np.float32)
        quot = tf_q
    else:
        raise ValueError(f"unknown scoring scheme {scheme!r}")
    score = np.zeros(F, np.float32)
    for j in range(len(t)):           # sequential term order, like the engine
        score = score + idf[j] * quot[:, j]
    k_eff = min(int(k), F)
    order = np.argsort(-score, kind="stable")[:k_eff].astype(np.int32)
    return order, score[order]


def oracle_sequence_count(ga: Meta, l: int, stream: np.ndarray
                          ) -> Tuple[np.ndarray, np.ndarray]:
    wins = [np.lib.stride_tricks.sliding_window_view(seg, l)
            for seg in stream_segments(ga, stream) if len(seg) >= l]
    if not wins:
        return np.zeros((0, l), np.int32), np.zeros(0, np.float32)
    grams, counts = np.unique(np.concatenate(wins), axis=0,
                              return_counts=True)
    return grams.astype(np.int32), counts.astype(np.float32)


# ---------------------------------------------------- query operators --
def oracle_filter(tv: np.ndarray, predicate) -> np.ndarray:
    """Ascending int32 file ids satisfying a canonical predicate tree."""
    F, V = tv.shape

    def ev(node):
        if node[0] == "term":
            _, t, c = node
            cnt = tv[:, t] if t < V else np.zeros(F, np.float32)
            return cnt >= np.float32(c)
        masks = [ev(ch) for ch in node[1]]
        out = masks[0]
        for m in masks[1:]:
            out = (out & m) if node[0] == "and" else (out | m)
        return out

    return np.flatnonzero(ev(predicate)).astype(np.int32)


def oracle_agg(tv: np.ndarray, terms, op: str = "sum"
               ) -> Tuple[np.ndarray, np.float32]:
    """(per_file [F] float32, total float32) sum/max of the term set's
    counts, accumulated over term slots in query order."""
    F, V = tv.shape
    pf = np.zeros(F, np.float32)
    for t in terms:
        cnt = tv[:, int(t)] if int(t) < V else np.zeros(F, np.float32)
        pf = pf + cnt if op == "sum" else np.maximum(pf, cnt)
    if op == "sum":
        total = np.float32(pf.sum(dtype=np.float32))
    else:
        total = np.float32(pf.max()) if F else np.float32(0.0)
    return pf, total


def oracle_phrase(ga: Meta, phrase, stream: np.ndarray) -> np.float32:
    """Exact occurrence count of the phrase (windows never cross a
    splitter)."""
    ph = np.asarray(phrase, np.int64)
    l = len(ph)
    count = 0
    for seg in stream_segments(ga, stream):
        if len(seg) >= l:
            wins = np.lib.stride_tricks.sliding_window_view(seg, l)
            count += int((wins == ph[None, :]).all(axis=1).sum())
    return np.float32(count)


#: kinds whose reference reads the per-file term vector
TV_KINDS = ("term_vector", "inverted_index", "ranked_inverted_index",
            "search_bm25", "search_tfidf", "filter_count", "agg_terms")


def answer(ga: Meta, stream: np.ndarray, kind: str, params: dict,
           tv: Optional[np.ndarray] = None):
    """One query's reference answer, shaped like the program's."""
    if kind in TV_KINDS and tv is None:
        tv = oracle_term_vector(ga, stream)
    if kind == "word_count":
        return oracle_word_count(ga, stream)
    if kind == "sort":
        return oracle_sort(ga, stream)
    if kind == "term_vector":
        return tv
    if kind == "inverted_index":
        return oracle_inverted_index(tv)
    if kind == "ranked_inverted_index":
        return oracle_ranked_inverted_index(tv)
    if kind == "sequence_count":
        return oracle_sequence_count(ga, params.get("l", 3), stream)
    if kind in ("search_bm25", "search_tfidf"):
        return oracle_search(tv, params["terms"], k=params.get("k", 10),
                             scheme=kind[len("search_"):])
    if kind == "filter_count":
        return oracle_filter(tv, params["predicate"])
    if kind == "agg_terms":
        return oracle_agg(tv, params["terms"], op=params.get("agg", "sum"))
    if kind == "phrase_count":
        return oracle_phrase(ga, params["terms"], stream)
    raise ValueError(f"unknown kind {kind!r}")


# ------------------------------------------------------------- digests --
def _canonical(part) -> np.ndarray:
    """Values in one dtype per family, so that equal values digest alike
    whatever integer or float width each side chose (the comparison is of
    values, as ``np.testing.assert_array_equal`` makes it).  A narrowing
    that would change a value is not made."""
    a = np.asarray(part)
    if a.dtype.kind in "biu":
        exact = a.dtype.itemsize <= 4 and a.dtype.kind != "u" or a.size == 0 \
            or (a.min() >= -2 ** 31 and a.max() < 2 ** 31)
        b = a.astype(np.int32, copy=False) if exact else a
    elif a.dtype.kind == "f":
        b = a.astype(np.float32, copy=False)
        if b.dtype != a.dtype and not np.array_equal(b, a):
            b = a
    else:
        raise TypeError(f"cannot digest an answer part of dtype {a.dtype}")
    return np.ascontiguousarray(b)


def digest(result) -> str:
    """Hash of an answer's shapes and values (tuple answers part by
    part)."""
    h = hashlib.sha256()
    for part in (result if isinstance(result, tuple) else (result,)):
        a = _canonical(part)
        h.update(repr((a.dtype.str, a.shape)).encode())
        h.update(a.reshape(-1).view(np.uint8))
    return h.hexdigest()
