"""Traffic: the queries a cell sends, generated from a mix file and a seed.

``client_trace`` and ``zipf_popularity`` are copies of
``benchmarks/_load_trace.py`` as it stood when the benchmark was defined
(``tests/chipbench/test_chipbench_copies.py`` checks that they still
agree); the copy also takes the kind weights as an argument, so one
generator serves every mix.  Everything else here reads a mix file
(``traffic/<mix>.json``):

* ``"loop": "open"`` — arrivals on a schedule (burst-modulated Poisson at
  ``rate_qps``), each query sent when it is due whether or not earlier ones
  have been answered; a share ``deadline_frac`` carries a deadline of
  ``deadline_s`` after its scheduled send;
* ``"loop": "closed"`` — one caller per entry of ``clients``, each
  sending its next batch-analytics job as soon as the answers to its last
  one have arrived.  A job is one kind and one parameter set over every
  corpus of the deployment, as the paper's analytics run over a whole
  dataset.  Each client owns the kinds its entry lists, no kind belongs
  to two clients, so two jobs never share a group and every chunk holds
  exactly one job; a client's kinds follow a fixed rotation that holds
  each in proportion to its weight in ``kinds``.  Every seed sends the
  same kinds in the same order and changes only the text and the
  parameters, not the amount of work.

``warmup_jobs`` is one job per kind and parameter shape a mix can send
(every phrase length, every top-k): the programs a window can reach.

In the open loop corpora are drawn by the configuration's popularity.
Parameterised kinds draw their parameters from a pool of ``pool`` sets per
kind, with zipf ``pool_zipf_s`` popularity over the pool, so popular
queries share a group and batch across corpora.  A query is a plain ``(corpus, kind, params)``
triple; the harness turns it into the program's ``Query``.
"""

from __future__ import annotations

import itertools
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

#: analytics + search mix, weighted toward cheap point lookups
KIND_WEIGHTS = (
    ("word_count", 0.30),
    ("term_vector", 0.20),
    ("sort", 0.15),
    ("sequence_count", 0.10),
    ("search_bm25", 0.15),
    ("search_tfidf", 0.10),
)

#: (corpus name, kind, params), params as keyword arguments of ``Query``
Triple = Tuple[str, str, dict]


# ------------------------------------------- copy of _load_trace.py --
def zipf_popularity(n: int, s: float) -> np.ndarray:
    """Normalized rank-zipf pmf over ``n`` corpora: p_r ∝ 1/(r+1)^s."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return w / w.sum()


def client_trace(args: tuple, kind_weights=KIND_WEIGHTS) -> list:
    """One client's arrival schedule.

    Returns ``[(at_s, corpus_idx, kind, rel_deadline | None), ...]`` with
    arrivals from a burst-modulated Poisson process: phase lengths are
    exponential, burst phases scale the instantaneous rate by
    ``burst_factor``, calm phases compensate so the long-run mean rate
    stays ``rate_qps`` (offered load is what the spec says it is).
    """
    (seed, duration_s, rate_qps, n_corpora, zipf_s, deadline_frac,
     dl_lo, dl_hi, burst_factor, burst_frac, mean_phase_s) = args
    rng = np.random.default_rng(seed)
    pop = zipf_popularity(n_corpora, zipf_s)
    kinds = [k for k, _ in kind_weights]
    kw = np.array([w for _, w in kind_weights])
    kw = kw / kw.sum()
    # calm rate chosen so  burst_frac*burst + (1-burst_frac)*calm == rate
    calm_rate = rate_qps / (1.0 - burst_frac + burst_frac * burst_factor)
    burst_rate = calm_rate * burst_factor
    out = []
    t = 0.0
    in_burst = rng.random() < burst_frac
    phase_end = float(rng.exponential(mean_phase_s))
    while t < duration_s:
        rate = burst_rate if in_burst else calm_rate
        t += float(rng.exponential(1.0 / max(rate, 1e-9)))
        while t >= phase_end:                  # cross into the next phase
            in_burst = not in_burst
            phase_end += float(rng.exponential(mean_phase_s))
        if t >= duration_s:
            break
        c = int(rng.choice(n_corpora, p=pop))
        kind = kinds[int(rng.choice(len(kinds), p=kw))]
        rel_dl = (float(rng.uniform(dl_lo, dl_hi))
                  if rng.random() < deadline_frac else None)
        out.append((t, c, kind, rel_dl))
    return out


# ------------------------------------------------------------- params --
def _terms(rng, head: np.ndarray, lo: int, hi: int) -> Tuple[int, ...]:
    n = int(rng.integers(lo, hi + 1))
    return tuple(int(t) for t in rng.choice(head, size=n, replace=False))


def _predicate(rng, head: np.ndarray):
    a, b, c = (int(t) for t in rng.choice(head, size=3, replace=False))
    th = [int(x) for x in rng.integers(1, 4, size=3)]
    shape = int(rng.integers(3))
    if shape == 0:
        return ("term", a, th[0])
    if shape == 1:
        return ("and", (("term", a, th[0]), ("term", b, th[1])))
    return ("and", (("term", a, th[0]),
                    ("or", (("term", b, th[1]), ("term", c, th[2])))))


def frequent_words(files: Dict[str, List[np.ndarray]], n: int) -> np.ndarray:
    """The ``n`` most frequent word ids of the deployment's text."""
    ids, counts = np.unique(np.concatenate(
        [f for fs in files.values() for f in fs]), return_counts=True)
    return ids[np.argsort(-counts, kind="stable")[:n]]


#: Kinds whose parameters are terms from the frequent head.
_HEAD_KINDS = ("search_bm25", "search_tfidf", "filter_count", "agg_terms")


def param_pools(mix: dict, files: Dict[str, List[np.ndarray]],
                seed: int) -> Dict[str, List[dict]]:
    """Per kind, the parameter sets its queries draw from.  Terms come from
    the text's ``head_terms`` most frequent words; phrases are cut from the
    corpora's own text."""
    p = mix["params"]
    rng = np.random.default_rng([seed % 2 ** 63, 0x9A])
    n = p["pool"]
    head = (frequent_words(files, p["head_terms"])
            if any(k in _HEAD_KINDS for k in mix["kinds"]) else None)
    names = list(files)
    pools: Dict[str, List[dict]] = {}
    for kind in mix["kinds"]:
        if kind in ("search_bm25", "search_tfidf"):
            pools[kind] = [{"terms": _terms(rng, head, 1, 4),
                            "k": int(rng.choice((5, 10)))} for _ in range(n)]
        elif kind == "filter_count":
            pools[kind] = [{"predicate": _predicate(rng, head)}
                           for _ in range(n)]
        elif kind == "agg_terms":
            pools[kind] = [{"terms": _terms(rng, head, 2, 4),
                            "agg": str(rng.choice(("sum", "max")))}
                           for _ in range(n)]
        elif kind == "phrase_count":
            pools[kind] = []
            for _ in range(n):
                corpus = files[names[int(rng.integers(len(names)))]]
                f = corpus[int(rng.integers(len(corpus)))]
                ln = int(rng.integers(p["phrase_min"], p["phrase_max"] + 1))
                at = int(rng.integers(0, len(f) - ln + 1))
                pools[kind].append(
                    {"terms": tuple(int(t) for t in f[at:at + ln])})
        elif kind == "sequence_count":
            pools[kind] = [{"l": p["sequence_l"]}]
        else:
            pools[kind] = [{}]
    return pools


class _Drawer:
    """Draws a kind's parameters from its pool with zipf popularity."""

    def __init__(self, pools: Dict[str, List[dict]], zipf_s: float):
        self.pools = pools
        self.pop = {k: zipf_popularity(len(v), zipf_s)
                    for k, v in pools.items()}

    def __call__(self, rng, kind: str) -> dict:
        pool = self.pools[kind]
        if len(pool) == 1:
            return pool[0]
        return pool[int(rng.choice(len(pool), p=self.pop[kind]))]


# ----------------------------------------------------------- schedules --
def open_schedule(mix: dict, files: Dict[str, List[np.ndarray]],
                  zipf_s: float, seed: int, seconds: float
                  ) -> List[Tuple[float, Triple, Optional[float]]]:
    """``[(at_s, (corpus, kind, params), rel_deadline | None)]`` over
    ``seconds``, from ``client_trace`` at the mix's rate and shape."""
    names = list(files)
    a = mix["arrivals"]
    arrivals = client_trace(
        (seed % 2 ** 63, float(seconds), float(mix["rate_qps"]), len(names),
         zipf_s, mix["deadline_frac"], mix["deadline_s"], mix["deadline_s"],
         a["burst_factor"], a["burst_frac"], a["mean_phase_s"]),
        kind_weights=tuple(mix["kinds"].items()))
    draw = _Drawer(param_pools(mix, files, seed), mix["params"]["pool_zipf_s"])
    rng = np.random.default_rng([seed % 2 ** 63, 0x0B])
    return [(t, (names[c], kind, draw(rng, kind)), dl)
            for t, c, kind, dl in arrivals]


def rotation(kinds: Dict[str, float]) -> List[str]:
    """The kinds in a fixed cycle that holds each in proportion to its
    weight, spread evenly (smooth weighted round robin)."""
    names = list(kinds)
    w = np.array([kinds[k] for k in names], np.float64)
    w /= w.sum()
    for n in range(1, 101):
        counts = np.round(w * n)
        if np.allclose(w * n, counts, atol=1e-9):
            break
    counts = counts.astype(np.int64)
    n = int(counts.sum())
    acc = np.zeros(len(names), np.int64)
    order = []
    for _ in range(n):
        acc += counts
        i = int(np.argmax(acc))
        acc[i] -= n
        order.append(names[i])
    return order


def _client_kinds(mix: dict) -> List[List[str]]:
    """Each client's kinds; together they are the mix's kinds, once each."""
    clients = [list(c) for c in mix["clients"]]
    owned = [k for c in clients for k in c]
    if sorted(owned) != sorted(mix["kinds"]) or not all(clients):
        raise ValueError(f"clients {clients} do not split the kinds "
                         f"{sorted(mix['kinds'])} among them, once each")
    return clients


def closed_streams(mix: dict, files: Dict[str, List[np.ndarray]], seed: int
                   ) -> List[Iterator[List[Triple]]]:
    """One endless stream of jobs per client of a closed loop; a job is the
    list of queries, one per corpus, that the client sends together."""
    names = list(files)
    draw = _Drawer(param_pools(mix, files, seed), mix["params"]["pool_zipf_s"])

    def stream(client: int, kinds: List[str]) -> Iterator[List[Triple]]:
        rng = np.random.default_rng([seed % 2 ** 63, 0xC1, client])
        order = rotation({k: mix["kinds"][k] for k in kinds})
        for at in itertools.count():
            kind = order[at % len(order)]
            params = draw(rng, kind)
            yield [(corpus, kind, params) for corpus in names]

    return [stream(c, kinds) for c, kinds in enumerate(_client_kinds(mix))]


def _shape(value, top: bool = True):
    """What of a parameter can change a compiled program: its structure,
    the lengths of its tuples, and its scalars at the top level (``k``,
    ``l``, an aggregation); the term ids and thresholds inside a tuple are
    only values."""
    if isinstance(value, tuple):
        return tuple(_shape(v, False) for v in value)
    if isinstance(value, str) or top:
        return value
    return None


def warmup_jobs(mix: dict, files: Dict[str, List[np.ndarray]], seed: int
                ) -> List[List[Triple]]:
    """One job over every corpus for each kind and parameter shape of the
    mix's pools: the parameters the window draws come from those pools."""
    names = list(files)
    jobs = []
    for kind, pool in param_pools(mix, files, seed).items():
        seen = set()
        for params in pool:
            key = repr(sorted((k, _shape(v)) for k, v in params.items()))
            if key not in seen:
                seen.add(key)
                jobs.append([(corpus, kind, params) for corpus in names])
    return jobs
