import os
import sys
import zlib

# Tests run on the real single CPU device — the dry-run (and only the
# dry-run) forces 512 host devices, in its own process.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


def test_seed(nodeid: str) -> int:
    """Deterministic per-test numpy seed: a stable hash of the test's node
    id, so every test (and every parametrized example) gets its own stream
    yet reruns reproduce it exactly.  ``REPRO_TEST_SEED`` overrides it — set
    it to the seed printed by a failing run to replay that run."""
    env = os.environ.get("REPRO_TEST_SEED")
    if env is not None:
        return int(env)
    return zlib.crc32(nodeid.encode()) & 0x7FFFFFFF


@pytest.fixture(autouse=True)
def _seed_numpy(request):
    """Seed numpy's global RNG per test (differential/fuzz suites draw from
    it via ``seeded_rng``); the seed is attached to the test item and
    printed in the failure report."""
    seed = test_seed(request.node.nodeid)
    request.node._repro_seed = seed
    np.random.seed(seed)
    yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    seed = getattr(item, "_repro_seed", None)
    if rep.failed and seed is not None:
        rep.sections.append(
            ("numpy seed",
             f"REPRO_TEST_SEED={seed}  (rerun with this env var to replay)"))


@pytest.fixture
def seeded_rng(request):
    """Fresh Generator derived from the per-test seed (preferred over the
    global stream for new tests: independent of draw order elsewhere)."""
    return np.random.default_rng(test_seed(request.node.nodeid))


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def make_repetitive_files(rng, vocab, n_files=3, motifs=True):
    """Corpus with nested repetition -> deep grammar DAGs."""
    phrase = rng.integers(0, vocab, int(rng.integers(3, 10)))
    motif = np.tile(phrase, int(rng.integers(2, 5)))
    files = []
    for _ in range(n_files):
        parts = []
        for _ in range(int(rng.integers(2, 8))):
            r = rng.random()
            if motifs and r < 0.5:
                parts.append(motif)
            elif r < 0.75:
                parts.append(phrase)
            else:
                parts.append(rng.integers(0, vocab, int(rng.integers(2, 20))))
        files.append(np.concatenate(parts))
    return files
