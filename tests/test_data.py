"""Data plane: tokenizer, store roundtrip, random access, pipeline."""

import dataclasses

import numpy as np
import pytest

from repro.core import word_count
from repro.data import (BatchPipeline, CompressedCorpus, Tokenizer,
                        synthetic)


def test_tokenizer_roundtrip(tmp_path):
    tok = Tokenizer()
    ids = tok.encode("the cat sat on the mat . the cat !")
    assert ids[0] == ids[4] == ids[7]       # "the"
    tok.save(str(tmp_path / "tok.json"))
    tok2 = Tokenizer.load(str(tmp_path / "tok.json"))
    assert tok2.decode(ids) == "the cat sat on the mat . the cat !"
    assert tok2.encode("unseen")[0] == 0     # frozen -> <unk>


def test_vocab_from_tadoc_counts():
    words = ["a", "b", "c"]
    counts = np.array([5, 50, 1])
    tok = Tokenizer.from_tadoc_counts(words, counts)
    assert tok.word_to_id["b"] < tok.word_to_id["a"] < tok.word_to_id["c"]


def test_store_roundtrip_and_window(tmp_path):
    files = synthetic.make_table2_corpus("D")
    cc = CompressedCorpus.build(files, vocab_size=400)
    p = str(tmp_path / "c.npz")
    cc.save(p)
    cc2 = CompressedCorpus.load(p)
    assert cc2.stats() == cc.stats()
    assert cc.stats()["compression_ratio"] > 1.2
    w = cc2.window(0, 37, 50)
    assert (w == files[0][37:87]).all()


def test_window_bounds_are_validated():
    """Regression: offset past the file end used to compute a negative
    length (np.empty crash) and a negative offset silently expanded the
    PREVIOUS file's tokens — both must raise, clearly."""
    files = synthetic.make_table2_corpus("A")     # multi-file corpus
    cc = CompressedCorpus.build(files, vocab_size=1200)
    flen = int(cc.file_lens[1])
    # interior reads still work, including the clamped tail ...
    assert (cc.window(1, flen - 5, 50) == files[1][flen - 5:]).all()
    # ... and the offset == file_len edge is an empty window, not an error
    assert cc.window(1, flen, 10).size == 0
    with pytest.raises(ValueError):
        cc.window(1, flen + 1, 10)          # past the end
    with pytest.raises(ValueError):
        cc.window(1, -3, 10)                # would read file 0's tokens
    with pytest.raises(ValueError):
        cc.window(1, 0, -1)                 # negative length
    with pytest.raises(IndexError):
        cc.window(len(cc.file_lens), 0, 1)  # no such file
    with pytest.raises(IndexError):
        cc.window(-1, 0, 1)


def test_global_window_bounds_are_validated():
    files = synthetic.make_table2_corpus("D")
    cc = CompressedCorpus.build(files, vocab_size=400)
    total = int(cc.ga.exp_len[0])
    # the full stream expands (splitters included), tail clamps, end edge
    # is empty
    assert cc.global_window(0, total).size == total
    assert cc.global_window(total - 3, 10).size == 3
    assert cc.global_window(total, 10).size == 0
    with pytest.raises(ValueError):
        cc.global_window(total + 1, 1)
    with pytest.raises(ValueError):
        cc.global_window(-1, 5)             # used to read from offset 0
    with pytest.raises(ValueError):
        cc.global_window(0, -2)


def test_store_roundtrip_preserves_every_array_field(tmp_path):
    """Regression: _ARRAY_FIELDS used to string-compare dataclass
    annotations (`f.type == "np.ndarray"`), so an annotation-style change
    silently dropped arrays from save/load.  Assert the field selection
    covers exactly the ndarray fields and that each one round-trips."""
    from repro.data.store import _ARRAY_FIELDS, _META_FIELDS
    files = synthetic.make_table2_corpus("D")
    cc = CompressedCorpus.build(files, vocab_size=400)
    array_fields = {f.name for f in dataclasses.fields(cc.ga)
                    if isinstance(getattr(cc.ga, f.name), np.ndarray)}
    assert set(_ARRAY_FIELDS) == array_fields
    assert set(_META_FIELDS) == {
        f.name for f in dataclasses.fields(cc.ga)} - array_fields
    p = str(tmp_path / "c.npz")
    cc.save(p)
    cc2 = CompressedCorpus.load(p)
    for name in _ARRAY_FIELDS:
        a, b = getattr(cc.ga, name), getattr(cc2.ga, name)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert (a == b).all(), f"array field {name} did not survive"
    for name in _META_FIELDS:
        assert getattr(cc.ga, name) == getattr(cc2.ga, name), name
    assert (cc2.file_starts == cc.file_starts).all()
    assert (cc2.file_lens == cc.file_lens).all()


def test_analytics_on_store():
    files = synthetic.make_table2_corpus("A")
    cc = CompressedCorpus.build(files, vocab_size=1200)
    wc = np.asarray(word_count(cc.ga))
    oracle = np.bincount(np.concatenate(files), minlength=1200)
    assert np.allclose(wc, oracle)


def test_pipeline_determinism_and_sharding():
    files = synthetic.make_table2_corpus("D")
    cc = CompressedCorpus.build(files, vocab_size=400)
    kw = dict(global_batch=8, seq_len=32, seed=7, prefetch=0)
    full = BatchPipeline(cc, **kw)
    s0 = BatchPipeline(cc, shard=0, num_shards=2, **kw)
    s1 = BatchPipeline(cc, shard=1, num_shards=2, **kw)
    xf, yf = full.batch_at(5)
    x0, _ = s0.batch_at(5)
    x1, _ = s1.batch_at(5)
    assert (np.concatenate([x0, x1]) == xf).all()
    assert (xf[:, 1:] == yf[:, :-1]).all()          # labels = next token
    # same (seed, step) -> identical batch, independent of history
    xf2, _ = BatchPipeline(cc, **kw).batch_at(5)
    assert (xf2 == xf).all()


def test_pipeline_iterator_prefetch():
    files = synthetic.make_table2_corpus("D")
    cc = CompressedCorpus.build(files, vocab_size=400)
    pl = BatchPipeline(cc, global_batch=4, seq_len=16, seed=1, prefetch=2)
    it = iter(pl)
    b0 = next(it)
    b1 = next(it)
    assert b0[0].shape == (4, 16) and b1[0].shape == (4, 16)
    x0, _ = pl.batch_at(0)
    assert (b0[0] == x0).all()
    pl.close()


def test_synthetic_table2_shapes():
    for name, spec in synthetic.TABLE2.items():
        files = synthetic.make_table2_corpus(name)
        assert len(files) == spec.n_files
        assert all(len(f) == spec.tokens_per_file for f in files)


def test_synthetic_redrawn_tail_and_revision_histories():
    """A redrawn zipf tail stays inside the vocabulary without piling the
    tail's mass onto the last word id, and revision histories deepen the
    rule DAG well past the phrase model's."""
    rng = np.random.default_rng(0)
    clipped = synthetic.zipf_words(rng, 20_000, 500)
    redrawn = synthetic.zipf_words(rng, 20_000, 500, redraw_tail=True)
    assert redrawn.min() >= 0 and redrawn.max() < 500
    assert (clipped == 499).sum() > 50 * max((redrawn == 499).sum(), 1)

    base = synthetic.CorpusSpec("h", n_files=2, tokens_per_file=6000,
                                vocab=5000, phrase_rate=0.6, n_phrases=30,
                                phrase_len=8, seed=1, redraw_tail=True)
    deep = dataclasses.replace(base, history_rate=0.01, revisions=24)
    levels = {}
    for spec in (base, deep):
        files = synthetic.make_corpus(spec)
        assert all(len(f) == spec.tokens_per_file for f in files)
        levels[spec.revisions] = CompressedCorpus.build(
            files, spec.vocab).ga.num_levels
    assert levels[24] >= 24 > levels[0]
