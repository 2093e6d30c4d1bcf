"""Device-side batch assembly (ops/device_batching) and the engine's
corpus-resident train scan.

Semantic ground truth is the host pipeline (corpus/batching.py): identical
window/validity structure given the same shrink draws, identical batch
packing for the subsample=0 stream, and the host-side words_done
accounting. The corpus scan must be mesh-shape-invariant like every other
engine path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.corpus.batching import (
    context_width, window_batch, window_offsets,
)
from glint_word2vec_tpu.ops.device_batching import (
    WINDOW_FOLD, corpus_words_done, corpus_words_done_compacted,
    device_window_batch, subsample_compact, subsample_keep_mask,
)
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
from glint_word2vec_tpu.parallel.mesh import make_mesh

V, D = 97, 16


def _corpus(n_sent=7, lens=(5, 1, 9, 3, 12, 2, 6), seed=0):
    rng = np.random.default_rng(seed)
    sents = [rng.integers(0, V, L).astype(np.int32) for L in lens[:n_sent]]
    ids = np.concatenate(sents)
    offsets = np.zeros(len(sents) + 1, np.int64)
    np.cumsum([len(s) for s in sents], out=offsets[1:])
    return ids, offsets, sents


def _device_b(key, rows, window):
    """The shrink draws device_window_batch makes for these rows."""
    base = jax.random.fold_in(key, WINDOW_FOLD)
    keys = jax.vmap(lambda r: jax.random.fold_in(base, r))(rows)
    return np.asarray(
        jax.vmap(
            lambda k: jax.random.randint(k, (), 0, window, dtype=jnp.int32)
        )(keys)
    )


@pytest.mark.parametrize("window", [2, 3, 5])
def test_device_window_batch_matches_host_semantics(window):
    ids, offsets, _ = _corpus()
    N = len(ids)
    B = 8
    key = jax.random.PRNGKey(7)
    for start in range(0, N + B, B):
        positions = jnp.arange(start, start + B, dtype=jnp.int32)
        rows = jnp.arange(B, dtype=jnp.int32)
        c, x, m = device_window_batch(
            jnp.asarray(ids), jnp.asarray(offsets, jnp.int32),
            positions, rows, key, window,
        )
        c, x, m = map(np.asarray, (c, x, m))
        b = _device_b(key, rows, window)
        offs = window_offsets(window)
        C = context_width(window)
        assert x.shape == (B, C) and m.shape == (B, C)
        for i in range(B):
            p = start + i
            if p >= N:  # epoch tail: fully masked
                assert c[i] == 0 and m[i].sum() == 0
                continue
            assert c[i] == ids[p]
            j = np.searchsorted(offsets, p, side="right") - 1
            s0, s1 = offsets[j], offsets[j + 1]
            # Reference window rule (mllib:384-388 as restated in
            # corpus/batching.py): offsets in [-b, b-1], in-sentence.
            for lane in range(C):
                o = offs[lane]
                q = p + o
                valid = (-b[i] <= o <= b[i] - 1) and s0 <= q < s1
                assert m[i, lane] == (1.0 if valid else 0.0)
                assert x[i, lane] == (ids[q] if valid else 0)


def test_device_window_batch_equals_host_window_batch_given_same_b():
    # Force identical shrink draws through both implementations: a
    # single-sentence corpus, host window_batch with a stub rng whose
    # integers() returns the device draws.
    window = 4
    ids, offsets, sents = _corpus(n_sent=1, lens=(14,))
    key = jax.random.PRNGKey(3)
    B = len(ids)
    rows = jnp.arange(B, dtype=jnp.int32)
    c, x, m = device_window_batch(
        jnp.asarray(ids), jnp.asarray(offsets, jnp.int32),
        jnp.arange(B, dtype=jnp.int32), rows, key, window,
    )
    b = _device_b(key, rows, window)

    class StubRng:
        def integers(self, lo, hi, size):
            assert (lo, hi, size) == (0, window, B)
            return b

    hc, hx, hm = window_batch(ids, window, StubRng())
    np.testing.assert_array_equal(np.asarray(c), hc)
    np.testing.assert_array_equal(np.asarray(x), hx)
    np.testing.assert_array_equal(np.asarray(m), hm)


def test_corpus_words_done_matches_host_accounting():
    ids, offsets, sents = _corpus()
    # Host rule: a sentence counts fully once any of its positions is
    # consumed (corpus/batching.py words_done).
    assert corpus_words_done(offsets, 0) == 0
    for end in range(1, len(ids) + 5):
        e = min(end, len(ids))
        j = np.searchsorted(offsets, e - 1, side="right") - 1
        assert corpus_words_done(offsets, end) == offsets[j + 1]


# ---------------- on-device frequency subsampling ----------------------


def _host_compact_reference(ids, offsets, keep):
    """Numpy ground truth for subsample_compact given the keep mask:
    kept tokens in order, sentence offsets remapped to kept-counts."""
    kept_ids = ids[keep]
    kept_before = np.concatenate([[0], np.cumsum(keep.astype(np.int64))])
    return kept_ids, kept_before[offsets], int(keep.sum())


def test_subsample_keep_mask_statistics():
    # The device keep mask must realize vocab.keep_probabilities as its
    # per-word kept fraction (the host-rule contract on a device RNG
    # stream). 4 words x ~5000 draws each: binomial std <= 0.008, gate
    # at 5 sigma.
    from glint_word2vec_tpu.corpus.vocab import Vocabulary

    counts = np.array([40000, 9000, 2500, 500], np.int64)
    vocab = Vocabulary.from_sorted(["a", "b", "c", "d"], counts)
    kp = vocab.device_keep_probabilities(subsample_ratio=0.01)
    assert kp.dtype == np.float32 and kp.shape == (4,)
    # Subsampling must actually bite for the frequent words and keep the
    # rare ones (keep prob 1.0) under this ratio.
    assert kp[0] < 0.6 and kp[3] == 1.0
    n_per_word = 5000
    ids = jnp.asarray(np.repeat(np.arange(4), n_per_word).astype(np.int32))
    keep = np.asarray(
        subsample_keep_mask(ids, jnp.asarray(kp), jax.random.PRNGKey(0))
    )
    for w in range(4):
        frac = keep[w * n_per_word : (w + 1) * n_per_word].mean()
        p_ = float(kp[w])
        tol = 5 * np.sqrt(max(p_ * (1 - p_), 1e-12) / n_per_word) + 1e-9
        assert abs(frac - p_) <= tol, (w, frac, p_, tol)


def test_subsample_compact_matches_host_reference():
    # The prefix-sum/scatter compaction must equal the numpy reference
    # given the same keep mask: kept tokens in order at the front,
    # offsets remapped (emptied sentences -> empty spans), exact n_kept.
    ids, offsets, _ = _corpus()
    kp = jnp.asarray(
        np.linspace(0.15, 0.9, V).astype(np.float32)
    )
    key = jax.random.PRNGKey(21)
    keep = np.asarray(subsample_keep_mask(jnp.asarray(ids), kp, key))
    assert 0 < keep.sum() < len(ids)  # the draw actually subsamples
    ids_c, offsets_c, n_kept = subsample_compact(
        jnp.asarray(ids), jnp.asarray(offsets, jnp.int32), kp, key
    )
    ids_c, offsets_c = np.asarray(ids_c), np.asarray(offsets_c)
    ref_ids, ref_offsets, ref_n = _host_compact_reference(ids, offsets, keep)
    assert int(n_kept) == ref_n
    np.testing.assert_array_equal(ids_c[:ref_n], ref_ids)
    np.testing.assert_array_equal(offsets_c, ref_offsets)
    assert offsets_c[-1] == ref_n  # batcher bound == kept count


def test_corpus_words_done_compacted_matches_host_accounting():
    # Host convention through the compacted stream: a sentence's FULL
    # pre-subsampling count is credited once any of its kept positions is
    # consumed; consuming everything credits the whole corpus (the host
    # batcher consumes emptied sentences too).
    ids, offsets, _ = _corpus()
    rng = np.random.default_rng(3)
    keep = rng.random(len(ids)) < 0.5
    keep[offsets[1] : offsets[2]] = False  # force an emptied sentence
    _, offsets_c, n_kept = _host_compact_reference(ids, offsets, keep)
    # Original sentence owning each compacted position.
    owner = np.repeat(np.arange(len(offsets) - 1), np.diff(offsets))[keep]
    assert corpus_words_done_compacted(offsets, offsets_c, 0, n_kept) == 0
    for end in range(1, n_kept + 3):
        if end >= n_kept:
            expect = int(offsets[-1])
        else:
            expect = int(offsets[owner[end - 1] + 1])
        got = corpus_words_done_compacted(offsets, offsets_c, end, n_kept)
        assert got == expect, (end, got, expect)


def _mk_engine(shape, V_, seed=11):
    counts = np.arange(V_, 0, -1).astype(np.int64) * 3
    return EmbeddingEngine(
        make_mesh(*shape), V_, D, counts, num_negatives=3, seed=seed,
    )


@pytest.mark.parametrize("shape", [(1, 1), (2, 2), (4, 1)])
def test_corpus_scan_mesh_invariance(shape):
    # The corpus-resident scan must produce identical tables/losses on
    # any mesh shape (same contract as train_steps).
    ids, offsets, _ = _corpus()
    ref = _mk_engine((1, 1), V)
    eng = _mk_engine(shape, V)
    key = jax.random.PRNGKey(5)
    alphas = np.array([0.05, 0.04, 0.04, 0.03], np.float32)
    for e in (ref, eng):
        e.upload_corpus(ids, offsets)
        e.train_steps_corpus(0, 8, 3, key, alphas, step0=2)
    np.testing.assert_allclose(
        np.asarray(eng.syn0, np.float32)[:V],
        np.asarray(ref.syn0, np.float32)[:V],
        rtol=2e-5, atol=1e-7,
    )
    np.testing.assert_allclose(
        np.asarray(eng.syn1, np.float32)[:V],
        np.asarray(ref.syn1, np.float32)[:V],
        rtol=2e-5, atol=1e-7,
    )


def test_corpus_scan_tail_positions_are_noop():
    # A scan dispatched entirely past the corpus end must not move the
    # tables (all rows masked), matching zero-mask host padding.
    ids, offsets, _ = _corpus()
    eng = _mk_engine((1, 1), V)
    eng.upload_corpus(ids, offsets)
    s0 = np.asarray(eng.syn0, np.float32).copy()
    s1 = np.asarray(eng.syn1, np.float32).copy()
    eng.train_steps_corpus(
        len(ids) + 64, 8, 3, jax.random.PRNGKey(0),
        np.array([0.05, 0.05], np.float32),
    )
    np.testing.assert_array_equal(np.asarray(eng.syn0, np.float32), s0)
    np.testing.assert_array_equal(np.asarray(eng.syn1, np.float32), s1)
    # int32-wrapped (negative) positions must also be fully masked — a
    # tail group near the 2**31 corpus bound wraps negative.
    c, x, m = device_window_batch(
        jnp.asarray(ids), jnp.asarray(offsets, jnp.int32),
        jnp.arange(-8, 0, dtype=jnp.int32),
        jnp.arange(8, dtype=jnp.int32), jax.random.PRNGKey(1), 3,
    )
    assert float(np.asarray(m).sum()) == 0.0
    assert np.asarray(c).sum() == 0


def test_upload_corpus_validates():
    eng = _mk_engine((1, 1), V)
    with pytest.raises(ValueError, match="offsets"):
        eng.upload_corpus(
            np.zeros(5, np.int32), np.array([0, 3], np.int64)
        )
    with pytest.raises(ValueError, match="no corpus uploaded"):
        _mk_engine((1, 1), V).train_steps_corpus(
            0, 8, 3, jax.random.PRNGKey(0), np.array([0.05], np.float32)
        )


def _skewed_keep_prob(seed=17):
    rng = np.random.default_rng(seed)
    return rng.uniform(0.2, 0.95, V).astype(np.float32)


@pytest.mark.parametrize("shape", [(2, 2), (4, 1), (1, 4)])
def test_subsample_compact_mesh_invariance(shape):
    # The compaction pass is integer-exact and elementwise-keyed, so its
    # output must be BITWISE identical on every mesh shape — and the
    # subsampled train scan over it must match the single-device run to
    # the same tolerance as the un-subsampled scan.
    ids, offsets, _ = _corpus()
    kp = _skewed_keep_prob()
    key = jax.random.PRNGKey(9)
    alphas = np.array([0.05, 0.04, 0.04, 0.03], np.float32)
    ref = _mk_engine((1, 1), V)
    eng = _mk_engine(shape, V)
    for e in (ref, eng):
        e.upload_corpus(ids, offsets)
        e.set_keep_probs(kp)
        n = e.compact_corpus(key)
        e.train_steps_corpus(0, 8, 3, key, alphas, step0=2)
    assert ref._n_kept == eng._n_kept == n
    assert 0 < n < len(ids)  # the pass actually subsampled
    np.testing.assert_array_equal(
        np.asarray(eng._corpus_compacted[0]),
        np.asarray(ref._corpus_compacted[0]),
    )
    np.testing.assert_array_equal(
        eng.compacted_offsets(), ref.compacted_offsets()
    )
    for table in ("syn0", "syn1"):
        np.testing.assert_allclose(
            np.asarray(getattr(eng, table), np.float32)[:V],
            np.asarray(getattr(ref, table), np.float32)[:V],
            rtol=2e-5, atol=1e-7, err_msg=table,
        )


def test_compact_corpus_scopes_train_scan_and_recompacts():
    # After compact_corpus the scan trains over the compacted view: a
    # dispatch past n_kept (but inside the static buffer) is a no-op, and
    # a different epoch key recompacts to a different (valid) stream.
    ids, offsets, _ = _corpus()
    eng = _mk_engine((1, 1), V)
    eng.upload_corpus(ids, offsets)
    eng.set_keep_probs(_skewed_keep_prob())
    n0 = eng.compact_corpus(jax.random.PRNGKey(0))
    assert eng.compacted_offsets()[-1] == n0
    s0 = np.asarray(eng.syn0, np.float32).copy()
    eng.train_steps_corpus(
        n0, 8, 3, jax.random.PRNGKey(1), np.array([0.05], np.float32)
    )
    np.testing.assert_array_equal(np.asarray(eng.syn0, np.float32), s0)
    n1 = eng.compact_corpus(jax.random.PRNGKey(1))
    assert eng.compacted_offsets()[-1] == n1
    # Same-key recompaction reproduces the epoch bitwise (resume path).
    n0b = eng.compact_corpus(jax.random.PRNGKey(0))
    assert n0b == n0


def test_compact_corpus_validates():
    eng = _mk_engine((1, 1), V)
    with pytest.raises(ValueError, match="no corpus uploaded"):
        eng.compact_corpus(jax.random.PRNGKey(0))
    ids, offsets, _ = _corpus()
    eng.upload_corpus(ids, offsets)
    with pytest.raises(ValueError, match="keep prob"):
        eng.compact_corpus(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="shape"):
        eng.set_keep_probs(np.ones(V + 1, np.float32))
    with pytest.raises(ValueError, match="no compacted corpus"):
        eng.compacted_offsets()


# ---------------- model-level routing and end-to-end -------------------

CORPUS = [
    "the quick brown fox jumps over the lazy dog".split(),
    "the dog sleeps all day long in the sun".split(),
    "a quick fox and a lazy dog meet in the field".split(),
    "the sun rises over the field every day".split(),
] * 30


def _w2v(**kw):
    from glint_word2vec_tpu import Word2Vec

    defaults = dict(
        vector_size=12, batch_size=32, min_count=1, num_iterations=2,
        seed=7, steps_per_call=4, window=3,
    )
    defaults.update(kw)
    return Word2Vec(**defaults)


def test_fit_routes_to_device_corpus_and_trains():
    model = _w2v().fit(CORPUS)
    assert model.training_metrics["pipeline"] == "device_corpus"
    assert model.training_metrics["steps"] > 0
    # Trained-word accounting matches the host convention: all epochs'
    # pre-subsampling words.
    assert model.transform("quick").shape == (12,)
    syn = model.find_synonyms("quick", 3)
    assert len(syn) == 3


def test_fit_subsampling_routes_to_device_corpus():
    # subsample_ratio > 0 no longer disqualifies the device path: the
    # per-epoch compaction runs on device and the fit stays on the
    # scalars-only dispatch pipeline (the production config).
    model = _w2v(subsample_ratio=0.01).fit(CORPUS)
    assert model.training_metrics["pipeline"] == "device_corpus"
    assert model.training_metrics["steps"] > 0
    assert model.transform("quick").shape == (12,)


def test_subsampled_words_done_parity_with_host_batcher(monkeypatch):
    # Both pipelines credit full PRE-subsampling word counts (the LR
    # anneal contract): same corpus + same ratio must land on the same
    # final words_done even though the kept streams differ.
    ratio = 0.01
    monkeypatch.setenv("GLINT_HOST_BATCHER", "1")
    m_host = _w2v(subsample_ratio=ratio).fit(CORPUS)
    monkeypatch.delenv("GLINT_HOST_BATCHER")
    m_dev = _w2v(subsample_ratio=ratio).fit(CORPUS)
    assert m_host.training_metrics["pipeline"] == "host"
    assert m_dev.training_metrics["pipeline"] == "device_corpus"
    assert (
        m_dev.training_metrics["words_done"]
        == m_host.training_metrics["words_done"]
    )


def test_subsampled_device_corpus_checkpoint_resume(tmp_path):
    # Resume recompacts each epoch from (seed, epoch) alone — no
    # compaction state is checkpointed — and completes the run on the
    # device pipeline.
    ck = str(tmp_path / "ck")
    import os as _os

    _os.makedirs(ck, exist_ok=True)
    w = _w2v(num_iterations=3, subsample_ratio=0.01)
    m1 = w.fit(CORPUS, checkpoint_dir=ck, stop_after_epochs=1)
    assert m1.training_metrics["pipeline"] == "device_corpus"
    m2 = _w2v(num_iterations=3, subsample_ratio=0.01).fit(
        CORPUS, checkpoint_dir=ck
    )
    assert m2.training_metrics["pipeline"] == "device_corpus"
    assert m2.training_metrics["steps"] > 0
    assert len(m2.find_synonyms("dog", 2)) == 2


def test_fit_env_escape_hatch_forces_host(monkeypatch):
    monkeypatch.setenv("GLINT_HOST_BATCHER", "1")
    model = _w2v().fit(CORPUS)
    assert model.training_metrics["pipeline"] == "host"


def test_device_corpus_loss_decreases_and_quality_comparable(monkeypatch):
    # The device pipeline must LEARN like the host one: train both on
    # the same corpus/schedule and compare final mean loss.
    monkeypatch.setenv("GLINT_HOST_BATCHER", "1")
    m_host = _w2v(num_iterations=3).fit(CORPUS)
    monkeypatch.delenv("GLINT_HOST_BATCHER")
    m_dev = _w2v(num_iterations=3).fit(CORPUS)
    lh = m_host.training_metrics["final_loss"]
    ld = m_dev.training_metrics["final_loss"]
    assert ld == pytest.approx(lh, rel=0.5), (ld, lh)
    # Same trained-word accounting on both pipelines.
    assert (
        m_dev.training_metrics["words_done"]
        == m_host.training_metrics["words_done"]
    )


def test_device_corpus_checkpoint_resume(tmp_path):
    ck = str(tmp_path / "ck")
    import os as _os

    _os.makedirs(ck, exist_ok=True)
    w = _w2v(num_iterations=3)
    m1 = w.fit(CORPUS, checkpoint_dir=ck, stop_after_epochs=1)
    assert m1.training_metrics["pipeline"] == "device_corpus"
    m2 = _w2v(num_iterations=3).fit(CORPUS, checkpoint_dir=ck)
    assert m2.training_metrics["pipeline"] == "device_corpus"
    # Resumed run completed the remaining epochs and produces a model.
    assert m2.training_metrics["steps"] > 0
    assert len(m2.find_synonyms("dog", 2)) == 2


def test_device_corpus_routing_respects_hbm_budget(monkeypatch):
    """A corpus larger than the device-corpus HBM budget must route to the
    host batcher even when otherwise eligible (subsample off, 1 process)."""
    from glint_word2vec_tpu.models.word2vec import Word2Vec

    m = Word2Vec(subsample_ratio=0.0)
    assert m._device_corpus_eligible(1000)
    assert not m._device_corpus_eligible((2 << 30) // 8 + 1)
    monkeypatch.setenv("GLINT_DEVICE_CORPUS_MAX_BYTES", "8000")
    assert m._device_corpus_eligible(1000)
    assert not m._device_corpus_eligible(1001)


def test_device_corpus_budget_charges_subsampled_path(monkeypatch):
    """With subsampling the path holds the flat corpus + the compacted
    buffer + its per-position record + the transient prefix sums (~16
    bytes/word, not 8): the budget check must charge accordingly,
    including under the env override."""
    from glint_word2vec_tpu.models.word2vec import Word2Vec

    sub = Word2Vec(subsample_ratio=1e-3)
    flat = Word2Vec(subsample_ratio=0.0)
    edge = (2 << 30) // 16  # largest subsampled-eligible corpus
    assert sub._device_corpus_eligible(edge)
    assert not sub._device_corpus_eligible(edge + 1)
    # The same corpus stays eligible without subsampling (8 bytes/word).
    assert flat._device_corpus_eligible(edge + 1)
    monkeypatch.setenv("GLINT_DEVICE_CORPUS_MAX_BYTES", "1600")
    assert sub._device_corpus_eligible(100)
    assert not sub._device_corpus_eligible(101)
    assert flat._device_corpus_eligible(200)
    assert not flat._device_corpus_eligible(201)


def test_device_corpus_budget_malformed_env_warns(monkeypatch, caplog):
    """A malformed GLINT_DEVICE_CORPUS_MAX_BYTES must warn and fall back
    to the 2 GiB default instead of crashing the routing decision."""
    import logging

    from glint_word2vec_tpu.models.word2vec import Word2Vec

    monkeypatch.setenv("GLINT_DEVICE_CORPUS_MAX_BYTES", "2 gigabytes")
    m = Word2Vec(subsample_ratio=0.0)
    with caplog.at_level(
        logging.WARNING, logger="glint_word2vec_tpu.models.word2vec"
    ):
        assert m._device_corpus_eligible(1000)
        assert not m._device_corpus_eligible((2 << 30) // 4 + 1)
    warned = [
        r for r in caplog.records
        if "GLINT_DEVICE_CORPUS_MAX_BYTES" in r.getMessage()
    ]
    assert warned and "2 gigabytes" in warned[0].getMessage()
