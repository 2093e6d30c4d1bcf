"""Tests for the native C++ host-ops library (native/host_ops.cpp) and its
equivalence to the Python reference implementations."""

import os
import time

import numpy as np
import pytest

from glint_word2vec_tpu.corpus.alias import AliasTable, unigram_weights
from glint_word2vec_tpu.corpus.batching import window_offsets
from glint_word2vec_tpu.native import (
    alias_build_native,
    get_lib,
    window_batch_epoch_native,
)

pytestmark = pytest.mark.skipif(
    get_lib() is None, reason="native host_ops unavailable"
)


def _alias_distribution(prob, alias):
    n = prob.shape[0]
    recon = prob.astype(np.float64).copy()
    for j in range(n):
        if prob[j] < 1.0:
            recon[alias[j]] += 1.0 - float(prob[j])
    return recon / n


def test_native_alias_matches_target_distribution():
    counts = np.array([1000, 100, 10, 7, 3, 1], np.int64)
    w = unigram_weights(counts)
    prob, alias = alias_build_native(w)
    np.testing.assert_allclose(
        _alias_distribution(prob, alias), w / w.sum(), atol=1e-7
    )


def test_native_alias_validates_inputs():
    with pytest.raises(ValueError):
        alias_build_native(np.array([0.0, 0.0]))
    with pytest.raises(ValueError):
        alias_build_native(np.array([-1.0, 1.0]))


def test_native_alias_sampling_statistics():
    counts = np.array([1000, 100, 10, 1], np.int64)
    w = unigram_weights(counts)
    prob, alias = alias_build_native(w)
    t = AliasTable(prob=prob, alias=alias)
    draws = t.sample(np.random.default_rng(0), 200_000)
    freq = np.bincount(draws, minlength=4) / draws.size
    np.testing.assert_allclose(freq, w / w.sum(), atol=0.01)


def _epoch(ids_list, window, keep_prob=None, seed=7):
    ids = np.concatenate(ids_list).astype(np.int32)
    lens = np.array([len(s) for s in ids_list], np.int64)
    offsets = np.zeros(len(lens) + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    if keep_prob is None:
        keep_prob = np.ones(int(ids.max()) + 1, np.float32)
    return window_batch_epoch_native(ids, offsets, keep_prob, window, seed)


def test_native_window_structural_invariants():
    W = 4
    offsets = window_offsets(W)
    sent = np.arange(1, 40, dtype=np.int32)  # distinct ids = positions+1
    centers, contexts, mask, words_done = _epoch([sent], W)
    assert words_done == 39
    assert centers.shape[0] == 39  # keep_prob 1 keeps everything
    np.testing.assert_array_equal(centers, sent)
    for i in range(39):
        valid = mask[i] > 0
        # Lane layout must match corpus.batching.window_offsets; every valid
        # lane holds the word at position i+offset.
        for lane in np.nonzero(valid)[0]:
            j = i + offsets[lane]
            assert 0 <= j < 39
            assert contexts[i, lane] == sent[j]
        # Valid offsets must be exactly [-b, b-1] (clipped): contiguous.
        offs = sorted(offsets[valid])
        if offs:
            # Infer the drawn b: reach is [-b, b-1] before boundary clipping.
            b = max(-offs[0], offs[-1] + 1)
            expected = [o for o in range(-b, b) if o != 0
                        and 0 <= i + o < 39]
            assert offs == expected
        # Masked lanes zero-padded.
        assert np.all(contexts[i][~valid] == 0)


def test_native_window_b_distribution():
    # b ~ U[0, W): mean context size for interior positions ~ 2*mean(b)-...
    # Just check b=0 occurs (empty rows) and max reach is W-1 / W-2.
    W = 5
    offsets = window_offsets(W)
    sent = np.arange(1, 2001, dtype=np.int32)
    centers, contexts, mask, _ = _epoch([sent], W, seed=3)
    sizes = (mask > 0).sum(axis=1)
    assert (sizes == 0).any()  # b=0 rows exist
    used = offsets[np.nonzero((mask > 0).any(axis=0))[0]]
    assert used.min() == -(W - 1) and used.max() == W - 2


def test_native_subsampling_statistics():
    keep = np.array([0.3, 1.0], np.float32)
    sent = np.zeros(20000, np.int32)
    centers, _, _, words_done = _epoch([sent], 3, keep_prob=keep, seed=9)
    assert words_done == 20000  # pre-subsampling count
    assert abs(centers.shape[0] / 20000 - 0.3) < 0.02


def test_native_epoch_determinism():
    sent = np.arange(1, 500, dtype=np.int32)
    a = _epoch([sent], 5, seed=42)
    b = _epoch([sent], 5, seed=42)
    c = _epoch([sent], 5, seed=43)
    np.testing.assert_array_equal(a[1], b[1])
    np.testing.assert_array_equal(a[2], b[2])
    assert not np.array_equal(a[2], c[2])


def test_native_throughput_sanity():
    # The reason this exists: the Python pass runs ~0.1M words/s. Require
    # >2M words/s so a silent fallback or a pathological regression fails.
    rng = np.random.default_rng(0)
    sents = [rng.integers(0, 50_000, rng.integers(5, 40)).astype(np.int32)
             for _ in range(20_000)]
    total = sum(len(s) for s in sents)
    # The fastest of three passes: the suite's other workers share the
    # cores, and one pass alone read under the line on a loaded host
    # (the driver's run of PR 48) where the idle host reads 13-19M.
    dt = float("inf")
    for _ in range(3):
        t0 = time.time()
        centers, contexts, mask, words_done = _epoch(sents, 5, keep_prob=np.ones(50_000, np.float32))
        dt = min(dt, time.time() - t0)
        assert words_done == total
    wps = total / dt
    assert wps > 2e6, f"native epoch pass too slow: {wps/1e6:.2f}M words/s"


def test_native_alias_large_vocab_fast():
    w = unigram_weights(np.random.default_rng(0).integers(1, 10**6, 1_000_000))
    t0 = time.time()
    prob, alias = alias_build_native(w)
    dt = time.time() - t0
    assert dt < 2.0, f"native alias build too slow: {dt:.1f}s at 1M vocab"
    assert prob.shape == (1_000_000,)


class TestCorpusScanner:
    """Native fit_file ingestion (corpus_open/encode) vs the Python passes."""

    CORPUS = (
        "the quick brown fox jumps over the lazy dog\n"
        "the the the\n"
        "tie1 tie2 tie1 tie2 tie1 tie2\n"
        "\n"
        "   \n"
        "singleton   words\twith\ttabs   here\n"
        + ("a b c " * 400)
        + "\n"
        + "trailing no newline"
    )

    @pytest.fixture()
    def corpus_path(self, tmp_path):
        p = tmp_path / "corpus.txt"
        p.write_text(self.CORPUS, encoding="utf-8")
        return str(p)

    @pytest.mark.parametrize(
        "min_count,max_len", [(1, 1000), (2, 1000), (1, 7), (3, 2)]
    )
    def test_native_matches_python_passes(self, corpus_path, min_count,
                                          max_len):
        from glint_word2vec_tpu.corpus.vocab import (
            build_vocab, encode_file, iter_text_file,
        )
        from glint_word2vec_tpu.native import corpus_scan_native

        res = corpus_scan_native(corpus_path, min_count, max_len)
        assert res is not None
        words, counts, ids, offsets = res
        vocab = build_vocab(
            iter_text_file(corpus_path), min_count=min_count
        )
        ids_py, offs_py = encode_file(
            corpus_path, vocab, max_sentence_length=max_len
        )
        assert words == vocab.words  # count desc, first-seen tie order
        np.testing.assert_array_equal(counts, vocab.counts)
        np.testing.assert_array_equal(ids, ids_py)
        np.testing.assert_array_equal(offsets, offs_py)

    def test_scan_and_encode_file_dispatcher(self, corpus_path):
        """The dispatcher returns identical results whichever path runs."""
        from glint_word2vec_tpu.corpus.vocab import scan_and_encode_file

        vocab, ids, offsets = scan_and_encode_file(
            corpus_path, min_count=1, max_sentence_length=1000
        )
        assert vocab.words[0] == "a"  # 1200 occurrences, most frequent
        assert vocab.train_words_count == int(vocab.counts.sum())
        assert ids.dtype == np.int32 and offsets.dtype == np.int64
        assert offsets[-1] == ids.size
        # Lowercase requests must take the (Unicode-aware) Python path and
        # still produce the same structure.
        v2, i2, o2 = scan_and_encode_file(
            corpus_path, min_count=1, max_sentence_length=1000,
            lowercase=True,
        )
        assert v2.words[0] == "a"
        np.testing.assert_array_equal(o2, offsets)

    def test_empty_vocab_raises_via_dispatcher(self, tmp_path):
        from glint_word2vec_tpu.corpus.vocab import scan_and_encode_file
        from glint_word2vec_tpu.native import corpus_scan_native

        p = tmp_path / "tiny.txt"
        p.write_text("one two three\n", encoding="utf-8")
        words, counts, ids, offs = corpus_scan_native(str(p), 5, 1000)
        assert words == [] and ids.size == 0 and offs.tolist() == [0]
        with pytest.raises(ValueError, match="vocabulary size"):
            scan_and_encode_file(str(p), min_count=5)

    def test_missing_file_returns_none(self):
        from glint_word2vec_tpu.native import corpus_scan_native

        assert corpus_scan_native("/nonexistent/x.txt", 1, 1000) is None

    @pytest.mark.parametrize(
        "text",
        [
            "a b\rc d\re f",          # lone-\r line endings
            "a b\r\nc d\r\ne",        # \r\n line endings
            "x y z w\n",    # NBSP + EM SPACE separators
            "one　two threefour\n",  # CJK space, LS, NEL
            "tok end\r\rmid\n\n",
            "x\u1680y\u202fz\u205fw\u200aq\n",  # OGHAM, NNBSP, MMSP, HAIR
        ],
    )
    def test_unicode_whitespace_and_newlines_match_python(
        self, tmp_path, text
    ):
        from glint_word2vec_tpu.corpus.vocab import (
            build_vocab, encode_file, iter_text_file,
        )
        from glint_word2vec_tpu.native import corpus_scan_native

        p = tmp_path / "ws.txt"
        p.write_text(text, encoding="utf-8")
        res = corpus_scan_native(str(p), 1, 1000)
        assert res is not None
        words, counts, ids, offsets = res
        vocab = build_vocab(iter_text_file(str(p)), min_count=1)
        ids_py, offs_py = encode_file(str(p), vocab, max_sentence_length=1000)
        assert words == vocab.words
        np.testing.assert_array_equal(counts, vocab.counts)
        np.testing.assert_array_equal(ids, ids_py)
        np.testing.assert_array_equal(offsets, offs_py)

    def test_invalid_utf8_falls_back_to_python(self, tmp_path):
        """Bytes Python would errors='replace'-merge make the native
        scanner decline, so the dispatcher's result always matches the
        Python semantics."""
        from glint_word2vec_tpu.corpus.vocab import (
            build_vocab, iter_text_file, scan_and_encode_file,
        )
        from glint_word2vec_tpu.native import corpus_scan_native

        p = tmp_path / "bad.txt"
        p.write_bytes(b"a\xff b\xfe a\xff valid word word\n")
        assert corpus_scan_native(str(p), 1, 1000) is None
        vocab, ids, offs = scan_and_encode_file(str(p), min_count=1)
        ref = build_vocab(iter_text_file(str(p)), min_count=1)
        assert vocab.words == ref.words  # a� and b� merged order
        assert offs[-1] == ids.size

    def test_utf8_words_roundtrip(self, tmp_path):
        from glint_word2vec_tpu.corpus.vocab import (
            build_vocab, iter_text_file,
        )
        from glint_word2vec_tpu.native import corpus_scan_native

        p = tmp_path / "de.txt"
        p.write_text(
            "österreich wien österreich grüße\nwien österreich\n",
            encoding="utf-8",
        )
        res = corpus_scan_native(str(p), 1, 1000)
        assert res is not None
        words, counts, _, _ = res
        vocab = build_vocab(iter_text_file(str(p)), min_count=1)
        assert words == vocab.words
        np.testing.assert_array_equal(counts, vocab.counts)


REFERENCE_CORPUS = "/root/reference/de_wikipedia_articles_country_capitals.txt"


@pytest.mark.skipif(
    not os.path.exists(REFERENCE_CORPUS),
    reason="reference fixture corpus not on disk",
)
def test_corpus_scanner_matches_python_on_reference_corpus():
    """Exact native/Python parity on the real (UTF-8, umlauted) reference
    corpus at the reference's own min_count — the corpus every quality
    gate trains on."""
    from glint_word2vec_tpu.corpus.vocab import (
        build_vocab, encode_file, iter_text_file,
    )
    from glint_word2vec_tpu.native import corpus_scan_native

    res = corpus_scan_native(REFERENCE_CORPUS, 5, 1000)
    assert res is not None, "scanner declined a valid-UTF-8 corpus"
    words, counts, ids, offsets = res
    vocab = build_vocab(iter_text_file(REFERENCE_CORPUS), min_count=5)
    ids_py, offs_py = encode_file(
        REFERENCE_CORPUS, vocab, max_sentence_length=1000
    )
    assert words == vocab.words
    np.testing.assert_array_equal(counts, vocab.counts)
    np.testing.assert_array_equal(ids, ids_py)
    np.testing.assert_array_equal(offsets, offs_py)
    # The known ground truth for this fixture (SURVEY.md §4 / verify
    # skill): vocab 3,609 at min_count=5, ~116.5k kept words.
    assert len(words) == 3609
    assert ids.size == 116561


def test_native_epoch_thread_count_invariance():
    """The parallel epoch pass must be byte-identical for every thread
    count (deterministic per-sentence seeds + two-phase count/fill)."""
    from glint_word2vec_tpu.native import window_batch_epoch_native

    rng = np.random.default_rng(0)
    sents = [rng.integers(0, 500, rng.integers(1, 40)).astype(np.int32)
             for _ in range(500)]
    ids = np.concatenate(sents)
    lens = np.array([len(s) for s in sents], np.int64)
    offs = np.zeros(len(lens) + 1, np.int64)
    np.cumsum(lens, out=offs[1:])
    kp = np.clip(rng.random(500).astype(np.float32) * 1.4, 0, 1)
    ref = window_batch_epoch_native(ids, offs, kp, 4, 7, threads=1)
    for t in (2, 3, 8):
        out = window_batch_epoch_native(ids, offs, kp, 4, 7, threads=t)
        for a, b in zip(ref[:3], out[:3]):
            np.testing.assert_array_equal(a, b)
        assert ref[3] == out[3]


class TestParallelScanner:
    """The mmap-parallel counting pass must be byte-identical to the
    streaming pass for every thread count and chunk size."""

    def _mixed_corpus(self, tmp_path, lines=4000):
        rng = np.random.default_rng(3)
        p = tmp_path / "mixed.txt"
        with open(p, "w", encoding="utf-8") as f:
            for i in range(lines):
                n = rng.integers(1, 25)
                f.write(" ".join(f"w{x}" for x in rng.integers(0, 800, n)))
                if i % 7 == 0:
                    f.write(" extra　tok")  # unicode separators
                f.write("\r\n" if i % 5 == 0 else "\n")
            f.write("trailing no newline")
        return str(p)

    def test_parallel_identical_to_streaming(self, tmp_path, monkeypatch):
        from glint_word2vec_tpu.native import corpus_scan_native

        path = self._mixed_corpus(tmp_path)
        # Tiny chunk floor so the file splits into many real chunks.
        monkeypatch.setenv("GLINT_NATIVE_CHUNK_BYTES", "4096")
        ref = corpus_scan_native(path, 2, 11, threads=1)
        assert ref is not None
        for t in (2, 3, 8):
            out = corpus_scan_native(path, 2, 11, threads=t)
            assert out is not None
            assert out[0] == ref[0]
            np.testing.assert_array_equal(out[1], ref[1])
            np.testing.assert_array_equal(out[2], ref[2])
            np.testing.assert_array_equal(out[3], ref[3])

    def test_parallel_matches_python(self, tmp_path, monkeypatch):
        from glint_word2vec_tpu.corpus.vocab import (
            build_vocab, encode_file, iter_text_file,
        )
        from glint_word2vec_tpu.native import corpus_scan_native

        path = self._mixed_corpus(tmp_path, lines=700)
        monkeypatch.setenv("GLINT_NATIVE_CHUNK_BYTES", "2048")
        out = corpus_scan_native(path, 1, 1000, threads=4)
        assert out is not None
        vocab = build_vocab(iter_text_file(path), min_count=1)
        ids_py, offs_py = encode_file(path, vocab, max_sentence_length=1000)
        assert out[0] == vocab.words
        np.testing.assert_array_equal(out[1], vocab.counts)
        np.testing.assert_array_equal(out[2], ids_py)
        np.testing.assert_array_equal(out[3], offs_py)

    def test_parallel_invalid_utf8_declines(self, tmp_path, monkeypatch):
        from glint_word2vec_tpu.native import corpus_scan_native

        p = tmp_path / "bad.txt"
        p.write_bytes(b"ok tokens here\n" * 500 + b"bro\xffken\n")
        monkeypatch.setenv("GLINT_NATIVE_CHUNK_BYTES", "1024")
        assert corpus_scan_native(str(p), 1, 1000, threads=4) is None
