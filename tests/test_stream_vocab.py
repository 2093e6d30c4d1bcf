"""Streaming vocabulary statistics (ISSUE 10): the space-saving sketch's
error guarantees on a zipf stream, online counting/encoding, promotion
alignment, and replay parity — a stream replayed as a fixed corpus must
induce the same adaptive distributions batch ``build_vocab`` computes.

Deliberately jax-free: corpus/stream_vocab.py is pure host code.
"""

import collections

import numpy as np
import pytest

from glint_word2vec_tpu.corpus.stream_vocab import (
    SpaceSavingSketch,
    StreamVocab,
    bootstrap_stream_vocab,
)
from glint_word2vec_tpu.corpus.vocab import build_vocab


def _zipf_stream(n_items, vocab=2000, alpha=1.2, seed=7):
    rng = np.random.default_rng(seed)
    items = rng.zipf(alpha, size=n_items)
    items = items[items <= vocab]
    return [f"z{int(i)}" for i in items]


# ----------------------------------------------------------------------
# SpaceSavingSketch
# ----------------------------------------------------------------------


def test_sketch_exact_under_capacity():
    sk = SpaceSavingSketch(capacity=64)
    for w in ["a", "b", "a", "c", "a", "b"]:
        sk.add(w)
    assert sk.estimate("a") == (3, 0)
    assert sk.estimate("b") == (2, 0)
    assert sk.estimate("c") == (1, 0)
    assert sk.guaranteed("a") == 3
    assert sk.guaranteed("missing") == 0
    assert sk.max_untracked_count == 0.0


def test_sketch_zipf_guarantees():
    # The classic space-saving guarantees on a heavy-tailed stream at a
    # capacity far below the distinct-item count.
    stream = _zipf_stream(50_000)
    truth = collections.Counter(stream)
    assert len(truth) > 400
    sk = SpaceSavingSketch(capacity=256)
    for w in stream:
        sk.add(w)
    assert len(sk) <= 256
    n = sk.items_seen
    bound = n / sk.capacity
    for w in list(truth):
        if w in sk:
            est, err = sk.estimate(w)
            # Overestimate-only, with its own per-item error bound.
            assert est >= truth[w] >= est - err
            assert err <= bound
        else:
            # Any untracked item's true count is under the global bound.
            assert truth[w] <= bound
    # Every item more frequent than N/capacity is guaranteed tracked.
    for w, c in truth.items():
        if c > bound:
            assert w in sk, (w, c, bound)


def test_sketch_eviction_inherits_error():
    sk = SpaceSavingSketch(capacity=2)
    sk.add("a", 5)
    sk.add("b", 3)
    sk.add("c")  # evicts b (the min), inherits its count as error
    est, err = sk.estimate("c")
    assert (est, err) == (4, 3)
    assert sk.guaranteed("c") == 1
    assert "b" not in sk
    # Pop removes promotion-taken items.
    assert sk.pop("c") == (4, 3)
    assert "c" not in sk


def test_sketch_over_threshold_uses_guaranteed_count():
    sk = SpaceSavingSketch(capacity=2)
    sk.add("a", 10)
    sk.add("b", 8)
    sk.add("c", 5)  # est 13, err 8 -> guaranteed 5
    out = sk.over_threshold(6)
    assert [w for w, _, _ in out] == ["a"]  # c's 13 is not GUARANTEED >= 6
    out = sk.over_threshold(5)
    assert {w for w, _, _ in out} == {"a", "c"}


def test_sketch_capacity_validation():
    with pytest.raises(ValueError):
        SpaceSavingSketch(0)


# ----------------------------------------------------------------------
# StreamVocab
# ----------------------------------------------------------------------


def _bootstrap(corpus, min_count=2, **kw):
    return bootstrap_stream_vocab(corpus, min_count=min_count, **kw)


def test_observe_counts_and_encodes():
    sv = _bootstrap([["a", "b", "a"], ["a", "b", "c", "c"]], min_count=2)
    # a(3), b(2), c(2) admitted; encode returns row ids, OOV sketched.
    ids = sv.observe(["a", "c", "newword", "b"])
    assert ids.tolist() == [sv.word_index["a"], sv.word_index["c"], sv.word_index["b"]]
    assert sv.oov_words_seen == 1
    assert "newword" in sv.sketch
    assert sv.counts_array()[sv.word_index["a"]] == 4  # 3 bootstrap + 1


def test_encode_never_counts():
    # The bootstrap window replays encode-only: its occurrences are
    # already in the counts (and the sketch), so encode() must leave
    # every statistic untouched — a double-counted bootstrap would
    # promote at half the documented threshold.
    sv = _bootstrap([["a", "b", "a"], ["a", "b", "c", "c"]], min_count=2)
    counts_before = sv.counts_array().copy()
    tw, oov = sv.train_words_count, sv.oov_words_seen
    seen = sv.sketch.items_seen
    ids = sv.encode(["a", "c", "newword", "b"])
    assert ids == [sv.word_index["a"], sv.word_index["c"], sv.word_index["b"]]
    assert (sv.counts_array() == counts_before).all()
    assert sv.train_words_count == tw
    assert sv.oov_words_seen == oov
    assert sv.sketch.items_seen == seen
    assert "newword" not in sv.sketch


def test_bootstrap_seeds_sketch_with_subthreshold_words():
    sv = _bootstrap([["a", "a", "rare"], ["a", "b", "b"]], min_count=2)
    assert "rare" not in sv
    assert sv.sketch.estimate("rare") == (1, 0)  # exact seed, not forgotten
    sv.observe(["rare"])
    assert sv.sketch.estimate("rare") == (2, 0)


def test_promote_appends_in_row_order():
    sv = _bootstrap([["a", "a"], ["b", "b"]], min_count=2)
    base = sv.base_size
    sv.sketch.add("x", 5)
    sv.sketch.add("y", 7)
    cands = sv.promotable(5)
    assert [w for w, _ in cands] == ["y", "x"]  # most frequent first
    assert sv.promote("y") == base
    assert sv.promote("x") == base + 1
    assert sv.words[base] == "y" and sv.words[base + 1] == "x"
    assert "y" not in sv.sketch
    assert sv.promoted == 2
    with pytest.raises(ValueError):
        sv.promote("y")  # already in vocabulary
    # Promoted counts fold into the subsample normalizer.
    assert sv.train_words_count == 4 + 7 + 5


def test_max_size_caps_promotion():
    sv = _bootstrap([["a", "a"], ["b", "b"]], min_count=2, max_size=3)
    sv.sketch.add("x", 9)
    sv.sketch.add("y", 9)
    assert len(sv.promotable(1)) == 1  # room for exactly one
    sv.promote("x")
    assert sv.promotable(1) == []
    with pytest.raises(ValueError):
        sv.promote("y")


def test_noise_counts_span_base_vocab_only():
    sv = _bootstrap([["a", "a"], ["b", "b"]], min_count=2)
    sv.sketch.add("x", 9)
    sv.promote("x")
    nc = sv.noise_counts()
    assert nc.shape == (sv.base_size,)
    w = sv.noise_weights()
    assert w.shape == (sv.base_size,)
    assert abs(w.sum() - 1.0) < 1e-12


# ----------------------------------------------------------------------
# Replay parity: stream == batch on the same data
# ----------------------------------------------------------------------


def _shifting_corpus(seed=3):
    rng = np.random.default_rng(seed)
    words = [f"w{i}" for i in range(60)]
    return [
        [str(w) for w in rng.choice(words, size=8)] for _ in range(800)
    ]


def test_replay_parity_with_batch_vocab():
    """A stream consumed as (bootstrap window + observes) induces the
    exact batch distributions when replayed over the same sentences:
    admitted words keep exact counts, so per-word noise counts and keep
    probabilities match ``build_vocab`` word for word."""
    corpus = _shifting_corpus()
    cut = 200
    sv = _bootstrap(corpus[:cut], min_count=5)
    for s in corpus[cut:]:
        sv.observe(s)

    batch = build_vocab(corpus, min_count=1)
    # No promotions happened (bootstrap admitted everything with
    # min_count 5 over a 60-word vocab x 200 sentences).
    assert sv.promoted == 0
    # Exact per-word count parity for every admitted word.
    for w, i in sv.word_index.items():
        assert sv.counts_array()[i] == batch.counts[batch.word_index[w]]
    assert sv.train_words_count == batch.train_words_count

    # The induced distributions agree as functions word -> value (index
    # ORDER differs by construction: batch ranks by global frequency,
    # the stream ranks by bootstrap-window frequency).
    keep_s = sv.keep_probabilities(1e-3)
    keep_b = batch.keep_probabilities(1e-3)
    nw_s = sv.noise_weights(0.75)
    bw = batch.counts.astype(np.float64) ** 0.75
    nw_b = bw / bw.sum()
    for w, i in sv.word_index.items():
        j = batch.word_index[w]
        np.testing.assert_allclose(keep_s[i], keep_b[j], rtol=1e-12)
        np.testing.assert_allclose(nw_s[i], nw_b[j], rtol=1e-12)


def test_space_saving_counts_vs_exact_on_zipf_sentences():
    """End-to-end OOV accounting: words kept out of the bootstrap vocab
    flow to the sketch, whose estimates track exact counts within the
    N/capacity bound."""
    stream = _zipf_stream(30_000, vocab=1500)
    sentences = [stream[i : i + 10] for i in range(0, len(stream), 10)]
    # Bootstrap on a tiny prefix with a high threshold: most of the
    # tail stays OOV and exercises the sketch.
    sv = bootstrap_stream_vocab(
        sentences[:20], min_count=10, sketch_capacity=128
    )
    # Exact OOV truth over the WHOLE stream (no promotions happen, so
    # membership never changes): bootstrap sub-threshold words seed the
    # sketch with their exact window counts and are part of it.
    truth: collections.Counter = collections.Counter()
    for s in sentences[:20]:
        truth.update(w for w in s if w not in sv.word_index)
    for s in sentences[20:]:
        truth.update(w for w in s if w not in sv.word_index)
        sv.observe(s)
    assert sv.oov_words_seen == sum(truth.values())
    bound = sv.sketch.items_seen / sv.sketch.capacity
    for w, c in truth.items():
        if w in sv.sketch:
            est, err = sv.sketch.estimate(w)
            assert est >= c >= est - err
        else:
            assert c <= bound


def test_snapshot_vocabulary_is_aligned():
    sv = _bootstrap([["a", "a"], ["b", "b"]], min_count=2)
    sv.sketch.add("x", 9)
    sv.promote("x")
    v = sv.snapshot_vocabulary()
    assert v.words == sv.words
    assert v.word_index == sv.word_index
    assert v.counts.tolist() == sv.counts_array().tolist()
    assert v.size == sv.base_size + 1


# ----------------------------------------------------------------------
# The chunked look-ups (PR 50): corpus/word_index.py behind
# observe_many / encode_many, held to the dictionary and to a call a
# sentence
# ----------------------------------------------------------------------

_ODD_WORDS = ["", "a b", "é", "日本語", "sixteenbyteslong", "seventeenbyteslon",
              "averyveryverylongwordindeed", "ab", "ab\0", "x\0y"]


@pytest.mark.parametrize("odd", [False, True])
def test_word_index_is_the_dictionary(odd):
    from glint_word2vec_tpu.corpus.word_index import WordIndex

    rng = np.random.default_rng(3)
    words = [f"w{i:05d}" for i in range(3000)] + (_ODD_WORDS if odd else [])
    index, rows = WordIndex(words), {w: i for i, w in enumerate(words)}
    # grown one word at a time, past the table it was built with
    for i in range(4000):
        w = f"g{i}" if i % 7 else "long" + "x" * 20 + str(i)
        index.add(w, len(rows))
        rows[w] = len(rows)
    asked = [list(rows)[i] for i in rng.integers(0, len(rows), 20000)]
    asked += ["nope", "w", "w000001", "ée", "sixteenbyteslonG", "g"]
    nul = [t for t in asked if "\0" in t]
    if nul:  # a NUL would read as a key's padding: the caller's dictionary
        assert index.lookup(asked) is None
        asked = [t for t in asked if "\0" not in t]
    got = index.lookup(asked)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, [rows.get(t, -1) for t in asked])


@pytest.mark.parametrize("chunk", [1, 3, 50])
def test_observe_many_is_observe_a_sentence(chunk):
    rng = np.random.default_rng(11)
    known = [f"k{i}" for i in range(300)] + ["é", "x\0y", "w" * 30]
    base = build_vocab([known], min_count=1)
    one = StreamVocab(base, sketch_capacity=16)  # evictions in the sketch
    many = StreamVocab(base, sketch_capacity=16)
    pool = known + [f"new{i}" for i in range(40)]
    sents = [list(rng.choice(pool, size=rng.integers(1, 90)))
             for _ in range(120)]
    for lo in range(0, len(sents), chunk):
        part = sents[lo:lo + chunk]
        want = [one.observe(s) for s in part]
        rows, lens = many.observe_many(part)
        np.testing.assert_array_equal(rows, np.concatenate(want))
        assert rows.dtype == np.int32
        assert lens.tolist() == [len(w) for w in want]
        enc, enc_lens = many.encode_many(part)  # counts nothing
        np.testing.assert_array_equal(enc, rows)
        np.testing.assert_array_equal(enc_lens, lens)
        if lo == 60:  # a promotion reaches the index too
            w = next(iter(one.sketch._counts))
            assert one.promote(w, 3) == many.promote(w, 3)
    np.testing.assert_array_equal(one.counts_array(), many.counts_array())
    assert one.train_words_count == many.train_words_count
    assert one.oov_words_seen == many.oov_words_seen > 0
    assert one.sketch._counts == many.sketch._counts
    assert one.sketch._errors == many.sketch._errors
    assert one.sketch.items_seen == many.sketch.items_seen


def test_keep_probabilities_are_the_rule_over_every_word():
    """Only the words the rule can bring under 1 get its arithmetic; the
    rest read what the whole-array form gave, bit for bit."""
    rng = np.random.default_rng(5)
    counts = np.concatenate([rng.zipf(1.3, 5000).astype(np.int64),
                             [0, 0, 1, 10**7]])
    base = build_vocab([["a"]], min_count=1)
    sv = StreamVocab(base)
    sv.words = [f"w{i}" for i in range(counts.size)]
    sv._counts = counts.copy()
    sv.train_words_count = int(counts.sum())
    for ratio in (1e-3, 1e-5, 0.3):
        pcn = counts.astype(np.float64) / float(counts.sum())
        with np.errstate(divide="ignore", invalid="ignore"):
            ran = (np.sqrt(pcn / ratio) + 1.0) * (ratio / pcn)
        want = np.clip(np.where(counts > 0, ran, 0.0), 0.0, 1.0)
        got = sv.keep_probabilities(ratio)
        np.testing.assert_array_equal(got, want)
        assert 0 < (got < 1).sum() < counts.size
