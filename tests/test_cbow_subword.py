"""fastText's CBOW over subword groups on the corpus-resident packed scan
(ISSUE 39).

* ``ops/cbow_subword_reference.py``, the plain reference in the source's
  form (the concatenated input, one mean, the whole gradient to every
  member), against a numpy transcription of ``FastText::cbow`` +
  ``Model::update`` run a position at a time with the tables frozen.
* ``bag_span_batch`` names the words ``bag_window_batch`` holds.
* The engine's scan, which sums each span word's group once and lets the
  bags read the sums, against that reference on the batches the scan drew,
  at 1x1, 1x2 and the four-chip cell's 1x4; 1x1 against 1x2. The two are
  written in different forms: their being equal is what proves the
  factorisation.
* A word twice in one span and a bucket row in two words' groups: counted
  twice in the mean, the gradient the sum.
* Groups cut to the word's own row are the word-level CBOW scan; a bag of
  one word is the subword skip-gram step on the swapped pair with the
  division left out.
* The estimator: the corpus-resident path, its counts, save and load, and
  what stays refused.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from test_cbow import (  # noqa: E402
    CORPUS,
    assert_two_shards_fit_as_one,
    gaps,
    seed_syn1,
    zipf_corpus,
)

from glint_word2vec_tpu.models.fasttext import (  # noqa: E402
    FastTextParams,
    FastTextWord2Vec,
)
from glint_word2vec_tpu.ops.cbow_subword_reference import (  # noqa: E402
    cbow_subword_step,
)
from glint_word2vec_tpu.ops.device_batching import (  # noqa: E402
    bag_lanes,
    bag_span_batch,
    bag_window_batch,
    position_sentences,
)
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine  # noqa: E402
from glint_word2vec_tpu.parallel.mesh import make_mesh  # noqa: E402

V, D, BUCKET, G, NEG, WINDOW, BATCH, K = 512, 32, 96, 8, 5, 5, 64, 3
# The word-level replay's float32 limits (tests/test_cbow.py, whose corpora
# and gap numbers these tests share: its V is this file's).
GAP, DNORM_GAP, LOSS_GAP = 1e-4, 1e-6, 1e-6


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _fasttext_cbow(syn0, syn1, groups, bags, centres, live, negs, alpha):
    """``FastText::cbow`` and ``Model::update`` with
    ``NegativeSamplingLoss``, a position at a time, the tables frozen for
    the batch (the tool's threads update in place): ``bow`` the
    concatenated subwords of the bag's words, ``hidden`` one mean over it,
    ``grad`` added WHOLE to every entry of ``bow``."""
    d0, d1 = np.zeros_like(syn0), np.zeros_like(syn1)
    loss, trained, input_rows = 0.0, 0, 0
    for p in range(centres.shape[0]):
        bow = []
        for c in bags[p]:
            if c >= 0:
                bow.extend(int(r) for r in groups[c] if r >= 0)
        if not live[p] or not bow:  # Model::update: input.size() == 0
            continue
        trained += 1
        input_rows += len(bow)
        hidden = np.zeros(syn0.shape[1], np.float32)
        for r in bow:
            hidden += syn0[r]
        hidden *= np.float32(1.0 / len(bow))
        grad = np.zeros_like(hidden)
        for k, target in enumerate([centres[p]] + list(negs[p])):
            label = k == 0
            if not label and target == centres[p]:
                continue  # the tool draws again; here it is masked
            score = _sigmoid(float(hidden @ syn1[target]))
            a = alpha * (float(label) - score)
            grad += a * syn1[target]
            d1[target] += a * hidden
            loss -= np.log(score if label else 1.0 - score)
        for r in bow:
            d0[r] += grad
    return syn0 + d0, syn1 + d1, loss / max(trained, 1), input_rows


def random_groups(seed=4, width=G, bucket=BUCKET):
    """A seeded group table: the word's own row, then 0 to width - 1
    bucket rows (few buckets: many words share a row), -1 padded."""
    rng = np.random.default_rng(seed)
    groups = V + rng.integers(0, bucket, (V, width)).astype(np.int32)
    groups[np.arange(width)[None, :] > rng.integers(0, width, V)[:, None]] = -1
    groups[:, 0] = np.arange(V)
    return groups


def test_reference_is_the_fasttext_transcription():
    rng = np.random.default_rng(0)
    P, L = 24, 4
    syn0 = rng.normal(0, 0.3, (V + BUCKET, D)).astype(np.float32)
    syn1 = rng.normal(0, 0.3, (V + BUCKET, D)).astype(np.float32)
    groups = random_groups()
    bags = rng.integers(0, 40, (P, L)).astype(np.int32)
    bags[rng.random((P, L)) < 0.3] = -1
    bags[3] = -1  # an empty bag is skipped
    bags[5, :2] = 7  # one word twice in a bag
    centres = rng.integers(0, 40, P).astype(np.int32)
    live = (bags >= 0).any(axis=1).astype(np.float32)
    negs = rng.integers(0, 40, (P, NEG)).astype(np.int32)
    negs[2, 1] = centres[2]  # a noise word equal to the target
    want0, want1, want_loss, _ = _fasttext_cbow(
        syn0, syn1, groups, bags, centres, live, negs, 0.05)
    got0, got1, loss = cbow_subword_step(
        jnp.asarray(syn0), jnp.asarray(syn1), jnp.asarray(groups),
        jnp.asarray(bags), jnp.asarray(centres), jnp.asarray(live),
        jnp.asarray(negs), jnp.float32(0.05))
    np.testing.assert_allclose(np.asarray(got0), want0, rtol=0, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got1), want1, rtol=0, atol=2e-6)
    np.testing.assert_allclose(float(loss), want_loss, rtol=1e-5)
    assert np.abs(want0[V:] - syn0[V:]).max() > 0  # bucket rows moved
    np.testing.assert_array_equal(np.asarray(got1)[V:], syn1[V:])


def test_span_lanes_name_the_words_of_the_bags():
    ids, offsets = zipf_corpus()
    ids_d = jnp.asarray(ids)
    sent = position_sentences(jnp.asarray(offsets, jnp.int32), ids.size)
    key = jax.random.PRNGKey(3)
    lanes = bag_lanes(WINDOW)
    for pos, n_valid in ((0, ids.size), (ids.size - 40, ids.size - 7)):
        kw = dict(window=WINDOW, batch=BATCH, grid_batch=BATCH,
                  n_valid=jnp.int32(n_valid))
        c, bags, mask, live = bag_window_batch(
            ids_d, sent, jnp.int32(pos), key, jnp.uint32(0), **kw)
        c2, span, mask2, live2 = bag_span_batch(
            ids_d, sent, jnp.int32(pos), key, jnp.uint32(0), **kw)
        span = np.asarray(span)
        assert span.shape == (BATCH + 2 * WINDOW,)
        at = pos - WINDOW + np.arange(span.size)
        inside = (at >= 0) & (at < n_valid)
        np.testing.assert_array_equal(
            span, np.where(inside, ids[np.clip(at, 0, ids.size - 1)], -1))
        read = np.stack([span[WINDOW + o:WINDOW + o + BATCH] for o in lanes],
                        axis=1)
        np.testing.assert_array_equal(
            np.where(np.asarray(mask2) > 0, read, -1), np.asarray(bags))
        for a, b in ((c, c2), (mask, mask2), (live, live2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def engine(shape, groups, architecture="cbow", seed=3):
    counts = np.arange(V, 0, -1).astype(np.int64) * 3
    eng = EmbeddingEngine(make_mesh(*shape), V, D, counts, num_negatives=NEG,
                          seed=seed, extra_rows=BUCKET,
                          architecture=architecture)
    eng.upload_center_groups(groups)
    return eng


def tables(eng):
    return (np.asarray(eng.syn0, np.float32)[:, :D],
            np.asarray(eng.syn1, np.float32)[:, :D])


def run_packed(eng, corpus, seed=3, total_words=5000, window=WINDOW,
               batch=BATCH, keep=0.8, steps=K):
    before = tables(eng)
    eng.upload_corpus(*corpus)
    eng.set_keep_probs(np.full(V, keep, np.float32))
    eng.compact_corpus(jax.random.PRNGKey(9))
    out = eng.train_steps_corpus_packed(
        0, batch, window, batch, jax.random.PRNGKey(seed), steps,
        step_size=0.05, total_words=total_words)
    return before, out


def captured(eng, seed=3, total_words=5000, window=WINDOW, batch=BATCH):
    from benchmark.kinds.train_cbow import capture_bags

    cfg = {"model": {"window": window, "negatives": NEG, "step_size": 0.05},
           "run": {"batch_size": batch}}
    return capture_bags(eng, cfg, seed, K, total_words)


@pytest.mark.parametrize("shape", [(1, 1), (1, 2), (1, 4)])
def test_packed_scan_is_the_reference_in_the_sources_form(shape):
    groups = random_groups()
    eng = engine(shape, groups)
    (init0, init1), out = run_packed(eng, zipf_corpus())
    losses, counts, pos_ends, _, written = (np.asarray(a) for a in out)
    ref0, ref1, ref_losses = jnp.asarray(init0), jnp.asarray(init1), []
    slots = trained = input_rows = 0
    batches = captured(eng)
    for b in batches:
        ref0, ref1, loss = cbow_subword_step(
            ref0, ref1, jnp.asarray(groups), jnp.asarray(b["bags"]),
            jnp.asarray(b["centres"]), jnp.asarray(b["live"]),
            jnp.asarray(b["negs"]), jnp.float32(b["alpha"]))
        ref_losses.append(float(loss))
        slots += int((b["bags"] >= 0).sum())
        trained += int(b["live"].sum())
        input_rows += int((groups[b["bags"][b["bags"] >= 0]] >= 0).sum())
    prog0, prog1 = tables(eng)
    for prog, ref, init in ((prog0, ref0, init0), (prog1, ref1, init1)):
        gap, dnorm = gaps(prog, np.asarray(ref), init)
        assert gap < GAP and dnorm < DNORM_GAP, (gap, dnorm)
    np.testing.assert_allclose(losses, ref_losses, rtol=LOSS_GAP)
    assert np.abs(prog0[V:] - init0[V:]).max() > 0  # the groups trained
    assert np.array_equal(prog1[V:], init1[V:])  # syn1: word rows alone
    assert pos_ends.tolist() == [BATCH * (i + 1) for i in range(K)]
    # the device's counts: word-level CBOW's two, then live group ids
    # gathered, span words composed, input rows
    assert written.shape == (K, 9)
    assert written[:, 4].sum() == slots == counts.sum()
    assert written[:, 5].sum() == trained
    assert written[:, 8].sum() == input_rows
    n_kept = eng._n_kept
    spans = [min(BATCH * (i + 1) + WINDOW, n_kept) - max(BATCH * i - WINDOW, 0)
             for i in range(K)]
    assert written[:, 7].tolist() == spans
    words = np.asarray(eng._corpus_compacted[0])
    assert written[0, 6] == (groups[words[:spans[0]]] >= 0).sum()
    # each group row is gathered once for the five or six bags it is in
    assert input_rows > 2 * written[:, 6].sum()
    # a row kept in bfloat16 would not pass, nor the mean's true gradient,
    # every input row taking e / |I| (the grouped step of the skip-gram
    # family with the concatenated input as its group)
    from glint_word2vec_tpu.ops.grouped_reference import grouped_sgns_step

    div0, div1 = jnp.asarray(init0), jnp.asarray(init1)
    for b in batches:
        inputs = np.where((b["bags"] >= 0)[..., None], groups[b["bags"]], -1)
        div0, div1, _ = grouped_sgns_step(
            div0, div1, jnp.asarray(inputs.reshape(BATCH, -1)),
            jnp.asarray(b["centres"]), jnp.asarray(b["live"]),
            jnp.asarray(b["negs"]), jnp.float32(b["alpha"]))
    assert gaps(prog0, np.asarray(div0), init0)[0] > 10 * GAP
    import ml_dtypes

    low = prog0.astype(ml_dtypes.bfloat16).astype(np.float32)
    assert gaps(low, np.asarray(ref0), init0)[0] > 10 * GAP


def test_one_by_one_equals_one_by_two():
    groups = random_groups()

    def fit(shape, steps, seeded):
        eng = engine(shape, groups)
        if seeded:
            seed_syn1(eng)
        before, out = run_packed(eng, zipf_corpus(), steps=steps)
        return before, tables(eng), [np.asarray(a) for a in out]

    assert_two_shards_fit_as_one(fit, K)


def test_a_shared_row_is_counted_twice_and_takes_the_sum():
    """One sentence ``a b a c``: word a twice in every span, and a and b
    share a bucket row. The row is in a bag's input once for each time a
    word that owns it is in the bag, and its gradient is the sum."""
    a, b, c, shared = 5, 9, 11, V + 1
    groups = np.full((V, G), -1, np.int32)
    groups[:, 0] = np.arange(V)
    groups[a, 1:3] = [shared, V + 2]
    groups[b, 1] = shared
    groups[c, 1] = V + 3
    ids = np.array([a, b, a, c], np.int32)
    offsets = np.array([0, 4], np.int64)
    eng = engine((1, 1), groups)
    # syn1 starts at zero, and the one step this corpus fills would move
    # no row of syn0: start from seeded output rows
    rng = np.random.default_rng(6)
    eng.set_tables(
        tables(eng)[0],
        rng.normal(0, 0.3, (V + BUCKET, D)).astype(np.float32))
    (init0, init1), out = run_packed(eng, (ids, offsets), keep=1.0, batch=8)
    batches = captured(eng, batch=8)
    ref0, ref1 = init0.copy(), init1.copy()
    input_rows = 0
    for bt in batches:
        ref0, ref1, _, n = _fasttext_cbow(
            ref0, ref1, groups, bt["bags"], bt["centres"], bt["live"],
            bt["negs"], float(bt["alpha"]))
        input_rows += n
    prog0, prog1 = tables(eng)
    for prog, ref, init in ((prog0, ref0, init0), (prog1, ref1, init1)):
        gap, dnorm = gaps(prog, ref, init)
        assert gap < GAP and dnorm < 10 * DNORM_GAP, (gap, dnorm)
    written = np.asarray(out[4])
    assert written[:, 8].sum() == input_rows
    b0 = batches[0]["bags"]
    # position 1 (word b) with full reach holds a twice: 3 + 3 + 2 rows
    both = (b0[1] == a).sum() == 2
    assert both or (b0[3] == a).sum() == 2
    assert np.abs(prog0[shared] - init0[shared]).max() > 0


def test_groups_of_one_row_are_the_word_level_cbow_scan():
    """Every group cut to its word's own row (no n-gram of the range fits
    any word): the same tables, losses and counts as the word-level CBOW
    scan on the same view, within the replay's float32 limits (both sum
    a span word's gradient over its bags first; the group of two slots,
    one of them padding, is summed over its second-minor axis where the
    word's one row is not)."""
    alone = np.full((V, 2), -1, np.int32)
    alone[:, 0] = np.arange(V)
    counts = np.arange(V, 0, -1).astype(np.int64) * 3
    word = EmbeddingEngine(make_mesh(1, 1), V, D, counts, num_negatives=NEG,
                           seed=3, extra_rows=BUCKET, architecture="cbow")
    (init0, init1), out_w = run_packed(word, zipf_corpus())
    sub = engine((1, 1), alone)
    _, out_s = run_packed(sub, zipf_corpus())
    for prog, ref, init in zip(tables(sub), tables(word), (init0, init1)):
        gap, dnorm = gaps(prog, ref, init)
        assert gap < GAP and dnorm < DNORM_GAP, (gap, dnorm)
    np.testing.assert_allclose(np.asarray(out_s[0]), np.asarray(out_w[0]),
                               rtol=LOSS_GAP)
    for i in (1, 2, 3):  # live bag slots, positions, alpha: the same
        np.testing.assert_array_equal(np.asarray(out_w[i]),
                                      np.asarray(out_s[i]))
    ws, ww = np.asarray(out_s[4]), np.asarray(out_w[4])
    np.testing.assert_array_equal(ws[:, 1], ww[:, 1])  # syn1 rows written
    np.testing.assert_array_equal(ws[:, 4:6], ww[:, 4:6])
    np.testing.assert_array_equal(ws[:, 8], ws[:, 4])  # one row a word


def test_one_word_bags_are_the_subword_skipgram_step_undivided():
    """Sentences of two words, window 1, every group exactly M rows: each
    bag is the one other word, so the hidden vector is that word's group
    mean, the skip-gram subword step's centre on the swapped pair, under
    the same keys and so the same negatives. One step from the same
    tables: ``syn1`` and the loss are that step's; ``syn0`` moves M times
    as far, since every row takes the whole gradient where the skip-gram
    step hands it 1 / M of it."""
    M = 4
    rng = np.random.default_rng(5)
    groups = np.full((V, G), -1, np.int32)
    groups[:, 0] = np.arange(V)
    groups[:, 1:M] = V + rng.integers(0, BUCKET, (V, M - 1))
    ids = rng.integers(0, V, 2 * BATCH).astype(np.int32)
    offsets = (2 * np.arange(BATCH + 1)).astype(np.int64)
    cbow = engine((1, 1), groups)
    skip = engine((1, 1), None, architecture="skipgram")
    # syn1 starts at zero, which would leave syn0 where it is
    start = (tables(cbow)[0],
             rng.normal(0, 0.3, (V + BUCKET, D)).astype(np.float32))
    for eng in (cbow, skip):
        eng.set_tables(*start)
    cbow.upload_corpus(ids, offsets)
    cbow.set_keep_probs(np.ones(V, np.float32))
    cbow.compact_corpus(jax.random.PRNGKey(9))
    out = cbow.train_steps_corpus_packed(
        0, BATCH, 1, BATCH, jax.random.PRNGKey(3), 1, step_size=0.05,
        total_words=5000)
    b = captured(cbow, window=1)[0]
    assert (b["bags"] >= 0).sum(axis=1).tolist() == [1] * BATCH
    group = groups[b["bags"].max(axis=1)][None]
    losses = skip.train_steps_grouped(
        np.maximum(group, 0), (group >= 0).astype(np.float32),
        b["centres"][None, :, None], b["live"][None, :, None],
        jax.random.PRNGKey(3), np.asarray([b["alpha"]]), 0)
    (c0, c1), (s0, s1) = tables(cbow), tables(skip)
    gap, dnorm = gaps(c1, s1, start[1])
    assert gap < GAP and dnorm < DNORM_GAP, (gap, dnorm)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(losses),
                               rtol=LOSS_GAP)
    moved = s0 - start[0]
    assert np.abs(moved).max() > 0
    gap, dnorm = gaps(c0, start[0] + M * moved, start[0])
    assert gap < GAP and dnorm < DNORM_GAP, (gap, dnorm)


def _ft(**kw):
    defaults = dict(
        vector_size=12, batch_size=32, min_count=1, num_iterations=2,
        seed=7, steps_per_call=4, window=3, architecture="cbow",
        bucket=200, min_n=3, max_n=4, max_subwords=8,
    )
    defaults.update(kw)
    return FastTextWord2Vec(**defaults)


def test_fit_takes_the_corpus_resident_path_and_counts_its_bags():
    m = _ft(num_shards=2, subsample_ratio=0.01, step_size=0.05,
            num_iterations=6).fit(CORPUS)
    tm = m.training_metrics
    assert tm["pipeline"] == "device_corpus" and tm["batch_packing"] == "dense"
    assert tm["words_done"] == 6 * sum(len(s) for s in CORPUS)
    assert 1.0 < tm["cbow_rows_per_bag"] <= 6.0  # words a bag
    assert 1.0 < tm["subword_rows_per_center"] <= 8.0  # rows a word
    # rows a bag: about the product, and never under one word's
    assert tm["subword_rows_per_center"] < tm["cbow_input_rows_per_bag"] < (
        6.0 * 8.0)
    assert 0 < tm["subword_rows_per_step"] <= 8 * (32 + 2 * 3)
    assert 0.9 < tm["packed_mask_density"] <= 1.0  # positions over slots
    assert 0 < tm["scatter_distinct_share_syn0"] < 1
    assert tm["final_loss"] < tm["first_loss"]
    assert len(m.find_synonyms("dog", 3)) == 3
    assert m.transform("doggo").shape == (12,)  # an OOV word composes


def test_cbow_span_reuse_is_the_input_rows_over_the_rows_gathered():
    """How many bags read a row the step gathered, each once, for its
    span's words: the rows the bags' means are over, over the live group
    ids gathered (PERF.md read it by hand: 5.39 in the cell)."""
    tm = _ft(subsample_ratio=0.01, step_size=0.05).fit(CORPUS).training_metrics
    reuse = (tm["cbow_input_rows_per_bag"] * tm["packed_mask_density"] * 32
             / tm["subword_rows_per_step"])
    assert abs(tm["cbow_span_reuse"] - reuse) < 5e-3
    assert 1.0 < tm["cbow_span_reuse"] < tm["cbow_rows_per_bag"] + 1.0


def test_save_and_load_keep_the_architecture_and_the_geometry(tmp_path):
    from glint_word2vec_tpu.models import load_model

    m = _ft().fit(CORPUS)
    path = str(tmp_path / "model")
    m.save(path)
    with open(os.path.join(path, "params.json")) as f:
        doc = json.load(f)
    assert doc["architecture"] == "cbow"
    assert (doc["min_n"], doc["max_n"], doc["bucket"],
            doc["max_subwords"]) == (3, 4, 200, 8)
    with open(os.path.join(path, "matrix", "engine.json")) as f:
        assert json.load(f)["architecture"] == "cbow"
    loaded = load_model(path)
    assert isinstance(loaded.params, FastTextParams)
    assert loaded.params.architecture == "cbow"
    assert loaded.engine.architecture == "cbow"
    np.testing.assert_array_equal(np.asarray(loaded.engine.syn0),
                                  np.asarray(m.engine.syn0))
    np.testing.assert_allclose(loaded.transform("fox"), m.transform("fox"))
    assert ([w for w, _ in loaded.find_synonyms("dog", 3)]
            == [w for w, _ in m.find_synonyms("dog", 3)])


def test_what_stays_refused_says_so(monkeypatch):
    with pytest.raises(ValueError, match="shared_negatives"):
        _ft(shared_negatives=1024)
    with pytest.raises(ValueError, match="batch_packing"):
        _ft(batch_packing="grid")
    with pytest.raises(ValueError, match="exchange"):
        _ft(exchange="sparse")
    with pytest.raises(ValueError, match="streaming"):
        _ft().fit_stream(iter(CORPUS))
    monkeypatch.setenv("GLINT_HOST_BATCHER", "1")
    with pytest.raises(ValueError, match="host-batcher"):
        _ft().fit(CORPUS)
    with pytest.raises(ValueError, match="host-batcher"):
        _ft().fit(iter(CORPUS))
    monkeypatch.delenv("GLINT_HOST_BATCHER")
    # the engine's skip-gram entries refuse a CBOW engine that holds a
    # group table as they refuse one that does not
    eng = engine((1, 1), random_groups())
    with pytest.raises(ValueError, match="train_steps_corpus_packed"):
        eng.train_steps_grouped(
            np.zeros((1, 8, G), np.int32), np.ones((1, 8, G), np.float32),
            np.zeros((1, 8, 1), np.int32), np.ones((1, 8, 1), np.float32),
            jax.random.PRNGKey(0), np.full(1, 0.05), 0)
    eng.upload_corpus(*zipf_corpus())
    with pytest.raises(ValueError, match="train_steps_corpus_packed"):
        eng.train_steps_corpus(0, 8, 2, jax.random.PRNGKey(0), np.full(1, 0.05))


def test_the_scan_keeps_the_programs_name_and_scopes():
    eng = engine((1, 1), random_groups())
    fn = eng._make_packed_corpus_scan(BATCH, WINDOW, BATCH, 0, K, G)
    sds = jax.ShapeDtypeStruct
    i32, u32, f32 = (sds((), t) for t in (jnp.int32, jnp.uint32, jnp.float32))
    words, offs = sds((900,), jnp.int32), sds((61,), jnp.int32)
    low = fn.lower(
        sds(eng.syn0.shape, jnp.float32), sds(eng.syn1.shape, jnp.float32),
        sds((-(-V // 64), 128), jnp.int32), words, words, offs, offs, i32,
        i32, sds((2,), jnp.uint32), u32, u32, f32, f32, f32,
        sds((V, G), jnp.int32))
    assert "packed_scan" in low.as_text()
    compiled = low.compile().as_text()
    for scope in ("glint.batch", "glint.sample", "glint.gather/syn0",
                  "glint.gather/syn1", "glint.compose/group",
                  "glint.compose/bag", "glint.grads",
                  "glint.scatter/syn0", "glint.scatter/syn1"):
        assert scope in compiled, scope
    # the memo tells it from the word-level CBOW scan and from the
    # skip-gram subword scan
    skip = engine((1, 1), random_groups(), architecture="skipgram")
    key = eng._scan_memo_key("packed", BATCH, WINDOW, BATCH, 0, K, G)
    assert key != eng._scan_memo_key("packed", BATCH, WINDOW, BATCH, 0, K, 0)
    assert key != skip._scan_memo_key("packed", BATCH, WINDOW, BATCH, 0, K, G)
    # slots and exchange bytes of the new scan, from shapes
    assert eng.packed_scatter_slots(BATCH, WINDOW) == (
        G * (BATCH + 2 * WINDOW), BATCH * (1 + NEG))
    two = engine((2, 2), random_groups())
    assert two.packed_scatter_slots(BATCH, WINDOW) == (
        G * (BATCH + 2 * 2 * WINDOW), BATCH * (1 + NEG))
    # a rank's group rows and its positions' d_center, and their logits
    assert two.packed_exchange_bytes(BATCH, WINDOW) == 4 * (
        two.padded_dim * (G * (BATCH // 2 + 2 * WINDOW) + BATCH // 2)
        + (BATCH // 2) * (1 + NEG))
