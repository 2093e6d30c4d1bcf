"""CBOW with negative sampling on the corpus-resident packed scan (ISSUE 34).

* ``ops/cbow_reference.py``, the plain reference of the CBOW step, against
  a numpy transcription of ``word2vec.c``'s loop body run position by
  position with the tables frozen for the batch.
* The bags ``bag_window_batch`` forms over an epoch against a numpy
  enumeration of word2vec's window under the draws the skip-gram stream
  makes for the same positions (``grid_window_shrink``), beside that
  stream's own pairs under the same draws.
* The engine's CBOW packed scan against the reference, on the batches the
  scan drew, at 1x1, 1x2, 2x2 and the four-chip cell's 1x4; 1x1 against
  1x2.
* The scan's span form (each span row gathered once, ISSUE 42) against the
  role-swapped form it replaced and ``word2vec.c``'s loop, on those meshes.
* Bags of one context each are the skip-gram step on the swapped pair.
* The architecture is saved and loaded, an older checkpoint is a skip-gram,
  and what cannot train CBOW says so.
"""

import json
import os
import sys
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from glint_word2vec_tpu import Word2Vec  # noqa: E402
from glint_word2vec_tpu.corpus.batching import (  # noqa: E402
    context_width,
    packed_pair_batch,
    window_offsets,
)
from glint_word2vec_tpu.ops.cbow_reference import cbow_step  # noqa: E402
from glint_word2vec_tpu.ops.device_batching import (  # noqa: E402
    bag_lanes,
    bag_window_batch,
    grid_window_shrink,
    pack_window_pairs,
    position_sentences,
)
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine  # noqa: E402
from glint_word2vec_tpu.parallel.mesh import make_mesh  # noqa: E402
from glint_word2vec_tpu.utils.params import Word2VecParams  # noqa: E402

V, D, NEG, WINDOW, BATCH, K = 512, 32, 5, 5, 64, 3
# The word-level replay's float32 limits (tests/test_sharded_cell.py says
# why they hold): entry gaps over the table's largest change, change norms,
# losses.
GAP, DNORM_GAP, LOSS_GAP = 1e-4, 1e-6, 1e-6


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _word2vec_c(syn0, syn1, bags, centres, live, negs, alpha):
    """``word2vec.c``'s CBOW loop body with negative sampling, a position at
    a time, the tables frozen for the batch (its threads update in place):
    ``neu1`` the mean of the bag's rows, ``neu1e`` the summed error, added
    WHOLE to every context word."""
    d0, d1 = np.zeros_like(syn0), np.zeros_like(syn1)
    loss, trained = 0.0, 0
    for p in range(centres.shape[0]):
        ctx = [c for c in bags[p] if c >= 0]
        cw = len(ctx)
        if not live[p] or cw == 0:
            continue
        trained += 1
        word = centres[p]
        neu1 = np.zeros(syn0.shape[1], np.float32)
        for c in ctx:
            neu1 += syn0[c]
        neu1 /= np.float32(cw)
        neu1e = np.zeros_like(neu1)
        for dneg in range(negs.shape[1] + 1):
            if dneg == 0:
                target, label = word, 1.0
            else:
                target, label = negs[p, dneg - 1], 0.0
                if target == word:
                    continue
            f = float(neu1 @ syn1[target])
            g = (label - _sigmoid(f)) * alpha
            loss -= np.log(_sigmoid(f if label else -f))
            neu1e += g * syn1[target]
            d1[target] += g * neu1
        for c in ctx:
            d0[c] += neu1e
    return syn0 + d0, syn1 + d1, loss / max(trained, 1)


def test_cbow_reference_is_the_word2vec_c_transcription():
    rng = np.random.default_rng(0)
    rows, positions, hot = 80, 48, 30
    syn0 = rng.normal(0, 0.1, (rows, 8)).astype(np.float32)
    syn1 = rng.normal(0, 0.1, (rows, 8)).astype(np.float32)
    bags = rng.integers(0, hot, (positions, 6)).astype(np.int32)
    bags[rng.random((positions, 6)) < 0.4] = -1
    bags[3] = -1  # an empty bag: skipped
    centres = rng.integers(0, hot, positions).astype(np.int32)
    negs = rng.integers(0, hot, (positions, 3)).astype(np.int32)
    negs[::5, 1] = centres[::5]  # a noise word equal to the position's word
    live = ((bags >= 0).any(axis=1) & (rng.random(positions) > 0.1)).astype(
        np.float32)
    new0, new1, loss = cbow_step(
        jnp.asarray(syn0), jnp.asarray(syn1), jnp.asarray(bags),
        jnp.asarray(centres), jnp.asarray(live), jnp.asarray(negs),
        jnp.float32(0.05))
    exp0, exp1, exp_loss = _word2vec_c(
        syn0, syn1, bags, centres, live, negs, 0.05)
    np.testing.assert_allclose(np.asarray(new0), exp0, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(new1), exp1, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(float(loss), exp_loss, rtol=1e-5)
    # the gradient is NOT divided by the bag's size: a bag of 4 moves each
    # of its rows as far as a bag of 1 would
    assert np.abs(exp0 - syn0).max() > 0


def zipf_corpus(seed=1, sentences=60):
    rng = np.random.default_rng(seed)
    lens = rng.integers(1, 30, sentences)
    p = 1.0 / np.arange(1, V + 1)
    ids = rng.choice(V, size=int(lens.sum()), p=p / p.sum()).astype(np.int32)
    return ids, np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)


@pytest.mark.parametrize("window", [1, 2, 5])
def test_bags_of_an_epoch_are_word2vecs_window_under_the_skipgram_draws(
        window):
    """Over an epoch the bags hold, for every position p, the positions
    within ``window - b_p`` of p in p's sentence, where ``b_p`` is the draw
    the skip-gram packed stream makes for p under the same key schedule.
    Both streams are read here under the same key, each against a numpy
    enumeration of its own rule over the same draws: the bags' (centre,
    context) multiset is word2vec.c's symmetric window, the pair stream's
    the reference's half-open [-b, b). (ISSUE 34 also asks that the two
    multisets be EQUAL; they cannot be, the windows differ: CHANGES.md.)"""
    ids, offsets = zipf_corpus(sentences=25)
    N, B, key = len(ids), 16, jax.random.PRNGKey(7)
    offs32 = jnp.asarray(offsets, jnp.int32)
    b = np.asarray(grid_window_shrink(
        key, jnp.arange(N, dtype=jnp.int32), B, jnp.uint32(0), window))
    sent = np.searchsorted(offsets, np.arange(N), side="right") - 1

    def enumerate_pairs(in_window):
        pairs = Counter()
        for p in range(N):
            for q in range(max(0, p - window), min(N, p + window + 1)):
                if q != p and sent[q] == sent[p] and in_window(q - p, b[p]):
                    pairs[(int(ids[p]), int(ids[q]))] += 1
        return pairs

    sent_of = position_sentences(offs32, N)
    draw = jax.jit(lambda pos: bag_window_batch(
        jnp.asarray(ids), sent_of, pos, key, jnp.uint32(0), window=window,
        batch=B, grid_batch=B, n_valid=jnp.int32(N)))
    bags, trained = Counter(), 0
    for pos in range(0, N + B, B):  # one step past the end: nothing live
        centres, bag, mask, live = (np.asarray(a) for a in draw(jnp.int32(pos)))
        assert bag.shape == (B, 2 * window) == mask.shape
        assert ((bag >= 0) == (mask > 0)).all()
        assert (live == mask.any(axis=1)).all()
        trained += int(live.sum())
        for i in range(B):
            for lane in range(2 * window):
                if mask[i, lane]:
                    bags[(int(centres[i]), int(bag[i, lane]))] += 1
    assert bags == enumerate_pairs(lambda o, bp: abs(o) <= window - bp)
    # every position of a sentence of two words and more has a neighbour
    # within reach 1, so it trains; a one-word sentence never does
    lens = np.diff(offsets)
    assert trained == int(lens[lens > 1].sum())
    assert bag_lanes(window) == [o for o in range(-window, window + 1) if o]

    # the skip-gram packed stream under the same key: its own lanes
    if window > 1:
        P = packed_pair_batch(B, window, 1)
        span = -(-3 * P // context_width(window))
        pack = jax.jit(lambda pos: pack_window_pairs(
            jnp.asarray(ids), offs32, pos, key, jnp.uint32(0), window=window,
            span=span, pair_batch=P, grid_batch=B, n_valid=jnp.int32(N),
            sent_of=sent_of))
        stream, pos = Counter(), 0
        while pos < N:
            pc, px, _, n_cons, n_pairs = pack(jnp.int32(pos))
            pc, px = np.asarray(pc), np.asarray(px)
            for j in range(int(n_pairs)):
                stream[(int(pc[j]), int(px[j]))] += 1
            pos += int(n_cons)
        lanes = set(window_offsets(window).tolist())
        assert stream == enumerate_pairs(
            lambda o, bp: o in lanes and -bp <= o <= bp - 1)


# The meshes the CBOW scan is held on; the last is the four-chip cell's.
MESHES = [(1, 1), (1, 2), (2, 2), (1, 4)]


def engine(shape, architecture="cbow", seed=3):
    counts = np.arange(V, 0, -1).astype(np.int64) * 3
    return EmbeddingEngine(make_mesh(*shape), V, D, counts, num_negatives=NEG,
                           seed=seed, architecture=architecture)


def tables(eng):
    return (np.asarray(eng.syn0, np.float32)[:, :D],
            np.asarray(eng.syn1, np.float32)[:, :D])


def run_packed(eng, corpus, seed=3, total_words=5000, window=WINDOW,
               batch=BATCH, keep=0.8, steps=K):
    """``steps`` CBOW steps from the seed's tables over the compacted view;
    returns (tables before, the scan's per-step outputs)."""
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


def gaps(prog, ref, init):
    """The replay's numbers (benchmark/reference.replay_gaps), over whole
    tables: largest entry gap over the largest change; change-norm gap."""
    change = np.abs(ref - init).max()
    d_prog = np.sqrt(np.square((prog - init).astype(np.float64)).sum())
    d_ref = np.sqrt(np.square((ref - init).astype(np.float64)).sum())
    return np.abs(prog - ref).max() / change, abs(d_prog - d_ref) / d_ref


@pytest.mark.parametrize("shape", MESHES)
def test_packed_cbow_scan_is_the_reference(shape):
    eng = engine(shape)
    (init0, init1), out = run_packed(eng, zipf_corpus())
    losses, counts, pos_ends, _, written = (np.asarray(a) for a in out)
    ref0, ref1, ref_losses = jnp.asarray(init0), jnp.asarray(init1), []
    slots = trained = 0
    batches = captured(eng)
    for b in batches:
        ref0, ref1, loss = cbow_step(
            ref0, ref1, jnp.asarray(b["bags"]), jnp.asarray(b["centres"]),
            jnp.asarray(b["live"]), jnp.asarray(b["negs"]),
            jnp.float32(b["alpha"]))
        ref_losses.append(float(loss))
        slots += int((b["bags"] >= 0).sum())
        trained += int(b["live"].sum())
    prog0, prog1 = tables(eng)
    for prog, ref, init in ((prog0, ref0, init0), (prog1, ref1, init1)):
        gap, dnorm = gaps(prog, np.asarray(ref), init)
        assert gap < GAP and dnorm < DNORM_GAP, (gap, dnorm)
    np.testing.assert_allclose(losses, ref_losses, rtol=LOSS_GAP)
    # a static advance of BATCH positions a step
    assert pos_ends.tolist() == [BATCH * (i + 1) for i in range(K)]
    # the device's counts: live bag slots (also the step's pair count) and
    # the positions that trained
    assert written.shape == (K, 6)
    assert written[:, 4].sum() == slots == counts.sum()
    assert written[:, 5].sum() == trained
    assert 2.0 < slots / trained <= 2 * WINDOW
    # syn0 wrote the bags' distinct words, syn1 the centres and negatives
    b0 = batches[0]
    assert written[0, 0] == np.unique(b0["bags"][b0["bags"] >= 0]).size
    # a row kept in bfloat16 would not pass
    import ml_dtypes

    low = prog0.astype(ml_dtypes.bfloat16).astype(np.float32)
    assert gaps(low, np.asarray(ref0), init0)[0] > 10 * GAP
    # nor would the mean's true gradient, the bag's rows taking e / |C|
    # (the grouped step of the subword family with the roles swapped)
    from glint_word2vec_tpu.ops.grouped_reference import grouped_sgns_step

    div0, div1 = jnp.asarray(init0), jnp.asarray(init1)
    for b in batches:
        div0, div1, _ = grouped_sgns_step(
            div0, div1, jnp.asarray(b["bags"]), jnp.asarray(b["centres"]),
            jnp.asarray(b["live"]), jnp.asarray(b["negs"]),
            jnp.float32(b["alpha"]))
    assert gaps(prog0, np.asarray(div0), init0)[0] > 10 * GAP


@pytest.mark.parametrize("shape", MESHES)
def test_the_span_form_is_the_role_swapped_form(shape):
    """ISSUE 42: the scan names a bag's words by where they stand in the
    step's span, gathers each span row once and sums a row's gradient over
    its bags before the scatter. Held here to the form it replaced, the
    step body with each position's bag (``bag_window_batch``) as its group
    and no ``lanes``, and to ``word2vec.c``'s loop in numpy, on the same
    draws. Only the order of two float32 sums differs."""
    span = engine(shape)
    (init0, init1), out = run_packed(span, zipf_corpus())
    losses, counts, _, alphas, written = (np.asarray(a) for a in out)
    batches = captured(span)
    swapped = engine(shape)
    for a, b in zip(tables(swapped), (init0, init1)):
        np.testing.assert_array_equal(a, b)
    c0, c1 = init0.astype(np.float32), init1.astype(np.float32)
    key, swapped_losses, c_losses = jax.random.PRNGKey(3), [], []
    for i, b in enumerate(batches):
        swapped.syn0, swapped.syn1, loss = swapped._train_step(
            swapped.syn0, swapped.syn1, swapped._alias_packed,
            jnp.asarray(b["bags"]),
            jnp.asarray(b["bags"] >= 0, jnp.float32),
            jnp.asarray(b["centres"])[:, None],
            jnp.asarray(b["live"], jnp.float32)[:, None],
            jax.random.fold_in(key, jnp.uint32(i)), jnp.float32(b["alpha"]))
        swapped_losses.append(float(loss))
        c0, c1, loss = _word2vec_c(
            c0, c1, b["bags"], b["centres"], b["live"], b["negs"],
            np.float32(b["alpha"]))
        c_losses.append(loss)
    np.testing.assert_array_equal(alphas, [b["alpha"] for b in batches])
    for prog, ref, c, init in zip(tables(span), tables(swapped), (c0, c1),
                                  (init0, init1)):
        # within 1e-6, entry by entry of the table's scale and as the
        # difference's norm over the change's (1.2e-7, one ulp of the
        # largest entry, and 4.7e-7 here)
        assert np.abs(prog - ref).max() < 1e-6 * np.abs(ref).max()
        assert np.sqrt(np.square(prog - ref).sum()
                       / np.square(ref - init).sum()) < 1e-6
        gap, dnorm = gaps(prog, c, init)
        assert gap < GAP and dnorm < DNORM_GAP, (gap, dnorm)
    # one float32 ulp
    np.testing.assert_allclose(losses, swapped_losses, rtol=2.0 ** -23)
    np.testing.assert_allclose(losses, c_losses, rtol=LOSS_GAP)
    # the counts are the bags': live bag slots, positions trained
    slots = [int((b["bags"] >= 0).sum()) for b in batches]
    trained = [int(b["live"].sum()) for b in batches]
    assert written[:, 4].tolist() == slots == counts.tolist()
    assert written[:, 5].tolist() == trained
    # and a span word no bag reads is no slot of the scatter: syn0 wrote
    # the bags' distinct words, step by step
    assert written[:, 0].tolist() == [
        np.unique(b["bags"][b["bags"] >= 0]).size for b in batches]


def test_the_benchmarks_enumeration_holds_the_bags_and_the_device_counts():
    """The CBOW cell's own check of the bags (``kinds/train_cbow``): the
    window rule in numpy from the compacted view's words and sentence
    offsets, given the draws. It equals the redrawn bags and what the scan
    counted on its device; a bag that reaches past its sentence, or a count
    that is off by one, reads as a fault."""
    from benchmark.kinds.train_cbow import bag_faults

    eng = engine((1, 2))
    _, out = run_packed(eng, zipf_corpus())
    batches = captured(eng)
    counted = np.asarray(out[4])[:, 4:6]
    assert bag_faults(eng, batches, WINDOW, counted) == (0, 0)
    # the last group alone is what a longer replay would hand over
    assert bag_faults(eng, batches, WINDOW, counted[-1:]) == (0, 0)
    sentences = np.asarray(eng._corpus_compacted[1])
    ends = sentences[(sentences > 0) & (sentences < BATCH)]
    p = int(ends[0]) - 1  # the last word of a sentence in step 0
    wrong = [dict(b) for b in batches]
    wrong[0]["bags"] = wrong[0]["bags"].copy()
    assert wrong[0]["bags"][p, WINDOW] == -1  # lane +1: the next sentence's
    wrong[0]["bags"][p, WINDOW] = wrong[0]["centres"][p + 1]
    assert bag_faults(eng, wrong, WINDOW, counted) == (1, 0)
    off = counted.copy()
    off[1, 0] += 1
    assert bag_faults(eng, batches, WINDOW, off) == (0, 1)


def seed_syn1(eng):
    """Seeded output rows in place of the zeros a fit starts from, under
    which the first step's logits are all 0 and its ``d_center`` too."""
    rows = np.random.default_rng(6).normal(0, 0.3, (eng.num_rows, D))
    eng.set_tables(tables(eng)[0], rows.astype(np.float32))


def assert_two_shards_fit_as_one(fit, steps):
    """``fit(shape, steps, seeded)`` runs packed steps on a mesh of
    ``shape`` (from :func:`seed_syn1`'s rows where ``seeded``) and returns
    (tables before, tables after, the scan's outputs, the losses first).

    On several shards a pair's logit is its owner's ``h . u`` (the others'
    terms are products with zeros), so the logits, the coefficients, the
    loss and what a step writes to ``syn1`` are the one shard's BITS for as
    long as the tables are: step 0. ``d_center``'s terms are summed by
    owner first and across the shards second (ISSUE 51: no ``syn1`` row
    crosses the model axis), so ``syn0``, and every later step with it, is
    the one shard's at the replay's limits, no longer to the bit."""
    for n, seeded in ((1, True), (steps, False)):
        (init, one, one_out), (_, two, two_out) = (
            fit(shape, n, seeded) for shape in ((1, 1), (1, 2)))
        losses, *rest = zip(one_out, two_out)
        assert losses[0][0] == losses[1][0]
        np.testing.assert_allclose(losses[1], losses[0], rtol=LOSS_GAP)
        for a, b in rest:  # positions and counts: whole numbers
            np.testing.assert_array_equal(a, b)
        if n == 1:
            np.testing.assert_array_equal(one[1], two[1])
        for before, a, b in zip(init, one, two):
            gap, dnorm = gaps(b, a, before)
            assert gap < GAP and dnorm < DNORM_GAP, (n, gap, dnorm)
        assert np.abs(one[0] - init[0]).max() > 0


def test_one_by_one_equals_one_by_two():
    def fit(shape, steps, seeded):
        eng = engine(shape)
        if seeded:
            seed_syn1(eng)
        before, out = run_packed(eng, zipf_corpus(), steps=steps)
        return before, tables(eng), [np.asarray(a) for a in out[:4]]

    assert_two_shards_fit_as_one(fit, K)


def test_one_context_bags_are_the_skipgram_step_on_the_swapped_pair():
    """Sentences of two words, window 1: every bag is the one other word
    (|C| = 1, so the mean is the row and dividing the gradient by |C|
    changes nothing). The CBOW scan is then the skip-gram step with the
    pair swapped: centre = the bag's word, context = the position's word,
    under the same keys and so the same negatives."""
    rng = np.random.default_rng(5)
    n_sent = BATCH * K // 2
    ids = rng.integers(0, V, 2 * n_sent).astype(np.int32)
    offsets = (2 * np.arange(n_sent + 1)).astype(np.int64)
    cbow = engine((1, 1))
    init = tables(cbow)
    _, out = run_packed(cbow, (ids, offsets), window=1, keep=1.0)
    batches = captured(cbow, window=1)
    assert all((b["bags"] >= 0).sum(axis=1).tolist() == [1] * BATCH
               for b in batches)
    skip = engine((1, 1), architecture="skipgram")
    for a, b in zip(tables(skip), init):
        np.testing.assert_array_equal(a, b)
    losses = skip.train_steps(
        np.stack([b["bags"].max(axis=1) for b in batches]),
        np.stack([b["centres"] for b in batches])[:, :, None],
        np.stack([b["live"] for b in batches])[:, :, None],
        jax.random.PRNGKey(3), np.asarray([b["alpha"] for b in batches]), 0)
    for prog, ref, start in zip(tables(cbow), tables(skip), init):
        gap, dnorm = gaps(prog, ref, start)
        assert gap < GAP and dnorm < DNORM_GAP, (gap, dnorm)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(losses),
                               rtol=LOSS_GAP)


CORPUS = [
    "the quick brown fox jumps over the lazy dog".split(),
    "the dog sleeps all day long in the sun".split(),
    "a quick fox and a lazy dog meet in the field".split(),
    "the sun rises over the field every day".split(),
] * 30


def _w2v(**kw):
    defaults = dict(
        vector_size=12, batch_size=32, min_count=1, num_iterations=2,
        seed=7, steps_per_call=4, window=3, architecture="cbow",
    )
    defaults.update(kw)
    return Word2Vec(**defaults)


def test_cbow_fit_takes_the_corpus_resident_path_and_counts_its_bags():
    m = _w2v(num_shards=2, subsample_ratio=0.01, step_size=0.05,
             num_iterations=6).fit(CORPUS)
    tm = m.training_metrics
    assert tm["pipeline"] == "device_corpus" and tm["batch_packing"] == "dense"
    assert tm["words_done"] == 6 * sum(len(s) for s in CORPUS)
    assert 1.0 < tm["cbow_rows_per_bag"] <= 6.0
    assert 0.9 < tm["packed_mask_density"] <= 1.0  # positions over slots
    assert tm["packed_pairs"] > tm["steps"] * 32  # live bag slots
    assert 0 < tm["scatter_distinct_share_syn0"] < 1
    assert "subword_rows_per_center" not in tm
    assert tm["final_loss"] < tm["first_loss"]
    assert len(m.find_synonyms("dog", 3)) == 3


@pytest.mark.parametrize("shards,partitions", [(1, 1), (2, 1), (1, 2)])
def test_cbow_span_reuse_is_the_live_bag_slots_over_the_span_rows(
        shards, partitions):
    """How many bags read a row the step gathered: live bag slots over the
    words of every rank's span (its 32 / ranks positions and their reach,
    2 x 3), a live step. From the counts the scan already returns."""
    m = _w2v(num_shards=shards, num_partitions=partitions,
             subsample_ratio=0.01, step_size=0.05).fit(CORPUS)
    tm = m.training_metrics
    span = 32 + 2 * 3 * partitions
    assert m.engine.packed_scatter_slots(32, 3) == (span, 32 * (1 + 5))
    reuse = tm["cbow_rows_per_bag"] * tm["packed_mask_density"] * 32 / span
    assert abs(tm["cbow_span_reuse"] - reuse) < 2e-3
    assert 1.0 < tm["cbow_span_reuse"] < tm["cbow_rows_per_bag"]
    # syn0's slots are the span's words, not the bags' lanes: most of them
    # are distinct rows some bag read
    assert 0.3 < tm["scatter_distinct_share_syn0"] <= 1.0


def test_save_and_load_keep_the_architecture(tmp_path, monkeypatch):
    from glint_word2vec_tpu.models import load_model

    m = _w2v().fit(CORPUS)
    path = str(tmp_path / "model")
    m.save(path)
    for name in ("params.json", os.path.join("matrix", "engine.json")):
        with open(os.path.join(path, name)) as f:
            assert json.load(f)["architecture"] == "cbow", name
    loaded = load_model(path)
    assert loaded.params.architecture == "cbow"
    assert loaded.engine.architecture == "cbow"
    np.testing.assert_array_equal(np.asarray(loaded.engine.syn0),
                                  np.asarray(m.engine.syn0))
    # a model saved before the parameter existed is a skip-gram
    old_meta = EmbeddingEngine._save_meta

    def meta_without(self, mode):
        meta = old_meta(self, mode)
        del meta["architecture"]
        return meta

    monkeypatch.setattr(EmbeddingEngine, "_save_meta", meta_without)
    path = str(tmp_path / "older")
    _w2v(architecture="skipgram").fit(CORPUS).save(path)
    monkeypatch.undo()
    with open(os.path.join(path, "params.json")) as f:
        doc = json.load(f)
    del doc["architecture"]
    with open(os.path.join(path, "params.json"), "w") as f:
        json.dump(doc, f)
    with open(os.path.join(path, "matrix", "engine.json")) as f:
        assert "architecture" not in json.load(f)
    old = load_model(path)
    assert old.params.architecture == "skipgram"
    assert old.engine.architecture == "skipgram"
    assert json.loads(Word2VecParams().to_json())["architecture"] == "skipgram"


def test_what_cannot_train_cbow_says_so(monkeypatch):
    from glint_word2vec_tpu.models.fasttext import FastTextWord2Vec

    with pytest.raises(ValueError, match="architecture"):
        Word2Vec(architecture="hierarchical")
    with pytest.raises(ValueError, match="shared_negatives"):
        Word2Vec(architecture="cbow", shared_negatives=1024)
    with pytest.raises(ValueError, match="batch_packing"):
        Word2Vec(architecture="cbow", batch_packing="grid")
    with pytest.raises(ValueError, match="exchange"):
        Word2Vec(architecture="cbow", exchange="sparse")
    # the subword family trains it too (tests/test_cbow_subword.py)
    assert FastTextWord2Vec(
        architecture="cbow", bucket=100).params.architecture == "cbow"
    with pytest.raises(ValueError, match="streaming"):
        _w2v().fit_stream(iter(CORPUS))
    # a fit the corpus-resident path does not take is refused, not routed
    # to the host batcher
    monkeypatch.setenv("GLINT_HOST_BATCHER", "1")
    with pytest.raises(ValueError, match="host-batcher"):
        _w2v().fit(CORPUS)
    with pytest.raises(ValueError, match="host-batcher"):
        _w2v().fit(iter(CORPUS))
    monkeypatch.delenv("GLINT_HOST_BATCHER")
    # the engine's skip-gram entries refuse a CBOW engine, and a CBOW
    # engine a shared pool
    eng = engine((1, 1))
    with pytest.raises(ValueError, match="train_steps_corpus_packed"):
        eng.train_steps(np.zeros((1, 8), np.int32),
                        np.zeros((1, 8, 1), np.int32),
                        np.ones((1, 8, 1), np.float32),
                        jax.random.PRNGKey(0), np.full(1, 0.05), 0)
    eng.upload_corpus(*zipf_corpus())
    with pytest.raises(ValueError, match="train_steps_corpus_packed"):
        eng.train_steps_corpus(0, 8, 2, jax.random.PRNGKey(0), np.full(1, 0.05))
    with pytest.raises(ValueError, match="shared_negatives"):
        EmbeddingEngine(make_mesh(1, 1), V, D, np.ones(V, np.int64),
                        shared_negatives=64, architecture="cbow")


def lowered(eng):
    """The CBOW packed scan an engine builds, lowered."""
    from jax.sharding import NamedSharding, PartitionSpec

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype,
            sharding=NamedSharding(eng.mesh, PartitionSpec(*spec)))

    table = sds(eng.syn0.shape, jnp.float32, *eng.syn0.sharding.spec)
    i32, u32, f32 = (sds((), t) for t in (jnp.int32, jnp.uint32, jnp.float32))
    words, offs = sds((900,), jnp.int32), sds((61,), jnp.int32)
    return eng._make_packed_corpus_scan(BATCH, WINDOW, BATCH, 0, K).lower(
        table, table, sds((-(-V // 64), 128), jnp.int32), words, words, offs,
        offs, i32, i32, sds((2,), jnp.uint32), u32, u32, f32, f32, f32)


# sha256[:16] of the lowered word-level CBOW scan's StableHLO text, by mesh
# and split, as tests/test_subword_packed.py holds the skip-gram scans':
# taken in ISSUE 42, which gave this scan the span form (CHANGES.md has the
# role-swapped form's); the skip-gram scans' and fastText's CBOW scan's
# stayed; taken again in ISSUE 44 with every packed scan's (a group's steps
# run in a `while` that stops at the corpus end; CHANGES.md has ISSUE 42's).
# ISSUE 46 deleted the `dims` engine and its 1 x 2 entry; the 1 x 4 mesh's
# was taken in its place on that issue's parent (7daf58c) and on its tree:
# the same. ISSUE 49 changed the step on meshes whose model axis has several
# shards (tests/test_subword_packed.py says how): those three entries were
# taken again on its tree (CHANGES.md has the old ones), (1, 1) is as it was.
# ISSUE 51 changed the same three again (the pair side sends logits and
# d_center, no syn1 row): taken again on its tree, (1, 1) as it was.
# A word-level CBOW fit must lower to the program it lowered to.
CBOW_PROGRAMS = {
    ((1, 1), "rows"): "c8731cfde68643af",
    ((1, 2), "rows"): "5ba78a0944e12514",
    ((2, 2), "rows"): "79c54474240d0be5",
    ((1, 4), "rows"): "bb443c9d949746cd",
}


@pytest.mark.parametrize("shape,split", sorted(CBOW_PROGRAMS))
def test_a_word_level_cbow_fit_lowers_to_the_program_it_lowered_to(
        shape, split):
    import hashlib

    eng = engine(shape)
    assert eng.step_body.split("/")[0] == split
    low = lowered(eng)
    assert (hashlib.sha256(low.as_text().encode()).hexdigest()[:16]
            == CBOW_PROGRAMS[(shape, split)])


def test_the_cbow_scan_keeps_the_programs_name_and_scopes():
    eng = engine((1, 1))
    low = lowered(eng)
    assert "packed_scan" in low.as_text()
    compiled = low.compile().as_text()
    for scope in ("glint.batch", "glint.sample", "glint.gather/syn0",
                  "glint.gather/syn1", "glint.compose", "glint.grads",
                  "glint.scatter/syn0", "glint.scatter/syn1"):
        assert scope in compiled, scope
    # the memo tells the architectures apart
    skip = engine((1, 1), architecture="skipgram")
    assert (eng._scan_memo_key("packed", 1) != skip._scan_memo_key("packed", 1))
