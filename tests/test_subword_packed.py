"""The subword family on the corpus-resident packed scan (ISSUE 31).

* ``ops/grouped_reference.py``, the plain reference of the grouped step,
  against a straight-line numpy transcription (as ``tests/test_sgns.py::
  _numpy_oracle`` is for the word-level step).
* The engine's packed scan with a group table on the device against that
  reference, on the batches the scan drew, at 1x1 and 1x2; 1x1 against 1x2.
* Groups of one word each give the word-level scan's tables bit for bit.
* The per-run centre side against a per-pair expansion of the same steps.
* A word-level engine lowers to the program it lowered to before the
  subword path existed (fingerprints taken on commit 7dbd80a).
"""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from test_cbow import assert_two_shards_fit_as_one, seed_syn1  # noqa: E402

from glint_word2vec_tpu.corpus.batching import (  # noqa: E402
    context_width,
    packed_pair_batch,
)
from glint_word2vec_tpu.ops.device_batching import center_runs  # noqa: E402
from glint_word2vec_tpu.ops.grouped_reference import (  # noqa: E402
    grouped_sgns_step,
)
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine  # noqa: E402
from glint_word2vec_tpu.parallel.mesh import make_mesh  # noqa: E402

V, BUCKET, D, NEG, WINDOW, BATCH, K, G = 512, 300, 32, 5, 5, 64, 3, 8
PAIRS = packed_pair_batch(BATCH, WINDOW, 1)
# The word-level replay's float32 limits (tests/test_sharded_cell.py says
# why they hold): entry gaps over the table's largest change, change norms,
# losses.
GAP, DNORM_GAP, LOSS_GAP = 1e-4, 1e-6, 1e-6


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _numpy_oracle(syn0, syn1, groups, contexts, mask, negs, alpha):
    """Straight-line per-pair transcription of the grouped SGNS update."""
    d0, d1 = np.zeros_like(syn0), np.zeros_like(syn1)
    loss = 0.0
    for p in range(contexts.shape[0]):
        if mask[p] == 0:
            continue
        rows = [r for r in groups[p] if r >= 0]
        h = sum(syn0[r] for r in rows) / np.float32(len(rows))
        ctx = contexts[p]
        f = float(h @ syn1[ctx])
        g = alpha * (1.0 - _sigmoid(f))
        loss -= np.log(_sigmoid(f))
        d1[ctx] += g * h
        d = g * syn1[ctx]
        for neg in negs[p]:
            if neg == ctx:
                continue
            fn = float(h @ syn1[neg])
            gn = -alpha * _sigmoid(fn)
            loss -= np.log(_sigmoid(-fn))
            d1[neg] += gn * h
            d = d + gn * syn1[neg]
        for r in rows:
            d0[r] += d / np.float32(len(rows))
    return syn0 + d0, syn1 + d1, loss / max(mask.sum(), 1.0)


def test_grouped_reference_is_the_numpy_transcription():
    rng = np.random.default_rng(0)
    rows, pairs, hot = 80, 48, 30
    syn0 = rng.normal(0, 0.1, (rows, 8)).astype(np.float32)
    syn1 = rng.normal(0, 0.1, (rows, 8)).astype(np.float32)
    groups = rng.integers(0, hot, (pairs, 6)).astype(np.int32)
    groups[rng.random((pairs, 6)) < 0.4] = -1
    groups[:, 0] = rng.integers(0, hot, pairs)  # never an empty group
    contexts = rng.integers(0, hot, pairs).astype(np.int32)
    negs = rng.integers(0, hot, (pairs, 3)).astype(np.int32)
    negs[::5, 1] = contexts[::5]  # a negative equal to its context
    mask = (rng.random(pairs) > 0.2).astype(np.float32)
    new0, new1, loss = grouped_sgns_step(
        jnp.asarray(syn0), jnp.asarray(syn1), jnp.asarray(groups),
        jnp.asarray(contexts), jnp.asarray(mask), jnp.asarray(negs),
        jnp.float32(0.05))
    exp0, exp1, exp_loss = _numpy_oracle(
        syn0, syn1, groups, contexts, mask, negs, 0.05)
    np.testing.assert_allclose(np.asarray(new0), exp0, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(new1), exp1, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(float(loss), exp_loss, rtol=1e-5)


def test_center_runs_groups_the_pair_list_by_centre():
    pc = jnp.asarray([7, 7, 3, 3, 3, 7, 0, 0, 0, 0], jnp.int32)
    pm = jnp.asarray([1, 1, 1, 1, 1, 1, 1, 0, 0, 0], jnp.float32)
    run_c, pair_run, live = (np.asarray(a) for a in center_runs(pc, pm, 6))
    assert pair_run.tolist() == [0, 0, 1, 1, 1, 2, 3, 3, 3, 3]
    assert run_c.tolist() == [7, 3, 7, 0, 0, 0]
    # the last live pair (centre 0) shares its run with the padding
    assert live.tolist() == [True, True, True, True, False, False]
    _, _, dead = center_runs(pc, jnp.zeros(10), 6)
    assert not np.asarray(dead).any()


def zipf_corpus(seed=1, sentences=60):
    rng = np.random.default_rng(seed)
    lens = rng.integers(3, 30, sentences)
    p = 1.0 / np.arange(1, V + 1)
    ids = rng.choice(V, size=int(lens.sum()), p=p / p.sum()).astype(np.int32)
    return ids, np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)


def cyclic_corpus(sentences=60):
    """Words 0, 1, 2, ... in turn: no step's span holds a word twice."""
    lens = np.random.default_rng(2).integers(3, 30, sentences)
    ids = (np.arange(int(lens.sum())) % V).astype(np.int32)
    return ids, np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)


def random_groups(seed=4):
    """A seeded group table: the word's own row, then 0 to G - 1 bucket
    rows (few buckets: many words share a row), -1 padded."""
    rng = np.random.default_rng(seed)
    groups = V + rng.integers(0, BUCKET, (V, G)).astype(np.int32)
    groups[np.arange(G)[None, :] > rng.integers(0, G, V)[:, None]] = -1
    groups[:, 0] = np.arange(V)
    return groups


def engine(shape, groups=None, bucket=BUCKET, seed=3):
    counts = np.arange(V, 0, -1).astype(np.int64) * 3
    eng = EmbeddingEngine(make_mesh(*shape), V, D, counts, num_negatives=NEG,
                          seed=seed, extra_rows=bucket)
    eng.upload_center_groups(groups)
    return eng


def run_packed(eng, corpus, seed=3, total_words=5000, steps=K):
    """``steps`` packed steps from the seed's tables; returns (tables
    before, the scan's per-step outputs)."""
    before = (np.asarray(eng.syn0, np.float32)[:, :D],
              np.asarray(eng.syn1, np.float32)[:, :D])
    eng.upload_corpus(*corpus)
    eng.set_keep_probs(np.ones(V, np.float32))
    eng.compact_corpus(jax.random.PRNGKey(9))
    out = eng.train_steps_corpus_packed(
        0, PAIRS, WINDOW, BATCH, jax.random.PRNGKey(seed), steps,
        step_size=0.025, total_words=total_words)
    return before, out


def tables(eng):
    return (np.asarray(eng.syn0, np.float32)[:, :D],
            np.asarray(eng.syn1, np.float32)[:, :D])


def captured(eng, seed=3, total_words=5000):
    from benchmark.kinds.train import capture_batches

    cfg = {"model": {"window": WINDOW, "negatives": NEG, "step_size": 0.025},
           "run": {"batch_size": BATCH}}
    return capture_batches(eng, cfg, seed, K, total_words)


def gaps(prog, ref, init):
    """The replay's numbers (benchmark/reference.replay_gaps), over whole
    tables: largest entry gap over the largest change; change-norm gap."""
    change = np.abs(ref - init).max()
    d_prog = np.sqrt(np.square((prog - init).astype(np.float64)).sum())
    d_ref = np.sqrt(np.square((ref - init).astype(np.float64)).sum())
    return np.abs(prog - ref).max() / change, abs(d_prog - d_ref) / d_ref


@pytest.mark.parametrize("shape", [(1, 1), (1, 2)])
def test_packed_subword_scan_is_the_grouped_reference(shape):
    groups = random_groups()
    eng = engine(shape, groups)
    (init0, init1), out = run_packed(eng, zipf_corpus())
    losses, written = np.asarray(out[0]), np.asarray(out[4])
    ref0, ref1, ref_losses = jnp.asarray(init0), jnp.asarray(init1), []
    live_ids = centres = 0
    for b in captured(eng):
        ref0, ref1, loss = grouped_sgns_step(
            ref0, ref1, jnp.asarray(groups[b["centers"]]),
            jnp.asarray(b["contexts"]), jnp.asarray(b["mask"]),
            jnp.asarray(b["negs"]), jnp.float32(b["alpha"]))
        ref_losses.append(float(loss))
        c = b["centers"][b["mask"] > 0]
        starts = np.r_[True, c[1:] != c[:-1]]
        centres += int(starts.sum())
        live_ids += int((groups[c[starts]] >= 0).sum())
    prog0, prog1 = tables(eng)
    for prog, ref, init in ((prog0, ref0, init0), (prog1, ref1, init1)):
        gap, dnorm = gaps(prog, np.asarray(ref), init)
        assert gap < GAP and dnorm < DNORM_GAP, (gap, dnorm)
    np.testing.assert_allclose(losses, ref_losses, rtol=LOSS_GAP)
    # bucket rows moved: the step trained the groups, not the words alone
    assert np.abs(prog0[V:] - init0[V:]).max() > 0
    assert np.array_equal(prog1[V:], init1[V:])  # syn1's are never touched
    # the device's counts: live group ids gathered, centres formed
    assert written.shape == (K, 6)
    assert written[:, 4].sum() == live_ids and written[:, 5].sum() == centres
    # a row kept in bfloat16 would not pass
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
        return before, tables(eng), [np.asarray(out[0])]

    assert_two_shards_fit_as_one(fit, K)


def test_groups_of_one_word_are_the_word_level_scan_bit_for_bit():
    """Each word its own group, nothing else: the same tables, losses and
    rows written as the word-level scan, to the bit. (Over a corpus in
    which no step meets a word twice: a word that is the centre of two
    runs of one step has its gradients summed run by run, the word-level
    scatter sums them pair by pair, and float32 addition does not
    associate; on Zipf text the two agree to the reference's limits,
    which the test above holds.)"""
    alone = np.full((V, 2), -1, np.int32)
    alone[:, 0] = np.arange(V)
    word = engine((1, 1))
    _, out_w = run_packed(word, cyclic_corpus())
    sub = engine((1, 1), alone)
    _, out_s = run_packed(sub, cyclic_corpus())
    for a, b in zip(tables(word), tables(sub)):
        np.testing.assert_array_equal(a, b)
    for i in range(4):  # loss, pairs, positions, alpha
        np.testing.assert_array_equal(np.asarray(out_w[i]),
                                      np.asarray(out_s[i]))
    # rows written: syn1's alike; of syn0 the word-level scatter also
    # counts row 0, which a step's padding pairs add zero to
    np.testing.assert_array_equal(np.asarray(out_w[4])[:, 1],
                                  np.asarray(out_s[4])[:, 1])
    assert (np.asarray(out_s[4])[:, 0] <= np.asarray(out_w[4])[:, 0]).all()


def test_per_run_centres_equal_a_per_pair_expansion():
    """The scan forms a centre once a run of pairs; ``train_steps_grouped``
    (the host batcher's entry) takes a group a pair. The same steps, the
    same negatives (one key schedule), the same sums in another order."""
    groups = random_groups()
    eng = engine((1, 1), groups)
    _, out = run_packed(eng, zipf_corpus())
    batches = captured(eng)
    pair = engine((1, 1))
    expanded = np.stack([groups[b["centers"]] for b in batches])
    losses = pair.train_steps_grouped(
        np.maximum(expanded, 0), (expanded >= 0).astype(np.float32),
        np.stack([b["contexts"] for b in batches])[:, :, None],
        np.stack([b["mask"] for b in batches])[:, :, None],
        jax.random.PRNGKey(3), np.asarray([b["alpha"] for b in batches]), 0)
    init = tables(engine((1, 1)))
    for run, exp, start in zip(tables(eng), tables(pair), init):
        gap, dnorm = gaps(run, exp, start)
        assert gap < GAP and dnorm < DNORM_GAP, (gap, dnorm)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(losses),
                               rtol=LOSS_GAP)


def test_the_grid_scan_forms_its_centres_from_the_group_table():
    groups = random_groups()
    eng = engine((1, 2), groups)
    init0, _ = tables(eng)
    eng.upload_corpus(*zipf_corpus())
    alphas = np.full(K, 0.025, np.float32)
    losses = np.asarray(eng.train_steps_corpus(
        0, BATCH, WINDOW, jax.random.PRNGKey(3), alphas))
    prog0, _ = tables(eng)
    assert np.isfinite(losses).all()
    assert np.abs(prog0[V:] - init0[V:]).max() > 0


# sha256[:16] of the lowered word-level scans' StableHLO text, by mesh and
# how the tables are split over it (the first part of `engine.step_body`):
# (packed, grid). Taken on the tree of ISSUE 33, which meant to
# change the word-level programs (the batch read from the view's
# per-position record, the negatives from the packed alias table); between
# ISSUE 31's parent (7dbd80a) and that tree they had not moved. Taken again
# on the tree of ISSUE 35, which meant to move `_scatter_rows`: the first
# sort stands before the choice of writer and the run totals inside XLA's
# (the same ops in another nesting: the tables a fit makes are the parent's
# bit for bit; the grid scans of `dims` and of the subword family lower as
# they did). All sixteen taken again on the tree of ISSUE 38, which meant to
# change every step program: the ids are transposed before the gather, the
# rows come a block of (batch, d) a gather, and the logits and `d_center`
# are multiplies and sums over those blocks where they were einsums over
# `(B, C, n, d)` (`tests/test_row_blocks.py` holds the scatters to what
# the parent formulation handed them).
# The eight packed scans taken again on the tree of ISSUE 44, which meant to
# change them all: a group's steps run in a `while` that stops at the corpus
# end where they ran in a `scan` of 32 (the step inside is the parent's:
# `tests/test_corpus_end.py`; the grid scans did not move).
# ISSUE 46 deleted the column-sharded `dims` engine and with it that
# layout's 1 x 2 entries; the 1 x 4 mesh (the four-chip cell's) was taken
# in their place, on ISSUE 46's PARENT (7daf58c) and again on its tree: the
# same. The other entries are untouched.
# ISSUE 49 meant to change the step wherever the model axis has several
# shards (the pair side's pulls end in a reduce-scatter over the pairs, a
# shard does the pair math of its slice, `d_center` and the scalars come
# back by all-gather): the 1 x 2, 2 x 2 and 1 x 4 entries, here and in
# SUBWORD_PROGRAMS, were taken again on its tree (CHANGES.md keeps the old
# ones). The (1, 1) entries are AS THEY WERE: the one-shard program is
# emitted by the parent's very ops, which is the proof that the one-chip
# cells run what they ran.
# ISSUE 51 meant to change the same entries again (no syn1 row crosses the
# model axis: the shards all-reduce the pairs' logit partials and their
# partial `d_center`; ISSUE 49's slices are deleted): the 1 x 2, 2 x 2 and
# 1 x 4 entries, here and in SUBWORD_PROGRAMS, were taken again on its tree
# (CHANGES.md keeps ISSUE 49's), and the (1, 1) entries are, once more,
# untouched.
WORD_LEVEL_PROGRAMS = {
    ((1, 1), "rows"): ("84c02214c885c063", "124ae8075d865073"),
    ((1, 2), "rows"): ("a1fd7f3b69139e67", "4bc8b68e6e898927"),
    ((2, 2), "rows"): ("0fd535cd1ee4a741", "259b4ce3ff2db77d"),
    ((1, 4), "rows"): ("b1351a93da7d9703", "c561b672f436946e"),
}


def lowered(eng, groups_width=0):
    """The (packed, grid) scans an engine builds, lowered."""
    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(eng.mesh, P(*spec)))

    pairs = packed_pair_batch(BATCH, WINDOW, eng.num_data)
    span = -(-3 * pairs // context_width(WINDOW))
    table = sds(eng.syn0.shape, jnp.float32, *eng.syn0.sharding.spec)
    offs = sds((61,), jnp.int32)
    i32, u32, f32 = (sds((), t) for t in (jnp.int32, jnp.uint32, jnp.float32))
    words = sds((900,), jnp.int32)
    head = (table, table, sds((-(-V // 64), 128), jnp.int32), words)
    extra = (sds((V, groups_width), jnp.int32),) if groups_width else ()
    packed = eng._make_packed_corpus_scan(
        pairs, WINDOW, BATCH, span, K, groups_width).lower(
            *head, words, offs, offs, i32, i32, sds((2,), jnp.uint32), u32,
            u32, f32, f32, f32, *extra)
    grid = eng._make_corpus_scan(BATCH, WINDOW, groups_width).lower(
        *head, offs, i32, i32, sds((2,), jnp.uint32), u32,
        sds((K,), jnp.float32), *extra)
    return packed, grid


@pytest.mark.parametrize("shape,split", sorted(WORD_LEVEL_PROGRAMS))
def test_a_word_level_fit_lowers_to_the_program_it_lowered_to(shape, split):
    eng = engine(shape, bucket=0)
    assert eng.step_body.split("/")[0] == split
    got = tuple(hashlib.sha256(low.as_text().encode()).hexdigest()[:16]
                for low in lowered(eng))
    assert got == WORD_LEVEL_PROGRAMS[(shape, split)]


# The same for the SUBWORD scans (a (V, G) group table on the device), taken
# on the parent of ISSUE 34 (069d339), which gave the step bodies a flag for
# CBOW's undivided gradient and the scan factory a second scan: a skip-gram
# fit, word level and subword, must lower to the program it lowered to.
# The packed scans' hashes taken again on ISSUE 35's tree, and all eight on
# ISSUE 38's, as above.
SUBWORD_PROGRAMS = {
    ((1, 1), "rows"): ("b4cb57206511569c", "5e7e6d3939851660"),
    ((1, 2), "rows"): ("2e048c3abec31589", "70321b9355fd3a3a"),
    ((2, 2), "rows"): ("309ddb9e453d6de3", "0787886b4b610cbb"),
    ((1, 4), "rows"): ("0572d737a6ecac22", "bfb34fd4d3e5b9a4"),
}


@pytest.mark.parametrize("shape,split", sorted(SUBWORD_PROGRAMS))
def test_a_subword_fit_lowers_to_the_program_it_lowered_to(shape, split):
    eng = engine(shape, random_groups())
    assert eng.step_body.split("/")[0] == split
    got = tuple(hashlib.sha256(low.as_text().encode()).hexdigest()[:16]
                for low in lowered(eng, G))
    assert got == SUBWORD_PROGRAMS[(shape, split)]


def test_the_subword_scan_keeps_its_name_and_scopes():
    eng = engine((1, 1), random_groups())
    packed, _ = lowered(eng, G)
    assert "local_packed_scan" in packed.as_text()
    compiled = packed.compile().as_text()
    for scope in ("glint.batch", "glint.sample", "glint.gather/syn0",
                  "glint.gather/syn1", "glint.compose", "glint.grads",
                  "glint.scatter/syn0", "glint.scatter/syn1"):
        assert scope in compiled, scope
