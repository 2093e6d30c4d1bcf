"""The packed step's index work (ISSUE 33): what is fixed for an epoch, or
for the fit, is not looked up element by element inside the step.

* ``position_sentences`` is the window functions' search, laid down once.
* ``pack_window_pairs`` with that record against the search path: the five
  outputs bit-equal, over the corpora and positions that could tell them
  apart (runs of emptied sentences longer than a span, a boundary at
  ``pos``, one-word sentences, the epoch's tail, ``pos`` past the end,
  compacted and never-compacted views).
* The engine holds a record for whatever view is active, a prefetched one
  included.
* The one-look-up sampler against the two-gather form, bit-equal.
* 64 steps of the packed scan, word-level and subword, end in the tables
  the search-and-two-gathers formulation of the same scan ends in.
* The benchmark's way of calling the seam (old signatures, nothing more)
  still gives the scan's batches.
* The lowered scan holds no conditional and no span-wide search loop
  under ``glint.batch``.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from glint_word2vec_tpu.corpus.alias import build_unigram_alias  # noqa: E402
from glint_word2vec_tpu.corpus.batching import (  # noqa: E402
    context_width,
    packed_pair_batch,
)
from glint_word2vec_tpu.ops import device_batching, sampling  # noqa: E402
from glint_word2vec_tpu.ops.device_batching import (  # noqa: E402
    device_words_done,
    pack_window_pairs,
    position_sentences,
    subsample_compact,
)
from glint_word2vec_tpu.parallel import engine as engine_mod  # noqa: E402
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine  # noqa: E402
from glint_word2vec_tpu.parallel.mesh import make_mesh  # noqa: E402

V = 97  # not a multiple of 64, nor of 128
SPAN, PAIRS, GRID = 12, 24, 8


def _corpus(lens, seed=0):
    rng = np.random.default_rng(seed)
    offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    ids = rng.integers(0, V, int(offsets[-1])).astype(np.int32)
    ids[::7] = 2**31 - 2  # a centre whose step to its neighbour wraps
    ids[3:6] = 5  # the same word at adjacent positions
    return ids, offsets


# name -> sentence lengths; the zeros are sentences subsampling emptied
# (repeated offsets), as a compacted view holds them.
CORPORA = {
    "plain": [5, 3, 9, 2, 7, 4, 6, 8, 3, 5],
    "one_word_sentences": [1] * 40,
    "empties_longer_than_a_span": [4] + [0] * (3 * SPAN) + [6, 1, 0, 0, 1, 3]
    + [0] * (2 * SPAN) + [5, 2],
    "empty_first_and_last": [0, 0, 7, 3, 0, 9, 0, 0],
    "one_long_sentence": [50],
}


def _positions(offsets, n_valid):
    """Positions that could tell the two paths apart: the start, a sentence
    boundary and its neighbours, the tail, the end, past it, before 0."""
    inner = [int(o) for o in offsets[1:-1] if 0 < o < n_valid][:3]
    near = [p + d for p in inner for d in (-1, 0, 1)]
    return sorted({0, 1, *near, max(n_valid - SPAN, 0), n_valid - SPAN // 2,
                   n_valid - 1, n_valid, n_valid + 5, -3})


def _parent_pack_window_pairs(ids, offsets, pos, base_key, grid_step0, *,
                              window, span, pair_batch, grid_batch, n_valid):
    """``pack_window_pairs`` as the parent of ISSUE 33 had it, transcribed:
    a search of the offsets a position, a gather a context lane, a scatter
    of every lane's centre."""
    from glint_word2vec_tpu.corpus.batching import window_offsets

    N, S, P = ids.shape[0], span, pair_batch
    offs = jnp.asarray(window_offsets(window), dtype=jnp.int32)
    C = offs.shape[0]
    positions = pos + jnp.arange(S, dtype=jnp.int32)
    in_corpus = (positions >= 0) & (positions < n_valid)
    p = jnp.clip(positions, 0, max(N - 1, 0))
    sent = jnp.searchsorted(offsets, p, side="right") - 1
    start, end = offsets[sent], offsets[sent + 1]
    b = device_batching.grid_window_shrink(
        base_key, positions, grid_batch, grid_step0, window)
    cpos = p[:, None] + offs[None, :]
    valid = ((offs[None, :] >= -b[:, None]) & (offs[None, :] <= b[:, None] - 1)
             & (cpos >= start[:, None]) & (cpos < end[:, None])
             & in_corpus[:, None])
    centers = jnp.where(in_corpus, ids[p], 0).astype(jnp.int32)
    contexts = jnp.where(
        valid, ids[jnp.clip(cpos, 0, max(N - 1, 0))], 0).astype(jnp.int32)
    cum = jnp.cumsum(valid.sum(axis=1).astype(jnp.int32))
    n_cons = jnp.sum((cum <= P).astype(jnp.int32))
    consumed = jnp.arange(S, dtype=jnp.int32) < n_cons
    take = (valid & consumed[:, None]).reshape(-1).astype(jnp.int32)
    incl = jnp.cumsum(take)
    n_pairs = incl[-1]
    idx = jnp.where(take > 0, incl - take, P)
    pcenters = jnp.zeros(P, jnp.int32).at[idx].set(
        jnp.repeat(centers, C), mode="drop")
    pcontexts = jnp.zeros(P, jnp.int32).at[idx].set(
        contexts.reshape(-1), mode="drop")
    pmask = (jnp.arange(P, dtype=jnp.int32) < n_pairs).astype(jnp.float32)
    return pcenters, pcontexts, pmask, n_cons, n_pairs


def _batches(ids, offsets, pos, n_valid, window, span=SPAN, pairs=PAIRS):
    """(the parent's batch, the search path's, the record path's)."""
    args = (jnp.asarray(ids), jnp.asarray(offsets), jnp.int32(pos),
            jax.random.PRNGKey(4), jnp.uint32(3))
    kw = dict(window=window, span=span, pair_batch=pairs, grid_batch=GRID,
              n_valid=jnp.int32(n_valid))
    sent = position_sentences(jnp.asarray(offsets), ids.shape[0])
    return (_parent_pack_window_pairs(*args, **kw),
            pack_window_pairs(*args, **kw),
            pack_window_pairs(*args, **kw, sent_of=sent))


def _assert_same(parent, search, record, what):
    for name, a, b, c in zip(
            ("pcenters", "pcontexts", "pmask", "n_cons", "n_pairs"),
            parent, search, record):
        a, b, c = np.asarray(a), np.asarray(b), np.asarray(c)
        assert a.dtype == b.dtype == c.dtype, (what, name)
        assert np.array_equal(a, b) and np.array_equal(a, c), (what, name)


@pytest.mark.parametrize("name", sorted(CORPORA))
def test_position_sentences_is_the_search(name):
    ids, offsets = _corpus(CORPORA[name])
    n = ids.shape[0]
    got = np.asarray(position_sentences(jnp.asarray(offsets), n))
    want = np.searchsorted(offsets, np.arange(n), side="right") - 1
    assert got.dtype == np.int32 and np.array_equal(got, want)


@pytest.mark.parametrize("window", [1, 2, 3, 5])
@pytest.mark.parametrize("name", sorted(CORPORA))
def test_record_path_equals_search_path(name, window):
    ids, offsets = _corpus(CORPORA[name])
    n = ids.shape[0]
    assert PAIRS >= context_width(window)
    for n_valid in (n, max(n - 3, 1)):  # the whole view, and a bounded one
        for pos in _positions(offsets, n_valid):
            _assert_same(*_batches(ids, offsets, pos, n_valid, window),
                         (name, window, n_valid, pos))


@pytest.mark.parametrize("name", ["plain", "one_word_sentences"])
def test_record_path_equals_search_path_on_a_compacted_view(name):
    # the view an epoch trains on: compacted ids, offsets that repeat where
    # a sentence lost every word, a dead tail past n_kept
    ids, offsets = _corpus(CORPORA[name] * 6, seed=2)
    keep = np.full(V, 0.35, np.float32)
    ids_c, offs_c, n_kept = (np.asarray(a) for a in subsample_compact(
        jnp.asarray(ids), jnp.asarray(offsets), jnp.asarray(keep),
        jax.random.PRNGKey(8)))
    n_kept = int(n_kept)
    assert 0 < n_kept < ids.shape[0] and (np.diff(offs_c) == 0).any()
    for pos in _positions(offs_c, n_kept):
        _assert_same(*_batches(ids_c, offs_c, pos, n_kept, 5),
                     (name, "compacted", pos))


@pytest.mark.parametrize("span,pairs", [(200, 64), (3, 7)])
def test_record_path_where_the_span_outgrows_the_corpus(span, pairs):
    # a span (and its lanes' reach) longer than the whole view, and one
    # shorter than a sentence
    ids, offsets = _corpus(CORPORA["plain"])
    n = ids.shape[0]
    for pos in (0, 7, n - 2, n + 1):
        _assert_same(*_batches(ids, offsets, pos, n, 5, span, pairs),
                     (span, pos))


def test_search_path_is_what_a_caller_without_a_record_gets():
    # the benchmark's replay calls the seam with the old arguments alone
    ids, offsets = _corpus(CORPORA["plain"])
    a = pack_window_pairs(
        jnp.asarray(ids), jnp.asarray(offsets), jnp.int32(2),
        jax.random.PRNGKey(4), jnp.uint32(3), window=5, span=SPAN,
        pair_batch=PAIRS, grid_batch=GRID, n_valid=jnp.int32(ids.shape[0]))
    b = pack_window_pairs(
        jnp.asarray(ids), jnp.asarray(offsets), jnp.int32(2),
        jax.random.PRNGKey(4), jnp.uint32(3), window=5, span=SPAN,
        pair_batch=PAIRS, grid_batch=GRID, n_valid=jnp.int32(ids.shape[0]),
        sent_of=None)
    _assert_same(a, a, b, "default")


# ---- the sampler ----------------------------------------------------------


def _alias_table(vocab, seed=0):
    rng = np.random.default_rng(seed)
    t = build_unigram_alias(rng.integers(1, 200, vocab))
    prob = t.prob.copy()
    prob[0], prob[-1] = 0.0, 1.0  # never kept; always kept
    return prob, t.alias.astype(np.int32)


@pytest.mark.parametrize("vocab", [2, 63, 64, 65, 97, 128, 129, 1000])
def test_packed_table_holds_every_entry(vocab):
    prob, alias = _alias_table(vocab)
    packed = sampling.pack_alias_table(prob, alias)
    assert packed.shape == (-(-vocab // 64), 128) and packed.dtype == np.int32
    k = np.arange(vocab)
    assert np.array_equal(packed[k // 64, k % 64], prob.view(np.int32))
    assert np.array_equal(packed[k // 64, 64 + k % 64], alias)
    # an entry decides its draw alone: u just under and at its probability
    k = jnp.arange(vocab, dtype=jnp.int32)
    for u, kept in ((np.nextafter(prob, np.float32(-1)), prob > 0),
                    (prob, np.zeros(vocab, bool))):
        got = sampling._accept_packed(k, jnp.asarray(u), jnp.asarray(packed))
        assert np.array_equal(np.asarray(got), np.where(kept, k, alias))


@pytest.mark.parametrize("vocab", [2, 63, 64, 65, 97, 128, 129, 1000])
def test_one_look_up_draws_what_two_gathers_draw(vocab):
    prob, alias = _alias_table(vocab, seed=vocab)
    packed = jnp.asarray(sampling.pack_alias_table(prob, alias))
    prob, alias = jnp.asarray(prob), jnp.asarray(alias)
    key = jax.random.PRNGKey(vocab)
    two = np.asarray(sampling.sample_negatives(key, prob, alias, (700, 3)))
    one = np.asarray(
        sampling.sample_negatives_packed(key, packed, vocab, (700, 3)))
    assert two.dtype == one.dtype == np.int32 and np.array_equal(two, one)
    assert 0 <= two.min() and two.max() < vocab
    rows = jnp.asarray([0, 5, 2**30 - 1, 17, 3], dtype=jnp.int32)
    two = sampling.sample_negatives_per_row(key, prob, alias, rows, (2, 5))
    one = sampling.sample_negatives_per_row_packed(
        key, packed, vocab, rows, (2, 5))
    assert np.array_equal(np.asarray(two), np.asarray(one))


def test_the_engine_keeps_both_forms_of_its_alias_table():
    eng = EmbeddingEngine(make_mesh(1, 2), V, 8,
                          np.arange(V, 0, -1).astype(np.int64))
    try:
        assert eng._prob.shape == (V,) and eng._prob.dtype == jnp.float32
        assert eng._alias.shape == (V,) and eng._alias.dtype == jnp.int32
        want = sampling.pack_alias_table(
            np.asarray(eng._prob), np.asarray(eng._alias))
        assert np.array_equal(np.asarray(eng._alias_packed), want)
        eng.set_noise_counts(np.arange(1, V + 1).astype(np.int64))
        want = sampling.pack_alias_table(
            np.asarray(eng._prob), np.asarray(eng._alias))
        assert np.array_equal(np.asarray(eng._alias_packed), want)
    finally:
        eng.destroy()


# ---- the engine's views ---------------------------------------------------

D, NEG, WINDOW, BATCH = 16, 3, 5, 16
P_STEP = packed_pair_batch(BATCH, WINDOW, 1)


def _zipf_corpus(seed=1, n_sent=90):
    rng = np.random.default_rng(seed)
    lens = rng.integers(1, 14, n_sent)
    offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    w = 1.0 / np.arange(1, V + 1)
    ids = rng.choice(V, int(offsets[-1]), p=w / w.sum()).astype(np.int32)
    return ids, offsets


def _engine(shape, extra_rows=0):
    return EmbeddingEngine(
        make_mesh(*shape), V, D, np.arange(V, 0, -1).astype(np.int64) * 3,
        num_negatives=NEG, seed=5, extra_rows=extra_rows)


def _record_of(eng, offsets):
    return np.asarray(position_sentences(
        jnp.asarray(np.asarray(offsets)), eng.corpus_positions))


def test_every_active_view_of_an_engine_has_its_record():
    eng = _engine((1, 1))
    try:
        ids, offsets = _zipf_corpus()
        eng.upload_corpus(ids, offsets, n_valid=ids.shape[0] - 7)
        key = jax.random.PRNGKey(2)
        # never compacted (the streaming trainer's bounded view): laid down
        # by the first packed dispatch over it, kept for the next
        assert eng._corpus_sent is None and eng._compacted_sent is None
        eng.train_steps_corpus_packed(0, P_STEP, WINDOW, BATCH, key, 2)
        first = eng._corpus_sent
        assert np.array_equal(np.asarray(first), _record_of(eng, offsets))
        eng.train_steps_corpus_packed(0, P_STEP, WINDOW, BATCH, key, 2)
        assert eng._corpus_sent is first
        # compacted: laid down with the compaction pass
        eng.upload_corpus(ids, offsets)
        eng.set_keep_probs(np.full(V, 0.5, np.float32))
        eng.compact_corpus(jax.random.PRNGKey(6))
        assert len(eng._corpus_compacted) == 2 and eng._corpus_sent is None
        assert np.array_equal(
            np.asarray(eng._compacted_sent),
            _record_of(eng, eng._corpus_compacted[1]))
        # a new upload drops both
        eng.upload_corpus(ids, offsets)
        assert eng._corpus_sent is None and eng._compacted_sent is None
    finally:
        eng.destroy()


def test_the_views_and_their_records_lie_on_every_device_of_the_mesh():
    # committed and replicated, so that a dispatch of a mesh program hands
    # the scan arrays it already holds and copies no view between devices
    eng = _engine((1, 4))
    try:
        ids, offsets = _zipf_corpus()
        eng.upload_corpus(ids, offsets)
        eng.set_keep_probs(np.full(V, 0.5, np.float32))
        eng.compact_corpus(jax.random.PRNGKey(6))
        eng.prefetch_compact_corpus(jax.random.PRNGKey(7))
        held = (*eng._corpus, *eng._corpus_compacted, eng._compacted_sent,
                eng._alias_packed, eng._compact_prefetch[1],
                eng._compact_prefetch[4])
        for a in held:
            assert a.committed and a.sharding.is_fully_replicated
            assert len(a.devices()) == 4
    finally:
        eng.destroy()


def test_a_prefetched_view_is_adopted_with_its_record():
    eng, fresh = _engine((1, 1)), _engine((1, 1))
    try:
        ids, offsets = _zipf_corpus()
        for e in (eng, fresh):
            e.upload_corpus(ids, offsets)
            e.set_keep_probs(np.full(V, 0.5, np.float32))
        k1, k2 = jax.random.PRNGKey(6), jax.random.PRNGKey(7)
        eng.compact_corpus(k1)
        eng.prefetch_compact_corpus(k2)
        assert len(eng._compact_prefetch) == 5
        eng.compact_corpus(k2)  # adopts the prefetched pass
        fresh.compact_corpus(k2)  # computes it
        assert eng._compact_prefetch is None and eng._n_kept == fresh._n_kept
        for a, b in zip((*eng._corpus_compacted, eng._compacted_sent),
                        (*fresh._corpus_compacted, fresh._compacted_sent)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(
            np.asarray(eng._compacted_sent),
            _record_of(eng, eng._corpus_compacted[1]))
        # prefetched for another key: dropped, computed anew
        eng.prefetch_compact_corpus(k1)
        eng.compact_corpus(k2)
        assert np.array_equal(np.asarray(eng._compacted_sent),
                              np.asarray(fresh._compacted_sent))
    finally:
        eng.destroy()
        fresh.destroy()


# ---- 64 steps against the search-and-two-gathers formulation ---------------

G_WIDTH, BUCKETS = 4, 40


def _groups():
    rng = np.random.default_rng(3)
    g = np.full((V, G_WIDTH), -1, np.int32)
    g[:, 0] = np.arange(V)
    for w in range(V):
        k = int(rng.integers(0, G_WIDTH))
        g[w, 1:1 + k] = V + rng.integers(0, BUCKETS, k)
    return g


def _parent_formulation(monkeypatch, prob, alias):
    """The same scan with the batch searched for in the offsets and each
    negative's entry read by two gathers: what the engine traced before it
    held a per-position record and a packed table."""
    real_pack = device_batching.pack_window_pairs

    def searched(*a, sent_of=None, **k):
        return real_pack(*a, **k)

    def two_gathers(key, packed, vocab, rows, shape):
        return sampling.sample_negatives_per_row(
            key, jnp.asarray(prob), jnp.asarray(alias), rows, shape)

    monkeypatch.setattr(device_batching, "pack_window_pairs", searched)
    monkeypatch.setattr(
        engine_mod, "sample_negatives_per_row_packed", two_gathers)
    monkeypatch.setattr(engine_mod, "_SCAN_MEMO", {})


def _sixty_four_steps(shape, subword):
    eng = _engine(shape, extra_rows=BUCKETS if subword else 0)
    try:
        if subword:
            eng.upload_center_groups(_groups())
        ids, offsets = _zipf_corpus(n_sent=400)
        eng.upload_corpus(ids, offsets)
        eng.set_keep_probs(np.full(V, 0.6, np.float32))
        eng.compact_corpus(jax.random.PRNGKey(9))
        pairs = packed_pair_batch(BATCH, WINDOW, eng.num_data)
        key, pos, outs = jax.random.PRNGKey(1), 0, []
        for call in range(8):
            out = eng.train_steps_corpus_packed(
                pos, pairs, WINDOW, BATCH, key, 8, step0=8 * call,
                step_size=0.05, total_words=4 * int(offsets[-1]))
            pos = int(out[2][-1])
            outs.append([np.asarray(o) for o in out])
        assert pos > 0 and outs[-1][1].sum() > 0  # still inside the epoch
        return np.asarray(eng.syn0), np.asarray(eng.syn1), outs
    finally:
        eng.destroy()


@pytest.mark.parametrize("subword", [False, True],
                         ids=["word_level", "subword"])
@pytest.mark.parametrize("shape", [(1, 1), (1, 2), (2, 2)])
def test_sixty_four_steps_end_in_the_parent_formulations_tables(
        shape, subword, monkeypatch):
    new0, new1, new_outs = _sixty_four_steps(shape, subword)
    probe = _engine((1, 1))
    prob, alias = np.array(probe._prob), np.array(probe._alias)
    probe.destroy()
    _parent_formulation(monkeypatch, prob, alias)
    old0, old1, old_outs = _sixty_four_steps(shape, subword)
    assert np.array_equal(new0, old0) and np.array_equal(new1, old1)
    for a, b in zip(new_outs, old_outs):
        for x, y in zip(a, b):  # losses, pairs, positions, alphas, counts
            assert np.array_equal(x, y)
    assert np.abs(new1).max() > 0  # and they trained


def test_the_benchmarks_call_pattern_still_gives_the_scans_batches():
    """``benchmark/kinds/train.py::capture_batches`` draws a dispatch
    group's batches once more from outside the scan, with the seam's old
    signatures and no new argument; what it draws must be what the scan
    (record, packed table) trained on: positions, pair counts and alphas
    are compared with the scan's own outputs, the negatives with the
    packed sampler's."""
    eng = _engine((1, 1))
    try:
        ids, offsets = _zipf_corpus(n_sent=300)
        eng.upload_corpus(ids, offsets)
        eng.set_keep_probs(np.full(V, 0.6, np.float32))
        eng.compact_corpus(jax.random.PRNGKey(9))
        K, total = 6, 3 * int(offsets[-1])
        base_key = jax.random.PRNGKey(11)
        span = -(-3 * P_STEP // context_width(WINDOW))
        ids_c, soffs = eng._corpus_compacted
        orig, n_valid = eng._corpus[1], jnp.int32(eng._n_kept)
        rows = jnp.arange(P_STEP, dtype=jnp.int32)

        def body(pos, i):
            key = jax.random.fold_in(base_key, jnp.uint32(0) + i)
            pc, px, pm, n_cons, n_pairs = pack_window_pairs(
                ids_c, soffs, pos, base_key, jnp.uint32(0), window=WINDOW,
                span=span, pair_batch=P_STEP, grid_batch=BATCH,
                n_valid=n_valid)
            end = pos + n_cons
            done = device_words_done(orig, soffs, end, n_valid)
            alpha = jnp.maximum(
                jnp.float32(0.05) * (1.0 - done.astype(jnp.float32)
                                     * jnp.float32(1.0 / total)),
                jnp.float32(0.05) * 1e-4)
            negs = sampling.sample_negatives_per_row(
                key, eng._prob, eng._alias, rows, (1, NEG))
            packed_negs = sampling.sample_negatives_per_row_packed(
                key, eng._alias_packed, V, rows, (1, NEG))
            return end, (n_pairs, end, alpha, negs, packed_negs)

        n_pairs, ends, alphas, negs, packed_negs = jax.lax.scan(
            body, jnp.int32(0), jnp.arange(K, dtype=jnp.uint32))[1]
        out = eng.train_steps_corpus_packed(
            0, P_STEP, WINDOW, BATCH, base_key, K, step_size=0.05,
            total_words=total)
        assert np.array_equal(np.asarray(out[1]), np.asarray(n_pairs))
        assert np.array_equal(np.asarray(out[2]), np.asarray(ends))
        assert np.array_equal(np.asarray(out[3]), np.asarray(alphas))
        assert np.array_equal(np.asarray(negs), np.asarray(packed_negs))
        assert out[4].shape == (K, 4)
    finally:
        eng.destroy()


# ---- the lowered program ----------------------------------------------------


def _ops_under(text, scope):
    """(op name, its line) of every op of a lowered module whose location
    names ``scope``."""
    locs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))

    def names(ref, seen=()):
        body = locs.get(ref, "")
        out = [body]
        for inner in re.findall(r"#loc\d+", body):
            if inner not in seen:
                out += names(inner, seen + (ref,))
        return out

    found = []
    for line in text.splitlines():
        m = re.search(r"(stablehlo\.\w+|func\.call).* loc\((#loc\d+)\)", line)
        if m and any(scope in n for n in names(m.group(2))):
            found.append((m.group(1), line))
    return found


@pytest.mark.parametrize("subword", [False, True],
                         ids=["word_level", "subword"])
def test_the_batch_holds_no_branch_and_no_span_wide_search(subword):
    """Nothing under ``glint.batch`` is chosen at run time, and no loop
    there carries a span-wide operand: the one search left is
    ``device_words_done``'s, over a scalar. (The chip's compiler is put
    the cell-sized scan in ``tests/test_tpu_compile.py``.)"""
    eng = _engine((1, 2), extra_rows=BUCKETS if subword else 0)
    try:
        sds = jax.ShapeDtypeStruct
        span = -(-3 * P_STEP // context_width(WINDOW))
        table = sds(eng.syn0.shape, eng.syn0.dtype)
        words, offs = sds((900,), jnp.int32), sds((61,), jnp.int32)
        i32, u32, f32 = (sds((), t) for t in (jnp.int32, jnp.uint32,
                                              jnp.float32))
        extra = (sds((V, G_WIDTH), jnp.int32),) if subword else ()
        text = eng._make_packed_corpus_scan(
            P_STEP, WINDOW, BATCH, span, 4, G_WIDTH if subword else 0).lower(
                table, table,
                sds(eng._alias_packed.shape, eng._alias_packed.dtype),
                words, words, offs, offs, i32, i32, sds((2,), jnp.uint32),
                u32, u32, f32, f32, f32, *extra).as_text(debug_info=True)
    finally:
        eng.destroy()
    ops = _ops_under(text, "glint.batch")
    assert len(ops) > 50  # the scope is there and the walk finds its ops
    kinds = {op for op, _ in ops}
    assert not kinds & {"stablehlo.case", "stablehlo.if"}, kinds
    # a while under the scope (words_done's binary search) carries scalars
    # and the offsets it searches, never a span-wide operand
    for op, line in ops:
        if op == "stablehlo.while":
            assert f"tensor<{span}x" not in line, line
    # and the span's sentence bounds are slices: no gather under the scope
    # reads the offsets with a span-wide index
    for op, line in ops:
        if op == "stablehlo.gather":
            assert not re.search(
                rf"tensor<61xi32>, tensor<{span}x\d+xi32>", line), line
