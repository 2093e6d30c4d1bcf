"""Dense pair packing for the device-resident train scan (ISSUE 4).

Contracts pinned here:
  * PAIR-MULTISET PARITY — the packed scan consumes exactly the valid
    (center, context) pair multiset the grid path trains on, verified
    three ways against a host-NumPy windowing oracle fed the same shrink
    draws (the grid position->draw mapping pack_window_pairs reproduces).
  * MESH INVARIANCE — packed assembly, negative draws (keyed by global
    pair row), and the resulting tables are identical on every shape of
    the virtual 8-device mesh.
  * UPDATE DECOMPOSITION — feeding a grid batch's pairs through the
    pair-form step applies the identical table update (scatter-adds sum).
  * LR/ACCOUNTING — the traced consumed-position words_done rule matches
    the host functions bit-for-bit, and a packed fit lands on the same
    per-epoch words_done as the grid fit (with and without subsampling).
  * CHECKPOINT/RESUME — a mid-epoch save carries the consumed-position
    counter and a resume reproduces the uninterrupted run exactly.
"""

import json
import os
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.corpus.batching import context_width, window_offsets
from glint_word2vec_tpu.ops import sgns
from glint_word2vec_tpu.ops.device_batching import (
    corpus_words_done,
    corpus_words_done_compacted,
    device_window_batch,
    device_words_done,
    grid_window_shrink,
    pack_window_pairs,
)
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
from glint_word2vec_tpu.parallel.mesh import make_mesh
from glint_word2vec_tpu.utils.params import Word2VecParams

V, D = 97, 16


def _corpus(n_sent=7, lens=(5, 1, 9, 3, 12, 2, 6), seed=0):
    rng = np.random.default_rng(seed)
    sents = [rng.integers(0, V, L).astype(np.int32) for L in lens[:n_sent]]
    ids = np.concatenate(sents)
    offsets = np.zeros(len(sents) + 1, np.int64)
    np.cumsum([len(s) for s in sents], out=offsets[1:])
    return ids, offsets, sents


def _host_pair_oracle(ids, offsets, b, window):
    """Host-NumPy ground truth: the valid-pair multiset over the whole
    corpus given per-position shrink draws ``b`` — pure numpy windowing
    (offsets in [-b, b-1], in-sentence), no device code."""
    offs = window_offsets(window)
    pairs = Counter()
    for p in range(len(ids)):
        j = np.searchsorted(offsets, p, side="right") - 1
        s0, s1 = offsets[j], offsets[j + 1]
        for o in offs:
            q = p + o
            if -b[p] <= o <= b[p] - 1 and s0 <= q < s1:
                pairs[(int(ids[p]), int(ids[q]))] += 1
    return pairs


def _grid_pair_multiset(ids, offsets, key, window, B):
    """The pair multiset the GRID corpus scan trains on: step i covers
    positions [i*B, (i+1)*B) with key fold_in(base, i) — exactly the
    make_corpus_scan schedule."""
    N = len(ids)
    idsj = jnp.asarray(ids)
    offj = jnp.asarray(offsets, jnp.int32)
    pairs = Counter()
    for step, start in enumerate(range(0, N + B, B)):
        k = jax.random.fold_in(key, np.uint32(step))
        c, x, m = device_window_batch(
            idsj, offj, jnp.arange(start, start + B, dtype=jnp.int32),
            jnp.arange(B, dtype=jnp.int32), k, window,
        )
        c, x, m = map(np.asarray, (c, x, m))
        for i in range(B):
            for lane in range(x.shape[1]):
                if m[i, lane] > 0:
                    pairs[(int(c[i]), int(x[i, lane]))] += 1
    return pairs


def _packed_pair_multiset(ids, offsets, key, window, B, P, span):
    N = len(ids)
    idsj = jnp.asarray(ids)
    offj = jnp.asarray(offsets, jnp.int32)
    fn = jax.jit(
        lambda pos: pack_window_pairs(
            idsj, offj, pos, key, jnp.uint32(0), window=window, span=span,
            pair_batch=P, grid_batch=B, n_valid=jnp.int32(N),
        )
    )
    pairs = Counter()
    pos = 0
    while pos < N:
        pc, px, pm, n_cons, n_pairs = fn(jnp.int32(pos))
        assert int(n_cons) >= 1  # guaranteed forward progress
        assert int(n_pairs) <= P
        pc, px = np.asarray(pc), np.asarray(px)
        for j in range(int(n_pairs)):
            pairs[(int(pc[j]), int(px[j]))] += 1
        pos += int(n_cons)
    return pairs


@pytest.mark.parametrize("window", [2, 3, 5])
def test_packed_multiset_matches_grid_and_host_oracle(window):
    # Three-way: host-NumPy oracle == grid scan pairs == packed pairs,
    # as exact multisets (centers, contexts, counts). Two packing
    # geometries so the position cut points differ from the grid batch
    # boundaries in both directions.
    ids, offsets, _ = _corpus()
    key = jax.random.PRNGKey(7)
    B = 8
    b = np.asarray(
        grid_window_shrink(
            key, jnp.arange(len(ids), dtype=jnp.int32), B, jnp.uint32(0),
            window,
        )
    )
    oracle = _host_pair_oracle(ids, offsets, b, window)
    grid = _grid_pair_multiset(ids, offsets, key, window, B)
    assert grid == oracle
    C = context_width(window)
    for P, span in ((16, 12), (max(C, 5), 4)):
        packed = _packed_pair_multiset(ids, offsets, key, window, B, P, span)
        assert packed == oracle, (P, span)


def test_pack_window_pairs_tail_and_invariants():
    ids, offsets, _ = _corpus()
    N = len(ids)
    key = jax.random.PRNGKey(3)
    # Past the corpus end: zero pairs, the whole span still consumed
    # (the epoch tail drains in span-sized strides).
    pc, px, pm, n_cons, n_pairs = pack_window_pairs(
        jnp.asarray(ids), jnp.asarray(offsets, jnp.int32),
        jnp.int32(N + 3), key, jnp.uint32(0),
        window=3, span=8, pair_batch=16, grid_batch=8,
        n_valid=jnp.int32(N),
    )
    assert int(n_pairs) == 0 and int(n_cons) == 8
    assert float(np.asarray(pm).sum()) == 0.0
    assert np.asarray(pc).sum() == 0 and np.asarray(px).sum() == 0
    # pair_batch below the lane count can deadlock a position: rejected.
    with pytest.raises(ValueError, match="pair_batch"):
        pack_window_pairs(
            jnp.asarray(ids), jnp.asarray(offsets, jnp.int32),
            jnp.int32(0), key, jnp.uint32(0),
            window=5, span=8, pair_batch=3, grid_batch=8,
            n_valid=jnp.int32(N),
        )


def test_device_words_done_matches_host_rules():
    # The traced rule the packed scan anneals the LR with must equal the
    # host accounting bit-for-bit: identity stream == corpus_words_done,
    # compacted stream == corpus_words_done_compacted (emptied sentence
    # included).
    ids, offsets, _ = _corpus()
    N = len(ids)
    offj = jnp.asarray(offsets, jnp.int32)
    fn = jax.jit(device_words_done)
    for end in range(0, N + 4):
        assert int(
            fn(offj, offj, jnp.int32(end), jnp.int32(N))
        ) == corpus_words_done(offsets, end)
    rng = np.random.default_rng(3)
    keep = rng.random(N) < 0.5
    keep[offsets[1] : offsets[2]] = False  # force an emptied sentence
    kept_before = np.concatenate([[0], np.cumsum(keep.astype(np.int64))])
    offsets_c = kept_before[offsets]
    n_kept = int(keep.sum())
    offcj = jnp.asarray(offsets_c, jnp.int32)
    for end in range(0, n_kept + 4):
        assert int(
            fn(offj, offcj, jnp.int32(end), jnp.int32(n_kept))
        ) == corpus_words_done_compacted(offsets, offsets_c, end, n_kept)


def _mk_engine(shape, seed=11):
    counts = np.arange(V, 0, -1).astype(np.int64) * 3
    return EmbeddingEngine(
        make_mesh(*shape), V, D, counts, num_negatives=3, seed=seed,
    )


def _run_packed(eng, ids, offsets, key, n_steps=4):
    eng.upload_corpus(ids, offsets)
    return eng.train_steps_corpus_packed(
        0, 16, 3, 8, key, n_steps, step0=2, grid_step0=0,
        step_size=0.05, total_words=1000, words_base=0,
    )


@pytest.mark.parametrize("shape", [(2, 2), (4, 1), (1, 4)])
def test_packed_scan_mesh_invariance(shape):
    # Packed assembly is replicated-deterministic and negatives are keyed
    # by GLOBAL pair row, so tables, pair counts, and position advances
    # must match the single-device run on every mesh shape.
    ids, offsets, _ = _corpus()
    key = jax.random.PRNGKey(5)
    ref = _mk_engine((1, 1))
    eng = _mk_engine(shape)
    r_ref = _run_packed(ref, ids, offsets, key)
    r_eng = _run_packed(eng, ids, offsets, key)
    for a, b in zip(r_ref[1:], r_eng[1:]):  # pair_counts, pos_ends, alphas
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for table in ("syn0", "syn1"):
        np.testing.assert_allclose(
            np.asarray(getattr(eng, table), np.float32)[:V],
            np.asarray(getattr(ref, table), np.float32)[:V],
            rtol=2e-5, atol=1e-7, err_msg=table,
        )


def test_packed_scan_validates():
    ids, offsets, _ = _corpus()
    eng = _mk_engine((2, 2))
    with pytest.raises(ValueError, match="no corpus uploaded"):
        eng.train_steps_corpus_packed(0, 16, 3, 8, jax.random.PRNGKey(0), 1)
    eng.upload_corpus(ids, offsets)
    with pytest.raises(ValueError, match="not divisible"):
        eng.train_steps_corpus_packed(0, 15, 3, 8, jax.random.PRNGKey(0), 1)
    with pytest.raises(ValueError, match="pair_batch"):
        eng.train_steps_corpus_packed(0, 2, 5, 8, jax.random.PRNGKey(0), 1)


def test_pair_step_decomposes_grid_update(monkeypatch):
    # Decomposing a grid batch into its pairs and feeding them through
    # the pair-form step must apply the IDENTICAL table update
    # (scatter-adds sum; no lane ever contributes twice). Negative draws
    # are stubbed to a deterministic per-(row, lane) map so both forms
    # see the same noise words.
    B, C, n = 6, 3, 2

    def stub_negs(key, prob, alias, rows, shape_per_row):
        rows = jnp.asarray(rows)
        k = jnp.arange(n, dtype=jnp.int32)[None, None, :]
        if shape_per_row[0] == C:  # grid call: rows are batch rows
            b = rows[:, None, None]
            c = jnp.arange(C, dtype=jnp.int32)[None, :, None]
        else:  # pair call: rows are pair rows b*C + c
            b = (rows // C)[:, None, None]
            c = (rows % C)[:, None, None]
        v = (b * 31 + c * 7 + k * 3 + 1) % V
        return jnp.broadcast_to(
            v, (rows.shape[0],) + tuple(shape_per_row)
        ).astype(jnp.int32)

    monkeypatch.setattr(sgns, "sample_negatives_per_row", stub_negs)
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(1)
    syn0, syn1 = sgns.init_tables(jax.random.PRNGKey(2), V, D)
    prob = jnp.ones(V, jnp.float32)
    alias = jnp.arange(V, dtype=jnp.int32)
    centers = rng.integers(0, V, B).astype(np.int32)
    contexts = rng.integers(0, V, (B, C)).astype(np.int32)
    mask = np.ones((B, C), np.float32)
    alpha = jnp.float32(0.05)
    g0, g1, gl = sgns.train_step(
        syn0, syn1, prob, alias, jnp.asarray(centers),
        jnp.asarray(contexts), jnp.asarray(mask), key, alpha, n,
    )
    p0, p1, pl = sgns.train_step_pairs(
        syn0, syn1, prob, alias,
        jnp.asarray(np.repeat(centers, C)),
        jnp.asarray(contexts.reshape(-1)),
        jnp.ones(B * C, jnp.float32), key, alpha, n,
    )
    np.testing.assert_allclose(np.asarray(p0), np.asarray(g0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(g1), rtol=1e-6)
    np.testing.assert_allclose(float(pl), float(gl), rtol=1e-6)


def _packed_stream(window, P=32):
    """One real dense pair batch (mask-0 tail slots included) from the
    packed assembly: repeated corpus words make duplicate rows."""
    ids, offsets, _ = _corpus()
    pc, px, pm, _, _ = pack_window_pairs(
        jnp.asarray(ids), jnp.asarray(offsets, jnp.int32),
        jnp.int32(0), jax.random.PRNGKey(7), jnp.uint32(0),
        window=window, span=16, pair_batch=P, grid_batch=8,
        n_valid=jnp.int32(len(ids)),
    )
    return pc, px, pm


def _numpy_pair_oracle(s0, s1, pc, px, pm, negs, nmask, alpha):
    s0h = np.asarray(s0, np.float32).copy()
    s1h = np.asarray(s1, np.float32).copy()
    c, x, m = np.asarray(pc), np.asarray(px), np.asarray(pm)
    nm = np.asarray(nmask)
    h, u, un = s0h[c], s1h[x], s1h[negs]
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))  # noqa: E731
    f_pos = (h * u).sum(-1)
    f_neg = (h[:, None, :] * un).sum(-1)
    c_pos = alpha * (1.0 - sig(f_pos)) * m
    c_neg = -alpha * sig(f_neg) * nm
    np.add.at(s0h, c, c_pos[:, None] * u + (c_neg[..., None] * un).sum(1))
    np.add.at(s1h, x, c_pos[:, None] * h)
    np.add.at(
        s1h, negs.reshape(-1),
        c_neg.reshape(-1)[:, None] * np.repeat(h, negs.shape[1], axis=0),
    )
    loss = (
        (-np.log(sig(f_pos)) - (np.log(sig(-f_neg)) * nm).sum(-1)) * m
    ).sum() / max(m.sum(), 1.0)
    return s0h, s1h, loss


@pytest.mark.parametrize("window", [2, 3, 5])
def test_pair_step_matches_numpy_oracle(window):
    # The pair-form step against a host-NumPy oracle fed the same
    # negative draws (the step keys them by global pair row; the oracle
    # replays the call), on a real packed pair stream.
    from glint_word2vec_tpu.corpus.alias import build_unigram_alias
    from glint_word2vec_tpu.ops.sampling import sample_negatives_per_row

    n = 3
    pc, px, pm = _packed_stream(window)
    key = jax.random.PRNGKey(1)
    s0, s1 = sgns.init_tables(jax.random.PRNGKey(2), V, D)
    s0 = s0 * 100.0  # lift the values off the 1/d init scale, so that
    s1 = s1 + 0.01 * s0  # the comparison is not of nothing with nothing
    t = build_unigram_alias(np.arange(V, 0, -1).astype(np.int64), power=0.75)
    prob, alias = jnp.asarray(t.prob), jnp.asarray(t.alias)
    g0, g1, gl = sgns.train_step_pairs(
        s0, s1, prob, alias, pc, px, pm, key, jnp.float32(0.05), n
    )
    negs = sample_negatives_per_row(
        key, prob, alias, jnp.arange(pc.shape[0], dtype=jnp.int32), (1, n)
    )
    nmask = np.asarray(
        sgns.negative_mask(negs, px[:, None], pm[:, None])
    )[:, 0, :]
    o0, o1, ol = _numpy_pair_oracle(
        s0, s1, pc, px, pm, np.asarray(negs)[:, 0, :], nmask, 0.05
    )
    # live pairs and a masked tail, and an update worth comparing
    assert 0 < np.asarray(pm).sum() < pm.shape[0]
    assert np.abs(o1 - np.asarray(s1)).max() > 1e-3
    np.testing.assert_allclose(np.asarray(g0), o0, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g1), o1, rtol=2e-5, atol=1e-6)
    assert float(gl) == pytest.approx(ol, rel=1e-5)


# ---------------- model-level routing, accounting, resume ---------------

CORPUS = [
    "the quick brown fox jumps over the lazy dog".split(),
    "the dog sleeps all day long in the sun".split(),
    "a quick fox and a lazy dog meet in the field".split(),
    "the sun rises over the field every day".split(),
] * 30


def _w2v_defaults():
    return dict(
        vector_size=12, batch_size=32, min_count=1, num_iterations=2,
        seed=7, steps_per_call=4, window=3,
    )


def _w2v(**kw):
    from glint_word2vec_tpu import Word2Vec

    return Word2Vec(**{**_w2v_defaults(), **kw})


def test_set_batch_packing_validates():
    from glint_word2vec_tpu import Word2Vec

    with pytest.raises(ValueError, match="batch_packing"):
        Word2VecParams(batch_packing="loose")
    # Dense is the default (ISSUE 11); grid stays selectable.
    assert Word2VecParams().batch_packing == "dense"
    w = Word2Vec().set_batch_packing("grid")
    assert w.params.batch_packing == "grid"
    # Round-trips through the persisted params metadata.
    p = Word2VecParams.from_json(w.params.to_json())
    assert p.batch_packing == "grid"
    # Old params.json without the field loads with the (dense) default.
    blob = json.loads(w.params.to_json())
    del blob["batch_packing"]
    assert (
        Word2VecParams.from_json(json.dumps(blob)).batch_packing == "dense"
    )


def test_packed_pair_batch_sizing():
    # The dense default's pair batch covers ~batch_size center positions
    # in EXPECTATION (E[pairs/position] = (W-1)^2/W), so a packed step
    # trains the same effective synchronous batch as a grid step — the
    # update-dynamics contract of the default flip (sizing at the grid's
    # full lane count trained a ~2.3x larger synchronous batch, which
    # destabilized hot rows on small vocabularies). Floors: the lane
    # count (pack_window_pairs forward progress) and the data-axis
    # multiple.
    from glint_word2vec_tpu.corpus.batching import packed_pair_batch

    assert packed_pair_batch(256, 5) == 820  # ceil(256 * (4^2/5))
    assert packed_pair_batch(256, 5) < 256 * context_width(5)  # << B*C
    assert packed_pair_batch(256, 5, 8) % 8 == 0
    assert packed_pair_batch(1, 5) >= context_width(5)
    assert packed_pair_batch(1, 2) >= context_width(2)


@pytest.mark.parametrize("subsample_ratio", [0.0, 0.01])
def test_packed_fit_words_done_matches_grid(subsample_ratio):
    # Same per-epoch pre-subsampling credit on both dispatch shapes: the
    # LR anneal contract. The packed fit also reports its fill (the
    # effective mask density of the dense dispatches).
    m_grid = _w2v(subsample_ratio=subsample_ratio).fit(CORPUS)
    m_dense = _w2v(
        subsample_ratio=subsample_ratio, batch_packing="dense"
    ).fit(CORPUS)
    assert m_grid.training_metrics["pipeline"] == "device_corpus"
    assert m_dense.training_metrics["pipeline"] == "device_corpus"
    assert (
        m_dense.training_metrics["words_done"]
        == m_grid.training_metrics["words_done"]
    )
    assert m_dense.training_metrics["batch_packing"] == "dense"
    assert m_dense.training_metrics["packed_mask_density"] >= 0.9
    # Position-matched pair batches (packed_pair_batch) keep the dense
    # fit at ~the grid fit's step cadence — the same effective
    # synchronous batch per step (the old B*C sizing ran ~0.35x the
    # steps, i.e. a ~2.3x larger synchronous batch, which destabilized
    # hot rows on small vocabularies).
    assert (
        m_dense.training_metrics["steps"]
        >= 0.6 * m_grid.training_metrics["steps"]
    ), (m_dense.training_metrics["steps"], m_grid.training_metrics["steps"])
    # The packed model still learns a queryable table.
    assert len(m_dense.find_synonyms("quick", 3)) == 3


def test_packed_fit_checkpoint_resume_mid_epoch(tmp_path, monkeypatch):
    # Preemption drill ON THE FULL 8-DEVICE MESH (2 data x 4 model): stop
    # after 3 dispatch groups (mid-epoch), assert the state file carries
    # a nonzero consumed-position counter, then resume and match the
    # uninterrupted run's tables exactly — the position/gstep restore
    # makes every subsequent dispatch identical.
    ck = str(tmp_path / "ck")
    os.makedirs(ck, exist_ok=True)
    mesh = make_mesh(2, 4)
    monkeypatch.setenv("GLINT_PACKED_STOP_AFTER_GROUPS", "3")
    _w2v(batch_packing="dense", mesh=mesh).fit(CORPUS, checkpoint_dir=ck)
    monkeypatch.delenv("GLINT_PACKED_STOP_AFTER_GROUPS")
    state = json.load(open(os.path.join(ck, "train_state.json")))
    assert state["position"] > 0, state
    assert state["epochs_completed"] == 0, state
    m_resumed = _w2v(batch_packing="dense", mesh=mesh).fit(
        CORPUS, checkpoint_dir=ck
    )
    m_full = _w2v(batch_packing="dense", mesh=mesh).fit(CORPUS)
    np.testing.assert_array_equal(
        np.asarray(m_resumed.engine.syn0, np.float32),
        np.asarray(m_full.engine.syn0, np.float32),
    )
    np.testing.assert_array_equal(
        np.asarray(m_resumed.engine.syn1, np.float32),
        np.asarray(m_full.engine.syn1, np.float32),
    )
    final = json.load(open(os.path.join(ck, "train_state.json")))
    assert final["epochs_completed"] == 2 and final["position"] == 0


def test_packed_fit_boundary_checkpoint_resume(tmp_path):
    # Epoch-boundary save/resume (the existing grid contract) under
    # packing: the resumed run completes and serves queries.
    ck = str(tmp_path / "ck")
    os.makedirs(ck, exist_ok=True)
    m1 = _w2v(num_iterations=3, batch_packing="dense").fit(
        CORPUS, checkpoint_dir=ck, stop_after_epochs=1
    )
    assert m1.training_metrics["pipeline"] == "device_corpus"
    state = json.load(open(os.path.join(ck, "train_state.json")))
    assert state["epochs_completed"] == 1 and state["position"] == 0
    m2 = _w2v(num_iterations=3, batch_packing="dense").fit(
        CORPUS, checkpoint_dir=ck
    )
    assert m2.training_metrics["steps"] > 0
    assert len(m2.find_synonyms("dog", 2)) == 2


def test_mid_epoch_state_refuses_cross_mode_resume(tmp_path, monkeypatch):
    # A mid-epoch packed state resumed in grid mode would silently drop
    # the consumed-position counter and re-train the epoch's consumed
    # prefix; the loop must refuse instead. Epoch-BOUNDARY states
    # (position 0) stay resumable from either mode.
    ck = str(tmp_path / "ck")
    os.makedirs(ck, exist_ok=True)
    monkeypatch.setenv("GLINT_PACKED_STOP_AFTER_GROUPS", "2")
    _w2v(batch_packing="dense").fit(CORPUS, checkpoint_dir=ck)
    monkeypatch.delenv("GLINT_PACKED_STOP_AFTER_GROUPS")
    assert json.load(open(os.path.join(ck, "train_state.json")))["position"] > 0
    with pytest.raises(ValueError, match="batch_packing"):
        _w2v(batch_packing="grid").fit(CORPUS, checkpoint_dir=ck)
    # The (dense) default resumes its own mid-epoch state fine.
    _w2v().fit(CORPUS, checkpoint_dir=ck)
    ck2 = str(tmp_path / "ck2")
    os.makedirs(ck2, exist_ok=True)
    _w2v(num_iterations=2, batch_packing="dense").fit(
        CORPUS, checkpoint_dir=ck2, stop_after_epochs=1
    )
    m = _w2v(num_iterations=2, batch_packing="grid").fit(
        CORPUS, checkpoint_dir=ck2
    )
    assert m.training_metrics["pipeline"] == "device_corpus"


@pytest.mark.parametrize("architecture,subword", [
    ("skipgram", False), ("cbow", False), ("cbow", True)])
def test_packed_subsampled_checkpoint_resume(tmp_path, monkeypatch,
                                             architecture, subword):
    # Mid-epoch resume with subsampling: the epoch recompacts from
    # (seed, epoch) alone, so the restored position indexes the identical
    # compacted stream. A CBOW fit (bags of positions, a static advance)
    # keeps the same counters in the same state file, over words and over
    # subword groups (fastText's CBOW).
    ck = str(tmp_path / "ck")
    os.makedirs(ck, exist_ok=True)
    kw = dict(batch_packing="dense", subsample_ratio=0.01,
              architecture=architecture)
    make = _w2v
    if subword:
        from glint_word2vec_tpu import FastTextWord2Vec

        kw.update(bucket=200, min_n=3, max_n=4, max_subwords=8)

        def make(**k):
            return FastTextWord2Vec(**{**_w2v_defaults(), **k})

    monkeypatch.setenv("GLINT_PACKED_STOP_AFTER_GROUPS", "2")
    make(**kw).fit(CORPUS, checkpoint_dir=ck)
    monkeypatch.delenv("GLINT_PACKED_STOP_AFTER_GROUPS")
    state = json.load(open(os.path.join(ck, "train_state.json")))
    assert state["position"] > 0
    m_resumed = make(**kw).fit(CORPUS, checkpoint_dir=ck)
    m_full = make(**kw).fit(CORPUS)
    np.testing.assert_array_equal(
        np.asarray(m_resumed.engine.syn0, np.float32),
        np.asarray(m_full.engine.syn0, np.float32),
    )
