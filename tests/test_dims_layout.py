"""Dim-sharded ("dims") engine layout tests on the virtual 8-device mesh.

The dims layout is the CIKM'16 column partitioning the reference's
parameter servers implement (SURVEY.md §2.2 sharding note: each server
holds a slice of every word's dimensions and returns *partial* dot
products). These tests pin the property that makes it worth having: the
layout is a pure execution-strategy choice — bitwise-equivalent training
(up to float reduction order) and identical query results vs the
row-sharded layout, with model-axis traffic reduced to scalar logits
(locked by the HLO test).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
from glint_word2vec_tpu.parallel.mesh import make_mesh

V, D = 50, 12  # D deliberately not divisible by 4/8: exercises col padding


def _mk(layout, num_data, num_model, shared=0, seed=3):
    counts = np.arange(V, 0, -1).astype(np.int64) * 10
    return EmbeddingEngine(
        make_mesh(num_data, num_model), V, D, counts, num_negatives=4,
        seed=seed, layout=layout, shared_negatives=shared,
    )


def _batch(B=16, C=5, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.integers(0, V, B).astype(np.int32)
    contexts = rng.integers(0, V, (B, C)).astype(np.int32)
    mask = (rng.random((B, C)) < 0.8).astype(np.float32)
    contexts = np.where(mask > 0, contexts, 0)
    return centers, contexts, mask


def _tables(eng):
    return (
        np.asarray(eng.syn0, np.float32)[:V, :D],
        np.asarray(eng.syn1, np.float32)[:V, :D],
    )


@pytest.mark.parametrize("shape", [(1, 1), (1, 8), (2, 4), (8, 1)])
def test_dims_train_step_matches_rows_layout(shape):
    ref = _mk("rows", 2, 4)
    eng = _mk("dims", *shape)
    np.testing.assert_array_equal(_tables(ref)[0], _tables(eng)[0])
    centers, contexts, mask = _batch()
    key = jax.random.PRNGKey(5)
    l_ref = ref.train_step(centers, contexts, mask, key, 0.05)
    l_eng = eng.train_step(centers, contexts, mask, key, 0.05)
    assert float(l_ref) == pytest.approx(float(l_eng), rel=1e-5)
    for a, b in zip(_tables(ref), _tables(eng)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_dims_shared_negatives_matches_rows_layout():
    ref = _mk("rows", 2, 4, shared=16)
    eng = _mk("dims", 4, 2, shared=16)
    centers, contexts, mask = _batch(seed=2)
    key = jax.random.PRNGKey(9)
    l_ref = ref.train_step(centers, contexts, mask, key, 0.05)
    l_eng = eng.train_step(centers, contexts, mask, key, 0.05)
    assert float(l_ref) == pytest.approx(float(l_eng), rel=1e-5)
    for a, b in zip(_tables(ref), _tables(eng)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_dims_query_ops_match_host():
    eng = _mk("dims", 2, 4)
    syn0 = _tables(eng)[0]
    idx = np.array([0, 7, 49, 3, 3], np.int32)
    np.testing.assert_allclose(
        np.asarray(eng.pull(idx)), syn0[idx], rtol=1e-6
    )
    # pull_average
    sent = np.array([[1, 2, 3, 0], [4, 4, 0, 0]], np.int32)
    m = np.array([[1, 1, 1, 0], [1, 1, 0, 0]], np.float32)
    got = np.asarray(eng.pull_average(sent, m))
    exp = np.stack([syn0[[1, 2, 3]].mean(0), syn0[[4, 4]].mean(0)])
    np.testing.assert_allclose(got[:, :D], exp, rtol=1e-5, atol=1e-7)
    # norms (replicated, num_rows length)
    nrm = np.asarray(eng.norms())
    np.testing.assert_allclose(
        nrm[:V], np.linalg.norm(syn0, axis=1), rtol=1e-5
    )
    # multiply
    v = np.linspace(-1, 1, D).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(eng.multiply(v))[:V], syn0 @ v, rtol=1e-4, atol=1e-6
    )
    # top-k
    q = syn0[17].copy()
    sims, idx = eng.top_k_cosine(q, 5)
    cos = (syn0 @ (q / np.linalg.norm(q))) / np.linalg.norm(syn0, axis=1)
    exp_idx = np.argsort(-cos)[:5]
    assert idx[0] == 17
    np.testing.assert_array_equal(np.sort(idx), np.sort(exp_idx))
    np.testing.assert_allclose(sims, cos[exp_idx], rtol=1e-5)
    # batched top-k
    qs = syn0[[5, 9]].copy()
    bs, bi = eng.top_k_cosine_batch(qs, 3)
    assert bi[0, 0] == 5 and bi[1, 0] == 9


def test_dims_save_load_roundtrips_across_layouts(tmp_path):
    eng = _mk("dims", 2, 4)
    centers, contexts, mask = _batch()
    eng.train_step(centers, contexts, mask, jax.random.PRNGKey(0), 0.05)
    s0, s1 = _tables(eng)
    p1 = str(tmp_path / "dims_ckpt")
    eng.save(p1)
    # dims checkpoint -> dims engine on another mesh
    e2 = EmbeddingEngine.load(p1, make_mesh(1, 8))
    assert e2.layout == "dims"
    np.testing.assert_array_equal(_tables(e2)[0], s0)
    # dims checkpoint -> ROWS engine (cross-layout re-homing)
    e3 = EmbeddingEngine.load(p1, make_mesh(2, 4), layout="rows")
    assert e3.layout == "rows"
    np.testing.assert_array_equal(_tables(e3)[0], s0)
    np.testing.assert_array_equal(_tables(e3)[1], s1)
    # rows checkpoint -> dims engine
    p2 = str(tmp_path / "rows_ckpt")
    e3.save(p2)
    e4 = EmbeddingEngine.load(p2, make_mesh(1, 8), layout="dims")
    np.testing.assert_array_equal(_tables(e4)[0], s0)
    # loaded engines keep training
    e4.train_step(centers, contexts, mask, jax.random.PRNGKey(1), 0.05)


def test_dims_grouped_centers_subword_path():
    ref = _mk("rows", 1, 1)
    eng = _mk("dims", 2, 4)
    rng = np.random.default_rng(7)
    B, S, C = 8, 3, 4
    groups = rng.integers(0, V, (B, S)).astype(np.int32)
    gmask = (rng.random((B, S)) < 0.7).astype(np.float32)
    gmask[:, 0] = 1.0  # at least one live row per group
    contexts = rng.integers(0, V, (B, C)).astype(np.int32)
    mask = np.ones((B, C), np.float32)
    key = jax.random.PRNGKey(3)
    l_ref = ref.train_step_grouped(groups, gmask, contexts, mask, key, 0.05)
    l_eng = eng.train_step_grouped(groups, gmask, contexts, mask, key, 0.05)
    assert float(l_ref) == pytest.approx(float(l_eng), rel=1e-5)
    for a, b in zip(_tables(ref), _tables(eng)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_dims_model_axis_traffic_is_scalar_logits():
    # The layout's reason to exist: the train step's model-axis collectives
    # carry logit partials and the pool update only — never gathered rows.
    # Budget: psums of (B, C), (B, C, n) [+ (S_pool, dl) + (B, S_pool) in
    # shared mode] + the loss scalar, with 2x slack; the rows layout's
    # row-psum traffic (B*C*(1+n)*d floats) must stay far above it.
    B, C, D2 = 16, 5, 64
    counts = np.arange(V, 0, -1).astype(np.int64) * 10
    eng = EmbeddingEngine(
        make_mesh(2, 4), V, D2, counts, num_negatives=4, layout="dims"
    )
    centers, contexts, mask = _batch(B=B, C=C)
    lowered = eng._train_step.lower(
        eng.syn0, eng.syn1, eng._alias_packed,
        jnp.asarray(centers[:, None]),
        jnp.ones((B, 1), jnp.float32),
        jnp.asarray(contexts), jnp.asarray(mask),
        jax.random.PRNGKey(0), jnp.float32(0.05),
    )
    hlo = lowered.compile().as_text()
    reduced = 0
    # psum lowers to (possibly tuple-shaped) all-reduce ops:
    #   %all-reduce = (f32[8,5]{1,0}, f32[8,5,4]{2,1,0}) all-reduce(...)
    for m in re.finditer(r"= (\([^)]*\)|[^ ]+) all-reduce", hlo):
        for t in re.finditer(r"(f32|s32|u32|bf16)\[([\d,]*)\]", m.group(1)):
            dims_ = [int(x) for x in t.group(2).split(",") if x]
            elems = int(np.prod(dims_)) if dims_ else 1
            reduced += elems * (2 if t.group(1) == "bf16" else 4)
    n = eng.num_negatives
    # Model-axis psums (logits) + data-axis psums (loss); all-gathers are
    # counted by the exchange test in test_engine.py.
    budget = 4 * (B * C + B * C * n + 4) * 2
    row_psum_traffic = B * C * (1 + n) * D2 * 4
    assert 0 < reduced <= budget, (reduced, budget)
    assert reduced < row_psum_traffic / 4, (reduced, row_psum_traffic)


@pytest.mark.parametrize("layout", ["rows", "dims"])
def test_topk_batch_empty_query_batch(layout):
    eng = _mk(layout, 2, 4)
    sims, idx = eng.top_k_cosine_batch(np.zeros((0, D), np.float32), 5)
    assert sims.shape == (0, 5) and idx.shape == (0, 5)


def test_dims_data_axis_exchange_ships_scalars_not_payloads():
    # Mirror of test_engine.py's rows-layout exchange test: the dims
    # layout's data-axis all-gathers must also carry only h slices +
    # scalar coefficients + ids, never expanded rank-1 payloads.
    B, C, D2 = 16, 5, 64
    counts = np.arange(V, 0, -1).astype(np.int64) * 10
    eng = EmbeddingEngine(
        make_mesh(4, 2), V, D2, counts, num_negatives=4, layout="dims"
    )
    centers, contexts, mask = _batch(B=B, C=C)
    lowered = eng._train_step.lower(
        eng.syn0, eng.syn1, eng._alias_packed,
        jnp.asarray(centers[:, None]), jnp.ones((B, 1), jnp.float32),
        jnp.asarray(contexts), jnp.asarray(mask),
        jax.random.PRNGKey(0), jnp.float32(0.05),
    )
    hlo = lowered.compile().as_text()
    gathered = 0
    for m in re.finditer(r"= (\([^)]*\)|[^ ]+) all-gather", hlo):
        for t in re.finditer(r"(f32|s32|u32|bf16)\[([\d,]*)\]", m.group(1)):
            dims_ = [int(x) for x in t.group(2).split(",") if x]
            elems = int(np.prod(dims_)) if dims_ else 1
            gathered += elems * (2 if t.group(1) == "bf16" else 4)
    n = eng.num_negatives
    dl = eng.cols_per_shard
    expanded_payload = B * C * (1 + n) * dl * 4
    # h slice + d_center slice (2*B*dl) + coef scalars + ids + group mask.
    budget = 4 * (2 * B * dl + 4 * B * C * (1 + n) + 2 * B) * 2
    assert 0 < gathered <= budget, (gathered, budget)
    assert gathered < expanded_payload, (gathered, expanded_payload)
