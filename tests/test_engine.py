"""Sharded embedding-engine tests on the virtual 8-device CPU mesh.

This is the distributed-correctness suite the reference runs as a Docker
pseudo-cluster integration test (SURVEY.md §4); here every Glint-op
equivalent is checked for exactness and for mesh-shape invariance.
"""

import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.corpus import build_unigram_alias
from glint_word2vec_tpu.ops import sgns
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
from glint_word2vec_tpu.parallel.mesh import make_mesh
from test_sgns import _numpy_oracle
from test_shared_negatives import _numpy_shared_grads

V, D = 50, 16  # deliberately not divisible by 8: exercises padding


def _mk_engine(num_data, num_model, seed=3):
    counts = np.arange(V, 0, -1).astype(np.int64) * 10
    mesh = make_mesh(num_data, num_model)
    return EmbeddingEngine(
        mesh, V, D, counts, num_negatives=4, seed=seed
    )


def _batch(B=16, C=5, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.integers(0, V, B).astype(np.int32)
    contexts = rng.integers(0, V, (B, C)).astype(np.int32)
    mask = (rng.random((B, C)) < 0.8).astype(np.float32)
    contexts = np.where(mask > 0, contexts, 0)
    return centers, contexts, mask


def test_mesh_construction_variants():
    assert make_mesh(2, 4).shape == {"data": 2, "model": 4}
    assert make_mesh(num_model=8).shape == {"data": 1, "model": 8}
    assert make_mesh(num_data=8).shape == {"data": 8, "model": 1}
    with pytest.raises(ValueError):
        make_mesh(3, 3)


def test_padding_geometry():
    eng = _mk_engine(2, 4)
    assert eng.padded_vocab == 52  # 50 -> multiple of 4
    assert eng.rows_per_shard == 13
    assert eng.cols == D
    # rows rest in whole 128-column lanes, the padding zero
    assert eng.padded_dim == 128 and eng.syn0.shape == (52, 128)
    assert not np.asarray(eng.syn0)[:, D:].any()
    with pytest.raises(ValueError, match="shape"):
        eng.top_k_cosine(np.ones(eng.padded_dim, np.float32), 3)


def test_pull_matches_host_tables():
    eng = _mk_engine(1, 8)
    syn0 = np.asarray(eng.syn0)[:V, :D]
    idx = np.array([0, 7, 49, 3, 3], np.int32)
    rows = np.asarray(eng.pull(idx))
    np.testing.assert_allclose(rows, syn0[idx], rtol=1e-6)


def test_norms_and_multiply_match_host():
    eng = _mk_engine(2, 4)
    syn0 = np.asarray(eng.syn0, dtype=np.float32)[:, :D]
    nrm = np.asarray(eng.norms())
    np.testing.assert_allclose(nrm, np.linalg.norm(syn0, axis=1), rtol=1e-5)
    v = np.random.default_rng(0).normal(size=D).astype(np.float32)
    scores = np.asarray(eng.multiply(v))
    np.testing.assert_allclose(scores, syn0 @ v, rtol=1e-4, atol=1e-5)


def test_pull_average_masked_mean_and_empty_row():
    eng = _mk_engine(1, 8)
    syn0 = np.asarray(eng.syn0)[:, :D]
    idx = np.array([[1, 2, 0], [5, 0, 0], [0, 0, 0]], np.int32)
    m = np.array([[1, 1, 0], [1, 0, 0], [0, 0, 0]], np.float32)
    out = np.asarray(eng.pull_average(idx, m))
    np.testing.assert_allclose(out[0], (syn0[1] + syn0[2]) / 2, rtol=1e-5)
    np.testing.assert_allclose(out[1], syn0[5], rtol=1e-6)
    # Empty sentence -> zero vector (reference empty-average semantics).
    np.testing.assert_array_equal(out[2], np.zeros(D, np.float32))


def test_top_k_cosine_matches_host():
    eng = _mk_engine(2, 4)
    syn0 = np.asarray(eng.syn0, dtype=np.float32)[:V, :D]
    q = syn0[17].copy()
    sims, idx = eng.top_k_cosine(q, 5)
    nrm = np.linalg.norm(syn0, axis=1)
    qn = q / np.linalg.norm(q)
    cos = (syn0 @ qn) / np.where(nrm > 0, nrm, 1.0)
    exp_idx = np.argsort(-cos)[:5]
    assert idx[0] == 17  # the word itself ranks first
    np.testing.assert_array_equal(np.sort(idx), np.sort(exp_idx))
    np.testing.assert_allclose(sims, cos[exp_idx], rtol=1e-5)


def test_train_step_matches_single_device_reference():
    # The sharded step on a (2,4) mesh must equal ops.sgns.train_step run
    # on the same (padded) tables — same key => same negatives (the
    # mesh-invariant sampling contract).
    eng = _mk_engine(2, 4)
    syn0_before = np.asarray(eng.syn0, dtype=np.float32)
    syn1_before = np.asarray(eng.syn1, dtype=np.float32)
    prob = np.asarray(eng._prob)
    alias = np.asarray(eng._alias)
    centers, contexts, mask = _batch(B=16, C=5)
    key = jax.random.PRNGKey(11)
    alpha = 0.03

    loss = eng.train_step(centers, contexts, mask, key, alpha)

    exp0, exp1, exp_loss = sgns.train_step(
        jnp.asarray(syn0_before), jnp.asarray(syn1_before),
        jnp.asarray(prob), jnp.asarray(alias),
        jnp.asarray(centers), jnp.asarray(contexts), jnp.asarray(mask),
        key, jnp.float32(alpha), num_negatives=4,
    )
    np.testing.assert_allclose(
        np.asarray(eng.syn0), np.asarray(exp0), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(eng.syn1), np.asarray(exp1), rtol=1e-5, atol=1e-6
    )
    assert float(loss) == pytest.approx(float(exp_loss), rel=1e-5)


@pytest.mark.parametrize(
    "shape", [(1, 1), (8, 1), (1, 8), (4, 2), (1, 4), (2, 4)])
def test_train_step_mesh_invariance(shape):
    # Identical seeds and batches must produce identical tables on every
    # mesh shape (up to float reduction order).
    ref = _mk_engine(2, 4)
    eng = _mk_engine(*shape)
    np.testing.assert_array_equal(
        np.asarray(ref.syn0, np.float32)[:V], np.asarray(eng.syn0, np.float32)[:V]
    )
    centers, contexts, mask = _batch(B=16, C=5, seed=4)
    key = jax.random.PRNGKey(5)
    l_ref = ref.train_step(centers, contexts, mask, key, 0.05)
    l_eng = eng.train_step(centers, contexts, mask, key, 0.05)
    assert float(l_ref) == pytest.approx(float(l_eng), rel=1e-5)
    np.testing.assert_allclose(
        np.asarray(ref.syn0, np.float32)[:V],
        np.asarray(eng.syn0, np.float32)[:V],
        rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(ref.syn1, np.float32)[:V],
        np.asarray(eng.syn1, np.float32)[:V],
        rtol=1e-5, atol=1e-6,
    )


def test_topk_batch_empty_query_batch():
    eng = _mk_engine(2, 4)
    sims, idx = eng.top_k_cosine_batch(np.zeros((0, D), np.float32), 5)
    assert sims.shape == (0, 5) and idx.shape == (0, 5)


def test_train_step_batch_divisibility_guard():
    eng = _mk_engine(2, 4)
    centers, contexts, mask = _batch(B=15)
    with pytest.raises(ValueError, match="divisible"):
        eng.train_step(centers, contexts, mask, jax.random.PRNGKey(0), 0.01)


def test_save_load_roundtrip_across_mesh_shapes(tmp_path):
    eng = _mk_engine(2, 4)
    centers, contexts, mask = _batch()
    eng.train_step(centers, contexts, mask, jax.random.PRNGKey(0), 0.05)
    syn0 = np.asarray(eng.syn0, np.float32)[:V]
    path = str(tmp_path / "m")
    eng.save(path)
    # Re-home onto a different "cluster" shape (mllib:696-725 analogue).
    eng2 = EmbeddingEngine.load(path, make_mesh(1, 8))
    np.testing.assert_allclose(
        np.asarray(eng2.syn0, np.float32)[:V], syn0, rtol=1e-6
    )
    assert eng2.vocab_size == V and eng2.dim == D
    # Loaded engine keeps training.
    eng2.train_step(centers, contexts, mask, jax.random.PRNGKey(1), 0.05)


def test_top_k_never_returns_padded_rows():
    # Padded vocab rows (zero norm) score -inf, so even a k covering most
    # of the vocab returns only real indices with finite sims.
    eng = _mk_engine(1, 8)  # padded_vocab 56 > V=50
    sims, idx = eng.top_k_cosine(np.ones(D, np.float32), V)
    assert np.all(idx < V)
    assert np.all(np.isfinite(sims))


def test_save_load_preserves_noise_geometry(tmp_path):
    counts = np.arange(V, 0, -1).astype(np.int64) * 10
    eng = EmbeddingEngine(
        make_mesh(1, 8), V, D, counts, num_negatives=4,
        unigram_power=0.5, seed=3,
    )
    path = str(tmp_path / "m")
    eng.save(path)
    eng2 = EmbeddingEngine.load(path, make_mesh(2, 4))
    assert eng2.unigram_power == 0.5
    np.testing.assert_array_equal(np.asarray(eng._prob), np.asarray(eng2._prob))


def test_write_rows_device_side():
    eng = _mk_engine(2, 4)
    block = jnp.ones((8, D), jnp.float32) * 3.0
    eng.write_rows(5, block)
    rows = np.asarray(eng.pull(np.arange(4, 14, dtype=np.int32)))
    np.testing.assert_array_equal(rows[1:9], np.full((8, D), 3.0, np.float32))
    assert not np.allclose(rows[0], 3.0)  # neighbors untouched
    assert not np.allclose(rows[9], 3.0)
    # Norms cache invalidated by the write.
    assert float(np.asarray(eng.norms())[5]) == pytest.approx(
        3.0 * np.sqrt(D), rel=1e-6
    )


def test_destroy_frees_tables():
    eng = _mk_engine(1, 8)
    eng.destroy()
    assert eng.syn0 is None and eng.syn1 is None


def test_train_steps_scan_matches_sequential_steps():
    # K scanned minibatches (one dispatch) must equal K train_step calls
    # with the fold_in(base_key, step0 + i) key schedule the scan uses.
    ref = _mk_engine(2, 4)
    eng = _mk_engine(2, 4)
    K, B, C = 3, 16, 5
    rng = np.random.default_rng(9)
    centers_k = rng.integers(0, V, (K, B)).astype(np.int32)
    contexts_k = rng.integers(0, V, (K, B, C)).astype(np.int32)
    mask_k = (rng.random((K, B, C)) < 0.8).astype(np.float32)
    base_key = jax.random.PRNGKey(21)
    alphas = np.array([0.05, 0.04, 0.03], np.float32)
    step0 = 7

    seq_losses = [
        float(
            ref.train_step(
                centers_k[i], contexts_k[i], mask_k[i],
                jax.random.fold_in(base_key, step0 + i), float(alphas[i]),
            )
        )
        for i in range(K)
    ]
    scan_losses = np.asarray(
        eng.train_steps(centers_k, contexts_k, mask_k, base_key, alphas, step0)
    )
    np.testing.assert_allclose(scan_losses, seq_losses, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(eng.syn0, np.float32)[:V],
        np.asarray(ref.syn0, np.float32)[:V],
        rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(eng.syn1, np.float32)[:V],
        np.asarray(ref.syn1, np.float32)[:V],
        rtol=1e-5, atol=1e-6,
    )


def test_train_steps_grouped_scan_matches_sequential():
    # Subword (grouped-center) scan path against step-at-a-time.
    counts = np.arange(V, 0, -1).astype(np.int64) * 10
    ref = EmbeddingEngine(
        make_mesh(2, 4), V, D, counts, num_negatives=4, seed=3, extra_rows=8
    )
    eng = EmbeddingEngine(
        make_mesh(2, 4), V, D, counts, num_negatives=4, seed=3, extra_rows=8
    )
    K, B, S, C = 2, 8, 3, 5
    rng = np.random.default_rng(10)
    groups_k = rng.integers(0, V + 8, (K, B, S)).astype(np.int32)
    gmask_k = (rng.random((K, B, S)) < 0.9).astype(np.float32)
    contexts_k = rng.integers(0, V, (K, B, C)).astype(np.int32)
    mask_k = (rng.random((K, B, C)) < 0.8).astype(np.float32)
    base_key = jax.random.PRNGKey(2)
    alphas = np.array([0.05, 0.02], np.float32)

    for i in range(K):
        ref.train_step_grouped(
            groups_k[i], gmask_k[i], contexts_k[i], mask_k[i],
            jax.random.fold_in(base_key, i), float(alphas[i]),
        )
    eng.train_steps_grouped(
        groups_k, gmask_k, contexts_k, mask_k, base_key, alphas, 0
    )
    np.testing.assert_allclose(
        np.asarray(eng.syn0, np.float32)[: V + 8],
        np.asarray(ref.syn0, np.float32)[: V + 8],
        rtol=1e-5, atol=1e-6,
    )


def test_zero_mask_batch_is_noop():
    # The fit() epoch-tail padding contract: a batch whose context mask is
    # all zero must leave both tables bitwise unchanged.
    eng = _mk_engine(2, 4)
    s0 = np.asarray(eng.syn0, np.float32).copy()
    s1 = np.asarray(eng.syn1, np.float32).copy()
    B, C = 16, 5
    centers = np.zeros(B, np.int32)
    contexts = np.zeros((B, C), np.int32)
    mask = np.zeros((B, C), np.float32)
    eng.train_step(centers, contexts, mask, jax.random.PRNGKey(0), 0.05)
    np.testing.assert_array_equal(np.asarray(eng.syn0, np.float32), s0)
    np.testing.assert_array_equal(np.asarray(eng.syn1, np.float32), s1)


def test_sharded_save_writes_per_shard_files_and_reloads(tmp_path):
    # Sharded save: one row-block file per model shard, manifest in
    # engine.json, reload onto a *different* mesh shape bit-exact.
    eng = _mk_engine(2, 4)
    centers, contexts, mask = _batch(B=16, C=5, seed=7)
    eng.train_step(centers, contexts, mask, jax.random.PRNGKey(3), 0.05)
    path = str(tmp_path / "m")
    eng.save(path)  # default sharded
    import json as _json

    files = sorted(os.listdir(path))
    assert "syn0.npy" not in files  # no full-table file
    assert sum(
        f.startswith("syn0.r") and f.endswith(".npy") for f in files
    ) == 4
    # ISSUE 15: every shard block carries its sidecar manifest.
    assert sum(
        f.startswith("syn0.r") and f.endswith(".npy.manifest.json")
        for f in files
    ) == 4
    with open(os.path.join(path, "engine.json")) as f:
        meta = _json.load(f)
    assert meta["format"] == "sharded"
    assert len(meta["shards"]["syn1"]) == 4

    eng2 = EmbeddingEngine.load(path, make_mesh(8, 1))
    np.testing.assert_array_equal(
        np.asarray(eng.syn0, np.float32)[:V],
        np.asarray(eng2.syn0, np.float32)[:V],
    )
    np.testing.assert_array_equal(
        np.asarray(eng.syn1, np.float32)[:V],
        np.asarray(eng2.syn1, np.float32)[:V],
    )


def test_single_mode_save_still_loads(tmp_path):
    eng = _mk_engine(1, 8)
    path = str(tmp_path / "m")
    eng.save(path, mode="single")
    assert os.path.exists(os.path.join(path, "syn0.npy"))
    eng2 = EmbeddingEngine.load(path, make_mesh(2, 4))
    np.testing.assert_array_equal(
        np.asarray(eng.syn0, np.float32)[:V],
        np.asarray(eng2.syn0, np.float32)[:V],
    )


def test_load_tables_geometry_mismatch_raises(tmp_path):
    eng = _mk_engine(1, 8)
    path = str(tmp_path / "m")
    eng.save(path)
    counts = np.arange(V + 1, 0, -1).astype(np.int64)
    other = EmbeddingEngine(make_mesh(1, 8), V + 1, D, counts, seed=0)
    with pytest.raises(ValueError, match="geometry"):
        other.load_tables(path)


def test_data_axis_exchange_ships_scalars_not_payloads():
    # Lock in the O(B*(d + pairs)) data-axis exchange (the TPU form of the
    # reference's ship-scalars-only property, mllib:422-425): total
    # all-gather output bytes in the compiled step must stay far below the
    # expanded rank-1 payload B*C*(1+n)*d it used to ship.
    import re

    B, C, D2 = 16, 5, 64
    counts = np.arange(V, 0, -1).astype(np.int64) * 10
    eng = EmbeddingEngine(make_mesh(4, 2), V, D2, counts, num_negatives=4)
    centers, contexts, mask = _batch(B=B, C=C)
    cg = jnp.asarray(centers[:, None])
    gm = jnp.ones((B, 1), jnp.float32)
    lowered = eng._train_step.lower(
        eng.syn0, eng.syn1, eng._alias_packed,
        cg, gm, jnp.asarray(contexts), jnp.asarray(mask),
        jax.random.PRNGKey(0), jnp.float32(0.05),
    )
    hlo = lowered.compile().as_text()
    gathered = 0
    for m in re.finditer(
        r"= (f32|s32|u32|bf16)\[([\d,]*)\][^=]*? all-gather\(", hlo
    ):
        dims = [int(x) for x in m.group(2).split(",") if x]
        elems = int(np.prod(dims)) if dims else 1
        width = 2 if m.group(1) == "bf16" else 4
        gathered += elems * width
    n = eng.num_negatives
    expanded_payload = B * C * (1 + n) * D2 * 4  # the old exchange, bytes
    # New exchange: h + d_center (2*B*d) + coefficient scalars + ids +
    # group mask — all small multiples of B.
    budget = 4 * (2 * B * D2 + 4 * B * C * (1 + n) + 2 * B) * 2  # 2x slack
    assert 0 < gathered <= budget, (gathered, budget)
    assert gathered < expanded_payload / 4, (gathered, expanded_payload)


@pytest.mark.parametrize("shape", [(1, 1), (2, 1), (4, 2)])
def test_negative_draws_slice_invariant_across_ranks(shape):
    # Round-3 directive: per-pair negatives must be drawn per GLOBAL row
    # (fold_in(key, global_row)) so a rank holding rows [r0, r0+Bl) draws
    # exactly what a 1-rank run draws for those rows, with no B_global in
    # any sampled shape.
    from glint_word2vec_tpu.ops.sampling import sample_negatives_per_row

    t = build_unigram_alias(np.arange(1, V + 1).astype(np.int64))
    prob, alias = jnp.asarray(t.prob), jnp.asarray(t.alias)
    key = jax.random.PRNGKey(3)
    full = np.asarray(
        sample_negatives_per_row(
            key, prob, alias, jnp.arange(16, dtype=jnp.int32), (3, 4)
        )
    )
    ranks, _ = shape
    Bl = 16 // ranks
    for r in range(ranks):
        rows = jnp.arange(r * Bl, (r + 1) * Bl, dtype=jnp.int32)
        part = np.asarray(
            sample_negatives_per_row(key, prob, alias, rows, (3, 4))
        )
        assert part.shape == (Bl, 3, 4)  # local rows only, no B_global
        np.testing.assert_array_equal(part, full[r * Bl : (r + 1) * Bl])


def test_device_resident_inputs_no_host_bounce():
    # Device-resident batches must be used in place: no device->host
    # transfer anywhere in train_step/train_steps, and results identical
    # to the numpy-input path. (A previous unconditional np.asarray
    # bounced every jax.Array input through the host — a blocking D2H
    # copy plus re-upload per dispatch.)
    ref = _mk_engine(2, 2, seed=5)
    eng = _mk_engine(2, 2, seed=5)
    centers, contexts, mask = _batch(B=16)
    key = jax.random.PRNGKey(11)

    ref.train_step(centers, contexts, mask, key, 0.04)

    dc, dx, dm = map(jax.device_put, (centers, contexts, mask))
    with jax.transfer_guard_device_to_host("disallow"):
        eng.train_step(dc, dx, dm, key, 0.04)
    np.testing.assert_allclose(
        np.asarray(eng.syn0, np.float32),
        np.asarray(ref.syn0, np.float32),
        rtol=1e-6,
    )

    K = 2
    rng = np.random.default_rng(13)
    ck = rng.integers(0, V, (K, 16)).astype(np.int32)
    xk = rng.integers(0, V, (K, 16, 5)).astype(np.int32)
    mk = (rng.random((K, 16, 5)) < 0.8).astype(np.float32)
    al = np.full(K, 0.03, np.float32)
    ref.train_steps(ck, xk, mk, key, al, 0)
    dck, dxk, dmk = map(jax.device_put, (ck, xk, mk))
    with jax.transfer_guard_device_to_host("disallow"):
        eng.train_steps(dck, dxk, dmk, key, al, 0)
    np.testing.assert_allclose(
        np.asarray(eng.syn1, np.float32),
        np.asarray(ref.syn1, np.float32),
        rtol=1e-6,
    )


@pytest.mark.parametrize(
    "kw,want",
    [
        ({}, "rows/per_pair/xla"),
        ({"shared_negatives": 8}, "rows/shared_pool/xla"),
    ],
)
def test_step_body_names_what_runs(kw, want):
    eng = EmbeddingEngine(make_mesh(1, 1), V, D, np.ones(V, np.int64), **kw)
    assert eng.step_body == want


@pytest.mark.parametrize(
    "mesh,negatives,dtype,form",
    list(itertools.product(
        [(2, 4), (1, 1)], ["per_pair", "shared_pool"],
        ["float32", "bfloat16"], ["grid", "pair"],
    )),
)
def test_engine_step_matches_numpy_oracle(mesh, negatives, dtype, form):
    """One step of every body the engine can trace (negatives x storage
    dtype x grid or pair form), on a 2 x 4 mesh and on one device, against
    a numpy oracle that is handed the step's own draws: the net under ROADMAP
    D1's matrix. float32 tables within reduction order; bfloat16 ones
    within the README's mixed-precision bound (a row's float32 batch
    total rounded once, then one bfloat16 add)."""
    from glint_word2vec_tpu.ops.sampling import (
        sample_negatives,
        sample_negatives_per_row,
    )

    n, S, B, alpha = 4, 16, 16, 0.05
    C = 5 if form == "grid" else 1
    counts = np.arange(V, 0, -1).astype(np.int64) * 10
    eng = EmbeddingEngine(
        make_mesh(*mesh), V, D, counts, num_negatives=n, seed=3, dtype=dtype,
        shared_negatives=S if negatives == "shared_pool" else 0,
    )
    assert eng.step_body == f"rows/{negatives}/xla"  # a CPU mesh
    rng = np.random.default_rng(12)
    eng.set_tables(
        rng.normal(0, 0.3, (V, D)).astype(np.float32),
        rng.normal(0, 0.3, (V, D)).astype(np.float32),
    )
    # what the tables hold once stored (bfloat16 engines round them)
    s0 = np.asarray(eng.syn0, np.float32)[:V, :D]
    s1 = np.asarray(eng.syn1, np.float32)[:V, :D]
    centers = rng.integers(0, V, B).astype(np.int32)
    centers[:3] = centers[3]  # one center four times
    contexts = rng.integers(0, V, (B, C)).astype(np.int32)
    mask = (rng.random((B, C)) < 0.8).astype(np.float32)
    contexts = np.where(mask > 0, contexts, 0)
    key = jax.random.PRNGKey(5)

    loss = eng.train_step(centers, contexts, mask, key, alpha)

    if negatives == "per_pair":
        negs = sample_negatives_per_row(
            key, eng._prob, eng._alias, jnp.arange(B, dtype=jnp.int32), (C, n)
        )
        nmask = np.asarray(sgns.negative_mask(
            negs, jnp.asarray(contexts), jnp.asarray(mask)
        ))
        exp0, exp1 = _numpy_oracle(
            s0, s1, centers, contexts, mask, np.asarray(negs), nmask, alpha
        )
    else:
        pool = np.asarray(sample_negatives(key, eng._prob, eng._alias, (S,)))
        collide = (
            (pool[None, None, :] == contexts[:, :, None])
            & (mask[:, :, None] > 0)
        ).any(axis=1).astype(np.float32)
        h, u_pos = s0[centers], s1[contexts]
        c_pos, _, d_center, d_pool, exp_loss = _numpy_shared_grads(
            h, u_pos, s1[pool], mask, collide, alpha, n
        )
        exp0, exp1 = s0.copy(), s1.copy()
        np.add.at(exp0, centers, d_center)
        np.add.at(exp1, contexts.reshape(-1),
                  (c_pos[:, :, None] * h[:, None, :]).reshape(-1, D))
        np.add.at(exp1, pool, d_pool)
        assert float(loss) == pytest.approx(exp_loss, rel=1e-4)
    assert np.isfinite(float(loss))
    for got, exp, start in ((eng.syn0, exp0, s0), (eng.syn1, exp1, s1)):
        got = np.asarray(got, np.float32)
        # the padding stays zero: rows past V, columns past D
        assert not got[V:].any() and not got[:, D:].any()
        got = got[:V, :D]
        assert np.abs(exp - start).max() > 1e-3  # an update worth the name
        if dtype == "float32":
            np.testing.assert_allclose(got, exp, rtol=2e-5, atol=2e-6)
        else:
            bound = 2.0 ** -8 * (np.abs(exp - start) + np.abs(exp)) + 1e-6
            assert (np.abs(got - exp) <= bound).all(), (
                (np.abs(got - exp) - bound).max()
            )
