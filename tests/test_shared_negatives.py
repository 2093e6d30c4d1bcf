"""Shared negative-pool estimator: gradient math against a numpy reference,
mesh invariance, persistence of the mode, and an end-to-end quality gate.

The estimator (ops/sgns.py shared_sgns_grads) replaces the reference's
per-pair server-side draws (mllib:420-421) with one pool per step weighted
to the same expected NCE gradient — these tests pin the exact weighting.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu import Word2Vec
from glint_word2vec_tpu.ops import sgns
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
from glint_word2vec_tpu.parallel.mesh import make_mesh


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _numpy_shared_grads(h, u_pos, u_pool, mask, collide, alpha, n):
    """The shared-pool estimator in plain numpy: ``(c_pos, c_pool,
    d_center, d_pool, loss)``, every pool term weighted ``m_i * n / S``
    and dropped where the pool word is one of the row's contexts."""
    S = u_pool.shape[0]
    f_pos = np.einsum("bd,bcd->bc", h, u_pos)
    f_pool = h @ u_pool.T
    m_i = mask.sum(axis=1)
    weight = (m_i * (n / S))[:, None] * (1.0 - collide)
    c_pos = alpha * (1.0 - _sigmoid(f_pos)) * mask
    c_pool = -alpha * _sigmoid(f_pool) * weight
    d_center = np.einsum("bc,bcd->bd", c_pos, u_pos) + c_pool @ u_pool
    d_pool = c_pool.T @ h
    loss = (
        (-np.log(_sigmoid(f_pos)) * mask).sum()
        + (-np.log(_sigmoid(-f_pool)) * weight).sum()
    ) / max(mask.sum(), 1.0)
    return c_pos, c_pool, d_center, d_pool, loss


def _blocks(u):
    """Batch-major rows ``(B, ..., d)`` as the ``(K, B, d)`` blocks
    ``ops/sgns.row_dots`` takes (the engine transposes the ids instead)."""
    return jnp.moveaxis(u.reshape(u.shape[0], -1, u.shape[-1]), 1, 0)


def test_shared_grads_match_numpy_reference():
    rng = np.random.default_rng(0)
    B, C, S, d, n = 4, 3, 6, 8, 5
    h = rng.normal(size=(B, d)).astype(np.float32)
    u_pos = rng.normal(size=(B, C, d)).astype(np.float32)
    u_pool = rng.normal(size=(S, d)).astype(np.float32)
    mask = (rng.random((B, C)) < 0.7).astype(np.float32)
    collide = (rng.random((B, S)) < 0.2).astype(np.float32)
    alpha = 0.05

    g = sgns.shared_sgns_grads(
        jnp.asarray(h), _blocks(jnp.asarray(u_pos)), jnp.asarray(u_pool),
        jnp.asarray(mask), jnp.asarray(collide), jnp.float32(alpha), n,
    )

    c_pos, c_pool, d_center, d_pool, loss = _numpy_shared_grads(
        h, u_pos, u_pool, mask, collide, alpha, n
    )
    np.testing.assert_allclose(np.asarray(g.c_pos), c_pos, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g.c_pool), c_pool, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g.d_center), d_center, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g.d_pool), d_pool, rtol=1e-4, atol=1e-5)
    assert float(g.loss) == pytest.approx(loss, rel=1e-5)


def test_pool_collision_mask():
    pool = jnp.asarray(np.array([3, 7, 9], np.int32))
    contexts = jnp.asarray(np.array([[3, 5], [7, 7], [1, 2]], np.int32))
    mask = jnp.asarray(np.array([[1, 1], [0, 1], [1, 1]], np.float32))
    m = np.asarray(sgns.pool_collision_mask(pool, contexts, mask))
    # row 0: pool word 3 hits context 3
    np.testing.assert_array_equal(m[0], [1, 0, 0])
    # row 1: context 7 at slot 0 is masked out, slot 1 is real
    np.testing.assert_array_equal(m[1], [0, 1, 0])
    np.testing.assert_array_equal(m[2], [0, 0, 0])


V, D = 50, 16


def _mk(shape, shared):
    counts = np.arange(V, 0, -1).astype(np.int64) * 10
    return EmbeddingEngine(
        make_mesh(*shape), V, D, counts, num_negatives=4, seed=3,
        shared_negatives=shared,
    )


@pytest.mark.parametrize("shape", [(1, 1), (4, 2), (1, 8)])
def test_shared_mode_mesh_invariance(shape):
    ref = _mk((2, 4), shared=16)
    eng = _mk(shape, shared=16)
    rng = np.random.default_rng(4)
    B, C = 16, 5
    centers = rng.integers(0, V, B).astype(np.int32)
    contexts = rng.integers(0, V, (B, C)).astype(np.int32)
    mask = (rng.random((B, C)) < 0.8).astype(np.float32)
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


def test_shared_mode_save_load_roundtrip(tmp_path):
    eng = _mk((2, 4), shared=32)
    path = str(tmp_path / "m")
    eng.save(path)
    eng2 = EmbeddingEngine.load(path, make_mesh(1, 8))
    assert eng2.shared_negatives == 32
    np.testing.assert_array_equal(
        np.asarray(eng.syn0, np.float32)[:V],
        np.asarray(eng2.syn0, np.float32)[:V],
    )


def test_shared_mode_quality_gate(tiny_corpus):
    # End-to-end: the shared-pool estimator must learn the same structure
    # the per-pair mode does (the reference's behavioral quality bar,
    # Spec.scala:297-302).
    m = (
        Word2Vec(mesh=make_mesh(2, 4))
        .set_vector_size(48)
        .set_window_size(5)
        .set_step_size(0.025)
        .set_batch_size(256)
        .set_min_count(5)
        .set_num_iterations(6)
        .set_seed(1)
        .set_shared_negatives(256)
    ).fit(tiny_corpus)
    try:
        for country, capital in [("germany", "berlin"), ("france", "paris")]:
            hits = [w for w, _ in m.find_synonyms(country, 10)]
            assert capital in hits, (country, capital, hits)
    finally:
        m.stop()


def test_bf16_compute_dtype_close_to_f32():
    # The MXU fast path (bf16 operands, f32 accumulation) must agree with
    # the exactness-tested f32 path to bf16 operand precision — the same
    # update directions, just ~3-decimal-digit rounding on the operands.
    import jax.numpy as jnp

    from glint_word2vec_tpu.ops import sgns as S

    rng = np.random.default_rng(5)
    B, C, Sp, d, n = 8, 3, 16, 32, 4
    h = jnp.asarray(rng.normal(0, 0.5, (B, d)).astype(np.float32))
    u_pos = jnp.asarray(rng.normal(0, 0.5, (B, C, d)).astype(np.float32))
    u_pool = jnp.asarray(rng.normal(0, 0.5, (Sp, d)).astype(np.float32))
    mask = jnp.asarray((rng.random((B, C)) < 0.8).astype(np.float32))
    collide = jnp.zeros((B, Sp), jnp.float32)
    a = jnp.float32(0.05)

    u_pos = _blocks(u_pos)
    g32 = S.shared_sgns_grads(h, u_pos, u_pool, mask, collide, a, n)
    g16 = S.shared_sgns_grads(
        h, u_pos, u_pool, mask, collide, a, n, compute_dtype=jnp.bfloat16
    )
    np.testing.assert_allclose(
        np.asarray(g16.d_pool), np.asarray(g32.d_pool), rtol=0.05, atol=5e-4
    )
    np.testing.assert_allclose(
        np.asarray(g16.d_center), np.asarray(g32.d_center), rtol=0.05,
        atol=5e-4,
    )

    u_neg = _blocks(
        jnp.asarray(rng.normal(0, 0.5, (B, C, n, d)).astype(np.float32)))
    nmask = jnp.asarray((rng.random((B, C, n)) < 0.9).astype(np.float32))
    p32 = S.sgns_grads(h, u_pos, u_neg, mask, nmask, a)
    p16 = S.sgns_grads(
        h, u_pos, u_neg, mask, nmask, a, compute_dtype=jnp.bfloat16
    )
    np.testing.assert_allclose(
        np.asarray(p16.d_center), np.asarray(p32.d_center), rtol=0.05,
        atol=5e-4,
    )
