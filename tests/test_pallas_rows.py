"""Pallas row kernels, exercised in interpret mode on CPU (semantics; the
performance question is a per-hardware measurement, the kernels are opt-in).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.ops.pallas_rows import gather_rows, scatter_add_rows

V, D = 64, 16


def test_gather_rows_matches_indexing():
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(V, D)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, V, 37).astype(np.int32))
    out = gather_rows(table, ids, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(table)[ids])


def test_scatter_add_rows_row0_duplicates():
    # Row 0 receiving both real updates and many duplicates is the exact
    # traffic the engine generates (disowned indices clip to local row 0):
    # the sorted/consecutive-accumulate design must sum them all correctly.
    rng = np.random.default_rng(3)
    table = rng.normal(size=(V, D)).astype(np.float32)
    ids = np.zeros(17, np.int32)
    ids[10:] = rng.integers(0, V, 7)
    upd = rng.normal(size=(17, D)).astype(np.float32)
    out = scatter_add_rows(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(upd),
        interpret=True,
    )
    expected = jnp.asarray(table).at[jnp.asarray(ids)].add(jnp.asarray(upd))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
    )


def test_scatter_add_rows_matches_at_add_with_duplicates():
    rng = np.random.default_rng(1)
    table = rng.normal(size=(V, D)).astype(np.float32)
    ids = rng.integers(0, V, 50).astype(np.int32)
    ids[:10] = 7  # heavy duplication
    upd = rng.normal(size=(50, D)).astype(np.float32)
    out = scatter_add_rows(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(upd),
        interpret=True,
    )
    expected = jnp.asarray(table).at[jnp.asarray(ids)].add(jnp.asarray(upd))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
    )


def test_scatter_add_rows_bfloat16_table():
    rng = np.random.default_rng(2)
    table = jnp.asarray(
        rng.normal(size=(V, D)).astype(np.float32), dtype=jnp.bfloat16
    )
    ids = jnp.asarray(rng.integers(0, V, 20).astype(np.int32))
    upd = jnp.asarray(rng.normal(size=(20, D)).astype(np.float32))
    out = scatter_add_rows(table, ids, upd, interpret=True)
    expected = table.at[ids].add(upd.astype(jnp.bfloat16))
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32),
        np.asarray(expected, dtype=np.float32),
        rtol=0.05, atol=0.05,  # bf16 rounding differs by accumulation path
    )


def test_engine_pallas_mode_matches_default():
    # Full sharded train step with the Pallas row kernels (interpret mode
    # on the CPU mesh) must match the XLA-lowered default bit-for-bit in
    # float32.
    import jax as _jax
    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    Vv, Dd = 50, 16
    counts = np.arange(Vv, 0, -1).astype(np.int64) * 10
    ref = EmbeddingEngine(make_mesh(2, 4), Vv, Dd, counts,
                          num_negatives=3, seed=3)
    eng = EmbeddingEngine(make_mesh(2, 4), Vv, Dd, counts,
                          num_negatives=3, seed=3, use_pallas=True)
    assert eng._pallas_interpret
    rng = np.random.default_rng(8)
    B, C = 8, 4
    centers = rng.integers(0, Vv, B).astype(np.int32)
    contexts = rng.integers(0, Vv, (B, C)).astype(np.int32)
    mask = (rng.random((B, C)) < 0.8).astype(np.float32)
    key = _jax.random.PRNGKey(5)
    l_ref = ref.train_step(centers, contexts, mask, key, 0.05)
    l_eng = eng.train_step(centers, contexts, mask, key, 0.05)
    assert float(l_ref) == pytest.approx(float(l_eng), rel=1e-5)
    np.testing.assert_allclose(
        np.asarray(ref.syn0, np.float32)[:Vv],
        np.asarray(eng.syn0, np.float32)[:Vv],
        rtol=1e-5, atol=1e-6,
    )
    # Query path through the pallas gather too.
    np.testing.assert_allclose(
        np.asarray(ref.pull(np.arange(5, dtype=np.int32))),
        np.asarray(eng.pull(np.arange(5, dtype=np.int32))),
        rtol=1e-6,
    )


@pytest.mark.parametrize("block_rows", [4, 8])
@pytest.mark.parametrize("n", [1, 7, 8, 9, 31])
def test_scatter_block_boundary_runs(block_rows, n):
    """Runs of equal ids spanning grid-step boundaries, pad rows extending
    the final run, and N not divisible by block_rows must all still SUM:
    the multi-row kernel's riskiest cases (sequential-step RMW ordering and
    the edge-padding rule)."""
    rng = np.random.default_rng(n * 31 + block_rows)
    table = rng.normal(size=(V, D)).astype(np.float32)
    # Long runs: few distinct ids so runs routinely cross block boundaries.
    ids = np.sort(rng.integers(0, 3, n).astype(np.int32))
    upd = rng.normal(size=(n, D)).astype(np.float32)
    out = scatter_add_rows(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(upd),
        interpret=True, block_rows=block_rows,
    )
    expected = jnp.asarray(table).at[jnp.asarray(ids)].add(jnp.asarray(upd))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("n", [1, 15, 16, 33])
def test_gather_non_multiple_sizes(n):
    rng = np.random.default_rng(n)
    table = jnp.asarray(rng.normal(size=(V, D)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, V, n).astype(np.int32))
    out = gather_rows(table, ids, interpret=True, block_rows=16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(table)[ids])


def test_scatter_single_id_whole_batch():
    # Every update targets one row (the worst-case hot-row skew): one run
    # spanning every block.
    rng = np.random.default_rng(9)
    table = rng.normal(size=(V, D)).astype(np.float32)
    ids = np.full(29, 5, np.int32)
    upd = rng.normal(size=(29, D)).astype(np.float32)
    out = scatter_add_rows(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(upd),
        interpret=True, block_rows=8,
    )
    expected = jnp.asarray(table).at[jnp.asarray(ids)].add(jnp.asarray(upd))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=1e-4, atol=1e-4
    )


def test_scatter_add_rank1_matches_numpy():
    # The fused-payload scatter: table.at[ids].add(coef * h[hidx]) with the
    # (N, d) payload formed in VMEM, never in HBM. Duplicates must sum.
    from glint_word2vec_tpu.ops.pallas_rows import scatter_add_rank1

    rng = np.random.default_rng(3)
    V, d, B, N = 40, 16, 12, 64
    table = jnp.asarray(rng.normal(0, 1, (V, d)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, V, N), jnp.int32)
    ids = ids.at[:8].set(7)  # forced duplicate run
    coef = jnp.asarray(rng.normal(0, 1, N).astype(np.float32))
    h = jnp.asarray(rng.normal(0, 1, (B, d)).astype(np.float32))
    hidx = jnp.asarray(rng.integers(0, B, N), jnp.int32)
    exp = np.asarray(table).copy()
    np.add.at(
        exp, np.asarray(ids),
        np.asarray(coef)[:, None] * np.asarray(h)[np.asarray(hidx)],
    )
    got = scatter_add_rank1(table, ids, coef, h, hidx, interpret=True)
    np.testing.assert_allclose(np.asarray(got), exp, rtol=1e-5, atol=1e-6)


def test_pallas_engine_syn1_matches_xla_both_layouts():
    # The fused rank-1 scatter writes syn1; compare BOTH tables against the
    # XLA engine, in both layouts.
    import jax as _jax

    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    Vv, Dd = 50, 16
    counts = np.arange(Vv, 0, -1).astype(np.int64) * 10
    rng = np.random.default_rng(8)
    B, C = 8, 4
    centers = rng.integers(0, Vv, B).astype(np.int32)
    contexts = rng.integers(0, Vv, (B, C)).astype(np.int32)
    mask = (rng.random((B, C)) < 0.8).astype(np.float32)
    key = _jax.random.PRNGKey(5)
    for layout in ("rows", "dims"):
        ref = EmbeddingEngine(make_mesh(2, 4), Vv, Dd, counts,
                              num_negatives=3, seed=3, layout=layout)
        eng = EmbeddingEngine(make_mesh(2, 4), Vv, Dd, counts,
                              num_negatives=3, seed=3, layout=layout,
                              use_pallas=True)
        l_ref = ref.train_step(centers, contexts, mask, key, 0.05)
        l_eng = eng.train_step(centers, contexts, mask, key, 0.05)
        assert float(l_ref) == pytest.approx(float(l_eng), rel=1e-5)
        for name in ("syn0", "syn1"):
            np.testing.assert_allclose(
                np.asarray(getattr(ref, name), np.float32)[:Vv, :Dd],
                np.asarray(getattr(eng, name), np.float32)[:Vv, :Dd],
                rtol=1e-5, atol=1e-6, err_msg=f"{layout}/{name}",
            )
