"""Fused Pallas SGNS pair-step megakernel (ISSUE 11), interpret mode.

Contracts pinned here:
  * 3-WAY UPDATE PARITY — the fused kernel applies the identical table
    update as the composed XLA pair step, both checked against a
    host-NumPy oracle fed the SAME negative draws, over real packed
    pair streams at windows 2/3/5 (duplicate rows included, with block
    sizes chosen so runs span kernel grid-step boundaries).
  * EXACT fp32 DUPLICATE SUMS — with dyadic-rational inputs (every
    partial sum exactly representable) the run-summing scatters equal
    ``np.add.at`` BITWISE, regardless of where block boundaries fall.
  * fp32 VMEM ACCUMULATION over bf16 STORAGE — a run of updates each
    below the target row's bf16 ulp lands as their fp32 sum (the
    composed bf16 scatter-add loses them one by one), and a fused bf16
    step stays within the documented tolerance of the fp32 step.
  * ENGINE SELECTION — pallas engines ride the fused path for the pair
    form on data-parallel meshes and match the composed engine's
    tables; model-sharded meshes fall back to the composed step.
  * FIT INTEGRATION — a fused packed fit reports its ``step_body`` and a
    mid-epoch checkpoint/resume reproduces the uninterrupted fused run
    bit-for-bit (slow; the pallas-interpret CI leg runs it).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.ops import sgns
from glint_word2vec_tpu.ops.device_batching import pack_window_pairs
from glint_word2vec_tpu.ops.pallas_sgns import (
    fused_pair_step,
    fused_pair_step_shared,
    scatter_add_rank1_hbm,
    scatter_add_rows_f32,
    shared_pool_vmem_ok,
)
from glint_word2vec_tpu.ops.sampling import sample_negatives_per_row
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
from glint_word2vec_tpu.parallel.mesh import make_mesh

V, D = 73, 16


# ---------------- run-summing scatters, fp32 accumulation ---------------


def test_scatter_add_rows_f32_exact_dyadic_sums():
    # Dyadic-rational table/updates: every run's partial sums are
    # exactly representable in fp32, so the sorted-run scatter must
    # equal np.add.at BITWISE — the "duplicate-row sums exact in fp32"
    # acceptance gate. Three distinct ids over 19 rows at block_rows=4
    # force runs to span grid-step boundaries.
    rng = np.random.default_rng(0)
    table = (rng.integers(-32, 32, (V, D)) / 4.0).astype(np.float32)
    ids = rng.integers(0, 3, 19).astype(np.int32)
    upd = (rng.integers(-32, 32, (19, D)) / 8.0).astype(np.float32)
    out = scatter_add_rows_f32(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(upd),
        interpret=True, block_rows=4,
    )
    exp = table.copy()
    np.add.at(exp, ids, upd)
    assert np.array_equal(np.asarray(out), exp)


def test_scatter_add_rows_f32_bf16_single_rounding():
    # The mixed-precision contract: within a grid-step block a run is
    # summed in fp32 VMEM and rounded to storage ONCE (a run spanning b
    # blocks rounds b times — still far better than once per update).
    # Target row value 256 (bf16 ulp = 2.0); 8 updates of 0.5 in one
    # block sum to 4.0 — the composed bf16 scatter-add loses every one
    # (0.5 < ulp/2), the fused scatter lands 260.
    table = np.zeros((V, D), np.float32)
    table[5] = 256.0
    tb = jnp.asarray(table, dtype=jnp.bfloat16)
    ids = np.full(8, 5, np.int32)
    upd = np.full((8, D), 0.5, np.float32)
    out = scatter_add_rows_f32(
        tb, jnp.asarray(ids), jnp.asarray(upd),
        interpret=True, block_rows=8,
    )
    np.testing.assert_array_equal(
        np.asarray(out[5], np.float32), np.full(D, 260.0, np.float32)
    )
    # The bf16-by-bf16 emulation of the composed path drops them all —
    # the regression this kernel exists to fix, pinned as a contrast.
    composed = tb.at[jnp.asarray(ids)].add(
        jnp.asarray(upd).astype(jnp.bfloat16)
    )
    np.testing.assert_array_equal(
        np.asarray(composed[5], np.float32), np.full(D, 256.0, np.float32)
    )


def test_scatter_add_rank1_hbm_matches_numpy():
    # Rank-1 payload formed in VMEM from HBM-resident h rows;
    # duplicates (incl. one run longer than a block) must sum. Dyadic
    # inputs again => bitwise.
    rng = np.random.default_rng(3)
    B, N = 12, 37
    table = (rng.integers(-16, 16, (V, D)) / 4.0).astype(np.float32)
    ids = rng.integers(0, V, N).astype(np.int32)
    ids[:11] = 7  # run spanning >1 block at block_rows=4
    coef = (rng.integers(-8, 8, N) / 8.0).astype(np.float32)
    h = (rng.integers(-16, 16, (B, D)) / 8.0).astype(np.float32)
    hidx = rng.integers(0, B, N).astype(np.int32)
    out = scatter_add_rank1_hbm(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(coef),
        jnp.asarray(h), jnp.asarray(hidx),
        interpret=True, block_rows=4,
    )
    exp = table.copy()
    np.add.at(exp, ids, coef[:, None] * h[hidx])
    assert np.array_equal(np.asarray(out), exp)


# ---------------- 3-way parity over real packed pair streams ------------


def _corpus(seed=0, lens=(5, 1, 9, 3, 12, 2, 6)):
    rng = np.random.default_rng(seed)
    sents = [rng.integers(0, V, L).astype(np.int32) for L in lens]
    ids = np.concatenate(sents)
    offsets = np.zeros(len(sents) + 1, np.int64)
    np.cumsum([len(s) for s in sents], out=offsets[1:])
    return ids, offsets


def _packed_stream(window, P=32):
    """One real dense pair batch (mask-0 tail slots included) from the
    packed assembly — duplicates arise naturally from repeated corpus
    words."""
    ids, offsets = _corpus()
    key = jax.random.PRNGKey(7)
    pc, px, pm, _, _ = pack_window_pairs(
        jnp.asarray(ids), jnp.asarray(offsets, jnp.int32),
        jnp.int32(0), key, jnp.uint32(0),
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


@pytest.mark.parametrize(
    "window",
    [pytest.param(2, marks=pytest.mark.slow), 3,
     pytest.param(5, marks=pytest.mark.slow)],
)
def test_fused_threeway_parity(window):
    # fused kernel == composed XLA pair step == host-NumPy oracle, on a
    # real packed pair stream (same negative draws everywhere — both
    # step functions key them by global pair row; the oracle replays
    # the identical call). block_rows=4 so duplicate runs cross kernel
    # grid-step boundaries.
    n = 3
    pc, px, pm = _packed_stream(window)
    key = jax.random.PRNGKey(1)
    s0, s1 = sgns.init_tables(jax.random.PRNGKey(2), V, D)
    s0 = s0 * 100.0  # lift values off the 1/d init scale so the
    s1 = s1 + 0.01 * s0  # parity comparison is not vacuously tiny
    counts = np.arange(V, 0, -1).astype(np.int64)
    from glint_word2vec_tpu.corpus.alias import build_unigram_alias

    t = build_unigram_alias(counts, power=0.75)
    prob, alias = jnp.asarray(t.prob), jnp.asarray(t.alias)
    alpha = jnp.float32(0.05)
    g0, g1, gl = sgns.train_step_pairs(
        s0, s1, prob, alias, pc, px, pm, key, alpha, n
    )
    p0, p1, plx = sgns.train_step_pairs_pallas(
        s0, s1, prob, alias, pc, px, pm, key, alpha, n,
        interpret=True, block_rows=4,
    )
    negs = np.asarray(sample_negatives_per_row(
        key, prob, alias, jnp.arange(pc.shape[0], dtype=jnp.int32), (1, n)
    ))[:, 0, :]
    nmask = np.asarray(sgns.negative_mask(
        jnp.asarray(negs)[:, None, :], px[:, None], pm[:, None]
    ))[:, 0, :]
    o0, o1, ol = _numpy_pair_oracle(s0, s1, pc, px, pm, negs, nmask, 0.05)
    for got, exp, name in ((p0, o0, "fused/syn0"), (p1, o1, "fused/syn1"),
                           (g0, o0, "composed/syn0"),
                           (g1, o1, "composed/syn1")):
        np.testing.assert_allclose(
            np.asarray(got), exp, rtol=2e-5, atol=1e-6, err_msg=name
        )
    assert float(plx) == pytest.approx(ol, rel=1e-5)
    assert float(gl) == pytest.approx(ol, rel=1e-5)


def test_fused_bf16_storage_within_documented_tolerance():
    # bf16 storage: rows round to ~2^-8 relative on every landed write;
    # one fused step must stay within that envelope of the fp32 step.
    n = 3
    pc, px, pm = _packed_stream(3)
    key = jax.random.PRNGKey(4)
    rng = np.random.default_rng(5)
    s0 = jnp.asarray(rng.normal(0, 0.1, (V, D)).astype(np.float32))
    s1 = jnp.asarray(rng.normal(0, 0.1, (V, D)).astype(np.float32))
    negs = sample_negatives_per_row(
        key, jnp.ones(V) * 0.5, jnp.arange(V, dtype=jnp.int32),
        jnp.arange(pc.shape[0], dtype=jnp.int32), (1, n),
    )[:, 0, :]
    nmask = sgns.negative_mask(
        negs[:, None, :], px[:, None], pm[:, None]
    )[:, 0, :]
    a = jnp.float32(0.05)
    f0, f1, _ = fused_pair_step(
        s0, s1, pc, px, pm, negs, nmask, a, interpret=True
    )
    b0, b1, _ = fused_pair_step(
        s0.astype(jnp.bfloat16), s1.astype(jnp.bfloat16),
        pc, px, pm, negs, nmask, a, interpret=True,
    )
    for got, exp in ((b0, f0), (b1, f1)):
        err = np.max(np.abs(
            np.asarray(got, np.float32) - np.asarray(exp, np.float32)
        ))
        assert err <= 0.05, err  # documented bf16-storage tolerance


@pytest.mark.slow
def test_fused_shared_pool_matches_numpy_oracle():
    # Shared-pool estimator: pool scoring/update are in-kernel level-3
    # BLAS blocks; verify against the dense numpy restatement (weights
    # m_i * n / S, pool==context collisions dropped, C=1 form).
    rng = np.random.default_rng(6)
    P, S, n = 21, 13, 4
    s0 = jnp.asarray(rng.normal(0, 0.1, (V, D)).astype(np.float32))
    s1 = jnp.asarray(rng.normal(0, 0.1, (V, D)).astype(np.float32))
    pc = jnp.asarray(rng.integers(0, V, P), jnp.int32)
    px = jnp.asarray(rng.integers(0, V, P), jnp.int32)
    pm = jnp.asarray((rng.random(P) < 0.8).astype(np.float32))
    pool = jnp.asarray(rng.integers(0, V, S), jnp.int32)
    pool = pool.at[3].set(int(np.asarray(px)[0]))  # forced collision
    a = jnp.float32(0.05)
    o0, o1 = np.asarray(s0).copy(), np.asarray(s1).copy()
    h, u = o0[np.asarray(pc)], o1[np.asarray(px)]
    up = o1[np.asarray(pool)]
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))  # noqa: E731
    f_pos = (h * u).sum(-1)
    f_pool = h @ up.T
    keep = (
        np.asarray(pool)[None, :] != np.asarray(px)[:, None]
    ).astype(np.float32)
    w = (np.asarray(pm) * (n / S))[:, None] * keep
    c_pos = 0.05 * (1 - sig(f_pos)) * np.asarray(pm)
    c_pool = -0.05 * sig(f_pool) * w
    np.add.at(o0, np.asarray(pc), c_pos[:, None] * u + c_pool @ up)
    np.add.at(o1, np.asarray(px), c_pos[:, None] * h)
    np.add.at(o1, np.asarray(pool), c_pool.T @ h)
    g0, g1, _ = fused_pair_step_shared(
        s0, s1, pc, px, pm, pool, a, n, interpret=True, block_rows=4
    )
    np.testing.assert_allclose(np.asarray(g0), o0, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g1), o1, rtol=2e-5, atol=1e-6)


def test_fused_shared_small_pool_drain():
    # Pool smaller than the DMA pipeline depth (S < 8): the one-time
    # pool staging must still wait EVERY copy before pinning the fp32
    # pool (an earlier drain indexed S - PIPELINE + j with a >= 0 guard
    # and silently skipped the tail copies for S < PIPELINE; interpret
    # mode runs copies synchronously, so this pins the fixed indexing —
    # the completeness itself is only observable on hardware).
    rng = np.random.default_rng(9)
    P, S, n = 13, 5, 3
    s0 = jnp.asarray(rng.normal(0, 0.1, (V, D)).astype(np.float32))
    s1 = jnp.asarray(rng.normal(0, 0.1, (V, D)).astype(np.float32))
    pc = jnp.asarray(rng.integers(0, V, P), jnp.int32)
    px = jnp.asarray(rng.integers(0, V, P), jnp.int32)
    pm = jnp.ones(P, jnp.float32)
    pool = jnp.asarray(rng.integers(0, V, S), jnp.int32)
    g0, g1, _ = fused_pair_step_shared(
        s0, s1, pc, px, pm, pool, jnp.float32(0.05), n,
        interpret=True, block_rows=4,
    )
    o0, o1 = np.asarray(s0).copy(), np.asarray(s1).copy()
    h, u = o0[np.asarray(pc)], o1[np.asarray(px)]
    up = o1[np.asarray(pool)]
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))  # noqa: E731
    keep = (
        np.asarray(pool)[None, :] != np.asarray(px)[:, None]
    ).astype(np.float32)
    w = (np.asarray(pm) * (n / S))[:, None] * keep
    c_pos = 0.05 * (1 - sig((h * u).sum(-1)))
    c_pool = -0.05 * sig(h @ up.T) * w
    np.add.at(o0, np.asarray(pc), c_pos[:, None] * u + c_pool @ up)
    np.add.at(o1, np.asarray(px), c_pos[:, None] * h)
    np.add.at(o1, np.asarray(pool), c_pool.T @ h)
    np.testing.assert_allclose(np.asarray(g0), o0, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g1), o1, rtol=2e-5, atol=1e-6)


def test_bf16_pallas_row_scatter_gets_f32_dup_sums():
    # The pallas-but-NOT-fused scatter path (model-sharded meshes, the
    # fused escape hatch) must keep the fp32 duplicate-sum contract on
    # bf16 tables: _scatter_rows hands the pallas_rows kernel (whose
    # accumulator is table dtype) fp32 run totals (_run_totals). Same
    # sub-ulp construction as the f32-scatter test above.
    from glint_word2vec_tpu.parallel.engine import _scatter_rows

    table = np.zeros((V, D), np.float32)
    table[5] = 256.0
    tb = jnp.asarray(table, dtype=jnp.bfloat16)
    ids = jnp.full((8,), 5, jnp.int32)
    upd = jnp.full((8, D), 0.5, jnp.float32)
    out, written = _scatter_rows(
        tb, ids, jnp.ones(8, jnp.float32), upd, jnp.arange(8), 0,
        pallas="rows",
    )
    np.testing.assert_array_equal(
        np.asarray(out[5], np.float32), np.full(D, 260.0, np.float32)
    )
    assert int(written) == 1


def test_shared_pool_vmem_gate():
    # 2048x300 bf16 pool: 1.2 MB storage + 2.5 MB fp32 + 2.5 MB d_pool
    # accumulator — fits. The 4096x300 bench pool (~12 MB total) does
    # NOT fit the budget and falls back to the composed step.
    assert shared_pool_vmem_ok(2048, 300, jnp.bfloat16)
    assert not shared_pool_vmem_ok(4096, 300, jnp.float32)
    assert not shared_pool_vmem_ok(400_000, 300, jnp.float32)


# ---------------- engine selection + parity ----------------------------


def _mk_engine(shape, **kw):
    counts = np.arange(V, 0, -1).astype(np.int64) * 3
    return EmbeddingEngine(
        make_mesh(*shape), V, D, counts, num_negatives=3, seed=11, **kw
    )


def _run_packed(eng, n_steps=3):
    ids, offsets = _corpus()
    eng.upload_corpus(ids, offsets)
    return eng.train_steps_corpus_packed(
        0, 16, 3, 8, jax.random.PRNGKey(5), n_steps, step0=2,
        grid_step0=0, step_size=0.05, total_words=1000, words_base=0,
    )


@pytest.mark.parametrize(
    "shape", [(1, 1), pytest.param((4, 1), marks=pytest.mark.slow)]
)
def test_engine_fused_matches_composed(shape):
    ref = _mk_engine((1, 1))
    eng = _mk_engine(shape, use_pallas=True)
    assert eng._pallas_fused
    r_ref = _run_packed(ref)
    r_eng = _run_packed(eng)
    # pair counts / position advances / alphas are integer-exact.
    for a, b in zip(r_ref[1:], r_eng[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for t in ("syn0", "syn1"):
        np.testing.assert_allclose(
            np.asarray(getattr(eng, t), np.float32)[:V],
            np.asarray(getattr(ref, t), np.float32)[:V],
            rtol=2e-5, atol=1e-6, err_msg=t,
        )


@pytest.mark.slow
def test_engine_fused_shared_pool_matches_composed():
    ref = _mk_engine((1, 1), shared_negatives=32)
    eng = _mk_engine((1, 1), shared_negatives=32, use_pallas=True)
    assert eng._pallas_fused
    _run_packed(ref)
    _run_packed(eng)
    for t in ("syn0", "syn1"):
        np.testing.assert_allclose(
            np.asarray(getattr(eng, t), np.float32)[:V],
            np.asarray(getattr(ref, t), np.float32)[:V],
            rtol=2e-5, atol=1e-6, err_msg=t,
        )


@pytest.mark.slow
def test_engine_fused_falls_back_when_model_sharded():
    eng = _mk_engine((2, 4), use_pallas=True)
    assert eng._pallas_interpret and not eng._pallas_fused
    ref = _mk_engine((1, 1))
    _run_packed(ref)
    _run_packed(eng)  # composed path, still correct
    np.testing.assert_allclose(
        np.asarray(eng.syn0, np.float32)[:V],
        np.asarray(ref.syn0, np.float32)[:V],
        rtol=2e-5, atol=1e-6,
    )


def test_engine_fused_env_escape_hatch(monkeypatch):
    monkeypatch.setenv("GLINT_W2V_PALLAS_FUSED", "0")
    eng = _mk_engine((1, 1), use_pallas=True)
    assert eng._pallas_interpret and not eng._pallas_fused


# ---------------- fit integration (pallas-interpret CI leg) -------------

CORPUS = [
    "the quick brown fox jumps over the lazy dog".split(),
    "the dog sleeps all day long in the sun".split(),
    "a quick fox and a lazy dog meet in the field".split(),
    "the sun rises over the field every day".split(),
] * 30


def _w2v(**kw):
    from glint_word2vec_tpu import Word2Vec

    defaults = dict(
        vector_size=12, batch_size=32, min_count=1, num_iterations=2,
        seed=7, steps_per_call=4, window=3,
    )
    defaults.update(kw)
    return Word2Vec(**defaults)


@pytest.mark.slow
def test_fused_fit_reports_and_learns(monkeypatch):
    monkeypatch.setenv("GLINT_W2V_PALLAS", "1")
    m = _w2v(num_iterations=1).fit(CORPUS)
    tm = m.training_metrics
    assert tm["pipeline"] == "device_corpus"
    assert tm["batch_packing"] == "dense"
    assert tm["step_body"] == "rows/per_pair/pallas_fused"
    assert tm["pallas_mode"] == "interpret"
    assert tm["packed_mask_density"] >= 0.9
    assert len(m.find_synonyms("quick", 3)) == 3


@pytest.mark.slow
def test_fused_fit_mid_epoch_resume_bit_parity(tmp_path, monkeypatch):
    # Mid-epoch checkpoint/resume under the fused path: the restored
    # position/gstep make every subsequent fused dispatch identical, so
    # the resumed tables are BITWISE the uninterrupted run's.
    monkeypatch.setenv("GLINT_W2V_PALLAS", "1")
    ck = str(tmp_path / "ck")
    os.makedirs(ck, exist_ok=True)
    monkeypatch.setenv("GLINT_PACKED_STOP_AFTER_GROUPS", "2")
    _w2v().fit(CORPUS, checkpoint_dir=ck)
    monkeypatch.delenv("GLINT_PACKED_STOP_AFTER_GROUPS")
    state = json.load(open(os.path.join(ck, "train_state.json")))
    assert state["position"] > 0 and state["batch_packing"] == "dense"
    m_resumed = _w2v().fit(CORPUS, checkpoint_dir=ck)
    m_full = _w2v().fit(CORPUS)
    assert m_resumed.training_metrics["step_body"].endswith("pallas_fused")
    np.testing.assert_array_equal(
        np.asarray(m_resumed.engine.syn0, np.float32),
        np.asarray(m_full.engine.syn0, np.float32),
    )
    np.testing.assert_array_equal(
        np.asarray(m_resumed.engine.syn1, np.float32),
        np.asarray(m_full.engine.syn1, np.float32),
    )


@pytest.mark.slow
def test_bf16_storage_quality_gates(tiny_corpus):
    # bf16 TABLE STORAGE at the matched e2e reference budget
    # (QUALITY.json methodology: identical corpus/config/epochs as the
    # fp32 vienna/berlin gates in tests/test_model_e2e.py) — low
    # precision must not cost the capital-structure quality bar. Runs
    # the (dense-default) packed path, i.e. bf16 + packing together.
    from glint_word2vec_tpu import Word2Vec

    m = (
        Word2Vec(mesh=make_mesh(2, 4))
        .set_vector_size(48).set_window_size(5).set_step_size(0.025)
        .set_batch_size(256).set_num_negatives(5).set_min_count(5)
        .set_num_iterations(6).set_seed(1).set_dtype("bfloat16")
    ).fit(tiny_corpus)
    try:
        assert m.training_metrics["batch_packing"] == "dense"
        syns = m.find_synonyms("austria", 10)
        words = [w for w, _ in syns]
        assert "vienna" in words, f"vienna not in {words}"
        assert dict(syns)["vienna"] > 0.5, syns
        ana = m.analogy(
            positive=["vienna", "germany"], negative=["austria"], num=10
        )
        assert "berlin" in [w for w, _ in ana], ana
        # capital-of generalizes across pairs, not just the gate pair.
        ana2 = m.analogy(
            positive=["paris", "germany"], negative=["france"], num=10
        )
        assert "berlin" in [w for w, _ in ana2], ana2
    finally:
        m.stop()
