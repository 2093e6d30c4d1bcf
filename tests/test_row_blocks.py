"""The step's gathered rows keep the batch axis beside d (ISSUE 38).

A float32 array is tiled (8, 128) on a TPU, so a ``(..., 5, d)`` tensor of
the negatives' rows pays for 8 and a reshape to or from it copies every
row. The CPU has no tiles, so these tests read shapes and values, never
times:

* the traced packed scan of each architecture holds no float32 value of
  rank 3 or more, d minor, whose second-minor axis is not whole tiles,
  and no ``broadcast_in_dim`` lays h out once a negative;
* the scatters are handed what the parent formulation hands them (the
  rows pair-major, ``(B, C, n, d)``, contracted by einsums: kept here as
  the plain statement of the mathematics), slot for slot.
"""

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

from glint_word2vec_tpu.corpus.batching import (  # noqa: E402
    context_width,
    packed_pair_batch,
)
from glint_word2vec_tpu.ops import sgns  # noqa: E402
from glint_word2vec_tpu.parallel import engine as engine_mod  # noqa: E402
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine  # noqa: E402
from glint_word2vec_tpu.parallel.mesh import make_mesh  # noqa: E402

# The cells' ratios at a small size: 5 negatives, a bag of 2 x 5 = 10
# slots, a subword group of 32, and a pair batch (205) that is not whole
# tiles, as the cells' 26,215 is not.
V, BUCKET, D, NEG, WINDOW, BATCH, G = 512, 300, 32, 5, 5, 64, 32
PAIRS = packed_pair_batch(BATCH, WINDOW, 1)
assert PAIRS % 8 and (2 * WINDOW) % 8 and NEG % 8 and G % 8 == 0
ARCHITECTURES = ("skipgram", "subword", "cbow")


def groups_table(seed=4):
    """The word's own row, then 0 to G - 1 bucket rows, -1 padded."""
    rng = np.random.default_rng(seed)
    groups = V + rng.integers(0, BUCKET, (V, G)).astype(np.int32)
    groups[np.arange(G)[None, :] > rng.integers(0, G, V)[:, None]] = -1
    groups[:, 0] = np.arange(V)
    return groups


def engine(architecture):
    counts = np.arange(V, 0, -1).astype(np.int64) * 3
    subword = architecture == "subword"
    eng = EmbeddingEngine(
        make_mesh(1, 1), V, D, counts, num_negatives=NEG, seed=3,
        extra_rows=BUCKET if subword else 0,
        architecture="cbow" if architecture == "cbow" else "skipgram")
    eng.upload_center_groups(groups_table() if subword else None)
    return eng


def zipf_corpus(seed=1, sentences=60):
    rng = np.random.default_rng(seed)
    lens = rng.integers(3, 30, sentences)
    p = 1.0 / np.arange(1, V + 1)
    ids = rng.choice(V, size=int(lens.sum()), p=p / p.sum()).astype(np.int32)
    return ids, np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)


# ---------------------------------------------------------------------------
# the rule, on the traced program
# ---------------------------------------------------------------------------


def traced_packed_scan(eng, steps=2):
    """The jaxpr of the packed scan an engine builds."""
    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(eng.mesh, P(*spec)))

    cbow = eng.architecture == "cbow"
    width = eng._group_width
    pairs = BATCH if cbow else PAIRS
    span = 0 if cbow else -(-3 * pairs // context_width(WINDOW))
    table = sds(eng.syn0.shape, jnp.float32, *eng.syn0.sharding.spec)
    offs = sds((61,), jnp.int32)
    i32, u32, f32 = (sds((), t) for t in (jnp.int32, jnp.uint32, jnp.float32))
    words = sds((900,), jnp.int32)
    extra = (sds((V, width), jnp.int32),) if width else ()
    fn = eng._make_packed_corpus_scan(pairs, WINDOW, BATCH, span, steps, width)
    return jax.make_jaxpr(fn)(
        table, table, sds((-(-V // 64), 128), jnp.int32), words, words, offs,
        offs, i32, i32, sds((2,), jnp.uint32), u32, u32, f32, f32, f32,
        *extra).jaxpr


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub)


@pytest.mark.parametrize("architecture", ARCHITECTURES)
def test_no_traced_row_tensor_has_a_small_axis_beside_d(architecture):
    eng = engine(architecture)
    d = eng.padded_dim
    rows = []  # float32 values of rank >= 3 with d minor
    # ... but a table as the slab writer is handed it (ISSUE 45): its own
    # bytes seen as (tile rows, 8, d), whole tiles and no copy
    slabs = (eng.syn0.shape[0] // 8, 8, d)
    for eqn in equations(traced_packed_scan(eng)):
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            if (len(shape) >= 3 and shape[-1] == d and shape != slabs
                    and var.aval.dtype == jnp.float32):
                rows.append((eqn.primitive.name, shape))
    # no axis of 5 negatives, 10 bag slots, 1 context or 205 pairs is ever
    # laid down in tiles of 8 ...
    assert not [r for r in rows if r[1][-2] % 8], rows
    # ... and h is never written out once a negative (it is broadcast in
    # registers, over a major axis)
    assert not [r for r in rows if r[0] == "broadcast_in_dim"], rows
    if architecture == "subword":
        # a group of whole tiles keeps its group-major form
        assert any(r[1][-2] == G for r in rows), rows
    else:
        assert not rows, rows


# ---------------------------------------------------------------------------
# the scatter is handed what it was handed
# ---------------------------------------------------------------------------


def parent_pull_blocks(table_l, ids, start, rows_per_shard, table=None):
    """One gather in the ids' own order, the rows cut batch-major:
    ``(B, K, d)``, the small axis beside d."""
    rows = engine_mod._pull_rows(
        table_l, ids.reshape(-1), start, rows_per_shard, table)
    return rows.reshape(ids.shape[0], -1, rows.shape[-1])


def parent_row_sums(c, u):
    """The masked sum of a group's rows, ``(R, S) x (R, S, d)``."""
    return (u * c[..., None]).sum(axis=1)


def parent_sgns_grads(h, u_pos, u_neg, mask, neg_mask, alpha,
                      compute_dtype=jnp.float32):
    """The SGNS forward and backward as the step stated it before ISSUE
    38: ``u_pos (B, C, d)``, ``u_neg (B, C, n, d)``."""
    B, C = mask.shape
    u_neg = u_neg.reshape(B, C, -1, u_neg.shape[-1])
    f_pos = jnp.einsum("bd,bcd->bc", h, u_pos)
    f_neg = jnp.einsum("bd,bcnd->bcn", h, u_neg)
    co = sgns.sgns_coefs(f_pos, f_neg, mask, neg_mask, alpha)
    d_center = jnp.einsum("bc,bcd->bd", co.c_pos, u_pos) + jnp.einsum(
        "bcn,bcnd->bd", co.c_neg, u_neg)
    return sgns.SgnsGrads(co.c_pos, co.c_neg, d_center, co.loss)


def handed_to_the_scatters(architecture, grid, monkeypatch, parent):
    """Three steps (a dispatch each) from seeded tables: what each call of
    ``_scatter_rows`` was handed, ``{0: syn0's, 1: syn1's}`` as lists of
    ``(ids, coefs, src, hidx)`` a step, and the steps' losses."""
    seen = {}
    real = engine_mod._scatter_rows

    def recording(table_l, idx, coefs, src, hidx, start):
        slot = seen.setdefault(len(seen), [])  # trace order: syn0, syn1
        jax.debug.callback(
            lambda *a: slot.append([np.asarray(x) for x in a]),
            idx, coefs, src, hidx)
        return real(table_l, idx, coefs, src, hidx, start)

    with monkeypatch.context() as patch:
        patch.setattr(engine_mod, "_SCAN_MEMO", {})
        patch.setattr(engine_mod, "_scatter_rows", recording)
        if parent:
            patch.setattr(engine_mod, "_pull_blocks", parent_pull_blocks)
            patch.setattr(sgns, "row_sums", parent_row_sums)
            patch.setattr(sgns, "sgns_grads", parent_sgns_grads)
        eng = engine(architecture)
        rng = np.random.default_rng(7)
        n_rows = V + (BUCKET if architecture == "subword" else 0)
        eng.set_tables(*(rng.normal(0, 0.3, (n_rows, D)).astype(np.float32)
                         for _ in range(2)))
        eng.upload_corpus(*zipf_corpus())
        losses, pos = [], 0
        if not grid:
            eng.set_keep_probs(np.full(V, 0.8, np.float32))
            eng.compact_corpus(jax.random.PRNGKey(9))
        for step in range(3):
            if grid:
                loss = eng.train_steps_corpus(
                    pos, BATCH, WINDOW, jax.random.PRNGKey(3),
                    np.full(1, 0.05, np.float32), step0=step)
                pos += BATCH
            else:
                pairs = BATCH if architecture == "cbow" else PAIRS
                out = eng.train_steps_corpus_packed(
                    pos, pairs, WINDOW, BATCH, jax.random.PRNGKey(3), 1,
                    step0=step, step_size=0.05, total_words=5000)
                loss, pos = out[0], int(out[2][-1])
            losses.append(np.asarray(jax.block_until_ready(loss)))
        jax.effects_barrier()
        eng.destroy()
    return seen, np.concatenate(losses)


@pytest.mark.parametrize("architecture,grid", [
    ("skipgram", False), ("skipgram", True), ("subword", False),
    ("subword", True), ("cbow", False)],
    ids=lambda v: v if isinstance(v, str) else "C=10" if v else "C=1")
def test_the_scatters_are_handed_what_the_parent_handed_them(
        architecture, grid, monkeypatch):
    """C = 1 is the packed scan (every cell's), C = 2 x window the grid
    scan, which a CBOW engine does not have (its one context is the
    position's own word)."""
    new, new_losses = handed_to_the_scatters(
        architecture, grid, monkeypatch, parent=False)
    old, old_losses = handed_to_the_scatters(
        architecture, grid, monkeypatch, parent=True)
    assert sorted(new) == sorted(old) == [0, 1]
    for table in (0, 1):
        assert len(new[table]) == len(old[table]) == 3
        for (ids, coefs, src, hidx), (ids_p, coefs_p, src_p, hidx_p) in zip(
                new[table], old[table]):
            np.testing.assert_array_equal(ids, ids_p)
            np.testing.assert_array_equal(hidx, hidx_p)
            np.testing.assert_allclose(coefs, coefs_p, rtol=0, atol=1e-6)
            # syn0's source is d_center (over the group's count), syn1's h
            np.testing.assert_allclose(src, src_p, rtol=1e-5, atol=1e-6)
            assert np.abs(src).max() > 1e-3 and np.abs(coefs).max() > 0
    np.testing.assert_allclose(new_losses, old_losses, rtol=1e-5)
