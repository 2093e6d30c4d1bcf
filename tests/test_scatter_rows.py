"""The one row scatter-add of the SGNS step (``engine._scatter_rows``)
against a float64 ``np.add.at``: every table dtype, and the id profiles
that break a scatter: all distinct, all equal, a Zipf batch with one run
of 600, ids another shard owns, sizes the writer's chunk does not divide."""

import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.parallel import engine

V, D = 6000, 24
START = 1000  # the shard's first global row: it owns [START, START + V)


def _ids(profile, rng):
    if profile == "distinct":
        return START + rng.permutation(V)[:3000]
    if profile == "equal":
        return np.full(700, START + 17)
    if profile == "zipf_run_600":
        p = 1.0 / np.arange(1, V + 1)
        ids = START + rng.choice(V, size=5000, p=p / p.sum())
        ids[rng.permutation(5000)[:600]] = START + 3
        return ids
    if profile == "other_shards":
        # three quarters belong to other shards, on both sides of this one
        return rng.integers(0, START + 3 * V, size=5000)
    if profile == "chunk_plus_one":
        return START + rng.integers(0, V, size=engine._SCATTER_CHUNK + 1)
    assert profile == "one"
    return np.asarray([START + V - 1])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("profile", [
    "distinct", "equal", "zipf_run_600", "other_shards", "chunk_plus_one",
    "one",
])
def test_scatter_rows_against_float64(profile, dtype):
    rng = np.random.default_rng(len(profile))
    ids = _ids(profile, rng).astype(np.int32)
    n = ids.size
    src = rng.normal(0, 1, (max(n // 3, 1), D)).astype(np.float32)
    hidx = rng.integers(0, src.shape[0], n).astype(np.int32)
    coefs = rng.normal(0, 0.05, n).astype(np.float32)
    table = jnp.asarray(rng.normal(0, 0.5, (V, D)), dtype)
    before = np.asarray(table, np.float64)

    out, written = engine._scatter_rows(
        table, jnp.asarray(ids), jnp.asarray(coefs), jnp.asarray(src),
        jnp.asarray(hidx), START,
    )

    own = (ids >= START) & (ids < START + V)
    loc = ids[own] - START
    upd = coefs[own, None].astype(np.float64) * src[hidx[own]]
    total = np.zeros((V, D))
    np.add.at(total, loc, upd)
    mass = np.zeros((V, D))  # what a run's in-order sum may lose an ulp of
    np.add.at(mass, loc, np.abs(upd))
    assert int(written) == np.unique(loc).size
    assert out.dtype == table.dtype
    got = np.asarray(out, np.float64)
    touched = np.zeros(V, bool)
    touched[loc] = True
    # rows no update names are not written at all, the clipped ends included
    np.testing.assert_array_equal(got[~touched], before[~touched])
    eps32 = np.finfo(np.float32).eps
    if dtype == "float32":
        # the run added in order in float32, then one add against the row
        bound = 4 * eps32 * (mass + np.abs(before))
    else:
        # the row's batch total rounded ONCE to bfloat16, then one
        # bfloat16 add: two roundings whatever the run's length
        eps16 = 2.0 ** -8
        bound = eps16 * (np.abs(total) + np.abs(before + total)) \
            + 4 * eps32 * mass
    err = np.abs(got - (before + total))
    assert (err <= bound + 1e-30).all(), (err - bound).max()
