"""The one row scatter-add of the SGNS step (``engine._scatter_rows``)
against a float64 ``np.add.at``: every table dtype, and the id profiles
that break a scatter: all distinct, all equal, a Zipf batch with one run
of 600, ids another shard owns, sizes the writer's chunk does not divide,
and distinct rows enough for the writer's second trip (the benchmark's
step makes 29), its last chunk partly live. Then exact sums, and the one
row gather (``engine._pull_rows``) across shard edges. The float64 cases
run twice: through XLA's writer, which the CPU's programs take, and through
the slab writer's kernel in interpret mode, which a TPU's take."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from glint_word2vec_tpu.ops import slab_writer
from glint_word2vec_tpu.parallel import engine
from glint_word2vec_tpu.parallel.mesh import MODEL_AXIS, make_mesh

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
    if profile == "one":
        return np.asarray([START + V - 1])
    if profile == "run_across_slab_chunk_edge":
        # the kernel's chunks cut at SLOTS: a run of 600 that, once sorted,
        # stands across the first chunk's last slot, every other row of the
        # shard once around it
        lo = slab_writer.CHUNK - 248
        ids = np.concatenate([
            np.arange(lo), np.full(600, lo), np.arange(lo + 1, V),
        ])
        return START + rng.permutation(ids)
    # The writer's trips: it walks the DISTINCT owned rows a chunk at a
    # time, so only these reach its second trip.
    chunk = engine._SCATTER_CHUNK
    if profile == "distinct_one_chunk":  # one trip, no sentinel in it
        return START + rng.permutation(V)[:chunk]
    if profile == "distinct_chunk_plus_one":
        # the second trip holds one live row and chunk - 1 sentinels
        return START + rng.permutation(V)[:chunk + 1]
    if profile == "distinct_two_trips":
        return START + rng.permutation(V)[:5000]
    if profile == "all_rows":  # every row of the shard, each twice
        return START + np.concatenate([rng.permutation(V), rng.permutation(V)])
    if profile == "run_across_chunk_edge":
        # 4,000 distinct rows and a run of 600 that, once sorted, stands
        # on slots 3,800..4,399: across slot 4,096
        ids = np.concatenate([
            np.arange(3800), np.full(600, 3800), np.arange(3801, 4001),
        ])
        return START + rng.permutation(ids)
    assert profile == "row0_and_last_row_runs"
    # Runs on the shard's first and last row, strays next to both: what
    # the clip and the sentinel (key V, sorted right after row V - 1)
    # must keep apart.
    ids = np.concatenate([
        np.full(40, START), np.full(9, START - 1), np.full(3, START - 5),
        np.full(50, START + V - 1), np.full(9, START + V),
        np.full(3, START + V + 3), [0, START + 1, START + V - 2],
    ])
    return rng.permutation(ids)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("profile", [
    "distinct", "equal", "zipf_run_600", "other_shards", "chunk_plus_one",
    "one", "distinct_one_chunk", "distinct_chunk_plus_one",
    "distinct_two_trips", "all_rows", "run_across_chunk_edge",
    "row0_and_last_row_runs", "run_across_slab_chunk_edge",
])
@pytest.mark.parametrize("writer", ["xla", "slab"])
def test_scatter_rows_against_float64(profile, dtype, writer, monkeypatch):
    rng = np.random.default_rng(len(profile))
    ids = _ids(profile, rng).astype(np.int32)
    n = ids.size
    # The kernel wants rows of whole lanes; on the CPU it stands where a
    # TPU's lowering puts it only if it is handed over as the default.
    D = {"xla": 24, "slab": 128}[writer]
    if writer == "slab":
        monkeypatch.setattr(engine, "_write_rows", functools.partial(
            slab_writer.write, interpret=True
        ))
    src = rng.normal(0, 1, (max(n // 3, 1), D)).astype(np.float32)
    hidx = rng.integers(0, src.shape[0], n).astype(np.int32)
    coefs = rng.normal(0, 0.05, n).astype(np.float32)
    table = jnp.asarray(rng.normal(0, 0.5, (V, D)), dtype)
    before = np.asarray(table, np.float64)

    out, written, moved = jax.jit(engine._scatter_rows)(
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
    sub = slab_writer.slab_rows(dtype)
    assert int(moved) == (np.unique(loc // sub).size if writer == "slab"
                          else 0)
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


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_scatter_rows_sums_exactly(dtype):
    """Dyadic updates, so every partial sum is exact whatever the order.
    float32: the table is bit-equal to ``np.add.at``, over two trips of
    the writer and runs of up to 40. bfloat16: a row's run is totalled in
    float32 and rounded ONCE, so 8 updates of 0.5, each under half an ulp
    of a row that stands at 256, land as 260; added one by one in
    bfloat16 they are all lost."""
    rng = np.random.default_rng(11)
    if dtype == "float32":
        n = 9000
        ids = rng.permutation(V)[:5000]  # distinct rows: two trips
        ids = np.concatenate([ids, rng.choice(ids[:400], n - ids.size)])
        ids[:40] = ids[0]  # one run of 40 and more
        before = (rng.integers(-32, 32, (V, D)) / 4.0).astype(np.float32)
        coefs = (rng.integers(-8, 8, n) / 8.0).astype(np.float32)
        src = (rng.integers(-16, 16, (64, D)) / 8.0).astype(np.float32)
    else:
        runs = {0: 8, 17: 16, V - 1: 8, 4100: 24}
        ids = rng.permutation(np.repeat(list(runs), list(runs.values())))
        n = ids.size
        before = np.full((V, D), 256.0, np.float32)  # bfloat16 ulp: 2
        coefs = np.full(n, 0.25, np.float32)
        src = np.full((64, D), 2.0, np.float32)
    hidx = rng.integers(0, 64, n).astype(np.int32)
    ids = (START + ids).astype(np.int32)
    table = jnp.asarray(before, dtype)

    out, written, _ = engine._scatter_rows(
        table, jnp.asarray(ids), jnp.asarray(coefs), jnp.asarray(src),
        jnp.asarray(hidx), START,
    )

    want = before.copy()
    np.add.at(want, ids - START, coefs[:, None] * src[hidx])
    assert int(written) == np.unique(ids).size
    assert out.dtype == table.dtype
    np.testing.assert_array_equal(np.asarray(out, np.float32), want)
    if dtype == "bfloat16":
        assert want[0, 0] == 260.0 and want[17, 0] == 264.0
        one_by_one = table.at[jnp.asarray(ids - START)].add(
            jnp.asarray(coefs[:, None] * src[hidx], jnp.bfloat16)
        )
        np.testing.assert_array_equal(
            np.asarray(one_by_one, np.float32), before
        )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n", [1, 15, 16, 33])
def test_pull_rows_across_shards(n, dtype):
    """``_pull_rows`` as the step calls it, under ``shard_map`` on a 1 x 4
    mesh: ids on both sides of every shard edge, the table's first and
    last row, repeats; each row comes from the one shard that owns it,
    in float32 whatever the table stores."""
    shards, rows = 4, 37
    mesh = make_mesh(1, shards)
    rng = np.random.default_rng(n)
    host = np.asarray(jnp.asarray(
        rng.normal(0, 1, (shards * rows, D)), dtype
    ))
    edges = np.asarray(
        [[k * rows, k * rows - 1] for k in range(1, shards)]
    ).reshape(-1)
    ids = np.concatenate([
        edges, [0, shards * rows - 1], edges[:3],
        rng.integers(0, shards * rows, 33),
    ])[:n].astype(np.int32)

    def pull(table_l, idx):
        start = jax.lax.axis_index(MODEL_AXIS) * rows
        return engine._pull_rows(table_l, idx, start, rows)

    got = jax.jit(jax.shard_map(
        pull, mesh=mesh, in_specs=(P(MODEL_AXIS, None), P()), out_specs=P(),
        check_vma=False,
    ))(jax.device_put(host, NamedSharding(mesh, P(MODEL_AXIS, None))), ids)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(got), host[ids].astype(np.float32)
    )
