"""The slab writer (``ops/slab_writer.py``) against the XLA writer of
``engine._scatter_rows`` on the same ``(u, tot, n_u)``: bit-equal, float32
and bfloat16. The kernel runs on the CPU in Pallas' TPU interpret mode,
through the module's own ``write(..., interpret=True)``; that it compiles
for the chip is ``tests/test_tpu_compile.py``'s, and what it costs is a
chip run's (PERF.md, PR 30). Then: which writer ``_scatter_rows`` picks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.ops import slab_writer
from glint_word2vec_tpu.parallel import engine

V, D = 2048, 128
CH = 64  # the chunk most cases pass: interpret mode takes 5 ms a slab


def _rows(case, sub, rng):
    """Distinct local rows a case writes, and the chunk it passes."""
    if case == "dense_head":  # every row of the first 40 slabs
        return np.arange(40 * sub), CH
    if case == "singletons":  # one row a slab, every sublane in turn
        return np.arange(100) * sub + np.arange(100) % sub, CH
    if case.startswith("n_u="):  # around the chunk's edge; 0: nothing
        n = {"0": 0, "1": 1, "CH-1": CH - 1, "CH": CH, "CH+1": CH + 1}[
            case[4:]]
        return np.sort(rng.permutation(V)[:n]), CH
    if case == "slab_across_chunks":
        # sorted rows 60..67 of u are one f32 slab (rows 8 x 70 + 0..7):
        # chunk 0 ends inside it, so two calls move it
        lone = np.arange(60) * sub
        return np.concatenate([lone, 70 * sub + np.arange(sub), [V - 1]]), CH
    if case == "last_slab":
        return np.asarray([0, V - sub, V - 2, V - 1]), CH
    if case == "fewer_slabs_than_buffers":
        return np.asarray([3, 4, 9 * sub, 20 * sub + 1]), CH
    assert case == "default_chunk"  # the shipped CHUNK, a Zipf-like mix
    p = 1.0 / np.arange(1, V + 1)
    return np.unique(rng.choice(V, size=700, p=p / p.sum())), slab_writer.CHUNK


CASES = [
    "dense_head", "singletons", "n_u=0", "n_u=1", "n_u=CH-1", "n_u=CH",
    "n_u=CH+1", "slab_across_chunks", "last_slab",
    "fewer_slabs_than_buffers", "default_chunk",
]


def _totals(rows, rng):
    """``(u, tot, n_u)`` as ``_run_totals`` makes them, each row named
    twice in the batch, the ``tot`` rows no writer may read poisoned."""
    ids = rng.permutation(np.concatenate([rows, rows, [V, V + 5]]))
    src = rng.normal(0, 1, (32, D)).astype(np.float32)
    hidx = rng.integers(0, 32, ids.size).astype(np.int32)
    coefs = rng.normal(0, 0.05, ids.size).astype(np.float32)
    key = np.where(ids < V, ids, V).astype(np.int32)
    u, tot, n_u = engine._run_totals(
        jnp.asarray(key), jnp.asarray(coefs), jnp.asarray(src),
        jnp.asarray(hidx), V,
    )
    assert int(n_u) == rows.size
    dead = jnp.arange(tot.shape[0]) >= n_u
    return u, jnp.where(dead[:, None], jnp.nan, tot), n_u


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_slab_writer_is_bit_equal_to_xla_writer(case, dtype):
    rng = np.random.default_rng(len(case))
    sub = slab_writer.slab_rows(dtype)
    rows, chunk = _rows(case, sub, rng)
    table = jnp.asarray(rng.normal(0, 0.5, (V, D)), dtype)
    u, tot, n_u = _totals(rows, rng)

    want, none = engine._write_rows(table, u, tot, n_u)
    got, moved = slab_writer.write(
        table, u, tot, n_u, chunk=chunk, interpret=True
    )

    assert got.dtype == table.dtype and int(none) == 0
    bits = np.uint32 if dtype == "float32" else np.uint16
    np.testing.assert_array_equal(
        np.asarray(got).view(bits), np.asarray(want).view(bits)
    )
    if rows.size:  # something was written, and nothing poisoned it
        assert (np.asarray(got) != np.asarray(table)).any()
        assert np.isfinite(np.asarray(got, np.float32)).all()
    # every distinct slab once, and once more where a chunk's edge cuts it
    step = min(chunk, -(-u.shape[0] // 8) * 8)
    slabs = np.sort(rows) // sub
    a_chunk = [np.unique(slabs[k:k + step]).size
               for k in range(0, rows.size, step)]
    assert int(moved) == sum(a_chunk)
    cut = sum(a_chunk) - np.unique(slabs).size
    if case == "slab_across_chunks" and dtype == "float32":
        assert cut == 1
    if case == "dense_head":
        assert int(moved) * sub == rows.size


@pytest.mark.parametrize("slots,ahead", [(2, 1), (4, 3), (8, 2), (32, 16)])
def test_slab_writer_whatever_the_buffers(slots, ahead):
    """More slabs than buffers, fewer, and as many: the four phases of
    the kernel's pipeline each run dry in one of these."""
    rng = np.random.default_rng(slots)
    table = jnp.asarray(rng.normal(0, 0.5, (V, D)), jnp.float32)
    for n in sorted({1, ahead, slots - ahead, slots, slots + 1, 3 * slots}):
        rows = np.sort(rng.permutation(V // 8)[:n]) * 8 + rng.integers(0, 8, n)
        u, tot, n_u = _totals(rows, rng)
        want, _ = engine._write_rows(table, u, tot, n_u)
        got, moved = slab_writer.write(
            table, u, tot, n_u, chunk=CH, slots=slots, ahead=ahead,
            interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(moved) == n


@pytest.mark.parametrize("shape,dtype,why", [
    ((6000, 75), "float32", "a dims shard: no whole lanes"),
    ((6004, 384), "float32", "rows not a multiple of 8"),
    ((6008, 384), "bfloat16", "rows not a multiple of 16"),
    ((6000, 384), "float32", "whole slabs, but the mesh is the CPU's"),
])
def test_scatter_rows_picks_the_xla_writer(shape, dtype, why):
    """No argument says which writer runs: a table the kernel cannot
    address never reaches it, and one it can reaches it only in a program
    lowered for a TPU. Here everything is lowered for the CPU."""
    rng = np.random.default_rng(5)
    n = 500
    ids = rng.integers(0, shape[0] + 50, n).astype(np.int32)
    args = (
        jnp.asarray(rng.normal(0, 0.5, shape), dtype), jnp.asarray(ids),
        jnp.asarray(rng.normal(0, 0.05, n).astype(np.float32)),
        jnp.asarray(rng.normal(0, 1, (16, shape[1])).astype(np.float32)),
        jnp.asarray(rng.integers(0, 16, n).astype(np.int32)), 0,
    )
    fits = slab_writer.fits(shape, dtype)
    assert fits == (why == "whole slabs, but the mesh is the CPU's")
    jaxpr = str(jax.make_jaxpr(engine._scatter_rows)(*args))
    assert ("pallas_call" in jaxpr) == fits  # offered, where it fits
    lowered = jax.jit(engine._scatter_rows).lower(*args).as_text()
    assert "tpu_custom_call" not in lowered and "scatter" in lowered

    out, n_u, moved = jax.jit(engine._scatter_rows)(*args)
    assert int(moved) == 0 and int(n_u) == np.unique(
        ids[ids < shape[0]]).size
    u, tot, n_live = engine._run_totals(
        jnp.where(args[1] < shape[0], args[1], shape[0]), *args[2:5],
        shape[0],
    )
    want, _ = engine._write_rows(args[0], u, tot, n_live)
    np.testing.assert_array_equal(
        np.asarray(out, np.float32), np.asarray(want, np.float32)
    )
