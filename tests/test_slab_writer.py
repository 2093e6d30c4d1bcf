"""The slab writer (``ops/slab_writer.py``) against the XLA writer of
``engine._scatter_rows`` on the same sorted slots: bit-equal, float32 and
bfloat16, whatever the runs, the slots a slab and where a chunk's edge
falls. The kernel runs on the CPU in Pallas' TPU interpret mode, through
the module's own ``write(..., interpret=True)``; that it compiles for the
chip is ``tests/test_tpu_compile.py``'s, and what it costs is a chip run's
(PERF.md, PRs 30 and 35). Then: which writer ``_scatter_rows`` picks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.ops import slab_writer
from glint_word2vec_tpu.parallel import engine

V, D = 2048, 128
CH = 64  # the chunk most cases pass: interpret mode takes 5 ms a slab


def _rows(case, sub, rng):
    """Distinct local rows a case writes, each named twice in the batch,
    and the chunk it passes."""
    if case == "dense_head":  # every row of the first 40 slabs
        return np.arange(40 * sub), CH
    if case == "singletons":  # one row a slab, every sublane in turn
        return np.arange(100) * sub + np.arange(100) % sub, CH
    if case.startswith("n_u="):  # around the chunk's edge; 0: nothing
        n = {"0": 0, "1": 1, "CH-1": CH - 1, "CH": CH, "CH+1": CH + 1}[
            case[4:]]
        return np.sort(rng.permutation(V)[:n]), CH
    if case == "slab_across_chunks":
        # sorted rows 60..67 are one f32 slab (rows 8 x 70 + 0..7), their
        # slots 120..135: a chunk's edge falls inside the slab, between
        # two of its rows' runs
        lone = np.arange(60) * sub
        return np.concatenate([lone, 70 * sub + np.arange(sub), [V - 1]]), CH
    if case == "last_slab":
        return np.asarray([0, V - sub, V - 2, V - 1]), CH
    if case == "fewer_slabs_than_buffers":
        return np.asarray([3, 4, 9 * sub, 20 * sub + 1]), CH
    assert case == "default_chunk"  # the shipped CHUNK, a Zipf-like mix
    p = 1.0 / np.arange(1, V + 1)
    return np.unique(rng.choice(V, size=700, p=p / p.sum())), slab_writer.CHUNK


ROW_CASES = [
    "dense_head", "singletons", "n_u=0", "n_u=1", "n_u=CH-1", "n_u=CH",
    "n_u=CH+1", "slab_across_chunks", "last_slab",
    "fewer_slabs_than_buffers", "default_chunk",
]


def _runs(case, sub):
    """The live slots' rows of a case that names its runs outright."""
    lone = np.arange(3, 40) * sub + 5  # 37 slabs of one slot each
    if case == "run_of_600":  # over nine chunks' edges, rows on both sides
        return np.concatenate([lone, np.full(600, 50 * sub + 2), [V - 1]])
    if case == "slab_over_sub_slots":  # 3 * sub + 1 slots, two of its rows
        return np.concatenate([
            lone, np.full(2 * sub + 1, 60 * sub), np.full(sub, 60 * sub + 3),
        ])
    if case == "runs_in_one_slab":  # every row of two slabs, a run each
        rows = np.concatenate([60 * sub + np.arange(sub),
                               61 * sub + np.arange(sub)])
        return np.concatenate([lone, np.repeat(rows, 1 + rows % 5)])
    if case == "sentinels_only":
        return np.zeros(0, np.int64)
    if case in ("live=CH", "live=CH+1", "live=2CH"):
        n = {"CH": CH, "CH+1": CH + 1, "2CH": 2 * CH}[case[5:]]
        return np.sort(np.random.default_rng(n).integers(0, V, n))
    if case == "run_cut_by_chunk_edge":
        # 37 lone slots, then a run of 40 on slots 37..76: across slot 64
        return np.concatenate([lone, np.full(40, 50 * sub + 1), [V - 2]])
    if case == "run_ends_at_chunk_edge":
        # the run stands on slots 37..63 and the next chunk starts another
        # row of the SAME slab: the slab is cut, no run is
        return np.concatenate([
            lone, np.full(27, 50 * sub + 1), np.full(5, 50 * sub + 2),
        ])
    assert case == "cut_slab_is_the_last"
    # the last live slab goes over a chunk's edge and the sentinels follow
    return np.concatenate([lone, np.full(30, V - 1)])


RUN_CASES = [
    "run_of_600", "slab_over_sub_slots", "runs_in_one_slab",
    "sentinels_only", "live=CH", "live=CH+1", "live=2CH",
    "run_cut_by_chunk_edge", "run_ends_at_chunk_edge",
    "cut_slab_is_the_last",
]

# What the kernel's unrolled loop can get wrong. These cases pass few
# buffers, so that a chunk of CH slots has slabs for the pipeline's every
# phase: TRIP["slots"] - TRIP["ahead"] slabs one by one while the buffers
# are fresh, then trips of ``slab_writer.UNROLL`` and what is left of one,
# then the last TRIP["ahead"] with nothing more to read.
TRIP = {"slots": 16, "ahead": 8}
U, A, S = slab_writer.UNROLL, TRIP["ahead"], TRIP["slots"]
SLAB_COUNTS = sorted({0, 1, U - 1, U, U + 1, A - 1, A + 1, S, S + U - 1,
                      S + U, S + U + 1, 3 * S})
# slabs a chunk moves whole before the one its edge cuts: the cut slab
# would have been a trip's first, or its last
CUT_AT = {"trip_first": S + 2 * U, "trip_last": S + 2 * U + U - 1}
TRIP_CASES = (
    [f"slabs={n}" for n in SLAB_COUNTS]
    + [f"slab_of={k}" for k in (1, 2, 3, 40)]
    + [f"{what}_cut_at_{at}" for what in ("run", "slab") for at in CUT_AT]
    + ["dead_chunk_behind_trips", "shard_owns_none"]
)


def _trips(case, sub):
    """``(live rows, dead slots, chunk)`` of a case of ``TRIP_CASES``."""
    lone = np.arange(3, 63) * sub + 5  # 60 slabs of one slot each
    if case.startswith("slabs="):  # so many slabs in the one chunk
        return lone[:int(case[6:])], 3, CH
    if case.startswith("slab_of="):
        # inside a trip (slab S - A + 2 * U + 1 of the chunk, not a
        # trip's first), a slab with so many slots over three of its rows
        k = int(case[8:])
        at = S - A + 2 * U + 1
        big = 70 * sub + np.sort(np.arange(k) % 3)
        return np.concatenate([lone[:at], big, lone[at:2 * at] + 60 * sub]
                              ), 3, 2 * CH
    if "_cut_at_" in case:
        what, at = case.split("_cut_at_")
        m = CUT_AT[at]
        # m lone slots, then a slab on slots m .. CH + 9: one row's run, or
        # two rows whose runs meet at the chunk's edge
        over = np.full(CH + 10 - m, 100 * sub + 1)
        if what == "slab":
            over[CH - m:] += 1
        return np.concatenate([lone[:m], over, [V - 1]]), 3, CH
    if case == "dead_chunk_behind_trips":  # CH live slots, then 70 dead
        return lone[:CH - 4].tolist() + [V - 4, V - 3, V - 2, V - 1], 70, CH
    assert case == "shard_owns_none"
    return np.zeros(0, np.int64), 200, CH


def _slots(live, rng, dead=3):
    """``(sid, coefs, src, hidx)`` as ``_scatter_rows`` hands them to its
    writers: the batch's slots (``live`` in a random order among ``dead``
    slots of other shards' rows) sorted by row."""
    ids = rng.permutation(np.concatenate([live, np.full(dead, V)]))
    src = rng.normal(0, 1, (32, D)).astype(np.float32)
    hidx = rng.integers(0, 32, ids.size).astype(np.int32)
    coefs = rng.normal(0, 0.05, ids.size).astype(np.float32)
    coefs[ids >= V] = np.nan  # what no writer may add to a row
    sid, coefs, hidx = engine._sort_slots(
        jnp.asarray(ids.astype(np.int32)), jnp.asarray(coefs),
        jnp.asarray(hidx),
    )
    return sid, coefs, jnp.asarray(src), hidx


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ROW_CASES + RUN_CASES + TRIP_CASES)
def test_slab_writer_is_bit_equal_to_xla_writer(case, dtype):
    rng = np.random.default_rng(len(case))
    sub = slab_writer.slab_rows(dtype)
    dead, buffers = 3, {}
    if case in ROW_CASES:
        rows, chunk = _rows(case, sub, rng)
        live = np.concatenate([rows, rows])
    elif case in RUN_CASES:
        live, chunk = _runs(case, sub), CH
    else:
        (live, dead, chunk), buffers = _trips(case, sub), TRIP
        live = np.asarray(live, np.int64)
    table = jnp.asarray(rng.normal(0, 0.5, (V, D)), dtype)
    slots = _slots(live, rng, dead)

    want, none = engine._write_rows(table, *slots)
    got, moved = slab_writer.write(
        table, *slots, chunk=chunk, interpret=True, **buffers
    )

    assert got.dtype == table.dtype and int(none) == 0
    bits = np.uint32 if dtype == "float32" else np.uint16
    np.testing.assert_array_equal(
        np.asarray(got).view(bits), np.asarray(want).view(bits)
    )
    if live.size:  # something was written, and no dead slot poisoned it
        assert (np.asarray(got) != np.asarray(table)).any()
        assert np.isfinite(np.asarray(got, np.float32)).all()
    else:
        np.testing.assert_array_equal(
            np.asarray(got).view(bits), np.asarray(table).view(bits)
        )
    # Every distinct slab once: one a chunk's edge cuts is carried, not
    # moved twice.
    slabs = np.sort(live) // sub
    assert int(moved) == np.unique(slabs).size
    cut = [k for k in range(chunk, live.size, chunk)
           if slabs[k] == slabs[k - 1]]
    if case in ("slab_across_chunks", "run_of_600", "run_cut_by_chunk_edge",
                "run_ends_at_chunk_edge", "cut_slab_is_the_last"
                ) or "_cut_at_" in case:
        assert cut, case  # the case is what its name says
    if "_cut_at_" in case:  # and so is where the cut slab stands
        whole = np.unique(slabs[:chunk]).size - 1
        assert (whole - (S - A)) % U == (0 if "first" in case else U - 1)
    if case == "run_of_600":
        assert len(cut) >= 9
    if case == "dense_head":
        assert int(moved) * sub == rows.size


@pytest.mark.parametrize("slots,ahead,unroll", [
    (2, 1, 1), (4, 3, 1), (8, 2, 2), (32, 16, 4),
    (8, 4, 4), (16, 8, 8), (64, 24, 4), (64, 32, 8),
])
def test_slab_writer_whatever_the_buffers(slots, ahead, unroll):
    """More slabs than buffers, fewer, and as many, and a trip more or
    less: the phases of the kernel's pipeline each run dry in one of
    these."""
    rng = np.random.default_rng(slots)
    table = jnp.asarray(rng.normal(0, 0.5, (V, D)), jnp.float32)
    for n in sorted({1, ahead, slots - ahead, slots, slots + 1,
                     slots + unroll - 1, slots + unroll, 3 * slots}):
        rows = np.sort(rng.permutation(V // 8)[:n]) * 8 + rng.integers(0, 8, n)
        args = _slots(np.concatenate([rows, rows]), rng)
        want, _ = engine._write_rows(table, *args)
        got, moved = slab_writer.write(
            table, *args, chunk=max(CH, 8 * slots), slots=slots, ahead=ahead,
            unroll=unroll, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(moved) == n


@pytest.mark.parametrize("shape,dtype,why", [
    ((6000, 75), "float32", "no whole lanes"),
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
    sid, coefs, hidx = engine._sort_slots(
        jnp.where(args[1] < shape[0], args[1], shape[0]), args[2], args[4]
    )
    want, _ = engine._write_rows(args[0], sid, coefs, args[3], hidx)
    np.testing.assert_array_equal(
        np.asarray(out, np.float32), np.asarray(want, np.float32)
    )
