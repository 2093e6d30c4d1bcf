"""Pallas TPU kernels for the sparse row traffic of the SGNS step.

The hot sparse ops in the engine (parallel/engine.py) are row gathers
(``_pull_rows``) and rank-1 row scatter-adds (``_scatter_rows``) into the
(V, d) embedding tables — the device-side restatement of what the reference
parameter servers do inside ``dotprod``/``adjust`` (mllib:421-425). XLA
lowers them to generic gather/scatter; these kernels instead move rows with
explicit per-row DMAs.

Design (round 2 — round 1 streamed one (1, d) block per grid step, a
sublane-1 block shape with an N-step scalar grid, flagged as probably slow):
both kernels now process ``block_rows`` rows per grid step with manual
HBM<->VMEM DMAs (``pltpu.make_async_copy``) issued from a scalar-prefetched
index vector, so up to ``block_rows`` row copies are in flight at once and
grid overhead is amortized ``block_rows``-fold. The table itself never
enters the automatic pipeline (``pl.ANY`` memory space): only the touched
rows move.

Correctness contract for the scatter: duplicate target rows must SUM their
updates (synchronous-batch semantics, SURVEY.md §7 hard part 1). Updates
are sorted by row id (duplicates become adjacent), each grid step
accumulates its block's runs of equal ids sequentially in VMEM, and one
read-modify-write DMA per run lands the total. TPU grid steps execute
sequentially on a core and every write DMA is waited before the step ends,
so a run spanning two blocks is just two ordered read-modify-writes of the
same row — still a sum.

These kernels are OPT-IN (engine flag / GLINT_W2V_PALLAS env var) and
INTERPRET-ONLY today: the TPU's compiler refuses all three ("Slice shape
along dimension 0 must be aligned to tiling (8), but is 1" — the per-row
``make_async_copy`` of a (1, d) slice of the tiled HBM table is not a legal
DMA; tests/test_tpu_compile.py keeps the verdicts as strict xfails), and the
engine raises on ``use_pallas`` under a tpu backend. On CPU they run in
interpret mode, which is how the unit tests exercise them. Keep or delete is
ROADMAP S5/D1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pad_rows(n: int, block_rows: int) -> int:
    return -(-n // block_rows) * block_rows


# ----------------------------------------------------------------------
# Gather
# ----------------------------------------------------------------------


def _gather_kernel(block_rows, ids_ref, table_ref, out_ref, sems):
    base = pl.program_id(0) * block_rows

    def start(j, _):
        pltpu.make_async_copy(
            table_ref.at[ids_ref[base + j]], out_ref.at[j], sems.at[j]
        ).start()
        return 0

    lax.fori_loop(0, block_rows, start, 0)

    def wait(j, _):
        pltpu.make_async_copy(
            table_ref.at[ids_ref[base + j]], out_ref.at[j], sems.at[j]
        ).wait()
        return 0

    lax.fori_loop(0, block_rows, wait, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def gather_rows(
    table: jax.Array,
    ids: jax.Array,
    *,
    interpret: bool = False,
    block_rows: int = 16,
):
    """``table[ids]`` as a Pallas row pipeline: ``block_rows`` per-row DMAs
    in flight per grid step, addresses from the prefetched ``ids``."""
    N = ids.shape[0]
    d = table.shape[1]
    Np = _pad_rows(N, block_rows)
    ids_p = jnp.pad(ids.astype(jnp.int32), (0, Np - N))  # pad rows read row 0
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Np // block_rows,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # table stays in HBM
        out_specs=pl.BlockSpec((block_rows, d), lambda i, ids: (i, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA((block_rows,))],
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, block_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Np, d), table.dtype),
        interpret=interpret,
    )(ids_p, table)
    return out[:N]


# ----------------------------------------------------------------------
# Scatter-add
# ----------------------------------------------------------------------


def _scatter_runs(
    block_rows, upd_fn, ids_ref, out_ref, tbl, wb, acc, rsems, wsems
):
    """The shared run-summing scatter pipeline. ``upd_fn(j, gj) -> row``
    produces update row j (block-local) / gj (global) in table dtype; the
    two kernels below differ ONLY in that producer.

    out_ref aliases the table's storage (input_output_aliases); all row
    traffic is explicit DMA against it. ids are sorted globally, so equal
    ids form runs that are contiguous within and across blocks.
    """
    base = pl.program_id(0) * block_rows

    # Read phase: fetch the current table row for every update row
    # (duplicates re-read the same row; only each run's first copy is used).
    def rstart(j, _):
        pltpu.make_async_copy(
            out_ref.at[ids_ref[base + j]], tbl.at[j], rsems.at[j]
        ).start()
        return 0

    lax.fori_loop(0, block_rows, rstart, 0)

    def rwait(j, _):
        pltpu.make_async_copy(
            out_ref.at[ids_ref[base + j]], tbl.at[j], rsems.at[j]
        ).wait()
        return 0

    lax.fori_loop(0, block_rows, rwait, 0)

    # Accumulate phase: sequential over the block's rows; a run of equal
    # ids sums into acc, and each run's END row materializes table+sum in
    # wb[j] (a stable per-row buffer, so write DMAs of earlier runs can
    # still be in flight) and starts its write-back.
    def body(j, _):
        gj = base + j
        # Clamp the previous-id lookup: at gj == 0 the unclamped index -1
        # would read before the scalar buffer (masked by j > 0, but the
        # read itself is out of bounds on hardware).
        prev_same = jnp.logical_and(
            j > 0, ids_ref[gj] == ids_ref[jnp.maximum(gj - 1, 0)]
        )
        cur = upd_fn(j, gj) + jnp.where(prev_same, acc[0], tbl[j])
        acc[0] = cur
        wb[j] = cur
        is_end = jnp.logical_or(
            j == block_rows - 1, ids_ref[gj + 1] != ids_ref[gj]
        )

        @pl.when(is_end)
        def _():
            pltpu.make_async_copy(
                wb.at[j], out_ref.at[ids_ref[gj]], wsems.at[j]
            ).start()

        return 0

    lax.fori_loop(0, block_rows, body, 0)

    # All writes must land before this grid step ends: the next step may
    # read a row this one wrote (a run spanning the block boundary).
    def wwait(j, _):
        gj = base + j
        is_end = jnp.logical_or(
            j == block_rows - 1, ids_ref[gj + 1] != ids_ref[gj]
        )

        @pl.when(is_end)
        def _():
            pltpu.make_async_copy(
                wb.at[j], out_ref.at[ids_ref[gj]], wsems.at[j]
            ).wait()

        return 0

    lax.fori_loop(0, block_rows, wwait, 0)


def _scatter_kernel(
    block_rows, ids_ref, upd_ref, table_ref, out_ref, tbl, wb, acc, rsems, wsems
):
    del table_ref
    _scatter_runs(
        block_rows, lambda j, gj: upd_ref[j],
        ids_ref, out_ref, tbl, wb, acc, rsems, wsems,
    )


def _scatter_rank1_kernel(
    block_rows, ids_ref, hidx_ref, coef_ref, h_ref, table_ref, out_ref,
    tbl, wb, acc, rsems, wsems,
):
    # Fused-payload variant: the update row is never materialized in HBM —
    # it is formed in VMEM as coef[j] * h[hidx[j]] with h resident whole in
    # VMEM. Same id-sorted run-summing contract (_scatter_runs).
    del table_ref

    def upd(j, gj):
        return (
            coef_ref[j, 0] * h_ref[hidx_ref[gj]].astype(jnp.float32)
        ).astype(tbl.dtype)

    _scatter_runs(
        block_rows, upd, ids_ref, out_ref, tbl, wb, acc, rsems, wsems
    )


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def scatter_add_rank1(
    table: jax.Array,
    ids: jax.Array,  # (N,) target row per update
    coef: jax.Array,  # (N,) scalar coefficient per update
    h: jax.Array,  # (B, d) center vectors; row hidx[j] scales into ids[j]
    hidx: jax.Array,  # (N,) which h row each update uses
    *,
    interpret: bool = False,
    block_rows: int = 8,
):
    """``table.at[ids].add(coef[:, None] * h[hidx])`` without materializing
    the (N, d) payload in HBM: h is pinned whole in VMEM and each update row
    is formed in-register inside the scatter pipeline. This is the TPU
    kernel form of the reference's ``adjust`` wire format — scalars in,
    rank-1 updates applied at the data (mllib:422-425).

    Requires h to fit VMEM alongside the block buffers (~9.8 MB at the
    bench shape B=8192, d=300, f32); callers gate on that.
    """
    N = ids.shape[0]
    d = table.shape[1]
    Np = _pad_rows(N, block_rows)
    sid, order = lax.sort_key_val(
        ids.astype(jnp.int32), jnp.arange(N, dtype=jnp.int32)
    )
    scoef = coef.astype(jnp.float32)[order]
    shidx = hidx.astype(jnp.int32)[order]
    sid = jnp.pad(sid, (0, Np - N), mode="edge")
    scoef = jnp.pad(scoef, (0, Np - N))  # zero coef: pad adds 0 to last run
    shidx = jnp.pad(shidx, (0, Np - N))
    ids_arg = jnp.concatenate([sid, jnp.full((1,), -1, jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # ids, hidx
        grid=(Np // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, 1), lambda i, ids, hidx: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.VMEM),  # h, whole in VMEM
            pl.BlockSpec(memory_space=pl.ANY),  # table (aliased to output)
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((block_rows, d), table.dtype),
            pltpu.VMEM((block_rows, d), table.dtype),
            pltpu.VMEM((1, d), table.dtype),
            pltpu.SemaphoreType.DMA((block_rows,)),
            pltpu.SemaphoreType.DMA((block_rows,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_scatter_rank1_kernel, block_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={4: 0},  # table arg (after prefetch) -> output
        interpret=interpret,
    )(ids_arg, shidx, scoef.reshape(-1, 1), h, table)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def scatter_add_rows(
    table: jax.Array,
    ids: jax.Array,
    upd: jax.Array,
    *,
    interpret: bool = False,
    block_rows: int = 8,
):
    """``table.at[ids].add(upd)`` with duplicate-summing semantics, as an
    in-place (aliased) Pallas row pipeline over id-sorted updates."""
    N, d = upd.shape
    Np = _pad_rows(N, block_rows)
    sid, order = lax.sort_key_val(
        ids.astype(jnp.int32), jnp.arange(N, dtype=jnp.int32)
    )
    supd = upd.astype(table.dtype)[order]
    # Pad by extending the LAST run (edge mode) with zero updates: the pad
    # rows add 0 to the final run's sum. Padding with any other id could
    # place a second run for an already-written row inside the same block,
    # whose stale read-modify-write would overwrite that row's real update.
    sid = jnp.pad(sid, (0, Np - N), mode="edge")
    supd = jnp.pad(supd, ((0, Np - N), (0, 0)))
    # The kernel indexes ids[gj+1] for the run-end test; append a sentinel
    # (never equal to a real id) so the final run closes at the last row.
    ids_arg = jnp.concatenate([sid, jnp.full((1,), -1, jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Np // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i, ids: (i, 0)),  # updates
            pl.BlockSpec(memory_space=pl.ANY),  # table (aliased to output)
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            # Same dtype as the table: these buffers are DMA endpoints for
            # its rows (copies require matching dtypes), and accumulating a
            # run in table dtype matches the XLA scatter-add's semantics.
            pltpu.VMEM((block_rows, d), table.dtype),  # tbl rows read
            pltpu.VMEM((block_rows, d), table.dtype),  # write-back buffers
            pltpu.VMEM((1, d), table.dtype),  # run accumulator
            pltpu.SemaphoreType.DMA((block_rows,)),  # read sems
            pltpu.SemaphoreType.DMA((block_rows,)),  # write sems
        ],
    )
    return pl.pallas_call(
        functools.partial(_scatter_kernel, block_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={2: 0},  # table arg (after prefetch) -> output
        interpret=interpret,
    )(ids_arg, supd, table)
