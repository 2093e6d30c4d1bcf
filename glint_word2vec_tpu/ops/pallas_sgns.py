"""Fused Pallas SGNS pair-step megakernel (ISSUE 11).

The packed pair step (ops/sgns.train_step_pairs through the engine's
packed corpus scan) is XLA-composed: gather h/u rows -> dot -> sigmoid
-> rank-1 outer products -> scatter-add. Every touched row round-trips
HBM *between* those ops, and whether bf16 tables pay off under the XLA
lowering has not been measured on a chip (ROADMAP S4).
This module fuses the whole pair update into Pallas kernels that move
each touched row across the HBM<->VMEM boundary once per phase and do
ALL arithmetic in fp32 VMEM registers regardless of the table's storage
dtype — bf16 tables become a pure bandwidth win (half the bytes per
row), with fp32 accumulation so low-precision storage never compounds
through a batch's duplicate-row sums.

Phase structure (one logical megakernel, staged as pallas_calls because
the synchronous-batch contract puts a hard barrier between the batch's
gathers and its scatters — every row value consumed by the update math
must be the PRE-batch value, ops/sgns.train_step semantics):

  1. ``pair_forward`` / ``pair_forward_shared``: per pair block, DMA the
     touched syn0/syn1 rows HBM->VMEM (block-DMA machinery of
     ops/pallas_rows.py), upcast to fp32 in VMEM, run dot -> sigmoid ->
     coefficient math, and emit ONLY the compact results: the scalar
     coefficients (the reference's gPlus/gMinus wire format), the center
     rows ``h`` (fp32), the center gradient ``d_center`` (fp32), and the
     summed monitoring loss. The (P, n, d) negative rows and the (P, S)
     pool logits never touch HBM — they live and die in VMEM. In shared
     mode the negative pool is DMA'd once, pinned in VMEM for the whole
     grid, and both pool contractions run as dense level-3 BLAS blocks
     on the MXU (the pSGNScc restructuring, arXiv:1611.06172):
     ``f_pool = h_blk @ pool^T`` and ``d_pool += c_pool^T @ h_blk``.
  2. ``scatter_add_rank1_hbm`` (syn1: contexts + per-pair negatives, or
     contexts alone in shared mode) and ``scatter_add_rows_f32`` (syn0:
     d_center rows; shared mode adds the dense pool payload): id-sorted
     run-summing scatters extending ops/pallas_rows._scatter_runs with
     (a) fp32 VMEM run accumulators over any-dtype tables (the composed
     XLA path scatter-adds bf16 tables IN bf16 — each duplicate row
     collision re-rounds; here a run is summed in fp32 and rounded to
     storage once per write-back, i.e. once per block it spans) and
     (b) rank-1 payloads formed in VMEM from
     ``coef * h[hidx]`` with ``h`` streamed per-row from HBM, so the
     (N, d) update payload never materializes. Exactly one accumulated
     read-modify-write lands per row run; runs spanning grid-step
     boundaries are two ordered RMWs of the same row (TPU grid steps on
     a core are sequential and every write DMA is waited before the
     step ends) — still a sum.

The only HBM intermediates between the phases are the (P,) coefficient
vectors and the two (P, d) fp32 arrays ``h`` and ``d_center`` (both
members of the minimal cut: the syn1 payload needs pre-update syn0 rows
and the syn0 payload needs pre-update syn1 rows, so whichever scatter
runs second cannot re-gather its payload source — see the ordering note
on :func:`fused_pair_step`). The composed path materializes those PLUS
u_pos (P, d), u_neg (P, n, d), and both expanded rank-1 payloads.

Like ops/pallas_rows.py these kernels are OPT-IN (engine flag /
``GLINT_W2V_PALLAS``) and INTERPRET-ONLY today, which is how the parity
tests (tests/test_pallas_sgns.py, 3-way vs the composed XLA step and a
host-NumPy oracle) exercise them on the CPU mesh. The TPU's compiler
refuses every entry point (tests/test_tpu_compile.py, strict xfails):
the scatters for the same unaligned (1, d) row DMA as ops/pallas_rows.py,
``pair_forward`` with "Cannot store scalars to VMEM", and
``pair_forward_shared`` with "Unimplemented primitive in Pallas TPU
lowering for KernelType.TC: scatter"; the engine raises on ``use_pallas``
under a tpu backend. Nothing above has been timed on a chip.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM budget for pinning the shared negative pool (storage + fp32
#: copies) in the shared-mode forward kernel, alongside the block
#: buffers: ~16 MB/core minus headroom. Callers gate with
#: :func:`shared_pool_vmem_ok` and fall back to the composed path.
_POOL_VMEM_BYTES = 10_000_000

#: DMA semaphores used to pipeline the one-time pool row fetch.
_POOL_DMA_PIPELINE = 8


def _pad_rows(n: int, block_rows: int) -> int:
    return -(-n // block_rows) * block_rows


def shared_pool_vmem_ok(pool_size: int, dim: int, table_dtype) -> bool:
    """Whether a shared pool of this geometry fits the forward kernel's
    VMEM budget (pool rows in table dtype + the fp32 working copy +
    the (S, d) fp32 d_pool accumulator block)."""
    itemsize = jnp.dtype(table_dtype).itemsize
    return pool_size * dim * (itemsize + 4 + 4) <= _POOL_VMEM_BYTES


# ----------------------------------------------------------------------
# Phase 1: forward (gather + dot + sigmoid + coefficient math in VMEM)
# ----------------------------------------------------------------------


class PairForward(NamedTuple):
    """Compact forward outputs of one dense pair batch — everything the
    scatter phase (and the loss record) needs, and nothing row-shaped
    beyond the two (P, d) members of the minimal phase cut."""

    c_pos: jax.Array  # (P,)   alpha * (1 - sigmoid(f_pos)) * mask
    c_neg: jax.Array  # (P, n) -alpha * sigmoid(f_neg) * nmask
    h: jax.Array  # (P, d) fp32 — pre-update syn0 rows of the centers
    d_center: jax.Array  # (P, d) fp32 — LR-folded center gradient
    loss_sum: jax.Array  # () summed pair loss (masked; divide by mask.sum())


def _pair_forward_kernel(
    block_rows, n,
    centers_ref, contexts_ref, negs_ref,  # scalar-prefetched ids
    mask_ref, nmask_ref, alpha_ref, syn0_ref, syn1_ref,  # inputs
    cpos_ref, cneg_ref, h_ref, dcen_ref, loss_ref,  # outputs
    hbuf, ubuf, nbuf, sems,  # scratch
):
    i = pl.program_id(0)
    base = i * block_rows

    # One DMA per touched row: h, u_pos, and the n negatives per pair,
    # all in flight together (block_rows * (2 + n) copies).
    def start(j, _):
        pltpu.make_async_copy(
            syn0_ref.at[centers_ref[base + j]], hbuf.at[j], sems.at[j, 0]
        ).start()
        pltpu.make_async_copy(
            syn1_ref.at[contexts_ref[base + j]], ubuf.at[j], sems.at[j, 1]
        ).start()

        def neg_start(k, _):
            pltpu.make_async_copy(
                syn1_ref.at[negs_ref[(base + j) * n + k]],
                nbuf.at[j * n + k], sems.at[j, 2 + k],
            ).start()
            return 0

        lax.fori_loop(0, n, neg_start, 0)
        return 0

    lax.fori_loop(0, block_rows, start, 0)

    def wait(j, _):
        pltpu.make_async_copy(
            syn0_ref.at[centers_ref[base + j]], hbuf.at[j], sems.at[j, 0]
        ).wait()
        pltpu.make_async_copy(
            syn1_ref.at[contexts_ref[base + j]], ubuf.at[j], sems.at[j, 1]
        ).wait()

        def neg_wait(k, _):
            pltpu.make_async_copy(
                syn1_ref.at[negs_ref[(base + j) * n + k]],
                nbuf.at[j * n + k], sems.at[j, 2 + k],
            ).wait()
            return 0

        lax.fori_loop(0, n, neg_wait, 0)
        return 0

    lax.fori_loop(0, block_rows, wait, 0)

    # All arithmetic in fp32 — the rows were DMA'd in table dtype and
    # upcast here, once, in VMEM (the mixed-precision contract).
    hb = hbuf[...].astype(jnp.float32)  # (Bk, d)
    ub = ubuf[...].astype(jnp.float32)  # (Bk, d)
    nb = nbuf[...].astype(jnp.float32).reshape(
        block_rows, n, hb.shape[-1]
    )  # (Bk, n, d)
    mask = mask_ref[...][:, 0]  # (Bk,)
    nmask = nmask_ref[...]  # (Bk, n)
    alpha = alpha_ref[0, 0]

    f_pos = jnp.sum(hb * ub, axis=-1)  # (Bk,)
    f_neg = jnp.sum(hb[:, None, :] * nb, axis=-1)  # (Bk, n)
    c_pos = alpha * (1.0 - jax.nn.sigmoid(f_pos)) * mask
    c_neg = -alpha * jax.nn.sigmoid(f_neg) * nmask
    d_center = c_pos[:, None] * ub + jnp.sum(
        c_neg[:, :, None] * nb, axis=1
    )  # (Bk, d)
    log_sig = jax.nn.log_sigmoid
    pair_loss = (
        -log_sig(f_pos) - jnp.sum(log_sig(-f_neg) * nmask, axis=-1)
    ) * mask

    cpos_ref[...] = c_pos[:, None]
    cneg_ref[...] = c_neg
    h_ref[...] = hb
    dcen_ref[...] = d_center

    @pl.when(i == 0)
    def _():
        loss_ref[0, 0] = 0.0

    loss_ref[0, 0] += jnp.sum(pair_loss)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def pair_forward(
    syn0: jax.Array,  # (V, d) table (fp32 or bf16 storage)
    syn1: jax.Array,  # (V, d)
    centers: jax.Array,  # (P,) int32
    contexts: jax.Array,  # (P,) int32
    mask: jax.Array,  # (P,) float32 — 1.0 where the pair is real
    negs: jax.Array,  # (P, n) int32 — per-pair negative draws
    nmask: jax.Array,  # (P, n) float32 — negatives kept
    alpha: jax.Array,  # () float32
    *,
    interpret: bool = False,
    block_rows: int = 8,
) -> PairForward:
    """Forward half of the fused pair step (per-pair negatives)."""
    P = centers.shape[0]
    n = negs.shape[1]
    d = syn0.shape[1]
    Pp = _pad_rows(P, block_rows)
    padn = (0, Pp - P)
    centers_p = jnp.pad(centers.astype(jnp.int32), padn)
    contexts_p = jnp.pad(contexts.astype(jnp.int32), padn)
    negs_p = jnp.pad(negs.astype(jnp.int32), (padn, (0, 0))).reshape(-1)
    mask_p = jnp.pad(mask.astype(jnp.float32), padn).reshape(-1, 1)
    nmask_p = jnp.pad(nmask.astype(jnp.float32), (padn, (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # centers, contexts, flat negatives
        grid=(Pp // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, 1), lambda i, *_: (i, 0)),  # mask
            pl.BlockSpec((block_rows, n), lambda i, *_: (i, 0)),  # nmask
            pl.BlockSpec(memory_space=pltpu.SMEM),  # alpha (1, 1)
            pl.BlockSpec(memory_space=pl.ANY),  # syn0 stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),  # syn1 stays in HBM
        ],
        out_specs=[
            pl.BlockSpec((block_rows, 1), lambda i, *_: (i, 0)),  # c_pos
            pl.BlockSpec((block_rows, n), lambda i, *_: (i, 0)),  # c_neg
            pl.BlockSpec((block_rows, d), lambda i, *_: (i, 0)),  # h
            pl.BlockSpec((block_rows, d), lambda i, *_: (i, 0)),  # d_center
            pl.BlockSpec((1, 1), lambda i, *_: (0, 0)),  # loss accumulator
        ],
        scratch_shapes=[
            pltpu.VMEM((block_rows, d), syn0.dtype),  # h rows (table dtype)
            pltpu.VMEM((block_rows, d), syn1.dtype),  # u_pos rows
            pltpu.VMEM((block_rows * n, d), syn1.dtype),  # negative rows
            pltpu.SemaphoreType.DMA((block_rows, 2 + n)),
        ],
    )
    c_pos, c_neg, h, d_center, loss = pl.pallas_call(
        functools.partial(_pair_forward_kernel, block_rows, n),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Pp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Pp, n), jnp.float32),
            jax.ShapeDtypeStruct((Pp, d), jnp.float32),
            jax.ShapeDtypeStruct((Pp, d), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(
        centers_p, contexts_p, negs_p,
        mask_p, nmask_p, alpha.astype(jnp.float32).reshape(1, 1),
        syn0, syn1,
    )
    return PairForward(
        c_pos=c_pos[:P, 0], c_neg=c_neg[:P], h=h[:P],
        d_center=d_center[:P], loss_sum=loss[0, 0],
    )


class SharedPairForward(NamedTuple):
    """Forward outputs of the shared-negative-pool estimator. The
    (P, S) pool coefficient matrix never leaves VMEM — its two uses
    (d_center's pool term, the dense d_pool payload) are contracted
    in-kernel on the MXU."""

    c_pos: jax.Array  # (P,)
    h: jax.Array  # (P, d) fp32
    d_center: jax.Array  # (P, d) fp32
    d_pool: jax.Array  # (S, d) fp32 — dense update for the pool rows
    loss_sum: jax.Array  # ()


def _pair_forward_shared_kernel(
    block_rows, n, pool_size,
    centers_ref, contexts_ref, pool_ref,  # scalar-prefetched ids
    mask_ref, pool_ids_ref, alpha_ref, syn0_ref, syn1_ref,  # inputs
    cpos_ref, h_ref, dcen_ref, dpool_ref, loss_ref,  # outputs
    hbuf, ubuf, poolbuf, pool32, sems, psems,  # scratch
):
    i = pl.program_id(0)
    base = i * block_rows
    S = pool_size

    # One-time pool staging: DMA the S pool rows HBM->VMEM at grid step
    # 0 (pipelined over a small semaphore ring) and pin the fp32 copy
    # for the whole grid — every later step reuses it from VMEM.
    @pl.when(i == 0)
    def _():
        def pstart(j, _):
            @pl.when(j >= _POOL_DMA_PIPELINE)
            def _():
                k = j - _POOL_DMA_PIPELINE
                pltpu.make_async_copy(
                    syn1_ref.at[pool_ref[k]], poolbuf.at[k],
                    psems.at[k % _POOL_DMA_PIPELINE],
                ).wait()

            pltpu.make_async_copy(
                syn1_ref.at[pool_ref[j]], poolbuf.at[j],
                psems.at[j % _POOL_DMA_PIPELINE],
            ).start()
            return 0

        lax.fori_loop(0, S, pstart, 0)

        # Drain: pstart already waited copies 0 .. S-1-PIPELINE (each
        # j >= PIPELINE waits j - PIPELINE before reusing its slot), so
        # the still-pending copies are exactly the LAST min(PIPELINE, S)
        # — wait those, starting at max(0, S - PIPELINE). (An earlier
        # form indexed S - PIPELINE + j with a >= 0 guard, which for
        # S < PIPELINE silently skipped the tail copies — interpret
        # mode executes copies synchronously and can never catch it.)
        drain_n = min(_POOL_DMA_PIPELINE, S)
        drain_0 = max(0, S - _POOL_DMA_PIPELINE)

        def pdrain(j, _):
            k = drain_0 + j
            pltpu.make_async_copy(
                syn1_ref.at[pool_ref[k]], poolbuf.at[k],
                psems.at[k % _POOL_DMA_PIPELINE],
            ).wait()
            return 0

        lax.fori_loop(0, drain_n, pdrain, 0)
        pool32[...] = poolbuf[...].astype(jnp.float32)
        dpool_ref[...] = jnp.zeros_like(dpool_ref[...])

    def start(j, _):
        pltpu.make_async_copy(
            syn0_ref.at[centers_ref[base + j]], hbuf.at[j], sems.at[j, 0]
        ).start()
        pltpu.make_async_copy(
            syn1_ref.at[contexts_ref[base + j]], ubuf.at[j], sems.at[j, 1]
        ).start()
        return 0

    lax.fori_loop(0, block_rows, start, 0)

    def wait(j, _):
        pltpu.make_async_copy(
            syn0_ref.at[centers_ref[base + j]], hbuf.at[j], sems.at[j, 0]
        ).wait()
        pltpu.make_async_copy(
            syn1_ref.at[contexts_ref[base + j]], ubuf.at[j], sems.at[j, 1]
        ).wait()
        return 0

    lax.fori_loop(0, block_rows, wait, 0)

    hb = hbuf[...].astype(jnp.float32)  # (Bk, d)
    ub = ubuf[...].astype(jnp.float32)  # (Bk, d)
    pool = pool32[...]  # (S, d) fp32, pinned
    mask = mask_ref[...][:, 0]  # (Bk,)
    pool_ids = pool_ids_ref[...][:, 0]  # (S,)
    alpha = alpha_ref[0, 0]

    f_pos = jnp.sum(hb * ub, axis=-1)  # (Bk,)
    # Level-3 BLAS pool block: one MXU matmul scores the whole block
    # against the whole pool.
    f_pool = jnp.dot(
        hb, pool.T, preferred_element_type=jnp.float32
    )  # (Bk, S)
    # Pool-wide target==word skip, C=1 form: a pool word colliding with
    # THE context word of the pair is dropped (ops/sgns
    # .pool_collision_mask restated for pair rows, computed in VMEM —
    # the (P, S) mask never materializes in HBM).
    ctx_ids = _block_ctx_ids(contexts_ref, base, block_rows)  # (Bk,)
    keep = (pool_ids[None, :] != ctx_ids[:, None]).astype(jnp.float32)
    weight = (mask * (n / S))[:, None] * keep  # (Bk, S)
    c_pos = alpha * (1.0 - jax.nn.sigmoid(f_pos)) * mask
    c_pool = -alpha * jax.nn.sigmoid(f_pool) * weight  # (Bk, S) VMEM-only
    d_center = c_pos[:, None] * ub + jnp.dot(
        c_pool, pool, preferred_element_type=jnp.float32
    )  # (Bk, d)
    log_sig = jax.nn.log_sigmoid
    loss_blk = jnp.sum(-log_sig(f_pos) * mask) + jnp.sum(
        -log_sig(-f_pool) * weight
    )

    cpos_ref[...] = c_pos[:, None]
    h_ref[...] = hb
    dcen_ref[...] = d_center
    # Dense pool gradient, accumulated across grid steps in the output
    # block (constant index map -> the block stays resident in VMEM):
    # d_pool += c_pool^T @ h_blk, the second MXU contraction.
    dpool_ref[...] += jnp.dot(
        c_pool.T, hb, preferred_element_type=jnp.float32
    )

    @pl.when(i == 0)
    def _():
        loss_ref[0, 0] = 0.0

    loss_ref[0, 0] += loss_blk


def _block_ctx_ids(contexts_ref, base, block_rows):
    """Read this block's context ids out of the scalar-prefetch ref as
    a (block_rows,) vector (SMEM scalars gathered by a tiny loop — the
    ids are already on-chip)."""
    def body(j, acc):
        return acc.at[j].set(contexts_ref[base + j])

    return lax.fori_loop(
        0, block_rows, body, jnp.zeros((block_rows,), jnp.int32)
    )


@functools.partial(
    jax.jit, static_argnames=("num_negatives", "interpret", "block_rows")
)
def pair_forward_shared(
    syn0: jax.Array,  # (V, d)
    syn1: jax.Array,  # (V, d)
    centers: jax.Array,  # (P,) int32
    contexts: jax.Array,  # (P,) int32
    mask: jax.Array,  # (P,) float32
    pool: jax.Array,  # (S,) int32 — the step's shared negative pool
    alpha: jax.Array,  # () float32
    num_negatives: int,  # n being emulated (weight n/S per pool word)
    *,
    interpret: bool = False,
    block_rows: int = 8,
) -> SharedPairForward:
    """Forward half of the fused pair step, shared-pool estimator."""
    P = centers.shape[0]
    S = pool.shape[0]
    d = syn0.shape[1]
    Pp = _pad_rows(P, block_rows)
    padn = (0, Pp - P)
    centers_p = jnp.pad(centers.astype(jnp.int32), padn)
    contexts_p = jnp.pad(contexts.astype(jnp.int32), padn)
    mask_p = jnp.pad(mask.astype(jnp.float32), padn).reshape(-1, 1)
    pool_i = pool.astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # centers, contexts, pool
        grid=(Pp // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, 1), lambda i, *_: (i, 0)),  # mask
            pl.BlockSpec(memory_space=pltpu.VMEM),  # pool ids (S, 1)
            pl.BlockSpec(memory_space=pltpu.SMEM),  # alpha (1, 1)
            pl.BlockSpec(memory_space=pl.ANY),  # syn0
            pl.BlockSpec(memory_space=pl.ANY),  # syn1
        ],
        out_specs=[
            pl.BlockSpec((block_rows, 1), lambda i, *_: (i, 0)),  # c_pos
            pl.BlockSpec((block_rows, d), lambda i, *_: (i, 0)),  # h
            pl.BlockSpec((block_rows, d), lambda i, *_: (i, 0)),  # d_center
            pl.BlockSpec((S, d), lambda i, *_: (0, 0)),  # d_pool (resident)
            pl.BlockSpec((1, 1), lambda i, *_: (0, 0)),  # loss
        ],
        scratch_shapes=[
            pltpu.VMEM((block_rows, d), syn0.dtype),
            pltpu.VMEM((block_rows, d), syn1.dtype),
            pltpu.VMEM((S, d), syn1.dtype),  # pool rows, table dtype
            pltpu.VMEM((S, d), jnp.float32),  # pool rows, fp32 pinned
            pltpu.SemaphoreType.DMA((block_rows, 2)),
            pltpu.SemaphoreType.DMA((_POOL_DMA_PIPELINE,)),
        ],
    )
    c_pos, h, d_center, d_pool, loss = pl.pallas_call(
        functools.partial(
            _pair_forward_shared_kernel, block_rows,
            # graftlint: ignore[sync-point] static python config int -> float for the kernel closure
            float(num_negatives), S,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Pp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Pp, d), jnp.float32),
            jax.ShapeDtypeStruct((Pp, d), jnp.float32),
            jax.ShapeDtypeStruct((S, d), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(
        centers_p, contexts_p, pool_i,
        mask_p, pool_i.reshape(-1, 1),
        alpha.astype(jnp.float32).reshape(1, 1),
        syn0, syn1,
    )
    return SharedPairForward(
        c_pos=c_pos[:P, 0], h=h[:P], d_center=d_center[:P],
        d_pool=d_pool, loss_sum=loss[0, 0],
    )


# ----------------------------------------------------------------------
# Phase 2: id-sorted run-summing scatters with fp32 VMEM accumulation
# ----------------------------------------------------------------------


def _scatter_runs_f32(
    block_rows, upd_fn, ids_ref, out_ref, tbl, wb, acc, rsems, wsems
):
    """ops/pallas_rows._scatter_runs generalized to MIXED precision:
    the table rows are DMA'd in storage dtype, each run of equal
    (globally sorted) ids is summed in a fp32 VMEM accumulator, and the
    run total is rounded to storage dtype exactly once at its single
    read-modify-write. ``upd_fn(j, gj) -> fp32 row`` produces update
    row j (block-local) / gj (global)."""
    base = pl.program_id(0) * block_rows

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

    def body(j, _):
        gj = base + j
        prev_same = jnp.logical_and(
            j > 0, ids_ref[gj] == ids_ref[jnp.maximum(gj - 1, 0)]
        )
        cur = upd_fn(j, gj) + jnp.where(
            prev_same, acc[0], tbl[j].astype(jnp.float32)
        )
        acc[0] = cur
        wb[j] = cur.astype(wb.dtype)
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

    # All writes land before the grid step ends: a run spanning the
    # block boundary is the next step's first read of this row.
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


def _scatter_rows_f32_kernel(
    block_rows, ids_ref, upd_ref, table_ref, out_ref,
    tbl, wb, acc, rsems, wsems,
):
    del table_ref
    _scatter_runs_f32(
        block_rows, lambda j, gj: upd_ref[j],
        ids_ref, out_ref, tbl, wb, acc, rsems, wsems,
    )


def _sorted_scatter_args(ids, N, block_rows):
    """Shared sort/pad plumbing: globally sort ids (duplicates become
    contiguous runs), pad by EXTENDING the last run (edge mode — pad
    rows add zero to the final run's sum; any other id could open a
    second run for an already-written row inside one block), and append
    the -1 sentinel the run-end test reads at gj + 1."""
    Np = _pad_rows(N, block_rows)
    sid, order = lax.sort_key_val(
        ids.astype(jnp.int32), jnp.arange(N, dtype=jnp.int32)
    )
    sid = jnp.pad(sid, (0, Np - N), mode="edge")
    ids_arg = jnp.concatenate([sid, jnp.full((1,), -1, jnp.int32)])
    return Np, order, ids_arg


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def scatter_add_rows_f32(
    table: jax.Array,  # (V, d) storage dtype (fp32 or bf16)
    ids: jax.Array,  # (N,) target row per update
    upd: jax.Array,  # (N, d) fp32 update rows
    *,
    interpret: bool = False,
    block_rows: int = 8,
):
    """``table.at[ids].add(upd)`` with duplicate-run sums accumulated in
    fp32 VMEM and exactly one storage-dtype read-modify-write per row
    run — the mixed-precision scatter of the fused pair step."""
    N, d = upd.shape
    Np, order, ids_arg = _sorted_scatter_args(ids, N, block_rows)
    supd = jnp.pad(
        upd.astype(jnp.float32)[order], ((0, Np - N), (0, 0))
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Np // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i, ids: (i, 0)),  # updates
            pl.BlockSpec(memory_space=pl.ANY),  # table (aliased)
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((block_rows, d), table.dtype),  # rows read
            pltpu.VMEM((block_rows, d), table.dtype),  # write-back
            pltpu.VMEM((1, d), jnp.float32),  # fp32 run accumulator
            pltpu.SemaphoreType.DMA((block_rows,)),
            pltpu.SemaphoreType.DMA((block_rows,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_scatter_rows_f32_kernel, block_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={2: 0},  # table arg (after prefetch) -> out
        interpret=interpret,
    )(ids_arg, supd, table)


def _scatter_rank1_hbm_kernel(
    block_rows, ids_ref, hidx_ref, coef_ref, h_ref, table_ref, out_ref,
    tbl, hbuf, wb, acc, rsems, hsems, wsems,
):
    # Rank-1 payload with h streamed from HBM: row j's payload is
    # coef[j] * h[hidx[j]], formed in VMEM after a per-row DMA of the
    # fp32 h row — no VMEM-resident copy of the whole h, so P is
    # unbounded (scatter_add_rank1 pins h whole and gates on its size).
    del table_ref
    base = pl.program_id(0) * block_rows

    def hstart(j, _):
        pltpu.make_async_copy(
            h_ref.at[hidx_ref[base + j]], hbuf.at[j], hsems.at[j]
        ).start()
        return 0

    lax.fori_loop(0, block_rows, hstart, 0)

    def hwait(j, _):
        pltpu.make_async_copy(
            h_ref.at[hidx_ref[base + j]], hbuf.at[j], hsems.at[j]
        ).wait()
        return 0

    lax.fori_loop(0, block_rows, hwait, 0)

    _scatter_runs_f32(
        block_rows, lambda j, gj: coef_ref[j, 0] * hbuf[j],
        ids_ref, out_ref, tbl, wb, acc, rsems, wsems,
    )


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def scatter_add_rank1_hbm(
    table: jax.Array,  # (V, d) storage dtype
    ids: jax.Array,  # (N,) target row per update
    coef: jax.Array,  # (N,) fp32 scalar coefficient per update
    h: jax.Array,  # (B, d) fp32 center rows (stays in HBM)
    hidx: jax.Array,  # (N,) which h row each update scales
    *,
    interpret: bool = False,
    block_rows: int = 8,
):
    """``table.at[ids].add(coef[:, None] * h[hidx])`` without ever
    materializing the (N, d) payload: h rows are DMA'd per update row,
    the product is formed in VMEM, runs are summed in fp32, and one
    storage-dtype read-modify-write lands per row run."""
    N = ids.shape[0]
    d = table.shape[1]
    Np, order, ids_arg = _sorted_scatter_args(ids, N, block_rows)
    scoef = jnp.pad(
        coef.astype(jnp.float32)[order], (0, Np - N)
    )  # zero coef: pad rows add 0 to the last run
    shidx = jnp.pad(hidx.astype(jnp.int32)[order], (0, Np - N))
    h32 = h.astype(jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # ids, hidx
        grid=(Np // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, 1), lambda i, *_: (i, 0)),  # coef
            pl.BlockSpec(memory_space=pl.ANY),  # h stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),  # table (aliased)
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((block_rows, d), table.dtype),  # rows read
            pltpu.VMEM((block_rows, d), jnp.float32),  # h rows
            pltpu.VMEM((block_rows, d), table.dtype),  # write-back
            pltpu.VMEM((1, d), jnp.float32),  # fp32 run accumulator
            pltpu.SemaphoreType.DMA((block_rows,)),
            pltpu.SemaphoreType.DMA((block_rows,)),
            pltpu.SemaphoreType.DMA((block_rows,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_scatter_rank1_hbm_kernel, block_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={4: 0},  # table arg (after prefetch) -> out
        interpret=interpret,
    )(ids_arg, shidx, scoef.reshape(-1, 1), h32, table)


# ----------------------------------------------------------------------
# The fused pair step (forward + scatters, both estimators)
# ----------------------------------------------------------------------


def fused_pair_step(
    syn0: jax.Array,
    syn1: jax.Array,
    centers: jax.Array,  # (P,) int32
    contexts: jax.Array,  # (P,) int32
    pair_mask: jax.Array,  # (P,) float32
    negs: jax.Array,  # (P, n) int32
    nmask: jax.Array,  # (P, n) float32
    alpha: jax.Array,  # () float32
    *,
    interpret: bool = False,
    block_rows: int = 8,
):
    """One fused dense-pair SGNS update (per-pair negatives). Returns
    ``(new_syn0, new_syn1, loss_sum)`` — the un-normalized summed loss;
    callers divide by ``pair_mask.sum()`` (the engine's global masked
    mean needs the sum form for its data-axis psum).

    Scatter ordering: syn1 first (its rank-1 payload reads the
    MATERIALIZED pre-update ``h``, never live syn0), then syn0 from the
    materialized ``d_center``. Neither scatter re-gathers from a table
    the other has already modified — the two (P, d) intermediates exist
    precisely to cut that dependency, preserving the composed step's
    all-gathers-before-all-scatters semantics.
    """
    P = centers.shape[0]
    n = negs.shape[1]
    fw = pair_forward(
        syn0, syn1, centers, contexts, pair_mask, negs, nmask, alpha,
        interpret=interpret, block_rows=block_rows,
    )
    rows = jnp.arange(P, dtype=jnp.int32)
    ids1 = jnp.concatenate([contexts.astype(jnp.int32), negs.reshape(-1)])
    coefs = jnp.concatenate([fw.c_pos, fw.c_neg.reshape(-1)])
    hidx = jnp.concatenate([rows, jnp.repeat(rows, n)])
    syn1 = scatter_add_rank1_hbm(
        syn1, ids1, coefs, fw.h, hidx,
        interpret=interpret, block_rows=block_rows,
    )
    syn0 = scatter_add_rows_f32(
        syn0, centers, fw.d_center,
        interpret=interpret, block_rows=block_rows,
    )
    return syn0, syn1, fw.loss_sum


def fused_pair_step_shared(
    syn0: jax.Array,
    syn1: jax.Array,
    centers: jax.Array,  # (P,) int32
    contexts: jax.Array,  # (P,) int32
    pair_mask: jax.Array,  # (P,) float32
    pool: jax.Array,  # (S,) int32
    alpha: jax.Array,  # () float32
    num_negatives: int,
    *,
    interpret: bool = False,
    block_rows: int = 8,
):
    """Shared-pool form of :func:`fused_pair_step`: the pool update is
    the dense (S, d) ``d_pool`` block computed on the MXU in the
    forward kernel, landed with the same run-summing fp32 scatter (pool
    ids may repeat — the alias draw is with replacement — and a pool
    word can also appear as a context: ordered RMWs still sum)."""
    P = centers.shape[0]
    fw = pair_forward_shared(
        syn0, syn1, centers, contexts, pair_mask, pool, alpha,
        num_negatives, interpret=interpret, block_rows=block_rows,
    )
    syn1 = scatter_add_rank1_hbm(
        syn1, contexts, fw.c_pos, fw.h, jnp.arange(P, dtype=jnp.int32),
        interpret=interpret, block_rows=block_rows,
    )
    syn1 = scatter_add_rows_f32(
        syn1, pool, fw.d_pool,
        interpret=interpret, block_rows=block_rows,
    )
    syn0 = scatter_add_rows_f32(
        syn0, centers, fw.d_center,
        interpret=interpret, block_rows=block_rows,
    )
    return syn0, syn1, fw.loss_sum
