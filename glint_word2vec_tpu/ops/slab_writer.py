"""The SGNS step's row writer for a TPU: move each touched slab once, and
total each row's run while the slab is held.

A table that rests in whole lanes (``engine.TABLE_LANES``) is tiled
``(8, 128)`` on the device (``(16, 128)`` for bfloat16), and the rows that
share a tile row, a SLAB, are contiguous in HBM: 12 KB at 384 columns. One
row is not a legal DMA slice of such a table ("Slice shape along dimension
0 must be aligned to tiling (8), but is 1": PERF.md, PR 29); a slab is. So
where XLA's TPU scatter costs about 96 ns for every row it is handed
(PERF.md, PR 26), this writer copies every DISTINCT slab among the rows
HBM -> VMEM, adds what the batch adds to its rows and copies it back, with
``AHEAD`` reads in flight and the write-backs waited lazily.

:func:`write` takes the update slots themselves as ``engine._sort_slots``
orders them: the target rows sorted (so the slots of one row, and the rows
of one slab, are neighbours, a run in the order it stood in the batch;
slots no row of the table takes sorted to the end), each with its
coefficient and its source row's index. It forms a chunk's payload
``coefs * src[hidx]`` in float32, and the kernel adds every slot of a slab
into a zeroed float32 accumulator at its row's sublane, rounds that once
to the table's dtype and adds it to the slab: the arithmetic of
``engine._run_totals`` + ``engine._write_rows`` (XLA's writer), so the
table is the same bit for bit, with no buffer of slots x columns, no
scatter and no second sort (PERF.md, PR 35). A slab whose slots a chunk's
edge cuts is not moved by the earlier call: its accumulator is carried
into the next. The kernel exists only for a TPU (Mosaic) and only for a
table :func:`fits` admits; the engine chooses between the two writers at
lowering time and no option selects either. Tests run the kernel on the
CPU through ``write(..., interpret=True)``.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Slots one call of the kernel takes: their payload into VMEM (6 MB of f32
#: at 384 columns; a wider table takes fewer slots, ``PAYLOAD_BYTES`` at
#: most), their rows into SMEM. Stand-alone on the chip 4,096 slots a call
#: took 1.5% (word level) to 17% (CBOW's bags) less than 2,048, and 8,192
#: little less again for twice the VMEM (PERF.md, PR 35).
CHUNK = 4096
PAYLOAD_BYTES = 6 << 20
#: Slab buffers in VMEM, and how many reads are started ahead of the slab
#: being added to. A buffer's write-back is waited for only when the
#: buffer is read into again, ``SLOTS - AHEAD`` slabs later.
SLOTS = 32
AHEAD = 16


def slab_rows(dtype) -> int:
    """Rows of one tile row: 8 of a 4-byte type, 16 of a 2-byte one."""
    return 32 // jnp.dtype(dtype).itemsize


def fits(shape, dtype) -> bool:
    """Whether the kernel can address the rows of such a (local) table by
    slabs: f32 or bf16, whole 128-column lanes, whole slabs."""
    return (
        jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16)
        and shape[1] % 128 == 0
        and shape[0] % slab_rows(dtype) == 0
    )


def _kernel(meta_ref, row_ref, nxt_ref, pay_ref, carry_ref, table_in, table,
            carry_out, buf, sem_in, sem_out, *, sub, slots, ahead):
    """One chunk of sorted slots. ``meta_ref`` = (live slots, slabs to
    move, whether the first slab's accumulator starts from ``carry_ref``,
    whether the slots after the last moved slab go to ``carry_out``);
    ``row_ref`` the slots' rows, ``nxt_ref[i]`` the chunk-local index of
    the first slot of the next slab after slot ``i``, ``pay_ref`` the
    slots' payload. ``table`` is ``table_in``, in place, in HBM."""
    del table_in
    n_live, n_slabs = meta_ref[0], meta_ref[1]
    shift = sub.bit_length() - 1
    sublane = lax.broadcasted_iota(jnp.int32, buf.shape[1:], 0)

    def slab_of(p):
        row0 = pl.multiple_of((row_ref[p] >> shift) << shift, sub)
        return table.at[pl.ds(row0, sub)]

    # A wait reads the semaphore and the copy's size, not its addresses.
    any_slab = table.at[pl.ds(0, sub)]

    def wait_read(s):
        pltpu.make_async_copy(any_slab, buf.at[s], sem_in.at[s]).wait()

    def wait_write(s):
        pltpu.make_async_copy(buf.at[s], any_slab, sem_out.at[s]).wait()

    def fetch(j, pf):
        """Start reading the slab whose first slot is ``pf`` into slab
        ``j``'s buffer; returns the next slab's first slot."""
        s = j & (slots - 1)
        pltpu.make_async_copy(slab_of(pf), buf.at[s], sem_in.at[s]).start()
        return nxt_ref[pf]

    def add_slot(i, acc):
        row = jnp.broadcast_to(pay_ref[pl.ds(i, 1), :], acc.shape)
        return jnp.where(sublane == (row_ref[i] & (sub - 1)), acc + row, acc)

    def carried(first):
        """The accumulator a slab starts from: what the last call carried
        for the chunk's first slab if that call cut it, else zero."""
        rows = jnp.where(first & (meta_ref[2] != 0), sub, 0)
        return jnp.where(sublane < rows, carry_ref[...], 0.0)

    def step(j, carry, *, prefetch, reuse):
        p, pf = carry
        if prefetch:
            if reuse:  # that buffer went out SLOTS - AHEAD slabs ago
                wait_write((j + ahead) & (slots - 1))
            pf = fetch(j + ahead, pf)
        s = j & (slots - 1)
        q = jnp.minimum(nxt_ref[p], n_live)
        # Most slabs carry one slot or two (the benchmark's steps: 1.2 to
        # 1.8 slots a slab), so the first is added outside the loop: 7 ns
        # a slab.
        acc = add_slot(p, carried(j == 0))
        acc = lax.fori_loop(p + 1, q, add_slot, acc)
        wait_read(s)
        # The run's total is rounded to the table's dtype, then added:
        # what ``t.at[u].add(tot.astype(t.dtype))`` does.
        acc = acc.astype(buf.dtype).astype(jnp.float32)
        buf[s] = (buf[s].astype(jnp.float32) + acc).astype(buf.dtype)
        pltpu.make_async_copy(buf.at[s], slab_of(p), sem_out.at[s]).start()
        return q, pf

    def steps(lo, hi, carry, **kw):
        return lax.fori_loop(lo, hi, functools.partial(step, **kw), carry)

    pf = lax.fori_loop(0, jnp.minimum(ahead, n_slabs), fetch, 0)
    fetching = jnp.maximum(n_slabs - ahead, 0)
    fresh = jnp.minimum(slots - ahead, fetching)
    carry = steps(0, fresh, (0, pf), prefetch=True, reuse=False)
    carry = steps(fresh, fetching, carry, prefetch=True, reuse=True)
    p, _ = steps(fetching, n_slabs, carry, prefetch=False, reuse=False)

    # The slots of a slab that goes on in the next chunk: summed, not moved.
    cut = meta_ref[3] != 0
    carry_out[...] = lax.fori_loop(
        p, jnp.where(cut, n_live, p), add_slot, carried(n_slabs == 0)
    )

    def drain(j, c):
        wait_write(j & (slots - 1))
        return c

    lax.fori_loop(jnp.maximum(n_slabs - slots, 0), n_slabs, drain, 0)


def _shifted(x, by, fill):
    """``x[:, by:]`` with ``fill`` behind it: slot ``i`` of a chunk reads
    slot ``i + by``."""
    return jnp.pad(x[:, by:], ((0, 0), (0, by)), constant_values=fill)


def write(table, rows, coefs, src, hidx, *, chunk=CHUNK, slots=SLOTS,
          ahead=AHEAD, interpret=False):
    """Add ``coefs[k] * src[hidx[k]]`` to row ``rows[k]`` of ``table`` for
    every slot ``k`` whose row the table has: ``rows`` sorted, as
    ``engine._sort_slots`` gives them with their ``coefs`` and ``hidx``
    (anything from ``table.shape[0]`` up marks a slot to skip, and sorts
    last). Returns ``(table, slabs moved)``: each touched slab once."""
    assert fits(table.shape, table.dtype)
    assert slots & (slots - 1) == 0 and 0 < ahead < slots
    sub = slab_rows(table.dtype)
    d = table.shape[1]
    n = rows.shape[0]
    while chunk > 8 and chunk * d * 4 > PAYLOAD_BYTES:
        chunk //= 2
    chunk = min(chunk, -(-n // 8) * 8)
    n_chunks = -(-n // chunk)
    pad = (0, n_chunks * chunk - n)
    rows = jnp.pad(rows, pad, constant_values=jnp.iinfo(jnp.int32).max)
    coefs = jnp.pad(coefs.astype(jnp.float32), pad)
    hidx = jnp.pad(hidx, pad)
    # Bookkeeping on the sorted rows alone. A slab's slots are neighbours,
    # any number of them; from a slot, the chunk-local index of the next
    # slab's first slot is a reverse running minimum over the starts. The
    # kernel's two cursors hop along it.
    live = (rows < table.shape[0]).reshape(n_chunks, chunk)
    slab = rows >> (sub.bit_length() - 1)
    new = jnp.concatenate(
        [jnp.ones(1, bool), slab[1:] != slab[:-1]]
    ).reshape(n_chunks, chunk)
    pos = jnp.arange(chunk, dtype=jnp.int32)
    start = new | (pos == 0)
    nxt = _shifted(jnp.where(start, pos, chunk), 1, chunk)
    # A doubling scan: ``lax.cummin`` lowers to reduce-windows that carry
    # no scope of the step's and take 0.12 ms.
    by = 1
    while by < chunk:
        nxt = jnp.minimum(nxt, _shifted(nxt, by, chunk))
        by *= 2
    n_live = live.sum(dtype=jnp.int32)
    # A chunk whose last slab goes on at the head of the next one leaves
    # it to that one, with what it summed of it.
    cut = jnp.pad(live[1:, 0] & ~new[1:, 0], (0, 1))
    meta = jnp.stack([
        live.sum(1, dtype=jnp.int32),
        (start & live).sum(1, dtype=jnp.int32) - cut,
        jnp.roll(cut, 1), cut,
    ], axis=1)

    call = pl.pallas_call(
        functools.partial(_kernel, sub=sub, slots=slots, ahead=ahead),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((chunk, d), lambda g, *_: (0, 0)),
                pl.BlockSpec((sub, d), lambda g, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((sub, d), lambda g, *_: (0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((slots, sub, d), table.dtype),
                pltpu.SemaphoreType.DMA((slots,)),
                pltpu.SemaphoreType.DMA((slots,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(table.shape, table.dtype),
            jax.ShapeDtypeStruct((sub, d), jnp.float32),
        ],
        input_output_aliases={5: 0, 4: 1},
        interpret=pltpu.InterpretParams() if interpret else False,
    )

    def one(k, state):
        t, carry = state
        at = k * chunk
        c = lax.dynamic_slice_in_dim(coefs, at, chunk)
        h = lax.dynamic_slice_in_dim(hidx, at, chunk)
        payload = c[:, None] * src[h].astype(jnp.float32)
        return tuple(call(
            meta[k], lax.dynamic_slice_in_dim(rows, at, chunk), nxt[k],
            payload, carry, t,
        ))

    table, _ = lax.fori_loop(
        0, -(-n_live // chunk), one,
        (table, jnp.zeros((sub, d), jnp.float32)),
    )
    return table, (new & live).sum(dtype=jnp.int32)
