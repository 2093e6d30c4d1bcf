"""The SGNS step's row writer for a TPU: move each touched slab once.

A table that rests in whole lanes (``engine.TABLE_LANES``) is tiled
``(8, 128)`` on the device (``(16, 128)`` for bfloat16), and the rows that
share a tile row, a SLAB, are contiguous in HBM: 12 KB at 384 columns. One
row is not a legal DMA slice of such a table ("Slice shape along dimension
0 must be aligned to tiling (8), but is 1": PERF.md, PR 29); a slab is. So
where XLA's TPU scatter costs about 96 ns for every row it is handed
(PERF.md, PR 26), this writer copies every DISTINCT slab among the rows
HBM -> VMEM, adds the slab's totals into their sublanes and copies it
back, with ``AHEAD`` reads in flight and the write-backs waited lazily.

:func:`write` takes what ``engine._run_totals`` returns (the distinct rows
sorted, so the rows of one slab are neighbours; their f32 totals; how many
are live) and gives the same table ``engine._scatter_rows``' XLA writer
gives, bit for bit: the total is rounded once to the table's dtype and
added to its row once. It exists only for a TPU (a Mosaic kernel) and only
for a table :func:`fits` admits; the engine chooses between the two at
lowering time and no option selects either. Tests run the kernel on the
CPU through ``write(..., interpret=True)``.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Rows of totals one call of the kernel takes into VMEM (3 MB of f32 at
#: 384 columns, which the call's pipeline holds twice; a wider table takes
#: fewer rows, ``TOT_BYTES`` at most) and whose ids it takes into SMEM. A
#: slab that straddles two chunks is moved twice, by two calls that run in
#: order.
CHUNK = 2048
TOT_BYTES = 3 << 20
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


def _kernel(meta_ref, u_ref, nxt_ref, tot_ref, table_in, table, buf, sem_in,
            sem_out, *, sub, slots, ahead):
    """One chunk: ``meta_ref`` = (chunk index, live rows, slabs) of it,
    ``u_ref`` its rows' ids, ``nxt_ref[i]`` the chunk-local index of the
    first row of the next slab after row ``i``, ``tot_ref`` its totals.
    ``table`` is ``table_in``, in place, in HBM."""
    del table_in
    n_rows, n_slabs = meta_ref[1], meta_ref[2]
    shift = sub.bit_length() - 1
    sublane = lax.broadcasted_iota(jnp.int32, buf.shape[1:], 0)

    def slab_of(p):
        row0 = pl.multiple_of((u_ref[p] >> shift) << shift, sub)
        return table.at[pl.ds(row0, sub)]

    # A wait reads the semaphore and the copy's size, not its addresses.
    any_slab = table.at[pl.ds(0, sub)]

    def wait_read(s):
        pltpu.make_async_copy(any_slab, buf.at[s], sem_in.at[s]).wait()

    def wait_write(s):
        pltpu.make_async_copy(buf.at[s], any_slab, sem_out.at[s]).wait()

    def fetch(j, pf):
        """Start reading the slab whose first row is ``pf`` into slab
        ``j``'s buffer; returns the next slab's first row."""
        s = j & (slots - 1)
        pltpu.make_async_copy(slab_of(pf), buf.at[s], sem_in.at[s]).start()
        return nxt_ref[pf]

    def add_row(i, acc):
        row = jnp.broadcast_to(tot_ref[pl.ds(i, 1), :], acc.shape)
        # The total is rounded to the table's dtype, then added: what
        # ``t.at[u].add(tot.astype(t.dtype))`` does.
        row = row.astype(buf.dtype).astype(jnp.float32)
        return jnp.where(sublane == (u_ref[i] & (sub - 1)), acc + row, acc)

    def step(j, carry, *, prefetch, reuse):
        p, pf = carry
        if prefetch:
            if reuse:  # that buffer went out SLOTS - AHEAD slabs ago
                wait_write((j + ahead) & (slots - 1))
            pf = fetch(j + ahead, pf)
        s = j & (slots - 1)
        wait_read(s)
        q = jnp.minimum(nxt_ref[p], n_rows)
        # Six slabs in seven carry one row (the benchmark's step: 1.35 rows
        # a slab), so the first is added outside the loop: 7 ns a slab.
        acc = add_row(p, buf[s].astype(jnp.float32))
        acc = lax.fori_loop(p + 1, q, add_row, acc)
        buf[s] = acc.astype(buf.dtype)
        pltpu.make_async_copy(buf.at[s], slab_of(p), sem_out.at[s]).start()
        return q, pf

    def steps(lo, hi, carry, **kw):
        return lax.fori_loop(lo, hi, functools.partial(step, **kw), carry)

    pf = lax.fori_loop(0, jnp.minimum(ahead, n_slabs), fetch, 0)
    fetching = jnp.maximum(n_slabs - ahead, 0)
    fresh = jnp.minimum(slots - ahead, fetching)
    carry = steps(0, fresh, (0, pf), prefetch=True, reuse=False)
    carry = steps(fresh, fetching, carry, prefetch=True, reuse=True)
    steps(fetching, n_slabs, carry, prefetch=False, reuse=False)

    def drain(j, c):
        wait_write(j & (slots - 1))
        return c

    lax.fori_loop(jnp.maximum(n_slabs - slots, 0), n_slabs, drain, 0)


def write(table, u, tot, n_u, *, chunk=CHUNK, slots=SLOTS, ahead=AHEAD,
          interpret=False):
    """Add ``tot[i]`` to row ``u[i]`` of ``table`` for ``i < n_u``: ``u``
    sorted and distinct, ``tot`` float32, as ``engine._run_totals`` makes
    them. Returns ``(table, slabs moved)``."""
    assert fits(table.shape, table.dtype)
    assert slots & (slots - 1) == 0 and 0 < ahead < slots
    sub = slab_rows(table.dtype)
    n = u.shape[0]
    while chunk > 8 and chunk * tot.shape[1] * 4 > TOT_BYTES:
        chunk //= 2
    chunk = min(chunk, -(-n // 8) * 8)
    n_pad = -(-n // chunk) * chunk
    u = jnp.pad(u, (0, n_pad - n), constant_values=jnp.iinfo(jnp.int32).max)
    tot = jnp.pad(tot, ((0, n_pad - n), (0, 0)))
    # Where a slab starts (every chunk starts one) and, from a start, the
    # chunk-local index of the next one: a slab's rows are neighbours in the
    # sorted ``u``, at most ``sub`` of them. The kernel's two cursors hop
    # along it.
    index = jnp.arange(n_pad, dtype=jnp.int32)
    pos = index % chunk
    slab = u >> (sub.bit_length() - 1)
    start = (pos == 0) | (slab != jnp.roll(slab, 1))
    nxt = pos + 1
    for t in range(1, sub):
        nxt += (jnp.roll(slab, -t) == slab) & (pos + t < chunk)
    slabs = (start & (index < n_u)).reshape(-1, chunk).sum(
        1, dtype=jnp.int32
    )

    call = pl.pallas_call(
        functools.partial(_kernel, sub=sub, slots=slots, ahead=ahead),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(
                    (chunk, tot.shape[1]), lambda g, meta, *_: (meta[0], 0)
                ),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((slots, sub, table.shape[1]), table.dtype),
                pltpu.SemaphoreType.DMA((slots,)),
                pltpu.SemaphoreType.DMA((slots,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={4: 0},
        interpret=pltpu.InterpretParams() if interpret else False,
    )

    def one(k, t):
        meta = jnp.stack([k, jnp.clip(n_u - k * chunk, 0, chunk), slabs[k]])
        return call(
            meta,
            lax.dynamic_slice_in_dim(u, k * chunk, chunk),
            lax.dynamic_slice_in_dim(nxt, k * chunk, chunk),
            tot, t,
        )

    table = lax.fori_loop(0, -(-n_u // chunk), one, table)
    return table, slabs.sum(dtype=jnp.int32)

