"""The sharded embedding engine — the parameter-server replacement.

This is the in-tree, TPU-native re-implementation of the external Glint
fork's capability surface (SURVEY.md §2.2): the ``BigWord2VecMatrix`` whose
vocab rows are sharded 1/n per server (README.md:69) becomes two jax arrays
sharded ``P("model", None)`` over a device mesh, and every server-side op
maps to a jitted SPMD function:

  Glint op (call site)                     -> engine method
  ------------------------------------------------------------------
  dotprod + adjust (mllib:421,425)         -> train_step (one fused op)
  pull (mllib:514,539,639,652; ml:353)     -> pull
  pullAverage (ml:453)                     -> pull_average
  norms (mllib:486)                        -> norms
  multiply (mllib:598)                     -> multiply (+ top_k_cosine,
                                              replacing the O(vocab) driver
                                              scan at mllib:601-617)
  save (mllib:494) / loadWord2vecMatrix    -> save / load
  destroy / cols (mllib:665,473)           -> destroy / dim

Communication design: a sum over the "model" axis replaces the
client<->server pull round-trip (each shard contributes its owned rows,
zeros elsewhere): a ``psum`` of the rows where every shard needs them (a
serving pull; the step's centre side). The step's pair side sends no row:
a ``syn1`` row has one owner, whose ``h . u`` is the pair's logit, so the
shards ``psum`` the logit partials and their partial ``d_center``
(``step_body_rows``). An ``all_gather`` over the "data" axis
replaces the async gradient push. The data-axis exchange carries ONLY the batch's
center representations ``h`` (B x d), the scalar gradient coefficients
(the reference's gPlus/gMinus payload, mllib:422-425), and int32 indices —
O(batch * (d + pairs)) bytes, never the O(batch * pairs * d) expanded
rank-1 updates, and never O(vocab). Consuming shards re-form the
``coef x h`` outer products locally, fused by XLA into the scatter-add
(locked in by the HLO-bytes test, tests/test_engine.py). There is no
message-size ceiling, so the reference's ``GranularBigWord2VecMatrix``
splitter (mllib:83-85,362) has no analogue; request batching survives only
as ``max_query_rows`` chunking in the model layer to bound HBM spikes.

Negative sampling is mesh-invariant AND shard-local: each rank derives
per-row keys from the shared per-step key and its rows' GLOBAL batch
indices (``fold_in(key, global_row)``), reproducing exactly the draws any
other mesh shape makes for the same rows — the (seed -> identical
negatives) contract the reference implements by broadcasting a seed to
all servers (``dotprod(..., seed)``, mllib:420-421) — while sampling only
O(local rows) draws.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from glint_word2vec_tpu.corpus.alias import build_unigram_alias
from glint_word2vec_tpu.obs import events as obs_events
from glint_word2vec_tpu.ops import sgns
from glint_word2vec_tpu.utils import (
    atomic_write_json,
    atomic_write_npy,
    next_pow2,
)
from glint_word2vec_tpu.ops import slab_writer
from glint_word2vec_tpu.ops.sampling import (
    pack_alias_table,
    sample_negatives_packed,
    sample_negatives_per_row_packed,
)
from glint_word2vec_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    pad_to_multiple,
    table_sharding,
)


#: A table keeps each row in whole lanes of the device's
#: (8, 128) tile: ``dim`` columns rest in ``pad_to_multiple(dim,
#: TABLE_LANES)``, the rest zero for good (a zero column adds nothing to
#: a dot product and its gradient is zero). The TPU's default layout for
#: ``f32[N, 300]`` is column-major (300 pads to 304 sublanes, not to 384
#: lanes), and every program that gathers or scatters ROWS then copied
#: the whole table at its edge: four copies a dispatch of the packed
#: scan, one a ``pull``. For ``f32[N, 384]`` the default IS row-major, so
#: the tables rest as every program wants them and nothing is pinned.
#: (Pinning ``jax.experimental.layout.Format`` on the 300-column shape
#: gives the same bytes, but jaxlib 0.9.0 labels the outputs of an
#: executable it loaded from the persistent compile cache with the
#: DEFAULT layout, whatever it was compiled with, so every warm process
#: lowers the next program for a layout the buffer does not have:
#: PERF.md, PR 28.)
TABLE_LANES = 128


def _free(*buffers) -> None:
    """Give device buffers back now, not at collection; what is no device
    array (None, a host copy) or is already gone is passed over."""
    for a in buffers:
        try:
            a.delete()
        except Exception:
            pass


def _host_or_device(a, dtype=None):
    """Normalize a batch input WITHOUT moving it across the host/device
    boundary: device-resident ``jax.Array`` inputs are kept on device
    (cast in place if needed); anything else becomes a numpy array. The
    previous unconditional ``np.asarray`` forced a blocking device->host
    copy (plus a re-upload) whenever a caller fed device-resident batches
    — exactly the zero-copy path a device-side data pipeline wants."""
    if isinstance(a, jax.Array):
        return a.astype(dtype) if dtype is not None and a.dtype != dtype else a
    return np.asarray(a) if dtype is None else np.asarray(a, dtype=dtype)


def _score(table_l, q):
    """``table_l @ q`` in fp32 for the similarity programs (multiply, top-k,
    batch top-k). ``HIGHEST`` because the TPU's default rounds f32 operands
    to bf16 on the MXU: a coalesced (Q > 1) query then scored 5.6e-4 off the
    same word asked alone and swapped near-ties (chip run, PR 22), so a
    served rank depended on how the request was batched. The pass stays
    bound by reading the table once: at 1M x 300 f32 score + top-k took
    0.2% longer at Q=8 and 1.8% at Q=64, Q=1 the same (same run)."""
    return jnp.matmul(
        table_l.astype(jnp.float32), q, precision=lax.Precision.HIGHEST
    )


def _mask_terms(norms_l, start, n_queryable):
    """Cosine masking as one multiply + one add instead of a division plus
    two (.., V)-wide boolean selects: ``inv`` is the reciprocal norm (0 on
    masked rows), ``neg`` pins masked rows at -inf. Zero-norm rows must
    never outrank a real word with negative cosine (the reference's
    zero-norm guard at mllib:603-609 only had to avoid a 0/0); likewise
    rows at or past ``n_queryable`` (padding / subword buckets / spare
    extra rows not yet assigned a streaming word): only real words may
    surface from similarity search. ``n_queryable`` is a TRACED scalar —
    vocab_size + assigned extra rows — so online vocab growth (streaming
    hot-swap, ISSUE 10) widens the mask without recompiling any warmed
    top-k program. Both vectors are (V,) so the per-score work is a fused
    multiply-add — on the serving path this cut batch top-k time ~30%
    (SERVING_BENCH)."""
    ok = (norms_l > 0) & (
        start + jnp.arange(norms_l.shape[0]) < n_queryable
    )
    inv = jnp.where(ok, 1.0 / jnp.where(norms_l > 0, norms_l, 1.0), 0.0)
    neg = jnp.where(ok, 0.0, -jnp.inf)
    return inv, neg


def _shard_topk(table_l, q, norms_l, nq, start, kk):
    """One shard's candidates for a query ``(d,)`` or a batch ``(Q, d)``:
    the ``kk`` best masked cosines over ITS rows and their LOCAL row ids
    (``start`` is the shard's first row). Two sibling scopes, neither
    inside the other (a device trace files an op under its outermost
    ``glint.`` scope, as in the step body): ``glint.score``, the pass over
    the shard's rows and the mask terms, and ``glint.topk``, the local
    top-k. Scores are ``(table @ q.T).T``, not ``q @ table.T``: the
    tall-skinny orientation streams the row-major table once
    (bandwidth-bound like the single-query matvec) — 2x faster for small
    Q buckets on CPU, a wash at Q=16+."""
    with jax.named_scope("glint.score"):
        # two spellings, so that each lowers to the text it always had
        if q.ndim == 1:
            scores = _score(table_l, q)
            inv, neg = _mask_terms(norms_l, start, nq)
            masked = scores * inv + neg
        else:
            scores = _score(table_l, q.T).T  # (Q, Vs)
            inv, neg = _mask_terms(norms_l, start, nq)
            masked = scores * inv[None, :] + neg[None, :]
    with jax.named_scope("glint.topk"):
        return lax.top_k(masked, kk)  # (.., kk) values, local rows


def _merge_topk(val, idx, start, k):
    """What exists only across shards, under ``glint.merge``: the shards'
    candidates all-gathered over the model axis (values, then the rows as
    global ids), the second top-k over the M * kk of them, and the take of
    the ids."""
    with jax.named_scope("glint.merge"):
        axis = val.ndim - 1
        cand_val = lax.all_gather(val, MODEL_AXIS, tiled=True, axis=axis)
        cand_idx = lax.all_gather(
            idx + start, MODEL_AXIS, tiled=True, axis=axis
        )
        mval, mpos = lax.top_k(cand_val, min(k, cand_val.shape[axis]))
        if axis == 0:
            return mval, cand_idx[mpos]
        return mval, jnp.take_along_axis(cand_idx, mpos, axis=1)


def _own_rows(table_l, idx, start, rows_per_shard, table=None):
    """A shard's half of a pull: its own rows of ``idx`` as float32, zeros
    for every row another shard holds, under ``glint.gather`` (the step
    bodies name the ``table``, ``syn0`` or ``syn1``, as an inner scope, as
    their scatters do). An id no shard owns (the -1 that pads a subword
    group) is zeros on every shard."""
    with jax.named_scope("glint.gather"), (
        jax.named_scope(table) if table else contextlib.nullcontext()
    ):
        loc = idx - start
        own = (loc >= 0) & (loc < rows_per_shard)
        clipped = jnp.clip(loc, 0, rows_per_shard - 1)
        rows = table_l[clipped].astype(jnp.float32)
        rows = jnp.where(own[:, None], rows, 0.0)
    return rows


def _pull_rows(table_l, idx, start, rows_per_shard, table=None):
    """Gather global rows from a shard-local table: contribute owned rows,
    zeros elsewhere (:func:`_own_rows`), then all-reduce over the model
    axis, so EVERY shard holds every row: what the serving pulls and the
    step's centre side want (each shard's ``syn1`` scatter needs every
    pair's ``h``). The TPU analogue of the servers each answering a pull
    with their slice (SURVEY.md §2.2 pull). With several shards the
    step's pair side, whose rows only ever make a pair's scalars and its
    ``d_center``, stops at :func:`_own_rows` and sums those instead.

    Opens its own scopes, so callers keep it OUT of theirs (a device trace
    files an op under its outermost ``glint.`` scope): the own-row gather
    and its mask under ``glint.gather``, the all-reduce, which is the only
    part that crosses chips, under its sibling ``glint.exchange``.
    """
    return _exchange_sum(_own_rows(table_l, idx, start, rows_per_shard, table))


#: Rows one dispatch of ``EmbeddingEngine.assign_extra_rows`` claims.
_EXTRA_ROW_BLOCK = 256

#: A float32 array is tiled (8, 128) on a TPU: its second-minor axis is laid
#: down in blocks of 8. An axis of 5 (the negatives) or 10 (a CBOW bag)
#: beside d therefore pays for 8 or 16, and a reshape that puts it there
#: is a copy of every row (PERF.md, PR 38).
_SUBLANES = 8


def _id_columns(ids):
    """The K columns of batch-major ``ids`` ``(B, ...)``: the ids are
    transposed, never the rows they name."""
    with jax.named_scope("glint.gather"):
        return list(ids.reshape(ids.shape[0], -1).T)


def _pull_blocks(table_l, ids, start, rows_per_shard, table=None):
    """:func:`_pull_rows` for batch-major ``ids`` ``(B, ...)`` with a few
    ids a batch row (a pair's contexts or negatives, a bag's slots): the
    K blocks of ``(B, d)`` rows ``ops/sgns.row_dots`` takes, block k the
    rows of the k-th id of every batch row, each all-reduced over the
    model axis. The ids are transposed, never the rows: the batch axis
    stays beside d."""
    return [_pull_rows(table_l, c, start, rows_per_shard, table)
            for c in _id_columns(ids)]


def _own_blocks(table_l, ids, start, rows_per_shard, table=None):
    """:func:`_pull_blocks` without the all-reduce: this shard's OWN rows
    (:func:`_own_rows`), zeros where another shard holds the row, so
    nothing crosses chips."""
    return [_own_rows(table_l, c, start, rows_per_shard, table)
            for c in _id_columns(ids)]


def _exchange_sum(x):
    """The model-axis all-reduce, under ``glint.exchange``: of pulled
    rows, and of the pair side's logit partials and partial ``d_center``
    (``EmbeddingEngine.packed_exchange_bytes`` counts what it is
    handed)."""
    with jax.named_scope("glint.exchange"):
        return lax.psum(x, MODEL_AXIS)


#: Update slots one trip of the row writer walks. XLA's TPU scatter into a
#: table costs about 100 ns for every slot it is handed, live or dropped,
#: and no promise changes that (PERF.md, PR 26's probes), so the writer
#: hands it the distinct rows a chunk at a time and stops after the last
#: live chunk: half a chunk a table is walked for nothing.
_SCATTER_CHUNK = 4096


def _writer_chunk(n: int) -> int:
    return min(_SCATTER_CHUNK, n)


def _run_ends(sid, n_rows):
    """``(is_start, live_end)`` over sorted keys ``sid``: where a run of
    equal keys starts, and where a run of a key below ``n_rows`` (a row of
    the table) ends. The live ends are the distinct rows a scatter
    writes."""
    change = sid[1:] != sid[:-1]
    one = jnp.ones(1, bool)
    return (
        jnp.concatenate([one, change]),
        jnp.concatenate([change, one]) & (sid < n_rows),
    )


def _sort_slots(key, coefs, hidx):
    """The N update slots ordered by target row, each with its coefficient
    and its source row's index: what both writers of :func:`_scatter_rows`
    start from. The sort is stable, so a run of equal rows keeps the order
    it stood in in the batch; a key past the table (another shard's row, a
    group's or a bag's padding) sorts to the end."""
    return lax.sort(
        (key.astype(jnp.int32), coefs.astype(jnp.float32), hidx), num_keys=1
    )


def _run_totals(sid, coefs, src, hidx, n_rows):
    """Total each run of equal rows once, in float32, for XLA's writer:
    slot k of the sorted slots (:func:`_sort_slots`) adds ``coefs[k] *
    src[hidx[k]]`` to row ``sid[k]``; a row of ``n_rows`` marks a slot
    whose row another shard owns. Returns ``(u, tot, n_u)``: the ``n_u``
    distinct owned rows in rising order with their totals in
    ``tot[:n_u]``, then sentinels (all different, all past ``n_rows``)
    whose ``tot`` rows no writer reads. Both are padded to a whole number
    of writer chunks.

    The payload is formed in sorted order (coefficient and source index
    rode through the sort, the source ROW is gathered after it), never in
    batch order. A run is totalled by a sorted scatter-add into
    consecutive rows of a fresh buffer, a pass over that buffer and 21 ns
    a slot on a TPU (where the slab writer totals the runs itself, in
    VMEM: PERF.md, PR 35): it adds a run in the order it stood in the
    batch, so its error is that of the plain sum, where a difference of
    prefix sums carries the whole prefix's (PERF.md, PR 26: 1.2 against
    3.8e8 units of eps * sum|x|)."""
    n = sid.shape[0]
    chunk = _writer_chunk(n)
    n_pad = -(-n // chunk) * chunk
    rows = coefs[:, None] * src[hidx].astype(jnp.float32)
    is_start, live_end = _run_ends(sid, n_rows)
    slot = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    tot = jnp.zeros((n_pad, rows.shape[1]), jnp.float32).at[slot].add(
        rows, indices_are_sorted=True
    )
    pad = (0, n_pad - n)
    u = lax.sort(jnp.where(
        jnp.pad(live_end, pad), jnp.pad(sid, pad),
        n_rows + jnp.arange(n_pad, dtype=jnp.int32),
    ))
    return u, tot, live_end.sum(dtype=jnp.int32)


def _write_rows(table_l, sid, coefs, src, hidx):
    """XLA's writer of :func:`_scatter_rows`: the runs totalled
    (:func:`_run_totals`), then the distinct rows handed to the scatter a
    chunk at a time, the loop stopping after the last live chunk. The
    scatter is told what is true of its rows (no two alike, strays
    dropped) but not that they are sorted: that flag selects XLA's other
    TPU emitter, which passes over the whole table (9.4 ms at 2M x 300)
    before it adds a slot. Returns ``(table_l, 0)``: it moves no slab."""
    u, tot, n_u = _run_totals(sid, coefs, src, hidx, table_l.shape[0])
    chunk = _writer_chunk(u.shape[0])

    def write(k, t):
        return t.at[lax.dynamic_slice_in_dim(u, k * chunk, chunk)].add(
            lax.dynamic_slice_in_dim(tot, k * chunk, chunk).astype(t.dtype),
            unique_indices=True, mode="drop",
        )

    return lax.fori_loop(0, -(-n_u // chunk), write, table_l), jnp.int32(0)


def _scatter_rows(table_l, idx, coefs, src, hidx, start):
    """Apply global rank-1 updates to the owned slice of a sharded table
    (the servers' half of ``adjust``, SURVEY.md §2.2): slot k adds
    ``coefs[k] * src[hidx[k]]`` to global row ``idx[k]``, where ``start``
    is the global id of local row 0. Updates of rows another shard owns
    are dropped, not walked. Every table dtype takes this one path: the
    slots are sorted by row (:func:`_sort_slots`), each row's run is
    summed once in float32 in the order it stood in the batch, and each
    distinct row is written once, its total rounded once to the table's
    dtype.

    Two writers share the sorted slots and the count of their runs, and
    which one runs follows from what is observed, never from an option:
    where the program is lowered for a TPU and the table's rows can be
    addressed by whole tile rows (``slab_writer.fits``: every table whose
    shard is a multiple of 16 rows), ``ops/slab_writer.py``'s kernel moves
    each touched slab once and totals the runs of its rows while it holds
    it; everywhere else (CPU, GPU) :func:`_write_rows` totals them into a
    buffer and hands XLA's scatter the distinct rows. The two give the
    same table bit for bit.
    Returns ``(table_l, rows written, slabs moved)``, the last 0 from
    XLA's writer."""
    Vs = table_l.shape[0]
    loc = idx - start
    own = (loc >= 0) & (loc < Vs)
    sid, coefs, hidx = _sort_slots(jnp.where(own, loc, Vs), coefs, hidx)
    n_u = _run_ends(sid, Vs)[1].sum(dtype=jnp.int32)
    if slab_writer.fits(table_l.shape, table_l.dtype):
        table_l, moved = lax.platform_dependent(
            table_l, sid, coefs, src, hidx,
            tpu=slab_writer.write, default=_write_rows,
        )
    else:
        table_l, moved = _write_rows(table_l, sid, coefs, src, hidx)
    return table_l, n_u, moved


#: Process-wide memo of the jitted corpus-scan programs, keyed by every
#: engine attribute their closures capture (:meth:`EmbeddingEngine
#:._scan_memo_key`) plus the scan shape. Short-lived engines with
#: identical configuration — test suites, notebooks, repeated small
#: fits — otherwise recompile the identical XLA program per engine
#: (each engine's fresh ``jax.jit`` closures cannot share an in-memory
#: jit cache), and the packed scan's program is the most expensive
#: compile in the repo. Plain python-level reuse of the jit objects:
#: every input that differs between engines (tables, noise tables,
#: corpus buffers, scalars) is a traced ARGUMENT, so a memo hit is the
#: same program by construction. The memo holds each entry's BUILDER
#: engine alive via the jit closures (and with it that engine's
#: current table pair, unless ``destroy()`` ran) — so it is BOUNDED:
#: insertion past ``_SCAN_MEMO_MAX`` evicts the oldest entry, keeping
#: the worst-case retention a fixed number of table pairs instead of
#: one per distinct config ever seen by the process.
_SCAN_MEMO: "dict" = {}
_SCAN_MEMO_MAX = 32


def _scan_memo_put(key, fn):
    while len(_SCAN_MEMO) >= _SCAN_MEMO_MAX:
        _SCAN_MEMO.pop(next(iter(_SCAN_MEMO)))
    _SCAN_MEMO[key] = fn
    return fn


#: Process-wide memo of the QUERY program family (pull, pull_average,
#: norms, multiply, and the per-k top-k / batch-top-k factories),
#: keyed on :meth:`EmbeddingEngine._query_memo_key` — the mesh
#: geometry plus the query-relevant engine attributes ONLY. Unlike the
#: scan memo, training-only attributes (negatives, compute dtype) are
#: deliberately EXCLUDED from the key: two models trained differently
#: but serving the same (V, d) shape share every compiled query program,
#: because tables and norms are traced ARGUMENTS to all of them (ISSUE
#: 20 — loading model #2..N of a same-shape catalog triggers zero new
#: XLA compiles). Entries hold only jit closures over specs and scalars,
#: never table buffers.
_QUERY_MEMO: "dict" = {}
_QUERY_MEMO_MAX = 64

#: Process-wide first-seen (geometry, op, shape) set + build counter:
#: the number of REAL XLA query compiles this process has paid. A
#: per-engine ``query_compiles`` tick whose (op, shape) was already
#: seen under the same geometry is a shared-program cache hit, counted
#: on the engine as ``shared_program_hits`` instead.
_QUERY_SHAPES_SEEN: "set" = set()
_QUERY_PROGRAM_BUILDS = [0]


def query_program_builds() -> int:
    """Process-wide count of distinct query (op, shape-bucket) programs
    actually compiled — flat when a same-shape engine joins the warm
    family (the multi-model zero-compile assertion)."""
    return _QUERY_PROGRAM_BUILDS[0]


def _query_memo_put(key, fn):
    while len(_QUERY_MEMO) >= _QUERY_MEMO_MAX:
        _QUERY_MEMO.pop(next(iter(_QUERY_MEMO)))
    _QUERY_MEMO[key] = fn
    return fn

#: Floor of the top-k k-bucket family. Requested k is rounded up to
#: ``max(next_pow2(k), TOPK_MIN_K_BUCKET)`` (capped at padded_vocab) and
#: the result truncated to k, so every small-k request — num defaults,
#: analogy exclusion fudge, coalesced maxima — lands on ONE compiled
#: program instead of one per distinct k. Top-16 vs top-2 on device is
#: free; a serving-path recompile is seconds of tail latency.
TOPK_MIN_K_BUCKET = 16

#: Floor of the batched top-k Q-bucket family for Q > 1. Batches of
#: 2..7 queries pad to 8 rows: skinny (Q=2..4)-row gemms fall off the
#: fast blocked path on some backends (XLA CPU runs them ~6x SLOWER
#: than the same scoring at Q=8), and matmul units pad small batches
#: internally anyway. Q=1 keeps its own bucket — the dominant
#: low-concurrency shape, served by the bandwidth-bound matvec.
TOPK_MIN_Q_BUCKET = 8

#: Per-dispatch query cap of the approximate top-k path: the rerank
#: gathers (Q, nprobe * slots, d) rows, so Q is chunked to bound the
#: transient at ~tens of MB regardless of the serving coalescer's
#: max_batch. Buckets {1, 8, 16} cover every chunk.
ANN_MAX_Q = 16


def _lane_weighted(weights, k: int, x):
    """``x`` as lane k carries it: times row k of the position table
    ``weights (L, d)``, or as it is where there is none."""
    return x if weights is None else weights[k] * x


def _bag_sums(lanes, rows, weights=None):
    """``(Bl, ...)``: row t the masked sum of a bag's lanes over composed
    ``rows (Bl + reach, ...)``. ``lanes`` is ``(starts, lmask (Bl, L))``:
    lane k of batch row t reads ``rows[t + starts[k]]`` where
    ``lmask[t, k]``. Batch rows are consecutive positions, so a lane is
    the whole array shifted: L masked adds of slices, no gather.
    ``weights (L, d)``, the position table, multiplies lane k's slice by
    its row k, column by column."""
    starts, lmask = lanes
    Bl = lmask.shape[0]
    return sum(
        lmask[:, k, None] * _lane_weighted(weights, k, rows[a:a + Bl])
        for k, a in enumerate(starts)
    )


def _bag_spread(lanes, e, n_rows: int, weights=None):
    """The transpose of :func:`_bag_sums`: ``(n_rows, d)``, row p the sum
    of ``e[t]`` (times lane k's row of ``weights``) over the batch rows t
    whose bag holds p (in lane k)."""
    starts, lmask = lanes
    Bl = e.shape[0]
    return sum(
        jnp.pad(
            lmask[:, k, None] * _lane_weighted(weights, k, e),
            ((a, n_rows - Bl - a), (0, 0)),
        )
        for k, a in enumerate(starts)
    )


def _lane_grads(lanes, rows, e):
    """The position table's gradient, ``(L, d)``: row k the sum over the
    batch rows t whose bag holds lane k of (the composed word the lane
    reads x ``e[t]``), column by column, and ``(L, 1)``, how many batch
    rows that is. Dense: L reductions over the batch, no scatter."""
    starts, lmask = lanes
    Bl = e.shape[0]
    return jnp.stack([
        (lmask[:, k, None] * (rows[a:a + Bl] * e)).sum(axis=0)
        for k, a in enumerate(starts)
    ]), lmask.sum(axis=0)[:, None]


def _pair_payload(ctx_g, negs_g, cpos_g, cneg_g, h_g):
    """``(ids, coefs, src, hidx)`` of the per-pair syn1 update for
    :func:`_scatter_rows`: slot order [contexts.flat | negs.flat]
    (rank-major batch axis), each slot its coefficient times its
    centre's row of ``h_g``."""
    B, C = cpos_g.shape
    n = cneg_g.shape[-1]
    rows = jnp.arange(B, dtype=jnp.int32)
    return (
        jnp.concatenate([ctx_g.reshape(-1), negs_g.reshape(-1)]),
        jnp.concatenate([cpos_g.reshape(-1), cneg_g.reshape(-1)]),
        h_g,
        jnp.concatenate([jnp.repeat(rows, C), jnp.repeat(rows, C * n)]),
    )


def _pool_payload(ctx_ids, pool, cpos_g, h_g, d_pool):
    """The same for the shared-pool step: the positives as in
    :func:`_pair_payload`, then the pool's rows, whose dense update
    ``d_pool`` rides behind ``h_g`` in the source with coefficient one."""
    B, C = cpos_g.shape
    S = pool.shape[0]
    return (
        jnp.concatenate([ctx_ids, pool]),
        jnp.concatenate([cpos_g.reshape(-1), jnp.ones(S, jnp.float32)]),
        jnp.concatenate([h_g, d_pool]),
        jnp.concatenate([
            jnp.repeat(jnp.arange(B, dtype=jnp.int32), C),
            B + jnp.arange(S, dtype=jnp.int32),
        ]),
    )


class EmbeddingEngine:
    """Owns the sharded syn0/syn1 tables and all device-side ops.

    Args:
      mesh: a ("data", "model") mesh from parallel.mesh.make_mesh.
      vocab_size: unpadded vocabulary size.
      dim: embedding dimension (reference ``vectorSize``; ``matrix.cols``).
      counts: per-word corpus counts driving the noise distribution
        (the broadcast ``bcVocabCns`` the servers build their unigram table
        from, mllib:355; SURVEY.md §2.2 Word2VecArguments).
      num_negatives / unigram_power / unigram_table_size: noise geometry.
      seed: table-init seed.
      dtype: table dtype (float32 | bfloat16); compute is always float32.
    """

    def __init__(
        self,
        mesh,
        vocab_size: int,
        dim: int,
        counts: np.ndarray,
        *,
        num_negatives: int = 5,
        unigram_power: float = 0.75,
        unigram_table_size: Optional[int] = None,
        seed: int = 1,
        dtype: str = "float32",
        extra_rows: int = 0,
        shared_negatives: int = 0,
        compute_dtype: Optional[str] = None,
        architecture: str = "skipgram",
        position_lanes: int = 0,
    ):
        """``architecture`` is the model's (``Word2VecParams.architecture``):
        a ``"cbow"`` engine trains through :meth:`train_steps_corpus_packed`
        alone, whose scan then forms bags (``make_packed_corpus_scan``): of
        words or, while the engine holds a group table, of their groups.

        ``position_lanes`` > 0 (a CBOW engine's alone, ``2 * window`` of
        the fit it trains) gives the engine a THIRD table, ``posw``
        ``(position_lanes, d)`` float32: row k the vector that multiplies,
        column by column, the word a bag reads in lane k before the bag is
        summed (arXiv:1712.09405 section 2.2), its rows in the order of
        ``ops/device_batching.bag_lanes``. Dense, replicated on every
        device of the mesh, started at ones and trained by every step of
        the bag scan, a row by the mean of its gradient over the step's
        positions that hold its lane (``step_body_rows``). It is a member
        of :attr:`table_names`, so whatever walks the tables (save, load,
        checkpoints, donation, byte counts) walks it; 0 and the engine
        holds, carries and allocates nothing of it.

        ``extra_rows`` appends non-vocabulary rows to both tables (e.g.
        fastText char-ngram buckets, models/fasttext.py): they are trained
        through subword center groups but are never negative-sampled (the
        noise table spans the vocab only) and never surface from the query
        ops (top-k masks them; norms/multiply callers slice).

        The tables rest split by ROWS over the model axis, 1/n of the rows
        a shard, each row in whole 128-column lanes (``TABLE_LANES``: d =
        300 rests in 384 columns, so that the device's default layout keeps
        rows contiguous and no program copies a table to reach a few
        rows). A pull sums whole rows over the model axis (``_pull_rows``;
        the step's pair side sums logits and ``d_center``, no ``syn1``
        row); a top-k scores ``(Q, V/n)`` a shard.
        """
        self._configure(
            mesh, vocab_size, dim, num_negatives=num_negatives,
            unigram_power=unigram_power,
            unigram_table_size=unigram_table_size, seed=seed, dtype=dtype,
            extra_rows=extra_rows, shared_negatives=shared_negatives,
            compute_dtype=compute_dtype, architecture=architecture,
            position_lanes=position_lanes,
        )
        if counts.shape != (vocab_size,):
            raise ValueError("counts must have shape (vocab_size,)")

        # Noise distribution over the *unpadded* vocab — draws are therefore
        # identical for every mesh shape (padding never enters sampling),
        # and padded rows can never be drawn as negatives.
        self._counts = np.asarray(counts, dtype=np.int64).copy()
        self._put_alias_table(build_unigram_alias(
            self._counts, power=unigram_power, table_size=unigram_table_size
        ))

        # Initialize tables directly sharded on-device (no host round-trip):
        # syn0 ~ U[-0.5/d, 0.5/d), syn1 = 0 (word2vec standard, ops/sgns.py).
        # Randoms are drawn for the unpadded rows/cols only, then
        # zero-padded, so initial values are mesh-shape-invariant.
        # That rests on partitionable threefry (JAX's default): the legacy
        # lowering produces sharding-DEPENDENT random values when GSPMD
        # partitions the draw.
        tsh = self._table_sharding()
        V, Vp, d, dp = self.num_rows, self.padded_vocab, self.dim, self.padded_dim

        def _init(key):
            s0, s1 = sgns.init_tables(key, V, d, self._dtype)
            pad = ((0, Vp - V), (0, dp - d))
            return jnp.pad(s0, pad), jnp.pad(s1, pad)

        self.syn0, self.syn1 = jax.jit(_init, out_shardings=(tsh, tsh))(
            jax.random.PRNGKey(seed)
        )
        if self.position_lanes:
            self.posw = self._put_table(
                "posw", np.ones((self.position_lanes, d), np.float32)
            )
        self._build_jitted_fns()

    def _put_alias_table(self, table) -> None:
        """Put the noise distribution's alias table on the device,
        replicated, in both its forms: the plain pair ``_prob (V,)
        float32`` / ``_alias (V,) int32``, and ``_alias_packed``, an
        entry's pair kept in one row (ops/sampling.pack_alias_table), which
        is what the step programs draw from: one look-up a draw."""
        repl = NamedSharding(self.mesh, P())
        self._prob = jax.device_put(table.prob, repl)
        self._alias = jax.device_put(table.alias, repl)
        self._alias_packed = jax.device_put(
            pack_alias_table(table.prob, table.alias), repl
        )

    def _configure(
        self, mesh, vocab_size: int, dim: int, *, num_negatives: int,
        unigram_power: float, unigram_table_size: Optional[int], seed: int,
        dtype: str, extra_rows: int, shared_negatives: int,
        compute_dtype: Optional[str], architecture: str = "skipgram",
        position_lanes: int = 0,
    ) -> None:
        """The host-only half of construction: validate, and derive every
        attribute the jitted closures capture (geometry, dtypes, step
        path). Places nothing on a device, so :meth:`_build_jitted_fns`
        can follow it on a mesh of described devices that hold no array
        (tests/test_tpu_compile.py)."""
        if vocab_size <= 0 or dim <= 0:
            raise ValueError("vocab_size and dim must be > 0")
        if extra_rows < 0:
            raise ValueError("extra_rows must be >= 0")
        if shared_negatives < 0:
            raise ValueError("shared_negatives must be >= 0")
        if architecture not in ("skipgram", "cbow"):
            raise ValueError("architecture must be 'skipgram' or 'cbow'")
        if architecture == "cbow" and shared_negatives:
            raise ValueError(
                "architecture='cbow' draws its negatives a position: "
                "shared_negatives must be 0"
            )
        if position_lanes < 0 or position_lanes % 2:
            raise ValueError(
                "position_lanes must be 2 * window of the fit, or 0"
            )
        if position_lanes and architecture != "cbow":
            raise ValueError(
                "position weights multiply the words of a CBOW bag: "
                "position_lanes needs architecture='cbow'"
            )
        self.architecture = architecture
        #: Rows of the position table ``posw``; 0: the engine has none.
        self.position_lanes = int(position_lanes)  # graftlint: ignore[sync-point] host config scalar
        self.posw = None
        self.mesh = mesh
        self.vocab_size = int(vocab_size)
        self._seed = int(seed)  # graftlint: ignore[sync-point] host config scalar
        self.num_rows = int(vocab_size) + int(extra_rows)
        self.dim = int(dim)
        self.num_negatives = int(num_negatives)
        #: Shared-pool size S per step; 0 = per-pair draws (reference
        #: semantics). See ops.sgns.shared_sgns_grads for the estimator.
        self.shared_negatives = int(shared_negatives)
        self.unigram_power = float(unigram_power)
        self.unigram_table_size = unigram_table_size
        self._dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
        # MXU operand dtype for the step's dense contractions (f32 accum
        # either way). Default f32 = exactness-tested reference numerics;
        # "bfloat16" is the MXU-native fast path (GLINT_W2V_MATMUL_DTYPE
        # env overrides when the ctor arg is unset).
        if compute_dtype is None:
            compute_dtype = os.environ.get(
                "GLINT_W2V_MATMUL_DTYPE", "float32"
            )
        self._compute_dtype = (
            jnp.bfloat16 if compute_dtype == "bfloat16" else jnp.float32
        )
        self.num_data = mesh.shape[DATA_AXIS]
        self.num_model = mesh.shape[MODEL_AXIS]
        self.padded_vocab = pad_to_multiple(self.num_rows, self.num_model)
        self.rows_per_shard = self.padded_vocab // self.num_model
        self.padded_dim = pad_to_multiple(self.dim, TABLE_LANES)

    @property
    def step_body(self) -> str:
        """Which step body this engine traces and which writer its
        scatters end in (:func:`_scatter_rows`), recorded in
        ``training_metrics``: ``rows/<per_pair|shared_pool>/<slab|xla>``
        (``rows``: how the tables are split over the model axis; readers
        of saved records expect the three parts). The writer is named by
        the rule :func:`_scatter_rows` applies when the program is lowered
        for this mesh's devices."""
        estimator = "shared_pool" if self.shared_negatives else "per_pair"
        slab = (
            self.mesh.devices.flat[0].platform == "tpu"
            and slab_writer.fits(
                (self.rows_per_shard, self.padded_dim), self._dtype
            )
        )
        return f"rows/{estimator}/{'slab' if slab else 'xla'}"

    def _table_sharding(self):
        return table_sharding(self.mesh)

    # ------------------------------------------------------------------
    # The table set
    # ------------------------------------------------------------------

    @property
    def table_names(self) -> Tuple[str, ...]:
        """The tables this engine owns, by attribute name, in the order
        the step programs take and return them. Everything that saves,
        loads, donates, frees or counts tables walks this."""
        return ("syn0", "syn1") + (("posw",) if self.position_lanes else ())

    def tables(self) -> dict:
        """``{name: live array}`` of :attr:`table_names`."""
        return {name: getattr(self, name) for name in self.table_names}

    def _table_layout(self, name: str):
        """``(rows, padded rows, sharding, dtype)`` of a table as it rests:
        ``syn0`` / ``syn1`` split by rows over the model axis in the
        engine's dtype, ``posw`` whole on every device in float32. All rest
        in ``padded_dim`` columns and speak ``dim``."""
        if name == "posw":
            return (self.position_lanes, self.position_lanes,
                    NamedSharding(self.mesh, P()), jnp.float32)
        return (self.num_rows, self.padded_vocab, self._table_sharding(),
                self._dtype)

    def position_table_stats(self) -> Optional[dict]:
        """``{"rows", "max_abs_dev", "finite"}`` of the position table,
        read back whole (it is a few rows): how far its furthest entry
        lies from the ones it started at, and whether every entry is
        finite. None for an engine without one."""
        if not self.position_lanes:
            return None
        # graftlint: ignore[sync-point] a (2 * window, d) table, at fit end
        d = np.asarray(self.posw, dtype=np.float32)[:, : self.dim]
        finite = bool(np.isfinite(d).all())
        return {
            "rows": self.position_lanes,
            # graftlint: ignore[sync-point] d is the host copy above
            "max_abs_dev": float(np.abs(d - 1.0).max()) if finite else None,
            "finite": finite,
        }

    def _put_table(self, name: str, host: np.ndarray) -> jax.Array:
        """A host table (unpadded: its rows x ``dim``) to the device as
        the table ``name`` rests, the padding zero."""
        rows, padded, sharding, dtype = self._table_layout(name)
        if host.shape != (rows, self.dim):
            raise ValueError(f"{name} shape mismatch")
        # Host array straight to its shards: going through jnp.asarray
        # first would land the whole table on the default device.
        full = np.pad(
            host, ((0, padded - rows), (0, self.padded_dim - self.dim))
        ).astype(np.float32, copy=False)
        return jax.device_put(full.astype(dtype, copy=False), sharding)

    # ------------------------------------------------------------------
    # Jitted SPMD program construction
    # ------------------------------------------------------------------

    def _shard_map(self, f, in_specs, out_specs):
        return shard_map(
            f, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

    def _build_jitted_fns(self) -> None:
        mesh = self.mesh
        Vs = self.rows_per_shard
        n = self.num_negatives
        # ``noise`` in every step program below is the packed alias table
        # (_put_alias_table); its rows do not say where their padding
        # starts, so the programs close over the vocabulary's size.
        V = self.vocab_size
        tspec = P(MODEL_AXIS, None)
        rep = P()

        def step_body_rows(syn0_l, syn1_l, noise, centers, cmask,
                           contexts, mask, key, alpha, pair_run=None,
                           mean_gradient=True, lanes=None, posw=None):
            # Data-sharded inputs: centers/cmask (Rl, S), contexts/mask
            # (Bl, C). S = subword-group width; word-level training is the
            # S=1 specialization. The center representation is the masked
            # mean of its group's syn0 rows (fastText composition; for S=1
            # this is exactly the plain word vector). ``pair_run`` (Bl,),
            # rising, says which group each batch row's centre is (the
            # packed subword scan forms a group once a run of pairs,
            # ops/device_batching.center_runs); None: row i has group i.
            # CBOW is the same step with the roles of the index sets
            # swapped: the one "context" (C = 1) is the position's own
            # word, the hidden vector the mean of its bag, and
            # ``mean_gradient`` False: every row of the bag takes the
            # whole gradient, as word2vec.c adds the undivided neu1e.
            # ``lanes`` (:func:`_bag_sums`) generalises ``pair_run`` from
            # "row i's hidden is group ``pair_run[i]``" to "row t's hidden
            # is the masked SUM of the groups its lanes name over the sum
            # of their counts": the groups are the words of the step's
            # span (a row each, or fastText's group of rows: then one
            # mean over the rows of every word of the bag), each summed
            # once (``group``) and read by every bag it is in (``bag``).
            # The whole gradient goes back the same way. Without
            # ``lanes`` a CBOW group is a position's whole bag (the form
            # ``_train_step`` keeps for the tests).
            # ``posw`` (L, d), with ``lanes`` alone: the position table.
            # Lane k's composed word is multiplied by row k, column by
            # column, before the bag is summed, and takes its gradient
            # through the same row; the row takes (composed word x
            # gradient), from the tables as they stood before the step,
            # as its MEAN over the lane's live positions of the step
            # (``posgrad``): a lane has some 4,400 of them in a batch of
            # 8,192, all pulling one way, and their SUM, which every
            # other row of the step takes, left the table at 2,000 after
            # 64 steps and nothing finite after 96 (PERF.md, PR 54). The
            # new table is then a fifth output.
            Rl, S = centers.shape
            Bl, C = contexts.shape
            # What a grouped centre or a bag adds to the step has a scope
            # of its own; at S = 1 without lanes the mean is the row and
            # stays the gather's.
            compose = (
                "glint.compose" if S > 1 or lanes else "glint.gather"
            )
            start = lax.axis_index(MODEL_AXIS) * Vs
            drank = lax.axis_index(DATA_AXIS)

            # The glint.* scopes (here, in _pull_rows and in the packed
            # scan's body) name the step's five phases and its model-axis
            # exchange in every op's metadata, and nothing else: the
            # device trace is split by them (benchmark/program_trace.py). A fusion is filed under its
            # root's scope, an op under its OUTERMOST one: _pull_rows
            # opens its own two and is called outside any other.
            #
            # Gathered rows keep the batch axis beside d (_pull_blocks). A
            # group of whole tiles (the subword family's 32) is free
            # group-major and is summed over its second-minor axis; any
            # other (one row, a CBOW bag of 10) lies slot-major and is
            # summed over the major one.
            slot_major = S % _SUBLANES != 0
            if slot_major:
                h_rows = _pull_blocks(syn0_l, centers, start, Vs, "syn0")
            else:
                h_rows = _pull_rows(
                    syn0_l, centers.reshape(-1), start, Vs, "syn0"
                )
            # With several model shards the per-pair step sends no syn1
            # row: a row has one owner, whose h . u is the pair's logit
            # (every other shard's term is a product with zeros), so the
            # pair side stops at a shard's own rows and only the logits
            # and d_center cross (below).
            own_pairs = self.num_model > 1 and not self.shared_negatives
            pull_pairs = _own_blocks if own_pairs else _pull_blocks
            u_pos = pull_pairs(syn1_l, contexts, start, Vs, "syn1")
            with jax.named_scope(compose):
                with (jax.named_scope("group") if lanes
                      else contextlib.nullcontext()):
                    cnt = cmask.sum(axis=1, keepdims=True)  # (Rl,1)
                    if lanes is None:
                        cnt = jnp.maximum(cnt, 1.0)
                    if slot_major:
                        h = sgns.row_sums(cmask, h_rows)
                    else:
                        h = (
                            h_rows.reshape(Rl, S, -1) * cmask[..., None]
                        ).sum(axis=1)
                if lanes is not None:
                    with jax.named_scope("bag"):
                        cnt = jnp.maximum(_bag_sums(lanes, cnt), 1.0)
                        composed = h  # (Rl, d): the span's words
                        h = _bag_sums(lanes, h, posw)  # (Bl, d)
                h = h / cnt
                if pair_run is not None:
                    h = h[pair_run]  # (Bl, d)
            with jax.named_scope("glint.gather"):
                # The data-axis exchange ships ONLY h (B, d), scalar
                # gradient coefficients, and int32 indices — the TPU
                # restatement of the reference's defining ship-scalars
                # property (gPlus/gMinus, mllib:422-425). The
                # O(B*C*(1+n)*d) rank-1 payloads are never exchanged: every
                # consuming shard re-forms coef x h outer products locally,
                # where XLA fuses them into the scatter-add.
                h_g = lax.all_gather(h, DATA_AXIS, tiled=True)  # (B, d)

            if self.shared_negatives:
                # Shared-pool mode: ONE pool of P negatives per step,
                # identical on every rank (drawn from the shared key — the
                # mesh-invariance contract needs no slicing here), scored
                # and updated by dense MXU matmuls instead of B*C*n sparse
                # row accesses (ops.sgns.shared_sgns_grads).
                with jax.named_scope("glint.sample"):
                    pool = sample_negatives_packed(
                        key, noise, V, (self.shared_negatives,)
                    )
                u_pool = _pull_rows(syn1_l, pool, start, Vs, "syn1")
                with jax.named_scope("glint.sample"):
                    collide = sgns.pool_collision_mask(pool, contexts, mask)
                with jax.named_scope("glint.grads"):
                    g = sgns.shared_sgns_grads(
                        h, u_pos, u_pool, mask, collide,
                        alpha.astype(jnp.float32), n,
                        compute_dtype=self._compute_dtype,
                    )
                    # The pool update sums contributions from every data
                    # rank; after the psum it is identical everywhere, so
                    # each model shard applies its owned slice exactly once
                    # per replica.
                    d_pool = lax.psum(g.d_pool, DATA_AXIS)
                    ids1 = lax.all_gather(
                        contexts.reshape(-1), DATA_AXIS, tiled=True
                    )
                    cpos_g = lax.all_gather(g.c_pos, DATA_AXIS, tiled=True)
                # The pool's dense update rides as rows of the source with
                # coefficient one, after h_g.
                with (jax.named_scope("glint.scatter"),
                      jax.named_scope("syn1")):
                    scat1 = _pool_payload(ids1, pool, cpos_g, h_g, d_pool)
            else:
                # Per-pair mode (reference semantics): n fresh negatives
                # per (center, context) pair, keyed by GLOBAL row index so
                # draws are mesh-invariant while each rank samples only its
                # own Bl rows (ops.sampling.sample_negatives_per_row).
                with jax.named_scope("glint.sample"):
                    rows_g = drank * Bl + jnp.arange(Bl, dtype=jnp.int32)
                    negs = sample_negatives_per_row_packed(
                        key, noise, V, rows_g, (C, n)
                    )
                u_neg = pull_pairs(syn1_l, negs, start, Vs, "syn1")
                with jax.named_scope("glint.sample"):
                    nmask = sgns.negative_mask(negs, contexts, mask)
                if not own_pairs:
                    with jax.named_scope("glint.grads"):
                        g = sgns.sgns_grads(
                            h, u_pos, u_neg, mask, nmask,
                            alpha.astype(jnp.float32),
                            compute_dtype=self._compute_dtype,
                        )
                else:
                    # The K = C * (1 + n) logit partials cross with the
                    # small axis MAJOR, (K, Bl): a float32 (Bl, K) rests
                    # in 128 lanes a row (PERF.md, PR 38). One shard's
                    # term is not zero, so the sum is the owner's logit to
                    # the bit, and the coefficients and the loss are the
                    # one-shard program's, formed on every shard over the
                    # whole batch. d_center's K terms are summed by owner
                    # first and across the shards second: that order is
                    # what differs from one shard.
                    with jax.named_scope("glint.grads"):
                        f_part = sgns.row_dots(
                            h, u_pos + u_neg, self._compute_dtype
                        ).T
                    f = _exchange_sum(f_part)
                    with jax.named_scope("glint.grads"):
                        f = f.T
                        co = sgns.sgns_coefs(
                            f[:, :C], f[:, C:].reshape(nmask.shape), mask,
                            nmask, alpha.astype(jnp.float32),
                        )
                        d_part = sgns.sgns_d_center(
                            co.c_pos, co.c_neg, u_pos, u_neg,
                            self._compute_dtype,
                        )
                        # The loss's terms are summed as a VECTOR of their
                        # own, a pair's in one, standing in memory: fused
                        # with the logits that lie pairs-minor the chip
                        # reduced them tile by tile of (1, 128), 1.26e-6
                        # off the replay's loss (PERF.md, PR 51; PR 49 met
                        # the same).
                        pair_loss = lax.optimization_barrier(
                            co.pair_loss.sum(axis=1)
                        )
                        loss = pair_loss.sum() / jnp.maximum(mask.sum(), 1.0)
                    g = sgns.SgnsGrads(
                        c_pos=co.c_pos, c_neg=co.c_neg,
                        d_center=_exchange_sum(d_part), loss=loss,
                    )
                with jax.named_scope("glint.grads"):
                    ctx_g = lax.all_gather(contexts, DATA_AXIS, tiled=True)
                    negs_g = lax.all_gather(negs, DATA_AXIS, tiled=True)
                    cpos_g = lax.all_gather(g.c_pos, DATA_AXIS, tiled=True)
                    cneg_g = lax.all_gather(g.c_neg, DATA_AXIS, tiled=True)
                with (jax.named_scope("glint.scatter"),
                      jax.named_scope("syn1")):
                    scat1 = _pair_payload(ctx_g, negs_g, cpos_g, cneg_g, h_g)

            # The center gradient is distributed over the group's rows
            # (d mean / d row = 1/count; undivided for CBOW): ship the
            # (Rl, d) gradient + the (Rl, S) group mask, expand to rows at
            # the consumer. A group that several batch rows share first
            # sums their gradients.
            d_center = g.d_center
            if pair_run is not None:
                with jax.named_scope(compose):
                    d_center = jnp.zeros(
                        (Rl, d_center.shape[1]), jnp.float32
                    ).at[pair_run].add(d_center, indices_are_sorted=True)
            if posw is not None:
                # Whole on every model shard already (the pull and the
                # pair side's exchange both are); the data ranks each
                # hold their own positions' share.
                with jax.named_scope(compose), jax.named_scope("posgrad"):
                    total, positions = lax.psum(
                        _lane_grads(lanes, composed, d_center), DATA_AXIS
                    )
                    posw_new = posw + total / jnp.maximum(positions, 1.0)
            if lanes is not None:
                with jax.named_scope(compose), jax.named_scope("bag"):
                    d_center = _bag_spread(lanes, d_center, Rl, posw)
            with jax.named_scope("glint.grads"):
                dcen_g = lax.all_gather(
                    d_center / cnt if mean_gradient else d_center,
                    DATA_AXIS, tiled=True,
                )
                cmask_g = lax.all_gather(cmask, DATA_AXIS, tiled=True)
                ids0_g = lax.all_gather(
                    centers.reshape(-1), DATA_AXIS, tiled=True
                )
            # The outer products coef x row are formed at the consumer, in
            # the scatter's sorted order (_scatter_rows' writers), never
            # exchanged.
            with jax.named_scope("glint.scatter"):
                with jax.named_scope("syn0"):
                    syn0_l, w0, m0 = _scatter_rows(
                        syn0_l, ids0_g, cmask_g.reshape(-1), dcen_g,
                        jnp.repeat(jnp.arange(dcen_g.shape[0]), S), start,
                    )
                with jax.named_scope("syn1"):
                    syn1_l, w1, m1 = _scatter_rows(syn1_l, *scat1, start)
                    written = lax.psum(
                        jnp.stack([w0, w1, m0, m1]), MODEL_AXIS
                    )

            # Masked-mean loss over the global batch.
            with jax.named_scope("glint.grads"):
                denom = mask.sum()
                loss_sum = g.loss * jnp.maximum(denom, 1.0)
                loss = lax.psum(loss_sum, DATA_AXIS) / jnp.maximum(
                    lax.psum(denom, DATA_AXIS), 1.0
                )
            if posw is not None:
                return syn0_l, syn1_l, loss, written, posw_new
            return syn0_l, syn1_l, loss, written

        # A CBOW engine's one-step program is the role-swapped form: the
        # groups are the positions' bags as ``bag_window_batch`` names
        # them, each row taking the whole gradient. Its fits train through
        # the packed scan alone (``_skipgram_only``); tests/test_cbow.py
        # holds that scan's span form to this one.
        self._train_step = jax.jit(
            self._shard_map(
                lambda *a: step_body_rows(
                    *a, mean_gradient=self.architecture != "cbow"
                )[:3],
                in_specs=(tspec, tspec, rep, P(DATA_AXIS, None),
                          P(DATA_AXIS, None), P(DATA_AXIS, None),
                          P(DATA_AXIS, None), rep, rep),
                out_specs=(tspec, tspec, rep),
            ),
            donate_argnums=(0, 1),
        )

        def local_train_scan(syn0_l, syn1_l, noise, centers_k, cmask_k,
                             contexts_k, mask_k, base_key, step0, alphas_k):
            # K stacked minibatches executed by one on-device lax.scan —
            # one dispatch + one host->device transfer per K steps instead
            # of per step. Per-step keys are fold_in(base_key, step0 + i),
            # the same derivation the single-step caller uses, so a scanned
            # run and a step-at-a-time run of the same schedule draw
            # identical negatives.
            def body(carry, xs):
                s0, s1 = carry
                centers, cmask, contexts, mask, i, alpha = xs
                key = jax.random.fold_in(base_key, step0 + i)
                s0, s1, loss, _ = step_body_rows(
                    s0, s1, noise, centers, cmask, contexts, mask,
                    key, alpha,
                )
                return (s0, s1), loss

            K = alphas_k.shape[0]
            (syn0_l, syn1_l), losses = lax.scan(
                body,
                (syn0_l, syn1_l),
                (centers_k, cmask_k, contexts_k, mask_k,
                 jnp.arange(K, dtype=jnp.uint32), alphas_k),
            )
            return syn0_l, syn1_l, losses

        # jit specializes on the leading scan length K.
        self._train_scan = jax.jit(
            self._shard_map(
                local_train_scan,
                in_specs=(tspec, tspec, rep,
                          P(None, DATA_AXIS, None), P(None, DATA_AXIS, None),
                          P(None, DATA_AXIS, None), P(None, DATA_AXIS, None),
                          rep, rep, rep),
                out_specs=(tspec, tspec, rep),
            ),
            donate_argnums=(0, 1),
        )

        num_data = self.num_data
        self._corpus_scan_cache: dict = {}
        self._ones_mask_cache: dict = {}

        def make_corpus_scan(B: int, W: int, G: int = 0):
            # Corpus-resident scan: batches are assembled ON DEVICE from
            # the uploaded flat corpus (ops/device_batching) — the only
            # per-dispatch host->device traffic is scalars. Step i of the
            # scan covers global center positions
            # [pstart + i*B, pstart + (i+1)*B); this rank materializes
            # only its Bl = B/num_data rows. Keys follow the exact
            # fold_in(base_key, step0 + i) schedule of local_train_scan,
            # so negatives match a host-batched run step for step.
            # ``n_valid`` (the corpus-end bound) is a TRACED scalar so
            # the subsampled path's per-epoch n_kept shares this one
            # compile with the full-corpus path.
            from glint_word2vec_tpu.ops.device_batching import (
                device_window_batch,
            )

            Bl = B // num_data

            # ``G`` > 0 (the subword family): one more replicated
            # argument, the (vocab, G) group table, and each row's centre
            # is its word's group (a row past the corpus end has none).
            def local_corpus_scan(syn0_l, syn1_l, noise, ids, soffs,
                                  n_valid, pstart, base_key, step0,
                                  alphas_k, groups=None):
                drank = lax.axis_index(DATA_AXIS)
                rows_l = (drank * Bl + jnp.arange(Bl)).astype(jnp.int32)

                def body(carry, xs):
                    s0, s1 = carry
                    i, alpha = xs
                    key = jax.random.fold_in(base_key, step0 + i)
                    positions = (
                        pstart + jnp.int32(i) * jnp.int32(B) + rows_l
                    )
                    centers, contexts, mask = device_window_batch(
                        ids, soffs, positions, rows_l, key, W,
                        n_valid=n_valid,
                    )
                    if G:
                        with jax.named_scope("glint.compose"):
                            grp = jnp.where(
                                (positions < n_valid)[:, None],
                                groups[centers], -1,
                            )
                            cmask = (grp >= 0).astype(jnp.float32)
                    else:
                        cmask = jnp.ones((Bl, 1), jnp.float32)
                        grp = centers[:, None]
                    s0, s1, loss, _ = step_body_rows(
                        s0, s1, noise, grp, cmask,
                        contexts, mask, key, alpha,
                    )
                    return (s0, s1), loss

                K = alphas_k.shape[0]
                (syn0_l, syn1_l), losses = lax.scan(
                    body,
                    (syn0_l, syn1_l),
                    (jnp.arange(K, dtype=jnp.uint32), alphas_k),
                )
                return syn0_l, syn1_l, losses

            return jax.jit(
                self._shard_map(
                    local_corpus_scan,
                    in_specs=(tspec, tspec) + (rep,) * (9 if G else 8),
                    out_specs=(tspec, tspec, rep),
                ),
                donate_argnums=(0, 1),
            )

        self._make_corpus_scan = make_corpus_scan
        self._packed_scan_cache: dict = {}

        def make_packed_corpus_scan(P: int, W: int, B_grid: int, S: int,
                                    K: int, G: int = 0):
            # PACKED corpus-resident scan (ISSUE 4): instead of a (B, C)
            # context grid that is ~57% masked lanes, each step assembles
            # windows over an oversized candidate span of center
            # positions, prefix-sum-compacts the valid (center, context)
            # pairs into a DENSE (P,) pair list
            # (ops/device_batching.pack_window_pairs), and runs the
            # step body in its pair form — batch rows ARE pairs (C=1), so
            # no contraction lane is masked padding. The position counter
            # advances data-dependently by whole consumed positions and is
            # carried through the scan; the LR alpha is derived on device
            # from the traced consumed-position count via the same
            # pre-subsampling words_done rule the host uses
            # (device_words_done == corpus_words_done_compacted). The
            # assembly is computed replicated on every rank (it is a
            # deterministic function of replicated inputs — mesh-invariant
            # by construction); each data rank then slices its own
            # Pl = P/num_data pair rows, and negatives are keyed by GLOBAL
            # pair row exactly like every other path
            # (sample_negatives_per_row discipline). Window-shrink draws
            # reproduce the grid scan's position->draw mapping
            # (grid_window_shrink), so the packed stream trains the exact
            # same valid-pair multiset as the grid path at the same
            # (B_grid, key schedule) — the parity gate that keeps "grid"
            # the default until it holds.
            #
            # ``G`` > 0 is the subword family (models/fasttext.py): the
            # scan takes one more replicated argument, the (vocab, G)
            # group table (:meth:`upload_center_groups`), draws its batch
            # exactly as above, and forms each centre from its group's
            # rows, once a run of pairs with the same centre
            # (ops/device_batching.center_runs), never once a pair. The
            # centre side is a function of the drawn centre ids and the
            # table alone. ``written`` then carries two more counts: live
            # group ids and the centres they formed.
            #
            # A CBOW engine (``architecture``) gets the position-major
            # scan below instead: ``P`` is then the positions of a step.
            from glint_word2vec_tpu.ops.device_batching import (
                bag_lanes,
                bag_span_batch,
                center_runs,
                device_words_done,
                pack_window_pairs,
            )

            Pl = P // num_data
            R = min(Pl, S + 1)  # runs of one rank's pair list

            def alpha_at(pos_end, orig_offs, soffs, n_valid, step_size,
                         inv_total_words, words_base):
                # The LR after the positions before ``pos_end``: the
                # host's pre-subsampling words_done rule, on the device.
                done = device_words_done(orig_offs, soffs, pos_end, n_valid)
                wd = words_base + done.astype(jnp.float32)
                return jnp.maximum(
                    step_size * (1.0 - wd * inv_total_words),
                    step_size * 1e-4,
                )

            def steps_to_corpus_end(body, tables, pstart, n_valid, counts):
                # Steps 0..K-1 of ``body``, as ``lax.scan`` would run
                # them, but only those that START inside the view
                # (``pos < n_valid``): a step past the corpus end trains
                # nothing and costs the device what a live one does, and
                # an epoch ends with most of a group of them and, under
                # the deferred schedule, one whole phantom group. The
                # condition reads replicated values alone, so every chip
                # makes the same trips around the step's all-reduces.
                # What the loop did not reach reads as the host's
                # accounting expects of a step that consumed nothing:
                # ``pos_ends`` the final position, every count and loss
                # 0, and alpha 0, which no step that ran writes (the
                # rule's floor is ``step_size * 1e-4``): the host counts
                # the steps the device ran from it. ``counts`` is the
                # width of the body's fifth output. The carry is the
                # ``tables`` (two, or three with ``posw``) and the
                # position; they come back first, in their order.
                bufs = (
                    jnp.zeros(K, jnp.float32), jnp.zeros(K, jnp.int32),
                    jnp.zeros(K, jnp.int32), jnp.zeros(K, jnp.float32),
                    jnp.zeros((K, counts), jnp.int32),
                )

                def live(state):
                    i, (*_, pos), _ = state
                    return (i < K) & (pos < n_valid)

                def step(state):
                    i, carry, bufs = state
                    carry, ys = body(carry, i)
                    return i + 1, carry, tuple(
                        lax.dynamic_update_index_in_dim(b, y, i, 0)
                        for b, y in zip(bufs, ys)
                    )

                ran, (*tables, pos), bufs = lax.while_loop(
                    live, step, (jnp.uint32(0), (*tables, pstart), bufs),
                )
                losses, n_pairs, pos_ends, alphas, written = bufs
                pos_ends = jnp.where(
                    jnp.arange(K, dtype=jnp.uint32) < ran, pos_ends, pos
                )
                return (*tables, losses, n_pairs, pos_ends, alphas, written)

            def local_bag_packed_scan(syn0_l, syn1_l, *rest):
                # ``rest``: the position table where the engine has one
                # (third, beside the tables it is donated with), then
                # ``local_packed_scan``'s arguments from ``noise`` on.
                posw = rest[:1] if self.position_lanes else ()
                (noise, ids, sent_of, soffs, orig_offs, n_valid, pstart,
                 base_key, step0, grid_step0, step_size, inv_total_words,
                 words_base, *groups) = rest[len(posw):]
                groups = groups[0] if groups else None
                # CBOW: step i trains the P consecutive positions from
                # ``pos``, each rank its own Pl of them, and the roles of
                # the two tables' index sets are swapped: a position's own
                # word is the step body's one context, its negatives are
                # drawn a position, keyed by its global row, and its
                # hidden vector is the mean of its bag. The bags of
                # consecutive positions draw on the words of ONE span,
                # ``Pl + 2 * W`` of them (``bag_span_batch``): the step
                # body's groups are the span's words, each gathered once,
                # and a bag is ``lanes`` into them (:func:`_bag_sums`), so
                # a word is shared by the bags it is in and takes its
                # gradient summed over them as one slot of the scatter. A
                # word is its group's rows where the engine holds a group
                # table (``G`` > 0: fastText's CBOW, one mean over all of
                # a bag's rows) and its own row where it holds none.
                # Nothing is compacted, the advance is the static P, and
                # the same key schedule, shrink draws and alpha rule hold
                # as in the pair scan below. The per-step outputs are
                # that scan's: ``n_pairs`` reads the live bag slots, and
                # ``written`` carries them again with the positions
                # trained; with a group table then the live group ids
                # gathered, the span words composed, and the input rows:
                # the rows a bag's mean is over, summed over the positions.
                # ``posw`` is carried and trained beside the two tables:
                # lane k of ``starts`` reads its row k.
                drank = lax.axis_index(DATA_AXIS)
                starts = [W + o for o in bag_lanes(W)]

                def body(carry, i):
                    s0, s1, *pw, pos = carry
                    with jax.named_scope("glint.batch"):
                        key = jax.random.fold_in(base_key, step0 + i)
                        c_l, span, lmask, live = bag_span_batch(
                            ids, sent_of, pos + drank * Pl, base_key,
                            grid_step0, window=W, batch=Pl,
                            grid_batch=B_grid, n_valid=n_valid,
                        )
                        lanes = (starts, lmask)
                        pos_end = pos + P
                        alpha = alpha_at(
                            pos_end, orig_offs, soffs, n_valid, step_size,
                            inv_total_words, words_base,
                        )
                        formed = lax.psum(
                            jnp.stack([
                                lmask.sum(dtype=jnp.int32),
                                live.sum(dtype=jnp.int32),
                            ]), DATA_AXIS,
                        )
                    if not G:
                        with jax.named_scope("glint.batch"):
                            # A span word no bag reads (a sentence's edge
                            # under a short shrink) is no row of the step.
                            read = _bag_spread(
                                lanes, jnp.ones((Pl, 1), jnp.float32),
                                Pl + 2 * W,
                            ) > 0
                            words = jnp.where(read, span[:, None], -1)
                            cmask = (words >= 0).astype(jnp.float32)
                    else:
                        with jax.named_scope("glint.compose"):
                            with jax.named_scope("group"):
                                composed = span >= 0
                                words = jnp.where(
                                    composed[:, None], groups[span], -1
                                )
                                cmask = (words >= 0).astype(jnp.float32)
                            with jax.named_scope("bag"):
                                input_rows = _bag_sums(
                                    lanes, cmask.sum(axis=1, keepdims=True)
                                ).sum()
                            formed = jnp.concatenate([formed, lax.psum(
                                jnp.stack([
                                    cmask.sum(dtype=jnp.int32),
                                    composed.sum(dtype=jnp.int32),
                                    input_rows.astype(jnp.int32),
                                ]), DATA_AXIS,
                            )])
                    s0, s1, loss, written, *pw = step_body_rows(
                        s0, s1, noise, words, cmask,
                        c_l[:, None], live[:, None], key, alpha,
                        mean_gradient=False, lanes=lanes,
                        posw=pw[0] if pw else None,
                    )
                    return (s0, s1, *pw, pos_end), (
                        loss, formed[0], pos_end, alpha,
                        jnp.concatenate([written, formed]),
                    )

                return steps_to_corpus_end(
                    body, (syn0_l, syn1_l, *posw), pstart, n_valid,
                    9 if G else 6,
                )

            def local_packed_scan(syn0_l, syn1_l, noise, ids, sent_of, soffs,
                                  orig_offs, n_valid, pstart, base_key,
                                  step0, grid_step0, step_size,
                                  inv_total_words, words_base, groups=None):
                drank = lax.axis_index(DATA_AXIS)

                def body(carry, i):
                    s0, s1, pos = carry
                    with jax.named_scope("glint.batch"):
                        key = jax.random.fold_in(base_key, step0 + i)
                        pc, px, pm, n_cons, n_pairs = pack_window_pairs(
                            ids, soffs, pos, base_key, grid_step0,
                            window=W, span=S, pair_batch=P,
                            grid_batch=B_grid, n_valid=n_valid,
                            sent_of=sent_of,
                        )
                        pos_end = pos + n_cons
                        alpha = alpha_at(
                            pos_end, orig_offs, soffs, n_valid, step_size,
                            inv_total_words, words_base,
                        )
                        c_l = lax.dynamic_slice_in_dim(pc, drank * Pl, Pl)
                        x_l = lax.dynamic_slice_in_dim(px, drank * Pl, Pl)
                        m_l = lax.dynamic_slice_in_dim(pm, drank * Pl, Pl)
                        if not G:
                            cmask = jnp.ones((Pl, 1), jnp.float32)
                    if G:
                        with jax.named_scope("glint.compose"):
                            run_c, pair_run, live = center_runs(c_l, m_l, R)
                            grp = jnp.where(
                                live[:, None], groups[run_c], -1
                            )
                            cmask = (grp >= 0).astype(jnp.float32)
                            formed = lax.psum(
                                jnp.stack([
                                    (grp >= 0).sum(dtype=jnp.int32),
                                    live.sum(dtype=jnp.int32),
                                ]), DATA_AXIS,
                            )
                    else:
                        grp, pair_run = c_l[:, None], None
                    s0, s1, loss, written = step_body_rows(
                        s0, s1, noise, grp, cmask,
                        x_l[:, None], m_l[:, None], key, alpha,
                        pair_run=pair_run,
                    )
                    if G:
                        written = jnp.concatenate([written, formed])
                    return (s0, s1, pos_end), (
                        loss, n_pairs, pos_end, alpha, written
                    )

                return steps_to_corpus_end(
                    body, (syn0_l, syn1_l), pstart, n_valid, 6 if G else 4
                )

            if self.position_lanes not in (0, 2 * W):
                raise ValueError(
                    f"the engine's position table has "
                    f"{self.position_lanes} rows: it trains window "
                    f"{self.position_lanes // 2}, not {W}"
                )
            # The tables lead the arguments and the results, and are
            # donated; every other table than syn0 and syn1 is replicated.
            others = (rep,) * (len(self.table_names) - 2)
            return jax.jit(
                self._shard_map(
                    local_bag_packed_scan if self.architecture == "cbow"
                    else local_packed_scan,
                    in_specs=(tspec, tspec) + others
                    + (rep,) * (14 if G else 13),
                    out_specs=(tspec, tspec) + others + (rep,) * 5,
                ),
                donate_argnums=tuple(range(len(self.table_names))),
            )

        self._make_packed_corpus_scan = make_packed_corpus_scan

        dim_real = self.dim

        def shared_query_program(op, build):
            """Process-level program sharing (ISSUE 20): same-geometry
            engines reuse one jitted callable — and with it one XLA
            compile cache — because tables/norms/scalars are all traced
            arguments. The closure the memo retains captures only specs
            and host scalars, never device buffers."""
            key = self._query_memo_key(op)
            fn = _QUERY_MEMO.get(key)
            if fn is None:
                fn = _query_memo_put(key, build())
            return fn

        def local_pull(table_l, idx):
            start = lax.axis_index(MODEL_AXIS) * Vs
            return _pull_rows(table_l, idx, start, Vs)[:, :dim_real]

        self._pull = shared_query_program("pull", lambda: jax.jit(
            self._shard_map(local_pull, in_specs=(tspec, rep), out_specs=rep)
        ))

        def local_pull_average(table_l, idx, m):
            # idx/m: (S, L) padded sentence word-indices + validity mask.
            S, L = idx.shape
            start = lax.axis_index(MODEL_AXIS) * Vs
            rows = _pull_rows(table_l, idx.reshape(-1), start, Vs)
            rows = rows[:, :dim_real].reshape(S, L, -1) * m[..., None]
            return rows.sum(axis=1) / jnp.maximum(
                m.sum(axis=1)[:, None], 1.0
            )

        self._pull_average = shared_query_program(
            "pull_average", lambda: jax.jit(
                self._shard_map(
                    local_pull_average, in_specs=(tspec, rep, rep),
                    out_specs=rep,
                )
            )
        )

        def local_norms(table_l):
            # Shard-local, no communication: output stays model-sharded.
            return jnp.sqrt(
                (table_l.astype(jnp.float32) ** 2).sum(axis=1)
            )

        self._norms = shared_query_program("norms", lambda: jax.jit(
            self._shard_map(
                local_norms, in_specs=(tspec,), out_specs=P(MODEL_AXIS),
            )
        ))

        def local_multiply(table_l, v):
            # Distributed matvec: each shard scores its own rows (the TP
            # matvec noted in SURVEY.md §2.3); output model-sharded.
            return _score(table_l, v)

        self._multiply = shared_query_program("multiply", lambda: jax.jit(
            self._shard_map(
                local_multiply, in_specs=(tspec, rep),
                out_specs=P(MODEL_AXIS),
            )
        ))

        def make_topk(k: int):
            def local_topk(table_l, v, norms_l, nq):
                # Cosine top-k without materializing all V scores on one
                # device: local top-k per shard, all_gather the M*k
                # candidates, merge. Replaces the reference's full-vocab
                # driver-side scan (mllib:601-617).
                start = lax.axis_index(MODEL_AXIS) * Vs
                val, idx = _shard_topk(
                    table_l, v, norms_l, nq, start, min(k, Vs)
                )
                return _merge_topk(val, idx, start, k)

            return jax.jit(
                self._shard_map(
                    local_topk,
                    in_specs=(tspec, rep, P(MODEL_AXIS), rep),
                    out_specs=(rep, rep),
                )
            )

        def make_topk_batch(k: int):
            def local_topk_batch(table_l, q, ids, norms_l, nq):
                # q: (Q, padded_dim) replicated query batch, ids: (Q,).
                # A query whose id is a row of the table is gathered here,
                # at the table's resting width, and divided by its norm as
                # the host divides the vectors it sends (a zero row stays
                # zero): a served round's rows never visit the host. An
                # id of -1 takes its row of ``q``. Then the same
                # candidate-merge scheme as the single-vector kernel,
                # vectorized over Q — one matmul scores all queries
                # against this shard.
                start = lax.axis_index(MODEL_AXIS) * Vs
                rows = _pull_rows(table_l, ids, start, Vs)
                with jax.named_scope("glint.query"):
                    nrm = jnp.sqrt((rows * rows).sum(axis=1, keepdims=True))
                    rows = rows / jnp.where(nrm > 0, nrm, 1.0)
                    q = jnp.where((ids >= 0)[:, None], rows, q)
                val, idx = _shard_topk(
                    table_l, q, norms_l, nq, start, min(k, Vs)
                )
                return _merge_topk(val, idx, start, k)

            return jax.jit(
                self._shard_map(
                    local_topk_batch,
                    in_specs=(tspec, rep, rep, P(MODEL_AXIS), rep),
                    out_specs=(rep, rep),
                )
            )

        self._topk_cache: dict = {}
        self._topk_batch_cache: dict = {}
        #: Q bucket -> the zero query block of a batch that is all ids
        #: (:meth:`_query_block`): put on the device once.
        self._zero_queries: dict = {}
        # The per-k factories consult the process memo first: a
        # same-geometry engine's k-bucket family is the SAME jitted
        # callable (tables/norms/queryable are traced arguments), so a
        # second same-shape model inherits every warmed top-k program.
        self._make_topk = lambda k: shared_query_program(
            # graftlint: ignore[sync-point] k is a host int bucket key
            ("topk", int(k)), lambda: make_topk(int(k))
        )
        self._make_topk_batch = lambda k: shared_query_program(
            # graftlint: ignore[sync-point] k is a host int bucket key
            ("topk_batch", int(k)), lambda: make_topk_batch(int(k))
        )
        # Query-shape compile accounting: every distinct (op, shape
        # bucket) a query op dispatches is one XLA compile (jit
        # specializes on shape). The serving layer pads its dispatches
        # to power-of-two buckets, so post-warmup this set stops
        # growing — the /metrics zero-compile contract (ISSUE 2).
        self._query_shapes: set = set()
        self.query_compiles: int = 0
        #: Query programs this engine has launched (every dispatch of a
        #: query op counts its shape once): what a served round reads
        #: before and after itself for its span's ``programs``.
        self.query_dispatches: int = 0
        #: ``perf_counter()`` at which the last batch top-k's launch
        #: returned: where a served round's read-back begins
        #: (``req.readback`` of the requests it carried).
        self.query_enqueued_at: float = 0.0
        #: First-seen shapes on THIS engine whose program was already
        #: compiled process-wide by a same-geometry engine (the shared
        #: warm family, ISSUE 20): a ``query_compiles`` tick that cost
        #: zero XLA work.
        self.shared_program_hits: int = 0
        # Lazy norms cache, invalidated by any table mutation — the engine-
        # side analogue of the reference's cached ``wordVecNorms``
        # (mllib:486). ``table_version`` ticks on the same mutations so
        # layers above (the serving result cache) can validate anything
        # derived from table values without holding device buffers.
        self._norms_cache = None
        self.table_version = 0
        #: Device-resident coarse index for approximate top-k (ISSUE
        #: 12): built via configure_ann()+ann_build(), flipped live by
        #: adopt_ann() — None keeps every query exact.
        self._ann = None
        self._ann_conf = None
        #: Spare extra rows claimed for runtime vocabulary growth
        #: (ISSUE 10 streaming): rows [vocab_size, vocab_size +
        #: extra_rows_assigned) hold words assigned online via
        #: :meth:`assign_extra_row` and ARE queryable (the top-k mask
        #: bound is the traced ``queryable_rows`` scalar, so growth
        #: never recompiles a warmed program). FastText bucket rows are
        #: NOT assigned this way and stay masked.
        self.extra_rows_assigned = 0
        # Non-blocking checkpoint machinery (ISSUE 5): the single
        # background writer (lazily created by save_async) and the
        # commit telemetry the heartbeat surfaces.
        self._ckpt_writer = None
        self._ckpt_last_commit: Optional[float] = None
        self._ckpt_last_write_s: Optional[float] = None
        self._ckpt_forced_sync = 0
        # Pre-dispatched next-epoch subsample-compact pass (ISSUE 5
        # prefetch overlap): (epoch_key host copy, ids_c, offsets_c,
        # n_kept, sent_c) awaiting adoption by compact_corpus.
        self._compact_prefetch = None
        # Touched-row replica-exchange telemetry (ISSUE 15,
        # parallel/exchange.py): per-engine counters surfaced on the
        # heartbeat and summed into the gang rollup.
        self._exchange_stats = {
            "exchange_bytes_total": 0,
            "exchange_rows_total": 0,
            "exchange_overflow_total": 0,
            "exchange_syncs_total": 0,
            "exchange_dense_syncs_total": 0,
            "exchange_last_seconds": None,
            # ISSUE 16 wire-layer telemetry: payload bytes by wire
            # encoding (dense/spill/flush rounds count as fp32 — that
            # is what they ship), dispatch groups folded into rounds
            # by coalescing, checkpoint flush rounds, world=1 skipped
            # rounds, per-hop byte split for the two-level topology,
            # the live capacity gauge with its adaptation counters,
            # and the error-feedback residual high-water gauge.
            "exchange_bytes_wire_fp32_total": 0,
            "exchange_bytes_wire_bf16_total": 0,
            "exchange_bytes_wire_int8_total": 0,
            "exchange_groups_total": 0,
            "exchange_flushes_total": 0,
            "exchange_world1_skips_total": 0,
            "exchange_intra_bytes_total": 0,
            "exchange_inter_bytes_total": 0,
            "exchange_capacity": None,
            "exchange_capacity_grows_total": 0,
            "exchange_capacity_shrinks_total": 0,
            "exchange_residual_abs": 0.0,
        }
        # Per-shard checkpoint bookkeeping (ISSUE 15): which shard
        # files are dirty since the last committed save (None = all —
        # the safe default every generic table mutation restores; the
        # exchange apply narrows it to the rows a round touched), the
        # path those clean bits describe, and the skip/streaming
        # telemetry checkpoint_stats surfaces.
        self._shard_dirty = None
        self._shard_clean_path = None
        self._ckpt_shards_skipped = 0
        self._ckpt_shard_write_s: Optional[float] = None
        self._ckpt_shard_verify_s: Optional[float] = None
        self._ckpt_peak_block_bytes = 0
        self._stage_peak_block_bytes = 0
        # Replica save split (rank, world): under replica-exchange
        # training every rank holds the FULL reconciled table; the
        # sharded save then splits rows into ``world`` blocks and each
        # rank writes only its own — rank-parallel checkpoint I/O with
        # per-shard manifests, no gather anywhere. None = mesh-derived
        # shard files (the SPMD path).
        self._save_split = None

    # ------------------------------------------------------------------
    # Training
    # ------------------------------------------------------------------

    def _skipgram_only(self, entry: str) -> None:
        """The host-batch entries and the grid corpus scan train skip-gram
        pairs; a CBOW engine trains through the packed corpus scan alone."""
        if self.architecture != "skipgram":
            raise ValueError(
                f"{entry} trains skip-gram batches; an engine with "
                f"architecture={self.architecture!r} trains through "
                "train_steps_corpus_packed (the corpus-resident path) only"
            )

    def train_step(self, centers, contexts, mask, key, alpha) -> float:
        """One synchronous SGNS minibatch update; returns the batch loss.

        The fused equivalent of one ``dotprod`` -> gradient-scale ->
        ``adjust`` round trip (mllib:421-425). Batch rows must be divisible
        by the data-axis size. Inputs may be host (numpy) or device-resident
        (jax) arrays; device arrays are used in place — no host bounce.
        """
        centers = _host_or_device(centers)
        B = centers.shape[0]
        # Same device-resident cached mask trick as train_steps: never
        # re-upload a constant per call (multi-host wants host arrays).
        if jax.process_count() > 1:
            gm = np.ones((B, 1), dtype=np.float32)
        else:
            if (B,) not in self._ones_mask_cache:
                self._ones_mask_cache[(B,)] = jnp.ones((B, 1), jnp.float32)
            gm = self._ones_mask_cache[(B,)]
        return self.train_step_grouped(
            centers[:, None], gm, contexts, mask, key, alpha,
        )

    def _device_batch(self, *arrays, data_axis: int):
        """Place batch arrays on the mesh. Single-process: plain
        ``jnp.asarray`` (a no-op for already-device-resident inputs; jit
        shards them). Multi-host: each process passes only ITS data-axis
        rows as HOST arrays; the global batch is assembled with every
        shard staying on the host that produced it
        (distributed.make_global_batch — the Spark partition-locality
        analogue, mllib:345)."""
        if jax.process_count() > 1:
            from glint_word2vec_tpu.parallel.distributed import (
                make_global_batch,
            )

            return make_global_batch(
                self.mesh, *(np.asarray(a) for a in arrays),
                data_axis=data_axis,
            )
        return tuple(jnp.asarray(a) for a in arrays)

    def train_step_grouped(
        self, center_groups, group_mask, contexts, mask, key, alpha
    ) -> float:
        """SGNS update with grouped centers: each center is the masked mean
        of its group's syn0 rows (fastText subword composition; the center
        gradient splits 1/count over the group's rows). Word-level training
        is the width-1 special case used by :meth:`train_step`."""
        self._skipgram_only("train_step_grouped")
        cg, gm, cx, mk = self._device_batch(
            _host_or_device(center_groups),
            _host_or_device(group_mask, jnp.float32),
            _host_or_device(contexts),
            _host_or_device(mask, jnp.float32),
            data_axis=0,
        )
        B = cg.shape[0]
        if B % self.num_data:
            raise ValueError(
                f"batch size {B} not divisible by data axis {self.num_data}"
            )
        self.syn0, self.syn1, loss = self._train_step(
            self.syn0, self.syn1, self._alias_packed,
            cg, gm, cx, mk, key, jnp.float32(alpha),
        )
        self._tick_tables("train_step")
        return loss

    def train_steps(
        self, centers_k, contexts_k, mask_k, base_key, alphas, step0: int = 0
    ) -> jax.Array:
        """K minibatches in ONE device dispatch via an on-device ``lax.scan``.

        ``centers_k (K, B)``, ``contexts_k (K, B, C)``, ``mask_k (K, B, C)``,
        ``alphas (K,)``. The per-step PRNG key is
        ``fold_in(base_key, step0 + i)``, so this is step-for-step identical
        (same negatives, same updates) to K calls of :meth:`train_step` with
        that key schedule. Returns the (K,) per-step losses.

        This is the dispatch-amortized hot path: the reference pays two RPC
        round-trips per 50-position minibatch (mllib:421-429); the scanned
        step pays one host round-trip per K minibatches, with all K updates
        running back-to-back on device.
        """
        centers_k = _host_or_device(centers_k)
        K, B = centers_k.shape[0], centers_k.shape[1]
        # Device-resident all-ones group mask, cached per shape: building
        # it as host numpy per call re-uploaded ~32 KB/step of constant
        # data every dispatch, contaminating the "only scalars cross per
        # dispatch" property of the device-resident hot path.
        if jax.process_count() > 1:
            # Multi-host assembles global batches from HOST arrays
            # (make_global_batch); a device-resident constant would bounce
            # device->host per call there.
            gm = np.ones((K, B, 1), dtype=np.float32)
        else:
            # Keyed by shape so callers alternating between batch shapes
            # don't rebuild and re-upload the constant mask every call.
            if (K, B) not in self._ones_mask_cache:
                self._ones_mask_cache[(K, B)] = jnp.ones(
                    (K, B, 1), jnp.float32
                )
            gm = self._ones_mask_cache[(K, B)]
        return self.train_steps_grouped(
            centers_k[:, :, None], gm,
            contexts_k, mask_k, base_key, alphas, step0,
        )

    def train_steps_grouped(
        self, center_groups_k, group_mask_k, contexts_k, mask_k, base_key,
        alphas, step0: int = 0
    ) -> jax.Array:
        """Grouped-center (subword) variant of :meth:`train_steps`:
        ``center_groups_k (K, B, S)``, ``group_mask_k (K, B, S)``. Under
        multi-host each process passes its own data-axis slice of every
        step's batch (B here = local rows); the global batch is assembled
        across processes before dispatch."""
        self._skipgram_only("train_steps_grouped")
        cg, gm, cx, mk = self._device_batch(
            _host_or_device(center_groups_k),
            _host_or_device(group_mask_k, jnp.float32),
            _host_or_device(contexts_k),
            _host_or_device(mask_k, jnp.float32),
            data_axis=1,
        )
        B = cg.shape[1]
        if B % self.num_data:
            raise ValueError(
                f"batch size {B} not divisible by data axis {self.num_data}"
            )
        self.syn0, self.syn1, losses = self._train_scan(
            self.syn0, self.syn1, self._alias_packed,
            cg, gm, cx, mk,
            base_key, jnp.uint32(step0),
            jnp.asarray(alphas, dtype=jnp.float32),
        )
        self._tick_tables("train_steps")
        return losses

    # ------------------------------------------------------------------
    # Corpus-resident training (device-side batch assembly)
    # ------------------------------------------------------------------

    def upload_corpus(self, ids: np.ndarray, offsets: np.ndarray,
                      n_valid: Optional[int] = None) -> None:
        """Upload the flat encoded corpus (corpus/vocab.encode_file's
        ``(ids, offsets)``) to device HBM once. Subsequent
        :meth:`train_steps_corpus` dispatches assemble minibatches
        entirely on device (ops/device_batching) — per-dispatch
        host->device traffic drops to scalars. ~4 bytes/word of HBM
        replicated per device, ~8 once a packed dispatch has laid down
        the view's per-position record (~16 with the subsampled path's
        compacted buffers, see :meth:`compact_corpus`).

        ``n_valid`` bounds the live center positions to a PREFIX of the
        buffer: positions at or past it never train (they become
        zero-mask lanes inside the scan). The streaming trainer (ISSUE
        10) re-fills one fixed-capacity buffer per mini-epoch and passes
        the real fill here — the bound is a traced scalar in the
        compiled scan, so every round reuses the same warmed program
        regardless of how many words the stream delivered."""
        n = int(np.asarray(ids).shape[0])
        if n < 1 or n >= 2**31 or int(np.asarray(offsets)[-1]) != n:
            raise ValueError(
                "corpus must be non-empty with offsets[-1] == len(ids) "
                f"< 2**31 (got len(ids)={n})"
            )
        if n_valid is None:
            n_valid = n
        if not 0 <= int(n_valid) <= n:
            raise ValueError(
                f"n_valid ({n_valid}) must be in [0, len(ids)={n}]"
            )
        # Replicated on the mesh and committed there, like the alias
        # table: what the compaction pass makes of them then lies on every
        # device too, and a dispatch hands the scans views they already
        # hold. (Left on one device, uncommitted, each dispatch of a mesh
        # program copied the whole view to every other device again.)
        repl = NamedSharding(self.mesh, P())
        self._corpus = (
            jax.device_put(np.asarray(ids, dtype=np.int32), repl),
            jax.device_put(np.asarray(offsets, dtype=np.int32), repl),
        )
        self._corpus_n_valid = int(n_valid)
        self._corpus_compacted = None
        self._n_kept = None
        # The views' per-position records (_position_sentences): the
        # uploaded view's is laid down by the first packed dispatch over
        # it (a fit that compacts every epoch never reads it), a compacted
        # view's with the compaction pass.
        self._corpus_sent = None
        self._compacted_sent = None

    def upload_center_groups(self, groups: Optional[np.ndarray]) -> None:
        """Put the subword family's group table on the device, replicated,
        once per fit: ``groups`` is ``(vocab_size, G)`` int32, row w the
        table rows whose mean is word w's centre vector (its own row, then
        its n-gram bucket rows), padded with -1, an id no shard owns.
        While a table is held the corpus scans form every centre from its
        group inside the jitted scan (the packed scan once a run of pairs:
        ``make_packed_corpus_scan``), and a CBOW engine's packed scan every
        word of a bag from its group, once a step; ``None`` drops it, and
        the scans are the word-level programs again."""
        if groups is None:
            self._center_groups = None
            return
        # graftlint: ignore[sync-point] the family's host-built table
        g = np.asarray(groups, dtype=np.int32)
        if g.ndim != 2 or g.shape[0] != self.vocab_size or g.shape[1] < 2:
            raise ValueError(
                f"groups must have shape ({self.vocab_size}, G >= 2), got "
                f"{g.shape}"
            )
        if g.max(initial=-1) >= self.num_rows or g.min(initial=0) < -1:
            raise ValueError("group ids must be table rows, or -1 (padding)")
        self._center_groups = jax.device_put(
            g, NamedSharding(self.mesh, P())
        )

    @property
    def _group_width(self) -> int:
        g = getattr(self, "_center_groups", None)
        return 0 if g is None else g.shape[1]

    @property
    def corpus_positions(self) -> int:
        """Total center positions of the uploaded corpus (= its words)."""
        if getattr(self, "_corpus", None) is None:
            raise ValueError("no corpus uploaded (call upload_corpus first)")
        return int(self._corpus[0].shape[0])

    def set_keep_probs(self, keep_prob: np.ndarray) -> None:
        """Install the per-word keep-probability table driving on-device
        frequency subsampling (Vocabulary.device_keep_probabilities).
        Required before :meth:`compact_corpus`."""
        kp = np.asarray(keep_prob, dtype=np.float32)
        if kp.shape != (self.vocab_size,):
            raise ValueError(
                f"keep_prob must have shape ({self.vocab_size},), "
                f"got {kp.shape}"
            )
        self._keep_prob = jax.device_put(kp, NamedSharding(self.mesh, P()))

    def compact_corpus(self, epoch_key) -> int:
        """Run one epoch's on-device subsample-and-compact pass
        (ops/device_batching.subsample_compact) over the uploaded corpus
        and make the compacted view the active corpus for subsequent
        :meth:`train_steps_corpus` dispatches. Returns ``n_kept`` — the
        single scalar the host reads back per epoch to size its step
        loop. The previous epoch's compacted buffers are freed first so
        HBM holds at most one compacted copy alongside the flat corpus.
        """
        if getattr(self, "_corpus", None) is None:
            raise ValueError("no corpus uploaded (call upload_corpus first)")
        if getattr(self, "_keep_prob", None) is None:
            raise ValueError(
                "no keep probabilities installed (call set_keep_probs first)"
            )
        if self._corpus_n_valid != int(self._corpus[0].shape[0]):
            # The device pass draws keep masks over the WHOLE static
            # buffer; a bounded prefix view would compact dead padding
            # tokens into the live stream. The streaming trainer
            # subsamples host-side while filling the buffer instead.
            raise ValueError(
                "on-device subsampling over an n_valid-bounded corpus "
                "view is unsupported (subsample host-side when filling "
                "the buffer)"
            )
        _free(*(self._corpus_compacted or ()), self._compacted_sent)
        self._corpus_compacted = self._compacted_sent = None
        self._compacted_offsets_host = None
        pre, self._compact_prefetch = self._compact_prefetch, None
        if pre is not None and np.array_equal(
            pre[0], np.asarray(epoch_key)
        ):
            # Adopt the pass prefetch_compact_corpus dispatched while the
            # previous epoch's tail group was still executing: same jitted
            # function, same key — bitwise-identical buffers, already (or
            # still becoming) computed on device.
            ids_c, offsets_c, n_kept, sent_c = pre[1:]
        else:
            # Prefetched for a different key (e.g. an out-of-order
            # resume): discard, recompute fresh.
            _free(*(pre or ()))
            ids_c, offsets_c, n_kept, sent_c = self._compact_dispatch(
                epoch_key
            )
        self._corpus_compacted = (ids_c, offsets_c)
        self._compacted_sent = sent_c
        self._n_kept = int(n_kept)
        return self._n_kept

    def _position_sentences(self, offsets, n: int):
        """Dispatch a view's per-position record
        (ops/device_batching.position_sentences): the sentence of each of
        its ``n`` positions, made on every device of the mesh, where the
        view's offsets lie."""
        if not hasattr(self, "_sentences_fn"):
            from glint_word2vec_tpu.ops.device_batching import (
                position_sentences,
            )

            self._sentences_fn = jax.jit(position_sentences, static_argnums=1)
        return self._sentences_fn(offsets, n)

    def _compact_dispatch(self, epoch_key):
        """Dispatch (without blocking) one subsample-compact pass over
        the uploaded flat corpus and the compacted view's per-position
        record; returns the lazy device ``(ids_c, offsets_c, n_kept,
        sent_c)``."""
        if not hasattr(self, "_compact_fn"):
            from glint_word2vec_tpu.ops.device_batching import (
                subsample_compact,
            )

            self._compact_fn = jax.jit(subsample_compact)
        ids, offsets = self._corpus
        ids_c, offsets_c, n_kept = self._compact_fn(
            ids, offsets, self._keep_prob, epoch_key
        )
        return ids_c, offsets_c, n_kept, self._position_sentences(
            offsets_c, ids.shape[0]
        )

    def prefetch_compact_corpus(self, epoch_key) -> None:
        """Dispatch the NEXT epoch's subsample-compact pass into fresh
        device buffers without adopting them — called by the fit loop
        while the current epoch's tail group is still executing, so the
        per-epoch compaction overlaps training instead of serializing
        the epoch boundary (ISSUE 5 prefetch overlap). The buffers are
        adopted by the next :meth:`compact_corpus` call with the same
        ``epoch_key`` (bitwise identical to computing them there); the
        currently-active compacted view is untouched until then. Costs
        one extra transient compacted buffer of HBM until adoption."""
        if getattr(self, "_corpus", None) is None:
            raise ValueError("no corpus uploaded (call upload_corpus first)")
        if getattr(self, "_keep_prob", None) is None:
            raise ValueError(
                "no keep probabilities installed (call set_keep_probs first)"
            )
        _free(*(self._compact_prefetch or ()))
        self._compact_prefetch = (
            np.asarray(epoch_key), *self._compact_dispatch(epoch_key)
        )

    def compacted_offsets(self) -> np.ndarray:
        """Host copy of the active epoch's compacted sentence offsets —
        one (S+1,) readback per epoch, feeding the pre-subsampling
        words_done accounting (corpus_words_done_compacted)."""
        if getattr(self, "_corpus_compacted", None) is None:
            raise ValueError("no compacted corpus (call compact_corpus)")
        if getattr(self, "_compacted_offsets_host", None) is None:
            self._compacted_offsets_host = np.asarray(
                self._corpus_compacted[1]
            )
        return self._compacted_offsets_host

    def _scan_memo_key(self, kind: str, *shape_key):
        """Memo key for :data:`_SCAN_MEMO`: the mesh geometry (device
        ids + axis names) plus every engine attribute the scan
        closures capture at trace time — two engines agreeing on this
        key trace bitwise-identical programs (everything else is a
        traced argument)."""
        return (
            kind,
            tuple(d.id for d in self.mesh.devices.flat),
            self.mesh.axis_names,
            tuple(self.mesh.shape.items()),
            self.architecture, self.position_lanes,
            str(self._dtype), str(self._compute_dtype),
            self.num_negatives, self.shared_negatives,
            self.rows_per_shard,
            self.padded_vocab, self.padded_dim, self.vocab_size,
            *shape_key,
        )

    def _query_memo_key(self, op):
        """Memo key for :data:`_QUERY_MEMO`: the mesh geometry plus
        ONLY the attributes the query closures capture — storage dtype,
        shard geometry. Training attributes (negatives, compute dtype)
        are excluded on purpose: they never reach a query program, so
        models that differ only in how they were trained still share the
        whole warm family."""
        return (
            "query", op,
            tuple(d.id for d in self.mesh.devices.flat),
            self.mesh.axis_names,
            tuple(self.mesh.shape.items()),
            str(self._dtype),
            self.rows_per_shard,
            self.padded_vocab, self.padded_dim, self.dim,
        )

    def train_steps_corpus(
        self, start_position: int, batch_size: int, window: int,
        base_key, alphas, step0: int = 0
    ) -> jax.Array:
        """K = len(alphas) scanned minibatches over the ACTIVE corpus
        view — the epoch's compacted buffers when :meth:`compact_corpus`
        has run (subsampled training; ``start_position`` is then a
        compacted-stream position), else the full uploaded corpus.
        Batch i covers positions [start + i*B, start + (i+1)*B);
        positions past the corpus end become zero-mask rows (the epoch
        tail). Returns the (K,) per-step losses. Key schedule matches
        :meth:`train_steps` exactly."""
        self._skipgram_only("train_steps_corpus")
        if getattr(self, "_corpus", None) is None:
            raise ValueError("no corpus uploaded (call upload_corpus first)")
        B, W = int(batch_size), int(window)
        if B % self.num_data:
            raise ValueError(
                f"batch size {B} not divisible by data axis {self.num_data}"
            )
        G = self._group_width
        fn = self._corpus_scan_cache.get((B, W, G))
        if fn is None:
            mk = self._scan_memo_key("grid", B, W, G)
            fn = _SCAN_MEMO.get(mk)
            if fn is None:
                fn = _scan_memo_put(mk, self._make_corpus_scan(B, W, G))
            self._corpus_scan_cache[(B, W, G)] = fn
        if getattr(self, "_corpus_compacted", None) is not None:
            ids, soffs = self._corpus_compacted
            n_valid = self._n_kept
        else:
            ids, soffs = self._corpus
            n_valid = getattr(self, "_corpus_n_valid", ids.shape[0])
        self.syn0, self.syn1, losses = fn(
            self.syn0, self.syn1, self._alias_packed, ids, soffs,
            jnp.int32(n_valid), jnp.int32(start_position), base_key,
            jnp.uint32(step0), jnp.asarray(alphas, dtype=jnp.float32),
            *((self._center_groups,) if G else ()),
        )
        self._tick_tables("train_steps_corpus")
        return losses

    def train_steps_corpus_packed(
        self, start_position: int, pair_batch: int, window: int,
        grid_batch: int, base_key, n_steps: int, step0: int = 0,
        grid_step0: int = 0, *, step_size: float = 0.025,
        total_words: int = 1, words_base: int = 0,
        span: Optional[int] = None,
    ):
        """K = ``n_steps`` PACKED minibatches over the active corpus view
        — the dense-pair alternative to :meth:`train_steps_corpus`
        (``set_batch_packing("dense")`` routes here). Each step packs the
        next valid (center, context) pairs of the position stream into a
        dense ``pair_batch``-slot batch and applies the rank-1 SGNS
        update over pairs, so ~every dispatched contraction lane is a
        real pair (grid dispatches run ~0.43 live lanes at window 5).

        The consumed-position advance is data-dependent and carried
        through the scan; LR alphas are computed ON DEVICE from the
        traced advance with the host's exact pre-subsampling words_done
        rule, parameterized by ``step_size``, ``total_words`` (the LR
        denominator, ``num_iterations * train_words + 1``) and
        ``words_base`` (words credited before this epoch).

        ``grid_batch``/``grid_step0`` pin the window-shrink RNG stream to
        the grid scan's position->draw mapping (see
        ops/device_batching.grid_window_shrink): with the batch size and
        per-epoch step base a grid run would use, the packed run consumes
        the exact same valid-pair multiset per epoch. Negatives are keyed
        by global PAIR row under the ``fold_in(base_key, step0 + i)``
        schedule — mesh-invariant, but a different draw stream than the
        grid path's (like host-vs-device RNG divergence, documented).

        Returns ``(losses (K,), pair_counts (K,), pos_ends (K,),
        alphas (K,), rows_written (K, 4))`` — per-step loss, live pairs
        packed, consumed position after the step, the device-computed
        alpha, and the distinct rows the step's scatters wrote into
        (syn0, syn1), of the :meth:`packed_scatter_slots` they were handed,
        then the slabs the slab writer moved for them (syn0, syn1; 0 where
        XLA's writer ran: :func:`_scatter_rows`). While a group table is
        held (:meth:`upload_center_groups`) ``rows_written`` is ``(K, 6)``:
        then the live group ids the step gathered and the centres they
        formed.
        The caller reads ``pos_ends[-1]`` to schedule the next dispatch
        (one scalar readback per K steps).

        The group stops at the corpus end ON THE DEVICE: a step runs while
        ``i < K`` and its start lies inside the view. Steps the device did
        not run (the tail of an epoch's last group, the whole of a group
        started past the end) come back as ``alphas`` 0, which no step
        that ran writes, ``losses`` / ``pair_counts`` / ``rows_written`` 0
        and ``pos_ends`` the final position, and leave the tables as they
        were; the caller's key schedule still counts K steps a dispatch.

        A CBOW engine trains POSITIONS, not pairs: ``pair_batch`` is then
        the consecutive centre positions of a step (each with its bag of
        up to ``2 * window`` context words, one draw of negatives a
        position), the advance a step is that many, and ``span`` does not
        apply. ``pair_counts`` reads the live bag slots of a step and
        ``rows_written`` is ``(K, 6)``: then the live bag slots again and
        the positions that trained (inside the corpus, bag not empty).
        The bags read the words of each rank's span (``pair_batch /
        num_data + 2 * window`` of them), every one gathered once a step;
        ``rows_written[:, 0]`` counts the distinct words some bag read.
        While it holds a group table a bag's word is its group
        (fastText's CBOW) and ``rows_written`` is ``(K, 9)``: then the
        live group ids the step gathered, the span words they composed
        (less what lies outside the view), and the input rows, the rows a
        bag's mean is over, summed over the step's positions. An engine
        with ``position_lanes`` trains its position table in the same
        steps (``window`` must be the one it was built for).
        """
        if getattr(self, "_corpus", None) is None:
            raise ValueError("no corpus uploaded (call upload_corpus first)")
        from glint_word2vec_tpu.corpus.batching import context_width

        P, W, B = int(pair_batch), int(window), int(grid_batch)
        cbow = self.architecture == "cbow"
        C = 1 if cbow else context_width(W)
        if P % self.num_data:
            raise ValueError(
                f"pair batch {P} not divisible by data axis {self.num_data}"
            )
        if P < C:
            raise ValueError(
                f"pair_batch ({P}) must be >= context lanes ({C})"
            )
        if cbow:
            span = 0
        elif span is None:
            # Enough candidates that the cumulative valid-pair count
            # almost always reaches P (expected live lanes per position
            # is ~0.43*C at W=5, ~0.5*C at W=2): 3*P/C positions carry
            # ~1.3-1.5x P expected pairs, so underfill is confined to
            # the epoch tail.
            span = -(-3 * P // C)
        S, K = int(span), int(n_steps)
        G = self._group_width
        fn = self._packed_scan_cache.get((P, W, B, S, K, G))
        if fn is None:
            mk = self._scan_memo_key("packed", P, W, B, S, K, G)
            fn = _SCAN_MEMO.get(mk)
            if fn is None:
                fn = _scan_memo_put(
                    mk, self._make_packed_corpus_scan(P, W, B, S, K, G)
                )
            self._packed_scan_cache[(P, W, B, S, K, G)] = fn
        if getattr(self, "_corpus_compacted", None) is not None:
            ids, soffs = self._corpus_compacted
            sent_of, n_valid = self._compacted_sent, self._n_kept
        else:
            ids, soffs = self._corpus
            if self._corpus_sent is None:
                self._corpus_sent = self._position_sentences(
                    soffs, ids.shape[0]
                )
            sent_of = self._corpus_sent
            n_valid = getattr(self, "_corpus_n_valid", ids.shape[0])
        names = self.table_names
        out = fn(
            *self.tables().values(), self._alias_packed, ids, sent_of, soffs,
            self._corpus[1], jnp.int32(n_valid),
            jnp.int32(start_position), base_key, jnp.uint32(step0),
            jnp.uint32(grid_step0), jnp.float32(step_size),
            jnp.float32(1.0 / float(total_words)),
            jnp.float32(words_base),
            *((self._center_groups,) if G else ()),
        )
        for name, table in zip(names, out):
            setattr(self, name, table)
        self._tick_tables("train_steps_corpus_packed")
        return tuple(out[len(names):])

    def _packed_center_slots(self, pair_batch: int, window) -> int:
        """``syn0`` rows one packed step pulls and update slots it hands the
        ``syn0`` scatter, over all data ranks: a centre a pair, or, while a
        group table is held, the whole group (padding included) of every
        run slot of the default span (``make_packed_corpus_scan``); on a
        CBOW engine every word of every rank's span (its positions and
        their reach, ``2 * window``), a row each or its whole group."""
        G = self._group_width
        if self.architecture == "cbow":
            return max(G, 1) * (pair_batch + self.num_data * 2 * window)
        if not G:
            return pair_batch
        from glint_word2vec_tpu.corpus.batching import context_width

        span = -(-3 * pair_batch // context_width(window))
        return self.num_data * G * min(pair_batch // self.num_data, span + 1)

    def packed_scatter_slots(self, pair_batch: int,
                             window: Optional[int] = None) -> Tuple[int, int]:
        """Update slots one packed step hands the (syn0, syn1) scatters:
        a centre a pair (:meth:`_packed_center_slots`: ``window`` matters
        to a subword or CBOW engine alone), and a context plus its
        negatives (or the shared pool once) a pair; on a CBOW engine a
        bag, and its own word plus the negatives, a position."""
        centers = self._packed_center_slots(pair_batch, window)
        if self.shared_negatives:
            return centers, pair_batch + self.shared_negatives
        return centers, pair_batch * (1 + self.num_negatives)

    def packed_exchange_bytes(self, pair_batch: int,
                              window: Optional[int] = None) -> int:
        """Bytes one device hands the model-axis all-reduces of one packed
        step (``glint.exchange``), from shapes alone, float32 rows as they
        rest, ``padded_dim`` wide: the centre rows it pulls and, with a
        shared pool, a context a pair and the pool once; with per-pair
        negatives no ``syn1`` row but a pair's ``d_center`` and its
        ``1 + num_negatives`` logits. 0 where the model axis has one
        shard."""
        if self.num_model == 1:
            return 0
        pairs = pair_batch // self.num_data
        centers = self._packed_center_slots(pair_batch, window)
        rows = centers // self.num_data + pairs
        if self.shared_negatives:
            return 4 * (rows + self.shared_negatives) * self.padded_dim
        return 4 * (rows * self.padded_dim
                    + pairs * (1 + self.num_negatives))

    def packed_exchange_send_bytes(self, pair_batch: int,
                                   window: Optional[int] = None) -> dict:
        """Bytes one device must SEND over the model axis in one packed
        step at least, by collective, from shapes alone: among n shards an
        all-reduce of S bytes sends 2 (n - 1) / n x S, and all-reduces
        are all the step has (:meth:`packed_exchange_bytes`): the other
        two keys read 0. All 0 where the model axis has one shard."""
        n = self.num_model
        handed = self.packed_exchange_bytes(pair_batch, window)
        return {"all_reduce": 2 * (n - 1) * handed // n,
                "reduce_scatter": 0, "all_gather": 0}

    # ------------------------------------------------------------------
    # Serving ops (the BigWord2VecMatrix query surface)
    # ------------------------------------------------------------------

    def _tick_tables(self, reason: str) -> None:
        """One table mutation: invalidate the norms cache, tick
        ``table_version`` (the token serving-layer caches validate
        against), and record the engine-level event (a no-op global read
        when no recorder is installed)."""
        self._norms_cache = None
        self.table_version += 1
        if reason != "exchange_adopt":
            # Any mutation whose touched-row set is unknown makes every
            # shard file dirty (the safe direction for the skip-clean
            # in-place save); exchange_adopt already narrowed the set.
            self._shard_dirty = None
        obs_events.emit(
            "table_mutation", reason=reason, version=self.table_version
        )

    # -- touched-row replica exchange (ISSUE 15, parallel/exchange.py) --

    def exchange_adopt(self, syn0, syn1, *, touched_ids=None) -> None:
        """Install the reconciled tables a replica-exchange round
        reconstructed (``base + sum of every rank's deltas``): two
        attribute flips and ONE ``table_version`` tick, exactly like
        :meth:`adopt_tables`. ``touched_ids`` (host int array, a sparse
        round's union of exchanged row ids) narrows the checkpoint
        dirty-shard set to the shard files covering those rows; None (a
        dense round) marks everything dirty."""
        self.syn0 = syn0
        self.syn1 = syn1
        self._mark_shards_dirty(touched_ids)
        self._tick_tables("exchange_adopt")

    def _mark_shards_dirty(self, touched_ids=None) -> None:
        """Fold one mutation's touched rows into the dirty-shard-file
        map: MERGE into the existing map, never narrow it — ``None``
        (everything dirty, the state every unknown mutation restores)
        stays ``None`` until a committed save re-establishes clean
        bits."""
        if touched_ids is None:
            self._shard_dirty = None
            return
        if self._shard_dirty is None:
            return  # already all-dirty; a narrower mark must not undo it
        per_shard = self._save_block_rows()
        starts = np.unique(
            # graftlint: ignore[sync-point] touched_ids is a host id array
            (np.asarray(touched_ids, dtype=np.int64) // per_shard)
            * per_shard
        )
        for start in starts:
            if 0 <= start < self.num_rows:
                for name in ("syn0", "syn1"):
                    self._shard_dirty[f"{name}.r{start:012d}.npy"] = True

    def _shard_is_dirty(self, fname: str, path: str) -> bool:
        """Whether an in-place save to ``path`` must rewrite ``fname``:
        True unless the last committed save that cleaned the bits wrote
        to this same path and nothing has dirtied the shard since
        (unknown shard names default to dirty — the safe direction)."""
        if self._shard_clean_path != path or self._shard_dirty is None:
            return True
        return bool(self._shard_dirty.get(fname, True))

    def _mark_shards_clean(self, path: str, fnames) -> None:
        """Record that ``path`` now holds current bytes for ``fnames``
        (called after the save's commit point)."""
        if self._shard_clean_path != path or self._shard_dirty is None:
            self._shard_dirty = {}
            self._shard_clean_path = path
        for f in fnames:
            self._shard_dirty[f] = False

    def _note_exchange(self, *, bytes_sent: int, rows: int,
                       overflow: bool, dense: bool,
                       seconds: float, wire: str = "fp32",
                       groups: int = 1, flush: bool = False,
                       world1_skip: bool = False, intra_bytes: int = 0,
                       capacity: Optional[int] = None,
                       cap_event: Optional[str] = None,
                       residual_abs: float = 0.0) -> None:
        st = self._exchange_stats
        st["exchange_bytes_total"] += int(bytes_sent)  # graftlint: ignore[sync-point] host stat
        st["exchange_rows_total"] += int(rows)  # graftlint: ignore[sync-point] host stat
        st["exchange_overflow_total"] += int(bool(overflow))
        st["exchange_syncs_total"] += 1
        st["exchange_dense_syncs_total"] += int(bool(dense))
        st["exchange_last_seconds"] = round(float(seconds), 6)  # graftlint: ignore[sync-point] host stat
        wire_key = "exchange_bytes_wire_%s_total" % (
            wire if wire in ("fp32", "bf16", "int8") else "fp32"
        )
        st[wire_key] += int(bytes_sent)  # graftlint: ignore[sync-point] host stat
        st["exchange_groups_total"] += int(groups)  # graftlint: ignore[sync-point] host stat
        st["exchange_flushes_total"] += int(bool(flush))
        st["exchange_world1_skips_total"] += int(bool(world1_skip))
        st["exchange_intra_bytes_total"] += int(intra_bytes)  # graftlint: ignore[sync-point] host stat
        inter = max(int(bytes_sent) - int(intra_bytes), 0)  # graftlint: ignore[sync-point] host stat
        st["exchange_inter_bytes_total"] += inter  # graftlint: ignore[sync-point] host stat
        if capacity is not None:
            st["exchange_capacity"] = int(capacity)  # graftlint: ignore[sync-point] host stat
        st["exchange_capacity_grows_total"] += int(cap_event == "grow")
        st["exchange_capacity_shrinks_total"] += int(cap_event == "shrink")
        st["exchange_residual_abs"] = float(residual_abs)  # graftlint: ignore[sync-point] host stat

    def exchange_stats(self) -> dict:
        """Replica-exchange telemetry for the heartbeat (zeros until a
        :class:`parallel.exchange.ReplicaExchanger` runs a round)."""
        return dict(self._exchange_stats)

    def _count_query_shape(self, *key) -> None:
        """Record one query-op dispatch shape; a first-seen shape is one
        jit compile (jit specializes per shape). Callers hold the query
        lock on the serving path; elsewhere races only over-count."""
        self.query_dispatches += 1
        if key not in self._query_shapes:
            self._query_shapes.add(key)
            self.query_compiles += 1
            # Process-level accounting (ISSUE 20): if a same-geometry
            # engine already dispatched this (op, shape), the shared
            # program memo means no XLA compile actually ran — the
            # per-engine counter keeps its first-seen-here semantics,
            # the process counter measures real compile work.
            pkey = self._query_memo_key("shape") + key
            shared = pkey in _QUERY_SHAPES_SEEN
            if shared:
                self.shared_program_hits += 1
            else:
                _QUERY_SHAPES_SEEN.add(pkey)
                _QUERY_PROGRAM_BUILDS[0] += 1
            obs_events.emit(
                "query_compile", op=str(key[0]), shape=list(key[1:]),
                total=self.query_compiles, shared=shared,
            )

    def _k_bucket(self, k: int) -> int:
        """Round a top-k request up to its compile bucket (see
        TOPK_MIN_K_BUCKET)."""
        return min(max(next_pow2(k), TOPK_MIN_K_BUCKET), self.padded_vocab)

    def _q_bucket(self, n: int) -> int:
        """Round a batch top-k row count up to its compile bucket (see
        TOPK_MIN_Q_BUCKET)."""
        return 1 if n <= 1 else max(next_pow2(n), TOPK_MIN_Q_BUCKET)

    def pull(self, indices) -> jax.Array:
        """Gather syn0 rows by global index (Glint ``pull``, mllib:514)."""
        idx = jnp.asarray(indices, dtype=jnp.int32)
        self._count_query_shape("pull", int(idx.shape[0]))
        return self._pull(self.syn0, idx)

    def pull_average(self, sentence_indices, mask) -> jax.Array:
        """Mean of syn0 rows per padded index-set row (Glint ``pullAverage``,
        ml:453): sentence embedding computed device-side; only S*d floats
        ever leave the device. All-masked rows yield zero vectors (the
        reference's empty-sentence average)."""
        idx = jnp.asarray(sentence_indices, dtype=jnp.int32)
        self._count_query_shape(
            "pull_average", int(idx.shape[0]), int(idx.shape[1])
        )
        return self._pull_average(
            self.syn0, idx, jnp.asarray(mask, dtype=jnp.float32)
        )

    def _row_writer(self):
        """Lazily-built jitted row-block writer shared by
        :meth:`write_rows` and the extra-row assignment path: one
        compiled program per block shape, start row traced.

        On a model axis of one it is a ``dynamic_update_slice``. Over
        several shards that op's traced start makes XLA's partitioner
        gather the WHOLE table on every device first (at 10M x 300 over
        four v5e chips the chip's compiler refuses the program: 14.66 GB
        of temporaries beside a 3.84 GB shard; PERF.md, PR 47), so there
        each shard writes its own rows of the block into a window of its
        own table, as the servers each take their slice of a push, and
        nothing of the table crosses chips."""
        if not hasattr(self, "_write_rows_fn"):
            if self.num_model == 1:
                def write(table, block, s):
                    return lax.dynamic_update_slice(
                        table, block.astype(table.dtype), (s, 0)
                    )
            else:
                write = self._shard_map(
                    self._write_own_rows,
                    in_specs=(P(MODEL_AXIS, None), P(), P()),
                    out_specs=P(MODEL_AXIS, None),
                )
            self._write_rows_fn = jax.jit(
                write,
                out_shardings=self._table_sharding(),
                donate_argnums=(0,),
            )
        return self._write_rows_fn

    def _write_own_rows(self, table_l, block, s):
        """One shard's share of :meth:`write_rows`: the rows of ``block``
        (global rows ``s ..``) that this shard owns, written in place.
        The window is as many rows as the block has (or the whole shard,
        if the block is longer), laid where the block meets the shard; a
        window row the block does not reach keeps what it held."""
        Vs = self.rows_per_shard
        n, cols = block.shape
        m = min(n, Vs)
        loc = s - lax.axis_index(MODEL_AXIS) * Vs  # local row of block[0]
        w = jnp.clip(loc, 0, Vs - m)
        held = lax.dynamic_slice(table_l, (w, 0), (m, cols))
        i = w + jnp.arange(m) - loc  # the block row a window row takes
        rows = jnp.where(
            ((i >= 0) & (i < n))[:, None],
            block[jnp.clip(i, 0, n - 1)].astype(table_l.dtype), held,
        )
        return lax.dynamic_update_slice(table_l, rows, (w, 0))

    def write_rows(self, start_row: int, rows: jax.Array) -> None:
        """Overwrite ``rows.shape[0]`` consecutive syn0 rows starting at
        ``start_row``, entirely on device (used to assemble derived tables,
        e.g. composed subword vectors, without a host round-trip). The
        start index is a traced argument, so chunked writers compile once
        per chunk shape."""
        # The block is ``dim`` wide and lands at column 0: the padding
        # columns beside it are zero and stay so, with no padded copy of
        # the block made first (a whole table's worth when a table is
        # installed in one call).
        self.syn0 = self._row_writer()(
            self.syn0, rows, jnp.int32(start_row)
        )
        self._tick_tables("write_rows")
        self._ann_touch_rows(range(start_row, start_row + rows.shape[0]))

    # ------------------------------------------------------------------
    # Runtime vocabulary growth (ISSUE 10 streaming)
    # ------------------------------------------------------------------

    @property
    def extra_rows_total(self) -> int:
        """Spare non-vocabulary rows reserved at construction."""
        return self.num_rows - self.vocab_size

    @property
    def extra_rows_free(self) -> int:
        """Spare rows still available to :meth:`assign_extra_row`."""
        return self.extra_rows_total - self.extra_rows_assigned

    @property
    def queryable_rows(self) -> int:
        """Rows the similarity ops may surface: the base vocab plus
        every assigned extra row. This bound enters the warmed top-k
        programs as a TRACED scalar, so growing (or freeing) rows never
        costs a compile — the streaming hot-swap contract (ISSUE 10)."""
        return self.vocab_size + self.extra_rows_assigned

    def _extra_row_writer(self):
        """The ONE program a promotion runs, whatever its size: rows
        ``[s, s + m)`` of ``syn0`` take the word2vec ``U[-0.5/d, 0.5/d)``
        draw, keyed per GLOBAL row by the engine seed (so a row's values
        do not depend on the burst it arrived in, and repeated runs draw
        identically), and the same rows of ``syn1`` take zeros. A call
        handles ``m <= _EXTRA_ROW_BLOCK`` rows; ``s`` and ``m`` are
        traced, the block is a static shape, and each shard writes its
        own rows of it into a window of its own table
        (:meth:`_write_own_rows`' layout), the rows past ``m`` keeping
        what they held. Blocks cut to the burst's size, a power of two
        each, compiled two programs a size: a stream's bursts differ
        round by round, and a trainer an hour old still met new ones
        (PERF.md, PR 50)."""
        if not hasattr(self, "_extra_rows_fn"):
            d, dp, Vs = self.dim, self.padded_dim, self.rows_per_shard
            B = min(_EXTRA_ROW_BLOCK, Vs)
            base = jax.random.PRNGKey(self._seed)
            sharded = self.num_model > 1

            def fresh(s):
                keys = jax.vmap(
                    lambda r: jax.random.fold_in(base, (1 << 30) + r)
                )(s + jnp.arange(B, dtype=jnp.int32))
                blk = jax.vmap(
                    lambda k: jax.random.uniform(
                        k, (dp,), jnp.float32,
                        minval=-0.5 / d, maxval=0.5 / d,
                    )
                )(keys)
                return blk.at[:, d:].set(0.0) if dp > d else blk

            def write(table_l, block, loc, m):
                w = jnp.clip(loc, 0, Vs - B)
                held = lax.dynamic_slice(table_l, (w, 0), (B, dp))
                i = w + jnp.arange(B) - loc  # the block row a window row takes
                rows = jnp.where(
                    ((i >= 0) & (i < m))[:, None],
                    block[jnp.clip(i, 0, B - 1)].astype(table_l.dtype),
                    held,
                )
                return lax.dynamic_update_slice(table_l, rows, (w, 0))

            def local(syn0_l, syn1_l, s, m):
                loc = s - (lax.axis_index(MODEL_AXIS) * Vs if sharded else 0)
                return (
                    write(syn0_l, fresh(s), loc, m),
                    write(syn1_l, jnp.zeros((B, dp), jnp.float32), loc, m),
                )

            tsh = self._table_sharding()
            self._extra_rows_fn = (B, jax.jit(
                self._shard_map(
                    local,
                    in_specs=(P(MODEL_AXIS, None),) * 2 + (P(), P()),
                    out_specs=(P(MODEL_AXIS, None),) * 2,
                ) if sharded else local,
                out_shardings=(tsh, tsh),
                donate_argnums=(0, 1),
            ))
        return self._extra_rows_fn

    def assign_extra_rows(self, words: Sequence[Optional[str]]) -> List[int]:
        """Claim ``len(words)`` consecutive spare extra rows in one
        batched mutation: the promotion-burst path (a vocabulary shift
        can promote thousands of words between two mini-epochs, and
        per-word writes would issue thousands of serialized single-row
        dispatches). The burst is written ``_EXTRA_ROW_BLOCK`` rows a
        dispatch by one program (:meth:`_extra_row_writer`), so a
        lifetime of arbitrary burst sizes compiles once, and the whole
        burst costs ONE ``table_version`` tick.

        Each claimed syn0 row gets the word2vec ``U[-0.5/d, 0.5/d)``
        init keyed by the engine seed + its GLOBAL row (identical to n
        single assignments — the draw does not depend on the batch it
        arrived in) and the syn1 row is zeroed, so a
        freed-and-recycled row never leaks its previous word's trained
        values. Returns the claimed GLOBAL row indices — always the
        next ``len(words)`` rows after ``queryable_rows``, so the
        caller's grown word list stays aligned with the table by
        construction. ``words`` feed the obs event only — the engine
        stays word-agnostic; the vocabulary layer owns the mapping."""
        words = list(words)
        n = len(words)
        if n == 0:
            return []
        if n > self.extra_rows_free:
            raise ValueError(
                f"no spare extra rows left for {n} word(s) "
                f"({self.extra_rows_assigned}/{self.extra_rows_total} "
                "assigned); construct the engine with more extra_rows "
                "headroom"
            )
        start = self.vocab_size + self.extra_rows_assigned
        block, fn = self._extra_row_writer()
        for s in range(start, start + n, block):
            self.syn0, self.syn1 = fn(
                self.syn0, self.syn1, jnp.int32(s),
                jnp.int32(min(block, start + n - s)),
            )
        self.extra_rows_assigned += n
        self._tick_tables("assign_extra_row")
        self._ann_touch_rows(range(start, start + n))
        obs_events.emit(
            "extra_rows_assigned", start=start, n=n,
            assigned=self.extra_rows_assigned, words=words[:8],
        )
        return list(range(start, start + n))

    def assign_extra_row(self, word: Optional[str] = None) -> int:
        """Claim the next spare extra row for a word that entered the
        vocabulary at runtime (ISGNS online vocab growth). Returns the
        claimed GLOBAL row index. Single-word form of
        :meth:`assign_extra_rows` — identical init, one
        ``table_version`` tick per call."""
        return self.assign_extra_rows([word])[0]

    def free_extra_rows(self, n: Optional[int] = None) -> int:
        """Release the last ``n`` assigned extra rows (default: all),
        zeroing both table rows so a later reassignment can never leak
        the previous word's vectors. Returns the number freed. Ticks
        ``table_version`` — the queryable bound shrank, so any cached
        top-k that surfaced a freed row must drop."""
        if n is None:
            n = self.extra_rows_assigned
        n = int(n)  # graftlint: ignore[sync-point] host argument, not a device value
        if n < 0 or n > self.extra_rows_assigned:
            raise ValueError(
                f"cannot free {n} extra rows "
                f"({self.extra_rows_assigned} assigned)"
            )
        if n == 0:
            return 0
        start = self.vocab_size + self.extra_rows_assigned - n
        fn = self._row_writer()
        zeros = jnp.zeros((n, self.padded_dim), jnp.float32)
        self.syn0 = fn(self.syn0, zeros, jnp.int32(start))
        self.syn1 = fn(self.syn1, zeros, jnp.int32(start))
        self.extra_rows_assigned -= n
        self._tick_tables("free_extra_rows")
        if self._ann is not None:
            from glint_word2vec_tpu.ops import ann as _ann_mod

            _ann_mod.remove_rows(
                self._ann, self.syn0, range(start, start + n)
            )
            self._ann.table_version = self.table_version
        return n

    def _ann_touch_rows(self, rows) -> None:
        """Incrementally re-bucket rows whose values just changed into
        the live coarse index (streaming promotions / row writes):
        ONLY the touched rows move — the ISSUE 12 incremental
        re-assignment contract. A no-op without an adopted index; the
        index version advances with the table so staleness gauges stay
        honest."""
        if self._ann is None:
            return
        from glint_word2vec_tpu.ops import ann as _ann_mod

        _ann_mod.update_rows(self._ann, self.syn0, self.norms(), rows)
        self._ann.table_version = self.table_version

    def noise_table(self, counts: np.ndarray):
        """The alias table :meth:`set_noise_counts` would install for
        ``counts``: host work only (two ``pow`` and the alias build over
        ``vocab_size`` counts, tens of milliseconds at 2M with
        native/host_ops.cpp), touching nothing of the engine, so a
        streaming trainer builds the next round's table while the device
        drains this one and hands it to :meth:`set_noise_counts`."""
        # graftlint: ignore[sync-point] counts arrive as a host numpy array
        c = np.asarray(counts, dtype=np.int64)
        if c.shape != (self.vocab_size,):
            raise ValueError(
                f"counts must have shape ({self.vocab_size},), got {c.shape}"
            )
        if c.sum() <= 0:
            raise ValueError("counts must sum to > 0")
        return build_unigram_alias(
            c, power=self.unigram_power, table_size=self.unigram_table_size
        )

    def set_noise_counts(self, counts: np.ndarray, table=None) -> None:
        """Install updated per-word corpus counts and rebuild the
        negative-sampling alias table from them — the ISGNS adaptive
        unigram distribution (arXiv:1704.03956): a long-lived streaming
        trainer re-derives the noise distribution from the counts it
        has actually observed, on a cadence, instead of freezing the
        bootstrap distribution forever. ``table`` is
        :meth:`noise_table` of the same counts where the caller built it
        ahead; left out, it is built here.

        Shapes are invariant (``prob``/``alias`` stay ``(vocab_size,)``
        arrays), so every compiled train program keeps running warm —
        the refresh is two replicated device_puts. Spare extra rows are
        never negative-sampled (the table spans the base vocab only, as
        for fastText buckets); checkpoints carry the updated counts."""
        if table is None:
            table = self.noise_table(counts)
        # graftlint: ignore[sync-point] counts arrive as a host numpy array
        self._counts = np.array(counts, dtype=np.int64)
        self._put_alias_table(table)

    def norms(self) -> jax.Array:
        """Per-row Euclidean norms of syn0, computed shard-local (Glint
        ``norms``, mllib:486), cached until the next table mutation.
        Returns the padded-row-count array. With ``extra_rows`` > 0 the
        bucket rows [vocab_size, num_rows) have nonzero norms — only
        rows past ``num_rows`` (padding) are guaranteed zero; query ops
        exclude non-vocab rows by index, not by norm."""
        if self._norms_cache is None:
            self._norms_cache = self._norms(self.syn0)
        return self._norms_cache

    def _pad_query(self, v: np.ndarray) -> jnp.ndarray:
        """Pad a (d,) or (Q, d) query to the tables' ``padded_dim`` (zero
        columns contribute zero to every dot product)."""
        pad = self.padded_dim - self.dim
        if pad:
            widths = [(0, 0)] * (v.ndim - 1) + [(0, pad)]
            v = np.pad(v, widths)
        return jnp.asarray(v)

    def multiply(self, vec) -> jax.Array:
        """Distributed matvec syn0 @ vec (Glint ``multiply``, mllib:598)."""
        v = np.asarray(vec, dtype=np.float32)
        if v.shape != (self.dim,):
            raise ValueError(f"vec must have shape ({self.dim},)")
        return self._multiply(self.syn0, self._pad_query(v))

    def top_k_cosine(self, vec, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """On-device distributed top-k by cosine similarity against syn0.

        Returns (similarities, indices), padded rows excluded by their zero
        norm. The query is normalized here (the reference normalizes with
        BLAS snrm2/sscal before ``multiply``, mllib:593-595)."""
        if not 0 < k <= self.padded_vocab:
            raise ValueError(f"k must be in [1, {self.padded_vocab}]")
        v = np.asarray(vec, dtype=np.float32)
        if v.shape != (self.dim,):
            raise ValueError(f"vec must have shape ({self.dim},)")
        nrm = float(np.linalg.norm(v))
        if nrm > 0:
            v = v / nrm
        # One compiled program per k-BUCKET, not per k: fetch the
        # bucket's top-k (a sorted superset) and truncate. Exact — the
        # global top-k is the prefix of the global top-k_bucket.
        k_b = self._k_bucket(k)
        if k_b not in self._topk_cache:
            self._topk_cache[k_b] = self._make_topk(k_b)
        self._count_query_shape("topk", k_b)
        val, idx = self._topk_cache[k_b](
            self.syn0, self._pad_query(v), self.norms(),
            jnp.int32(self.queryable_rows),
        )
        return np.asarray(val)[:k], np.asarray(idx)[:k]

    def _put_replicated(self, a: np.ndarray) -> jax.Array:
        """A host block on every device of the mesh, as the query programs
        take their replicated arguments. Always the same placement, so a
        program warmed with one block runs the next without a new
        executable."""
        return jax.device_put(a, NamedSharding(self.mesh, P()))

    @staticmethod
    def _query_ids(ids, q_b: int) -> np.ndarray:
        """A batch's query ids padded with -1 to its Q bucket, as the
        batch top-k takes them: an id >= 0 names the row of syn0 that IS
        the query, -1 a query the host sends as a vector. A host block:
        its few bytes go to the device inside the program's launch, with
        no dispatch of their own."""
        block = np.full(q_b, -1, np.int32)
        if ids is not None:
            block[: len(ids)] = ids
        return block

    def _query_block(self, vecs, q_b: int) -> jax.Array:
        """The ``(q_b, padded_dim)`` block the batch top-k reads where an
        id is -1: ``vecs`` normalized (the reference normalizes with BLAS
        snrm2/sscal before ``multiply``, mllib:593-595), zero rows and
        columns up to the bucket and the tables' resting width. With no
        vector to send it is a block of zeros that stays on the device."""
        if vecs is None:
            if q_b not in self._zero_queries:
                self._zero_queries[q_b] = self._put_replicated(
                    np.zeros((q_b, self.padded_dim), np.float32)
                )
            return self._zero_queries[q_b]
        nrm = np.linalg.norm(vecs, axis=1, keepdims=True)
        q = np.zeros((q_b, self.padded_dim), np.float32)
        q[: vecs.shape[0], : self.dim] = vecs / np.where(nrm > 0, nrm, 1.0)
        return self._put_replicated(q)

    def top_k_cosine_batch(
        self, vecs, k: int, ids=None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Batched :meth:`top_k_cosine`: (Q, d) queries -> ((Q, k) sims,
        (Q, k) indices) in one distributed dispatch. The batch analogue of
        the reference's findSynonyms(Array) delegation loop
        (ml:375-420), scored as one sharded matmul per call.

        ``ids`` (Q row ids) says which queries are rows of syn0: the
        program gathers and normalizes those itself, so they never visit
        the host, and reads ``vecs`` only where an id is -1. With every
        id >= 0 ``vecs`` may be None."""
        if not 0 < k <= self.padded_vocab:
            raise ValueError(f"k must be in [1, {self.padded_vocab}]")
        if vecs is not None:
            vecs = np.asarray(vecs, dtype=np.float32)
            if vecs.ndim != 2 or vecs.shape[1] != self.dim:
                raise ValueError(f"vecs must have shape (Q, {self.dim})")
        elif ids is None:
            raise ValueError("top_k_cosine_batch needs vecs or ids")
        n = vecs.shape[0] if ids is None else len(ids)
        if vecs is not None and vecs.shape[0] != n:
            raise ValueError(f"vecs has {vecs.shape[0]} rows for {n} ids")
        kk = min(k, self.padded_vocab)
        if n == 0:
            empty = np.zeros((0, kk))
            return empty.astype(np.float32), empty.astype(np.int64)
        k_b = self._k_bucket(k)
        if k_b not in self._topk_batch_cache:
            self._topk_batch_cache[k_b] = self._make_topk_batch(k_b)
        # Pad Q up to its bucket (power of two, floored at
        # TOPK_MIN_Q_BUCKET) so concurrency jitter (every distinct
        # coalesced batch size) maps onto a small compiled family.
        # The padding rows (id -1, a zero vector) score 0 for real words
        # and are sliced off; they can never perturb a real row's top-k
        # (each query row ranks independently).
        q_b = self._q_bucket(n)
        self._count_query_shape("topk_batch", q_b, k_b)
        # The launch and the read-back are two spans of a served round
        # (no-ops without a recorder): the first is what the host pays to
        # put the program on the device's queue, the second the device's
        # run, the transfer and this thread's turn at the interpreter
        # lock.
        with obs_events.phase_span(
            "req.enqueue", program="topk_batch", q=q_b,
            shards=self.num_model,
        ):
            val, idx = self._topk_batch_cache[k_b](
                self.syn0, self._query_block(vecs, q_b),
                self._query_ids(ids, q_b), self.norms(),
                np.int32(self.queryable_rows),
            )
        self.query_enqueued_at = time.perf_counter()
        with obs_events.phase_span("req.result", program="topk_batch"):
            val, idx = np.asarray(val), np.asarray(idx)
        return val[:n, :kk], idx[:n, :kk]

    # ------------------------------------------------------------------
    # Approximate top-k (device-resident ANN index, ISSUE 12)
    # ------------------------------------------------------------------

    def configure_ann(
        self,
        *,
        clusters: int = -1,
        nprobe: int = 8,
        iters: int = 6,
        sample: int = 65536,
    ) -> dict:
        """Fix the coarse-index geometry for this engine. ``clusters``
        -1 picks ``ops.ann.auto_clusters`` (≈ next_pow2(√rows) — the
        O(√V·d) operating point); the member-slot count follows from
        the engine's FULL row capacity, so streaming growth and every
        later rebuild share one compiled shape family. Returns the
        resolved geometry."""
        from glint_word2vec_tpu.ops import ann as _ann

        clusters = int(clusters)  # graftlint: ignore[sync-point] host config scalar
        nprobe = int(nprobe)  # graftlint: ignore[sync-point] host config scalar
        iters = int(iters)  # graftlint: ignore[sync-point] host config scalar
        sample = int(sample)  # graftlint: ignore[sync-point] host config scalar
        C = clusters if clusters > 0 else _ann.auto_clusters(self.num_rows)
        self._ann_conf = {
            "clusters": C,
            "slots": _ann.member_slots(self.num_rows, C),
            "nprobe": max(1, min(nprobe, C)),
            "iters": max(1, iters),
            "sample": max(1, sample),
        }
        return dict(self._ann_conf)

    @property
    def ann_index(self):
        """The adopted live index, or None."""
        return getattr(self, "_ann", None)

    def ann_build(self, syn0=None, norms=None, queryable=None):
        """Build a coarse index (k-means centroids + packed member
        layout) from ``syn0`` — the LIVE table by default, or a STAGED
        generation's (pass its arrays) so a hot-swap can prepare the
        index entirely off the request path. Returns the index WITHOUT
        adopting it; flip it live with :meth:`adopt_ann` (the serving
        swap does both under one device-lock hold). Requires
        :meth:`configure_ann` first."""
        from glint_word2vec_tpu.ops import ann as _ann

        conf = getattr(self, "_ann_conf", None)
        if conf is None:
            raise RuntimeError("call configure_ann() before ann_build()")
        if syn0 is None:
            syn0 = self.syn0
            norms = self.norms()
            queryable = self.queryable_rows
        elif norms is None:
            norms = self._norms(syn0)
        if queryable is None:
            queryable = self.queryable_rows
        queryable = int(queryable)  # graftlint: ignore[sync-point] host row-count scalar
        return _ann.build(
            syn0,
            norms,
            queryable,
            clusters=conf["clusters"],
            iters=conf["iters"],
            sample=conf["sample"],
            seed=self._seed,
            table_version=self.table_version,
            num_rows=self.num_rows,
            sharding=NamedSharding(self.mesh, P()),
        )

    def adopt_ann(self, index) -> None:
        """Flip the live coarse index: one attribute assignment — the
        serving hot-swap pairs it with :meth:`adopt_tables` under the
        same device-lock hold so tables and index always flip together.
        ``None`` disables the approximate path."""
        self._ann = index
        if index is not None:
            index.table_version = self.table_version

    def ann_stats(self) -> dict:
        """Index telemetry for the serving ``index_*`` family; safe to
        call with no index (reports disabled)."""
        idx = self.ann_index
        if idx is None:
            return {"enabled": False}
        st = idx.stats()
        st["enabled"] = True
        st["nprobe"] = self._ann_conf["nprobe"]
        st["table_versions_behind"] = max(
            0, self.table_version - idx.table_version
        )
        return st

    def ann_top_k_batch(
        self, vecs, k: int, nprobe: Optional[int] = None, *, index=None,
        queryable=None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Approximate :meth:`top_k_cosine_batch` through the coarse
        index: coarse centroid scores pick ``nprobe`` clusters per
        query, exact masked rerank inside their padded member-row
        blocks. Same bucketing contract as the exact path (Q padded to
        its power-of-two bucket capped at ``ANN_MAX_Q``, k rounded to
        its bucket and truncated), so serving concurrency jitter rides
        one small warmed family. The search reads ONLY the index (the
        member blocks are a copy of the index's source table), so
        ``index``/``queryable`` overrides run staged-generation recall
        checks on the very same compiled programs the live path uses."""
        from glint_word2vec_tpu.ops import ann as _ann

        idx = index if index is not None else self.ann_index
        if idx is None:
            raise RuntimeError("no ANN index adopted (ann_build/adopt_ann)")
        if queryable is None:
            queryable = self.queryable_rows
        if nprobe is None:
            nprobe = self._ann_conf["nprobe"]
        nprobe = max(1, min(int(nprobe), idx.clusters))
        if not 0 < k <= self.padded_vocab:
            raise ValueError(f"k must be in [1, {self.padded_vocab}]")
        if k > nprobe * idx.slots:
            # The probed slots cannot hold k candidates — a silent
            # truncation would diverge from the exact path with no
            # signal. Callers (the model layer) route oversized k to
            # the exact path instead.
            raise ValueError(
                f"k={k} exceeds the index's probe capacity "
                f"({nprobe} probes x {idx.slots} slots); raise nprobe "
                "or use the exact path"
            )
        q = np.asarray(vecs, dtype=np.float32)
        if q.ndim != 2 or q.shape[1] != self.dim:
            raise ValueError(f"vecs must have shape (Q, {self.dim})")
        nrm = np.linalg.norm(q, axis=1, keepdims=True)
        q = q / np.where(nrm > 0, nrm, 1.0)
        kk = min(k, self.padded_vocab)
        if q.shape[0] == 0:
            empty = np.zeros((0, kk))
            return empty.astype(np.float32), empty.astype(np.int64)
        k_b = min(self._k_bucket(k), nprobe * idx.slots)  # bucket pad only
        vals, idxs = [], []
        for s in range(0, q.shape[0], ANN_MAX_Q):
            qc = q[s : s + ANN_MAX_Q]
            n = qc.shape[0]
            q_b = min(self._q_bucket(n), ANN_MAX_Q)
            if q_b != n:
                qc = np.concatenate(
                    [qc, np.zeros((q_b - n, qc.shape[1]), np.float32)]
                )
            fn = _ann._search_fn(
                q_b, k_b, nprobe, idx.clusters, idx.slots, idx.dim
            )
            self._count_query_shape("ann_topk", q_b, k_b, nprobe)
            val, ids = fn(
                idx.member_rows, idx.centroids, idx.members,
                idx.member_invn, self._pad_query(qc),
                jnp.int32(queryable),
            )
            vals.append(np.asarray(val)[:n, :kk])
            idxs.append(np.asarray(ids)[:n, :kk])
        return np.concatenate(vals), np.concatenate(idxs)

    def warmup_ann(self, q_buckets=(1, 8, ANN_MAX_Q),
                   k_buckets=(TOPK_MIN_K_BUCKET,),
                   nprobes=()) -> int:
        """Compile the approximate dispatch family — coarse score +
        bucketed rerank for every (Q bucket, k bucket, nprobe), plus
        the incremental-assignment program promotions ride — so the
        serving warmup covers the ANN path too and
        ``post_warmup_compiles`` stays 0 (ISSUE 12 satellite). Requires
        an adopted index."""
        from glint_word2vec_tpu.ops import ann as _ann

        idx = self.ann_index
        if idx is None:
            raise RuntimeError("adopt an index before warmup_ann()")
        before = self.query_compiles
        # Buckets arrive as host int tuples from the serving warmup.
        nps = sorted(
            {max(1, min(p, idx.clusters))
             for p in (*nprobes, self._ann_conf["nprobe"])}
        )
        d = self.dim
        for p in nps:
            for q in sorted(
                {min(self._q_bucket(q), ANN_MAX_Q)
                 for q in q_buckets}
            ):
                for k in sorted(
                    {self._k_bucket(k) for k in k_buckets}
                ):
                    self.ann_top_k_batch(
                        np.zeros((q, d), np.float32), k, p
                    )
        # The promotion path's fixed-chunk assignment program.
        _ann._score_fn(
            _ann.INCREMENTAL_BLOCK, idx.clusters, idx.dim
        )(
            self.syn0, self.norms(),
            jnp.zeros(_ann.INCREMENTAL_BLOCK, jnp.int32),
            idx.centroids,
        )
        return self.query_compiles - before

    def ann_recall_at_k(
        self, k: int = 10, sample: int = 64, nprobe: Optional[int] = None,
        *, index=None, syn0=None, norms=None, queryable=None,
        q_chunk: int = 64,
    ) -> float:
        """Measured recall@k of the approximate path against the exact
        path on the SAME tables (live by default; pass a staged
        generation's arrays to gate a hot-swap before adopting it).
        Queries are ``sample`` deterministic table rows; for each, the
        exact and approximate top-(k+1) sets are compared with the
        query row itself excluded — the serving ``/synonyms``
        semantics. Both sides ride the already-warmed bucketed
        programs (``q_chunk`` should be the serving max_batch), so a
        post-warmup recall check never compiles."""
        idx = index if index is not None else self.ann_index
        if idx is None:
            raise RuntimeError("no ANN index adopted")
        if syn0 is None:
            syn0 = self.syn0
            norms = self.norms()
            queryable = self.queryable_rows
        elif norms is None:
            norms = self._norms(syn0)
        if queryable is None:
            queryable = self.queryable_rows
        queryable = int(queryable)
        rng = np.random.default_rng(self._seed)
        n_q = min(int(sample), queryable)
        if n_q == 0:
            return 1.0
        qids = rng.choice(queryable, n_q, replace=False).astype(np.int32)
        qvecs = np.asarray(
            syn0[jnp.asarray(qids)].astype(jnp.float32)
        )[:, : self.dim]
        live = np.linalg.norm(qvecs, axis=1) > 0
        if not live.any():
            return 1.0
        qids, qvecs = qids[live], qvecs[live]
        k_b = self._k_bucket(k + 1)
        if k_b not in self._topk_batch_cache:
            self._topk_batch_cache[k_b] = self._make_topk_batch(k_b)
        exact_fn = self._topk_batch_cache[k_b]
        hits = 0
        total = 0
        for s in range(0, qids.shape[0], q_chunk):
            qc = qvecs[s : s + q_chunk]
            ic = qids[s : s + q_chunk]
            n = qc.shape[0]
            q_b = self._q_bucket(n)
            self._count_query_shape("topk_batch", q_b, k_b)
            ex_val, ex_idx = exact_fn(
                syn0, self._query_block(qc, q_b),
                self._query_ids(None, q_b), norms, np.int32(queryable),
            )
            ex_val = np.asarray(ex_val)[:n]
            ex_idx = np.asarray(ex_idx)[:n]
            ap_val, ap_idx = self.ann_top_k_batch(
                qc, k + 1, nprobe, index=idx, queryable=queryable,
            )
            for row in range(n):
                # -inf entries are masked filler (padding rows, empty
                # member slots) surfacing only when fewer than k+1 rows
                # are queryable — they are NOT results on either side.
                ex = [
                    int(i) for i, v in zip(ex_idx[row], ex_val[row])
                    if np.isfinite(v) and int(i) != int(ic[row])
                ]
                ap = {
                    int(i) for i, v in zip(ap_idx[row], ap_val[row])
                    if np.isfinite(v) and int(i) != int(ic[row])
                }
                want = ex[:k]
                hits += len(set(want) & ap)
                total += len(want)
        return hits / max(1, total)

    def warmup(
        self,
        q_buckets=(1, 2, 4, 8, 16, 32, 64),
        k_buckets=(TOPK_MIN_K_BUCKET,),
        *,
        sentence_lens=(),
        sentence_rows=(1,),
    ) -> int:
        """Compile the query-op shape family up front so no real request
        ever pays a jit compile (the serving warmup entry point, ISSUE 2).

        Exercises ``pull`` and ``top_k_cosine_batch`` for every Q bucket,
        ``top_k_cosine`` for every k bucket, and — when ``sentence_lens``
        is given — ``pull_average`` for the (rows, len) sentence grid.
        Buckets are quantized exactly as the query ops quantize real
        requests, so a warmed bucket can never re-compile. Returns the
        number of shapes this call compiled (0 = already warm)."""
        before = self.query_compiles
        d = self.dim
        ks = sorted({self._k_bucket(int(k)) for k in k_buckets})
        for k in ks:
            self.top_k_cosine(np.zeros(d, np.float32), k)
        for q in sorted({next_pow2(int(q)) for q in q_buckets}):
            self.pull(np.zeros(q, np.int32))
        for q in sorted({self._q_bucket(int(q)) for q in q_buckets}):
            # by ids, so that the zero block of an all-ids round is on
            # the device too; a round that sends vectors runs the same
            # program
            for k in ks:
                self.top_k_cosine_batch(None, k, ids=np.zeros(q, np.int32))
        for s in sorted({next_pow2(int(s)) for s in sentence_rows}):
            for L in sorted({next_pow2(int(L)) for L in sentence_lens}):
                self.pull_average(
                    np.zeros((s, L), np.int32),
                    np.zeros((s, L), np.float32),
                )
        return self.query_compiles - before

    # ------------------------------------------------------------------
    # Persistence / lifecycle
    # ------------------------------------------------------------------

    def save(self, path: str, mode: str = "sharded") -> None:
        """Write both matrices + engine metadata (Glint ``matrix.save``,
        mllib:494 — each server flushing its shard to HDFS becomes each
        mesh slice flushing its row block). Blocks until committed.

        ``mode="sharded"`` (default) writes one ``.npy`` per owned model-axis
        row block — no host ever materializes a full table (the save-side
        analogue of killing the 8 GB broadcast ceiling, README.md:71-73),
        and under multi-host each process writes only its addressable
        shards. ``mode="single"`` writes one full-table file (handy for
        small models / interop). Both re-load onto any mesh shape.

        Crash safety (single-process): a fresh ``path`` is written as a
        temp directory and committed with one atomic rename — a kill
        mid-write leaves only an unreferenced ``*.tmp-*`` directory; an
        existing ``path`` is updated per-file via temp + ``os.replace``
        with the ``engine.json`` manifest written last. Multi-host keeps
        the legacy in-place protocol (every process writes disjoint
        shard files; the fit loop's barrier + ``train_state.json`` flip
        is the commit point there).
        """
        if jax.process_count() > 1:
            return self._save_multihost(path, mode)
        # Blocking path: views of the live tables are safe to serialize
        # directly — no donating dispatch can run until this returns —
        # so skip the deep copy (and its transient 2x host memory). In
        # sharded mode the blocks are LAZY (ISSUE 15 shard streaming):
        # each is copied to host, written, hashed into its sidecar
        # manifest, and dropped before the next one materializes — peak
        # host memory is one shard, never one table.
        files, meta = self._snapshot_host(
            self.tables(), mode, deep_copy=False, lazy=(mode == "sharded"),
        )
        self._write_snapshot(path, files, meta,
                             table_version=self.table_version)
        if mode == "sharded":
            # Only the blocks THIS engine serialized become clean —
            # under a replica save split the manifest names every
            # rank's blocks, but this rank vouches only for its own.
            shard_set = {
                b["file"] for t in meta["shards"].values() for b in t
            }
            self._mark_shards_clean(path, [
                fname for fname, _ in files if fname in shard_set
            ])

    # -- non-blocking checkpointing (ISSUE 5) ---------------------------

    def async_saves_enabled(self) -> bool:
        """Whether :meth:`save_async` will actually run non-blocking:
        single-process only (multi-host saves need the cross-process
        barrier before the state flip) and not escape-hatched by
        ``GLINT_SYNC_CKPT=1`` (README "Checkpointing")."""
        return (
            jax.process_count() == 1
            and os.environ.get("GLINT_SYNC_CKPT", "0") != "1"
        )

    def save_async(self, path: str, mode: str = "sharded",
                   on_commit=None) -> bool:
        """Non-blocking :meth:`save`: snapshot the (donation-cycled)
        tables to host memory — the device->host copy is the ONLY work
        on the calling thread — then hand serialization + atomic commit
        to the single background writer thread (utils/async_ckpt.py).
        At most one snapshot is in flight — a second request blocks for
        the first (counted in ``async_save_waits``). ``on_commit`` runs
        on the writer thread strictly AFTER the snapshot directory is
        committed (the fit loops flip ``train_state.json`` there), so a
        crash mid-write can never dangle the manifest. Falls back to a
        blocking save (returning False) under multi-host or
        ``GLINT_SYNC_CKPT=1``."""
        if not self.async_saves_enabled():
            self.save(path, mode)
            self._ckpt_forced_sync += 1
            if on_commit is not None:
                on_commit()
            return False
        if self._ckpt_writer is None:
            from glint_word2vec_tpu.utils.async_ckpt import (
                AsyncSnapshotWriter,
            )

            self._ckpt_writer = AsyncSnapshotWriter()
        writer = self._ckpt_writer
        # Block for any in-flight snapshot BEFORE materializing this one
        # (counted as back-pressure): transient host memory stays
        # bounded to one extra table pair.
        writer.wait_for_slot()
        files, meta = self._snapshot_host(self.tables(), mode)
        tv = self.table_version

        def job():
            with obs_events.span("ckpt_write", ckpt=path):
                self._write_snapshot(path, files, meta, table_version=tv)
                if on_commit is not None:
                    on_commit()

        writer.submit(job, label=path)
        return True

    def wait_pending_saves(self, *, reraise: bool = True,
                           timeout=None) -> None:
        """Barrier: block until no async save is in flight. The fit
        loops run it at fit exit (and implicitly before every state
        flip, since commits are ordered through the single writer);
        ``reraise=False`` is the exception-path variant that must not
        mask the original failure. ``timeout`` (seconds) raises
        ``utils.async_ckpt.SnapshotWriterHung`` naming the stuck job
        instead of hanging fit exit forever on a dead filesystem."""
        if self._ckpt_writer is not None:
            self._ckpt_writer.wait(reraise=reraise, timeout=timeout)

    def checkpoint_stats(self) -> dict:
        """Checkpoint telemetry for the heartbeat / serving snapshots:
        ``pending_async_saves`` (0/1), ``async_save_waits`` (blocked
        second requests — checkpoint back-pressure),
        ``checkpoint_write_seconds`` (last write job wall time),
        ``last_checkpoint_age_seconds`` (since the last commit, sync or
        async; None before any), ``forced_sync_saves``."""
        w = self._ckpt_writer
        last_write = self._ckpt_last_write_s
        last_commit = self._ckpt_last_commit
        ws = w.stats() if w is not None else {}
        if ws.get("last_write_seconds") is not None:
            last_write = ws["last_write_seconds"]
        if ws.get("last_commit_time") is not None:
            last_commit = max(last_commit or 0.0, ws["last_commit_time"])
        return {
            "pending_async_saves": int(ws.get("pending", 0)),
            "async_save_waits": int(ws.get("blocked_waits", 0)),
            "checkpoint_write_seconds": (
                round(last_write, 4) if last_write is not None else None
            ),
            "last_checkpoint_age_seconds": (
                round(time.time() - last_commit, 2)
                if last_commit else None
            ),
            "forced_sync_saves": self._ckpt_forced_sync,
            # Shard-streaming checkpoint telemetry (ISSUE 15): seconds
            # spent writing/verifying table shard blocks in the most
            # recent save/stage, in-place shards skipped as clean, and
            # the save path's peak concurrently-live host block bytes
            # (the bounded-by-one-shard contract, tests assert it).
            "checkpoint_shard_write_seconds": self._ckpt_shard_write_s,
            "checkpoint_shard_verify_seconds": self._ckpt_shard_verify_s,
            "checkpoint_shards_skipped": int(self._ckpt_shards_skipped),  # graftlint: ignore[sync-point] host counter
            "checkpoint_peak_block_bytes": int(  # graftlint: ignore[sync-point] host counter
                self._ckpt_peak_block_bytes
            ),
        }

    def _snapshot_host(self, tables: dict, mode: str, *,
                       deep_copy: bool = True, lazy: bool = False):
        """Blocking device->host snapshot of the given :meth:`tables`:
        returns ``(files, meta)`` where ``files`` is a list of
        ``(filename, ndarray)`` blocks and ``meta`` the ``engine.json``
        manifest dict. With ``deep_copy`` (the async path) every block
        is a DEEP host copy — the live tables may be donated to the next
        dispatch the moment the caller resumes, and a zero-copy
        CPU-backend view of a donated buffer would read garbage; the
        copies run on a small thread pool (numpy releases the GIL for
        the memcpy) and their latency is the async checkpoint pause.
        ``deep_copy=False`` (the blocking save, which serializes before
        returning) keeps the views and skips the extra table-pair of
        transient host memory. ``lazy`` (blocking sharded saves only)
        defers each block to a zero-arg callable the writer materializes
        one at a time — the shard-streaming path whose peak host memory
        is ONE block (ISSUE 15); incompatible with ``deep_copy`` (an
        async snapshot must copy before the tables are donated)."""
        files = []
        if lazy and mode == "sharded" and not deep_copy:
            # Same ownership iteration as the materialized path (this
            # matters under a replica save split: each rank serializes
            # ONLY its own row block), just deferred: each producer
            # copies its one block at write time.
            shard_files = self._shard_manifest()
            for name, table in tables.items():
                for fname, produce in self._iter_owned_block_producers(
                    name, table
                ):
                    files.append([
                        fname,
                        lambda p=produce: np.asarray(
                            p(), dtype=np.float32
                        ),
                    ])
        elif mode == "sharded":
            shard_files = self._shard_manifest()
            for name, table in tables.items():
                for fname, block in self._iter_owned_blocks(name, table):
                    files.append([fname, block])
        elif mode == "single":
            for name, table in tables.items():
                files.append([
                    f"{name}.npy",
                    np.asarray(table)[
                        : self._table_layout(name)[0], : self.dim
                    ],
                ])
        else:
            raise ValueError("mode must be 'sharded' or 'single'")
        if deep_copy:
            # Deep-copy every block in parallel: np.asarray above may be
            # a zero-copy view of the live device buffer on the CPU
            # backend.
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(
                max_workers=min(max(len(files), 1), 8),
                thread_name_prefix="glint-snap",
            ) as pool:
                for entry, copied in zip(
                    files,
                    pool.map(
                        lambda e: np.array(e[1], dtype=np.float32), files
                    ),
                ):
                    entry[1] = copied
        else:
            # Cast-only (no copy for f32 tables): the blocking caller
            # serializes before any donating dispatch can run. Lazy
            # blocks cast inside their own producer.
            for entry in files:
                if not callable(entry[1]):
                    entry[1] = np.asarray(entry[1], dtype=np.float32)
        files = [tuple(e) for e in files]
        files.append(
            ("counts.npy", np.asarray(self._counts_unpadded(), np.int64))
        )
        meta = self._save_meta(mode)
        if mode == "sharded":
            meta["shards"] = shard_files
        return files, meta

    def set_save_split(self, rank: int, world: int) -> None:
        """Configure the replica save split (ISSUE 15): sharded saves
        slice the (replicated) tables into ``world`` row blocks and this
        engine writes only block ``rank`` — N replica ranks checkpoint
        one table in parallel, each copying/hashing 1/N of it.
        ``world == 1`` clears the split."""
        if world <= 1:
            self._save_split = None
            return
        if not 0 <= rank < world:
            raise ValueError(f"rank {rank} not in [0, {world})")
        self._save_split = (int(rank), int(world))  # graftlint: ignore[sync-point] host config
        self._shard_dirty = None  # file geometry changed: all dirty

    def _save_block_rows(self, name: str = "syn0") -> int:
        """Rows a block of the sharded save holds — the one place the
        manifest and the block producers agree on. Under a replica save
        split the block size comes from the split world, not the mesh
        model axis (every rank addresses every row). A table that
        :meth:`_table_layout` rests whole on every device is one block."""
        _, padded_rows, sharding, _ = self._table_layout(name)
        if not any(sharding.spec):  # split over no axis of the mesh
            return padded_rows
        if self._save_split is not None:
            _, world = self._save_split
            return max(1, -(-self.padded_vocab // world))
        return self.rows_per_shard

    def _shard_manifest(self) -> dict:
        """Deterministic (mesh-geometry-only) shard-file manifest shared
        by the single-process snapshot and the multi-host in-place save
        — identical producers, so checkpoints from either path re-load
        interchangeably."""
        shard_files = {name: [] for name in self.table_names}
        for name in self.table_names:
            per_shard = self._save_block_rows(name)
            rows = self._table_layout(name)[0]
            for k in range(-(-rows // per_shard)):
                start = k * per_shard
                stop = min(start + per_shard, rows)
                if start >= stop:
                    continue  # pure-padding block
                shard_files[name].append({
                    "file": f"{name}.r{start:012d}.npy",
                    "start": start, "stop": stop, "axis": "rows",
                })
        return shard_files

    def _iter_owned_block_producers(self, name: str, table):
        """Yield ``(fname, producer)`` for every shard block this
        process owns — ``producer()`` materializes the host copy, so a
        caller can decide per shard whether to pay it (the skip-clean
        path never does). Ownership: replica 0 of each mesh-addressed
        block once, or — under a replica save split
        (:meth:`set_save_split`, tables replicated across ranks) — the
        rank's own row block, device-sliced so no producer ever copies
        more than one block."""
        per_shard = self._save_block_rows(name)
        rows = self._table_layout(name)[0]
        if self._save_split is not None:
            rank, world = self._save_split
            start = rank * per_shard
            stop = min(start + per_shard, rows)
            if start < stop:
                yield (
                    f"{name}.r{start:012d}.npy",
                    lambda: np.asarray(table[start:stop, : self.dim]),
                )
            return
        for shard in table.addressable_shards:
            if shard.replica_id != 0:
                continue
            start = shard.index[0].start or 0
            if start >= rows:
                continue
            stop = min(start + per_shard, rows)

            def produce(shard=shard, start=start, stop=stop):
                return np.asarray(shard.data)[: stop - start]

            yield f"{name}.r{start:012d}.npy", produce

    def _iter_owned_blocks(self, name: str, table):
        """Materialized form of :meth:`_iter_owned_block_producers`:
        yields ``(fname, block)``. Blocks may be zero-copy views of the
        device buffers — callers that outlive the next donating
        dispatch must deep-copy."""
        for fname, produce in self._iter_owned_block_producers(
            name, table
        ):
            yield fname, produce()

    def _save_meta(self, mode: str) -> dict:
        return {
            "format": mode,
            "vocab_size": self.vocab_size,
            "dim": self.dim,
            "num_negatives": self.num_negatives,
            "unigram_power": self.unigram_power,
            "unigram_table_size": self.unigram_table_size,
            "extra_rows": self.num_rows - self.vocab_size,
            "extra_rows_assigned": self.extra_rows_assigned,
            "dtype": (
                "bfloat16" if self._dtype == jnp.bfloat16 else "float32"
            ),
            "shared_negatives": self.shared_negatives,
            "architecture": self.architecture,
            # Rows of the position table saved beside the two; a snapshot
            # without the key (every one before PR 54) holds none.
            "position_lanes": self.position_lanes,
        }

    def _write_snapshot(self, path: str, files, meta: dict,
                        table_version=None) -> None:
        """Serialize a host snapshot to disk with a crash-safe commit.

        Fresh ``path`` (every checkpoint dir): everything lands in a
        sibling temp directory first — each file fsync'd, so the rename
        can never commit a checkpoint whose bytes are still only in the
        page cache (a power loss after the rename must not roll the
        DATA back) — plus a ``manifest.json`` (per-file sha256 + sizes +
        ``table_version``, utils/integrity.py) so the committed
        directory is verifiable end to end — then ONE atomic rename
        makes the whole snapshot appear, followed by a parent-directory
        fsync to make the rename itself durable. A kill at any earlier
        point leaves only an unreferenced ``*.tmp-*`` directory (pruned
        by the next state flip). ``GLINT_CKPT_NO_FSYNC=1`` skips the
        fsyncs (fast local scratch / tests). Existing ``path``
        (re-saving a model dir in place): each file goes through temp +
        ``os.replace`` with ``engine.json`` after the data files and the
        integrity manifest last, so no file is ever truncated."""
        from glint_word2vec_tpu.utils import faults, integrity

        t0 = time.time()
        fsync = os.environ.get("GLINT_CKPT_NO_FSYNC", "0") != "1"
        # Table shard files get per-shard sidecar manifests (ISSUE 15)
        # and may arrive as LAZY zero-arg producers: materialize one,
        # write it, hash it, drop it — the shard-streaming memory bound
        # checkpoint_stats reports as ckpt_peak_block_bytes.
        shard_set = {
            b["file"] for t in (meta.get("shards") or {}).values()
            for b in t
        }
        eager_bytes = sum(
            a.nbytes for _, a in files if not callable(a)
        )
        peak = eager_bytes
        t_shards = 0.0

        def _emit(dirpath, fname, arr) -> None:
            nonlocal peak, t_shards
            ts = time.time()
            with open(os.path.join(dirpath, fname), "wb") as f:
                np.save(f, arr)
                if fsync:
                    f.flush()
                    os.fsync(f.fileno())
            if fname in shard_set:
                integrity.write_shard_manifest(
                    dirpath, fname,
                    integrity.build_shard_manifest(
                        dirpath, fname, table_version
                    ),
                    fsync=fsync,
                )
                faults.fire("ckpt.shard_commit")
                t_shards += time.time() - ts

        if not os.path.exists(path):
            tmp = f"{path}.tmp-{os.getpid()}"
            if os.path.exists(tmp):
                import shutil

                shutil.rmtree(tmp, ignore_errors=True)
            os.makedirs(tmp)
            for fname, arr in files:
                if callable(arr):
                    arr = arr()
                    peak = max(peak, eager_bytes + arr.nbytes)
                _emit(tmp, fname, arr)
                del arr
            with open(os.path.join(tmp, "engine.json"), "w") as f:
                json.dump(meta, f)
                if fsync:
                    f.flush()
                    os.fsync(f.fileno())
            integrity.write_manifest(
                tmp,
                integrity.build_manifest(
                    tmp,
                    [
                        fname for fname, _ in files
                        if fname not in shard_set
                    ] + ["engine.json"],
                    table_version,
                    table_dtype=meta.get("dtype"),
                ) | (
                    {"version": 2, "shard_files": sorted(shard_set)}
                    if shard_set else {}
                ),
                fsync=fsync,
            )
            if fsync:
                # The dirents too, not just the file data: fsync(file)
                # alone need not persist the entry in its directory.
                self._fsync_dir(tmp)
            faults.fire("ckpt.pre_rename")
            self._commit_snapshot_dir(tmp, path)
            faults.fire("ckpt.post_rename")
            if fsync:
                self._fsync_dir(os.path.dirname(os.path.abspath(path)))
        else:
            # In-place update (model re-save over an existing dir, or
            # re-writing an orphaned checkpoint dir after a crash):
            # per-file temp + replace — every file is always either the
            # old or the new complete version — with the same fsync
            # durability as the fresh-dir path, and the engine.json
            # manifest last.
            def _put(fname, writer_fn):
                tmp_f = os.path.join(path, f"{fname}.tmp.{os.getpid()}")
                with open(tmp_f, "wb") as f:
                    writer_fn(f)
                    if fsync:
                        f.flush()
                        os.fsync(f.fileno())
                os.replace(tmp_f, os.path.join(path, fname))

            for fname, arr in files:
                # Skip-clean fast path (ISSUE 15 satellite): an
                # in-place re-save never copies or rewrites a shard the
                # last committed save to this path already holds —
                # ranks whose shards are all clean pay zero host-copy
                # time on the caller thread.
                if (
                    fname in shard_set
                    and not self._shard_is_dirty(fname, path)
                    and os.path.exists(os.path.join(path, fname))
                    and os.path.exists(os.path.join(
                        path, fname + integrity.SHARD_MANIFEST_SUFFIX
                    ))
                ):
                    self._ckpt_shards_skipped += 1
                    continue
                if callable(arr):
                    arr = arr()
                    peak = max(peak, eager_bytes + arr.nbytes)
                ts = time.time()
                _put(fname, lambda f, a=arr: np.save(f, a))
                if fname in shard_set:
                    integrity.write_shard_manifest(
                        path, fname,
                        integrity.build_shard_manifest(
                            path, fname, table_version
                        ),
                        fsync=fsync,
                    )
                    faults.fire("ckpt.shard_commit")
                    t_shards += time.time() - ts
                del arr
            _put(
                "engine.json",
                lambda f: f.write(json.dumps(meta).encode()),
            )
            integrity.write_manifest(
                path,
                integrity.build_manifest(
                    path,
                    [
                        fname for fname, _ in files
                        if fname not in shard_set
                    ] + ["engine.json"],
                    table_version,
                    table_dtype=meta.get("dtype"),
                ) | (
                    {"version": 2, "shard_files": sorted(shard_set)}
                    if shard_set else {}
                ),
                fsync=fsync,
            )
            if fsync:
                self._fsync_dir(os.path.abspath(path))
        self._ckpt_last_write_s = time.time() - t0
        self._ckpt_last_commit = time.time()
        self._ckpt_shard_write_s = round(t_shards, 6)
        self._ckpt_peak_block_bytes = int(peak)  # graftlint: ignore[sync-point] host counter

    @staticmethod
    def _fsync_dir(dirpath: str) -> None:
        """Make renames inside ``dirpath`` durable; best-effort (some
        filesystems refuse directory fsync)."""
        try:
            dfd = os.open(dirpath, os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except OSError:
            pass

    @staticmethod
    def _commit_snapshot_dir(tmp: str, path: str) -> None:
        """THE commit point of a fresh-directory snapshot: one atomic
        rename. Kept as its own (monkeypatchable) seam so the
        crash-mid-checkpoint test can kill the writer between temp-write
        and rename and assert the previous checkpoint survives."""
        os.rename(tmp, path)

    def _save_multihost(self, path: str, mode: str = "sharded") -> None:
        """In-place save for multi-host runs: every process writes its
        own shard files into ``path`` — mesh-addressed blocks on the
        SPMD path, the rank's row block under a replica save split
        (:meth:`set_save_split`) — each with its per-shard sidecar
        manifest (ISSUE 15: integrity without any rank ever seeing the
        whole table); process 0 writes counts + the version-2 top-level
        manifest. Commit/crash-safety is the caller's barrier +
        ``train_state.json`` flip. Clean shards (unchanged since the
        last committed save to this same path) are skipped entirely —
        no host copy, no write (``shards_skipped``)."""
        from glint_word2vec_tpu.utils import faults, integrity

        t0 = time.time()
        os.makedirs(path, exist_ok=True)
        shard_files = {name: [] for name in self.table_names}
        written = []
        t_shards = 0.0
        peak = 0
        if mode == "sharded":
            # The manifest is deterministic from mesh geometry (identical on
            # every process); files are written only by a process that can
            # address the block, each block by exactly one process. Blocks
            # are row ranges.
            shard_files = self._shard_manifest()
            for name, table in self.tables().items():
                for fname, produce in self._iter_owned_block_producers(
                    name, table
                ):
                    if (
                        not self._shard_is_dirty(fname, path)
                        and os.path.exists(os.path.join(path, fname))
                        and os.path.exists(os.path.join(
                            path,
                            fname + integrity.SHARD_MANIFEST_SUFFIX,
                        ))
                    ):
                        self._ckpt_shards_skipped += 1
                        written.append(fname)
                        continue
                    ts = time.time()
                    block = np.asarray(produce(), dtype=np.float32)
                    peak = max(peak, block.nbytes)
                    atomic_write_npy(os.path.join(path, fname), block)
                    del block
                    integrity.write_shard_manifest(
                        path, fname,
                        integrity.build_shard_manifest(
                            path, fname, self.table_version
                        ),
                    )
                    faults.fire("ckpt.shard_commit")
                    t_shards += time.time() - ts
                    written.append(fname)
        else:
            if mode != "single":
                raise ValueError("mode must be 'sharded' or 'single'")
            if jax.process_index() == 0:
                for name, table in self.tables().items():
                    atomic_write_npy(
                        os.path.join(path, f"{name}.npy"),
                        np.asarray(table, dtype=np.float32)[
                            : self._table_layout(name)[0], : self.dim
                        ],
                    )
        if jax.process_index() == 0:
            counts = np.asarray(self._counts_unpadded(), dtype=np.int64)
            atomic_write_npy(os.path.join(path, "counts.npy"), counts)
        meta = self._save_meta(mode)
        if mode == "sharded":
            meta["shards"] = shard_files
        # Multi-host: every process wrote disjoint shard files; exactly one
        # writes the manifest (it is deterministic from mesh geometry).
        # Per-file atomic (temp + replace, engine.json last) so a worker
        # killed mid-save into a previously-committed dir can never leave
        # a torn .npy behind — the in-place twin of the fresh-dir
        # temp+rename commit.
        if jax.process_index() == 0:
            atomic_write_json(os.path.join(path, "engine.json"), meta)
            # Version-2 integrity manifest (ISSUE 15): shard files are
            # named here but hashed by their OWN writers into sidecar
            # manifests, so the multi-host path is finally verifiable —
            # no single writer ever needed to see every shard. Process
            # 0 hashes only the small files it wrote itself. The
            # caller's barrier orders this before any state flip that
            # would make the directory authoritative.
            if mode == "sharded":
                all_shards = sorted(
                    b["file"] for t in shard_files.values() for b in t
                )
                integrity.write_manifest(
                    path,
                    integrity.build_manifest(
                        path, ["counts.npy", "engine.json"],
                        self.table_version,
                        table_dtype=meta.get("dtype"),
                    ) | {"version": 2, "shard_files": all_shards},
                )
            else:
                # Single-file multi-host saves stay manifest-less (one
                # writer, but the shard protocol does not apply); drop
                # any stale manifest a previous save left behind.
                try:
                    os.remove(os.path.join(path, "manifest.json"))
                except OSError:
                    pass
        self._ckpt_last_write_s = time.time() - t0
        self._ckpt_last_commit = time.time()
        self._ckpt_shard_write_s = round(t_shards, 6)
        self._ckpt_peak_block_bytes = int(peak)  # graftlint: ignore[sync-point] host counter
        if mode == "sharded":
            self._mark_shards_clean(path, written)

    def _counts_unpadded(self) -> np.ndarray:
        # Recover counts from the alias table is lossy; engines keep them.
        return self._counts

    @classmethod
    def load(cls, path: str, mesh, **overrides) -> "EmbeddingEngine":
        """Rebuild an engine from :meth:`save` output onto any mesh shape —
        the analogue of re-homing a saved model onto a different PS cluster
        (mllib:696-725, ml:584-586). The source and target mesh shapes are
        independent: sharded files are re-sliced to whatever row blocks the
        new mesh owns, streamed via mmap (no full-table host copy)."""
        with open(os.path.join(path, "engine.json")) as f:
            meta = json.load(f)
        counts = np.load(os.path.join(path, "counts.npy"))
        eng = cls(
            mesh,
            meta["vocab_size"],
            meta["dim"],
            counts,
            num_negatives=overrides.get("num_negatives", meta["num_negatives"]),
            unigram_power=overrides.get(
                "unigram_power", meta.get("unigram_power", 0.75)
            ),
            unigram_table_size=overrides.get(
                "unigram_table_size", meta.get("unigram_table_size")
            ),
            dtype=overrides.get("dtype", meta["dtype"]),
            extra_rows=meta.get("extra_rows", 0),
            shared_negatives=overrides.get(
                "shared_negatives", meta.get("shared_negatives", 0)
            ),
            architecture=meta.get("architecture", "skipgram"),
            position_lanes=meta.get("position_lanes", 0),
        )
        eng.load_tables(path)
        return eng

    def load_tables(self, path: str, *, verify: bool = True) -> None:
        """Install table values from a :meth:`save` directory (either
        format) into this engine, re-sharding to its mesh. Each device
        shard is assembled independently from the overlapping source row
        blocks (mmap-sliced), so peak host memory is one shard, not one
        table.

        ``verify`` (default on) checks the directory against its
        ``manifest.json`` first — sizes + sha256 of every file — and
        raises ``utils.integrity.CheckpointCorruptError`` on mismatch
        or a partial directory, so bit rot can never load silently.
        Legacy directories with no manifest load unverified;
        ``GLINT_CKPT_NO_VERIFY=1`` downgrades to size-only checks.

        Implemented as :meth:`stage_tables` (disk reads + device
        transfers, safe to run concurrently with live query dispatches)
        followed by :meth:`adopt_tables` (the attribute flip + version
        tick). The serving hot-swap path (ISSUE 10) calls the two
        halves itself so a new table generation loads entirely OFF the
        request path and the flip happens under the device lock."""
        self.adopt_tables(self.stage_tables(path, verify=verify))

    def stage_tables(self, path: str, *, verify: bool = True):
        """Read a :meth:`save` directory and build the re-sharded device
        arrays WITHOUT touching the engine's live state: no attribute is
        assigned, no version ticked, and in-flight dispatches against
        the current tables are unaffected. Returns an opaque staged
        payload for :meth:`adopt_tables`. Raises exactly as
        :meth:`load_tables` (geometry mismatch, integrity failure)."""
        if verify:
            from glint_word2vec_tpu.utils import integrity

            tv0 = time.time()
            integrity.verify_snapshot_dir(path)
            # Shard verify cost is the dominant share on big tables
            # (per-shard sidecar hashing, ISSUE 15) — surfaced on the
            # heartbeat next to the write-side twin.
            self._ckpt_shard_verify_s = round(time.time() - tv0, 6)
        with open(os.path.join(path, "engine.json")) as f:
            meta = json.load(f)
        if (meta["vocab_size"], meta.get("extra_rows", 0)) != (
            self.vocab_size, self.num_rows - self.vocab_size
        ) or meta["dim"] != self.dim:
            raise ValueError(
                f"checkpoint at {path} has geometry "
                f"(V={meta['vocab_size']}, extra={meta.get('extra_rows', 0)}, "
                f"d={meta['dim']}), engine has (V={self.vocab_size}, "
                f"extra={self.num_rows - self.vocab_size}, d={self.dim})"
            )
        if meta.get("position_lanes", 0) != self.position_lanes:
            raise ValueError(
                f"checkpoint at {path} holds a position table of "
                f"{meta.get('position_lanes', 0)} rows, the engine one of "
                f"{self.position_lanes}: position_weights and window are "
                "the model's, saved with it"
            )
        fmt = meta.get("format", "single")
        staged = {"meta": meta}
        for name in self.table_names:
            _, padded_rows, sharding, dtype = self._table_layout(name)
            # Source blocks as (row range, col range, data), covering
            # row-block files (what :meth:`save` writes; a manifest entry
            # with no "axis" is one too), whole-table files and the
            # col-block files ("axis": "cols") that the column-sharded
            # ``dims`` engines of PRs before 46 wrote — so every
            # checkpoint on disk re-homes onto any mesh shape.
            if fmt == "sharded":
                blocks = []
                for b in meta["shards"][name]:
                    data = np.load(
                        os.path.join(path, b["file"]), mmap_mode="r"
                    )
                    if b.get("axis", "rows") == "rows":
                        blocks.append(
                            ((b["start"], b["stop"]), (0, data.shape[1]), data)
                        )
                    else:
                        blocks.append(
                            ((0, data.shape[0]), (b["start"], b["stop"]), data)
                        )
            else:
                arr = np.load(os.path.join(path, f"{name}.npy"), mmap_mode="r")
                blocks = [((0, arr.shape[0]), (0, arr.shape[1]), arr)]

            def assemble(index, _blocks=blocks, _rows=padded_rows,
                         _dtype=dtype):
                row_sl, col_sl = index[0], index[1]
                r0 = row_sl.start or 0
                r1 = (
                    row_sl.stop if row_sl.stop is not None else _rows
                )
                c0 = col_sl.start or 0
                c1 = (
                    col_sl.stop if col_sl.stop is not None
                    else self.padded_dim
                )
                out = np.zeros((r1 - r0, c1 - c0), np.float32)
                for (br0, br1), (bc0, bc1), data in _blocks:
                    rlo, rhi = max(r0, br0), min(r1, br1)
                    clo, chi = max(c0, bc0), min(c1, bc1)
                    if rlo < rhi and clo < chi:
                        out[rlo - r0 : rhi - r0, clo - c0 : chi - c0] = data[
                            rlo - br0 : rhi - br0, clo - bc0 : chi - bc0
                        ]
                # Restore-side memory bound (ISSUE 15): each device
                # shard assembles from mmap slices into exactly one
                # shard-sized host buffer — the peak the shard-streaming
                # restore test asserts against.
                self._stage_peak_block_bytes = max(
                    self._stage_peak_block_bytes, out.nbytes
                )
                return out.astype(_dtype)

            staged[name] = jax.make_array_from_callback(
                (padded_rows, self.padded_dim), sharding, assemble
            )
        return staged

    def adopt_tables(self, staged) -> None:
        """Flip the live tables to a :meth:`stage_tables` payload: two
        attribute assignments, the assigned-extra-row count from the
        snapshot's manifest, and ONE ``table_version`` tick (norms
        cache + serving result caches drop). Microseconds — the whole
        point of the split is that this is all the serving hot-swap
        holds the device lock for."""
        for name in self.table_names:
            setattr(self, name, staged[name])
        # graftlint: ignore[sync-point] meta is the parsed engine.json dict
        self.extra_rows_assigned = int(
            staged["meta"].get("extra_rows_assigned", 0)
        )
        self._tick_tables("load_tables")

    def set_tables(self, syn0: np.ndarray, syn1: np.ndarray,
                   posw: Optional[np.ndarray] = None) -> None:
        """Install host table values (unpadded: all num_rows rows of
        ``syn0`` and ``syn1``; ``posw``, for an engine that has one, its
        ``position_lanes`` rows, left as it is where not given),
        re-padding and re-sharding."""
        if posw is not None and not self.position_lanes:
            raise ValueError("this engine has no position table")
        given = {"syn0": syn0, "syn1": syn1, "posw": posw}
        placed = {
            name: self._put_table(name, host)
            for name, host in given.items() if host is not None
        }
        for name, table in placed.items():
            setattr(self, name, table)
        self._tick_tables("set_tables")

    def _resident_arrays(self):
        """The live tables and the adopted ANN index's arrays."""
        for a in self.tables().values():
            if a is not None:
                yield a
        idx = self._ann
        if idx is not None:
            for name in ("centroids", "members", "member_invn",
                         "member_rows"):
                a = getattr(idx, name, None)
                if a is not None and hasattr(a, "size"):
                    yield a

    def resident_bytes(self) -> int:
        """Device bytes the live tables (+ adopted ANN index) hold —
        the per-model cost the serving catalog's memory budget accounts
        (ISSUE 20). Zero after :meth:`release_tables`.

        The model's total over ALL its devices (an array's ``size`` is
        the global array's): a 10M x 300 f32 pair split by rows over
        four chips is 30.7 GB here and 7.68 GB to a chip.
        :meth:`resident_bytes_per_device` is what one chip holds."""
        # graftlint: ignore[sync-point] .size is array metadata
        return sum(int(a.size) * a.dtype.itemsize
                   for a in self._resident_arrays())

    def resident_bytes_per_device(self) -> int:
        """What the FULLEST device holds of :meth:`resident_bytes`, from
        the arrays' shardings alone (a replicated array counts whole on
        every device it lies on)."""
        held: dict = {}
        for a in self._resident_arrays():
            sharding = getattr(a, "sharding", None)
            if sharding is None:  # a host array: no device holds it
                continue
            shard = math.prod(sharding.shard_shape(a.shape))
            for dev in sharding.device_set:
                held[dev] = held.get(dev, 0) + shard * a.dtype.itemsize
        return max(held.values(), default=0)

    @property
    def tables_resident(self) -> bool:
        """Whether the tables currently occupy device memory (False
        between :meth:`release_tables` and the next adopt/stage-in)."""
        return self.syn0 is not None

    def release_tables(self) -> None:
        """Stage-out: free the table (+ ANN index) device buffers
        WITHOUT destroying the engine — compiled programs, vocabulary
        geometry, and checkpoint machinery all survive, so a later
        :meth:`stage_tables` + :meth:`adopt_tables` round trip makes
        the engine serve again with zero new compiles. Querying while
        released fails (callers gate on :attr:`tables_resident`);
        unlike :meth:`destroy` the corpus/training buffers (if any)
        are left alone."""
        self.wait_pending_saves(reraise=False)
        for name, a in self.tables().items():
            try:
                a.delete()
            except Exception:
                pass
            setattr(self, name, None)
        self._ann = None
        self._tick_tables("release_tables")

    def destroy(self) -> None:
        """Free device memory (Glint ``matrix.destroy``, mllib:665).
        Drains any in-flight async save first (its snapshot copies are
        separate buffers, but a half-written checkpoint helps nobody)."""
        self.wait_pending_saves(reraise=False)
        _free(
            *self.tables().values(), self._prob, self._alias,
            self._alias_packed, getattr(self, "_keep_prob", None),
            *(getattr(self, "_corpus", None) or ()),
            *(getattr(self, "_corpus_compacted", None) or ()),
            getattr(self, "_corpus_sent", None),
            getattr(self, "_compacted_sent", None),
            *(getattr(self, "_compact_prefetch", None) or ()),
        )
        self._compact_prefetch = None
        for name in self.table_names:
            setattr(self, name, None)
        self._prob = self._alias = self._alias_packed = None
        self._corpus = None
        self._corpus_compacted = None
        self._corpus_sent = self._compacted_sent = None
        self._keep_prob = None
        self._ann = None
        self._tick_tables("destroy")

    @property
    def cols(self) -> int:
        """Column count == vector size (Glint ``matrix.cols``, mllib:473)."""
        return self.dim
