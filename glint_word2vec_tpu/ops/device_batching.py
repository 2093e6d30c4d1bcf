"""On-device skip-gram batch assembly from a device-resident corpus.

The host pipeline (corpus/batching.py) streams fixed-shape minibatches and
re-uploads ~60 bytes/center-position per step. This module is the
TPU-native alternative: the flat encoded corpus (``ids int32[N]``,
``offsets int32[S+1]`` — corpus/vocab.encode_file's representation) is
uploaded to HBM ONCE (~4 bytes per kept word), and every minibatch is
assembled *inside* the jitted train scan from nothing but a position
counter and the step's PRNG key. Host->device traffic per dispatch drops
from O(steps_per_call * batch * context) to a handful of scalars.

Semantics mirror the reference's windowing exactly as restated in
corpus/batching.py (mllib:381-390): for center position ``i`` draw
``b ~ U[0, window)`` and take context positions ``[max(0, i-b),
min(i+b, len)) \\ {i}`` — the half-open upper bound inherited from
Scala's ``until``, hence lanes spanning offsets ``[-(W-1), W-2]`` and
``context_width(W) = 2W-3``. Sentence bounds come from ``offsets`` via
``searchsorted``, or, where the caller holds the view's per-position
record (:func:`position_sentences`), from a slice of it. Without subsampling the center-position stream is the
corpus in order — exactly the host batcher's packing — so the device
path is batch-for-batch identical to the Python path modulo the window
shrink RNG stream (device threefry vs host PCG64; the host native/C++
pass already diverges from Python the same way).

Frequency subsampling *compacts* sentences before windowing (it changes
neighbor distances, exactly the reference's per-iteration pass,
mllib:371-390). That compaction now ALSO runs on device: once per epoch
:func:`subsample_compact` draws the keep mask (Bernoulli with
``keep_prob[ids[t]]``, keyed ``fold_in(epoch_key, position)`` so the
draws are mesh-invariant by construction), prefix-sums it, and
scatter-compacts ``(ids, offsets)`` into same-shape device buffers
``(ids_c, offsets_c)`` plus a traced element count ``n_kept``.
:func:`device_window_batch` then runs unchanged over the compacted
arrays with ``n_kept`` as its (traced) corpus-end bound — so
``subsample_ratio > 0`` keeps the scalars-only dispatch path instead of
falling back to the host batcher (models/word2vec.py routes the device
path for both settings).

The shrink draw plus sentence clipping leave only ~0.43 of the ``(B, C)``
context grid live (``E[max(2b-1, 0)]/C`` for ``b ~ U[0, W)``), so the
grid-shaped step burns >2x the FLOPs per useful pair. The PACKED dispatch
mode (:func:`pack_window_pairs`, ``set_batch_packing("dense")``) fixes
that the pSGNScc way (arxiv 1604.04661: restructure the batch so the
matrix work is dense): windows are assembled over an oversized candidate
span, the valid (center, context) pairs are prefix-sum scatter-compacted
into a fixed-shape dense pair list, and the step runs the rank-1 SGNS
update over pairs — effective mask density ~0.43 -> >=0.95 on the corpus
path at the same dispatched step cost.

CBOW (``Word2VecParams.architecture``) takes the same view a POSITION at
a time: for B consecutive positions, each position's bag of context words
(word2vec's own window, ``2 * window`` lanes) from the same slices and the
same shrink draws; nothing is compacted, and a step advances by the static
B. :func:`bag_span_batch` names a bag's words by where they stand in the
step's span (what the scan trains from: each span word is gathered once),
:func:`bag_window_batch` by what they are (what a replay or a reference
reads).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from glint_word2vec_tpu.corpus.batching import window_offsets

#: Domain-separation constant for the window-shrink draws ("wind").
WINDOW_FOLD = 0x77696E64

#: Domain-separation constant for the per-epoch subsample draws ("subs"):
#: folded into the epoch key before the per-position fold, so the
#: subsample stream can never collide with the window/negative streams
#: (those fold WINDOW_FOLD or a global row index < 2**30 instead).
SUBSAMPLE_FOLD = 0x73756273


def device_window_batch(
    ids: jax.Array,  # (N,) int32 flat corpus
    offsets: jax.Array,  # (S+1,) int32 sentence offsets
    positions: jax.Array,  # (B,) int32 center positions (may exceed N: masked)
    rows: jax.Array,  # (B,) int32 GLOBAL batch-row indices (key the draws)
    key: jax.Array,
    window: int,
    n_valid=None,  # traced int32 scalar corpus-end bound (None = ids.shape[0])
):
    """Assemble one (centers, contexts, mask) minibatch on device.

    ``positions`` beyond the corpus end yield fully-masked rows (the
    epoch-tail padding the host batcher expresses with zero-mask rows).
    The shrink draw for row ``i`` depends only on ``(key, rows[i])`` —
    the per-GLOBAL-row keying of ops/sampling.sample_negatives_per_row —
    so a data rank holding global rows [r0, r0+Bl) draws exactly what a
    single-rank run draws for those rows while doing only O(local rows)
    work (no global-batch over-draw).

    ``n_valid`` is the corpus-end bound as a *traced* scalar — the
    subsampled path passes the epoch's ``n_kept`` (compacted buffers
    keep the static shape N, only a prefix is live). None (the
    un-subsampled path) means the full static extent. Context validity
    needs no extra bound: compacted sentence offsets never exceed
    ``n_kept``, so the sentence-end check already excludes the dead tail.
    """
    N = ids.shape[0]
    W = int(window)
    if n_valid is None:
        n_valid = N

    # Both bounds: upload_corpus permits N up to 2**31-1, so a tail
    # group's positions can overflow int32 and wrap negative — without
    # the >= 0 check a wrapped position would clip to 0 and train real
    # updates on sentence-0 windows instead of masking out.
    in_corpus = (positions >= 0) & (positions < n_valid)
    p = jnp.clip(positions, 0, max(N - 1, 0))
    sent = jnp.searchsorted(offsets, p, side="right") - 1
    start = offsets[sent]
    end = offsets[sent + 1]

    base = jax.random.fold_in(key, WINDOW_FOLD)
    keys = jax.vmap(lambda r: jax.random.fold_in(base, r))(rows)
    b = jax.vmap(
        lambda k: jax.random.randint(k, (), 0, W, dtype=jnp.int32)
    )(keys)
    offs = jnp.asarray(window_offsets(W), dtype=jnp.int32)  # (C,) static
    cpos = p[:, None] + offs[None, :]
    valid = (
        (offs[None, :] >= -b[:, None])
        & (offs[None, :] <= b[:, None] - 1)
        & (cpos >= start[:, None])
        & (cpos < end[:, None])
        & in_corpus[:, None]
    )
    centers = jnp.where(in_corpus, ids[p], 0).astype(jnp.int32)
    contexts = jnp.where(valid, ids[jnp.clip(cpos, 0, max(N - 1, 0))], 0)
    return centers, contexts.astype(jnp.int32), valid.astype(jnp.float32)


def subsample_keep_mask(
    ids: jax.Array, keep_prob: jax.Array, epoch_key: jax.Array
) -> jax.Array:
    """Per-position Bernoulli keep mask for frequency subsampling.

    Position ``t`` is kept iff ``u_t <= keep_prob[ids[t]]`` with
    ``u_t ~ U[0, 1)`` — the host rule (corpus/batching.subsample_sentence)
    on a device RNG stream. Each draw is keyed
    ``fold_in(fold_in(epoch_key, SUBSAMPLE_FOLD), t)``: purely elementwise
    in the position, so the values cannot depend on how GSPMD partitions
    the computation — mesh-invariant by construction (a bulk
    ``uniform(key, (N,))`` draw is NOT under the legacy non-partitionable
    threefry lowering).
    """
    N = ids.shape[0]
    base = jax.random.fold_in(epoch_key, SUBSAMPLE_FOLD)
    u = jax.vmap(
        lambda t: jax.random.uniform(jax.random.fold_in(base, t), ())
    )(jnp.arange(N, dtype=jnp.uint32))
    return u <= keep_prob[ids]


def subsample_compact(
    ids: jax.Array,  # (N,) int32 flat corpus
    offsets: jax.Array,  # (S+1,) int32 sentence offsets
    keep_prob: jax.Array,  # (V,) float32 per-word keep probability
    epoch_key: jax.Array,
):
    """One epoch's subsample-and-compact pass, entirely on device.

    Returns ``(ids_c, offsets_c, n_kept)``: the kept tokens compacted to
    the front of a same-shape (N,) buffer (tail zeros are dead — bounded
    off by ``n_kept`` and the compacted sentence offsets), the per-
    sentence offsets remapped into compacted coordinates (a sentence
    subsampled to nothing becomes an empty span, which ``searchsorted``
    in :func:`device_window_batch` skips naturally), and the traced kept
    count. Compaction happens BEFORE windowing — the reference's
    semantics (mllib:371-390): dropping a word shortens the distances
    between its surviving neighbors.

    The pass is integer-exact (elementwise draws, int32 prefix sum,
    deterministic scatter), so its output is bitwise identical on every
    mesh shape. HBM cost: one extra int32 buffer per corpus word plus
    the transient prefix sums (~12 bytes/word peak together with the
    flat corpus; models/word2vec._device_corpus_eligible budgets this).
    """
    N = ids.shape[0]
    keep = subsample_keep_mask(ids, keep_prob, epoch_key)
    k32 = keep.astype(jnp.int32)
    incl = jnp.cumsum(k32)  # inclusive prefix: kept in [0, t]
    n_kept = incl[-1] if N else jnp.int32(0)
    dest = incl - k32  # exclusive prefix = compacted destination
    scatter_idx = jnp.where(keep, dest, N)  # dropped tokens land out of range
    ids_c = jnp.zeros(N, jnp.int32).at[scatter_idx].set(ids, mode="drop")
    kept_before = jnp.concatenate([jnp.zeros(1, jnp.int32), incl])
    offsets_c = kept_before[offsets].astype(jnp.int32)
    return ids_c, offsets_c, n_kept


def position_sentences(offsets: jax.Array, n: int) -> jax.Array:
    """The sentence of every position of a corpus view, ``(n,) int32``:
    entry ``p`` is ``searchsorted(offsets, p, side="right") - 1``, the
    index the window functions' search finds, laid down once for the view
    (a one at every sentence offset, summed along the positions; repeated
    offsets, sentences subsampling emptied, add up by themselves, and an
    offset at ``n`` falls off the end). Two positions lie in the same
    sentence iff their entries are equal, so a step that holds this record
    reads its span's sentence bounds as a slice
    (:func:`pack_window_pairs`) where it would search ``offsets`` once a
    position. The dead tail of a compacted view reads the sentence count,
    which no live position has."""
    marks = jnp.zeros(n, jnp.int32).at[offsets].add(
        1, mode="drop", indices_are_sorted=True
    )
    return jnp.cumsum(marks) - 1


def _span_slice(arr: jax.Array, first, length: int, fill) -> jax.Array:
    """``arr[first : first + length]`` for a traced ``first``, with
    ``fill`` wherever that leaves the array (a span that starts before 0,
    runs past the end, or is longer than the array): two slices and no
    gather, the same ops whatever ``first`` is."""
    n = arr.shape[0]
    m = min(length, n)
    at = jnp.clip(first, 0, n - m)
    pad = jnp.full(length, fill, arr.dtype)
    padded = jnp.concatenate([pad, lax.dynamic_slice(arr, (at,), (m,)), pad])
    # dynamic_slice holds the start inside [0, length + m]: a span wholly
    # outside the array reads one of the pads.
    return lax.dynamic_slice(padded, (length + (first - at),), (length,))


def _span_record(ids, sent_of, pos, length: int, lanes: list):
    """The words and the sentences of the ``length`` positions from ``pos``
    and the reach of their lanes, ``[pos + lo, pos + length + hi)``, read
    from the view and its per-position record as two slices: ``(words,
    sentences, lo)``, position t of the span at index ``t - lo``."""
    lo, hi = min(lanes + [0]), max(lanes + [0])
    ids_x = _span_slice(ids, pos + lo, length + hi - lo, 0)
    sent_x = _span_slice(sent_of, pos + lo, length + hi - lo, -1)
    return ids_x, sent_x, lo


def _lanes_in_sentence(sent_x, length: int, lo: int, lanes: list):
    """``(length, len(lanes))``: whether lane k of position t lies in t's
    sentence, which it does iff the two records are equal."""
    own = sent_x[-lo:length - lo]
    # A sentence index is >= 0 wherever offsets[0] == 0; before a first
    # offset the search finds no sentence either.
    return (own[:, None] >= 0) & (
        jnp.stack([sent_x[o - lo:o - lo + length] for o in lanes], axis=1)
        == own[:, None]
    )


def _span_lanes(ids, sent_of, pos, length: int, lanes: list):
    """The ``length`` positions from ``pos`` and their context lanes, read
    from the view and its per-position record as slices: ``(words
    (length,), lane words (length, len(lanes)), in_sentence)``, lane k of
    position t the word at ``t + lanes[k]``. Nothing is searched or
    gathered."""
    ids_x, sent_x, lo = _span_record(ids, sent_of, pos, length, lanes)
    in_sentence = _lanes_in_sentence(sent_x, length, lo, lanes)
    words = ids_x[-lo:length - lo]
    lane_words = jnp.stack(
        [ids_x[o - lo:o - lo + length] for o in lanes], axis=1
    )
    return words, lane_words, in_sentence


def grid_window_shrink(
    base_key: jax.Array,
    positions: jax.Array,  # (S,) int32 center positions, >= 0
    grid_batch: int,  # B of the grid scan being reproduced
    grid_step0,  # traced uint32: grid step counter at position 0
    window: int,
) -> jax.Array:
    """The window-shrink draw the GRID corpus scan makes for each position.

    In the grid scan (parallel/engine.make_corpus_scan) position ``p``
    lands in grid step ``grid_step0 + p // B`` at batch row ``p % B``, and
    its shrink is drawn from
    ``fold_in(fold_in(fold_in(base_key, step), WINDOW_FOLD), row)``. The
    packed scan reproduces exactly those draws — a deterministic function
    of the global position — so the packed pair stream is the *same
    multiset of valid (center, context) pairs* the grid scan trains on
    (the parity contract tests/test_packed.py pins down), and is
    mesh-invariant for free (no dependence on where packing boundaries or
    data ranks fall).
    """
    W = int(window)
    gi = (positions // jnp.int32(grid_batch)).astype(jnp.uint32)
    row = (positions % jnp.int32(grid_batch)).astype(jnp.uint32)

    def draw(g, r):
        k = jax.random.fold_in(base_key, grid_step0 + g)
        k = jax.random.fold_in(k, WINDOW_FOLD)
        k = jax.random.fold_in(k, r)
        return jax.random.randint(k, (), 0, W, dtype=jnp.int32)

    return jax.vmap(draw)(gi, row)


def pack_window_pairs(
    ids: jax.Array,  # (N,) int32 flat corpus (active view)
    offsets: jax.Array,  # (S+1,) int32 sentence offsets (active view)
    pos,  # traced int32 scalar: first unconsumed center position
    base_key: jax.Array,
    grid_step0,  # traced uint32 (see grid_window_shrink)
    *,
    window: int,
    span: int,  # candidate center positions examined per step
    pair_batch: int,  # P: dense pair slots per step
    grid_batch: int,  # B of the grid scan whose draws are reproduced
    n_valid,  # traced int32 corpus-end bound
    sent_of=None,  # (N,) int32 position_sentences(offsets, N), if held
):
    """Assemble one DENSE (center, context) pair batch on device.

    Windows are built over the oversized candidate span
    ``[pos, pos + span)`` exactly as :func:`device_window_batch` builds
    them (shrink draw + sentence bounds), then the valid pairs are
    prefix-sum scatter-compacted to the front of a fixed ``(P,)`` pair
    list — the same compaction machinery :func:`subsample_compact` proved
    out, applied to pair lanes instead of tokens. Only *whole* center
    positions are consumed: ``n_cons`` is the largest prefix of the span
    whose cumulative valid-pair count fits in ``P``, so the unconsumed
    remainder is carried simply as the position counter (positions past
    ``pos + n_cons`` are re-assembled next step — the draws are
    position-deterministic, so recomputation is exact) and no partial
    position ever splits across steps.

    Returns ``(pcenters (P,), pcontexts (P,), pmask (P,), n_cons (),
    n_pairs ())``: the dense pair list in position-major, lane-minor
    order (slots past ``n_pairs`` are index-0 / mask-0 padding),
    the consumed-position advance, and the live pair count. Guarantees
    ``n_cons >= 1`` whenever ``P >= context_width(window)`` (a single
    position yields at most C pairs), so the scan always makes progress;
    positions at or past ``n_valid`` contribute zero pairs but are still
    consumed (the epoch tail drains in ``span``-sized strides).

    ``sent_of`` is the view's per-position record
    (:func:`position_sentences` of the same ``offsets``). With it the
    span's words and their sentences are SLICES at ``pos`` (the positions
    of a step are consecutive), a context lane is the slice shifted by the
    lane's offset, and a context is its centre's iff their sentences are
    equal: no search, no gather, nothing that depends on what the corpus
    holds. Without it every position's sentence is searched for in
    ``offsets`` and its words gathered. The five outputs are bit-equal.
    """
    N = ids.shape[0]
    W = int(window)
    S = int(span)
    P = int(pair_batch)
    lanes = window_offsets(W).tolist()  # C static offsets
    offs = jnp.asarray(lanes, dtype=jnp.int32)
    C = offs.shape[0]
    if P < C:
        raise ValueError(f"pair_batch ({P}) must be >= context lanes ({C})")

    positions = pos + jnp.arange(S, dtype=jnp.int32)
    in_corpus = (positions >= 0) & (positions < n_valid)
    if sent_of is None:
        p = jnp.clip(positions, 0, max(N - 1, 0))
        sent = jnp.searchsorted(offsets, p, side="right") - 1
        start = offsets[sent]
        end = offsets[sent + 1]
        cpos = p[:, None] + offs[None, :]
        in_sentence = (cpos >= start[:, None]) & (cpos < end[:, None])
        center_ids = ids[p]
        lane_ids = ids[jnp.clip(cpos, 0, max(N - 1, 0))]
    else:
        center_ids, lane_ids, in_sentence = _span_lanes(
            ids, sent_of, pos, S, lanes
        )
    b = grid_window_shrink(base_key, positions, grid_batch, grid_step0, W)
    valid = (
        (offs[None, :] >= -b[:, None])
        & (offs[None, :] <= b[:, None] - 1)
        & in_sentence
        & in_corpus[:, None]
    )  # (S, C)
    centers = jnp.where(in_corpus, center_ids, 0).astype(jnp.int32)
    contexts = jnp.where(valid, lane_ids, 0).astype(jnp.int32)

    # Whole-position consumption: take the longest span prefix whose
    # cumulative pair count fits in P (cum is non-decreasing, so the
    # count of cum <= P IS that prefix length).
    v = valid.sum(axis=1).astype(jnp.int32)  # (S,)
    cum = jnp.cumsum(v)
    n_cons = jnp.sum((cum <= P).astype(jnp.int32))
    consumed = jnp.arange(S, dtype=jnp.int32) < n_cons

    take = (valid & consumed[:, None]).reshape(-1).astype(jnp.int32)
    incl = jnp.cumsum(take)
    n_pairs = incl[-1]  # == cum[n_cons - 1] <= P by construction
    dest = incl - take
    scatter_idx = jnp.where(take > 0, dest, P)  # dropped lanes out of range
    pcontexts = (
        jnp.zeros(P, jnp.int32)
        .at[scatter_idx]
        .set(contexts.reshape(-1), mode="drop")
    )
    live = jnp.arange(P, dtype=jnp.int32) < n_pairs
    # A position's pairs are consecutive slots that hold one centre, so the
    # centres are a step function of the slot: lay each consumed position's
    # step (its centre less the one before) at its first slot, S adds where
    # a scatter of every lane's centre would be S x C, and sum along the
    # slots. A position with no pair shares the next one's first slot and
    # the steps add up; int32 sums wrap and telescope exactly.
    step = centers - jnp.concatenate([jnp.zeros(1, jnp.int32), centers[:-1]])
    first = jnp.where(consumed, cum - v, P)
    pcenters = jnp.where(
        live,
        jnp.cumsum(jnp.zeros(P, jnp.int32).at[first].add(step, mode="drop")),
        0,
    )
    return pcenters, pcontexts, live.astype(jnp.float32), n_cons, n_pairs


def bag_lanes(window: int) -> list:
    """The context offsets of a CBOW bag, ``2 * window`` of them: every
    position within ``window`` of the centre, either side, the centre left
    out (``word2vec.c``'s ``a != window``). Not the skip-gram lanes
    (``corpus.batching.window_offsets``, the reference's half-open
    ``[-b, b)``): the bag is word2vec's own window."""
    return [o for o in range(-window, window + 1) if o]


def _bag_valid(positions, in_corpus, in_sentence, offs, base_key, grid_batch,
               grid_step0, W: int, n_valid):
    """``(B, 2W)``: which lanes are in their position's bag. The position
    draws its shrink, and a lane is in iff it is within reach, in the
    position's sentence, and both lie inside the view."""
    b = grid_window_shrink(base_key, positions, grid_batch, grid_step0, W)
    return (
        (jnp.abs(offs)[None, :] <= (W - b)[:, None])
        & in_sentence
        & in_corpus[:, None]
        # a bounded view's sentence may run past its live prefix
        & (positions[:, None] + offs[None, :] < n_valid)
    )


def bag_window_batch(
    ids: jax.Array,  # (N,) int32 flat corpus (active view)
    sent_of: jax.Array,  # (N,) int32 position_sentences of the view
    pos,  # traced int32 scalar: the batch's first centre position
    base_key: jax.Array,
    grid_step0,  # traced uint32 (see grid_window_shrink)
    *,
    window: int,
    batch: int,  # B: consecutive centre positions of the batch
    grid_batch: int,  # B of the grid scan whose draws are reproduced
    n_valid,  # traced int32 corpus-end bound
):
    """Assemble the CBOW bags of ``batch`` consecutive positions on device.

    Position ``t`` of ``[pos, pos + batch)`` draws its shrink ``b`` in
    ``[0, window)`` and its bag is every position within ``window - b`` of
    ``t`` in ``t``'s sentence, ``t`` left out: ``word2vec.c``'s loop
    ``for (a = b; a < window * 2 + 1 - b; a++) if (a != window)``. The draw
    is :func:`grid_window_shrink`'s for that position, the one the
    skip-gram scans make under the same key schedule, so a bag is a
    function of the view, the key and the position alone, on every mesh.

    As :func:`pack_window_pairs` with the view's record: the span's words
    and sentences are two SLICES at ``pos`` (:func:`_span_slice`), a lane
    is the slice shifted by its offset, and a lane is in the bag iff its
    sentence equals the centre's and its offset is within reach. No
    search, no gather, no compaction: the batch is position-major and the
    advance a step is the static ``batch``.

    Returns ``(centres (B,), bags (B, 2 * window), mask (B, 2 * window),
    live (B,))``: each position's word, its bag's words with -1 (a row no
    table has) wherever ``mask`` is 0, and which positions train: those
    inside the corpus whose bag is not empty (``word2vec.c`` skips a
    position with ``cw == 0``).
    """
    W, B = window, batch
    lanes = bag_lanes(W)
    offs = jnp.asarray(lanes, dtype=jnp.int32)
    positions = pos + jnp.arange(B, dtype=jnp.int32)
    in_corpus = (positions >= 0) & (positions < n_valid)
    words, lane_ids, in_sentence = _span_lanes(ids, sent_of, pos, B, lanes)
    valid = _bag_valid(
        positions, in_corpus, in_sentence, offs, base_key, grid_batch,
        grid_step0, W, n_valid,
    )
    centres = jnp.where(in_corpus, words, 0).astype(jnp.int32)
    bags = jnp.where(valid, lane_ids, -1).astype(jnp.int32)
    live = valid.any(axis=1)
    return centres, bags, valid.astype(jnp.float32), live.astype(jnp.float32)


def bag_span_batch(
    ids: jax.Array,
    sent_of: jax.Array,
    pos,
    base_key: jax.Array,
    grid_step0,
    *,
    window: int,
    batch: int,
    grid_batch: int,
    n_valid,
):
    """:func:`bag_window_batch` for a step that forms each word of the
    batch's SPAN once and lets every bag it is in read it (the CBOW scan
    of both families: a word is its row, or its group of rows). The same
    positions, draws and rule, and so the same bags; what is returned
    names a bag's words by where they stand, not by what they are.

    Returns ``(centres (B,), span words (B + 2 * window,), mask (B, 2 *
    window), live (B,))``: the words of the positions ``[pos - window, pos
    + B + window)``, -1 outside the view; ``mask[t, k]`` is 1 iff lane k of
    position t is in its bag, and the word it reads is then span word
    ``t + window + bag_lanes(window)[k]``.
    """
    W, B = window, batch
    lanes = bag_lanes(W)
    offs = jnp.asarray(lanes, dtype=jnp.int32)
    positions = pos + jnp.arange(B, dtype=jnp.int32)
    in_corpus = (positions >= 0) & (positions < n_valid)
    ids_x, sent_x, lo = _span_record(ids, sent_of, pos, B, lanes)
    in_sentence = _lanes_in_sentence(sent_x, B, lo, lanes)
    valid = _bag_valid(
        positions, in_corpus, in_sentence, offs, base_key, grid_batch,
        grid_step0, W, n_valid,
    )
    centres = jnp.where(in_corpus, ids_x[W:W + B], 0).astype(jnp.int32)
    span_at = pos - W + jnp.arange(B + 2 * W, dtype=jnp.int32)
    span_words = jnp.where(
        (span_at >= 0) & (span_at < n_valid), ids_x, -1
    ).astype(jnp.int32)
    live = valid.any(axis=1)
    return (
        centres, span_words, valid.astype(jnp.float32),
        live.astype(jnp.float32),
    )


def center_runs(pcenters: jax.Array, pmask: jax.Array, n_runs: int):
    """Group a packed pair list by centre, for a step whose centre is
    formed from several rows (the subword family): consecutive pair slots
    with the same centre id are one RUN, and the centre side of the step
    (gather of the group's rows, their mean, the gradient's fan-out) is
    formed once a run instead of once a pair.

    :func:`pack_window_pairs` emits pairs position-major, so a run is one
    consumed centre position, or several adjacent positions that hold the
    same word (their centre vector is the same, and their gradients add:
    the sums are those of the per-pair form). A position with no valid
    pair has no run. ``n_runs`` bounds the runs of one list statically:
    at most one per examined position and one for the padding, so
    ``span + 1`` (or the list's length, if shorter).

    Returns ``(run_center (n_runs,), pair_run (P,), run_live (n_runs,))``:
    the centre id of each run, each pair slot's run, and which runs hold a
    live pair (padding slots, centre 0 and mask 0, form a dead last run;
    unused run slots are dead too). A function of the pair list alone.
    """
    change = (pcenters[1:] != pcenters[:-1]).astype(jnp.int32)
    pair_run = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(change)])
    run_center = (
        jnp.zeros(n_runs, jnp.int32)
        .at[pair_run]
        .set(pcenters, mode="drop")
    )
    # Live pairs are a prefix of the list (pmask is 1 below n_pairs).
    n_live = pmask.sum().astype(jnp.int32)
    last = pair_run[jnp.maximum(n_live - 1, 0)]
    run_live = jnp.arange(n_runs, dtype=jnp.int32) < jnp.where(
        n_live > 0, last + 1, 0
    )
    return run_center, pair_run, run_live


def device_words_done(
    offsets: jax.Array,  # (S+1,) int32 ORIGINAL sentence offsets
    offsets_c: jax.Array,  # (S+1,) int32 active (possibly compacted) offsets
    end_position,  # traced int32: consumed center positions [0, end)
    n_valid,  # traced int32: live extent of the active position stream
) -> jax.Array:
    """Traced restatement of :func:`corpus_words_done_compacted` — the
    pre-subsampling words_done rule, evaluated inside the jitted packed
    scan so the LR anneal can follow the data-dependent position advance
    without a host round-trip. For the un-subsampled stream pass the
    original offsets as both arguments (then this equals
    :func:`corpus_words_done`). Bit-for-bit the host rule: the parity
    test drives both over every prefix."""
    j = jnp.searchsorted(offsets_c, end_position - 1, side="right") - 1
    done = offsets[jnp.clip(j + 1, 0, offsets.shape[0] - 1)]
    done = jnp.where(end_position >= n_valid, offsets[-1], done)
    return jnp.where(end_position <= 0, 0, done).astype(jnp.int32)


def corpus_words_done(offsets: np.ndarray, end_position: int) -> int:
    """Host-side words_done after consuming center positions [0, end).

    Matches the host batcher's accounting (corpus/batching.py): a
    sentence's full word count is added as soon as ANY of its positions
    is consumed, so the value after a batch ending inside sentence ``j``
    is ``offsets[j+1]``.
    """
    if end_position <= 0:
        return 0
    end_position = min(int(end_position), int(offsets[-1]))
    j = int(np.searchsorted(offsets, end_position - 1, side="right")) - 1
    return int(offsets[j + 1])


def corpus_words_done_compacted(
    offsets: np.ndarray,  # (S+1,) ORIGINAL sentence offsets
    offsets_c: np.ndarray,  # (S+1,) compacted sentence offsets
    end_position: int,  # consumed compacted center positions [0, end)
    n_kept: int,
) -> int:
    """Host-side words_done over the epoch's COMPACTED position stream.

    Same convention as :func:`corpus_words_done` — a sentence's FULL
    pre-subsampling word count is credited as soon as any of its kept
    positions is consumed (the host batcher counts pre-subsampling words
    so the LR anneal never stalls; corpus/batching.SkipGramBatcher) —
    looked up through the compacted offsets. Consuming the whole
    compacted stream credits the whole corpus: the host batcher consumes
    every sentence by then, including ones subsampling emptied.
    """
    if end_position >= n_kept:
        return int(offsets[-1])
    if end_position <= 0:
        return 0
    # side="right" over possibly-repeated compacted offsets: lands past
    # every emptied sentence preceding the one owning position end-1.
    j = int(np.searchsorted(offsets_c, end_position - 1, side="right")) - 1
    return int(offsets[j + 1])
