"""Skip-gram negative-sampling step math, as pure jit-compatible functions.

This module is the TPU restatement of the reference's hot loop: the
client/server round-trip pair ``matrix.dotprod`` (servers compute partial dot
products for positive and negative pairs, mllib:421) + ``matrix.adjust``
(servers replay cached indices and apply rank-1 SGD updates, mllib:425),
with the client-side sigmoid-LUT gradient scaling between them
(mllib:422-424). Here all three fuse into one on-device function: gather ->
batched dot products (MXU) -> exact sigmoid (documented divergence from the
reference's 1000-bin LUT, mllib:281-302 — the LUT was a CPU optimization; on
TPU the exact form is free) -> scalar gradient coefficients -> scatter-add
rank-1 updates. The RPC cache keys (``cacheKeys``) dissolve: the "cache" is
simply values held in registers/VMEM between the two halves of the fused op.

All functions are shape-polymorphic in batch B, context lanes C, negatives n,
and embedding dim d, and run identically per-shard under ``shard_map`` (the
sharded engine in parallel/engine.py supplies gathered rows and consumes
per-row updates).

Padding convention: padded context lanes / padded batch rows carry index 0
and mask 0.0; every gradient coefficient is multiplied by its mask, so
padded entries contribute exactly zero to every scatter-add.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from glint_word2vec_tpu.ops.sampling import sample_negatives_per_row


class SgnsCoefs(NamedTuple):
    """Logit-stage outputs: the scalar SGD coefficients (the reference's
    gPlus/gMinus wire format, mllib:422-425) plus the monitoring loss,
    computed from FULL logits."""

    c_pos: jax.Array  # (B, C)
    c_neg: jax.Array  # (B, C, n)
    loss: jax.Array  # ()
    pair_loss: jax.Array  # (B, C) the masked terms ``loss`` is the mean of


def sgns_coefs(
    f_pos: jax.Array,  # (B, C) float32 FULL positive logits
    f_neg: jax.Array,  # (B, C, n) float32 FULL negative logits
    mask: jax.Array,  # (B, C) float32
    neg_mask: jax.Array,  # (B, C, n) float32
    alpha: jax.Array,  # () float32
) -> SgnsCoefs:
    """Coefficients + loss from full logits."""
    s_pos = jax.nn.sigmoid(f_pos)
    s_neg = jax.nn.sigmoid(f_neg)
    c_pos = alpha * (1.0 - s_pos) * mask
    c_neg = -alpha * s_neg * neg_mask
    log_sig = jax.nn.log_sigmoid
    pair_loss = -log_sig(f_pos) * mask - jnp.sum(
        log_sig(-f_neg) * neg_mask, axis=-1
    ) * mask
    loss = pair_loss.sum() / jnp.maximum(mask.sum(), 1.0)
    return SgnsCoefs(c_pos=c_pos, c_neg=c_neg, loss=loss, pair_loss=pair_loss)


class SgnsGrads(NamedTuple):
    """Scalar gradient coefficients + center-row gradient for one minibatch.

    ``c_pos``/``c_neg`` are exactly the reference's ``gPlus``/``gMinus``
    scalars (the only payload the client ever sends back to the servers,
    mllib:422-425): the SGD coefficient multiplying the *other* side's row in
    each rank-1 update, learning rate included.
    """

    c_pos: jax.Array  # (B, C)    alpha * (1 - sigmoid(f_pos)) * mask
    c_neg: jax.Array  # (B, C, n) alpha * (0 - sigmoid(f_neg)) * mask
    d_center: jax.Array  # (B, d)  gradient w.r.t. syn0[centers]
    loss: jax.Array  # () masked-mean SGNS loss (monitoring only)


def _rounded(x: jax.Array, compute_dtype) -> jax.Array:
    return x.astype(compute_dtype).astype(jnp.float32)


def row_dots(h: jax.Array, u, compute_dtype=jnp.float32) -> jax.Array:
    """``f[b, k] = sum_d h[b, d] * u[k][b, d]``, ``(B, K)`` float32: the
    logits of K blocks of ``(B, d)`` rows (a sequence, or a ``(K, B, d)``
    array) against one centre a batch row. Gathered rows keep the batch
    axis beside d; a small axis (contexts, negatives, a bag's slots) is
    only ever a MAJOR one, made on the ids before the gather and never on
    the rows after it: a float32 ``(..., k, d)`` is tiled ``(8, 128)`` on
    a TPU, pays for 8 where k is 5, and a reshape to or from it copies
    every row (PERF.md, PR 38). ``compute_dtype=bfloat16`` rounds both
    operands; products and sums stay float32, as on the MXU."""
    hc = _rounded(h, compute_dtype)
    return jnp.stack(
        [(hc * _rounded(block, compute_dtype)).sum(axis=-1) for block in u],
        axis=-1,
    )


def row_sums(c: jax.Array, u, compute_dtype=jnp.float32) -> jax.Array:
    """``sum_k c[b, k] * u[k][b, :]``, ``(B, d)`` float32: the blocks of
    :func:`row_dots`, each row weighted, summed over the major axis."""
    cc = _rounded(c, compute_dtype)
    terms = [
        cc[:, k, None] * _rounded(block, compute_dtype)
        for k, block in enumerate(u)
    ]
    return sum(terms[1:], terms[0])


def sgns_grads(
    h: jax.Array,  # (B, d) float32 — syn0 rows of the centers
    u_pos,  # C blocks of (B, d) float32 — syn1 rows of the contexts
    u_neg,  # C * n blocks of (B, d) float32 — syn1 rows of the negatives
    mask: jax.Array,  # (B, C) float32 — 1.0 where the context slot is real
    neg_mask: jax.Array,  # (B, C, n) float32 — negatives kept (see train step)
    alpha: jax.Array,  # () float32 learning rate
    compute_dtype=jnp.float32,
) -> SgnsGrads:
    """Forward + backward of the SGNS objective for pre-gathered rows, the
    blocks as :func:`row_dots` takes them (``u_neg[c * n + k]``: every
    pair ``(b, c)``'s k-th negative); every scalar array is batch-major,
    as the sampler and the scatter meet it.

    Objective per real (center, context) pair (README.md:10-15 model):
        L = -log sigma(u_ctx . h) - sum_n log sigma(-u_neg . h)
    SGD coefficients (matching the reference's label-vs-sigmoid form at
    mllib:422-424): c_pos = alpha*(1 - sigma(f_pos)), c_neg = -alpha*sigma(f_neg).

    ``compute_dtype=bfloat16`` feeds the d-contractions bf16 operands with
    f32 accumulation (the MXU-native regime); coefficient math, masking,
    and the loss stay f32. Word2vec SGD tolerates the ~3-decimal-digit
    operand rounding (embeddings are trained with far noisier estimators);
    the exactness-tested default stays f32.
    """
    f_pos = row_dots(h, u_pos, compute_dtype)
    f_neg = row_dots(h, u_neg, compute_dtype).reshape(neg_mask.shape)
    co = sgns_coefs(f_pos, f_neg, mask, neg_mask, alpha)
    d_center = sgns_d_center(co.c_pos, co.c_neg, u_pos, u_neg, compute_dtype)
    return SgnsGrads(
        c_pos=co.c_pos, c_neg=co.c_neg, d_center=d_center, loss=co.loss
    )


def sgns_d_center(
    c_pos: jax.Array,  # (B, C)
    c_neg: jax.Array,  # (B, C, n)
    u_pos,  # C blocks of (B, d)
    u_neg,  # C * n blocks of (B, d)
    compute_dtype=jnp.float32,
) -> jax.Array:
    """d L/d h with the learning rate folded in (pure SGD step direction)."""
    return row_sums(c_pos, u_pos, compute_dtype) + row_sums(
        c_neg.reshape(c_neg.shape[0], -1), u_neg, compute_dtype
    )


def gather_blocks(table: jax.Array, ids: jax.Array):
    """Float32 rows of ``table`` for batch-major ``ids`` ``(B, ...)``, as
    the K blocks of ``(B, d)`` :func:`row_dots` takes, block k the rows of
    every batch row's k-th id: the ids are transposed, not the rows."""
    return [
        table[col].astype(jnp.float32)
        for col in ids.reshape(ids.shape[0], -1).T
    ]


def init_tables(
    key: jax.Array, vocab_size: int, dim: int, dtype=jnp.float32
) -> Tuple[jax.Array, jax.Array]:
    """word2vec-standard init: syn0 ~ U[-0.5/d, 0.5/d), syn1 = 0."""
    syn0 = (
        jax.random.uniform(key, (vocab_size, dim), dtype=jnp.float32) - 0.5
    ) / dim
    return syn0.astype(dtype), jnp.zeros((vocab_size, dim), dtype=dtype)


def negative_mask(
    negs: jax.Array,  # (B, C, n) int32
    contexts: jax.Array,  # (B, C) int32
    mask: jax.Array,  # (B, C) float32
) -> jax.Array:
    """Mask for negative draws: drop a negative that equals its positive
    context word (the standard word2vec "target == word" skip), and zero out
    draws for padded context lanes."""
    keep = (negs != contexts[..., None]).astype(jnp.float32)
    return keep * mask[..., None]


class SharedSgnsGrads(NamedTuple):
    """Gradient pieces for the shared-negative-pool estimator."""

    c_pos: jax.Array  # (B, C)  alpha * (1 - sigmoid(f_pos)) * mask
    c_pool: jax.Array  # (B, S)  weighted negative coefficients per center
    d_center: jax.Array  # (B, d)
    d_pool: jax.Array  # (S, d)  dense update for the pool's syn1 rows
    loss: jax.Array  # ()


def shared_sgns_grads(
    h: jax.Array,  # (B, d) float32 — syn0 rows of the centers
    u_pos,  # C blocks of (B, d) float32 — syn1 rows of the contexts
    u_pool: jax.Array,  # (S, d) float32 — syn1 rows of the shared pool
    mask: jax.Array,  # (B, C) float32
    collide: jax.Array,  # (B, S) float32 — 1.0 where pool word hits one of
    #   the center's real context words (excluded, word2vec's target==word
    #   skip applied pool-wide)
    alpha: jax.Array,  # () float32
    num_negatives: int,  # n — the per-pair draw count being emulated
    compute_dtype=jnp.float32,
) -> SharedSgnsGrads:
    """SGNS gradients with one shared negative pool per step.

    The TPU-first restatement of negative sampling: the reference draws
    ``n`` fresh negatives per (center, context) pair server-side
    (``dotprod``'s seeded draws, mllib:420-421) — on TPU that becomes a
    gather of B*C*n arbitrary rows, a bandwidth-bound sparse access. Here
    each step draws ONE pool of S negatives shared by the whole batch and
    weights every center's pool term by ``m_i * n / S`` (m_i = its real
    context count): an unbiased Monte-Carlo estimator of the same expected
    NCE gradient (each pair still sees n expected noise draws from the
    same unigram^0.75 distribution), usually with *lower* variance since
    S >> n. All pool compute is dense:

        f_pool = h @ u_pool.T          (B, S)  MXU
        d_pool = c_pool.T @ h          (S, d)  MXU
        d_center += c_pool @ u_pool    (B, d)  MXU

    so the only sparse traffic left is the centers and positive contexts.

    ``compute_dtype=bfloat16`` runs the three dense matmuls with bf16
    operands and f32 accumulation — the MXU-native regime (v5e bf16 peak
    is ~2x its f32-via-passes rate); coefficients and loss stay f32.
    """
    hc = h.astype(compute_dtype)
    upool_c = u_pool.astype(compute_dtype)
    f_pos = row_dots(h, u_pos, compute_dtype)  # (B, C)
    f_pool = jnp.dot(
        hc, upool_c.T, preferred_element_type=jnp.float32
    )  # (B, S)
    co = shared_sgns_coefs(f_pos, f_pool, mask, collide, alpha, num_negatives)
    d_center, d_pool = shared_sgns_updates(
        co.c_pos, co.c_pool, h, u_pos, u_pool, compute_dtype
    )
    return SharedSgnsGrads(
        c_pos=co.c_pos, c_pool=co.c_pool, d_center=d_center, d_pool=d_pool,
        loss=co.loss,
    )


class SharedSgnsCoefs(NamedTuple):
    """Logit-stage outputs of the shared-pool estimator (see
    :class:`SgnsCoefs`)."""

    c_pos: jax.Array  # (B, C)
    c_pool: jax.Array  # (B, S)
    loss: jax.Array  # ()


def shared_sgns_coefs(
    f_pos: jax.Array,  # (B, C) float32 FULL positive logits
    f_pool: jax.Array,  # (B, S) float32 FULL pool logits
    mask: jax.Array,  # (B, C) float32
    collide: jax.Array,  # (B, S) float32
    alpha: jax.Array,  # () float32
    num_negatives: int,
) -> SharedSgnsCoefs:
    """Coefficients + loss from already-reduced logits."""
    s_pos = jax.nn.sigmoid(f_pos)
    s_pool = jax.nn.sigmoid(f_pool)
    m_i = mask.sum(axis=1)  # (B,) real context count per center
    S = f_pool.shape[1]
    keep = 1.0 - collide
    weight = (m_i * (num_negatives / S))[:, None] * keep  # (B, S)
    c_pos = alpha * (1.0 - s_pos) * mask
    c_pool = -alpha * s_pool * weight
    log_sig = jax.nn.log_sigmoid
    pos_loss = (-log_sig(f_pos) * mask).sum()
    pool_loss = (-log_sig(-f_pool) * weight).sum()
    loss = (pos_loss + pool_loss) / jnp.maximum(mask.sum(), 1.0)
    return SharedSgnsCoefs(c_pos=c_pos, c_pool=c_pool, loss=loss)


def shared_sgns_updates(
    c_pos: jax.Array,  # (B, C)
    c_pool: jax.Array,  # (B, S)
    h: jax.Array,  # (B, d)
    u_pos,  # C blocks of (B, d)
    u_pool: jax.Array,  # (S, d)
    compute_dtype=jnp.float32,
) -> Tuple[jax.Array, jax.Array]:
    """(d_center, d_pool) from coefficients."""
    cpool_c = c_pool.astype(compute_dtype)
    upool_c = u_pool.astype(compute_dtype)
    d_center = row_sums(c_pos, u_pos, compute_dtype) + jnp.dot(
        cpool_c, upool_c, preferred_element_type=jnp.float32
    )
    d_pool = jnp.dot(
        cpool_c.T, h.astype(compute_dtype), preferred_element_type=jnp.float32
    )  # (S, dl)
    return d_center, d_pool


def pool_collision_mask(
    pool: jax.Array,  # (S,) int32 — shared negative pool
    contexts: jax.Array,  # (B, C) int32
    mask: jax.Array,  # (B, C) float32
) -> jax.Array:
    """(B, S) mask, 1.0 where a pool word equals one of that row's real
    context words — the pool-wide generalization of the per-draw
    ``target == word`` skip (see :func:`negative_mask`).

    Membership is tested by sorting each row's C context words and binary-
    searching the pool into them, so the peak intermediate is O(B*S) — the
    same order as the (B, S) result — rather than the O(B*C*S) boolean of
    the naive broadcast compare (~235 MB of transient at the bench shape
    B=8192, C=7, S=4096, which could dominate step memory)."""
    sentinel = jnp.iinfo(jnp.int32).max
    ctx = jnp.where(mask > 0, contexts, sentinel)  # padded lanes never match
    ctx_sorted = jnp.sort(ctx, axis=1)  # (B, C) — C is tiny
    idx = jax.vmap(
        lambda row: jnp.searchsorted(row, pool, side="left")
    )(ctx_sorted)  # (B, S)
    idx = jnp.clip(idx, 0, ctx_sorted.shape[1] - 1)
    found = jnp.take_along_axis(ctx_sorted, idx, axis=1) == pool[None, :]
    return found.astype(jnp.float32)


def train_step(
    syn0: jax.Array,  # (V, d)
    syn1: jax.Array,  # (V, d)
    prob: jax.Array,  # (V,) alias acceptance probs
    alias: jax.Array,  # (V,) alias targets
    centers: jax.Array,  # (B,) int32
    contexts: jax.Array,  # (B, C) int32
    mask: jax.Array,  # (B, C) float32
    key: jax.Array,
    alpha: jax.Array,  # () float32
    num_negatives: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One fused single-device SGNS minibatch update.

    Returns (new_syn0, new_syn1, loss). Jit with donated syn0/syn1 for
    in-place HBM updates. Duplicate indices within a batch *sum* their
    updates (XLA scatter-add) — the synchronous-batch semantics replacing the
    reference's async Hogwild races (SURVEY.md §2.3, §7 hard part 3).
    """
    B, C = contexts.shape
    negs = sample_negatives_per_row(
        key, prob, alias, jnp.arange(B, dtype=jnp.int32), (C, num_negatives)
    )
    compute = jnp.float32
    h = syn0[centers].astype(compute)
    u_pos = gather_blocks(syn1, contexts)
    u_neg = gather_blocks(syn1, negs)
    nmask = negative_mask(negs, contexts, mask)

    g = sgns_grads(h, u_pos, u_neg, mask, nmask, alpha.astype(compute))

    # Rank-1 updates, scatter-added into the tables.
    d_upos = g.c_pos[..., None] * h[:, None, :]  # (B, C, d)
    d_uneg = g.c_neg[..., None] * h[:, None, None, :]  # (B, C, n, d)
    syn0 = syn0.at[centers].add(g.d_center.astype(syn0.dtype))
    syn1 = syn1.at[contexts.reshape(-1)].add(
        d_upos.reshape(B * C, -1).astype(syn1.dtype)
    )
    syn1 = syn1.at[negs.reshape(-1)].add(
        d_uneg.reshape(B * C * num_negatives, -1).astype(syn1.dtype)
    )
    return syn0, syn1, g.loss


def train_step_pairs(
    syn0: jax.Array,  # (V, d)
    syn1: jax.Array,  # (V, d)
    prob: jax.Array,  # (V,) alias acceptance probs
    alias: jax.Array,  # (V,) alias targets
    centers: jax.Array,  # (P,) int32 — one CENTER per pair row
    contexts: jax.Array,  # (P,) int32 — one CONTEXT per pair row
    pair_mask: jax.Array,  # (P,) float32 — 1.0 where the pair is real
    key: jax.Array,
    alpha: jax.Array,  # () float32
    num_negatives: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One fused SGNS update over a DENSE pair list — the packed-dispatch
    step (ops/device_batching.pack_window_pairs feeds it). Each batch row
    is exactly one (center, context) pair, i.e. the C=1 specialization of
    :func:`train_step`: no lane of the contraction is ever masked padding,
    so every dispatched FLOP is a useful pair (the pSGNScc dense-batch
    restructuring, arxiv 1604.04661). Negatives are drawn per PAIR row,
    keyed by the row's global index — the same mesh-invariant keying
    discipline as :func:`~glint_word2vec_tpu.ops.sampling
    .sample_negatives_per_row` everywhere else. Because scatter-adds sum,
    decomposing a grid batch into its valid pairs and feeding them here
    applies the identical table update (pinned by the decomposition test
    in tests/test_packed.py)."""
    return train_step(
        syn0, syn1, prob, alias, centers, contexts[:, None],
        pair_mask[:, None], key, alpha, num_negatives,
    )


def sgns_loss(
    syn0: jax.Array,
    syn1: jax.Array,
    prob: jax.Array,
    alias: jax.Array,
    centers: jax.Array,
    contexts: jax.Array,
    mask: jax.Array,
    key: jax.Array,
    num_negatives: int,
) -> jax.Array:
    """Forward-only masked-mean SGNS loss (the jittable inference/eval fn)."""
    B, C = contexts.shape
    negs = sample_negatives_per_row(
        key, prob, alias, jnp.arange(B, dtype=jnp.int32), (C, num_negatives)
    )
    h = syn0[centers].astype(jnp.float32)
    u_pos = gather_blocks(syn1, contexts)
    u_neg = gather_blocks(syn1, negs)
    nmask = negative_mask(negs, contexts, mask)
    g = sgns_grads(h, u_pos, u_neg, mask, nmask, jnp.float32(1.0))
    return g.loss
