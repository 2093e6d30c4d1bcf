"""On-device negative sampling from the unigram^0.75 noise distribution.

Reference: the Glint servers draw ``n`` negatives per (center, context) pair
from a shared quantized unigram table, seeded by the client so all servers
draw identically (``matrix.dotprod(..., seed)``, mllib:420-421; SURVEY.md
§2.2). Here the draw happens *inside* the jit-compiled train step from a
replicated alias table (see corpus/alias.py): O(1) per draw, exact
distribution, reproducible from the step's PRNG key — the same contract
(seed -> identical negatives everywhere) without a server round-trip.

The alias table has two forms that give the same draws bit for bit. The
plain one is the pair ``prob (V,) float32``, ``alias (V,) int32``: a draw
reads both at its entry, two scalar gathers. The packed one
(:func:`pack_alias_table`) keeps an entry's pair in one row of whole lanes,
so a draw is ONE row gather and a lane select: a TPU prices a gather by
the element it fetches, not by its size, and a row of 128 lanes costs
less than one scalar does. The engine's step programs read the packed
form (``EmbeddingEngine._alias_packed``, made where the plain pair is
uploaded); the plain functions stay for callers that hold the pair.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: Alias entries in one row of the packed table: lanes [0, 64) hold the
#: entries' acceptance probabilities (their float32 bits), lanes
#: [64, 128) the alias targets.
ENTRIES_PER_ROW = 64


def pack_alias_table(prob: np.ndarray, alias: np.ndarray) -> np.ndarray:
    """The alias table as ``(ceil(V / 64), 128) int32`` rows: entry ``k``
    lies in row ``k // 64``, its probability's bits at lane ``k % 64`` and
    its alias at lane ``64 + k % 64``. Entries past V (the last row's
    padding) are zero and never drawn."""
    prob = np.ascontiguousarray(prob, dtype=np.float32)
    alias = np.ascontiguousarray(alias, dtype=np.int32)
    E = ENTRIES_PER_ROW
    pad = -prob.shape[0] % E
    return np.concatenate(
        [
            np.pad(prob.view(np.int32), (0, pad)).reshape(-1, E),
            np.pad(alias, (0, pad)).reshape(-1, E),
        ],
        axis=1,
    )


def _draws(key: jax.Array, vocab: int, shape: tuple):
    """The alias method's two random numbers a draw: a uniform entry ``k``
    and the ``u`` it is kept against. The key schedule is the same for
    every form of the table."""
    k_key, u_key = jax.random.split(key)
    k = jax.random.randint(k_key, shape, 0, vocab, dtype=jnp.int32)
    u = jax.random.uniform(u_key, shape, dtype=jnp.float32)
    return k, u


def _accept(k: jax.Array, u: jax.Array, prob: jax.Array, alias: jax.Array):
    """A draw keeps its entry with the entry's acceptance probability, else
    takes the entry's alias: two scalar gathers a draw."""
    return jnp.where(u < prob[k], k, alias[k])


def _accept_packed(k: jax.Array, u: jax.Array, packed: jax.Array):
    """:func:`_accept` from the packed table: ONE row gather a draw. The
    row's 64 candidates are decided lane by lane (``u < prob`` of the
    lane, then ``k`` or the lane's alias), and the draw's own lane is
    picked out by a compare and one sum over the row (one term is not
    zero: exact). Done flat, whatever shape the draws have: the rows then
    lie eight to a vector register."""
    E = ENTRIES_PER_ROW
    kf, uf = k.reshape(-1, 1), u.reshape(-1, 1)
    row = packed[kf[:, 0] // E]  # (M, 2E)
    keep = uf < lax.bitcast_convert_type(row[:, :E], jnp.float32)
    cand = jnp.where(keep, kf, row[:, E:])
    hot = kf % E == jnp.arange(E, dtype=jnp.int32)
    return jnp.where(hot, cand, 0).sum(axis=1).reshape(k.shape)


def sample_negatives(
    key: jax.Array,
    prob: jax.Array,  # (V,) float32 alias acceptance probabilities
    alias: jax.Array,  # (V,) int32 alias targets
    shape: tuple,
) -> jax.Array:
    """Draw ``shape`` samples from the alias table: int32 indices in [0, V)."""
    return _accept(*_draws(key, prob.shape[0], shape), prob, alias)


def sample_negatives_packed(
    key: jax.Array,
    packed: jax.Array,  # pack_alias_table's rows
    vocab: int,  # V: the rows do not say where their padding starts
    shape: tuple,
) -> jax.Array:
    """:func:`sample_negatives` from the packed table: the same draws."""
    return _accept_packed(*_draws(key, vocab, shape), packed)


def _draws_per_row(key: jax.Array, vocab: int, rows: jax.Array,
                   shape_per_row: tuple):
    """:func:`_draws` for each batch row, keyed by the row's global index."""
    # Domain-separate before the per-row fold: user step keys are often
    # low-entropy (PRNGKey(step)), and one threefry round over both a small
    # key and a small row id can yield streams unlucky enough to matter in
    # tiny-vocab training; the constant mix adds a full extra round.
    base = jax.random.fold_in(key, 0x6E656773)  # "negs"
    keys = jax.vmap(lambda r: jax.random.fold_in(base, r))(rows)
    return jax.vmap(lambda k: _draws(k, vocab, shape_per_row))(keys)


def sample_negatives_per_row(
    key: jax.Array,
    prob: jax.Array,  # (V,) float32 alias acceptance probabilities
    alias: jax.Array,  # (V,) int32 alias targets
    rows: jax.Array,  # (B,) int32 GLOBAL batch-row indices
    shape_per_row: tuple,
) -> jax.Array:
    """Per-batch-row negative draws keyed by global row index.

    Returns ``(B, *shape_per_row)`` int32 samples where row ``i``'s draws
    depend only on ``(key, rows[i])``. This is the sharded-sampling form of
    the reference's seed contract (servers all draw identically from the
    broadcast seed, mllib:420-421): a data rank holding global rows
    [r0, r0+Bl) reproduces exactly the draws a single-rank run makes for
    those rows, while doing only O(local rows) sampling work — no rank ever
    draws the global batch (round-3 directive: no ``B_global`` in the
    sampled shape).
    """
    return _accept(
        *_draws_per_row(key, prob.shape[0], rows, shape_per_row), prob, alias
    )


def sample_negatives_per_row_packed(
    key: jax.Array,
    packed: jax.Array,
    vocab: int,
    rows: jax.Array,
    shape_per_row: tuple,
) -> jax.Array:
    """:func:`sample_negatives_per_row` from the packed table: the same
    draws."""
    return _accept_packed(
        *_draws_per_row(key, vocab, rows, shape_per_row), packed
    )
