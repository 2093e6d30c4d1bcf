"""The plain reference of the subword family's nearest-neighbour query.

``fasttext nn <model>`` (facebookresearch/fastText, ``FastText::getNN``) in
straightforward ``jax.numpy`` float32, the one product at ``highest``
precision, in the SOURCE's form: compose every dictionary word's vector
(``precomputeWordVectors``), normalise each, compose the query word's vector
(``getWordVector``), one matrix-vector product, ban the query word, take the
``num`` largest. Nothing here is shared with the serving path
(``serving._SynonymCoalescer``, ``models/fasttext.FastTextModel``), which
``tests/test_serving_subword.py`` holds to it.

For a dictionary word ``w`` with row ``w`` and 5-gram (``min_n`` to
``max_n``-gram) bucket rows ``b_1 .. b_j``, and a word ``q`` that is in no
dictionary and so has bucket rows only::

    u_w = (syn0[w] + syn0[b_1] + ... + syn0[b_j]) / (1 + j)
    u_q = (syn0[b_1] + ... + syn0[b_j]) / j
    answer(q, num) = the num dictionary words w != q of largest
                     (u_q . u_w) / (|u_q| |u_w|)

Departures from the tool:

* **A word with no n-gram is refused, not zero.** A ``q`` outside the
  dictionary and too short for any n-gram has no rows; the tool hands back a
  zero vector and ranks every word at 0, here it is a ``KeyError`` (the
  server's 404).
* **Groups are cut at ``max_subwords``.** The word's own row and its first
  n-grams, the shorter first, then by start
  (``corpus/subword.subword_group``); fastText keeps them all.
* **Ties go to the lower row.** The tool's heap leaves them in no stated
  order.
* A dictionary word whose vector is zero is never an answer (the tool
  divides by a norm floored at 1e-8 and ranks it at 0).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from glint_word2vec_tpu.corpus.subword import subword_group

_HI = jax.lax.Precision.HIGHEST


def _mean_rows(syn0, ids: Sequence[int]):
    return syn0[jnp.asarray(ids, jnp.int32)].sum(axis=0) / len(ids)


def word_vectors(syn0, words: Sequence[str], bucket: int, min_n: int,
                 max_n: int, max_subwords: int):
    """``(V, d)``: every dictionary word's composed vector, word by word."""
    V = len(words)
    return jnp.stack([
        _mean_rows(syn0, subword_group(
            w, i, V, bucket, min_n, max_n, max_subwords))
        for i, w in enumerate(words)
    ])


def query_vector(syn0, words: Sequence[str], query: str, bucket: int,
                 min_n: int, max_n: int, max_subwords: int):
    """The query word's vector and its dictionary row (None outside the
    dictionary). ``KeyError`` where it has no row and no n-gram."""
    words = list(words)
    row: Optional[int] = words.index(query) if query in words else None
    ids = subword_group(
        query, row, len(words), bucket, min_n, max_n, max_subwords
    )
    if not ids:
        raise KeyError(f"word {query!r} has no dictionary row and no n-gram")
    return _mean_rows(syn0, ids), row


def nn_vector(composed, vec, num: int,
              ban: Optional[int] = None) -> List[Tuple[int, float]]:
    """The ``num`` rows of ``composed`` of largest cosine to ``vec``, row
    ``ban`` left out: ``[(row, cosine), ...]``, best first."""
    norms = jnp.linalg.norm(composed, axis=1)
    unit = composed / jnp.where(norms > 0, norms, 1.0)[:, None]
    q = vec / jnp.maximum(jnp.linalg.norm(vec), 1e-30)
    cos = jnp.einsum("vd,d->v", unit, q, precision=_HI)
    # graftlint: ignore[sync-point] the reference's answer is read back
    cos = np.array(jnp.where(norms > 0, cos, -jnp.inf), np.float32)
    if ban is not None:
        cos[ban] = -np.inf
    order = np.argsort(-cos, kind="stable")[:num]  # stable: lower row first
    return [(i, s) for i, s in zip(order.tolist(), cos[order].tolist())
            if np.isfinite(s)]


def nn(syn0, words: Sequence[str], query: str, num: int, *, bucket: int,
       min_n: int, max_n: int, max_subwords: int) -> List[Tuple[str, float]]:
    """``fasttext nn``: the ``num`` dictionary words nearest ``query`` by
    cosine over composed vectors, ``[(word, cosine), ...]``."""
    syn0 = jnp.asarray(syn0, jnp.float32)
    geometry = (bucket, min_n, max_n, max_subwords)
    composed = word_vectors(syn0, words, *geometry)
    vec, row = query_vector(syn0, words, query, *geometry)
    return [(words[i], s) for i, s in nn_vector(composed, vec, num, row)]
