"""The plain reference of the word-level family's synonym query.

The reference's ``findSynonyms(vector, num, wordOpt)``
(``mllib/ServerSideGlintWord2Vec.scala:583-629``; ``SURVEY.md`` section 3.3)
in straightforward float32 numpy, in the SOURCE's form: normalise the query
(``blas.snrm2`` / ``sscal``, left as it is where its norm is zero), score
EVERY row against it (``matrix.multiply``, which each of the n servers
answers for its 1/n of the rows), divide each score by the row's norm
(``matrix.norms``) with the zero-norm guard, drop the query word, take the
``num`` largest. ``findSynonyms(word, num)`` is that over the word's own
row (``transform(word)``, a ``matrix.pull``); an analogy is the caller's
arithmetic on pulled rows, then the same query by vector.

The table is handed over in row BLOCKS, ``(first_row, rows)`` in any order,
as the servers hold it: the table is never needed whole, and a model that
no one machine holds is scored as it lies. Nothing here is shared with the
serving path (``serving._SynonymCoalescer``, ``EmbeddingEngine``'s top-k
programs), which ``tests/test_serving_sharded.py`` holds to it.

    answer(q, num) = the num rows v != ban of largest
                     (table[v] . q / |q|) / |table[v]|

Departures from the source:

* **The score is a cosine for any query.** ``mllib:598-609`` multiplies by
  the query as handed in, so its ranks are cosine ranks while its scores
  are cosines only for a query of norm one; the word form normalises the
  pulled row first (``mllib:593-595``), and so does every form here.
* **A row whose norm is zero is never an answer.** The source's guard
  (``mllib:603-609``) gives it the score 0, above every word of negative
  cosine; the engine's mask drops it, and so does this.
* **Only the first ``n_queryable`` rows are words.** Rows behind them
  (padding, bucket rows, spare rows) are not scored.
* **Ties go to the lower row.** The source's bounded priority queue leaves
  them in no stated order.
"""

from __future__ import annotations

from typing import Iterable, List, Sequence, Tuple

import numpy as np

Block = Tuple[int, np.ndarray]


def pull(blocks: Iterable[Block], row: int) -> np.ndarray:
    """``transform(word)``: one row of the table, from the block that
    holds it."""
    for first, rows in blocks:
        if first <= row < first + rows.shape[0]:
            return np.asarray(rows[row - first], np.float32)
    raise KeyError(f"no block holds row {row}")


def cosines(blocks: Iterable[Block], vec, n_queryable: int) -> np.ndarray:
    """``(n_queryable,)`` float32: every word row's cosine to ``vec``,
    ``-inf`` for a row of norm zero."""
    q = np.asarray(vec, np.float32)
    norm = np.float32(np.sqrt(np.dot(q, q)))
    if norm > 0:
        q = q / norm
    out = np.full(n_queryable, -np.inf, np.float32)
    for first, rows in blocks:
        rows = np.asarray(rows, np.float32)[: max(0, n_queryable - first)]
        if not rows.shape[0]:
            continue
        score = rows @ q  # matrix.multiply: this server's rows
        norms = np.sqrt(np.einsum("vd,vd->v", rows, rows))  # matrix.norms
        ok = norms > 0
        out[first:first + rows.shape[0]] = np.where(
            ok, score / np.where(ok, norms, np.float32(1)), -np.inf
        )
    return out


def find_synonyms_vector(
    blocks: Iterable[Block], vec, num: int, n_queryable: int,
    ban: Sequence[int] = (),
) -> List[Tuple[int, float]]:
    """The ``num`` rows of largest cosine to ``vec``, the rows of ``ban``
    left out: ``[(row, cosine), ...]``, best first, the lower row first
    among equals."""
    cos = cosines(blocks, vec, n_queryable)
    cos[list(ban)] = -np.inf
    order = np.argsort(-cos, kind="stable")[:num]
    return [(int(i), float(cos[i])) for i in order if np.isfinite(cos[i])]


def find_synonyms(blocks, row: int, num: int,
                  n_queryable: int) -> List[Tuple[int, float]]:
    """``findSynonyms(word, num)`` for the word of ``row``."""
    blocks = list(blocks)
    return find_synonyms_vector(
        blocks, pull(blocks, row), num, n_queryable, ban=[row]
    )


def analogy(blocks, positive: Sequence[int], negative: Sequence[int],
            num: int, n_queryable: int) -> List[Tuple[int, float]]:
    """The caller-side analogy (``ServerSideGlintWord2VecSpec.scala:
    342-344``): the sum of the ``positive`` rows less the ``negative``
    ones, then the query by vector; the input rows are not answers."""
    blocks = list(blocks)
    vec = sum(pull(blocks, r) for r in positive) - sum(
        pull(blocks, r) for r in negative
    )
    return find_synonyms_vector(
        blocks, vec, num, n_queryable, ban=[*positive, *negative]
    )
