"""The plain reference of the sharded served cell: the reference's
``findSynonyms`` over a table held in row blocks, numpy float32.

The benchmark's own copy (it imports nothing of ``glint_word2vec_tpu``): in
the source's form, each block, as each of the n servers, scores ITS rows
against the normalised query (``matrix.multiply``) and divides by ITS rows'
norms (``matrix.norms``, computed here from the block) with the zero-norm
guard; the driver drops the query word and takes ``num``. The blocks are the
table as the kind read it back from the devices during set-up, so the table
is never needed whole in one array.

A row of norm zero is never an answer, and ties go to the lower row.
"""

import numpy as np


class Blocks:
    """``(first_row, rows)`` float32 blocks of one table, in row order,
    with each block's own norms."""

    def __init__(self, dim: int):
        self.dim = int(dim)
        self.blocks = []  # (first, rows (n, d) f32, norms (n,) f32)
        self.rows_total = 0

    def add(self, first: int, rows) -> None:
        rows = np.ascontiguousarray(rows, dtype=np.float32)
        if first != self.rows_total or rows.shape[1] != self.dim:
            raise ValueError("blocks are added in row order, dim wide")
        self.blocks.append((int(first), rows, None))
        self.rows_total += rows.shape[0]

    def rows(self, ids) -> np.ndarray:
        """``matrix.pull``: the rows ``ids``, each from its block."""
        firsts = np.asarray([b[0] for b in self.blocks])
        out = np.empty((len(ids), self.dim), np.float32)
        for j, i in enumerate(ids):
            first, rows, _ = self.blocks[
                int(np.searchsorted(firsts, i, side="right")) - 1]
            out[j] = rows[i - first]
        return out

    def cosines(self, queries) -> np.ndarray:
        """``(rows_total, Q)`` float32: every row's cosine to each of the
        ``(Q, d)`` queries, ``-inf`` for a row of norm zero."""
        q = np.asarray(queries, np.float32)
        norm = np.sqrt(np.einsum("qd,qd->q", q, q))
        q = q / np.where(norm > 0, norm, np.float32(1))[:, None]
        out = np.empty((self.rows_total, q.shape[0]), np.float32)
        for k, (first, rows, norms) in enumerate(self.blocks):
            if norms is None:
                norms = np.sqrt(np.einsum("vd,vd->v", rows, rows))
                self.blocks[k] = (first, rows, norms)
            ok = norms > 0
            cos = (rows @ q.T) / np.where(ok, norms, np.float32(1))[:, None]
            cos[~ok] = -np.inf
            out[first:first + rows.shape[0]] = cos
        return out


def top(cos, num: int, ban=None):
    """``[(row, cosine), ...]``: the ``num`` largest of ``cos`` (V,), row
    ``ban`` left out, best first, the lower row first among equals."""
    cos = cos.copy()
    if ban is not None:
        cos[ban] = -np.inf
    order = np.argpartition(-cos, num)[:num] if num < cos.shape[0] else (
        np.arange(cos.shape[0]))
    # argpartition keeps some of a tie that straddles its edge: take
    # every row that ties with the last kept one, then order them all
    edge = cos[order].min()
    order = np.flatnonzero(cos >= edge)
    order = order[np.argsort(-cos[order], kind="stable")][:num]
    return [(int(i), float(cos[i])) for i in order if np.isfinite(cos[i])]


def gap(cos, got, num: int, ban=None) -> float:
    """How far ``got`` ([(row, score), ...]) is from the reference's answer,
    given ``cos`` (V,): the largest of |score - reference's| and, rank by
    rank, the reference-score distance between the row served and the row
    the reference ranks there (0 for the same order; a swap is small only
    between near-ties). inf for a wrong count or an unknown row."""
    want = top(cos, num, ban)
    if len(got) != len(want):
        return float("inf")
    worst = 0.0
    for (i, s), (_, ref) in zip(got, want):
        if i is None or not 0 <= i < cos.shape[0] or i == ban:
            return float("inf")
        worst = max(worst, abs(float(s) - float(cos[i])),
                    abs(float(cos[i]) - ref))
    return worst
