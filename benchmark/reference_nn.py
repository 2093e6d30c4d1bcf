"""The plain reference of the served subword configuration
(``ft-nn-300-1m-2mb``): ``fasttext nn`` in numpy float32. Nothing here
imports the program: nothing of ``ops/``, ``models/`` or ``corpus/subword.py``.

The public tool's query (facebookresearch/fastText ``FastText::getNN``):
``precomputeWordVectors`` composes the vector of every dictionary word, the
mean of its own ``syn0`` row and the rows its character n-grams hash to, and
normalises it; the query word's vector is ``getWordVector``, the same mean,
over the n-gram rows alone where the word is in no dictionary
(``Dictionary::getSubwords``); the answer is the ``num`` dictionary words of
largest cosine, the query word itself banned.

The reference's own: the group tables, through ``reference_subword.py``'s
n-gram cutter and FNV-1a (``groups`` for dictionary words, ``oov_groups`` for
words outside it: the same cut without a word row); ``compose``, the groups'
means from a host copy of ``syn0``, in blocks of words and a group slot at a
time (over the dictionary's groups: ``precomputeWordVectors`` before its
normalisation); ``NN``, cosines of query vectors against that table and
how far a served answer lies from the reference's.

Departures from the tool, the program's and so the reference's: a group is cut
at ``max_subwords`` rows; a word with no row and no n-gram is refused (the
traffic holds none); ties go to the lower row.
"""

import numpy as np

from benchmark.reference_subword import group_table

BLOCK = 1 << 17  # words a piece: bounds what one gather holds


def groups(words, bucket: int, min_n: int, max_n: int,
           max_subwords: int) -> np.ndarray:
    """(len(words), max_subwords) int32: the rows of each DICTIONARY word's
    group, its own row first, -1 where it has fewer."""
    return group_table(words, len(words), bucket, min_n, max_n, max_subwords)


def oov_groups(words, vocab: int, bucket: int, min_n: int, max_n: int,
               max_subwords: int) -> np.ndarray:
    """The same for words OUTSIDE a dictionary of ``vocab`` words: their
    n-grams' bucket rows alone, cut at ``max_subwords``."""
    return group_table(words, vocab, bucket, min_n, max_n,
                       max_subwords + 1)[:, 1:]


def compose(syn0: np.ndarray, grp: np.ndarray) -> np.ndarray:
    """(len(grp), d) float32: each group's mean over its live rows, summed a
    slot at a time in slot order (a group with no live row gives zeros)."""
    out = np.zeros((grp.shape[0], syn0.shape[1]), np.float32)
    for s in range(0, grp.shape[0], BLOCK):
        g = grp[s:s + BLOCK]
        acc = out[s:s + BLOCK]
        for slot in range(g.shape[1]):
            live = np.flatnonzero(g[:, slot] >= 0)
            if not live.size:
                break  # a group's live slots lead
            acc[live] += syn0[g[live, slot]]
        acc /= np.maximum((g >= 0).sum(axis=1), 1).astype(
            np.float32)[:, None]
    return out


class NN:
    """Cosines against the composed dictionary, and the distance of a served
    answer from the reference's."""

    def __init__(self, composed: np.ndarray):
        self.w = np.ascontiguousarray(composed, dtype=np.float32)
        self.norms = np.linalg.norm(self.w, axis=1)

    def cosines(self, queries: np.ndarray) -> np.ndarray:
        """(V, Q) cosines of every dictionary word against the query
        vectors; -inf for a word whose vector is zero."""
        q = np.asarray(queries, np.float32)
        q = q / np.linalg.norm(q, axis=1, keepdims=True)
        safe = np.where(self.norms > 0, self.norms, 1.0)
        cos = (self.w @ q.T) / safe[:, None]
        cos[self.norms <= 0] = -np.inf
        return cos

    @staticmethod
    def gap(row, cos: np.ndarray, got, k: int) -> float:
        """How far ``got`` ([(row, score), ...]) is from the reference top-k
        given ``cos`` (V,), dictionary row ``row`` banned (None for a word
        outside the dictionary: nothing is): the largest of |score -
        reference| and, rank by rank, the reference-score distance between
        the row served and the row the reference ranks there. inf for a
        wrong count, an unknown row or the banned row."""
        cos = cos.copy()
        if row is not None:
            cos[row] = -np.inf
        order = np.argpartition(-cos, k)[:k]
        order = order[np.lexsort((order, -cos[order]))]  # ties: lower row
        if len(got) != k:
            return float("inf")
        worst = 0.0
        for j, (i, s) in enumerate(got):
            if i is None or not 0 <= i < cos.shape[0] or i == row:
                return float("inf")
            worst = max(worst, abs(float(s) - float(cos[i])),
                        abs(float(cos[i]) - float(cos[order[j]])))
        return worst
