"""A word -> row index that looks up a whole chunk of tokens in numpy.

``dict.get`` over a 2M-word vocabulary costs a chain of cache misses a rare
word (the hash slot, the entry, the key's string, the value's int): a third
of a microsecond a token on a Zipf stream, one token at a time under the
interpreter. Here a chunk's tokens are joined into ONE byte string, each
token's first 16 UTF-8 bytes are read as two uint64 words, and the rows come
from an open-addressing table of (key, key, row) by a few numpy gathers,
whose misses overlap: about 0.15 us a token at 2M words (PERF.md, PR 50).

Exact, not approximate: a hit compares all 16 key bytes, and a word the
table cannot key (over 16 bytes, empty, or holding a NUL, which would read
as padding) lives in a plain dictionary beside it. :class:`StreamVocab`
keeps its ``word_index`` dictionary as the statement of the vocabulary; this
is only the fast path of its chunked look-ups, held to it by tests.
"""

from __future__ import annotations

from operator import methodcaller
from typing import Dict, Optional, Sequence

import numpy as np

KEY_BYTES = 16
_ENCODE = methodcaller("encode", "utf-8", "surrogatepass")
_C0 = np.uint64(0x9E3779B97F4A7C15)
_C1 = np.uint64(0xC2B2AE3D27D4EB4F)
#: ``_MASKS[n]`` keeps the low ``n`` bytes of a little-endian uint64.
_MASKS = np.array(
    [(1 << (8 * i)) - 1 for i in range(8)] + [(1 << 64) - 1], dtype=np.uint64
)


def _keys(tokens: Sequence[str]):
    """``(k0, k1, ok)`` of a chunk: each token's UTF-8 bytes, zero padded to
    16, as two little-endian uint64 words, and whether the pair keys it
    (1 to 16 bytes). None when some token holds a NUL byte."""
    n = len(tokens)
    text = " ".join(tokens)
    blob = _ENCODE(text)
    if blob.find(b"\0") >= 0:
        return None
    if text.isascii():
        lens = np.fromiter(map(len, tokens), np.int64, n)
    else:
        lens = np.fromiter(map(len, map(_ENCODE, tokens)), np.int64, n)
    starts = np.zeros(n, np.int64)
    np.cumsum(lens[:-1] + 1, out=starts[1:])
    padded = np.frombuffer(blob + b"\0" * KEY_BYTES, np.uint8)
    # every byte offset read as an (unaligned) uint64
    u64 = np.ndarray((padded.size - 7,), np.uint64, padded, 0, (1,))
    k0 = u64[starts] & _MASKS[np.minimum(lens, 8)]
    k1 = u64[starts + 8] & _MASKS[np.clip(lens - 8, 0, 8)]
    return k0, k1, (lens > 0) & (lens <= KEY_BYTES)


class WordIndex:
    """Rows of ``words`` (row ``i`` for ``words[i]``), growable by
    :meth:`add`."""

    def __init__(self, words: Sequence[str]):
        self._n = 0
        self._other: Dict[str, int] = {}
        self._alloc(max(len(words), 8))
        self._add_many(list(words), 0)

    def _alloc(self, rows: int) -> None:
        bits = int(np.ceil(np.log2(rows * 2)))  # load factor under a half
        self._shift = np.uint64(64 - bits)
        self._wrap = (1 << bits) - 1
        self._k0 = np.zeros(1 << bits, np.uint64)
        self._k1 = np.zeros(1 << bits, np.uint64)
        self._row = np.full(1 << bits, -1, np.int32)

    def _slot(self, k0: np.ndarray, k1: np.ndarray) -> np.ndarray:
        with np.errstate(over="ignore"):
            h = ((k0 * _C0) ^ (k1 * _C1 + (k0 >> np.uint64(29)))) * _C0
        return (h >> self._shift).astype(np.int64)

    def _place(self, k0, k1, rows) -> None:
        """Put keyed rows into free slots, linear probing, a whole batch a
        pass: the first claimant of a free slot takes it, the rest move
        on."""
        slot = self._slot(k0, k1)
        pend = np.arange(rows.size)
        while pend.size:
            s = slot[pend]
            free = np.flatnonzero(self._row[s] < 0)
            _, first = np.unique(s[free], return_index=True)
            won = pend[free[first]]
            self._k0[slot[won]] = k0[won]
            self._k1[slot[won]] = k1[won]
            self._row[slot[won]] = rows[won]
            lost = np.ones(pend.size, bool)
            lost[free[first]] = False
            pend = pend[lost]
            slot[pend] = (slot[pend] + 1) & self._wrap

    def _add_many(self, words, first_row: int) -> None:
        if not words:
            return
        keyed = _keys(words)
        if keyed is None:  # a NUL somewhere: key word by word
            for i, w in enumerate(words):
                self.add(w, first_row + i)
            return
        k0, k1, ok = keyed
        for i in np.flatnonzero(~ok):
            self._other[words[i]] = first_row + int(i)
        keep = np.flatnonzero(ok)
        self._place(k0[keep], k1[keep], (first_row + keep).astype(np.int32))
        self._n += len(words)

    def add(self, word: str, row: int) -> None:
        """Key one more word (a promotion)."""
        if 2 * (self._n + 1) > self._row.size:
            live = np.flatnonzero(self._row >= 0)
            k0, k1, rows = self._k0[live], self._k1[live], self._row[live]
            self._alloc(2 * (self._n + 1))
            self._place(k0, k1, rows)
        keyed = _keys([word])
        if keyed is None or not keyed[2][0]:
            self._other[word] = row
        else:
            self._place(keyed[0], keyed[1], np.array([row], np.int32))
        self._n += 1

    def lookup(self, tokens: Sequence[str]) -> Optional[np.ndarray]:
        """int32 rows of a chunk's tokens, -1 where a token is no word of
        the index. None when the chunk cannot be keyed (a NUL byte in it):
        the caller falls back to its dictionary."""
        keyed = _keys(tokens)
        if keyed is None:
            return None
        k0, k1, ok = keyed
        out = np.full(len(tokens), -1, np.int32)
        slot = self._slot(k0, k1)
        pend = np.flatnonzero(ok)
        while pend.size:
            s = slot[pend]
            row = self._row[s]
            taken = row >= 0
            hit = taken & (self._k0[s] == k0[pend]) & (self._k1[s] == k1[pend])
            out[pend[hit]] = row[hit]
            pend = pend[taken & ~hit]
            slot[pend] = (slot[pend] + 1) & self._wrap
        if not ok.all():
            get = self._other.get
            for i in np.flatnonzero(~ok):
                out[i] = get(tokens[i], -1)
        return out
