"""Character n-gram subwords (fastText-style) for the subword model family.

The reference framework is word-level only; subword buckets are the stretch
capability named in this repo's target configs (BASELINE.json: "fastText
char-ngram subword buckets — stretch sharded-matrix API beyond word-level").
Conventions follow fastText: words are wrapped in '<'/'>' boundary markers,
n-grams of length [min_n, max_n] are hashed with FNV-1a(32) into ``bucket``
slots, and a word's input representation is the mean of its own vector and
its n-gram bucket vectors. OOV words compose from buckets alone.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

FNV_OFFSET = 2166136261
FNV_PRIME = 16777619
MASK32 = 0xFFFFFFFF


def fnv1a_32(data: bytes) -> int:
    """FNV-1a 32-bit hash (the fastText n-gram hash)."""
    h = FNV_OFFSET
    for b in data:
        h ^= b
        h = (h * FNV_PRIME) & MASK32
    return h


def word_ngrams(word: str, min_n: int = 3, max_n: int = 6) -> List[str]:
    """Character n-grams of '<word>' with lengths in [min_n, max_n].

    The full wrapped token is excluded (it is represented by the word's own
    vector); a wrapped token shorter than min_n yields no n-grams.
    """
    if min_n <= 0 or max_n < min_n:
        raise ValueError("need 0 < min_n <= max_n")
    wrapped = f"<{word}>"
    L = len(wrapped)
    out = []
    # n is capped at L-1: the whole wrapped token (n == L) is excluded —
    # it is represented by the word's own vector.
    for n in range(min_n, min(max_n, L - 1) + 1):
        for i in range(L - n + 1):
            out.append(wrapped[i : i + n])
    return out


def ngram_bucket_ids(
    word: str, vocab_size: int, bucket: int, min_n: int, max_n: int
) -> List[int]:
    """Bucket-row ids (offset by vocab_size) for a word's n-grams."""
    return [
        vocab_size + (fnv1a_32(g.encode("utf-8")) % bucket)
        for g in word_ngrams(word, min_n, max_n)
    ]


def subword_group(
    word: str,
    word_id: int | None,
    vocab_size: int,
    bucket: int,
    min_n: int,
    max_n: int,
    max_subwords: int,
) -> List[int]:
    """The id group whose mean represents ``word``: the word's own row (if
    in-vocab) followed by its n-gram bucket rows, truncated to
    ``max_subwords`` (the word's own row is never truncated away)."""
    ids = [] if word_id is None else [word_id]
    ids += ngram_bucket_ids(word, vocab_size, bucket, min_n, max_n)
    return ids[:max_subwords]


#: Words one vectorised pass of :func:`build_subword_table` handles: bounds
#: its index arrays (about 30 n-grams a word, a few int64 arrays each).
_TABLE_CHUNK = 1 << 17


def _chunk_groups(words, base, vocab_size, bucket, min_n, max_n, ids, mask):
    """Fill rows ``base .. base + len(words)`` of ``ids`` / ``mask`` with
    :func:`subword_group`'s ids, for all the words at once: the wrapped
    words' UTF-8 bytes lie in one buffer, an n-gram is a span between two
    character starts, and FNV-1a runs over every span in step, a byte a
    pass (uint32 arithmetic wraps as the hash wants)."""
    S = ids.shape[1]
    blobs = [b"<" + w.encode("utf-8") + b">" for w in words]
    buf = np.frombuffer(b"".join(blobs), np.uint8)
    word_end = np.cumsum([len(b) for b in blobs])
    # A character starts at every byte that is not a UTF-8 continuation.
    is_start = (buf & 0xC0) != 0x80
    cstart = np.flatnonzero(is_start)  # byte offset of every character
    cstart = np.append(cstart, buf.size)
    chars_before = np.concatenate([[0], np.cumsum(is_start)])
    first_char = chars_before[np.concatenate([[0], word_end[:-1]])]
    n_chars = chars_before[word_end] - first_char  # wrapped length L
    rows = np.arange(len(words))
    ids[base + rows, 0] = base + rows
    mask[base + rows, 0] = 1.0
    filled = np.ones(len(words), np.int64)  # slots taken, the word's own
    for n in range(min_n, max_n + 1):
        # n-grams of length n: L - n + 1 of them where n <= L - 1, in
        # order of their start, cut where the group is full.
        count = np.where(n <= n_chars - 1, n_chars - n + 1, 0)
        count = np.minimum(count, S - filled)
        total = int(count.sum())
        if total == 0:
            continue
        w = np.repeat(rows, count)
        i = np.arange(total) - np.repeat(np.cumsum(count) - count, count)
        c0 = first_char[w] + i
        lo, hi = cstart[c0], cstart[c0 + n]
        h = np.full(total, FNV_OFFSET, np.uint32)
        for k in range(int((hi - lo).max())):
            live = lo + k < hi
            b = buf[np.minimum(lo + k, buf.size - 1)].astype(np.uint32)
            h = np.where(live, (h ^ b) * np.uint32(FNV_PRIME), h)
        slot = filled[w] + i
        ids[base + w, slot] = vocab_size + (h % np.uint32(bucket)).astype(
            np.int64
        )
        mask[base + w, slot] = 1.0
        filled += count


def build_subword_table(
    words: Sequence[str],
    vocab_size: int,
    bucket: int,
    min_n: int = 3,
    max_n: int = 6,
    max_subwords: int = 32,
) -> Tuple[np.ndarray, np.ndarray]:
    """Precompute the (V, S) id/mask arrays mapping each vocab word to its
    subword group (row w is ``subword_group(words[w], w, ...)``, zero
    padded, the mask telling ids from padding). Vectorised over the words'
    bytes: a million words take seconds, where the scalar loop over every
    byte of every n-gram took minutes; a test holds the two together."""
    if min_n <= 0 or max_n < min_n:
        raise ValueError("need 0 < min_n <= max_n")
    V = len(words)
    ids = np.zeros((V, max_subwords), np.int32)
    mask = np.zeros((V, max_subwords), np.float32)
    for base in range(0, V, _TABLE_CHUNK):
        _chunk_groups(
            words[base : base + _TABLE_CHUNK], base, vocab_size, bucket,
            min_n, max_n, ids, mask,
        )
    return ids, mask
