"""Streaming vocabulary: approximate counts over an unbounded sentence
stream plus online vocab growth — the ISGNS construction
(arXiv:1704.03956) the streaming trainer builds on.

Batch training scans the corpus twice: once for exact counts
(:func:`corpus.vocab.build_vocab`), once to encode. A stream gets one
look at each sentence and has no end, so three things change:

- **Admitted words keep exact counts.** Incrementing an int per
  occurrence is free; the adaptive subsample and negative-sampling
  distributions are recomputed from these live counts on a cadence
  (``EmbeddingEngine.set_noise_counts`` keeps the alias-table shapes
  fixed, so the refresh never recompiles a train program).
- **Candidate (out-of-vocabulary) words go through a space-saving
  sketch** (:class:`SpaceSavingSketch`, Misra-Gries family): bounded
  memory regardless of how many distinct junk tokens the stream carries,
  with the classic guarantee that any word occurring more than
  ``stream_words / capacity`` times since the sketch started is
  guaranteed present, and every estimate carries its own error bound.
- **Promotion assigns new words to the engine's spare extra rows**
  (``EmbeddingEngine.assign_extra_row``): a candidate whose GUARANTEED
  count (estimate minus error) clears ``min_count`` joins the
  vocabulary at the next free row index, so the grown word list stays
  aligned with the table by construction and the serving top-k mask
  (a traced scalar bound) widens without a recompile.

The vocabulary INDEX ordering therefore differs from a batch build
(batch ranks by frequency; streaming appends in promotion order).
Everything downstream keys on words, not ranks — the distributions are
functions word -> value — which is what the replay-parity test in
tests/test_stream_vocab.py pins down.
"""

from __future__ import annotations

import heapq
from itertools import chain, repeat
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from glint_word2vec_tpu.corpus.vocab import Vocabulary
from glint_word2vec_tpu.corpus.word_index import WordIndex


class SpaceSavingSketch:
    """Space-saving heavy-hitter counter over a bounded ``capacity`` of
    tracked items (Metwally et al.; the Misra-Gries family ISGNS uses
    for its candidate vocabulary).

    Semantics: while under capacity, counts are exact (``error == 0``).
    At capacity, a new item evicts the currently-smallest tracked item
    and inherits its count as overestimation ``error``. Guarantees:

    - ``estimate(w) >= true_count(w)`` for every tracked ``w``, and
      ``estimate(w) - error(w) <= true_count(w)`` (the guaranteed lower
      bound promotion thresholds use);
    - any item with ``true_count > items_seen / capacity`` is tracked;
    - ``error(w) <= items_seen / capacity`` for every tracked item.

    Eviction uses a lazy min-heap over (count, item) snapshots: stale
    heap entries (the item's count moved on, or it was evicted) are
    skipped on pop, and the heap is rebuilt when it outgrows
    ``4 * capacity`` entries — amortized O(log capacity) per add,
    bounded memory.
    """

    __slots__ = ("capacity", "items_seen", "_counts", "_errors", "_heap")

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = int(capacity)
        #: Total items ever added (the N in the error bound N/capacity).
        self.items_seen = 0
        self._counts: Dict[str, int] = {}
        self._errors: Dict[str, int] = {}
        self._heap: List[Tuple[int, str]] = []

    def __len__(self) -> int:
        return len(self._counts)

    def __contains__(self, item: str) -> bool:
        return item in self._counts

    def add(self, item: str, n: int = 1) -> None:
        self.items_seen += n
        c = self._counts.get(item)
        if c is not None:
            self._counts[item] = c + n
            heapq.heappush(self._heap, (c + n, item))
        elif len(self._counts) < self.capacity:
            self._counts[item] = n
            self._errors[item] = 0
            heapq.heappush(self._heap, (n, item))
        else:
            m, victim = self._pop_min()
            del self._counts[victim]
            del self._errors[victim]
            self._counts[item] = m + n
            self._errors[item] = m
            heapq.heappush(self._heap, (m + n, item))
        if len(self._heap) > 4 * self.capacity:
            self._heap = [(c, w) for w, c in self._counts.items()]
            heapq.heapify(self._heap)

    def _pop_min(self) -> Tuple[int, str]:
        """Current (count, item) minimum among tracked items, popping
        stale heap snapshots on the way."""
        while self._heap:
            c, w = heapq.heappop(self._heap)
            if self._counts.get(w) == c:
                return c, w
        # Heap drained of live entries (all stale): rebuild and retry.
        self._heap = [(c, w) for w, c in self._counts.items()]
        heapq.heapify(self._heap)
        return heapq.heappop(self._heap)

    def estimate(self, item: str) -> Tuple[int, int]:
        """(count_estimate, error) for a tracked item — the estimate
        overcounts by at most ``error``. Raises ``KeyError`` when the
        item is not tracked (its true count is then bounded by
        ``items_seen / capacity``)."""
        return self._counts[item], self._errors[item]

    def guaranteed(self, item: str) -> int:
        """Lower bound on the item's true count (0 when untracked)."""
        c = self._counts.get(item)
        if c is None:
            return 0
        return c - self._errors[item]

    def pop(self, item: str) -> Tuple[int, int]:
        """Remove a tracked item (promotion took it), returning its
        final (estimate, error)."""
        c = self._counts.pop(item)
        e = self._errors.pop(item)
        return c, e

    def over_threshold(self, threshold: int) -> List[Tuple[str, int, int]]:
        """Tracked items whose GUARANTEED count clears ``threshold``,
        as (item, estimate, error), largest estimates first — the
        promotion candidate scan."""
        out = [
            (w, c, self._errors[w])
            for w, c in self._counts.items()
            if c - self._errors[w] >= threshold
        ]
        out.sort(key=lambda t: (-t[1], t[0]))
        return out

    @property
    def max_untracked_count(self) -> float:
        """Upper bound on the true count of any UNtracked item — the
        sketch's blind spot, surfaced as a gauge."""
        if len(self._counts) < self.capacity:
            return 0.0
        return self.items_seen / self.capacity


class StreamVocab:
    """A vocabulary that grows while a stream is consumed.

    Wraps a bootstrap :class:`~glint_word2vec_tpu.corpus.vocab
    .Vocabulary` (exact counts from the bootstrap window) and maintains:
    exact live counts for every admitted word, the candidate sketch for
    everything else, and the word -> row mapping that mirrors the
    engine's row assignment (base vocab rows first, promoted words
    appended in promotion order at ``vocab_size + j``).
    """

    def __init__(self, base: Vocabulary, *, sketch_capacity: int = 65536,
                 max_size: Optional[int] = None):
        self.words: List[str] = list(base.words)
        self.word_index: Dict[str, int] = dict(base.word_index)
        # Live counts: an int64 array with spare room behind ``size``
        # (promotion appends), and the sentences observed since the last
        # read, whose ids are folded in by ONE bincount when the counts
        # are next read (:meth:`_live_counts`). A Python list of 2M ints
        # was copied whole three times a refresh; and the pending ids are
        # held as arrays, which the cyclic collector does not track: as
        # 30,000 lists a round they brought on a full collection over the
        # 2M-entry dictionary every second round (PERF.md, PR 50).
        self._counts = np.array(base.counts, dtype=np.int64)
        # The chunked look-ups' index (corpus/word_index.py), built when
        # the first chunk asks for it; ``word_index`` stays the statement.
        self._chunk_index: Optional[WordIndex] = None
        self._pending: List[np.ndarray] = []
        self._pending_words = 0
        #: Engine ``vocab_size``: rows below this came from the
        #: bootstrap scan; rows at or above it are promoted words on
        #: extra rows.
        self.base_size = base.size
        #: Total KEPT (in-vocabulary) word occurrences observed,
        #: bootstrap included — the ``train_words_count`` analogue the
        #: adaptive subsample distribution normalizes by.
        self.train_words_count = int(base.train_words_count)
        #: Out-of-vocabulary occurrences routed to the sketch.
        self.oov_words_seen = 0
        self.promoted = 0
        self.sketch = SpaceSavingSketch(sketch_capacity)
        #: Hard cap on len(words) (base + promotable); None = unbounded
        #: here (the engine's spare-row pool still bounds promotion).
        self.max_size = max_size

    @property
    def size(self) -> int:
        return len(self.words)

    def __contains__(self, word: str) -> bool:
        return word in self.word_index

    def _live_counts(self) -> np.ndarray:
        """The counts of ``words`` as a VIEW of the live array, with
        every observed sentence folded in."""
        n = len(self.words)
        if self._pending:
            self._counts[:n] += np.bincount(
                np.concatenate(self._pending), minlength=n
            )
            self._pending, self._pending_words = [], 0
        return self._counts[:n]

    def counts_array(self) -> np.ndarray:
        """Live counts snapshot aligned with ``words`` (int64): one copy
        of ``size`` counts, 16 MB at 2M words (milliseconds), after one
        bincount of what was observed since the last read (about a
        tenth of a second a million words)."""
        return self._live_counts().copy()

    #: Observed words held back before they are folded into the counts
    #: unasked: bounds the memory of a run that never reads them.
    _PENDING_MAX_WORDS = 1 << 22

    def observe(self, sentence: Sequence[str]) -> np.ndarray:
        """Count one sentence and encode its in-vocabulary words (an
        int32 array of row indices).

        Admitted words get an exact count increment and their row index
        in the output; OOV words feed the candidate sketch, in arrival
        order (and are dropped from the encoding, exactly as batch
        training drops OOV — until promotion admits them, from which
        point on they train). The returned array is also what the counts
        are folded from: the caller must not change it in place.
        """
        ids = list(map(self.word_index.get, sentence))
        if None in ids:
            add = self.sketch.add
            for w, i in zip(sentence, ids):
                if i is None:
                    add(w)
            n = len(ids)
            ids = [i for i in ids if i is not None]
            self.oov_words_seen += n - len(ids)
        ids = np.array(ids, np.int32)
        self._pending.append(ids)
        self._pending_words += len(ids)
        self.train_words_count += len(ids)
        if self._pending_words >= self._PENDING_MAX_WORDS:
            self._live_counts()
        return ids

    def encode(self, sentence: Sequence[str]) -> List[int]:
        """Encode WITHOUT counting — for replaying sentences whose
        occurrences are already in the counts (the bootstrap window,
        whose exact counts seeded the base vocabulary and the sketch).
        OOV words are dropped, not sketched."""
        wi = self.word_index
        return [i for w in sentence if (i := wi.get(w)) is not None]

    #: Chunks under this many tokens go through the dictionary: the
    #: vector index's fixed cost a call is a few dozen numpy calls.
    _INDEX_MIN_TOKENS = 64

    def _rows_of(self, tokens: List[str]) -> np.ndarray:
        """int32 rows of a chunk's tokens, -1 for a word outside the
        vocabulary."""
        n = len(tokens)
        if n >= self._INDEX_MIN_TOKENS:
            if self._chunk_index is None:
                self._chunk_index = WordIndex(self.words)
            rows = self._chunk_index.lookup(tokens)
            if rows is not None:
                return rows
        return np.fromiter(
            map(self.word_index.get, tokens, repeat(-1)), np.int32, n
        )

    def _encode_chunk(self, sentences: Sequence[Sequence[str]], count: bool):
        tokens = list(chain.from_iterable(sentences))
        lens = np.fromiter(map(len, sentences), np.int64, len(sentences))
        rows = self._rows_of(tokens)
        known = rows >= 0
        if not known.all():
            unknown = np.flatnonzero(~known)
            if count:
                add = self.sketch.add
                for i in unknown.tolist():
                    add(tokens[i])
                self.oov_words_seen += unknown.size
            sentence_of = np.repeat(np.arange(lens.size), lens)
            lens = np.bincount(sentence_of[known], minlength=lens.size)
            rows = rows[known]
        if count:
            self._pending.append(rows)
            self._pending_words += rows.size
            self.train_words_count += rows.size
            if self._pending_words >= self._PENDING_MAX_WORDS:
                self._live_counts()
        return rows, lens

    def observe_many(self, sentences: Sequence[Sequence[str]]):
        """:meth:`observe` over a chunk of sentences in one pass: the
        in-vocabulary rows of all of them, concatenated (int32), and how
        many each sentence contributed (int64). The same counts, the same
        sketch (OOV words are added in arrival order) as a call a
        sentence; the rows are what the counts are folded from, so the
        caller must not change them in place."""
        return self._encode_chunk(sentences, count=True)

    def encode_many(self, sentences: Sequence[Sequence[str]]):
        """:meth:`encode` over a chunk of sentences: rows and lengths as
        :meth:`observe_many` gives them, nothing counted or sketched."""
        return self._encode_chunk(sentences, count=False)

    def promotable(self, min_count: int,
                   limit: Optional[int] = None) -> List[Tuple[str, int]]:
        """Candidates whose guaranteed sketch count clears
        ``min_count``, as (word, estimated_count), most frequent first,
        at most ``limit`` of them. Respects ``max_size``."""
        room = None
        if self.max_size is not None:
            room = max(0, self.max_size - self.size)
        out = [
            (w, est)
            for w, est, _err in self.sketch.over_threshold(min_count)
        ]
        if room is not None:
            out = out[:room]
        if limit is not None:
            out = out[:limit]
        return out

    def promote(self, word: str, count: Optional[int] = None) -> int:
        """Admit a candidate: append it to the vocabulary at the next
        row index (which the caller pairs with
        ``engine.assign_extra_row`` — both count assignments in the
        same order, so the indices agree by construction). ``count``
        defaults to the sketch estimate; the word leaves the sketch.
        Returns the new index."""
        if word in self.word_index:
            raise ValueError(f"word {word!r} already in vocabulary")
        if self.max_size is not None and self.size >= self.max_size:
            raise ValueError(
                f"vocabulary at max_size ({self.max_size}); cannot "
                f"promote {word!r}"
            )
        if count is None:
            count = self.sketch.estimate(word)[0]
        if word in self.sketch:
            self.sketch.pop(word)
        idx = len(self.words)
        if idx == self._counts.shape[0]:
            self._counts = np.concatenate(
                [self._counts, np.zeros(max(idx, 1024), np.int64)]
            )
        self.words.append(word)
        self.word_index[word] = idx
        if self._chunk_index is not None:
            self._chunk_index.add(word, idx)
        self._counts[idx] = int(count)
        # A promoted word's pre-promotion occurrences were counted by
        # the sketch, not train_words_count; fold the estimate in so
        # the subsample normalizer reflects what the counts claim.
        self.train_words_count += int(count)
        self.promoted += 1
        return idx

    # -- adaptive distributions ----------------------------------------

    def keep_probabilities(self, subsample_ratio: float) -> np.ndarray:
        """Per-word keep probability over the GROWN vocabulary — the
        exact :meth:`Vocabulary.keep_probabilities` formula evaluated
        on the live counts (the ISGNS adaptive subsample
        distribution). The streaming trainer applies these host-side
        while filling each round's buffer."""
        if subsample_ratio <= 0:
            return np.ones(self.size, dtype=np.float64)
        counts = self._live_counts()
        total = float(max(self.train_words_count, 1))
        # The rule reads 1 or more (clipped to 1) wherever a word's share
        # of the stream is at most 2.618 times the ratio, so only the few
        # words over that get the arithmetic: tens of words of 2M, where
        # the whole array was 45 ms a refresh (PERF.md, PR 50). The bound
        # used, 2.5, leaves the rule 3% over 1 at the edge: no rounding
        # brings a skipped word under 1.
        keep = (counts > 0).astype(np.float64)
        often = np.flatnonzero(counts > 2.5 * subsample_ratio * total)
        pcn = counts[often].astype(np.float64) / total
        ran = (np.sqrt(pcn / subsample_ratio) + 1.0) * (subsample_ratio / pcn)
        keep[often] = np.clip(ran, 0.0, 1.0)
        return keep

    def noise_counts(self) -> np.ndarray:
        """Live counts over the BASE vocabulary only — the adaptive
        negative-sampling distribution (``engine.set_noise_counts``
        keeps the alias shapes fixed at vocab_size; promoted words are
        never negative-sampled, like fastText bucket rows). One copy of
        ``base_size`` counts: 16 MB at 2M words, milliseconds."""
        return self._live_counts()[: self.base_size].copy()

    def noise_weights(self, power: float = 0.75) -> np.ndarray:
        """Normalized ``count^power`` noise distribution over the base
        vocab — what :meth:`noise_counts` induces; used for the
        distribution-drift gauge."""
        w = np.power(
            self._live_counts()[: self.base_size].astype(np.float64), power
        )
        s = w.sum()
        return w / s if s > 0 else w

    def snapshot_vocabulary(self) -> Vocabulary:
        """Immutable :class:`Vocabulary` of the current grown state —
        what a published model generation carries (words.txt order ==
        row order)."""
        return Vocabulary(
            words=list(self.words),
            counts=self.counts_array(),
            word_index=dict(self.word_index),
            train_words_count=int(self.train_words_count),
        )


def bootstrap_stream_vocab(
    sentences: Iterable[Sequence[str]],
    *,
    min_count: int = 5,
    sketch_capacity: int = 65536,
    max_size: Optional[int] = None,
) -> StreamVocab:
    """Build a :class:`StreamVocab` from a bootstrap window of the
    stream: exact batch-style counts (``build_vocab`` semantics —
    frequency-ranked indices, first-seen ties) seed the base
    vocabulary, and every bootstrap word that fell below ``min_count``
    seeds the candidate sketch with its exact count, so a word that
    was warming up during bootstrap is not forgotten."""
    import collections

    from glint_word2vec_tpu.corpus.vocab import build_vocab

    counter: collections.Counter = collections.Counter()
    materialized = []
    for s in sentences:
        counter.update(s)
        materialized.append(s)
    base = build_vocab(materialized, min_count=min_count)
    sv = StreamVocab(
        base, sketch_capacity=sketch_capacity, max_size=max_size
    )
    for w, c in counter.items():
        if w not in base.word_index:
            sv.sketch.add(w, c)
            sv.oov_words_seen += c
    return sv
