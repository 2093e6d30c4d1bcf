"""The plain reference of the streamed fit: incremental skip-gram with
negative sampling over a sentence stream (Kaji & Kobayashi, EMNLP 2017,
arXiv:1704.03956) as THIS repo states it, in plain Python and float32
numpy. Imports nothing of ``streaming/trainer.py``, ``corpus/
stream_vocab.py`` or the engine: tests hold the trainer to it.

What it states, in the order a run meets it:

* the BOOTSTRAP count: the first sentences that cover ``bootstrap_words``
  raw words are counted exactly; the words with ``min_count`` occurrences
  become the base vocabulary, most frequent first, ties by first
  occurrence; the rest seed the candidate sketch with their exact counts;
* the Space-Saving SKETCH (Metwally et al.) over every out-of-vocabulary
  word: at capacity a new word evicts the tracked word of smallest
  (count, word) and inherits its count as error; a word's GUARANTEED count
  is its count less its error;
* ROUNDS: sentences are taken in arrival order (the bootstrap window first,
  encoded without counting: its counts are in already), each word counted,
  each sentence thinned by the keep probabilities in force (one
  ``random(len)`` of ``numpy.random.default_rng(seed)`` a sentence, over its
  in-vocabulary words: the draws are the trainer's, stated here as the
  seam), and packed into a buffer of ``buffer_words`` words and
  ``buffer_sentences`` sentences; the sentence that no longer fits is
  carried, already thinned, into the next round;
* PROMOTION after a round's fill: every candidate whose guaranteed count
  has reached ``promote_min_count`` joins the vocabulary, largest estimate
  first (ties by word), on the next spare row, with its estimate as count,
  while spare rows remain;
* the REFRESH after that, when a word was promoted or ``refresh_words``
  in-vocabulary words were counted since the last one: keep probabilities
  ``min(1, (sqrt(f/t) + 1) t/f)`` over every word from the live counts, and
  noise weights ``count^0.75`` over the BASE vocabulary;
* one SGNS UPDATE a step (:func:`sgns_step`) in float32 on batches handed
  to it: no independent code repeats the device's draws.

Departures from the paper: the distributions are refreshed a round, not a
word (the paper updates the unigram table with every word); a step is a
synchronous batch of 8,192 positions, every update computed from the
pre-step rows and duplicates summed (the paper updates a pair at a time);
promoted words are never drawn as negatives (the noise table spans the
base vocabulary); alpha decays linearly over a backfill of known length
(``anneal_words``, counted in kept words) and is constant otherwise.
"""

from __future__ import annotations

import numpy as np


class SpaceSaving:
    """Space-Saving over ``capacity`` tracked words, in dictionaries."""

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        self.count = {}
        self.error = {}

    def add(self, word: str, n: int = 1) -> None:
        if word in self.count:
            self.count[word] += n
        elif len(self.count) < self.capacity:
            self.count[word], self.error[word] = n, 0
        else:
            least, victim = min((c, w) for w, c in self.count.items())
            del self.count[victim], self.error[victim]
            self.count[word], self.error[word] = least + n, least

    def over(self, threshold: int) -> list:
        """(word, estimate) of the words whose guaranteed count reaches
        ``threshold``, largest estimate first, ties by word."""
        hit = [(w, c) for w, c in self.count.items()
               if c - self.error[w] >= threshold]
        return sorted(hit, key=lambda wc: (-wc[1], wc[0]))

    def pop(self, word: str) -> None:
        del self.count[word], self.error[word]


def keep_probabilities(counts: np.ndarray, total: int,
                       ratio: float) -> np.ndarray:
    """word2vec's subsampling rule on live counts (float64)."""
    if ratio <= 0:
        return np.ones(counts.shape[0], np.float64)
    keep = np.zeros(counts.shape[0], np.float64)
    seen = counts > 0
    f = counts[seen].astype(np.float64) / float(max(total, 1))
    keep[seen] = np.minimum((np.sqrt(f / ratio) + 1.0) * (ratio / f), 1.0)
    return keep


def noise_weights(counts: np.ndarray, power: float = 0.75) -> np.ndarray:
    """``count^power`` over the base vocabulary, normalised (float64)."""
    w = np.power(counts.astype(np.float64), power)
    return w / w.sum()


def alias_pmf(prob: np.ndarray, alias: np.ndarray) -> np.ndarray:
    """The distribution an alias table draws from: entry k is hit with
    probability 1/V, kept with ``prob[k]`` and sent to ``alias[k]``
    otherwise."""
    prob = np.asarray(prob, np.float64)
    n = prob.shape[0]
    pmf = prob / n
    np.add.at(pmf, np.asarray(alias, np.int64), (1.0 - prob) / n)
    return pmf


class StreamReference:
    """The host half of the streamed fit: vocabulary, counts, sketch,
    distributions and buffers, a round a call of :meth:`next_round`."""

    def __init__(self, sentences, *, bootstrap_words: int, min_count: int,
                 promote_min_count: int, extra_rows: int,
                 sketch_capacity: int, buffer_words: int,
                 buffer_sentences: int, refresh_words: int,
                 subsample_ratio: float, seed: int,
                 unigram_power: float = 0.75,
                 max_sentence_length: int = 1000):
        self.it = self._pieces(iter(sentences), max_sentence_length)
        self.buffer_words, self.buffer_sentences = (
            int(buffer_words), int(buffer_sentences))
        self.refresh_words = int(refresh_words)
        self.promote_min_count = int(promote_min_count)
        self.ratio, self.power = float(subsample_ratio), float(unigram_power)
        self.rng = np.random.default_rng(seed)
        # -- the bootstrap count ----------------------------------------
        self.window, seen, first = [], 0, {}
        for s in self.it:
            self.window.append(s)
            seen += len(s)
            for w in s:
                first[w] = first.get(w, 0) + 1
            if seen >= bootstrap_words:
                break
        # most frequent first; a dict keeps first-seen order and the sort
        # is stable, so ties fall by first occurrence
        ranked = sorted(((w, c) for w, c in first.items() if c >= min_count),
                        key=lambda wc: -wc[1])
        self.words = [w for w, _ in ranked]
        self.index = {w: i for i, w in enumerate(self.words)}
        self.base_size = len(self.words)
        self.counts = [c for _, c in ranked]
        self.total = sum(self.counts)
        self.sketch = SpaceSaving(sketch_capacity)
        self.oov_seen = 0
        for w, c in first.items():
            if w not in self.index:
                self.sketch.add(w, c)
                self.oov_seen += c
        self.spare = int(extra_rows)
        self.keep = keep_probabilities(
            np.asarray(self.counts, np.int64), self.total, self.ratio)
        self.noise = noise_weights(np.asarray(self.counts, np.int64),
                                   self.power)
        self.total_at_refresh = 0
        self.words_trained = 0
        self.rounds = 0
        self.refreshes = 0
        self.promoted = []  # (word, row), in order
        self._pending = list(self.window)  # replayed before the stream
        self._pending.reverse()
        self._carry = None
        self._done = False

    @staticmethod
    def _pieces(it, longest):
        for s in it:
            s = list(s)
            for i in range(0, len(s), longest):
                if s[i:i + longest]:
                    yield s[i:i + longest]

    def _next_sentence(self):
        """(ids of its in-vocabulary words, counted unless it is of the
        bootstrap window); None at the stream's end."""
        if self._pending:
            s = self._pending.pop()
            self._raw_words += len(s)
            self._from_stream = False
            return [self.index[w] for w in s if w in self.index]
        s = next(self.it, None)
        if s is None:
            return None
        self._raw_words += len(s)
        self._from_stream = True
        ids = []
        for w in s:
            i = self.index.get(w)
            if i is None:
                self.sketch.add(w)
                self.oov_seen += 1
            else:
                self.counts[i] += 1
                self.total += 1
                ids.append(i)
        return ids

    def next_round(self):
        """Fill, promote, refresh. Returns None at the stream's end, else a
        dict: ``ids`` (buffer_words,) int32 and ``offsets``
        (buffer_sentences + 2,) int64 as the device is handed them,
        ``fill``, ``promoted`` [(word, row)] of this round, ``refreshed``,
        the ``keep`` / ``noise`` in force once the round trains,
        ``raw_words`` (the raw tokens of the sentences this round pulled,
        one it had to carry over included) and ``live`` (every sentence
        in the buffer came from past the bootstrap window)."""
        if self._done:
            return None
        ids = np.zeros(self.buffer_words, np.int32)
        offsets, fill = [0], 0
        self._raw_words, live = 0, True
        while (fill < self.buffer_words
               and len(offsets) <= self.buffer_sentences):
            if self._carry is not None:
                (kept, from_stream), self._carry = self._carry, None
            else:
                enc = self._next_sentence()
                if enc is None:
                    self._done = True
                    break
                if not enc:
                    continue
                kept = np.asarray(enc, np.int32)
                if self.ratio > 0:
                    draws = self.rng.random(kept.shape[0])
                    kept = kept[draws < self.keep[kept]]
                if not kept.shape[0]:
                    continue
                from_stream = self._from_stream
            if fill + kept.shape[0] > self.buffer_words:
                self._carry = (kept, from_stream)
                break
            live = live and from_stream
            ids[fill:fill + kept.shape[0]] = kept
            fill += kept.shape[0]
            offsets.append(fill)
        if fill == 0:  # only at the stream's end
            return None
        # -- promotion ---------------------------------------------------
        promoted = []
        while self.spare > 0:
            cands = self.sketch.over(self.promote_min_count)[:self.spare]
            if not cands:
                break
            for word, estimate in cands:
                self.sketch.pop(word)
                row = len(self.words)
                self.words.append(word)
                self.index[word] = row
                self.counts.append(estimate)
                self.total += estimate
                self.spare -= 1
                promoted.append((word, row))
        self.promoted += promoted
        # -- refresh -----------------------------------------------------
        refreshed = bool(
            promoted
            or self.total - self.total_at_refresh >= self.refresh_words)
        if refreshed:
            self.total_at_refresh = self.total
            counts = np.asarray(self.counts, np.int64)
            self.keep = keep_probabilities(counts, self.total, self.ratio)
            self.noise = noise_weights(counts[:self.base_size], self.power)
            self.refreshes += 1
        offs = np.full(self.buffer_sentences + 2, fill, np.int64)
        offs[:len(offsets)] = offsets
        offs[-1] = self.buffer_words
        self.words_trained += fill
        self.rounds += 1
        return {"ids": ids, "offsets": offs, "fill": fill,
                "promoted": promoted, "refreshed": refreshed,
                "keep": self.keep, "noise": self.noise,
                "raw_words": self._raw_words, "live": live}


# -- the device half: one step, on batches handed to it ------------------


def _sigmoid(x):
    return np.float32(1.0) / (np.float32(1.0) + np.exp(-x))


def _log_sigmoid(x):
    return np.minimum(x, 0.0) - np.log1p(np.exp(-np.abs(x)))


def _scatter_add(table, ids, upd) -> None:
    order = np.argsort(ids, kind="stable")
    ids_s = ids[order]
    starts = np.flatnonzero(np.r_[True, ids_s[1:] != ids_s[:-1]])
    table[ids_s[starts]] += np.add.reduceat(upd[order], starts, axis=0)


def sgns_step(syn0, syn1, centers, contexts, mask, negs, alpha) -> float:
    """One synchronous step over P pairs, in place, in float32:
    centers / contexts / mask (P,), negs (P, n). Every update is computed
    from the pre-step rows, duplicates are summed, and a negative equal to
    its pair's context is skipped. Returns the masked-mean loss."""
    alpha = np.float32(alpha)
    h, u_pos, u_neg = syn0[centers], syn1[contexts], syn1[negs]
    f_pos = np.einsum("pd,pd->p", h, u_pos)
    f_neg = np.einsum("pd,pnd->pn", h, u_neg)
    nmask = (negs != contexts[:, None]).astype(np.float32) * mask[:, None]
    c_pos = alpha * (1.0 - _sigmoid(f_pos)) * mask
    c_neg = -alpha * _sigmoid(f_neg) * nmask
    loss = (-_log_sigmoid(f_pos) * mask
            - (_log_sigmoid(-f_neg) * nmask).sum(axis=1) * mask)
    loss = loss.sum(dtype=np.float32) / max(
        mask.sum(dtype=np.float32), np.float32(1.0))
    d_center = c_pos[:, None] * u_pos + np.einsum("pn,pnd->pd", c_neg, u_neg)
    _scatter_add(
        syn1, np.concatenate([contexts, negs.reshape(-1)]),
        np.concatenate([
            c_pos[:, None] * h,
            (c_neg[:, :, None] * h[:, None, :]).reshape(-1, h.shape[1]),
        ]))
    _scatter_add(syn0, centers, d_center.astype(np.float32))
    return float(loss)
