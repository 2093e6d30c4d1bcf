"""The benchmark's copy of the streamed fit's plain reference (incremental
SGNS, arXiv:1704.03956, as this repo states it): plain Python and float32
numpy, and nothing here imports the program. The text of
``glint_word2vec_tpu/streaming/stream_reference.py`` (its docstring has what
is stated and the departures from the paper; a by-hand test holds the two
copies together), and behind it what the cell's comparison adds: the replay
of a dispatch group from the rows the program held before it, in plain
``jax.numpy`` float32 as ``benchmark/reference.py`` follows the batch cells',
and the gaps between the two.
"""

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


# -- the cell's comparison: a dispatch group, from the rows before it -----


#: Rows a replayed group's row list is padded to a multiple of: their
#: number is a static shape of the replay's programs, so seeds whose groups
#: touch about as many rows share one compile.
ROWS_PAD = 1 << 17


def touched_rows(batches) -> np.ndarray:
    """Sorted table rows the batches touch, each once, then the last one
    repeated up to a multiple of ROWS_PAD (the repeats are read with the
    rest and left out of every comparison)."""
    rows = np.unique(np.concatenate([
        np.concatenate([b["centers"], b["contexts"], b["negs"].reshape(-1)])
        for b in batches
    ]))
    return np.pad(rows, (0, -rows.size % ROWS_PAD), mode="edge")


def sgns_replay(syn0_rows, syn1_rows, rows, batches):
    """Follow ``batches`` from ``syn0_rows`` / ``syn1_rows`` (row i of each
    is table row ``rows[i]``; sorted unique). :func:`sgns_step` in plain
    ``jax.numpy`` float32 (contractions at ``highest`` precision, one
    ``.at[].add`` a table), ``benchmark/reference.sgns_replay`` with a
    ``syn1`` that need not start at zero. Returns (syn0, syn1, [loss]), the
    tables' rows still on the device."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def step(tables, b):
        syn0, syn1 = tables
        centers, contexts, mask, negs, alpha = b
        h, u_pos, u_neg = syn0[centers], syn1[contexts], syn1[negs]
        f_pos = jnp.einsum("pd,pd->p", h, u_pos, precision=hi)
        f_neg = jnp.einsum("pd,pnd->pn", h, u_neg, precision=hi)
        nmask = (negs != contexts[:, None]).astype(jnp.float32) * mask[:, None]
        c_pos = alpha * (1.0 - jax.nn.sigmoid(f_pos)) * mask
        c_neg = -alpha * jax.nn.sigmoid(f_neg) * nmask
        pair_loss = -jax.nn.log_sigmoid(f_pos) * mask - (
            jax.nn.log_sigmoid(-f_neg) * nmask).sum(axis=1) * mask
        loss = pair_loss.sum() / jnp.maximum(mask.sum(), 1.0)
        d_center = c_pos[:, None] * u_pos + jnp.einsum(
            "pn,pnd->pd", c_neg, u_neg, precision=hi)
        syn1 = syn1.at[contexts].add(c_pos[:, None] * h)
        syn1 = syn1.at[negs.reshape(-1)].add(
            (c_neg[:, :, None] * h[:, None, :]).reshape(-1, h.shape[1]))
        syn0 = syn0.at[centers].add(d_center)
        return (syn0, syn1), loss

    stacked = tuple(
        jnp.asarray(np.stack([
            np.searchsorted(rows, b[k]).astype(np.int32)
            if k in ("centers", "contexts", "negs")
            else np.asarray(b[k], np.float32) for b in batches]))
        for k in ("centers", "contexts", "mask", "negs", "alpha"))
    # the rows are a gigabyte a table at the cell's size: the scan works in
    # the buffers it is handed
    (syn0, syn1), losses = jax.jit(
        lambda s0, s1, bs: jax.lax.scan(step, (s0, s1), bs),
        donate_argnums=(0, 1))(
            jnp.asarray(syn0_rows, jnp.float32),
            jnp.asarray(syn1_rows, jnp.float32), stacked)
    return syn0, syn1, np.asarray(losses)


def replay_gaps(rows, batches, before0, before1, prog0, prog1,
                prog_losses) -> dict:
    """``benchmark/reference.replay_gaps``' numbers for one dispatch group:
    follow ``batches`` from the rows held before it (``before*``) and read
    how far the program's rows after it (``prog*``) and its losses lie
    from that. A row counts where it first stands in ``rows`` (the repeats
    that pad them to a static shape were never updated). Compared on the
    device, ROWS_PAD rows at a time: at the cell's size a group touches
    nine rows in ten of a table, and each array here is gigabytes."""
    import jax
    import jax.numpy as jnp

    ref0, ref1, ref_losses = sgns_replay(before0, before1, rows, batches)
    valid = np.r_[True, rows[1:] != rows[:-1]]

    @jax.jit
    def piece(prog, ref, init, valid):
        prog, ref, init = (jnp.where(valid[:, None], x, 0.0)
                           for x in (prog, ref, init))
        return jnp.stack([
            jnp.abs(prog - ref).max(), jnp.abs(ref - init).max(),
            jnp.square(prog - init).sum(), jnp.square(ref - init).sum()])

    out = {}
    for name, prog, ref, init in (("syn0", prog0, ref0, before0),
                                  ("syn1", prog1, ref1, before1)):
        stats = np.stack([
            np.asarray(piece(
                jnp.asarray(prog[s:s + ROWS_PAD]), ref[s:s + ROWS_PAD],
                jnp.asarray(init[s:s + ROWS_PAD]),
                jnp.asarray(valid[s:s + ROWS_PAD])), np.float64)
            for s in range(0, rows.size, ROWS_PAD)])
        d_prog, d_ref = np.sqrt(stats[:, 2].sum()), np.sqrt(stats[:, 3].sum())
        out[f"{name}_gap"] = float(stats[:, 0].max() / stats[:, 1].max())
        out[f"{name}_dnorm_gap"] = float(abs(d_prog - d_ref) / d_ref)
    ref_losses = np.asarray(ref_losses, np.float32)
    out["loss_gap"] = float(np.max(
        np.abs(np.asarray(prog_losses, np.float32) - ref_losses)
        / ref_losses))
    return out
