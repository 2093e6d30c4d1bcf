"""fastText-style subword model family on the same sharded-matrix engine.

Extends the word-level SGNS framework with character-n-gram bucket rows
(BASELINE.json stretch config): the engine's table grows by ``bucket``
extra rows (corpus/subword.py), a center word trains as the mean of its
subword group's rows (formed inside the corpus-resident scans from a
device-resident group table, ``EmbeddingEngine.upload_center_groups``; by
``train_steps_grouped`` under the host batcher), and word
vectors — including OOV words, which the word-level reference cannot
represent at all — compose on device via ``pull_average``.

Both of fastText's unsupervised commands train here: ``architecture=
"skipgram"`` (``fasttext skipgram``) and ``"cbow"`` (``fasttext cbow``: one
mean over the rows of every word of a position's bag predicts the
position's word, ``ops/cbow_subword_reference.py``), the latter on the
corpus-resident packed path only, as at word level. A word's vector is its
group's mean whichever trained it.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import numpy as np

from glint_word2vec_tpu.corpus.subword import build_subword_table, subword_group
from glint_word2vec_tpu.corpus.vocab import Vocabulary
from glint_word2vec_tpu.obs import events as obs_events
from glint_word2vec_tpu.models.word2vec import (
    MAX_QUERY_ROWS,
    LocalWord2VecModel,
    Word2Vec,
    Word2VecModel,
)
from glint_word2vec_tpu.utils import next_pow2
from glint_word2vec_tpu.utils.params import Word2VecParams, _require


@dataclass
class FastTextParams(Word2VecParams):
    """Word2Vec params + subword geometry (fastText conventions). Both
    architectures train (see the module docstring); what ``architecture=
    "cbow"`` refuses at word level (a shared pool, grid packing, replica
    exchange, the streaming trainer, any fit the corpus-resident path
    does not take) it refuses here."""

    min_n: int = 3
    max_n: int = 6
    bucket: int = 2_000_000
    max_subwords: int = 32

    def validate(self) -> None:
        super().validate()
        _require(0 < self.min_n <= self.max_n, "need 0 < min_n <= max_n")
        _require(self.bucket > 0, "bucket must be > 0")
        _require(self.max_subwords >= 2, "max_subwords must be >= 2")


class FastTextWord2Vec(Word2Vec):
    """Subword estimator, skip-gram or CBOW. Same fluent surface as
    Word2Vec, plus subword knobs; fit() shares the full word-level
    training loop (LR anneal, metrics, checkpoint/resume) via the family
    hooks."""

    def __init__(self, params: Optional[FastTextParams] = None, mesh=None, **kw):
        super().__init__(params or FastTextParams(), mesh=mesh, **kw)
        if not isinstance(self.params, FastTextParams):
            raise TypeError("FastTextWord2Vec requires FastTextParams")
        self._sub_ids: Optional[np.ndarray] = None
        self._sub_mask: Optional[np.ndarray] = None

    def set_min_n(self, v: int) -> "FastTextWord2Vec":
        return self._set(min_n=v)

    def set_max_n(self, v: int) -> "FastTextWord2Vec":
        return self._set(max_n=v)

    def set_bucket(self, v: int) -> "FastTextWord2Vec":
        return self._set(bucket=v)

    def set_max_subwords(self, v: int) -> "FastTextWord2Vec":
        return self._set(max_subwords=v)

    # Family hooks -----------------------------------------------------

    def _center_groups(self) -> np.ndarray:
        # The corpus-resident fit forms every centre's group on the
        # device from this table; the host-side expansion below is the
        # host batcher's alone.
        return np.where(self._sub_mask > 0, self._sub_ids, -1).astype(np.int32)

    def _make_engine(self, mesh, vocab: Vocabulary):
        from glint_word2vec_tpu.parallel.engine import EmbeddingEngine

        p = self.params
        self._sub_ids, self._sub_mask = build_subword_table(
            vocab.words, vocab.size, p.bucket, p.min_n, p.max_n, p.max_subwords
        )
        return EmbeddingEngine(
            mesh,
            vocab.size,
            p.vector_size,
            vocab.counts,
            num_negatives=p.num_negatives,
            unigram_power=p.unigram_power,
            unigram_table_size=p.unigram_table_size,
            seed=p.seed,
            dtype=p.dtype,
            extra_rows=p.bucket,
            shared_negatives=p.shared_negatives,
            compute_dtype=p.compute_dtype,
            architecture=p.architecture,
            position_lanes=2 * p.window if p.position_weights else 0,
        )

    def _train_batches(self, engine, group, base_key, step0, alphas):
        # Host-side expansion of center words to their subword groups;
        # padded batch rows (center 0) carry zero context masks, so their
        # group updates are zeroed by the gradient coefficients. The
        # expansion is this family's extra host-side phase, so it gets
        # its own span inside the fit loop's device_steps window. The
        # batch stacking itself already happened on the producer thread
        # (the group arrives as a pre-stacked BatchGroup).
        with obs_events.span("subword_expand", step0=step0):
            groups = self._sub_ids[group.centers]
            gmask = self._sub_mask[group.centers]
        return engine.train_steps_grouped(
            groups,
            gmask,
            group.contexts,
            group.mask,
            base_key,
            alphas,
            step0,
        )

    def _make_model(self, vocab: Vocabulary, engine) -> "FastTextModel":
        return FastTextModel(
            vocab, engine, self.params, self._sub_ids, self._sub_mask
        )


class FastTextModel(Word2VecModel):
    """Fitted subword model: all word vectors (in-vocab AND out-of-vocab)
    compose on device as the mean of subword rows."""

    def __init__(self, vocab, engine, params: FastTextParams, sub_ids, sub_mask):
        super().__init__(vocab, engine, params)
        self._sub_ids = sub_ids
        self._sub_mask = sub_mask
        #: The composed query engine, the training engine's
        #: ``table_version`` its rows were composed from, and how often
        #: and how long it was built (``/metrics`` reads the last two).
        self._qeng = None
        self._qeng_version = None
        self.query_engine_builds = 0
        self.query_engine_build_seconds = 0.0

    # -- composition ---------------------------------------------------

    #: Most rows one composition call hands the device: a longer input
    #: goes in blocks of this many, and a shorter one (or a remainder) in
    #: its power-of-two bucket, so XLA sees log2(COMPOSE_BLOCK) + 1 shapes
    #: whatever the input sizes.
    COMPOSE_BLOCK = 4096

    def _compose_device(self, groups: np.ndarray, gmask: np.ndarray):
        """Compose one block on device; returns a device array."""
        return self.engine.pull_average(groups, gmask)

    def _compose(self, groups: np.ndarray, gmask: np.ndarray) -> np.ndarray:
        """Compose arbitrarily many rows: whole COMPOSE_BLOCKs, then the
        remainder padded (row 0 / zero mask, sliced off after) to its
        power of two. One word costs a ``(1, max_subwords)`` block, a
        coalesced round its Q bucket's, and no call compiles a shape
        :meth:`warm_compose` has not."""
        out = np.empty((groups.shape[0], self.vector_size), np.float32)
        for s, k, g, m in self._compose_blocks(groups, gmask):
            out[s : s + k] = np.asarray(self._compose_device(g, m))[:k]
        return out

    def _compose_blocks(self, groups: np.ndarray, gmask: np.ndarray):
        """``(first row, rows, ids, mask)`` of each block :meth:`_compose`
        dispatches, the last padded to its power of two."""
        B = self.COMPOSE_BLOCK
        for s in range(0, groups.shape[0], B):
            g, m = groups[s : s + B], gmask[s : s + B]
            k = g.shape[0]
            pad = next_pow2(k) - k
            if pad:
                g = np.pad(g, ((0, pad), (0, 0)))
                m = np.pad(m, ((0, pad), (0, 0)))
            yield s, k, g, m

    def warm_compose(self, max_rows: Optional[int] = None) -> int:
        """Compile every block shape :meth:`_compose` can dispatch for
        inputs of up to ``max_rows`` rows (default: any size). Returns
        the number of shapes compiled (0 = already warm)."""
        before = self.engine.query_compiles
        top = self.COMPOSE_BLOCK
        if max_rows is not None:
            top = min(top, next_pow2(max_rows))
        S = self.params.max_subwords
        n = 1
        while n <= top:
            g = np.zeros((n, S), np.int32)
            np.asarray(self._compose_device(g, np.zeros(g.shape, np.float32)))
            n *= 2
        return self.engine.query_compiles - before

    def _oov_groups(
        self, words: Sequence[str]
    ) -> Tuple[np.ndarray, np.ndarray]:
        """``(n, max_subwords)`` ids and mask of words taken as having no
        dictionary row: their n-grams' bucket rows alone. A word too
        short for any n-gram gets an all-zero mask row, which the caller
        refuses (it has no vector)."""
        p: FastTextParams = self.params
        g = np.zeros((len(words), p.max_subwords), np.int32)
        m = np.zeros((len(words), p.max_subwords), np.float32)
        for i, word in enumerate(words):
            ids = subword_group(
                word, None, self.vocab.size, p.bucket, p.min_n, p.max_n,
                p.max_subwords,
            )
            g[i, : len(ids)] = ids
            m[i, : len(ids)] = 1.0
        return g, m

    def _too_short(self, word: str) -> KeyError:
        p: FastTextParams = self.params
        return KeyError(
            f"word {word!r} is OOV and too short for any "
            f"[{p.min_n},{p.max_n}]-gram"
        )

    def _oov_group(self, word: str) -> Tuple[np.ndarray, np.ndarray]:
        g, m = self._oov_groups([word])
        if not m.any():
            raise self._too_short(word)
        return g, m

    def compose_oov(self, words: Sequence[str]):
        """Vectors of words that have no dictionary row, for a coalesced
        serving round: host n-gram hashing, then ONE bucketed compose
        over the words that have a group. Returns ``(vectors, errors,
        slots, rows)``: ``vectors[i]`` is word i's vector or None,
        ``errors[i]`` the KeyError of a word too short for any n-gram
        (it fails alone), and the group slots gathered (padding
        included) with the live rows among them."""
        g, m = self._oov_groups(words)
        ok = m.any(axis=1)
        vectors = [None] * len(words)
        errors = [
            None if ok[i] else self._too_short(w)
            for i, w in enumerate(words)
        ]
        n = int(ok.sum())
        if n:
            # :meth:`_compose`, with the launch and the read-back of its
            # block as the round's spans (no-ops without a recorder).
            vecs = np.empty((n, self.vector_size), np.float32)
            for s, k, gb, mb in self._compose_blocks(g[ok], m[ok]):
                with obs_events.phase_span(
                    "req.enqueue", program="pull_average", q=gb.shape[0],
                    shards=self.engine.num_model,
                ):
                    block = self._compose_device(gb, mb)
                with obs_events.phase_span(
                    "req.result", program="pull_average"
                ):
                    vecs[s : s + k] = np.asarray(block)[:k]
            for i, v in zip(np.flatnonzero(ok), vecs):
                vectors[i] = v
        return (
            vectors, errors,
            next_pow2(n) * g.shape[1] if n else 0, int(m.sum()),
        )

    def transform(self, word: str) -> np.ndarray:
        """Word -> composed vector. Unlike the word-level model, OOV words
        are representable (fastText's defining capability)."""
        idx = self.vocab.word_index.get(word)
        if idx is not None:
            g, m = self._sub_ids[idx : idx + 1], self._sub_mask[idx : idx + 1]
        else:
            g, m = self._oov_group(word)
        return self._compose(g, m)[0]

    def transform_words(self, words: Sequence[str]) -> np.ndarray:
        out = np.empty((len(words), self.vector_size), np.float32)
        for s in range(0, len(words), MAX_QUERY_ROWS):
            chunk = words[s : s + MAX_QUERY_ROWS]
            idx = self.vocab.encode_strict(chunk)  # strict, like word-level
            out[s : s + len(chunk)] = self._compose(
                self._sub_ids[idx], self._sub_mask[idx]
            )
        return out

    def transform_sentences(self, sentences) -> np.ndarray:
        """Mean of composed word vectors per sentence (OOV words dropped,
        matching the word-level DataFrame-transform semantics).

        All chunk words are composed in bucketed device blocks
        (``_compose``), then segment-averaged on host — no per-sentence
        device calls."""
        sentences = list(sentences)
        out = np.zeros((len(sentences), self.vector_size), np.float32)
        encoded = [self.vocab.encode(s) for s in sentences]
        flat = (
            np.concatenate([e for e in encoded if e.size])
            if any(e.size for e in encoded)
            else np.zeros(0, np.int32)
        )
        if flat.size == 0:
            return out
        vecs = self._compose(self._sub_ids[flat], self._sub_mask[flat])
        pos = 0
        for i, e in enumerate(encoded):
            if e.size:
                out[i] = vecs[pos : pos + e.size].mean(axis=0)
                pos += e.size
        return out

    def transform_packed(self, idx: np.ndarray, mask: np.ndarray) -> np.ndarray:
        """Bulk-transform hook on the subword-compose path: the packed
        word-id block is flattened back to its real tokens (row-major, so
        the flat order matches :meth:`transform_sentences`' concatenation)
        and composed in the usual bucketed device blocks, then
        segment-averaged on host. Row results are independent of how the
        producer batched the stream — each composed word vector is a
        within-row reduction — so resume/bitwise guarantees carry over."""
        rows = idx.shape[0]
        out = np.zeros((rows, self.vector_size), np.float32)
        lens = mask.astype(bool).sum(axis=1)
        flat = idx[mask > 0.0].astype(np.int32)
        if flat.size == 0:
            return out
        vecs = self._compose(self._sub_ids[flat], self._sub_mask[flat])
        pos = 0
        for i in range(rows):
            n = int(lens[i])
            if n:
                out[i] = vecs[pos : pos + n].mean(axis=0)
                pos += n
        return out

    def bulk_warmup(self, rows: int, max_len: int) -> int:
        """The compose path dispatches ``(n, max_subwords)`` pull-average
        blocks, n a power of two up to COMPOSE_BLOCK, whatever the
        producer's packing (``_compose`` pads a remainder to its bucket):
        a block of ``rows`` x ``max_len`` tokens reaches the buckets up
        to its own size, and those are warmed here. The producer's
        (rows, len) geometry never reaches the device."""
        return self.warm_compose(rows * max_len)

    # -- similarity over composed vectors ------------------------------

    def _query_engine(self):
        """A second sharded engine whose syn0 holds the composed per-word
        vectors, assembled entirely on device (compose block ->
        ``write_rows``; nothing of O(vocab x dim) ever touches the host).
        Composed from the training engine's tables as of its
        ``table_version``: built on the first call (the server makes
        that call before its port binds) and composed anew, in place,
        once that version has moved (a training step, ``write_rows``,
        ``set_tables``). Similarity queries then reuse the standard
        distributed top-k."""
        ver = self.engine.table_version
        if self._qeng is not None and self._qeng_version == ver:
            return self._qeng
        t0 = time.perf_counter()
        with obs_events.span(
            "query_engine_build", words=self.vocab.size, version=ver
        ):
            if self._qeng is None:
                from glint_word2vec_tpu.parallel.engine import EmbeddingEngine

                self._qeng = EmbeddingEngine(
                    self.engine.mesh,
                    self.vocab.size,
                    self.vector_size,
                    self.vocab.counts,
                    num_negatives=self.engine.num_negatives,
                    seed=0,
                )
            B = self.COMPOSE_BLOCK
            for s in range(0, self.vocab.size, B):
                e = min(s + B, self.vocab.size)
                block = self._compose_device(
                    self._sub_ids[s:e], self._sub_mask[s:e]
                )
                self._qeng.write_rows(s, block)
            # The span and the seconds are the build's, not its enqueue's.
            self._qeng.syn0.block_until_ready()
        self._qeng_version = ver
        self.query_engine_builds += 1
        self.query_engine_build_seconds += time.perf_counter() - t0
        return self._qeng

    def to_local(self) -> LocalWord2VecModel:
        qeng = self._query_engine()
        vecs = np.empty((self.vocab.size, self.vector_size), np.float32)
        for s in range(0, self.vocab.size, MAX_QUERY_ROWS):
            idx = np.arange(s, min(s + MAX_QUERY_ROWS, self.vocab.size), dtype=np.int32)
            vecs[s : s + len(idx)] = np.asarray(qeng.pull(idx))
        return LocalWord2VecModel(list(self.vocab.words), vecs)

    def get_vectors(self):
        qeng = self._query_engine()
        for s in range(0, self.vocab.size, MAX_QUERY_ROWS):
            idx = np.arange(s, min(s + MAX_QUERY_ROWS, self.vocab.size), dtype=np.int32)
            rows = np.asarray(qeng.pull(idx))
            for i, r in zip(idx, rows):
                yield self.vocab.words[int(i)], r

    def stop(self) -> None:
        if self._qeng is not None:
            self._qeng.destroy()
            self._qeng = None
        super().stop()

    # -- persistence ---------------------------------------------------
    # save() is inherited: engine.save persists bucket rows via extra_rows
    # and params.json carries the subword geometry. load() shares the base
    # path via the hooks below; the subword table is rebuilt
    # deterministically from the words + geometry.

    _PARAMS_CLS = FastTextParams

    @classmethod
    def _from_loaded(cls, vocab, engine, params) -> "FastTextModel":
        sub_ids, sub_mask = build_subword_table(
            vocab.words, vocab.size, params.bucket, params.min_n,
            params.max_n, params.max_subwords,
        )
        return cls(vocab, engine, params, sub_ids, sub_mask)
