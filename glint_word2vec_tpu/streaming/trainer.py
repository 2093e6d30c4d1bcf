"""The ``fit_stream`` loop: incremental ISGNS over an unbounded
sentence stream (arXiv:1704.03956), wired to the serving fleet through
the generation publish protocol.

Shape discipline is the whole design: the stream is consumed in bounded
**mini-epochs** through ONE fixed-capacity device buffer. Each round
fills the buffer host-side (counting via the online vocabulary,
subsampling with the current adaptive keep distribution), re-uploads it
with the real fill as the ``n_valid`` prefix bound, and drains it
through the packed pair scan — every round reuses the same compiled
programs because every traced shape (ids buffer, offsets buffer, pair
batch) is constant, exactly the fixed-shape batching insight
(arXiv:1611.06172) the batch engine already exploits. Likewise the
adaptive refreshes: ``set_noise_counts`` swaps alias-table VALUES at
fixed shapes, promotion widens the serving top-k mask through a traced
scalar, so a week-long trainer compiles in its first minute and never
again.

Differences from batch ``fit`` (all inherent to one-look streaming,
documented in README "Streaming training & hot-swap serving"):

- subsampling runs host-side while filling (the device compaction pass
  needs the whole static buffer; ``compact_corpus`` rejects an
  ``n_valid``-bounded view);
- the LR is constant at ``step_size`` by default (``anneal_words``
  restores a linear decay horizon) — an unbounded stream has no
  ``total_words`` to anneal against;
- promoted words join mid-run on spare extra rows with fresh init, and
  are never negative-sampled (the noise table spans the bootstrap
  vocabulary, like fastText bucket rows).
"""

from __future__ import annotations

import logging
import time
from typing import Iterable, Iterator, List, Optional, Sequence

import numpy as np

from glint_word2vec_tpu.corpus.stream_vocab import (
    StreamVocab,
    bootstrap_stream_vocab,
)
from glint_word2vec_tpu.obs import start_run
from glint_word2vec_tpu.streaming.publish import SnapshotPublisher
from glint_word2vec_tpu.utils import faults
from glint_word2vec_tpu.utils.metrics import TrainingMetrics

logger = logging.getLogger(__name__)

#: LR denominator standing in for "unbounded": alpha stays within one
#: part in ~1e12 of step_size for any realistic stream.
_NO_ANNEAL_WORDS = 1 << 50


class StreamTrainer:
    """One long-lived streaming fit over a ``Word2Vec`` estimator's
    hyperparameters.

    Cadence knobs (all optional):

    - ``bootstrap_words``: stream prefix scanned batch-style (exact
      counts, frequency-ranked base vocabulary) before the engine is
      built. The bootstrap window itself is then trained first.
    - ``buffer_words`` / ``buffer_sentences``: the mini-epoch buffer
      capacity — the unit of training, accounting, and shape reuse.
    - ``extra_rows``: spare table rows reserved for online vocab
      growth (the promotion budget for the whole run).
    - ``refresh_words``: kept-word cadence for recomputing the
      adaptive noise + subsample distributions from live counts.
    - ``publish_seconds`` / ``publish_words``: generation publish
      cadence (whichever fires first); needs ``publish_dir``.
    - ``max_words`` / ``max_seconds``: optional stop bounds (smoke
      tests, bounded backfills); None = run until the stream ends.
    """

    def __init__(
        self,
        w2v,
        *,
        publish_dir: Optional[str] = None,
        bootstrap_words: int = 10_000,
        buffer_words: int = 65_536,
        buffer_sentences: Optional[int] = None,
        extra_rows: int = 1024,
        refresh_words: Optional[int] = None,
        publish_seconds: float = 30.0,
        publish_words: Optional[int] = None,
        publish_keep: int = 3,
        promote_min_count: Optional[int] = None,
        sketch_capacity: int = 65_536,
        anneal_words: Optional[int] = None,
        max_words: Optional[int] = None,
        max_seconds: Optional[float] = None,
    ):
        if buffer_words < 256:
            raise ValueError("buffer_words must be >= 256")
        if extra_rows < 0:
            raise ValueError("extra_rows must be >= 0")
        if w2v.params.architecture != "skipgram":
            raise ValueError(
                "the streaming trainer trains skip-gram only "
                f"(architecture={w2v.params.architecture!r}): fit a CBOW "
                "model with fit / fit_file"
            )
        self.w2v = w2v
        self.publish_dir = publish_dir
        self.bootstrap_words = bootstrap_words
        self.buffer_words = buffer_words
        self.buffer_sentences = (
            buffer_sentences or max(16, buffer_words // 8)
        )
        self.extra_rows = extra_rows
        self.refresh_words = refresh_words or buffer_words
        self.publish_seconds = publish_seconds
        self.publish_words = publish_words
        self.publish_keep = publish_keep
        self.promote_min_count = promote_min_count
        self.sketch_capacity = sketch_capacity
        self.anneal_words = anneal_words
        self.max_words = max_words
        self.max_seconds = max_seconds
        # Run state, exposed for tests and the final metrics dict.
        self.engine = None
        self.vocab: Optional[StreamVocab] = None
        self.publisher: Optional[SnapshotPublisher] = None
        self.rounds = 0
        self.steps = 0
        self.words_trained = 0
        self.sentences_streamed = 0
        self.stream_lag_seconds = 0.0
        self.noise_drift_l1 = 0.0

    # -- stream plumbing -----------------------------------------------

    def _chunked(self, sentences: Iterable[Sequence[str]]) -> Iterator[List[str]]:
        """Sentences clipped to ``max_sentence_length`` pieces — the
        same chunking the batch paths apply at encode time. Empty
        sentences pass through unchanged: an idle follow-mode source
        yields ``[]`` heartbeats so the consumer can re-check its stop
        bounds and publish cadence instead of blocking forever."""
        msl = self.w2v.params.max_sentence_length
        for s in sentences:
            s = list(s)
            if len(s) <= msl:
                yield s
                continue
            for i in range(0, len(s), msl):
                piece = s[i : i + msl]
                if piece:
                    yield piece

    def _bootstrap(self, it: Iterator[List[str]], t_start: float) -> List[List[str]]:
        """Pull the bootstrap window off the stream: enough sentences
        to cover ``bootstrap_words`` raw words (or the whole stream —
        or the ``max_seconds`` budget — if either ends first)."""
        window: List[List[str]] = []
        seen = 0
        for s in it:
            if not s:
                # idle heartbeat: a quiet follow-mode stream must not
                # pin a bounded run inside the bootstrap
                if (
                    self.max_seconds
                    and time.time() - t_start >= self.max_seconds
                ):
                    break
                continue
            window.append(s)
            seen += len(s)
            if seen >= self.bootstrap_words:
                break
        if not window:
            raise ValueError("empty stream: nothing to bootstrap from")
        return window

    # -- engine construction -------------------------------------------

    def _make_engine(self, mesh):
        from glint_word2vec_tpu.parallel.engine import EmbeddingEngine

        p = self.w2v.params
        return EmbeddingEngine(
            mesh,
            self.vocab.base_size,
            p.vector_size,
            self.vocab.noise_counts(),
            num_negatives=p.num_negatives,
            unigram_power=p.unigram_power,
            unigram_table_size=p.unigram_table_size,
            seed=p.seed,
            dtype=p.dtype,
            extra_rows=self.extra_rows,
            shared_negatives=p.shared_negatives,
            compute_dtype=p.compute_dtype,
        )

    # -- the loop -------------------------------------------------------

    def run(self, sentences: Iterable[Sequence[str]]):
        """Consume the stream; returns the final fitted
        ``Word2VecModel`` (grown vocabulary included) when the stream
        ends or a stop bound trips."""
        import jax

        from glint_word2vec_tpu.corpus.batching import packed_pair_batch
        from glint_word2vec_tpu.models.word2vec import (
            Word2VecModel,
            _ckpt_wait_timeout,
        )

        p = self.w2v.params
        if self.buffer_words < p.max_sentence_length:
            # _chunked clips every sentence to max_sentence_length
            # pieces; a piece that can never fit the buffer would spin
            # the carry loop forever.
            raise ValueError(
                f"buffer_words ({self.buffer_words}) must be >= "
                f"max_sentence_length ({p.max_sentence_length}) so "
                "every sentence piece fits the mini-epoch buffer"
            )
        t_start = time.time()
        it = self._chunked(sentences)
        window = self._bootstrap(it, t_start)
        self.vocab = bootstrap_stream_vocab(
            window,
            min_count=p.min_count,
            sketch_capacity=self.sketch_capacity,
            max_size=None,
        )
        sv = self.vocab
        mesh = self.w2v._make_mesh()
        if p.batch_size % mesh.shape["data"]:
            raise ValueError(
                f"batch_size ({p.batch_size}) must be divisible by the "
                f"data-axis size ({mesh.shape['data']})"
            )
        engine = self.engine = self._make_engine(mesh)
        logger.info(
            "stream bootstrap: %d words vocab, %d spare rows, "
            "buffer %d words",
            sv.base_size, self.extra_rows, self.buffer_words,
        )
        if self.publish_dir:
            self.publisher = SnapshotPublisher(
                self.publish_dir, engine, p, keep=self.publish_keep,
            )
        obs_run = start_run(
            self.w2v.obs, pipeline="stream", total_epochs=0,
            total_words=0, engine=engine,
        )
        metrics = TrainingMetrics()
        obs_run.attach_metrics(metrics)
        min_count = (
            self.promote_min_count
            if self.promote_min_count is not None else p.min_count
        )
        total_words = (
            self.anneal_words + 1
            if self.anneal_words else _NO_ANNEAL_WORDS
        )
        B, W, spc = p.batch_size, p.window, p.steps_per_call
        # ~B positions per packed step (grid-equivalent synchronous
        # batch — see corpus/batching.packed_pair_batch).
        pair_batch = packed_pair_batch(B, W, mesh.shape["data"])
        base_key = jax.random.PRNGKey(p.seed)
        keep = sv.keep_probabilities(p.subsample_ratio)
        rng = np.random.default_rng(p.seed)
        prev_noise = sv.noise_weights(p.unigram_power)
        words_at_refresh = 0
        words_at_publish = 0
        last_publish_t = time.time()

        def publish_now(fill_gauge: int) -> None:
            nonlocal last_publish_t, words_at_publish
            with obs_run.span("publish", round=self.rounds):
                self.publisher.publish(sv.snapshot_vocabulary())
            last_publish_t = time.time()
            words_at_publish = self.words_trained
            self._update_stream_gauges(obs_run, fill_gauge)

        # The bootstrap window is the first training data: replay it
        # through the same buffer path the live stream uses.
        import itertools

        stream = itertools.chain(window, it)
        # The bootstrap window's occurrences are already in the counts
        # (exact, from bootstrap_stream_vocab) — replay it encode-only
        # so nothing is counted twice.
        bootstrap_left = len(window)
        carry: Optional[List[int]] = None
        exhausted = False
        try:
            while not exhausted:
                if self.max_words and self.words_trained >= self.max_words:
                    break
                if (
                    self.max_seconds
                    and time.time() - t_start >= self.max_seconds
                ):
                    break
                # -- fill one mini-epoch buffer host-side --------------
                t_fill0 = time.time()
                ids_buf = np.zeros(self.buffer_words, np.int32)
                offsets = [0]
                fill = 0
                with obs_run.span("stream_fill", round=self.rounds):
                    while (
                        fill < self.buffer_words
                        and len(offsets) <= self.buffer_sentences
                    ):
                        # A slow or idle stream must not starve the
                        # bounds or the publish cadence: re-check them
                        # between pulls (the source yields [] heartbeats
                        # while idle), training whatever partial buffer
                        # is on hand when a deadline fires.
                        if (
                            self.max_seconds
                            and time.time() - t_start >= self.max_seconds
                        ):
                            break
                        if (
                            self.publisher is not None
                            and time.time() - last_publish_t
                            >= self.publish_seconds
                            and (
                                fill
                                or self.words_trained > words_at_publish
                            )
                        ):
                            # fill > 0: train the partial buffer so the
                            # due publish carries it. fill == 0 with
                            # unpublished words: break so the idle
                            # branch below can publish — an UNBOUNDED
                            # run would otherwise spin here on
                            # heartbeats and starve the cadence.
                            break
                        if carry is not None:
                            # Stashed AFTER last round's subsample pass:
                            # running it through the keep draw again
                            # would thin its frequent words to p^2.
                            enc, carry = carry, None
                            from_carry = True
                        else:
                            from_carry = False
                            sent = next(stream, None)
                            if sent is None:
                                exhausted = True
                                break
                            if not sent:
                                # idle-stream heartbeat: nothing to
                                # count, just re-check the bounds above
                                continue
                            # Count + encode through the online vocab
                            # (OOV feeds the candidate sketch), then
                            # subsample with the live keep distribution.
                            if bootstrap_left > 0:
                                bootstrap_left -= 1
                                enc = sv.encode(sent)
                            else:
                                enc = sv.observe(sent)
                            self.sentences_streamed += 1
                        if not enc:
                            continue
                        if p.subsample_ratio > 0 and not from_carry:
                            # graftlint: ignore[sync-point] enc is a host id list from the online vocab
                            arr = np.asarray(enc, np.int32)
                            enc = arr[
                                rng.random(arr.shape[0]) < keep[arr]
                            ].tolist()
                            if not enc:
                                continue
                        if fill + len(enc) > self.buffer_words:
                            carry = enc
                            break
                        ids_buf[fill : fill + len(enc)] = enc
                        fill += len(enc)
                        offsets.append(fill)
                if fill == 0:
                    if exhausted:
                        break
                    # An idle stream must not starve the publish
                    # cadence: rounds trained since the last publish
                    # still reach the fleet while no new data arrives.
                    if (
                        self.publisher is not None
                        and self.words_trained > words_at_publish
                        and time.time() - last_publish_t
                        >= self.publish_seconds
                    ):
                        publish_now(0)
                    continue
                # -- grow: promote candidates onto spare rows ----------
                promoted_round = 0
                while engine.extra_rows_free > 0:
                    cands = sv.promotable(
                        min_count, limit=engine.extra_rows_free
                    )
                    if not cands:
                        break
                    # One batched mutation per burst: a vocabulary
                    # shift can promote many words at once, and
                    # per-word writes would serialize tiny dispatches.
                    rows = engine.assign_extra_rows(
                        [word for word, _ in cands]
                    )
                    for row, (word, est) in zip(rows, cands):
                        idx = sv.promote(word, est)
                        if row != idx:  # pragma: no cover - invariant
                            raise AssertionError(
                                f"row/vocab drift: engine row {row} != "
                                f"vocab index {idx} for {word!r}"
                            )
                    promoted_round += len(cands)
                # -- adapt: refresh noise + subsample distributions ----
                if (
                    promoted_round
                    or sv.train_words_count - words_at_refresh
                    >= self.refresh_words
                ):
                    words_at_refresh = sv.train_words_count
                    engine.set_noise_counts(sv.noise_counts())
                    keep = sv.keep_probabilities(p.subsample_ratio)
                    cur = sv.noise_weights(p.unigram_power)
                    # graftlint: ignore[sync-point] both operands are host numpy distributions
                    self.noise_drift_l1 = float(
                        np.abs(cur - prev_noise).sum()
                    )
                    prev_noise = cur
                # -- train: one bounded mini-epoch ---------------------
                # +2: up to buffer_sentences real boundaries after the
                # leading 0, plus the dedicated final pad boundary — a
                # full sentence buffer must not have its last real
                # boundary overwritten by the pad one.
                offsets_arr = np.full(
                    self.buffer_sentences + 2, fill, np.int64
                )
                offsets_arr[: len(offsets)] = offsets
                # The trailing pad run is its own "sentence": centers in
                # it sit at/past n_valid (zero-mask lanes), and no real
                # sentence window can cross into it.
                offsets_arr[-1] = self.buffer_words
                with obs_run.span("upload_corpus", words=fill):
                    engine.upload_corpus(ids_buf, offsets_arr, n_valid=fill)
                pos = 0
                while pos < fill:
                    faults.fire("worker.step")
                    with metrics.timing("step"), obs_run.span(
                        "device_steps", step0=self.steps, n=spc, packed=True
                    ):
                        losses, pair_counts, pos_ends, alphas, _ = (
                            engine.train_steps_corpus_packed(
                                pos, pair_batch, W, B, base_key, spc,
                                step0=self.steps, grid_step0=self.steps,
                                step_size=p.step_size,
                                total_words=total_words,
                                words_base=self.words_trained,
                            )
                        )
                    pos = self._harvest(
                        metrics, obs_run, losses, pos_ends, alphas,
                        pos, fill,
                    )
                self.words_trained += fill
                self.rounds += 1
                self.stream_lag_seconds = time.time() - t_fill0
                obs_run.update(
                    epoch=self.rounds, step=self.steps,
                    words_done=self.words_trained,
                )
                self._update_stream_gauges(obs_run, fill)
                # -- publish on cadence --------------------------------
                if self.publisher is not None:
                    due_t = (
                        time.time() - last_publish_t
                        >= self.publish_seconds
                    )
                    due_w = (
                        self.publish_words is not None
                        and self.words_trained - words_at_publish
                        >= self.publish_words
                    )
                    if due_t or due_w:
                        publish_now(fill)
            # Final publish: the stream's last words must reach the
            # fleet even when the cadence did not fire.
            if self.publisher is not None and self.words_trained:
                self.publisher.publish(sv.snapshot_vocabulary())
            engine.wait_pending_saves(timeout=_ckpt_wait_timeout())
            self._update_stream_gauges(obs_run, 0)
        except BaseException:
            engine.wait_pending_saves(
                reraise=False, timeout=_ckpt_wait_timeout()
            )
            obs_run.close(failed=True)
            raise
        finally:
            obs_run.close()
        logger.info(
            "stream done: %d rounds, %d words trained, %d promoted, "
            "%d generations",
            self.rounds, self.words_trained, sv.promoted,
            self.publisher.published if self.publisher else 0,
        )
        model = Word2VecModel(sv.snapshot_vocabulary(), engine, p)
        model.training_metrics = {
            **metrics.summary(),
            "pipeline": "stream",
            "step_body": engine.step_body,
            "rounds": self.rounds,
            "words_trained": self.words_trained,
            "vocab_size": sv.size,
            "promoted_words": sv.promoted,
            "oov_words_seen": sv.oov_words_seen,
            "generations_published": (
                self.publisher.published if self.publisher else 0
            ),
        }
        return model

    def _harvest(self, metrics, obs_run, losses, pos_ends, alphas,
                 start: int, n_valid: int) -> int:
        """Sync one dispatched group's result scalars into the metrics
        and return the consumed position. The streaming loop harvests
        synchronously — its host work between groups (nothing: the
        buffer is already uploaded) cannot starve the device the way
        the batch fit loop's could."""
        with metrics.timing("step"), obs_run.span(
            "readback_harvest", packed=True
        ) as span:
            pos_ends_h = np.asarray(pos_ends)
            losses_h = np.asarray(losses)
            alphas_h = np.asarray(alphas)
            starts = np.concatenate(([start], pos_ends_h[:-1]))
            n_real = int((starts < n_valid).sum())
            span.update(n=n_real)
            for i in range(n_real):
                self.steps += 1
                metrics.record_step(
                    self.words_trained + int(min(pos_ends_h[i], n_valid)),
                    loss=losses_h[i], alpha=float(alphas_h[i]),
                )
            obs_run.observe_losses(
                self.steps - n_real, losses_h, n_real
            )
            self.steps += losses_h.shape[0] - n_real  # tail keys consumed
        return int(pos_ends_h[-1])

    def _update_stream_gauges(self, obs_run, fill: int) -> None:
        sv, engine = self.vocab, self.engine
        pub = self.publisher
        obs_run.update_streaming(
            words_streamed=sv.train_words_count,
            sentences_streamed=self.sentences_streamed,
            oov_words=sv.oov_words_seen,
            vocab_size=sv.size,
            promoted_words=sv.promoted,
            extra_rows_free=engine.extra_rows_free,
            sketch_fill=len(sv.sketch) / max(sv.sketch.capacity, 1),
            noise_drift_l1=self.noise_drift_l1,
            stream_lag_seconds=self.stream_lag_seconds,
            generations_published=pub.published if pub else 0,
            last_publish_unix=pub.last_publish_time if pub else None,
            buffer_fill=fill / max(self.buffer_words, 1),
        )
