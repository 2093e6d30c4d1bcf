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
fixed shapes, a promotion writes its rows a fixed block at a time
(``engine.assign_extra_rows``: one program whatever the burst) and
widens the serving top-k mask through a traced scalar, so a week-long
trainer compiles in its first minute and never again: held to a chip at
2M x 300 by the cell ``w2v-stream-300-2m.train``, whose window sees a
partial buffer, promotion bursts and refreshes and must compile nothing.

A round has a HOST half (fill the buffer, promote, re-derive the
distributions: ``_host_rounds``) and a DEVICE half (install the round's
noise table, upload its buffer, drain it a dispatch group at a time).
The host half depends on nothing the device computes, so the loop runs it
AHEAD, up to ``_ROUNDS_AHEAD`` rounds, in slices of a few milliseconds
between a group's dispatch and its harvest, on the one thread that also
drives the device: while the device works the host fills, and when a
group's outputs are there (asked between two slices, never waited on) it
is harvested and the next dispatched. What a buffer holds, what is
promoted when and what the distributions read does not depend on when the
slices run; the noise table a round trains under is still the one its own
refresh built, installed when the round starts. Inside a drain each group
is read back before the next is dispatched, so the device still waits out
a read-back and a dispatch between two groups, and an install and an
upload between two rounds (PERF.md section 5 has a traced round at 2M
rows: what each part takes, and the device's idle share). A loop that
filled each buffer only once the last was drained left the device idle
for more than half of every round there, and its pace to the host's
(PERF.md section 6, PR 50). Spans: ``stream_round`` around a round's
device half, with ``stream_install``, ``upload_corpus`` and a
``device_steps`` / ``readback_harvest`` pair a group inside it; the host
half's ``stream_fill`` (a chunk of sentences a span), ``stream_promote``
and ``stream_adapt`` carry the ``round`` they prepare and lie wherever
they ran.

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

import collections
import gc
import logging
import time
from typing import Iterable, Iterator, List, NamedTuple, Optional, Sequence

import numpy as np

from glint_word2vec_tpu.corpus.stream_vocab import (
    StreamVocab,
    bootstrap_stream_vocab,
)
from glint_word2vec_tpu.obs import start_run
from glint_word2vec_tpu.streaming.publish import SnapshotPublisher
from glint_word2vec_tpu.utils import faults
from glint_word2vec_tpu.utils.metrics import TrainingMetrics, scatter_summary

logger = logging.getLogger(__name__)

#: LR denominator standing in for "unbounded": alpha stays within one
#: part in ~1e12 of step_size for any realistic stream.
_NO_ANNEAL_WORDS = 1 << 50

#: Sentences a slice of the fill pulls, counts and encodes at once: some
#: 15,000 words, a few milliseconds, which is how long a finished dispatch
#: group can wait for its harvest.
_CHUNK_SENTENCES = 512
#: Rounds the host half may be ahead of the round that trains: a slow
#: stretch of the host is then paid from what is ready, not by the device.
_ROUNDS_AHEAD = 2
#: A long slice starts only where this many times what it took last
#: still fit in what is left of the dispatch group, so that it ends
#: before the group does.
_LONG_ROOM = 1.3

_DONE = object()  # the host half has ended
_IDLE = object()  # a fill came back empty from a stream that goes on


class _Long(NamedTuple):
    """The host half's next slice is a long one (a promotion's scan of
    the sketch, a refresh): the seconds the last such took."""

    seconds: float


class _Round(NamedTuple):
    """One round as the host half hands it over: the buffer, what filled
    it, and the noise distribution to install before it trains."""

    ids: np.ndarray  # (buffer_words,) int32
    offsets: np.ndarray  # (buffer_sentences + 2,) int64
    fill: int  # kept words, the buffer's valid prefix
    raw_words: int  # raw tokens of the sentences the fill pulled
    live: bool  # every sentence came from past the bootstrap window
    t_fill0: float  # time.time() when the fill began
    noise: Optional[tuple]  # (counts, alias table) of a refresh, or None


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
        #: Rounds whose every sentence came from past the bootstrap window.
        self.live_rounds = 0
        #: Refreshes of the noise and keep distributions.
        self.refreshes = 0
        self.steps = 0
        self.words_trained = 0
        self.sentences_streamed = 0
        #: Raw tokens of the sentences the rounds pulled (the bootstrap
        #: window's replay included), before OOV drop and subsampling.
        self.raw_words_streamed = 0
        self.packed_pairs = 0
        self.packed_slots = 0
        self.rows_written = np.zeros(4, np.int64)
        #: Whether native/host_ops.cpp built the alias tables: a refresh
        #: over V counts is milliseconds with it and a Python loop over V
        #: without (corpus/alias.build_alias).
        self.alias_native = False
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
        from glint_word2vec_tpu.native import get_lib

        self.alias_native = get_lib() is not None
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
        total_words = (
            self.anneal_words + 1
            if self.anneal_words else _NO_ANNEAL_WORDS
        )
        B, W, spc = p.batch_size, p.window, p.steps_per_call
        # ~B positions per packed step (grid-equivalent synchronous
        # batch — see corpus/batching.packed_pair_batch).
        pair_batch = packed_pair_batch(B, W, mesh.shape["data"])
        base_key = jax.random.PRNGKey(p.seed)
        self._last_publish_t = time.time()
        self._words_at_publish = 0

        def publish_now() -> None:
            with obs_run.span("publish", round=self.rounds):
                self.publisher.publish(sv.snapshot_vocabulary())
            self._last_publish_t = time.time()
            self._words_at_publish = self.words_trained

        # The bootstrap window is the first training data: replay it
        # through the same buffer path the live stream uses. Its
        # occurrences are already in the counts (exact, from
        # bootstrap_stream_vocab), so it is replayed encode-only and
        # nothing is counted twice. The chain lets go of the window once
        # it is replayed: held for the run, its sentences are so many
        # lists for every full pass of the collector to walk.
        import itertools

        host = self._host_rounds(
            itertools.chain(window, it), len(window), t_start, obs_run
        )
        del window
        # The vocabulary's two million words, its dictionary and the
        # bootstrap window are there for the whole run: a full pass of the
        # cyclic collector would walk them, 100 ms at 2M words, a few
        # times a minute, with the device waiting where it falls between
        # a group's end and the next dispatch (PERF.md section 6, PR 50).
        # Frozen, they are no pass's to walk; what the rounds allocate is.
        gc.collect()
        gc.freeze()
        # Rounds the host half has made ready, oldest first, and the
        # idle marks between them.
        ready: collections.deque = collections.deque()
        host_done = False
        # Seconds of the host half's next slice where it said it is a
        # long one (_Long), else None.
        long_next = None

        def host_step() -> None:
            nonlocal host_done, long_next
            long_next = None
            item = next(host, _DONE)
            if item is _DONE:
                host_done = True
            elif isinstance(item, _Long):
                long_next = item.seconds
            elif item is not None:
                ready.append(item)

        # Seconds from a group's dispatch to the end of its harvest, the
        # last one seen: what a long slice has to fit in.
        group_s = float("inf")
        try:
            while True:
                while not ready and not host_done:
                    host_step()
                if not ready:
                    break
                rnd = ready.popleft()
                if rnd is _IDLE:
                    # An idle stream must not starve the publish
                    # cadence: rounds trained since the last publish
                    # still reach the fleet while no new data arrives.
                    if (
                        self.publisher is not None
                        and self.words_trained > self._words_at_publish
                        and time.time() - self._last_publish_t
                        >= self.publish_seconds
                    ):
                        publish_now()
                        self._update_stream_gauges(obs_run, 0)
                    continue
                fill = rnd.fill
                with obs_run.span(
                    "stream_round", round=self.rounds, fill=fill,
                    raw_words=rnd.raw_words, live=rnd.live,
                ) as rspan:
                    # -- the round's distributions and its buffer ------
                    if rnd.noise is not None:
                        with obs_run.span(
                            "stream_install", round=self.rounds
                        ):
                            engine.set_noise_counts(*rnd.noise)
                    with obs_run.span("upload_corpus", words=fill):
                        engine.upload_corpus(
                            rnd.ids, rnd.offsets, n_valid=fill
                        )
                    # -- train: one bounded mini-epoch -----------------
                    pos = 0
                    groups = 0
                    while pos < fill:
                        faults.fire("worker.step")
                        with metrics.timing("step"), obs_run.span(
                            "device_steps", step0=self.steps, n=spc,
                            packed=True,
                        ):
                            group = engine.train_steps_corpus_packed(
                                pos, pair_batch, W, B, base_key, spc,
                                step0=self.steps, grid_step0=self.steps,
                                step_size=p.step_size,
                                total_words=total_words,
                                words_base=self.words_trained,
                            )
                        t_group = time.perf_counter()
                        # The host half of the rounds to come, a slice at
                        # a time, while the device works on this group.
                        while (
                            len(ready) < _ROUNDS_AHEAD
                            and not host_done
                            and self._device_busy(group)
                        ):
                            if long_next is not None and (
                                time.perf_counter() - t_group
                                + _LONG_ROOM * long_next > group_s
                            ):
                                # after the next dispatch, with a whole
                                # group to fit in
                                break
                            host_step()
                        pos = self._harvest(
                            metrics, obs_run, group, pos, fill, pair_batch
                        )
                        group_s = time.perf_counter() - t_group
                        groups += 1
                    self.words_trained += fill
                    self.raw_words_streamed += rnd.raw_words
                    self.rounds += 1
                    self.live_rounds += rnd.live
                    self.stream_lag_seconds = time.time() - rnd.t_fill0
                    rspan.update(groups=groups)
                    obs_run.update(
                        epoch=self.rounds, step=self.steps,
                        words_done=self.words_trained,
                    )
                    # -- publish on cadence ----------------------------
                    if self.publisher is not None:
                        due_t = (
                            time.time() - self._last_publish_t
                            >= self.publish_seconds
                        )
                        due_w = (
                            self.publish_words is not None
                            and self.words_trained - self._words_at_publish
                            >= self.publish_words
                        )
                        if due_t or due_w:
                            publish_now()
                    self._update_stream_gauges(obs_run, fill)
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
            host.close()
            obs_run.close()
            gc.unfreeze()
        logger.info(
            "stream done: %d rounds, %d words trained, %d promoted, "
            "%d generations",
            self.rounds, self.words_trained, sv.promoted,
            self.publisher.published if self.publisher else 0,
        )
        model = Word2VecModel(sv.snapshot_vocabulary(), engine, p)
        live_steps = self.packed_slots // pair_batch
        model.training_metrics = {
            **metrics.summary(),
            **(
                {"packed_pairs": self.packed_pairs,
                 "packed_mask_density": round(
                     self.packed_pairs / self.packed_slots, 4)}
                if self.packed_slots else {}
            ),
            **scatter_summary(
                self.rows_written, live_steps,
                engine.packed_scatter_slots(pair_batch, W),
            ),
            "pipeline": "stream",
            "step_body": engine.step_body,
            "rounds": self.rounds,
            "live_rounds": self.live_rounds,
            "refreshes": self.refreshes,
            "alias_native": self.alias_native,
            "raw_words_streamed": self.raw_words_streamed,
            "words_trained": self.words_trained,
            "vocab_size": sv.size,
            "promoted_words": sv.promoted,
            "oov_words_seen": sv.oov_words_seen,
            "generations_published": (
                self.publisher.published if self.publisher else 0
            ),
        }
        return model

    # -- the host half ---------------------------------------------------

    def _host_rounds(self, stream: Iterator[List[str]], bootstrap_left: int,
                     t_start: float, obs_run):
        """The host half of every round, in stream order, as a generator
        the loop steps a SLICE at a time while the device works: fill one
        mini-epoch buffer (pull a chunk of sentences, count and encode it
        through the online vocabulary, subsample it with the keep
        distribution in force), promote candidates onto spare rows,
        re-derive the noise and keep distributions from the live counts.

        Yields None after a slice that finished nothing, a :class:`_Long`
        before a slice of tens of milliseconds (a promotion's scan of the
        sketch, a refresh), a :class:`_Round`
        when one is ready to train, ``_IDLE`` when a fill came back empty
        from a stream that has not ended. What a round's buffer holds,
        what is promoted when and what the distributions read does not
        depend on when the slices run: the host state moves through the
        same sequence as in a loop that trains each round before it fills
        the next (streaming/stream_reference.py states it that way, and
        tests hold this to it). ``bootstrap_left`` leading sentences of
        ``stream`` are the bootstrap window, already counted."""
        p, sv, engine = self.w2v.params, self.vocab, self.engine
        min_count = (
            self.promote_min_count
            if self.promote_min_count is not None else p.min_count
        )
        keep = sv.keep_probabilities(p.subsample_ratio)
        rng = np.random.default_rng(p.seed)
        prev_noise = sv.noise_weights(p.unigram_power)
        words_at_refresh = 0
        # Kept words of the rounds made ready: what ``words_trained``
        # reads once they are trained, so ``max_words`` stops the fills
        # where it would stop a loop that trained each round first.
        words_ready = 0
        hround = 0
        # A sentence that did not fit a round's buffer, AFTER its keep
        # draw (running it through the draw again would thin its
        # frequent words to p^2), with whether it came from past the
        # bootstrap window; and a sentence pulled past a chunk's end,
        # which nothing has counted yet.
        carry = None
        held = None
        exhausted = False
        # What the last promotion and the last refresh took: the loop
        # starts the next where that much still fits behind the device.
        promote_s = adapt_s = 0.0

        def out_of_time() -> bool:
            return bool(
                self.max_seconds
                and time.time() - t_start >= self.max_seconds
            )

        while not (exhausted and carry is None):
            if self.max_words and words_ready >= self.max_words:
                return
            if out_of_time():
                return
            # -- fill one mini-epoch buffer ----------------------------
            t_fill0 = time.time()
            ids_buf = np.zeros(self.buffer_words, np.int32)
            ends: List[np.ndarray] = []
            n_sent = fill = raw_words = 0
            # every sentence of the round came from past the bootstrap
            # window
            live = True
            while fill < self.buffer_words and n_sent < self.buffer_sentences:
                # A slow or idle stream must not starve the bounds or
                # the publish cadence: re-check them between chunks (the
                # source yields [] heartbeats while idle, which end a
                # chunk), training whatever partial buffer is on hand
                # when a deadline fires.
                if out_of_time():
                    break
                if (
                    self.publisher is not None
                    and time.time() - self._last_publish_t
                    >= self.publish_seconds
                    and (
                        fill
                        or self.words_trained > self._words_at_publish
                    )
                ):
                    # fill > 0: train the partial buffer so the due
                    # publish carries it. fill == 0 with unpublished
                    # words: break so the loop can publish — an
                    # UNBOUNDED run would otherwise spin here on
                    # heartbeats and starve the cadence.
                    break
                with obs_run.span("stream_fill", round=hround):
                    if carry is not None:
                        (kept, chunk_live), carry = carry, None
                        kept_lens = np.full(1, kept.size)
                    else:
                        # A chunk: sentences whose raw words all fit what
                        # is left of the buffer, so none of them can
                        # overflow it whatever the keep draws (but the
                        # first, taken whatever its length: alone, it is
                        # the sentence that may have to be carried).
                        chunk: List[List[str]] = []
                        chunk_words = 0
                        room = min(
                            self.buffer_sentences - n_sent, _CHUNK_SENTENCES
                        )
                        chunk_live = bootstrap_left == 0
                        while len(chunk) < room:
                            if held is not None:
                                sent, held = held, None
                            else:
                                sent = next(stream, None)
                                if sent is None:
                                    exhausted = True
                                    break
                                if not sent:
                                    # idle-stream heartbeat: nothing to
                                    # count, re-check the bounds
                                    break
                            if chunk and (
                                fill + chunk_words + len(sent)
                                > self.buffer_words
                            ):
                                held = sent
                                break
                            chunk.append(sent)
                            chunk_words += len(sent)
                            if not chunk_live:
                                bootstrap_left -= 1
                                if bootstrap_left == 0:
                                    break
                        # Count + encode through the online vocab (OOV
                        # feeds the candidate sketch), then subsample
                        # with the live keep distribution: one draw a
                        # known word, in stream order.
                        if not chunk:
                            # a heartbeat, or the stream's end
                            rows = lens = np.zeros(0, np.int64)
                        elif chunk_live:
                            rows, lens = sv.observe_many(chunk)
                        else:
                            rows, lens = sv.encode_many(chunk)
                        self.sentences_streamed += len(chunk)
                        raw_words += chunk_words
                        if p.subsample_ratio > 0 and rows.size:
                            drawn = rng.random(rows.size) < keep[rows]
                            kept = rows[drawn]
                            kept_lens = np.bincount(
                                np.repeat(np.arange(lens.size), lens)[drawn],
                                minlength=lens.size,
                            )
                        else:
                            kept, kept_lens = rows, lens
                    if kept.size:
                        if fill + kept.size > self.buffer_words:
                            carry = (kept, chunk_live)
                            break
                        live = live and chunk_live
                        ids_buf[fill : fill + kept.size] = kept
                        kept_lens = kept_lens[kept_lens > 0]
                        ends.append(fill + np.cumsum(kept_lens))
                        n_sent += kept_lens.size
                        fill += kept.size
                    if exhausted:
                        break
                yield None
            if fill == 0:
                if exhausted:
                    return
                yield _IDLE
                continue
            # -- grow: promote candidates onto spare rows --------------
            promoted_round = 0
            yield _Long(promote_s)
            t_slice = time.perf_counter()
            with obs_run.span("stream_promote", round=hround) as pspan:
                while engine.extra_rows_free > 0:
                    cands = sv.promotable(
                        min_count, limit=engine.extra_rows_free
                    )
                    if not cands:
                        break
                    # One batched mutation per burst: a vocabulary shift
                    # can promote many words at once, and per-word
                    # writes would serialize tiny dispatches. The rows
                    # are spare: no buffer on its way to the device
                    # names them, so the write may run rounds ahead.
                    rows = engine.assign_extra_rows(
                        [word for word, _ in cands]
                    )
                    for row, (word, est) in zip(rows, cands):
                        idx = sv.promote(word, est)
                        if row != idx:  # pragma: no cover - invariant
                            raise AssertionError(
                                f"row/vocab drift: engine row "
                                f"{row} != vocab index {idx} for "
                                f"{word!r}"
                            )
                    promoted_round += len(cands)
                pspan.update(promoted=promoted_round)
            promote_s = time.perf_counter() - t_slice
            # -- adapt: refresh noise + subsample distributions --------
            refreshed = bool(
                promoted_round
                or sv.train_words_count - words_at_refresh
                >= self.refresh_words
            )
            noise = None
            if refreshed:
                yield _Long(adapt_s)
            t_slice = time.perf_counter()
            with obs_run.span(
                "stream_adapt", round=hround, refreshed=refreshed,
                alias_native=self.alias_native,
            ):
                if refreshed:
                    words_at_refresh = sv.train_words_count
                    counts = sv.noise_counts()
                    # built here, installed when the round trains
                    noise = (counts, engine.noise_table(counts))
                    keep = sv.keep_probabilities(p.subsample_ratio)
                    cur = sv.noise_weights(p.unigram_power)
                    # graftlint: ignore[sync-point] both operands are host numpy distributions
                    self.noise_drift_l1 = float(
                        np.abs(cur - prev_noise).sum()
                    )
                    prev_noise = cur
                    self.refreshes += 1
            if refreshed:
                adapt_s = time.perf_counter() - t_slice
            # +2: up to buffer_sentences real boundaries after the
            # leading 0, plus the dedicated final pad boundary — a full
            # sentence buffer must not have its last real boundary
            # overwritten by the pad one.
            offsets = np.full(self.buffer_sentences + 2, fill, np.int64)
            offsets[0] = 0
            if ends:
                offsets[1 : n_sent + 1] = np.concatenate(ends)
            # The trailing pad run is its own "sentence": centers in it
            # sit at/past n_valid (zero-mask lanes), and no real sentence
            # window can cross into it.
            offsets[-1] = self.buffer_words
            words_ready += fill
            hround += 1
            yield _Round(ids_buf, offsets, fill, raw_words, live, t_fill0,
                         noise)

    @staticmethod
    def _device_busy(group) -> bool:
        """Whether the device still works on a dispatched group (its
        outputs are not there yet): asked between two slices of the host
        half, never waited on."""
        return not group[2].is_ready()

    def _harvest(self, metrics, obs_run, group, start: int, n_valid: int,
                 pair_batch: int) -> int:
        """Sync one dispatched group's result scalars into the metrics
        and return the consumed position. The next group is dispatched
        only once this one has been read back (it starts where this one
        ended), so the device waits out a read-back, this accounting and
        a dispatch between every two groups of a round
        (``stream.drain_device_share``, PERF.md section 5, has what that
        costs at four groups a round)."""
        with metrics.timing("step"), obs_run.span(
            "readback_harvest", packed=True
        ) as span:
            losses, pair_counts, pos_ends, alphas, written = group
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
            # The steps' own counts, as the batch fit keeps them: live
            # pairs of the slots dispatched, rows the scatters wrote.
            self.packed_pairs += int(np.asarray(pair_counts)[:n_real].sum())
            self.packed_slots += n_real * pair_batch
            self.rows_written += np.asarray(written)[:n_real, :4].sum(axis=0)
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
