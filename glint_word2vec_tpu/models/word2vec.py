"""The Word2Vec estimator and fitted model — the user-facing API layer.

Reference mapping (SURVEY.md §2):
  - :class:`Word2Vec` = the trainer/estimator pair C1+C6
    (mllib/feature/ServerSideGlintWord2Vec.scala:65-451 and
    ml/feature/ServerSideGlintWord2Vec.scala:228-317), with the reference's
    fluent setter surface (mllib:92-243) in snake_case.
  - :class:`Word2VecModel` = the model pair C3+C7 (mllib:460-669,
    ml:319-497): transform in its three reference flavors, findSynonyms,
    analogy arithmetic, getVectors, toLocal, save/load/stop.
  - :class:`LocalWord2VecModel` = the ``toLocal`` result (mllib:651-657):
    a host-only numpy model with the same query surface.

The PS-cluster topology parameters (``parameterServerHost``,
``parameterServerConfig``) have no analogue — device placement is a
``jax.sharding.Mesh`` passed directly (or defaulted) — and the training loop
is synchronous: one jit step per minibatch instead of the reference's
per-partition async future chains (mllib:417-429).
"""

from __future__ import annotations

import functools
import json
import logging
import os
import time
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from glint_word2vec_tpu.corpus.batching import (
    BatchGroup,
    SkipGramBatcher,
    chunk_sentences,
    context_width,
    encode_sentences,
    group_batches,
    packed_pair_batch,
)
from glint_word2vec_tpu.corpus.vocab import (
    Vocabulary,
    build_vocab,
    saved_model_vocabulary,
)
from glint_word2vec_tpu.obs import TrainingDiverged, start_run
from glint_word2vec_tpu.utils import faults, next_pow2
from glint_word2vec_tpu.utils.metrics import TrainingMetrics, scatter_summary
from glint_word2vec_tpu.utils.params import Word2VecParams
from glint_word2vec_tpu.utils.prefetch import prefetch

logger = logging.getLogger(__name__)

#: Rows per query chunk — the reference batches word/sentence requests
#: 10,000 at a time (mllib:531, ml:449). Here it only bounds HBM spikes.
MAX_QUERY_ROWS = 10_000


def _flip_checkpoint_state(
    checkpoint_dir: str, state_path: str, ck_name: str, *,
    epochs_completed: int, step: int, words_done: int,
    extra: Optional[dict] = None,
) -> None:
    """Atomically point train_state.json at a finished table snapshot and
    prune superseded snapshot dirs. The tables must already be on disk:
    a crash mid-write can never yield a state file referencing partial
    tables (shared by the batcher and corpus-resident training loops).
    ``extra`` merges additional progress counters into the state (the
    packed corpus loop records its consumed-position counter and
    grid-equivalent step base so mid-epoch resumes are exact).

    Keep-last-2 retention: the previously committed record rides along
    under ``"prev"`` and its snapshot directory survives the prune, so a
    checkpoint that later fails integrity verification (bit rot, torn
    write) has a committed fallback
    (utils.integrity.resolve_train_state). Everything older is GC'd."""
    import shutil

    prev = None
    if os.path.exists(state_path):
        try:
            with open(state_path) as f:
                prev = json.load(f)
            prev.pop("prev", None)  # keep exactly two, not a chain
        except (OSError, ValueError):
            prev = None
    if prev is not None and (
        # A legacy record with no snapshot-dir name cannot serve as a
        # fallback; re-committing the same name (repeated
        # stop_after_epochs runs) must not point prev at ourselves.
        "ckpt" not in prev or prev["ckpt"] == ck_name
    ):
        prev = None
    tmp = state_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(
            {
                "epochs_completed": epochs_completed,
                "step": step,
                "words_done": words_done,
                "ckpt": ck_name,
                **(extra or {}),
                **({"prev": prev} if prev else {}),
            },
            f,
        )
    os.replace(tmp, state_path)
    keep = {ck_name}
    if prev:
        keep.add(prev["ckpt"])
    for entry in os.listdir(checkpoint_dir):
        if entry.startswith("ckpt-") and entry not in keep:
            shutil.rmtree(
                os.path.join(checkpoint_dir, entry), ignore_errors=True
            )


def _resolve_resume(checkpoint_dir: str) -> Optional[dict]:
    """Resume-state resolution shared by both fit loops: the newest
    committed checkpoint whose snapshot passes integrity verification
    (manifest sha256 + sizes), falling back to the previous committed
    record kept by the keep-last-2 retention. One clean log line per
    rejected candidate; ``CheckpointCorruptError`` when nothing
    verifies (never a silent from-scratch retrain)."""
    from glint_word2vec_tpu.utils.integrity import resolve_train_state

    resolved = resolve_train_state(checkpoint_dir)
    if resolved is None:
        return None
    state, _ = resolved
    return state


def _process_count() -> int:
    import jax

    return jax.process_count()


def _multiprocess_barrier(tag: str) -> None:
    """All-process rendezvous (replica-exchange checkpoints, ISSUE 15):
    a rank must not flip ``train_state.json`` while peers are still
    writing their shard blocks — the flip would commit a snapshot whose
    per-shard manifests don't all exist yet. No-op single-process."""
    import jax

    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("ckpt_" + tag)


def _ckpt_wait_timeout() -> Optional[float]:
    """Fit-exit barrier timeout for in-flight async checkpoint writes:
    a writer thread wedged on a dead filesystem must fail the run with
    a named job, not pin fit exit forever. Seconds;
    ``GLINT_CKPT_WAIT_TIMEOUT=0`` restores the unbounded wait."""
    raw = os.environ.get("GLINT_CKPT_WAIT_TIMEOUT", "900")
    try:
        t = float(raw)
    except ValueError:
        logger.warning(
            "GLINT_CKPT_WAIT_TIMEOUT=%r is not a number; using 900", raw
        )
        t = 900.0
    return t if t > 0 else None


def _checkpoint_tables(
    engine, obs_run, metrics, ck_path: str, ck_name: str, commit
) -> None:
    """Write one checkpoint without stalling the dispatch pipeline.

    Default (single-process): ``engine.save_async`` — the calling thread
    blocks only for the device->host snapshot copy (``ckpt_snapshot``
    span) and returns to dispatching; serialization, durability fsyncs,
    the atomic directory commit, and the ``commit`` callback (the
    ``train_state.json`` flip) all run on the engine's single writer
    thread (``ckpt_write`` span), strictly in that order, so a crash at
    any point leaves the previous committed checkpoint authoritative.
    ``GLINT_SYNC_CKPT=1`` (or multi-process) forces the fully blocking
    path. Either way the call-site duration is charged to the
    ``device_stall_seconds`` proxy — the wall-clock pause ``bench.py
    stall_overlap`` measures (async removes the write/fsync share of
    it, >80% at the benched config)."""
    t0 = time.time()
    if engine.async_saves_enabled():
        with obs_run.span("ckpt_snapshot", ckpt=ck_name):
            engine.save_async(ck_path, on_commit=commit)
    else:
        with obs_run.span("checkpoint_save", ckpt=ck_name):
            engine.save(ck_path)
            # Multi-process saves write disjoint shard files; the
            # barrier orders every rank's writes (and sidecar
            # manifests) before ANY rank's state flip makes the
            # snapshot authoritative.
            _multiprocess_barrier(ck_name)
            commit()
    metrics.record_stall(time.time() - t0)


def _save_diverged_snapshot(engine, checkpoint_dir, obs_run) -> None:
    """Canary abort tail shared by both fit loops: the event log is
    already flushed (ObsRun); leave a final table snapshot for the
    post-mortem WITHOUT flipping train_state.json — a resume must
    restart from the last healthy checkpoint, not the diverged tables."""
    if not checkpoint_dir:
        return
    ck = os.path.join(checkpoint_dir, "ckpt-diverged")
    with obs_run.span("checkpoint_save", ckpt="ckpt-diverged"):
        engine.save(ck)
    logger.error("canary abort: diverged tables saved to %s", ck)


class Word2Vec:
    """Skip-gram/negative-sampling estimator over a TPU mesh.

    Construct with a :class:`Word2VecParams`, keyword overrides, or use the
    reference-style fluent setters::

        model = (Word2Vec()
                 .set_vector_size(100)
                 .set_window_size(5)
                 .set_step_size(0.025)
                 .set_seed(1)
                 .fit(sentences))
    """

    def __init__(
        self,
        params: Optional[Word2VecParams] = None,
        mesh=None,
        obs=None,
        **overrides,
    ):
        self.params = (params or Word2VecParams()).replace(**overrides)
        self.mesh = mesh
        #: Optional obs.ObsConfig: run-scoped observability (event log,
        #: heartbeat, canary). Like ``mesh``, it is run config — never
        #: part of Word2VecParams or the saved model.
        self.obs = obs

    # Fluent setters (reference mllib:92-243 / python bindings :172-302).
    def _set(self, **kw) -> "Word2Vec":
        self.params = self.params.replace(**kw)
        return self

    def set_vector_size(self, v: int) -> "Word2Vec":
        return self._set(vector_size=v)

    def set_window_size(self, v: int) -> "Word2Vec":
        return self._set(window=v)

    def set_step_size(self, v: float) -> "Word2Vec":
        return self._set(step_size=v)

    def set_batch_size(self, v: int) -> "Word2Vec":
        return self._set(batch_size=v)

    def set_num_negatives(self, v: int) -> "Word2Vec":
        """Reference param ``n`` (negative samples per positive pair)."""
        return self._set(num_negatives=v)

    def set_subsample_ratio(self, v: float) -> "Word2Vec":
        return self._set(subsample_ratio=v)

    def set_min_count(self, v: int) -> "Word2Vec":
        return self._set(min_count=v)

    def set_num_iterations(self, v: int) -> "Word2Vec":
        return self._set(num_iterations=v)

    def set_max_sentence_length(self, v: int) -> "Word2Vec":
        return self._set(max_sentence_length=v)

    def set_seed(self, v: int) -> "Word2Vec":
        return self._set(seed=v)

    def set_num_partitions(self, v: int) -> "Word2Vec":
        """Data-parallel axis size (reference ``numPartitions``)."""
        return self._set(num_partitions=v)

    def set_num_shards(self, v: int) -> "Word2Vec":
        """Model-parallel axis size (reference ``numParameterServers``)."""
        return self._set(num_shards=v)

    def set_dtype(self, v: str) -> "Word2Vec":
        return self._set(dtype=v)

    def set_compute_dtype(self, v: str) -> "Word2Vec":
        """MXU operand dtype for the step's dense contractions ("float32"
        default, "bfloat16" = MXU-native fast path; f32 accumulation
        either way)."""
        return self._set(compute_dtype=v)

    def set_steps_per_call(self, v: int) -> "Word2Vec":
        return self._set(steps_per_call=v)

    def set_shared_negatives(self, v: int) -> "Word2Vec":
        """Shared noise-pool size per step (0 = per-pair reference
        semantics; see Word2VecParams.shared_negatives)."""
        return self._set(shared_negatives=v)

    def set_architecture(self, v: str) -> "Word2Vec":
        """"skipgram" (default) or "cbow" (see Word2VecParams)."""
        return self._set(architecture=v)

    def set_position_weights(self, v: bool) -> "Word2Vec":
        """CBOW's position weights (see Word2VecParams)."""
        return self._set(position_weights=v)

    def set_batch_packing(self, v: str) -> "Word2Vec":
        """Device-corpus dispatch shape: "dense" (the default — valid
        (center, context) pairs prefix-sum-compacted into dense
        fixed-shape pair batches on device before the update, so ~every
        dispatched FLOP is a useful pair) or "grid" (the legacy reference
        (batch, context) window grids — ~43% live lanes at window 5 —
        kept for A/B comparison and old mid-epoch grid checkpoints).
        See README "Dense pair packing"."""
        return self._set(batch_packing=v)

    def set_exchange(self, v: str) -> "Word2Vec":
        """Cross-replica reconciliation mode for multi-process runs
        (ISSUE 15): "none" = SPMD global mesh, "sparse" = touched-row
        delta exchange between data-parallel replicas, "dense" = full
        delta exchange on the same cadence (parity baseline). See
        README "Pod-scale training"."""
        return self._set(exchange=v)

    def set_exchange_capacity(self, v: int) -> "Word2Vec":
        """Fixed touched-row buffer capacity per exchange sync (0 =
        auto-sized from the dispatch-group pair budget, then adapted
        down from observed telemetry; nonzero pins it)."""
        return self._set(exchange_capacity=v)

    def set_exchange_wire(self, v: str) -> "Word2Vec":
        """Sparse exchange payload encoding (ISSUE 16): "fp32" (exact),
        "bf16", or "int8" (per-row maxabs scale with error-feedback
        residual carry). See README "Pod-scale training"."""
        return self._set(exchange_wire=v)

    def set_exchange_every(self, v: int) -> "Word2Vec":
        """Coalesce R dispatch groups into one exchange round (ISSUE
        16); 1 = sync every group."""
        return self._set(exchange_every=v)

    def set_exchange_topology(self, v: str) -> "Word2Vec":
        """Exchange sync topology (ISSUE 16): "flat" or "twolevel"
        (intra-node exact hop + leaders-only quantized inter-node
        hop; GLINT_RANKS_PER_NODE sets the node size)."""
        return self._set(exchange_topology=v)

    def set_exchange_shard(self, v: str) -> "Word2Vec":
        """Replica corpus sharding: "roundrobin" or "locality"
        (sentences clustered by rarest token to concentrate each
        replica's touched rows; ISSUE 16)."""
        return self._set(exchange_shard=v)

    def set_observability(self, obs) -> "Word2Vec":
        """Attach an :class:`obs.ObsConfig` for subsequent fits (event
        log, live heartbeat, status file, divergence canary)."""
        self.obs = obs
        return self

    # ------------------------------------------------------------------

    def _make_mesh(self, local: bool = False):
        from glint_word2vec_tpu.parallel.mesh import make_mesh

        if self.mesh is not None:
            return self.mesh
        p = self.params
        if local:
            # Replica-exchange mode (ISSUE 15): each process owns a
            # mesh over ITS devices only — cross-process traffic is the
            # host-level delta exchange, never an SPMD collective.
            import jax

            return make_mesh(
                p.num_partitions, p.num_shards,
                devices=jax.local_devices(),
            )
        return make_mesh(p.num_partitions, p.num_shards)

    def fit(
        self,
        sentences: Iterable[Sequence[str]],
        checkpoint_dir: Optional[str] = None,
        checkpoint_every_epochs: int = 1,
        stop_after_epochs: Optional[int] = None,
    ) -> "Word2VecModel":
        """Train on an iterable of tokenized sentences.

        The full reference ``fit`` path (mllib:310-439): vocab scan ->
        encode/chunk -> per-epoch subsample+window passes -> minibatched
        SGNS with the linear LR anneal (floor ``step_size * 1e-4``,
        mllib:405-413) -> fitted model.

        ``checkpoint_dir`` enables epoch-granular checkpoint/resume — a
        capability the reference lacks entirely (SURVEY.md §5 "no checkpoint
        mid-training"): after every ``checkpoint_every_epochs`` epochs the
        tables + progress counters are written, and a rerun of the same fit
        with the same directory resumes after the last completed epoch.
        ``stop_after_epochs`` ends the run early after that many epochs
        *this invocation* (train-in-slices operation; the LR schedule is
        unaffected because it depends only on global progress counters).
        """
        p = self.params
        if not isinstance(sentences, list):
            # Non-rewindable input: single-pass streaming scan+encode
            # into the flat representation (~4 bytes/kept word) instead
            # of materializing a Python sentence list (~15x the RAM).
            # Produces the same vocab/encoding as the list path below.
            from glint_word2vec_tpu.corpus.vocab import scan_and_encode_stream

            vocab, ids, offsets = scan_and_encode_stream(
                sentences, min_count=p.min_count,
                max_sentence_length=p.max_sentence_length,
            )
            return self._fit_flat(
                vocab, ids, offsets, checkpoint_dir,
                checkpoint_every_epochs, stop_after_epochs,
            )
        vocab = build_vocab(sentences, min_count=p.min_count)
        encoded = chunk_sentences(
            encode_sentences(sentences, vocab), p.max_sentence_length
        )
        lens = np.array([s.size for s in encoded], dtype=np.int64)
        if p.exchange != "none" and _process_count() > 1:
            ids = (
                np.concatenate(encoded).astype(np.int32, copy=False)
                if encoded else np.zeros(0, np.int32)
            )
            offsets = np.zeros(len(lens) + 1, np.int64)
            np.cumsum(lens, out=offsets[1:])
            return self._fit_replica_exchange(
                vocab, ids, offsets, checkpoint_dir,
                checkpoint_every_epochs, stop_after_epochs,
            )
        pc, local_batch, steps_per_epoch = self._multihost_plan(lens)
        if pc == 1 and self._device_corpus_eligible(int(lens.sum())):
            # encode_sentences already yields int32; copy=False avoids a
            # second full-corpus copy at peak host-memory time.
            ids = (
                np.concatenate(encoded).astype(np.int32, copy=False)
                if encoded else np.zeros(0, np.int32)
            )
            offsets = np.zeros(len(lens) + 1, np.int64)
            np.cumsum(lens, out=offsets[1:])
            return self._fit_corpus_resident(
                vocab, ids, offsets, checkpoint_dir,
                checkpoint_every_epochs, stop_after_epochs,
            )
        self._skipgram_for_host_batcher()
        if pc > 1:
            from glint_word2vec_tpu.parallel import distributed as dist

            encoded = dist.shard_sentences_for_process(encoded)
        batcher = SkipGramBatcher(
            encoded,
            vocab,
            batch_size=local_batch,
            window=p.window,
            subsample_ratio=p.subsample_ratio,
            seed=p.seed,
        )
        return self._fit_with_batcher(
            vocab, batcher, checkpoint_dir, checkpoint_every_epochs,
            stop_after_epochs, steps_per_epoch=steps_per_epoch,
        )

    def fit_file(
        self,
        path: str,
        lowercase: bool = False,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every_epochs: int = 1,
        stop_after_epochs: Optional[int] = None,
    ) -> "Word2VecModel":
        """Train directly from a text file (one sentence per line) with
        streaming ingestion: two passes over the file (vocab scan, then
        flat int32 encode), never materializing Python sentence objects —
        host memory is ~4 bytes/kept word. The scaling path for the
        Common-Crawl-class configs (BASELINE.json): the reference gets the
        same property from Spark RDD streaming; a plain Python list of
        sentences costs ~15x more RAM than the flat encoding."""
        from glint_word2vec_tpu.corpus.vocab import scan_and_encode_file

        p = self.params
        vocab, ids, offsets = scan_and_encode_file(
            path, min_count=p.min_count,
            max_sentence_length=p.max_sentence_length, lowercase=lowercase,
        )
        return self._fit_flat(
            vocab, ids, offsets, checkpoint_dir, checkpoint_every_epochs,
            stop_after_epochs,
        )

    def fit_stream(
        self,
        sentences: Iterable[Sequence[str]],
        publish_dir: Optional[str] = None,
        **stream_kw,
    ) -> "Word2VecModel":
        """Incremental training on an unbounded sentence stream (ISSUE
        10, the ISGNS construction arXiv:1704.03956): one look at each
        sentence, adaptive noise/subsample distributions recomputed
        from live counts on a cadence, online vocabulary growth onto
        the engine's spare extra rows, and — with ``publish_dir`` —
        committed model generations published for a serving fleet to
        hot-swap under load (streaming/publish.py).

        Returns the fitted model when the stream ends or a
        ``max_words``/``max_seconds`` bound trips. Cadence and capacity
        knobs are forwarded to
        :class:`glint_word2vec_tpu.streaming.trainer.StreamTrainer`."""
        from glint_word2vec_tpu.streaming.trainer import StreamTrainer

        return StreamTrainer(
            self, publish_dir=publish_dir, **stream_kw
        ).run(sentences)

    def _fit_flat(
        self,
        vocab: Vocabulary,
        ids: np.ndarray,
        offsets: np.ndarray,
        checkpoint_dir: Optional[str],
        checkpoint_every_epochs: int,
        stop_after_epochs: Optional[int],
    ) -> "Word2VecModel":
        """Train from the flat encoded corpus ``(ids, offsets)`` — the
        common tail of ``fit_file`` and streaming-``fit``: route to the
        device-resident scan when eligible, else shard across processes
        and run the host batcher pipeline."""
        p = self.params
        if p.exchange != "none" and _process_count() > 1:
            return self._fit_replica_exchange(
                vocab, ids, offsets, checkpoint_dir,
                checkpoint_every_epochs, stop_after_epochs,
            )
        pc, local_batch, steps_per_epoch = self._multihost_plan(np.diff(offsets))
        if pc == 1 and self._device_corpus_eligible(int(ids.size)):
            return self._fit_corpus_resident(
                vocab, ids, offsets, checkpoint_dir,
                checkpoint_every_epochs, stop_after_epochs,
            )
        self._skipgram_for_host_batcher()
        if pc > 1:
            from glint_word2vec_tpu.parallel import distributed as dist

            ids, offsets = dist.shard_flat_for_process(ids, offsets)
        batcher = SkipGramBatcher.from_flat(
            ids, offsets, vocab,
            batch_size=local_batch,
            window=p.window,
            subsample_ratio=p.subsample_ratio,
            seed=p.seed,
        )
        return self._fit_with_batcher(
            vocab, batcher, checkpoint_dir, checkpoint_every_epochs,
            stop_after_epochs, steps_per_epoch=steps_per_epoch,
        )

    def _fit_replica_exchange(
        self,
        vocab: Vocabulary,
        ids: np.ndarray,
        offsets: np.ndarray,
        checkpoint_dir: Optional[str],
        checkpoint_every_epochs: int,
        stop_after_epochs: Optional[int],
    ) -> "Word2VecModel":
        """Multi-process replica-exchange fit (ISSUE 15): every process
        takes its round-robin corpus shard, trains it on a LOCAL mesh,
        and reconciles tables with its peers through the touched-row
        delta exchange after every dispatch group
        (parallel/exchange.py) — no SPMD collective ever crosses
        processes, so cross-host bytes scale with rows touched instead
        of vocab size. Identical engine seeds give every replica the
        same initial tables; each sync leaves all replicas
        value-identical again."""
        from glint_word2vec_tpu.parallel import distributed as dist

        if self.params.exchange_shard == "locality":
            ids, offsets = dist.shard_flat_locality(ids, offsets)
        else:
            ids, offsets = dist.shard_flat_for_process(ids, offsets)
        # graftlint: ignore[sync-point] ids is host numpy here
        if not self._device_corpus_eligible(int(ids.size)):
            raise ValueError(
                "replica-exchange training needs the device-resident "
                "corpus path: this process's corpus shard exceeds the "
                "device corpus budget (GLINT_DEVICE_CORPUS_MAX_BYTES) "
                "or GLINT_HOST_BATCHER=1 is set"
            )
        return self._fit_corpus_resident(
            vocab, ids, offsets, checkpoint_dir,
            checkpoint_every_epochs, stop_after_epochs,
        )

    def _skipgram_for_host_batcher(self) -> None:
        """A fit that the corpus-resident path does not take goes to the
        host batcher, which builds skip-gram batches: a CBOW fit is
        refused there, not trained as something else or somewhere
        slower."""
        if self.params.architecture != "skipgram":
            raise ValueError(
                f"architecture={self.params.architecture!r} trains on the "
                "corpus-resident path only, which this fit does not take "
                "(one process; the corpus within GLINT_DEVICE_CORPUS_MAX_"
                "BYTES, 2 GiB by default; GLINT_HOST_BATCHER unset): "
                "there is no host-batcher CBOW"
            )

    def _device_corpus_eligible(self, corpus_words: int = 0) -> bool:
        """Whether the device-resident corpus path applies (to every
        model family: a subword fit adds its group table,
        :meth:`_center_groups`): the corpus
        fits the HBM budget reserved for it (GLINT_DEVICE_CORPUS_MAX_BYTES
        overrides the 2 GiB default; tables need the rest), and no env
        escape hatch. Frequency subsampling no longer disqualifies —
        the per-epoch compaction pass runs on device
        (ops/device_batching.subsample_compact) — but it doubles the HBM
        charge: the flat corpus, the compacted buffer, its per-position
        record (ops/device_batching.position_sentences) and the transient
        prefix sums hold ~16 bytes/word replicated per device, vs ~8
        bytes/word without subsampling (the corpus and its record).
        Single-process only — the caller checks process count."""
        raw_budget = os.environ.get("GLINT_DEVICE_CORPUS_MAX_BYTES")
        try:
            budget = int(raw_budget) if raw_budget is not None else 2 << 30
        except ValueError:
            logger.warning(
                "GLINT_DEVICE_CORPUS_MAX_BYTES=%r is not an integer; "
                "using the 2 GiB default", raw_budget,
            )
            budget = 2 << 30
        bytes_per_word = 16 if self.params.subsample_ratio > 0 else 8
        return (
            bytes_per_word * corpus_words <= budget
            # upload_corpus indexes the flat corpus with int32; an
            # oversized corpus routes to the host batcher, not an error.
            and corpus_words < 2**31
            and os.environ.get("GLINT_HOST_BATCHER", "0") != "1"
        )

    def _fit_corpus_resident(
        self,
        vocab: Vocabulary,
        ids: np.ndarray,
        offsets: np.ndarray,
        checkpoint_dir: Optional[str],
        checkpoint_every_epochs: int,
        stop_after_epochs: Optional[int],
    ) -> "Word2VecModel":
        """Training loop for the device-resident corpus path: the flat
        encoded corpus is uploaded to HBM once (EmbeddingEngine
        .upload_corpus) and every minibatch is assembled inside the
        jitted scan (ops/device_batching) — per-dispatch host->device
        traffic is scalars, and the host thread's only jobs are the LR
        schedule and metrics. With ``subsample_ratio > 0`` a per-epoch
        jitted pass subsample-compacts the corpus on device
        (EmbeddingEngine.compact_corpus); the host reads back one scalar
        (``n_kept``) plus the compacted sentence offsets per epoch to
        size the step loop and keep the pre-subsampling words_done
        accounting. Batch-for-batch the un-subsampled stream matches the
        host pipeline's packing, so quality gates and LR accounting
        match; the subsample/window-shrink RNG streams differ (device
        threefry), like the native C++ pass already differs from the
        Python fallback."""
        import jax

        p = self.params
        subsampling = p.subsample_ratio > 0
        logger.info(
            "vocab: %d words, %d train words (device-resident corpus%s)",
            vocab.size, vocab.train_words_count,
            ", on-device subsampling" if subsampling else "",
        )
        from glint_word2vec_tpu.ops.device_batching import (
            corpus_words_done,
            corpus_words_done_compacted,
        )

        replica_mode = p.exchange != "none" and jax.process_count() > 1
        mesh = self._make_mesh(local=replica_mode)
        if p.batch_size % mesh.shape["data"]:
            raise ValueError(
                f"batch_size ({p.batch_size}) must be divisible by the "
                f"data-axis size ({mesh.shape['data']})"
            )
        engine = self._make_engine(mesh, vocab)
        twc = vocab.train_words_count
        obs_run = start_run(
            self.obs, pipeline="device_corpus",
            total_epochs=p.num_iterations,
            total_words=p.num_iterations * twc, engine=engine,
        )
        try:
            with obs_run.span("upload_corpus", words=int(ids.shape[0])):
                engine.upload_corpus(ids, offsets)
                engine.upload_center_groups(self._center_groups())
            if subsampling:
                engine.set_keep_probs(
                    vocab.device_keep_probabilities(p.subsample_ratio)
                )
            N = int(ids.shape[0])
            B, spc = p.batch_size, p.steps_per_call
            total_words = p.num_iterations * twc + 1
            base_key = jax.random.PRNGKey(p.seed)
            step = 0
            start_epoch = 0
            # Dense pair packing (the default): dispatch prefix-sum-
            # compacted pair batches instead of half-masked window
            # grids. Pair slots per step cover ~B center positions in
            # EXPECTATION (corpus/batching.packed_pair_batch), so a
            # packed step trains the same effective synchronous batch
            # as a grid step — identical update dynamics/stability —
            # while spending ~zero dispatched lanes on masked padding
            # (each step is ~density x the grid step's FLOPs).
            packed = p.batch_packing == "dense"
            cbow = p.architecture == "cbow"
            # A CBOW step trains B positions, each with its bag: its
            # batch rows are positions and its advance is the static B.
            pair_batch = B if cbow else packed_pair_batch(
                B, p.window, mesh.shape["data"]
            )
            resume_position = 0
            # Grid-equivalent step counter: pins the packed path's
            # window-shrink draws to the position->draw mapping the grid
            # scan would use for this run, keeping the per-epoch valid-
            # pair multiset identical across the two modes.
            gstep = 0
            # Preemption drill / mid-epoch checkpoint test hook: stop the
            # packed run after this many dispatch groups, saving a
            # mid-epoch checkpoint carrying the consumed-position counter.
            stop_after_groups = os.environ.get(
                "GLINT_PACKED_STOP_AFTER_GROUPS"
            )
            stop_after_groups = (
                int(stop_after_groups) if stop_after_groups else None
            )
            packed_groups = packed_pairs = packed_slots = 0
            steps_run = 0  # steps the device ran, by what it wrote
            # distinct rows written (syn0, syn1), slabs moved (syn0, syn1);
            # a subword fit also: live group ids gathered, centres formed;
            # a CBOW fit: live bag slots, positions trained; a subword
            # CBOW fit, after those: live group ids gathered, span words
            # composed, input rows (EmbeddingEngine.train_steps_corpus_
            # packed)
            rows_written = np.zeros(9, np.int64)
            early_stop = False

            state_path = (
                os.path.join(checkpoint_dir, "train_state.json")
                if checkpoint_dir
                else None
            )
            resume_words = None
            state = _resolve_resume(checkpoint_dir) if state_path else None
            if state is not None:
                with obs_run.span("checkpoint_restore", ckpt=state["ckpt"]):
                    engine.load_tables(
                        os.path.join(checkpoint_dir, state["ckpt"])
                    )
                start_epoch = state["epochs_completed"]
                step = state["step"]
                # Packed states carry the mid-epoch consumed-position
                # counter and the epoch's grid-equivalent step base; a
                # grid-written state implies position 0 and gstep == step
                # (the grid step counter IS the grid-equivalent counter).
                # A MID-EPOCH state is only resumable in the dispatch
                # mode that wrote it: a cross-mode resume would silently
                # drop (or misread) the consumed-position counter and
                # re-train the epoch's consumed prefix on tables that
                # already hold its updates.
                state_packing = state.get("batch_packing", "grid")
                if (
                    int(state.get("position", 0)) > 0
                    and state_packing != p.batch_packing
                ):
                    raise ValueError(
                        f"mid-epoch checkpoint at {checkpoint_dir} was "
                        f"written with batch_packing="
                        f"{state_packing!r} (position "
                        f"{state['position']}); resume with the same "
                        "packing mode, or restart from an epoch-boundary "
                        "checkpoint"
                    )
                # position is 0 in every epoch-boundary state (both
                # modes record it uniformly); a nonzero value already
                # passed the same-mode check above.
                resume_position = int(state.get("position", 0))
                gstep = int(state.get("gstep", state["step"]))
                resume_words = int(state.get("words_done", start_epoch * twc))
                logger.info(
                    "resuming after epoch %d (step %d, position %d)",
                    start_epoch, step, resume_position,
                )
            metrics = TrainingMetrics(
                base_words=(
                    resume_words if resume_words is not None
                    else start_epoch * twc
                )
            )
            obs_run.attach_metrics(metrics)
            # Replica exchange (ISSUE 15): constructed AFTER any resume
            # restore so the reconciliation base snapshots the restored
            # tables. Per-rank key decorrelation folds the process rank
            # into the step-key stream (table INIT stays seed-identical
            # across replicas — reconciliation depends on it); the save
            # split makes every rank checkpoint only its own row block.
            exchanger = None
            if p.exchange != "none":
                from glint_word2vec_tpu.parallel import exchange as exmod

                transport = (
                    exmod.ProcessTransport()
                    if jax.process_count() > 1 else exmod.NullTransport()
                )
                if transport.world > 1:
                    if stop_after_groups is not None:
                        # The stop-early drill breaks the lockstep
                        # protocol mid-epoch: peers would wait in the
                        # exchange collective forever. Fail loudly
                        # instead of deadlocking the gang.
                        raise ValueError(
                            "GLINT_PACKED_STOP_AFTER_GROUPS is not "
                            "supported with multi-process replica "
                            "exchange (peers would deadlock in the "
                            "exchange collective)"
                        )
                    engine.set_save_split(transport.rank, transport.world)
                    base_key = jax.random.fold_in(
                        base_key, transport.rank
                    )
                else:
                    logger.info(
                        "replica exchange on a single process: the "
                        "reconciliation protocol runs for parity/"
                        "telemetry (one extra table pair of HBM, one "
                        "sync per dispatch group) with no cross-rank "
                        "traffic"
                    )
                exchanger = exmod.ReplicaExchanger(
                    engine, mode=p.exchange,
                    capacity=p.exchange_capacity or None,
                    transport=transport,
                    pair_batch=pair_batch if packed else B,
                    steps_per_call=spc,
                    wire=p.exchange_wire,
                    every=p.exchange_every,
                    topology=p.exchange_topology,
                )
            # Mutated by _harvest_packed (declared before the epoch loop
            # so the closure binds the method scope, not a loop body).
            n_pos, offsets_c, epoch, epoch_wd = N, None, start_epoch, 0

            def _prefetch_next_compact(next_epoch: int) -> None:
                # ISSUE 5 prefetch overlap: DISPATCH (don't adopt) the
                # next epoch's subsample-compact pass while the current
                # epoch's tail group is still executing; the next
                # compact_corpus call adopts the bitwise-identical
                # buffers without re-running the pass. Skipped when the
                # run won't reach that epoch (the transient buffer would
                # just burn HBM). GLINT_NO_COMPACT_PREFETCH=1 restores
                # the serialized epoch boundary (debug escape hatch).
                if not subsampling or next_epoch >= p.num_iterations:
                    return
                if (
                    stop_after_epochs is not None
                    and (next_epoch - start_epoch) >= stop_after_epochs
                ):
                    return
                if os.environ.get("GLINT_NO_COMPACT_PREFETCH", "0") == "1":
                    return
                with obs_run.span("subsample_prefetch", epoch=next_epoch):
                    engine.prefetch_compact_corpus(
                        jax.random.fold_in(base_key, next_epoch)
                    )

            def _harvest_packed(pend) -> int:
                # Convert ONE dispatched packed group's result scalars
                # and fold them into the step/LR/canary accounting;
                # returns the group's final consumed position. Under the
                # deferred schedule the NEXT group is already dispatched
                # when this blocks, so the device never idles behind the
                # conversion — and the metric/canary view lags the
                # device by exactly one dispatch group (documented;
                # tests/test_stall.py pins it). A group dispatched
                # entirely past the corpus end (the deferred schedule's
                # one possible phantom tail group) records nothing and
                # does NOT advance the step counter: the device ran none
                # of its steps (the scan stops at the corpus end, as it
                # does in the last group's tail), and the epoch-end
                # ``dstep = step`` reset drops its fold_in keys so the
                # next epoch's key schedule matches the synchronous loop
                # bitwise.
                nonlocal step, epoch_wd
                nonlocal packed_pairs, packed_slots, packed_groups, steps_run
                (losses, pair_counts, pos_ends, alphas_d, written,
                 start_h) = pend
                # The three children part the host BLOCKED on the device
                # from the read-back and from the host's own accounting
                # (benchmark/fit_trace.py files the device's idle under
                # the wait and under the rest). They are no ledger phases:
                # readback_harvest charges the whole.
                with metrics.timing("step"), obs_run.span(
                    "readback_harvest", packed=True
                ) as hspan:
                    with obs_run.span("harvest_wait"):
                        jax.block_until_ready(pend[:5])
                    with obs_run.span("harvest_convert"):
                        pos_ends_h = np.asarray(pos_ends)
                        pairs_h = np.asarray(pair_counts)
                        alphas_h = np.asarray(alphas_d)
                        written_h = np.asarray(written)
                    starts = np.concatenate(([start_h], pos_ends_h[:-1]))
                    # Live steps form a prefix: positions only ever
                    # advance, so the first start past the corpus end
                    # makes all later steps no-ops.
                    n_real = int((starts < n_pos).sum())
                    # The scan stops at the corpus end and leaves alpha 0
                    # where it ran no step; a step that ran wrote at least
                    # the rule's floor.
                    ran = int((alphas_h > 0).sum())
                    hspan.update(n=n_real, ran=ran)
                    with obs_run.span("harvest_account", n=n_real):
                        for i in range(n_real):
                            step += 1
                            end_pos = int(min(pos_ends_h[i], n_pos))
                            if subsampling:
                                done = corpus_words_done_compacted(
                                    offsets, offsets_c, end_pos, n_pos
                                )
                            else:
                                done = corpus_words_done(offsets, end_pos)
                            epoch_wd = epoch * twc + done
                            # losses[i] stays a device value (record_step
                            # reads it at log points only)
                            metrics.record_step(
                                int(epoch_wd), loss=losses[i],
                                alpha=float(alphas_h[i]),
                            )
                        obs_run.observe_losses(
                            step - n_real, losses, n_real
                        )
                if n_real:
                    obs_run.update(
                        step=step, words_done=int(epoch_wd),
                        alpha=float(alphas_h[n_real - 1]),
                    )
                    step += spc - n_real  # tail no-ops consumed keys
                packed_pairs += int(pairs_h[:n_real].sum())
                packed_slots += n_real * pair_batch
                steps_run += ran
                written_h = written_h[:n_real].sum(axis=0)
                rows_written[:written_h.size] += written_h
                packed_groups += 1
                return int(pos_ends_h[-1])

            for epoch in range(start_epoch, p.num_iterations):
                obs_run.update(epoch=epoch)
                if subsampling:
                    # The epoch's subsample draws are keyed by epoch alone
                    # (the reference reseeds per iteration, mllib:371-373),
                    # so a resumed run recompacts epoch e to the identical
                    # buffers — no compaction state needs checkpointing.
                    # The blocking n_kept sync is charged to the stall
                    # proxy; with the pass prefetched during the previous
                    # epoch's tail it is near zero.
                    with metrics.timing("step"), metrics.stall_timing(), \
                            obs_run.span("subsample_compact", epoch=epoch):
                        n_pos = engine.compact_corpus(
                            jax.random.fold_in(base_key, epoch)
                        )
                    offsets_c = engine.compacted_offsets()
                else:
                    n_pos, offsets_c = N, None
                steps_per_epoch = max(1, -(-n_pos // B))
                groups = max(1, -(-steps_per_epoch // spc))
                if packed:
                    pos = resume_position
                    resume_position = 0
                    epoch_wd = epoch * twc
                    # Deferred readbacks (ISSUE 5): the dispatch of group
                    # g+1 chains on group g's final position as a DEVICE
                    # scalar (no host sync), and group g's scalars are
                    # harvested while g+1 executes — the per-group host
                    # conversion stops serializing the device. Identical
                    # dispatch arguments to the synchronous schedule
                    # except one possible zero-pair phantom tail group
                    # per epoch (rolled out of the key schedule at epoch
                    # end), so tables are bitwise-identical either way
                    # (tests/test_stall.py). GLINT_SYNC_READBACK=1 — and
                    # the stop-after-groups drill, which must know each
                    # group's end position before deciding to dispatch —
                    # force the synchronous schedule.
                    defer = (
                        stop_after_groups is None
                        and os.environ.get("GLINT_SYNC_READBACK", "0")
                        != "1"
                        # Exchange rounds are reconciliation barriers:
                        # every group ends with a host-level sync, so
                        # the one-group-deferred schedule has nothing
                        # to overlap.
                        and exchanger is None
                    )
                    gang_live = (
                        exchanger is not None
                        and exchanger.transport.world > 1
                    )
                    pending = None
                    next_start = pos  # host int now, device scalar later
                    dstep = step  # dispatch-time step0 (runs ahead)
                    while pos < n_pos:
                        faults.fire("worker.step")
                        with metrics.timing("step"), obs_run.span(
                            "device_steps", step0=dstep, n=spc, packed=True,
                            epoch=epoch,
                        ):
                            (
                                losses, pair_counts, pos_ends, alphas_d,
                                written,
                            ) = engine.train_steps_corpus_packed(
                                next_start, pair_batch, p.window, B,
                                base_key, spc, step0=dstep,
                                grid_step0=gstep, step_size=p.step_size,
                                total_words=total_words,
                                words_base=epoch * twc,
                            )
                        dstep += spc
                        next_start = pos_ends[-1]  # device scalar chain
                        new_pend = [
                            losses, pair_counts, pos_ends, alphas_d,
                            written, pos,
                        ]
                        if pending is not None:
                            # Harvest g-1 while g runs; its end position
                            # is g's true start for the live-step count.
                            pos = _harvest_packed(pending)
                            new_pend[5] = pos
                        pending = new_pend
                        if not defer:
                            pos = _harvest_packed(pending)
                            pending = None
                            next_start = pos
                            if exchanger is not None:
                                with metrics.timing("step"), obs_run.span(
                                    "exchange_sync", packed=True
                                ):
                                    gang_live = exchanger.group_end(
                                        live=True, done=pos >= n_pos
                                    )
                            if (
                                stop_after_groups is not None
                                and packed_groups >= stop_after_groups
                            ):
                                early_stop = True
                                break
                    if not early_stop:
                        # Enqueue the next epoch's compaction BEFORE
                        # draining: it lands behind the tail group in the
                        # device queue and runs while the host drains.
                        _prefetch_next_compact(epoch + 1)
                    if pending is not None:
                        pos = _harvest_packed(pending)
                        pending = None
                    # Lockstep fillers (replica exchange): a drained
                    # rank keeps answering the gang's exchange rounds
                    # with empty payloads until EVERY rank reports done
                    # — no peer is ever left waiting in a collective.
                    if exchanger is not None and not early_stop:
                        while gang_live:
                            with metrics.timing("step"), obs_run.span(
                                "exchange_sync", filler=True
                            ):
                                gang_live = exchanger.group_end(
                                    live=False, done=True
                                )
                        exchanger.epoch_reset()
                    # Drop the phantom tail group's keys (if any) so the
                    # next epoch's step0 matches the synchronous loop.
                    dstep = step
                    if early_stop:
                        if state_path:
                            ck_name = f"ckpt-e{epoch}-p{pos}"
                            _checkpoint_tables(
                                engine, obs_run, metrics,
                                os.path.join(checkpoint_dir, ck_name),
                                ck_name,
                                functools.partial(
                                    _flip_checkpoint_state,
                                    checkpoint_dir, state_path, ck_name,
                                    epochs_completed=epoch, step=step,
                                    words_done=int(epoch_wd),
                                    extra={
                                        "position": pos, "gstep": gstep,
                                        "batch_packing": "dense",
                                    },
                                ),
                            )
                        logger.info(
                            "stopping mid-epoch %d at position %d "
                            "(GLINT_PACKED_STOP_AFTER_GROUPS)", epoch, pos,
                        )
                        break
                    # Advance the grid-equivalent counter exactly as the
                    # grid loop advances its step counter for this epoch
                    # (spc keys per group, tail no-ops included).
                    gstep += groups * spc
                else:
                    gang_live = (
                        exchanger is not None
                        and exchanger.transport.world > 1
                    )
                    for g in range(groups):
                        faults.fire("worker.step")
                        start_pos = g * spc * B
                        with metrics.timing("host"), obs_run.span(
                            "host_batch", epoch=epoch, group=g
                        ):
                            # LR anneal: the host batcher's
                            # pre-subsampling words_done accounting —
                            # from the original offsets alone, or looked
                            # up through the epoch's compacted offsets
                            # when subsampling.
                            alphas = np.empty(spc, np.float32)
                            wds = np.empty(spc, np.int64)
                            for j in range(spc):
                                end_pos = min(start_pos + (j + 1) * B, n_pos)
                                if subsampling:
                                    done = corpus_words_done_compacted(
                                        offsets, offsets_c, end_pos, n_pos
                                    )
                                else:
                                    done = corpus_words_done(
                                        offsets, end_pos
                                    )
                                wd = epoch * twc + done
                                wds[j] = wd
                                alphas[j] = max(
                                    p.step_size * (1 - wd / total_words),
                                    p.step_size * 1e-4,
                                )
                        # An epoch subsampled to nothing dispatches its
                        # one no-op group but records no steps — the host
                        # batcher likewise yields no batches then.
                        n_real = min(
                            spc, max(0, -(-(n_pos - start_pos) // B))
                        )
                        with metrics.timing("step"), obs_run.span(
                            "device_steps", step0=step, n=n_real
                        ):
                            losses = engine.train_steps_corpus(
                                start_pos, B, p.window, base_key, alphas,
                                step,
                            )
                            for i in range(n_real):
                                step += 1
                                metrics.record_step(
                                    int(wds[i]), loss=losses[i],
                                    alpha=float(alphas[i]),
                                )
                            # Inside the step bucket: the canary's
                            # periodic loss sync waits on the device, and
                            # device waits outside both buckets would
                            # skew host_frac.
                            obs_run.observe_losses(
                                step - n_real, losses, n_real
                            )
                        if n_real:
                            obs_run.update(
                                step=step, words_done=int(wds[n_real - 1]),
                                alpha=float(alphas[n_real - 1]),
                            )
                        step += spc - n_real  # tail no-ops consumed keys
                        if exchanger is not None:
                            with metrics.timing("step"), obs_run.span(
                                "exchange_sync"
                            ):
                                gang_live = exchanger.group_end(
                                    live=True, done=(g == groups - 1)
                                )
                    if exchanger is not None:
                        # Lockstep fillers: see the packed branch.
                        while gang_live:
                            with metrics.timing("step"), obs_run.span(
                                "exchange_sync", filler=True
                            ):
                                gang_live = exchanger.group_end(
                                    live=False, done=True
                                )
                        exchanger.epoch_reset()
                    gstep = step
                    # Grid dispatches are asynchronous: the tail group is
                    # still executing here, so the next epoch's
                    # compaction queues right behind it.
                    _prefetch_next_compact(epoch + 1)
                stopping = (
                    stop_after_epochs is not None
                    and (epoch + 1 - start_epoch) >= stop_after_epochs
                )
                if state_path and (
                    stopping
                    or (epoch + 1) % max(checkpoint_every_epochs, 1) == 0
                ):
                    if exchanger is not None:
                        # Drain the error-feedback carry through one
                        # exact wire round (no-op unless the int8 wire
                        # accumulated one) so a resume from this
                        # checkpoint replays bitwise against the
                        # uninterrupted run. Config-gated on every
                        # rank identically — collective-safe.
                        with obs_run.span("exchange_flush"):
                            exchanger.flush()
                    ck_name = f"ckpt-{epoch + 1}"
                    _checkpoint_tables(
                        engine, obs_run, metrics,
                        os.path.join(checkpoint_dir, ck_name), ck_name,
                        functools.partial(
                            _flip_checkpoint_state, checkpoint_dir,
                            state_path, ck_name,
                            epochs_completed=epoch + 1, step=step,
                            words_done=(epoch + 1) * twc,
                            # Uniform state record for BOTH dispatch
                            # modes (the grid-only special case is
                            # gone): epoch boundaries always carry
                            # position 0, the grid-equivalent step
                            # base, and the mode that wrote them.
                            extra={
                                "position": 0, "gstep": gstep,
                                "batch_packing": p.batch_packing,
                                # Exchange wire config at write time:
                                # a resumed run replays bitwise only
                                # under the same (wire, every) cell
                                # (the flush above zeroed the carry).
                                "exchange_wire": p.exchange_wire,
                                "exchange_every": p.exchange_every,
                            },
                        ),
                    )
                if stopping:
                    logger.info("stopping early after epoch %d", epoch + 1)
                    break
            # Fit-exit barrier: the fit must not return (and the model
            # must not be saved over) while a snapshot write is in
            # flight; a failed async write surfaces HERE, loudly — and a
            # HUNG writer raises after the bounded wait instead of
            # pinning fit exit forever (GLINT_CKPT_WAIT_TIMEOUT).
            engine.wait_pending_saves(timeout=_ckpt_wait_timeout())
            position_table = engine.position_table_stats()
            if position_table:
                obs_run.note_end(position_table=position_table)
        except TrainingDiverged:
            engine.wait_pending_saves(
                reraise=False, timeout=_ckpt_wait_timeout()
            )
            _save_diverged_snapshot(engine, checkpoint_dir, obs_run)
            raise
        except BaseException:
            engine.wait_pending_saves(
                reraise=False, timeout=_ckpt_wait_timeout()
            )
            obs_run.close(failed=True)
            raise
        finally:
            obs_run.close()
        logger.info("training done: %s", metrics.summary())
        model = self._make_model(vocab, engine)
        model.training_metrics = {
            **metrics.summary(), "pipeline": "device_corpus",
        }
        if position_table:
            model.training_metrics["position_table"] = position_table
        # Step-time attribution (ISSUE 8): where the fit thread's wall
        # went, by phase — the breakdown that replaces eyeballing the
        # single device_stall_seconds proxy. None when obs is off.
        steptime = obs_run.steptime_totals()
        if steptime:
            model.training_metrics["steptime"] = steptime
        model.training_metrics["batch_packing"] = p.batch_packing
        model.training_metrics["step_body"] = engine.step_body
        if exchanger is not None:
            model.training_metrics["exchange_mode"] = p.exchange
            model.training_metrics["exchange_wire"] = p.exchange_wire
            model.training_metrics["exchange_every"] = p.exchange_every
            model.training_metrics["exchange_topology"] = p.exchange_topology
            model.training_metrics["exchange"] = engine.exchange_stats()
        if packed and packed_slots:
            # Packed fill = live pairs / dispatched pair slots — the
            # effective mask density of the packed dispatches (the grid
            # path runs ~0.43 at window 5; the CI smoke job gates >= 0.9).
            # A CBOW step's slots are positions: positions trained over
            # position slots (packed_pairs still counts the live bag
            # slots, the rows the bags gathered).
            model.training_metrics.update(
                steps_dispatched=packed_groups * spc,
                steps_run=steps_run,
                packed_pairs=packed_pairs,
                packed_mask_density=round(
                    (rows_written[5] if cbow else packed_pairs)
                    / packed_slots, 4),
                exchange_bytes_per_step=engine.packed_exchange_bytes(
                    pair_batch, p.window),
                exchange_send_bytes_per_step=(
                    engine.packed_exchange_send_bytes(pair_batch, p.window)),
            )
            # Live steps, and the update slots one hands the scatters.
            steps = packed_slots // pair_batch
            slots = engine.packed_scatter_slots(pair_batch, p.window)
            if cbow and rows_written[5]:
                # Words a bag is the mean of: live bag slots over the
                # positions that trained.
                model.training_metrics.update(
                    cbow_rows_per_bag=round(
                        rows_written[4] / rows_written[5], 4),
                )
                if rows_written[7]:
                    # A subword fit: rows a composed word is the sum of
                    # (live group ids over the span words composed), the
                    # rows a bag's mean is over (fastText's input.size(),
                    # a position trained), the group rows a step gathered,
                    # each once for all the bags it is in, and how many
                    # bags read a gathered row.
                    model.training_metrics.update(
                        subword_rows_per_center=round(
                            rows_written[6] / rows_written[7], 4),
                        cbow_input_rows_per_bag=round(
                            rows_written[8] / rows_written[5], 4),
                        subword_rows_per_step=round(
                            rows_written[6] * pair_batch / packed_slots, 4),
                        cbow_span_reuse=round(
                            rows_written[8] / rows_written[6], 4),
                    )
                else:
                    # Word level: a span word is one row, gathered once a
                    # step (``syn0``'s slots), and a bag's mean is over
                    # its live slots.
                    model.training_metrics.update(
                        cbow_span_reuse=round(
                            rows_written[4] / (steps * slots[0]), 4),
                    )
            elif rows_written[5]:
                # Rows a subword centre is the mean of: live group ids the
                # packed steps gathered over the centres they formed.
                model.training_metrics.update(
                    subword_rows_per_center=round(
                        rows_written[4] / rows_written[5], 4),
                )
            model.training_metrics.update(
                scatter_summary(rows_written[:4], steps, slots)
            )
        return model

    # -- multi-host helpers (SURVEY.md §2.3 DP row; VERDICT.md missing #1) --

    def _multihost_plan(self, sentence_lengths: np.ndarray):
        """(process_count, local_batch_size, steps_per_epoch) for this run.

        Multi-host contract (shared by fit and fit_file): every process
        reads the same corpus (the shared-filesystem contract, like the
        reference's HDFS corpus), builds the identical global vocab with
        zero communication, and materializes only its round-robin shard
        (Client.runWithWord2VecMatrixOnSpark's partition placement,
        mllib:345,354-362). The per-epoch step count is fixed up front from
        the max shard word count so every process dispatches in lockstep
        (SPMD collectives deadlock otherwise). Single process returns
        (1, batch_size, None).
        """
        import jax

        pc = jax.process_count()
        if pc <= 1:
            return 1, self.params.batch_size, None
        local_batch = self._local_batch_size(pc)
        return pc, local_batch, self._steps_per_epoch(
            sentence_lengths, pc, local_batch
        )

    def _local_batch_size(self, pc: int) -> int:
        """Per-process rows of the global batch (each host feeds only the
        data-axis rows its own devices hold)."""
        p = self.params
        if p.batch_size % pc:
            raise ValueError(
                f"batch_size ({p.batch_size}) must be divisible by the "
                f"process count ({pc}) for multi-host training"
            )
        return p.batch_size // pc

    @staticmethod
    def _steps_per_epoch(
        sentence_lengths: np.ndarray, pc: int, local_batch: int
    ) -> int:
        """Agreed per-epoch step count: enough for the wordiest shard.

        Computable identically on every host with no communication (see
        distributed.per_process_word_counts). Subsampling only *removes*
        center positions, so this is always an upper bound; short hosts pad
        zero-mask batches up to it.
        """
        from glint_word2vec_tpu.parallel import distributed as dist

        counts = dist.per_process_word_counts(sentence_lengths, pc)
        return max(1, int(-(-int(counts.max()) // local_batch)))

    def _fit_with_batcher(
        self,
        vocab: Vocabulary,
        batcher: SkipGramBatcher,
        checkpoint_dir: Optional[str],
        checkpoint_every_epochs: int,
        stop_after_epochs: Optional[int],
        steps_per_epoch: Optional[int] = None,
    ) -> "Word2VecModel":
        """Shared training loop. ``steps_per_epoch`` (multi-host only) fixes
        the number of steps every process dispatches per epoch; None (single
        process) runs the batcher to exhaustion."""
        import jax

        p = self.params
        pc = jax.process_count()
        if p.batch_packing == "dense":
            # Dense packing is the default but applies only to the
            # device-resident corpus path; host-batcher routes
            # (multi-process, HBM budget, GLINT_HOST_BATCHER, subword
            # grouping) always build grid-shaped batches. One info line,
            # not a warning — the default config lands here legitimately.
            logger.info(
                "host-batcher route: training with grid-shaped batches "
                "(dense pair packing applies to the device-resident "
                "corpus path only)"
            )
        logger.info(
            "vocab: %d words, %d train words", vocab.size, vocab.train_words_count
        )
        mesh = self._make_mesh()
        if p.batch_size % mesh.shape["data"]:
            raise ValueError(
                f"batch_size ({p.batch_size}) must be divisible by the "
                f"data-axis size ({mesh.shape['data']})"
            )
        if pc > 1 and mesh.shape["data"] % pc:
            raise ValueError(
                f"data-axis size ({mesh.shape['data']}) must be a multiple "
                f"of the process count ({pc}) so each host's devices form "
                "whole data rows (set num_partitions accordingly)"
            )
        engine = self._make_engine(mesh, vocab)
        obs_run = start_run(
            self.obs, pipeline="host", total_epochs=p.num_iterations,
            total_words=p.num_iterations * vocab.train_words_count,
            engine=engine,
        )
        try:
            # LR schedule denominator: iterations * total train words + 1
            # (reference ``totalWordsCount``, mllib:405-410).
            total_words = p.num_iterations * vocab.train_words_count + 1
            base_key = jax.random.PRNGKey(p.seed)
            step = 0
            start_epoch = 0

            state_path = (
                os.path.join(checkpoint_dir, "train_state.json")
                if checkpoint_dir
                else None
            )
            # Integrity-verified resolution with fallback to the
            # previous committed snapshot (keep-last-2); legacy records
            # without a "ckpt" key come back as-is for the legacy path.
            state = _resolve_resume(checkpoint_dir) if state_path else None
            if state is not None:
                with obs_run.span(
                    "checkpoint_restore", ckpt=state.get("ckpt", "ckpt")
                ):
                    if "ckpt" in state:
                        engine.load_tables(
                            os.path.join(checkpoint_dir, state["ckpt"])
                        )
                    else:  # legacy single-file layout
                        engine.set_tables(
                            np.load(
                                os.path.join(checkpoint_dir, "ckpt", "syn0.npy")
                            ),
                            np.load(
                                os.path.join(checkpoint_dir, "ckpt", "syn1.npy")
                            ),
                        )
                start_epoch = state["epochs_completed"]
                step = state["step"]
                batcher.words_done = state["words_done"]
                logger.info(
                    "resuming after epoch %d (step %d)", start_epoch, step
                )
            # Metrics count only THIS invocation's work; on resume the restored
            # global counter must not inflate throughput numbers.
            metrics = TrainingMetrics(base_words=batcher.words_done)
            obs_run.attach_metrics(metrics)

            def save_checkpoint(epochs_completed: int) -> None:
                # Atomic: the sharded table snapshot lands in a fresh directory
                # first; state.json (atomic rename) flips to it last, so a crash
                # mid-write can never yield a state file pointing at mismatched
                # or partial tables. Older snapshot dirs are pruned after.
                # Single-process: the whole sequence runs on the engine's
                # background writer thread (non-blocking checkpointing,
                # ISSUE 5) — the fit loop keeps dispatching.
                # Multi-host: every process writes its own table shards
                # (engine.save, blocking — the barrier needs them on
                # disk), then a barrier ensures all shards are written
                # before process 0 alone flips state.json and prunes —
                # per-host counters can diverge only by padding, and a
                # lone writer keeps the flip atomic.
                ck_name = f"ckpt-{epochs_completed}"
                # words_done feeds the resumed run's metrics base and the
                # single-host LR accounting; under the multi-host schedule
                # the global pro-rata count is the coherent value (the
                # local batcher count is per-shard and would mix units).
                wd = (
                    batcher.words_done
                    if steps_per_epoch is None
                    else epochs_completed * vocab.train_words_count
                )
                if pc == 1:
                    _checkpoint_tables(
                        engine, obs_run, metrics,
                        os.path.join(checkpoint_dir, ck_name), ck_name,
                        functools.partial(
                            _flip_checkpoint_state, checkpoint_dir,
                            state_path, ck_name,
                            epochs_completed=epochs_completed, step=step,
                            words_done=wd,
                        ),
                    )
                    return
                with obs_run.span("checkpoint_save", ckpt=ck_name):
                    engine.save(os.path.join(checkpoint_dir, ck_name))
                if pc > 1:
                    from jax.experimental import multihost_utils

                    multihost_utils.sync_global_devices(
                        f"glint_w2v_ckpt_{epochs_completed}"
                    )
                if jax.process_index() == 0:
                    _flip_checkpoint_state(
                        checkpoint_dir, state_path, ck_name,
                        epochs_completed=epochs_completed, step=step,
                        words_done=wd,
                    )
                if pc > 1:
                    from jax.experimental import multihost_utils

                    multihost_utils.sync_global_devices(
                        f"glint_w2v_ckpt_done_{epochs_completed}"
                    )

            spc = p.steps_per_call
            twc = vocab.train_words_count
            # Multi-host: steps_per_epoch fixes the dispatch count; groups are
            # the scan-length quantized version of it.
            forced_groups = (
                None if steps_per_epoch is None
                else max(1, -(-steps_per_epoch // spc))
            )

            def _zero_group() -> BatchGroup:
                # Lockstep padding group: exactly spc zero-mask batches
                # (the scan length every host dispatches) so batch
                # stacks, alphas, and PRNG key advancement stay in
                # multi-host lockstep; excluded from metrics (n_real=0).
                B, C = batcher.batch_size, context_width(batcher.window)
                return BatchGroup(
                    centers=np.zeros((spc, B), np.int32),
                    contexts=np.zeros((spc, B, C), np.int32),
                    mask=np.zeros((spc, B, C), np.float32),
                    words_done=[batcher.words_done] * spc,
                    n_real=0,
                )

            def _harvest_host(pend) -> None:
                # Deferred loss sync (ISSUE 5): group g's records and
                # canary check run after group g+1 is dispatched, so the
                # periodic loss sync they force waits on a device that
                # already has the next group queued behind it — the
                # metric/canary view lags the device by exactly one
                # dispatch group. The dispatch schedule itself is
                # untouched (records only), so tables are unaffected.
                losses, wds_l, alphas_l, n_real, step_base = pend
                if not n_real:
                    return
                with metrics.timing("step"), obs_run.span(
                    "readback_harvest", step0=step_base, n=n_real
                ):
                    for i in range(n_real):
                        metrics.record_step(
                            wds_l[i], loss=losses[i], alpha=alphas_l[i]
                        )
                    obs_run.observe_losses(step_base, losses, n_real)
                obs_run.update(
                    step=step_base + n_real,
                    words_done=int(wds_l[n_real - 1]),
                    alpha=float(alphas_l[n_real - 1]),
                )

            def _sched_alpha(idx_in_epoch: int, epoch: int) -> tuple:
                # Deterministic global LR schedule for multi-host lockstep:
                # every process must compute the identical alpha without
                # exchanging its (slightly different) local word counts. The
                # epoch's words are attributed pro-rata over its agreed step
                # count — the same linear anneal as the reference's global
                # wordCount-driven schedule (mllib:405-413), quantized to steps.
                frac = min((idx_in_epoch + 1) / steps_per_epoch, 1.0)
                wd = epoch * twc + frac * twc
                return (
                    max(p.step_size * (1 - wd / total_words), p.step_size * 1e-4),
                    int(wd),
                )

            for epoch in range(start_epoch, p.num_iterations):
                obs_run.update(epoch=epoch)
                # Group-granular producer pipeline: windowing, batch
                # stacking, and tail padding ALL run on a background
                # thread (corpus/batching.group_batches under
                # utils/prefetch, depth 2 dispatch groups), so the
                # training thread's per-group host work collapses to one
                # queue pop + the LR schedule. The pop's wait time is
                # charged to the device_stall_seconds proxy — if the
                # producer falls behind the device, it shows up there.
                it = prefetch(
                    group_batches(batcher.epoch(epoch), spc), depth=2
                )
                g = 0
                pending = None  # previous group's deferred loss records
                while True:
                    if forced_groups is not None and g >= forced_groups:
                        if next(it, None) is not None:
                            raise RuntimeError(
                                "internal error: local shard produced more "
                                "batches than the agreed per-epoch step count"
                            )
                        break
                    faults.fire("worker.step")
                    with metrics.timing("host"), metrics.stall_timing(), \
                            obs_run.span("host_batch", epoch=epoch,
                                         group=g):
                        grp = next(it, None)
                    pad_only = False
                    if grp is None:
                        if forced_groups is None:
                            break
                        # This host's shard is exhausted but other hosts
                        # still have batches — keep dispatching zero-mask
                        # groups up to the agreed count (see _zero_group).
                        grp = _zero_group()
                        pad_only = True
                    n_real = 0 if pad_only else grp.n_real
                    if steps_per_epoch is None:
                        wds = list(grp.words_done)
                        alphas = [
                            max(
                                p.step_size * (1 - wd / total_words),
                                p.step_size * 1e-4,
                            )
                            for wd in wds
                        ]
                    else:
                        sched = [
                            _sched_alpha(g * spc + j, epoch)
                            for j in range(spc)
                        ]
                        alphas = [a for a, _ in sched]
                        wds = [w for _, w in sched]
                    with metrics.timing("step"), obs_run.span(
                        "device_steps", step0=step, n=n_real
                    ):
                        losses = self._train_batches(
                            engine, grp, base_key, step,
                            np.asarray(alphas, np.float32),
                        )
                    new_pend = (losses, wds, alphas, n_real, step)
                    step += spc  # pad/tail steps consumed keys too
                    # Harvest group g-1's records while group g runs
                    # (one-group deferred loss sync, see _harvest_host).
                    if pending is not None:
                        _harvest_host(pending)
                    pending = new_pend
                    g += 1
                if pending is not None:
                    # Epoch-end drain: metrics/canary catch up before the
                    # checkpoint reads words_done.
                    _harvest_host(pending)
                    pending = None
                stopping = (
                    stop_after_epochs is not None
                    and (epoch + 1 - start_epoch) >= stop_after_epochs
                )
                if state_path and (
                    stopping
                    or (epoch + 1) % max(checkpoint_every_epochs, 1) == 0
                ):
                    save_checkpoint(epoch + 1)
                if stopping:
                    logger.info("stopping early after epoch %d", epoch + 1)
                    break
            # Fit-exit barrier for in-flight async checkpoint writes
            # (failed writes surface here loudly; hung writers raise
            # after the bounded wait, GLINT_CKPT_WAIT_TIMEOUT).
            engine.wait_pending_saves(timeout=_ckpt_wait_timeout())
        except TrainingDiverged:
            engine.wait_pending_saves(
                reraise=False, timeout=_ckpt_wait_timeout()
            )
            _save_diverged_snapshot(engine, checkpoint_dir, obs_run)
            raise
        except BaseException:
            engine.wait_pending_saves(
                reraise=False, timeout=_ckpt_wait_timeout()
            )
            obs_run.close(failed=True)
            raise
        finally:
            obs_run.close()
        logger.info("training done: %s", metrics.summary())
        model = self._make_model(vocab, engine)
        model.training_metrics = {
            **metrics.summary(), "pipeline": "host",
            "step_body": engine.step_body,
        }
        steptime = obs_run.steptime_totals()
        if steptime:
            model.training_metrics["steptime"] = steptime
        return model

    # Hooks specialized by subword/other model families (models/fasttext.py).

    def _center_groups(self) -> Optional[np.ndarray]:
        """Family hook: the ``(vocab, G)`` table of the rows each word's
        centre vector is the mean of, -1 padded, for the corpus-resident
        fit to put on the device beside the corpus
        (``EmbeddingEngine.upload_center_groups``); None at word level,
        where a centre is its own row. Called after :meth:`_make_engine`."""
        return None

    def _make_engine(self, mesh, vocab: Vocabulary):
        from glint_word2vec_tpu.parallel.engine import EmbeddingEngine

        p = self.params
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
            shared_negatives=p.shared_negatives,
            compute_dtype=p.compute_dtype,
            architecture=p.architecture,
            position_lanes=2 * p.window if p.position_weights else 0,
        )

    def _train_batches(self, engine, group: BatchGroup, base_key, step0,
                       alphas):
        """Dispatch one pre-stacked :class:`BatchGroup` as one on-device
        scan; returns the per-batch losses (lazy device array). The
        stacking itself happens on the producer thread
        (corpus/batching.group_batches) so this hook is dispatch-only."""
        return engine.train_steps(
            group.centers, group.contexts, group.mask, base_key, alphas,
            step0,
        )

    def _make_model(self, vocab: Vocabulary, engine) -> "Word2VecModel":
        return Word2VecModel(vocab, engine, self.params)


class Word2VecModel:
    """Fitted model: query/serving surface over the sharded matrix."""

    def __init__(self, vocab: Vocabulary, engine, params: Word2VecParams):
        self.vocab = vocab
        self.engine = engine
        self.params = params
        self.training_metrics: Optional[dict] = None

    # ------------------------------------------------------------------
    # transform — the reference's three flavors (SURVEY.md §3.2)
    # ------------------------------------------------------------------

    @property
    def vector_size(self) -> int:
        return self.engine.cols

    def transform(self, word: str) -> np.ndarray:
        """Single word -> vector. Raises KeyError on OOV (mllib:511-519;
        documented there as the slow path — one pull per word)."""
        idx = self.vocab.word_index.get(word)
        if idx is None:
            raise KeyError(f"word {word!r} not in vocabulary")
        return np.asarray(self.engine.pull(np.array([idx], np.int32)))[0]

    def transform_words(self, words: Sequence[str]) -> np.ndarray:
        """Batch of words -> (N, d). Raises on OOV, requests chunked
        MAX_QUERY_ROWS at a time (mllib:529-543)."""
        idx = self.vocab.encode_strict(words)
        out = np.empty((len(idx), self.vector_size), np.float32)
        for s in range(0, len(idx), MAX_QUERY_ROWS):
            out[s : s + MAX_QUERY_ROWS] = np.asarray(
                self.engine.pull(idx[s : s + MAX_QUERY_ROWS])
            )
        return out

    def transform_sentences(
        self, sentences: Iterable[Sequence[str]]
    ) -> np.ndarray:
        """Sentences -> (S, d) mean vectors, computed device-side.

        The DataFrame ``transform`` path (ml:443-459): OOV words silently
        dropped, rows chunked MAX_QUERY_ROWS at a time, empty/all-OOV
        sentences yield zero vectors. Only S*d floats return to host
        (the ``pullAverage`` network-efficiency property)."""
        sents = [self.vocab.encode(s) for s in sentences]
        d = self.vector_size
        out = np.zeros((len(sents), d), np.float32)
        for s in range(0, len(sents), MAX_QUERY_ROWS):
            block = sents[s : s + MAX_QUERY_ROWS]
            L = max((len(x) for x in block), default=0)
            if L == 0:
                continue
            # Rows and max-length pad to power-of-two buckets so repeated
            # serving calls with jittering shapes hit a small compiled
            # family instead of one jit per (S, L). Padding is mask-0:
            # padded rows come back as the zero vector (sliced off) and
            # padded columns add exact +0.0 terms to each masked mean.
            idx = np.zeros((next_pow2(len(block)), next_pow2(L)), np.int32)
            m = np.zeros(idx.shape, np.float32)
            for i, x in enumerate(block):
                idx[i, : len(x)] = x
                m[i, : len(x)] = 1.0
            out[s : s + len(block)] = np.asarray(
                self.engine.pull_average(idx, m)
            )[: len(block)]
        return out

    def transform_packed(self, idx: np.ndarray, mask: np.ndarray) -> np.ndarray:
        """One pre-packed pow2 ``(rows, len)`` block -> ``(rows, d)`` host
        means — the bulk-transform hot path (``glint_word2vec_tpu.batch``).
        The producer owns encoding and padding
        (:func:`corpus.batching.pack_query_block`); this is exactly the
        per-chunk ``pull_average`` dispatch of :meth:`transform_sentences`
        with the packing factored out, so the two paths share the padding
        exactness contract (mask-0 rows -> zero vectors, mask-0 columns
        -> exact +0.0 terms). Subword families override with their
        compose dispatch."""
        return np.asarray(self.engine.pull_average(idx, mask))

    def bulk_warmup(self, rows: int, max_len: int) -> int:
        """Compile the whole program family the bulk transform will
        dispatch — one ``pull_average`` shape per pow2 length bucket up
        to ``next_pow2(max_len)`` at the fixed ``rows`` bucket — before
        the stream starts, so steady state pays zero jit compiles
        (asserted by the pipeline via ``engine.query_compiles``, the
        serving warmup discipline applied to batch inference). Returns
        the number of shapes compiled (0 = already warm)."""
        lens, L = [], 1
        top = next_pow2(max_len)
        while L <= top:
            lens.append(L)
            L *= 2
        return self.engine.warmup(
            q_buckets=(), k_buckets=(),
            sentence_lens=tuple(lens), sentence_rows=(rows,),
        )

    # ------------------------------------------------------------------
    # Similarity / analogy serving (SURVEY.md §3.3)
    # ------------------------------------------------------------------

    def find_synonyms(self, word: str, num: int) -> List[Tuple[str, float]]:
        """Top-``num`` most-similar words, the query word excluded
        (mllib:554-560: fetch num+1 then drop the word itself)."""
        vec = self.transform(word)
        results = self.find_synonyms_vector(vec, num + 1)
        return [(w, s) for w, s in results if w != word][:num]

    def _query_engine(self):
        """Engine whose syn0 answers similarity queries. The word-level
        model queries the training table directly; subword families override
        (FastTextModel composes per-word vectors into a second engine)."""
        return self.engine

    def _decode_hits(self, sims, idx) -> List[Tuple[str, float]]:
        # Non-finite scores are masked filler, never results: the
        # exact path's -inf entries ride padding-row ids (>= vocab
        # size, caught by the index check), but the ANN path's empty
        # member slots carry id 0 — a REAL word — so dropping by score
        # is the only filter that covers both (and a -inf would also
        # serialize as invalid JSON).
        return [
            (self.vocab.words[int(i)], float(s))
            for s, i in zip(sims, idx)
            if int(i) < self.vocab.size and np.isfinite(s)
        ]

    def find_synonyms_vector(
        self, vector: np.ndarray, num: int
    ) -> List[Tuple[str, float]]:
        """Top-``num`` words by cosine similarity to an arbitrary vector
        (mllib:570-629) — distributed matvec + on-device top-k instead of
        the reference's O(vocab) driver-side scan."""
        if num <= 0:
            raise ValueError("num must be > 0")
        num = min(num, self.vocab.size)
        sims, idx = self._query_engine().top_k_cosine(
            np.asarray(vector, np.float32), num
        )
        return self._decode_hits(sims, idx)

    def find_synonyms_batch(
        self, vectors: Optional[np.ndarray], num: int, *,
        approximate: bool = False, ids=None,
    ) -> List[List[Tuple[str, float]]]:
        """Top-``num`` neighbors for a whole (Q, d) query batch in one
        distributed dispatch — the batch form of
        :meth:`find_synonyms_vector` (the reference answers findSynonyms
        for arrays by looping single queries, ml:375-420).
        ``ids`` (the exact path's alone) names the queries that are rows
        of the queried table, which the program then gathers for itself:
        see ``EmbeddingEngine.top_k_cosine_batch``.
        ``approximate=True`` rides the engine's two-stage coarse index
        (ISSUE 12) instead of the exact masked GEMM — requires an
        adopted index; the serving layer owns the recall gate. A
        ``num`` beyond the index's probe capacity (nprobe x member
        slots — thousands at the default geometry) silently routes to
        the exact path: correctness outranks the speedup there."""
        return [
            self._decode_hits(s, i)
            for s, i in zip(*self.top_k_batch(
                vectors, num, approximate=approximate, ids=ids
            ))
        ]

    def top_k_batch(
        self, vectors: Optional[np.ndarray], num: int, *,
        approximate: bool = False, ids=None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """:meth:`find_synonyms_batch` up to its decode: the ``(Q, num)``
        scores and row ids on the host. A served round decodes them
        itself, under a span of its own."""
        if num <= 0:
            raise ValueError("num must be > 0")
        num = min(num, self.vocab.size)
        eng = self._query_engine()
        if approximate:
            idx_obj = eng.ann_index
            conf = getattr(eng, "_ann_conf", None) or {}
            cap = (
                conf.get("nprobe", 0) * idx_obj.slots
                if idx_obj is not None else 0
            )
            approximate = num <= cap
        if approximate:
            return eng.ann_top_k_batch(np.asarray(vectors, np.float32), num)
        return eng.top_k_cosine_batch(vectors, num, ids=ids)

    def analogy(
        self, positive: Sequence[str], negative: Sequence[str], num: int
    ) -> List[Tuple[str, float]]:
        """king - man + woman style queries: sum(positive) - sum(negative),
        query words excluded from results. The reference exposes this as
        caller-side vector arithmetic + findSynonyms
        (ServerSideGlintWord2VecSpec.scala:342-344); provided here as a
        first-class method."""
        vec = np.zeros(self.vector_size, np.float32)
        for w in positive:
            vec += self.transform(w)
        for w in negative:
            vec -= self.transform(w)
        exclude = set(positive) | set(negative)
        res = self.find_synonyms_vector(vec, num + len(exclude))
        return [(w, s) for w, s in res if w not in exclude][:num]

    # ------------------------------------------------------------------
    # Export (SURVEY.md §2 C3 getVectors / toLocal)
    # ------------------------------------------------------------------

    def get_vectors(self) -> Iterator[Tuple[str, np.ndarray]]:
        """Stream (word, vector) pairs, pulled MAX_QUERY_ROWS at a time
        (mllib:638-644 / ml:342-364) — never materializes the full matrix
        on host, killing the reference's 8 GB broadcast ceiling
        (README.md:71-73)."""
        for s in range(0, self.vocab.size, MAX_QUERY_ROWS):
            idx = np.arange(s, min(s + MAX_QUERY_ROWS, self.vocab.size), dtype=np.int32)
            rows = np.asarray(self.engine.pull(idx))
            for i, r in zip(idx, rows):
                yield self.vocab.words[int(i)], r

    def to_local(self) -> "LocalWord2VecModel":
        """Materialize a host-side numpy model (mllib:651-657)."""
        vecs = np.empty((self.vocab.size, self.vector_size), np.float32)
        for s in range(0, self.vocab.size, MAX_QUERY_ROWS):
            idx = np.arange(s, min(s + MAX_QUERY_ROWS, self.vocab.size), dtype=np.int32)
            vecs[s : s + len(idx)] = np.asarray(self.engine.pull(idx))
        return LocalWord2VecModel(list(self.vocab.words), vecs)

    # ------------------------------------------------------------------
    # Persistence / lifecycle (SURVEY.md §3.4)
    # ------------------------------------------------------------------

    def save(self, path: str) -> None:
        """Matrix shards + words list + params metadata (mllib:493-498:
        ``matrix.save`` + the words text file; ml:504-507 params metadata).

        Crash-safe: every file goes through write-temp-then-rename (the
        matrix via the engine's snapshot commit, words/params here), so
        re-saving over an existing model directory can never leave a
        truncated words file or params blob behind."""
        from glint_word2vec_tpu.utils import (
            atomic_write_json,
            atomic_write_text,
        )

        os.makedirs(path, exist_ok=True)
        self.engine.save(os.path.join(path, "matrix"))
        for w in self.vocab.words:
            if "\n" in w or "\r" in w:
                raise ValueError(
                    f"vocab word {w!r} contains a newline and cannot be "
                    "saved to the line-oriented words file"
                )
        atomic_write_text(
            os.path.join(path, "words.txt"),
            "".join(w + "\n" for w in self.vocab.words),
        )
        atomic_write_json(
            os.path.join(path, "params.json"),
            json.loads(self.params.to_json()),
        )

    #: Params class used by :meth:`load`; model families override.
    _PARAMS_CLS = Word2VecParams

    @classmethod
    def load(cls, path: str, mesh=None) -> "Word2VecModel":
        """Rebuild from :meth:`save` output onto any mesh — the analogue of
        loading onto a fresh or *different* PS cluster (mllib:696-725;
        host-override at ml:584-586). With no explicit mesh, the saved
        topology is clamped to the live device count, so a model trained
        on a big mesh loads on a small host. Shared by all model families;
        the family-specific tail lives in :meth:`_from_loaded`."""
        import jax

        from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
        from glint_word2vec_tpu.parallel.mesh import make_mesh

        with open(os.path.join(path, "params.json")) as f:
            try:
                params = cls._PARAMS_CLS.from_json(f.read())
            except TypeError as e:
                # e.g. a params.json from a different model family fed to
                # the wrong loader (use models.load_model to dispatch).
                raise ValueError(
                    f"params.json at {path} does not describe a "
                    f"{cls._PARAMS_CLS.__name__} model: {e}"
                )
        if mesh is None:
            n_dev = len(jax.devices())
            num_model = max(1, min(params.num_shards, n_dev))
            num_data = max(1, min(params.num_partitions, n_dev // num_model))
            mesh = make_mesh(num_data, num_model)
        engine = EmbeddingEngine.load(os.path.join(path, "matrix"), mesh)
        vocab = saved_model_vocabulary(
            path, engine._counts,
            engine.vocab_size + engine.extra_rows_assigned,
        )
        return cls._from_loaded(vocab, engine, params)

    @classmethod
    def _from_loaded(cls, vocab, engine, params) -> "Word2VecModel":
        return cls(vocab, engine, params)

    def stop(self) -> None:
        """Release device memory (reference ``model.stop`` terminating the
        PS client/cluster, mllib:664-667)."""
        self.engine.destroy()


class LocalWord2VecModel:
    """Host-only numpy model — the ``toLocal`` result (mllib:651-657).

    Same query surface, no device required; convertible back by training
    code via ``EmbeddingEngine.set_tables`` if needed.
    """

    def __init__(self, words: List[str], vectors: np.ndarray):
        if vectors.shape[0] != len(words):
            raise ValueError("words/vectors length mismatch")
        self.words = words
        self.vectors = vectors.astype(np.float32)
        self.word_index = {w: i for i, w in enumerate(words)}
        self._norms = np.linalg.norm(self.vectors, axis=1)

    @property
    def vector_size(self) -> int:
        return self.vectors.shape[1]

    def transform(self, word: str) -> np.ndarray:
        idx = self.word_index.get(word)
        if idx is None:
            raise KeyError(f"word {word!r} not in vocabulary")
        return self.vectors[idx]

    def find_synonyms_vector(self, vector, num: int) -> List[Tuple[str, float]]:
        v = np.asarray(vector, np.float32)
        nv = np.linalg.norm(v)
        if nv > 0:
            v = v / nv
        safe = np.where(self._norms > 0, self._norms, 1.0)
        cos = np.where(self._norms > 0, (self.vectors @ v) / safe, 0.0)
        top = np.argsort(-cos)[:num]
        return [(self.words[i], float(cos[i])) for i in top]

    def find_synonyms(self, word: str, num: int) -> List[Tuple[str, float]]:
        res = self.find_synonyms_vector(self.transform(word), num + 1)
        return [(w, s) for w, s in res if w != word][:num]

    def get_vectors(self) -> Dict[str, np.ndarray]:
        return {w: self.vectors[i] for i, w in enumerate(self.words)}

    def save(self, path: str) -> None:
        """Crash-safe: both files land via write-temp-then-rename
        (utils.atomic_write_npy), so overwriting a previous save can
        never leave a truncated ``vectors.npy`` behind."""
        from glint_word2vec_tpu.utils import (
            atomic_write_npy,
            atomic_write_text,
        )

        os.makedirs(path, exist_ok=True)
        atomic_write_npy(os.path.join(path, "vectors.npy"), self.vectors)
        atomic_write_text(
            os.path.join(path, "words.txt"),
            "".join(w + "\n" for w in self.words),
        )

    @classmethod
    def load(cls, path: str) -> "LocalWord2VecModel":
        vectors = np.load(os.path.join(path, "vectors.npy"))
        with open(os.path.join(path, "words.txt"), encoding="utf-8") as f:
            words = [line.rstrip("\n") for line in f if line.rstrip("\n")]
        return cls(words, vectors)
