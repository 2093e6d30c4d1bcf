"""Hyperparameter surface with reference-parity validation.

The reference exposes these as Spark ML ``Params`` with validators
(ml/feature/ServerSideGlintWord2Vec.scala:40-222) mirrored by fluent setters
with ``require`` guards on the MLlib trainer (mllib:92-243) and by 11 py4j
``Param``s in the Python bindings (ml_glintword2vec.py:101-136). Defaults here
are the reference defaults (mllib:67-81; SURVEY.md §5 config tier 1).

Parameters that exist only to describe the Spark/Akka deployment
(``numParameterServers``, ``parameterServerHost``, ``parameterServerConfig``,
``unigramTableSize``) are replaced by mesh geometry: ``num_shards`` is the
model-axis size of the TPU mesh (the direct analogue of the number of
parameter servers — each shard owns ``1/num_shards`` of both matrices,
README.md:69) and ``num_partitions`` maps to the data-parallel axis. The
Akka message-size guard ``batchSize*n*window <= 10000`` (mllib:154-156) has no
TPU analogue — there is no message ceiling on ICI — so it is intentionally NOT
enforced (documented divergence); batch geometry is limited by HBM only.
"""

from __future__ import annotations

import dataclasses
import json
import logging
from dataclasses import dataclass

logger = logging.getLogger(__name__)


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise ValueError(msg)


@dataclass
class Word2VecParams:
    """All training/serving hyperparameters, validated on construction.

    Attributes (reference param in parens):
      vector_size: embedding dimension d (``vectorSize``, default 100).
      window: max context window (``windowSize``, default 5).
      step_size: initial learning rate (``stepSize``, default 0.01875).
      batch_size: center positions per minibatch (``batchSize``, default 50 in
        the reference; here defaults to 1024 — a TPU-shaped batch. 50-position
        batches underutilize the chip; quality at larger sync batches is
        validated by the analogy gates).
      num_negatives: negatives per (center, context) pair (``n``, default 5).
      subsample_ratio: frequency subsampling ratio (``subsampleRatio``).
        The reference *declares* a default of 1e-6 (mllib:75) but its
        integer-division bug (mllib:375) makes subsampling a no-op, so the
        reference's de-facto default is "disabled" — and 1e-6 under the
        *correct* formula discards ~95% of a typical corpus. We therefore
        default to 0 (disabled, the de-facto reference behavior) and users
        opting in get the fixed semantics (typical useful values 1e-3..1e-5;
        see Vocabulary.keep_probabilities).
      min_count: minimum token frequency (``minCount``, default 5).
      num_iterations: epochs (``maxIter``/``numIterations``, default 1).
      max_sentence_length: sentence chunk bound (``maxSentenceLength``, 1000).
      seed: RNG seed for subsampling/windowing/negatives/init (``seed``).
      num_partitions: data-parallel axis size (``numPartitions``, default 1).
      num_shards: model-parallel axis size; 1/num_shards of the vocab rows of
        syn0/syn1 live on each mesh slice (``numParameterServers``, default 5
        in the reference; default 1 here — set from the mesh).
      unigram_power: noise-distribution exponent (fixed 0.75 in word2vec).
      unigram_table_size: optional quantized-table compatibility mode
        (``unigramTableSize``; None = exact alias sampling, see corpus.alias).
      dtype: parameter dtype for the embedding tables ("float32" or
        "bfloat16"). Dots/updates always accumulate in float32.
      steps_per_call: minibatches executed per device dispatch (an on-device
        ``lax.scan`` over stacked batches). The TPU analogue of the
        reference's RPC flow control — it kept ~1 minibatch in flight per
        worker (mllib:419-429); here each dispatch carries this many, so
        host round-trip latency amortizes away. 1 = step-at-a-time.
      shared_negatives: 0 (default) draws ``num_negatives`` fresh noise
        words per (center, context) pair — the reference's server-side
        semantics (mllib:420-421). > 0 draws ONE pool of this many noise
        words per step, shared across the batch and weighted to the same
        expected gradient (ops.sgns.shared_sgns_grads) — the TPU-shaped
        estimator: dense MXU matmuls instead of batch*contexts*n sparse
        row accesses. 1024-8192 are typical pool sizes.
      architecture: "skipgram" (default: each word predicts each of its
        context words, the reference's model) or "cbow" (continuous bag of
        words with negative sampling, the word2vec tool's own default,
        ``-cbow 1``: the mean of a position's context rows predicts the
        position's word, and every context row takes the whole gradient).
        A property of the model, saved with it. Both families train
        both: with ``FastTextParams`` a bag's word is its subword group
        and the mean is one mean over every row of the bag (``fasttext
        cbow``). Both form a bag from the rows of the step's span, each
        word gathered once and read by every bag it is in
        (ops/device_batching.bag_span_batch). CBOW trains on the
        corpus-resident packed path only, draws its negatives a
        position, and is refused with a shared pool, grid packing,
        replica exchange, the streaming trainer, or a fit that path does
        not take (there is no host-batcher CBOW).
      position_weights: CBOW with position-dependent weighting (Mikolov
        et al., LREC 2018, arXiv:1712.09405 section 2.2; the recipe of
        the published ``cc.<lang>.300`` fastText tables, Grave et al.,
        arXiv:1802.06893): a vector ``d_p`` for each relative position
        p of the window multiplies, column by column, the word a bag
        holds at p before the bag is summed. The model then owns a third
        table, ``posw`` ``(2 * window, vector_size)`` float32, started at
        ones and trained with the two others (every step adds to each row
        the MEAN of its gradient over the step's positions that hold a
        word there: their sum, which the rows of ``syn0`` take, does not
        stay finite at a batch of 8,192), saved with the model and restored by ``load``
        and by a resumed fit; queries read ``syn0`` alone, as the
        published ``.vec`` files hold input vectors alone. Valid with
        ``architecture="cbow"`` only, both families.
    """

    vector_size: int = 100
    window: int = 5
    step_size: float = 0.01875
    batch_size: int = 1024
    num_negatives: int = 5
    subsample_ratio: float = 0.0
    min_count: int = 5
    num_iterations: int = 1
    max_sentence_length: int = 1000
    seed: int = 1
    num_partitions: int = 1
    num_shards: int = 1
    unigram_power: float = 0.75
    unigram_table_size: int | None = None
    dtype: str = "float32"
    #: MXU operand dtype for the train step's dense contractions (f32
    #: accumulation either way): "float32" = exactness-tested numerics,
    #: "bfloat16" = the MXU-native fast path (ops/sgns.py). None defers to
    #: the engine's GLINT_W2V_MATMUL_DTYPE env default (so the env knob
    #: works through the model/CLI path too).
    compute_dtype: str | None = None
    steps_per_call: int = 16
    shared_negatives: int = 0
    #: Device-resident corpus dispatch shape: "dense" (the default since
    #: ISSUE 11) prefix-sum-compacts the valid (center, context) pairs
    #: into dense fixed-shape pair batches before the update
    #: (ops/device_batching.pack_window_pairs), spending ~every
    #: dispatched FLOP on a real pair. "grid" restores the legacy
    #: (batch, context) window grids — the reference's shape, ~43% live
    #: lanes at window 5 — for A/B comparison or to resume an old
    #: grid-written mid-epoch checkpoint. Same valid-pair multiset
    #: per epoch either way (window draws reproduce the grid mapping);
    #: negative/loss RNG streams differ like host-vs-device already do.
    #: Ignored (with a log line) when training routes to the host
    #: batcher, which always builds grid-shaped batches.
    batch_packing: str = "dense"
    #: Cross-replica reconciliation for multi-process training (ISSUE
    #: 15, parallel/exchange.py). "none" (default) keeps the SPMD
    #: global-mesh path (batch payloads exchanged inside every jitted
    #: step). "sparse" switches pc > 1 runs to data-parallel replicas:
    #: each process trains its corpus shard on a LOCAL mesh and ships
    #: only (touched row ids, accumulated fp32 deltas) after every
    #: dispatch group — wire cost scales with rows touched, not vocab
    #: size. "dense" ships full per-rank table deltas on the same
    #: cadence (the parity baseline / escape hatch; also forced by
    #: GLINT_DENSE_EXCHANGE=1 or any capacity overflow, per round).
    exchange: str = "none"
    #: Fixed touched-row buffer capacity per sync (0 = auto-size from
    #: the dispatch-group pair budget, then adapt down from the
    #: observed touched-row high-water mark; see
    #: exchange.default_capacity. A nonzero value PINS the capacity —
    #: no adaptation). Constant shapes keep the protocol compile-once.
    exchange_capacity: int = 0
    #: Sparse exchange payload encoding (ISSUE 16): "fp32" (exact),
    #: "bf16" (half the payload, one rounding per component), or
    #: "int8" (per-row maxabs scale + error feedback — the local
    #: quantization residual folds into the next round, keeping the
    #: update stream unbiased). Dense/spill/flush rounds always ship
    #: exact fp32. Ignored unless exchange="sparse".
    exchange_wire: str = "fp32"
    #: Round coalescing (ISSUE 16): run a wire round every R dispatch
    #: groups instead of every group — repeated touches of a hot row
    #: within the window cost one wire row. 1 = sync every group (the
    #: PR 15 cadence).
    exchange_every: int = 1
    #: Exchange sync topology (ISSUE 16): "flat" allgathers every
    #: rank's payload (the PR 15 protocol); "twolevel" ships exact
    #: fp32 sparse payloads on the fast intra-node hop, folds them
    #: into one node delta, and only node LEADERS ship the quantized
    #: node payload over the slow inter-node hop (Ji et al.
    #: arXiv:1604.04661; GLINT_RANKS_PER_NODE sets the node size).
    exchange_topology: str = "flat"
    #: Replica corpus sharding: "roundrobin" (the PR 15 interleave) or
    #: "locality" — sentences clustered by their rarest token so each
    #: replica's touched-row set concentrates, shrinking the
    #: touched-row unions that size every exchange buffer
    #: (arXiv:1909.03359).
    exchange_shard: str = "roundrobin"
    #: Which model is trained: "skipgram" or "cbow" (see the class
    #: docstring). A saved model without the key is a skip-gram.
    architecture: str = "skipgram"
    #: CBOW's position weights (see the class docstring). A saved model
    #: without the key has none.
    position_weights: bool = False

    def __post_init__(self) -> None:
        self.validate()

    def validate(self) -> None:
        _require(self.vector_size > 0, "vector_size must be > 0")
        _require(self.window > 0, "window must be > 0")
        _require(self.step_size > 0, "step_size must be > 0")
        _require(self.batch_size > 0, "batch_size must be > 0")
        _require(self.num_negatives > 0, "num_negatives must be > 0")
        _require(self.subsample_ratio >= 0, "subsample_ratio must be >= 0")
        _require(self.min_count >= 0, "min_count must be >= 0")
        _require(self.num_iterations > 0, "num_iterations must be > 0")
        _require(self.max_sentence_length > 0, "max_sentence_length must be > 0")
        _require(self.num_partitions > 0, "num_partitions must be > 0")
        _require(self.num_shards > 0, "num_shards must be > 0")
        _require(0 < self.unigram_power <= 1, "unigram_power must be in (0, 1]")
        _require(
            self.unigram_table_size is None or self.unigram_table_size > 0,
            "unigram_table_size must be > 0 or None",
        )
        _require(self.dtype in ("float32", "bfloat16"), "dtype must be float32|bfloat16")
        _require(
            self.compute_dtype in (None, "float32", "bfloat16"),
            "compute_dtype must be float32|bfloat16|None",
        )
        _require(self.steps_per_call > 0, "steps_per_call must be > 0")
        _require(self.shared_negatives >= 0, "shared_negatives must be >= 0")
        _require(
            self.batch_packing in ("grid", "dense"),
            "batch_packing must be grid|dense",
        )
        _require(
            self.exchange in ("none", "sparse", "dense"),
            "exchange must be none|sparse|dense",
        )
        _require(
            self.exchange_capacity >= 0, "exchange_capacity must be >= 0"
        )
        _require(
            self.exchange_wire in ("fp32", "bf16", "int8"),
            "exchange_wire must be fp32|bf16|int8",
        )
        _require(self.exchange_every >= 1, "exchange_every must be >= 1")
        _require(
            self.exchange_topology in ("flat", "twolevel"),
            "exchange_topology must be flat|twolevel",
        )
        _require(
            self.exchange_shard in ("roundrobin", "locality"),
            "exchange_shard must be roundrobin|locality",
        )
        _require(
            self.architecture in ("skipgram", "cbow"),
            "architecture must be skipgram|cbow",
        )
        _require(
            isinstance(self.position_weights, bool),
            "position_weights must be true or false",
        )
        _require(
            not self.position_weights or self.architecture == "cbow",
            "position_weights weight the words of a CBOW bag by where they "
            "stand: architecture must be 'cbow'",
        )
        if self.architecture == "cbow":
            _require(
                self.shared_negatives == 0,
                "architecture='cbow' draws its negatives a position: "
                "shared_negatives must be 0",
            )
            _require(
                self.batch_packing == "dense" and self.exchange == "none",
                "architecture='cbow' trains on the corpus-resident packed "
                "path only: batch_packing must be 'dense' and exchange "
                "'none'",
            )

    def replace(self, **kwargs) -> "Word2VecParams":
        return dataclasses.replace(self, **kwargs)

    def to_json(self) -> str:
        """Persistence metadata, analogous to DefaultParamsWriter metadata +
        the custom JSON codec for the PS config param (ml:183-195, 504-507)."""
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "Word2VecParams":
        """Read :meth:`to_json` output. A blob written before PR 46 carries
        ``"layout"``: the tables now rest split by rows whatever it says
        (a column-sharded checkpoint is re-homed as it loads,
        ``EmbeddingEngine.stage_tables``)."""
        d = json.loads(s)
        layout = d.pop("layout", "rows")
        _require(layout in ("rows", "dims"), "layout must be rows|dims")
        if layout == "dims":
            logger.warning(
                "params say layout=dims (column sharding, removed): the "
                "tables are re-homed by rows"
            )
        return cls(**d)
