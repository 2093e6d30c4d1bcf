"""Multi-host distributed backend: DCN bring-up, global meshes, host-local
batch feeding.

Reference mapping (SURVEY.md §2.2, §3.5): the Glint fork's cluster substrate
is an Akka-remoting actor system spanning a Spark app — a master on the
driver, servers on executors, workers connecting by host
(``Client.getHostConfig(parameterServerHost)``, mllib:358-360), launched
either inside the training app (``Client.runWithWord2VecMatrixOnSpark``,
mllib:355) or as a standalone cluster app (``glint.Main``, README.md:52-57).
The TPU-native restatement has no server processes at all:

  * cluster bring-up   -> :func:`initialize` (JAX distributed runtime over
    DCN: one coordinator, N host processes, each owning its local chips)
  * PS/worker topology -> :func:`make_global_mesh` (("data", "model") mesh
    over ALL processes' devices; ICI inside a slice, DCN across slices)
  * Spark partition feeding its executor -> :func:`process_batch_slice` +
    :func:`make_global_batch` (each host materializes only its data-axis
    rows; ``jax.make_array_from_process_local_data`` assembles the global
    batch without any host ever holding it all)
  * separate-cluster mode / host override at load -> meshes are
    reconstructable on any topology; checkpoints re-home freely
    (engine.load, mllib:696-725 analogue)

Single-process use is the degenerate case throughout: every helper works
unchanged (and is unit-tested) with ``process_count == 1``.
"""

from __future__ import annotations

import logging
from typing import Optional, Sequence, Tuple

import numpy as np

from glint_word2vec_tpu.parallel.mesh import DATA_AXIS, make_mesh

logger = logging.getLogger(__name__)


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids: Optional[Sequence[int]] = None,
) -> None:
    """Bring up the JAX distributed runtime (DCN coordination layer).

    The analogue of starting/joining the Glint cluster: where the reference
    spawns a master + parameter servers and connects by host:port
    (mllib:354-360; separate-glint.conf ports), TPU pods coordinate through
    one bootstrap service. With no arguments, TPU pod environments
    auto-discover topology (the "integrated" deployment, README.md:45-50);
    explicit arguments are the "separate cluster" analogue (README.md:52-57)
    for GPU/CPU multi-host or custom launchers.

    Call once per host process, before any other JAX API. No-op if the
    distributed runtime is already initialized.
    """
    import os

    import jax

    if jax.distributed.is_initialized():  # already up
        logger.info("jax.distributed already initialized; skipping")
        return
    # Multi-process CPU runs (the supervisor's gang mode on dev boxes /
    # CI) need an explicit cross-host collectives backend: without it
    # jaxlib raises "Multiprocess computations aren't implemented on
    # the CPU backend" at the first psum. Opt into gloo when the run is
    # pinned to CPU and the operator hasn't chosen an implementation.
    if (
        "cpu" in (jax.config.jax_platforms or "")
        and "JAX_CPU_COLLECTIVES_IMPLEMENTATION" not in os.environ
    ):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if local_device_ids is not None:
        kwargs["local_device_ids"] = list(local_device_ids)
    jax.distributed.initialize(**kwargs)
    logger.info(
        "distributed runtime up: process %d/%d, %d local / %d global devices",
        jax.process_index(),
        jax.process_count(),
        jax.local_device_count(),
        jax.device_count(),
    )


def make_global_mesh(
    num_data: Optional[int] = None, num_model: Optional[int] = None
):
    """("data", "model") mesh over ALL hosts' devices.

    Layout policy: the device grid is built from the global device list in
    process-major order, so with ``num_data >= process_count`` each host's
    chips form whole data-axis rows — the model axis (the hot psum/all_gather
    paths, engine._pull_rows/_scatter_rows) stays inside one host's slice and
    rides ICI, while the data axis alone crosses DCN. That is the same
    locality split the reference gets from server-side compute: heavy traffic
    stays server-local; only batch-level exchange crosses the network
    (SURVEY.md §2.3 comm-backend row).
    """
    import jax

    return make_mesh(num_data, num_model, devices=jax.devices())


def process_batch_slice(mesh, process_index: Optional[int] = None,
                        process_count: Optional[int] = None) -> Tuple[float, float]:
    """This host's fraction [lo, hi) of the global batch's data-axis rows.

    The feeding contract mirrors Spark's partition->executor locality
    (repartition(numPartitions) at mllib:345): each host's corpus reader
    produces only the rows its local devices will consume. Returns fractions
    so callers can slice any global batch size.
    """
    import jax

    pi = jax.process_index() if process_index is None else process_index
    pc = jax.process_count() if process_count is None else process_count
    return pi / pc, (pi + 1) / pc


def make_global_batch(mesh, *host_arrays: np.ndarray, data_axis: int = 0):
    """Assemble global device arrays from per-host batch slices.

    Each process passes its own rows (``global_rows / process_count`` each,
    along ``data_axis``); the result is a tuple of global ``jax.Array``s
    sharded over "data" on that axis, with every shard living on the host
    that produced it — no cross-host copy of batch data, exactly like a
    Spark partition never leaving its executor until the (index-only) PS
    traffic. Use ``data_axis=1`` for the stacked (K, B, ...) groups fed to
    ``EmbeddingEngine.train_steps``. Works unchanged for one process.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    out = []
    for a in host_arrays:
        dims = [None] * a.ndim
        dims[data_axis] = DATA_AXIS
        spec = P(*dims)
        out.append(
            jax.make_array_from_process_local_data(
                NamedSharding(mesh, spec), np.asarray(a)
            )
        )
    return tuple(out)


def shard_sentences_for_process(
    sentences, process_index: Optional[int] = None,
    process_count: Optional[int] = None,
):
    """Partition a sentence list across host processes (round-robin).

    The analogue of ``repartition(numPartitions)`` placing RDD partitions on
    executors (mllib:345): each host trains on its own corpus slice. Round-
    robin (not contiguous blocks) so document-ordered corpora spread topical
    clusters evenly across hosts within every epoch. Every process receives
    the SAME number of sentences (the remainder ``len % process_count`` is
    dropped): multi-host SPMD training requires every process to dispatch
    the same step count, or the program deadlocks at the first collective
    one host doesn't reach. Equal sentence counts make per-host step counts
    *near*-equal; the feeding loop must still equalize exactly (pad the
    short hosts' final groups with zero-mask batches, as fit() already does
    for epoch tails) before dispatching.
    """
    import jax

    pi = jax.process_index() if process_index is None else process_index
    pc = jax.process_count() if process_count is None else process_count
    per = len(sentences) // pc
    return [sentences[i * pc + pi] for i in range(per)]


def shard_flat_for_process(
    ids: np.ndarray,
    offsets: np.ndarray,
    process_index: Optional[int] = None,
    process_count: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Flat-encoding (ids, offsets) variant of
    :func:`shard_sentences_for_process`: same round-robin split, same
    drop-the-remainder equal-count contract, without materializing
    per-sentence Python objects (the streaming fit_file path)."""
    import jax

    pi = jax.process_index() if process_index is None else process_index
    pc = jax.process_count() if process_count is None else process_count
    n = len(offsets) - 1
    per = n // pc
    picks = np.arange(per) * pc + pi
    lens = np.diff(offsets)
    my_lens = lens[picks]
    out_offsets = np.zeros(per + 1, dtype=np.int64)
    np.cumsum(my_lens, out=out_offsets[1:])
    total = int(my_lens.sum())
    # Vectorized shard copy (this is the streaming path built for corpora
    # with tens of millions of sentences — a per-sentence Python loop here
    # would dominate every fit_file start): source index of each output
    # word = its sentence's source start + its position within the sentence.
    src_start = np.repeat(offsets[picks], my_lens)
    pos_in_sent = np.arange(total, dtype=np.int64) - np.repeat(
        out_offsets[:-1], my_lens
    )
    out_ids = np.ascontiguousarray(ids[src_start + pos_in_sent], dtype=np.int32)
    return out_ids, out_offsets


def shard_span(
    n_items: int, process_index: int, process_count: int
) -> Tuple[int, int]:
    """Contiguous, balanced ``[start, end)`` span for one rank over
    ``n_items`` — the bulk-transform input split
    (``glint_word2vec_tpu.batch``). Unlike
    :func:`shard_flat_for_process` (round-robin, drop-the-remainder:
    gradient-path semantics where equal per-rank counts matter more
    than coverage), this covers EVERY item exactly once: the bulk
    transform's contract is one output row per input line, so nothing
    may be dropped. The first ``n_items % process_count`` ranks take
    one extra item; spans are a pure function of the three arguments,
    so every rank (and every resume) derives the same split with no
    coordination."""
    if process_count < 1:
        raise ValueError("process_count must be >= 1")
    if not 0 <= process_index < process_count:
        raise ValueError(
            f"process_index {process_index} out of range for "
            f"{process_count} processes"
        )
    if n_items < 0:
        raise ValueError("n_items must be >= 0")
    q, r = divmod(n_items, process_count)
    start = process_index * q + min(process_index, r)
    return start, start + q + (1 if process_index < r else 0)


def shard_flat_locality(
    ids: np.ndarray,
    offsets: np.ndarray,
    process_index: Optional[int] = None,
    process_count: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Locality-aware replica sharding (ISSUE 16, arXiv:1909.03359):
    cluster each replica's sentences by their RAREST token so per-rank
    touched-row sets concentrate, shrinking the touched-row unions
    that size every exchange buffer (and letting the adaptive capacity
    walk down further).

    Vocabulary ids are frequency-ordered (0 = most frequent), so a
    sentence's max token id is its rarest word — the tail rows only
    that sentence's shard will touch. Sentences sort by that key
    (stable, so equal-key sentences keep corpus order) and split into
    ``process_count`` CONTIGUOUS runs balanced by cumulative word
    count: every replica sees the same deterministic assignment
    (computed redundantly from the full corpus on every rank — same
    contract as the round-robin sharder), head-word rows stay shared
    (they appear everywhere) while tail rows concentrate on one rank.
    Ranks can differ by up to one sentence in word count — the
    lockstep filler protocol absorbs the skew, exactly as it does for
    the round-robin remainder."""
    import jax

    pi = jax.process_index() if process_index is None else process_index
    pc = jax.process_count() if process_count is None else process_count
    n = len(offsets) - 1
    if n == 0 or pc == 1:
        return (
            np.ascontiguousarray(ids, dtype=np.int32),
            np.asarray(offsets, dtype=np.int64),  # graftlint: ignore[sync-point] host corpus array
        )
    lens = np.diff(offsets)
    nonempty = lens > 0
    # Rarest-token key per sentence: segment max over the flat ids
    # (reduceat needs in-range starts; empty segments return a
    # neighbor's value and are masked to -1, sorting first and landing
    # harmlessly in rank 0's run).
    seg_max = np.zeros(n, dtype=np.int64)
    if len(ids):
        starts = np.minimum(offsets[:-1], len(ids) - 1)
        seg_max = np.maximum.reduceat(ids.astype(np.int64), starts)
    keys = np.where(nonempty, seg_max, -1)
    order = np.argsort(keys, kind="stable")
    # Contiguous word-count-balanced runs over the sorted order: rank r
    # takes sentences whose cumulative word count lands in
    # (r * total/pc, (r+1) * total/pc].
    sorted_lens = lens[order]
    cum = np.cumsum(sorted_lens)
    total = int(cum[-1]) if n else 0  # graftlint: ignore[sync-point] host numpy scalar
    bounds = (total * (np.arange(pc + 1))) // pc
    # Sentence s goes to the rank whose (lo, hi] word-window contains
    # its cumulative end — searchsorted on the shared boundary grid.
    assign = np.searchsorted(bounds[1:-1], cum, side="left")
    picks = order[assign == pi]
    picks.sort()  # keep corpus order within the shard (RNG streams)
    my_lens = lens[picks]
    per = len(picks)
    out_offsets = np.zeros(per + 1, dtype=np.int64)
    np.cumsum(my_lens, out=out_offsets[1:])
    tot = int(my_lens.sum())  # graftlint: ignore[sync-point] host numpy scalar
    src_start = np.repeat(offsets[picks], my_lens)
    pos_in_sent = np.arange(tot, dtype=np.int64) - np.repeat(
        out_offsets[:-1], my_lens
    )
    out_ids = np.ascontiguousarray(
        ids[src_start + pos_in_sent], dtype=np.int32
    )
    return out_ids, out_offsets


def allgather_host(arr: np.ndarray) -> np.ndarray:
    """Host-level allgather of one fixed-shape numpy array: returns
    ``(process_count, *shape)`` with rank order preserved. The wire of
    the replica-exchange protocol (parallel/exchange.py): gloo between
    CPU gang processes, DCN across pod hosts, via
    ``multihost_utils.process_allgather`` — each distinct buffer shape
    compiles exactly one collective, so the exchange's fixed-capacity
    padded buffers keep this compile-once. Single-process returns
    ``arr[None]`` without touching the collective machinery."""
    import jax

    a = np.asarray(arr)
    if jax.process_count() == 1:
        return a[None]
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(a))


def per_process_word_counts(
    sentence_lengths: np.ndarray, process_count: int
) -> np.ndarray:
    """Word count each process's shard will hold under the round-robin
    split — computable on EVERY host with no communication (each host sees
    the full corpus; only its own slice is materialized). The max of these
    fixes the per-epoch step count every process must dispatch (SPMD
    lockstep: a host short on batches pads zero-mask steps up to it)."""
    lens = np.asarray(sentence_lengths, dtype=np.int64)
    pc = int(process_count)
    per = len(lens) // pc
    return np.array(
        [int(lens[pi : per * pc : pc].sum()) for pi in range(pc)],
        dtype=np.int64,
    )
