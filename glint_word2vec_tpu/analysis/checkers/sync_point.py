"""sync-point: host<->device synchronization only in blessed seams.

PR 5 made the fit loop stall-free by confining every device->host
readback to explicit harvest seams (one-group-deferred scalar harvest,
the checkpoint snapshot, serving query ops that must return host
values). A stray ``float(tracer_result)`` or ``.block_until_ready()``
anywhere else re-serializes the loop — the device waits on the host
again and the stall telemetry quietly degrades. This rule flags the
sync-inducing forms (``float()`` / ``int()`` / ``np.asarray()`` /
``np.array()`` on non-obviously-host values, ``.block_until_ready()``,
``jax.device_get`` / ``jax.block_until_ready``) in every jax-importing
module of the package, EXCEPT inside the ``SYNC_SEAMS`` allowlist
below — the audited harvest/readback seams where syncing is the whole
point.

Scope note: ``scripts/`` and ``bench.py`` are exempt by design —
benches and probes measure by syncing (that is what a measurement IS);
the rule guards the library's hot paths, where an eager sync is a perf
regression. Their persistence sites remain covered by atomic-persist.

The heuristic is deliberately about *candidate* sites: a ``float(x)``
on a config value in a jax module is noise the HOST_ROOTS skip-list
removes, and anything left that is genuinely host-only gets an inline
``# graftlint: ignore[sync-point] <why>`` — the audit trail is the
feature.
"""

from __future__ import annotations

import ast
from typing import Dict, List

from glint_word2vec_tpu.analysis.core import Finding, ModuleCache, checker
from glint_word2vec_tpu.analysis.checkers.common import (
    call_name,
    enclosing_map,
    root_name,
)

RULE = "sync-point"

#: Blessed harvest/readback seams: "<repo-relative path>::<qualname>"
#: -> why syncing is legal there. A seam blesses everything lexically
#: inside the named function (including nested helpers).
SYNC_SEAMS: Dict[str, str] = {
    # The deferred-readback harvests: sync group g's scalars while
    # group g+1 runs — the PR 5 design's one legal fit-loop sync.
    "glint_word2vec_tpu/models/word2vec.py::"
    "Word2Vec._fit_corpus_resident._harvest_packed":
        "the one-group-deferred scalar harvest seam (PR 5): syncs "
        "group g while group g+1 runs: the packed scan's result "
        "scalars (losses, pair counts, position advances, rows "
        "written)",
    "glint_word2vec_tpu/models/word2vec.py::"
    "Word2Vec._fit_with_batcher._harvest_host":
        "host-batcher twin of the deferred harvest: one-group-lagged "
        "loss/word records",
    "glint_word2vec_tpu/streaming/trainer.py::StreamTrainer._harvest":
        "streaming mini-epoch harvest seam (ISSUE 10): syncs one "
        "dispatched group's result scalars; the buffer is already "
        "uploaded, so nothing starves behind the sync",
    # Checkpoint harvest: device->host shard copies on the save path
    # run on the caller thread by design (PR 5's async protocol).
    "glint_word2vec_tpu/parallel/engine.py::"
    "EmbeddingEngine._iter_owned_blocks":
        "checkpoint harvest seam: device->host copies of the owned "
        "table blocks",
    # Corpus staging + compaction: upload is host->device staging, the
    # compaction sync is stall-accounted and overlapped by prefetch.
    "glint_word2vec_tpu/parallel/engine.py::EmbeddingEngine.upload_corpus":
        "host->device corpus staging; np.asarray here normalizes host "
        "input, the device transfer is the put",
    "glint_word2vec_tpu/parallel/engine.py::EmbeddingEngine.compact_corpus":
        "subsample-compaction readback seam: the n_kept sync is "
        "stall-accounted and prefetch-overlapped (PR 5)",
    "glint_word2vec_tpu/parallel/engine.py::"
    "EmbeddingEngine.prefetch_compact_corpus":
        "async twin of compact_corpus: dispatches next epoch's "
        "compaction, harvest deferred to adoption",
    "glint_word2vec_tpu/parallel/engine.py::"
    "EmbeddingEngine.compacted_offsets":
        "compaction offsets readback: host accounting needs the "
        "compacted offsets once per epoch",
    # Checkpoint snapshot seams: device->host table copies on the save
    # path, by design on the calling thread (PR 5's async protocol).
    "glint_word2vec_tpu/parallel/engine.py::EmbeddingEngine._snapshot_host":
        "checkpoint harvest seam: device->host copy of tables + counts "
        "before handing off to the writer",
    "glint_word2vec_tpu/parallel/engine.py::EmbeddingEngine._save_multihost":
        "legacy multihost in-place checkpoint harvest: per-process "
        "device->host shard copies",
    # Serving query ops return host values to HTTP clients — the
    # dispatch IS the sync, coalesced and warmed upstream (PR 2).
    "glint_word2vec_tpu/parallel/engine.py::EmbeddingEngine.multiply":
        "serving query op: stages the host query vector and returns "
        "host scores by contract",
    "glint_word2vec_tpu/parallel/engine.py::EmbeddingEngine.top_k_cosine":
        "serving query op: returns host (vals, ids) by contract",
    "glint_word2vec_tpu/parallel/engine.py::"
    "EmbeddingEngine.top_k_cosine_batch":
        "serving query op: returns host (vals, ids) by contract",
    # The model query surface: host numpy out by contract (PR 2 warms
    # and buckets the device dispatches underneath).
    "glint_word2vec_tpu/models/word2vec.py::Word2VecModel._decode_hits":
        "serving surface: decodes device top-k hits into host "
        "(word, score) pairs",
    "glint_word2vec_tpu/models/word2vec.py::"
    "Word2VecModel.find_synonyms_vector":
        "model query surface: stages the host query vector, returns "
        "host (word, score) pairs",
    "glint_word2vec_tpu/models/word2vec.py::"
    "Word2VecModel.top_k_batch":
        "model query surface (find_synonyms_batch up to its decode): "
        "stages host query vectors, returns host scores and ids",
    "glint_word2vec_tpu/models/word2vec.py::Word2VecModel.transform":
        "model query surface: returns host vector by contract",
    "glint_word2vec_tpu/models/word2vec.py::"
    "Word2VecModel.transform_sentences":
        "model query surface: returns host vectors by contract",
    "glint_word2vec_tpu/models/word2vec.py::Word2VecModel.transform_words":
        "model query surface: returns host vectors by contract",
    "glint_word2vec_tpu/models/word2vec.py::"
    "Word2VecModel.transform_packed":
        "bulk-transform hot path (ISSUE 17): harvests one packed "
        "pull_average block to host vectors by contract — the batch "
        "pipeline's only device sync",
    "glint_word2vec_tpu/models/word2vec.py::Word2VecModel.get_vectors":
        "model export surface: pulls the table to host by contract",
    "glint_word2vec_tpu/models/word2vec.py::Word2VecModel.to_local":
        "model export surface: materializes a host-numpy local model",
    "glint_word2vec_tpu/models/word2vec.py::"
    "LocalWord2VecModel.find_synonyms_vector":
        "local numpy model: every value is already host",
    # ANN index lifecycle seams (ISSUE 12): builds and incremental
    # re-bucketing run OFF the request path by contract (boot, the
    # hot-swap staging thread, or a streaming promotion burst) — the
    # assignment readbacks and host member packing are the design.
    "glint_word2vec_tpu/ops/ann.py::build":
        "index build seam: k-means assignment readbacks + host member "
        "packing, off the request path (boot / hot-swap staging)",
    "glint_word2vec_tpu/ops/ann.py::add_rows":
        "incremental re-bucket seam: score readback for only the "
        "touched rows (streaming promotions), off the request path",
    "glint_word2vec_tpu/ops/ann.py::_pack_members":
        "host member packing invoked only from the build seam: every "
        "value is a host numpy scalar by then",
    "glint_word2vec_tpu/ops/ann.py::_drop_row":
        "host member-layout bookkeeping: slot ids are host numpy ints",
    "glint_word2vec_tpu/ops/ann.py::remove_rows":
        "host member-layout bookkeeping: freed row ids arrive as host "
        "ints from the engine",
    "glint_word2vec_tpu/parallel/engine.py::"
    "EmbeddingEngine.ann_top_k_batch":
        "serving query op: returns host (vals, ids) by contract, the "
        "approximate twin of top_k_cosine_batch",
    "glint_word2vec_tpu/parallel/engine.py::"
    "EmbeddingEngine.ann_recall_at_k":
        "recall-gate seam: compares exact vs approximate host id sets "
        "at build/refresh time, off the request path",
    # Replica-exchange seams (ISSUE 15): a reconciliation round IS a
    # sync point by design — the harvest brings the fixed-capacity
    # payload buffers to host for the cross-rank transport, and the
    # protocol drivers shuffle host numpy throughout.
    "glint_word2vec_tpu/parallel/exchange.py::ReplicaExchanger.harvest":
        "exchange harvest seam: the padded (ids, deltas) buffers must "
        "reach host for the cross-rank transport",
    "glint_word2vec_tpu/parallel/exchange.py::"
    "ReplicaExchanger._dense_delta":
        "dense/spill harvest seam: the full per-rank delta is by "
        "definition a host wire payload",
    "glint_word2vec_tpu/parallel/exchange.py::ReplicaExchanger.sync":
        "the exchange round itself: a deliberate reconciliation "
        "barrier between dispatch groups (headers and payloads are "
        "host numpy)",
    "glint_word2vec_tpu/parallel/exchange.py::"
    "ReplicaExchanger._twolevel_round":
        "level-1/level-2 legs of the sync seam (ISSUE 16): node fold "
        "and leader payloads are host wire traffic of the same "
        "reconciliation barrier",
    "glint_word2vec_tpu/parallel/exchange.py::sync_group":
        "in-process N-replica exchange driver (tests/harness): same "
        "reconciliation barrier as ReplicaExchanger.sync",
    "glint_word2vec_tpu/parallel/exchange.py::NullTransport.allgather":
        "1-replica transport: wraps an already-host payload",
    "glint_word2vec_tpu/parallel/exchange.py::"
    "ProcessTransport.allgather":
        "cross-process transport: process_allgather returns host "
        "arrays by contract",
    "glint_word2vec_tpu/parallel/distributed.py::allgather_host":
        "host-level collective wire of the replica exchange: input and "
        "output are host numpy by contract",
    "glint_word2vec_tpu/parallel/engine.py::"
    "EmbeddingEngine._iter_owned_block_producers":
        "checkpoint harvest seam (shard-streaming form of "
        "_iter_owned_blocks): each producer copies exactly one owned "
        "block to host for the writer",
}

#: Expression roots that are host values by construction — calling
#: float()/int() on them synchronizes nothing.
HOST_ROOTS = frozenset({
    "os", "time", "len", "sys", "math", "random", "args", "json", "re",
    "str", "repr", "round", "min", "max", "sum", "abs", "sorted", "ord",
    "int", "float", "bool", "env", "environ",
})

_CAST_CALLS = ("float", "int", "np.asarray", "numpy.asarray",
               "np.array", "numpy.array")

_FORCED_SYNCS = ("jax.device_get", "jax.block_until_ready")


def _is_candidate_arg(arg: ast.AST) -> bool:
    """Could this expression hold a device value? Literals and
    host-rooted chains cannot."""
    if isinstance(arg, ast.Constant):
        return False
    if isinstance(arg, (ast.JoinedStr, ast.Compare, ast.BoolOp)):
        return False  # strings and python bools are host values
    root = root_name(arg)
    if root is not None and root in HOST_ROOTS:
        return False
    if isinstance(arg, ast.BinOp):
        # A binop of two non-candidates is a non-candidate.
        return _is_candidate_arg(arg.left) or _is_candidate_arg(arg.right)
    return True


@checker(RULE,
         "host<->device syncs (float()/int()/np.asarray on device "
         "values, .block_until_ready(), jax.device_get) only in the "
         "blessed harvest/readback seams")
def check_sync_point(cache: ModuleCache) -> List[Finding]:
    findings: List[Finding] = []
    for mod in cache.modules():
        if mod.tree is None:
            continue
        if not mod.rel.startswith("glint_word2vec_tpu/"):
            continue  # scripts/bench measure by syncing — see docstring
        if "jax" not in mod.imports():
            continue
        enclosing = enclosing_map(mod.tree)

        def in_seam(node: ast.AST) -> bool:
            qn = enclosing.get(id(node), "")
            while qn:
                if SYNC_SEAMS.get(f"{mod.rel}::{qn}") is not None:
                    return True
                qn = qn.rsplit(".", 1)[0] if "." in qn else ""
            return False

        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node) or ""
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "block_until_ready":
                if not in_seam(node):
                    findings.append(mod.finding(
                        RULE, node,
                        ".block_until_ready() outside a blessed seam "
                        "serializes the dispatch pipeline",
                        hint="defer the sync into a harvest seam, or "
                             "bless this function in SYNC_SEAMS with "
                             "its reason",
                    ))
                continue
            if name in _FORCED_SYNCS:
                if not in_seam(node):
                    findings.append(mod.finding(
                        RULE, node,
                        f"{name}() outside a blessed seam forces a "
                        f"device->host transfer",
                        hint="harvest through the deferred-readback "
                             "seam instead",
                    ))
                continue
            if name in _CAST_CALLS and node.args:
                if name in ("float", "int") and (
                        len(node.args) != 1 or node.keywords):
                    # int(s, 16) / float(x, ...) forms are string
                    # parses, never device syncs.
                    continue
                # np.asarray/np.array keep their dtype arg/kwarg — the
                # first positional is the (possibly device) value.
                if not _is_candidate_arg(node.args[0]):
                    continue
                if in_seam(node):
                    continue
                findings.append(mod.finding(
                    RULE, node,
                    f"{name}() on a possibly-device value outside a "
                    f"blessed seam is an implicit sync",
                    hint="if the value is host-only, add `# graftlint: "
                         "ignore[sync-point] <why>`; if it is a device "
                         "value, harvest it in a seam",
                ))
    return findings
