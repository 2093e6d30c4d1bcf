"""Persistent model serving: the separate-PS-cluster deployment, restated.

The reference's second deployment topology keeps a Glint parameter-server
cluster alive independently of any one training/serving app
(README.md:45-57: `glint.Main` launched standalone; trainers and
transformers connect by host and come and go; the cluster survives
`model.stop()` unless a client passes ``terminateOtherClients=true``,
mllib:664-667). The TPU-native restatement: the model lives in one serving
process's device memory, exposed over HTTP; client apps (trainers, batch
jobs, notebooks) query it without loading the tables themselves, and their
lifecycles don't affect it.

Endpoints (JSON in/out, stdlib-only server):

  GET  /healthz            -> {"status": "ok", "vocab_size": V, "dim": d,
                               "compiles": n, "post_warmup_compiles": n, ...}
  GET  /metrics            per-endpoint latency histograms (p50/p95/p99),
                           coalesced-batch-size distribution, compile counts
  GET  /metrics?format=prometheus
                           the same snapshot as Prometheus text exposition
                           (scrape-ready; JSON stays the default)
  POST /synonyms           {"word": w, "num": k}
  POST /synonyms_vector    {"vector": [...], "num": k}
  POST /analogy            {"positive": [...], "negative": [...], "num": k}
  POST /vector             {"word": w}            (word-level family:
                           strict, OOV -> 404; subword family: an OOV
                           word's vector is its n-gram rows' mean, 404
                           only where it is too short for any n-gram)
  POST /transform          {"sentences": [[w, ...], ...]}  (OOV dropped,
                           in both families)
  POST /shutdown           stops the server (the terminateOtherClients
                           analogue: an explicit, remote, cross-client kill)
  POST /reload             hot-swap the served tables to a published
                           generation: {"dir": GEN_DIR} loads that
                           directory; {} polls the --watch-checkpoint
                           publish dir immediately

Hot-swap (ISSUE 10): a :class:`SnapshotWatcher` polls a streaming
trainer's publish directory (``LATEST.json``, streaming/publish.py) and
flips each new generation into the live engine. Staging — disk reads,
integrity verification, building the re-sharded device arrays — runs
entirely OFF the request path (``EmbeddingEngine.stage_tables``); the
flip itself (``adopt_tables`` + the vocabulary swap) happens under the
device lock, so every in-flight dispatch drains against the tables it
started with and no response ever mixes generations. The flip ticks
``table_version``, emptying the synonym result cache wholesale, and the
swapped tables have the same shapes as the old ones, so every warmed
compiled program is reused — zero post-warmup compiles across swaps.

Every device dispatch on the hot path belongs to a small, pre-warmed
shape family: coalesced batches pad to power-of-two Q buckets (capped at
``max_batch``), top-k requests round up to k buckets
(engine.TOPK_MIN_K_BUCKET), the approximate path's coalesced word pull
chunks at ``MAX_QUERY_ROWS`` exactly like ``transform_words``, and
``ModelServer``
compiles the whole family BEFORE binding the port — so the first real
request (and every later one inside the family) never pays a jit compile.

Synonym queries that miss the result cache are coalesced
(:class:`_SynonymCoalescer`): one request at a time leads a round, drains
what is pending the moment the device is free, and names the leader of
the next round from what arrived meanwhile. No round waits for
stragglers: a round's time is flat in its batch (the score pass over the
whole table), so the round in flight is the batching window, and a
waiter waits on its answer, never on the device lock.

Both model families ride that path. A word-level model's query vector is
a row of its table. A subword (fastText) model's is composed: a
dictionary word's from the composed word table (``FastTextModel.
_query_engine``, built before the port binds and composed anew once the
training tables' ``table_version`` has moved), a word outside the
dictionary from its character n-grams' bucket rows, hashed on the host
and averaged by one bucketed ``pull_average`` inside the coalesced round
(span ``req.compose``); its compose buckets are warmed with the rest.

Overload protection (ISSUE 7): device-touching requests are bounded by
an admission high-water mark (shed with 429 + ``Retry-After`` past
``max_inflight``), carry a per-request deadline answered with 504
instead of occupying a dispatch slot, and while the device lock is held
past ``degraded_after`` the server runs a degraded cache-only mode —
cache hits served, misses shed with 429. Shed/deadline/degraded
counters are on ``/metrics`` in both renderers.

Multi-model serving (ISSUE 20): one process hosts N models behind one
port through a :class:`ModelCatalog`. Every endpoint takes a model id
— a ``/m/<id>/`` path prefix or the ``X-Glint-Model`` header; neither
routes to the default model, so every pre-catalog client keeps
working unchanged. Each entry owns its result cache, metrics (+SLO
engine), and publish watcher; the compiled program family is
process-level and shape-keyed (parallel/engine ``_QUERY_MEMO``), so a
same-(V, d) second model warms with ZERO new XLA compiles. With
``--model-memory-budget`` set, cold models LRU stage-out to their
committed host snapshots (``release_tables``) and stage back in
through ``stage_tables`` off the request path on first miss —
requests to a staging model queue behind the bounded stage-in and are
answered from the new tables, never a 5xx.

Start from the CLI:  glint-word2vec-tpu serve --model DIR --port 8801
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import sys
import threading
import time
from collections import OrderedDict
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, urlparse

import numpy as np

from glint_word2vec_tpu.obs import events as obs_events
from glint_word2vec_tpu.obs.prometheus import serving_to_prometheus
from glint_word2vec_tpu.obs.slo import (
    FlightRecorder,
    ShedBurstDetector,
    SloEngine,
)
from glint_word2vec_tpu.utils import faults, next_pow2
from glint_word2vec_tpu.utils.metrics import ServingMetrics

logger = logging.getLogger(__name__)

#: Endpoints whose requests touch the device (or wait on the device
#: lock) — the population the admission bound, per-request deadlines,
#: and degraded mode govern. /healthz, /metrics, /shutdown stay exempt:
#: an overloaded server must still be probeable and stoppable.
_DEVICE_PATHS = frozenset(
    ("/synonyms", "/synonyms_vector", "/analogy", "/vector", "/transform")
)

#: Model id every request without an explicit id routes to — the whole
#: pre-catalog single-model surface (clients, fleet probes, CI smokes)
#: keeps working unchanged against it.
DEFAULT_MODEL_ID = "default"


def split_model_path(path: str, header: Optional[str] = None):
    """Resolve ``(model_id, endpoint_path)`` for one request (ISSUE 20).

    A ``/m/<id>/<endpoint>`` path prefix wins; otherwise the
    ``X-Glint-Model`` header names the model; otherwise ``model_id`` is
    None (the default model). The returned endpoint path is what
    routing, metrics keys, and the admission population see — so
    ``/m/a/synonyms`` and a header-addressed ``/synonyms`` land in the
    same per-model histogram bucket."""
    if path.startswith("/m/"):
        sep = path.find("/", 3)
        if sep < 0:
            return (path[3:] or None), "/"
        return (path[3:sep] or None), (path[sep:] or "/")
    return (header or None), path


def parse_memory_budget(value) -> Optional[int]:
    """``--model-memory-budget`` parser: plain bytes, or a
    kb/mb/gb-suffixed size ("512mb", "1.5gb"). None/empty/0 disables
    the budget (every model stays resident)."""
    if value is None:
        return None
    if isinstance(value, (int, float)):
        n = int(value)
        return n if n > 0 else None
    s = str(value).strip().lower()
    if not s:
        return None
    mult = 1
    for suffix, m in (
        ("kb", 1 << 10), ("mb", 1 << 20), ("gb", 1 << 30),
        ("k", 1 << 10), ("m", 1 << 20), ("g", 1 << 30), ("b", 1),
    ):
        if s.endswith(suffix):
            s = s[: -len(suffix)]
            mult = m
            break
    n = int(float(s) * mult)
    return n if n > 0 else None


class DeadlineExceeded(Exception):
    """A request's deadline passed before (or while) it could reach the
    device — answered 504 so the client's own timeout budget, not the
    server's queue depth, bounds its wait."""


class _TrackedLock:
    """``threading.Lock`` that remembers when it was acquired, so the
    overload layer can observe "the device has been busy for X seconds"
    without instrumenting every dispatch site. API-compatible with the
    plain lock for ``with`` use; ``acquire`` grows a timeout."""

    __slots__ = ("_lock", "_held_since")

    def __init__(self):
        self._lock = threading.Lock()
        self._held_since: Optional[float] = None

    def acquire(self, timeout: Optional[float] = None) -> bool:
        if timeout is None:
            ok = self._lock.acquire()
        else:
            ok = self._lock.acquire(timeout=max(0.0, timeout))
        if ok:
            self._held_since = time.monotonic()
        return ok

    def release(self) -> None:
        self._held_since = None
        self._lock.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def held_for(self) -> float:
        """Seconds the lock has been continuously held; 0.0 when free.
        Reads a single attribute — safe (and deliberately lock-free)
        from any thread; a racing release just reads as 0.0."""
        hs = self._held_since
        return 0.0 if hs is None else time.monotonic() - hs


def _pull_coalesced(engine, idx: np.ndarray) -> np.ndarray:
    """Pull word rows for a coalesced batch through the same
    ``MAX_QUERY_ROWS`` chunking ``transform_words`` uses (the coalescer
    used to bypass it entirely — an unbounded HBM spike under a giant
    burst, ADVICE.md round 5), with each chunk padded to its
    power-of-two bucket (row-0 padding, sliced off) so concurrency
    jitter never compiles a fresh pull shape."""
    from glint_word2vec_tpu.models import word2vec as _w2v

    out = np.empty((idx.shape[0], engine.dim), np.float32)
    mqr = _w2v.MAX_QUERY_ROWS
    for s in range(0, idx.shape[0], mqr):
        sub = idx[s : s + mqr]
        n = sub.shape[0]
        n_b = next_pow2(n)
        if n_b != n:
            sub = np.concatenate([sub, np.zeros(n_b - n, np.int32)])
        out[s : s + n] = np.asarray(engine.pull(sub), np.float32)[:n]
    return out


class _SynonymCoalescer:
    """Leader-elected micro-batching for the synonym endpoints.

    Device queries are serialized by the server lock, so under N
    concurrent clients each /synonyms request used to wait for N-1
    single-query dispatches (QPS flat in N). Here every waiting request
    lands in a pending list and waits on its own event; ONE of them at a
    time leads: it takes the device lock, drains the list, answers ALL
    of them with ONE ``top_k_batch`` dispatch (``find_synonyms_batch`` up
    to its decode, which the round does itself) per ``max_batch`` chunk
    (the batch top-k the reference lacks — it loops findSynonyms,
    ml:375-420; a dictionary word rides it as its row id, which the
    program gathers for itself, so no row visits the host), and wakes
    the waiters. Exclusion semantics match find_synonyms exactly (fetch
    num+1, drop the query word, truncate). Dispatches are shape-bucketed:
    the engine pads Q to powers of two and rounds k up to its bucket, so
    every chunk reuses a pre-warmed compiled program.

    The hand-off between rounds (ISSUE 53). The round in flight IS the
    batching window: a leader drains what is pending the moment it holds
    the device, with no wait for stragglers, and whatever arrives during
    its round rides the next one. The leader is chosen under ``_mu``,
    never by a race for the device lock: a miss that finds nobody
    leading leads; a leader that ends its round with requests pending
    names one of them leader (wakes that thread alone to lead) and
    returns to its own caller. So a leader never runs a second round
    with its own answer in hand, and an answered waiter never touches
    the device lock: one acquisition a round. What the policy rests on:
    a round's time is flat in its batch (on the chip it is the score
    pass over the whole table, at Q = 1 or 16 alike), so a request that
    misses a drain waits at most one round, and holding a round back to
    fill it costs everybody more than it spares the one. Where a
    round's cost does grow with its batch (a CPU back end; a table small
    enough that the top-k and not the score pass sets the time) rounds
    that queue behind a busy device still fill by themselves.

    Two families batch. The base word-level family: a query word's
    vector is its row of the table. The subword family
    (``FastTextModel``, ``self.composes``): a dictionary word's vector is
    its row of the COMPOSED table and its neighbours are scored against
    that table; a word outside the dictionary is composed inside the
    round from its n-gram rows (``req.compose``) and excludes nothing.
    Any other subclass overriding ``find_synonyms``/
    ``find_synonyms_vector``/``transform`` keeps its own semantics via
    the single-query path.
    """

    def __init__(self, model, device_lock, max_batch: int = 64,
                 metrics: Optional[ServingMetrics] = None,
                 cache_size: int = 65536):
        from glint_word2vec_tpu.models.fasttext import FastTextModel
        from glint_word2vec_tpu.models.word2vec import Word2VecModel

        self.model = model
        self.device_lock = device_lock
        #: Device-dispatch cap: a drained pending list larger than this
        #: is served in max_batch-sized chunks. Rounded up to a power of
        #: two so chunk shapes coincide with the warmed Q buckets.
        self.max_batch = next_pow2(max(1, int(max_batch)))
        self.metrics = metrics
        self._mu = threading.Lock()
        self._pending: list = []
        #: The request that leads now or has been named to lead next
        #: (under ``_mu``); None where nobody does and the next miss
        #: leads its own round.
        self._leader: Optional[dict] = None
        #: Bounded (word, num) -> result cache for the base word family.
        #: Synonym traffic over a vocabulary is zipfian, so a hot set a
        #: tiny fraction of vocab_size absorbs most of the load without
        #: a device dispatch; entries are validated against the engine's
        #: ``table_version`` so any table mutation (a training step, a
        #: push, set_tables) empties it wholesale. FIFO eviction at
        #: ``cache_size`` entries (0 disables). Word queries only — the
        #: raw-vector endpoint has no hashable hot key.
        self.cache_size = max(0, int(cache_size))
        self._cache: dict = {}
        self._cache_version = None
        #: Mode supplier installed by the server once the ANN index is
        #: built and gated: True = default requests ride the
        #: approximate path. Requests carrying ``exact=true`` always
        #: take the exact path (the escape hatch); cache keys carry
        #: the mode so the two paths never serve each other's results.
        self.ann_active = lambda: False
        #: nprobe the server resolved for the approximate path (for
        #: the probes/query accounting).
        self.ann_nprobe = 0
        #: True while an index EXISTS but the recall gate is holding
        #: the approximate path back — those exact serves are counted
        #: as gate fallbacks, not user-requested ones.
        self.gate_failing = lambda: False
        #: The subword family, decided once: a round composes the
        #: vectors of its out-of-dictionary words (``compose_oov``) and
        #: pulls and scores against ``model._query_engine()``. Its
        #: ``transform`` is the one override the round reproduces.
        self.composes = (
            isinstance(model, FastTextModel)
            and type(model).transform is FastTextModel.transform
        )
        self.can_batch = (
            isinstance(model, Word2VecModel)
            and type(model).find_synonyms is Word2VecModel.find_synonyms
            # A family overriding only the vector endpoint must not be
            # silently served base batched top-k (ADVICE.md round 5).
            and type(model).find_synonyms_vector
            is Word2VecModel.find_synonyms_vector
            and (
                type(model).transform is Word2VecModel.transform
                or self.composes
            )
        )

    def _acquire_device(self, deadline: Optional[float]) -> bool:
        """Take the device lock, bounded by the request deadline: a
        request that cannot reach the device in time must answer 504
        WITHOUT ever occupying a dispatch slot."""
        if deadline is None:
            return self.device_lock.acquire()
        return self.device_lock.acquire(
            timeout=max(0.0, deadline - time.monotonic())
        )

    def cache_lookup(self, word, num, exact: bool = False):
        """Result-cache probe with NO device work — the degraded
        cache-only mode's read path. Returns the cached hit list or
        None; never blocks on the device lock."""
        if word is None or not self.cache_size:
            return None
        mode = "exact" if (exact or not self.ann_active()) else "ann"
        with self._mu:
            self._cache_sync_locked()
            return self._cache.get((word, int(num), mode))

    def query(self, word=None, vector=None, num: int = 10,
              deadline: Optional[float] = None, exact: bool = False,
              trace=None):
        tr = trace if trace is not None else obs_events.NULL_TRACE
        if not self.can_batch:
            # Overriding families define their own semantics end to end
            # (their own vectors, their own num validation).
            with tr.phase("req.queue"):
                acquired = self._acquire_device(deadline)
            if not acquired:
                raise DeadlineExceeded("deadline waiting for device")
            try:
                with tr.phase("req.query", mode="exact"):
                    if word is not None:
                        return self.model.find_synonyms(word, num)
                    return self.model.find_synonyms_vector(vector, num)
            finally:
                self.device_lock.release()
        if num <= 0:
            # Exact single-query behavior for the base family.
            # find_synonyms(w, num): transform(w) runs FIRST (OOV ->
            # KeyError -> 404), then find_synonyms_vector(vec, num+1)
            # raises unless num+1 > 0 — so num=0 with a known word is []
            # (truncation) and num<0 is a 400. The bare vector endpoint
            # always raises on num<=0.
            if word is not None:
                if word not in self.model.vocab.word_index:
                    if not self.composes:
                        raise KeyError(f"word {word!r} not in vocabulary")
                    self.model._oov_group(word)  # too short: KeyError
                if num == 0:
                    return []
            raise ValueError("num must be > 0")
        # Mode resolves ONCE at enqueue (not at dispatch): a gate flip
        # mid-wait must not hand a request a mode its cache key and
        # accounting never saw.
        mode = "exact" if (exact or not self.ann_active()) else "ann"
        if word is not None and self.cache_size:
            with tr.phase("req.lookup") as lookup:
                with self._mu:
                    self._cache_sync_locked()
                    hit = self._cache.get((word, num, mode))
                lookup.update(hit=hit is not None)
            tr.annotate(cache="miss" if hit is None else "hit")
            if self.metrics is not None:
                self.metrics.record_cache(hit is not None)
            if hit is not None:
                return hit
        req = {
            "word": word, "vector": vector, "num": int(num),
            "event": threading.Event(), "result": None, "error": None,
            "deadline": deadline, "abandoned": False,
            # ``lead``: set under ``_mu`` where this request leads a
            # round, by itself on finding nobody leading or by the leader
            # that names it; ``handoff_from``: the perf_counter() at
            # which that leader's round was answered.
            "lead": False, "handoff_from": None,
            "mode": mode, "exact_requested": bool(exact),
            # Tracing (ISSUE 18): the leader stamps dispatch-window
            # perf_counter() pairs onto the dict; THIS waiter thread
            # converts them into queue/query/readback phases below.
            "trace": tr.trace_id if trace is not None else None,
            "t_enq": time.perf_counter(),
        }
        with self._mu:
            self._pending.append(req)
            if self._leader is None:  # nobody leads: this request does
                self._leader, req["lead"] = req, True
        if not req["lead"]:
            # A waiter waits on its answer, never on the device lock.
            # Its event fires for one of two reasons: a leader answered
            # it, or a leader whose round it missed named it to lead
            # the next one (``lead`` set under ``_mu`` first).
            if not req["event"].wait(
                None if deadline is None else deadline - time.monotonic()
            ):
                # Timed out waiting for a leader. Mark the request
                # abandoned AND pull it out of the pending list under
                # the lock, so the list cannot grow without bound while
                # the device is wedged and a leader that does run spends
                # no dispatch work on a client that already got its 504.
                # If the answer, or the lead, landed in the race, take it.
                with self._mu:
                    if not req["event"].is_set():
                        req["abandoned"] = True
                        try:
                            self._pending.remove(req)
                        except ValueError:
                            pass  # a leader already drained it
                if req["abandoned"]:
                    raise DeadlineExceeded("deadline waiting for dispatch")
        if req["lead"]:
            self._lead(req)
        if req.get("t_dis0") is not None:
            # Leader-stamped dispatch window -> this request's phases:
            # queue wait (enqueue to leader drain), the query up to the
            # round's last launch, the read-back and decode of its
            # results, and how long the finished answer then waited for
            # this thread to run.
            t_woken = time.perf_counter()
            tr.add_phase("req.queue", req["t_enq"],
                         req["t_dis0"] - req["t_enq"])
            tr.add_phase("req.query", req["t_dis0"],
                         req["t_rb0"] - req["t_dis0"], mode=mode)
            tr.add_phase("req.readback", req["t_rb0"],
                         req["t_rb1"] - req["t_rb0"])
            if req.get("t_wake") is not None:
                tr.add_phase("req.wake", req["t_wake"],
                             t_woken - req["t_wake"])
        if req["error"] is not None:
            raise req["error"]
        return req["result"]

    def _lead(self, req) -> None:
        """One round, led by ``req``'s thread: the device lock, ONE drain
        of the pending list with no wait before it, ``_process``, and the
        hand-off. ``req`` is still pending (a request leaves the list by
        a drain or by its own abandonment), so the round answers it."""
        if not self._acquire_device(req["deadline"]):
            # The device stayed busy past this request's deadline: its
            # 504, and the lead to whoever is still waiting behind it.
            with self._mu:
                req["abandoned"] = True
                self._pending.remove(req)
                self._name_next_locked(req["handoff_from"])
            raise DeadlineExceeded("deadline waiting for device")
        answered_at = None
        try:
            with self._mu:
                batch, self._pending = self._pending, []
            handoff_ms = None
            if req["handoff_from"] is not None:  # back to back
                handoff_ms = 1e3 * (time.perf_counter() - req["handoff_from"])
            answered_at = self._process(batch, handoff_ms)
        finally:
            with self._mu:
                self._name_next_locked(answered_at)
            self.device_lock.release()

    def _name_next_locked(self, handoff_from: Optional[float]) -> None:
        """Pass the lead on: to the oldest request that arrived during
        this round, whose thread alone is woken to lead the next one; to
        nobody where none is pending. Caller holds ``self._mu``."""
        nxt = self._leader = self._pending[0] if self._pending else None
        if nxt is not None:
            nxt["lead"], nxt["handoff_from"] = True, handoff_from
            nxt["event"].set()

    def _cache_sync_locked(self) -> int:
        """Drop every cached result if the tables moved since they were
        computed; returns the version the cache is now valid for.
        Caller holds ``self._mu``."""
        ver = self.model.engine.table_version
        if ver != self._cache_version:
            self._cache.clear()
            self._cache_version = ver
        return ver

    def _process(self, batch, handoff_ms: Optional[float] = None) -> float:
        """Answer a drained batch and set its events; returns the
        perf_counter() at which it set them. ``handoff_ms``: where the
        round's leader was named by the round before, the ms from that
        round's events being set to this one's drain; it goes on the
        round's first ``req.dispatch``."""
        m = self.model
        live = []
        now = time.monotonic()
        for r in batch:
            # Dead requests first: an abandoned waiter already answered
            # 504, and one whose deadline passed while queued must not
            # consume dispatch work either — its waiter raises
            # DeadlineExceeded from the recorded error.
            if r.get("abandoned"):
                r["event"].set()
                continue
            dl = r.get("deadline")
            if dl is not None and now > dl:
                r["error"] = DeadlineExceeded(
                    "deadline exceeded before dispatch"
                )
                r["event"].set()
                continue
            # Validation failures must fail ONLY their own request: an
            # exception escaping here would strand every co-batched
            # waiter on an event that never fires.
            try:
                if r["word"] is not None:
                    i = m.vocab.word_index.get(r["word"])
                    if i is not None:
                        r["idx"] = i
                    elif not self.composes:
                        raise KeyError(
                            f"word {r['word']!r} not in vocabulary"
                        )
                    # else: the round composes it (``_compose_round``),
                    # and refuses it there alone if it has no n-gram.
                else:
                    v = np.asarray(r["vector"], dtype=np.float32)
                    if v.shape != (m.vector_size,):
                        raise ValueError(
                            f"vector must have shape ({m.vector_size},), "
                            f"got {v.shape}"
                        )
                    r["vec"] = v
            except KeyError as e:
                r["error"] = e
                r["event"].set()
                continue
            except Exception as e:
                # Anything np.asarray can throw on garbage (TypeError,
                # ragged-list ValueError) is a bad request, not a 500.
                r["error"] = ValueError(f"bad vector: {e}")
                r["event"].set()
                continue
            live.append(r)
        try:
            # A drained batch can mix modes (per-request exact=true
            # riding alongside approximate defaults): each mode group
            # is its own dispatch — the approximate and exact programs
            # are different compiled families.
            for mode in ("ann", "exact"):
                group = [r for r in live if r.get("mode", "exact") == mode]
                for s in range(0, len(group), self.max_batch):
                    self._dispatch(
                        group[s : s + self.max_batch], mode, handoff_ms
                    )
                    handoff_ms = None
        except Exception as e:  # pragma: no cover - device failure path
            for r in live:
                if r["error"] is None and r["result"] is None:
                    r["error"] = e
        finally:
            # One stamp a batch: a waiter's ``req.wake`` runs from here
            # to its return from ``event.wait()``.
            t_wake = time.perf_counter()
            for r in live:
                r["t_wake"] = t_wake
                r["event"].set()
        return t_wake

    def _dispatch(self, chunk, mode: str = "exact",
                  handoff_ms: Optional[float] = None) -> None:
        """Answer one <= max_batch slice of the drained batch with one
        bucketed batch top-k dispatch: the exact masked GEMM, which
        gathers its dictionary words' rows itself, or, when ``mode ==
        "ann"``, one bucketed pull and the two-stage coarse+rerank. The
        subword family's out-of-dictionary words add one bucketed
        compose before it."""
        faults.fire("serving.dispatch")
        m = self.model
        # Version BEFORE the reads: if a table mutation lands mid-
        # dispatch these results are from the old tables and must not
        # enter the cache under the new version.
        ver = m.engine.table_version
        # Device lane (ISSUE 18): one always-recorded span per coalesced
        # dispatch (never tail-sampled — a kept request's stitched trace
        # must always show the batch it rode in; the trace ids it
        # carried are on the args).
        t_dis0 = time.perf_counter()
        with obs_events.phase_span(
            "req.dispatch", batch=len(chunk), mode=mode,
            shards=int(getattr(m.engine, "num_model", 1)),
            traces=[r["trace"] for r in chunk if r.get("trace")],
        ) as span:
            if handoff_ms is not None:
                span.update(handoff_ms=handoff_ms)
            # The table a word's row comes from is the table its
            # neighbours are scored against: the training table at word
            # level, the composed one for the subword family (composed
            # anew here, first, if the training tables have moved).
            qeng = m._query_engine()
            engines = {qeng, m.engine}
            launched = sum(e.query_dispatches for e in engines)
            n_words = sum("idx" in r for r in chunk)
            if self.composes:
                chunk = self._compose_round(chunk, n_words)
                if not chunk:
                    return
            # What a request carries decides how it enters the top-k: a
            # dictionary word as its row id, which the exact program
            # gathers for itself (nothing of the row visits the host); a
            # raw vector and a composed one as the vector. The
            # approximate search takes vectors alone, so there the words'
            # rows are pulled first.
            ids = None
            if n_words:
                with obs_events.phase_span("req.pull", rows=n_words):
                    word_ids = np.asarray(
                        [r.get("idx", -1) for r in chunk], np.int32
                    )
                    if mode == "ann":
                        rows = iter(
                            _pull_coalesced(qeng, word_ids[word_ids >= 0])
                        )
                        for r in chunk:
                            if "idx" in r:
                                r["vec"] = next(rows)
                    else:
                        ids = word_ids
            vectors = None
            if ids is None or n_words < len(chunk):
                vectors = np.zeros((len(chunk), m.vector_size), np.float32)
                for row, r in zip(vectors, chunk):
                    if "vec" in r:
                        row[:] = r["vec"]
            k = max(
                r["num"] + (1 if r["word"] is not None else 0)
                for r in chunk
            )
            sims, idx = m.top_k_batch(
                vectors, min(k, m.vocab.size),
                approximate=(mode == "ann"), ids=ids,
            )
            # The read-back began where the batch top-k's launch returned
            # (the approximate search stamps none: there it is the
            # decode alone).
            t_rb0 = qeng.query_enqueued_at
            if t_rb0 < t_dis0:
                t_rb0 = time.perf_counter()
            with obs_events.phase_span("req.decode", batch=len(chunk)):
                for r, sc, ix in zip(chunk, sims, idx):
                    hs = m._decode_hits(sc, ix)
                    if r["word"] is not None:
                        hs = [(w, s) for w, s in hs if w != r["word"]]
                    r["result"] = hs[: r["num"]]
            t_rb1 = time.perf_counter()
            span.update(
                programs=sum(e.query_dispatches for e in engines) - launched
            )
        if self.metrics is not None:
            self.metrics.record_batch(len(chunk))
            if mode == "ann":
                self.metrics.record_ann_query(len(chunk), self.ann_nprobe)
            elif self.ann_active() or self.gate_failing():
                # Attribute per REQUEST, not from dispatch-time global
                # state: an explicit exact=true is the escape hatch
                # ("requested") even while the gate is failing; only
                # defaults held back BY the gate count as "gate".
                n_req = sum(
                    1 for r in chunk if r.get("exact_requested")
                )
                if n_req:
                    self.metrics.record_exact_fallback(
                        n_req, "requested"
                    )
                if len(chunk) - n_req and self.gate_failing():
                    self.metrics.record_exact_fallback(
                        len(chunk) - n_req, "gate"
                    )
        for r in chunk:
            # Dispatch-window stamps the waiter threads convert into
            # their own queue/query/readback phases (single-writer per
            # trace: only the owning waiter touches its RequestTrace).
            r["t_dis0"], r["t_rb0"], r["t_rb1"] = t_dis0, t_rb0, t_rb1
        if self.cache_size:
            with self._mu:
                if self._cache_sync_locked() != ver:
                    return  # mutated mid-dispatch: results are stale
                for r in chunk:
                    if r["word"] is not None:
                        while len(self._cache) >= self.cache_size:
                            self._cache.pop(next(iter(self._cache)))
                        self._cache[
                            (r["word"], r["num"], mode)
                        ] = r["result"]

    def _compose_round(self, chunk, n_dictionary: int):
        """The subword family's share of a round: the vectors of the
        chunk's out-of-dictionary words, hashed on the host and averaged
        from ``syn0`` by ONE bucketed ``pull_average``. A word too short
        for any n-gram takes its KeyError (-> 404) alone; returns the
        requests that go on to the top-k."""
        oov = [r for r in chunk if r["word"] is not None and "idx" not in r]
        if not oov:
            return chunk
        with obs_events.phase_span(
            "req.compose", words=n_dictionary, oov=len(oov)
        ) as span:
            vectors, errors, slots, rows = self.model.compose_oov(
                [r["word"] for r in oov]
            )
            span.update(slots=slots, rows=rows)
        for r, v, e in zip(oov, vectors, errors):
            if e is None:
                r["vec"] = v
            else:
                r["error"] = e
        if self.metrics is not None:
            self.metrics.record_compose(len(oov), slots, rows)
        return [r for r in chunk if r["error"] is None]


class SnapshotWatcher:
    """Background poller that follows a publish directory's
    ``LATEST.json`` pointer (streaming/publish.py) and hot-swaps each
    new generation into the live server.

    The pointer is only ever flipped AFTER a generation's atomic
    commit, so the watcher can never observe a partial snapshot — and
    staging verifies the matrix manifest besides, so a corrupt
    generation is a counted ``swap_failure`` (the previous tables stay
    live), never a bad serve. A failed generation is not retried until
    the pointer moves again.

    Transient storage trouble is NOT failure: a pointer or
    generation-dir read error (mid-rename visibility on a network
    filesystem, an NFS attribute-cache hiccup) backs off with a capped
    doubling delay and retries on a later poll — counted as
    ``watch_errors`` on ``/metrics`` — instead of either stalling the
    watcher thread or permanently skipping a generation that is in
    fact committed and fine."""

    #: Transient-error backoff ceiling (seconds).
    BACKOFF_CAP = 30.0
    #: Consecutive polls a referenced generation directory may be
    #: invisible before it is branded failed: on network filesystems
    #: the directory rename's visibility can lag the pointer flip by a
    #: beat (transient — retried with backoff), while an operator
    #: deletion stays missing forever (permanent after the strikes).
    MISSING_DIR_STRIKES = 2
    #: Consecutive transient staging read errors (OSError inside an
    #: EXISTING generation dir) tolerated for one generation before it
    #: too is branded failed: storage hiccups clear within a few
    #: backed-off polls; a permanently unreadable file (deleted shard,
    #: permissions) does not, and must not retry forever.
    STAGING_ERROR_STRIKES = 5

    def __init__(self, server: "ModelServer", watch_dir: str,
                 poll_seconds: float = 1.0,
                 model_id: Optional[str] = None):
        self.server = server
        self.watch_dir = watch_dir
        #: Which catalog entry this watcher swaps (None = the default
        #: model): one model's pointer move rolls ONLY that model, and
        #: its swap/watch-error counters land on that model's metrics.
        self.model_id = model_id
        self.poll_seconds = max(0.05, float(poll_seconds))
        #: Current transient-error backoff (seconds; 0 while healthy —
        #: doubles per consecutive error up to BACKOFF_CAP, resets on
        #: any successful poll).
        self._backoff = 0.0
        #: monotonic time before which polls are skipped (backoff).
        self._retry_at = 0.0
        #: (generation, consecutive polls its dir was missing).
        self._missing = (None, 0)
        #: (generation, consecutive transient staging read errors).
        self._stage_errs = (None, 0)
        #: Generation name currently served (watcher-thread written;
        #: /reload reads it for its "unchanged" answer — a stale read
        #: only costs one redundant poll).
        self.current: Optional[str] = None
        #: Last generation that failed staging — not retried until the
        #: pointer names a different one.
        self._failed: Optional[str] = None
        #: Serializes polls between the watcher thread and POST
        #: /reload request threads: without it both could stage the
        #: same generation (duplicate disk reads + device transfers)
        #: and adopt it twice, double-counting table_swaps.
        self._poll_mu = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @property
    def metrics(self) -> ServingMetrics:
        """The watched model's own metrics — per-model swap and
        watch-error counters (ISSUE 20). Servers without a catalog
        (duck-typed test stands-ins) expose ``.metrics`` directly."""
        lookup = getattr(self.server, "_entry", None)
        if lookup is None:
            return self.server.metrics
        return lookup(self.model_id).metrics

    def poll_once(self) -> Optional[str]:
        """One pointer check; returns the generation name when a swap
        happened, else None. Never raises — failures are logged and
        counted on the serving metrics."""
        with self._poll_mu:
            return self._poll_once_locked()

    def _poll_once_locked(self) -> Optional[str]:
        from glint_word2vec_tpu.streaming.publish import read_latest

        if time.monotonic() < self._retry_at:
            return None  # backing off after a transient read error
        try:
            latest = read_latest(self.watch_dir, raise_errors=True)
        except (OSError, ValueError) as e:
            return self._watch_error_locked(f"unreadable pointer: {e}")
        if latest is None:
            self._backoff = 0.0
            return None
        gen = str(latest["generation"])
        if gen == self.current or gen == self._failed:
            self._backoff = 0.0
            return None
        gen_dir = os.path.join(self.watch_dir, gen)
        if not os.path.isdir(gen_dir):
            mgen, n = self._missing
            n = n + 1 if mgen == gen else 1
            self._missing = (gen, n)
            if n < self.MISSING_DIR_STRIKES:
                # First miss(es): rename-visibility lag on a network
                # filesystem looks exactly like this — back off and
                # look again before condemning the generation.
                return self._watch_error_locked(
                    f"referenced generation {gen} not visible yet "
                    f"(miss {n}/{self.MISSING_DIR_STRIKES})"
                )
            # Still missing after the strikes: an operator deletion —
            # branded failed and not retried until the pointer moves
            # (the PR 10 contract).
            logger.error(
                "hot-swap of %s failed: generation directory missing "
                "after %d polls", gen, n,
            )
            self.metrics.record_swap(gen, ok=False)
            self._failed = gen
            return None
        self._missing = (None, 0)
        try:
            kwargs = {"generation": gen}
            if self.model_id is not None:
                kwargs["model_id"] = self.model_id
            self.server.reload_generation(gen_dir, **kwargs)
        except OSError as e:
            # The directory EXISTS but a read inside it failed: the
            # pointer only ever names committed generations, so this
            # is transient storage trouble (mid-rename visibility, an
            # NFS attribute-cache hiccup) — back off and retry the
            # poll. Only a sustained run of read errors on the same
            # generation brands it failed (a permanently unreadable
            # file is not a hiccup).
            sgen, n = self._stage_errs
            n = n + 1 if sgen == gen else 1
            self._stage_errs = (gen, n)
            if n >= self.STAGING_ERROR_STRIKES:
                logger.error(
                    "hot-swap of %s failed: %d consecutive staging "
                    "read errors (%s)", gen, n, e,
                )
                self.metrics.record_swap(gen, ok=False)
                self._failed = gen
                return None
            return self._watch_error_locked(
                f"transient read error staging {gen}: {e} "
                f"(strike {n}/{self.STAGING_ERROR_STRIKES})"
            )
        except Exception as e:
            logger.error("hot-swap of %s failed: %s", gen, e)
            self.metrics.record_swap(gen, ok=False)
            self._failed = gen
            return None
        self.current = gen
        self._failed = None
        self._backoff = 0.0
        self._stage_errs = (None, 0)
        return gen

    def _watch_error_locked(self, msg: str) -> None:
        """Count one transient publish-dir read failure and arm the
        capped-doubling retry delay; the watcher thread stays live and
        the next eligible poll retries from scratch."""
        self._backoff = min(
            max(self.poll_seconds, self._backoff * 2), self.BACKOFF_CAP
        )
        self._retry_at = time.monotonic() + self._backoff
        self.metrics.record_watch_error()
        logger.warning(
            "snapshot watcher: %s (retrying in %.1fs)", msg, self._backoff
        )
        return None

    def start(self) -> None:
        suffix = f"-{self.model_id}" if self.model_id else ""
        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name=f"glint-snapshot-watcher{suffix}",
        )
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self.poll_seconds):
            self.poll_once()

    def stop(self) -> None:
        self._stop.set()


class ServedModel:
    """One catalog entry (ISSUE 20): a loaded model plus everything the
    server keys PER model — its result-cache coalescer, its
    ServingMetrics (+SLO engine), its publish watcher, and its
    residency state under the device-memory budget. The per-model
    coalescer is what makes a cross-model cache hit structurally
    impossible: each cache validates against its own engine's
    ``table_version`` and is never consulted for another model id."""

    def __init__(self, model_id: str, model, coalescer, metrics,
                 source_dir: Optional[str] = None):
        self.model_id = model_id
        self.model = model
        self.coalescer = coalescer
        self.metrics = metrics
        #: Committed snapshot directory (a loadable model dir) the
        #: entry stages back in from after an eviction; refreshed by
        #: every successful per-model hot-swap.
        self.source_dir = source_dir
        self.watcher: Optional["SnapshotWatcher"] = None
        #: Pin count: a pinned entry is never staged out — the default
        #: model (permanently), a mid-swap generation, a fleet hold or
        #: warm spare (via POST /models/pin).
        self.pins = 0
        #: LRU clock: last request touch (catalog-lock guarded).
        self.last_used = time.monotonic()
        #: Device bytes the tables cost while resident — remembered
        #: across stage-out so the budget can plan the stage-in.
        self.cost_bytes = 0
        #: Serializes stage-in: the first request to a cold model
        #: stages; the rest queue here (bounded by their own deadlines)
        #: and are answered from the newly resident tables.
        self.stage_mu = threading.Lock()
        self.stage_ins = 0
        self.evictions = 0

    @property
    def resident(self) -> bool:
        """Whether the tables are on device right now. Models without
        a stage-out-capable engine always read resident."""
        eng = getattr(self.model, "engine", None)
        return bool(getattr(eng, "tables_resident", True))

    @property
    def evictable(self) -> bool:
        """Only the base word-level family round-trips through
        ``release_tables``/``stage_tables``, and only with a committed
        snapshot to stage back from."""
        from glint_word2vec_tpu.models.word2vec import Word2VecModel

        return (
            self.source_dir is not None
            and type(self.model) is Word2VecModel
        )

    def _engines(self):
        """The entry's engines with tables on the device: the training
        engine and, for the subword family, the composed query engine
        that rests beside it."""
        if not self.resident:
            return []
        engines = (getattr(self.model, "engine", None),
                   getattr(self.model, "_qeng", None))
        return [e for e in engines if hasattr(e, "resident_bytes")]

    def resident_bytes(self) -> int:
        """Device bytes this entry holds right now (0 when staged
        out), the subword family's composed query engine counted beside
        its training tables. The total over ALL the model's devices,
        which is what ``--model-memory-budget`` is held against: a
        model split by rows over four chips counts its whole 30.7 GB,
        not a chip's 7.68 GB (:meth:`resident_bytes_per_device`)."""
        return sum(int(e.resident_bytes()) for e in self._engines())

    def resident_bytes_per_device(self) -> int:
        """What the fullest device holds of :meth:`resident_bytes`."""
        return sum(
            int(e.resident_bytes_per_device()) for e in self._engines()
        )


class ModelCatalog:
    """model-id -> :class:`ServedModel` routing table plus the
    device-memory budget (ISSUE 20).

    All N models share ONE device lock, ONE admission/overload layer,
    and ONE process-level shape-keyed compiled program family
    (parallel/engine ``_QUERY_MEMO`` — loading a same-(V, d) model #2
    builds zero new programs); the catalog adds per-model result
    caches/metrics/watchers and, when ``budget_bytes`` is set, LRU
    stage-out of cold tables to their committed host snapshots.
    Stage-in runs OFF the request path: the winning request stages
    (``stage_tables`` with no lock held, ``adopt_tables`` under the
    device lock), concurrent requests queue behind ``entry.stage_mu``
    bounded by their own deadlines and are answered from the new
    tables — never a 5xx."""

    #: Read-mostly references guarded by insertion discipline rather
    #: than the catalog lock: ``entries`` is only ever grown (install
    #: holds ``_mu``; dict reads are atomic under the GIL and a racing
    #: reader simply sees the catalog before/after the install —
    #: equally correct), ``default_id`` is written once at install
    #: time, and ``budget_bytes`` is a boot-time scalar.
    _ATOMIC_ATTRS = frozenset({"entries", "default_id", "budget_bytes"})

    def __init__(self, server: "ModelServer",
                 budget_bytes: Optional[int] = None):
        self._server = server
        self._mu = threading.Lock()
        self.entries: "OrderedDict[str, ServedModel]" = OrderedDict()
        self.default_id: Optional[str] = None
        self.budget_bytes = budget_bytes
        self.evictions = 0
        self.stage_ins = 0
        self.stage_in_seconds = 0.0
        #: Requests that found their model cold (the eviction-miss
        #: population: each either staged in or queued behind one).
        self.cold_hits = 0

    # -- membership ----------------------------------------------------

    def install(self, entry: ServedModel, default: bool = False) -> None:
        with self._mu:
            if entry.model_id in self.entries:
                raise ValueError(
                    f"model id {entry.model_id!r} already served"
                )
            self.entries[entry.model_id] = entry
            if default or self.default_id is None:
                self.default_id = entry.model_id

    @property
    def default(self) -> ServedModel:
        return self.entries[self.default_id]

    def get(self, model_id: Optional[str]) -> ServedModel:
        """Entry for a model id (None = default); KeyError -> 404."""
        mid = model_id if model_id is not None else self.default_id
        entry = self.entries.get(mid)
        if entry is None:
            raise KeyError(f"unknown model {mid!r}")
        return entry

    def ids(self):
        return list(self.entries)

    # -- pin / hold -----------------------------------------------------

    def pin(self, model_id: Optional[str]) -> None:
        """Hold a model resident: a pinned entry is never staged out
        (rollout holds, shadow canaries, warm spares)."""
        entry = self.get(model_id)
        with self._mu:
            entry.pins += 1

    def unpin(self, model_id: Optional[str]) -> None:
        entry = self.get(model_id)
        with self._mu:
            entry.pins = max(0, entry.pins - 1)

    # -- residency ------------------------------------------------------

    def touch(self, entry: ServedModel) -> None:
        """LRU bookkeeping for one request: most-recently-used moves to
        the back of the eviction order."""
        with self._mu:
            entry.last_used = time.monotonic()
            if entry.model_id in self.entries:
                self.entries.move_to_end(entry.model_id)

    def resident_bytes(self) -> int:
        return sum(
            e.resident_bytes() for e in list(self.entries.values())
        )

    def ensure_resident(self, entry: ServedModel,
                        deadline: Optional[float] = None) -> None:
        """Return once the entry's tables are on device.

        The winning request thread stages in (budget eviction first,
        then manifest-verified reads + device assembly with NO lock
        held, then the flip under the device lock); every concurrent
        request to the same model queues here — bounded by its own
        deadline — and is answered from the newly resident tables.
        A cold model therefore costs its callers latency, never a
        5xx."""
        self.touch(entry)
        if entry.resident:
            return
        with self._mu:
            self.cold_hits += 1
        if deadline is None:
            ok = entry.stage_mu.acquire()
        else:
            ok = entry.stage_mu.acquire(
                timeout=max(0.0, deadline - time.monotonic())
            )
        if not ok:
            raise DeadlineExceeded("deadline waiting for model stage-in")
        try:
            if not entry.resident:
                self._stage_in(entry)
        finally:
            entry.stage_mu.release()

    def _stage_in(self, entry: ServedModel) -> None:
        """Bring an evicted model's tables back from its committed host
        snapshot. Caller holds ``entry.stage_mu`` (NOT the device
        lock — staging reads disk and assembles device arrays while
        other models keep serving)."""
        src = entry.source_dir
        if src is None:
            raise ValueError(
                f"model {entry.model_id!r} has no committed snapshot "
                "to stage in from"
            )
        t0 = time.monotonic()
        self._make_room(entry)
        engine = entry.model.engine
        staged = engine.stage_tables(os.path.join(src, "matrix"))
        with self._server._lock:
            engine.adopt_tables(staged)
        dt = time.monotonic() - t0
        with self._mu:
            entry.cost_bytes = entry.resident_bytes()
            entry.stage_ins += 1
            self.stage_ins += 1
            self.stage_in_seconds += dt
        logger.info(
            "staged model %r back in from %s (%.2fs, %d bytes)",
            entry.model_id, src, dt, entry.cost_bytes,
        )

    def _make_room(self, entry: Optional[ServedModel]) -> None:
        """Evict LRU unpinned models until ``entry`` (or, with None,
        the current residency) fits the budget. With nothing evictable
        left the catalog runs over budget rather than failing requests
        — the budget is a target, pins are a guarantee."""
        budget = self.budget_bytes
        if not budget:
            return
        need = max(0, entry.cost_bytes) if entry is not None else 0
        while True:
            with self._mu:
                used = sum(
                    e.resident_bytes() for e in self.entries.values()
                )
                if used + need <= budget:
                    return
                victim = None
                for e in self.entries.values():  # LRU iteration order
                    if e is entry or not e.resident:
                        continue
                    if e.pins == 0 and e.evictable:
                        victim = e
                        break
            if victim is None:
                logger.warning(
                    "model-memory budget exceeded (%d resident + %d "
                    "needed > %d) with nothing evictable — running "
                    "over budget", used, need, budget,
                )
                return
            self.evict(victim)

    def evict(self, entry: ServedModel) -> bool:
        """Stage one model's tables out of device memory. The bytes
        are already safe on disk (the committed snapshot in
        ``source_dir``), so eviction is pure release — pending async
        saves are drained first inside ``release_tables``."""
        with self._mu:
            if entry.pins or not entry.evictable or not entry.resident:
                return False
        engine = entry.model.engine
        with self._server._lock:
            with self._mu:
                if entry.pins:  # pinned in the race window
                    return False
                entry.cost_bytes = (
                    entry.resident_bytes() or entry.cost_bytes
                )
            engine.release_tables()
        with self._mu:
            entry.evictions += 1
            self.evictions += 1
        logger.info(
            "staged model %r out (%d bytes freed; snapshot %s)",
            entry.model_id, entry.cost_bytes, entry.source_dir,
        )
        return True

    def enforce_budget(self) -> None:
        """Re-establish the budget after a load/reload grew residency."""
        self._make_room(None)

    # -- exposition ------------------------------------------------------

    def snapshot(self) -> dict:
        """Catalog block for /metrics: membership, residency vs budget,
        LRU churn counters, and the process-level program-sharing
        proof (builds vs shared-hit counts)."""
        from glint_word2vec_tpu.parallel.engine import (
            query_program_builds,
        )

        with self._mu:
            entries = list(self.entries.values())
            doc = {
                "models": len(entries),
                "default_model": self.default_id,
                "budget_bytes": self.budget_bytes,
                "evictions_total": self.evictions,
                "stage_ins_total": self.stage_ins,
                "stage_in_seconds_total": round(
                    self.stage_in_seconds, 3
                ),
                "cold_hits_total": self.cold_hits,
            }
        doc["resident_models"] = sum(1 for e in entries if e.resident)
        doc["resident_bytes"] = sum(e.resident_bytes() for e in entries)
        doc["query_program_builds"] = query_program_builds()
        shared = 0
        for e in entries:
            eng = getattr(e.model, "engine", None)
            shared += int(getattr(eng, "shared_program_hits", 0) or 0)
        doc["shared_program_hits"] = shared
        return doc


class ModelServer:
    """Holds one loaded model and serves its query surface over HTTP.

    ``max_batch`` caps (and shape-quantizes, rounded up to a power of
    two) the coalesced device dispatch; ``warmup=True`` compiles the
    whole serving shape family — Q buckets 1..max_batch, the
    ``warm_ks`` top-k buckets, and the (``warm_sentence_rows`` x
    ``warm_sentence_lens``) sentence-transform grid — BEFORE the port
    binds, so no real request inside the family ever pays a jit
    compile (a /transform of more than max(warm_sentence_rows)
    sentences per MAX_QUERY_ROWS chunk still compiles its row bucket
    lazily). Per-endpoint latency histograms, the
    coalesced-batch-size distribution, and compile counters are served
    on ``/metrics`` (and summarized on ``/healthz``).
    """

    #: Lock-free by design: ``_ann_live`` is a single bool flag —
    #: written at boot (no request threads yet) and under the device
    #: lock on hot-swap, read by request threads where a stale read
    #: only routes one request to the other (equally correct) path.
    _ATOMIC_ATTRS = frozenset({"_ann_live"})

    def __init__(
        self,
        model,
        host: str = "127.0.0.1",
        port: int = 8801,
        *,
        max_batch: int = 64,
        warmup: bool = True,
        # k buckets 16 and 32: num < 16 rounds into the 16 bucket and
        # num in [16, 31] (fetching num+1) into the 32 bucket, so the
        # default num range AND generous clients stay compile-free;
        # num >= 32 pays one lazy compile per further pow2 bucket.
        warm_ks=(16, 32),
        warm_sentence_lens=(1, 2, 4, 8, 16, 32, 64),
        warm_sentence_rows=(1, 2, 4, 8, 16),
        cache_size: int = 65536,
        max_inflight: int = 256,
        request_deadline: Optional[float] = 30.0,
        degraded_after: Optional[float] = 5.0,
        ann: bool = False,
        ann_clusters: int = -1,
        ann_nprobe: int = 8,
        ann_iters: int = 6,
        ann_sample: int = 65536,
        ann_recall_gate: float = 0.95,
        ann_recall_sample: int = 64,
    ):
        self.model = model
        self._prev_switch: Optional[float] = None
        #: Fleet launch-generation handshake (PR 7 pattern, serving
        #: tier): the fleet supervisor exports ``GLINT_FLEET_GEN`` on
        #: every replica launch and this server echoes it on
        #: ``/healthz`` and in its ``--port-file``, so a probe answered
        #: by a stale pre-restart process (or a stale port file) can
        #: never count as the NEW replica being healthy/ready.
        self.fleet_generation = os.environ.get("GLINT_FLEET_GEN")
        # Device queries are jitted functions on shared tables; serialize
        # them (the reference's PS likewise processes a shard's requests
        # on its actor mailbox, one at a time). The synonym endpoints
        # additionally coalesce concurrent waiters into one batched
        # dispatch (_SynonymCoalescer). Tracked so the overload layer
        # can see how long the device has been continuously busy.
        self._lock = _TrackedLock()
        self.metrics = ServingMetrics()
        # -- SLO burn rates + anomaly flight recorder (ISSUE 18) -------
        #: Per-endpoint availability/latency objectives over the device
        #: paths; ServingMetrics.observe feeds it and its snapshot rides
        #: /metrics under "slo" (rendered as glint_slo_*).
        self.metrics.slo = SloEngine.default_serving(_DEVICE_PATHS)
        self._shed_burst = ShedBurstDetector()
        #: Optional postmortem bundle writer — installed by
        #: :meth:`enable_flight_recorder`; None keeps every trigger
        #: path a no-op.
        self.flight: Optional[FlightRecorder] = None
        # -- overload protection (ISSUE 7) -----------------------------
        #: Admission high-water mark: device-touching requests past this
        #: many in flight shed with 429 + Retry-After instead of
        #: queueing without bound (the _pending list and the handler
        #: thread pool both used to grow arbitrarily under overload).
        self.max_inflight = max(0, int(max_inflight))
        #: Per-request deadline (seconds; None/0 disables): a request
        #: that cannot reach the device in time answers 504 without
        #: occupying a dispatch slot.
        self.request_deadline = (
            float(request_deadline) if request_deadline else None
        )
        #: Device-lock hold time (seconds; None/0 disables) past which
        #: the server enters degraded cache-only mode: cache hits are
        #: served, everything needing the device sheds with 429.
        self.degraded_after = (
            float(degraded_after) if degraded_after else None
        )
        self._inflight = 0
        self._inflight_mu = threading.Lock()
        self._degraded_flag = False
        self._coalescer = _SynonymCoalescer(
            model, self._lock, max_batch=max_batch, metrics=self.metrics,
            cache_size=cache_size,
        )
        self.max_batch = self._coalescer.max_batch
        self.cache_size = max(0, int(cache_size))
        #: Serving warm family parameters, reused verbatim by
        #: ``add_model`` so every catalog entry warms the SAME shape
        #: family — same-(V, d) models then share every compiled
        #: program through the process-level memo.
        self._warm_params = (
            tuple(warm_ks),
            tuple(warm_sentence_lens),
            tuple(warm_sentence_rows),
        )
        self._do_warmup = bool(warmup)
        # -- model catalog (ISSUE 20) ----------------------------------
        self.catalog = ModelCatalog(self)
        _default_entry = ServedModel(
            DEFAULT_MODEL_ID, model, self._coalescer, self.metrics
        )
        #: The default model is permanently pinned: the back-compat
        #: single-model surface must never stage out under budget
        #: pressure.
        _default_entry.pins = 1
        self.catalog.install(_default_entry, default=True)
        # -- approximate top-k (ISSUE 12) ------------------------------
        #: Whether the two-stage device index serves default /synonyms
        #: traffic. Only the base word-level family qualifies (the index
        #: is built over the training table's rows, which are not the
        #: subword family's word vectors: that family stays exact);
        #: per-request ``exact=true`` always escapes to the exact masked
        #: GEMM, and the measured recall gate can hold the approximate
        #: path back entirely.
        self.ann = (
            bool(ann) and self._coalescer.can_batch
            and not self._coalescer.composes
        )
        if ann and not self.ann:
            logger.warning(
                "ANN index refused for a %s: the approximate path covers "
                "the word-level family only; serving exact",
                type(model).__name__,
            )
        self.ann_recall_gate = float(ann_recall_gate)
        self.ann_recall_sample = max(1, int(ann_recall_sample))
        self._ann_live = False
        if self.ann:
            eng = model.engine
            conf = eng.configure_ann(
                clusters=ann_clusters, nprobe=ann_nprobe,
                iters=ann_iters, sample=ann_sample,
            )
            self._coalescer.ann_nprobe = conf["nprobe"]
            if eng.ann_index is None:
                t0 = time.time()
                eng.adopt_ann(eng.ann_build())
                logger.info(
                    "ANN index built in %.1fs (%d clusters x %d slots)",
                    time.time() - t0, conf["clusters"], conf["slots"],
                )
        if warmup:
            self._warmup(self.catalog.default)
        if self.ann:
            # Recall gate AFTER warmup: the check rides the warmed
            # exact + approximate programs, so it proves the index AND
            # costs zero compiles. A failing gate keeps the exact path
            # serving (counted on /metrics as gate fallbacks) — a fast
            # wrong answer is not an answer.
            self._gate_index(self.model.engine, self.metrics.generation)
        # Shapes compiled from here on are serving-path misses the
        # /metrics "post_warmup" counter (and the CI smoke) watches.
        self.metrics.warmup_compiles = self._query_compiles()
        server = self

        class Handler(BaseHTTPRequestHandler):
            # Keep-alive: reconnecting per request dominated measured
            # latency at high concurrency on the closed-loop bench.
            protocol_version = "HTTP/1.1"
            # Responses go out as two small writes (header buffer, then
            # body); without TCP_NODELAY, Nagle holds the body segment
            # until the client ACKs the headers — a delayed-ACK 40ms
            # stall that was the entire >1-client p95 (SERVING_BENCH).
            disable_nagle_algorithm = True

            def log_message(self, fmt, *args):  # route to logging, not stderr
                logger.debug("serve: " + fmt, *args)

            def parse_request(self):
                # The request line has just arrived on the keep-alive
                # connection: what http.server does from here to
                # do_POST's entry is the request's ``req.head``.
                self._t_head = time.perf_counter()
                return super().parse_request()

            def _send(self, code: int, obj, headers=None) -> None:
                tr = getattr(self, "_trace", None) or obs_events.NULL_TRACE
                with tr.phase("req.serialize"):
                    body = json.dumps(obj).encode()
                    self._status = code
                    self.send_response(code)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    for k, v in (headers or {}).items():
                        self.send_header(k, v)
                    self.end_headers()
                    self.wfile.write(body)

            def _send_text(self, code: int, text: str) -> None:
                body = text.encode()
                self._status = code
                self.send_response(code)
                self.send_header(
                    "Content-Type",
                    "text/plain; version=0.0.4; charset=utf-8",
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                t0 = time.perf_counter()
                self._status = 500
                # No request trace on GETs (probe/scrape traffic), and a
                # finished trace from an earlier POST on this keep-alive
                # connection must not collect this response's spans.
                self._trace = None
                # Parsed path: routing and metric keys must not vary with
                # the query string (?format=... would otherwise mint a
                # fresh latency histogram per variant).
                url = urlparse(self.path)
                mid, path = split_model_path(
                    url.path, self.headers.get("X-Glint-Model")
                )
                try:
                    entry = server._entry(mid)
                except KeyError:
                    self._send(404, {"error": f"unknown model {mid!r}"})
                    server._observe_request(
                        server.catalog.default, path,
                        time.perf_counter() - t0, 404,
                    )
                    return
                try:
                    if path == "/healthz":
                        m = entry.model
                        compiles = server._query_compiles(entry)
                        degraded = server._degraded()
                        doc = {
                            # Degraded is still alive-but-impaired: 200
                            # with the flag (a 5xx here would make the
                            # fleet LB pull a server that is shedding
                            # exactly as designed).
                            "status": (
                                "degraded" if degraded else "ok"
                            ),
                            "model": entry.model_id,
                            "family": type(m).__name__,
                            "vocab_size": m.vocab.size,
                            "dim": m.vector_size,
                            "max_batch": server.max_batch,
                            "compiles": compiles,
                            "post_warmup_compiles": compiles
                            - entry.metrics.warmup_compiles,
                            "max_inflight": server.max_inflight,
                            "request_deadline_seconds":
                                server.request_deadline,
                            "degraded_after_seconds":
                                server.degraded_after,
                            "ann_enabled": server._ann_live,
                            "ann_recall_gate_ok":
                                server.metrics.index_recall_gate_ok,
                            "generation":
                                entry.metrics.generation,
                            "fleet_generation":
                                server.fleet_generation,
                            "resident": entry.resident,
                        }
                        if mid is None and len(server.catalog.entries) > 1:
                            doc["models"] = server._models_summary()
                        self._send(200, doc)
                    elif path == "/metrics":
                        # Scoped /m/<id>/metrics answers ONE model's
                        # block; the bare path keeps the default
                        # model's snapshot at the root (back-compat)
                        # with per-model + catalog blocks folded in.
                        if mid is not None:
                            snap = server._entry_snapshot(entry)
                        else:
                            snap = server._metrics_doc()
                        fmt = parse_qs(url.query).get("format", ["json"])[0]
                        if fmt == "prometheus":
                            self._send_text(200, serving_to_prometheus(snap))
                        else:
                            self._send(200, snap)
                    elif path == "/models":
                        self._send(200, server._models_doc())
                    elif path == "/trace":
                        # Flight-recorder scrape: the last N seconds of
                        # this process's span ring plus the clock anchor,
                        # so the balancer's postmortem bundle can rebase
                        # every replica onto one timeline.
                        rec = obs_events.get_recorder()
                        try:
                            secs = float(parse_qs(url.query).get(
                                "seconds", ["30"]
                            )[0])
                        except ValueError:
                            secs = 30.0
                        if rec is None:
                            self._send(200, {"events": [], "anchor": None})
                        else:
                            self._send(200, {
                                "events": rec.recent_events(secs),
                                "anchor": {"wall_t0": rec.wall_t0,
                                           "mono_t0": rec.mono_t0},
                            })
                    else:
                        self._send(404, {"error": f"no route {path}"})
                finally:
                    server._observe_request(
                        entry, path, time.perf_counter() - t0,
                        self._status,
                    )

            def do_POST(self):
                t0 = time.perf_counter()
                self._status = 500
                # Same parsed-path rule as do_GET: routing and metric
                # keys must not vary with the query string.
                mid, path = split_model_path(
                    urlparse(self.path).path,
                    self.headers.get("X-Glint-Model"),
                )
                # Distributed tracing (ISSUE 18): adopt the propagated
                # trace id (the balancer's X-Glint-Trace) or mint one at
                # the edge. Phase spans buffer on the trace and flush
                # into the ring only if the tail sampler keeps the
                # request (always: errors/sheds/slow; 1-in-N otherwise).
                tr = obs_events.request_trace(
                    self.headers.get(obs_events.TRACE_HEADER)
                )
                self._trace = tr
                try:
                    entry = server._entry(mid)
                except KeyError:
                    self._send(404, {"error": f"unknown model {mid!r}"})
                    tr.finish(404)
                    server._observe_request(
                        server.catalog.default, path,
                        time.perf_counter() - t0, 404,
                    )
                    return
                if tr.live:
                    tr.add_phase("req.head", self._t_head,
                                 time.perf_counter() - self._t_head)
                try:
                    with tr.phase("req.accept", path=path):
                        # The handler thread's own CPU clock, read on a
                        # live trace alone: the span's wall less its
                        # ``cpu_ms`` is the time this thread did not run
                        # (the interpreter lock, the coalescer's lock,
                        # the socket).
                        cpu0 = time.thread_time() if tr.live else None
                        try:
                            self._handle_post(path, entry)
                        finally:
                            if cpu0 is not None:
                                tr.annotate(cpu_ms=round(
                                    (time.thread_time() - cpu0) * 1e3, 4
                                ))
                finally:
                    kept = tr.finish(self._status)
                    server._observe_request(
                        entry, path, time.perf_counter() - t0,
                        self._status,
                        trace_id=tr.trace_id if kept else None,
                    )

            def _handle_post(self, path, entry):
                try:
                    with self._trace.phase("req.parse"):
                        n = int(self.headers.get("Content-Length", 0))
                        req = json.loads(self.rfile.read(n) or b"{}")
                except (ValueError, json.JSONDecodeError) as e:
                    return self._send(400, {"error": f"bad request: {e}"})
                if path in _DEVICE_PATHS:
                    # Admission bound: past the high-water mark the
                    # request sheds NOW — cheaper for everyone than
                    # joining a queue whose wait already exceeds any
                    # reasonable client timeout.
                    with self._trace.phase("req.admission") as adm:
                        admitted = server._admit()
                        adm.update(admitted=admitted)
                    if not admitted:
                        server._record_shed("admission", entry)
                        return self._send(
                            429,
                            {"error": "server overloaded "
                                      "(admission queue full)"},
                            headers={"Retry-After": "1"},
                        )
                    try:
                        return self._handle_device(path, req, entry)
                    finally:
                        server._release_slot()
                out = None
                if path == "/reload":
                    # Admin hot-swap of THIS entry's model: explicit
                    # generation dir, or an immediate poll of its
                    # watched publish dir. Not a _DEVICE_PATHS member —
                    # an overloaded server must still be swappable
                    # (staging runs lock-free; the flip queues behind
                    # in-flight dispatches only).
                    if "dir" in req:
                        gen_dir = str(req["dir"])
                        gen = req.get("generation") or os.path.basename(
                            os.path.normpath(gen_dir)
                        )
                        # Serialize against the entry's watcher poll
                        # thread — an explicit reload racing a pointer
                        # poll must not stage/adopt the same generation
                        # twice.
                        mu = (
                            entry.watcher._poll_mu
                            if entry.watcher is not None
                            else contextlib.nullcontext()
                        )
                        with mu:
                            try:
                                server.reload_generation(
                                    gen_dir, generation=gen,
                                    model_id=entry.model_id,
                                )
                            except OSError as e:
                                if os.path.isdir(gen_dir):
                                    # The dir EXISTS but a read inside
                                    # it failed: transient storage
                                    # trouble, answered 503 so a fleet
                                    # rollout coordinator retries
                                    # instead of branding the
                                    # generation failed (the
                                    # SnapshotWatcher classification,
                                    # preserved across the HTTP
                                    # boundary).
                                    entry.metrics.record_watch_error()
                                    return self._send(
                                        503,
                                        {"error": "transient staging "
                                                  f"error: {e}"},
                                        headers={"Retry-After": "1"},
                                    )
                                entry.metrics.record_swap(gen, ok=False)
                                return self._send(400, {"error": str(e)})
                            except Exception as e:
                                entry.metrics.record_swap(gen, ok=False)
                                return self._send(400, {"error": str(e)})
                            if entry.watcher is not None:
                                entry.watcher.current = gen
                        return self._send(
                            200, {"status": "reloaded", "generation": gen,
                                  "model": entry.model_id}
                        )
                    if entry.watcher is None:
                        return self._send(
                            400,
                            {"error": "no watched publish dir for "
                                      f"model {entry.model_id!r}; "
                                      'pass {"dir": ...}'},
                        )
                    gen = entry.watcher.poll_once()
                    if gen is None:
                        return self._send(
                            200,
                            {"status": "unchanged",
                             "generation": entry.watcher.current,
                             "model": entry.model_id},
                        )
                    return self._send(
                        200, {"status": "reloaded", "generation": gen,
                              "model": entry.model_id}
                    )
                if path == "/models/pin":
                    # Pin/hold admin surface: the fleet's rollout
                    # coordinator and autoscaler pin the model they are
                    # rolling/warming so the LRU can never stage it out
                    # from under a held generation or a warm spare.
                    target = req.get("model", entry.model_id)
                    try:
                        if bool(req.get("pinned", True)):
                            server.catalog.pin(target)
                        else:
                            server.catalog.unpin(target)
                        pins = server.catalog.get(target).pins
                    except KeyError:
                        return self._send(
                            404, {"error": f"unknown model {target!r}"}
                        )
                    return self._send(
                        200, {"model": target or DEFAULT_MODEL_ID,
                              "pins": pins}
                    )
                if path == "/shutdown":
                    with server._lock:
                        out = server._dispatch(path, req)
                    self._send(200, out)
                    threading.Thread(
                        target=server.stop, daemon=True
                    ).start()
                    return
                self._send(404, {"error": f"no route {path}"})

            def _handle_device(self, path, req, entry):
                """One admitted device-touching request: degraded-mode
                gate, per-request deadline, residency (the LRU
                stage-in rendezvous), then dispatch."""
                if server._degraded():
                    # Cache-only mode: the device is wedged — serve
                    # what needs no dispatch, shed the rest. 429 (not
                    # 5xx): the condition is load/availability, the
                    # client should back off and retry.
                    if path == "/synonyms":
                        try:
                            num = int(req.get("num", 10))
                        except (TypeError, ValueError) as e:
                            # Same 400 contract as the normal path — a
                            # malformed num must not change behavior
                            # just because the server is impaired.
                            return self._send(
                                400, {"error": f"bad num: {e}"}
                            )
                        hit = entry.coalescer.cache_lookup(
                            req.get("word"), num,
                            exact=bool(req.get("exact", False)),
                        )
                        if hit is not None:
                            entry.metrics.record_cache(True)
                            return self._send(
                                200, [[w, float(s)] for w, s in hit]
                            )
                    server._record_shed("degraded", entry)
                    return self._send(
                        429,
                        {"error": "degraded cache-only mode "
                                  "(device busy)"},
                        headers={"Retry-After": "1"},
                    )
                deadline = (
                    time.monotonic() + server.request_deadline
                    if server.request_deadline else None
                )
                # Deadline propagation (ISSUE 19): a balancer forwards
                # the client's REMAINING budget as X-Glint-Deadline-Ms;
                # it can only tighten the replica's own deadline, never
                # extend it.
                hdr = self.headers.get("X-Glint-Deadline-Ms")
                if hdr is not None:
                    try:
                        budget = max(0.0, float(hdr)) / 1e3
                    except (TypeError, ValueError):
                        budget = None
                    if budget is not None:
                        remote = time.monotonic() + budget
                        deadline = (
                            remote if deadline is None
                            else min(deadline, remote)
                        )
                try:
                    # LRU rendezvous: a cold model stages back in OFF
                    # the request path (the winning thread stages, the
                    # rest queue bounded by their deadlines) before
                    # any dispatch below touches its tables.
                    server.catalog.ensure_resident(
                        entry, deadline=deadline
                    )
                    if path == "/synonyms":
                        out = [
                            [w, float(s)]
                            for w, s in entry.coalescer.query(
                                word=req["word"],
                                num=int(req.get("num", 10)),
                                deadline=deadline,
                                exact=bool(req.get("exact", False)),
                                trace=self._trace,
                            )
                        ]
                    elif path == "/synonyms_vector":
                        out = [
                            [w, float(s)]
                            for w, s in entry.coalescer.query(
                                vector=req["vector"],
                                num=int(req.get("num", 10)),
                                deadline=deadline,
                                exact=bool(req.get("exact", False)),
                                trace=self._trace,
                            )
                        ]
                    else:
                        with self._trace.phase("req.queue"):
                            if deadline is None:
                                acquired = server._lock.acquire()
                            else:
                                acquired = server._lock.acquire(
                                    timeout=deadline - time.monotonic()
                                )
                        if not acquired:
                            raise DeadlineExceeded(
                                "deadline waiting for device"
                            )
                        try:
                            with self._trace.phase(
                                "req.query", mode="exact"
                            ):
                                out = server._dispatch(
                                    path, req, entry.model
                                )
                        finally:
                            server._lock.release()
                except DeadlineExceeded as e:
                    entry.metrics.record_deadline()
                    return self._send(504, {"error": str(e)})
                except KeyError as e:
                    return self._send(
                        404, {"error": e.args[0] if e.args else str(e)}
                    )
                except ValueError as e:
                    return self._send(400, {"error": str(e)})
                if out is None:
                    return self._send(404, {"error": f"no route {path}"})
                self._send(200, out)

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self.host, self.port = self._httpd.server_address[:2]
        self._thread: Optional[threading.Thread] = None
        # Mode suppliers LAST: no request thread exists yet, and the
        # coalescer must never see ann before the gate ran.
        self._coalescer.ann_active = lambda: self._ann_live
        self._coalescer.gate_failing = (
            lambda: self.ann and not self._ann_live
        )

    # -- model catalog (ISSUE 20) ---------------------------------------

    @property
    def watcher(self) -> Optional[SnapshotWatcher]:
        """The DEFAULT model's publish watcher (back-compat alias —
        each catalog entry owns its own watcher)."""
        return self.catalog.default.watcher

    @watcher.setter
    def watcher(self, w: Optional[SnapshotWatcher]) -> None:
        self.catalog.default.watcher = w

    def _entry(self, model_id: Optional[str]) -> ServedModel:
        """Catalog entry for a request's model id (None = default);
        KeyError -> the handler's 404."""
        return self.catalog.get(model_id)

    def add_model(self, model_id: str, model=None,
                  model_dir: Optional[str] = None, *,
                  warmup: Optional[bool] = None,
                  generation: Optional[str] = None) -> ServedModel:
        """Serve another model from this process (ISSUE 20).

        The new entry gets its OWN result cache, metrics, and SLO
        engine, but shares the device lock, the admission layer, and —
        decisively — the process-level shape-keyed program memo: the
        warmup below re-walks the exact bucket family the default
        model compiled, so a same-(V, d) model costs ZERO new XLA
        programs (``query_program_builds()`` is the proof the bench
        gates assert on). The ANN lifecycle stays a default-model
        feature; catalog models serve the exact path."""
        from glint_word2vec_tpu import load_model
        from glint_word2vec_tpu.streaming.publish import _GEN_RE

        if model is None:
            if model_dir is None:
                raise ValueError("add_model needs model or model_dir")
            model = load_model(model_dir)
        metrics = ServingMetrics()
        metrics.slo = SloEngine.default_serving(_DEVICE_PATHS)
        coalescer = _SynonymCoalescer(
            model, self._lock, max_batch=self.max_batch,
            metrics=metrics, cache_size=self.cache_size,
        )
        entry = ServedModel(
            model_id, model, coalescer, metrics, source_dir=model_dir
        )
        if generation is None and model_dir is not None:
            base = os.path.basename(os.path.normpath(model_dir))
            if _GEN_RE.match(base):
                generation = base
        if generation is not None:
            metrics.generation = generation
        self.catalog.install(entry)
        do_warm = self._do_warmup if warmup is None else bool(warmup)
        if do_warm:
            self._warmup(entry)
        metrics.warmup_compiles = self._query_compiles(entry)
        self.catalog.enforce_budget()
        logger.info(
            "added model %r (%d words, dim %d, resident %s)",
            model_id, model.vocab.size, model.vector_size,
            entry.resident,
        )
        return entry

    def _models_summary(self) -> dict:
        """Per-model overview for /healthz and GET /models."""
        out = {}
        for mid, e in list(self.catalog.entries.items()):
            compiles = self._query_compiles(e)
            out[mid] = {
                "family": type(e.model).__name__,
                "vocab_size": e.model.vocab.size,
                "dim": e.model.vector_size,
                "resident": e.resident,
                "pinned": e.pins > 0,
                "generation": e.metrics.generation,
                "post_warmup_compiles": compiles
                - e.metrics.warmup_compiles,
            }
        return out

    def _models_doc(self) -> dict:
        return {
            "default": self.catalog.default_id,
            "models": self._models_summary(),
            "catalog": self.catalog.snapshot(),
        }

    def _entry_snapshot(self, entry: ServedModel) -> dict:
        """One model's full metrics snapshot + its residency state."""
        is_default = entry is self.catalog.default
        snap = entry.metrics.snapshot(
            self._query_compiles(entry),
            checkpoint=self._checkpoint_stats(entry),
            composed_table=self._composed_table_stats(entry),
            index_staleness=(
                self._index_staleness(entry) if is_default else None
            ),
        )
        snap["model_id"] = entry.model_id
        snap["resident"] = entry.resident
        # Integer twin of "resident" so the merged fleet view can fold
        # it additively (resident replica count per model) and the
        # Prometheus renderer maps ONE key in both shapes.
        snap["resident_replicas"] = 1 if entry.resident else 0
        snap["pinned"] = entry.pins > 0
        snap["resident_bytes"] = entry.resident_bytes()
        # How the model lies on its devices: the model axis' size, the
        # table rows a shard holds, and the fullest device's bytes of
        # the total above.
        eng = getattr(entry.model, "engine", None)
        snap["shards"] = int(getattr(eng, "num_model", 1))
        snap["rows_per_shard"] = int(getattr(eng, "rows_per_shard", 0))
        snap["resident_bytes_per_device"] = (
            entry.resident_bytes_per_device()
        )
        snap["stage_ins_total"] = entry.stage_ins
        snap["evictions_total"] = entry.evictions
        return snap

    def _metrics_doc(self) -> dict:
        """The top-level /metrics document: the default model's
        snapshot at the root (every pre-catalog consumer keeps
        parsing), plus per-model blocks and the catalog block."""
        doc = self._entry_snapshot(self.catalog.default)
        doc["models"] = {
            mid: self._entry_snapshot(e)
            for mid, e in list(self.catalog.entries.items())
        }
        doc["catalog"] = self.catalog.snapshot()
        return doc

    # -- approximate index lifecycle (ISSUE 12) ------------------------

    def _gate_index(self, engine, generation, *, index=None, syn0=None,
                    norms=None, queryable=None):
        """Measure recall@10 of the approximate path against the exact
        path on the SAME tables (live, or a staged generation's) and
        record the refresh on /metrics. For the LIVE index this also
        flips ``_ann_live``; for a staged one the caller adopts the
        verdict together with the tables. Returns (recall, gate_ok)."""
        eng_conf = engine._ann_conf or {}
        recall = engine.ann_recall_at_k(
            10, sample=self.ann_recall_sample, index=index, syn0=syn0,
            norms=norms, queryable=queryable, q_chunk=self.max_batch,
        )
        ok = recall >= self.ann_recall_gate
        stats = (
            engine.ann_stats() if index is None
            else {**index.stats(), "enabled": True}
        )
        self.metrics.record_index_refresh(
            stats, recall, ok, self.ann_recall_gate,
            eng_conf.get("nprobe", 0),
        )
        if index is None:
            self._ann_live = ok
        if not ok:
            logger.warning(
                "ANN recall gate FAILED (%.3f < %.3f)%s: exact path "
                "keeps serving",
                recall, self.ann_recall_gate,
                f" for {generation}" if generation else "",
            )
        else:
            logger.info(
                "ANN recall gate ok: %.3f >= %.3f", recall,
                self.ann_recall_gate,
            )
        return recall, ok

    def _index_staleness(
        self, entry: Optional[ServedModel] = None
    ) -> Optional[int]:
        """Table versions the live index is behind (None = no index)."""
        model = (entry or self.catalog.default).model
        eng = getattr(model, "engine", None)
        idx = getattr(eng, "ann_index", None)
        if eng is None or idx is None:
            return None
        return max(0, eng.table_version - idx.table_version)

    # -- hot-swap (ISSUE 10) ------------------------------------------

    def watch(self, watch_dir: str, poll_seconds: float = 1.0,
              current: Optional[str] = None,
              model_id: Optional[str] = None) -> SnapshotWatcher:
        """Follow a publish directory for ONE model (None = default):
        every new committed generation is staged off the request path
        and flipped into that model only. ``current`` names the
        generation already loaded at startup so the first poll doesn't
        re-load it."""
        entry = self._entry(model_id)
        w = SnapshotWatcher(
            self, watch_dir, poll_seconds, model_id=model_id
        )
        w.current = current
        if current is not None:
            entry.metrics.generation = current
        entry.watcher = w
        w.start()
        logger.info(
            "watching %s for published generations of model %r "
            "(poll %.2fs)", watch_dir, entry.model_id, poll_seconds,
        )
        return w

    def reload_generation(self, gen_dir: str,
                          generation: Optional[str] = None,
                          model_id: Optional[str] = None) -> None:
        """Hot-swap ONE model's served tables (None = the default) to
        a committed generation directory (a model dir: ``matrix/`` +
        ``words.txt``). Other catalog entries are untouched — their
        caches, generations, and swap counters never move.

        Staging — manifest verification, disk reads, building the
        re-sharded device arrays, and (with the index enabled)
        training the new generation's centroids, packing its member
        layout, and measuring its recall gate — runs on the calling
        thread with NO lock held, concurrent with live dispatches
        against the old tables. The flip is a few attribute
        assignments + one ``table_version`` tick under the device
        lock: in-flight dispatches drain first (no response mixes
        generations — the index flips WITH the tables, so a coarse
        probe can never rank one generation's members against
        another's vectors), the synonym result cache empties
        wholesale, and the same-shape tables AND index reuse every
        warmed compiled program (zero post-warmup compiles — the PR 2
        contract, preserved across swaps on both paths)."""
        from glint_word2vec_tpu.corpus.vocab import saved_model_vocabulary
        from glint_word2vec_tpu.models.word2vec import Word2VecModel

        entry = self._entry(model_id)
        faults.fire("serving.reload")
        if type(entry.model) is not Word2VecModel:
            raise ValueError(
                f"hot-swap supports the base word-level family only "
                f"(serving a {type(entry.model).__name__})"
            )
        # Pinned for the duration: the LRU must never stage out the
        # generation being swapped in (the rollout-hold guarantee).
        self.catalog.pin(model_id)
        try:
            engine = entry.model.engine
            staged = engine.stage_tables(os.path.join(gen_dir, "matrix"))
            meta = staged["meta"]
            vocab = saved_model_vocabulary(
                gen_dir,
                np.load(os.path.join(gen_dir, "matrix", "counts.npy")),
                int(meta["vocab_size"]) + int(
                    meta.get("extra_rows_assigned", 0)
                ),
            )
            staged_ann = None
            staged_ok = False
            if self.ann and entry is self.catalog.default:
                # Refresh the coarse index against the STAGED tables —
                # new centroids, fresh member packing, and the recall
                # gate all run off the request path; only the flip
                # below is held.
                staged_q = int(meta["vocab_size"]) + int(
                    meta.get("extra_rows_assigned", 0)
                )
                staged_norms = engine._norms(staged["syn0"])
                staged_ann = engine.ann_build(
                    staged["syn0"], staged_norms, staged_q
                )
                _, staged_ok = self._gate_index(
                    engine, generation, index=staged_ann,
                    syn0=staged["syn0"], norms=staged_norms,
                    queryable=staged_q,
                )
            with self._lock:
                engine.adopt_tables(staged)
                entry.model.vocab = vocab
                if staged_ann is not None:
                    engine.adopt_ann(staged_ann)
                    self._ann_live = staged_ok
            entry.metrics.record_swap(generation, ok=True)
            entry.source_dir = gen_dir
        finally:
            self.catalog.unpin(model_id)
        self.catalog.enforce_budget()
        logger.info(
            "hot-swapped %r to %s (%d words, table_version %d%s)",
            entry.model_id, generation or gen_dir, len(vocab.words),
            engine.table_version,
            ", index refreshed" if staged_ann is not None else "",
        )

    # -- overload protection ------------------------------------------

    def _admit(self) -> bool:
        """Claim one in-flight slot for a device-touching request;
        False = past the high-water mark, shed with 429."""
        if not self.max_inflight:
            return True
        with self._inflight_mu:
            if self._inflight >= self.max_inflight:
                return False
            self._inflight += 1
            self.metrics.record_inflight(self._inflight)
            return True

    def _release_slot(self) -> None:
        if not self.max_inflight:
            return
        with self._inflight_mu:
            self._inflight -= 1

    def _degraded(self) -> bool:
        """Whether the server is in degraded cache-only mode: the
        device lock has been continuously held past ``degraded_after``
        (a wedged or pathologically slow dispatch). Tracks entry
        transitions for the ``degraded_entered`` counter; exits
        automatically the moment the lock frees."""
        if self.degraded_after is None:
            return False
        d = self._lock.held_for() > self.degraded_after
        with self._inflight_mu:
            if d and not self._degraded_flag:
                self._degraded_flag = True
                self.metrics.record_degraded_entered()
                logger.warning(
                    "entering degraded cache-only mode: device lock "
                    "held > %.1fs", self.degraded_after,
                )
            elif not d:
                self._degraded_flag = False
        return d

    # -- SLO + anomaly flight recorder (ISSUE 18) ---------------------

    def _observe_request(self, entry: ServedModel, path: str,
                         seconds: float, status: int,
                         trace_id: Optional[str] = None) -> None:
        """Single funnel for per-request accounting, keyed to the
        request's MODEL: the latency histogram + SLO observation (with
        the exemplar trace id when the tail sampler kept the trace),
        then the SLO fast-burn flight-recorder trigger (throttled
        inside the engine)."""
        entry.metrics.observe(
            path, seconds, status=status, trace_id=trace_id
        )
        fl, slo = self.flight, entry.metrics.slo
        if fl is not None and slo is not None:
            for ep in slo.fast_burn_transitions():
                fl.trigger("slo_fast_burn", endpoint=ep)

    def _record_shed(self, reason: str,
                     entry: Optional[ServedModel] = None) -> None:
        """Count one shed on the request's model and fire the flight
        recorder on the burst EDGE (one bundle per burst, not one per
        shed)."""
        (entry or self.catalog.default).metrics.record_shed(reason)
        if self._shed_burst.note() and self.flight is not None:
            self.flight.trigger("shed_burst", reason=reason)

    def enable_flight_recorder(
        self, out_dir: str, *, window_seconds: float = 30.0,
        min_interval_seconds: float = 60.0,
    ) -> FlightRecorder:
        """Install the anomaly flight recorder: on a shed burst or an
        SLO fast-burn edge it bundles this process's recent span ring
        and full metrics snapshot into ``out_dir`` for postmortem."""
        fl = FlightRecorder(
            out_dir, window_seconds=window_seconds,
            min_interval_seconds=min_interval_seconds,
        )
        fl.add_source("spans", self._flight_spans)
        fl.add_source("metrics", self._flight_metrics)
        self.flight = fl
        return fl

    def _flight_spans(self, window_seconds: float) -> dict:
        rec = obs_events.get_recorder()
        if rec is None:
            return {"events": [], "anchor": None}
        return {
            "events": rec.recent_events(window_seconds),
            "anchor": {"wall_t0": rec.wall_t0, "mono_t0": rec.mono_t0},
        }

    def _flight_metrics(self, window_seconds: float) -> dict:
        return self._metrics_doc()

    # -- warmup / compile accounting ----------------------------------

    def _checkpoint_stats(
        self, entry: Optional[ServedModel] = None
    ) -> dict:
        """Checkpoint telemetry of the served engine (ISSUE 5): a model
        served straight out of a training process reports its snapshot
        pipeline; a freshly-loaded model reports Nones. Never raises —
        /metrics must stay up regardless."""
        model = (entry or self.catalog.default).model
        eng = getattr(model, "engine", None)
        stats = getattr(eng, "checkpoint_stats", None)
        if stats is None:
            return {}
        try:
            return stats()
        except Exception:
            return {}

    def _composed_table_stats(self, entry: ServedModel) -> dict:
        """How often, and for how many seconds, the subword family's
        composed word table was built ({} for a family without one)."""
        model = entry.model
        builds = getattr(model, "query_engine_builds", None)
        if builds is None:
            return {}
        return {
            "builds": int(builds),
            "seconds": float(model.query_engine_build_seconds),
        }

    def _query_compiles(
        self, entry: Optional[ServedModel] = None
    ) -> int:
        """Total query-op shapes compiled across one model's engines
        (the training engine plus the subword family's composed query
        engine, once it exists). Per-engine first-seen counts: a shape
        another model already built still counts here (that is the
        warmed-family contract each model asserts individually);
        process-level build counts live on the catalog snapshot."""
        model = (entry or self.catalog.default).model
        engines = [getattr(model, "engine", None)]
        qeng = getattr(model, "_qeng", None)
        if qeng is not None:
            engines.append(qeng)
        return sum(
            int(getattr(e, "query_compiles", 0) or 0)
            for e in engines
            if e is not None
        )

    def _warmup(self, entry: ServedModel) -> None:
        """Compile one model's serving shape family before the port
        binds (or, for ``add_model``, before the entry takes requests):
        the SAME bucket family for every entry, so same-(V, d) models
        share every compiled program. The word-level family warms its
        training engine (pull and top-k over the Q and k buckets, the
        sentence grid). The subword family builds its composed word
        table first, warms the pull and the top-k family over THAT
        engine, and its compose buckets (``warm_compose``: every block
        shape a round's out-of-dictionary words, ``/vector``,
        ``/analogy`` or ``/transform`` can dispatch) over the training
        engine. Any other overriding family keeps its own dispatch
        shapes and its single-query path: nothing to warm here."""
        model = entry.model
        if not entry.coalescer.can_batch:
            return
        warm_ks, warm_sentence_lens, warm_sentence_rows = self._warm_params
        q_buckets = [1 << i for i in range(self.max_batch.bit_length())]
        t0 = time.time()
        if entry.coalescer.composes:
            n = model._query_engine().warmup(q_buckets, warm_ks)
            n += model.warm_compose()
        else:
            n = model.engine.warmup(
                q_buckets,
                warm_ks,
                sentence_lens=warm_sentence_lens,
                sentence_rows=warm_sentence_rows,
            )
        ann = (
            self.ann and entry is self.catalog.default
            and model.engine.ann_index is not None
        )
        if ann:
            # The approximate dispatch family (coarse score + bucketed
            # rerank + the promotion-path assignment program) warms
            # with the exact family, BEFORE the port binds — the
            # zero-post-warmup-compiles contract covers both paths
            # (ISSUE 12 satellite).
            n += model.engine.warmup_ann(
                q_buckets=q_buckets, k_buckets=warm_ks,
            )
        logger.info(
            "serving warmup of %r: %d shapes compiled in %.1fs "
            "(Q buckets %s, k buckets %s%s%s)",
            entry.model_id, n, time.time() - t0, q_buckets,
            tuple(warm_ks), ", +ann" if ann else "",
            ", +composed table and compose buckets"
            if entry.coalescer.composes else "",
        )

    # -- request dispatch ---------------------------------------------

    def _dispatch(self, path: str, req: dict, model=None):
        if path != "/shutdown":
            faults.fire("serving.dispatch")
        m = model if model is not None else self.model
        if path == "/analogy":
            return [
                [w, float(s)]
                for w, s in m.analogy(
                    req.get("positive", []),
                    req.get("negative", []),
                    int(req.get("num", 10)),
                )
            ]
        if path == "/vector":
            return [float(x) for x in m.transform(req["word"])]
        if path == "/transform":
            vecs = m.transform_sentences(req["sentences"])
            return [[float(x) for x in v] for v in np.asarray(vecs)]
        if path == "/shutdown":
            return {"status": "shutting down"}
        return None

    # -- lifecycle -----------------------------------------------------

    def _tighten_gil_switch(self) -> None:
        # The serving process is a convoy of short GIL-holding sections
        # (HTTP parse, JSON, event wakeups) across one handler thread
        # per connection; at CPython's default 5ms switch interval each
        # round of N coalesced responses can pay N preemption quanta of
        # pure scheduling latency. 1ms keeps the handoff tight — worth
        # ~5x on p95 at 16 clients on a 2-core host (SERVING_BENCH).
        # Process-global, so taken only once serving actually starts
        # and restored by stop().
        if self._prev_switch is None:
            self._prev_switch = sys.getswitchinterval()
            sys.setswitchinterval(0.001)

    def serve_forever(self) -> None:
        logger.info("serving model on %s:%d", self.host, self.port)
        self._tighten_gil_switch()
        self._httpd.serve_forever()

    def start_background(self) -> None:
        self._tighten_gil_switch()
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        for e in list(self.catalog.entries.values()):
            if e.watcher is not None:
                e.watcher.stop()
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._prev_switch is not None:
            sys.setswitchinterval(self._prev_switch)
            self._prev_switch = None


def serve_model_dir(
    model_dir: Optional[str],
    host: str = "127.0.0.1",
    port: int = 8801,
    *,
    max_batch: int = 64,
    warmup: bool = True,
    cache_size: int = 65536,
    max_inflight: int = 256,
    request_deadline: Optional[float] = 30.0,
    degraded_after: Optional[float] = 5.0,
    watch_dir: Optional[str] = None,
    watch_poll: float = 1.0,
    ann: bool = False,
    ann_clusters: int = -1,
    ann_nprobe: int = 8,
    ann_iters: int = 6,
    ann_sample: int = 65536,
    ann_recall_gate: float = 0.95,
    ann_recall_sample: int = 64,
    port_file: Optional[str] = None,
    trace_log: Optional[str] = None,
    flight_dir: Optional[str] = None,
    models: Optional[dict] = None,
    model_memory_budget=None,
    watch_models: Optional[str] = None,
) -> None:
    """Load a saved model (any family) and serve it until killed.

    ``watch_dir`` follows a streaming trainer's publish directory:
    ``model_dir=None`` then boots from its newest committed generation
    (waiting for the first one to appear), and every later generation
    hot-swaps in under load. ``port_file`` writes the bound
    ``{"host", "port"}`` atomically once the server is warmed and
    listening — the fleet launcher's (and CI's) readiness barrier for
    ``--port 0`` ephemeral replicas. ``trace_log`` installs a
    process-wide event recorder with a size-rotated JSONL sink (the
    per-replica half of distributed request tracing: ``cli
    trace-merge`` stitches these across processes); ``flight_dir``
    arms the anomaly flight recorder.

    Multi-model (ISSUE 20): ``models`` maps extra model ids to model
    dirs served from this same process; ``model_memory_budget``
    ("512mb", "2gb", or bytes) bounds their combined device residency
    with LRU stage-out; ``watch_models`` names a catalog root whose
    ``<id>/LATEST.json`` subdirectories each get their own model +
    per-model SnapshotWatcher (one trainer's publish rolls only its
    model)."""
    from glint_word2vec_tpu import load_model

    if trace_log:
        obs_events.set_recorder(
            obs_events.EventRecorder(jsonl_path=trace_log)
        )
    current = None
    model = None
    if model_dir is None:
        if watch_dir is None:
            raise ValueError("model_dir or watch_dir required")
        from glint_word2vec_tpu.streaming.publish import resolve_latest

        while True:
            gen_dir = resolve_latest(watch_dir)
            if gen_dir is None:
                logger.info(
                    "waiting for a first committed generation in %s",
                    watch_dir,
                )
                time.sleep(max(0.05, watch_poll))
                continue
            try:
                model = load_model(gen_dir)
            except Exception as e:
                # Retention can prune this generation while we read it
                # (a fast publish cadence and a slow cold load): chase
                # the pointer instead of dying at boot. An unchanged
                # pointer to a still-present dir is real corruption.
                if (
                    resolve_latest(watch_dir) != gen_dir
                    or not os.path.isdir(gen_dir)
                ):
                    logger.warning(
                        "boot load of %s failed (%s) — generation "
                        "pruned mid-read; chasing the pointer",
                        gen_dir, e,
                    )
                    time.sleep(max(0.05, watch_poll))
                    continue
                raise
            model_dir = gen_dir
            current = os.path.basename(gen_dir)
            break
    elif watch_dir is not None:
        # An explicit --model that names a generation inside the
        # watched dir is already loaded: seed the watcher with it so
        # the first poll doesn't redundantly re-stage and hot-swap the
        # very tables being served (spurious swap count + cache flush).
        md = os.path.abspath(model_dir)
        if os.path.dirname(md) == os.path.abspath(watch_dir):
            current = os.path.basename(md)
    if model is None:
        model = load_model(model_dir)
    if current is None and model_dir is not None:
        # Booting straight from a published generation dir (the fleet
        # supervisor's coordinated relaunch path): stamp the served
        # generation so the merged fleet view doesn't read "mixed"
        # forever just because this process never hot-swapped.
        from glint_word2vec_tpu.streaming.publish import _GEN_RE

        base = os.path.basename(os.path.normpath(model_dir))
        if _GEN_RE.match(base):
            current = base
    server = ModelServer(
        model, host=host, port=port,
        max_batch=max_batch, warmup=warmup, cache_size=cache_size,
        max_inflight=max_inflight, request_deadline=request_deadline,
        degraded_after=degraded_after,
        ann=ann, ann_clusters=ann_clusters, ann_nprobe=ann_nprobe,
        ann_iters=ann_iters, ann_sample=ann_sample,
        ann_recall_gate=ann_recall_gate,
        ann_recall_sample=ann_recall_sample,
    )
    if flight_dir:
        server.enable_flight_recorder(flight_dir)
    if model_memory_budget is not None:
        server.catalog.budget_bytes = parse_memory_budget(
            model_memory_budget
        )
    # Stamp the default model's snapshot source so the LRU could stage
    # it back in were it ever unpinned (it is pinned by default).
    server.catalog.default.source_dir = model_dir
    for mid in sorted(models or {}):
        server.add_model(mid, model_dir=(models or {})[mid])
    if watch_dir is not None:
        server.watch(watch_dir, poll_seconds=watch_poll, current=current)
    elif current is not None:
        server.metrics.generation = current
    if watch_models:
        from glint_word2vec_tpu.streaming.publish import (
            discover_model_publish_dirs,
            resolve_latest as _resolve_latest,
        )

        for mid, pub in sorted(
            discover_model_publish_dirs(watch_models).items()
        ):
            if mid == DEFAULT_MODEL_ID:
                w_mid = None
            elif mid in server.catalog.entries:
                w_mid = mid
            else:
                gen_dir = _resolve_latest(pub)
                if gen_dir is None:
                    logger.info(
                        "watch-models: %r has a pointer but no "
                        "committed generation — skipped", mid,
                    )
                    continue
                server.add_model(mid, model_dir=gen_dir)
                w_mid = mid
            entry = server._entry(w_mid)
            if entry.watcher is not None:
                continue  # --watch-checkpoint already covers it
            # Seed the watcher with the generation already loaded so
            # its first poll doesn't redundantly re-stage it.
            cur = None
            src = entry.source_dir
            if src is not None and os.path.dirname(
                os.path.abspath(src)
            ) == os.path.abspath(pub):
                cur = os.path.basename(os.path.normpath(src))
            server.watch(
                pub, poll_seconds=watch_poll, current=cur,
                model_id=w_mid,
            )
    if port_file:
        from glint_word2vec_tpu.utils import atomic_write_json

        atomic_write_json(
            port_file,
            {
                "host": server.host,
                "port": server.port,
                # Launch-generation handshake: the fleet supervisor
                # refuses a port file whose generation is not the one
                # it just launched (a stale file from the previous
                # incarnation must never be adopted as readiness).
                "fleet_generation": server.fleet_generation,
            },
        )
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        server.stop()
