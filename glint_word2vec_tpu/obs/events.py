"""Low-overhead span/event recording for run-wide observability.

The reference's entire training telemetry is one log line every 10k words
(mllib:399-413; SURVEY.md §5 "tracing: none"). This is the structured
replacement: instrumentation sites (the fit loops' phases, engine table
mutations, query-shape compiles) record spans and instant events into a
thread-safe bounded ring with an optional JSONL sink, and the ring
re-exports as a Chrome-trace (``chrome://tracing`` / Perfetto) JSON so
host spans can be eyeballed against the device xplane traces
``scripts/trace_summarize.py`` parses. In a process that has JAX loaded,
every ring-direct span is also a ``jax.profiler.TraceAnnotation``
(``glint.<name>``, see :class:`_Span`): a profiler capture shows the
spans beside the device's ops, on its own clock.

Three usage layers:

- ``EventRecorder`` — the recorder object a run owns (obs.ObsRun wires
  one per instrumented fit).
- Module-level ``emit(name, **args)`` / ``span(name, **args)`` — the
  process-wide hooks instrumentation sites call unconditionally. With no
  recorder installed they cost one global read (and ``span`` returns a
  shared no-op context manager), so the disabled path stays off the fit
  hot loop's profile.
- ``RequestTrace`` (ISSUE 18) — the per-request phase-span buffer the
  serving data plane uses for distributed tracing: the balancer mints a
  trace id (:func:`mint_trace_id`), propagates it over the wire
  (``X-Glint-Trace``), and every hop buffers its request-path phase
  spans locally, flushing them into the ring only when the tail-based
  sampler keeps the request (always: errors, sheds, slow requests;
  1-in-N otherwise). Request-path span NAMES are drawn from the
  :data:`REQUEST_SPANS` registry — graftlint's span-registry rule
  rejects ad-hoc string literals at instrumentation sites.
"""

from __future__ import annotations

import atexit
import itertools
import json
import logging
import os
import sys
import threading
import time
from collections import deque
from typing import Optional

logger = logging.getLogger(__name__)

#: The device-scope vocabulary: every ``jax.named_scope`` literal of the
#: package is a key here. An op's metadata carries the scopes it was traced
#: under, outermost first, and the device trace is split by them
#: (``benchmark/program_trace.py`` by the outer ``glint.<phase>``, a
#: per-layer reader by an inner name lifted into it): a scope renamed or
#: mistyped at one site silently moves its time to another metric.
#: graftlint's scope-registry rule holds every literal to this registry.
DEVICE_SCOPES = {
    "glint.batch": "a step's batch assembly on the device",
    "glint.sample": "negative sampling and its masks",
    "glint.gather": "row gathers (inner: the table) and the data-axis "
                    "gather of the hidden vectors",
    "glint.compose": "what a grouped centre or a bag adds to a step "
                     "(inner: group, bag, posgrad)",
    "glint.grads": "logits, coefficients, d_center, the loss",
    "glint.scatter": "the row updates (inner: the table)",
    "glint.exchange": "the model-axis all-reduces of a step",
    "glint.query": "a serving pull of query rows",
    "glint.score": "a shard's scores of its rows",
    "glint.topk": "a shard's own top-k",
    "glint.merge": "the model-axis merge of the shards' top-k",
    "syn0": "inner: the input table's rows",
    "syn1": "inner: the output table's rows",
    "group": "inner, under glint.compose: a span word's group summed once",
    "bag": "inner, under glint.compose: the bags' shifted (weighted) adds "
           "and their transpose",
    "posgrad": "inner, under glint.compose: the position table's lane "
               "reductions, their mean and the table's update",
}

#: The request-path span vocabulary (ISSUE 18). Every distributed-
#: tracing instrumentation site (``RequestTrace.phase`` /
#: ``RequestTrace.add_phase`` / module-level ``phase_span``) MUST name
#: its span with a literal key of this dict — graftlint's span-registry
#: checker statically enforces both directions (no ad-hoc names at
#: sites, no dead registry entries), so the trace-merge tooling and the
#: CI stitch assertions can rely on the vocabulary.
REQUEST_SPANS = {
    "req.accept": "per-process root span of one request hop "
                  "(balancer or replica handler, accept to response; the "
                  "replica's carries `cache`, hit or miss, of a word "
                  "query and `cpu_ms`, the handler thread's own CPU time "
                  "inside the span: the wall less it is the time the "
                  "thread did not run)",
    "req.head": "http.server's parse of the request line and headers: "
                "the request line read off the connection to the "
                "handler's entry, which lies before req.accept",
    "req.parse": "the body's read off the socket and its json.loads",
    "req.admission": "admission gate: inflight-slot acquire or shed",
    "req.lookup": "the coalescer's result-cache probe, the wait for its "
                  "lock included (args: hit)",
    "req.queue": "coalescer queue wait, enqueue to leader drain",
    "req.hop": "balancer -> replica proxy attempt (one per retry hop)",
    "req.dispatch": "warm-bucket device dispatch of one coalesced batch "
                    "(args: batch, mode, shards, traces, `programs`, the "
                    "query programs the round launched, and, only where "
                    "the round before named this one's leader, "
                    "`handoff_ms`: that round's events set to this "
                    "round's drain)",
    "req.pull": "what is left of the dispatch's row pull on the host: the "
                "words' row ids built, padded to their bucket and put on "
                "the device for the top-k to gather from (the approximate "
                "path: the pull's program and read-back) (child of "
                "req.dispatch; args: rows)",
    "req.compose": "the dispatch's compose of its out-of-dictionary "
                   "query words (subword family): host n-gram hashing, "
                   "one bucketed pull_average and its read-back (child "
                   "of req.dispatch; args: words, oov, slots, rows)",
    "req.enqueue": "one query program of a round put on the device's "
                   "queue: its arguments built, the jitted call made and "
                   "returned, the device not yet done (child of "
                   "req.dispatch; args: program, q, shards)",
    "req.result": "that program's outputs read back: from the enqueue's "
                  "return to the results held by the host, which is the "
                  "device's run, the transfer and the leader's turn at "
                  "the interpreter lock (child of req.dispatch; args: "
                  "program)",
    "req.decode": "the round's pure-Python tail: scores and ids on the "
                  "host to the results set on the requests, the "
                  "self-word filter included (child of req.dispatch; "
                  "args: batch)",
    "req.query": "engine query path up to the round's last enqueue "
                 "(args carry mode=ann|exact)",
    "req.readback": "device result harvest / host materialization: the "
                    "round's last enqueue returned to its results set",
    "req.wake": "a finished answer waiting for its handler thread: the "
                "leader's stamp before it sets the batch's events to the "
                "waiter's return from its wait",
    "req.serialize": "response serialization + socket write",
}

#: Wire header carrying the trace id across the balancer -> replica hop.
TRACE_HEADER = "X-Glint-Trace"

#: Tail-sampling knobs: keep 1 in GLINT_TRACE_SAMPLE of the healthy/fast
#: requests; always keep errors (status >= 400, which covers sheds and
#: deadline 504s) and requests slower than GLINT_TRACE_SLOW_MS.
_TRACE_SAMPLE_EVERY = max(
    1, int(os.environ.get("GLINT_TRACE_SAMPLE") or 32)
)
_TRACE_SLOW_MS = float(os.environ.get("GLINT_TRACE_SLOW_MS") or 250.0)

#: Default JSONL sink rotation bound (satellite: a long traced run must
#: not grow the sink without limit). One rotated generation is kept
#: (``<path>.1``), so worst-case disk is ~2x this.
_SINK_MAX_BYTES = int(
    os.environ.get("GLINT_EVENT_SINK_MAX_BYTES") or 64 * 1024 * 1024
)

_sample_counter = itertools.count()


def mint_trace_id() -> str:
    """A 16-hex-char request trace id (no RNG seeding interplay: reads
    the OS entropy pool directly)."""
    return os.urandom(8).hex()


class _NullSpan:
    """Shared no-op context manager returned when recording is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def update(self, **args) -> None:
        """No-op twin of :meth:`_Span.update`."""


NULL_SPAN = _NullSpan()


class _Span:
    """A ring-direct span. While it is open it is also a
    ``jax.profiler.TraceAnnotation`` named ``glint.<name>`` whose
    ``t0_us`` stat is the ``ts`` its ring event will carry: inside a
    profiler capture every such annotation is one reading of (trace
    clock - ring clock), which puts the whole ring, the request phases
    stamped after the fact included, on the device trace's clock. JAX is
    taken from ``sys.modules``: a process that never imported it (the
    balancer) records its spans and annotates nothing."""

    __slots__ = ("_rec", "_name", "_args", "_t0", "_ann")

    def __init__(self, rec: "EventRecorder", name: str, args: dict):
        self._rec = rec
        self._name = name
        self._args = args

    def __enter__(self):
        self._t0 = time.perf_counter()
        jax = sys.modules.get("jax")
        if jax is None:
            self._ann = None
        else:
            self._ann = jax.profiler.TraceAnnotation(
                "glint." + self._name, t0_us=self._rec._ts(self._t0)
            )
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._rec._record(self._name, "X", self._t0, t1 - self._t0, self._args)
        return False

    def update(self, **args) -> None:
        """Amend the span's attributes before it closes — for values only
        known mid-span (the packed train loop's live step count is read
        back from the device inside the span). Args are recorded at
        ``__exit__``, so updates land in the emitted event."""
        self._args.update(args)


class _Phase:
    """Context manager buffering one request phase span into its
    :class:`RequestTrace` (not the ring — the tail sampler decides at
    ``finish`` whether the buffered spans are flushed at all)."""

    __slots__ = ("_tr", "_name", "_args", "_t0")

    def __init__(self, tr: "RequestTrace", name: str, args: dict):
        self._tr = tr
        self._name = name
        self._args = args

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._tr._spans.append(
            (self._name, self._t0, t1 - self._t0, self._args)
        )
        return False

    def update(self, **args) -> None:
        self._args.update(args)


class NullRequestTrace:
    """Trace-id-carrying no-op returned when no recorder is installed:
    the id still propagates over the wire (a downstream hop may be
    recording even when this one is not), but nothing is buffered."""

    __slots__ = ("trace_id", "kept")

    #: Nothing is buffered: a site whose reading costs something (the
    #: thread's CPU clock) takes it on a live trace alone.
    live = False

    def __init__(self, trace_id: str):
        self.trace_id = trace_id
        self.kept = False

    def phase(self, name: str, **args):
        return NULL_SPAN

    def add_phase(self, name: str, t0: float, dur: float, **args) -> None:
        pass

    def annotate(self, **args) -> None:
        pass

    def finish(self, status: int = 200, *, force: bool = False) -> bool:
        return False


#: Shared stateless no-op trace for call sites whose caller passed no
#: trace at all (direct library use of the coalescer, tests).
NULL_TRACE = NullRequestTrace("")


class RequestTrace:
    """Per-request phase-span buffer with tail-based sampling.

    One hop's handler owns one instance (single-writer; cross-thread
    phases — the coalescer leader's queue/dispatch timestamps — are
    converted by the OWNING handler thread via :meth:`add_phase`).
    ``finish(status)`` applies the tail-sampling policy: errors/sheds
    (status >= 400), slow requests, and ``force`` are always kept;
    everything else is kept 1 in ``GLINT_TRACE_SAMPLE``. Kept spans are
    flushed into the recorder ring/sink with the trace id attached, so
    ``cli trace-merge`` can stitch hops across processes by id.
    """

    __slots__ = ("trace_id", "_rec", "_spans", "_root", "_t0", "kept")

    live = True

    def __init__(self, trace_id: str, rec: "EventRecorder"):
        self.trace_id = trace_id
        self._rec = rec
        self._spans: list = []
        self._root: dict = {}
        self._t0 = time.perf_counter()
        self.kept = False

    def phase(self, name: str, **args) -> _Phase:
        """Context manager buffering one registry-named phase span."""
        return _Phase(self, name, args)

    def add_phase(self, name: str, t0: float, dur: float, **args) -> None:
        """Buffer a phase from externally captured timestamps (the
        coalescer leader stamps perf_counter() pairs into the request
        dict; the waiter thread converts them here)."""
        self._spans.append((name, t0, dur, args))

    def annotate(self, **args) -> None:
        """Attributes of the hop's root span known only below it (the
        coalescer knows whether the cache answered, the handler owns
        ``req.accept``): merged into the root at :meth:`finish`."""
        self._root.update(args)

    def finish(self, status: int = 200, *, force: bool = False) -> bool:
        """Apply tail sampling; flush buffered spans if kept. Returns
        whether the trace was kept (the caller can use that to attach
        an exemplar only for traces that actually exist in the ring)."""
        spans, self._spans = self._spans, []
        if not spans:
            return False
        slow = (time.perf_counter() - self._t0) * 1e3 >= _TRACE_SLOW_MS
        keep = (
            force or slow or int(status) >= 400
            or next(_sample_counter) % _TRACE_SAMPLE_EVERY == 0
        )
        if not keep:
            return False
        # The root span (req.accept closes last, so it is the final
        # buffered entry) carries the response status and what the
        # layers below noted for it.
        spans[-1][3].update(self._root)
        spans[-1][3].setdefault("status", int(status))
        for name, t0, dur, args in spans:
            a = dict(args)
            a["trace"] = self.trace_id
            self._rec._record(name, "X", t0, dur, a)
        self.kept = True
        return True


def request_trace(trace_id: Optional[str] = None, rec=None):
    """Start one hop's request trace: adopts the propagated trace id (or
    mints one at the edge) and binds the process recorder. With no
    recorder installed this degrades to a :class:`NullRequestTrace` that
    still carries the id for onward propagation."""
    if rec is None:
        rec = _current
    tid = trace_id or mint_trace_id()
    if rec is None:
        return NullRequestTrace(tid)
    return RequestTrace(tid, rec)


class EventRecorder:
    """Thread-safe span/event log: the newest ``capacity`` events in a
    bounded ring (overflow counted in ``dropped``, never unbounded host
    memory) plus an optional JSONL sink that receives EVERY event.

    Event timestamps (``ts``, microseconds) run on a process-local
    monotonic clock anchored at recorder construction; the
    ``(mono_t0, wall_t0)`` pair recorded at creation (and emitted as the
    sink's ``clock_anchor`` metadata line) maps them back to the epoch,
    so multi-process merges align per-process timelines exactly. Span
    events use the Chrome-trace complete form (``ph: "X"`` with
    ``dur``), instants ``ph: "i"`` — each JSONL line IS a valid
    traceEvents entry, and :meth:`chrome_trace` wraps the ring into a
    full document.

    The sink is bounded: once it exceeds ``max_sink_bytes`` it rotates
    to ``<path>.1`` (one generation kept) and restarts with a fresh
    clock-anchor line, and an ``atexit`` flush makes sure an
    un-``close()``-d recorder still leaves complete lines behind.
    """

    def __init__(self, capacity: int = 65536,
                 jsonl_path: Optional[str] = None,
                 max_sink_bytes: Optional[int] = None):
        self._mu = threading.Lock()
        self._ring: deque = deque(maxlen=max(1, int(capacity)))
        self.recorded = 0
        self.dropped = 0
        self.jsonl_path = jsonl_path
        self.max_sink_bytes = int(
            max_sink_bytes if max_sink_bytes is not None
            else _SINK_MAX_BYTES
        )
        self.sink_rotations = 0
        self._sink_bytes = 0
        self.wall_t0 = time.time()
        self._t0 = time.perf_counter()
        # graftlint: ignore[atomic-persist] streaming JSONL sink, not an artifact: a crash leaves a valid line-prefix that the merge/summarize tools accept
        self._sink = open(jsonl_path, "w") if jsonl_path else None
        if self._sink is not None:
            self._write_anchor_locked()
            # Crash/exit hygiene: a run that never reaches close() (a
            # SIGTERMed replica, a test that leaks the recorder) still
            # flushes buffered lines. close() unregisters.
            atexit.register(self.flush)

    @property
    def capacity(self) -> int:
        return self._ring.maxlen

    @property
    def mono_t0(self) -> float:
        """Monotonic half of the clock-anchor pair (``ts`` zero)."""
        return self._t0

    def _anchor_args(self) -> dict:
        args = {"wall_t0": self.wall_t0, "mono_t0": self._t0}
        # A supervisor-minted gang trace id (one per launch generation)
        # stitches this process's whole timeline to its gang.
        gang = os.environ.get("GLINT_TRACE_ID")
        if gang:
            args["trace"] = gang
        return args

    def _write_anchor_locked(self) -> None:
        """Clock-anchor metadata line (Chrome-trace "M" event, ignored
        by viewers): the (monotonic, wall) epoch pair mapping this
        recorder's ts=0 back to the epoch, so trace-merge tools can
        align per-process JSONLs onto one shared timeline."""
        try:
            line = json.dumps({
                "name": "clock_anchor", "ph": "M", "ts": 0,
                "pid": os.getpid(),
                "args": self._anchor_args(),
            }) + "\n"
            self._sink.write(line)
            self._sink_bytes += len(line)
        except OSError as e:
            self._drop_sink_locked(e)

    def _rotate_sink_locked(self) -> None:
        """Size-bounded sink: rotate the full file to ``<path>.1`` and
        restart (fresh anchor). One kept generation bounds disk at
        ~2x ``max_sink_bytes`` however long the traced run lives."""
        try:
            self._sink.flush()
            self._sink.close()
            os.replace(self.jsonl_path, self.jsonl_path + ".1")
            # graftlint: ignore[atomic-persist] streaming JSONL sink (see __init__)
            self._sink = open(self.jsonl_path, "w")
        except OSError as e:
            self._drop_sink_locked(e)
            return
        self._sink_bytes = 0
        self.sink_rotations += 1
        self._write_anchor_locked()

    def _ts(self, t: float) -> float:
        """``perf_counter`` seconds -> this recorder's ``ts``."""
        return round((t - self._t0) * 1e6, 1)

    def _record(self, name: str, ph: str, t0: float, dur: float,
                args: dict) -> None:
        ev = {
            "name": name,
            "ph": ph,
            "ts": self._ts(t0),
            "pid": os.getpid(),
            "tid": threading.get_ident(),
        }
        if ph == "X":
            ev["dur"] = round(dur * 1e6, 1)
        else:
            ev["s"] = "t"  # instant scope: this thread
        if args:
            ev["args"] = args
        with self._mu:
            self.recorded += 1
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(ev)
            if self._sink is not None:
                try:
                    line = json.dumps(ev) + "\n"
                    self._sink.write(line)
                    self._sink_bytes += len(line)
                except OSError as e:
                    # Observability must never take down the run it
                    # monitors: a dying sink (disk full, quota) degrades
                    # to ring-only recording.
                    self._drop_sink_locked(e)
                    return
                if self._sink_bytes >= self.max_sink_bytes:
                    self._rotate_sink_locked()

    def event(self, name: str, **args) -> None:
        """Record one instant event."""
        self._record(name, "i", time.perf_counter(), 0.0, args)

    def span(self, name: str, **args) -> _Span:
        """Context manager recording one complete ("X") span on exit."""
        return _Span(self, name, args)

    def events(self) -> list:
        """Snapshot of the ring (oldest first)."""
        with self._mu:
            return list(self._ring)

    def recent_events(self, seconds: float) -> list:
        """Ring events whose span/instant started within the last
        ``seconds`` — the flight recorder's bundle window."""
        cutoff = (time.perf_counter() - self._t0 - seconds) * 1e6
        with self._mu:
            return [e for e in self._ring if e.get("ts", 0.0) >= cutoff]

    def counts(self) -> dict:
        with self._mu:
            return {
                "recorded": self.recorded,
                "dropped": self.dropped,
                "capacity": self._ring.maxlen,
            }

    # -- export --------------------------------------------------------

    def chrome_trace(self) -> dict:
        """The ring as a ``chrome://tracing`` / Perfetto JSON document."""
        events = self.events()
        with self._mu:
            dropped = self.dropped
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "wall_t0": self.wall_t0,
                "mono_t0": self._t0,
                "dropped": dropped,
            },
        }

    def export_chrome_trace(self, path: str) -> None:
        from glint_word2vec_tpu.utils import atomic_write_json

        atomic_write_json(path, self.chrome_trace())

    def _drop_sink_locked(self, err) -> None:
        """Disable the JSONL sink after an I/O failure; caller holds
        the lock. Recording continues ring-only."""
        logger.warning(
            "event-log sink %s failed, continuing ring-only: %s",
            self.jsonl_path, err,
        )
        sink, self._sink = self._sink, None
        try:
            sink.close()
        except OSError:
            pass

    def flush(self) -> None:
        with self._mu:
            if self._sink is not None:
                try:
                    self._sink.flush()
                except OSError as e:
                    self._drop_sink_locked(e)

    def close(self) -> None:
        with self._mu:
            if self._sink is not None:
                try:
                    self._sink.flush()
                    self._sink.close()
                except OSError as e:
                    logger.warning(
                        "event-log sink %s failed at close: %s",
                        self.jsonl_path, e,
                    )
                self._sink = None
                try:
                    atexit.unregister(self.flush)
                except Exception:  # pragma: no cover - interpreter teardown
                    pass


# ----------------------------------------------------------------------
# Process-wide current recorder: what engine-level instrumentation sites
# emit through without threading a recorder handle down every call path.
# ----------------------------------------------------------------------

_current: Optional[EventRecorder] = None


def set_recorder(rec: Optional[EventRecorder]) -> Optional[EventRecorder]:
    """Install the process-wide recorder (None disables). Returns ``rec``.
    One instrumented fit at a time is the supported shape; a second
    concurrent fit in the same process shares (or displaces) the
    recorder rather than corrupting anything."""
    global _current
    _current = rec
    return rec


def get_recorder() -> Optional[EventRecorder]:
    return _current


def emit(name: str, **args) -> None:
    """Instant event on the current recorder; no-op when recording is off."""
    rec = _current
    if rec is not None:
        rec.event(name, **args)


def span(name: str, **args):
    """Span on the current recorder; the shared no-op context manager
    when recording is off (the disabled path must cost ~nothing on the
    fit hot loop)."""
    rec = _current
    if rec is None:
        return NULL_SPAN
    return rec.span(name, **args)


def phase_span(name: str, **args):
    """Request-path span recorded DIRECTLY into the ring (never
    tail-sampled away): the coalescer leader's device-dispatch lane uses
    this so the stitched trace always shows the batch a kept request
    rode in. ``name`` must be a :data:`REQUEST_SPANS` literal —
    graftlint's span-registry rule checks call sites statically."""
    rec = _current
    if rec is None:
        return NULL_SPAN
    return rec.span(name, **args)
