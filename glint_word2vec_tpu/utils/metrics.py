"""Training observability: throughput counters, step timing, profiler hook.

The reference's only training telemetry is a log line every 10k words with
the annealed alpha and the last positive dot product as a divergence canary
(mllib:399-413; SURVEY.md §5 "tracing: none"). This module is the richer
TPU-native replacement: words/sec and steps/sec over a sliding window,
wall-clock split between host batching and device step dispatch, a running
loss, and an optional ``jax.profiler`` trace capture around a step range.
"""

from __future__ import annotations

import bisect
import contextlib
import logging
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

logger = logging.getLogger(__name__)


@dataclass
class TrainingMetrics:
    """Accumulates per-run training statistics; cheap enough for every step."""

    log_every: int = 200
    #: Global words_done at construction (nonzero after a checkpoint
    #: resume); rates count only words processed by this invocation.
    base_words: int = 0
    steps: int = 0
    words_done: int = 0
    host_time: float = 0.0  # seconds spent producing batches
    step_time: float = 0.0  # seconds spent in train-step dispatch
    #: Host-side seconds during which the dispatch pipeline was starved:
    #: blocking checkpoint saves, waits for the batch producer, and
    #: epoch-boundary compaction syncs. An upper-bound PROXY for device
    #: idle time (the host may block on work the device is still busy
    #: with), but its direction is exact: async checkpointing, deferred
    #: readbacks, and prefetch overlap each shrink it (ISSUE 5).
    stall_time: float = 0.0
    last_loss: Optional[float] = None
    #: Most recent per-step loss as an UNSYNCED device array; float()ed
    #: only at log points and in summary().
    _last_loss_lazy: Optional[object] = None
    #: The first recorded step's loss, kept unsynced the same way: with
    #: ``final_loss`` it says whether the run learned anything.
    first_loss: Optional[float] = None
    _first_loss_lazy: Optional[object] = None
    _t_start: float = field(default_factory=time.time)
    _t_window: float = field(default_factory=time.time)
    _words_window: int = -1  # sentinel: initialized on first record_step
    history: List[dict] = field(default_factory=list)
    #: Bound on retained history entries: one dict lands every
    #: ``log_every`` steps, and unbounded it leaks host memory into the
    #: final dump() on long production runs. Oldest entries drop first;
    #: ``history_dropped`` counts them so the dump is honest about gaps.
    history_max: int = 4096
    history_dropped: int = 0

    def __post_init__(self) -> None:
        self.words_done = self.base_words
        self._words_window = self.base_words
        self.history = deque(self.history, maxlen=max(1, self.history_max))

    def record_step(self, words_done: int, loss=None, alpha=None) -> None:
        self.steps += 1
        self.words_done = words_done
        if loss is not None:
            # Keep the device array without forcing a sync: float() blocks
            # the dispatch pipeline, so it happens only at log points and
            # in summary() — never per step.
            self._last_loss_lazy = loss
            if self.steps == 1:
                self._first_loss_lazy = loss
        if self.steps % self.log_every == 0:
            now = time.time()
            wps = (words_done - self._words_window) / max(now - self._t_window, 1e-9)
            if loss is not None:
                # graftlint: ignore[sync-point] deliberate log-cadence sync: once per log_every groups, never per step
                self.last_loss = float(loss)
            entry = {
                "step": self.steps,
                "words_done": words_done,
                "words_per_sec": round(wps, 1),
                "alpha": alpha,
                "loss": self.last_loss,
                "host_frac": round(
                    self.host_time / max(self.host_time + self.step_time, 1e-9), 3
                ),
            }
            if len(self.history) == self.history.maxlen:
                self.history_dropped += 1
            self.history.append(entry)
            logger.info(
                "step %d: %.0f words/s alpha=%s loss=%s host_frac=%s",
                self.steps, wps, alpha, self.last_loss, entry["host_frac"],
            )
            self._t_window, self._words_window = now, words_done

    @contextlib.contextmanager
    def timing(self, kind: str):
        t0 = time.time()
        try:
            yield
        finally:
            dt = time.time() - t0
            if kind == "host":
                self.host_time += dt
            else:
                self.step_time += dt

    def record_stall(self, seconds: float) -> None:
        self.stall_time += seconds

    @contextlib.contextmanager
    def stall_timing(self):
        """Charge the wrapped block to ``stall_time`` (composable with
        :meth:`timing`; the buckets are independent)."""
        t0 = time.time()
        try:
            yield
        finally:
            self.record_stall(time.time() - t0)

    def summary(self) -> dict:
        wall = max(time.time() - self._t_start, 1e-9)
        if self._last_loss_lazy is not None:
            # One sync at summary time so short runs (fewer than log_every
            # steps) still report a final loss. An async dispatch failure
            # surfaces here, not at the step — keep the last synced loss
            # rather than crashing the summary; either way drop the device
            # buffer so it is not pinned for the run's lifetime.
            try:
                # graftlint: ignore[sync-point] the one end-of-fit lazy-loss sync; the device is idle by the time summary() runs
                self.last_loss = float(self._last_loss_lazy)
            except Exception as e:
                # Keep the last synced loss, but never silently: a stale
                # last_loss with no trace hid real dispatch failures
                # (ADVICE.md round 5).
                logger.warning(
                    "final lazy-loss sync failed (last_loss=%s may be "
                    "stale): %s", self.last_loss, e,
                )
            self._last_loss_lazy = None
        if self._first_loss_lazy is not None:
            # graftlint: ignore[sync-point] end-of-fit sync of a loss the device finished long ago
            self.first_loss = float(self._first_loss_lazy)
            self._first_loss_lazy = None
        return {
            "steps": self.steps,
            "words_done": self.words_done,
            "wall_seconds": round(wall, 2),
            "words_per_sec": round((self.words_done - self.base_words) / wall, 1),
            "host_time": round(self.host_time, 2),
            "step_time": round(self.step_time, 2),
            "device_stall_seconds": round(self.stall_time, 3),
            "first_loss": self.first_loss,
            "final_loss": self.last_loss,
        }

    def dump(self, path: str) -> None:
        """Atomic (temp + ``os.replace``): a crash mid-write can never
        leave a truncated JSON that poisons downstream tooling."""
        from glint_word2vec_tpu.utils import atomic_write_json

        atomic_write_json(path, {
            "summary": self.summary(),
            "history": list(self.history),
            "history_dropped": self.history_dropped,
        })


def scatter_summary(rows_written, steps: int, slots) -> dict:
    """``training_metrics`` keys of the packed steps' scatters, from the
    scans' summed ``rows_written[:4]`` (distinct rows written into syn0 and
    syn1, then the slabs the slab writer moved for them), the live steps
    and the update slots one step hands the scatters (syn0, syn1:
    ``EmbeddingEngine.packed_scatter_slots``). Rows written over slots
    handed (the step sums a row's duplicates before it writes), both
    tables and each; where the slab writer ran (ops/slab_writer.py),
    distinct rows over the slabs it moved, 1 to 8 (16 in bfloat16): XLA's
    writer moves none. {} for a fit whose steps wrote nothing."""
    rows, slabs = rows_written[:2], rows_written[2:4]
    if not (any(rows) or any(slabs)) or not steps:
        return {}
    out = {
        "scatter_distinct_share": round(
            sum(rows) / (steps * sum(slots)), 4),
        "scatter_distinct_share_syn0": round(
            rows[0] / (steps * slots[0]), 4),
        "scatter_distinct_share_syn1": round(
            rows[1] / (steps * slots[1]), 4),
    }
    if all(slabs):
        out.update(
            scatter_rows_per_slab=round(sum(rows) / sum(slabs), 4),
            scatter_rows_per_slab_syn0=round(rows[0] / slabs[0], 4),
            scatter_rows_per_slab_syn1=round(rows[1] / slabs[1], 4),
        )
    return out


class LatencyHistogram:
    """Fixed log-spaced latency histogram: O(1) memory per endpoint,
    quantiles by linear interpolation inside the winning bucket.

    Bucket edges run 50µs .. ~20min with a sqrt(2) growth factor, so
    every quantile estimate is within ~±20% of the true value — plenty
    for the p50/p95/p99 serving dashboards this feeds (the reference has
    no serving telemetry at all, SURVEY.md §5)."""

    _EDGES = [5e-5 * (2 ** (i / 2.0)) for i in range(64)]

    __slots__ = ("counts", "n", "total", "max")

    def __init__(self) -> None:
        self.counts = [0] * (len(self._EDGES) + 1)
        self.n = 0
        self.total = 0.0
        self.max = 0.0

    def record(self, seconds: float) -> None:
        self.counts[bisect.bisect_right(self._EDGES, seconds)] += 1
        self.n += 1
        self.total += seconds
        if seconds > self.max:
            self.max = seconds

    def quantile(self, q: float) -> float:
        if self.n == 0:
            return 0.0
        target = q * self.n
        acc = 0
        for i, c in enumerate(self.counts):
            acc += c
            if c and acc >= target:
                lo = self._EDGES[i - 1] if i > 0 else 0.0
                hi = self._EDGES[i] if i < len(self._EDGES) else self.max
                hi = min(max(hi, lo), self.max) if self.max else hi
                return lo + (hi - lo) * ((target - (acc - c)) / c)
        return self.max

    # -- cross-process state / merging (ISSUE 8) -----------------------
    # The gang aggregator merges per-rank histograms into one fleet
    # histogram: bucket counts are position-aligned (every instance
    # shares _EDGES), so merging is exact — the merged quantiles are
    # what one histogram fed the whole population would report.

    def state(self) -> dict:
        """JSON-serializable snapshot of the histogram (sparse bucket
        counts), for crossing a process boundary (heartbeat status
        files, serving /metrics) into :meth:`from_state`/:meth:`merge`."""
        return {
            "counts": {
                str(i): c for i, c in enumerate(self.counts) if c
            },
            "n": self.n,
            "total": self.total,
            "max": self.max,
        }

    @classmethod
    def from_state(cls, state: dict) -> "LatencyHistogram":
        h = cls()
        for i, c in (state.get("counts") or {}).items():
            i = int(i)
            if 0 <= i < len(h.counts):
                h.counts[i] += int(c)
        h.n = int(state.get("n", 0))
        h.total = float(state.get("total", 0.0))
        h.max = float(state.get("max", 0.0))
        return h

    @classmethod
    def merge(cls, hists) -> "LatencyHistogram":
        """Merge histograms (objects or :meth:`state` dicts) into a new
        one. Bucket-exact: recorded into parts then merged equals
        recorded whole, except the quantile interpolation clamp, which
        uses the merged (global) max."""
        out = cls()
        for h in hists:
            if isinstance(h, dict):
                h = cls.from_state(h)
            for i, c in enumerate(h.counts):
                out.counts[i] += c
            out.n += h.n
            out.total += h.total
            if h.max > out.max:
                out.max = h.max
        return out


#: The step-time attribution ledger's named phases (ISSUE 8). Fixed so
#: dashboards, STEPTIME.json trends, and the gang aggregator never see a
#: phase they don't know:
#:   dispatch          — device train-step dispatch calls
#:   readback_harvest  — converting a dispatched group's result scalars
#:   producer_wait     — waiting on / running host batch production
#:   compact           — on-device subsample-compact passes (+ prefetch
#:                       dispatch)
#:   checkpoint        — snapshot copies, blocking saves, restores
#:   other             — explicitly-charged misc (corpus upload) plus
#:                       the wall-clock gap no span covered
LEDGER_PHASES = (
    "dispatch", "readback_harvest", "producer_wait", "compact",
    "checkpoint", "other",
)


class StepTimeLedger:
    """Step-time attribution for one fit: every accounted span charges
    its wall time to a named phase, replacing the single
    ``device_stall_seconds`` proxy with a breakdown that says WHERE the
    wall went (ISSUE 8). Fed by ``obs.ObsRun.span`` — the fit loops'
    existing span instrumentation — so when observability is off the
    cost is the NULL_SPAN path's single module-global read.

    Per phase: total seconds, span count, and a :class:`LatencyHistogram`
    of span durations (the gang aggregator merges these across ranks).
    Thread-safe; in practice only the fit thread accounts (ObsRun.span
    is a fit-loop hook — producer/writer threads use the module-level
    recorder hooks, which bypass the ledger by design so writer-thread
    time can never inflate a wall-clock-sum breakdown)."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._t0 = time.time()
        self._t_end: Optional[float] = None
        self._seconds = {p: 0.0 for p in LEDGER_PHASES}
        self._counts = {p: 0 for p in LEDGER_PHASES}
        self._hists = {p: LatencyHistogram() for p in LEDGER_PHASES}
        #: Gang trace id (supervisor-minted, one per launch generation,
        #: ISSUE 18): rides the snapshot so the training/gang Prometheus
        #: steptime histograms can carry it as an exemplar pointing at
        #: the merged gang trace.
        self.trace_id: Optional[str] = os.environ.get("GLINT_TRACE_ID")

    def account(self, phase: str, seconds: float) -> None:
        with self._mu:
            self._seconds[phase] += seconds
            self._counts[phase] += 1
            self._hists[phase].record(seconds)

    def finalize(self) -> None:
        """Freeze the wall clock (run end); later snapshots stop growing
        ``other``. Idempotent — first call wins."""
        with self._mu:
            if self._t_end is None:
                self._t_end = time.time()

    def wall_seconds(self) -> float:
        with self._mu:
            return (self._t_end or time.time()) - self._t0

    def totals(self) -> Dict[str, float]:
        """{phase: seconds} with the unattributed wall gap folded into
        ``other`` — the phases sum to the ledger's wall clock."""
        snap = self.snapshot(include_hists=False)
        return {
            p: info["seconds"] for p, info in snap["phases"].items()
        }

    def snapshot(self, include_hists: bool = True) -> dict:
        """Full breakdown: wall, per-phase seconds/count (+ histogram
        state for cross-rank merging), and the unattributed gap, which
        is folded into ``other`` so the phase totals always sum to the
        wall clock."""
        with self._mu:
            wall = (self._t_end or time.time()) - self._t0
            accounted = sum(self._seconds.values())
            gap = max(0.0, wall - accounted)
            phases = {}
            for p in LEDGER_PHASES:
                info = {
                    "seconds": round(
                        self._seconds[p] + (gap if p == "other" else 0.0),
                        4,
                    ),
                    "count": self._counts[p],
                }
                if include_hists:
                    info["hist"] = self._hists[p].state()
                phases[p] = info
            out = {
                "wall_seconds": round(wall, 4),
                "accounted_seconds": round(accounted, 4),
                "unattributed_seconds": round(gap, 4),
                "phases": phases,
            }
            if self.trace_id:
                out["trace_id"] = self.trace_id
            return out

    def dump(self, path: str) -> None:
        """Write the per-run STEPTIME.json artifact (atomic): the phase
        breakdown plus per-phase span-duration quantiles, so bench
        trends can attribute a regression to a phase."""
        from glint_word2vec_tpu.utils import atomic_write_json

        snap = self.snapshot(include_hists=False)
        with self._mu:
            for p in LEDGER_PHASES:
                h = self._hists[p]
                snap["phases"][p].update(
                    p50_ms=round(h.quantile(0.50) * 1e3, 3),
                    p95_ms=round(h.quantile(0.95) * 1e3, 3),
                    p99_ms=round(h.quantile(0.99) * 1e3, 3),
                )
        snap["schema_version"] = 1
        atomic_write_json(path, snap)


class ServingMetrics:
    """Serving-path observability for ``serving.ModelServer``:
    per-endpoint latency histograms (p50/p95/p99), request/error
    counters, the coalesced-batch-size distribution, and the engine's
    query-shape compile counters — surfaced on ``/healthz`` and the
    ``/metrics`` endpoint. Thread-safe (the HTTP server is threaded)."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._hist: Dict[str, LatencyHistogram] = {}
        self._errors: Dict[str, int] = {}
        self._batches: Dict[int, int] = {}
        #: Per-endpoint latency exemplar (ISSUE 18): the latest KEPT
        #: request trace's id + observed latency, so the Prometheus
        #: exposition can point a dashboard at a trace that actually
        #: exists in the span ring (tail sampling guarantees kept ids).
        self._exemplars: Dict[str, dict] = {}
        #: Optional obs.slo.SloEngine fed by :meth:`observe` (attached
        #: by the server, duck-typed here — utils must not import obs).
        self.slo = None
        #: Engine query-shape compiles at the end of server warmup;
        #: ``snapshot`` reports compiles past this as ``post_warmup``.
        self.warmup_compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        # Overload-protection counters (ISSUE 7): sheds by reason,
        # deadline 504s, degraded-mode entries, in-flight peak.
        self.shed_admission = 0
        self.shed_degraded = 0
        self.deadline_hits = 0
        self.degraded_entered = 0
        self.inflight_peak = 0
        # Hot-swap accounting (ISSUE 10): table generations flipped
        # into the live engine by the snapshot watcher / /reload.
        self.table_swaps = 0
        self.swap_failures = 0
        #: Transient publish-dir read errors the snapshot watcher
        #: absorbed (backed off and retried instead of marking the
        #: generation failed).
        self.watch_errors = 0
        self.last_swap_time: Optional[float] = None
        #: Name of the generation currently served (None until the
        #: first swap names one — a freshly-loaded model predates the
        #: publish protocol's naming).
        self.generation: Optional[str] = None
        # ANN index accounting (ISSUE 12): refresh/build telemetry set
        # by the server on every index build (boot + each hot-swap),
        # query-side counters fed by the coalescer's dispatches.
        self.index_enabled = False
        self.index_refreshes = 0
        self.index_stats: dict = {}
        self.index_recall_at10: Optional[float] = None
        self.index_recall_gate_ok: Optional[bool] = None
        self.index_recall_gate: Optional[float] = None
        self.index_nprobe: Optional[int] = None
        self.last_index_refresh_time: Optional[float] = None
        self.ann_queries = 0
        self.ann_probes = 0
        self.exact_fallbacks: Dict[str, int] = {}
        # Subword family (ISSUE 43): the coalesced rounds' composes of
        # out-of-dictionary query words.
        self.oov_queries = 0
        self.compose_dispatches = 0
        self.compose_slots = 0
        self.compose_rows = 0

    #: Cap on distinct tracked endpoint paths: the key is the raw
    #: client-supplied request path, and without a bound a port scanner
    #: (or a path-building client bug) grows one persistent histogram
    #: per probe for the server's lifetime. Overflow aggregates under
    #: "_other". 64 >> the real endpoint count.
    MAX_PATHS = 64

    def observe(self, path: str, seconds: float, status: int = 200,
                trace_id: Optional[str] = None) -> None:
        with self._mu:
            h = self._hist.get(path)
            if h is None:
                if len(self._hist) >= self.MAX_PATHS:
                    path = "_other"
                    h = self._hist.get(path)
                if h is None:
                    h = self._hist[path] = LatencyHistogram()
            h.record(seconds)
            if status >= 400:
                self._errors[path] = self._errors.get(path, 0) + 1
            if trace_id:
                self._exemplars[path] = {
                    "trace_id": trace_id,
                    "value_ms": round(seconds * 1e3, 3),
                }
        slo = self.slo
        if slo is not None:
            # Outside the metrics lock: the engine has its own (fixed
            # lock order, no nesting).
            slo.observe(path, seconds, status)

    def record_batch(self, size: int) -> None:
        """One coalesced device dispatch of ``size`` queries."""
        with self._mu:
            self._batches[size] = self._batches.get(size, 0) + 1

    def record_cache(self, hit: bool) -> None:
        """One synonym result-cache lookup."""
        with self._mu:
            if hit:
                self.cache_hits += 1
            else:
                self.cache_misses += 1

    def record_shed(self, kind: str) -> None:
        """One shed request: ``"admission"`` (past the in-flight
        high-water mark, 429) or ``"degraded"`` (cache-only mode
        shedding a device-needing request, 429)."""
        with self._mu:
            if kind == "admission":
                self.shed_admission += 1
            else:
                self.shed_degraded += 1

    def record_deadline(self) -> None:
        """One request answered 504: its deadline passed before it
        could reach the device."""
        with self._mu:
            self.deadline_hits += 1

    def record_degraded_entered(self) -> None:
        """One transition INTO degraded cache-only mode."""
        with self._mu:
            self.degraded_entered += 1

    def record_inflight(self, n: int) -> None:
        """Track the admitted in-flight high-water mark."""
        with self._mu:
            if n > self.inflight_peak:
                self.inflight_peak = n

    def record_swap(self, generation: Optional[str] = None,
                    ok: bool = True) -> None:
        """One hot-swap attempt: ``ok`` flips the live generation,
        failure means the previous tables stayed live (staging or
        verification rejected the candidate)."""
        with self._mu:
            if ok:
                self.table_swaps += 1
                self.last_swap_time = time.time()
                if generation is not None:
                    self.generation = generation
            else:
                self.swap_failures += 1

    def record_watch_error(self) -> None:
        """One transient ``LATEST.json``/generation-dir read failure
        the snapshot watcher absorbed: it backed off and will retry on
        a later poll — the pointer was NOT marked failed and the
        watcher thread did not stall."""
        with self._mu:
            self.watch_errors += 1

    def record_index_refresh(self, stats: dict, recall: Optional[float],
                             gate_ok: Optional[bool], gate: float,
                             nprobe: int) -> None:
        """One index build/refresh (boot or hot-swap staging): the
        engine's ``ann_stats()`` dict plus the measured recall@10 of
        the approximate path against the exact path on the same tables
        and the pass/fail of the recall gate."""
        with self._mu:
            self.index_enabled = True
            self.index_refreshes += 1
            self.index_stats = dict(stats)
            self.index_recall_at10 = (
                # graftlint: ignore[sync-point] recall is a host float from the gate measurement
                round(float(recall), 4) if recall is not None else None
            )
            self.index_recall_gate_ok = gate_ok
            self.index_recall_gate = float(gate)  # graftlint: ignore[sync-point] host config scalar
            self.index_nprobe = int(nprobe)  # graftlint: ignore[sync-point] host config scalar
            self.last_index_refresh_time = time.time()

    def record_ann_query(self, n: int, nprobe: int) -> None:
        """``n`` queries answered through the coarse index, each
        probing ``nprobe`` clusters."""
        with self._mu:
            self.ann_queries += int(n)  # graftlint: ignore[sync-point] host batch-size count
            # graftlint: ignore[sync-point] host counts from the coalescer
            self.ann_probes += int(n) * int(nprobe)

    def record_exact_fallback(self, n: int, reason: str) -> None:
        """``n`` queries served by the EXACT path while the index is
        enabled: ``"requested"`` (the per-request ``exact=true`` escape
        hatch) or ``"gate"`` (the recall gate is failing, so the server
        held the approximate path back)."""
        with self._mu:
            self.exact_fallbacks[reason] = (
                # graftlint: ignore[sync-point] host batch-size count
                self.exact_fallbacks.get(reason, 0) + int(n)
            )

    def record_compose(self, words: int, slots: int, rows: int) -> None:
        """One coalesced round's compose of ``words`` out-of-dictionary
        query words: ``slots`` group slots gathered (the bucket's
        padding included; 0 where no word had a group and nothing was
        dispatched), ``rows`` of them live table rows."""
        with self._mu:
            self.oov_queries += words
            self.compose_dispatches += 1 if slots else 0
            self.compose_slots += slots
            self.compose_rows += rows

    def snapshot(self, total_compiles: int = 0,
                 checkpoint: Optional[dict] = None,
                 index_staleness: Optional[int] = None,
                 composed_table: Optional[dict] = None) -> dict:
        """``checkpoint`` is the engine's ``checkpoint_stats()`` dict
        (pending_async_saves / last_checkpoint_age_seconds /
        checkpoint_write_seconds); serving a freshly-loaded model reports
        Nones — the keys exist either way so dashboards never branch.
        ``composed_table`` is the subword family's ``{"builds",
        "seconds"}`` of its composed word table (zeros elsewhere)."""
        slo = self.slo
        slo_snap = slo.snapshot() if slo is not None else None
        with self._mu:
            endpoints = {}
            for path, h in sorted(self._hist.items()):
                endpoints[path] = {
                    "count": h.n,
                    "errors": self._errors.get(path, 0),
                    "p50_ms": round(h.quantile(0.50) * 1e3, 3),
                    "p95_ms": round(h.quantile(0.95) * 1e3, 3),
                    "p99_ms": round(h.quantile(0.99) * 1e3, 3),
                    "mean_ms": round(h.total / max(h.n, 1) * 1e3, 3),
                    "max_ms": round(h.max * 1e3, 3),
                    # Raw histogram state: quantiles cannot be merged,
                    # bucket counts can — the fleet aggregator combines
                    # replica snapshots exactly (obs.aggregate).
                    "hist": h.state(),
                }
                ex = self._exemplars.get(path)
                if ex:
                    endpoints[path]["exemplar"] = dict(ex)
            out = {
                "endpoints": endpoints,
                "coalesced_batch_sizes": {
                    str(k): v for k, v in sorted(self._batches.items())
                },
                "synonym_cache": {
                    "hits": self.cache_hits,
                    "misses": self.cache_misses,
                },
                "overload": {
                    "shed_admission_total": self.shed_admission,
                    "shed_degraded_total": self.shed_degraded,
                    "deadline_504_total": self.deadline_hits,
                    "degraded_entered_total": self.degraded_entered,
                    "inflight_peak": self.inflight_peak,
                },
                "compiles": {
                    "total": int(total_compiles),
                    "warmup": int(self.warmup_compiles),
                    "post_warmup": int(total_compiles)
                    - int(self.warmup_compiles),
                },
                "hot_swap": {
                    "table_swaps_total": self.table_swaps,
                    "swap_failures_total": self.swap_failures,
                    "watch_errors_total": self.watch_errors,
                    "last_swap_age_seconds": (
                        round(time.time() - self.last_swap_time, 2)
                        if self.last_swap_time else None
                    ),
                    "generation": self.generation,
                },
                "checkpoint": {
                    "pending_async_saves": (checkpoint or {}).get(
                        "pending_async_saves", 0
                    ),
                    "last_checkpoint_age_seconds": (checkpoint or {}).get(
                        "last_checkpoint_age_seconds"
                    ),
                    "checkpoint_write_seconds": (checkpoint or {}).get(
                        "checkpoint_write_seconds"
                    ),
                },
                "index": {
                    "enabled": self.index_enabled,
                    "clusters": self.index_stats.get("clusters"),
                    "member_slots": self.index_stats.get("member_slots"),
                    "nprobe": self.index_nprobe,
                    "build_seconds": self.index_stats.get("build_seconds"),
                    "spilled_rows": self.index_stats.get("spilled_rows"),
                    "updated_rows": self.index_stats.get("updated_rows"),
                    "refreshes_total": self.index_refreshes,
                    "last_refresh_age_seconds": (
                        round(time.time() - self.last_index_refresh_time, 2)
                        if self.last_index_refresh_time else None
                    ),
                    "recall_at10": self.index_recall_at10,
                    "recall_gate_ok": self.index_recall_gate_ok,
                    "recall_gate_threshold": self.index_recall_gate,
                    "ann_queries_total": self.ann_queries,
                    "probes_total": self.ann_probes,
                    "probes_per_query": (
                        round(self.ann_probes / self.ann_queries, 2)
                        if self.ann_queries else None
                    ),
                    "exact_fallbacks": dict(self.exact_fallbacks),
                    "table_versions_behind": index_staleness,
                },
            }
            out["compose"] = {
                "oov_queries_total": self.oov_queries,
                "dispatches_total": self.compose_dispatches,
                "group_slots_total": self.compose_slots,
                "group_rows_total": self.compose_rows,
                "table_builds_total": (composed_table or {}).get(
                    "builds", 0
                ),
                "table_build_seconds_total": round(
                    (composed_table or {}).get("seconds", 0.0), 3
                ),
            }
            if slo_snap is not None:
                out["slo"] = slo_snap
            return out


@contextlib.contextmanager
def profile_trace(out_dir: Optional[str]):
    """Capture a ``jax.profiler`` trace into ``out_dir`` (None = no-op).

    View with TensorBoard's profile plugin or xprof; the TPU-native answer
    to the reference having no profiling at all (SURVEY.md §5).
    """
    if not out_dir:
        yield
        return
    import jax

    jax.profiler.start_trace(out_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
