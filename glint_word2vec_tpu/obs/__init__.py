"""Run-wide observability: the layer both training and serving feed.

Four pieces (ISSUE 3), all opt-in and all zero-cost when disabled:

- **Span event log** (:mod:`obs.events`): a thread-safe bounded ring +
  JSONL sink instrumenting the fit loops' phases (subsample-compact,
  host batching, device step dispatch, checkpoint save/restore) and
  engine-level events (table mutations ticking ``table_version``,
  query-shape compiles, warmup), exportable as a Chrome-trace JSON for
  side-by-side reading with device xplane traces
  (``scripts/trace_summarize.py --host-spans``).
- **Live training heartbeat** (:mod:`obs.heartbeat`): ``/healthz`` +
  ``/metrics`` (JSON and Prometheus) on the training process, plus an
  atomic status-file mirror for multihost workers that can't bind ports.
- **Divergence canary** (:mod:`obs.canary`): rolling-loss NaN/explosion
  detection, warn-or-abort; abort writes a final checkpoint and flushes
  the event log before raising :class:`TrainingDiverged`.
- **Prometheus exposition** (:mod:`obs.prometheus`): text-format
  renderers for the training and serving snapshots, shared by the
  heartbeat server and ``serving.ModelServer``.

The training loops own one :class:`ObsRun` per fit (``start_run``
returns the shared no-op :data:`NULL_RUN` when observability is off, so
the hot loop calls its hooks unconditionally).
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from typing import Optional

from glint_word2vec_tpu.obs import events
from glint_word2vec_tpu.obs.canary import DivergenceCanary, TrainingDiverged
from glint_word2vec_tpu.obs.events import EventRecorder
from glint_word2vec_tpu.obs.heartbeat import HeartbeatServer, TrainingStatus
from glint_word2vec_tpu.utils.metrics import StepTimeLedger

__all__ = [
    "DivergenceCanary", "EventRecorder", "HeartbeatServer", "NULL_RUN",
    "ObsConfig", "ObsRun", "StepTimeLedger", "TrainingDiverged",
    "TrainingStatus", "start_run",
]

logger = logging.getLogger(__name__)


@dataclass
class ObsConfig:
    """Observability configuration for ONE fit invocation.

    Deliberately NOT part of ``Word2VecParams``: it never affects the
    trained model and must not be persisted into ``params.json`` (a
    model is loadable on a machine with none of these paths/ports)."""

    #: JSONL sink receiving every span/event (None = ring only).
    event_log: Optional[str] = None
    #: Bounded in-memory event ring size; overflow counts as dropped.
    event_capacity: int = 65536
    #: Chrome-trace (chrome://tracing / Perfetto) JSON written at run end.
    chrome_trace: Optional[str] = None
    #: Force the event recorder on even with no sink configured.
    record_events: bool = False
    #: Heartbeat HTTP port (None = no server; 0 = ephemeral — the bound
    #: port is published back on ``bound_port``).
    status_port: Optional[int] = None
    status_host: str = "127.0.0.1"
    #: Atomic JSON mirror of the status snapshot, for multihost workers
    #: that can't bind ports; rewritten at most every status_interval s.
    status_file: Optional[str] = None
    status_interval: float = 1.0
    #: Divergence canary: "off", "warn" (log + event), or "abort"
    #: (final checkpoint + event flush, then TrainingDiverged).
    canary: str = "off"
    canary_window: int = 64
    canary_factor: float = 10.0
    #: Steps between canary loss syncs. Each check forces one device
    #: sync (blocking the async dispatch pipeline), so keep >> 1 on
    #: real runs; 1 checks every group.
    canary_check_every: int = 32
    #: Per-run step-time attribution artifact (STEPTIME.json): the
    #: ledger's phase breakdown + per-phase quantiles, written
    #: atomically at run end. The ledger itself runs whenever
    #: observability is enabled (it rides the fit loops' ObsRun.span
    #: hooks); this only controls the file dump.
    steptime_path: Optional[str] = None
    #: Filled in by start_run when a heartbeat server binds.
    bound_port: Optional[int] = None

    @property
    def wants_recorder(self) -> bool:
        return bool(self.event_log or self.chrome_trace or self.record_events)

    @property
    def enabled(self) -> bool:
        return bool(
            self.wants_recorder
            or self.status_port is not None
            or self.status_file
            or self.canary != "off"
            or self.steptime_path
        )


#: Span-name -> ledger-phase map for the step-time attribution ledger
#: (ISSUE 8). Only these fit-thread spans are accounted — nested or
#: engine-internal spans (subword_expand inside device_steps, ckpt_write
#: on the writer thread) are deliberately absent so phase totals stay a
#: non-overlapping decomposition of the fit thread's wall clock.
_LEDGER_PHASE_OF = {
    "device_steps": "dispatch",
    "readback_harvest": "readback_harvest",
    "host_batch": "producer_wait",
    "subsample_compact": "compact",
    "subsample_prefetch": "compact",
    "ckpt_snapshot": "checkpoint",
    "checkpoint_save": "checkpoint",
    "checkpoint_restore": "checkpoint",
    "upload_corpus": "other",
}


class _LedgerSpan:
    """Context manager charging a span's wall time to a ledger phase on
    top of (optionally) recording it. ``with`` yields the inner span so
    ``span.update(...)`` keeps working at instrumentation sites."""

    __slots__ = ("_ledger", "_phase", "_inner", "_t0")

    def __init__(self, ledger, phase: str, inner):
        self._ledger = ledger
        self._phase = phase
        self._inner = inner

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self._inner.__enter__()

    def __exit__(self, *exc):
        self._ledger.account(
            self._phase, time.perf_counter() - self._t0
        )
        return self._inner.__exit__(*exc)


class _NullRun:
    """Disabled observability: every hook the fit loops call is a no-op,
    so instrumentation costs ~nothing when off."""

    recorder = None
    canary = None
    status = None
    server = None
    ledger = None

    def span(self, name: str, **args):
        return events.NULL_SPAN

    def event(self, name: str, **args) -> None:
        pass

    def steptime_totals(self):
        return None

    def attach_metrics(self, metrics) -> None:
        pass

    def update(self, **kw) -> None:
        pass

    def update_streaming(self, **kw) -> None:
        pass

    def update_transform(self, **kw) -> None:
        pass

    def observe_losses(self, first_step: int, losses, n_real: int) -> None:
        pass

    def note_end(self, **args) -> None:
        pass

    def close(self, failed: bool = False) -> None:
        pass


NULL_RUN = _NullRun()


class ObsRun:
    """Wired-up observability for one fit: event recorder (installed as
    the process-wide recorder so engine-level sites emit too), heartbeat
    server, status-file mirror, and divergence canary — owned by the
    training loop through the four hooks ``span``/``update``/
    ``observe_losses``/``close``."""

    def __init__(self, config: ObsConfig, *, pipeline: str = "",
                 total_epochs: int = 0, total_words: int = 0, engine=None):
        self.config = config
        self.recorder = (
            EventRecorder(config.event_capacity, config.event_log)
            if config.wants_recorder else None
        )
        #: Step-time attribution ledger (ISSUE 8): every phase-mapped
        #: ObsRun.span charges it, so the breakdown exists whenever
        #: observability is on — heartbeat, Prometheus, STEPTIME.json.
        self.ledger = StepTimeLedger()
        self._prev_recorder = events.get_recorder()
        events.set_recorder(self.recorder)
        try:
            self.canary = (
                DivergenceCanary(window=config.canary_window,
                                 factor=config.canary_factor)
                if config.canary != "off" else None
            )
            self.status = TrainingStatus(
                pipeline=pipeline, total_epochs=total_epochs,
                total_words=total_words, engine=engine,
                recorder=self.recorder, ledger=self.ledger,
            )
            if self.canary is not None:
                self.status.set_canary(config.canary, 0, None)
            self.server: Optional[HeartbeatServer] = None
            if config.status_port is not None:
                self.server = HeartbeatServer(
                    self.status, config.status_host, config.status_port
                )
                self.server.start()
                config.bound_port = self.server.port
                logger.info(
                    "training heartbeat on http://%s:%d "
                    "(/healthz, /metrics)",
                    self.server.host, self.server.port,
                )
        except BaseException:
            # A constructor failure (e.g. EADDRINUSE on --status-port)
            # yields no ObsRun for the fit loop to close(): uninstall the
            # process-wide recorder and release the sink here or they
            # leak for the process lifetime.
            events.set_recorder(self._prev_recorder)
            if self.recorder is not None:
                self.recorder.close()
            raise
        self._status_written = 0.0
        self._since_check = 0
        self._aborted = False
        self._closed = False
        self._end_args: dict = {}
        self.status.update(state="running")
        self.event("run_start", pipeline=pipeline, total_epochs=total_epochs)
        self._write_status(force=True)

    def attach_metrics(self, metrics) -> None:
        self.status.attach(metrics=metrics)

    # -- hooks for the fit loops ---------------------------------------

    def span(self, name: str, **args):
        inner = (
            self.recorder.span(name, **args)
            if self.recorder is not None else events.NULL_SPAN
        )
        phase = _LEDGER_PHASE_OF.get(name)
        if phase is None:
            return inner
        return _LedgerSpan(self.ledger, phase, inner)

    def steptime_totals(self) -> dict:
        """{phase: seconds} (unattributed gap folded into ``other``) —
        what the fit loops surface in ``training_metrics``."""
        return {
            p: round(s, 3) for p, s in self.ledger.totals().items()
        }

    def event(self, name: str, **args) -> None:
        if self.recorder is not None:
            self.recorder.event(name, **args)

    def update(self, **kw) -> None:
        self.status.update(**kw)
        self._write_status()

    def update_streaming(self, **kw) -> None:
        """Streaming-trainer gauge hook (ISSUE 10): forwards to
        ``TrainingStatus.set_streaming`` and mirrors the status file on
        the usual cadence."""
        self.status.set_streaming(**kw)
        self._write_status()

    def update_transform(self, **kw) -> None:
        """Bulk-transform gauge hook (ISSUE 17): forwards to
        ``TrainingStatus.set_transform`` and mirrors the status file on
        the usual cadence — the supervisor's liveness sweep reads the
        same heartbeat it reads for training ranks."""
        self.status.set_transform(**kw)
        self._write_status()

    def observe_losses(self, first_step: int, losses, n_real: int) -> None:
        """Canary hook, called after each dispatched group with the (K,)
        lazy per-step loss array. Syncs ONE loss every
        ``canary_check_every`` steps (a sync blocks the dispatch
        pipeline — that cost is the whole reason for the cadence) and
        runs it through the rolling window. Warn mode logs and records
        an event; abort mode flushes the event log and raises
        :class:`TrainingDiverged` — the fit loop writes the final
        checkpoint on the way out."""
        if self.canary is None or n_real <= 0:
            return
        self._since_check += n_real
        if self._since_check < max(1, self.config.canary_check_every):
            return
        self._since_check = 0
        step = first_step + n_real
        try:
            val = float(losses[n_real - 1])
        except Exception as e:
            # Under async dispatch a poisoned buffer raises at read
            # time: the failed sync IS the divergence signal.
            logger.warning("canary loss sync failed at step %d: %s", step, e)
            val = float("nan")
        reason = self.canary.check(step, val)
        if reason is None:
            return
        self.status.set_canary(
            self.config.canary, self.canary.trips, reason
        )
        self.event("canary_trip", step=step, mode=self.config.canary,
                   reason=reason)
        if self.config.canary == "abort":
            self._aborted = True
            self.status.update(state="diverged")
            if self.recorder is not None:
                self.recorder.flush()
            self._write_status(force=True)
            raise TrainingDiverged(reason)
        logger.warning("divergence canary: %s", reason)

    # -- status file ----------------------------------------------------

    def _write_status(self, force: bool = False) -> None:
        path = self.config.status_file
        if not path:
            return
        now = time.time()
        if not force and now - self._status_written < self.config.status_interval:
            return
        self._status_written = now
        from glint_word2vec_tpu.utils import atomic_write_json

        try:
            atomic_write_json(path, self.status.snapshot())
        except OSError as e:
            logger.warning("status-file write to %s failed: %s", path, e)
        # Ride the (throttled) status cadence to keep the JSONL event
        # sink near-current on disk: a SIGKILLed worker's last ~1s of
        # events is then recoverable by the supervisor's crash flight
        # recorder (the postmortem bundle copies the sink file).
        if self.recorder is not None:
            self.recorder.flush()

    def note_end(self, **args) -> None:
        """What the fit knows only at its end (the position table's
        state): carried by the ``run_end`` event."""
        self._end_args.update(args)

    def close(self, failed: bool = False) -> None:
        """Idempotent teardown: final state, Chrome-trace export, JSONL
        flush/close, recorder uninstall, final status write, server stop.

        The fit loops call ``close(failed=True)`` from their generic
        exception handler and plain ``close()`` from ``finally`` (first
        call wins): a crashed run must never publish a status file that
        looks like success, and a successful run must not be misread as
        failed just because a caller invoked fit inside an ``except``
        block (which is why this takes an explicit flag instead of
        sniffing ``sys.exc_info``)."""
        if self._closed:
            return
        self._closed = True
        if self._aborted:
            state = "diverged"
        elif failed:
            state = "failed"
        else:
            state = "done"
        self.status.update(state=state)
        self.event("run_end", state=state, **self._end_args)
        self.ledger.finalize()
        if self.config.steptime_path:
            try:
                self.ledger.dump(self.config.steptime_path)
            except OSError as e:
                logger.warning(
                    "STEPTIME dump to %s failed: %s",
                    self.config.steptime_path, e,
                )
        if self.recorder is not None:
            if self.config.chrome_trace:
                self.recorder.export_chrome_trace(self.config.chrome_trace)
            self.recorder.close()
        events.set_recorder(self._prev_recorder)
        self._write_status(force=True)
        if self.server is not None:
            self.server.stop()
            self.server = None


def start_run(config: Optional[ObsConfig], **kw):
    """:data:`NULL_RUN` when observability is off; a live ObsRun else."""
    if config is None or not config.enabled:
        return NULL_RUN
    return ObsRun(config, **kw)
