"""Integration tests for run-wide observability on real fits (ISSUE 3
acceptance): an instrumented CPU fit produces a parseable JSONL event
log whose Chrome-trace export round-trips through json.loads, serves a
LIVE /healthz + /metrics (JSON and Prometheus) mid-fit, and a forced-NaN
run trips the canary abort path with a final checkpoint written."""

import json
import os
import urllib.request

import numpy as np
import pytest

from glint_word2vec_tpu import Word2Vec
from glint_word2vec_tpu.obs import ObsConfig, TrainingDiverged
from glint_word2vec_tpu.obs import events as obs_events
from glint_word2vec_tpu.obs.prometheus import lint_prometheus_text
from glint_word2vec_tpu.parallel.mesh import make_mesh


def _small(corpus, n=1200):
    return corpus[:n]


def test_instrumented_fit_event_log_and_chrome_trace(tiny_corpus, tmp_path):
    log = str(tmp_path / "events.jsonl")
    trace = str(tmp_path / "trace.json")
    status_file = str(tmp_path / "status.json")
    obs = ObsConfig(event_log=log, chrome_trace=trace,
                    status_file=status_file, status_interval=0.0)
    model = Word2Vec(
        mesh=make_mesh(1, 2), obs=obs, vector_size=16, min_count=5,
        batch_size=128, seed=3, num_iterations=1,
    ).fit(_small(tiny_corpus))
    assert model.training_metrics["steps"] > 0

    # JSONL event log: every line parses; the fit's phases and the
    # engine-level events are all present.
    events = [json.loads(line) for line in open(log) if line.strip()]
    names = {e["name"] for e in events}
    # The (dense-default) packed loop computes its LR schedule on
    # device, so the grid loop's host_batch span is replaced by the
    # deferred readback_harvest seam.
    assert {"run_start", "run_end", "readback_harvest", "device_steps",
            "upload_corpus", "table_mutation"} <= names
    spans = [e for e in events if e["ph"] == "X"]
    assert spans and all(e["dur"] >= 0 for e in spans)

    # Chrome-trace export round-trips through json.loads with the
    # traceEvents structure chrome://tracing / Perfetto expects.
    doc = json.loads(open(trace).read())
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    assert {"name", "ph", "ts"} <= set(doc["traceEvents"][0])

    # Status file: final atomic write has the terminal state and real
    # progress; no temp file leftovers from the atomic writes.
    status = json.loads(open(status_file).read())
    assert status["state"] == "done"
    assert status["step"] > 0 and status["words_done"] > 0
    assert status["pipeline"] == "device_corpus"
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]

    # The process-wide recorder was uninstalled at close.
    assert obs_events.get_recorder() is None
    model.stop()


def test_heartbeat_live_during_fit_both_formats(tiny_corpus, tmp_path,
                                                monkeypatch):
    # Deterministic "live mid-fit" probe: the first dispatched group
    # queries the heartbeat from inside the fit (the server runs on its
    # own daemon thread), so there is no race against fit completion.
    monkeypatch.setenv("GLINT_HOST_BATCHER", "1")
    status_file = str(tmp_path / "status.json")
    obs = ObsConfig(status_port=0, status_file=status_file,
                    status_interval=0.0)
    seen = {}
    orig = Word2Vec._train_batches

    def spy(self, engine, batches, base_key, step0, alphas):
        if not seen:
            port = obs.bound_port
            assert port
            for path, key in (("/healthz", "healthz"),
                              ("/metrics", "metrics")):
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=30
                ) as r:
                    seen[key] = json.loads(r.read())
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics?format=prometheus",
                timeout=30,
            ) as r:
                seen["prom"] = r.read().decode()
        return orig(self, engine, batches, base_key, step0, alphas)

    monkeypatch.setattr(Word2Vec, "_train_batches", spy)
    model = Word2Vec(
        mesh=make_mesh(1, 2), obs=obs, vector_size=16, min_count=5,
        batch_size=128, seed=3, num_iterations=1,
    ).fit(_small(tiny_corpus))

    assert seen["healthz"]["status"] == "ok"
    assert seen["healthz"]["state"] == "running"
    assert seen["metrics"]["pipeline"] == "host"
    assert seen["metrics"]["total_epochs"] == 1
    lint_prometheus_text(seen["prom"])
    assert "glint_training_words_per_sec" in seen["prom"]
    # After the fit the server is down and the status file is terminal.
    assert json.loads(open(status_file).read())["state"] == "done"
    with pytest.raises(Exception):
        urllib.request.urlopen(
            f"http://127.0.0.1:{obs.bound_port}/healthz", timeout=2
        )
    model.stop()


def test_canary_abort_writes_final_checkpoint_and_flushes(tiny_corpus,
                                                          tmp_path,
                                                          monkeypatch):
    # Forced-NaN run: the host-batcher loop gets NaN losses from the
    # first dispatch; the abort canary must save ckpt-diverged (WITHOUT
    # flipping train_state.json), flush the event log with the
    # canary_trip event, mark the status diverged, and raise.
    monkeypatch.setenv("GLINT_HOST_BATCHER", "1")
    ckdir = str(tmp_path / "ck")
    log = str(tmp_path / "events.jsonl")
    status_file = str(tmp_path / "status.json")
    obs = ObsConfig(event_log=log, status_file=status_file,
                    status_interval=0.0, canary="abort",
                    canary_check_every=1)

    def nan_batches(self, engine, batches, base_key, step0, alphas):
        return np.full(len(batches), np.nan, np.float32)

    monkeypatch.setattr(Word2Vec, "_train_batches", nan_batches)
    w2v = Word2Vec(
        mesh=make_mesh(1, 2), obs=obs, vector_size=16, min_count=5,
        batch_size=128, seed=3, num_iterations=1,
    )
    with pytest.raises(TrainingDiverged, match="non-finite"):
        w2v.fit(_small(tiny_corpus), checkpoint_dir=ckdir)

    # Final post-mortem snapshot written...
    diverged = os.path.join(ckdir, "ckpt-diverged")
    assert os.path.isdir(diverged)
    assert os.path.exists(os.path.join(diverged, "engine.json"))
    # ...but resume state NOT flipped to it (no healthy epoch finished).
    assert not os.path.exists(os.path.join(ckdir, "train_state.json"))

    events = [json.loads(line) for line in open(log) if line.strip()]
    trip = [e for e in events if e["name"] == "canary_trip"]
    assert trip and trip[0]["args"]["mode"] == "abort"
    assert json.loads(open(status_file).read())["state"] == "diverged"
    assert obs_events.get_recorder() is None


def test_crashed_fit_publishes_failed_not_done(tiny_corpus, tmp_path,
                                               monkeypatch):
    # A fit dying on an ordinary exception must not leave a status file
    # claiming success — monitoring keys off this state.
    monkeypatch.setenv("GLINT_HOST_BATCHER", "1")
    status_file = str(tmp_path / "status.json")
    obs = ObsConfig(status_file=status_file, status_interval=0.0)

    def boom(self, engine, batches, base_key, step0, alphas):
        raise RuntimeError("device fell over")

    monkeypatch.setattr(Word2Vec, "_train_batches", boom)
    with pytest.raises(RuntimeError, match="device fell over"):
        Word2Vec(
            mesh=make_mesh(1, 2), obs=obs, vector_size=16, min_count=5,
            batch_size=128, seed=3, num_iterations=1,
        ).fit(_small(tiny_corpus))
    assert json.loads(open(status_file).read())["state"] == "failed"
    assert obs_events.get_recorder() is None


def test_fit_inside_except_block_still_publishes_done(tiny_corpus,
                                                      tmp_path,
                                                      monkeypatch):
    # Retry/fallback pattern: a successful fit launched from inside a
    # caller's except handler must publish "done" (failure is an
    # explicit signal from the fit loop, never sniffed from the
    # thread's in-flight exception).
    monkeypatch.setenv("GLINT_HOST_BATCHER", "1")
    status_file = str(tmp_path / "status.json")
    obs = ObsConfig(status_file=status_file, status_interval=0.0)
    try:
        raise FileNotFoundError("no cached model")
    except FileNotFoundError:
        model = Word2Vec(
            mesh=make_mesh(1, 2), obs=obs, vector_size=16, min_count=5,
            batch_size=128, seed=3, num_iterations=1,
        ).fit(_small(tiny_corpus))
    assert json.loads(open(status_file).read())["state"] == "done"
    model.stop()


def test_canary_warn_keeps_training(tiny_corpus, tmp_path, monkeypatch):
    monkeypatch.setenv("GLINT_HOST_BATCHER", "1")
    status_file = str(tmp_path / "status.json")
    obs = ObsConfig(status_file=status_file, status_interval=0.0,
                    canary="warn", canary_check_every=1)

    def nan_batches(self, engine, batches, base_key, step0, alphas):
        return np.full(len(batches), np.nan, np.float32)

    monkeypatch.setattr(Word2Vec, "_train_batches", nan_batches)
    model = Word2Vec(
        mesh=make_mesh(1, 2), obs=obs, vector_size=16, min_count=5,
        batch_size=128, seed=3, num_iterations=1,
    ).fit(_small(tiny_corpus))
    # Warn mode completes the fit; trips are visible in the status file.
    status = json.loads(open(status_file).read())
    assert status["state"] == "done"
    assert status["canary"]["mode"] == "warn"
    assert status["canary"]["trips"] >= 1
    model.stop()


def test_canary_abort_on_device_corpus_path(tiny_corpus, monkeypatch):
    # The device-resident corpus loop shares the canary plumbing: NaN
    # losses from the scanned corpus dispatch must abort there too.
    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine

    def nan_steps_packed(self, start_position, pair_batch, window,
                         grid_batch, base_key, n_steps, step0=0,
                         grid_step0=0, **kw):
        # NaN losses + whole-corpus position advance: the (dense
        # default) fit loop harvests one real step and the canary must
        # trip on it.
        K = int(n_steps)
        return (
            np.full(K, np.nan, np.float32),
            np.full(K, int(pair_batch), np.int64),
            np.full(K, 10**9, np.int64),
            np.full(K, 0.025, np.float32),
            np.zeros((K, 2), np.int32),
        )

    monkeypatch.setattr(
        EmbeddingEngine, "train_steps_corpus_packed", nan_steps_packed
    )
    obs = ObsConfig(canary="abort", canary_check_every=1)
    w2v = Word2Vec(
        mesh=make_mesh(1, 2), obs=obs, vector_size=16, min_count=5,
        batch_size=128, seed=3, num_iterations=1,
    )
    with pytest.raises(TrainingDiverged, match="non-finite"):
        w2v.fit(_small(tiny_corpus))


def test_steptime_ledger_attributes_fit_wall_time(tiny_corpus, tmp_path):
    # ISSUE 8 acceptance: STEPTIME.json phase totals sum to within 5%
    # of the measured fit wall time, the breakdown reaches the status
    # file / training_metrics, and the attribution is real (the span
    # gap folded into "other" stays a small share of the run).
    steptime = str(tmp_path / "STEPTIME.json")
    status_file = str(tmp_path / "status.json")
    obs = ObsConfig(steptime_path=steptime, status_file=status_file,
                    status_interval=0.0)
    model = Word2Vec(
        mesh=make_mesh(1, 2), obs=obs, vector_size=16, min_count=5,
        batch_size=128, seed=3, num_iterations=2,
    ).fit(_small(tiny_corpus))

    doc = json.loads(open(steptime).read())
    assert doc["schema_version"] == 1
    phases = doc["phases"]
    from glint_word2vec_tpu.utils.metrics import LEDGER_PHASES

    assert set(phases) == set(LEDGER_PHASES)
    total = sum(p["seconds"] for p in phases.values())
    # Phase totals are a decomposition of the ledger's wall clock...
    assert total == pytest.approx(doc["wall_seconds"], rel=0.05)
    # ...and the ledger's wall clock IS the fit's (both wrap the same
    # loop; construction-order skew only).
    fit_wall = model.training_metrics["wall_seconds"]
    assert total == pytest.approx(fit_wall, rel=0.05, abs=0.75)
    # The attribution is real: the device dispatch phase was exercised
    # and the unattributed gap is a minor share of the run.
    assert phases["dispatch"]["seconds"] > 0
    assert phases["dispatch"]["count"] > 0
    assert phases["dispatch"]["p50_ms"] > 0
    assert doc["unattributed_seconds"] <= 0.5 * doc["wall_seconds"]

    # Same breakdown on the heartbeat snapshot (with histogram state
    # for the gang aggregator) and in training_metrics.
    status = json.loads(open(status_file).read())
    st = status["steptime"]
    assert st["phases"]["dispatch"]["count"] == phases["dispatch"]["count"]
    assert st["phases"]["dispatch"]["hist"]["n"] > 0
    tm = model.training_metrics["steptime"]
    assert set(tm) == set(LEDGER_PHASES)
    assert tm["dispatch"] > 0
    model.stop()


def test_steptime_ledger_costs_nothing_when_obs_off(tiny_corpus):
    # The satellite bound: with obs off the fit loops' span hooks stay
    # on the NULL_SPAN path — no ledger exists, no steptime key appears.
    model = Word2Vec(
        mesh=make_mesh(1, 2), vector_size=16, min_count=5,
        batch_size=128, seed=3, num_iterations=1,
    ).fit(_small(tiny_corpus))
    assert "steptime" not in model.training_metrics
    from glint_word2vec_tpu.obs import NULL_RUN

    assert NULL_RUN.steptime_totals() is None
    assert NULL_RUN.span("device_steps") is obs_events.NULL_SPAN
    model.stop()


@pytest.mark.slow
def test_event_recorder_overhead_within_3_percent(tiny_corpus, tmp_path):
    # ISSUE 3 overhead guard, bench-style. An end-to-end A/B of two fits
    # is noise-bound on a shared 2-core host (identical consecutive fits
    # swing ~2x words/sec — the A/B numbers are recorded in
    # BENCH_OBS.json via bench.py's obs_overhead mode). Assert the 3%
    # bound the stable way instead: from one real instrumented fit,
    # measure (a) the wall time of a dispatch group and (b) how many
    # recorder operations the run issued per group, then microbench the
    # recorder's per-operation cost — the product is the throughput tax
    # the recorder can charge, and it must be <= 3% of the group time.
    import time as _time

    from glint_word2vec_tpu.obs.events import EventRecorder

    log = str(tmp_path / "events.jsonl")
    obs = ObsConfig(
        event_log=log, chrome_trace=str(tmp_path / "trace.json"),
        status_port=0, status_file=str(tmp_path / "status.json"),
        canary="warn",
    )
    model = Word2Vec(
        mesh=make_mesh(1, 1), obs=obs, vector_size=32, min_count=5,
        batch_size=256, seed=3, num_iterations=2,
    ).fit(tiny_corpus)
    model.stop()

    events = [json.loads(line) for line in open(log) if line.strip()]
    groups = [e for e in events if e["name"] == "device_steps"]
    assert groups
    mean_group_us = sum(e["dur"] for e in groups) / len(groups)
    ops_per_group = len(events) / len(groups)  # everything the run logged

    # Per-operation recorder cost, JSONL sink included, measured hot.
    rec = EventRecorder(capacity=1024,
                        jsonl_path=str(tmp_path / "micro.jsonl"))
    n = 20000
    t0 = _time.perf_counter()
    for _ in range(n):
        with rec.span("s", a=1):
            pass
    per_op_us = (_time.perf_counter() - t0) / n * 1e6
    rec.close()

    overhead = per_op_us * ops_per_group / mean_group_us
    assert overhead <= 0.03, (per_op_us, ops_per_group, mean_group_us)
