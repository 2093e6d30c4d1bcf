"""End-to-end request tracing, SLO burn-rate engine, and anomaly flight
recorder (ISSUE 18): tail-based sampling semantics, bounded JSONL sinks
with fresh clock anchors on rotation, the multi-window burn-rate math
on a fake clock, flight-recorder bundles (including the breaker-open
drill through a traced stub fleet), the trace-merge collector's
cross-process stitching, and the Prometheus renderer edge cases
(label escaping, non-finite values, empty snapshots, exemplars)."""

import importlib.util
import itertools
import json
import math
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from glint_word2vec_tpu.fleet import LoadBalancer
from glint_word2vec_tpu.obs import events as obs_events
from glint_word2vec_tpu.obs.aggregate import merge_trace_logs
from glint_word2vec_tpu.obs.events import EventRecorder
from glint_word2vec_tpu.obs.prometheus import (
    _esc,
    _num,
    fleet_to_prometheus,
    gang_to_prometheus,
    lint_prometheus_text,
    serving_to_prometheus,
    training_to_prometheus,
)
from glint_word2vec_tpu.obs.slo import (
    FlightRecorder,
    ShedBurstDetector,
    SloEngine,
    SloObjective,
    merge_slo_snapshots,
)
from glint_word2vec_tpu.utils.metrics import ServingMetrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_global_recorder():
    """Tests install process-wide recorders; never leak one."""
    prev = obs_events.get_recorder()
    yield
    obs_events.set_recorder(prev)


# ----------------------------------------------------------------------
# RequestTrace: tail-based sampling
# ----------------------------------------------------------------------


def _trace(rec):
    return obs_events.request_trace(rec=rec)


def test_tail_sampling_drops_fast_ok_requests(tmp_path, monkeypatch):
    monkeypatch.setattr(obs_events, "_TRACE_SAMPLE_EVERY", 10**9)
    monkeypatch.setattr(obs_events, "_TRACE_SLOW_MS", 10**9)
    # Pin the head-sample counter off zero: 0 % N == 0 would keep the
    # process's very first request regardless of the stride.
    monkeypatch.setattr(obs_events, "_sample_counter", itertools.count(1))
    rec = EventRecorder()
    tr = _trace(rec)
    with tr.phase("req.accept", path="/synonyms"):
        with tr.phase("req.query"):
            pass
    assert tr.finish(200) is False and tr.kept is False
    assert rec.events() == []  # buffered spans discarded, not recorded


def test_tail_sampling_always_keeps_errors(monkeypatch):
    monkeypatch.setattr(obs_events, "_TRACE_SAMPLE_EVERY", 10**9)
    monkeypatch.setattr(obs_events, "_TRACE_SLOW_MS", 10**9)
    rec = EventRecorder()
    tr = _trace(rec)
    with tr.phase("req.accept", path="/x"):
        pass
    assert tr.finish(503) is True
    evs = rec.events()
    assert len(evs) == 1
    # Every flushed span carries the trace id; the root span carries
    # the final status.
    assert evs[0]["args"]["trace"] == tr.trace_id
    assert evs[0]["args"]["status"] == 503


def test_tail_sampling_keeps_slow_requests(monkeypatch):
    monkeypatch.setattr(obs_events, "_TRACE_SAMPLE_EVERY", 10**9)
    monkeypatch.setattr(obs_events, "_TRACE_SLOW_MS", 0.0)
    rec = EventRecorder()
    tr = _trace(rec)
    with tr.phase("req.accept"):
        pass
    assert tr.finish(200) is True


def test_tail_sampling_keeps_forced_and_sampled(monkeypatch):
    monkeypatch.setattr(obs_events, "_TRACE_SLOW_MS", 10**9)
    monkeypatch.setattr(obs_events, "_TRACE_SAMPLE_EVERY", 10**9)
    rec = EventRecorder()
    tr = _trace(rec)
    with tr.phase("req.accept"):
        pass
    assert tr.finish(200, force=True) is True
    # Sample-every-1: every request is head-sampled regardless of
    # status or latency.
    monkeypatch.setattr(obs_events, "_TRACE_SAMPLE_EVERY", 1)
    tr2 = _trace(rec)
    with tr2.phase("req.accept"):
        pass
    assert tr2.finish(200) is True


def test_trace_id_adoption_and_minting():
    # No recorder: a null trace that still CARRIES the id downstream.
    tr = obs_events.request_trace("abc123", rec=None)
    assert isinstance(tr, obs_events.NullRequestTrace)
    assert tr.trace_id == "abc123"
    with tr.phase("req.hop", replica=0) as hop:
        hop.update(outcome=200)
    assert tr.finish(200) is False
    # No id propagated: the edge mints one.
    minted = obs_events.request_trace(None, rec=None)
    assert minted.trace_id and minted.trace_id != "abc123"
    assert obs_events.NULL_TRACE.trace_id == ""


def test_request_span_registry_is_closed():
    assert set(obs_events.REQUEST_SPANS) == {
        "req.accept", "req.admission", "req.queue", "req.hop",
        "req.dispatch", "req.pull", "req.compose",
        "req.query", "req.readback", "req.serialize",
        # ISSUE 52: the round's launch, read-back and decode; the
        # request's head, body parse, cache probe and wake
        "req.enqueue", "req.result", "req.decode",
        "req.head", "req.parse", "req.lookup", "req.wake",
    }
    assert obs_events.TRACE_HEADER.lower() == "x-glint-trace"


# ----------------------------------------------------------------------
# EventRecorder sink: rotation + anchors
# ----------------------------------------------------------------------


def test_sink_rotates_at_size_bound_with_fresh_anchor(tmp_path):
    log = str(tmp_path / "events.jsonl")
    rec = EventRecorder(jsonl_path=log, max_sink_bytes=2048)
    for i in range(200):
        rec.event("filler", i=i, pad="x" * 40)
    rec.close()
    assert rec.sink_rotations >= 1
    assert os.path.exists(log) and os.path.exists(log + ".1")
    # Disk stays bounded at ~2 generations of max_sink_bytes.
    assert os.path.getsize(log) + os.path.getsize(log + ".1") < 3 * 2048
    for path in (log, log + ".1"):
        first = json.loads(open(path).readline())
        assert first["name"] == "clock_anchor" and first["ph"] == "M"
        # The (monotonic, wall) pair the merge tools rebase with.
        assert first["args"]["wall_t0"] == rec.wall_t0
        assert first["args"]["mono_t0"] == rec.mono_t0


def test_anchor_carries_gang_trace_id(tmp_path, monkeypatch):
    monkeypatch.setenv("GLINT_TRACE_ID", "gang777")
    log = str(tmp_path / "events.jsonl")
    rec = EventRecorder(jsonl_path=log)
    rec.close()
    first = json.loads(open(log).readline())
    assert first["args"]["trace"] == "gang777"


def test_recent_events_window():
    rec = EventRecorder()
    rec.event("old")
    rec.event("new")
    assert [e["name"] for e in rec.recent_events(60.0)] == ["old", "new"]
    assert rec.recent_events(0.0) == []


# ----------------------------------------------------------------------
# SLO engine: multi-window burn rates on a fake clock
# ----------------------------------------------------------------------


class FakeClock:
    def __init__(self, t=100000.0):
        self.t = t

    def __call__(self):
        return self.t


def test_slo_windows_and_fast_burn_alert():
    clk = FakeClock()
    eng = SloEngine(
        [SloObjective("/synonyms", availability_target=0.999,
                      latency_target=0.99, latency_threshold_ms=250.0)],
        now_fn=clk,
    )
    # 100 requests over ~100s: half 500s — a 500x burn, over every
    # trigger on both the 5m and 1h windows.
    for i in range(100):
        eng.observe("/synonyms", 0.01, 500 if i % 2 else 200)
        clk.t += 1.0
    snap = eng.snapshot()
    ep = snap["endpoints"]["/synonyms"]
    assert ep["windows"]["5m"]["total"] == 100
    assert ep["windows"]["5m"]["bad_availability"] == 50
    assert ep["windows"]["6h"]["total"] == 100
    assert ep["burn_rates"]["availability"]["5m"] > 14.4
    assert ep["alerts"]["fast_burn"] is True
    # Latency SLI is measured over non-5xx only: all good responses
    # were 10ms, so latency burn stays 0.
    assert ep["burn_rates"]["latency"]["5m"] == 0.0
    # Endpoints without an objective are ignored (bounded cardinality).
    eng.observe("/unknown", 0.01, 500)
    assert "/unknown" not in eng.snapshot()["endpoints"]


def test_slo_latency_sli_and_no_traffic_is_no_alert():
    clk = FakeClock()
    eng = SloEngine(
        [SloObjective("/transform", latency_threshold_ms=50.0)],
        now_fn=clk,
    )
    snap = eng.snapshot()["endpoints"]["/transform"]
    assert snap["windows"]["5m"]["total"] == 0
    assert snap["burn_rates"]["availability"]["5m"] == 0.0
    assert snap["alerts"] == {"fast_burn": False, "slow_burn": False}
    for _ in range(20):
        eng.observe("/transform", 0.2, 200)  # 200ms > 50ms threshold
        clk.t += 1.0
    ep = eng.snapshot()["endpoints"]["/transform"]
    assert ep["windows"]["5m"]["bad_latency"] == 20
    assert ep["burn_rates"]["latency"]["5m"] > 14.4
    assert ep["alerts"]["fast_burn"] is True


def test_slo_fast_burn_transitions_edge_triggered():
    clk = FakeClock()
    eng = SloEngine([SloObjective("/synonyms")], now_fn=clk)
    for _ in range(50):
        eng.observe("/synonyms", 0.01, 500)
    clk.t += 10.0
    assert eng.fast_burn_transitions(min_interval=5.0) == ["/synonyms"]
    clk.t += 10.0
    # Still burning, but already reported: no new edge.
    assert eng.fast_burn_transitions(min_interval=5.0) == []
    # Throttle: evaluations inside min_interval return nothing.
    assert eng.fast_burn_transitions(min_interval=5.0) == []


def test_merge_slo_snapshots_sums_counts_and_rederives():
    clk = FakeClock()
    a = SloEngine([SloObjective("/synonyms")], now_fn=clk)
    b = SloEngine([SloObjective("/synonyms")], now_fn=clk)
    for _ in range(30):
        a.observe("/synonyms", 0.01, 200)
        b.observe("/synonyms", 0.01, 500)
    merged = merge_slo_snapshots(
        [a.snapshot(), None, {}, b.snapshot()]
    )
    ep = merged["endpoints"]["/synonyms"]
    assert ep["windows"]["5m"]["total"] == 60
    assert ep["windows"]["5m"]["bad_availability"] == 30
    # Burns re-derived from the SUMMED counts, not averaged.
    assert ep["burn_rates"]["availability"]["5m"] == pytest.approx(
        (30 / 60) / 0.001, rel=1e-3
    )
    assert ep["alerts"]["fast_burn"] is True
    assert merge_slo_snapshots([None, {}]) is None


# ----------------------------------------------------------------------
# Shed-burst detector + flight recorder
# ----------------------------------------------------------------------


def test_shed_burst_detector_edge_and_rearm():
    clk = FakeClock()
    det = ShedBurstDetector(threshold=3, window_seconds=10.0, now_fn=clk)
    assert det.note() is False
    assert det.note() is False
    assert det.note() is True     # threshold crossed: one trigger
    assert det.note() is False    # still in the same burst
    clk.t += 11.0                 # window drains
    assert det.note() is False    # re-armed, below threshold again
    assert det.note() is False
    assert det.note() is True     # next burst fires again


def test_flight_recorder_bundle_contents_and_rate_limit(tmp_path):
    clk = FakeClock()
    fl = FlightRecorder(str(tmp_path), window_seconds=5.0,
                        min_interval_seconds=60.0, now_fn=clk)
    seen = {}
    fl.add_source("spans", lambda w: (
        seen.setdefault("w", w),
        {"events": [{"name": "req.accept"}]},
    )[1])
    fl.add_source("broken", lambda w: (_ for _ in ()).throw(
        RuntimeError("scrape failed")))
    bundle = fl.trigger("breaker_open", replica=1)
    assert bundle and os.path.isdir(bundle)
    assert os.path.basename(bundle) == "flightrec-001-breaker_open"
    # Sources receive the span window.
    assert seen["w"] == 5.0
    meta = json.load(open(os.path.join(bundle, "meta.json")))
    assert meta["reason"] == "breaker_open"
    assert meta["context"] == {"replica": 1}
    assert meta["sources"]["spans"] == "ok"
    assert meta["sources"]["broken"].startswith("error:")
    spans = json.load(open(os.path.join(bundle, "spans.json")))
    assert spans["events"][0]["name"] == "req.accept"
    assert not os.path.exists(os.path.join(bundle, "broken.json"))
    # Rate limit: a second trigger inside the interval is suppressed.
    assert fl.trigger("shed_burst") is None
    clk.t += 61.0
    assert fl.trigger("shed_burst") is not None
    stats = fl.stats()
    assert stats["triggered_total"] == 2
    assert stats["suppressed_total"] == 1
    # A hostile reason cannot escape the bundle directory.
    clk.t += 61.0
    odd = fl.trigger("../weird reason!")
    assert odd and os.path.dirname(odd) == str(tmp_path)


# ----------------------------------------------------------------------
# Prometheus renderers: escaping, non-finite, empty, exemplars, SLO
# ----------------------------------------------------------------------


def test_esc_escapes_prometheus_label_specials():
    assert _esc('a"b') == 'a\\"b'
    assert _esc("a\\b") == "a\\\\b"
    assert _esc("a\nb") == "a\\nb"
    assert _esc(123) == "123"


def test_num_renders_non_finite_as_prometheus_specials():
    assert _num(float("nan")) == "NaN"
    assert _num(float("inf")) == "+Inf"
    assert _num(float("-inf")) == "-Inf"
    assert _num(True) == "1"
    assert _num(None) == "NaN"  # missing value renders as absent-data
    assert float(_num(1.5)) == 1.5


@pytest.mark.parametrize("render", [
    training_to_prometheus, serving_to_prometheus,
    gang_to_prometheus, fleet_to_prometheus,
])
def test_renderers_accept_empty_snapshots(render):
    text = render({})
    lint_prometheus_text(text)
    assert text.endswith("\n")


def test_serving_renderer_escapes_hostile_path_labels():
    m = ServingMetrics()
    hostile = '/syn"onyms\\x\nboom'
    m.observe(hostile, 0.01, status=200)
    text = serving_to_prometheus(m.snapshot())
    lint_prometheus_text(text)
    assert '/syn\\"onyms\\\\x\\nboom' in text
    assert "\nboom" not in text  # raw newline would tear the line


def test_serving_renderer_non_finite_values_lint():
    m = ServingMetrics()
    m.observe("/synonyms", 0.01, status=200)
    snap = m.snapshot()
    snap["endpoints"]["/synonyms"]["p99_ms"] = float("inf")
    snap["endpoints"]["/synonyms"]["p95_ms"] = float("nan")
    text = serving_to_prometheus(snap)
    lint_prometheus_text(text)
    assert "+Inf" in text and "NaN" in text


def test_latency_exemplar_rendered_with_trace_id():
    m = ServingMetrics()
    m.observe("/synonyms", 0.033, status=200, trace_id="feedc0de")
    snap = m.snapshot()
    assert snap["endpoints"]["/synonyms"]["exemplar"]["trace_id"] == (
        "feedc0de"
    )
    text = serving_to_prometheus(snap)
    lint_prometheus_text(text)
    assert 'trace_id="feedc0de"' in text


def test_slo_gauges_in_all_three_renderers():
    clk = FakeClock()
    eng = SloEngine([SloObjective("/synonyms")], now_fn=clk)
    for _ in range(50):
        eng.observe("/synonyms", 0.01, 500)
    slo = eng.snapshot()
    serving_text = serving_to_prometheus({"slo": slo})
    gang_text = gang_to_prometheus({"slo": slo})
    training_text = training_to_prometheus({"slo": slo})
    for text in (serving_text, gang_text, training_text):
        lint_prometheus_text(text)
    assert 'glint_slo_burn_rate{endpoint="/synonyms"' in serving_text
    assert "glint_slo_fast_burn" in serving_text
    assert "glint_gang_slo_burn_rate" in gang_text
    assert "glint_training_slo_burn_rate" in training_text
    # The alert gauge carries the fired state, not just presence.
    assert (
        'glint_slo_fast_burn{endpoint="/synonyms"} 1' in serving_text
    )


# ----------------------------------------------------------------------
# Trace-merge collector: cross-process stitching
# ----------------------------------------------------------------------


def _write_lane(path, wall_t0, events, trace=None):
    anchor = {"name": "clock_anchor", "ph": "M", "ts": 0, "pid": 1234,
              "args": {"wall_t0": wall_t0, "mono_t0": 55.5}}
    if trace:
        anchor["args"]["trace"] = trace
    with open(path, "w") as f:
        f.write(json.dumps(anchor) + "\n")
        for ev in events:
            f.write(json.dumps(ev) + "\n")


def test_merge_trace_logs_rebases_and_stitches(tmp_path):
    t0 = 1700000000.0
    bal = str(tmp_path / "balancer.jsonl")
    rep = str(tmp_path / "replica-0.jsonl")
    _write_lane(bal, t0, [
        {"name": "req.accept", "ph": "X", "ts": 100.0, "dur": 5000.0,
         "pid": 10, "tid": 1, "args": {"trace": "t1"}},
    ])
    # The replica's clock started 1s later: its ts must land INSIDE the
    # balancer's accept span after rebasing.
    _write_lane(rep, t0 + 1.0, [
        {"name": "req.query", "ph": "X", "ts": 50.0, "dur": 200.0,
         "pid": 20, "tid": 2, "args": {"trace": "t1"}},
        {"name": "req.query", "ph": "X", "ts": 300.0, "dur": 200.0,
         "pid": 20, "tid": 2, "args": {"trace": "only-here"}},
    ])
    doc = merge_trace_logs([bal, rep])
    assert doc["displayTimeUnit"] == "ms"
    other = doc["otherData"]
    assert other["wall_t0"] == t0
    assert other["trace_ids"] == 2
    assert other["stitched_traces"] == 1  # t1 spans both lanes
    by_name = {}
    for ev in doc["traceEvents"]:
        by_name.setdefault(ev["name"], []).append(ev)
    # Per-file process_name metadata for the Perfetto lane labels.
    lanes = {m["args"]["name"] for m in by_name["process_name"]}
    assert lanes == {"balancer", "replica-0"}
    q = by_name["req.query"][0]
    assert q["ts"] == pytest.approx(1e6 + 50.0)  # +1s rebased to µs
    # Events come out time-sorted.
    ts = [e["ts"] for e in doc["traceEvents"] if e["ph"] != "M"]
    assert ts == sorted(ts)
    json.loads(json.dumps(doc))  # valid Chrome-trace JSON round trip


def test_merge_trace_logs_skips_unanchored_and_torn_lines(tmp_path):
    good = str(tmp_path / "good.jsonl")
    _write_lane(good, 1.0, [
        {"name": "req.accept", "ph": "X", "ts": 1.0, "dur": 2.0,
         "pid": 1, "tid": 1},
    ])
    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write('{"name": "no_anchor", "ph": "i", "ts": 1.0}\n')
        f.write('{"torn line')
    doc = merge_trace_logs([good, bad])
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] != "M"]
    assert names == ["req.accept"]
    src = doc["otherData"]["sources"]
    assert "no clock_anchor" in src[bad]
    assert src[good].startswith("ok")


def test_trace_summarize_merge_ranks_consumes_anchor_pair(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "trace_summarize_for_tracing",
        os.path.join(ROOT, "scripts", "trace_summarize.py"),
    )
    ts_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ts_mod)
    e0 = str(tmp_path / "events-0.jsonl")
    e1 = str(tmp_path / "events-1.jsonl")
    _write_lane(e0, 10.0, [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 1.0, "tid": 1},
    ], trace="gang1")
    _write_lane(e1, 12.5, [
        {"name": "b", "ph": "X", "ts": 0.0, "dur": 1.0, "tid": 1},
    ], trace="gang1")
    doc = ts_mod.merge_rank_traces([e0, e1])
    other = doc["otherData"]
    assert other["wall_t0"] == 10.0
    # The FULL (monotonic, wall) anchor pair is surfaced per rank, with
    # the gang trace id the supervisor exported.
    assert other["anchors"]["0"] == {
        "wall_t0": 10.0, "mono_t0": 55.5, "trace": "gang1",
    }
    b = next(e for e in doc["traceEvents"] if e["name"] == "b")
    assert b["ts"] == pytest.approx(2.5e6)  # 2.5s skew rebased


# ----------------------------------------------------------------------
# Traced stub fleet: wire propagation, stitching, breaker drill
# ----------------------------------------------------------------------

_TRACED_STUB = r"""
import json, os, sys, threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

sys.path.insert(0, sys.argv[3])
from glint_word2vec_tpu.obs import events as obs_events

port_file, trace_log = sys.argv[1], sys.argv[2]
obs_events.set_recorder(obs_events.EventRecorder(jsonl_path=trace_log))


class H(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def _send(self, code, obj):
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        rec = obs_events.get_recorder()
        if self.path == "/healthz":
            return self._send(200, {"status": "ok",
                                    "post_warmup_compiles": 0})
        if self.path.startswith("/trace"):
            return self._send(200, {
                "events": rec.recent_events(60.0),
                "anchor": {"wall_t0": rec.wall_t0,
                           "mono_t0": rec.mono_t0},
            })
        if self.path == "/metrics":
            return self._send(200, {"endpoints": {},
                                    "compiles": {"post_warmup": 0}})
        self._send(404, {})

    def do_POST(self):
        n = int(self.headers.get("Content-Length", 0))
        self.rfile.read(n)
        tr = obs_events.request_trace(
            self.headers.get(obs_events.TRACE_HEADER)
        )
        with tr.phase("req.accept", path=self.path):
            with tr.phase("req.query", mode="exact"):
                pass
        if self.path == "/synonyms":
            tr.finish(200, force=True)
            obs_events.get_recorder().flush()
            return self._send(200, [["w", 0.5]])
        tr.finish(404, force=True)
        self._send(404, {})


httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
tmp = port_file + ".tmp"
with open(tmp, "w") as f:
    json.dump({"host": "127.0.0.1", "port": httpd.server_address[1]}, f)
os.replace(tmp, port_file)
httpd.serve_forever()
"""


def _wait_port_file(path, proc, timeout=30.0):
    deadline = time.monotonic() + timeout
    while not os.path.exists(path):
        if proc.poll() is not None:
            raise RuntimeError(f"stub died rc={proc.returncode}")
        if time.monotonic() > deadline:
            raise TimeoutError("stub not ready")
        time.sleep(0.02)
    with open(path) as f:
        info = json.load(f)
    return f"http://{info['host']}:{info['port']}"


def test_traced_fleet_stitches_and_breaker_drill(tmp_path, monkeypatch):
    """The ISSUE 18 end-to-end drill, jax-free: two subprocess replicas
    running the REAL tracing machinery behind a real LoadBalancer with
    its own recorder. Asserts (a) the trace id propagates over the wire
    and the merged Chrome trace stitches balancer and replica lanes on
    one id, and (b) a breaker CLOSED->OPEN transition triggers a
    flight-recorder bundle holding balancer state plus per-replica span
    and metrics scrapes."""
    # Deterministic keep on the balancer side (replicas force-keep).
    monkeypatch.setattr(obs_events, "_TRACE_SAMPLE_EVERY", 1)
    stub = tmp_path / "traced_stub.py"
    stub.write_text(_TRACED_STUB)
    bal_log = str(tmp_path / "balancer.jsonl")
    rep_logs = [str(tmp_path / f"replica-{i}.jsonl") for i in range(2)]
    procs, urls = [], []
    rec = EventRecorder(jsonl_path=bal_log)
    obs_events.set_recorder(rec)
    lb = None
    try:
        for i in range(2):
            pf = str(tmp_path / f"r{i}.port")
            procs.append(subprocess.Popen(
                [sys.executable, str(stub), pf, rep_logs[i], ROOT]
            ))
            urls.append(_wait_port_file(pf, procs[-1]))
        lb = LoadBalancer(urls, port=0)
        lb.start_background()
        flight_dir = str(tmp_path / "flight")
        fl = lb.enable_flight_recorder(
            flight_dir, window_seconds=60.0, min_interval_seconds=0.0
        )
        for _ in range(4):
            req = urllib.request.Request(
                f"http://{lb.host}:{lb.port}/synonyms",
                data=json.dumps({"word": "w1", "num": 3}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                assert r.status == 200

        # -- breaker drill: CLOSED -> OPEN fires exactly one bundle ----
        b = lb.breakers[1]
        assert fl.triggered_total == 0
        b.force_open()
        assert fl.triggered_total == 1
        b.force_open()  # already open: no re-trigger spam
        assert fl.triggered_total == 1
        bundles = sorted(os.listdir(flight_dir))
        assert bundles == ["flightrec-001-breaker_open"]
        bundle = os.path.join(flight_dir, bundles[0])
        meta = json.load(open(os.path.join(bundle, "meta.json")))
        assert meta["context"] == {"replica": 1}
        assert set(meta["sources"]) == {
            "balancer", "replica_spans", "replica_metrics",
        }
        assert all(v == "ok" for v in meta["sources"].values())
        spans = json.load(
            open(os.path.join(bundle, "replica_spans.json"))
        )
        # Both replicas answered the scrape with their recent spans and
        # their clock anchor.
        for i in range(2):
            doc = spans[f"replica_{i}"]
            assert "error" not in doc
            assert doc["trace"]["anchor"]["wall_t0"] > 0
            assert any(
                e["name"] == "req.accept" for e in doc["trace"]["events"]
            )
        balancer_doc = json.load(
            open(os.path.join(bundle, "balancer.json"))
        )
        assert len(balancer_doc["breakers"]) == 2
    finally:
        if lb is not None:
            lb.stop()
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        obs_events.set_recorder(None)
        rec.close()

    # -- merged trace: one id stitched across balancer + replica lanes -
    doc = merge_trace_logs([bal_log] + rep_logs)
    other = doc["otherData"]
    assert other["stitched_traces"] >= 1
    assert len(other["sources"]) == 3
    lanes = {
        m["args"]["name"] for m in doc["traceEvents"]
        if m.get("name") == "process_name"
    }
    assert lanes == {"balancer", "replica-0", "replica-1"}
    # Find one stitched request: a balancer req.hop and a replica
    # req.accept sharing a trace id across different pids.
    by_trace = {}
    for ev in doc["traceEvents"]:
        tid = (ev.get("args") or {}).get("trace")
        if tid:
            by_trace.setdefault(tid, []).append(ev)
    stitched = [
        evs for evs in by_trace.values()
        if len({e["pid"] for e in evs}) > 1
    ]
    assert stitched
    names = {e["name"] for e in stitched[0]}
    assert "req.hop" in names and "req.accept" in names
    json.loads(json.dumps(doc))


# ----------------------------------------------------------------------
# The bridge onto the profiler's clock, the leader's lane, the step's
# scopes (ISSUE 25)
# ----------------------------------------------------------------------


def _glint_annotations(trace_dir):
    """{name: (start_ns, t0_us)} of the glint.* events in a capture."""
    import glob

    from jax.profiler import ProfileData

    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    assert paths, "the profiler wrote no xplane"
    out = {}
    for plane in ProfileData.from_file(sorted(paths)[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("glint."):
                    out[ev.name] = (ev.start_ns, dict(ev.stats).get("t0_us"))
    return out


def test_recorded_spans_are_profiler_annotations_with_ring_ts(tmp_path):
    import jax

    rec = obs_events.set_recorder(EventRecorder())
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_events.span("device_steps", steps=4):
            time.sleep(0.002)
        time.sleep(0.02)
        with obs_events.phase_span("req.dispatch", batch=2):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    seen = _glint_annotations(str(tmp_path))
    assert set(seen) == {"glint.device_steps", "glint.req.dispatch"}
    ring = {e["name"]: e["ts"] for e in rec.events()}
    offsets = []
    for name, (start_ns, t0_us) in seen.items():
        # the stat IS the ring event's ts: the whole ring maps through it
        assert float(t0_us) == ring[name[len("glint."):]]
        offsets.append(start_ns / 1e3 - float(t0_us))
    assert abs(offsets[0] - offsets[1]) < 1000.0  # us: one clock offset


def test_no_recorder_means_no_annotation(monkeypatch):
    import jax

    made = []
    monkeypatch.setattr(
        jax.profiler, "TraceAnnotation",
        lambda *a, **k: made.append(a) or obs_events.NULL_SPAN,
    )
    obs_events.set_recorder(None)
    assert obs_events.span("device_steps") is obs_events.NULL_SPAN
    assert obs_events.phase_span("req.dispatch") is obs_events.NULL_SPAN
    with obs_events.span("device_steps"), \
            obs_events.phase_span("req.dispatch"):
        pass
    assert made == []
    obs_events.set_recorder(EventRecorder())
    with obs_events.span("device_steps"):
        pass
    assert [a[0] for a in made] == ["glint.device_steps"]


def test_recorded_span_in_a_process_without_jax():
    code = (
        "import sys\n"
        "from glint_word2vec_tpu.obs import events\n"
        "rec = events.set_recorder(events.EventRecorder())\n"
        "with events.span('device_steps') as s:\n"
        "    s.update(steps=1)\n"
        "with events.phase_span('req.dispatch', batch=1):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'the bridge imported jax'\n"
        "print([e['name'] for e in rec.events()])\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "['device_steps', 'req.dispatch']"


def _untrained_model(vocab_size=64, dim=8, mesh=(1, 1), **engine_kw):
    import numpy as np

    from glint_word2vec_tpu.corpus.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    from glint_word2vec_tpu.utils.params import Word2VecParams

    counts = np.arange(vocab_size, 0, -1).astype(np.int64)
    vocab = Vocabulary.from_sorted(
        [f"w{i}" for i in range(vocab_size)], counts
    )
    engine = EmbeddingEngine(
        make_mesh(*mesh), vocab_size, dim, counts, num_negatives=2, seed=3,
        **engine_kw,
    )
    return Word2VecModel(vocab, engine, Word2VecParams(
        vector_size=dim, num_negatives=2, seed=3,
    ))


def test_coalesced_round_is_one_dispatch_with_no_sleep_before_it(
        monkeypatch):
    # The hand-off (ISSUE 53): six callers enqueue behind a held device
    # lock; on release ONE round of batch 6 is dispatched, with no sleep
    # before it, and ``req.dispatch`` holds ``req.pull`` on one leader's
    # lane. Nobody named that leader, so the round carries no
    # ``handoff_ms``.
    import threading
    import types

    from glint_word2vec_tpu import serving
    from glint_word2vec_tpu.serving import _SynonymCoalescer

    def no_sleep(seconds):
        raise AssertionError(f"the coalescer slept {seconds}s")

    model = _untrained_model()
    rec = obs_events.set_recorder(EventRecorder())
    lock = threading.Lock()
    co = _SynonymCoalescer(model, lock, cache_size=0)
    monkeypatch.setattr(serving, "time", types.SimpleNamespace(
        monotonic=time.monotonic, perf_counter=time.perf_counter,
        sleep=no_sleep))
    results = [None] * 6

    def call(i):
        try:
            results[i] = co.query(word=f"w{i}", num=3)
        except BaseException as e:
            results[i] = e

    try:
        lock.acquire()
        callers = [threading.Thread(target=call, args=(i,))
                   for i in range(6)]
        for t in callers:
            t.start()
        deadline = time.monotonic() + 30
        while len(co._pending) < 6 and time.monotonic() < deadline:
            time.sleep(0.005)
        lock.release()
        for t in callers:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in callers)
    finally:
        model.stop()
    assert all(isinstance(r, list) and len(r) == 3 for r in results), results
    spans = {}
    for e in rec.events():
        if e["ph"] == "X":
            spans.setdefault(e["name"], []).append(e)
    assert "req.grace" not in spans
    (dispatch,), (pull,) = spans["req.dispatch"], spans["req.pull"]
    assert dispatch["args"]["batch"] == 6 and pull["args"] == {"rows": 6}
    assert "handoff_ms" not in dispatch["args"]
    # one leader's lane: the dispatch holds the pull
    assert dispatch["tid"] == pull["tid"]
    assert dispatch["ts"] <= pull["ts"]
    assert pull["ts"] + pull["dur"] <= dispatch["ts"] + dispatch["dur"]
    assert co._pending == [] and co._leader is None


def test_new_request_spans_leave_graftlint_clean():
    from glint_word2vec_tpu.analysis import baseline as bl
    from glint_word2vec_tpu.analysis import core

    findings, _ = core.run_analysis(ROOT)
    entries = bl.load_baseline(os.path.join(ROOT, bl.BASELINE_REL))
    new, stale, _ = bl.compare_to_baseline(findings, entries)
    assert new == [] and stale == []
    # the two new spans are registered and have call sites, not baselined
    assert not [e for e in entries if e["rule"] == "span-registry"]
    assert {"req.pull", "req.enqueue", "req.result",
            "req.decode", "req.head", "req.parse", "req.lookup",
            "req.wake"} <= set(obs_events.REQUEST_SPANS)


# ----------------------------------------------------------------------
# A served request measured from inside (ISSUE 52): the hit path phase by
# phase with the thread's CPU time, the round split into its launch, its
# read-back and its decode
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_server():
    from glint_word2vec_tpu.serving import ModelServer

    model = _untrained_model()
    server = ModelServer(model, port=0)
    server.start_background()
    yield server, model
    server.stop()
    model.stop()


def _synonyms(server, word, trace_id):
    req = urllib.request.Request(
        f"http://{server.host}:{server.port}/synonyms",
        data=json.dumps({"word": word, "num": 3}).encode(),
        headers={"Content-Type": "application/json",
                 obs_events.TRACE_HEADER: trace_id},
    )
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def _miss_then_hit(server, monkeypatch, word):
    """(ring events, phases of the miss, phases of the hit) of one word
    asked twice with every trace kept; a request's phases by name."""
    monkeypatch.setattr(obs_events, "_TRACE_SAMPLE_EVERY", 1)
    rec = obs_events.set_recorder(EventRecorder())
    first = _synonyms(server, word, "miss-" + word)
    assert _synonyms(server, word, "hit-" + word) == first
    # a reply is on the wire before its handler flushes the trace
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and not any(
            e["name"] == "req.accept"
            and e["args"]["trace"] == "hit-" + word for e in rec.events()):
        time.sleep(0.005)
    obs_events.set_recorder(None)
    events = rec.events()

    def phases(trace_id):
        out = {}
        for e in events:
            if e.get("args", {}).get("trace") == trace_id:
                assert e["name"] not in out, e["name"]
                out[e["name"]] = e
        return out

    return events, phases("miss-" + word), phases("hit-" + word)


def _end(e):
    return e["ts"] + e["dur"]


def test_kept_hit_holds_its_phases_inside_head_to_accept(
        traced_server, monkeypatch):
    server, _ = traced_server
    _, _, hit = _miss_then_hit(server, monkeypatch, "w5")
    assert set(hit) == {"req.head", "req.accept", "req.parse",
                        "req.admission", "req.lookup", "req.serialize"}
    assert hit["req.lookup"]["args"]["hit"] is True
    t0, t1 = hit["req.head"]["ts"], _end(hit["req.accept"])
    for e in hit.values():
        assert t0 <= e["ts"] and _end(e) <= t1 + 0.2, e["name"]
    # the head ends where the handler's root span begins
    assert _end(hit["req.head"]) <= hit["req.accept"]["ts"] + 0.2
    order = ["req.head", "req.parse", "req.admission", "req.lookup",
             "req.serialize"]
    for a, b in zip(order, order[1:]):
        assert _end(hit[a]) <= hit[b]["ts"] + 0.2, (a, b)
    accept = hit["req.accept"]["args"]
    assert accept["cache"] == "hit" and accept["status"] == 200
    assert 0 <= accept["cpu_ms"] <= hit["req.accept"]["dur"] / 1e3


def test_kept_miss_holds_wake_and_a_readback_over_the_result(
        traced_server, monkeypatch):
    server, _ = traced_server
    events, miss, _ = _miss_then_hit(server, monkeypatch, "w6")
    assert {"req.head", "req.parse", "req.lookup", "req.queue",
            "req.query", "req.readback", "req.wake",
            "req.serialize"} <= set(miss)
    assert miss["req.lookup"]["args"]["hit"] is False
    assert miss["req.accept"]["args"]["cache"] == "miss"
    assert miss["req.wake"]["dur"] >= 0
    # the answer was finished before its handler thread was woken
    assert _end(miss["req.readback"]) <= miss["req.wake"]["ts"] + 0.2
    rounds = [e for e in events if e["name"] == "req.dispatch"
              and "miss-w6" in e["args"]["traces"]]
    assert len(rounds) == 1
    result = [e for e in events if e["name"] == "req.result"
              and rounds[0]["ts"] <= e["ts"] <= _end(rounds[0])]
    assert len(result) == 1
    # req.readback means what its registry line says: the round's last
    # launch returned to the results set, so it covers the read-back
    rb = miss["req.readback"]
    assert rb["ts"] <= result[0]["ts"] + 0.2
    assert _end(result[0]) <= _end(rb) + 0.2
    assert _end(miss["req.query"]) <= rb["ts"] + 0.2
    assert _end(rb) <= _end(rounds[0]) + 0.2


def _round_children(events, dispatch):
    """The launch, read-back and decode spans inside one req.dispatch,
    oldest first."""
    return sorted(
        (e for e in events
         if e["name"] in ("req.enqueue", "req.result", "req.decode")
         and e["tid"] == dispatch["tid"]
         and dispatch["ts"] <= e["ts"] and _end(e) <= _end(dispatch) + 0.2),
        key=lambda e: e["ts"])


def test_a_round_is_enqueue_result_decode_disjoint_and_in_order(
        traced_server, monkeypatch):
    server, model = traced_server
    events, _, _ = _miss_then_hit(server, monkeypatch, "w7")
    (dispatch,) = [e for e in events if e["name"] == "req.dispatch"]
    kids = _round_children(events, dispatch)
    assert [e["name"] for e in kids] == [
        "req.enqueue", "req.result", "req.decode"]
    for a, b in zip(kids, kids[1:]):
        assert _end(a) <= b["ts"] + 0.2
    assert kids[0]["args"] == {"program": "topk_batch", "q": 1,
                               "shards": model.engine.num_model}
    assert kids[1]["args"] == {"program": "topk_batch"}
    assert kids[2]["args"] == {"batch": 1}


def test_no_recorder_no_request_trace_and_no_cpu_clock(
        traced_server, monkeypatch):
    server, _ = traced_server

    def never(*a, **k):
        raise AssertionError("called without a recorder")

    obs_events.set_recorder(None)
    monkeypatch.setattr(time, "thread_time", never)
    monkeypatch.setattr(obs_events.RequestTrace, "__init__", never)
    monkeypatch.setattr(obs_events._Span, "__init__", never)
    first = _synonyms(server, "w8", "a")   # a miss: a whole round
    assert len(first) == 3 and _synonyms(server, "w8", "b") == first


def test_plain_find_synonyms_batch_records_nothing_without_a_recorder():
    model = _untrained_model()
    try:
        obs_events.set_recorder(None)
        before = model.engine.query_enqueued_at
        hits = model.find_synonyms_batch(None, 3, ids=[1, 2])
        assert [len(h) for h in hits] == [3, 3]
        assert model.engine.query_enqueued_at > before
        # with one, outside any server, the launch and the read-back are
        # two spans and nothing else is
        rec = obs_events.set_recorder(EventRecorder())
        model.find_synonyms_batch(None, 3, ids=[1, 2])
        assert [e["name"] for e in rec.events()
                if e["ph"] == "X"] == ["req.enqueue", "req.result"]
    finally:
        model.stop()


_PACKED_CASES = pytest.mark.parametrize(
    "shared_negatives,mesh", [(0, (1, 1)), (16, (1, 1)), (0, (1, 4))],
    ids=["per_pair", "shared_pool", "per_pair-1x4"],
)


def _lowered_packed_scan(shared_negatives, mesh):
    """(text, a shard's table type) of the packed scan lowered on the CPU,
    with the ops' locations in the text."""
    import jax
    import jax.numpy as jnp

    model = _untrained_model(shared_negatives=shared_negatives, mesh=mesh)
    eng = model.engine
    try:
        sds = jax.ShapeDtypeStruct
        table = sds(eng.syn0.shape, eng.syn0.dtype)
        offs = sds((9,), jnp.int32)
        i32, u32, f32 = (sds((), t) for t in (jnp.int32, jnp.uint32,
                                              jnp.float32))
        text = eng._make_packed_corpus_scan(32, 2, 16, 24, 2).lower(
            table, table,
            sds(eng._alias_packed.shape, eng._alias_packed.dtype),
            sds((100,), jnp.int32), sds((100,), jnp.int32),
            offs, offs, i32, i32, sds((2,), jnp.uint32), u32, u32, f32, f32,
            f32,
        ).as_text(debug_info=True)
    finally:
        model.stop()
    return text, f"tensor<{eng.rows_per_shard}x{eng.padded_dim}xf32>"


@_PACKED_CASES
def test_packed_scan_ops_carry_the_phase_scopes(shared_negatives, mesh):
    import re

    text, _ = _lowered_packed_scan(shared_negatives, mesh)
    scopes = set(re.findall(r"glint\.\w+(?:/syn[01])?", text))
    # the gathers name their table since ISSUE 31, as the scatters do; what
    # else lies under glint.gather (reshapes, the all-gather of h) does not
    assert scopes == {
        "glint.batch", "glint.sample", "glint.gather", "glint.exchange",
        "glint.gather/syn0", "glint.gather/syn1",
        "glint.grads", "glint.scatter/syn0", "glint.scatter/syn1",
    }


@_PACKED_CASES
def test_packed_scan_writes_each_distinct_row_once(shared_negatives, mesh):
    """What the step's scatters promise the compiler, and where the work
    that earns the promise is filed: each table is written by ONE scatter,
    told its rows are distinct (``unique_indices``; not that they are
    sorted, though they are: that flag picks XLA's other TPU emitter, a
    pass over the whole table, PERF.md PR 26), and the sorts and the run
    totals that make them distinct lie under the table's own scope, so
    ``step.scatter_ms`` counts them."""
    import re

    text, table = _lowered_packed_scan(shared_negatives, mesh)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    ops = re.findall(
        r'"stablehlo\.(scatter|sort)"\(.*?\) <\{(.*?)\}> \(\{.*?\n\s*\}\) : '
        r'\(.*?\) -> \(?(tensor<[^>]*>).*? loc\((#loc\d+)\)', text, re.S)
    under = {"syn0": [], "syn1": []}
    for op, attrs, result, loc in ops:
        # an op inside a ``while`` is named with the loop's path in front
        # (``jit(f)/while/body/glint...``); inside a ``scan`` it was not
        scope = re.search(r"(?:^|/)glint\.scatter/(syn[01])/", names[loc])
        if op == "scatter" and result == table:
            assert scope, names[loc]
            assert "unique_indices = true" in attrs
            assert "indices_are_sorted = false" in attrs
        if scope:
            sorted_ = "indices_are_sorted = true" in attrs
            under[scope.group(1)].append(
                "write" if result == table else
                "totals" if op == "scatter" and sorted_ else op)
    for table_ops in under.values():
        assert sorted(table_ops) == ["sort", "sort", "totals", "write"]
