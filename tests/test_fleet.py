"""Replica fleet behind one load balancer (ISSUE 12): round-robin
spread, overload-aware retry on the replicas' own 429/503
backpressure, merged fleet exposition, fan-out shutdown."""

import json
import threading
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from glint_word2vec_tpu.corpus.vocab import Vocabulary
from glint_word2vec_tpu.fleet import LoadBalancer
from glint_word2vec_tpu.models.word2vec import Word2VecModel
from glint_word2vec_tpu.obs.prometheus import lint_prometheus_text
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
from glint_word2vec_tpu.parallel.mesh import make_mesh
from glint_word2vec_tpu.serving import ModelServer
from glint_word2vec_tpu.utils.params import Word2VecParams

V, D = 256, 16


def _make_server(**kw):
    rng = np.random.default_rng(0)
    pts = rng.standard_normal((V, D)).astype(np.float32)
    vocab = Vocabulary.from_sorted(
        [f"w{i}" for i in range(V)],
        np.arange(V, 0, -1, dtype=np.int64) + 4,
    )
    eng = EmbeddingEngine(make_mesh(1, 1), V, D, vocab.counts, seed=1)
    eng.set_tables(pts, np.zeros_like(pts))
    model = Word2VecModel(vocab, eng, Word2VecParams(vector_size=D))
    server = ModelServer(model, port=0, warmup=False, **kw)
    server.start_background()
    return server, model


class _Always429Handler(BaseHTTPRequestHandler):
    """A replica stand-in that sheds EVERYTHING — deterministic
    backpressure for the retry tests."""

    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):
        pass

    def _shed(self):
        body = json.dumps({"error": "stub overloaded"}).encode()
        self.send_response(429)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.send_header("Retry-After", "7")
        self.end_headers()
        self.wfile.write(body)

    do_GET = do_POST = _shed


@pytest.fixture()
def shed_stub():
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _Always429Handler)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield f"http://{httpd.server_address[0]}:{httpd.server_address[1]}"
    httpd.shutdown()
    httpd.server_close()


def _post(host, port, path, payload):
    req = urllib.request.Request(
        f"http://{host}:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, dict(r.headers), json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read())


def _get(host, port, path):
    with urllib.request.urlopen(
        f"http://{host}:{port}{path}", timeout=30
    ) as r:
        return r.status, r.read()


def test_round_robin_and_merged_exposition():
    s1, m1 = _make_server()
    s2, m2 = _make_server()
    lb = LoadBalancer(
        [f"http://{s.host}:{s.port}" for s in (s1, s2)], port=0
    )
    lb.start_background()
    try:
        for i in range(12):
            code, _, out = _post(
                lb.host, lb.port, "/synonyms", {"word": f"w{i}", "num": 3}
            )
            assert code == 200 and len(out) == 3
        # Round robin spread the load over both replicas: each served,
        # twelve answers in all, no request ran out of replicas. (Six
        # each on an idle host; a replica slow to answer under load hands
        # its request to the other, which is the balancer working.)
        code, body = _get(lb.host, lb.port, "/metrics")
        doc = json.loads(body)
        proxied = [r["proxied_total"] for r in doc["replicas"]]
        assert all(n > 0 for n in proxied) and sum(proxied) == 12, proxied
        assert doc["balancer"]["proxied_total"] == 12
        assert doc["balancer"]["exhausted_total"] == 0
        assert all(r["up"] for r in doc["replicas"])
        # The merged fleet doc sums per-replica counters and reports
        # per-replica blocks alongside.
        assert doc["fleet"]["replicas"] == 2
        served = [r["snapshot"]["endpoints"]["/synonyms"]["count"]
                  for r in doc["replicas"]]
        assert doc["fleet"]["endpoints"]["/synonyms"]["count"] == sum(served)
        assert sum(served) >= 12, served
        # Scrape-ready text: fleet family + merged serving family in
        # one lint-clean exposition.
        code, text = _get(lb.host, lb.port, "/metrics?format=prometheus")
        text = text.decode()
        lint_prometheus_text(text)
        assert "glint_fleet_replicas 2" in text
        assert "glint_serving_requests_total" in text
        # Fleet health view.
        code, body = _get(lb.host, lb.port, "/healthz")
        h = json.loads(body)
        assert (code, h["replicas_up"]) == (200, 2)
        # Errors proxy through untouched (404 is an answer, not a
        # replica failure — no retry).
        code, _, _ = _post(lb.host, lb.port, "/synonyms",
                           {"word": "missing", "num": 3})
        assert code == 404
    finally:
        lb.stop()
        for s, m in ((s1, m1), (s2, m2)):
            s.stop()
            m.stop()


def test_shed_retries_onto_healthy_replica(shed_stub):
    s1, m1 = _make_server()
    lb = LoadBalancer([shed_stub, f"http://{s1.host}:{s1.port}"], port=0)
    lb.start_background()
    try:
        for i in range(8):
            code, _, _ = _post(
                lb.host, lb.port, "/synonyms", {"word": f"w{i}", "num": 2}
            )
            assert code == 200  # the healthy replica absorbed every shed
        code, body = _get(lb.host, lb.port, "/metrics")
        doc = json.loads(body)
        assert doc["balancer"]["shed_retries_total"] >= 4
        assert doc["balancer"]["exhausted_total"] == 0
    finally:
        lb.stop()
        s1.stop()
        m1.stop()


def test_all_shed_relays_backpressure(shed_stub):
    """When EVERY replica sheds, the client sees the fleet's own 429 —
    Retry-After included — not an invented error."""
    lb = LoadBalancer([shed_stub], port=0)
    lb.start_background()
    try:
        code, headers, out = _post(
            lb.host, lb.port, "/synonyms", {"word": "w0", "num": 2}
        )
        assert code == 429
        assert headers.get("Retry-After") == "7"
        code, body = _get(lb.host, lb.port, "/metrics")
        assert json.loads(body)["balancer"]["exhausted_total"] == 1
    finally:
        lb.stop()


def test_dead_replica_degrades_not_fails():
    s1, m1 = _make_server()
    # A replica that was never started: connection refused.
    lb = LoadBalancer(
        [f"http://{s1.host}:{s1.port}", "http://127.0.0.1:9"], port=0
    )
    lb.start_background()
    try:
        for i in range(6):
            code, _, _ = _post(
                lb.host, lb.port, "/synonyms", {"word": f"w{i}", "num": 2}
            )
            assert code == 200
        code, body = _get(lb.host, lb.port, "/healthz")
        h = json.loads(body)
        assert code == 200  # >= 1 replica up keeps the fleet serving
        assert h["status"] == "degraded"
        assert h["replicas_up"] == 1
        code, body = _get(lb.host, lb.port, "/metrics")
        doc = json.loads(body)
        ups = {r["url"]: r["up"] for r in doc["replicas"]}
        assert ups[f"http://{s1.host}:{s1.port}"] is True
        assert ups["http://127.0.0.1:9"] is False
        # The merged doc still renders lint-clean with a dead replica.
        code, text = _get(lb.host, lb.port, "/metrics?format=prometheus")
        lint_prometheus_text(text.decode())
    finally:
        lb.stop()
        s1.stop()
        m1.stop()


def test_shutdown_fans_out():
    s1, m1 = _make_server()
    s2, m2 = _make_server()
    lb = LoadBalancer(
        [f"http://{s.host}:{s.port}" for s in (s1, s2)], port=0
    )
    lb.start_background()
    try:
        code, _, out = _post(lb.host, lb.port, "/shutdown", {})
        assert code == 200
        assert all(r.get("status") == 200 for r in out["replicas"]), out
        # The accept loop must actually EXIT (closing a listening fd
        # does not wake a blocked accept — stop() shuts the listener
        # down and nudges it; a hang here left `serve-fleet` running
        # forever after its fleet was gone).
        lb._thread.join(timeout=10)
        assert not lb._thread.is_alive(), "balancer accept loop hung"
    finally:
        for m in (m1, m2):
            m.stop()
