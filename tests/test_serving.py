"""Model-serving tests: the separate-PS-cluster deployment analogue
(README.md:45-57 of the reference; serving.py module docstring)."""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from glint_word2vec_tpu import Word2Vec
from glint_word2vec_tpu.parallel.mesh import make_mesh
from glint_word2vec_tpu.serving import ModelServer


@pytest.fixture(scope="module", params=[(1, 2), (1, 1)],
                ids=["1x2", "1x1"])
def served(request, tiny_corpus):
    # A model axis of two and the one device both serving cells run on,
    # behind the same HTTP surface: every serving test (coalescing, error
    # paths, num semantics) runs against each.
    model = Word2Vec(
        mesh=make_mesh(*request.param), vector_size=16, min_count=5,
        batch_size=128, seed=2, num_iterations=2,
    ).fit(tiny_corpus)
    server = ModelServer(model, port=0)  # ephemeral port
    server.start_background()
    yield server, model
    server.stop()
    model.stop()


def _post(server, path, payload):
    req = urllib.request.Request(
        f"http://{server.host}:{server.port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def test_healthz_and_queries(served):
    server, model = served
    with urllib.request.urlopen(
        f"http://{server.host}:{server.port}/healthz", timeout=30
    ) as r:
        health = json.loads(r.read())
    assert health["status"] == "ok"
    assert health["vocab_size"] == model.vocab.size

    syn = _post(server, "/synonyms", {"word": "austria", "num": 5})
    assert len(syn) == 5
    # Served results identical to in-process queries (same tables).
    direct = model.find_synonyms("austria", 5)
    assert [w for w, _ in direct] == [w for w, _ in syn]

    vec = _post(server, "/vector", {"word": "vienna"})
    np.testing.assert_allclose(vec, model.transform("vienna"), rtol=1e-6)

    ana = _post(
        server, "/analogy",
        {"positive": ["vienna", "germany"], "negative": ["austria"], "num": 3},
    )
    assert len(ana) == 3

    emb = _post(server, "/transform", {"sentences": [["austria", "zzz"]]})
    assert len(emb) == 1 and len(emb[0]) == 16


def test_error_paths(served):
    server, _ = served
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server, "/vector", {"word": "notaword_xyz"})
    assert e.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server, "/nosuchroute", {})
    assert e.value.code == 404


def test_concurrent_synonyms_coalesced_match_sequential(served):
    # The coalescer (serving._SynonymCoalescer) answers concurrent
    # synonym queries with one batched dispatch; results must be
    # identical to sequential single queries, mixed num values and OOV
    # errors included.
    import threading

    server, model = served
    words = [model.vocab.words[i] for i in range(6)]
    jobs = (
        [("/synonyms", {"word": w, "num": 3 + (i % 3)})
         for i, w in enumerate(words)]
        + [("/synonyms", {"word": "notaword_xyz", "num": 5})]
        + [("/synonyms_vector",
            {"vector": [float(x) for x in model.transform(words[0])],
             "num": 4})]
    )
    results = [None] * len(jobs)
    errors = [None] * len(jobs)

    def hit(i, path, payload):
        try:
            results[i] = _post(server, path, payload)
        except urllib.error.HTTPError as e:
            errors[i] = e.code

    threads = [
        threading.Thread(target=hit, args=(i, p, pl))
        for i, (p, pl) in enumerate(jobs)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)

    for i, w in enumerate(words):
        expect = model.find_synonyms(w, 3 + (i % 3))
        assert results[i] is not None
        assert [x[0] for x in results[i]] == [x[0] for x in expect]
        np.testing.assert_allclose(
            [x[1] for x in results[i]], [x[1] for x in expect], rtol=1e-5
        )
    assert errors[len(words)] == 404  # OOV inside a coalesced batch
    vec_expect = model.find_synonyms_vector(model.transform(words[0]), 4)
    assert [x[0] for x in results[-1]] == [x[0] for x in vec_expect]


def test_malformed_vector_fails_only_its_own_request(served):
    # A garbage /synonyms_vector payload inside a coalesced batch must
    # 400 by itself without stranding co-batched waiters.
    import threading

    server, model = served
    ok_res, bad_code = [], []

    def good():
        ok_res.append(
            _post(server, "/synonyms", {"word": model.vocab.words[0],
                                        "num": 3})
        )

    def bad():
        try:
            _post(server, "/synonyms_vector",
                  {"vector": ["a", "b"], "num": 3})
        except urllib.error.HTTPError as e:
            bad_code.append(e.code)

    ts = [threading.Thread(target=good), threading.Thread(target=bad)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert bad_code == [400]
    assert len(ok_res) == 1 and len(ok_res[0]) == 3


def test_q_bucketing_exact_with_at_most_one_compile_per_bucket(served):
    # Batched top-k pads Q to power-of-two buckets (engine.next_pow2) and
    # rounds k up to its bucket; results must equal the single-query path
    # for every batch size (padded rows can never win a real row's
    # top-k), and the compile counter must grow at most once per NEW
    # bucket across varied Q — zero times inside the warmed family.
    server, model = served
    engine = model.engine
    rng = np.random.default_rng(3)

    before = engine.query_compiles
    for q in range(1, 10):
        vecs = rng.standard_normal((q, model.vector_size)).astype(np.float32)
        batch = model.find_synonyms_batch(vecs, 3)
        assert len(batch) == q
        for row, v in zip(batch, vecs):
            single = model.find_synonyms_vector(v, 3)
            assert [w for w, _ in row] == [w for w, _ in single]
            np.testing.assert_allclose(
                [s for _, s in row], [s for _, s in single], rtol=1e-5
            )
    # Q 1..9 and k=3 all land inside the warmed family (Q buckets
    # 1..max_batch, k bucket TOPK_MIN_K_BUCKET): zero fresh compiles.
    assert engine.query_compiles == before

    # Past the warmed range, every Q in (64, 128] shares ONE bucket.
    before = engine.query_compiles
    for q in (65, 100, 128):
        model.find_synonyms_batch(
            rng.standard_normal((q, model.vector_size)).astype(np.float32), 3
        )
    assert engine.query_compiles == before + 1


def test_chunked_coalesced_pull_matches_unchunked(served, monkeypatch):
    # A coalesced batch larger than MAX_QUERY_ROWS must pull in chunks
    # (the coalescer used to bypass the cap entirely) and match the
    # unchunked gather bit-for-bit.
    from glint_word2vec_tpu.models import word2vec as w2v_mod
    from glint_word2vec_tpu.serving import _pull_coalesced

    server, model = served
    idx = np.arange(23, dtype=np.int32) % model.vocab.size
    unchunked = np.asarray(model.engine.pull(idx), np.float32)
    monkeypatch.setattr(w2v_mod, "MAX_QUERY_ROWS", 8)
    chunked = _pull_coalesced(model.engine, idx)
    np.testing.assert_array_equal(chunked, unchunked)


def test_coalescer_chunks_at_max_batch(served):
    # A drained pending list larger than max_batch is served in
    # max_batch-sized device dispatches, each recorded in the
    # coalesced-batch-size distribution, with per-request results still
    # exactly the single-query answers.
    import threading

    from glint_word2vec_tpu.serving import _SynonymCoalescer
    from glint_word2vec_tpu.utils.metrics import ServingMetrics

    _, model = served
    metrics = ServingMetrics()
    co = _SynonymCoalescer(
        model, threading.Lock(), max_batch=2, metrics=metrics
    )
    words = [model.vocab.words[i] for i in range(5)]
    batch = [
        {"word": w, "vector": None, "num": 3, "event": threading.Event(),
         "result": None, "error": None}
        for w in words
    ]
    co._process(batch)
    for r, w in zip(batch, words):
        assert r["event"].is_set() and r["error"] is None
        expect = model.find_synonyms(w, 3)
        assert [x[0] for x in r["result"]] == [x[0] for x in expect]
    sizes = metrics.snapshot()["coalesced_batch_sizes"]
    assert sizes == {"1": 1, "2": 2}


@pytest.mark.parametrize("kind", ["words", "vectors", "mixed"])
def test_an_exact_round_is_one_query_program(served, monkeypatch, kind):
    # What a request carries decides how it enters the top-k: a dictionary
    # word goes in as its row id and the program gathers the row itself,
    # so an exact round launches ONE query program and never calls
    # ``engine.pull``; a raw vector goes in as the vector. After the
    # warm-up no such round compiles anything.
    import threading

    from glint_word2vec_tpu.obs import events as obs_events

    server, model = served
    co = server._coalescer
    words = [model.vocab.words[i] for i in (3, 1, 4, 15, 9)]
    vecs = [np.asarray(model.transform(w), np.float32) for w in words]
    by_vector = {"words": [False] * 5, "vectors": [True] * 5,
                 "mixed": [False, True, False, False, True]}[kind]
    batch = [
        {"word": None if v else w, "vector": vec.tolist() if v else None,
         "num": 4, "event": threading.Event(), "result": None,
         "error": None}
        for w, vec, v in zip(words, vecs, by_vector)
    ]

    def no_pull(*a, **k):
        raise AssertionError("an exact round pulled rows to the host")

    monkeypatch.setattr(model.engine, "pull", no_pull)
    compiled_before = model.engine.query_compiles
    recorder = obs_events.EventRecorder(capacity=4096)
    prev = obs_events.get_recorder()
    obs_events.set_recorder(recorder)
    try:
        with co.device_lock:
            co._process(batch)
    finally:
        obs_events.set_recorder(prev)
    monkeypatch.undo()
    for r, w, v in zip(batch, words, by_vector):
        assert r["event"].is_set() and r["error"] is None
        want = (model.find_synonyms_vector(model.transform(w), 4) if v
                else model.find_synonyms(w, 4))
        assert [x[0] for x in r["result"]] == [x[0] for x in want]
        np.testing.assert_allclose(
            [x[1] for x in r["result"]], [x[1] for x in want], atol=2e-6)
    spans = recorder.events()
    rounds = [e for e in spans if e["name"] == "req.dispatch"]
    assert len(rounds) == 1
    assert rounds[0]["args"]["programs"] == 1
    assert rounds[0]["args"]["batch"] == 5
    pulls = [e for e in spans if e["name"] == "req.pull"]
    assert len(pulls) == (0 if kind == "vectors" else 1)
    if pulls:
        assert pulls[0]["args"]["rows"] == by_vector.count(False)
    # warmed shapes only (an earlier test of this file may have asked
    # for a bucket past the warm-up's: count from here)
    assert model.engine.query_compiles == compiled_before


def test_smoke_every_endpoint_zero_post_warmup_compiles(served):
    # The CI serving smoke (ISSUE 2): a freshly warmed ModelServer
    # answers every endpoint once plus a concurrent coalesced burst
    # without a single post-warmup jit compile, and /metrics shows the
    # latency histograms and batch-size distribution filling in.
    import threading

    _, model = served
    smoke = ModelServer(model, port=0)
    smoke.start_background()
    try:
        w0, w1 = model.vocab.words[0], model.vocab.words[1]
        _post(smoke, "/synonyms", {"word": w0, "num": 5})
        _post(smoke, "/synonyms_vector",
              {"vector": [float(x) for x in model.transform(w0)], "num": 4})
        _post(smoke, "/analogy",
              {"positive": [w0], "negative": [w1], "num": 3})
        _post(smoke, "/vector", {"word": w0})
        _post(smoke, "/transform", {"sentences": [[w0, w1, w0]]})
        # Multi-sentence transforms exercise the (rows, len) grid: both
        # dims bucket to powers of two inside the warmed family (a
        # 3-sentence request once compiled post-warmup because only
        # rows=1 was warmed).
        _post(smoke, "/transform", {"sentences": [[w0], [w1], [w0, w1]]})

        burst_words = [model.vocab.words[i % model.vocab.size]
                       for i in range(12)]

        # Prometheus exposition mid-smoke: scraping must lint clean and
        # must not disturb the zero-post-warmup-compile contract the
        # assertions below enforce (ISSUE 3 acceptance).
        from glint_word2vec_tpu.obs.prometheus import lint_prometheus_text

        with urllib.request.urlopen(
            f"http://{smoke.host}:{smoke.port}/metrics?format=prometheus",
            timeout=30,
        ) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            lint_prometheus_text(r.read().decode())
        errs = []

        def hit(w):
            try:
                _post(smoke, "/synonyms", {"word": w, "num": 6})
            except Exception as e:  # pragma: no cover - burst must succeed
                errs.append(e)

        threads = [threading.Thread(target=hit, args=(w,))
                   for w in burst_words]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errs

        with urllib.request.urlopen(
            f"http://{smoke.host}:{smoke.port}/healthz", timeout=30
        ) as r:
            health = json.loads(r.read())
        assert health["post_warmup_compiles"] == 0
        # A handler accounts for its request AFTER it has sent the reply
        # (``_observe_request`` in its ``finally``), so the burst's last
        # request may not be counted yet when its client returns.
        for _ in range(50):
            with urllib.request.urlopen(
                f"http://{smoke.host}:{smoke.port}/metrics", timeout=30
            ) as r:
                metrics = json.loads(r.read())
            syn = metrics["endpoints"]["/synonyms"]
            if syn["count"] >= 13:
                break
            time.sleep(0.1)
        assert metrics["compiles"]["post_warmup"] == 0
        assert metrics["compiles"]["warmup"] >= 0
        assert syn["count"] >= 13 and syn["errors"] == 0
        assert syn["p95_ms"] >= syn["p50_ms"] >= 0
        assert metrics["coalesced_batch_sizes"]  # burst coalesced
        for path in ("/synonyms_vector", "/analogy", "/vector",
                     "/transform"):
            assert metrics["endpoints"][path]["count"] >= 1
    finally:
        smoke.stop()


def test_metrics_prometheus_format(served):
    # /metrics?format=prometheus renders the SAME snapshot as the JSON
    # default (which stays the default), passes the text-format lint,
    # and scraping compiles nothing.
    from glint_word2vec_tpu.obs.prometheus import lint_prometheus_text

    server, model = served
    _post(server, "/synonyms", {"word": model.vocab.words[0], "num": 3})
    before = model.engine.query_compiles
    with urllib.request.urlopen(
        f"http://{server.host}:{server.port}/metrics?format=prometheus",
        timeout=30,
    ) as r:
        assert r.headers["Content-Type"].startswith(
            "text/plain; version=0.0.4"
        )
        text = r.read().decode()
    lint_prometheus_text(text)
    assert 'glint_serving_requests_total{path="/synonyms"}' in text
    assert "glint_serving_compiles_total" in text
    assert model.engine.query_compiles == before

    # JSON stays the default format, unchanged shape.
    with urllib.request.urlopen(
        f"http://{server.host}:{server.port}/metrics", timeout=30
    ) as r:
        assert r.headers["Content-Type"].startswith("application/json")
        snap = json.loads(r.read())
    assert "endpoints" in snap and "compiles" in snap
    # The format variant query string must not mint its own metric key.
    assert all("format=" not in k for k in snap["endpoints"])


def test_post_query_string_routes_and_keys_on_bare_path(served):
    # POST routing and metric keying use the parsed path, so a query
    # string neither 404s a real endpoint nor mints a fresh histogram.
    server, model = served
    out = _post(server, "/synonyms?trace=1",
                {"word": model.vocab.words[0], "num": 3})
    assert len(out) == 3
    with urllib.request.urlopen(
        f"http://{server.host}:{server.port}/metrics", timeout=30
    ) as r:
        snap = json.loads(r.read())
    assert "/synonyms?trace=1" not in snap["endpoints"]
    assert snap["endpoints"]["/synonyms"]["count"] >= 1


def test_synonym_cache_hit_invalidation_and_bound(served):
    # The (word, num) result cache: a repeat query is served without a
    # device dispatch, any table mutation (engine.table_version tick)
    # empties it wholesale, and the entry count never exceeds
    # cache_size (FIFO eviction).
    import threading

    from glint_word2vec_tpu.serving import _SynonymCoalescer
    from glint_word2vec_tpu.utils.metrics import ServingMetrics

    _, model = served
    metrics = ServingMetrics()
    co = _SynonymCoalescer(
        model, threading.Lock(), metrics=metrics, cache_size=2
    )
    w = model.vocab.words[0]
    dispatches = []
    orig = model.top_k_batch
    model.top_k_batch = (
        lambda *a, **k: dispatches.append(1) or orig(*a, **k)
    )
    try:
        first = co.query(word=w, num=4)
        again = co.query(word=w, num=4)
        assert again == first and len(dispatches) == 1
        snap = metrics.snapshot()["synonym_cache"]
        assert snap == {"hits": 1, "misses": 1}

        # A real table mutation (same values, so results are unchanged)
        # ticks table_version and must empty the cache.
        ver = model.engine.table_version
        row0 = np.asarray(model.engine.pull(np.zeros(1, np.int32)))
        model.engine.write_rows(0, row0[:, : model.engine.dim])
        assert model.engine.table_version > ver
        third = co.query(word=w, num=4)
        assert len(dispatches) == 2
        assert [x[0] for x in third] == [x[0] for x in first]

        # FIFO bound: filling past cache_size=2 evicts the oldest.
        for i in range(4):
            co.query(word=model.vocab.words[i], num=3)
        assert len(co._cache) <= 2
    finally:
        model.top_k_batch = orig


def test_cache_disabled_always_dispatches(served):
    import threading

    from glint_word2vec_tpu.serving import _SynonymCoalescer

    _, model = served
    co = _SynonymCoalescer(model, threading.Lock(), cache_size=0)
    w = model.vocab.words[1]
    dispatches = []
    orig = model.top_k_batch
    model.top_k_batch = (
        lambda *a, **k: dispatches.append(1) or orig(*a, **k)
    )
    try:
        co.query(word=w, num=4)
        co.query(word=w, num=4)
        assert len(dispatches) == 2 and not co._cache
    finally:
        model.top_k_batch = orig


def test_num_zero_and_negative_match_single_query_semantics(served):
    server, model = served
    w = model.vocab.words[0]
    # num=0 with a known word: 200 [] (find_synonyms truncation).
    assert _post(server, "/synonyms", {"word": w, "num": 0}) == []
    # num=0 with an OOV word: transform runs first -> 404.
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server, "/synonyms", {"word": "notaword_xyz", "num": 0})
    assert e.value.code == 404
    # Negative num: 400 either way.
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server, "/synonyms", {"word": w, "num": -1})
    assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server, "/synonyms_vector",
              {"vector": [0.0] * model.vector_size, "num": 0})
    assert e.value.code == 400


# ----------------------------------------------------------------------
# The hand-off between rounds (ISSUE 53): what is pending rides the next
# round, a waiter waits on its answer and not on the device lock
# ----------------------------------------------------------------------


class _DeviceLock:
    """A device lock that counts the coalescer's acquisitions. The test
    is the "other endpoint": ``hold`` / ``free`` take and give the lock
    uncounted, and ``keep_at_release`` has it take the device the moment
    the round in flight lets go."""

    def __init__(self):
        import threading

        self._lock = threading.Lock()
        self.acquisitions = 0
        self.keep_at_release = False
        self.hold, self.free = self._lock.acquire, self._lock.release

    def acquire(self, timeout=None):
        ok = (self._lock.acquire() if timeout is None
              else self._lock.acquire(timeout=timeout))
        self.acquisitions += ok
        return ok

    def release(self):
        if self.keep_at_release:
            self.keep_at_release = False
        else:
            self._lock.release()


class _Rounds:
    """A coalescer of its own over the served model whose first rounds
    the test holds inside their dispatch, callers on threads of their
    own, and every ``req.dispatch`` recorded."""

    def __init__(self, model, gated=0, failing=()):
        import threading

        from glint_word2vec_tpu.obs import events as obs_events
        from glint_word2vec_tpu.serving import _SynonymCoalescer
        from glint_word2vec_tpu.utils.metrics import ServingMetrics

        self.model = model
        self.lock = _DeviceLock()
        self.metrics = ServingMetrics()
        self.co = _SynonymCoalescer(
            model, self.lock, metrics=self.metrics, cache_size=0)
        self.entered = [threading.Event() for _ in range(gated)]
        self.opened = [threading.Event() for _ in range(gated)]
        self.threads, self.answers = {}, {}
        dispatch, rounds = self.co._dispatch, iter(range(10**6))

        def gated_dispatch(chunk, mode="exact", handoff_ms=None):
            i = next(rounds)
            if i < gated:
                self.entered[i].set()
                assert self.opened[i].wait(60)
            if i in failing:
                raise RuntimeError("the device fell over")
            return dispatch(chunk, mode, handoff_ms)

        self.co._dispatch = gated_dispatch
        self._events = obs_events
        self._prev = obs_events.get_recorder()
        self.recorder = obs_events.set_recorder(
            obs_events.EventRecorder(capacity=4096))

    def close(self):
        for gate in self.opened:
            gate.set()
        self._events.set_recorder(self._prev)

    def ask(self, name, timeout=None, enqueued=True, **kw):
        """Start a caller; returns once its request is pending (not
        awaited of one that leads at once: it drains itself)."""
        import threading

        def call():
            try:
                self.answers[name] = self.co.query(deadline=(
                    None if timeout is None
                    else time.monotonic() + timeout), **kw)
            except Exception as e:  # the request's own error
                self.answers[name] = e

        before = len(self.co._pending)
        self.threads[name] = threading.Thread(target=call)
        self.threads[name].start()
        if enqueued:
            self.until(lambda: len(self.co._pending) > before
                       or name in self.answers)

    def ask_word(self, name, i, **kw):
        self.ask(name, word=self.model.vocab.words[i], num=3 + i % 3, **kw)

    @staticmethod
    def until(cond):
        t_end = time.monotonic() + 60
        while not cond():
            assert time.monotonic() < t_end
            time.sleep(0.002)

    def join(self, *names):
        for name in names:
            self.threads[name].join(timeout=60)
            assert not self.threads[name].is_alive(), name

    def right(self, name, i):
        want = self.model.find_synonyms(self.model.vocab.words[i], 3 + i % 3)
        got = self.answers[name]
        assert isinstance(got, list), got
        assert [w for w, _ in got] == [w for w, _ in want]

    def dispatches(self):
        return [e for e in self.recorder.events()
                if e["name"] == "req.dispatch"]

    def idle(self):
        return self.co._pending == [] and self.co._leader is None


def _two_rounds(h):
    """``a`` leads a round; ``b`` then ``c`` arrive while it is in
    flight and ride the next one, held in its dispatch until ``a``'s
    thread has returned."""
    h.ask_word("a", 0, enqueued=False)
    assert h.entered[0].wait(60)
    h.ask_word("b", 1)
    h.ask_word("c", 2)
    h.opened[0].set()
    assert h.entered[1].wait(60)
    h.join("a")  # back at its caller while the second round is in flight
    h.opened[1].set()
    h.join("b", "c")
    for name, i in (("a", 0), ("b", 1), ("c", 2)):
        h.right(name, i)
    return h.dispatches()


def _case_rides_next_round(h):
    first, second = _two_rounds(h)
    assert first["args"]["batch"] == 1 and second["args"]["batch"] == 2
    assert "handoff_ms" not in first["args"]
    assert 0 <= second["args"]["handoff_ms"] < 60e3
    # the second round is led by one of those who missed the first
    assert first["tid"] != second["tid"]
    assert second["ts"] >= first["ts"] + first["dur"]
    assert h.idle()


def _case_one_lock_acquisition_a_round(h):
    # an answered waiter (c) never touches the device lock, and neither
    # does a leader with its answer in hand
    assert len(_two_rounds(h)) == 2
    assert h.lock.acquisitions == 2


def _case_waiter_deadline(h):
    from glint_word2vec_tpu.serving import DeadlineExceeded

    h.lock.hold()
    h.ask_word("a", 0)  # leads, and waits for the device
    h.ask_word("b", 1, timeout=0.2)
    h.join("b")
    assert isinstance(h.answers["b"], DeadlineExceeded)
    assert [r["word"] for r in h.co._pending] == [h.model.vocab.words[0]]
    h.lock.free()
    h.join("a")
    h.right("a", 0)
    (only,) = h.dispatches()  # no dispatch slot for the 504
    assert only["args"]["batch"] == 1 and h.idle()


def _case_leader_deadline_passes_lead_on(h):
    from glint_word2vec_tpu.serving import DeadlineExceeded

    h.lock.hold()
    h.ask_word("a", 0, timeout=0.3)  # leads; the device stays busy
    h.ask_word("b", 1)
    h.ask_word("c", 2)
    h.join("a")
    assert isinstance(h.answers["a"], DeadlineExceeded)
    h.until(lambda: h.co._leader is not None and h.co._leader["lead"])
    assert len(h.co._pending) == 2
    h.lock.free()
    h.join("b", "c")
    h.right("b", 1)
    h.right("c", 2)
    (only,) = h.dispatches()
    # named by a leader that never ran a round: no hand-off to measure
    assert only["args"]["batch"] == 2 and "handoff_ms" not in only["args"]
    assert h.lock.acquisitions == 1 and h.idle()


def _case_named_leader_deadline_passes_lead_on(h):
    from glint_word2vec_tpu.serving import DeadlineExceeded

    h.ask_word("a", 0, enqueued=False)
    assert h.entered[0].wait(60)
    h.ask_word("b", 1, timeout=1.5)
    h.ask_word("c", 2)
    # another endpoint takes the device the moment the round lets go:
    # b is named, cannot reach the device by its deadline, and passes
    # the lead on to c
    h.lock.keep_at_release = True
    h.opened[0].set()
    h.join("a", "b")
    h.right("a", 0)
    assert isinstance(h.answers["b"], DeadlineExceeded)
    h.until(lambda: h.co._leader is not None
            and h.co._leader["word"] == h.model.vocab.words[2])
    h.lock.free()
    h.join("c")
    h.right("c", 2)
    first, second = h.dispatches()
    assert second["args"]["batch"] == 1
    assert second["args"]["handoff_ms"] > 0  # since a's round ended
    assert h.idle()


def _case_validation_error_fails_alone(h):
    h.lock.hold()
    h.ask_word("good", 0)
    h.ask("bad", vector=["a", "b"], num=3)
    h.ask("oov", word="notaword_xyz", num=5)
    h.ask_word("fine", 4)
    h.lock.free()
    h.join("good", "bad", "oov", "fine")
    h.right("good", 0)
    h.right("fine", 4)
    assert isinstance(h.answers["bad"], ValueError)
    assert isinstance(h.answers["oov"], KeyError)
    (only,) = h.dispatches()
    assert only["args"]["batch"] == 2
    assert h.lock.acquisitions == 1 and h.idle()


def _case_dispatch_error_strands_nobody(h):
    # the first round's dispatch raises: its requests take the error,
    # and those that arrived meanwhile are still named a leader and
    # answered
    h.ask_word("a", 0, enqueued=False)
    assert h.entered[0].wait(60)
    h.ask_word("b", 1)
    h.ask_word("c", 2)
    h.opened[0].set()
    h.join("a", "b", "c")
    assert isinstance(h.answers["a"], RuntimeError)
    h.right("b", 1)
    h.right("c", 2)
    (only,) = h.dispatches()  # the failed one never opened its span
    assert only["args"]["batch"] == 2 and "handoff_ms" in only["args"]
    assert h.lock.acquisitions == 2 and h.idle()


def _case_pending_grows_behind_another_endpoint(h):
    h.lock.hold()  # /vector, /transform, a training step
    for i in range(6):
        h.ask_word(f"r{i}", i)
    assert len(h.co._pending) == 6
    h.lock.free()
    h.join(*(f"r{i}" for i in range(6)))
    for i in range(6):
        h.right(f"r{i}", i)
    assert h.metrics.snapshot()["coalesced_batch_sizes"] == {"6": 1}
    assert h.lock.acquisitions == 1 and h.idle()


@pytest.mark.parametrize("case, gated, failing", [
    (_case_rides_next_round, 2, ()),
    (_case_one_lock_acquisition_a_round, 2, ()),
    (_case_waiter_deadline, 0, ()),
    (_case_leader_deadline_passes_lead_on, 0, ()),
    (_case_named_leader_deadline_passes_lead_on, 1, ()),
    (_case_validation_error_fails_alone, 0, ()),
    (_case_dispatch_error_strands_nobody, 1, (0,)),
    (_case_pending_grows_behind_another_endpoint, 0, ()),
], ids=lambda v: v.__name__[6:] if callable(v) else "")
def test_hand_off_between_rounds(served, case, gated, failing):
    _, model = served
    h = _Rounds(model, gated=gated, failing=failing)
    try:
        case(h)
    finally:
        h.close()
