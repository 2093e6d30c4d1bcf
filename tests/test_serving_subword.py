"""The subword family on the system's normal serving path (ISSUE 43): a
saved ``FastTextModel`` behind ``ModelServer`` answers ``/synonyms`` through
``_SynonymCoalescer``'s coalesced, cached, warmed rounds, a word outside the
dictionary composed from its n-gram rows inside the round, and every answer
equals the plain reference ``ops/nn_reference.py`` (``fasttext nn`` in the
source's form) on seeded random tables. The word-level family runs what it
ran before."""

import json
import threading
import time
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.corpus.vocab import Vocabulary
from glint_word2vec_tpu.models.fasttext import FastTextModel, FastTextParams
from glint_word2vec_tpu.models.word2vec import Word2VecModel
from glint_word2vec_tpu.obs import events as obs_events
from glint_word2vec_tpu.ops import nn_reference
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
from glint_word2vec_tpu.parallel.mesh import make_mesh
from glint_word2vec_tpu.serving import ModelServer, _SynonymCoalescer
from glint_word2vec_tpu.utils.metrics import ServingMetrics
from glint_word2vec_tpu.utils.params import Word2VecParams

V, BUCKET, D, MIN_N, MAX_N, WIDTH = 300, 523, 16, 3, 4, 16
GEOMETRY = dict(bucket=BUCKET, min_n=MIN_N, max_n=MAX_N, max_subwords=WIDTH)


def _words():
    """Seeded word-like strings; "aaaa" is among them and "aaaaa" is not:
    the two have the same n-grams (in other numbers)."""
    rng = np.random.default_rng(43)
    out = ["aaaa"]
    while len(out) < V:
        w = "".join(rng.choice(list("abcdefgh"), size=rng.integers(3, 10)))
        if w not in out and w != "aaaaa":
            out.append(w)
    return out


def _table(seed):
    return np.random.default_rng(seed).normal(
        0.0, 0.1, (V + BUCKET, D)).astype(np.float32)


def _counts():
    return np.maximum(1, V / np.arange(1, V + 1)).astype(np.int64)


@pytest.fixture(scope="module")
def ft():
    """(server, model): a FastTextModel built as ``_from_loaded`` builds
    it, its syn0 seeded, behind a server with ``cli serve``'s defaults."""
    words = _words()
    engine = EmbeddingEngine(
        make_mesh(1, 2), V, D, _counts(), num_negatives=5, seed=0,
        extra_rows=BUCKET)
    engine.write_rows(0, jnp.asarray(_table(1)))
    model = FastTextModel._from_loaded(
        Vocabulary.from_sorted(words, _counts()), engine,
        FastTextParams(vector_size=D, num_shards=2, **GEOMETRY))
    server = ModelServer(model, port=0)
    server.start_background()
    yield server, model
    server.stop()
    model.stop()


class Reference:
    """``nn_reference`` over one state of the tables (the composed
    dictionary computed once)."""

    def __init__(self, table, words):
        self.syn0 = jnp.asarray(table)
        self.words = list(words)
        self.composed = nn_reference.word_vectors(
            self.syn0, self.words, **GEOMETRY)

    def nn(self, query, num):
        vec, row = nn_reference.query_vector(
            self.syn0, self.words, query, **GEOMETRY)
        return [(self.words[i], s) for i, s in
                nn_reference.nn_vector(self.composed, vec, num, row)]

    def nn_of_vector(self, vec, num):
        return [(self.words[i], s) for i, s in nn_reference.nn_vector(
            self.composed, jnp.asarray(vec, jnp.float32), num)]


@pytest.fixture(scope="module")
def ref(ft):
    _, model = ft
    return Reference(_table(1), model.vocab.words)


def _post(server, path, payload):
    req = urllib.request.Request(
        f"http://{server.host}:{server.port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def _metrics(server):
    with urllib.request.urlopen(
        f"http://{server.host}:{server.port}/metrics", timeout=30
    ) as r:
        return json.loads(r.read())


def _same(got, want):
    assert [w for w, _ in got] == [w for w, _ in want]
    np.testing.assert_allclose(
        [s for _, s in got], [s for _, s in want], rtol=0, atol=1e-5)


def _round(co, lock, jobs):
    """Run ``jobs`` ([(kwargs of ``query``), ...]) as ONE drained batch:
    the device lock is held until every caller has enqueued. Returns
    (results, errors), one of the two set for each job."""
    results, errors = [None] * len(jobs), [None] * len(jobs)

    def call(i, kw):
        try:
            results[i] = co.query(**kw)
        except Exception as e:  # the request's own error
            errors[i] = e

    lock.acquire()
    try:
        threads = [threading.Thread(target=call, args=(i, kw))
                   for i, kw in enumerate(jobs)]
        for t in threads:
            t.start()
        t_end = time.monotonic() + 30
        while len(co._pending) < len(jobs) and time.monotonic() < t_end:
            time.sleep(0.002)
        assert len(co._pending) == len(jobs)
    finally:
        lock.release()
    for t in threads:
        t.join(timeout=60)
    return results, errors


OOV = ["aaaaa", "zzzqqq", "abcabcabc"]


@pytest.mark.parametrize("kind", ["dictionary", "oov", "mixed_round"])
def test_served_answer_equals_the_reference(ft, ref, kind):
    server, model = ft
    words = model.vocab.words
    if kind == "dictionary":
        for w, num in ((words[5], 5), (words[77], 10), ("aaaa", 3)):
            _same(_post(server, "/synonyms", {"word": w, "num": num}),
                  ref.nn(w, num))
        return
    if kind == "oov":
        for w, num in zip(OOV, (5, 10, 17)):
            assert w not in model.vocab.word_index
            _same(_post(server, "/synonyms", {"word": w, "num": num}),
                  ref.nn(w, num))
        return
    # One round of dictionary words, out-of-dictionary words and a raw
    # vector, through a coalescer of its own (no cache from the cases
    # above), all in one dispatch.
    metrics = ServingMetrics()
    lock = threading.Lock()
    co = _SynonymCoalescer(model, lock, metrics=metrics)
    vec = np.random.default_rng(7).normal(size=D).astype(np.float32)
    jobs = ([dict(word=words[i], num=4 + i % 3) for i in (1, 9, 40)]
            + [dict(word=w, num=6) for w in OOV]
            + [dict(vector=[float(x) for x in vec], num=5)])
    results, errors = _round(co, lock, jobs)
    assert errors == [None] * len(jobs)
    for kw, got in zip(jobs[:-1], results[:-1]):
        _same(got, ref.nn(kw["word"], kw["num"]))
    _same(results[-1], ref.nn_of_vector(vec, 5))
    snap = metrics.snapshot()
    assert snap["coalesced_batch_sizes"] == {str(len(jobs)): 1}
    assert snap["compose"]["oov_queries_total"] == len(OOV)
    assert snap["compose"]["dispatches_total"] == 1
    # three words in the bucket of 4, WIDTH slots each
    assert snap["compose"]["group_slots_total"] == 4 * WIDTH


@pytest.mark.parametrize("kind", ["dictionary", "mixed"])
def test_a_round_hands_its_dictionary_words_to_the_top_k_as_ids(
        ft, ref, monkeypatch, kind):
    """A dictionary word enters the top-k as its row id of the COMPOSED
    table and is gathered there: the query engine's ``pull`` is not
    called and the round is ONE query program, TWO when it composes
    out-of-dictionary words first (their vectors, and a raw one, enter as
    vectors). ``req.pull`` and ``req.dispatch`` are still recorded, and
    after the warm-up the round compiles nothing."""
    server, model = ft
    words = model.vocab.words
    lock = threading.Lock()
    co = _SynonymCoalescer(model, lock, metrics=ServingMetrics())
    vec = np.random.default_rng(8).normal(size=D).astype(np.float32)
    jobs = [dict(word=words[i], num=3 + i % 4) for i in (2, 11, 40, 77, 5)]
    if kind == "mixed":
        jobs += ([dict(word=w, num=6) for w in OOV]
                 + [dict(vector=[float(x) for x in vec], num=5)])

    def no_pull(*a, **k):
        raise AssertionError("an exact round pulled rows to the host")

    monkeypatch.setattr(model._query_engine(), "pull", no_pull)
    recorder = obs_events.EventRecorder(capacity=4096)
    prev = obs_events.get_recorder()
    obs_events.set_recorder(recorder)
    try:
        results, errors = _round(co, lock, jobs)
    finally:
        obs_events.set_recorder(prev)
    assert errors == [None] * len(jobs)
    for kw, got in zip(jobs, results):
        _same(got, ref.nn(kw["word"], kw["num"]) if "word" in kw
              else ref.nn_of_vector(vec, kw["num"]))
    spans = recorder.events()
    rounds = [e for e in spans if e["name"] == "req.dispatch"]
    assert [e["args"]["batch"] for e in rounds] == [len(jobs)]
    assert rounds[0]["args"]["programs"] == (2 if kind == "mixed" else 1)
    assert [e["args"]["rows"] for e in spans
            if e["name"] == "req.pull"] == [5]
    assert len([e for e in spans if e["name"] == "req.compose"]) == (
        kind == "mixed")
    assert _metrics(server)["compiles"]["post_warmup"] == 0


def test_oov_word_sharing_every_ngram_is_not_the_dictionary_word(ft, ref):
    """"aaaaa" (outside) has exactly the n-grams of "aaaa" (inside): its
    vector is its bucket rows' mean with no word row in it, it is not
    "aaaa"'s vector, and "aaaa" is an answer to it, not banned."""
    server, model = ft
    assert "aaaa" in model.vocab.word_index
    assert "aaaaa" not in model.vocab.word_index
    V_ = model.vocab.size
    from glint_word2vec_tpu.corpus.subword import subword_group

    inside = subword_group("aaaa", 0, V_, BUCKET, MIN_N, MAX_N, WIDTH)
    outside = subword_group("aaaaa", None, V_, BUCKET, MIN_N, MAX_N, WIDTH)
    assert set(outside) == set(inside[1:]) and inside[0] == 0
    table = _table(1)
    got = np.asarray(_post(server, "/vector", {"word": "aaaaa"}), np.float32)
    np.testing.assert_allclose(
        got, table[outside].mean(axis=0), rtol=0, atol=1e-6)
    its_word = np.asarray(_post(server, "/vector", {"word": "aaaa"}))
    np.testing.assert_allclose(
        its_word, table[inside].mean(axis=0), rtol=0, atol=1e-6)
    assert np.abs(got - its_word).max() > 1e-3
    answer = _post(server, "/synonyms", {"word": "aaaaa", "num": 5})
    _same(answer, ref.nn("aaaaa", 5))
    assert answer[0][0] == "aaaa"
    # ... while the dictionary word is banned from its own answer
    assert "aaaa" not in [
        w for w, _ in _post(server, "/synonyms", {"word": "aaaa", "num": 5})]


def test_concurrent_callers_share_dispatches_and_repeats_hit_the_cache(ft):
    _, model = ft
    words = model.vocab.words
    metrics = ServingMetrics()
    lock = threading.Lock()
    co = _SynonymCoalescer(model, lock, metrics=metrics)
    jobs = ([dict(word=words[i], num=10) for i in range(20, 28)]
            + [dict(word=w, num=10) for w in OOV])
    results, errors = _round(co, lock, jobs)
    assert errors == [None] * len(jobs)
    dispatches = sum(metrics.snapshot()["coalesced_batch_sizes"].values())
    assert dispatches < len(jobs)
    # A repeat of either kind is answered from the cache while the
    # device lock is HELD by someone else: it takes no lock.
    out = {}

    def repeat():
        out["dictionary"] = co.query(word=words[20], num=10)
        out["oov"] = co.query(word=OOV[0], num=10)

    with lock:
        t = threading.Thread(target=repeat)
        t.start()
        t.join(timeout=20)
        assert not t.is_alive()
    assert out["dictionary"] == results[0]
    assert out["oov"] == results[8]
    snap = metrics.snapshot()
    assert snap["synonym_cache"] == {"hits": 2, "misses": len(jobs)}
    assert sum(snap["coalesced_batch_sizes"].values()) == dispatches


def test_mixed_window_after_warmup_compiles_and_builds_nothing(ft):
    server, model = ft
    # the composed table was built before the port bound, once
    assert model.query_engine_builds == 1
    words = model.vocab.words
    before = _metrics(server)
    assert before["compiles"]["post_warmup"] == 0
    assert before["compose"]["table_builds_total"] == 1
    for i, num in enumerate((1, 3, 10, 15, 16, 31)):
        _post(server, "/synonyms", {"word": words[100 + i], "num": num})
        _post(server, "/synonyms", {"word": OOV[i % 3] + "x" * i, "num": num})
    _post(server, "/synonyms_vector", {"vector": [0.5] * D, "num": 7})
    _post(server, "/vector", {"word": words[3]})
    _post(server, "/vector", {"word": "notinthedictionary"})
    _post(server, "/analogy", {"positive": [words[1], "outsideword"],
                               "negative": [words[2]], "num": 4})
    _post(server, "/transform", {"sentences": [
        [words[1], words[2], "dropped_oov"], [words[4]] * 40]})
    results = [None] * 12
    jobs = [(i, {"word": (words[150 + i] if i % 2 else f"oov{i}word"),
                 "num": 10}) for i in range(12)]
    threads = [threading.Thread(
        target=lambda i, p: results.__setitem__(
            i, _post(server, "/synonyms", p)), args=j) for j in jobs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert all(r is not None and len(r) == 10 for r in results)
    after = _metrics(server)
    assert after["compiles"]["post_warmup"] == 0
    assert after["compose"]["table_builds_total"] == 1
    assert model.query_engine_builds == 1
    assert after["compose"]["oov_queries_total"] >= 12
    assert (after["compose"]["group_rows_total"]
            <= after["compose"]["group_slots_total"])
    # the catalog's accounting counts the composed engine beside the pair
    assert after["resident_bytes"] == (
        model.engine.resident_bytes() + model._qeng.resident_bytes())


def test_too_short_word_fails_alone(ft, ref):
    _, model = ft
    words = model.vocab.words
    lock = threading.Lock()
    co = _SynonymCoalescer(model, lock, metrics=ServingMetrics())
    jobs = [dict(word=words[60], num=5), dict(word="z", num=5),
            dict(word="qqqrrr", num=5)]
    results, errors = _round(co, lock, jobs)
    assert isinstance(errors[1], KeyError) and results[1] is None
    assert errors[0] is None and errors[2] is None
    _same(results[0], ref.nn(words[60], 5))
    _same(results[2], ref.nn("qqqrrr", 5))
    # alone in its round, and at num 0, the same 404
    for kw in (dict(word="z", num=5), dict(word="z", num=0)):
        with pytest.raises(KeyError):
            co.query(**kw)
    assert co.query(word="qqqrrr", num=0) == []
    server, _ = ft
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server, "/synonyms", {"word": "z", "num": 5})
    assert e.value.code == 404


def test_one_word_gathers_one_bucket_not_a_block(ft):
    server, model = ft
    seen = []
    orig = model._compose_device
    model._compose_device = lambda g, m: seen.append(g.shape) or orig(g, m)
    try:
        model.transform(model.vocab.words[8])
        model.transform("outsideword")
        _post(server, "/vector", {"word": "anotheroutsider"})
        assert seen == [(1, WIDTH)] * 3
        del seen[:]
        model.transform_words(model.vocab.words[:5])
        assert seen == [(8, WIDTH)]
        del seen[:]
        n = model.COMPOSE_BLOCK + 3
        g = np.zeros((n, WIDTH), np.int32)
        model._compose(g, np.ones(g.shape, np.float32))
        assert seen == [(model.COMPOSE_BLOCK, WIDTH), (4, WIDTH)]
    finally:
        model._compose_device = orig


def test_table_mutation_empties_the_cache_and_recomposes(ft):
    """Last in the file: it leaves the module's tables changed."""
    server, model = ft
    words = model.vocab.words
    first = _post(server, "/synonyms", {"word": words[10], "num": 5})
    first_oov = _post(server, "/synonyms", {"word": "mutatedoov", "num": 5})
    hits = _metrics(server)["synonym_cache"]["hits"]
    assert _post(server, "/synonyms", {"word": words[10], "num": 5}) == first
    assert _metrics(server)["synonym_cache"]["hits"] == hits + 1
    builds = model.query_engine_builds
    with server._lock:
        model.engine.write_rows(0, jnp.asarray(_table(2)))
    new = Reference(_table(2), words)
    got = _post(server, "/synonyms", {"word": words[10], "num": 5})
    got_oov = _post(server, "/synonyms", {"word": "mutatedoov", "num": 5})
    _same(got, new.nn(words[10], 5))
    _same(got_oov, new.nn("mutatedoov", 5))
    assert [w for w, _ in got] != [w for w, _ in first]
    assert [w for w, _ in got_oov] != [w for w, _ in first_oov]
    assert model.query_engine_builds == builds + 1
    snap = _metrics(server)
    assert snap["synonym_cache"]["hits"] == hits + 1  # both were misses
    assert snap["compose"]["table_builds_total"] == builds + 1
    assert snap["compiles"]["post_warmup"] == 0


# -- the word-level family runs what it ran -------------------------------


@pytest.fixture(scope="module")
def word_level():
    engine = EmbeddingEngine(
        make_mesh(1, 1), V, D, _counts(), num_negatives=5, seed=0)
    engine.write_rows(0, jnp.asarray(_table(3)[:V]))
    model = Word2VecModel(
        Vocabulary.from_sorted(_words(), _counts()), engine,
        Word2VecParams(vector_size=D))
    server = ModelServer(model, port=0)
    server.start_background()
    yield server, model
    server.stop()
    model.stop()


def test_word_level_warmup_shapes_are_the_parents(word_level):
    """The warm-up of ``cli serve``'s defaults as the parent of PR 43 ran
    it (its shape list, printed by the parent's own code): 7 pulls, the
    5 x 7 sentence grid, 2 single top-k and 5 x 2 batched ones, all on
    the one engine."""
    server, model = word_level
    qs = [1, 2, 4, 8, 16, 32, 64]
    want = ({("pull", q) for q in qs}
            | {("pull_average", s, L) for s in qs[:5] for L in qs}
            | {("topk", k) for k in (16, 32)}
            | {("topk_batch", q, k) for q in (1, 8, 16, 32, 64)
               for k in (16, 32)})
    assert model.engine._query_shapes == want
    assert model.engine.query_compiles == 54
    assert server.metrics.warmup_compiles == 54
    assert not server._coalescer.composes and server._coalescer.can_batch
    assert model._query_engine() is model.engine


def test_word_level_round_is_the_parents(word_level):
    server, model = word_level
    words = model.vocab.words
    recorder = obs_events.EventRecorder(capacity=4096)
    prev = obs_events.get_recorder()
    obs_events.set_recorder(recorder)
    try:
        got = _post(server, "/synonyms", {"word": words[7], "num": 6})
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(server, "/synonyms", {"word": "aaaaa", "num": 6})
        assert e.value.code == 404
        assert "not in vocabulary" in json.loads(e.value.read())["error"]
    finally:
        obs_events.set_recorder(prev)
    want = model.find_synonyms(words[7], 6)
    assert [w for w, _ in got] == [w for w, _ in want]
    names = [ev["name"] for ev in recorder.events()]
    assert "req.dispatch" in names and "req.pull" in names
    assert "req.compose" not in names
    snap = _metrics(server)
    assert snap["compose"] == {
        "oov_queries_total": 0, "dispatches_total": 0,
        "group_slots_total": 0, "group_rows_total": 0,
        "table_builds_total": 0, "table_build_seconds_total": 0.0}
    assert snap["compiles"]["post_warmup"] == 0
    assert snap["resident_bytes"] == model.engine.resident_bytes()


def test_compose_span_is_on_the_ring(ft):
    """Reads the span alone, no answer: the state the mutation test left
    the tables in does not matter."""
    server, model = ft
    recorder = obs_events.EventRecorder(capacity=4096)
    prev = obs_events.get_recorder()
    obs_events.set_recorder(recorder)
    try:
        _post(server, "/synonyms", {"word": "spanoutsider", "num": 4})
    finally:
        obs_events.set_recorder(prev)
    spans = [ev for ev in recorder.events() if ev["name"] == "req.compose"]
    assert len(spans) == 1
    args = spans[0]["args"]
    assert (args["oov"], args["words"], args["slots"]) == (1, 0, WIDTH)
    assert 0 < args["rows"] <= WIDTH
    assert "req.compose" in obs_events.REQUEST_SPANS


def test_a_composing_round_launches_and_reads_back_twice(ft):
    """Inside one ``req.dispatch`` the compose's launch and read-back,
    then the top-k's, then the decode: disjoint, in order, the compose's
    pair inside ``req.compose``."""
    server, model = ft
    recorder = obs_events.EventRecorder(capacity=4096)
    prev = obs_events.get_recorder()
    obs_events.set_recorder(recorder)
    try:
        _post(server, "/synonyms", {"word": "twiceoutsider", "num": 4})
    finally:
        obs_events.set_recorder(prev)
    events = recorder.events()
    (dispatch,) = [e for e in events if e["name"] == "req.dispatch"]
    (compose,) = [e for e in events if e["name"] == "req.compose"]
    kids = sorted(
        (e for e in events
         if e["name"] in ("req.enqueue", "req.result", "req.decode")),
        key=lambda e: e["ts"])
    assert [(e["name"], e["args"].get("program")) for e in kids] == [
        ("req.enqueue", "pull_average"), ("req.result", "pull_average"),
        ("req.enqueue", "topk_batch"), ("req.result", "topk_batch"),
        ("req.decode", None)]
    end = lambda e: e["ts"] + e["dur"]  # noqa: E731
    assert dispatch["ts"] <= kids[0]["ts"]
    assert end(kids[-1]) <= end(dispatch) + 0.2
    for a, b in zip(kids, kids[1:]):
        assert end(a) <= b["ts"] + 0.2
    assert compose["ts"] <= kids[0]["ts"]
    assert end(kids[1]) <= end(compose) + 0.2 <= kids[2]["ts"] + 0.4
    assert kids[0]["args"]["q"] == 1
    assert kids[0]["args"]["shards"] == model.engine.num_model
    assert dispatch["args"]["programs"] == 2
