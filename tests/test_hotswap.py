"""Hot-swap under load (ISSUE 10): a threaded client fleet hammers
``/synonyms`` while published generations flip beneath it.

The tables of each generation are CRAFTED one-hot directions so every
response is attributable to exactly one generation — including a "mix"
sentinel row that would surface as top-1 if a stale query vector from
generation N were ever ranked against generation N+1's tables (the
pull and the top-k happen inside one device-lock hold, so it must
never appear). Asserted across the run: zero dropped/5xx responses,
zero post-warmup compiles, result-cache invalidation on swap, no
cross-generation mixing, and a word that did not exist at serve start
resolving after its generation swaps in.
"""

import json
import os
import threading
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu import Word2Vec, load_model
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
from glint_word2vec_tpu.parallel.mesh import make_mesh
from glint_word2vec_tpu.serving import ModelServer
from glint_word2vec_tpu.streaming.publish import (
    LATEST_NAME,
    SnapshotPublisher,
    read_latest,
)
from glint_word2vec_tpu.utils import atomic_write_json

WORDS = ["q", "a1", "a2", "mix", "f1", "f2", "f3", "f4"]
DIM = 16


def _e(i, scale=1.0):
    v = np.zeros(DIM, np.float32)
    v[i] = scale
    return v


def _tables(rows: dict, num_rows: int) -> np.ndarray:
    t = np.zeros((num_rows, DIM), np.float32)
    for idx, vec in rows.items():
        t[idx] = vec
    return t


class _Vocab:
    def __init__(self, words):
        self.words = list(words)


@pytest.fixture(scope="module")
def publish_dir(tmp_path_factory):
    """Three crafted generations in one publish dir.

    gen1: q=e1, a1=e1          -> top-1 of q is a1
    gen2: q=e2, a2=e2, mix=e1  -> top-1 is a2; a STALE gen1 q-vector
                                  ranked here would surface mix
    gen3: q=e8, fresh=e8 (a promoted word on an extra row), mix=e1+e2
          -> top-1 is fresh; any stale q-vector surfaces mix
    """
    pub = str(tmp_path_factory.mktemp("pub"))
    counts = np.arange(len(WORDS), 0, -1, dtype=np.int64) * 10
    eng = EmbeddingEngine(
        make_mesh(1, 1), len(WORDS), DIM, counts, num_negatives=2,
        seed=5, extra_rows=4,
    )
    params = Word2Vec(vector_size=DIM).params
    publisher = SnapshotPublisher(pub, eng, params, keep=3)
    N = eng.num_rows
    base = {4: _e(4), 5: _e(5), 6: _e(6), 7: _e(7)}  # fillers, stable
    zeros = np.zeros((N, DIM), np.float32)

    eng.set_tables(
        _tables({**base, 0: _e(1), 1: _e(1), 2: _e(2), 3: _e(3)}, N),
        zeros,
    )
    publisher.publish(_Vocab(WORDS))
    eng.wait_pending_saves()

    eng.set_tables(
        _tables({**base, 0: _e(2), 1: _e(0), 2: _e(2), 3: _e(1)}, N),
        zeros,
    )
    publisher.publish(_Vocab(WORDS))
    eng.wait_pending_saves()

    fresh_row = eng.assign_extra_row("fresh")
    assert fresh_row == len(WORDS)
    mix3 = (_e(1) + _e(2)) / np.sqrt(2)
    eng.set_tables(
        _tables(
            {**base, 0: _e(8), 1: _e(9), 2: _e(10), 3: mix3,
             fresh_row: _e(8)},
            N,
        ),
        zeros,
    )
    publisher.publish(_Vocab(WORDS + ["fresh"]))
    eng.wait_pending_saves()

    # Rewind the pointer to gen1: the test flips it forward by hand.
    atomic_write_json(
        os.path.join(pub, LATEST_NAME),
        {"generation": "gen-000001", "seq": 1},
    )
    eng.destroy()
    return pub


#: Generation -> the only legal top-1 for /synonyms of "q" there.
EXPECT = {
    "gen-000001": "a1",
    "gen-000002": "a2",
    "gen-000003": "fresh",
}


def _flip(pub, gen):
    atomic_write_json(
        os.path.join(pub, LATEST_NAME),
        {"generation": gen, "seq": int(gen.split("-")[1])},
    )


def _post(server, path, payload, timeout=30):
    req = urllib.request.Request(
        f"http://{server.host}:{server.port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _metrics(server):
    with urllib.request.urlopen(
        f"http://{server.host}:{server.port}/metrics", timeout=30
    ) as r:
        return json.loads(r.read())


def test_hotswap_under_load(publish_dir):
    pub = publish_dir
    model = load_model(os.path.join(pub, "gen-000001"))
    server = ModelServer(model, port=0, cache_size=1024)
    server.watch(pub, poll_seconds=0.05, current="gen-000001")
    server.start_background()
    try:
        results = []  # (status, top1) for q queries — any thread
        errors = []
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    code, out = _post(
                        server, "/synonyms", {"word": "q", "num": 3}
                    )
                except Exception as e:  # dropped connection = dropped request
                    errors.append(repr(e))
                    continue
                top1 = out[0][0] if code == 200 and out else None
                results.append((code, top1))

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()

        def wait_responses(n):
            import time as _t
            deadline = _t.monotonic() + 60
            while len(results) < n:
                assert _t.monotonic() < deadline, "load stalled"
                _t.sleep(0.01)

        def wait_generation(gen):
            import time as _t
            deadline = _t.monotonic() + 60
            while server.metrics.generation != gen:
                assert _t.monotonic() < deadline, f"no swap to {gen}"
                _t.sleep(0.01)

        # Phase 1: gen1 serving; the fresh word must not exist yet.
        wait_responses(25)
        code, _ = _post(server, "/synonyms", {"word": "fresh", "num": 3})
        assert code == 404
        # Identical repeated query: the second hit rides the cache.
        _post(server, "/synonyms", {"word": "q", "num": 3})
        hits_before = _metrics(server)["synonym_cache"]["hits"]
        _post(server, "/synonyms", {"word": "q", "num": 3})
        assert _metrics(server)["synonym_cache"]["hits"] > hits_before

        # Phase 2 + 3: flip generations mid-load.
        _flip(pub, "gen-000002")
        wait_generation("gen-000002")
        wait_responses(len(results) + 25)
        # Cache invalidation on swap: the SAME (word, num) key now
        # answers from the new tables.
        code, out = _post(server, "/synonyms", {"word": "q", "num": 3})
        assert (code, out[0][0]) == (200, "a2")

        _flip(pub, "gen-000003")
        wait_generation("gen-000003")
        wait_responses(len(results) + 25)
        # The word that did not exist at serve start now resolves.
        code, out = _post(server, "/synonyms", {"word": "fresh", "num": 3})
        assert code == 200
        code, out = _post(server, "/synonyms", {"word": "q", "num": 3})
        assert (code, out[0][0]) == (200, "fresh")

        stop.set()
        for t in threads:
            t.join(timeout=30)

        # Zero dropped requests, zero 5xx across the whole run.
        assert errors == []
        assert all(code == 200 for code, _ in results), set(
            c for c, _ in results
        )
        # Every response belongs to exactly one generation's expected
        # answer — never the cross-generation "mix" sentinel, never a
        # blend (a stale pull ranked against new tables would have
        # surfaced mix as top-1 by construction).
        seen = {t for _, t in results}
        assert seen <= set(EXPECT.values()), seen
        assert "mix" not in seen
        # The load actually spanned a swap (both sides observed).
        assert len(seen) >= 2, seen

        snap = _metrics(server)
        assert snap["hot_swap"]["table_swaps_total"] == 2
        assert snap["hot_swap"]["swap_failures_total"] == 0
        assert snap["hot_swap"]["generation"] == "gen-000003"
        # The zero-compile contract holds ACROSS swaps: same-shape
        # tables reuse every warmed program.
        assert snap["compiles"]["post_warmup"] == 0
        # /healthz reflects the grown vocabulary.
        with urllib.request.urlopen(
            f"http://{server.host}:{server.port}/healthz", timeout=30
        ) as r:
            health = json.loads(r.read())
        assert health["vocab_size"] == len(WORDS) + 1
    finally:
        server.stop()
        model.stop()


def test_reload_endpoint_explicit_dir(publish_dir):
    pub = publish_dir
    _flip(pub, "gen-000001")
    model = load_model(os.path.join(pub, "gen-000001"))
    # warmup=False: the zero-compile-across-swap contract is asserted by
    # test_hotswap_under_load; this test only exercises /reload semantics.
    server = ModelServer(model, port=0, warmup=False)
    server.start_background()
    try:
        # No watcher, no dir -> 400 with guidance.
        code, out = _post(server, "/reload", {})
        assert code == 400
        code, out = _post(
            server, "/reload", {"dir": os.path.join(pub, "gen-000002")}
        )
        assert (code, out["status"]) == (200, "reloaded")
        assert out["generation"] == "gen-000002"
        code, out = _post(server, "/synonyms", {"word": "q", "num": 3})
        assert (code, out[0][0]) == (200, "a2")
        # A bad dir is a counted failure; the live tables survive.
        code, out = _post(
            server, "/reload", {"dir": os.path.join(pub, "gen-999999")}
        )
        assert code == 400
        snap = _metrics(server)
        assert snap["hot_swap"]["swap_failures_total"] == 1
        code, out = _post(server, "/synonyms", {"word": "q", "num": 3})
        assert (code, out[0][0]) == (200, "a2")
    finally:
        server.stop()
        model.stop()


def test_watcher_never_loads_unreferenced_generation(publish_dir):
    """The SIGKILL-mid-publish contract from the serving side: a
    complete generation directory that LATEST never referenced (the
    crash window between rename and pointer flip) must not be loaded."""
    pub = publish_dir
    _flip(pub, "gen-000001")
    model = load_model(os.path.join(pub, "gen-000001"))
    server = ModelServer(model, port=0, warmup=False)
    watcher = server.watch(pub, poll_seconds=3600, current="gen-000001")
    server.start_background()  # stop() joins the serve loop
    try:
        # gen-000003 exists on disk, complete — but the pointer says 1.
        assert watcher.poll_once() is None
        assert server.metrics.table_swaps == 0
        # A malformed pointer never swaps anything — since ISSUE 14 it
        # is COUNTED as a transient watch error and backed off, not
        # silently treated as "no publish yet".
        with open(os.path.join(pub, LATEST_NAME), "w") as f:
            f.write("{torn")
        assert watcher.poll_once() is None
        assert server.metrics.table_swaps == 0
        assert server.metrics.watch_errors == 1
        watcher._retry_at = 0.0  # collapse the backoff for the test
        _flip(pub, "gen-000002")
        assert watcher.poll_once() == "gen-000002"
        # A failed generation is not retried until the pointer moves:
        # point at a missing dir, then back at a good one. Since
        # ISSUE 14 the first miss is treated as rename-visibility lag
        # (a counted watch error + backoff); the dir still missing on
        # the next look brands the generation failed.
        _flip(pub, "gen-777777")
        assert watcher.poll_once() is None
        assert server.metrics.swap_failures == 0  # strike 1: transient
        assert server.metrics.watch_errors == 2
        watcher._retry_at = 0.0
        assert watcher.poll_once() is None
        assert server.metrics.swap_failures == 1  # strike 2: branded
        assert watcher.poll_once() is None
        assert server.metrics.swap_failures == 1  # no retry
        _flip(pub, "gen-000003")
        assert watcher.poll_once() == "gen-000003"
    finally:
        server.stop()
        model.stop()


def test_reload_rejects_geometry_mismatch(publish_dir, tmp_path):
    """A generation with different table geometry cannot hot-swap (it
    would recompile every warmed program): staging raises, the old
    tables stay live."""
    pub = publish_dir
    eng8 = EmbeddingEngine(
        make_mesh(1, 1), 4, 8, np.full(4, 10, np.int64),
        num_negatives=2, seed=3,
    )
    other_pub = str(tmp_path / "otherpub")
    SnapshotPublisher(
        other_pub, eng8, Word2Vec(vector_size=8).params
    ).publish(_Vocab(["w", "x", "y", "z"]))
    eng8.wait_pending_saves()
    eng8.destroy()
    gen_dir = os.path.join(other_pub, "gen-000001")
    _flip(pub, "gen-000001")
    model = load_model(os.path.join(pub, "gen-000001"))
    server = ModelServer(model, port=0, warmup=False)
    server.start_background()
    try:
        code, out = _post(server, "/reload", {"dir": gen_dir})
        assert code == 400
        assert server.metrics.swap_failures == 1
        code, out = _post(server, "/synonyms", {"word": "q", "num": 2})
        assert code == 200  # old generation still serving
    finally:
        server.stop()
        model.stop()


def test_hotswap_with_ann_index_under_load(publish_dir):
    """ISSUE 12 swap-aware indexing: the hammering-clients drill with
    the approximate path LIVE. The coarse index flips WITH the tables
    under the device lock, so the mix sentinel must never surface from
    an ANN dispatch either; every swap refreshes the index off the
    request path (refreshes_total grows), the recall gate re-passes
    per generation, and the compile-free contract holds across swaps
    on the approximate family too."""
    pub = publish_dir
    _flip(pub, "gen-000001")
    model = load_model(os.path.join(pub, "gen-000001"))
    server = ModelServer(
        model, port=0, cache_size=1024, ann=True, ann_recall_sample=8,
    )
    assert server._ann_live, "tiny crafted tables must clear the gate"
    server.watch(pub, poll_seconds=0.05, current="gen-000001")
    server.start_background()
    try:
        results, errors = [], []
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    code, out = _post(
                        server, "/synonyms", {"word": "q", "num": 3}
                    )
                except Exception as e:
                    errors.append(repr(e))
                    continue
                top1 = out[0][0] if code == 200 and out else None
                results.append((code, top1))

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()

        def wait_responses(n):
            import time as _t
            deadline = _t.monotonic() + 60
            while len(results) < n:
                assert _t.monotonic() < deadline, "load stalled"
                _t.sleep(0.01)

        def wait_generation(gen):
            import time as _t
            deadline = _t.monotonic() + 60
            while server.metrics.generation != gen:
                assert _t.monotonic() < deadline, f"no swap to {gen}"
                _t.sleep(0.01)

        wait_responses(25)
        _flip(pub, "gen-000002")
        wait_generation("gen-000002")
        wait_responses(len(results) + 25)
        _flip(pub, "gen-000003")
        wait_generation("gen-000003")
        wait_responses(len(results) + 25)
        code, out = _post(server, "/synonyms", {"word": "q", "num": 3})
        assert (code, out[0][0]) == (200, "fresh")
        stop.set()
        for t in threads:
            t.join(timeout=30)

        assert errors == []
        assert all(code == 200 for code, _ in results), set(
            c for c, _ in results
        )
        seen = {t for _, t in results}
        assert seen <= set(EXPECT.values()), seen
        assert "mix" not in seen
        assert len(seen) >= 2, seen

        snap = _metrics(server)
        assert snap["hot_swap"]["table_swaps_total"] == 2
        assert snap["hot_swap"]["swap_failures_total"] == 0
        # Boot + one refresh per swap, every generation gate-clean.
        assert snap["index"]["refreshes_total"] == 3
        assert snap["index"]["recall_gate_ok"] is True
        assert snap["index"]["ann_queries_total"] > 0
        assert snap["index"]["table_versions_behind"] == 0
        # Zero compiles across swaps on BOTH dispatch families.
        assert snap["compiles"]["post_warmup"] == 0
    finally:
        server.stop()
        model.stop()


def test_corrupt_generation_keeps_old_index_serving(publish_dir):
    """A generation that fails staging is a counted swap_failure: the
    previous tables AND the previous index keep serving the
    approximate path, and no index refresh is recorded."""
    pub = publish_dir
    _flip(pub, "gen-000001")
    model = load_model(os.path.join(pub, "gen-000001"))
    server = ModelServer(
        model, port=0, ann=True, ann_recall_sample=8,
    )
    server.start_background()
    try:
        refreshes = _metrics(server)["index"]["refreshes_total"]
        code, _ = _post(
            server, "/reload", {"dir": os.path.join(pub, "gen-999999")}
        )
        assert code == 400
        snap = _metrics(server)
        assert snap["hot_swap"]["swap_failures_total"] == 1
        assert snap["index"]["refreshes_total"] == refreshes
        # Old generation + old index still answering approximately.
        before = snap["index"]["ann_queries_total"]
        code, out = _post(server, "/synonyms", {"word": "q", "num": 3})
        assert (code, out[0][0]) == (200, "a1")
        assert (
            _metrics(server)["index"]["ann_queries_total"] == before + 1
        )
    finally:
        server.stop()
        model.stop()


def test_bf16_generation_round_trip(tmp_path):
    """ISSUE 11 dtype round-trip: a bf16-STORAGE trainer publishes a
    generation (fp32 .npy payloads, dtype recorded in engine.json AND
    the integrity manifest); a bf16 serving engine hot-swaps it through
    stage_tables/adopt_tables and the query path — fp32 norms, fp32
    top-k scoring — returns ranks bitwise-stable against the
    fp32-upcast oracle (numpy cosine over the upcast bf16 table)."""
    Vv, d = 24, 16
    words = [f"w{i}" for i in range(Vv)]
    counts = np.arange(Vv, 0, -1, dtype=np.int64) * 5
    rng = np.random.default_rng(0)
    trainer = EmbeddingEngine(
        make_mesh(1, 1), Vv, d, counts, num_negatives=2, seed=1,
        dtype="bfloat16",
    )
    syn0 = rng.normal(0, 1.0, (Vv, d)).astype(np.float32)
    trainer.set_tables(syn0, np.zeros_like(syn0))
    pub = str(tmp_path / "pub")
    SnapshotPublisher(
        pub, trainer, Word2Vec(vector_size=d, dtype="bfloat16").params,
    ).publish(_Vocab(words))
    trainer.wait_pending_saves()
    gen_matrix = os.path.join(pub, "gen-000001", "matrix")
    # The integrity manifest records the storage dtype (the .npy
    # payloads themselves are fp32 — numpy has no bf16).
    manifest = json.load(open(os.path.join(gen_matrix, "manifest.json")))
    assert manifest["table_dtype"] == "bfloat16"
    meta = json.load(open(os.path.join(gen_matrix, "engine.json")))
    assert meta["dtype"] == "bfloat16"
    trainer.destroy()

    server_eng = EmbeddingEngine(
        make_mesh(1, 1), Vv, d, counts, num_negatives=2, seed=9,
        dtype="bfloat16",
    )
    server_eng.adopt_tables(server_eng.stage_tables(gen_matrix))
    assert server_eng.syn0.dtype == jnp.bfloat16
    # Query path stays fp32: norms cache and top-k scores.
    norms = server_eng.norms()
    assert np.asarray(norms).dtype == np.float32
    upcast = np.asarray(server_eng.syn0, np.float32)[:Vv, :d]
    safe = np.linalg.norm(upcast, axis=1)
    for qi in (0, 3, 17):
        q = upcast[qi] / np.linalg.norm(upcast[qi])
        oracle = (upcast @ q) / safe
        oracle_rank = np.argsort(-oracle)[:5]
        sims, idx = server_eng.top_k_cosine(upcast[qi], 5)
        np.testing.assert_array_equal(idx, oracle_rank)
        np.testing.assert_allclose(
            sims, oracle[oracle_rank], rtol=1e-6, atol=1e-7
        )
    server_eng.destroy()
