"""A model that lies on several devices, a share of its rows on each, served
through the normal path and held to the plain reference
(``ops/synonyms_reference.py``: the reference's ``findSynonyms`` over row
blocks, float32 numpy, nothing shared with the serving path).

CPU, forced host devices (``conftest.py``), seeded random tables at tiny
sizes: ``/synonyms``, ``/synonyms_vector`` and ``/analogy`` over meshes
1 x 2, 1 x 4 and 2 x 2; the share test (the per-shard candidates the
program merges, gathered by hand, give the whole table's answer); a model
saved by a ``num_shards`` 4 fit and loaded again; the one-device top-k
program's text against the parent's; ``/metrics``.
"""

import hashlib
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from glint_word2vec_tpu.corpus.vocab import Vocabulary
from glint_word2vec_tpu.models.word2vec import Word2VecModel
from glint_word2vec_tpu.ops import synonyms_reference as ref
from glint_word2vec_tpu.parallel import engine as engine_mod
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
from glint_word2vec_tpu.parallel.mesh import MODEL_AXIS, make_mesh
from glint_word2vec_tpu.serving import ModelServer
from glint_word2vec_tpu.utils.params import Word2VecParams

MESHES = [(1, 2), (1, 4), (2, 2)]
#: float32 on both sides, the sums in another order: a cosine's last bits.
TOL = 2e-6
D = 20


def _table(rows: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).normal(
        0.0, 0.1, (rows, D)).astype(np.float32)


def _model(mesh_shape, table, vocab_size=None, extra_rows=0):
    """A served model's parts, built as ``Word2VecModel.load`` leaves them:
    the first ``vocab_size`` rows of ``table`` are the words', the rest
    lie in the engine's extra rows (unassigned: not queryable)."""
    V = vocab_size or table.shape[0]
    words = [f"w{i:05d}" for i in range(V)]
    counts = np.maximum(1, V // np.arange(1, V + 1)).astype(np.int64)
    engine = EmbeddingEngine(
        make_mesh(*mesh_shape), V, D, counts, num_negatives=2, seed=1,
        extra_rows=extra_rows)
    engine.write_rows(0, jnp.asarray(table))
    model = Word2VecModel(
        Vocabulary.from_sorted(words, counts), engine, Word2VecParams(
            vector_size=D, window=2, num_negatives=2,
            num_shards=mesh_shape[1], seed=1))
    return model, words


def _blocks(table, cuts=(0, 7, 400)):
    """The table in row blocks that match no shard's edges."""
    edges = [c for c in cuts if c < table.shape[0]] + [table.shape[0]]
    return [(a, table[a:b]) for a, b in zip(edges, edges[1:])]


def _post(server, path, payload):
    req = urllib.request.Request(
        f"http://{server.host}:{server.port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def _same_by_score(got, want, words, cos):
    """``got`` ([[word, score], ...] as served) is ``want`` ([(row, cos),
    ...], the reference's) by score: as many answers, each served score
    the reference's cosine of THAT row, and rank by rank the reference's
    score; rows may differ only among equals."""
    assert len(got) == len(want), (got, want)
    index = {w: i for i, w in enumerate(words)}
    for (word, score), (row, ref_score) in zip(got, want):
        assert abs(score - ref_score) <= TOL, (word, score, row, ref_score)
        assert abs(score - cos[index[word]]) <= TOL, (word, score)


@pytest.fixture(scope="module", params=MESHES,
                ids=[f"{a}x{b}" for a, b in MESHES])
def served(request):
    """1,003 words (no shard count divides them) and 200 extra rows that
    are no words (``n_queryable`` falls inside the last shard), with a row
    of norm zero and equal rows on both sides of every shard edge."""
    shards = request.param[1]
    V, extra = 1003, 200
    table = _table(V + extra, 11)
    table[V:] *= 50.0  # rows that would win every query, were they words
    table[5] = 0.0  # a word whose row has norm zero
    per_shard = -(-(V + extra) // shards)
    for s in range(1, shards):  # ties that straddle a shard edge
        if s * per_shard < V:
            table[s * per_shard] = table[s * per_shard - 1]
    model, words = _model(request.param, table, V, extra)
    assert model.engine.rows_per_shard == per_shard
    server = ModelServer(model, port=0, warmup=False)
    server.start_background()
    yield server, words, table, V, per_shard
    server.stop()
    model.stop()


def test_synonyms_by_word_equal_the_reference(served):
    server, words, table, V, per_shard = served
    blocks = _blocks(table)
    asked = [0, 1, 6, per_shard - 1, per_shard, V - 1, 500]
    for row in asked:
        got = _post(server, "/synonyms", {"word": words[row], "num": 10})
        cos = ref.cosines(blocks, ref.pull(blocks, row), V)
        _same_by_score(got, ref.find_synonyms(blocks, row, 10, V), words, cos)
        assert words[row] not in [w for w, _ in got]  # the banned query word
        assert words[5] not in [w for w, _ in got]  # the zero-norm row
    # a tie across a shard edge: the twin rows score alike, the lower first
    if per_shard < V:
        twin = _post(server, "/synonyms",
                     {"word": words[per_shard - 1], "num": 3})
        assert twin[0][0] == words[per_shard]
        assert abs(twin[0][1] - 1.0) <= TOL


def test_a_zero_norm_query_word_is_answered_as_the_reference_does(served):
    server, words, table, V, _ = served
    got = _post(server, "/synonyms", {"word": words[5], "num": 4})
    blocks = _blocks(table)
    want = ref.find_synonyms(blocks, 5, 4, V)
    # every cosine to the zero vector is 0: equals, the lower rows first
    assert [s for _, s in want] == [0.0] * 4
    assert [s for _, s in got] == [0.0] * 4


def test_synonyms_by_vector_and_analogy_equal_the_reference(served):
    server, words, table, V, _ = served
    blocks = _blocks(table)
    vec = _table(1, 12)[0] * 3.0  # no row's, and of no unit norm
    got = _post(server, "/synonyms_vector",
                {"vector": [float(x) for x in vec], "num": 12})
    _same_by_score(got, ref.find_synonyms_vector(blocks, vec, 12, V),
                   words, ref.cosines(blocks, vec, V))
    pos, neg = [3, 700], [40]
    got = _post(server, "/analogy", {
        "positive": [words[i] for i in pos],
        "negative": [words[i] for i in neg], "num": 7})
    query = table[3] + table[700] - table[40]
    _same_by_score(got, ref.analogy(blocks, pos, neg, 7, V), words,
                   ref.cosines(blocks, query, V))
    assert not {words[i] for i in pos + neg} & {w for w, _ in got}


@pytest.mark.parametrize("mesh_shape", MESHES,
                         ids=[f"{a}x{b}" for a, b in MESHES])
def test_more_answers_asked_for_than_a_shard_has_rows(mesh_shape):
    """Ten words over up to four shards, three rows a shard: ``num`` 8 is
    more than any shard holds, so every shard hands over all its rows."""
    table = _table(10, 13)
    model, words = _model(mesh_shape, table)
    server = ModelServer(model, port=0, warmup=False)
    server.start_background()
    try:
        blocks = _blocks(table, cuts=(0, 4))
        got = _post(server, "/synonyms", {"word": words[2], "num": 8})
        _same_by_score(got, ref.find_synonyms(blocks, 2, 8, 10), words,
                       ref.cosines(blocks, table[2], 10))
        everyone = _post(server, "/synonyms", {"word": words[2], "num": 10})
        assert len(everyone) == 9  # all but the query word
    finally:
        server.stop()
        model.stop()


def test_the_shards_candidates_merged_by_hand_give_the_whole_tables_answer():
    """The share test: what each of four shards contributes to a query is
    ITS rows' top-k (``engine._shard_topk``, the function the served
    program runs before its merge); the four lists, put side by side and
    cut to the best ``k`` in numpy, are the reference's answer over the
    whole table, and no shard's list names a row outside its own."""
    V, k, shards = 1003, 16, 4
    table = _table(V, 14)
    model, _ = _model((1, shards), table)
    eng = model.engine
    Vs = eng.rows_per_shard
    queries = np.stack([table[0], table[Vs], _table(1, 15)[0]])
    unit = queries / np.linalg.norm(queries, axis=1, keepdims=True)

    def local(table_l, q, norms_l, nq):
        start = jax.lax.axis_index(MODEL_AXIS) * Vs
        return tuple(
            engine_mod._shard_topk(table_l, q, norms_l, nq, start, k))

    val, idx = jax.jit(eng._shard_map(
        local, in_specs=(P(MODEL_AXIS, None), P(), P(MODEL_AXIS), P()),
        out_specs=(P(None, MODEL_AXIS), P(None, MODEL_AXIS)),
    ))(eng.syn0, eng._pad_query(unit), eng.norms(), jnp.int32(V))
    val = np.asarray(val).reshape(3, shards, k)
    idx = np.asarray(idx).reshape(3, shards, k)
    assert idx.min() >= 0 and idx.max() < Vs  # its own rows, by local id
    blocks = _blocks(table)
    for j, q in enumerate(queries):
        cos = ref.cosines(blocks, q, V)
        rows = idx[j] + np.arange(shards)[:, None] * Vs
        live = np.isfinite(val[j])
        # each shard scored the rows it owns, and scored them right
        assert np.abs(val[j][live] - cos[rows[live]]).max() <= TOL
        order = np.argsort(-val[j].ravel(), kind="stable")[:k]
        want = ref.find_synonyms_vector(blocks, q, k, V)
        assert [int(r) for r in rows.ravel()[order]] == [r for r, _ in want]
        # and the served program's own merge says the same
        sims, got = eng.top_k_cosine_batch(q[None, :], k)
        assert [int(r) for r in got[0]] == [r for r, _ in want]
        assert np.abs(sims[0] - [s for _, s in want]).max() <= TOL
    model.stop()


def test_a_model_saved_over_four_shards_loads_a_quarter_on_each_device(
        tiny_corpus, tmp_path):
    """``Word2VecModel.load`` re-homes the saved topology: a quarter of
    each table on each of four devices, and the answers of the same tables
    on one device."""
    from glint_word2vec_tpu import Word2Vec

    fit = Word2Vec(
        num_shards=4, vector_size=16, min_count=5, batch_size=128, seed=2,
        num_iterations=1).fit(tiny_corpus)
    path = str(tmp_path / "model")
    fit.save(path)
    fit.stop()
    model = Word2VecModel.load(path)
    eng = model.engine
    assert (eng.num_data, eng.num_model) == (1, 4)
    for table in (eng.syn0, eng.syn1):
        shards = table.addressable_shards
        assert len({s.device for s in shards}) == 4
        assert {s.data.shape for s in shards} == {
            (eng.rows_per_shard, eng.padded_dim)}
    assert eng.resident_bytes_per_device() * 4 == eng.resident_bytes()
    one = Word2VecModel.load(path, mesh=make_mesh(1, 1))
    server = ModelServer(model, port=0)  # cli serve's defaults, warm-up on
    server.start_background()
    try:
        table = np.asarray(one.engine.syn0)[:model.vocab.size, :16]
        blocks = _blocks(table)
        for word in ("austria", "vienna", model.vocab.words[-1]):
            got = _post(server, "/synonyms", {"word": word, "num": 5})
            alone = one.find_synonyms(word, 5)
            assert [w for w, _ in got] == [w for w, _ in alone]
            np.testing.assert_allclose(
                [s for _, s in got], [s for _, s in alone], atol=TOL)
            row = model.vocab.word_index[word]
            _same_by_score(
                got, ref.find_synonyms(blocks, row, 5, model.vocab.size),
                model.vocab.words, ref.cosines(blocks, table[row],
                                               model.vocab.size))
        with urllib.request.urlopen(
                f"http://{server.host}:{server.port}/metrics",
                timeout=60) as r:
            metrics = json.loads(r.read())
        assert metrics["compiles"]["post_warmup"] == 0
    finally:
        server.stop()
        model.stop()
        one.stop()


#: sha256 of the lowered text (no debug info, so no scope names) of the
#: one-device programs of ``_lowered`` at commit 3e53f4c, PR 47's parent;
#: the batch top-k's is PR 48's, which gathers its own query rows from
#: the ids it is handed (the parent's read aec390e826e93b37).
PARENT_PROGRAMS = {
    "topk_batch": "7802c35ced09e595",
    "topk": "3ee5ab35d74818d8",
    "pull": "87f1cec0cba1736c",
}


def _lowered(eng):
    pad = eng.padded_dim
    return {
        "topk_batch": eng._make_topk_batch(16).lower(
            eng.syn0, jnp.zeros((16, pad), jnp.float32),
            jnp.zeros((16,), jnp.int32), eng.norms(), jnp.int32(1000)),
        "topk": eng._make_topk(16).lower(
            eng.syn0, jnp.zeros((pad,), jnp.float32), eng.norms(),
            jnp.int32(1000)),
        "pull": eng._pull.lower(eng.syn0, jnp.zeros((16,), jnp.int32)),
    }


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_the_one_device_query_programs_are_the_parents_but_for_metadata(
        name, monkeypatch):
    """The ``glint.*`` scopes of the top-k are op metadata alone: the
    program lowered with them is, text for text, the program lowered with
    every scope taken away, and the parent's."""
    import contextlib

    def build():
        monkeypatch.setattr(engine_mod, "_QUERY_MEMO", {})
        eng = EmbeddingEngine(
            make_mesh(1, 1), 1000, D, np.ones(1000, np.int64),
            num_negatives=2, seed=1)
        return _lowered(eng)[name]

    scoped = build()
    assert "glint.score" in scoped.as_text(debug_info=True) or name == "pull"
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda _: contextlib.nullcontext())
        bare = build()
    assert "glint." not in bare.as_text(debug_info=True)
    assert scoped.as_text() == bare.as_text()
    assert hashlib.sha256(
        scoped.as_text().encode()).hexdigest()[:16] == PARENT_PROGRAMS[name]


@pytest.mark.parametrize("mesh_shape", [(1, 1), (1, 4), (2, 2)],
                         ids=["1x1", "1x4", "2x2"])
def test_metrics_say_how_the_model_lies_on_its_devices(mesh_shape):
    V = 1003
    model, words = _model(mesh_shape, _table(V, 16))
    server = ModelServer(model, port=0, warmup=False)
    server.start_background()
    try:
        _post(server, "/synonyms", {"word": words[1], "num": 3})
        with urllib.request.urlopen(
                f"http://{server.host}:{server.port}/metrics",
                timeout=60) as r:
            doc = json.loads(r.read())
        shards = mesh_shape[1]
        rows = -(-V // shards)
        padded_d = model.engine.padded_dim
        for entry in (doc, doc["models"]["default"]):
            assert entry["shards"] == shards
            assert entry["rows_per_shard"] == rows
            # both tables, every device of the mesh: the budget's number
            assert entry["resident_bytes"] == 2 * rows * shards * padded_d * 4
            # and one device's: its shard of each table
            assert entry["resident_bytes_per_device"] == (
                2 * rows * padded_d * 4)
        with urllib.request.urlopen(
                f"http://{server.host}:{server.port}/metrics?format=prometheus",
                timeout=60) as r:
            text = r.read().decode()
        if "glint_model_resident_bytes" in text:
            assert f'glint_model_shards{{model="default"}} {shards}' in text
    finally:
        server.stop()
        model.stop()


def test_the_round_span_says_how_many_shards_it_launched_on():
    from glint_word2vec_tpu.obs import events as obs_events

    model, words = _model((1, 4), _table(64, 17))
    recorder = obs_events.EventRecorder(capacity=4096)
    prev = obs_events.get_recorder()
    obs_events.set_recorder(recorder)
    server = ModelServer(model, port=0, warmup=False)
    server.start_background()
    try:
        _post(server, "/synonyms", {"word": words[1], "num": 3})
    finally:
        server.stop()
        obs_events.set_recorder(prev)
        model.stop()
    rounds = [e for e in recorder.events() if e["name"] == "req.dispatch"]
    assert rounds and all(e["args"]["shards"] == 4 for e in rounds)


@pytest.mark.parametrize("chunk", ["ids", "vectors", "mixed"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mesh_shape", [(1, 1), (1, 4)], ids=["1x1", "1x4"])
def test_the_round_by_ids_is_the_pull_and_then_the_top_k(
        mesh_shape, dtype, chunk):
    """ONE program a round: the batch top-k handed row ids (and vectors
    where an id is -1) answers as the pull of those rows followed by the
    top-k of what was pulled: the same rows, similarities to ``TOL``. Q is
    5 (its bucket is 8) and 13 (16); row 5 has norm zero; ids fall on both
    sides of every shard edge."""
    V = 1003
    table = _table(V, 23)
    table[5] = 0.0
    counts = np.ones(V, np.int64)
    eng = EmbeddingEngine(make_mesh(*mesh_shape), V, D, counts,
                          num_negatives=2, seed=1, dtype=dtype)
    eng.write_rows(0, jnp.asarray(table))
    per_shard = eng.rows_per_shard
    rng = np.random.default_rng(5)
    for rows in ([0, 5, per_shard - 1, per_shard % V, V - 1],
                 list(rng.choice(V, 12, replace=False)) + [5]):
        rows = np.asarray(rows, np.int32)
        pulled = np.asarray(eng.pull(rows), np.float32)
        sent = rng.normal(0.0, 0.1, pulled.shape).astype(np.float32)
        if chunk == "ids":
            ids, vecs, queries = rows, None, pulled
        elif chunk == "vectors":
            ids, vecs, queries = np.full_like(rows, -1), sent, sent
        else:
            by_vector = np.arange(rows.shape[0]) % 3 == 1
            ids = np.where(by_vector, -1, rows)
            vecs = np.where(by_vector[:, None], sent, 0.0)
            queries = np.where(by_vector[:, None], sent, pulled)
        before = eng.query_dispatches
        val, idx = eng.top_k_cosine_batch(vecs, 10, ids=ids)
        assert eng.query_dispatches == before + 1
        want_val, want_idx = eng.top_k_cosine_batch(queries, 10)
        assert val.shape == (rows.shape[0], 10)
        live = np.isfinite(want_val)
        np.testing.assert_array_equal(np.isfinite(val), live)
        np.testing.assert_array_equal(idx[live], want_idx[live])
        np.testing.assert_allclose(val[live], want_val[live],
                                   rtol=0, atol=TOL)
