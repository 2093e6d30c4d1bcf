"""CBOW with position weights (ISSUE 54): the engine's third table, ``posw``.

* ``benchmark/reference_cbow_pw_subword.py``, the plain reference in the
  source's form (each position's list of (row, lane) inputs, one mean, the
  whole gradient to each), against a numpy transcription a position at a
  time.
* The engine's bag scan against that reference over 32 steps, both families,
  on all three tables and the losses; on the meshes 1x2, 2x1 and 2x2 against
  one chip's.
* Step one leaves ``syn0`` and ``syn1`` the unweighted program's bits.
* Planted faults (the table's rows in the mirrored order, a table never
  updated, the sum or the whole batch's mean in place of the lane's mean) are
  caught by the table's own gap.
* With the parameter off the lowered scan carries, donates and allocates
  nothing of the table.
* The estimator: the parameter and its refusals, the fit's summary and the
  ring's ``run_end``, save and load, resume, an older checkpoint, queries.
"""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from test_cbow import CORPUS, gaps, zipf_corpus  # noqa: E402
from test_cbow_subword import random_groups  # noqa: E402

from benchmark import reference_cbow_pw_subword as reference  # noqa: E402
from glint_word2vec_tpu import Word2Vec  # noqa: E402
from glint_word2vec_tpu.models import load_model  # noqa: E402
from glint_word2vec_tpu.models.fasttext import FastTextWord2Vec  # noqa: E402
from glint_word2vec_tpu.ops.device_batching import bag_lanes  # noqa: E402
from glint_word2vec_tpu.parallel import engine as engine_mod  # noqa: E402
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine  # noqa: E402
from glint_word2vec_tpu.parallel.mesh import make_mesh  # noqa: E402
from glint_word2vec_tpu.utils.params import Word2VecParams  # noqa: E402

V, D, BUCKET, G, NEG, WINDOW, BATCH, K = 512, 32, 96, 8, 5, 3, 32, 32
L = 2 * WINDOW
GAP, DNORM_GAP, LOSS_GAP = 1e-4, 1e-6, 1e-6
FAMILIES = ["word", "subword"]


def groups_of(family):
    """The family's group table: a word's own row alone, or with its
    bucket rows."""
    if family == "word":
        return np.arange(V, dtype=np.int32)[:, None]
    return random_groups()


def engine(family, shape=(1, 1), lanes=L, seed=3):
    counts = np.arange(V, 0, -1).astype(np.int64) * 3
    eng = EmbeddingEngine(
        make_mesh(*shape), V, D, counts, num_negatives=NEG, seed=seed,
        extra_rows=BUCKET if family == "subword" else 0,
        architecture="cbow", position_lanes=lanes)
    if family == "subword":
        eng.upload_center_groups(groups_of(family))
    return eng


def tables(eng):
    return tuple(np.asarray(t, np.float32)[:, :D]
                 for t in eng.tables().values())


def run_packed(eng, steps=K, seed=3):
    before = tables(eng)
    eng.upload_corpus(*zipf_corpus(sentences=140))
    eng.set_keep_probs(np.full(V, 0.8, np.float32))
    eng.compact_corpus(jax.random.PRNGKey(9))
    assert eng._n_kept > steps * BATCH
    out = eng.train_steps_corpus_packed(
        0, BATCH, WINDOW, BATCH, jax.random.PRNGKey(seed), steps,
        step_size=0.05, total_words=20000)
    return before, [np.asarray(a) for a in out]


def captured(eng, steps=K, seed=3):
    from benchmark.kinds.train_cbow import capture_bags

    cfg = {"model": {"window": WINDOW, "negatives": NEG, "step_size": 0.05},
           "run": {"batch_size": BATCH}}
    return capture_bags(eng, cfg, seed, steps, 20000)


def seeded(eng):
    """Seeded output rows in place of the zeros a fit starts from, under
    which the first steps hardly move ``syn0`` and the position table."""
    rows = np.random.default_rng(6).normal(0, 0.3, (eng.num_rows, D))
    eng.set_tables(tables(eng)[0], rows.astype(np.float32))
    return eng


def replayed(eng, family, init, steps=K):
    """The reference's three tables and losses over the steps the engine
    just ran, from ``init`` (whole tables: every row is "touched")."""
    rows = np.arange(eng.num_rows)
    ref0, ref1, refp, losses = reference.replay(
        init[0], rows, rows, groups_of(family), captured(eng, steps),
        syn1_rows=init[1], posw=init[2])
    return (np.asarray(ref0), np.asarray(ref1), np.asarray(refp),
            np.asarray(losses))


@pytest.mark.parametrize("family", FAMILIES)
def test_reference_is_the_transcription(family):
    rng = np.random.default_rng(0)
    P, rows = 24, V + BUCKET
    groups = groups_of(family)
    syn0 = rng.normal(0, 0.3, (rows, D)).astype(np.float32)
    syn1 = rng.normal(0, 0.3, (rows, D)).astype(np.float32)
    posw = rng.normal(1, 0.3, (L, D)).astype(np.float32)
    bags = rng.integers(0, 40, (P, L)).astype(np.int32)
    bags[rng.random((P, L)) < 0.3] = -1
    bags[3] = -1  # an empty bag is skipped
    bags[5, :2] = 7  # one word in two lanes: each through its own vector
    centres = rng.integers(0, 40, P).astype(np.int32)
    live = (bags >= 0).any(axis=1).astype(np.float32)
    negs = rng.integers(0, 40, (P, NEG)).astype(np.int32)
    negs[2, 1] = centres[2]  # a noise word equal to the target
    want = reference.cbow_pw_step(
        syn0, syn1, posw, groups, bags, centres, live, negs, 0.05)
    batch = {"bags": bags, "centres": centres, "live": live, "negs": negs,
             "alpha": 0.05}
    all_rows = np.arange(rows)
    got0, got1, gotp, losses = reference.replay(
        syn0, all_rows, all_rows, groups, [batch], syn1_rows=syn1, posw=posw)
    for got, exp in zip((got0, got1, gotp), want):
        np.testing.assert_allclose(np.asarray(got), exp, rtol=0, atol=3e-6)
    np.testing.assert_allclose(float(losses[0]), want[3], rtol=1e-5)
    assert np.abs(want[2] - posw).max() > 1e-3  # the table trained
    # lane k's vector takes only what came in by lane k
    only = np.where(np.arange(L)[None, :] == 2, bags, -1)
    moved = reference.cbow_pw_step(
        syn0, syn1, posw, groups, only, centres,
        (only >= 0).any(axis=1).astype(np.float32), negs, 0.05)[2] - posw
    assert np.abs(moved[2]).max() > 0
    assert np.abs(np.delete(moved, 2, axis=0)).max() == 0


@pytest.mark.parametrize("family", FAMILIES)
def test_the_bag_scan_is_the_reference_over_32_steps(family):
    eng = seeded(engine(family))
    init, out = run_packed(eng)
    prog = tables(eng)
    *ref, ref_losses = replayed(eng, family, init)
    for name, p, r, i in zip(eng.table_names, prog, ref, init):
        gap, dnorm = gaps(p, r, i)
        assert gap < GAP and dnorm < DNORM_GAP, (name, gap, dnorm)
    np.testing.assert_allclose(out[0], ref_losses, rtol=LOSS_GAP)
    assert np.abs(prog[2] - 1).max() > 1e-3  # every lane's vector moved
    assert (np.abs(prog[2] - 1).max(axis=1) > 0).all()
    # the unweighted step's tables are NOT these: the weights are in it
    plain = seeded(engine(family, lanes=0))
    run_packed(plain)
    assert gaps(tables(plain)[0], ref[0], init[0])[0] > 10 * GAP
    # the table's padding columns stay zero, as every table's do
    assert not np.asarray(eng.posw)[:, D:].any()


@pytest.mark.parametrize("family", FAMILIES)
def test_from_a_seeded_table_every_bag_meets_its_weights(family, monkeypatch):
    # The benchmark's SEEDED replay at a toy size: from ones the table
    # hardly moves, and a bag without its weights is the weighted one to
    # the table's small change; from a table drawn U[0.5, 1.5) it is not.
    def run():
        eng = seeded(engine(family))
        eng.set_tables(None, None, posw=reference.seeded_posw(5, L, D))
        init, out = run_packed(eng, steps=8)
        return eng, init, out

    eng, init, out = run()
    assert np.abs(init[2] - 1).max() > 0.4
    *ref, ref_losses = replayed(eng, family, init, steps=8)
    for name, p, r, i in zip(eng.table_names, tables(eng), ref, init):
        gap, dnorm = gaps(p, r, i)
        assert gap < GAP and dnorm < DNORM_GAP, (name, gap, dnorm)
    np.testing.assert_allclose(out[0], ref_losses, rtol=LOSS_GAP)
    # the weights never applied, forward or back; the table still trains
    monkeypatch.setattr(engine_mod, "_lane_weighted", lambda w, k, x: x)
    monkeypatch.setattr(engine_mod, "_SCAN_MEMO", {})
    bare, init, _ = run()
    for t in (0, 1):
        assert gaps(tables(bare)[t], ref[t], init[t])[0] > 100 * GAP, t


@pytest.mark.parametrize("family", FAMILIES)
def test_step_one_is_the_unweighted_programs_bits(family):
    """``posw`` starts at ones: the first step's ``syn0`` and ``syn1`` are
    the unweighted program's bit for bit, from the zero ``syn1`` a fit
    starts at and from seeded output rows, under which the table moves."""
    for from_seeded in (False, True):
        with_pw, without = engine(family), engine(family, lanes=0)
        if from_seeded:
            seeded(with_pw), seeded(without)
        (_, out_a), (_, out_b) = (
            run_packed(eng, steps=1) for eng in (with_pw, without))
        for a, b in zip(tables(with_pw)[:2], tables(without)):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(out_a[0], out_b[0])  # the loss
        moved = np.abs(tables(with_pw)[2] - 1).max()
        assert (moved > 0) == from_seeded


def _reversed_rows(real_weighted, real_grads):
    """The table's rows in the mirrored order: lane k reads and trains
    the row of the lane opposite it."""
    def weighted(w, k, x):
        return real_weighted(None if w is None else w[::-1], k, x)

    def grads(lanes, rows, e):
        total, positions = real_grads(lanes, rows, e)
        return total[::-1], positions[::-1]
    return {"_lane_weighted": weighted, "_lane_grads": grads}


def _with_grads(change):
    """``_lane_grads`` with ``change(lanes, total, positions)`` applied."""
    def plant(real_weighted, real_grads):
        def grads(lanes, rows, e):
            return change(lanes, *real_grads(lanes, rows, e))
        return {"_lane_grads": grads}
    return plant


FAULTS = {
    "mirrored": _reversed_rows,
    # the table never updated
    "untrained": _with_grads(lambda lanes, t, n: (0.0 * t, n)),
    # the sum of a lane's shares, not their mean
    "summed": _with_grads(lambda lanes, t, n: (t, jnp.ones_like(n))),
    # the mean over the whole batch, not over the lane's live positions
    "batch_mean": _with_grads(
        lambda lanes, t, n: (t, jnp.full_like(n, lanes[1].shape[0]))),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_caught_by_the_tables_gap(fault, monkeypatch):
    planted = FAULTS[fault](engine_mod._lane_weighted, engine_mod._lane_grads)
    for name, fn in planted.items():
        monkeypatch.setattr(engine_mod, name, fn)
    monkeypatch.setattr(engine_mod, "_SCAN_MEMO", {})
    eng = seeded(engine("subword"))
    init, _ = run_packed(eng, steps=4)
    ref = replayed(eng, "subword", init, steps=4)
    gap, _ = gaps(tables(eng)[2], ref[2], init[2])
    assert gap > 100 * GAP, (fault, gap)
    if fault == "untrained":
        assert gap == 1.0


@pytest.mark.parametrize("shape", [(1, 2), (2, 1), (2, 2)])
def test_a_mesh_gives_one_chips_position_table(shape):
    one, many = engine("subword"), engine("subword", shape)
    assert many.posw.sharding.is_fully_replicated
    assert len(many.posw.sharding.device_set) == shape[0] * shape[1]
    init, out_one = run_packed(one, steps=4)
    _, out_many = run_packed(many, steps=4)
    for name, a, b, i in zip(one.table_names, tables(one), tables(many),
                             init):
        gap, dnorm = gaps(b, a, i)
        assert gap < GAP and dnorm < DNORM_GAP, (name, gap, dnorm)
    np.testing.assert_allclose(out_many[0], out_one[0], rtol=LOSS_GAP)
    assert np.abs(tables(many)[2] - 1).max() > 0
    # every device holds the same table
    held = [np.asarray(s.data) for s in many.posw.addressable_shards]
    for h in held[1:]:
        np.testing.assert_array_equal(h, held[0])


def lowered(eng, groups=False):
    fn = eng._make_packed_corpus_scan(BATCH, WINDOW, BATCH, 0, 2,
                                      G if groups else 0)
    sds = jax.ShapeDtypeStruct
    i32, u32, f32 = (sds((), t) for t in (jnp.int32, jnp.uint32, jnp.float32))
    words, offs = sds((900,), jnp.int32), sds((61,), jnp.int32)
    return fn.lower(
        *(sds(t.shape, t.dtype) for t in eng.tables().values()),
        sds((-(-V // 64), 128), jnp.int32), words, words, offs, offs, i32,
        i32, sds((2,), jnp.uint32), u32, u32, f32, f32, f32,
        *((sds((V, G), jnp.int32),) if groups else ()))


@pytest.mark.parametrize("family", FAMILIES)
def test_with_the_parameter_off_the_scan_holds_nothing_of_the_table(family):
    """The lowered bag scan of an engine without position weights: its
    operands are ``syn0``, ``syn1`` and the parent's inputs, two of them
    donated, and no value of ``(2 * window, columns)`` is anywhere in the
    program: nothing of ``posw`` is carried, donated or allocated."""
    sub = family == "subword"
    off, on = engine(family, lanes=0), engine(family)
    assert off.posw is None and off.table_names == ("syn0", "syn1")
    assert off.tables().keys() == {"syn0", "syn1"}
    cols = off.padded_dim
    shape = re.compile(rf"tensor<{L}x{cols}xf32>")

    def signature(text):
        main = text[text.index("func.func public @main("):]
        args = main[:main.index(") -> ")]
        return args.count("%arg"), args.count("jax.buffer_donor = true") + (
            args.count("tf.aliasing_output"))

    text_off, text_on = (lowered(e, sub).as_text() for e in (off, on))
    n_args = 16 if sub else 15
    assert signature(text_off) == (n_args, 2)
    assert not shape.search(text_off)
    assert signature(text_on) == (n_args + 1, 3)
    assert shape.search(text_on)
    # the memo tells the two apart
    key = ("packed", BATCH, WINDOW, BATCH, 0, 2, 0)
    assert off._scan_memo_key(*key) != on._scan_memo_key(*key)
    # bytes and names: whatever walks the tables walks the third
    assert on.resident_bytes() - off.resident_bytes() == L * cols * 4
    assert on.position_table_stats() == {
        "rows": L, "max_abs_dev": 0.0, "finite": True}
    assert off.position_table_stats() is None


def test_the_weighted_scan_keeps_the_programs_name_and_its_scopes():
    low = lowered(engine("subword"), groups=True)
    assert "packed_scan" in low.as_text()
    compiled = low.compile().as_text()
    for scope in ("glint.compose/group", "glint.compose/bag",
                  "glint.compose/posgrad", "glint.scatter/syn0"):
        assert scope in compiled, scope
    assert "glint.compose/posgrad" not in lowered(
        engine("subword", lanes=0), groups=True).compile().as_text()


def test_what_is_refused_says_so():
    with pytest.raises(ValueError, match="architecture must be 'cbow'"):
        Word2VecParams(position_weights=True)
    with pytest.raises(ValueError, match="architecture must be 'cbow'"):
        FastTextWord2Vec(position_weights=True, architecture="skipgram")
    with pytest.raises(ValueError, match="true or false"):
        Word2VecParams(architecture="cbow", position_weights=1)
    # wherever CBOW is refused, so is this
    for kw in ({"shared_negatives": 64}, {"batch_packing": "grid"},
               {"exchange": "sparse"}):
        with pytest.raises(ValueError):
            Word2VecParams(architecture="cbow", position_weights=True, **kw)
    counts = np.ones(V, np.int64)
    with pytest.raises(ValueError, match="architecture='cbow'"):
        EmbeddingEngine(make_mesh(1, 1), V, D, counts, position_lanes=L)
    with pytest.raises(ValueError, match="2 \\* window"):
        EmbeddingEngine(make_mesh(1, 1), V, D, counts, architecture="cbow",
                        position_lanes=3)
    eng = engine("word")
    eng.upload_corpus(*zipf_corpus())
    with pytest.raises(ValueError, match="trains window 3, not 5"):
        eng.train_steps_corpus_packed(
            0, BATCH, 5, BATCH, jax.random.PRNGKey(0), 1)
    with pytest.raises(ValueError, match="no position table"):
        engine("word", lanes=0).set_tables(
            *tables(eng)[:2], posw=np.ones((L, D), np.float32))
    assert bag_lanes(WINDOW) == [-3, -2, -1, 1, 2, 3]  # the rows' order


def estimator(family, **kw):
    defaults = dict(
        vector_size=12, batch_size=32, min_count=1, num_iterations=4,
        seed=7, steps_per_call=4, window=3, architecture="cbow",
        position_weights=True, subsample_ratio=0.01, step_size=0.05)
    if family == "subword":
        defaults.update(bucket=200, min_n=3, max_n=4, max_subwords=8)
    defaults.update(kw)
    return (FastTextWord2Vec if family == "subword" else Word2Vec)(**defaults)


@pytest.mark.parametrize("family", FAMILIES)
def test_the_fit_trains_the_table_and_says_so(family, tmp_path):
    from glint_word2vec_tpu.obs import ObsConfig

    log = str(tmp_path / "events.jsonl")
    m = estimator(family, obs=ObsConfig(event_log=log)).fit(CORPUS)
    tm = m.training_metrics
    assert tm["pipeline"] == "device_corpus"
    table = tm["position_table"]
    assert table["rows"] == 6 and table["finite"]
    assert table["max_abs_dev"] == pytest.approx(
        float(np.abs(np.asarray(m.engine.posw)[:, :12] - 1).max()))
    assert table["max_abs_dev"] > 0
    assert tm["final_loss"] < tm["first_loss"]
    with open(log) as f:
        ends = [e for e in map(json.loads, f) if e.get("name") == "run_end"]
    assert ends[-1]["args"]["position_table"] == table
    assert "position_table" not in estimator(
        family, position_weights=False).fit(CORPUS).training_metrics


@pytest.mark.parametrize("family", FAMILIES)
def test_save_load_and_resume_give_the_uninterrupted_fits_tables(
        family, tmp_path):
    whole = estimator(family).fit(CORPUS)
    ck = str(tmp_path / "ck")
    first = estimator(family).fit(
        CORPUS, checkpoint_dir=ck, stop_after_epochs=2)
    assert np.abs(np.asarray(first.engine.posw) - 1).max() > 0
    resumed = estimator(family).fit(CORPUS, checkpoint_dir=ck)
    for name in whole.engine.table_names:
        np.testing.assert_array_equal(
            np.asarray(getattr(resumed.engine, name)),
            np.asarray(getattr(whole.engine, name)), err_msg=name)
    # a saved model keeps the table and the parameter, in both formats
    path = str(tmp_path / "model")
    whole.save(path)
    with open(os.path.join(path, "params.json")) as f:
        assert json.load(f)["position_weights"] is True
    with open(os.path.join(path, "matrix", "engine.json")) as f:
        meta = json.load(f)
    assert meta["position_lanes"] == 6
    assert [b["file"] for b in meta["shards"]["posw"]] == [
        "posw.r000000000000.npy"]
    loaded = load_model(path)
    assert loaded.params.position_weights is True
    assert loaded.engine.table_names == ("syn0", "syn1", "posw")
    for name in whole.engine.table_names:
        np.testing.assert_array_equal(
            np.asarray(getattr(loaded.engine, name)),
            np.asarray(getattr(whole.engine, name)), err_msg=name)
    single = str(tmp_path / "single")
    whole.engine.save(single, mode="single")
    np.testing.assert_array_equal(
        np.load(os.path.join(single, "posw.npy")),
        np.asarray(whole.engine.posw)[:, :12])
    again = EmbeddingEngine.load(single, make_mesh(1, 2))
    np.testing.assert_array_equal(
        np.asarray(again.posw), np.asarray(whole.engine.posw))
    # a fit with the weights does not resume a checkpoint without them
    with pytest.raises(ValueError, match="position table"):
        estimator(family, position_weights=False).fit(
            CORPUS, checkpoint_dir=ck)


@pytest.mark.parametrize("family", FAMILIES)
def test_a_checkpoint_of_a_fit_without_the_table_still_loads(
        family, tmp_path, monkeypatch):
    """A model saved before the parameter existed: no ``position_weights``
    in ``params.json``, no ``position_lanes`` in ``engine.json``, no third
    table on disk."""
    old_meta = EmbeddingEngine._save_meta

    def meta_without(self, mode):
        meta = old_meta(self, mode)
        del meta["position_lanes"]
        return meta

    monkeypatch.setattr(EmbeddingEngine, "_save_meta", meta_without)
    path = str(tmp_path / "older")
    m = estimator(family, position_weights=False, num_iterations=1).fit(
        CORPUS)
    m.save(path)
    monkeypatch.undo()
    with open(os.path.join(path, "params.json")) as f:
        doc = json.load(f)
    del doc["position_weights"]
    with open(os.path.join(path, "params.json"), "w") as f:
        json.dump(doc, f)
    assert not [f for f in os.listdir(os.path.join(path, "matrix"))
                if f.startswith("posw")]
    old = load_model(path)
    assert old.params.position_weights is False
    assert old.engine.position_lanes == 0 and old.engine.posw is None
    np.testing.assert_array_equal(np.asarray(old.engine.syn0),
                                  np.asarray(m.engine.syn0))
    assert ([w for w, _ in old.find_synonyms("dog", 3)]
            == [w for w, _ in m.find_synonyms("dog", 3)])


@pytest.mark.parametrize("family", FAMILIES)
def test_queries_read_syn0_alone(family):
    """``/synonyms`` of a fitted model is the exact top-k over (composed)
    ``syn0``: the position table is no part of a word's vector, as the
    published ``.vec`` files hold input vectors alone."""
    from glint_word2vec_tpu.serving import ModelServer

    m = estimator(family).fit(CORPUS)
    words = m.vocab.words
    vecs = np.stack([m.transform(w) for w in words]).astype(np.float64)
    if family == "word":
        np.testing.assert_array_equal(
            vecs.astype(np.float32),
            np.asarray(m.engine.syn0)[:len(words), :12])
    unit = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)

    def exact(word, k):
        sims = unit @ unit[words.index(word)]
        sims[words.index(word)] = -np.inf
        return [words[i] for i in np.argsort(-sims)[:k]]

    server = ModelServer(m, port=0)
    server.start_background()
    try:
        import urllib.request

        def ask(word):
            req = urllib.request.Request(
                f"http://{server.host}:{server.port}/synonyms",
                data=json.dumps({"word": word, "num": 4}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                return [w for w, _ in json.loads(r.read())]

        before = {w: ask(w) for w in ("dog", "fox", "sun")}
        for w, got in before.items():
            assert got == exact(w, 4), w
        # another position table (a table mutation: the caches drop), the
        # same answers
        m.engine.set_tables(
            *(np.asarray(t)[:, :12] for t in (m.engine.syn0, m.engine.syn1)),
            posw=np.full((6, 12), 3.0, np.float32))
        assert {w: ask(w) for w in before} == before
    finally:
        server.stop()
        m.stop()
