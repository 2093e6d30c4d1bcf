"""One table layout (ISSUE 46): the tables rest split by rows, and what a
column-sharded ``dims`` engine of an earlier tree left on disk still loads.

* ``tests/data/dims_checkpoint_2x4``: a sharded checkpoint the PARENT of
  ISSUE 46 (7daf58c) wrote from ``EmbeddingEngine(layout="dims")`` on a 2 x 4
  mesh (V = 50, d = 12, one trained step; column blocks of 3, ``"axis":
  "cols"`` in the manifest), with the tables it held beside it. It verifies
  and re-homes by rows onto any mesh.
* A saved model whose ``params.json`` still says ``"layout": "dims"`` loads
  and answers as before; ``Word2VecParams.from_json`` drops the key.
* The query ops against the host at d = 12, where the lane padding shows
  (12 columns rest in 128), on the meshes the cells and the oracle use.
"""

import json
import logging
import os

import numpy as np
import pytest

from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
from glint_word2vec_tpu.parallel.mesh import make_mesh
from glint_word2vec_tpu.utils import integrity
from glint_word2vec_tpu.utils.params import Word2VecParams

V, D = 50, 12
DATA = os.path.join(os.path.dirname(__file__), "data")
CHECKPOINT = os.path.join(DATA, "dims_checkpoint_2x4")


def _tables(eng):
    return (np.asarray(eng.syn0, np.float32)[:V, :D],
            np.asarray(eng.syn1, np.float32)[:V, :D])


def test_a_dims_checkpoint_on_disk_still_verifies():
    assert integrity.verify_snapshot_dir(CHECKPOINT) is True
    with open(os.path.join(CHECKPOINT, "engine.json")) as f:
        meta = json.load(f)
    assert meta["layout"] == "dims"
    assert {b["axis"] for t in meta["shards"].values() for b in t} == {"cols"}


@pytest.mark.parametrize("shape", [(1, 1), (1, 8), (2, 4), (8, 1)])
def test_a_dims_checkpoint_loads_by_rows_on_any_mesh(shape):
    eng = EmbeddingEngine.load(CHECKPOINT, make_mesh(*shape))
    assert eng.step_body.startswith("rows/")
    assert eng.syn0.shape == (eng.padded_vocab, 128)
    assert eng.syn0.sharding.is_equivalent_to(eng._table_sharding(), 2)
    for got, name in zip(_tables(eng), ("syn0", "syn1")):
        np.testing.assert_array_equal(
            got, np.load(f"{CHECKPOINT}_expected_{name}.npy"))
    assert not np.asarray(eng.syn1)[:, D:].any()


def test_a_saved_model_that_says_dims_loads_and_answers_as_before(
        tiny_corpus, tmp_path, caplog):
    from glint_word2vec_tpu import Word2Vec
    from glint_word2vec_tpu.models import load_model

    model = Word2Vec(mesh=make_mesh(1, 2), vector_size=16, min_count=5,
                     batch_size=128, seed=2, num_iterations=1).fit(tiny_corpus)
    word = model.vocab.words[3]
    before = model.find_synonyms(word, 5)
    path = str(tmp_path / "model")
    model.save(path)
    model.stop()
    with open(os.path.join(path, "params.json")) as f:
        params = json.load(f)
    assert "layout" not in params
    params["layout"] = "dims"
    with open(os.path.join(path, "params.json"), "w") as f:
        json.dump(params, f)
    with caplog.at_level(logging.INFO, "glint_word2vec_tpu.utils.params"):
        loaded = load_model(path, mesh=make_mesh(1, 2))
    assert len([r for r in caplog.records if "dims" in r.getMessage()]) == 1
    after = loaded.find_synonyms(word, 5)
    assert [w for w, _ in after] == [w for w, _ in before]
    np.testing.assert_allclose([s for _, s in after], [s for _, s in before],
                               rtol=1e-6)
    loaded.stop()


@pytest.mark.parametrize("layout,lines", [("rows", 0), ("dims", 1)])
def test_from_json_drops_a_legacy_layout_key(layout, lines, caplog):
    blob = json.loads(Word2VecParams(vector_size=8).to_json())
    assert "layout" not in blob
    blob["layout"] = layout
    with caplog.at_level(logging.INFO, "glint_word2vec_tpu.utils.params"):
        params = Word2VecParams.from_json(json.dumps(blob))
    assert params == Word2VecParams(vector_size=8)
    assert not hasattr(params, "layout")
    assert len(caplog.records) == lines


def test_from_json_refuses_a_layout_that_never_was():
    blob = json.loads(Word2VecParams().to_json())
    blob["layout"] = "diagonal"
    with pytest.raises(ValueError, match="layout"):
        Word2VecParams.from_json(json.dumps(blob))


@pytest.mark.parametrize("shape", [(1, 1), (1, 4), (2, 4), (1, 8)])
def test_query_ops_match_host(shape):
    counts = np.arange(V, 0, -1).astype(np.int64) * 10
    eng = EmbeddingEngine(make_mesh(*shape), V, D, counts, num_negatives=4,
                          seed=3)
    assert eng.padded_dim == 128
    syn0 = _tables(eng)[0]
    idx = np.array([0, 7, 49, 3, 3], np.int32)
    pulled = np.asarray(eng.pull(idx))
    assert pulled.shape == (5, D)
    np.testing.assert_allclose(pulled, syn0[idx], rtol=1e-6)
    sent = np.array([[1, 2, 3, 0], [4, 4, 0, 0]], np.int32)
    m = np.array([[1, 1, 1, 0], [1, 1, 0, 0]], np.float32)
    got = np.asarray(eng.pull_average(sent, m))
    assert got.shape == (2, D)
    exp = np.stack([syn0[[1, 2, 3]].mean(0), syn0[[4, 4]].mean(0)])
    np.testing.assert_allclose(got, exp, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(eng.norms())[:V], np.linalg.norm(syn0, axis=1), rtol=1e-5)
    v = np.linspace(-1, 1, D).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(eng.multiply(v))[:V], syn0 @ v, rtol=1e-4, atol=1e-6)
    q = syn0[17].copy()
    sims, top = eng.top_k_cosine(q, 5)
    cos = (syn0 @ (q / np.linalg.norm(q))) / np.linalg.norm(syn0, axis=1)
    exp_idx = np.argsort(-cos)[:5]
    assert top[0] == 17
    np.testing.assert_array_equal(np.sort(top), np.sort(exp_idx))
    np.testing.assert_allclose(sims, cos[exp_idx], rtol=1e-5)
    bs, bi = eng.top_k_cosine_batch(syn0[[5, 9, 17]].copy(), 5)
    assert bs.shape == bi.shape == (3, 5)
    assert bi[0, 0] == 5 and bi[1, 0] == 9
    np.testing.assert_array_equal(bi[2], top)
    np.testing.assert_allclose(bs[2], sims, rtol=1e-5)
