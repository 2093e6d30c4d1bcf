"""Subword (fastText-style) model family tests."""

import numpy as np
import pytest

from glint_word2vec_tpu.corpus.subword import (
    build_subword_table,
    fnv1a_32,
    subword_group,
    word_ngrams,
)
from glint_word2vec_tpu.models.fasttext import (
    FastTextModel,
    FastTextParams,
    FastTextWord2Vec,
)
from glint_word2vec_tpu.parallel.mesh import make_mesh


def test_fnv1a_known_vectors():
    # Standard FNV-1a 32-bit test vectors.
    assert fnv1a_32(b"") == 2166136261
    assert fnv1a_32(b"a") == 0xE40C292C
    assert fnv1a_32(b"foobar") == 0xBF9CF968


def test_word_ngrams_boundaries():
    # '<ab>' has length 4: 3-grams are '<ab', 'ab>'; the full token (n=4)
    # is excluded (it is the word's own vector).
    assert word_ngrams("ab", 3, 6) == ["<ab", "ab>"]
    assert word_ngrams("a", 3, 6) == []  # '<a>' too short for any 3-gram
    with pytest.raises(ValueError):
        word_ngrams("x", 0, 3)


def test_subword_group_word_first_and_truncation():
    g = subword_group("berlin", 7, 100, 1000, 3, 6, max_subwords=4)
    assert g[0] == 7  # the word's own row leads
    assert len(g) == 4
    assert all(i >= 100 for i in g[1:])  # buckets offset by vocab size
    # OOV: no word row.
    g_oov = subword_group("berlin", None, 100, 1000, 3, 6, 8)
    assert all(i >= 100 for i in g_oov)


def test_build_subword_table_shapes():
    ids, mask = build_subword_table(["aa", "bb"], 2, 50, 3, 4, 8)
    assert ids.shape == (2, 8) and mask.shape == (2, 8)
    assert mask[0].sum() >= 1  # at least the word's own row
    assert ids[0, 0] == 0 and ids[1, 0] == 1


@pytest.mark.parametrize("min_n,max_n,width,bucket", [
    (3, 6, 32, 2_000_000), (1, 3, 8, 97), (5, 5, 4, 1000), (2, 7, 64, 50)])
def test_build_subword_table_is_the_scalar_group(min_n, max_n, width, bucket):
    # The table is built over all the words' bytes at once; row by row it
    # is subword_group, for words of one to fifteen characters, of one to
    # four bytes a character, and for the empty word.
    rng = np.random.default_rng(0)
    letters = list("abcdefghijklmnopqrstuvwxyzäöüßéñ日本語🙂_0")
    words = ["".join(rng.choice(letters, size=rng.integers(1, 16)))
             for _ in range(400)] + ["a", "", "ab", "日", "🙂" * 12]
    ids, mask = build_subword_table(
        words, len(words), bucket, min_n, max_n, width)
    for w_id, w in enumerate(words):
        g = subword_group(w, w_id, len(words), bucket, min_n, max_n, width)
        assert ids[w_id, :len(g)].tolist() == g, w
        assert mask[w_id].tolist() == [1.0] * len(g) + [0.0] * (width - len(g))
        assert not ids[w_id, len(g):].any()


def test_fasttext_fit_file_takes_the_corpus_resident_path(tiny_corpus, tmp_path):
    path = tmp_path / "corpus.txt"
    path.write_text("\n".join(" ".join(s) for s in tiny_corpus) + "\n")
    m = FastTextWord2Vec(
        mesh=make_mesh(1, 2), vector_size=32, min_count=5, batch_size=256,
        num_iterations=2, seed=1, bucket=5000, min_n=3, max_n=5,
        steps_per_call=4,
    ).fit_file(str(path))
    tm = m.training_metrics
    assert tm["pipeline"] == "device_corpus"
    assert tm["final_loss"] < tm["first_loss"]
    # live rows a centre is the mean of: its own and some n-grams'
    assert 2 < tm["subword_rows_per_center"] <= 32
    assert np.isfinite(m.transform("austriaa")).all()  # OOV still composes
    m.stop()


@pytest.fixture(scope="module")
def ft_model(tiny_corpus):
    ft = FastTextWord2Vec(
        mesh=make_mesh(2, 4), vector_size=32, min_count=5, batch_size=256,
        num_iterations=4, step_size=0.025, seed=1, bucket=5000,
        min_n=3, max_n=5,
    )
    m = ft.fit(tiny_corpus)
    yield m
    m.stop()


def test_fasttext_trains_and_queries(ft_model):
    v = ft_model.transform("austria")
    assert v.shape == (32,) and np.isfinite(v).all() and np.linalg.norm(v) > 0
    syns = ft_model.find_synonyms("austria", 5)
    assert len(syns) == 5 and "austria" not in [w for w, _ in syns]


def test_fasttext_oov_composition(ft_model):
    # The defining capability: an unseen word still gets a vector from its
    # character n-grams, and a near-miss spelling lands near the original.
    v_oov = ft_model.transform("austriaa")
    assert np.isfinite(v_oov).all() and np.linalg.norm(v_oov) > 0
    v = ft_model.transform("austria")
    cos = v @ v_oov / (np.linalg.norm(v) * np.linalg.norm(v_oov))
    assert cos > 0.5, f"shared-ngram word should be similar, cos={cos}"
    # Too-short OOV with no representable ngrams ('<q>' can't host a
    # 3-gram other than itself) raises.
    with pytest.raises(KeyError):
        ft_model.transform("q")


def test_fasttext_engine_rows_and_no_bucket_leakage(ft_model):
    eng = ft_model.engine
    assert eng.num_rows == ft_model.vocab.size + 5000
    # Similarity search must never surface bucket rows.
    sims, idx = eng.top_k_cosine(ft_model.transform("austria"), 20)
    assert np.all(idx < ft_model.vocab.size)


def test_fasttext_transform_sentences(ft_model):
    out = ft_model.transform_sentences([["austria", "zzz-unk"], []])
    assert out.shape == (2, 32)
    assert np.linalg.norm(out[0]) > 0
    np.testing.assert_array_equal(out[1], 0)


def test_fasttext_save_load_roundtrip(ft_model, tmp_path):
    path = str(tmp_path / "ft")
    ft_model.save(path)
    loaded = FastTextModel.load(path, mesh=make_mesh(1, 8))
    np.testing.assert_allclose(
        loaded.transform("austria"), ft_model.transform("austria"),
        rtol=1e-5, atol=1e-6,
    )
    # OOV composition survives the round trip (bucket rows persisted).
    np.testing.assert_allclose(
        loaded.transform("austriaa"), ft_model.transform("austriaa"),
        rtol=1e-5, atol=1e-6,
    )


def test_fasttext_params_validation():
    with pytest.raises(ValueError):
        FastTextParams(min_n=0)
    with pytest.raises(ValueError):
        FastTextParams(bucket=0)
    p = FastTextParams(bucket=100)
    assert FastTextParams.from_json(p.to_json()) == p
