"""How the tables rest on the device (``engine.TABLE_LANES``, PR 28).

A table keeps each row in whole 128-column lanes: ``dim`` columns
rest in ``padded_dim`` and the rest is zero. The device's default layout
for such a shape is row-major, so no program copies a whole table at its
edge to reach a few rows, and nothing has to be pinned. What has to hold
is that EVERY producer of a table hands back that shape in the engine's
sharding with the padding still zero (a column that picked up a value
would enter every dot product), that donation still aliases, and that the
real columns are what an engine without the padding computes.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from glint_word2vec_tpu.parallel import engine as engine_mod
from glint_word2vec_tpu.parallel import exchange as exmod
from glint_word2vec_tpu.parallel.engine import TABLE_LANES, EmbeddingEngine
from glint_word2vec_tpu.parallel.mesh import make_mesh

V, D, EXTRA = 50, 16, 8


def _engine(shape=(2, 4)):
    counts = np.arange(V, 0, -1).astype(np.int64) * 10
    return EmbeddingEngine(
        make_mesh(*shape), V, D, counts, num_negatives=3, seed=3,
        extra_rows=EXTRA,
    )


def _corpus(n=400, sentence=8):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, V, n).astype(np.int32)
    return ids, np.arange(0, n + 1, sentence).astype(np.int32)


def _batch(B=16, C=4):
    rng = np.random.default_rng(1)
    return (
        rng.integers(0, V, B).astype(np.int32),
        rng.integers(0, V, (B, C)).astype(np.int32),
        np.ones((B, C), np.float32),
    )


def _packed(eng):
    eng.upload_corpus(*_corpus())
    eng.train_steps_corpus_packed(
        0, 16, 3, 8, jax.random.PRNGKey(5), 4, step0=2, grid_step0=0,
        step_size=0.05, total_words=1000, words_base=0,
    )


def _corpus_scan(eng):
    eng.upload_corpus(*_corpus())
    eng.train_steps_corpus(
        0, 16, 3, jax.random.PRNGKey(5), np.full(2, 0.05, np.float32),
    )


def _step(eng):
    eng.train_step(*_batch(), jax.random.PRNGKey(1), 0.05)


def _scan(eng):
    c, x, m = _batch()
    eng.train_steps(
        np.stack([c, c]), np.stack([x, x]), np.stack([m, m]),
        jax.random.PRNGKey(1), np.full(2, 0.05, np.float32),
    )


def _write_rows(eng):
    eng.write_rows(3, jnp.arange(2 * D, dtype=jnp.float32).reshape(2, D))


def _extra_rows(eng):
    eng.assign_extra_row("w")
    eng.assign_extra_rows(["x", "y", "z"])
    eng.free_extra_rows(2)


def _set_tables(eng):
    rng = np.random.default_rng(2)
    eng.set_tables(
        rng.normal(size=(V + EXTRA, D)).astype(np.float32),
        rng.normal(size=(V + EXTRA, D)).astype(np.float32),
    )


def _exchange(eng):
    # The replica exchange's snapshot and apply programs, through the
    # loopback wire: what it reconstructs is installed as the tables.
    ex = exmod.ReplicaExchanger(eng, mode="sparse", capacity=64)
    _step(eng)
    ex.sync(live=True)


def _exchange_dense(eng):
    ex = exmod.ReplicaExchanger(eng, mode="dense")
    _step(eng)
    ex.sync(live=True)


#: name -> (what installs tables, which of (syn0, syn1) it donates, kwargs
#: of the engine it runs on).
PRODUCERS = {
    "init": (lambda eng: None, (), {}),
    "packed_scan": (_packed, (0, 1), {}),
    "corpus_scan": (_corpus_scan, (0, 1), {}),
    "train_step": (_step, (0, 1), {}),
    "train_scan": (_scan, (0, 1), {}),
    "write_rows": (_write_rows, (0,), {}),
    "extra_rows": (_extra_rows, (0, 1), {}),
    "set_tables": (_set_tables, (), {}),
    "exchange_sparse": (_exchange, (), {}),
    "exchange_dense": (_exchange_dense, (), {}),
    "four_chip_step": (_step, (0, 1), {"shape": (1, 4)}),
    "four_chip_packed_scan": (_packed, (0, 1), {"shape": (1, 4)}),
    "one_device_packed_scan": (_packed, (0, 1), {"shape": (1, 1)}),
}


def _run(name, tmp_path=None):
    """Build an engine, let the producer install its tables, and return
    (engine, the table pair it started from, the pair's host values)."""
    if name == "checkpoint":
        src = _engine()
        _step(src)
        src.save(str(tmp_path / "ck"))
        eng = _engine()
        before = (eng.syn0, eng.syn1)
        eng.adopt_tables(eng.stage_tables(str(tmp_path / "ck")))
        donated = ()
    else:
        produce, donated, kw = PRODUCERS[name]
        eng = _engine(**kw)
        before = (eng.syn0, eng.syn1)
        produce(eng)
    values = tuple(np.asarray(t) for t in (eng.syn0, eng.syn1))
    return eng, tuple(before[i] for i in donated), values


def _fresh_programs(monkeypatch):
    # The scan and query memos hand a same-geometry engine the programs
    # of an earlier one, built for whatever width was in force then.
    monkeypatch.setattr(engine_mod, "_SCAN_MEMO", {})
    monkeypatch.setattr(engine_mod, "_QUERY_MEMO", {})


@pytest.fixture
def forced_setup(monkeypatch):
    monkeypatch.setenv("GLINT_EXCHANGE_FORCE_WIRE", "1")
    _fresh_programs(monkeypatch)


@pytest.mark.parametrize("name", [*PRODUCERS, "checkpoint"])
def test_every_producer_returns_the_tables_as_they_rest(
    name, tmp_path, monkeypatch, forced_setup
):
    eng, donated, values = _run(name, tmp_path)
    assert eng.padded_dim == TABLE_LANES  # 16 columns, one lane
    for table, value in zip((eng.syn0, eng.syn1), values):
        assert table.shape == (eng.padded_vocab, eng.padded_dim)
        assert table.format.layout.major_to_minor == (0, 1)
        assert table.format.sharding.is_equivalent_to(
            eng._table_sharding(), 2
        )
        assert not value[:, D:].any(), "the padding picked up a value"
    assert all(t.is_deleted() for t in donated)

    # An engine whose rows rest unpadded (the parent's): the same values
    # in the real columns. A dot product over zeros adds nothing, but the
    # backend may sum the real terms in another order.
    _fresh_programs(monkeypatch)
    monkeypatch.setattr(engine_mod, "TABLE_LANES", 1)
    bare, _, parent = _run(name, tmp_path / "parent")
    assert bare.padded_dim == D
    for got, ref in zip(values, parent):
        np.testing.assert_allclose(
            got[:, :D], ref[:, :D], rtol=2e-6, atol=1e-9
        )


def test_rows_rest_in_whole_lanes():
    assert TABLE_LANES == 128
    assert _engine().padded_dim == 128


def test_readers_hand_back_the_real_columns_and_compile_once(monkeypatch):
    # pull and pull_average return ``dim`` columns, not the lanes; tables
    # from different producers are one shape and one layout, so each
    # reader compiles once: a written or staged table that differed would
    # compile anew after warm-up (serve.post_warmup_compiles).
    _fresh_programs(monkeypatch)
    eng = _engine()
    idx = np.array([0, 7, 49, 3], np.int32)

    def read():
        rows = np.asarray(eng.pull(idx))
        assert rows.shape == (4, D)
        np.testing.assert_array_equal(rows, np.asarray(eng.syn0)[idx, :D])
        mean = eng.pull_average(idx[None, :], np.ones((1, 4), np.float32))
        assert mean.shape == (1, D)
        eng.norms()
        eng.top_k_cosine_batch(rows, 4)

    read()
    readers = (eng._pull, eng._pull_average, eng._norms)
    sizes = [f._cache_size() for f in readers]
    for produce in (_write_rows, _step, _set_tables, _extra_rows):
        produce(eng)
        read()
    assert [f._cache_size() for f in readers] == sizes
