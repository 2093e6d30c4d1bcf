"""The row-sharded configuration (``w2v-300-10m-x4``) at sizes the CPU holds.

* ``benchmark/reference_sharded.py``, the plain reference whose rows are split
  over four devices by XLA's own partitioner, against the numpy transcription
  of the step (``benchmark.reference.sgns_step``).
* The engine's packed scan on a (1, 4) mesh against that reference, on the
  batches the scan drew.
* The packed scan's model-axis exchange: its scope in the compiled program
  and the program's count of its bytes against ``benchmark/bytes_sharded.py``.
"""

import functools
import os
import re
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import bytes_sharded, reference, reference_sharded  # noqa: E402
from glint_word2vec_tpu.corpus.batching import (  # noqa: E402
    context_width,
    packed_pair_batch,
)
from glint_word2vec_tpu.parallel.engine import (  # noqa: E402
    TABLE_LANES,
    EmbeddingEngine,
)
from glint_word2vec_tpu.parallel.mesh import make_mesh  # noqa: E402

V, D, NEG, WINDOW, BATCH, K = 512, 32, 5, 5, 64, 3
PAIRS = packed_pair_batch(BATCH, WINDOW, 1)
# The cell's own limits (benchmark/traffic/w2v-300-10m-x4.train.json). Why
# they hold: reference and program add the same float32 terms, each row's
# duplicates in another order, so an entry differs by a few ulp of the
# largest partial sum, 1e-6 to 1e-5 of the table's largest change; rows
# kept in bfloat16 carry 2**-9 of their own size, 1e-3 of that change and
# more. Change norms are summed in float64 from float32 row sums, so they
# agree to a float32 ulp of a row's norm, 1e-7, not to the ulp of one
# float32 sum over the table.
GAP, DNORM_GAP, LOSS_GAP = 1e-4, 1e-6, 1e-6


def devices4():
    return jax.devices()[:4]


def touched(batches):
    rows = np.unique(np.concatenate([
        np.concatenate([b["centers"], b["contexts"], b["negs"].reshape(-1)])
        for b in batches]))
    return np.pad(rows, (0, -rows.size % 8), mode="edge")


def bf16(x):
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))


def test_sharded_reference_is_the_numpy_step():
    rng = np.random.default_rng(0)
    pairs, hot = 384, 160  # 6 x 384 draws among 160 rows: duplicates
    batches = []
    for _ in range(5):
        contexts = rng.integers(0, hot, pairs).astype(np.int32)
        negs = rng.integers(0, hot, (pairs, NEG)).astype(np.int32)
        negs[::7, 0] = contexts[::7]  # a negative equal to its context
        batches.append({
            "centers": rng.integers(0, hot, pairs).astype(np.int32),
            "contexts": contexts, "negs": negs,
            "mask": (rng.random(pairs) > 0.15).astype(np.float32),
            "alpha": np.float32(0.025)})
    rows = touched(batches)
    init0 = reference_sharded.seed_rows(5, V, D, rows, devices4())
    assert len(init0.sharding.device_set) == 4
    assert {s.data.shape for s in init0.addressable_shards} == {
        (rows.size // 4, D)}
    syn0 = np.array((jax.random.uniform(
        jax.random.PRNGKey(5), (V, D), dtype=np.float32) - 0.5) / D)
    np.testing.assert_array_equal(np.asarray(init0), syn0[rows])
    syn1, losses = np.zeros_like(syn0), []
    for b in batches:
        losses.append(reference.sgns_step(
            syn0, syn1, b["centers"], b["contexts"], b["mask"], b["negs"],
            b["alpha"]))
    gaps = reference_sharded.replay_gaps(
        5, V, D, rows, batches, syn0[rows], syn1[rows],
        np.asarray(losses, np.float32), devices4())
    assert set(gaps) == {"replay.syn0_gap", "replay.syn1_gap",
                         "replay.syn0_dnorm_gap", "replay.syn1_dnorm_gap",
                         "replay.loss_gap"}
    assert gaps["replay.syn0_gap"] < GAP and gaps["replay.syn1_gap"] < GAP
    assert gaps["replay.syn0_dnorm_gap"] < DNORM_GAP, gaps
    assert gaps["replay.syn1_dnorm_gap"] < DNORM_GAP, gaps
    assert gaps["replay.loss_gap"] < LOSS_GAP
    low = reference_sharded.replay_gaps(
        5, V, D, rows, batches, bf16(syn0[rows]), bf16(syn1[rows]),
        np.asarray(losses, np.float32), devices4())
    assert low["replay.syn0_gap"] > 10 * GAP, low
    assert low["replay.syn1_gap"] > 10 * GAP, low
    assert low["replay.syn0_dnorm_gap"] > 10 * DNORM_GAP, low


def corpus(seed=1, sentences=60):
    rng = np.random.default_rng(seed)
    lens = rng.integers(3, 30, sentences)
    p = 1.0 / np.arange(1, V + 1)
    ids = rng.choice(V, size=int(lens.sum()), p=p / p.sum()).astype(np.int32)
    return ids, np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)


def engine(shape, seed=3):
    counts = np.arange(V, 0, -1).astype(np.int64) * 3
    return EmbeddingEngine(make_mesh(*shape), V, D, counts,
                           num_negatives=NEG, seed=seed)


def test_packed_scan_on_a_1x4_mesh_is_the_sharded_reference():
    from benchmark.kinds.train import capture_batches

    seed, total_words = 3, 5000
    eng = engine((1, 4), seed=seed)
    ids, offsets = corpus()
    eng.upload_corpus(ids, offsets)
    eng.set_keep_probs(np.ones(V, np.float32))
    eng.compact_corpus(jax.random.PRNGKey(9))
    losses = eng.train_steps_corpus_packed(
        0, PAIRS, WINDOW, BATCH, jax.random.PRNGKey(seed), K,
        step_size=0.025, total_words=total_words)[0]
    cfg = {"model": {"window": WINDOW, "negatives": NEG, "step_size": 0.025},
           "run": {"batch_size": BATCH}}
    batches = capture_batches(eng, cfg, seed, K, total_words)
    rows = touched(batches)
    # as benchmark/kinds/train_sharded.py reads them: the D real columns
    # of rows that rest in whole lanes
    prog0 = np.asarray(eng.syn0, np.float32)[rows][:, :D]
    prog1 = np.asarray(eng.syn1, np.float32)[rows][:, :D]
    assert {s.data.shape[0] for s in eng.syn0.addressable_shards} == {V // 4}
    gaps = reference_sharded.replay_gaps(
        seed, V, D, rows, batches, prog0, prog1,
        np.asarray(losses, np.float32), devices4())
    assert gaps["replay.syn0_gap"] < GAP and gaps["replay.syn1_gap"] < GAP
    assert gaps["replay.syn0_dnorm_gap"] < DNORM_GAP, gaps
    assert gaps["replay.syn1_dnorm_gap"] < DNORM_GAP, gaps
    assert gaps["replay.loss_gap"] < LOSS_GAP
    low = reference_sharded.replay_gaps(
        seed, V, D, rows, batches, bf16(prog0), bf16(prog1),
        np.asarray(losses, np.float32), devices4())
    assert low["replay.syn0_gap"] > 10 * GAP, low
    assert low["replay.syn1_gap"] > 10 * GAP, low


@functools.lru_cache(maxsize=None)
def all_reduces(shape):
    """(shape text, op name, replica groups) of every all-reduce of the
    packed scan an engine on a mesh of ``shape`` compiles."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    eng = engine(shape)

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(eng.mesh, P(*spec)))

    span = -(-3 * PAIRS // context_width(WINDOW))
    fn = eng._make_packed_corpus_scan(PAIRS, WINDOW, BATCH, span, K)
    table = sds(eng.syn0.shape, jnp.float32, *eng.syn0.sharding.spec)
    offs = sds((61,), jnp.int32)
    i32, u32, f32 = (sds((), t) for t in (jnp.int32, jnp.uint32, jnp.float32))
    text = fn.lower(
        table, table, sds((-(-V // 64), 128), jnp.int32),
        sds((900,), jnp.int32), sds((900,), jnp.int32), offs, offs, i32, i32,
        sds((2,), jnp.uint32), u32, u32, f32, f32, f32).compile().as_text()
    found = []
    for line in text.splitlines():
        m = re.search(r"= (.*?) all-reduce(?:-start)?\(", line)
        if m:
            found.append((
                m.group(1), re.search(r'op_name="([^"]*)"', line).group(1),
                re.search(r"replica_groups=(\{\{.*?\}\})", line).group(1)))
    return found


# rows cross the model axis as they rest, in whole lanes
D_REST = -(-D // TABLE_LANES) * TABLE_LANES


def test_the_exchange_has_its_own_scope():
    found = all_reduces((1, 4))
    across = [f for f in found if f[2] == "{{0,1,2,3}}"]
    data = [f for f in across if f",{D_REST}]" in f[0]]
    assert data, found  # the rows
    for shape, op_name, _ in data:
        assert "/glint.exchange/" in op_name, (shape, op_name)
    for shape, op_name, _ in across:
        assert "glint.gather" not in op_name, (shape, op_name)
        # what else crosses the model axis is the scatters' counts: rows
        # written and slabs moved, of each table
        assert "glint.exchange" in op_name or shape.startswith("s32[4]")


def test_one_shard_exchanges_nothing():
    # The CPU's compiler keeps a psum over one device as an all-reduce
    # among {0} alone (the chip's removes it: tests/test_tpu_compile.py).
    found = all_reduces((1, 1))
    assert found and {f[2] for f in found} == {"{{0}}"}, found


@pytest.mark.parametrize(
    "shape", [(1, 4), (2, 2), (1, 1), (4, 1), (2, 4)])
def test_exchange_bytes_is_what_the_shapes_say(shape):
    eng = engine(shape)
    n_data, n_model = shape
    # one data rank's pairs are the benchmark's, whose mesh has no data axis
    counted = eng.packed_exchange_bytes(n_data * PAIRS)
    if n_model == 1:
        assert counted == 0
        assert bytes_sharded.exchange_bytes(BATCH, WINDOW, NEG, D, 1) == 0
    else:
        assert counted == bytes_sharded.exchange_bytes(
            BATCH, WINDOW, NEG, D_REST, n_model)
    if shape == (1, 4):
        # ... and is what the compiled step hands its row all-reduces
        rows = sum(int(n) for f in all_reduces(shape)
                   if "glint.exchange" in f[1]
                   for n in re.findall(r"f32\[(\d+),%d\]" % D_REST, f[0]))
        assert 4 * rows * D_REST == counted


def test_fit_reports_the_exchange(tmp_path):
    from glint_word2vec_tpu import Word2Vec

    path = tmp_path / "corpus.txt"
    rng = np.random.default_rng(2)
    path.write_text("\n".join(
        " ".join(f"w{i}" for i in rng.integers(0, 200, 12))
        for _ in range(300)) + "\n")
    seen = {}
    for shards in (1, 4):
        model = Word2Vec(vector_size=D, window=WINDOW, num_negatives=NEG,
                         min_count=1, batch_size=BATCH, steps_per_call=2,
                         num_shards=shards, num_iterations=1, seed=1,
                         subsample_ratio=1e-3).fit_file(str(path))
        seen[shards] = model.training_metrics["exchange_bytes_per_step"]
        model.stop()
    assert seen[1] == 0
    assert seen[4] == bytes_sharded.exchange_bytes(
        BATCH, WINDOW, NEG, D_REST, 4)
    assert bytes_sharded.all_reduce_wire_bytes(seen[4], 4) == 1.5 * seen[4]
