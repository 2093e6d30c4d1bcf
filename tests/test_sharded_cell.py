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


def engine(shape, seed=3, architecture="skipgram", groups=None):
    counts = np.arange(V, 0, -1).astype(np.int64) * 3
    eng = EmbeddingEngine(make_mesh(*shape), V, D, counts,
                          num_negatives=NEG, seed=seed,
                          architecture=architecture,
                          extra_rows=0 if groups is None else BUCKET)
    eng.upload_center_groups(groups)
    return eng


# A data rank's pairs (205, 101, 101) are no multiple of the model axis:
# the cell's 26,215 over four. The batch of 63 positions packs 202 pairs on
# a mesh with one data rank or two, so the reference replays what two ranks
# drew.
@pytest.mark.parametrize(
    "shape,batch", [((1, 4), BATCH), ((2, 2), 63), ((2, 4), 63)])
def test_packed_scan_on_a_mesh_is_the_sharded_reference(shape, batch):
    from benchmark.kinds.train import capture_batches

    seed, total_words = 3, 5000
    n_data, n_model = shape
    pairs = packed_pair_batch(batch, WINDOW, n_data)
    assert pairs == packed_pair_batch(batch, WINDOW, 1)
    assert (pairs // n_data) % n_model
    eng = engine(shape, seed=seed)
    ids, offsets = corpus()
    eng.upload_corpus(ids, offsets)
    eng.set_keep_probs(np.ones(V, np.float32))
    eng.compact_corpus(jax.random.PRNGKey(9))
    losses = eng.train_steps_corpus_packed(
        0, pairs, WINDOW, batch, jax.random.PRNGKey(seed), K,
        step_size=0.025, total_words=total_words)[0]
    cfg = {"model": {"window": WINDOW, "negatives": NEG, "step_size": 0.025},
           "run": {"batch_size": batch}}
    batches = capture_batches(eng, cfg, seed, K, total_words)
    rows = touched(batches)
    # as benchmark/kinds/train_sharded.py reads them: the D real columns
    # of rows that rest in whole lanes
    prog0 = np.asarray(eng.syn0, np.float32)[rows][:, :D]
    prog1 = np.asarray(eng.syn1, np.float32)[rows][:, :D]
    assert {s.data.shape[0] for s in eng.syn0.addressable_shards} == {
        V // n_model}
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


BUCKET, G = 300, 8


def random_groups(seed=4):
    """A seeded group table (tests/test_subword_packed.py's): the word's
    own row, then 0 to G - 1 bucket rows, -1 padded."""
    rng = np.random.default_rng(seed)
    groups = V + rng.integers(0, BUCKET, (V, G)).astype(np.int32)
    groups[np.arange(G)[None, :] > rng.integers(0, G, V)[:, None]] = -1
    groups[:, 0] = np.arange(V)
    return groups


def fit_on(shape, family):
    """(tables before, tables after, losses) of K packed steps of one
    ``family``'s scan on a mesh of ``shape``; a step's pairs (205; CBOW:
    60 positions) are no multiple of four shards' sublanes."""
    cbow = family == "cbow"
    eng = engine(shape, architecture="cbow" if cbow else "skipgram",
                 groups=random_groups() if family == "subword" else None)

    def tables():
        return (np.asarray(eng.syn0, np.float32)[:, :D],
                np.asarray(eng.syn1, np.float32)[:, :D])

    before = tables()
    eng.upload_corpus(*corpus())
    eng.set_keep_probs(np.full(V, 0.8 if cbow else 1.0, np.float32))
    eng.compact_corpus(jax.random.PRNGKey(9))
    batch = 60 if cbow else BATCH
    losses = eng.train_steps_corpus_packed(
        0, batch if cbow else PAIRS, WINDOW, batch, jax.random.PRNGKey(3), K,
        step_size=0.05 if cbow else 0.025, total_words=5000)[0]
    return before, tables(), np.asarray(losses)


@pytest.mark.parametrize("family", ["word", "subword", "cbow"])
def test_a_1x4_fit_is_the_one_device_fit(family):
    # A pair's logit is its owner's h . u and the coefficients and the
    # loss are formed whole on every shard; d_center's terms are summed by
    # owner first and across the shards second. The tables and losses are
    # the one device's at the replay's limits.
    befores, ones, one_losses = fit_on((1, 1), family)
    _, fours, four_losses = fit_on((1, 4), family)
    for before, one, four in zip(befores, ones, fours):
        change = np.abs(one - before).max()
        assert change > 0
        assert np.abs(four - one).max() / change < GAP
        d_one = np.sqrt(np.square((one - before).astype(np.float64)).sum())
        d_four = np.sqrt(np.square((four - before).astype(np.float64)).sum())
        assert abs(d_four - d_one) / d_one < DNORM_GAP
    np.testing.assert_allclose(four_losses, one_losses, rtol=LOSS_GAP)


@functools.lru_cache(maxsize=None)
def compiled_text(shape):
    """The compiled text of the packed scan an engine on a mesh of
    ``shape`` builds: ``rank_pairs(shape)`` pairs a data rank a step."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    eng = engine(shape)

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(eng.mesh, P(*spec)))

    pairs = packed_pair_batch(BATCH, WINDOW, eng.num_data)
    span = -(-3 * pairs // context_width(WINDOW))
    fn = eng._make_packed_corpus_scan(pairs, WINDOW, BATCH, span, K)
    table = sds(eng.syn0.shape, jnp.float32, *eng.syn0.sharding.spec)
    offs = sds((61,), jnp.int32)
    i32, u32, f32 = (sds((), t) for t in (jnp.int32, jnp.uint32, jnp.float32))
    return fn.lower(
        table, table, sds((-(-V // 64), 128), jnp.int32),
        sds((900,), jnp.int32), sds((900,), jnp.int32), offs, offs, i32, i32,
        sds((2,), jnp.uint32), u32, u32, f32, f32, f32).compile().as_text()


def rank_pairs(shape):
    return packed_pair_batch(BATCH, WINDOW, shape[0]) // shape[0]


def collectives(shape):
    """(shape text, op name, replica groups, opcode, operand text) of every
    all-reduce, reduce-scatter, all-gather, all-to-all and
    collective-permute of the packed scan an engine on a mesh of ``shape``
    compiles."""
    found = []
    for line in compiled_text(shape).splitlines():
        m = re.search(r"= (.*?) (all-reduce|reduce-scatter|all-gather|"
                      r"all-to-all|collective-permute)(?:-start)?\((.*?)\)",
                      line)
        if m:
            groups = re.search(
                r"(?:replica_groups|source_target_pairs)=(\{\{.*?\}\})", line)
            found.append((
                m.group(1), re.search(r'op_name="([^"]*)"', line).group(1),
                groups.group(1), m.group(2), m.group(3)))
    return found


# rows cross the model axis as they rest, in whole lanes
D_REST = -(-D // TABLE_LANES) * TABLE_LANES


def test_the_exchange_has_its_own_scope():
    found = collectives((1, 4))
    across = [f for f in found if f[2] == "{{0,1,2,3}}"]
    data = [f for f in across if f",{D_REST}]" in f[0]]
    assert data, found  # the rows
    for shape, op_name, *_ in data:
        assert "/glint.exchange/" in op_name, (shape, op_name)
    for shape, op_name, *_ in across:
        assert "glint.gather" not in op_name, (shape, op_name)
        # what else crosses the model axis is the scatters' counts: rows
        # written and slabs moved, of each table
        assert "glint.exchange" in op_name or shape.startswith("s32[4]")
    # No syn1 row crosses: the centre side is all-reduced (every shard's
    # syn1 scatter wants every pair's h), the pair side's 1 + NEG logit
    # partials a pair cross with the small axis major (a float32 (pairs,
    # 6) would rest in 128 lanes a pair), the coefficients and the loss are
    # formed on every shard, and the partial d_center is all-reduced. All
    # that crosses is all-reduced.
    assert {f[3] for f in across} == {"all-reduce"}, across
    assert sorted(f[0] for f in data) == [f"f32[{PAIRS},{D_REST}]{{1,0}}"] * 2
    logits = [f[0] for f in across
              if "glint.exchange" in f[1] and f not in data]
    assert logits == [f"f32[{1 + NEG},{PAIRS}]{{1,0}}"], logits
    # ... at the cell's 26,215 pairs under 1 MB as the chip tiles it,
    # (8, 128): 13.4 MB the other way round
    assert 8 * -(-26_215 // 128) * 128 * 4 < 1e6 < 26_215 * 128 * 4
    assert [f[0] for f in across if f[0].startswith("s32")] == ["s32[4]{0}"]


@pytest.mark.parametrize("shape", [(1, 4), (2, 2)])
def test_no_syn1_row_crosses_the_model_axis(shape):
    # Over the model axis the compiled packed scan has two collectives
    # whose operand is d-wide, h's and d_center's, and every one sums
    # (nothing is gathered, scattered or permuted); the d-wide
    # all-gathers of h_g and dcen_g are the data axis's.
    n_data, n_model = shape
    ranks = np.arange(n_data * n_model).reshape(n_data, n_model)
    model_axis = "{" + ",".join(
        "{" + ",".join(map(str, row)) + "}" for row in ranks) + "}"
    data_axis = "{" + ",".join(
        "{" + ",".join(map(str, col)) + "}" for col in ranks.T) + "}"
    found = collectives(shape)
    assert {f[2] for f in found} <= {model_axis, data_axis}, found
    over = [f for f in found if f[2] == model_axis]
    assert over and {f[3] for f in over} == {"all-reduce"}, over
    wide = [f for f in over if f",{D_REST}]" in f[0]]
    assert [f[0] for f in wide] == (
        [f"f32[{rank_pairs(shape)},{D_REST}]{{1,0}}"] * 2)
    # the first is handed a gather of syn0 (h), the second a sum formed
    # under glint.grads (d_center); no operand is a gather of syn1
    text = compiled_text(shape)
    producers = [re.search(r'%s = .*?op_name="([^"]*)"' % re.escape(
        f[4].split(" ")[-1]), text).group(1) for f in wide]
    assert "glint.gather/syn0" in producers[0], producers
    assert "glint.grads" in producers[1], producers
    if n_data > 1:
        gathered = [f for f in found if f[2] == data_axis
                    and f[3] == "all-gather" and f",{D_REST}]" in f[0]]
        assert len(gathered) == 2, gathered  # h_g, dcen_g


def test_one_shard_exchanges_nothing():
    # The CPU's compiler keeps a psum over one device as an all-reduce
    # among {0} alone (the chip's removes it: tests/test_tpu_compile.py).
    found = collectives((1, 1))
    assert found and {f[2] for f in found} == {"{{0}}"}, found
    assert {f[3] for f in found if "glint.exchange" in f[1]} == {
        "all-reduce"}, found


@pytest.mark.parametrize(
    "shape", [(1, 4), (2, 2), (1, 1), (4, 1), (2, 4)])
def test_exchange_bytes_is_what_the_shapes_say(shape):
    eng = engine(shape)
    n_data, n_model = shape
    # one data rank's pairs are the benchmark's, whose mesh has no data axis
    counted = eng.packed_exchange_bytes(n_data * PAIRS)
    sent = eng.packed_exchange_send_bytes(n_data * PAIRS)
    if n_model == 1:
        assert counted == 0
        assert bytes_sharded.exchange_bytes(BATCH, WINDOW, NEG, D, 1) == 0
        assert sent == {"all_reduce": 0, "reduce_scatter": 0,
                        "all_gather": 0}
    else:
        # a pair's centre row (h) and its d_center, rows as they rest, and
        # its 1 + NEG logits: no syn1 row, so under a third of the seven
        # row blocks the benchmark's numerator still counts
        assert counted == 4 * PAIRS * (2 * D_REST + 1 + NEG)
        assert 3 * counted < bytes_sharded.exchange_bytes(
            BATCH, WINDOW, NEG, D_REST, n_model)
        # what a chip must send at least: all the step has is all-reduces,
        # of S bytes twice (n - 1) / n x S
        assert sent == {
            "all_reduce": 2 * (n_model - 1) * counted // n_model,
            "reduce_scatter": 0, "all_gather": 0}
        assert sent["all_reduce"] == bytes_sharded.all_reduce_wire_bytes(
            counted, n_model)
    if n_model > 1 and n_data == 1:
        # ... and is what the compiled step hands its collectives under
        # the scope, shape by shape
        found = [f for f in collectives(shape) if "glint.exchange" in f[1]]
        assert {f[3] for f in found} == {"all-reduce"}
        assert 4 * sum(
            int(np.prod([int(n) for n in dims.split(",")]))
            for f in found for dims in re.findall(r"f32\[([\d,]+)\]", f[0])
        ) == counted


def test_fit_reports_the_exchange(tmp_path):
    from glint_word2vec_tpu import Word2Vec

    path = tmp_path / "corpus.txt"
    rng = np.random.default_rng(2)
    path.write_text("\n".join(
        " ".join(f"w{i}" for i in rng.integers(0, 200, 12))
        for _ in range(300)) + "\n")
    seen, sent = {}, {}
    for shards in (1, 4):
        model = Word2Vec(vector_size=D, window=WINDOW, num_negatives=NEG,
                         min_count=1, batch_size=BATCH, steps_per_call=2,
                         num_shards=shards, num_iterations=1, seed=1,
                         subsample_ratio=1e-3).fit_file(str(path))
        seen[shards] = model.training_metrics["exchange_bytes_per_step"]
        sent[shards] = model.training_metrics[
            "exchange_send_bytes_per_step"]
        model.stop()
    assert seen[1] == 0 and set(sent[1].values()) == {0}
    # the step's collectives by kind: all-reduces alone, of h, d_center and
    # the logits, which is under a third of seven row blocks' bytes
    assert seen[4] == 4 * PAIRS * (2 * D_REST + 1 + NEG)
    assert sent[4] == {"all_reduce": 3 * seen[4] // 2, "reduce_scatter": 0,
                       "all_gather": 0}
    assert 3 * seen[4] < bytes_sharded.exchange_bytes(
        BATCH, WINDOW, NEG, D_REST, 4)
