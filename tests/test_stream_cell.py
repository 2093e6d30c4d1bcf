"""The streamed fit (``w2v-stream-300-2m``) at sizes the CPU holds.

* ``StreamTrainer`` against ``streaming/stream_reference.py`` on a seeded
  stream (``benchmark/corpus_stream.py``: a bootstrap window, then live
  sentences with new words): every round's buffer, every promotion and its
  row, the counts and the keep probabilities exactly, the alias table's
  distribution to 1e-6, the tables after every round within the batch
  reference's tolerance on the batches the scan drew.
* A bounded view (``upload_corpus(n_valid)``) trains no id at or past its
  end and stops there.
* Nothing compiles after the round of the first promotion, whatever the
  later bursts' sizes (the counter the benchmark uses).
* The round's spans and the new ``training_metrics`` keys.
* The packed step a stream-built engine lowers to is the parent's.
* The cell kind's set-up refuses a large vocabulary without the native
  alias builder, and names ``native/``.
"""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import corpus_stream  # noqa: E402
from benchmark.kinds import train_stream  # noqa: E402
from glint_word2vec_tpu import Word2Vec  # noqa: E402
from glint_word2vec_tpu.corpus.batching import (  # noqa: E402
    context_width,
    packed_pair_batch,
)
from glint_word2vec_tpu.corpus.stream_vocab import StreamVocab  # noqa: E402
from glint_word2vec_tpu.corpus.vocab import iter_text_file  # noqa: E402
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine  # noqa: E402
from glint_word2vec_tpu.parallel.mesh import make_mesh  # noqa: E402
from glint_word2vec_tpu.streaming import stream_reference  # noqa: E402

V, EXTRA, D, NEG, WINDOW, BATCH, K = 2000, 64, 32, 5, 5, 256, 4
BUFFER = 4096
TRAFFIC = {
    "sentence_words": 40, "bootstrap_tokens": 4800, "new_word_share": 0.03,
    "new_word_pool": 200, "planted_per_sentence": 80000 / 150000,
    "nominal_words_per_s": 12000,
}
CFG = {"model": {"vector_size": D, "negatives": NEG}}
SEED = 5
# benchmark/traffic/w2v-stream-300-2m.train.json's limit on a table's
# largest gap over its largest change, there over one dispatch group and
# here over a whole fit: reference and program add the same float32 terms,
# a row's duplicates in another order (tests/test_sharded_cell.py).
GAP = 1e-4


@pytest.fixture(scope="module")
def stream(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("stream") / "stream.txt")
    sizes = corpus_stream.make_stream(path, V, TRAFFIC, SEED, 2.0)
    return path, sizes


def estimator(**kw):
    return Word2Vec(
        vector_size=D, window=WINDOW, num_negatives=NEG, step_size=0.025,
        subsample_ratio=1e-3, min_count=1, batch_size=BATCH,
        steps_per_call=K, seed=SEED, **kw)


def stream_kw(sizes, buffer_sentences):
    return dict(
        bootstrap_words=TRAFFIC["bootstrap_tokens"], buffer_words=BUFFER,
        buffer_sentences=buffer_sentences, extra_rows=EXTRA,
        promote_min_count=5, sketch_capacity=512,
        anneal_words=sizes["bootstrap_tokens"] + sizes["live_tokens"])


class Watch:
    """Wrappers around the engine's calls that follow a fit: the buffers,
    the alias tables, the keep probabilities, and the plain reference's
    tables taken through every step on the batches the scan drew."""

    def __init__(self, monkeypatch, follow_tables=True):
        self.buffers, self.pmfs, self.keeps = [], [], []
        self.bursts = []  # (start row, words) of every promotion
        self.ref = None  # [syn0, syn1] of the reference, float32
        self.init = None
        self.after_round = []  # largest gap over largest change, a round
        self.times = []  # perf_counter of every upload
        self.promoted_at_upload = []  # words promoted before every upload
        self.first_promotion_upload = None
        watch, orig = self, {}
        for name in ("upload_corpus", "train_steps_corpus_packed",
                     "set_noise_counts", "assign_extra_rows"):
            orig[name] = getattr(EmbeddingEngine, name)

        def tables(engine):
            return [np.array(t, np.float32)[:engine.num_rows, :D]
                    for t in (engine.syn0, engine.syn1)]

        def upload(engine, ids, offsets, n_valid=None):
            import time

            if not follow_tables:
                pass
            elif watch.ref is None:
                watch.ref = tables(engine)
                watch.init = [t.copy() for t in watch.ref]
            else:
                watch.after_round.append(watch.gap(tables(engine)))
            watch.times.append(time.perf_counter())
            watch.promoted_at_upload.append(
                sum(len(ws) for _, ws in watch.bursts))
            if watch.bursts and watch.first_promotion_upload is None:
                watch.first_promotion_upload = watch.times[-1]
            watch.buffers.append((np.array(ids), np.array(offsets), n_valid))
            return orig["upload_corpus"](engine, ids, offsets,
                                         n_valid=n_valid)

        def packed(engine, *a, **k):
            if not follow_tables:  # capture compiles a program a call
                return orig["train_steps_corpus_packed"](engine, *a, **k)
            cap = train_stream.capture(engine, CFG, a, k)
            out = orig["train_steps_corpus_packed"](engine, *a, **k)
            for b in cap["batches"]:
                stream_reference.sgns_step(
                    watch.ref[0], watch.ref[1], b["centers"], b["contexts"],
                    b["mask"], b["negs"], b["alpha"])
            return out

        def set_noise_counts(engine, counts, table=None):
            out = orig["set_noise_counts"](engine, counts, table)
            watch.pmfs.append(stream_reference.alias_pmf(
                np.asarray(engine._prob), np.asarray(engine._alias)))
            return out

        def assign_extra_rows(engine, words):
            rows = orig["assign_extra_rows"](engine, words)
            watch.bursts.append((rows[0], list(words)))
            if not follow_tables:
                return rows
            # a promoted row starts from the device's draw, handed over
            # as the batches are, and from zeros in syn1
            now = tables(engine)
            watch.ref[0][rows] = now[0][rows]
            watch.ref[1][rows] = 0.0
            assert np.abs(now[1][rows]).max() == 0
            return rows

        keep_orig = StreamVocab.keep_probabilities

        def keep(sv, ratio):
            out = keep_orig(sv, ratio)
            watch.keeps.append(out.copy())
            return out

        monkeypatch.setattr(EmbeddingEngine, "upload_corpus", upload)
        monkeypatch.setattr(EmbeddingEngine, "train_steps_corpus_packed",
                            packed)
        monkeypatch.setattr(EmbeddingEngine, "set_noise_counts",
                            set_noise_counts)
        monkeypatch.setattr(EmbeddingEngine, "assign_extra_rows",
                            assign_extra_rows)
        monkeypatch.setattr(StreamVocab, "keep_probabilities", keep)
        self.tables = tables

    def gap(self, prog):
        return max(
            float(np.abs(p - r).max() / np.abs(r - i).max())
            for p, r, i in zip(prog, self.ref, self.init))


def reference_of(path, sizes, buffer_sentences):
    return stream_reference.StreamReference(
        iter_text_file(path), bootstrap_words=TRAFFIC["bootstrap_tokens"],
        min_count=1, promote_min_count=5, extra_rows=EXTRA,
        sketch_capacity=512, buffer_words=BUFFER,
        buffer_sentences=buffer_sentences, refresh_words=BUFFER,
        subsample_ratio=1e-3, seed=SEED)


# 512 sentences never cut a buffer of 4,096 words at 40-word sentences;
# 90 cut every one of them. ``ahead``: the device reads as busy whenever it
# is asked, so the host half runs its two rounds ahead of the round that
# trains (on the CPU a group is done before the first question); what it
# makes must not depend on when it runs.
@pytest.mark.parametrize("buffer_sentences,ahead",
                         [(512, False), (90, False), (512, True), (90, True)])
def test_stream_trainer_is_the_plain_reference(stream, monkeypatch,
                                               buffer_sentences, ahead):
    from glint_word2vec_tpu.streaming import trainer as trainer_mod

    path, sizes = stream
    watch = Watch(monkeypatch)
    # not ahead: never busy, so every round is made when the last is trained
    monkeypatch.setattr(trainer_mod.StreamTrainer, "_device_busy",
                        staticmethod(lambda group: ahead))
    if ahead:
        monkeypatch.setattr(trainer_mod, "_CHUNK_SENTENCES", 7)
    model = estimator().fit_stream(
        iter_text_file(path), **stream_kw(sizes, buffer_sentences))
    watch.after_round.append(watch.gap(watch.tables(model.engine)))
    tm = model.training_metrics

    ref = reference_of(path, sizes, buffer_sentences)
    rounds = []
    while (rnd := ref.next_round()) is not None:
        rounds.append(rnd)
    # -- the host half, exactly ----------------------------------------
    assert len(rounds) == len(watch.buffers) == tm["rounds"]
    for rnd, (ids, offsets, n_valid) in zip(rounds, watch.buffers):
        assert n_valid == rnd["fill"]
        np.testing.assert_array_equal(ids, rnd["ids"])
        np.testing.assert_array_equal(offsets, rnd["offsets"])
    fills = [r["fill"] for r in rounds]
    cut_by_words = [BUFFER - f < 40 for f in fills[:-1]]
    if buffer_sentences == 90:
        assert not any(cut_by_words)  # every buffer cut by its sentences
        assert all(r["offsets"][90] == r["fill"] for r in rounds[:-1])
    else:
        assert all(cut_by_words)  # a sentence carried over every seam
    assert 0 < fills[-1] < min(fills[:-1])  # a partial last buffer
    got = [(w, row + i) for row, ws in watch.bursts for i, w in enumerate(ws)]
    assert got == ref.promoted and len(got) >= 10
    through = np.cumsum([len(r["promoted"]) for r in rounds])
    if ahead:  # some promotion's rows were written rounds before its own
        assert any(watch.promoted_at_upload[:-1] > through[:-1])
    else:
        assert list(watch.promoted_at_upload) == list(through)
    assert max(len(ws) for _, ws in watch.bursts) >= 4  # a burst
    assert model.vocab.words == ref.words
    np.testing.assert_array_equal(model.vocab.counts, ref.counts)
    assert model.vocab.train_words_count == ref.total
    assert tm["words_trained"] == ref.words_trained
    assert tm["refreshes"] == ref.refreshes == len(watch.pmfs)
    assert tm["promoted_words"] == len(ref.promoted)
    # -- the distributions in force a round ----------------------------
    refreshed = [r for r in rounds if r["refreshed"]]
    # the trainer's first keep probabilities are the bootstrap counts'
    assert len(watch.keeps) == len(refreshed) + 1
    for rnd, keep, pmf in zip(refreshed, watch.keeps[1:], watch.pmfs):
        np.testing.assert_array_equal(keep, rnd["keep"])
        assert pmf.shape == (V,)  # no spare row has mass
        assert np.abs(pmf - rnd["noise"]).sum() < 1e-6
    # -- the device half: the tables after every round -----------------
    assert len(watch.after_round) == len(rounds)
    assert max(watch.after_round) < GAP, watch.after_round
    assert watch.after_round[-1] > 0  # two computations, not one copied


def test_space_saving_sketch_is_the_program_s_at_capacity():
    """Evictions, which the cell's stream never reaches: the reference's
    sketch against the program's on a stream of more words than either
    holds."""
    from glint_word2vec_tpu.corpus.stream_vocab import SpaceSavingSketch

    rng = np.random.default_rng(3)
    words = [f"w{min(int(z), 400)}" for z in rng.zipf(1.3, 6000)]
    prog, ref = SpaceSavingSketch(32), stream_reference.SpaceSaving(32)
    for w in words:
        prog.add(w)
        ref.add(w)
    assert dict(prog._counts) == ref.count and prog._errors == ref.error
    assert [(w, c) for w, c, _ in prog.over_threshold(5)] == ref.over(5)


def test_a_bounded_view_trains_nothing_at_or_past_its_end():
    """``upload_corpus(n_valid)`` then the packed scan: at a fill that is
    no multiple of the batch, no id at or past ``n_valid`` is touched and
    the scan stops at the view's end."""
    vocab, fill, words = 600, 1000 + 37, 2048
    counts = np.r_[np.full(300, 50), np.zeros(300)].astype(np.int64)
    eng = EmbeddingEngine(make_mesh(1, 1), vocab, D, counts,
                          num_negatives=NEG, seed=3)
    rng = np.random.default_rng(1)
    ids = np.r_[rng.integers(0, 300, fill),
                rng.integers(300, 600, words - fill)].astype(np.int32)
    offsets = np.r_[np.arange(0, fill, 25), fill, words].astype(np.int64)
    before = [np.array(t) for t in (eng.syn0, eng.syn1)]
    eng.upload_corpus(ids, offsets, n_valid=fill)
    pairs = packed_pair_batch(64, WINDOW, 1)
    pos, groups = 0, 0
    while pos < fill:
        _, n_pairs, pos_ends, alphas, _ = eng.train_steps_corpus_packed(
            pos, pairs, WINDOW, 64, jax.random.PRNGKey(2), K, step0=groups * K,
            grid_step0=groups * K, total_words=10**6)
        pos_ends, alphas = np.asarray(pos_ends), np.asarray(alphas)
        starts = np.r_[pos, pos_ends[:-1]]
        # a step ran exactly where it started inside the view
        np.testing.assert_array_equal(alphas > 0, starts < fill)
        assert np.all(np.asarray(n_pairs)[starts >= fill] == 0)
        pos, groups = int(pos_ends[-1]), groups + 1
    # the last live step may look past the end (zero-mask lanes); none
    # starts there
    assert fill <= pos < fill + 64 and fill % 64 and groups >= 2
    after = [np.array(t) for t in (eng.syn0, eng.syn1)]
    for b, a in zip(before, after):
        np.testing.assert_array_equal(a[300:], b[300:])
        assert np.abs(a[:300] - b[:300]).max() > 0


def test_nothing_compiles_after_the_first_promotion(stream, monkeypatch):
    from benchmark.run import Run

    path, sizes = stream
    watch = Watch(monkeypatch, follow_tables=False)
    with Run.count_compiles(None) as compiles:
        model = estimator().fit_stream(
            iter_text_file(path), **stream_kw(sizes, 512))
    t0 = watch.first_promotion_upload
    later = [e for t, e in compiles if t >= t0]
    assert compiles and not later, later
    # what the rest of the run held: bursts of other sizes, refreshes, a
    # partial buffer
    later_sizes = {len(ws) for _, ws in watch.bursts[1:]}
    assert len(later_sizes) >= 2, watch.bursts
    assert len(watch.bursts[0][1]) not in later_sizes
    assert model.training_metrics["refreshes"] >= 4
    fills = [n for _, _, n in watch.buffers]
    assert fills[-1] < min(fills[:-1])


def test_a_round_s_spans_and_counters(stream, tmp_path):
    from glint_word2vec_tpu.obs import ObsConfig

    path, sizes = stream
    ring = str(tmp_path / "spans.json")
    model = estimator(obs=ObsConfig(chrome_trace=ring)).fit_stream(
        iter_text_file(path), **stream_kw(sizes, 512))
    tm = model.training_metrics
    with open(ring) as f:
        events = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)
    rounds = by["stream_round"]
    assert len(rounds) == tm["rounds"] == len(by["stream_promote"]) == len(
        by["stream_adapt"]) == len(by["upload_corpus"])
    # the fill is a span a slice (a chunk of sentences), each with the
    # round it fills
    fills = by["stream_fill"]
    assert len(fills) > tm["rounds"]
    assert {e["args"]["round"] for e in fills} == set(range(tm["rounds"]))
    assert len(by["stream_install"]) == tm["refreshes"]
    assert [e["args"]["round"] for e in rounds] == list(range(tm["rounds"]))
    for e in rounds:
        assert set(e["args"]) == {"round", "fill", "raw_words", "live",
                                  "groups"}
    assert sum(e["args"]["live"] for e in rounds) == tm["live_rounds"] > 0
    assert not rounds[0]["args"]["live"]  # the bootstrap window's replay
    assert sum(e["args"]["raw_words"] for e in rounds) == (
        tm["raw_words_streamed"]
    ) == sizes["bootstrap_tokens"] + sizes["live_tokens"]
    assert sum(e["args"]["fill"] for e in rounds) == tm["words_trained"]
    # what a round pulled and whether it is live, as the reference counts
    # them (the benchmark's window opens by these)
    ref = reference_of(path, sizes, 512)
    want = []
    while (rnd := ref.next_round()) is not None:
        want.append((rnd["fill"], rnd["raw_words"], rnd["live"]))
    assert [(e["args"]["fill"], e["args"]["raw_words"], e["args"]["live"])
            for e in rounds] == want
    assert sum(e["args"]["groups"] for e in rounds) == len(
        by["device_steps"]) == len(by["readback_harvest"])
    assert sum(e["args"]["promoted"] for e in by["stream_promote"]) == (
        tm["promoted_words"]) > 0
    assert sum(e["args"]["refreshed"] for e in by["stream_adapt"]) == (
        tm["refreshes"])
    assert {e["args"]["alias_native"] for e in by["stream_adapt"]} == {
        tm["alias_native"]}
    # the host half of a round (fill, promote, adapt) ends before the
    # round opens: it may run rounds ahead, behind the device's work
    for name in ("stream_fill", "stream_promote", "stream_adapt"):
        for e in by[name]:
            rnd = rounds[e["args"]["round"]]
            assert e["ts"] + e["dur"] <= rnd["ts"] + 0.2
    # the device half lies inside it, in order
    for r, rnd in enumerate(rounds):
        up = by["upload_corpus"][r]
        inside = [e for n in ("device_steps", "readback_harvest")
                  for e in by[n]
                  if rnd["ts"] <= e["ts"] <= rnd["ts"] + rnd["dur"]]
        assert len(inside) == 2 * rnd["args"]["groups"]
        assert rnd["ts"] <= up["ts"] + 0.2
        assert up["ts"] + up["dur"] <= min(e["ts"] for e in inside) + 0.2
    # the steps' own counts, as the batch fit reports them
    assert 0.9 < tm["packed_mask_density"] <= 1.0
    assert 0 < tm["scatter_distinct_share"] <= 1.0
    # the gauges are written once a round (and once at the end)
    assert model.training_metrics["pipeline"] == "stream"


def test_the_gauges_are_updated_once_a_round(stream, monkeypatch, tmp_path):
    from glint_word2vec_tpu.streaming.trainer import StreamTrainer

    path, sizes = stream
    calls = []
    orig = StreamTrainer._update_stream_gauges
    monkeypatch.setattr(
        StreamTrainer, "_update_stream_gauges",
        lambda self, obs_run, fill: (calls.append((self.rounds, fill)),
                                     orig(self, obs_run, fill))[1])
    model = estimator().fit_stream(
        iter_text_file(path), publish_dir=str(tmp_path / "gens"),
        publish_words=1, **stream_kw(sizes, 512))
    rounds = model.training_metrics["rounds"]
    assert model.training_metrics["generations_published"] >= rounds
    # one call a round, each after its publish, and the closing one
    assert [r for r, _ in calls] == list(range(1, rounds + 1)) + [rounds]


def test_span_registry_check_is_clean():
    from glint_word2vec_tpu.analysis import core

    findings, _ = core.run_analysis(ROOT, rules=["span-registry"])
    assert findings == []


def test_a_stream_built_engine_lowers_the_parent_s_packed_step():
    """sha256[:16] of the packed scan's StableHLO text at the test's sizes,
    taken on PR 50's parent (74a10e4) with the engine the trainer builds
    there: vocabulary and spare rows as here. The stream cell and the batch
    cell compile one step; what this PR adds lies outside it."""
    from glint_word2vec_tpu.streaming.trainer import StreamTrainer

    class Sv:
        base_size = V

        @staticmethod
        def noise_counts():
            return np.ones(V, np.int64)

    trainer = StreamTrainer(
        Word2Vec(vector_size=D, num_negatives=NEG, seed=1),
        extra_rows=EXTRA)
    trainer.vocab = Sv
    eng = trainer._make_engine(make_mesh(1, 1))
    assert (eng.vocab_size, eng.num_rows) == (V, V + EXTRA)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(eng.mesh, P()))

    pairs = packed_pair_batch(BATCH, WINDOW, 1)
    span = -(-3 * pairs // context_width(WINDOW))
    table = jax.ShapeDtypeStruct(
        (eng.padded_vocab, eng.padded_dim), jnp.float32,
        sharding=eng._table_sharding())
    offs = sds((512 + 2,), jnp.int32)
    i32, u32, f32 = (sds((), t) for t in (jnp.int32, jnp.uint32, jnp.float32))
    low = eng._make_packed_corpus_scan(
        pairs, WINDOW, BATCH, span, K, 0).lower(
            table, table, sds((-(-V // 64), 128), jnp.int32),
            sds((BUFFER,), jnp.int32), sds((BUFFER,), jnp.int32), offs, offs,
            i32, i32, sds((2,), jnp.uint32), u32, u32, f32, f32, f32)
    assert hashlib.sha256(low.as_text().encode()).hexdigest()[:16] == (
        "19e4157091a815be")


def test_set_up_names_native_when_the_alias_builder_is_absent(monkeypatch):
    import glint_word2vec_tpu.native as native

    assert train_stream.require_native_alias(2_000_000) is True
    monkeypatch.setattr(native, "get_lib", lambda: None)
    assert train_stream.require_native_alias(100_000) is False
    with pytest.raises(RuntimeError, match="native/"):
        train_stream.require_native_alias(100_001)
