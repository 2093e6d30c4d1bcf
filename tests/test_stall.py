"""Stall-free fit loop (ISSUE 5): async checkpointing, deferred scalar
readbacks, and prefetch overlap.

Contracts pinned here:
  * COMMIT PROTOCOL — an async save commits via temp-dir + atomic
    rename, and the ``train_state.json`` manifest flips only after; a
    writer killed between temp-write and rename leaves the previous
    committed checkpoint authoritative and the manifest never
    references a partial file.
  * RESUME PARITY — a packed mid-epoch checkpoint written by an async
    save resumes to bitwise-identical tables vs one written by a
    blocking save (GLINT_SYNC_CKPT=1).
  * DEFERRED-READBACK PARITY — the deferred packed schedule (harvest
    group g while g+1 runs, device-carried position, phantom-tail key
    rollback) produces bitwise-identical tables to the synchronous
    schedule (GLINT_SYNC_READBACK=1), including across epochs.
  * ONE-GROUP LAG — the deferred schedule's metric/canary view lags the
    device by exactly one dispatch group (the harvest span for group g
    is recorded after group g+1's dispatch span).
  * PREFETCH — group assembly and next-epoch compaction overlap without
    changing any trained value; ``BatchGroup`` stacking equals the
    inline stacking it replaced.
  * TELEMETRY — heartbeat + Prometheus expose device_stall_seconds,
    pending_async_saves, checkpoint_write_seconds,
    last_checkpoint_age_seconds; serving snapshots carry the
    checkpoint section; everything lints.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from glint_word2vec_tpu import Word2Vec
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
from glint_word2vec_tpu.parallel.mesh import make_mesh

CORPUS = [
    "the quick brown fox jumps over the lazy dog".split(),
    "the dog sleeps all day long in the sun".split(),
    "a quick fox and a lazy dog meet in the field".split(),
    "the sun rises over the field every day".split(),
] * 30


def _w2v(**kw):
    defaults = dict(
        vector_size=12, batch_size=32, min_count=1, num_iterations=2,
        seed=7, steps_per_call=4, window=3,
    )
    defaults.update(kw)
    return Word2Vec(**defaults)


def _tables(model):
    return (
        np.asarray(model.engine.syn0, np.float32),
        np.asarray(model.engine.syn1, np.float32),
    )


def _small_engine(seed=0, mesh=None):
    counts = np.arange(1, 101, dtype=np.int64)[::-1].copy()
    return EmbeddingEngine(
        mesh or make_mesh(1, 1), 100, 16, counts, seed=seed
    )


# ---------------------- async save / commit protocol --------------------


def test_async_save_equals_sync_save(tmp_path):
    eng = _small_engine()
    sync_dir, async_dir = str(tmp_path / "s"), str(tmp_path / "a")
    eng.save(sync_dir)
    assert eng.save_async(async_dir) is True
    eng.wait_pending_saves()
    other = _small_engine(seed=9)
    other.load_tables(async_dir)
    np.testing.assert_array_equal(
        np.asarray(eng.syn0, np.float32), np.asarray(other.syn0, np.float32)
    )
    # Identical manifests + shard files from both paths.
    ms = json.load(open(os.path.join(sync_dir, "engine.json")))
    ma = json.load(open(os.path.join(async_dir, "engine.json")))
    assert ms == ma
    assert sorted(os.listdir(sync_dir)) == sorted(os.listdir(async_dir))


def test_sync_ckpt_env_forces_blocking(tmp_path, monkeypatch):
    monkeypatch.setenv("GLINT_SYNC_CKPT", "1")
    eng = _small_engine()
    committed = []
    assert (
        eng.save_async(str(tmp_path / "ck"), on_commit=lambda: committed.append(1))
        is False
    )
    # Blocking path: committed before the call returned, nothing pending.
    assert committed == [1]
    stats = eng.checkpoint_stats()
    assert stats["pending_async_saves"] == 0
    assert stats["forced_sync_saves"] == 1


def test_crash_between_temp_write_and_rename(tmp_path, monkeypatch):
    # Kill the writer at the commit point: temp dir fully written, rename
    # never runs. The previous committed checkpoint must stay
    # authoritative and the manifest must never reference a partial file.
    ckdir = tmp_path / "ckpts"
    ckdir.mkdir()
    state_path = str(ckdir / "train_state.json")
    eng = _small_engine()

    def flip(ck_name):
        from glint_word2vec_tpu.models.word2vec import (
            _flip_checkpoint_state,
        )

        _flip_checkpoint_state(
            str(ckdir), state_path, ck_name,
            epochs_completed=1, step=10, words_done=100,
        )

    eng.save(str(ckdir / "ckpt-1"))
    flip("ckpt-1")
    before = np.asarray(eng.syn0, np.float32).copy()

    orig_commit = EmbeddingEngine._commit_snapshot_dir
    monkeypatch.setattr(
        EmbeddingEngine, "_commit_snapshot_dir",
        staticmethod(lambda tmp, path: (_ for _ in ()).throw(
            RuntimeError("simulated SIGKILL between write and rename")
        )),
    )
    eng.save_async(str(ckdir / "ckpt-2"), on_commit=lambda: flip("ckpt-2"))
    with pytest.raises(RuntimeError, match="checkpoint write failed"):
        eng.wait_pending_saves()
    monkeypatch.setattr(
        EmbeddingEngine, "_commit_snapshot_dir", staticmethod(orig_commit)
    )

    # The manifest still points at the committed checkpoint; the aborted
    # snapshot exists only as an unreferenced temp dir.
    state = json.load(open(state_path))
    assert state["ckpt"] == "ckpt-1"
    assert not os.path.exists(ckdir / "ckpt-2")
    leftovers = [e for e in os.listdir(ckdir) if ".tmp-" in e]
    assert leftovers, "temp dir should exist (write finished, commit did not)"
    # A restore through the manifest loads the good checkpoint.
    other = _small_engine(seed=3)
    other.load_tables(os.path.join(str(ckdir), state["ckpt"]))
    np.testing.assert_array_equal(
        before, np.asarray(other.syn0, np.float32)
    )
    # The next state flip prunes the orphaned temp dir.
    eng.save(str(ckdir / "ckpt-3"))
    flip("ckpt-3")
    assert not [e for e in os.listdir(ckdir) if ".tmp-" in e]


def test_second_async_save_blocks_and_is_counted(tmp_path, monkeypatch):
    eng = _small_engine()
    release = threading.Event()
    orig = EmbeddingEngine._write_snapshot

    def slow_write(self, path, files, meta, **kw):
        release.wait(timeout=30)
        return orig(self, path, files, meta, **kw)

    monkeypatch.setattr(EmbeddingEngine, "_write_snapshot", slow_write)
    eng.save_async(str(tmp_path / "ck-1"))
    assert eng.checkpoint_stats()["pending_async_saves"] == 1

    t0 = time.time()
    threading.Timer(0.3, release.set).start()
    eng.save_async(str(tmp_path / "ck-2"))  # must block for ck-1
    assert time.time() - t0 >= 0.25
    eng.wait_pending_saves()
    stats = eng.checkpoint_stats()
    assert stats["async_save_waits"] == 1
    assert stats["pending_async_saves"] == 0
    assert os.path.exists(tmp_path / "ck-1" / "engine.json")
    assert os.path.exists(tmp_path / "ck-2" / "engine.json")


def test_async_save_snapshot_is_immune_to_later_training(tmp_path):
    # The snapshot point is the save_async CALL: train steps dispatched
    # after it (which donate the live tables) must not leak into the
    # written checkpoint.
    eng = _small_engine()
    expect0 = np.asarray(eng.syn0, np.float32).copy()
    expect1 = np.asarray(eng.syn1, np.float32).copy()
    eng.save_async(str(tmp_path / "ck"))
    import jax

    eng.train_step(
        np.zeros(8, np.int32) + 3, np.ones((8, 3), np.int32),
        np.ones((8, 3), np.float32), jax.random.PRNGKey(0), 0.5,
    )
    eng.wait_pending_saves()
    other = _small_engine(seed=5)
    other.load_tables(str(tmp_path / "ck"))
    np.testing.assert_array_equal(
        expect0, np.asarray(other.syn0, np.float32)
    )
    np.testing.assert_array_equal(
        expect1, np.asarray(other.syn1, np.float32)
    )
    # The step really trained (syn1 gets first-step updates; syn0's
    # center gradient is zero while syn1 is still all-zero).
    assert not np.array_equal(expect1, np.asarray(eng.syn1, np.float32))


# ---------------------- fit-loop parity ---------------------------------


def test_packed_deferred_readback_bitwise_parity(monkeypatch):
    # The tentpole acceptance gate: deferred-readback epochs produce
    # bitwise-identical tables to the synchronous loop.
    m_def = _w2v(batch_packing="dense").fit(CORPUS)
    monkeypatch.setenv("GLINT_SYNC_READBACK", "1")
    m_sync = _w2v(batch_packing="dense").fit(CORPUS)
    monkeypatch.delenv("GLINT_SYNC_READBACK")
    for a, b in zip(_tables(m_def), _tables(m_sync)):
        np.testing.assert_array_equal(a, b)
    # Identical step/words accounting too (phantom groups roll out).
    assert (
        m_def.training_metrics["steps"] == m_sync.training_metrics["steps"]
    )
    assert (
        m_def.training_metrics["words_done"]
        == m_sync.training_metrics["words_done"]
    )
    assert (
        m_def.training_metrics["packed_pairs"]
        == m_sync.training_metrics["packed_pairs"]
    )
    # The scan stops at the corpus end (ISSUE 44): under either schedule
    # the device ran the live steps and no other, so what was dispatched
    # and not run is the tail's count, a phantom group an epoch longer
    # under the deferred schedule.
    tails = []
    for tm in (m_def.training_metrics, m_sync.training_metrics):
        assert tm["steps_run"] == tm["steps"]
        assert tm["steps_dispatched"] % 4 == 0
        tails.append(tm["steps_dispatched"] - tm["steps_run"])
    assert 0 <= tails[1] < 2 * 4 and tails[0] == tails[1] + 2 * 4


@pytest.mark.parametrize("subsample_ratio", [0.0, 0.01])
def test_packed_deferred_parity_with_subsampling(monkeypatch,
                                                 subsample_ratio):
    m_def = _w2v(
        batch_packing="dense", subsample_ratio=subsample_ratio,
        num_iterations=3,
    ).fit(CORPUS)
    monkeypatch.setenv("GLINT_SYNC_READBACK", "1")
    monkeypatch.setenv("GLINT_NO_COMPACT_PREFETCH", "1")
    m_sync = _w2v(
        batch_packing="dense", subsample_ratio=subsample_ratio,
        num_iterations=3,
    ).fit(CORPUS)
    for a, b in zip(_tables(m_def), _tables(m_sync)):
        np.testing.assert_array_equal(a, b)


def test_grid_subsampled_prefetch_parity(monkeypatch):
    # The grid corpus loop with subsampling adopts the prefetched
    # compaction; disabling the prefetch must change nothing.
    m_pre = _w2v(subsample_ratio=0.01, num_iterations=3).fit(CORPUS)
    monkeypatch.setenv("GLINT_NO_COMPACT_PREFETCH", "1")
    m_ser = _w2v(subsample_ratio=0.01, num_iterations=3).fit(CORPUS)
    for a, b in zip(_tables(m_pre), _tables(m_ser)):
        np.testing.assert_array_equal(a, b)


def test_async_vs_sync_ckpt_resume_parity_packed_mid_epoch(tmp_path,
                                                           monkeypatch):
    # Satellite gate: bitwise resume parity async vs sync save on the
    # packed mid-epoch state (the preemption drill writes a checkpoint
    # carrying the consumed-position counter through both save paths).
    def drill(ck, sync_ckpt):
        os.makedirs(ck, exist_ok=True)
        if sync_ckpt:
            monkeypatch.setenv("GLINT_SYNC_CKPT", "1")
        monkeypatch.setenv("GLINT_PACKED_STOP_AFTER_GROUPS", "3")
        _w2v(batch_packing="dense").fit(CORPUS, checkpoint_dir=ck)
        monkeypatch.delenv("GLINT_PACKED_STOP_AFTER_GROUPS")
        if sync_ckpt:
            monkeypatch.delenv("GLINT_SYNC_CKPT")
        state = json.load(open(os.path.join(ck, "train_state.json")))
        assert state["position"] > 0, state
        return _w2v(batch_packing="dense").fit(CORPUS, checkpoint_dir=ck)

    m_async = drill(str(tmp_path / "a"), sync_ckpt=False)
    m_sync = drill(str(tmp_path / "s"), sync_ckpt=True)
    for a, b in zip(_tables(m_async), _tables(m_sync)):
        np.testing.assert_array_equal(a, b)


def test_host_batcher_deferred_records_match_totals(monkeypatch):
    # The host path's one-group-deferred loss sync is records-only: the
    # dispatch schedule (and so the tables) cannot change, but the
    # drained totals must still account every live batch.
    monkeypatch.setenv("GLINT_HOST_BATCHER", "1")
    model = _w2v().fit(CORPUS)
    tm = model.training_metrics
    assert tm["pipeline"] == "host"
    assert tm["steps"] > 0
    assert tm["words_done"] == 2 * sum(len(s) for s in CORPUS)
    assert "device_stall_seconds" in tm
    model.stop()


def test_deferred_harvest_lags_exactly_one_group(tmp_path):
    # Pin the one-group lag: under the deferred packed schedule, group
    # g's readback_harvest is recorded AFTER group g+1's device_steps
    # dispatch span (the canary/metrics therefore run one group behind,
    # which the canary window tolerates by design).
    from glint_word2vec_tpu.obs import ObsConfig

    log = str(tmp_path / "events.jsonl")
    model = _w2v(
        batch_packing="dense", num_iterations=1,
        obs=ObsConfig(event_log=log),
    ).fit(CORPUS)
    events = [json.loads(line) for line in open(log) if line.strip()]
    dispatches = [
        e for e in events
        if e["name"] == "device_steps" and e.get("args", {}).get("packed")
    ]
    harvests = [e for e in events if e["name"] == "readback_harvest"]
    assert len(dispatches) >= 2
    # Every dispatched group is harvested exactly once.
    assert len(harvests) == len(dispatches)
    ordered = [
        e for e in events
        if e["name"] == "readback_harvest"
        or (e["name"] == "device_steps" and e.get("args", {}).get("packed"))
    ]
    d_pos = [i for i, e in enumerate(ordered)
             if e["name"] == "device_steps"]
    h_pos = [i for i, e in enumerate(ordered)
             if e["name"] == "readback_harvest"]
    # Harvest of group g lands AFTER the dispatch of group g+1 (the
    # one-group lag) but BEFORE the dispatch of group g+2 (exactly one,
    # not more). The final group is drained after its own dispatch.
    for g in range(len(h_pos) - 1):
        assert h_pos[g] > d_pos[g + 1], (g, d_pos, h_pos)
        if g + 2 < len(d_pos):
            assert h_pos[g] < d_pos[g + 2], (g, d_pos, h_pos)
    assert h_pos[-1] > d_pos[-1]
    model.stop()


# ---------------------- prefetch / group assembly -----------------------


def test_group_batches_matches_inline_stacking():
    from glint_word2vec_tpu.corpus.batching import (
        Batch,
        group_batches,
    )

    rng = np.random.default_rng(0)
    batches = [
        Batch(
            centers=rng.integers(0, 50, 8).astype(np.int32),
            contexts=rng.integers(0, 50, (8, 3)).astype(np.int32),
            mask=(rng.random((8, 3)) < 0.5).astype(np.float32),
            words_done=10 * (i + 1),
        )
        for i in range(7)
    ]
    groups = list(group_batches(iter(batches), 3))
    assert [g.n_real for g in groups] == [3, 3, 1]
    assert [len(g) for g in groups] == [3, 3, 3]
    np.testing.assert_array_equal(
        groups[0].centers, np.stack([b.centers for b in batches[:3]])
    )
    # Tail group: one live batch + zero-mask pad carrying the last live
    # words_done.
    tail = groups[2]
    np.testing.assert_array_equal(tail.centers[0], batches[6].centers)
    assert not tail.mask[1:].any()
    assert tail.words_done == [70, 70, 70]


def test_prefetch_compact_adoption_bitwise(tmp_path):
    import jax

    eng = _small_engine()
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 100, 4000).astype(np.int32)
    offsets = np.arange(0, 4001, 20, dtype=np.int64)
    eng.upload_corpus(ids, offsets)
    eng.set_keep_probs(np.full(100, 0.6, np.float32))
    key = jax.random.fold_in(jax.random.PRNGKey(3), 2)
    n_direct = eng.compact_corpus(key)
    direct = (
        np.asarray(eng._corpus_compacted[0]),
        np.asarray(eng._corpus_compacted[1]),
    )
    eng.prefetch_compact_corpus(key)
    assert eng._compact_prefetch is not None
    assert eng.compact_corpus(key) == n_direct
    assert eng._compact_prefetch is None  # consumed
    np.testing.assert_array_equal(
        direct[0], np.asarray(eng._corpus_compacted[0])
    )
    np.testing.assert_array_equal(
        direct[1], np.asarray(eng._corpus_compacted[1])
    )
    # Key mismatch: the stale prefetch is discarded, not adopted.
    eng.prefetch_compact_corpus(key)
    eng.compact_corpus(jax.random.fold_in(jax.random.PRNGKey(3), 5))
    assert eng._compact_prefetch is None


# ---------------------- crash-safe model saves --------------------------


def test_atomic_write_npy_round_trip_and_crash(tmp_path, monkeypatch):
    from glint_word2vec_tpu.utils import atomic_write_npy

    path = str(tmp_path / "v.npy")
    a = np.arange(12, dtype=np.float32).reshape(3, 4)
    atomic_write_npy(path, a)
    np.testing.assert_array_equal(np.load(path), a)
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]

    # Crash between temp write and rename: the original file survives.
    orig_replace = os.replace
    monkeypatch.setattr(
        os, "replace",
        lambda *args: (_ for _ in ()).throw(OSError("killed")),
    )
    with pytest.raises(OSError):
        atomic_write_npy(path, a * 2)
    monkeypatch.setattr(os, "replace", orig_replace)
    np.testing.assert_array_equal(np.load(path), a)


def test_local_model_save_is_crash_safe(tmp_path, monkeypatch):
    from glint_word2vec_tpu.models.word2vec import LocalWord2VecModel

    m = LocalWord2VecModel(
        ["a", "b"], np.ones((2, 4), np.float32)
    )
    out = str(tmp_path / "local")
    m.save(out)
    loaded = LocalWord2VecModel.load(out)
    assert loaded.words == ["a", "b"]
    # Overwrite-in-place with a crash mid-vectors-write: the previous
    # complete files survive.
    import glint_word2vec_tpu.utils as utils_mod

    monkeypatch.setattr(
        utils_mod._os, "replace",
        lambda *a: (_ for _ in ()).throw(OSError("killed")),
    )
    m2 = LocalWord2VecModel(["a", "b"], np.zeros((2, 4), np.float32))
    with pytest.raises(OSError):
        m2.save(out)
    monkeypatch.undo()
    again = LocalWord2VecModel.load(out)
    np.testing.assert_array_equal(again.vectors, loaded.vectors)


# ---------------------- telemetry ---------------------------------------


def test_heartbeat_and_prometheus_checkpoint_telemetry(tmp_path):
    from glint_word2vec_tpu.obs.heartbeat import TrainingStatus
    from glint_word2vec_tpu.obs.prometheus import (
        lint_prometheus_text,
        training_to_prometheus,
    )
    from glint_word2vec_tpu.utils.metrics import TrainingMetrics

    eng = _small_engine()
    eng.save_async(str(tmp_path / "ck"))
    eng.wait_pending_saves()
    metrics = TrainingMetrics()
    metrics.record_stall(0.25)
    status = TrainingStatus(pipeline="device_corpus", metrics=metrics,
                            engine=eng)
    snap = status.snapshot(include_devices=False)
    assert snap["device_stall_seconds"] == 0.25
    assert snap["pending_async_saves"] == 0
    assert snap["checkpoint_write_seconds"] is not None
    assert snap["last_checkpoint_age_seconds"] is not None
    text = training_to_prometheus(snap)
    lint_prometheus_text(text)
    for name in (
        "glint_training_device_stall_seconds",
        "glint_training_pending_async_saves",
        "glint_training_checkpoint_write_seconds",
        "glint_training_last_checkpoint_age_seconds",
        "glint_training_async_save_waits_total",
    ):
        assert name in text, name


def test_serving_snapshot_checkpoint_section():
    from glint_word2vec_tpu.obs.prometheus import (
        lint_prometheus_text,
        serving_to_prometheus,
    )
    from glint_word2vec_tpu.utils.metrics import ServingMetrics

    sm = ServingMetrics()
    sm.observe("/synonyms", 0.002)
    # Loaded-model serving: no checkpoint stats -> present, None-valued.
    snap = sm.snapshot(total_compiles=3)
    assert snap["checkpoint"]["pending_async_saves"] == 0
    assert snap["checkpoint"]["last_checkpoint_age_seconds"] is None
    # Engine stats flow through verbatim.
    snap = sm.snapshot(
        total_compiles=3,
        checkpoint={
            "pending_async_saves": 1,
            "last_checkpoint_age_seconds": 4.5,
            "checkpoint_write_seconds": 0.8,
        },
    )
    assert snap["checkpoint"]["pending_async_saves"] == 1
    text = serving_to_prometheus(snap)
    lint_prometheus_text(text)
    assert "glint_serving_pending_async_saves 1" in text
    assert "glint_serving_last_checkpoint_age_seconds 4.5" in text


def test_fit_reports_stall_and_checkpoints_async(tiny_corpus, tmp_path):
    # End-to-end: a checkpointed device-corpus fit under the default
    # async regime completes, commits every epoch checkpoint, reports
    # the stall proxy, and the final heartbeat snapshot carries the
    # checkpoint telemetry.
    from glint_word2vec_tpu.obs import ObsConfig

    ck = str(tmp_path / "ck")
    status_file = str(tmp_path / "status.json")
    model = Word2Vec(
        mesh=make_mesh(1, 2), vector_size=16, min_count=5, batch_size=128,
        seed=3, num_iterations=2,
        obs=ObsConfig(status_file=status_file, status_interval=0.0),
    ).fit(tiny_corpus[:1200], checkpoint_dir=ck)
    assert model.training_metrics["pipeline"] == "device_corpus"
    assert "device_stall_seconds" in model.training_metrics
    state = json.load(open(os.path.join(ck, "train_state.json")))
    assert state["epochs_completed"] == 2
    assert os.path.isdir(os.path.join(ck, state["ckpt"]))
    assert not [e for e in os.listdir(ck) if ".tmp-" in e]
    status = json.loads(open(status_file).read())
    assert status["state"] == "done"
    assert status["pending_async_saves"] == 0
    assert status["checkpoint_write_seconds"] is not None
    assert status["device_stall_seconds"] >= 0
    model.stop()
