"""The fit driver's spans around a packed dispatch group (ISSUE 36): the
harvest parted into the host blocked on the device, the read-back and the
host's own accounting; an epoch on every dispatch.

Contracts pinned here:
  * the three children lie inside their ``readback_harvest``, in order,
    once a harvested group, and fill it;
  * ``readback_harvest`` says the steps the device ran (``ran``, ISSUE
    44) beside the live ones (``n``), and they are the same;
  * ``run_end`` follows the last harvest and closes the ring (the
    benchmark's ``fit.tail_ms`` starts at the one and passes the other);
  * the children are no step-time ledger phases: ``readback_harvest``
    still charges the whole;
  * the split changes nothing the fit computes: tables and per-step
    losses are bit-equal with the spans on, off, and under the
    synchronous schedule;
  * with ``obs=None`` the fit installs no recorder and opens no
    ``glint.*`` annotation;
  * the names PR 36 removed are no longer recorded.
"""

import json
import statistics

import jax
import numpy as np
import pytest

from glint_word2vec_tpu import Word2Vec
from glint_word2vec_tpu.obs import _LEDGER_PHASE_OF, ObsConfig
from glint_word2vec_tpu.obs import events as obs_events
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
from glint_word2vec_tpu.utils.metrics import LEDGER_PHASES

CORPUS = [
    "the quick brown fox jumps over the lazy dog".split(),
    "the dog sleeps all day long in the sun".split(),
    "a quick fox and a lazy dog meet in the field".split(),
    "the sun rises over the field every day".split(),
] * 30
CHILDREN = ("harvest_wait", "harvest_convert", "harvest_account")
EPOCHS = 2


def _fit(obs=None, **kw):
    params = dict(
        vector_size=12, batch_size=32, min_count=1, num_iterations=EPOCHS,
        seed=7, steps_per_call=4, window=3, subsample_ratio=1e-2, obs=obs,
    )
    params.update(kw)
    return Word2Vec(**params).fit(CORPUS)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One packed fit with the spans on: (ring events, training_metrics)."""
    path = str(tmp_path_factory.mktemp("spans") / "trace.json")
    model = _fit(ObsConfig(chrome_trace=path))
    metrics = model.training_metrics
    model.stop()
    with open(path) as f:
        return json.load(f)["traceEvents"], metrics


def _spans(events, name):
    return sorted(((e["ts"], e["ts"] + e["dur"], e.get("args", {}))
                   for e in events if e["name"] == name and e["ph"] == "X"),
                  key=lambda s: s[:2])


def test_children_lie_inside_their_harvest_in_order_once_a_group(traced):
    events, _ = traced
    harvests = _spans(events, "readback_harvest")
    assert len(harvests) == len(_spans(events, "device_steps")) >= 2 * EPOCHS
    kids = {name: _spans(events, name) for name in CHILDREN}
    assert {len(v) for v in kids.values()} == {len(harvests)}
    rests = []
    for i, (start, end, args) in enumerate(harvests):
        wait, convert, account = (kids[name][i] for name in CHILDREN)
        # ts and dur are rounded to 0.1 us each
        assert start - 0.2 <= wait[0] and account[1] <= end + 0.2
        assert wait[1] <= convert[0] + 0.2 and convert[1] <= account[0] + 0.2
        assert account[2]["n"] == args["n"]
        rests.append((end - start) - sum(
            k[1] - k[0] for k in (wait, convert, account)))
    # what the parent keeps for itself: the spans' own bookkeeping and the
    # count of live steps, well under a millisecond a group
    assert min(rests) >= -1.0 and statistics.median(rests) < 1000.0


def test_every_harvest_says_the_steps_the_device_ran(traced):
    # ISSUE 44: ``ran`` beside ``n``, counted from what the device wrote.
    # The scan stops at the corpus end, so the device runs a group's live
    # steps and no other; the fit's totals are the spans' sums.
    events, metrics = traced
    args = [a for _, _, a in _spans(events, "readback_harvest")]
    assert all(0 <= a["n"] == a["ran"] <= 4 for a in args)
    assert {a["n"] for a in args} >= {0, 4}  # phantom groups, full groups
    assert metrics["steps_run"] == sum(a["ran"] for a in args)
    assert metrics["steps_dispatched"] == 4 * len(args)


def test_run_end_follows_the_last_harvest_and_closes_the_ring(traced):
    events, _ = traced
    (run_end,) = [e["ts"] for e in events if e["name"] == "run_end"]
    assert run_end + 0.2 >= max(
        e for _, e, _ in _spans(events, "readback_harvest"))
    assert run_end + 0.2 >= max(e["ts"] + e.get("dur", 0.0) for e in events)


def test_every_packed_dispatch_says_its_epoch(traced):
    events, _ = traced
    epochs = [a["epoch"] for _, _, a in _spans(events, "device_steps")]
    assert epochs == sorted(epochs)
    assert set(epochs) == set(range(EPOCHS))


def test_the_children_charge_no_ledger_phase(traced):
    events, metrics = traced
    assert not set(CHILDREN) & set(_LEDGER_PHASE_OF)
    assert set(metrics["steptime"]) == set(LEDGER_PHASES)
    # readback_harvest charges its spans' own seconds, once: a child that
    # charged too would double it (rounded to a millisecond a phase)
    spans_s = sum(e - s for s, e, _ in _spans(events, "readback_harvest"))
    assert metrics["steptime"]["readback_harvest"] == pytest.approx(
        spans_s / 1e6, abs=0.01 + 0.05 * spans_s / 1e6)


def _tables_and_losses(monkeypatch, obs=None, env=()):
    """The fitted tables and every dispatch group's per-step losses."""
    for k, v in env:
        monkeypatch.setenv(k, v)
    losses = []
    real = EmbeddingEngine.train_steps_corpus_packed

    def keep(self, *a, **k):
        out = real(self, *a, **k)
        losses.append(out[0])
        return out

    monkeypatch.setattr(EmbeddingEngine, "train_steps_corpus_packed", keep)
    model = _fit(obs)
    got = (np.asarray(model.engine.syn0), np.asarray(model.engine.syn1),
           np.concatenate([np.asarray(x) for x in losses]))
    model.stop()
    monkeypatch.undo()
    return got


@pytest.fixture(scope="module")
def plain():
    """The same fit with ``obs=None`` under the deferred schedule."""
    with pytest.MonkeyPatch.context() as mp:
        return _tables_and_losses(mp)


@pytest.mark.parametrize("spans,sync", [(True, False), (False, True),
                                        (True, True)])
def test_tables_and_losses_are_bit_equal(plain, tmp_path, monkeypatch,
                                         spans, sync):
    obs = ObsConfig(chrome_trace=str(tmp_path / "t.json")) if spans else None
    env = (("GLINT_SYNC_READBACK", "1"),) if sync else ()
    got = _tables_and_losses(monkeypatch, obs, env)
    # the deferred schedule may dispatch one phantom group past an
    # epoch's end, all no-op steps of loss 0: compare the live steps
    live = lambda x: x[x != 0]  # noqa: E731
    assert live(plain[2]).size >= 8 * EPOCHS
    for a, b in zip(got[:2] + (live(got[2]),), plain[:2] + (live(plain[2]),)):
        np.testing.assert_array_equal(a, b)


class _Annotations:
    """Stands where ``jax.profiler.TraceAnnotation`` does and keeps the
    names and stats it was opened with."""

    def __init__(self, monkeypatch):
        self.opened = []
        real, opened = jax.profiler.TraceAnnotation, self.opened

        class Recording(real):
            def __init__(self, name, **stats):
                opened.append((name, stats))
                super().__init__(name, **stats)

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)


def test_obs_none_installs_no_recorder_and_opens_no_annotation(monkeypatch):
    seen = _Annotations(monkeypatch)
    recorders = []
    real = EmbeddingEngine.train_steps_corpus_packed

    def spy(self, *a, **k):
        recorders.append(obs_events.get_recorder())
        return real(self, *a, **k)

    monkeypatch.setattr(EmbeddingEngine, "train_steps_corpus_packed", spy)
    _fit(None).stop()
    assert recorders and set(recorders) == {None}
    assert not [n for n, _ in seen.opened if n.startswith("glint.")]


def test_the_spans_are_annotations_on_the_ring_clock(monkeypatch, tmp_path):
    seen = _Annotations(monkeypatch)
    _fit(ObsConfig(chrome_trace=str(tmp_path / "t.json"))).stop()
    names = {n for n, _ in seen.opened}
    assert {"glint." + n for n in CHILDREN + (
        "readback_harvest", "device_steps")} <= names
    assert all("t0_us" in stats for n, stats in seen.opened
               if n.startswith("glint."))


def test_the_names_nothing_read_are_no_longer_recorded():
    rec = obs_events.EventRecorder()
    obs_events.set_recorder(rec)
    try:
        model = _fit(None, num_iterations=1)
        model.engine.warmup(k_buckets=(4,), q_buckets=(1,))
        model.engine.set_noise_counts(np.ones(model.vocab.size, np.int64))
        model.stop()
    finally:
        obs_events.set_recorder(None)
    names = {e["name"] for e in rec.events()}
    assert "table_mutation" in names  # the recorder was live
    assert not names & {"engine_warmup", "engine_warmup_ann",
                        "noise_counts_updated", "extra_rows_freed",
                        "compose_query_engine"}
