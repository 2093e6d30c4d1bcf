"""``benchmark/fit_trace.py`` and the eleven ``fit.*`` readers built on it
(PR 36): a synthetic xplane and rings with known answers, one tiny
rehearsal. Run by hand like its neighbours:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_fit_trace.py -q
"""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_benchmark import BENCH, bench, harness  # noqa: E402

TRAIN_CELLS = ["w2v-300-2m.train", "w2v-300-10m-x4.train",
               "ft-300-1m-2mb.train", "w2v-cbow-300-3m.train"]
ON_THE_DEVICE = ["fit.scan_gap_ms", "fit.gap_programs", "fit.launch_lead_ms",
                 "fit.idle_in_dispatch", "fit.idle_in_wait",
                 "fit.idle_in_harvest_host"]
IN_THE_RING = ["fit.dispatch_ms", "fit.harvest_host_ms", "fit.epoch_turn_ms",
               "fit.tail_ms", "fit.group_spread"]


def _reader(name):
    from benchmark.run import load_module

    return load_module(os.path.join(BENCH, "layers", name + ".py"))


def test_the_new_names_resolve_to_files_and_list_the_training_cells():
    specs = {m["name"]: m for m in bench()["per_layer"]}
    for name in ON_THE_DEVICE + IN_THE_RING:
        for cell in TRAIN_CELLS:
            assert cell in specs[name]["workloads"], (name, cell)
        assert specs[name]["layer"] == "fit driver"
        assert specs[name]["moves"] == "train_words_per_s"
        assert callable(_reader(name).read)
    for name in ON_THE_DEVICE:
        assert specs[name]["source"] == "device_trace"
    for name in IN_THE_RING:
        assert specs[name]["source"] == "program_span"


# Times in ms on the trace's clock; the ring's clock runs 1,000 ms ahead.
# Three runs of the scan, 400 ms each, 20 ms apart, one 0.3 ms program in
# each gap. The dispatch of scan B returned at 100, 300 ms before scan A
# ended; the dispatch of scan C returned at 825, 5 ms after scan B ended.
# Idle: [400, 405] (2 under the wait, 3 under the harvest's rest),
# [405.3, 420] (12.7 under the harvest's rest, 2 under nothing), [820, 825]
# (under the dispatch), [825.3, 840] (0.7 under nothing, 14 under the
# wait); the last op ends 60 ms before the 1,300 ms window does.
SCANS = [(0, 400), (420, 400), (840, 400)]
OTHERS = [(405, 0.3), (825, 0.3)]
ANNOTATIONS = [  # (name, start, duration)
    ("glint.device_steps", 50, 50),
    ("glint.readback_harvest", 100, 318),
    ("glint.harvest_wait", 100, 302),
    ("glint.device_steps", 422, 403),
    ("glint.readback_harvest", 826, 424),
    ("glint.harvest_wait", 826, 419),
]
WINDOW_MS = 1300.0


def _ps(ms):
    return int(round(ms * 1e9))


def _trace(modules=True, rename=None):
    names = {n for n, _, _ in ANNOTATIONS}
    ids = {n: i + 1 for i, n in enumerate(sorted(names))}
    ops = "".join(
        f"events {{ metadata_id: 1 offset_ps: {_ps(s)} "
        f"duration_ps: {_ps(d)} }}\n" for s, d in SCANS) + "".join(
        f"events {{ metadata_id: 2 offset_ps: {_ps(s)} "
        f"duration_ps: {_ps(d)} }}\n" for s, d in OTHERS)
    mods = "".join(
        f"events {{ metadata_id: 3 offset_ps: {_ps(s)} "
        f"duration_ps: {_ps(d)} }}\n" for s, d in SCANS) + "".join(
        f"events {{ metadata_id: 4 offset_ps: {_ps(s)} "
        f"duration_ps: {_ps(d)} }}\n" for s, d in OTHERS)
    host = "".join(
        f"events {{ metadata_id: {ids[n]} offset_ps: {_ps(s)} "
        f"duration_ps: {_ps(d)} stats {{ metadata_id: 1 "
        f"str_value: \"{(s + 1000.0) * 1e3:.1f}\" }} }}\n"
        for n, s, d in ANNOTATIONS)
    metas = "".join(
        f"event_metadata {{ key: {i} value {{ id: {i} "
        f"name: \"{(rename or {}).get(n, n)}\" }} }}\n"
        for n, i in ids.items())
    return f"""
planes {{ name: "/device:TPU:0"
  lines {{ name: "XLA Ops" timestamp_ns: 0
    {ops} }}
  lines {{ name: "{'XLA Modules' if modules else 'Steps'}" timestamp_ns: 0
    {mods} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%while.1" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%slice.2" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "jit_local_packed_scan(123)" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "jit__getitem(456)" }} }}
}}
planes {{ name: "/host:CPU"
  lines {{ name: "python3" timestamp_ns: 0
    {host} }}
  {metas}
  stat_metadata {{ key: 1 value {{ id: 1 name: "t0_us" }} }}
}}
"""


def _span(name, start_ms, dur_ms, **args):
    e = {"name": name, "ph": "X", "ts": start_ms * 1e3, "dur": dur_ms * 1e3}
    if args:
        e["args"] = args
    return e


#: the ring of the traced stretch: one dispatch before the window opened
TRACED_RING = [_span("device_steps", 970, 40, epoch=0),
               _span("device_steps", 1050, 50, epoch=0),
               _span("device_steps", 1422, 403, epoch=0)]


def _run(tmp_path, trace_text, events):
    from jax.profiler import ProfileData

    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(trace_text))
    said = []
    return types.SimpleNamespace(
        trace={"window_s": WINDOW_MS / 1e3}, trace_dir=str(tmp_path),
        trace_t=None, window=None, cfg={"run": {"steps_per_call": 32}},
        say=said.append, said=said, program_spans_path=None,
        program_spans=events)


def _ring_file(tmp_path, run, events, closes_ms):
    """The ring as the fit writes it, its ``ts`` 0 at perf_counter 500 s,
    in a window that closes ``closes_ms`` on the ring's clock."""
    path = tmp_path / "spans.json"
    path.write_text(json.dumps(
        {"traceEvents": events, "otherData": {"mono_t0": 500.0}}))
    run.program_spans_path, run.program_spans = str(path), None
    run.window = (500.0, 500.0 + closes_ms / 1e3)
    return run


def test_the_gap_between_two_scans_and_the_programs_in_it(tmp_path):
    from benchmark import fit_trace

    run = _run(tmp_path, _trace(), TRACED_RING)
    assert _reader("fit.scan_gap_ms").read(run) == pytest.approx(20.0)
    assert _reader("fit.gap_programs").read(run) == 1
    gaps = fit_trace.scan_gaps(run)
    assert [g["programs"] for g in gaps] == [
        [("jit__getitem", pytest.approx(0.3e-3))]] * 2
    assert [g["busy_s"] for g in gaps] == [pytest.approx(0.3e-3)] * 2
    assert [g["idle_s"] for g in gaps] == [pytest.approx(19.7e-3)] * 2
    said = "\n".join(run.said)
    assert "3 scan runs, 2 gaps of 20.000 20.000 ms" in said
    assert "in the gap, jit__getitem: 1 runs, 0.3000 ms a gap" in said


def test_a_dispatch_that_returned_early_and_one_that_returned_late(tmp_path):
    from benchmark import fit_trace

    run = _run(tmp_path, _trace(), TRACED_RING)
    leads, seen = fit_trace.launch_leads(run)
    # the span at 970 ms began before the window and is not among them
    assert seen == 2
    assert leads == [pytest.approx(0.300), pytest.approx(-0.005)]
    assert _reader("fit.launch_lead_ms").read(run) == pytest.approx(147.5)
    assert any("2 of 2 device_steps spans" in line for line in run.said)


def test_idle_is_filed_under_each_span_and_under_none(tmp_path):
    from benchmark import fit_trace

    run = _run(tmp_path, _trace(), TRACED_RING)
    share = lambda ms: pytest.approx(100.0 * ms / WINDOW_MS)  # noqa: E731
    assert _reader("fit.idle_in_dispatch").read(run) == share(5.0)
    assert _reader("fit.idle_in_wait").read(run) == share(2.0 + 14.0)
    assert _reader("fit.idle_in_harvest_host").read(run) == share(3.0 + 12.7)
    parts = fit_trace.idle_parts(run)
    assert parts["under_neither"] == share(2.0 + 0.7)
    assert parts["at_the_edges"] == share(60.0)
    # the five add up to 1 - busy / window, what device.idle_share.train is
    busy = sum(d for _, d in SCANS + OTHERS)
    assert sum(parts.values()) == pytest.approx(
        100.0 * (1.0 - busy / WINDOW_MS))
    assert any("together" in line for line in run.said)


def _three_epochs():
    """Three epochs of six dispatch groups 100 ms apart (one of the
    second epoch's 140; an epoch's first two distances, 4 and 196 ms, are
    the pipeline filling), each group harvested while the next runs: a
    harvest of 90 ms, 80 of them the wait. The first epoch starts 40 ms
    after its compaction pass did, the second 6 ms and the third 8 ms
    after the previous epoch's last harvest ended; ``run_end`` comes 12
    ms after the last harvest."""
    events, t = [_span("subsample_compact", 0, 30, epoch=0)], 40.0
    for epoch, distances in enumerate(
            [[4, 196, 100, 100, 100], [4, 196, 100, 140, 100],
             [4, 196, 100, 100, 100]]):
        starts = [t]
        for d in distances:
            starts.append(starts[-1] + d)
        for i, s in enumerate(starts):
            events.append(_span("device_steps", s, 690 if (epoch, i) == (1, 3)
                                else 2, epoch=epoch, packed=True))
            if i:
                events += [_span("readback_harvest", s + 3, 90, packed=True),
                           _span("harvest_wait", s + 3, 80)]
        last = starts[-1] + 94
        events += [_span("readback_harvest", last, 90, packed=True),
                   _span("harvest_wait", last, 80)]
        t = last + 90 + (6, 8, 0)[epoch]
    events.append({"name": "run_end", "ph": "i", "ts": (t + 12) * 1e3})
    return events


def _run_end_ms(events):
    (at,) = [e["ts"] / 1e3 for e in events if e["name"] == "run_end"]
    return at


def test_the_ring_alone_gives_the_turn_the_tail_and_the_spread(tmp_path):
    events = _three_epochs()
    # the window closes 7 ms after run_end, 19 after the last harvest
    run = _ring_file(tmp_path, _run(tmp_path, _trace(), None), events,
                     _run_end_ms(events) + 7)
    assert _reader("fit.dispatch_ms").read(run) == pytest.approx(2.0)
    assert _reader("fit.harvest_host_ms").read(run) == pytest.approx(10.0)
    assert _reader("fit.epoch_turn_ms").read(run) == pytest.approx(8.0)
    assert _reader("fit.tail_ms").read(run) == pytest.approx(19.0)
    assert _reader("fit.group_spread").read(run) == pytest.approx(40.0)
    said = "\n".join(run.said)
    for line in ("epoch 0 turned in 40.000 ms", "epoch 1 turned in 6.000 ms",
                 "epoch 2 turned in 8.000 ms",
                 "longest 140.000 ms (epoch 1, group 3), second longest "
                 "100.000 ms, 1 beyond 1.1 x the median",
                 "the window closes 19.000 ms after the last harvest, "
                 "7.000 ms after run_end"):
        assert line in said


def test_the_groups_the_profiler_stretched_are_left_out(tmp_path):
    events = _three_epochs()
    # the benchmark started its profiler inside the second epoch's fourth
    # dispatch and stopped it inside the third epoch's fourth: the 140 ms
    # group is the first's, and every distance left is 100 ms
    run = _ring_file(tmp_path, _run(tmp_path, _trace(), None), events,
                     _run_end_ms(events))
    starts = sorted(e["ts"] / 1e3 for e in events
                    if e["name"] == "device_steps")
    run.trace_t = [500.0 + (starts[9] + 1.0) / 1e3,
                   500.0 + (starts[15] + 1.0) / 1e3]
    assert _reader("fit.group_spread").read(run) == pytest.approx(0.0)
    # two of the nine are gone, and two lie between them: the second
    # epoch's last and the third's one before its profiler stop
    assert any("7 group distances" in line
               and "the 2 under the profiler 100.000 ms" in line
               for line in run.said)


def test_every_reader_reads_nothing_from_an_older_program_or_trace(tmp_path):
    # a CPU trace: no module line; a parent's ring and annotations: no
    # harvest_wait, no epoch on device_steps
    older = [
        {k: v for k, v in e.items() if k != "args"}
        for e in _three_epochs() if e["name"] != "harvest_wait"]
    run = _run(tmp_path, _trace(modules=False), older)
    for name in ("fit.scan_gap_ms", "fit.gap_programs",
                 "fit.launch_lead_ms", "fit.harvest_host_ms",
                 "fit.epoch_turn_ms", "fit.group_spread"):
        assert _reader(name).read(run) is None, name
    assert _reader("fit.dispatch_ms").read(run) == pytest.approx(2.0)
    # no window on the run and no mono_t0 in the ring: no tail
    assert _reader("fit.tail_ms").read(run) is None
    run = _ring_file(tmp_path, _run(tmp_path, _trace(modules=False), None),
                     older, _run_end_ms(_three_epochs()))
    assert _reader("fit.tail_ms").read(run) == pytest.approx(12.0)

    run = _run(tmp_path, _trace(
        rename={"glint.harvest_wait": "other.harvest_wait"}), TRACED_RING)
    assert _reader("fit.idle_in_wait").read(run) is None
    assert _reader("fit.idle_in_harvest_host").read(run) is None
    assert _reader("fit.idle_in_dispatch").read(run) == pytest.approx(
        100.0 * 5.0 / WINDOW_MS)

    bare = _run(tmp_path, _trace().replace("glint.", "other."), [])
    bare.trace = None  # an untraced run
    for name in ON_THE_DEVICE + IN_THE_RING:
        assert _reader(name).read(bare) is None, name


def test_traced_rehearsal_reads_the_ring_and_omits_the_device(tmp_path):
    doc, out = harness("w2v-300-2m.train", "--trace", "1")
    assert "fit trace: harvest, medians of" in out and ", account " in out
    got = {k for k in doc["metrics"] if k in ON_THE_DEVICE + IN_THE_RING}
    # a CPU trace has no module line and no device plane
    assert got == set(IN_THE_RING)
    assert doc["metrics"]["fit.harvest_host_ms"]["value"] > 0
    assert doc["metrics"]["fit.tail_ms"]["value"] >= 0
