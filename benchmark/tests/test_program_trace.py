"""``benchmark/program_trace.py`` and the per-layer readers built on it:
CPU, a synthetic xplane, tiny rehearsals. Run by hand like its neighbour:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_benchmark import BENCH, CELLS, bench, harness  # noqa: E402

NEW = {
    CELLS["train"]: ["step.index_ms", "step.gather_ms", "step.grads_ms",
                     "step.scatter_ms", "step.unscoped_share"],
    CELLS["synonyms"]: ["serve.round_ms", "serve.pull_ms", "serve.grace_ms",
                        "serve.idle_in_round", "serve.idle_no_round"],
}

# One run of the packed scan, [0, 100] us: a while that spans six scoped
# fusions (85 us) and 15 us of its own; then, outside the scan, two ops
# with idle gaps [100, 200] and [210, 260] us before them. The host holds
# a round [95, 230] us with its pull [120, 180] us inside: the first gap
# lies under the round, its middle part under the pull too; the round ends
# inside the second gap, whose rest lies under nothing.
TRACE = """
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 30000000 duration_ps: 10000000 }
    events { metadata_id: 4 offset_ps: 40000000 duration_ps: 10000000 }
    events { metadata_id: 5 offset_ps: 50000000 duration_ps: 10000000 }
    events { metadata_id: 6 offset_ps: 60000000 duration_ps: 30000000 }
    events { metadata_id: 7 offset_ps: 90000000 duration_ps: 5000000 }
    events { metadata_id: 8 offset_ps: 200000000 duration_ps: 10000000 }
    events { metadata_id: 8 offset_ps: 260000000 duration_ps: 10000000 }
  }
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 9 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 10 offset_ps: 200000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = (...)" stats { metadata_id: 1 str_value: "jit(local_packed_scan)/while" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2" stats { metadata_id: 1 str_value: "jit(local_packed_scan)/while/body/closed_call/glint.batch/jit(searchsorted)/gather" } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3" stats { metadata_id: 1 str_value: "jit(local_packed_scan)/while/body/closed_call/glint.sample/gather" } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4" stats { metadata_id: 1 str_value: "jit(local_packed_scan)/while/body/closed_call/glint.gather/gather" } } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.5" stats { metadata_id: 1 str_value: "jit(local_packed_scan)/while/body/closed_call/glint.grads/mul" } } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.6" stats { metadata_id: 1 ref_value: 2 } } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.7" stats { metadata_id: 1 str_value: "jit(local_packed_scan)/while/body/closed_call/glint.scatter/syn0/scatter-add" } } }
  event_metadata { key: 8 value { id: 8 name: "%copy.8" } }
  event_metadata { key: 9 value { id: 9 name: "jit_local_packed_scan(123)" } }
  event_metadata { key: 10 value { id: 10 name: "jit_local_pull(456)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "jit(local_packed_scan)/while/body/closed_call/glint.scatter/syn1/scatter-add" } }
}
planes { name: "/host:CPU"
  lines { name: "leader" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 95000000 duration_ps: 135000000
             stats { metadata_id: 1 str_value: "1095.0" } }
    events { metadata_id: 2 offset_ps: 120000000 duration_ps: 60000000
             stats { metadata_id: 1 str_value: "1120.5" } }
  }
  event_metadata { key: 1 value { id: 1 name: "glint.req.dispatch" } }
  event_metadata { key: 2 value { id: 2 name: "glint.req.pull" } }
  stat_metadata { key: 1 value { id: 1 name: "t0_us" } }
}
"""


@pytest.fixture
def run(tmp_path):
    from jax.profiler import ProfileData

    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(TRACE))
    said = []
    return types.SimpleNamespace(
        trace={"window_s": 300e-6}, trace_dir=str(tmp_path),
        cfg={"run": {"steps_per_call": 2}}, say=said.append, said=said,
        program_spans_path=None, program_spans=[
            {"name": "req.queue", "ph": "X", "ts": 1100.0, "dur": 20.0},
            {"name": "req.queue", "ph": "X", "ts": 5000.0, "dur": 20.0},
        ])


def test_offset_and_ring_spans_on_the_trace_clock(run):
    from benchmark import program_trace

    data = program_trace.read(run)
    # two readings: 95.0 - 1095.0 and 120.0 - 1120.5
    assert data["offset_us"] == pytest.approx(-1000.25)
    assert 0 < data["offset_spread_us"] < 1.0
    # the ring's first span lands at 99.75 us; the second began after the
    # traced window and is left out
    assert program_trace.ring_spans(run, "req.queue") == [
        (pytest.approx(99.75e-6), pytest.approx(20e-6))]
    assert any("clock offset" in line and "-1000.2" in line
               for line in run.said)


def test_idle_gaps_are_cut_and_filed_under_the_innermost_annotation(run):
    from benchmark import program_trace

    data = program_trace.read(run)
    us = pytest.approx
    assert data["gaps"] == [
        (us(20e-6), ["glint.req.dispatch"]),
        (us(60e-6), ["glint.req.dispatch", "glint.req.pull"]),
        (us(20e-6), ["glint.req.dispatch"]),
        (us(20e-6), ["glint.req.dispatch"]),
        (us(30e-6), []),
    ]
    assert program_trace.idle_share(
        run, under=("glint.req.dispatch",)) == us(100 * 120 / 300)
    assert program_trace.idle_share(
        run, outside=("glint.req.dispatch", "glint.req.grace")
    ) == us(100 * 30 / 300)
    said = [line for line in run.said if " idle " in line]
    assert [line.split(" under ")[1] for line in said] == [
        "glint.req.dispatch", "glint.req.pull",
        program_trace.NO_ANNOTATION]  # 60, 60 and 30 us: longest first


def test_self_times_by_scope_inside_the_packed_scan_runs(run):
    from benchmark import program_trace

    assert program_trace.scope_ms(
        run, "glint.batch", "glint.sample") == pytest.approx(30e-3 / 2)
    assert program_trace.scope_ms(run, "glint.gather") == pytest.approx(5e-3)
    assert program_trace.scope_ms(run, "glint.grads") == pytest.approx(5e-3)
    # syn1 (its tf_op a referenced text) and syn0 together
    assert program_trace.scope_ms(
        run, "glint.scatter") == pytest.approx(35e-3 / 2)
    # the while's own 15 of 100 us; the copies outside the scan are not in
    assert program_trace.unscoped_share(run) == pytest.approx(15.0)


def test_a_trace_without_bridge_or_scopes_reads_as_nothing(run, tmp_path):
    from jax.profiler import ProfileData

    bare = TRACE.replace("glint.", "other.")
    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(bare))
    for cell in NEW.values():
        for name in cell:
            assert _reader(name).read(run) is None, name


def _reader(name):
    from benchmark.run import load_module

    return load_module(os.path.join(BENCH, "layers", name + ".py"))


def test_new_per_layer_names_resolve_to_files():
    specs = {m["name"]: m for m in bench()["per_layer"]}
    for cell, names in NEW.items():
        for name in names:
            assert specs[name]["workloads"] == [cell]
            assert callable(_reader(name).read)


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_traced_rehearsal_reads_the_spans_and_omits_the_rest(kind):
    doc, out = harness(CELLS[kind], "--trace", "1")
    assert doc["correct"] is True, out
    assert "program trace: clock offset" in out
    got = set(doc["metrics"]) & set(NEW[CELLS[kind]])
    # a CPU trace has no device plane: no scopes, no idle gaps
    want = {"serve.round_ms", "serve.pull_ms", "serve.grace_ms"}
    assert got == (want if kind == "synonyms" else set())
