"""``benchmark/request_trace.py`` and the twelve readers built on it (PR 52):
a synthetic ring and xplane, then one tiny rehearsal. Run by hand like its
neighbours:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_request_trace.py -q
"""

import copy
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_benchmark import CELLS, bench, harness  # noqa: E402
from test_subword import _reader  # noqa: E402

HIT = ["serve.hit_ms", "serve.hit_cpu_ms", "serve.hit_head_ms",
       "serve.hit_parse_ms", "serve.hit_lookup_ms", "serve.hit_serialize_ms"]
ROUND = ["serve.enqueue_ms", "serve.readback_ms", "serve.readback_lag_ms",
         "serve.decode_ms", "serve.wake_ms", "serve.idle_in_result"]
#: Read from the device's planes: nothing to read in a CPU trace.
DEVICE = ["serve.readback_lag_ms", "serve.idle_in_result"]

# The trace's clock is the ring's less 1,000 us. The profiler's window is
# [0, 300] us. One round [90, 260] us composes, then scores: the compose
# [92, 130] holds a launch [95, 100] and a read-back [100, 128] of
# pull_average, whose program ran [101, 103] on the device (the leader held
# its result 25 us after it ended); the ids [131, 133]; the top-k's launch
# [135, 145] and read-back [145, 240], its program [146, 226] (14 us); the
# decode [241, 255]. The device also ran [10, 20] and [280, 290], so it
# idled [20, 101], [103, 146], [226, 280]: 1 + 25 + 1 + 14 = 41 us of that
# under a read-back.
TRACE = """
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 101000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 146000000 duration_ps: 80000000 }
    events { metadata_id: 1 offset_ps: 280000000 duration_ps: 10000000 }
  }
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 101000000 duration_ps: 2000000 }
    events { metadata_id: 5 offset_ps: 146000000 duration_ps: 80000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%copy.1" } }
  event_metadata { key: 2 value { id: 2 name: "%gather.2" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3" } }
  event_metadata { key: 4 value { id: 4 name: "jit_local_pull_average(1)" } }
  event_metadata { key: 5 value { id: 5 name: "jit_local_topk_batch(2)" } }
}
planes { name: "/host:CPU"
  lines { name: "leader" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 90000000 duration_ps: 170000000
             stats { metadata_id: 1 str_value: "1090.0" } }
    events { metadata_id: 2 offset_ps: 95000000 duration_ps: 5000000
             stats { metadata_id: 1 str_value: "1095.0" } }
    events { metadata_id: 3 offset_ps: 100000000 duration_ps: 28000000
             stats { metadata_id: 1 str_value: "1100.0" } }
    events { metadata_id: 2 offset_ps: 135000000 duration_ps: 10000000
             stats { metadata_id: 1 str_value: "1135.0" } }
    events { metadata_id: 3 offset_ps: 145000000 duration_ps: 95000000
             stats { metadata_id: 1 str_value: "1145.0" } }
    events { metadata_id: 4 offset_ps: 241000000 duration_ps: 14000000
             stats { metadata_id: 1 str_value: "1241.0" } }
  }
  event_metadata { key: 1 value { id: 1 name: "glint.req.dispatch" } }
  event_metadata { key: 2 value { id: 2 name: "glint.req.enqueue" } }
  event_metadata { key: 3 value { id: 3 name: "glint.req.result" } }
  event_metadata { key: 4 value { id: 4 name: "glint.req.decode" } }
  stat_metadata { key: 1 value { id: 1 name: "t0_us" } }
}
"""


def _span(name, ts, dur, tid=7, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "tid": tid,
            "args": args}


def _request(trace, ts, head, accept, cache, cpu_ms, **phases):
    """A kept request's events: the head, then the root span; its phases
    are laid end to end inside the root (their places do not matter)."""
    out = [_span("req.head", ts, head, tid=9, trace=trace),
           _span("req.accept", ts + head, accept, tid=9, trace=trace,
                 path="/synonyms", status=200, cpu_ms=cpu_ms,
                 **({"cache": cache} if cache else {}))]
    at = ts + head
    for name, dur in phases.items():
        out.append(_span("req." + name, at, dur, tid=9, trace=trace))
        at += dur
    return out


ROUND_SPANS = [
    _span("req.dispatch", 1090.0, 170.0, batch=2, programs=2),
    _span("req.compose", 1092.0, 38.0, oov=1),
    _span("req.enqueue", 1095.0, 5.0, program="pull_average", q=1, shards=1),
    _span("req.result", 1100.0, 28.0, program="pull_average"),
    _span("req.pull", 1131.0, 2.0, rows=1),
    _span("req.enqueue", 1135.0, 10.0, program="topk_batch", q=8, shards=1),
    _span("req.result", 1145.0, 95.0, program="topk_batch"),
    _span("req.decode", 1241.0, 14.0, batch=2),
    # the warm-up's own launch, outside any round: no round's child
    _span("req.enqueue", 400.0, 50.0, program="topk_batch", q=8, shards=1),
]

RING = (
    ROUND_SPANS
    # inside the profiler's window: the tracer stretches them
    + _request("hit-in", 1050.0, 20.0, 60.0, "hit", 0.03, parse=9.0,
               admission=1.0, lookup=2.0, serialize=30.0)
    + _request("miss-in", 1060.0, 20.0, 200.0, "miss", 0.05, parse=9.0,
               lookup=2.0, queue=30.0, query=50.0, readback=110.0,
               wake=30.0, serialize=30.0)
    # outside it, inside the load window [-1,000, 2,000] us
    + _request("hit-a", 1500.0, 10.0, 40.0, "hit", 0.02, parse=5.0,
               admission=1.0, lookup=1.0, serialize=20.0)
    + _request("hit-b", 1700.0, 14.0, 50.0, "hit", 0.04, parse=7.0,
               admission=1.0, lookup=3.0, serialize=24.0)
    + _request("miss-a", 1900.0, 10.0, 150.0, "miss", 0.05, parse=5.0,
               lookup=1.0, queue=20.0, query=40.0, readback=60.0,
               wake=7.0, serialize=20.0)
    # a vector query: no cache to hit or miss
    + _request("vector", 1950.0, 10.0, 150.0, None, 0.05, parse=5.0)
    # after the load window closed: no reader's
    + _request("late", 6000.0, 1000.0, 4000.0, "hit", 3.0, parse=500.0,
               lookup=100.0, serialize=2000.0)
    + [{"name": "query_compile", "ph": "i", "ts": 1400.0, "tid": 7,
        "args": {"op": "topk_batch", "shape": [32, 16], "shared": False}},
       {"name": "query_compile", "ph": "i", "ts": -5000.0, "tid": 7,
        "args": {"op": "topk", "shape": [16], "shared": False}}]
)


def _run(tmp_path, trace_text, ring):
    from jax.profiler import ProfileData

    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(trace_text))
    said = []
    cache = {"hits": 0, "misses": 0}
    return types.SimpleNamespace(
        trace={"window_s": 300e-6}, trace_dir=str(tmp_path),
        trace_t=[10.0, 10.0003], window=(10.0 - 1e-3, 10.0 + 2e-3),
        cfg={"run": {"num_shards": 1}}, say=said.append, said=said,
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        end_to_end={"synonyms_p50_ms": 0.07},
        serving_metrics_before={"synonym_cache": cache},
        serving_metrics={"synonym_cache": {"hits": 64, "misses": 36}},
        program_spans_path=None, program_spans=ring)


def test_the_hit_readers_take_the_hits_outside_the_profilers_window(
        tmp_path):
    run = _run(tmp_path, TRACE, RING)
    ms = pytest.approx
    # hit-a and hit-b: medians of two
    assert _reader("serve.hit_ms").read(run) == ms((50 + 64) / 2 / 1e3)
    assert _reader("serve.hit_cpu_ms").read(run) == ms(0.03)
    assert _reader("serve.hit_head_ms").read(run) == ms(12e-3)
    assert _reader("serve.hit_parse_ms").read(run) == ms(6e-3)
    assert _reader("serve.hit_lookup_ms").read(run) == ms(2e-3)
    assert _reader("serve.hit_serialize_ms").read(run) == ms(22e-3)
    # the one miss outside it
    assert _reader("serve.wake_ms").read(run) == ms(7e-3)
    said = "\n".join(run.said)
    # the tracer's stretch: outside beside inside
    assert ("a hit's whole (head + accept): median 0.0570 ms outside the "
            "profiler's window, 0.0800 ms inside it") in said
    assert "a miss's req.wake: median 0.0070 ms outside" in said
    assert "4 sampled requests outside the profiler's window (2 hits), " \
           "2 inside it (1 hits); 1 rounds inside it" in said
    # hit share 64%: the callers' p50 is the hits' p78.1, the slower of two
    assert "the callers' p50 is the hits' p78.1: 0.0640 ms" in said
    assert "synonyms_p50_ms 0.0700" in said
    assert ("query_compile in the load window: op topk_batch shape "
            "[32, 16]") in said
    assert "op topk " not in said


def test_the_round_readers_split_a_round_with_two_programs(tmp_path):
    run = _run(tmp_path, TRACE, RING)
    ms = pytest.approx
    assert _reader("serve.enqueue_ms").read(run) == ms(15e-3)
    assert _reader("serve.readback_ms").read(run) == ms(123e-3)
    assert _reader("serve.decode_ms").read(run) == ms(14e-3)
    # the FIRST read-back's: the compose's program ended at 103, held at 128
    assert _reader("serve.readback_lag_ms").read(run) == ms(25e-3)
    assert _reader("serve.idle_in_result").read(run) == ms(100 * 41 / 300)
    said = "\n".join(run.said)
    assert ("read-back lag of pull_average, the round's first: median "
            "0.0250 ms over 1") in said
    assert ("read-back lag of topk_batch, the round's later: median "
            "0.0140 ms over 1") in said
    # the parts sum to the round: 170 = 15 + 123 + 14 + 2 + (38 - 33) + 11
    assert ("req.enqueue 0.0150, req.result 0.1230, req.decode 0.0140, "
            "req.pull 0.0020, req.compose 0.0050, no child span 0.0110"
            ) in said
    assert "device idle 0.0000s under glint.req.enqueue" in said
    assert "device idle 0.0000s under glint.req.grace" in said
    assert "the ring holds %d events" % len(RING) in said
    assert "1 rounds inside the profiler's window, mean 0.1700 ms" in said
    # the tick of the thread's CPU clock: none here, every reading above 0
    assert ("a hit's cpu_ms outside the profiler's window: mean 0.0300 of "
            "a mean req.accept of 0.0450 ms over 2; 0.0% read 0, the least "
            "above 0 0.0200 ms") in said


def test_a_ring_without_the_spans_gives_every_reader_nothing(tmp_path):
    """PR 52's parent with this benchmark laid over it: the round and the
    requests are there, none of the new spans, phases or args."""
    bare = TRACE
    for name in ("enqueue", "result", "decode"):
        bare = bare.replace(f'name: "glint.req.{name}"',
                            f'name: "other.{name}"')
    old = [e for e in copy.deepcopy(RING)
           if e["name"] in ("req.dispatch", "req.compose", "req.pull",
                            "req.accept", "req.parse", "req.serialize",
                            "req.queue", "req.query", "req.readback")]
    for e in old:
        e = e["args"]
        e.pop("cache", None), e.pop("cpu_ms", None)
    run = _run(tmp_path, bare, old)
    for name in HIT + ROUND:
        assert _reader(name).read(run) is None, name
    assert any("no request of the ring has a req.head" in s for s in run.said)
    # no trace at all, no ring at all
    run = _run(tmp_path, TRACE, [])
    run.trace = None
    for name in HIT + ROUND:
        assert _reader(name).read(run) is None, name


def test_a_trace_without_program_lines_reads_no_lag(tmp_path):
    cut = TRACE.replace('lines { name: "XLA Modules"',
                        'lines { name: "Other line"')
    run = _run(tmp_path, cut, RING)
    assert _reader("serve.readback_lag_ms").read(run) is None
    assert _reader("serve.readback_ms").read(run) == pytest.approx(123e-3)


def test_the_twelve_are_declared_for_the_three_served_cells():
    b = bench()
    specs = {m["name"]: m for m in b["per_layer"]}
    cells = [w["name"] for w in b["workloads"] if w["traffic"] == "synonyms"]
    assert len(cells) == 3
    assert [m["name"] for m in b["per_layer"][-12:]] == HIT + ROUND
    for name in HIT + ROUND:
        spec = specs[name]
        assert set(spec) == {"name", "unit", "better", "source", "layer",
                             "moves", "workloads"}
        assert spec["workloads"] == cells and spec["better"] == "lower"
        assert spec["layer"] == "serving host"
        assert spec["moves"] == ("synonyms_p50_ms" if name in HIT
                                 else "synonyms_p95_ms")
        assert spec["source"] == ("device_trace" if name in DEVICE
                                  else "program_span")
        assert spec["unit"] == ("%" if name == "serve.idle_in_result"
                                else "ms")
        assert callable(_reader(name).read)


def test_a_tiny_traced_rehearsal_prints_a_value_for_each():
    """On the CPU: the ten that read the ring print a value; the two that
    read the device's planes find none in a CPU trace and are left out."""
    doc, out = harness(CELLS["synonyms"], "--trace", "1")
    assert doc["correct"] is True, out
    for name in HIT + ROUND:
        if name in DEVICE:
            assert name not in doc["metrics"]
        else:
            assert doc["metrics"][name]["value"] >= 0, name
            assert f"metric {name} = " in out
    m = {k: v["value"] for k, v in doc["metrics"].items()}
    assert m["serve.hit_cpu_ms"] <= m["serve.hit_ms"]
    assert (m["serve.enqueue_ms"] + m["serve.readback_ms"]
            + m["serve.decode_ms"]) <= m["serve.round_ms"] * 1.05
    assert "rounds inside the profiler's window, median " in out
    assert "request trace: no query_compile instant in the load window" in out
