"""The four-chip cell ``w2v-300-10m-x4.train``: CPU, four virtual devices,
tiny sizes, a synthetic xplane. Run by hand like its neighbours:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_x4.py -q
"""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_benchmark import BENCH, BROKEN, KEYS, ROOT, bench, harness  # noqa: E402

CELL = "w2v-300-10m-x4.train"
NEW = ["step.exchange_ms", "collective.share", "exchange.ici_share"]


@pytest.fixture
def four_devices(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")


def _reader(name):
    from benchmark.run import load_module

    return load_module(os.path.join(BENCH, "layers", name + ".py"))


def test_the_new_names_resolve_to_files():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    config = next(c for c in b["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 4 and config["reduced"] == []
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    assert cfg["run"]["num_shards"] == cell["chips"]
    assert cfg["model"]["vector_size"] == 300  # no width is cut
    assert cfg["model"]["table_dtype"] == "float32"
    with open(os.path.join(BENCH, "traffic", CELL + ".json")) as f:
        traffic = json.load(f)
    assert os.path.isfile(
        os.path.join(BENCH, "kinds", traffic["kind"] + ".py"))
    # every word once + the Zipf draws + 8 words a planted sentence
    assert (cfg["model"]["vocab"] - 44 + traffic["zipf_tokens"]
            + 8 * traffic["planted_sentences"]) == 15_639_956
    specs = {m["name"]: m for m in b["per_layer"]}
    for name in NEW:
        assert specs[name]["workloads"] == [CELL]
        assert callable(_reader(name).read)
    assert CELL not in specs["sgns_step_roofline"]["workloads"]
    with open(os.path.join(BENCH, "peaks_ici.json")) as f:
        assert json.load(f)["devices"]["TPU v5 lite"][
            "ici_bytes_per_s"] == 1600e9 / 8


def test_exchange_bytes_at_the_cells_size():
    from benchmark import bytes_sharded

    payload = bytes_sharded.exchange_bytes(8192, 5, 5, 300, 4)
    assert payload == 183_505 * 300 * 4  # 220 MB a device a step
    assert bytes_sharded.all_reduce_wire_bytes(payload, 4) == 1.5 * payload
    assert bytes_sharded.exchange_bytes(8192, 5, 5, 300, 1) == 0
    assert bytes_sharded.all_reduce_wire_bytes(payload, 1) == 0


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(four_devices, trace):
    doc, out = harness(CELL, "--trace", str(trace))
    assert set(doc) == KEYS | ({"breakdown"} if trace else set())
    assert doc["correct"] is True, out
    assert doc["device"] == dict(doc["device"], platform="cpu", count=4)
    assert doc["attempted"] > 0 and doc["failed"] == 0
    b = bench()
    wanted = b["per_layer"] if trace else b["end_to_end"]
    listed = {m["name"] for m in wanted
              if CELL in m.get("workloads", [CELL])}
    assert set(doc["metrics"]) <= listed
    if not trace:
        assert set(doc["metrics"]) == listed
    assert "sharded reference: " in out and "on 4 device(s)" in out
    assert "compare tables.rows_on_fullest_device_over_share: 0 " in out


def test_the_control_in_lower_precision_is_not_correct(four_devices):
    doc, out = harness(CELL, "--trace", "0", "--control", "bf16")
    assert doc["correct"] is False, out


def test_a_broken_timed_path_is_not_correct(four_devices):
    doc, out = harness(CELL, "--trace", "0", prelude=BROKEN["train"])
    assert doc["correct"] is False, out


def test_too_few_devices_is_refused_at_once():
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0", "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3 and "needs 4 chip(s)" in p.stderr


# One run of the packed scan, [0, 100] us, on a chip of a 1x4 mesh: a while
# that spans the own-row gather [10, 20], the rows' all-reduce [30, 50] under
# glint.exchange (named %psum.3, as jax names it), a reshape of its result
# [50, 55] and a scatter [60, 90].
TRACE = """
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 30000000 duration_ps: 20000000 }
    events { metadata_id: 6 offset_ps: 50000000 duration_ps: 5000000 }
    events { metadata_id: 4 offset_ps: 60000000 duration_ps: 30000000 }
  }
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 5 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = (...)" stats { metadata_id: 1 str_value: "jit(local_packed_scan)/shard_map/while" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2" stats { metadata_id: 1 str_value: "jit(local_packed_scan)/shard_map/while/body/closed_call/glint.gather/gather" } } }
  event_metadata { key: 3 value { id: 3 name: "%psum.3 = f32[26215,300]{1,0} all-reduce(f32[26215,300]{1,0} %fusion.2), replica_groups={{0,1,2,3}}" stats { metadata_id: 1 str_value: "jit(local_packed_scan)/shard_map/while/body/closed_call/glint.exchange/psum" } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4" stats { metadata_id: 1 str_value: "jit(local_packed_scan)/shard_map/while/body/closed_call/glint.scatter/syn1/scatter-add" } } }
  event_metadata { key: 5 value { id: 5 name: "jit_local_packed_scan(123)" } }
  event_metadata { key: 6 value { id: 6 name: "%reshape.6 = f32[26215,1,300]{2,1,0} reshape(f32[26215,300]{1,0} %psum.3)" stats { metadata_id: 1 str_value: "jit(local_packed_scan)/shard_map/while/body/closed_call/glint.gather/reshape" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
"""


def _run(tmp_path, text, platform="tpu"):
    from jax.profiler import ProfileData

    from benchmark import trace_reduce

    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    said = []
    return types.SimpleNamespace(
        trace=trace_reduce.reduce_profile(
            ProfileData.from_text_proto(text), 100e-6),
        trace_dir=str(tmp_path), say=said.append, said=said,
        device={"platform": platform, "kind": "TPU v5 lite", "count": 4},
        cfg={"model": {"window": 5, "negatives": 5, "vector_size": 32},
             "run": {"batch_size": 256, "steps_per_call": 2,
                     "num_shards": 4}},
        training_metrics={"exchange_bytes_per_step": 734_720},
        program_spans_path=None, program_spans=[])


def test_the_three_readers_on_a_synthetic_xplane(tmp_path):
    run = _run(tmp_path, TRACE)
    # 20 us over two steps
    assert _reader("step.exchange_ms").read(run) == pytest.approx(10e-3)
    # the all-reduce's 20 us of 100 busy; not the reshape of its result,
    # which trace_reduce's search of the whole text counts (25 us)
    assert _reader("collective.share").read(run) == pytest.approx(20.0)
    assert run.trace["collective_s"] == pytest.approx(25e-6)
    # 820 pair slots x 7 rows x 32 x 4 B = 734,720 B handed, 1.5 times that
    # on the wire, at 200 GB/s: 5.5104 us of the 10 us a step
    assert _reader("exchange.ici_share").read(run) == pytest.approx(55.104)
    assert any("734720 bytes handed" in line for line in run.said)
    assert _reader("step.gather_ms").read(run) == pytest.approx(7.5e-3)


def test_a_program_without_the_scope_reads_as_nothing(tmp_path):
    # PR 27's parent: the all-reduce lies under glint.gather
    run = _run(tmp_path, TRACE.replace("glint.exchange", "glint.gather"))
    assert _reader("step.exchange_ms").read(run) is None
    assert _reader("exchange.ici_share").read(run) is None
    assert _reader("collective.share").read(run) == pytest.approx(20.0)
    assert _reader("step.gather_ms").read(run) == pytest.approx(17.5e-3)


def test_no_trace_and_no_chip_read_as_nothing(tmp_path):
    run = _run(tmp_path, TRACE, platform="cpu")
    assert _reader("exchange.ici_share").read(run) is None  # a chip's peak
    run = _run(tmp_path, TRACE)
    run.trace = None
    for name in NEW:
        assert _reader(name).read(run) is None, name
