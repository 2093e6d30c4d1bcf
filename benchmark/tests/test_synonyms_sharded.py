"""The four-chip served cell ``w2v-nn-300-10m-x4.synonyms``: CPU, tiny sizes
on four forced host devices, a synthetic xplane. Run by hand like its
neighbours:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_synonyms_sharded.py -q

``FAULT`` is the fault the cell's ``correct`` has to catch besides the
control in lower precision, planted underneath the harness; on the chip it
is planted by hand from here:

    python3 -c "import sys; sys.path[:0] = ['.', 'benchmark/tests']; \\
        import test_synonyms_sharded as t; exec(t.FAULT); \\
        import benchmark.run as r; sys.exit(r.main([...]))"
"""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_benchmark import BENCH, KEYS, ROOT, bench, harness  # noqa: E402
from test_subword import _reader  # noqa: E402

CELL = "w2v-nn-300-10m-x4.synonyms"
NEW = {"topk.score_ms": ("query programs", "device_trace"),
       "topk.local_ms": ("query programs", "device_trace"),
       "topk.merge_ms": ("query programs", "device_trace"),
       "topk_sharded_roofline": ("query programs", "device_trace"),
       "serve.launch_ms": ("serving host", "program_span")}
#: The accepted readers that move a metric the cell reports. The cell is off
#: ``synonyms_qps`` (ISSUE 47's proviso: the parent cannot load the cell's
#: table, so no bound can be reckoned from its rate), and so off the readers
#: that move it; ``topk_roofline`` counts the WHOLE table over a quarter's
#: time and is never listed here.
SHARED = ["serve.queue_wait_ms", "serve.post_warmup_compiles",
          "serve.round_ms", "serve.pull_ms", "serve.grace_ms"]

#: Run in a child before the harness: the merge loses one shard's candidates.
FAULT = """
from glint_word2vec_tpu.parallel import engine
import jax.numpy as jnp
from jax import lax
real = engine._merge_topk
def one_shard_dropped(val, idx, start, k):
    lost = lax.axis_index(engine.MODEL_AXIS) == 1
    return real(jnp.where(lost, -jnp.inf, val), idx, start, k)
engine._merge_topk = one_shard_dropped
"""


@pytest.fixture
def four_devices(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")


def test_the_new_names_resolve_to_files():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    config = next(c for c in b["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 4 and cell["traffic"] == "synonyms"
    assert config["reduced"] == []
    assert len(config["source"]) <= 200 and "findSynonyms" in config["source"]
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    # two of the eight cells take four chips: the quarter the contract allows
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= len(
        b["workloads"]) // 4
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "configs", "w2v-300-10m-x4.json")) as f:
        trained = json.load(f)
    assert cfg["model"] == trained["model"]  # what that cell's fit saves
    assert cfg["run"] == {"num_shards": 4}
    assert cfg["architecture"] is None and cfg["reduced"] == []
    assert cfg["source"] == config["source"]
    for key in ("deployment", "departure", "assumed", "guarantee", "sized",
                "source_detail"):
        assert cfg[key], key
    assert cfg["tiny"]["run"] == {"num_shards": 4}
    with open(os.path.join(BENCH, "traffic", CELL + ".json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "synonyms_sharded"
    assert os.path.exists(os.path.join(BENCH, "kinds", "synonyms_sharded.py"))
    assert (traffic["callers"], traffic["num"], traffic["zipf_exponent"],
            traffic["cache_warm_words"], traffic["table_std"],
            traffic["checked_answers"]) == (16, 10, 1.0, 16384, 0.1, 48)
    assert traffic["limits"] == {"answers.score_gap": 1e-5}
    # the one-chip serving cell's load: the same callers, num and window
    with open(os.path.join(BENCH, "traffic", "w2v-300-2m.synonyms.json")) as f:
        sibling = json.load(f)
    for key in ("callers", "num", "zipf_exponent", "table_std",
                "checked_answers", "trace_window_s", "limits"):
        assert traffic[key] == sibling[key], key
    specs = {s["name"]: s for s in b["per_layer"]}
    for name, (layer, source) in NEW.items():
        assert specs[name]["workloads"] == [CELL]
        assert (specs[name]["layer"], specs[name]["source"],
                specs[name]["moves"]) == (layer, source, "synonyms_p95_ms")
        assert callable(_reader(name).read)
    for name in SHARED:
        assert CELL in specs[name]["workloads"], name
    assert CELL not in specs["topk_roofline"]["workloads"]
    e2e = {s["name"]: s for s in b["end_to_end"]}
    for name in ("synonyms_p50_ms", "synonyms_p95_ms"):
        assert CELL in e2e[name]["workloads"]
    assert CELL not in e2e["synonyms_qps"]["workloads"]
    # every reader that lists the cell moves a metric the cell reports
    reported = {n for n, s in e2e.items() if CELL in s.get("workloads", [CELL])}
    for name, spec in specs.items():
        if CELL in spec.get("workloads", [CELL]):
            assert spec["moves"] in reported, name


def test_bytes_of_a_dispatch_at_the_cells_size():
    from benchmark import bytes_topk_sharded as b

    # 2.5M rows a chip, 300 columns at rest in 384, f32: 3.84 GB, a quarter
    # of one table's 15.36 GB; 4.69 ms at 819 GB/s
    assert b.rows_per_shard(10_000_000, 4) == 2_500_000
    assert b.resting_columns(300) == 384 and b.resting_columns(384) == 384
    assert b.topk_shard_bytes(10_000_000, 4, 300) == 3_840_000_000
    assert b.topk_shard_bytes(10_000_001, 4, 300, 2) == 2_500_001 * 384 * 2
    assert b.topk_shard_bytes(2_000_000, 1, 300) == 2_000_000 * 384 * 4


def test_the_reference_over_blocks_is_the_reference_over_the_table():
    import numpy as np

    from benchmark import reference, reference_nn_sharded

    rng = np.random.default_rng(5)
    table = rng.normal(0, 0.1, (500, 24)).astype(np.float32)
    table[17] = 0.0
    table[300] = table[299]  # equals on both sides of a block's edge
    blocks = reference_nn_sharded.Blocks(24)
    for a, b in ((0, 130), (130, 300), (300, 500)):
        blocks.add(a, table[a:b])
    rows = [3, 299, 17, 499]
    cos = blocks.cosines(blocks.rows(rows))
    whole = reference.TopK(table)
    np.testing.assert_allclose(
        cos[:, [0, 1, 3]], whole.cosines(np.asarray([3, 299, 499])),
        atol=2e-7)
    assert np.isneginf(cos[17]).all()
    top = reference_nn_sharded.top(cos[:, 1], 4, ban=299)
    assert top[0][0] == 300 and abs(top[0][1] - 1.0) < 1e-6
    assert reference_nn_sharded.gap(cos[:, 1], top, 4, ban=299) == 0.0
    # an answer that lacks the best row, one that names the query word
    # and one that is too short are not the reference's
    worse = top[1:] + [reference_nn_sharded.top(cos[:, 1], 5, ban=299)[4]]
    assert reference_nn_sharded.gap(cos[:, 1], worse, 4, ban=299) > 0.1
    assert reference_nn_sharded.gap(
        cos[:, 1], [(299, 1.0)] + top[:3], 4, ban=299) == float("inf")
    assert reference_nn_sharded.gap(
        cos[:, 1], top[:3], 4, ban=299) == float("inf")


# Two chips of the mesh, one round [90, 260] us on the host: the pull's
# program [100, 102] us, then the top-k program, [110, 210] us on the first
# chip and [110, 230] on the second (which waits in the all-gather for
# nobody here, and is simply the slower). On the first chip: the score fusion
# [110, 170] under glint.score, the local top-k [170, 195] under glint.topk,
# an all-gather [195, 200] and the second top-k [200, 206] under glint.merge,
# and a copy of no scope [206, 210].
TRACE = """
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 100000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 110000000 duration_ps: 60000000 }
    events { metadata_id: 3 offset_ps: 170000000 duration_ps: 25000000 }
    events { metadata_id: 4 offset_ps: 195000000 duration_ps: 5000000 }
    events { metadata_id: 5 offset_ps: 200000000 duration_ps: 6000000 }
    events { metadata_id: 6 offset_ps: 206000000 duration_ps: 4000000 }
  }
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 7 offset_ps: 100000000 duration_ps: 2000000 }
    events { metadata_id: 8 offset_ps: 110000000 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%gather.1" stats { metadata_id: 1 str_value: "jit(local_pull)/glint.gather/gather" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2" stats { metadata_id: 1 str_value: "jit(local_topk_batch)/glint.score/dot_general" } } }
  event_metadata { key: 3 value { id: 3 name: "%TopK.3" stats { metadata_id: 1 str_value: "jit(local_topk_batch)/glint.topk/top_k" } } }
  event_metadata { key: 4 value { id: 4 name: "%all-gather.4" stats { metadata_id: 1 str_value: "jit(local_topk_batch)/glint.merge/all_gather" } } }
  event_metadata { key: 5 value { id: 5 name: "%TopK.5" stats { metadata_id: 1 str_value: "jit(local_topk_batch)/glint.merge/top_k" } } }
  event_metadata { key: 6 value { id: 6 name: "%copy.6" } }
  event_metadata { key: 7 value { id: 7 name: "jit_local_pull(456)" } }
  event_metadata { key: 8 value { id: 8 name: "jit_local_topk_batch(123)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
planes { name: "/device:TPU:1"
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 110000000 duration_ps: 120000000 }
  }
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 110000000 duration_ps: 120000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.2" stats { metadata_id: 1 str_value: "jit(local_topk_batch)/glint.score/dot_general" } } }
  event_metadata { key: 2 value { id: 2 name: "jit_local_topk_batch(123)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
planes { name: "/host:CPU"
  lines { name: "leader" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 90000000 duration_ps: 170000000
             stats { metadata_id: 1 str_value: "1090.0" } }
  }
  event_metadata { key: 1 value { id: 1 name: "glint.req.dispatch" } }
  stat_metadata { key: 1 value { id: 1 name: "t0_us" } }
}
"""


def _run(tmp_path, trace_text, spans):
    from jax.profiler import ProfileData

    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(trace_text))
    said = []
    return types.SimpleNamespace(
        trace={"window_s": 300e-6}, trace_dir=str(tmp_path),
        cfg={"run": {"num_shards": 4},
             "model": {"vector_size": 300, "table_dtype": "float32"}},
        notes={"padded_rows": 10_000_000, "shards": 4},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
        say=said.append, said=said, program_spans_path=None,
        program_spans=spans)


def test_the_readers_on_a_synthetic_trace_of_two_chips(tmp_path):
    run = _run(tmp_path, TRACE, [
        {"name": "req.dispatch", "ph": "X", "ts": 1090.0, "dur": 170.0}])
    ms = pytest.approx
    assert _reader("topk.score_ms").read(run) == ms(60e-3)
    assert _reader("topk.local_ms").read(run) == ms(25e-3)
    assert _reader("topk.merge_ms").read(run) == ms(11e-3)
    # one read of a chip's 3.84 GB at 819 GB/s over the SLOWER chip's 120 us
    assert _reader("topk_sharded_roofline").read(run) == ms(
        100 * 3.84e9 / 819e9 / 120e-6)
    # the round's 170 us less the first chip's 2 + 100 us of programs
    assert _reader("serve.launch_ms").read(run) == ms(68e-3)
    assert any("top-k trace: 1 runs" in line and "/device:TPU:1" in line
               for line in run.said)


def test_a_program_without_the_scopes_gives_the_readers_nothing(tmp_path):
    """PR 47's parent: the same programs, no ``glint.`` scope on the top-k's
    ops. The three scope readers return None and raise nothing; the two that
    need no scope still read."""
    bare = TRACE
    for scope in ("glint.score/", "glint.topk/", "glint.merge/"):
        bare = bare.replace(scope, "")
    run = _run(tmp_path, bare, [
        {"name": "req.dispatch", "ph": "X", "ts": 1090.0, "dur": 170.0}])
    for name in ("topk.score_ms", "topk.local_ms", "topk.merge_ms"):
        assert _reader(name).read(run) is None
    assert _reader("topk_sharded_roofline").read(run) is not None
    assert _reader("serve.launch_ms").read(run) == pytest.approx(68e-3)
    # and a run that was not traced at all
    run.trace = None
    run._program_trace = run._topk_trace = None
    for name in NEW:
        assert _reader(name).read(run) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(four_devices, trace):
    doc, out = harness(CELL, "--trace", str(trace))
    assert set(doc) == KEYS | ({"breakdown"} if trace else set())
    assert doc["correct"] is True, out
    assert doc["device"]["platform"] == "cpu"  # a rehearsal, never a metric
    assert doc["device"]["count"] == 4
    assert doc["attempted"] > 0 and doc["failed"] == 0
    b = bench()
    wanted = b["per_layer"] if trace else b["end_to_end"]
    listed = {m["name"] for m in wanted
              if CELL in m.get("workloads", [CELL])}
    assert set(doc["metrics"]) <= listed
    if trace:
        # the CPU backend's trace has no program line: the device readers
        # find nothing; the spans and counters are the program's own
        assert set(SHARED) <= set(doc["metrics"])
    else:
        assert set(doc["metrics"]) == {
            "synonyms_p50_ms", "synonyms_p95_ms", "setup_s"}
    for name in ("answers.score_gap", "tables.devices_missing",
                 "tables.rows_on_fullest_device_over_share",
                 "server.post_warmup_compiles"):
        assert f"compare {name}: " in out
    assert "queries/s completed" in out and "shards 4" in out


def test_the_control_in_lower_precision_is_not_correct(four_devices):
    doc, out = harness(CELL, "--trace", "0", "--control", "bf16")
    assert doc["correct"] is False, out
    assert "compare answers.score_gap" in out and "NOT OK" in out


def test_a_merge_that_drops_a_shards_candidates_is_not_correct(four_devices):
    doc, out = harness(CELL, "--trace", "0", prelude=FAULT)
    assert doc["correct"] is False, out
    bad = [line for line in out.splitlines() if "NOT OK" in line]
    assert bad and all("answers.score_gap" in line for line in bad), out
