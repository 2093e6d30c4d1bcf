"""The streamed cell ``w2v-stream-300-2m.train``: CPU, tiny sizes. Run by
hand like its neighbours:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_train_stream.py -q
"""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_benchmark import BENCH, KEYS, ROOT, bench, harness  # noqa: E402

CELL = "w2v-stream-300-2m.train"
NEW = ["stream.round_ms", "stream.fill_ms", "stream.adapt_ms",
       "stream.upload_ms", "stream.drain_ms", "stream.buffer_fill",
       "stream.drain_device_share"]
SHARED = ["step.device_ms", "step.index_ms", "step.batch_ms",
          "step.sample_ms", "step.gather_ms", "step.grads_ms",
          "step.scatter_ms", "step.unscoped_share", "scatter.rows_per_slab",
          "scatter.distinct_share", "batcher.pack_fill",
          "device.idle_share.train", "sgns_step_roofline"]


def _reader(name):
    from benchmark.run import load_module

    return load_module(os.path.join(BENCH, "layers", name + ".py"))


def test_the_new_names_resolve_to_files():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert b["workloads"][-1] is cell and cell["chips"] == 1
    config = b["configs"][-1]
    assert config["name"] == cell["config"] and config["reduced"] == []
    assert len(config["source"]) <= 200 and "1704.03956" in config["source"]
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "configs", "w2v-300-2m.json")) as f:
        sibling = json.load(f)
    # the batch cell's widths and hyperparameters, and the stream's own
    assert cfg["architecture"] is None and cfg["reduced"] == []
    for key, value in sibling["model"].items():
        assert cfg["model"][key] == value, key
    assert {k: v for k, v in cfg["model"].items()
            if k not in sibling["model"]} == {
                "extra_rows": 65536, "promote_min_count": 5,
                "sketch_capacity": 65536}
    assert cfg["run"] == {
        "batch_size": 8192, "steps_per_call": 32, "num_shards": 1,
        "buffer_words": 1048576, "buffer_sentences": 131072,
        "refresh_words": None, "bootstrap_words": 2400000,
        "publish_dir": None}
    assert len(cfg["source"]) <= 200 and "1704.03956" in cfg["source"]
    for key in ("deployment", "assumed", "guarantee", "tiny", "sized"):
        assert cfg[key], key
    with open(os.path.join(BENCH, "traffic", CELL + ".json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "train_stream"
    assert traffic["nominal_words_per_s"] % 10_000 == 0
    with open(os.path.join(BENCH, "traffic", "w2v-300-2m.train.json")) as f:
        batch = json.load(f)
    for key in batch["limits"]:  # the batch cell's limits, unwidened
        assert traffic["limits"][key] == batch["limits"][key], key
    assert traffic["planted_per_sentence"] == pytest.approx(
        batch["planted_sentences"] * batch["sentence_words"]
        / (sibling["model"]["vocab"] + batch["zipf_tokens"]))
    specs = {s["name"]: s for s in b["per_layer"]}
    assert [s["name"] for s in b["per_layer"][-len(NEW):]] == NEW
    for name in NEW:
        assert specs[name]["workloads"] == [CELL]
        assert specs[name]["moves"] == "train_words_per_s"
        assert specs[name]["layer"] == "stream host"
        assert callable(_reader(name).read)
    for name in SHARED:
        assert specs[name]["workloads"][-1] == CELL, name
    listed = {n for n, s in specs.items() if CELL in s.get("workloads", [])}
    assert listed == set(NEW) | set(SHARED)  # no fit.* reader among them
    e2e = {s["name"]: s for s in b["end_to_end"]}
    assert e2e["train_words_per_s"]["workloads"][-1] == CELL


def test_the_two_copies_of_the_reference_agree():
    from benchmark import reference_stream
    from glint_word2vec_tpu.streaming import stream_reference

    def body(module):
        with open(module.__file__) as f:
            text = f.read()
        return text[text.index("import numpy as np"):]

    mine, theirs = body(reference_stream), body(stream_reference)
    assert mine.startswith(theirs.rstrip("\n"))  # the text, then the replay


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(trace):
    doc, out = harness(CELL, "--trace", str(trace))
    assert set(doc) == KEYS | ({"breakdown"} if trace else set())
    assert doc["correct"] is True, out
    assert doc["device"]["platform"] == "cpu"  # a rehearsal, never a metric
    assert doc["attempted"] > 0 and doc["failed"] == 0
    if trace:
        # no device plane on the CPU: the device_trace readers stay silent
        assert set(doc["metrics"]) >= {
            "stream.round_ms", "stream.fill_ms", "stream.adapt_ms",
            "stream.upload_ms", "stream.drain_ms", "stream.buffer_fill",
            "batcher.pack_fill", "scatter.distinct_share"}
        m = {k: v["value"] for k, v in doc["metrics"].items()}
        assert 90 < m["stream.buffer_fill"] <= 100
    else:
        assert set(doc["metrics"]) == {"train_words_per_s", "setup_s"}
    for name in ("host.buffer_words_wrong", "host.promotions_wrong",
                 "host.counts_wrong", "spare.rows_misplaced",
                 "noise.pmf_l1_gap", "replay.syn0_gap",
                 "replay_live.syn1_gap", "window.compiles"):
        assert f"compare {name}:" in out


def test_the_control_in_lower_precision_is_not_correct():
    doc, out = harness(CELL, "--control", "bf16")
    assert doc["correct"] is False
    assert "NOT OK" in out


# Two planted faults, each patched into the program before the run.
STALE_REFRESH = (
    "from glint_word2vec_tpu.parallel.engine import EmbeddingEngine as E\n"
    "E.set_noise_counts = lambda self, counts, table=None: None\n")
WRONG_ROW = (
    "import jax.numpy as jnp\n"
    "from glint_word2vec_tpu.parallel.engine import EmbeddingEngine as E\n"
    "_writer = E._extra_row_writer\n"
    "def _shifted(self):\n"
    "    block, fn = _writer(self)\n"
    "    return block, lambda s0, s1, s, m: fn(s0, s1, s + jnp.int32(1), m)\n"
    "E._extra_row_writer = _shifted\n")


@pytest.mark.parametrize("fault,number", [
    (STALE_REFRESH, "noise.pmf_l1_gap"), (WRONG_ROW, "spare.rows_misplaced")])
def test_a_planted_fault_is_not_correct(fault, number):
    doc, out = harness(CELL, prelude=fault)
    assert doc["correct"] is False
    bad = [line for line in out.splitlines() if "NOT OK" in line]
    assert any(f"compare {number}:" in line for line in bad), bad


def test_the_parent_s_promotion_fails_set_up_at_once():
    """A program that compiles a block a burst size cannot run the cell."""
    from benchmark.kinds import train_stream
    from benchmark.run import Run

    train_stream.require_one_promotion_program(
        types.SimpleNamespace(count_compiles=lambda: Run.count_compiles(None)))
    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine as E

    orig = E.assign_extra_rows

    def by_size(self, words):  # a fresh program a burst size, as the parent
        import jax

        jax.jit(lambda x: x + len(words))(0)
        return orig(self, words)

    E.assign_extra_rows = by_size
    try:
        with pytest.raises(RuntimeError, match="cannot run this cell"):
            train_stream.require_one_promotion_program(types.SimpleNamespace(
                count_compiles=lambda: Run.count_compiles(None)))
    finally:
        E.assign_extra_rows = orig
