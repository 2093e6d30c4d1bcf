"""The subword CBOW cell ``ft-cbow-300-1m-2mb.train``: CPU, tiny sizes, a
synthetic xplane. Run by hand like its neighbours:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_cbow_subword.py -q
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_benchmark import BENCH, BROKEN, KEYS, ROOT, bench, harness  # noqa: E402
from test_subword import TRACE, _reader, _run  # noqa: E402

CELL = "ft-cbow-300-1m-2mb.train"
NEW = ["step.bag_ms", "bag.input_rows", "cbow_subword_step_roofline"]
SHARED = ["fit.group_ms", "fit.harvest_share", "batcher.pack_fill",
          "step.device_ms", "step.index_ms", "step.batch_ms",
          "step.sample_ms", "step.gather_ms", "step.grads_ms",
          "step.scatter_ms", "step.unscoped_share", "step.compose_ms",
          "step.centre_share", "scatter.distinct_share",
          "scatter.rows_per_slab", "device.idle_share.train",
          "subword.rows_per_center", "cbow.rows_per_bag"]


def test_the_new_names_resolve_to_files():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    config = next(c for c in b["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and config["reduced"] == ["vocab"]
    assert len(config["source"]) <= 200 and "fasttext cbow" in config["source"]
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    m = cfg["model"]
    # the command at the crawl vectors' sizes; the vocabulary cut alone
    assert (m["architecture"], m["vocab"], m["bucket"], m["vector_size"],
            m["min_n"], m["max_n"], m["max_subwords"], m["window"],
            m["negatives"], m["subsample_ratio"], m["unigram_power"],
            m["min_count"]) == (
                "cbow", 1_000_000, 2_000_000, 300, 5, 5, 16, 5, 10, 1e-4,
                0.5, 1)
    assert m["step_size"] in (0.05, 0.025) and m["table_dtype"] == "float32"
    assert cfg["run"] == {"batch_size": 8192, "steps_per_call": 32,
                          "num_shards": 1}
    assert cfg["architecture"] is None and cfg["reduced"] == ["vocab"]
    assert cfg["source"] == config["source"]
    assert "position weights" in cfg["departure"]
    for key in ("deployment", "assumed", "guarantee", "tiny", "reduced_why"):
        assert cfg[key], key
    with open(os.path.join(BENCH, "traffic", CELL + ".json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "train_cbow_subword"
    assert traffic["nominal_words_per_s"] % 10_000 == 0
    # the skip-gram subword cell's text: the same generator and counts
    with open(os.path.join(BENCH, "traffic", "ft-300-1m-2mb.train.json")) as f:
        sibling = json.load(f)
    for key in ("zipf_tokens", "sentence_words", "planted_sentences"):
        assert traffic[key] == sibling[key], key
    assert (m["vocab"] + traffic["zipf_tokens"]
            + 8 * traffic["planted_sentences"]) == 5_640_000
    specs = {s["name"]: s for s in b["per_layer"]}
    for name in NEW:
        assert specs[name]["workloads"] == [CELL]
        assert specs[name]["moves"] == "train_words_per_s"
        assert specs[name]["layer"] == specs["step.compose_ms"]["layer"]
        assert callable(_reader(name).read)
    for name in SHARED + [n for n in specs if n.startswith("fit.")]:
        assert specs[name]["workloads"][-1] == CELL, name
    for name in ("sgns_step_roofline", "subword_step_roofline",
                 "cbow_step_roofline"):
        assert CELL not in specs[name]["workloads"]
    e2e = {s["name"]: s for s in b["end_to_end"]}
    assert e2e["train_words_per_s"]["workloads"][-1] == CELL


def _groups(rng, words, bucket, width):
    groups = words + rng.integers(0, bucket, (words, width)).astype(np.int32)
    groups[np.arange(width)[None, :] > rng.integers(0, width, words)[:, None]] = -1
    groups[:, 0] = np.arange(words)
    return groups


def _batches(rng, words, positions, lanes, steps=4):
    out = []
    for _ in range(steps):
        bags = rng.integers(0, words, (positions, lanes)).astype(np.int32)
        bags[rng.random((positions, lanes)) < 0.4] = -1
        bags[5] = -1  # an empty bag
        bags[7, :2] = 3  # a word twice in one bag
        centres = rng.integers(0, words, positions).astype(np.int32)
        negs = rng.integers(0, words, (positions, 3)).astype(np.int32)
        negs[::7, 0] = centres[::7]  # a noise word equal to the centre
        out.append({"bags": bags, "centres": centres, "negs": negs,
                    "live": (bags >= 0).any(axis=1).astype(np.float32),
                    "alpha": np.float32(0.05)})
    return out


def test_replay_is_the_numpy_transcription_and_the_repos_reference():
    import jax.numpy as jnp

    from benchmark import reference_cbow_subword as reference
    from benchmark import reference_subword
    from glint_word2vec_tpu.ops.cbow_subword_reference import (
        cbow_subword_step,
    )

    rng = np.random.default_rng(0)
    words, bucket, dim = 70, 12, 8
    groups = _groups(rng, words, bucket, 4)  # few buckets: rows are shared
    batches = _batches(rng, words, 96, 6)
    rows0, rows1 = (r[np.r_[True, r[1:] != r[:-1]]]
                    for r in reference.touched_rows(batches, groups))
    assert rows0.max() >= words > rows1.max()  # bucket rows: syn0's alone
    init = rng.normal(0, 0.1, (words + bucket, dim)).astype(np.float32)
    ref0, ref1, ref_losses = reference.replay(
        init[rows0], rows0, rows1, groups, batches)
    syn0, syn1 = init.copy(), np.zeros((words + bucket, dim), np.float32)
    j0, j1 = jnp.asarray(syn0), jnp.asarray(syn1)
    for b, ref_loss in zip(batches, np.asarray(ref_losses)):
        syn0, syn1, loss = reference.cbow_subword_step(
            syn0, syn1, groups, b["bags"], b["centres"], b["live"],
            b["negs"], b["alpha"])
        assert loss == pytest.approx(float(ref_loss), rel=1e-5)
        j0, j1, j_loss = cbow_subword_step(
            j0, j1, jnp.asarray(groups), jnp.asarray(b["bags"]),
            jnp.asarray(b["centres"]), jnp.asarray(b["live"]),
            jnp.asarray(b["negs"]), b["alpha"])
        assert float(j_loss) == pytest.approx(float(ref_loss), rel=1e-6)
    for got in (syn0, np.asarray(j0)):
        np.testing.assert_allclose(
            np.asarray(ref0), got[rows0], rtol=2e-5, atol=2e-7)
    for got in (syn1, np.asarray(j1)):
        np.testing.assert_allclose(
            np.asarray(ref1), got[rows1], rtol=2e-5, atol=2e-7)
    untouched = np.setdiff1d(np.arange(words + bucket), rows0)
    np.testing.assert_array_equal(syn0[untouched], init[untouched])
    # the concatenated input counts a shared row once a word that owns it
    inputs = reference.inputs_of(batches[0]["bags"], groups)
    assert inputs.shape == (96, 6 * 4)
    assert (inputs[7, :4] == inputs[7, 4:8]).all()  # the word twice
    assert (inputs[5] == -1).all()
    # the numbers the kind compares: zero against itself, far in bfloat16
    gaps = reference_subword.table_gaps(
        np.asarray(ref0), ref0, jnp.asarray(init[rows0]), rows0)
    assert gaps == (0.0, 0.0)
    import ml_dtypes

    low = np.asarray(ref0).astype(ml_dtypes.bfloat16).astype(np.float32)
    assert reference_subword.table_gaps(
        low, ref0, jnp.asarray(init[rows0]), rows0)[0] > 1e-3


def test_bytes_of_the_subword_cbow_step():
    from benchmark import bytes_cbow_subword

    got = bytes_cbow_subword.cbow_subword_step_bytes(8192, 10, 300, 45_000)
    assert got["rows"] == 45_000 + 8192 * 11 == 135_112
    assert got["total"] == 3 * 135_112 * 300 * 4  # 486 MB a step
    assert got["scatter"] == 2 * got["gather"]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(trace):
    doc, out = harness(CELL, "--trace", str(trace))
    assert set(doc) == KEYS | ({"breakdown"} if trace else set())
    assert doc["correct"] is True, out
    assert doc["device"]["platform"] == "cpu"  # a rehearsal, never a metric
    assert doc["attempted"] > 0 and doc["failed"] == 0
    b = bench()
    wanted = b["per_layer"] if trace else b["end_to_end"]
    listed = {m["name"] for m in wanted if CELL in m.get("workloads", [CELL])}
    assert set(doc["metrics"]) <= listed
    if not trace:
        assert set(doc["metrics"]) == listed
    else:  # the program's counters need no chip
        words = doc["metrics"]["cbow.rows_per_bag"]["value"]
        rows = doc["metrics"]["subword.rows_per_center"]["value"]
        assert 2 < words <= 10 and 2 < rows <= 16
        # rows a bag: near the product (frequent words are short)
        assert 0.5 * words * rows < doc["metrics"]["bag.input_rows"][
            "value"] < 1.5 * words * rows
        assert 90 < doc["metrics"]["batcher.pack_fill"]["value"] <= 100
    assert "pipeline device_corpus" in out
    assert "0 rows differ from the device's" in out
    assert "rows a bag" in out
    for name in ("groups.rows_differing", "bags.lanes_differing",
                 "bags.counts_differing"):
        assert f"compare {name}: 0 (limit 0) ok" in out, name


def test_the_control_in_lower_precision_is_not_correct():
    doc, out = harness(CELL, "--trace", "0", "--control", "bf16")
    assert doc["correct"] is False, out


def test_a_step_that_leaves_its_state_unchanged_is_not_correct():
    doc, out = harness(CELL, "--trace", "0", prelude=BROKEN["train"])
    assert doc["correct"] is False, out


# The timed path broken where the mechanism is. The program sums each span
# word's group and lets the bags read the sums (``engine._bag_sums``); the
# reference forms every position's concatenated input and takes ONE mean.
BROKEN_BAGS = {
    # a mean of the words' means: each composed word divided by its own
    # count before the bags read it, and the bag's sum by its number of
    # words
    "mean_of_means": """
import jax.numpy as jnp
from glint_word2vec_tpu.parallel import engine
real = engine._bag_sums
def sums(lanes, rows):
    if rows.shape[-1] == 1:  # the words' counts, which stand
        engine._N = [rows]
        return real(lanes, rows)
    n = engine._N[0]
    words = jnp.maximum(real(lanes, jnp.minimum(n, 1.0)), 1.0)
    # what the step divides by the bag's rows to get the mean of the means
    return real(lanes, rows / jnp.maximum(n, 1.0)) * real(lanes, n) / words
engine._bag_sums = sums
""",
    # the gradient divided: every input row takes e / |I|, the mean's true
    # gradient, where Model::update adds the whole of e
    "divided": """
import jax.numpy as jnp
from glint_word2vec_tpu.parallel import engine
real_sums, real_spread = engine._bag_sums, engine._bag_spread
def sums(lanes, rows):
    if rows.shape[-1] == 1:
        engine._SIZE = [jnp.maximum(real_sums(lanes, rows), 1.0)]
    return real_sums(lanes, rows)
def spread(lanes, e, n_rows):
    return real_spread(lanes, e / engine._SIZE[0], n_rows)
engine._bag_sums, engine._bag_spread = sums, spread
""",
}


@pytest.mark.parametrize("fault", sorted(BROKEN_BAGS))
def test_a_timed_path_with_another_mean_or_gradient_is_not_correct(fault):
    doc, out = harness(CELL, "--trace", "0", prelude=BROKEN_BAGS[fault])
    assert doc["correct"] is False, out
    bad = [line.split()[2] for line in out.splitlines() if "NOT OK" in line]
    # the replay is at fault (and a tiny fit whose rows take 1 / 30 of the
    # gradient may not train in its second): the bags and the group table
    # stand
    assert "replay.syn0_gap:" in bad
    assert not [n for n in bad if n.startswith(("bags.", "groups."))], bad


# A program whose subword family still refuses the architecture (the parent
# of ISSUE 39): the kind builds the estimator before anything is made.
REFUSES = """
from glint_word2vec_tpu.models import fasttext
real = fasttext.FastTextParams.validate
def validate(self):
    real(self)
    if self.architecture != "skipgram":
        raise ValueError("the subword family trains skip-gram only")
fasttext.FastTextParams.validate = validate
"""


def test_a_program_that_refuses_the_architecture_is_out_at_once():
    import subprocess

    code = (f"import sys; sys.path.insert(0, {ROOT!r})\n" + REFUSES
            + "import benchmark.run as r\n"
            + f"sys.exit(r.main(['--workload', {CELL!r}, '--seed', '1', "
            "'--seconds', '1', '--trace', '0', '--tiny']))\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 1
    assert "skip-gram only" in p.stdout
    assert "corpus:" not in p.stdout  # refused before anything is made


# The subword scan's trace with the compose scope split as the new scan
# splits it: the group's sum [25, 31], the bags' [31, 35].
BAG_TRACE = TRACE.replace(
    'events { metadata_id: 3 offset_ps: 25000000 duration_ps: 10000000 }',
    'events { metadata_id: 3 offset_ps: 25000000 duration_ps: 6000000 }\n'
    '    events { metadata_id: 8 offset_ps: 31000000 duration_ps: 4000000 }'
).replace(
    "glint.compose/reduce_sum", "glint.compose/group/reduce_sum").replace(
    '  stat_metadata { key: 1',
    '  event_metadata { key: 8 value { id: 8 name: "%fusion.8" stats { '
    'metadata_id: 1 str_value: "jit(local_bag_packed_scan)/shard_map/while/'
    'body/closed_call/glint.compose/bag/add" } } }\n'
    '  stat_metadata { key: 1')


def _bag_run(tmp_path, text, **kw):
    run = _run(tmp_path, text, **kw)
    run.cfg["model"]["negatives"] = 10
    run.training_metrics = {
        "cbow_rows_per_bag": 5.5, "subword_rows_per_center": 6.0,
        "cbow_input_rows_per_bag": 31.0, "subword_rows_per_step": 1500.0}
    return run


def test_the_three_readers_on_a_synthetic_xplane(tmp_path):
    run = _bag_run(tmp_path, BAG_TRACE)
    # 4 us under glint.compose/bag over two steps
    assert _reader("step.bag_ms").read(run) == pytest.approx(2e-3)
    # the outer scope's readers still take compose whole: 10 us, two steps
    assert _reader("step.compose_ms").read(run) == pytest.approx(5e-3)
    assert _reader("step.centre_share").read(run) == pytest.approx(50.0)
    assert _reader("bag.input_rows").read(run) == 31.0
    assert any("bags read each" in line for line in run.said)
    # (1500 + 256 x 11) rows x 3 x 32 x 4 B at 819 GB/s over 50 us a step
    need = 3 * (1500 + 256 * 11) * 32 * 4
    assert _reader("cbow_subword_step_roofline").read(run) == pytest.approx(
        100 * need / 819e9 / 50e-6)


def test_a_program_without_the_scope_or_the_counters_reads_as_nothing(tmp_path):
    # the parent's traces have glint.compose and no inner scope
    run = _bag_run(tmp_path, TRACE)
    assert _reader("step.bag_ms").read(run) is None
    run.training_metrics = {"subword_rows_per_center": 16.0}  # a skip-gram's
    assert _reader("bag.input_rows").read(run) is None
    assert _reader("cbow_subword_step_roofline").read(run) is None
    run = _bag_run(tmp_path, BAG_TRACE, platform="cpu")
    assert _reader("cbow_subword_step_roofline").read(run) is None
    run = _bag_run(tmp_path, BAG_TRACE)
    run.trace = None
    assert _reader("step.bag_ms").read(run) is None
    assert _reader("cbow_subword_step_roofline").read(run) is None
