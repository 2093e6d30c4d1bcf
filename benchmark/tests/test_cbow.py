"""The CBOW cell ``w2v-cbow-300-3m.train``: CPU, tiny sizes, a synthetic
xplane. Run by hand like its neighbours:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_cbow.py -q
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

CELL = "w2v-cbow-300-3m.train"
NEW = ["cbow.rows_per_bag", "cbow_step_roofline"]
SHARED = ["fit.group_ms", "fit.harvest_share", "batcher.pack_fill",
          "step.device_ms", "step.index_ms", "step.batch_ms",
          "step.sample_ms", "step.gather_ms", "step.grads_ms",
          "step.scatter_ms", "step.unscoped_share", "step.compose_ms",
          "step.centre_share", "scatter.distinct_share",
          "scatter.rows_per_slab", "device.idle_share.train"]


def test_the_new_names_resolve_to_files():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    config = next(c for c in b["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and config["reduced"] == []
    assert len(config["source"]) <= 200 and "word2vec.c" in config["source"]
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    m = cfg["model"]
    # the tool's defaults for -cbow 1, at GoogleNews' shape, nothing cut
    assert (m["architecture"], m["vocab"], m["vector_size"], m["window"],
            m["negatives"], m["subsample_ratio"], m["min_count"]) == (
                "cbow", 3_000_000, 300, 5, 5, 1e-3, 1)
    assert m["step_size"] in (0.05, 0.025) and m["table_dtype"] == "float32"
    assert cfg["run"] == {"batch_size": 8192, "steps_per_call": 32,
                          "num_shards": 1}
    assert cfg["architecture"] is None and cfg["reduced"] == []
    for key in ("source", "deployment", "assumed", "guarantee", "tiny"):
        assert cfg[key], key
    with open(os.path.join(BENCH, "traffic", CELL + ".json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "train_cbow"
    assert traffic["nominal_words_per_s"] % 10_000 == 0
    # every word once + the Zipf draws + 8 words a planted sentence
    assert (m["vocab"] - 44 + traffic["zipf_tokens"]
            + 8 * traffic["planted_sentences"]) == 7_639_956
    specs = {s["name"]: s for s in b["per_layer"]}
    for name in NEW:
        assert specs[name]["workloads"] == [CELL]
        assert specs[name]["moves"] == "train_words_per_s"
        assert callable(_reader(name).read)
    for name in SHARED:
        assert specs[name]["workloads"][-1] == CELL, name
    for name in ("sgns_step_roofline", "subword_step_roofline"):
        assert CELL not in specs[name]["workloads"]
    e2e = {s["name"]: s for s in b["end_to_end"]}
    assert e2e["train_words_per_s"]["workloads"][-1] == CELL


def _batches(rng, words, positions, lanes, steps=4):
    out = []
    for _ in range(steps):
        bags = rng.integers(0, words, (positions, lanes)).astype(np.int32)
        bags[rng.random((positions, lanes)) < 0.4] = -1
        bags[5] = -1  # an empty bag
        centres = rng.integers(0, words, positions).astype(np.int32)
        negs = rng.integers(0, words, (positions, 3)).astype(np.int32)
        negs[::7, 0] = centres[::7]  # a noise word equal to the centre
        out.append({"bags": bags, "centres": centres, "negs": negs,
                    "live": (bags >= 0).any(axis=1).astype(np.float32),
                    "alpha": np.float32(0.05)})
    return out


def test_replay_is_the_numpy_transcription_and_the_repos_reference():
    import jax.numpy as jnp

    from benchmark import reference_cbow, reference_subword
    from glint_word2vec_tpu.ops.cbow_reference import cbow_step

    rng = np.random.default_rng(0)
    words, dim = 70, 8
    batches = _batches(rng, words, 96, 6)
    rows0, rows1 = (r[np.r_[True, r[1:] != r[:-1]]]
                    for r in reference_cbow.touched_rows(batches))
    init = rng.normal(0, 0.1, (words, dim)).astype(np.float32)
    ref0, ref1, ref_losses = reference_cbow.replay(
        init[rows0], rows0, rows1, batches)
    syn0, syn1 = init.copy(), np.zeros((words, dim), np.float32)
    j0, j1 = jnp.asarray(syn0), jnp.asarray(syn1)
    for b, ref_loss in zip(batches, np.asarray(ref_losses)):
        syn0, syn1, loss = reference_cbow.cbow_step(
            syn0, syn1, b["bags"], b["centres"], b["live"], b["negs"],
            b["alpha"])
        assert loss == pytest.approx(float(ref_loss), rel=1e-5)
        j0, j1, j_loss = cbow_step(
            j0, j1, jnp.asarray(b["bags"]), jnp.asarray(b["centres"]),
            jnp.asarray(b["live"]), jnp.asarray(b["negs"]), b["alpha"])
        assert float(j_loss) == pytest.approx(float(ref_loss), rel=1e-6)
    for got in (syn0, np.asarray(j0)):
        np.testing.assert_allclose(
            np.asarray(ref0), got[rows0], rtol=2e-5, atol=2e-7)
    for got in (syn1, np.asarray(j1)):
        np.testing.assert_allclose(
            np.asarray(ref1), got[rows1], rtol=2e-5, atol=2e-7)
    # rows no bag holds never move; the whole gradient reaches every row
    # of a bag (a bag of 4 and a bag of 1 move their rows alike)
    untouched = np.setdiff1d(np.arange(words), rows0)
    np.testing.assert_array_equal(syn0[untouched], init[untouched])
    # the numbers the kind compares: zero against itself, far in bfloat16
    gaps = reference_subword.table_gaps(
        np.asarray(ref0), ref0, jnp.asarray(init[rows0]), rows0)
    assert gaps == (0.0, 0.0)
    import ml_dtypes

    low = np.asarray(ref0).astype(ml_dtypes.bfloat16).astype(np.float32)
    assert reference_subword.table_gaps(
        low, ref0, jnp.asarray(init[rows0]), rows0)[0] > 1e-3


def test_bytes_of_the_cbow_step():
    from benchmark import bytes_cbow

    got = bytes_cbow.cbow_step_bytes(8192, 5, 300, 5.5)
    assert got["rows"] == 8192 * (5.5 + 6) == 94_208
    assert got["total"] == 3 * 94_208 * 300 * 4  # 339 MB a step
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
        assert 2 < doc["metrics"]["cbow.rows_per_bag"]["value"] <= 10
        assert 90 < doc["metrics"]["batcher.pack_fill"]["value"] <= 100
    assert "pipeline device_corpus" in out
    assert "rows a bag" in out


def test_the_control_in_lower_precision_is_not_correct():
    doc, out = harness(CELL, "--trace", "0", "--control", "bf16")
    assert doc["correct"] is False, out


def test_a_step_that_leaves_its_state_unchanged_is_not_correct():
    doc, out = harness(CELL, "--trace", "0", prelude=BROKEN["train"])
    assert doc["correct"] is False, out


# The division restored: every row of a bag takes e / |C|, the true gradient
# of the mean, where word2vec.c adds the whole of e. A mask of 1 / |C| a live
# lane leaves the mean what it was (its weights still sum to one) and scales
# each row's share of the gradient.
DIVIDED = """
import jax.numpy as jnp
from glint_word2vec_tpu.ops import device_batching
real = device_batching.bag_window_batch
def divided(*a, **k):
    centres, bags, mask, live = real(*a, **k)
    count = jnp.maximum(mask.sum(axis=1, keepdims=True), 1.0)
    return centres, bags, mask / count, live
device_batching.bag_window_batch = divided
"""


def test_a_timed_path_with_the_division_restored_is_not_correct():
    doc, out = harness(CELL, "--trace", "0", prelude=DIVIDED)
    assert doc["correct"] is False, out
    assert "compare replay.syn0_gap" in out
    # the input side is at fault (syn1 follows it from the second step on)
    bad = [line for line in out.splitlines() if "NOT OK" in line]
    assert any("replay.syn0_gap" in line for line in bad), bad


# A wrong bag reaches the program and the redraw alike (both call the
# program's bag function), so the reference follows it on both sides and the
# replay's gaps read nothing: the window rule enumerated in numpy from the
# view's sentence offsets and the draws read back is what holds the bags.
WRONG_BAGS = {
    # every position in one sentence: a bag reaches past its sentence's end
    "past_sentence": """
import jax.numpy as jnp
from glint_word2vec_tpu.ops import device_batching
real = device_batching.bag_window_batch
def one_sentence(ids, sent_of, *a, **k):
    return real(ids, jnp.zeros_like(sent_of), *a, **k)
device_batching.bag_window_batch = one_sentence
""",
    # a reach one short on the right: the skip-gram stream's half-open
    # [-b, b) where word2vec.c's window is closed
    "half_open": """
import jax.numpy as jnp
from glint_word2vec_tpu.ops import device_batching
real = device_batching.bag_window_batch
def half_open(*a, window, **k):
    centres, bags, mask, live = real(*a, window=window, **k)
    b = device_batching.grid_window_shrink(
        a[3], a[2] + jnp.arange(k["batch"]), k["grid_batch"], a[4], window)
    lane = jnp.arange(2 * window)[None, :] - window + 1  # offsets 1..W at W..
    keep = lane != (window - b)[:, None]
    mask = mask * keep
    return (centres, jnp.where(mask > 0, bags, -1), mask,
            (mask.sum(axis=1) > 0).astype(live.dtype))
device_batching.bag_window_batch = half_open
""",
}


@pytest.mark.parametrize("fault", sorted(WRONG_BAGS))
def test_a_wrong_bag_is_not_correct_though_both_sides_are_fed_it(fault):
    doc, out = harness(CELL, "--trace", "0", prelude=WRONG_BAGS[fault])
    assert doc["correct"] is False, out
    bad = [line.split()[2] for line in out.splitlines() if "NOT OK" in line]
    # the bags alone are at fault: the replay follows them on both sides
    assert bad == ["bags.lanes_differing:", "bags.counts_differing:"], bad


def test_the_enumeration_is_word2vec_cs_window():
    from benchmark.kinds.train_cbow import enumerate_bags

    # two sentences, [0, 5) and [5, 8), the view's live words end at 7
    words = np.arange(100, 100 + 6 + 2, dtype=np.int32)
    soffs = np.array([0, 5, 8, 8])
    shrink = np.array([0, 1, 0, 1, 0, 0], np.int32)
    centres, bags = enumerate_bags(words, soffs, 7, shrink, window=2)
    # lanes -2, -1, +1, +2; reach 2 - b
    np.testing.assert_array_equal(centres, [100, 101, 102, 103, 104, 105])
    np.testing.assert_array_equal(bags, [
        [-1, -1, 101, 102],      # sentence start
        [-1, 100, 102, -1],      # reach 1
        [100, 101, 103, 104],
        [-1, 102, 104, -1],
        [102, 103, -1, -1],      # sentence end: 105 is the next one's
        [-1, -1, 106, -1],       # next sentence; 107 lies past the live end
    ])


NO_ARCHITECTURE = """
from glint_word2vec_tpu.utils import params
real = params.Word2VecParams.replace
def replace(self, **kw):
    if "architecture" in kw:
        raise TypeError("__init__() got an unexpected keyword argument "
                        "'architecture'")
    return real(self, **kw)
params.Word2VecParams.replace = replace
"""


def test_a_program_without_the_architecture_is_refused_at_once():
    import subprocess

    code = (f"import sys; sys.path.insert(0, {ROOT!r})\n" + NO_ARCHITECTURE
            + "import benchmark.run as r\n"
            + f"sys.exit(r.main(['--workload', {CELL!r}, '--seed', '1', "
            "'--seconds', '1', '--trace', '0', '--tiny']))\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 1
    assert "architecture" in p.stdout
    assert "corpus:" not in p.stdout  # refused before anything is made


def _cbow_run(tmp_path, text, **kw):
    run = _run(tmp_path, text, **kw)
    run.training_metrics = {"cbow_rows_per_bag": 5.5}
    return run


def test_the_two_readers_on_a_synthetic_xplane(tmp_path):
    run = _cbow_run(tmp_path, TRACE)
    assert _reader("cbow.rows_per_bag").read(run) == 5.5
    # 256 positions x (5.5 + 1 + 5) rows x 3 x 32 x 4 B at 819 GB/s over
    # 50 us a step
    need = 3 * 256 * 11.5 * 32 * 4
    assert _reader("cbow_step_roofline").read(run) == pytest.approx(
        100 * need / 819e9 / 50e-6)
    # the subword cell's readers find the bag's scopes where they found a
    # group's: compose 10 us over two steps; compose + syn0 of 100 us
    assert _reader("step.compose_ms").read(run) == pytest.approx(5e-3)
    assert _reader("step.centre_share").read(run) == pytest.approx(50.0)


def test_a_program_without_the_counter_reads_as_nothing(tmp_path):
    run = _cbow_run(tmp_path, TRACE)
    run.training_metrics = {"subword_rows_per_center": 16.0}  # a skip-gram's
    for name in NEW:
        assert _reader(name).read(run) is None, name
    run = _cbow_run(tmp_path, TRACE, platform="cpu")
    assert _reader("cbow_step_roofline").read(run) is None  # a chip's peak
    run = _cbow_run(tmp_path, TRACE)
    run.trace = None
    assert _reader("cbow_step_roofline").read(run) is None
