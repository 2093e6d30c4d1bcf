"""The position-weighted subword CBOW cell ``ft-cbow-pw-300-1m-2mb.train``:
CPU, tiny sizes, a synthetic xplane. Run by hand like its neighbours:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_cbow_pw_subword.py -q
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_benchmark import BENCH, BROKEN, KEYS, ROOT, bench, harness  # noqa: E402
from test_cbow_subword import BAG_TRACE, _bag_run, _batches, _groups  # noqa: E402
from test_subword import TRACE, _reader  # noqa: E402

CELL = "ft-cbow-pw-300-1m-2mb.train"
SIBLING = "ft-cbow-300-1m-2mb.train"
NEW = ["step.posgrad_ms", "cbow_pw_subword_step_roofline"]


def test_the_new_names_resolve_to_files():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    config = next(c for c in b["configs"] if c["name"] == cell["config"])
    assert b["workloads"][-1] == cell and b["configs"][-1] == config
    assert cell["chips"] == 1 and config["reduced"] == ["vocab"]
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    assert len(cell["why"]) <= 200
    for word in ("1802.06893", "position-weights", "1712.09405"):
        assert word in config["source"], word
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "configs", "ft-cbow-300-1m-2mb.json")) as f:
        sibling = json.load(f)
    # the sibling's model and run, key for key, plus the one parameter
    assert cfg["model"] == dict(sibling["model"], position_weights=True)
    assert cfg["run"] == sibling["run"]
    assert cfg["architecture"] is None and cfg["reduced"] == ["vocab"]
    assert cfg["source"] == config["source"]
    assert "MEAN" in cfg["departure"] and "probe" in cfg["departure"]
    for key in ("deployment", "assumed", "guarantee", "tiny", "reduced_why",
                "equations"):
        assert cfg[key], key
    for key in ("normalisation", "gradient", "posw_start", "posw_dtype"):
        assert cfg["assumed"][key], key
    with open(os.path.join(BENCH, "traffic", CELL + ".json")) as f:
        traffic = json.load(f)
    with open(os.path.join(BENCH, "traffic", SIBLING + ".json")) as f:
        text = json.load(f)
    assert traffic["kind"] == "train_cbow_pw_subword"
    assert traffic["nominal_words_per_s"] % 10_000 == 0
    for key in ("zipf_tokens", "sentence_words", "planted_sentences",
                "replay_groups", "trace_groups"):
        assert traffic[key] == text[key], key
    for key, limit in text["limits"].items():  # the sibling's, unwidened
        assert traffic["limits"][key] == limit, key
    tables = [f"{t}_{g}" for t in ("syn0", "syn1", "posw")
              for g in ("gap", "dnorm_gap")]
    assert set(traffic["limits"]) - set(text["limits"]) == {
        "replay.posw_gap", "replay.posw_dnorm_gap", "seeded.loss_gap",
        *(f"seeded.{name}" for name in tables)}
    for name in tables:  # the seeded replay's, no wider than the replay's
        assert (traffic["limits"][f"seeded.{name}"]
                <= traffic["limits"][f"replay.{name}"]), name
    for name in ("kinds/train_cbow_pw_subword.py",
                 "reference_cbow_pw_subword.py", "bytes_cbow_pw_subword.py"):
        assert os.path.exists(os.path.join(BENCH, name)), name
    specs = {s["name"]: s for s in b["per_layer"]}
    assert [s["name"] for s in b["per_layer"][-2:]] == NEW
    for name in NEW:
        assert specs[name]["workloads"] == [CELL]
        assert specs[name]["moves"] == "train_words_per_s"
        assert specs[name]["layer"] == specs["step.bag_ms"]["layer"]
        assert specs[name]["source"] == "device_trace"
        assert callable(_reader(name).read)
    listed = [n for n, s in specs.items() if SIBLING in s["workloads"]]
    assert len(listed) == 33
    # every reader of the sibling, but its roofline (the sibling's bytes) and
    # ``step.bag_ms`` (with the weights the kernel that forms the positions'
    # gradient is rooted under posgrad, not bag: layers/step.posgrad_ms.py)
    for name in listed:
        if name in ("cbow_subword_step_roofline", "step.bag_ms"):
            assert CELL not in specs[name]["workloads"]
        else:
            assert specs[name]["workloads"][-1] == CELL, name
    e2e = {s["name"]: s for s in b["end_to_end"]}
    assert e2e["train_words_per_s"]["workloads"][-1] == CELL
    assert len(b["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 2


def test_replay_is_the_numpy_transcription():
    from benchmark import reference_cbow_pw_subword as reference

    rng = np.random.default_rng(0)
    words, bucket, dim = 70, 12, 8
    groups = _groups(rng, words, bucket, 4)
    batches = _batches(rng, words, 96, 6)
    rows = np.arange(words + bucket)
    syn0 = rng.normal(0, 0.1, (words + bucket, dim)).astype(np.float32)
    syn1 = rng.normal(0, 0.1, (words + bucket, dim)).astype(np.float32)
    posw = np.ones((6, dim), np.float32)
    got = reference.replay(syn0, rows, rows, groups, batches, syn1_rows=syn1)
    want, losses = (syn0, syn1, posw), []
    for b in batches:
        *want, loss = reference.cbow_pw_step(
            *want, groups, b["bags"], b["centres"], b["live"], b["negs"],
            float(b["alpha"]))
        losses.append(loss)
    for g, w in zip(got[:3], want):
        np.testing.assert_allclose(np.asarray(g), w, rtol=0, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got[3]), losses, rtol=1e-5)
    assert np.abs(want[2] - 1).max() > 1e-5


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(trace):
    doc, out = harness(CELL, "--trace", str(trace))
    assert set(doc) == KEYS | ({"breakdown"} if trace else set())
    assert doc["correct"] is True, out
    assert doc["device"]["platform"] == "cpu"
    for name in ("replay.posw_gap", "replay.posw_dnorm_gap",
                 "seeded.syn0_gap", "seeded.syn1_gap", "seeded.posw_gap",
                 "seeded.loss_gap", "window.posw_not_finite",
                 "replay.syn0_gap",
                 "bags.lanes_differing", "groups.rows_differing"):
        assert f"compare {name}:" in out, name
    assert "position table: 10 rows" in out
    if not trace:
        assert set(doc["metrics"]) == {"train_words_per_s", "setup_s"}


def test_the_control_in_lower_precision_is_not_correct():
    doc, out = harness(CELL, "--trace", "0", "--control", "bf16")
    assert doc["correct"] is False, out


def test_a_step_that_leaves_its_state_unchanged_is_not_correct():
    doc, out = harness(CELL, "--trace", "0", prelude=BROKEN["train"])
    assert doc["correct"] is False, out


# The timed path broken where the position table is. To plant one by hand on
# the chip: paste the prelude before ``import benchmark.run`` in a ``python3
# -c`` that calls ``benchmark.run.main([...])``, as ``harness`` does.
BROKEN_WEIGHTS = {
    # the weights applied to the mirrored lane: lane k reads and trains the
    # row of the lane opposite it
    "mirrored": """
from glint_word2vec_tpu.parallel import engine
rw, rg = engine._lane_weighted, engine._lane_grads
engine._lane_weighted = lambda w, k, x: rw(
    None if w is None else w[::-1], k, x)
def grads(lanes, rows, e):
    total, positions = rg(lanes, rows, e)
    return total[::-1], positions[::-1]
engine._lane_grads = grads
""",
    # the weights never applied to the bags, forward or back (the sibling's
    # bag), while the table still trains by its lane reductions
    "unapplied": """
from glint_word2vec_tpu.parallel import engine
engine._lane_weighted = lambda w, k, x: x
""",
    # posw never updated
    "untrained": """
from glint_word2vec_tpu.parallel import engine
rg = engine._lane_grads
def grads(lanes, rows, e):
    total, positions = rg(lanes, rows, e)
    return 0.0 * total, positions
engine._lane_grads = grads
""",
    # the gradient to posw divided by |I_t|, as a mean's true gradient is
    "divided": """
import jax.numpy as jnp
from glint_word2vec_tpu.parallel import engine
rg, rs = engine._lane_grads, engine._bag_sums
def sums(lanes, rows, weights=None):
    if rows.shape[-1] == 1:  # the words' counts: the bag's size
        engine._SIZE = [jnp.maximum(rs(lanes, rows), 1.0)]
    return rs(lanes, rows, weights)
def grads(lanes, rows, e):
    return rg(lanes, rows, e / engine._SIZE[0])
engine._bag_sums, engine._lane_grads = sums, grads
""",
}


@pytest.mark.parametrize("fault", sorted(BROKEN_WEIGHTS))
def test_a_fault_in_the_position_table_is_not_correct(fault):
    doc, out = harness(CELL, "--trace", "0", prelude=BROKEN_WEIGHTS[fault])
    assert doc["correct"] is False, out
    bad = [line.split()[2] for line in out.splitlines() if "NOT OK" in line]
    # by the limit meant for it; the bags and the group table stand
    assert not [n for n in bad if n.startswith(("bags.", "groups."))], bad
    if fault == "unapplied":
        # from ones the table's gradient is the sound one's and the rows'
        # gap the table's small change; from the seeded table every row of
        # syn0 and syn1 shows it
        assert {"seeded.syn0_gap:", "seeded.syn1_gap:"} <= set(bad), bad
        return
    assert "replay.posw_gap:" in bad
    if fault == "mirrored":
        # from ones, the rows' order alone: nothing else moves; from the
        # seeded table, whose rows differ, every table does
        assert [n for n in bad if n.startswith("replay.")] == [
            "replay.posw_gap:"], bad
        assert {"seeded.syn0_gap:", "seeded.syn1_gap:",
                "seeded.posw_gap:"} <= set(bad), bad


# The parent: a program whose estimator has no such parameter.
PARENT = """
from glint_word2vec_tpu.models import word2vec
real = word2vec.Word2Vec.__init__
def init(self, *a, **kw):
    if "position_weights" in kw:
        raise TypeError("__init__() got an unexpected keyword argument "
                        "'position_weights'")
    real(self, *a, **kw)
word2vec.Word2Vec.__init__ = init
"""


def test_a_program_without_the_parameter_is_out_at_once():
    code = (f"import sys; sys.path.insert(0, {ROOT!r})\n" + PARENT
            + "import benchmark.run as r\n"
            + f"sys.exit(r.main(['--workload', {CELL!r}, '--seed', '1', "
            "'--seconds', '1', '--trace', '0', '--tiny']))\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 1
    assert "position_weights" in p.stdout
    assert "corpus:" not in p.stdout  # refused before anything is made


# The subword CBOW scan's trace with the table's reductions split off the
# bags' adds: the bags [31, 34], the lane reductions [34, 35].
PW_TRACE = BAG_TRACE.replace(
    'events { metadata_id: 8 offset_ps: 31000000 duration_ps: 4000000 }',
    'events { metadata_id: 8 offset_ps: 31000000 duration_ps: 3000000 }\n'
    '    events { metadata_id: 9 offset_ps: 34000000 duration_ps: 1000000 }'
).replace(
    '  stat_metadata { key: 1',
    '  event_metadata { key: 9 value { id: 9 name: "%fusion.9" stats { '
    'metadata_id: 1 str_value: "jit(local_bag_packed_scan)/shard_map/while/'
    'body/closed_call/glint.compose/posgrad/reduce_sum" } } }\n'
    '  stat_metadata { key: 1')


def test_the_two_readers_on_a_synthetic_xplane(tmp_path):
    from benchmark import bytes_cbow_pw_subword as pw_bytes

    run = _bag_run(tmp_path, PW_TRACE)
    # 1 us under glint.compose/posgrad over two steps; the bags keep 3
    assert _reader("step.posgrad_ms").read(run) == pytest.approx(0.5e-3)
    assert _reader("step.bag_ms").read(run) == pytest.approx(1.5e-3)
    assert _reader("step.compose_ms").read(run) == pytest.approx(5e-3)
    # the sibling's rows, and (256 + 266 + 2 x 10) rows of 128 columns read
    # once, at 819 GB/s over 50 us a step
    rows = 3 * (1500 + 256 * 11) * 32 * 4
    extra = (256 + 266 + 20) * 128 * 4
    assert pw_bytes.posgrad_bytes(256, 5, 32) == extra
    assert pw_bytes.posgrad_bytes(8192, 5, 300) == (8192 + 8202 + 20) * 384 * 4
    assert _reader("cbow_pw_subword_step_roofline").read(run) == (
        pytest.approx(100 * (rows + extra) / 819e9 / 50e-6))


def test_a_program_without_the_scope_reads_as_nothing(tmp_path):
    # the parent's trace: glint.compose/bag and no posgrad
    run = _bag_run(tmp_path, BAG_TRACE)
    assert _reader("step.posgrad_ms").read(run) is None
    assert _reader("step.bag_ms").read(run) == pytest.approx(2e-3)
    run = _bag_run(tmp_path, TRACE)
    assert _reader("step.posgrad_ms").read(run) is None
    run.training_metrics = {"subword_rows_per_center": 16.0}
    assert _reader("cbow_pw_subword_step_roofline").read(run) is None
