"""``serve.round_programs``: the programs a round launches, counted on a
synthetic xplane. Run by hand like its neighbours:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_round_programs.py -q
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_benchmark import bench  # noqa: E402
from test_subword import _reader  # noqa: E402
from test_synonyms_sharded import TRACE, _run  # noqa: E402

ROUND = [{"name": "req.dispatch", "ph": "X", "ts": 1090.0, "dur": 170.0}]
PULL = ('    events { metadata_id: 7 offset_ps: 100000000 '
        'duration_ps: 2000000 }\n')


def test_a_round_that_pulls_and_then_scores_reads_two(tmp_path):
    assert _reader("serve.round_programs").read(
        _run(tmp_path, TRACE, ROUND)) == 2


def test_a_round_whose_top_k_gathers_its_own_rows_reads_one(tmp_path):
    assert PULL in TRACE
    assert _reader("serve.round_programs").read(
        _run(tmp_path, TRACE.replace(PULL, ""), ROUND)) == 1


def test_no_round_or_no_trace_reads_nothing(tmp_path):
    run = _run(tmp_path, TRACE, [])
    assert _reader("serve.round_programs").read(run) is None
    # a round the trace caught no program of: before the first one began
    run = _run(tmp_path, TRACE, [
        {"name": "req.dispatch", "ph": "X", "ts": 1010.0, "dur": 20.0},
        {"name": "req.dispatch", "ph": "X", "ts": 1090.0, "dur": 170.0}])
    assert _reader("serve.round_programs").read(run) == 2
    run.trace = None
    run._program_trace = None
    assert _reader("serve.round_programs").read(run) is None


def test_the_metric_is_declared_for_the_three_served_cells():
    b = bench()
    spec = next(m for m in b["per_layer"]
                if m["name"] == "serve.round_programs")
    assert spec["workloads"] == [
        w["name"] for w in b["workloads"] if w["traffic"] == "synonyms"]
    assert (spec["layer"], spec["source"], spec["moves"], spec["better"]) == (
        "serving host", "device_trace", "synonyms_p95_ms", "lower")
