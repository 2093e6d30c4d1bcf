"""``fit.noop_steps_share`` (PR 44): made-up rings with known answers, one
tiny rehearsal. Run by hand like its neighbours:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_noop_steps.py -q
"""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_benchmark import BENCH, bench, harness  # noqa: E402
from test_fit_trace import TRAIN_CELLS  # noqa: E402

NAME = "fit.noop_steps_share"


def _read(harvests):
    from benchmark.run import load_module

    events = [{"name": "readback_harvest", "ph": "X", "ts": 1e3 * i,
               "dur": 500.0, "args": dict(args, packed=True)}
              for i, args in enumerate(harvests)]
    run = types.SimpleNamespace(
        cfg={"run": {"steps_per_call": 32}}, program_spans_path=None,
        program_spans=events)
    return load_module(os.path.join(BENCH, "layers", NAME + ".py")).read(run)


def test_the_name_resolves_and_lists_the_training_cells():
    (spec,) = [m for m in bench()["per_layer"] if m["name"] == NAME]
    assert spec == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_span", "layer": "fit driver",
        "moves": "train_words_per_s",
        "workloads": TRAIN_CELLS + ["ft-cbow-300-1m-2mb.train"]}
    assert bench()["per_layer"][-1] == spec  # appended, nothing moved


@pytest.mark.parametrize("harvests,share", [
    # a parent's spans say the live steps alone
    ([{"n": 32}, {"n": 13}, {"n": 0}], None),
    ([], None),
    # every group stopped at the corpus end: a last group, a phantom one
    ([{"n": 32, "ran": 32}, {"n": 13, "ran": 13}, {"n": 0, "ran": 0}], 0.0),
    # a last group of 13 live steps that ran all 32
    ([{"n": 32, "ran": 32}, {"n": 13, "ran": 32}], 100.0 * 19 / 64),
])
def test_the_share_of_a_made_up_ring(harvests, share):
    assert _read(harvests) == (
        None if share is None else pytest.approx(share))


def test_traced_rehearsal_runs_no_step_that_trains_nothing():
    doc, out = harness("w2v-300-2m.train", "--trace", "1")
    assert doc["correct"] and doc["metrics"][NAME]["value"] == 0.0
