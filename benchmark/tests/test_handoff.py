"""``benchmark/handoff_trace.py`` and its two readers (PR 53): a synthetic
ring over ``test_request_trace``'s xplane. Run by hand like its neighbours:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_handoff.py -q
"""

import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_benchmark import bench  # noqa: E402
from test_request_trace import RING, TRACE, _run, _span  # noqa: E402
from test_subword import _reader  # noqa: E402

NEW = ["serve.back_to_back_share", "serve.handoff_ms"]

# The profiler's window is [0, 300] us of the trace, [1,000, 1,300] of the
# ring. RING's one round, at 1,090, was led by a request that found nobody
# leading. Three more inside the window, two of them handed the lead; one
# before it (the cache's warm-up) and one after it, both handed.
HANDED = RING + [
    _span("req.dispatch", 1010.0, 30.0, batch=1, handoff_ms=0.9),
    _span("req.dispatch", 1050.0, 30.0, batch=3),
    _span("req.dispatch", 1262.0, 30.0, batch=5, handoff_ms=0.3),
    _span("req.dispatch", 500.0, 30.0, batch=16, handoff_ms=7.0),
    _span("req.dispatch", 1400.0, 30.0, batch=4, handoff_ms=9.0),
]


def test_the_readers_take_the_rounds_of_the_traced_window(tmp_path):
    run = _run(tmp_path, TRACE, HANDED)
    assert _reader("serve.back_to_back_share").read(run) == 50.0
    assert _reader("serve.handoff_ms").read(run) == pytest.approx(0.6)
    # the report: the load window is [-1,000, 2,000] us of the trace
    assert [s for s in run.said if s.startswith("hand-off")] == [
        "hand-off: 4 rounds inside the profiler's window, 2 handed the "
        "lead by the round before, handoff_ms median 0.6000, longest 0.9000",
        "hand-off: 2 rounds outside the profiler's window, 2 handed the "
        "lead by the round before, handoff_ms median 8.0000, longest 9.0000"]


def test_rounds_that_all_found_nobody_leading_read_zero_and_no_median(
        tmp_path):
    ring = copy.deepcopy(HANDED)
    for e in ring:
        if 1000.0 <= e["ts"] <= 1300.0:
            e["args"].pop("handoff_ms", None)
    run = _run(tmp_path, TRACE, ring)
    assert _reader("serve.back_to_back_share").read(run) == 0.0
    assert _reader("serve.handoff_ms").read(run) is None


def test_a_program_that_stamps_nothing_gives_both_nothing(tmp_path):
    """PR 53's parent with this benchmark laid over it."""
    run = _run(tmp_path, TRACE, RING)
    for name in NEW:
        assert _reader(name).read(run) is None, name
    # no round in the window; no ring; no trace
    run = _run(tmp_path, TRACE, [e for e in HANDED
                                 if not 1000.0 <= e["ts"] <= 1300.0])
    for name in NEW:
        assert _reader(name).read(run) is None, name
    run = _run(tmp_path, TRACE, [])
    for name in NEW:
        assert _reader(name).read(run) is None, name
    run.trace = None
    for name in NEW:
        assert _reader(name).read(run) is None, name


def test_the_two_are_declared_for_the_three_served_cells():
    b = bench()
    cells = [w["name"] for w in b["workloads"] if w["traffic"] == "synonyms"]
    assert [m["name"] for m in b["per_layer"][-2:]] == NEW
    for spec, better, unit in zip(b["per_layer"][-2:], ("higher", "lower"),
                                  ("%", "ms")):
        assert spec == {"name": spec["name"], "unit": unit, "better": better,
                        "source": "program_span", "layer": "serving host",
                        "moves": "synonyms_p95_ms", "workloads": cells}
