"""Percent of a packed step's device time that the centre side owns: self
time under ``glint.compose``, ``glint.gather/syn0`` and
``glint.scatter/syn0`` over the step's device time. What the subword
mechanism costs (a word-level step reads about 5% here). Nothing to read
where the program does not name the gathers' tables (before ISSUE 31)."""

from benchmark import program_trace
from benchmark.layer_util import step_seconds

PARTS = ("glint.compose", "glint.gather/syn0", "glint.scatter/syn0")


def read(run):
    data, step_s = program_trace.read(run), step_seconds(run)
    if not data or not data["scan_runs"] or not step_s:
        return None
    if "glint.gather/syn0" not in data["scope_s"]:
        return None
    steps = data["scan_runs"] * run.cfg["run"]["steps_per_call"]
    parts = {k: 1e3 * data["scope_s"].get(k, 0.0) / steps for k in PARTS}
    run.say("centre side, ms a step: " + ", ".join(
        f"{k} {v:.3f}" for k, v in parts.items()))
    return 100.0 * sum(parts.values()) / (1e3 * step_s)
