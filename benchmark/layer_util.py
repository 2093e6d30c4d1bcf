"""What the per-layer readers share: the program's spans, the counters'
difference over the window, the peaks of the device."""

import json
import os
import re


def program_spans(run, name: str) -> list:
    """(start_s, duration_s) of the program's spans called ``name``, on
    the recorder's clock, oldest first."""
    events = run.program_spans
    if events is None and run.program_spans_path and os.path.exists(
            run.program_spans_path):
        with open(run.program_spans_path) as f:
            events = run.program_spans = json.load(f)["traceEvents"]
    return sorted((e["ts"] / 1e6, e.get("dur", 0.0) / 1e6)
                  for e in events or [] if e["name"] == name
                  and e.get("ph") == "X")


def counter_delta(run, *path):
    """A /metrics counter's growth over the window."""
    def get(doc):
        for k in path:
            doc = doc[k]
        return doc

    return get(run.serving_metrics) - get(run.serving_metrics_before)


def peaks_for(kind: str) -> dict:
    """The published peaks of one chip; an unknown device is an error."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


def hbm_bytes_per_s(run):
    """None on a CPU rehearsal: a share of a chip's peak is a chip's."""
    if run.device["platform"] == "cpu":
        return None
    return peaks_for(run.device["kind"])["hbm_bytes_per_s"]


def module_time(run, pattern):
    """(seconds, runs) of the traced programs whose name matches, per
    device. (0, 0) where the trace has no such program line: the CPU
    backend's has none, and a renamed program has to be named anew."""
    hit = [m for n, m in run.trace["modules"].items() if pattern.search(n)]
    return sum(m["seconds"] for m in hit), sum(m["count"] for m in hit)


def step_seconds(run):
    """Device seconds of one packed SGNS step: the traced runs of the
    packed-scan program, over the steps they ran."""
    if not run.trace:
        return None
    seconds, runs = module_time(run, re.compile(r"packed_scan"))
    return seconds / (runs * run.cfg["run"]["steps_per_call"]) if runs else None


def topk_seconds(run):
    """Device seconds of one top-k dispatch: the traced runs of the top-k
    programs, over their number."""
    if not run.trace:
        return None
    seconds, runs = module_time(run, re.compile(r"top_?k", re.I))
    return seconds / runs if runs else None
