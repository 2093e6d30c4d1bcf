"""Median distance between the starts of successive ``device_steps`` spans
of the measured fit (the program's spans), in ms: one dispatch group's
cadence as the fit driver sees it."""

import statistics

from benchmark.layer_util import program_spans


def read(run):
    starts = [s for s, _ in program_spans(run, "device_steps")]
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    return statistics.median(gaps) * 1e3 if gaps else None
