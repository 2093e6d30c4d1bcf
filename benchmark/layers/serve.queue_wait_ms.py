"""Median ``req.queue`` phase of the sampled request traces in the
recorder ring: enqueue to the coalescer leader's drain."""

import statistics

from benchmark.layer_util import program_spans


def read(run):
    waits = [d for _, d in program_spans(run, "req.queue")]
    return statistics.median(waits) * 1e3 if waits else None
