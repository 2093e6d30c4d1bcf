"""Median ``req.dispatch`` span of the traced window, in ms: one coalesced
round as its leader sees it, the device lock held throughout."""

from benchmark.program_trace import median_span_ms


def read(run):
    return median_span_ms(run, "req.dispatch")
