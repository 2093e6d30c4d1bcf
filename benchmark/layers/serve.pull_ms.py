"""Median ``req.pull`` span of the traced window, in ms: the round's row
pull, device program and read-back."""

from benchmark.program_trace import median_span_ms


def read(run):
    return median_span_ms(run, "req.pull")
