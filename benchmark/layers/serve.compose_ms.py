"""Median ``req.compose`` span of the traced window, in ms: a round's
compose of its out-of-dictionary query words, host n-gram hashing, the one
pull-average and its read-back. None where the program records no such
span."""

from benchmark.program_trace import median_span_ms


def read(run):
    return median_span_ms(run, "req.compose")
