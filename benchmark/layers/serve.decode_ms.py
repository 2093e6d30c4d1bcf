"""Median ``req.decode`` of the traced rounds, in ms: the round's
pure-Python tail, scores and ids on the host to the results set on the
requests."""

from benchmark.request_trace import round_child_ms


def read(run):
    return round_child_ms(run, "req.decode")
