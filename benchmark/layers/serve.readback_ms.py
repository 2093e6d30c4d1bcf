"""A round's read-backs, in ms: per traced round the sum of its
``req.result`` spans (from the launch's return to the results held by the
host: the device's run, the transfer, the leader's turn at the interpreter
lock), median over the rounds."""

from benchmark.request_trace import round_child_ms


def read(run):
    return round_child_ms(run, "req.result")
