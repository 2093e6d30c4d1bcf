"""Median ``req.lookup`` of the sampled cache hits, in ms: the coalescer's
cache probe, the wait for its lock included: a hit's only work there."""

from benchmark.request_trace import hit_ms


def read(run):
    return hit_ms(run, "req.lookup")
