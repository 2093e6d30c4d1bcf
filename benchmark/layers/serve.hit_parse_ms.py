"""Median ``req.parse`` of the sampled cache hits, in ms: the body's read
off the socket and its ``json.loads``."""

from benchmark.request_trace import hit_ms


def read(run):
    return hit_ms(run, "req.parse")
