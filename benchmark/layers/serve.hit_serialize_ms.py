"""Median ``req.serialize`` of the sampled cache hits, in ms: the JSON
body, the headers and the two writes to the socket."""

from benchmark.request_trace import hit_ms


def read(run):
    return hit_ms(run, "req.serialize")
