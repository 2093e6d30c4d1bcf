"""Median ``req.head`` of the sampled cache hits, in ms: ``http.server``'s
parse of the request line and headers, before the handler is entered."""

from benchmark.request_trace import hit_ms


def read(run):
    return hit_ms(run, "req.head")
