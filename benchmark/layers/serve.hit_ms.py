"""A cache hit as the server sees it, in ms: median, over the sampled
requests whose ``req.accept`` says ``cache == "hit"`` and that began
outside the profiler's window, of ``req.head`` + ``req.accept``: the
request line on the socket to the last byte written. None where the
program records no ``req.head`` (PR 52's parent)."""

from benchmark.request_trace import hit_ms


def read(run):
    return hit_ms(run)
