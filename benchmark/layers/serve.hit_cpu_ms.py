"""The handler thread's own CPU time in a cache hit, in ms: MEAN ``cpu_ms``
of those requests' ``req.accept`` (``time.thread_time()`` at entry and
exit; the mean, because the chip's host charges thread CPU time by the
scheduler's tick and a single reading is 0 or a whole tick). The mean
``req.accept`` less this is the time the thread did not run: the
interpreter lock, the coalescer's lock, the socket."""

from benchmark.request_trace import hit_cpu_ms


def read(run):
    return hit_cpu_ms(run)
