"""Median ``req.wake`` of the sampled misses that began outside the
profiler's window, in ms: a finished answer waiting for its handler
thread to run (the leader's stamp before it sets the batch's events to the
waiter's return from its wait)."""

from benchmark.request_trace import wake_ms


def read(run):
    return wake_ms(run)
