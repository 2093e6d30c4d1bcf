"""Median duration of the ``device_steps`` spans of the whole fit, in ms:
what the host pays to enqueue one dispatch group."""

from benchmark import fit_trace


def read(run):
    return fit_trace.read(run, "fit.dispatch_ms")
