"""Median, over the traced dispatch groups, of (the end of the previous
scan run on the device) less (the end of the ``device_steps`` span that
dispatched this scan, on the trace's clock), in ms. Positive: the
dispatch had returned before the device came free, so the gap is not the
host's Python being late. Negative: the host was late by that much."""

from benchmark import fit_trace


def read(run):
    return fit_trace.read(run, "fit.launch_lead_ms")
