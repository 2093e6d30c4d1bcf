"""Median, a dispatch group of the whole fit, of ``readback_harvest`` less
the ``harvest_wait`` inside it, in ms: the host's own work a group. With
``fit.dispatch_ms``, against 32 x ``step.device_ms``, the host's slack."""

from benchmark import fit_trace


def read(run):
    return fit_trace.read(run, "fit.harvest_host_ms")
