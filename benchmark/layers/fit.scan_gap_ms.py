"""Median, over the traced dispatch groups, of the ms on the first device
from the end of one run of the packed scan to the start of the next: the
host's gap a group, measured where ``fit.group_ms`` less 32 x
``step.device_ms`` reckons it."""

from benchmark import fit_trace


def read(run):
    return fit_trace.read(run, "fit.scan_gap_ms")
