"""Median number of OTHER program runs (``XLA Modules`` events) that start
between two runs of the packed scan on the first device: what a dispatch
group launches besides its scan. The report lines name each."""

from benchmark import fit_trace


def read(run):
    return fit_trace.read(run, "fit.gap_programs")
