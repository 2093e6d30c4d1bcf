"""Percent of the traced window the device idled while a
``glint.harvest_wait`` annotation was open: the host blocked on a group's
outputs, nothing left for it to do, and the device not running."""

from benchmark import fit_trace


def read(run):
    return fit_trace.read(run, "fit.idle_in_wait")
