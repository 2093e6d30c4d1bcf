"""Percent of the traced window the device idled while a
``glint.device_steps`` annotation was open: the host enqueueing a group."""

from benchmark import fit_trace


def read(run):
    return fit_trace.read(run, "fit.idle_in_dispatch")
