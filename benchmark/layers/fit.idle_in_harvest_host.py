"""Percent of the traced window the device idled under
``glint.readback_harvest`` and not ``glint.harvest_wait``: the host's own
read-back and accounting. With ``fit.idle_in_dispatch``,
``fit.idle_in_wait`` and the two rests on the report line it adds up to
``device.idle_share.train``."""

from benchmark import fit_trace


def read(run):
    return fit_trace.read(run, "fit.idle_in_harvest_host")
