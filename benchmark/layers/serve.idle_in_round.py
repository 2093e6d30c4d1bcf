"""Percent of the traced window the device idled while a
``glint.req.dispatch`` annotation was open: the leader holds the device
lock and the device waits for the host."""

from benchmark.program_trace import idle_share


def read(run):
    return idle_share(run, under=("glint.req.dispatch",))
