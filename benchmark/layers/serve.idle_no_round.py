"""Percent of the traced window the device idled under neither
``glint.req.dispatch`` nor ``glint.req.grace``: no miss is waiting, the
device has no demand."""

from benchmark.program_trace import idle_share


def read(run):
    return idle_share(run, outside=("glint.req.dispatch", "glint.req.grace"))
