"""Percent of the packed-scan runs' device time under no ``glint.``
scope: the guard that a refactor has not dropped a scope."""

from benchmark.program_trace import unscoped_share


def read(run):
    return unscoped_share(run)
