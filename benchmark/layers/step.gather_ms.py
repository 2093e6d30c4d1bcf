"""Device ms a packed step spends under ``glint.gather``: the row pulls
of centers, contexts and negatives, and the masked mean."""

from benchmark.program_trace import scope_ms


def read(run):
    return scope_ms(run, "glint.gather")
