"""Device ms a packed step spends under ``glint.exchange``: the model-axis
all-reduces of the rows it pulls (``engine._pull_rows``). A program without
the scope (PR 27's parent, which files them under ``glint.gather``) or a
mesh without the axis gives nothing to read."""

from benchmark.program_trace import scope_ms


def read(run):
    return scope_ms(run, "glint.exchange")
