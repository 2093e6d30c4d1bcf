"""Device ms a packed step spends under ``glint.batch``: the window
packing (the span's words and sentence bounds, the shrink draws, the two
compactions of the valid pairs), the words done and alpha. One half of
``step.index_ms``, cut to the same runs."""

from benchmark.program_trace import scope_ms


def read(run):
    return scope_ms(run, "glint.batch")
