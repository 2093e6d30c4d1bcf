"""Device ms a packed step spends under ``glint.compose``, the scope of
what a grouped (subword) centre adds to the step: the group-table look-up,
the runs of equal centres, the masked mean of a group's rows, the sum of a
run's centre gradients. A word-level program has no such scope, and an
older one neither: nothing to read."""

from benchmark.program_trace import scope_ms


def read(run):
    return scope_ms(run, "glint.compose")
