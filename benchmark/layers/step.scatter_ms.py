"""Device ms a packed step spends under ``glint.scatter``: forming the
rank-1 payloads and the row scatter-adds into ``syn0`` and ``syn1``."""

from benchmark.program_trace import scope_ms


def read(run):
    return scope_ms(run, "glint.scatter")
