"""Device ms a packed step spends drawing its batch: ops under the scopes
``glint.batch`` (window packing, words done, alpha) and ``glint.sample``
(the negative sampler's lookups and masks)."""

from benchmark.program_trace import scope_ms


def read(run):
    return scope_ms(run, "glint.batch", "glint.sample")
