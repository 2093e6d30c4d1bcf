"""Device ms a packed step spends under ``glint.sample``: the negative
sampler's random draws, its look-ups in the alias table and the mask of
negatives that hit their own context. The other half of
``step.index_ms``, cut to the same runs."""

from benchmark.program_trace import scope_ms


def read(run):
    return scope_ms(run, "glint.sample")
