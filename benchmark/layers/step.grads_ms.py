"""Device ms a packed step spends under ``glint.grads``: the dots, the
sigmoid, the coefficients and their data-axis gathers, the loss."""

from benchmark.program_trace import scope_ms


def read(run):
    return scope_ms(run, "glint.grads")
