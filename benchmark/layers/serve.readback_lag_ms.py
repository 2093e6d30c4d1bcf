"""How long after the device had finished the leader held the result, in
ms: median over the traced rounds of the end of the round's FIRST
``req.result`` (on the trace's clock) less the end of the last run, on the
first device's ``XLA Modules`` line, of a program that began inside that
read-back's ``req.enqueue`` .. ``req.result``. None where the trace has no
program line (the CPU backend's)."""

from benchmark.request_trace import readback_lag_ms


def read(run):
    return readback_lag_ms(run)
