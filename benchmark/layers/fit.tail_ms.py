"""The window's close less the end of the last ``readback_harvest``, in
ms: what the window pays after its last dispatch group (the barrier on
pending saves, ``run_end``, the ring's export, the model handed back)."""

from benchmark import fit_trace


def read(run):
    return fit_trace.read(run, "fit.tail_ms")
