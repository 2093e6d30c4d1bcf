"""Share of the window the fit thread spent inside ``readback_harvest``
spans, blocked on the device's results: high means device-bound."""

from benchmark.layer_util import program_spans


def read(run):
    spans = program_spans(run, "readback_harvest")
    if not spans or not run.window:
        return None
    return 100.0 * sum(d for _, d in spans) / (run.window[1] - run.window[0])
