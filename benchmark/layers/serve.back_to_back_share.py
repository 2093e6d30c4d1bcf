"""Percent of the traced window's rounds whose leader was named by the
round before: ``req.dispatch`` spans that carry ``handoff_ms`` over all of
them. How often what arrived during a round rode the next one at once.
None where the program stamps no hand-off."""

from benchmark import handoff_trace


def read(run):
    rounds = handoff_trace.read(run)
    if not rounds:
        return None
    return 100.0 * sum(ms is not None for ms in rounds) / len(rounds)
