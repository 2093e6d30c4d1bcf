"""Promotion and refresh (``stream_promote`` + ``stream_adapt``: candidates
onto spare rows, the noise table built and the keep probabilities re-derived
from the live counts) that ran behind one round's drain: median over the
window's live rounds, in ms."""

from benchmark import stream_trace


def read(run):
    return stream_trace.median_ms(run, "adapt")
