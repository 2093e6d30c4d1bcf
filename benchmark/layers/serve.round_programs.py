"""Device programs a coalesced round launches: median, over the traced
rounds, of the programs on the first device's ``XLA Modules`` line that
began inside a ``req.dispatch`` span. A round that pulls its query rows
and then scores them reads 2 (3 with a subword compose); one whose top-k
gathers its own rows reads 1 (2). It needs nothing of the program but the
span, so every program that records ``req.dispatch`` is counted alike.
None where the trace has no program line (the CPU backend's) or no round."""

import bisect
import statistics

from benchmark import program_trace


def read(run):
    data = program_trace.read(run)
    rounds = program_trace.ring_spans(run, "req.dispatch")
    if not data or not data["modules"] or not rounds:
        return None
    starts = sorted(s / 1e9 for s, _, _ in data["modules"])
    counts = [bisect.bisect_right(starts, start + dur)
              - bisect.bisect_left(starts, start) for start, dur in rounds]
    counts = [c for c in counts if c]  # a round whose programs the trace caught
    return statistics.median(counts) if counts else None
