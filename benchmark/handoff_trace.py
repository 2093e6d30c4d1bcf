"""The hand-off between two coalesced rounds, as the program stamps it.

A round whose leader was named by the round before (requests were pending
when that one ended) carries ``handoff_ms`` on its ``req.dispatch`` ring
span: the ms from the previous round's events being set to this round's
drain of the pending list. A round led by a request that found nobody
leading carries none. The readers take the rounds that began inside the
traced window, as ``serve.round_ms`` reads them; a report line sets the
rounds of the load window outside it beside them, where no Python tracer
stretches the host (``request_trace``'s two windows).

A program that stamps nothing (the parent of PR 53: no ``req.dispatch`` of
the whole ring carries the argument) gives None, and raises nothing.
"""

import statistics

from benchmark import program_trace


def read(run):
    """``handoff_ms``, or None where the round carries none, of every
    ``req.dispatch`` that began inside the traced window, oldest first;
    None where the ring has no round there or never stamps one."""
    if not hasattr(run, "_handoff_trace"):
        run._handoff_trace = _reduce(run)
    return run._handoff_trace


def _reduce(run):
    if not program_trace.ring_spans(run, "req.dispatch"):
        return None  # no trace, no bridge to the ring, or no round
    offset_s = program_trace.read(run)["offset_us"] / 1e6
    rounds = sorted(
        (e["ts"] / 1e6 + offset_s, (e.get("args") or {}).get("handoff_ms"))
        for e in run.program_spans
        if e["name"] == "req.dispatch" and e.get("ph") == "X")
    if all(ms is None for _, ms in rounds):
        return None
    window_s, t0 = run.trace["window_s"], run.trace_t[0]
    inside = [ms for start, ms in rounds if 0 <= start <= window_s]
    outside = [ms for start, ms in rounds
               if run.window[0] - t0 <= start <= run.window[1] - t0
               and not 0 <= start <= window_s]
    for where, some in (("inside", inside), ("outside", outside)):
        handed = [ms for ms in some if ms is not None]
        if handed:
            run.say(f"hand-off: {len(some)} rounds {where} the profiler's "
                    f"window, {len(handed)} handed the lead by the round "
                    f"before, handoff_ms median "
                    f"{statistics.median(handed):.4f}, longest "
                    f"{max(handed):.4f}")
    return inside
