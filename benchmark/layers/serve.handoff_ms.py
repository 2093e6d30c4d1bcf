"""Median ``handoff_ms`` of the traced window's rounds that carry one, in
ms: the previous round's events set to this round's drain, which is the
named leader's wake, its turn at the interpreter lock and its taking of
the device lock. None where no round there was handed the lead."""

import statistics

from benchmark import handoff_trace


def read(run):
    handed = [ms for ms in handoff_trace.read(run) or [] if ms is not None]
    return statistics.median(handed) if handed else None
