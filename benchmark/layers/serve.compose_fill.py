"""Live group rows over the group slots the rounds' composes gathered in the
window, per cent (``/metrics`` ``compose``): what is left is the padding of
the Q bucket and of the groups' width. None where the program has no such
counters."""

from benchmark.layer_util import counter_delta


def read(run):
    if not run.serving_metrics or "compose" not in run.serving_metrics:
        return None
    slots = counter_delta(run, "compose", "group_slots_total")
    rows = counter_delta(run, "compose", "group_rows_total")
    return 100.0 * rows / slots if slots else None
