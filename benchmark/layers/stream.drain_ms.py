"""A round's first ``device_steps`` open to its last ``readback_harvest``
close: the packed steps over the buffer, harvested a group at a time: median
over the window's live rounds, in ms."""

from benchmark import stream_trace


def read(run):
    return stream_trace.median_ms(run, "drain")
