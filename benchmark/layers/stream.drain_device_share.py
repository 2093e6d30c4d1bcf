"""The device's busy time inside the traced rounds' drains (first
``device_steps`` open to last ``readback_harvest`` close) over those
drains' length, in percent: what the synchronous harvest, a read-back and a
dispatch between every two groups, leaves of a drain."""

from benchmark import stream_trace


def read(run):
    return stream_trace.drain_device_share(run)
