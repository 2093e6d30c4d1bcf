"""The host filling buffers (``stream_fill``: pull a chunk, count, encode,
keep draws), summed over the slices that ran behind one round's drain: median
over the window's live rounds, in ms. A round's worth in a steady window; over
``stream.drain_ms`` and the host, not the device, sets the pace."""

from benchmark import stream_trace


def read(run):
    return stream_trace.median_ms(run, "fill")
