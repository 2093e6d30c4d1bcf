"""A round's buffer put on the device (``upload_corpus``):
median over the window's live rounds, in ms."""

from benchmark import stream_trace


def read(run):
    return stream_trace.median_ms(run, "upload")
