"""Kept words a round put in its buffer over the buffer's words
(``stream_round``'s ``fill``, the trainer's own count): median over the
window's live rounds, in percent."""

from benchmark import stream_trace


def read(run):
    return stream_trace.buffer_fill(run)
