"""The device half of a round, the trainer's ``stream_round`` span: its noise
table installed, its buffer uploaded, its dispatch groups drained (the host
half of later rounds runs behind the drain): median over the window's live
rounds, in ms."""

from benchmark import stream_trace


def read(run):
    return stream_trace.median_ms(run, "round")
