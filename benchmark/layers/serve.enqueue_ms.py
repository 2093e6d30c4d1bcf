"""What the host pays to launch a round's programs, in ms: per traced
round the sum of its ``req.enqueue`` spans (the arguments built, the
jitted call returned; one a program, so two in a round that composes),
median over the rounds."""

from benchmark.request_trace import round_child_ms


def read(run):
    return round_child_ms(run, "req.enqueue")
