"""Ms a round's leader spends in ``req.grace`` (the straggler-absorbing
sleeps): seconds inside the spans over the rounds of the traced window.
0 where rounds ran and none slept; None where the program has no such
span (no ``req.pull`` either)."""

from benchmark.program_trace import ring_spans


def read(run):
    rounds = ring_spans(run, "req.dispatch")
    if not rounds or not ring_spans(run, "req.pull"):
        return None
    return 1e3 * sum(d for _, d in ring_spans(run, "req.grace")) / len(rounds)
