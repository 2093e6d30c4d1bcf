"""What the host pays around a round's programs, in ms: median over the
traced rounds of ``req.dispatch`` less the device time of the programs
that began inside it (the query rows' pull, the top-k): the launches on
every device of the model's mesh and the read-backs of their results."""

from benchmark.topk_trace import launch_ms


def read(run):
    return launch_ms(run)
