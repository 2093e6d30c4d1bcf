"""``/metrics`` ``compiles.post_warmup``: query shapes compiled after the
server's warm-up. Must be 0; the run is not correct otherwise."""


def read(run):
    if not run.serving_metrics:
        return None
    return run.serving_metrics["compiles"]["post_warmup"]
