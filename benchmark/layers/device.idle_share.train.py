"""1 - (union of the device's op intervals) / (the traced window)."""


def read(run):
    if not run.trace:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
