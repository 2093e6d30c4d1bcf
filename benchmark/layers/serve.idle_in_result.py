"""Percent of the traced window the device idled while a
``glint.req.result`` annotation was open: the device done, the host not
yet holding the answer."""

from benchmark.request_trace import idle_in_result


def read(run):
    return idle_in_result(run)
