"""Table rows a subword centre is the mean of: the live group ids the
packed steps gathered over the centres they formed, counted on the device
(``training_metrics.subword_rows_per_center``). A property of the text and
of the n-gram range; the ``syn0`` gather and scatter grow with it. A
word-level fit, or a program without the count, gives nothing to read."""


def read(run):
    return (run.training_metrics or {}).get("subword_rows_per_center")
