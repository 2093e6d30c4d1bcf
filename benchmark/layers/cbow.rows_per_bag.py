"""Table rows a CBOW bag is the mean of: the live bag slots the packed steps
gathered over the positions they trained, counted on the device
(``training_metrics.cbow_rows_per_bag``). A property of the window, the
shrink draws and the sentences; the ``syn0`` gather and scatter grow with
it. A skip-gram fit, or a program without the count, gives nothing to
read."""


def read(run):
    return (run.training_metrics or {}).get("cbow_rows_per_bag")
