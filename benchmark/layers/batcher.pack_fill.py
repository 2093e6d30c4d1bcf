"""Live pairs over dispatched pair slots of the packed steps: the program's
own count (``training_metrics.packed_mask_density``)."""


def read(run):
    tm = run.training_metrics or {}
    fill = tm.get("packed_mask_density")
    return None if fill is None else 100.0 * fill
