"""Distinct rows the packed steps' scatters wrote over the slabs the slab
writer moved for them (``ops/slab_writer.py``), both tables, over the fit:
the program's own count (``training_metrics.scatter_rows_per_slab``; the
report line gives the two tables apart). 1 = every slab moved for one row;
8 = every f32 tile row written whole. A program without the count (PR 30's
parent), or one whose scatters ran XLA's writer, gives nothing to read."""


def read(run):
    tm = run.training_metrics or {}
    rows = tm.get("scatter_rows_per_slab")
    if rows is None:
        return None
    run.say("scatter: rows written a slab moved, syn0 "
            f"{tm['scatter_rows_per_slab_syn0']:.3f}, syn1 "
            f"{tm['scatter_rows_per_slab_syn1']:.3f}")
    return rows
