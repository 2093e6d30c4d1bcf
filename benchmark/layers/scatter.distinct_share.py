"""Rows the packed steps' scatters wrote over the update slots they were
handed, both tables, over the fit: the program's own count
(``training_metrics.scatter_distinct_share``; the report line gives the two
tables apart). A program without the count (PR 26's parent) gives nothing
to read."""


def read(run):
    tm = run.training_metrics or {}
    share = tm.get("scatter_distinct_share")
    if share is None:
        return None
    run.say("scatter: rows written over slots submitted, syn0 "
            f"{100.0 * tm['scatter_distinct_share_syn0']:.2f}%, syn1 "
            f"{100.0 * tm['scatter_distinct_share_syn1']:.2f}%")
    return 100.0 * share
