"""Table rows a subword CBOW bag's mean is over: the sum of the group sizes
of a bag's words, over the positions trained, counted on the device
(``training_metrics.cbow_input_rows_per_bag``): fastText's ``input.size()``.
Over ``subword.rows_per_center`` x ``cbow.rows_per_bag`` it says nothing;
against the group rows a step gathers it is the reuse, how many bags read
each composed word. A word-level fit, a skip-gram fit, or a program without
the count, gives nothing to read."""


def read(run):
    tm = run.training_metrics or {}
    rows, gathered = (tm.get("cbow_input_rows_per_bag"),
                      tm.get("subword_rows_per_step"))
    if rows and gathered:
        run.say(f"bag input: {rows} rows a bag; "
                f"{rows * run.cfg['run']['batch_size'] / gathered:.3f} "
                f"bags read each of the {gathered} group rows a step "
                f"gathers (an upper bound: positions trained <= slots)")
    return rows
