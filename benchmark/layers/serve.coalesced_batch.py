"""Mean size of the coalesced /synonyms dispatches in the window, from
``/metrics`` ``coalesced_batch_sizes`` (a histogram of counts)."""


def read(run):
    if not run.serving_metrics:
        return None
    after = run.serving_metrics["coalesced_batch_sizes"]
    before = run.serving_metrics_before["coalesced_batch_sizes"]
    n = {int(k): v - before.get(k, 0) for k, v in after.items()}
    total = sum(n.values())
    return sum(k * v for k, v in n.items()) / total if total else None
