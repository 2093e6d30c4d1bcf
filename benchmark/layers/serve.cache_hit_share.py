"""Result-cache hits over lookups in the window (``/metrics``
``synonym_cache``): the share of requests that never reach the device."""

from benchmark.layer_util import counter_delta


def read(run):
    if not run.serving_metrics:
        return None
    hits = counter_delta(run, "synonym_cache", "hits")
    misses = counter_delta(run, "synonym_cache", "misses")
    return 100.0 * hits / (hits + misses) if hits + misses else None
