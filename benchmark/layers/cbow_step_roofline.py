"""The bytes one CBOW step must move (benchmark/bytes_cbow.py: the rows a
bag is the mean of as the program counted them, the rest from shapes) at
the chip's peak HBM bandwidth, over the step's device time."""

from benchmark import bytes_cbow
from benchmark.layer_util import hbm_bytes_per_s, step_seconds


def read(run):
    s = step_seconds(run)
    peak = hbm_bytes_per_s(run)
    per_bag = (run.training_metrics or {}).get("cbow_rows_per_bag")
    if not s or not peak or not per_bag:
        return None
    m, r = run.cfg["model"], run.cfg["run"]
    itemsize = 2 if m["table_dtype"] == "bfloat16" else 4
    need = bytes_cbow.cbow_step_bytes(
        r["batch_size"], m["negatives"], m["vector_size"], per_bag,
        itemsize)["total"]
    return 100.0 * need / peak / s
