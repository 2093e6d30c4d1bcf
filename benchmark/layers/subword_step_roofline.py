"""The bytes one packed subword step must move (benchmark/bytes_subword.py:
the rows a centre is the mean of as the program counted them, the rest from
shapes) at the chip's peak HBM bandwidth, over the step's device time."""

from benchmark import bytes_subword
from benchmark.layer_util import hbm_bytes_per_s, step_seconds


def read(run):
    s = step_seconds(run)
    peak = hbm_bytes_per_s(run)
    per_center = (run.training_metrics or {}).get("subword_rows_per_center")
    if not s or not peak or not per_center:
        return None
    m, r = run.cfg["model"], run.cfg["run"]
    itemsize = 2 if m["table_dtype"] == "bfloat16" else 4
    need = bytes_subword.subword_step_bytes(
        r["batch_size"], m["window"], m["negatives"], m["vector_size"],
        per_center, itemsize)["total"]
    return 100.0 * need / peak / s
