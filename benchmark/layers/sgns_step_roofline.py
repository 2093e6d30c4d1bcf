"""The bytes one packed step must move (benchmark/bytes.py, from shapes
alone) at the chip's peak HBM bandwidth, over the step's device time."""

from benchmark import bytes as bytes_model
from benchmark.layer_util import hbm_bytes_per_s, step_seconds


def read(run):
    s = step_seconds(run)
    peak = hbm_bytes_per_s(run)
    if not s or not peak:
        return None
    m, r = run.cfg["model"], run.cfg["run"]
    itemsize = 2 if m["table_dtype"] == "bfloat16" else 4
    need = bytes_model.packed_step_bytes(
        r["batch_size"], m["window"], m["negatives"], m["vector_size"],
        itemsize)["total"]
    return 100.0 * need / peak / s
