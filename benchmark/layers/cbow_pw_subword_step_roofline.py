"""The bytes one subword CBOW step with position weights must move
(benchmark/bytes_cbow_pw_subword.py: the sibling cell's count, the group rows
a step gathers as the program counted them, plus one read of the step's
gradient and of the span's composed words and the position table read and
written) at the chip's peak HBM bandwidth, over the step's device time. Bound
by bytes."""

from benchmark import bytes_cbow_pw_subword
from benchmark.layer_util import hbm_bytes_per_s, step_seconds


def read(run):
    s = step_seconds(run)
    peak = hbm_bytes_per_s(run)
    group_rows = (run.training_metrics or {}).get("subword_rows_per_step")
    if not s or not peak or not group_rows:
        return None
    m, r = run.cfg["model"], run.cfg["run"]
    itemsize = 2 if m["table_dtype"] == "bfloat16" else 4
    need = bytes_cbow_pw_subword.cbow_pw_subword_step_bytes(
        r["batch_size"], m["negatives"], m["window"], m["vector_size"],
        group_rows, itemsize)["total"]
    return 100.0 * need / peak / s
