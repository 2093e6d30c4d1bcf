"""One read of the padded table (benchmark/bytes.py) at the chip's peak
HBM bandwidth, over a top-k dispatch's device time."""

from benchmark import bytes as bytes_model
from benchmark.layer_util import hbm_bytes_per_s, topk_seconds


def read(run):
    s = topk_seconds(run)
    peak = hbm_bytes_per_s(run)
    if not s or not peak:
        return None
    m = run.cfg["model"]
    itemsize = 2 if m["table_dtype"] == "bfloat16" else 4
    need = bytes_model.topk_dispatch_bytes(
        run.notes["padded_rows"], m["vector_size"], itemsize)
    return 100.0 * need / peak / s
