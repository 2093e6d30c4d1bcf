"""One read of the FULLEST chip's share of the resting table
(``benchmark/bytes_topk_sharded.py``) at the chip's peak HBM bandwidth,
over a top-k dispatch's time on its slowest device. The chips read their
shards side by side, so the whole table's bytes over this time
(``topk_roofline``) would pass 100% here."""

from benchmark import bytes_topk_sharded
from benchmark.layer_util import hbm_bytes_per_s
from benchmark.topk_trace import read as read_topk


def read(run):
    data = read_topk(run)
    peak = hbm_bytes_per_s(run)
    if not data or not data["slowest_s"] or not peak:
        return None
    m = run.cfg["model"]
    itemsize = 2 if m["table_dtype"] == "bfloat16" else 4
    need = bytes_topk_sharded.topk_shard_bytes(
        run.notes["padded_rows"], run.notes["shards"], m["vector_size"],
        itemsize)
    return 100.0 * need / peak / data["slowest_s"]
