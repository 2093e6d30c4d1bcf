"""Bytes one top-k dispatch over a row-sharded table MUST move on its
fullest chip, from shapes alone: the numerator of ``topk_sharded_roofline``.
Computed bytes, never speeds; nothing here imports the program.

The chips read their shards in parallel, so the least a dispatch can take is
one read of the FULLEST chip's share of the table as it rests: the rows of
a shard (the padded vocabulary over the chips, rounded up), each in whole
lanes of 128 columns (300 rest in 384), whatever Q is and whatever
implements the pass. What the merge moves over the model axis (chips x Q x
k candidates, a few KB) is not counted: it only makes the share smaller.
"""

LANES = 128


def rows_per_shard(rows: int, shards: int) -> int:
    """Rows the fullest of ``shards`` chips holds of ``rows``."""
    return -(-int(rows) // int(shards))


def resting_columns(dim: int) -> int:
    """Columns a row of ``dim`` rests in: whole lanes."""
    return -(-int(dim) // LANES) * LANES


def topk_shard_bytes(rows: int, shards: int, dim: int,
                     itemsize: int = 4) -> int:
    """One read of the fullest chip's share of the resting table."""
    return rows_per_shard(rows, shards) * resting_columns(dim) * int(itemsize)
