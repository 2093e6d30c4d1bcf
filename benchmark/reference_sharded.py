"""The plain reference of a configuration whose tables no one chip holds
(``w2v-300-10m-x4``). Nothing here imports the program.

The same equations as ``reference.sgns_step`` (every update from the
pre-step rows, duplicates summed, a negative equal to its context skipped,
masked mean loss), in plain ``jax.numpy`` float32 with contractions at
``highest``, over the rows the replayed steps touch. ``reference.sgns_replay``
keeps those rows on the first device; at this size that is 4.4 GB a table
carried through a scan beside its temporaries, and it does not fit. Here they
are split by rows over the cell's devices from the draw of the seed's table to
the last comparison, and never stand whole on one device.

The partitioning is XLA's own: ``jit`` with ``NamedSharding``s over a mesh of
one axis, ``rows``. The compiler turns a gather from the split rows into each
device's own rows plus an all-reduce, and a scatter-add into each device's own
writes. The program under test is a ``shard_map`` with hand-written
collectives; the two share the chip's compiler and nothing else.
"""

import math

import numpy as np

from benchmark.reference import touched_rows  # noqa: F401  (the kind's)

PIECE = 1 << 17  # rows of one piece of a comparison, at most


def _mesh(devices):
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices), ("rows",))


def _by_rows(mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec("rows", None))


def _replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def _piece(n_rows: int, n_devices: int) -> int:
    """Rows of one piece: a divisor of a device's share, so that no piece
    lies across two devices."""
    if n_rows % n_devices:
        raise ValueError(f"{n_rows} rows do not split over {n_devices} "
                         "devices")
    return math.gcd(n_rows // n_devices, PIECE)


def shard_rows(host_rows: np.ndarray, devices):
    """A host array of rows, split by rows over ``devices``."""
    import jax

    return jax.device_put(np.asarray(host_rows, np.float32),
                          _by_rows(_mesh(devices)))


def seed_rows(seed: int, vocab: int, dim: int, rows: np.ndarray, devices):
    """Rows ``rows`` of the table the configuration starts from (word2vec's
    standard init, syn0 ~ U[-0.5/d, 0.5/d) from ``PRNGKey(seed)``; syn1 is
    zero), split by rows over ``devices``: device k holds rows[k * R / n :
    (k + 1) * R / n]. The whole table is drawn split the same way; each
    piece of ``rows`` is gathered from it (the compiler's all-reduce leaves
    the piece on every device), the owner keeps its copy, and the result
    is put together from what each device kept."""
    import jax
    import jax.numpy as jnp

    mesh = _mesh(devices)
    n = len(devices)
    padded = -(-vocab // n) * n
    full = jax.jit(
        lambda key: jnp.pad(
            (jax.random.uniform(key, (vocab, dim), dtype=jnp.float32) - 0.5)
            / dim, ((0, padded - vocab), (0, 0))),
        out_shardings=_by_rows(mesh),
    )(jax.random.PRNGKey(int(seed)))
    take = jax.jit(lambda t, i: t[i], out_shardings=_replicated(mesh))
    piece = _piece(rows.size, n)
    share = rows.size // n
    kept = []
    for k, dev in enumerate(devices):
        parts = []
        for s in range(k * share, (k + 1) * share, piece):
            got = take(full, jnp.asarray(rows[s:s + piece], jnp.int32))
            parts.append(next(x.data for x in got.addressable_shards
                              if x.device == dev))
        kept.append(parts[0] if len(parts) == 1 else jnp.concatenate(parts))
    full.delete()
    return jax.make_array_from_single_device_arrays(
        (rows.size, dim), _by_rows(mesh), kept)


def sgns_replay(syn0, rows: np.ndarray, batches):
    """Follow ``batches`` from ``syn0`` (the seed's rows ``rows``, split by
    rows; it is left as it was) and a zero syn1. Returns (syn0, syn1, [loss
    per step]), the tables split as ``syn0`` is."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    by_rows = syn0.sharding
    rep = _replicated(by_rows.mesh)

    def step(tables, b):
        syn0, syn1 = tables
        centers, contexts, mask, negs, alpha = b
        h, u_pos, u_neg = syn0[centers], syn1[contexts], syn1[negs]
        f_pos = jnp.einsum("pd,pd->p", h, u_pos, precision=hi)
        f_neg = jnp.einsum("pd,pnd->pn", h, u_neg, precision=hi)
        nmask = (negs != contexts[:, None]).astype(jnp.float32) * mask[:, None]
        c_pos = alpha * (1.0 - jax.nn.sigmoid(f_pos)) * mask
        c_neg = -alpha * jax.nn.sigmoid(f_neg) * nmask
        pair_loss = -jax.nn.log_sigmoid(f_pos) * mask - (
            jax.nn.log_sigmoid(-f_neg) * nmask).sum(axis=1) * mask
        loss = pair_loss.sum() / jnp.maximum(mask.sum(), 1.0)
        d_center = c_pos[:, None] * u_pos + jnp.einsum(
            "pn,pnd->pd", c_neg, u_neg, precision=hi)
        syn1 = syn1.at[contexts].add(c_pos[:, None] * h)
        syn1 = syn1.at[negs.reshape(-1)].add(
            (c_neg[:, :, None] * h[:, None, :]).reshape(-1, h.shape[1]))
        syn0 = syn0.at[centers].add(d_center)
        return (syn0, syn1), loss

    stacked = tuple(
        jnp.asarray(np.stack([
            np.searchsorted(rows, b[k]).astype(np.int32)
            if k in ("centers", "contexts", "negs")
            else np.asarray(b[k], np.float32) for b in batches]))
        for k in ("centers", "contexts", "mask", "negs", "alpha"))
    # The tables are donated: the scan carries them in place. ``syn0`` is
    # wanted again for the comparison, so its copy is what is given away.
    start = jax.jit(lambda s0: (s0 + 0.0, jnp.zeros_like(s0)),
                    out_shardings=(by_rows, by_rows))(syn0)
    (out0, out1), losses = jax.jit(
        lambda s0, s1, bs: jax.lax.scan(step, (s0, s1), bs),
        in_shardings=(by_rows, by_rows, rep),
        out_shardings=((by_rows, by_rows), rep),
        donate_argnums=(0, 1),
    )(*start, jax.device_put(stacked, rep))
    return out0, out1, losses


def table_stats(prog, ref, init, valid: np.ndarray) -> np.ndarray:
    """(rows, 4) float64: for each row, the largest |prog - ref|, the
    largest |ref - init|, the sum of (prog - init)**2 and that of (ref -
    init)**2; zeros where ``valid`` is false. A row is reduced on the device
    that holds it, in float32 over its ``dim`` entries; the rows are put
    together on the host in float64, so that a norm over a billion squares
    is not quantised by one float32 sum. ``init`` None is a zero table."""
    import jax
    import jax.numpy as jnp

    by_rows = _by_rows(ref.sharding.mesh)

    def stats(prog, ref, init, valid):
        if init is None:
            init = jnp.zeros_like(ref)
        out = jnp.stack([
            jnp.abs(prog - ref).max(axis=1), jnp.abs(ref - init).max(axis=1),
            jnp.square(prog - init).sum(axis=1),
            jnp.square(ref - init).sum(axis=1)], axis=1)
        return jnp.where(valid, out, 0.0)

    flags = jax.device_put(np.asarray(valid, bool)[:, None], by_rows)
    return np.asarray(jax.jit(stats, out_shardings=by_rows)(
        prog, ref, init, flags), np.float64)


def replay_gaps(seed, vocab, dim, rows, batches, prog0, prog1, prog_losses,
                devices) -> dict:
    """The numbers of ``reference.replay_gaps``, under the same names, with
    the touched rows split over ``devices`` throughout. ``prog0`` and
    ``prog1`` are host copies of the program's rows ``rows`` after the same
    steps; each goes to the devices split as the reference's rows are."""
    init0 = seed_rows(seed, vocab, dim, rows, devices)
    ref0, ref1, ref_losses = sgns_replay(init0, rows, batches)
    # Each row counts where it first stands; the repeats of the last one
    # (touched_rows) were never updated by the steps, and count for nothing.
    valid = np.r_[True, rows[1:] != rows[:-1]]
    out = {}
    for name, prog, ref, init in (("syn0", prog0, ref0, init0),
                                  ("syn1", prog1, ref1, None)):
        prog = shard_rows(prog, devices)
        stats = table_stats(prog, ref, init, valid)
        prog.delete()
        ref.delete()
        d_prog, d_ref = np.sqrt(stats[:, 2].sum()), np.sqrt(stats[:, 3].sum())
        out[f"replay.{name}_gap"] = stats[:, 0].max() / stats[:, 1].max()
        out[f"replay.{name}_dnorm_gap"] = abs(d_prog - d_ref) / d_ref
    ref_losses = np.asarray(ref_losses, np.float32)
    out["replay.loss_gap"] = float(np.max(
        np.abs(np.asarray(prog_losses, np.float32) - ref_losses)
        / ref_losses))
    return out
