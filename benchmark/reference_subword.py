"""The plain reference of the subword configuration (``ft-300-1m-2mb``).
Nothing here imports the program, and nothing of ``corpus/subword.py``.

fastText's subword skip-gram (Bojanowski et al., TACL 2017): a word's centre
vector is the mean of its own ``syn0`` row and the rows its character n-grams
hash to. Two things are the reference's own:

* ``group_table``: the rows of every word's group, from the words alone. The
  word is wrapped in ``<`` and ``>``; its n-grams of ``min_n`` to ``max_n``
  characters, the shorter first, then by their start, the whole wrapped word
  left out, are hashed by FNV-1a (32 bit) into ``bucket`` rows that follow
  the vocabulary's; the word's own row leads, and the group is cut at
  ``max_subwords``. Cut here from a fixed-width matrix of the words' bytes,
  one (length, start) column window at a time, all words in step. The kind
  compares it with the table the program holds on its device, row for row.
* ``replay``: the grouped step, the equations of ``reference.sgns_step`` with
  the centre a masked mean and its gradient divided by the group's count onto
  every row of the group (a numpy transcription in ``benchmark/tests`` and the
  repo's ``ops/grouped_reference.py`` are held to it by tests), in plain
  ``jax.numpy`` float32 at ``highest`` precision over the rows the steps
  touch: ``syn0``'s (centre words and their buckets) and ``syn1``'s (contexts
  and negatives) are two lists, since the bucket rows of ``syn1`` are never
  touched.
"""

import numpy as np

from benchmark.reference import CHUNK, seed_rows

FNV_OFFSET, FNV_PRIME = 2166136261, 16777619


def group_table(words, vocab: int, bucket: int, min_n: int, max_n: int,
                max_subwords: int) -> np.ndarray:
    """(len(words), max_subwords) int32: row w the table rows of word w's
    group, -1 where it has fewer. ASCII words (the benchmark's corpus)."""
    wrapped = np.char.add(np.char.add("<", np.asarray(words, str)), ">")
    raw = np.char.encode(wrapped, "ascii")  # raises on anything else
    width = raw.dtype.itemsize
    mat = np.frombuffer(raw.tobytes(), np.uint8).reshape(len(words), width)
    length = (mat != 0).sum(axis=1)  # numpy pads a short string with NULs
    table = np.full((len(words), max_subwords), -1, np.int32)
    table[:, 0] = np.arange(len(words))
    filled = np.ones(len(words), np.int64)
    every = np.arange(len(words))
    for n in range(min_n, max_n + 1):
        for start in range(width - n + 1):
            # the n-gram at ``start`` exists where it ends inside the word
            # and is not the whole wrapped word; it is kept where the
            # group still has room
            has = (start + n <= length) & (n <= length - 1) & (
                filled < max_subwords)
            if not has.any():
                break
            h = np.full(len(words), FNV_OFFSET, np.uint64)
            for k in range(n):
                h = ((h ^ mat[:, start + k]) * FNV_PRIME) & 0xFFFFFFFF
            w = every[has]
            table[w, filled[w]] = vocab + (h[w] % bucket).astype(np.int64)
            filled[w] += 1
    return table


def _pad(rows: np.ndarray) -> np.ndarray:
    """Sorted distinct rows, the last repeated up to a multiple of CHUNK:
    their number is a static shape of the replay's programs."""
    return np.pad(rows, (0, -rows.size % CHUNK), mode="edge")


def touched_rows(batches, groups: np.ndarray):
    """(``syn0`` rows, ``syn1`` rows) the batches touch, each sorted and
    padded (``reference.touched_rows``'s rule)."""
    centers = np.unique(np.concatenate([b["centers"] for b in batches]))
    ids = np.unique(groups[centers])
    rows1 = np.unique(np.concatenate([
        np.concatenate([b["contexts"], b["negs"].reshape(-1)])
        for b in batches]))
    return _pad(ids[ids >= 0]), _pad(rows1)


def replay(syn0_rows, rows0: np.ndarray, rows1: np.ndarray,
           groups: np.ndarray, batches):
    """Follow ``batches`` from the seed's ``syn0`` restricted to ``rows0``
    and a zero ``syn1`` restricted to ``rows1``. Returns (syn0_rows,
    syn1_rows, [loss per step]). A pair's group is walked a slot at a time
    (``max_subwords`` gathers and scatter-adds of P rows), so that no
    (P x max_subwords x d) block is ever formed."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    width = groups.shape[1]

    def step(tables, b):
        syn0, syn1 = tables
        grp, contexts, mask, negs, alpha = b  # grp (P, G): index or -1
        live = (grp >= 0).astype(jnp.float32)
        count = jnp.maximum(live.sum(axis=1, keepdims=True), 1.0)

        def gather(g, acc):
            return acc + syn0[jnp.maximum(grp[:, g], 0)] * live[:, g, None]

        h = jax.lax.fori_loop(
            0, width, gather,
            jnp.zeros((grp.shape[0], syn0.shape[1]), jnp.float32)) / count
        u_pos, u_neg = syn1[contexts], syn1[negs]
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
        share = d_center / count

        def scatter(g, delta):
            return delta.at[jnp.maximum(grp[:, g], 0)].add(
                share * live[:, g, None])

        # A row's shares are summed among themselves and added to the row
        # once: a share is a thousandth of the row's own entries in the
        # first steps (syn1 starts at zero), and added one by one each
        # would be rounded at the row's size, not at its own.
        syn0 = syn0 + jax.lax.fori_loop(
            0, width, scatter, jnp.zeros_like(syn0))
        return (syn0, syn1), loss

    def local(rows, ids):
        return np.where(ids >= 0, np.searchsorted(rows, ids), -1).astype(
            np.int32)

    stacked = (
        jnp.asarray(np.stack([local(rows0, groups[b["centers"]])
                              for b in batches])),
        jnp.asarray(np.stack([local(rows1, b["contexts"]) for b in batches])),
        jnp.asarray(np.stack([np.asarray(b["mask"], np.float32)
                              for b in batches])),
        jnp.asarray(np.stack([local(rows1, b["negs"]) for b in batches])),
        jnp.asarray(np.stack([np.float32(b["alpha"]) for b in batches])),
    )
    syn0 = jnp.asarray(syn0_rows, jnp.float32)
    syn1 = jnp.zeros((rows1.size, syn0.shape[1]), jnp.float32)
    (syn0, syn1), losses = jax.jit(
        lambda s0, s1, bs: jax.lax.scan(step, (s0, s1), bs))(
            syn0, syn1, stacked)
    return syn0, syn1, losses


def table_gaps(prog: np.ndarray, ref, init, rows: np.ndarray):
    """(largest |prog - ref| over the largest |ref - init|, the change
    norms' gap) of one table's touched rows; ``init`` None is a zero table.
    Compared on the device piece by piece; a row's squares are summed in
    float32, the rows in float64 on the host, so that no norm is quantised
    by one float32 sum over the table. The repeats of the last row count
    for nothing."""
    import jax
    import jax.numpy as jnp

    valid = np.r_[True, rows[1:] != rows[:-1]]

    @jax.jit
    def piece(prog, ref, init, valid):
        out = jnp.stack([
            jnp.abs(prog - ref).max(axis=1), jnp.abs(ref - init).max(axis=1),
            jnp.square(prog - init).sum(axis=1),
            jnp.square(ref - init).sum(axis=1)], axis=1)
        return jnp.where(valid[:, None], out, 0.0)

    stats = []
    for s in range(0, rows.size, CHUNK):
        r = ref[s:s + CHUNK]
        stats.append(np.asarray(piece(
            jnp.asarray(prog[s:s + CHUNK]), r,
            jnp.zeros_like(r) if init is None else init[s:s + CHUNK],
            jnp.asarray(valid[s:s + CHUNK])), np.float64))
    stats = np.concatenate(stats)
    d_prog, d_ref = np.sqrt(stats[:, 2].sum()), np.sqrt(stats[:, 3].sum())
    return stats[:, 0].max() / stats[:, 1].max(), abs(d_prog - d_ref) / d_ref


def replay_gaps(seed, table_rows, dim, rows0, rows1, groups, batches, prog0,
                prog1, prog_losses, devices) -> dict:
    """The numbers of ``reference.replay_gaps``, under the same names:
    follow ``batches`` from the seed's rows (``table_rows`` = vocabulary +
    buckets: the engine draws one table over both) and read how far the
    program's rows and losses lie from the reference's."""
    init0 = seed_rows(seed, table_rows, dim, rows0, devices)
    ref0, ref1, ref_losses = replay(init0, rows0, rows1, groups, batches)
    out = {}
    for name, prog, ref, init, rows in (("syn0", prog0, ref0, init0, rows0),
                                        ("syn1", prog1, ref1, None, rows1)):
        gap, dnorm = table_gaps(prog, ref, init, rows)
        out[f"replay.{name}_gap"] = gap
        out[f"replay.{name}_dnorm_gap"] = dnorm
    ref_losses = np.asarray(ref_losses, np.float32)
    out["replay.loss_gap"] = float(np.max(
        np.abs(np.asarray(prog_losses, np.float32) - ref_losses)
        / ref_losses))
    return out
