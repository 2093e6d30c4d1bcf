"""The plain references, in float32. Nothing here imports the program.

``sgns_step``: one synchronous-batch step of skip-gram with negative
sampling, the straight-line ``tests/test_sgns.py::_numpy_oracle`` vectorised:
every update is computed from the pre-step rows and duplicates are summed.
``sgns_replay`` follows many such steps (plain ``jax.numpy`` float32).

``TopK``: cosine top-k over a host copy of a table (``chip_smoke.Reference``).
"""

import numpy as np


def _log_sigmoid(x):
    # log(sigmoid(x)), stable for both signs
    return np.minimum(x, 0.0) - np.log1p(np.exp(-np.abs(x)))


def _sigmoid(x):
    return np.float32(1.0) / (np.float32(1.0) + np.exp(-x))


def scatter_add(table: np.ndarray, ids: np.ndarray, upd: np.ndarray) -> None:
    """table[ids] += upd with duplicates summed (float32), in place."""
    order = np.argsort(ids, kind="stable")
    ids_s = ids[order]
    starts = np.flatnonzero(np.r_[True, ids_s[1:] != ids_s[:-1]])
    table[ids_s[starts]] += np.add.reduceat(upd[order], starts, axis=0)


def sgns_step(syn0, syn1, centers, contexts, mask, negs, alpha):
    """One step over P pairs, in place. centers/contexts/mask (P,), negs
    (P, n). Returns the masked-mean loss."""
    alpha = np.float32(alpha)
    h = syn0[centers]  # (P, d)
    u_pos = syn1[contexts]  # (P, d)
    u_neg = syn1[negs]  # (P, n, d)
    f_pos = np.einsum("pd,pd->p", h, u_pos)
    f_neg = np.einsum("pd,pnd->pn", h, u_neg)
    # A negative equal to its positive context is skipped (the word2vec
    # "target == word" rule); padded pair slots carry mask 0.
    nmask = (negs != contexts[:, None]).astype(np.float32) * mask[:, None]
    c_pos = alpha * (1.0 - _sigmoid(f_pos)) * mask
    c_neg = -alpha * _sigmoid(f_neg) * nmask
    pair_loss = -_log_sigmoid(f_pos) * mask - (
        _log_sigmoid(-f_neg) * nmask).sum(axis=1) * mask
    loss = pair_loss.sum(dtype=np.float32) / max(
        mask.sum(dtype=np.float32), np.float32(1.0))
    d_center = c_pos[:, None] * u_pos + np.einsum("pn,pnd->pd", c_neg, u_neg)
    scatter_add(
        syn1, np.concatenate([contexts, negs.reshape(-1)]),
        np.concatenate([
            c_pos[:, None] * h,
            (c_neg[:, :, None] * h[:, None, :]).reshape(-1, h.shape[1]),
        ]),
    )
    scatter_add(syn0, centers, d_center.astype(np.float32))
    return float(loss)


CHUNK = 1 << 19  # rows per piece: bounds what one gather or compare holds


def touched_rows(batches) -> np.ndarray:
    """Sorted table rows the batches touch, each once, then the last one
    repeated up to a multiple of CHUNK: the number of rows is a static shape
    of the replay's programs, and a seed that changed it would compile them
    anew (the repeats are read with the rest and left out of every
    comparison)."""
    rows = np.unique(np.concatenate([
        np.concatenate([b["centers"], b["contexts"], b["negs"].reshape(-1)])
        for b in batches
    ]))
    return np.pad(rows, (0, -rows.size % CHUNK), mode="edge")


def sgns_replay(syn0_rows: np.ndarray, rows: np.ndarray, batches):
    """Follow ``batches`` from the seed's tables restricted to ``rows``
    (sorted unique row ids; syn0_rows[i] is row rows[i] of syn0, syn1
    starts at zero). Returns (syn0_rows, syn1_rows, [loss per step]).

    The same step as :func:`sgns_step`, written in plain ``jax.numpy``
    float32 (contractions at ``highest`` precision, one ``.at[].add`` per
    table) so that 32 steps over a million rows take the device a second,
    not the host a minute; a test holds the two forms together."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

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
    syn0 = jnp.asarray(syn0_rows, jnp.float32)
    (syn0, syn1), losses = jax.jit(
        lambda s0, bs: jax.lax.scan(step, (s0, jnp.zeros_like(s0)), bs))(
            syn0, stacked)
    return syn0, syn1, losses


def seed_rows(seed: int, vocab: int, dim: int, rows: np.ndarray, devices):
    """Rows ``rows`` of the tables the configuration starts from: word2vec's
    standard init, syn0 ~ U[-0.5/d, 0.5/d) from ``PRNGKey(seed)`` (syn1 is
    zero). The whole table is drawn sharded over ``devices``, its rows
    gathered piece by piece onto the first, and freed."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(devices), ("rows",))
    full = jax.jit(
        lambda key: (jax.random.uniform(key, (vocab, dim), dtype=jnp.float32)
                     - 0.5) / dim,
        out_shardings=NamedSharding(mesh, PartitionSpec("rows", None)),
    )(jax.random.PRNGKey(int(seed)))
    take = jax.jit(lambda t, i: t[i],
                   out_shardings=NamedSharding(mesh, PartitionSpec()))
    parts = []
    for s in range(0, rows.size, CHUNK):
        idx = rows[s:s + CHUNK]
        got = take(full, jnp.asarray(np.pad(idx, (0, CHUNK - idx.size))))
        parts.append(jax.device_put(got, devices[0])[:idx.size])
    full.delete()
    return jnp.concatenate(parts)


def replay_gaps(seed, vocab, dim, rows, batches, prog0, prog1, prog_losses,
                devices) -> dict:
    """The replay's numbers: follow ``batches`` from the seed's rows and
    read how far the program's rows (``prog0``, ``prog1``, host copies of
    its tables' ``rows`` after the same steps) and losses lie from it.
    Compared on the first device, piece by piece: the arrays are gigabytes."""
    import jax
    import jax.numpy as jnp

    init0 = seed_rows(seed, vocab, dim, rows, devices)
    ref0, ref1, ref_losses = sgns_replay(init0, rows, batches)
    # Each row counts where it first stands; the repeats of the last one
    # (touched_rows) were never updated by the steps, and count for nothing.
    valid = np.r_[True, rows[1:] != rows[:-1]]

    @jax.jit
    def piece(prog, ref, init, valid):
        prog, ref, init = (jnp.where(valid[:, None], x, 0.0)
                           for x in (prog, ref, init))
        return jnp.stack([
            jnp.abs(prog - ref).max(), jnp.abs(ref - init).max(),
            jnp.square(prog - init).sum(), jnp.square(ref - init).sum()])

    out = {}
    for name, prog, ref, init in (("syn0", prog0, ref0, init0),
                                  ("syn1", prog1, ref1, None)):
        stats = []
        for s in range(0, rows.size, CHUNK):
            r = ref[s:s + CHUNK]
            stats.append(np.asarray(piece(
                jnp.asarray(prog[s:s + CHUNK]), r,
                jnp.zeros_like(r) if init is None else init[s:s + CHUNK],
                jnp.asarray(valid[s:s + CHUNK])), np.float64))
        stats = np.stack(stats)
        d_prog, d_ref = np.sqrt(stats[:, 2].sum()), np.sqrt(stats[:, 3].sum())
        out[f"replay.{name}_gap"] = stats[:, 0].max() / stats[:, 1].max()
        out[f"replay.{name}_dnorm_gap"] = abs(d_prog - d_ref) / d_ref
    ref_losses = np.asarray(ref_losses, np.float32)
    out["replay.loss_gap"] = float(np.max(
        np.abs(np.asarray(prog_losses, np.float32) - ref_losses)
        / ref_losses))
    return out


class TopK:
    """numpy float32 cosine top-k over a host copy of the table."""

    def __init__(self, table: np.ndarray):
        self.w = np.ascontiguousarray(table, dtype=np.float32)
        self.norms = np.linalg.norm(self.w, axis=1)

    def cosines(self, rows: np.ndarray) -> np.ndarray:
        """(V, Q) cosines of every table row against table rows ``rows``."""
        q = self.w[rows]
        q = q / np.linalg.norm(q, axis=1, keepdims=True)
        safe = np.where(self.norms > 0, self.norms, 1.0)
        cos = (self.w @ q.T) / safe[:, None]
        cos[self.norms <= 0] = -np.inf
        return cos

    def gap(self, row: int, cos: np.ndarray, got, k: int):
        """How far ``got`` ([(row, score), ...]) is from the reference top-k
        of ``row``, given ``cos`` (V,): the largest of |score - reference|
        and, rank by rank, the reference-score distance between the row
        served and the row the reference ranks there (0 when the order is
        identical; a swap is small only between near-ties). inf for a wrong
        count or an unknown row."""
        cos = cos.copy()
        cos[row] = -np.inf  # the query word is not an answer
        order = np.argpartition(-cos, k)[:k]
        order = order[np.argsort(-cos[order], kind="stable")]
        if len(got) != k:
            return float("inf")
        worst = 0.0
        for j, (i, s) in enumerate(got):
            if i is None or not 0 <= i < cos.shape[0]:
                return float("inf")
            worst = max(worst, abs(float(s) - float(cos[i])),
                        abs(float(cos[i]) - float(cos[order[j]])))
        return worst
