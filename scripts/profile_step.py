"""Attribute the SGNS step time on the real chip.

Measures, in PRIORITY order (the decisive numbers come first, and partial
results are flushed to --out after every section):

  1. full engine train steps in the bench's three mode configs
     (per_pair f32, per_pair bf16 tables+compute, shared bf16)
  2. isolated sparse row traffic (gather; scatter with materialized vs
     XLA-fused rank-1 payloads)
  3. the shared-mode matmuls f32 vs bf16, per-pair einsums, sampling

so the step-time budget in PARITY.md is measurement-backed rather than
modeled (round-3 weak #2: "nobody knows where the 2ms goes").

Usage:  python scripts/profile_step.py [--out FILE] [--dtype float32]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from glint_word2vec_tpu.utils.platform import force_platform  # noqa: E402

# Default: the real chip. GLINT_PROFILE_PLATFORM=cpu for mechanism smoke.
force_platform(os.environ.get("GLINT_PROFILE_PLATFORM"))

import jax
import jax.numpy as jnp
import numpy as np

from glint_word2vec_tpu.ops import sgns  # noqa: E402

V, d, B, C, n, S = 1_000_000, 300, 8192, 7, 5, 4096


def note(msg):
    print(f"[profile] {msg}", file=sys.stderr, flush=True)


def timeit(fn, *args, iters=20, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / iters * 1e6, 1)  # us


def timeit_donated(fn, table, *args, iters=10, warmup=2):
    """Time a donated-table update fn, threading the table through calls."""
    for _ in range(warmup):
        table = fn(table, *args)
    jax.block_until_ready(table)
    t0 = time.perf_counter()
    for _ in range(iters):
        table = fn(table, *args)
    jax.block_until_ready(table)
    return round((time.perf_counter() - t0) / iters * 1e6, 1), table


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="/tmp/profile_step_results.json")
    ap.add_argument("--dtype", default="float32")
    args = ap.parse_args()

    res = {"dtype": args.dtype}

    def flush():
        from glint_word2vec_tpu.utils import atomic_write_json

        atomic_write_json(args.out, res, indent=2)

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    rng = np.random.default_rng(0)
    ranks = np.arange(1, V + 1, dtype=np.float64)
    p = 1.0 / ranks
    p /= p.sum()

    res["device"] = str(jax.devices()[0])
    flush()

    # ================= 1. FULL ENGINE STEPS (decisive) =================
    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(1, 1, devices=[jax.devices()[0]])
    counts = np.maximum(1e9 / ranks, 1.0).astype(np.int64)
    centers = rng.choice(V, size=(B,), p=p).astype(np.int32)
    contexts = rng.choice(V, size=(B, C), p=p).astype(np.int32)
    mask = (rng.random((B, C)) < 0.85).astype(np.float32)
    key = jax.random.PRNGKey(0)

    step_cfgs = [
        ("per_pair_f32", dict(shared_negatives=0, dtype="float32")),
        ("per_pair_bf16ct", dict(shared_negatives=0, dtype="bfloat16",
                                 compute_dtype="bfloat16")),
        ("shared_bf16ct", dict(shared_negatives=S, dtype="bfloat16",
                               compute_dtype="bfloat16")),
    ]
    for tag, kw in step_cfgs:
        note(f"full_step_{tag}...")
        try:
            eng = EmbeddingEngine(mesh, V, d, counts, num_negatives=n,
                                  seed=0, **kw)

            def step(e=eng):
                return e.train_step(centers, contexts, mask, key, 0.025)

            res[f"full_step_{tag}_us"] = timeit(step, iters=10)
            del eng
        except Exception as e:  # keep later sections alive
            res[f"full_step_{tag}_error"] = str(e)[:300]
        flush()

    # ================= 2. Sparse row traffic ===========================
    note("generating device data...")

    @jax.jit
    def gen(key):
        ks = jax.random.split(key, 7)
        table = jax.random.normal(ks[0], (V, d), jnp.float32).astype(dtype)

        def zipfish(k, shape):
            u = jax.random.uniform(k, shape, jnp.float32)
            return jnp.minimum((u**6 * V).astype(jnp.int32), V - 1)

        idx_pos = zipfish(ks[1], (B * C,))
        idx_neg = zipfish(ks[2], (B * C * n,))
        h = jax.random.normal(ks[3], (B, d), jnp.float32)
        coef = jax.random.normal(ks[4], (B, C * (1 + n)), jnp.float32)
        payload = jax.random.normal(ks[5], (B * C * (1 + n), d), jnp.float32)
        pool = jax.random.normal(ks[6], (S, d), jnp.float32)
        return table, idx_pos, idx_neg, h, coef, payload, pool

    table, idx_pos, idx_neg, h, coef, payload, pool = gen(jax.random.PRNGKey(0))
    idx_all = jnp.concatenate([idx_pos, idx_neg])
    jax.block_until_ready(table)

    note("gathers...")
    gather = jax.jit(lambda t, i: t[i].astype(jnp.float32).sum(0))
    res["gather_BCn_us"] = timeit(gather, table, idx_neg)
    res["gather_BC_us"] = timeit(gather, table, idx_pos)
    flush()

    note("scatter_materialized...")
    scat_mat = jax.jit(
        lambda t, i, u: t.at[i].add(u.astype(t.dtype)), donate_argnums=0
    )
    res["scatter_materialized_BC1n_us"], table = timeit_donated(
        scat_mat, table, idx_all, payload
    )
    flush()

    # Does XLA fuse the coef x h broadcast into the scatter?
    def scat_fused(t, i, c, hh):
        upd = c[:, :, None] * hh[:, None, :]  # (B, C(1+n), d)
        return t.at[i].add(upd.reshape(-1, d).astype(t.dtype))

    note("scatter_fused_bcast...")
    res["scatter_fused_bcast_us"], table = timeit_donated(
        jax.jit(scat_fused, donate_argnums=0), table, idx_all, coef, h
    )
    flush()

    # Fused gather->logit: does XLA avoid materializing the gathered rows?
    def gather_dot(t, i, hh):
        rows = t[i].astype(jnp.float32).reshape(B, C * n, -1)
        return jnp.einsum("bd,bkd->bk", hh, rows).sum()

    note("gather_dot...")
    res["gather_dot_BCn_us"] = timeit(jax.jit(gather_dot), table, idx_neg, h)
    flush()

    # ================= 3. Dense compute + sampling =====================
    def shared_mm(hh, pp):
        f = hh @ pp.T
        c = jax.nn.sigmoid(f)
        return (c.T @ hh).sum() + (c @ pp).sum()

    note("shared_matmuls_f32...")
    res["shared_matmuls_f32_us"] = timeit(jax.jit(shared_mm), h, pool)

    hb, pb = h.astype(jnp.bfloat16), pool.astype(jnp.bfloat16)

    def shared_mm_bf16(hh, pp):
        f = jnp.dot(hh, pp.T, preferred_element_type=jnp.float32)
        c = jax.nn.sigmoid(f).astype(jnp.bfloat16)
        return (
            jnp.dot(c.T, hh, preferred_element_type=jnp.float32).sum()
            + jnp.dot(c, pp, preferred_element_type=jnp.float32).sum()
        )

    note("shared_matmuls_bf16...")
    res["shared_matmuls_bf16_us"] = timeit(jax.jit(shared_mm_bf16), hb, pb)
    flush()

    @jax.jit
    def gen2(key):
        k1, k2 = jax.random.split(key)
        # blocks of (B, d) rows, the small axes major (ops/sgns.row_dots)
        return (
            jax.random.normal(k1, (C, B, d), jnp.float32),
            jax.random.normal(k2, (C * n, B, d), jnp.float32),
        )

    u_pos, u_neg = gen2(jax.random.PRNGKey(1))

    def pp_einsums(hh, up, un):
        cp = jax.nn.sigmoid(sgns.row_dots(hh, up))
        cn = jax.nn.sigmoid(sgns.row_dots(hh, un))
        return (sgns.row_sums(cp, up) + sgns.row_sums(cn, un)).sum()

    note("per_pair_einsums...")
    res["per_pair_einsums_us"] = timeit(jax.jit(pp_einsums), h, u_pos, u_neg)
    flush()

    from glint_word2vec_tpu.ops.sampling import (
        sample_negatives,
        sample_negatives_per_row,
    )

    # prob/alias are jit arguments, not closed-over constants: (V,)-sized
    # constants baked into the program are not what the train step does.
    prob = jnp.asarray(rng.random(V, dtype=np.float32))
    alias = jnp.asarray(rng.integers(0, V, V), jnp.int32)
    note("sampling...")
    res["sample_negatives_us"] = timeit(
        jax.jit(
            lambda k, pr, al: sample_negatives(k, pr, al, (B, C, n)).sum()
        ),
        key, prob, alias,
    )
    rows = jnp.arange(B, dtype=jnp.int32)
    res["sample_negatives_per_row_us"] = timeit(
        jax.jit(
            lambda k, pr, al, r: sample_negatives_per_row(
                k, pr, al, r, (C, n)
            ).sum()
        ),
        key, prob, alias, rows,
    )
    flush()
    print(json.dumps(res, indent=2))


if __name__ == "__main__":
    main()
