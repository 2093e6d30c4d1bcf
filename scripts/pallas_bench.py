"""Measure XLA vs Pallas kernel paths on the current device.

The decision record VERDICT asked for: per-hardware step times for the
sparse row traffic (gather / scatter-add) and the full fused train step
with the engine's ``use_pallas`` flag off vs on. The winner should be the
engine default; the loser stays opt-in. Today only the CPU form runs: the
TPU's compiler refuses every kernel (engine.PALLAS_TPU_REFUSAL,
tests/test_tpu_compile.py), and ``use_pallas`` raises on a tpu backend.

    GLINT_PB_PLATFORM=cpu python scripts/pallas_bench.py   # CPU (interpret)

Prints one JSON line per measurement and a final summary line, and
(ISSUE 11) writes ``BENCH_FUSED.json`` — the fused-megakernel surface:
the composed XLA pair step vs ops/pallas_sgns.fused_pair_step at both
table dtypes (fp32, bf16 storage + fp32 VMEM accumulation), the 3-way
parity errors, and the acceptance checks. Off-TPU the kernels run in
INTERPRET mode, so the recorded gate is parity + no packed-path
regression (a fresh XLA ``corpus_packed`` cell at the BENCH_PACKED
headline shape, GLINT_PB_PACKED_CHECK=0 to skip); the bf16-storage >=
fp32 throughput gate is recorded as a TPU-conditional check, exactly
like BENCH_PACKED.json records its platform caveats.
"""

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

from glint_word2vec_tpu.utils.platform import force_platform  # noqa: E402

force_platform(os.environ.get("GLINT_PB_PLATFORM"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def timed(fn, *args, iters=20, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6  # us


def _fused_surface(jax, np, interpret, dev):
    """Composed XLA pair step vs the fused Pallas megakernel, fp32 and
    bf16 table storage: timings + 3-way parity errors (fused vs
    composed vs a host-NumPy oracle with the identical negative
    draws)."""
    import jax.numpy as jnp

    from glint_word2vec_tpu.corpus.alias import build_unigram_alias
    from glint_word2vec_tpu.ops import sgns
    from glint_word2vec_tpu.ops.sampling import sample_negatives_per_row

    V = int(os.environ.get("GLINT_PB_FUSED_VOCAB", 200_000))
    d = int(os.environ.get("GLINT_PB_FUSED_DIM", 300))
    P = int(os.environ.get("GLINT_PB_FUSED_PAIRS", 7168))  # B*C bench shape
    n = 5
    if interpret:
        # Interpret mode measures the emulator, not the kernel: shrink
        # to a semantics-check shape so the artifact lands in seconds.
        V, d, P = min(V, 20_000), min(d, 64), min(P, 1_024)
    rng = np.random.default_rng(0)
    counts = np.maximum(1e9 / np.arange(1, V + 1), 1.0).astype(np.int64)
    alias_t = build_unigram_alias(counts, power=0.75)
    prob = jnp.asarray(alias_t.prob)
    alias = jnp.asarray(alias_t.alias)
    p = counts / counts.sum()
    centers = jnp.asarray(rng.choice(V, P, p=p).astype(np.int32))
    contexts = jnp.asarray(rng.choice(V, P, p=p).astype(np.int32))
    mask = jnp.ones(P, jnp.float32)
    key = jax.random.PRNGKey(0)
    alpha = jnp.float32(0.025)

    composed = jax.jit(
        lambda s0, s1: sgns.train_step_pairs(
            s0, s1, prob, alias, centers, contexts, mask, key, alpha, n
        )
    )
    fused = jax.jit(
        lambda s0, s1: sgns.train_step_pairs_pallas(
            s0, s1, prob, alias, centers, contexts, mask, key, alpha, n,
            interpret=interpret,
        )
    )

    def oracle(s0, s1):
        negs = np.asarray(sample_negatives_per_row(
            key, prob, alias, jnp.arange(P, dtype=jnp.int32), (1, n)
        ))[:, 0, :]
        s0h = np.asarray(s0, np.float32).copy()
        s1h = np.asarray(s1, np.float32).copy()
        ch, xh = np.asarray(centers), np.asarray(contexts)
        h, u, un = s0h[ch], s1h[xh], s1h[negs]
        sig = lambda x: 1.0 / (1.0 + np.exp(-x))  # noqa: E731
        f_pos = (h * u).sum(-1)
        f_neg = (h[:, None, :] * un).sum(-1)
        nm = (negs != xh[:, None]).astype(np.float32)
        c_pos = 0.025 * (1 - sig(f_pos))
        c_neg = -0.025 * sig(f_neg) * nm
        np.add.at(
            s0h, ch, c_pos[:, None] * u + (c_neg[..., None] * un).sum(1)
        )
        np.add.at(s1h, xh, c_pos[:, None] * h)
        np.add.at(
            s1h, negs.reshape(-1),
            c_neg.reshape(-1)[:, None] * np.repeat(h, n, axis=0),
        )
        return s0h, s1h

    out = {
        "config": {"vocab": V, "dim": d, "pairs": P, "negatives": n},
        "composed_us": {}, "fused_us": {}, "parity": {},
    }
    for tag, dtype in (("float32", jnp.float32),
                       ("bfloat16_tables", jnp.bfloat16)):
        syn0 = jnp.asarray(
            rng.normal(0, 0.1, (V, d)).astype(np.float32), dtype=dtype
        )
        syn1 = jnp.asarray(
            rng.normal(0, 0.1, (V, d)).astype(np.float32), dtype=dtype
        )
        c0, c1, _ = composed(syn0, syn1)
        f0, f1, _ = fused(syn0, syn1)
        o0, o1 = oracle(syn0, syn1)
        errs = {
            "fused_vs_oracle_syn0": float(np.max(np.abs(
                np.asarray(f0, np.float32) - o0))),
            "fused_vs_oracle_syn1": float(np.max(np.abs(
                np.asarray(f1, np.float32) - o1))),
            "composed_vs_oracle_syn0": float(np.max(np.abs(
                np.asarray(c0, np.float32) - o0))),
            "composed_vs_oracle_syn1": float(np.max(np.abs(
                np.asarray(c1, np.float32) - o1))),
        }
        out["parity"][tag] = {k: round(v, 8) for k, v in errs.items()}
        out["composed_us"][tag] = round(
            timed(composed, syn0, syn1, iters=5), 1
        )
        out["fused_us"][tag] = round(timed(fused, syn0, syn1, iters=5), 1)
    return out


def _packed_no_regression(jax, np):
    """Fresh XLA ``corpus_packed`` cell at the BENCH_PACKED headline
    shape (the default dispatch path nobody opted out of), compared to
    the committed artifact's effective_words_per_sec with a generous
    noise floor — the CPU-recordable half of the acceptance gate."""
    import bench as bench_mod
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    try:
        with open(os.path.join(_ROOT, "BENCH_PACKED.json")) as f:
            ref = json.load(f)["headline"]["corpus_packed"]
    except (OSError, KeyError, ValueError):
        ref = None
    cfg = bench_mod._config_from_env()
    cfg.update(vocab=100_000, batch=1024, dim=300)
    mesh = make_mesh(1, 1, devices=[jax.devices()[0]])
    fresh = bench_mod._bench_mode(jax, mesh, cfg, "corpus_packed", np)
    res = {
        "fresh_effective_words_per_sec": fresh.get(
            "effective_words_per_sec"
        ),
        "fresh_mask_density": fresh.get("mask_density"),
        "reference_effective_words_per_sec": (
            ref and ref.get("effective_words_per_sec")
        ),
        "noise_floor_ratio": 0.6,
    }
    if ref and fresh.get("effective_words_per_sec"):
        ratio = (
            fresh["effective_words_per_sec"]
            / ref["effective_words_per_sec"]
        )
        res["ratio_vs_reference"] = round(ratio, 3)
        res["pass"] = bool(ratio >= 0.6)
    else:
        res["pass"] = None
        res["reason"] = "no BENCH_PACKED reference cell to compare"
    return res


def _write_bench_fused(fused, dev, interpret) -> None:
    from glint_word2vec_tpu.utils import atomic_write_json

    import jax
    import numpy as np

    par = fused["parity"]
    # fp32: everything accumulates in fp32 on every path; differences
    # are reduction-order ulps. bf16 storage: table values are rounded
    # to bf16 (eps ~ 2^-8) on every write, so the documented tolerance
    # scales with the update magnitude.
    fp32_gate = 1e-4
    bf16_gate = 0.05
    checks = {
        "fused_parity_fp32": {
            "pass": bool(max(par["float32"].values()) <= fp32_gate),
            "gate": f"max |fused - oracle| <= {fp32_gate} (fp32 tables; "
                    "composed-vs-oracle recorded alongside as the "
                    "reduction-order noise floor)",
        },
        "fused_parity_bf16": {
            "pass": bool(
                max(par["bfloat16_tables"].values()) <= bf16_gate
            ),
            "gate": f"max |fused - oracle| <= {bf16_gate} (bf16 "
                    "storage rounds every landed row to ~2^-8 relative)",
        },
        "bf16_storage_ge_fp32_throughput": {
            "status": "tpu_conditional",
            "pass": (
                bool(
                    fused["fused_us"]["bfloat16_tables"]
                    <= fused["fused_us"]["float32"]
                )
                if not interpret else None
            ),
            "reason": (
                "interpret-mode timings measure the Pallas emulator, "
                "not the kernel; the bf16-bandwidth gate (bf16 storage "
                ">= fp32 throughput, targeting ~2x) evaluates on real "
                "TPU hardware" if interpret else
                "evaluated on hardware"
            ),
        },
    }
    if os.environ.get("GLINT_PB_PACKED_CHECK", "1") == "1":
        checks["packed_path_no_regression"] = _packed_no_regression(
            jax, np
        )
    else:
        checks["packed_path_no_regression"] = {
            "pass": None, "reason": "skipped (GLINT_PB_PACKED_CHECK=0)"
        }
    doc = {
        "metric": "fused_pallas_pair_step",
        "issue": 11,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        # Artifact convention (tests/test_artifacts.py): any non-TPU
        # platform must carry the top-level fallback marker.
        **({"fallback": dev.platform} if dev.platform != "tpu" else {}),
        "interpret_mode": bool(interpret),
        **fused,
        "checks": checks,
        "caveats": [
            "parity errors are max-abs over both full tables after one "
            "identical pair step (identical negative draws on all "
            "three paths)",
            "composed-vs-oracle errors bound the reduction-order noise "
            "floor the fused gate is read against",
        ] + ([
            "CPU fallback: fused timings are Pallas INTERPRET mode — a "
            "semantics check, not a measurement (the emulator is "
            "orders of magnitude off kernel speed); the recorded gate "
            "on this platform is parity + no packed-path regression, "
            "with the bf16-storage throughput gate TPU-conditional "
            "(BENCH_PACKED.json records its caveats the same way)",
        ] if interpret else []),
    }
    out_path = os.environ.get(
        "GLINT_PB_FUSED_OUT", os.path.join(_ROOT, "BENCH_FUSED.json")
    )
    atomic_write_json(out_path, doc, indent=2)
    print(json.dumps({"bench_fused_written": out_path,
                      "checks": {k: v.get("pass") for k, v in
                                 checks.items()}}))


def main() -> None:
    V = int(os.environ.get("GLINT_PB_VOCAB", 1_000_000))
    d = int(os.environ.get("GLINT_PB_DIM", 300))
    N = int(os.environ.get("GLINT_PB_ROWS", 286_720))  # ~B*C*(1+n) at bench shapes
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    interpret = not on_tpu
    if interpret:
        # Interpret mode is a semantics check, not a measurement; shrink.
        V, d, N = min(V, 20_000), min(d, 64), min(N, 4_096)

    from glint_word2vec_tpu.ops.pallas_rows import gather_rows, scatter_add_rows

    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(V, d)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, V, N).astype(np.int32))
    upd = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32) * 1e-3)

    results = {"platform": dev.platform, "device_kind": dev.device_kind,
               "V": V, "d": d, "N": N}

    xla_gather = jax.jit(lambda t, i: t[i])
    results["gather_xla_us"] = round(timed(xla_gather, table, ids), 1)
    for br in (8, 16, 32):
        results[f"gather_pallas_b{br}_us"] = round(
            timed(gather_rows, table, ids, interpret=interpret, block_rows=br), 1
        )

    xla_scatter = jax.jit(lambda t, i, u: t.at[i].add(u))
    results["scatter_xla_us"] = round(timed(xla_scatter, table, ids, upd), 1)
    for br in (8, 16, 32):
        results[f"scatter_pallas_b{br}_us"] = round(
            timed(
                scatter_add_rows, table, ids, upd,
                interpret=interpret, block_rows=br,
            ),
            1,
        )

    # Fused rank-1 scatter (scatter_add_rank1): coef x h formed in VMEM vs
    # the XLA outer-product + scatter it replaces in the engine's pm path.
    from glint_word2vec_tpu.ops.pallas_rows import scatter_add_rank1

    B_h = min(8192, N)
    coef = jnp.asarray(rng.normal(size=N).astype(np.float32) * 1e-3)
    h = jnp.asarray(rng.normal(size=(B_h, d)).astype(np.float32))
    hidx = jnp.asarray(rng.integers(0, B_h, N).astype(np.int32))
    xla_rank1 = jax.jit(
        lambda t, i, c, hh, x: t.at[i].add(c[:, None] * hh[x])
    )
    results["scatter_rank1_xla_us"] = round(
        timed(xla_rank1, table, ids, coef, h, hidx), 1
    )
    for br in (8, 16, 32):
        results[f"scatter_rank1_pallas_b{br}_us"] = round(
            timed(
                scatter_add_rank1, table, ids, coef, h, hidx,
                interpret=interpret, block_rows=br,
            ),
            1,
        )

    fused = _fused_surface(jax, np, interpret, dev)
    print(json.dumps({"fused": {
        k: fused[k] for k in ("composed_us", "fused_us", "parity")
    }}))
    _write_bench_fused(fused, dev, interpret)

    # Full fused train step, engine-level: default vs pallas path.
    if on_tpu:
        from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
        from glint_word2vec_tpu.parallel.mesh import make_mesh

        counts = np.maximum(1e9 / np.arange(1, V + 1), 1.0).astype(np.int64)
        B, C, spc = 8192, 7, 16
        centers = rng.integers(0, V, size=(spc, B)).astype(np.int32)
        contexts = rng.integers(0, V, size=(spc, B, C)).astype(np.int32)
        mask = np.ones((spc, B, C), np.float32)
        alphas = np.full(spc, 0.025, np.float32)
        key = jax.random.PRNGKey(0)
        for use_pallas in (False, True):
            eng = EmbeddingEngine(
                make_mesh(1, 1, devices=[dev]), V, d, counts,
                use_pallas=use_pallas,
            )
            us = timed(
                eng.train_steps, centers, contexts, mask, key, alphas, 0,
                iters=5,
            )
            results[f"train_step_{'pallas' if use_pallas else 'xla'}_us"] = (
                round(us / spc, 1)
            )
            del eng

    print(json.dumps(results))


if __name__ == "__main__":
    main()
