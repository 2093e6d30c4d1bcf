"""Weak-scaling harness for pod-scale training (ISSUE 15) — the real
curves that retired the MULTICHIP_r0*.json dry-run smokes (deleted in PR 29).

What it measures, into ``MULTICHIP_BENCH.json`` (repo root):

  * **Weak scaling** 1 -> N supervised-style worker PROCESSES over gloo,
    each fitting a FIXED per-process corpus shard with the sparse
    touched-row exchange after every dispatch group
    (``parallel/exchange.py``): words/sec/rank per world size, weak
    efficiency (rank throughput at N / rank throughput at 1), and the
    ``rank_skew`` straggler gauge (max/median of per-rank mean step
    seconds — the same definition as ``obs/aggregate.py``).
  * **Bytes on the wire**: sparse vs dense exchange bytes per sync at a
    matched 2-rank config — the tentpole gate is sparse moving >= 5x
    fewer bytes/step than the dense full-delta schedule.
  * **Wire variants** (ISSUE 16): the per-variant bytes surface at a
    matched config — fp32/bf16/int8 wire encodings, int8 + round
    coalescing (every=2), and the two-level topology's intra/inter
    hop split — each with replica identity and drift vs the fp32
    baseline. The new gate is int8+coalesced moving >= 3x fewer
    bytes per dispatch group than fp32 sparse.
  * **world=1 short-circuit**: the single-rank sweep leg reports
    exchange bytes/sync == 0 (one replica reconciling with itself
    skips the wire entirely).
  * **Per-wire quality**: a fit per wire format (int8 coalesced
    included) over the capital-structure corpus clearing the
    vienna/berlin gates — quantization must not cost the analogy.
  * **Parity**: sparse-vs-dense final tables value-identical at a
    matched in-process 2-replica config (plus an overflow-spill leg),
    and every worker of every world size reporting the identical
    post-fit table fingerprint.
  * **Shard-streaming checkpoints**: per-rank save seconds, restore
    (verify + stage) seconds, and the peak host block bytes staying
    bounded by one shard, from the replica save split each worker runs.

Gates (explicit in the artifact, exit nonzero if any fails):
  sparse_bytes_5x, int8_coalesced_3x, wire_parity_ok, wire_quality_ok,
  world1_zero_bytes, parity_ok, spill_parity_ok, replicas_identical,
  ckpt_peak_bounded, weak_efficiency_recorded.

``--drill`` additionally runs the kill-one-rank supervised drill: a
2-process ``cli supervise ... train --exchange sparse`` gang with a
scripted SIGKILL on rank 1, asserting teardown + relaunch + resume +
completion (the multichip-smoke CI leg).

Usage:
  python scripts/multichip_bench.py [--ranks 1,2] [--quick] [--drill]
      [--out MULTICHIP_BENCH.json]
"""

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("GLINT_CKPT_NO_FSYNC", "1")

VEC, WINDOW, BATCH, SPC = 48, 5, 256, 4
MIN_COUNT = 2
BASE_SENTENCES = 1500  # per rank (weak scaling: corpus grows with N)
VOCAB_WORDS = 4000


def _synth_corpus(n_sentences: int, seed: int = 5):
    import numpy as np

    rng = np.random.default_rng(seed)
    # Zipf-ish draw over a fixed word universe so the touched-row set
    # per group is realistically skewed (the regime sparse exchange
    # exploits).
    ranks = np.arange(1, VOCAB_WORDS + 1)
    probs = 1.0 / ranks
    probs /= probs.sum()
    out = []
    for _ in range(n_sentences):
        ln = int(rng.integers(6, 14))
        ws = rng.choice(VOCAB_WORDS, size=ln, p=probs)
        out.append(" ".join(f"w{w}" for w in ws))
    return out


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ----------------------------------------------------------------------
# Worker (one rank of a weak-scaling run)
# ----------------------------------------------------------------------


def worker_main(args) -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from glint_word2vec_tpu import Word2Vec
    from glint_word2vec_tpu.parallel import distributed as dist
    from glint_word2vec_tpu.utils import integrity

    if args.world > 1:
        dist.initialize(
            coordinator_address=f"127.0.0.1:{args.port}",
            num_processes=args.world, process_id=args.rank,
        )
    sentences = [
        s.split() for s in _synth_corpus(BASE_SENTENCES * args.world)
    ]
    ck_dir = os.path.join(args.workdir, "ck")
    t0 = time.time()
    model = Word2Vec(
        vector_size=VEC, window=WINDOW, batch_size=BATCH,
        min_count=MIN_COUNT, num_iterations=args.iterations,
        seed=3, steps_per_call=SPC, exchange=args.mode,
        exchange_capacity=args.capacity, exchange_wire=args.wire,
        exchange_every=args.every,
    ).fit(sentences, checkpoint_dir=ck_dir)
    wall = time.time() - t0
    tm = model.training_metrics
    eng = model.engine
    ck = eng.checkpoint_stats()
    # Restore cost: resolve + verify + stage the last committed
    # snapshot (no adoption needed for the measurement).
    t1 = time.time()
    resolved = integrity.resolve_train_state(ck_dir)
    staged = eng.stage_tables(resolved[1])
    restore_s = time.time() - t1
    del staged
    fp = float(np.abs(np.asarray(eng.syn0, dtype=np.float32)).sum())
    out = {
        "rank": args.rank,
        "world": args.world,
        "mode": args.mode,
        "wire": args.wire,
        "every": args.every,
        "wall_seconds": round(wall, 3),
        "steps": tm["steps"],
        "words_done": tm["words_done"],
        "words_per_sec": tm["words_per_sec"],
        "step_time": tm.get("step_time"),
        "exchange": tm.get("exchange", {}),
        "checkpoint": {
            "shard_write_seconds": ck["checkpoint_shard_write_seconds"],
            "write_seconds": ck["checkpoint_write_seconds"],
            "peak_block_bytes": ck["checkpoint_peak_block_bytes"],
            "shards_skipped": ck["checkpoint_shards_skipped"],
            "restore_seconds": round(restore_s, 3),
            "shard_verify_seconds":
                ck["checkpoint_shard_verify_seconds"],
        },
        "table_fingerprint": fp,
        "vocab_size": model.vocab.size,
        "dim": VEC,
    }
    # graftlint: ignore[atomic-persist] single-reader result file in the run's private tmp dir
    with open(
        os.path.join(args.workdir, f"rank{args.rank}.json"), "w"
    ) as f:
        json.dump(out, f)
    print(f"worker {args.rank}/{args.world} done", flush=True)
    return 0


# ----------------------------------------------------------------------
# Parent: weak-scaling sweep + gates
# ----------------------------------------------------------------------


def _run_world(world: int, mode: str, capacity: int,
               iterations: int, wire: str = "fp32",
               every: int = 1) -> list:
    """Launch one weak-scaling run of ``world`` worker processes;
    returns their per-rank result dicts (rank order)."""
    tmp = tempfile.mkdtemp(prefix=f"multichip_w{world}_{mode}_")
    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # each worker sees its real devices
    procs = []
    for r in range(world):
        argv = [
            sys.executable, os.path.abspath(__file__), "--worker",
            "--rank", str(r), "--world", str(world),
            "--port", str(port), "--workdir", tmp,
            "--mode", mode, "--capacity", str(capacity),
            "--iterations", str(iterations),
            "--wire", wire, "--every", str(every),
        ]
        log = open(  # graftlint: ignore[atomic-persist] live subprocess log stream
            os.path.join(tmp, f"rank{r}.log"), "wb"
        )
        procs.append((
            subprocess.Popen(argv, stdout=log, stderr=subprocess.STDOUT,
                             env=env),
            log,
        ))
    rcs = []
    for p, log in procs:
        rcs.append(p.wait(timeout=1800))
        log.close()
    if any(rcs):
        for r in range(world):
            lp = os.path.join(tmp, f"rank{r}.log")
            sys.stderr.write(f"--- rank {r} log tail ---\n")
            sys.stderr.write(open(lp, errors="replace").read()[-3000:])
        raise RuntimeError(f"world={world} {mode} workers failed: {rcs}")
    return [
        json.load(open(os.path.join(tmp, f"rank{r}.json")))
        for r in range(world)
    ]


def _rank_skew(results: list):
    import statistics

    means = [
        r["step_time"] / r["steps"]
        for r in results if r.get("step_time") and r.get("steps")
    ]
    if not means:
        return None
    med = statistics.median(means)
    return round(max(means) / med, 4) if med > 0 else None


def _inprocess_parity(quick: bool) -> dict:
    """Deterministic 2-replica sparse-vs-dense parity + spill-parity
    check (the in-process twin of the gloo protocol — same harvest,
    same decide rule, same apply order)."""
    import numpy as np
    import jax

    from glint_word2vec_tpu.parallel import exchange as exmod
    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    # The bytes gate's regime: a vocab much larger than one group's
    # touched-row set — the pod-scale shape (at 100M-row vocabs the
    # ratio is ~V/capacity; this config keeps the in-process check
    # cheap while staying honestly inside that regime).
    V, d = (4000, 32) if quick else (12000, 48)
    B = 16  # touched <= B*(1 + C + n) ~ 400 rows << capacity << V
    ROUNDS = 4  # a multiple of every coalescing factor exercised below
    rng = np.random.default_rng(1)
    counts = rng.integers(1, 1000, V)

    def run(mode, cap, wire="fp32", every=1, topology="flat"):
        engines = [
            EmbeddingEngine(make_mesh(1, 1), V, d, counts, seed=3)
            for _ in range(2)
        ]
        exs = [
            exmod.ReplicaExchanger(e, mode=mode, capacity=cap,
                                   wire=wire, every=every,
                                   topology=topology)
            for e in engines
        ]
        key = jax.random.PRNGKey(0)
        for rnd in range(ROUNDS):
            for r, e in enumerate(engines):
                rl = np.random.default_rng(50 + 10 * rnd + r)
                e.train_step(
                    rl.integers(0, V, B).astype(np.int32),
                    rl.integers(0, V, (B, 4)).astype(np.int32),
                    np.ones((B, 4), np.float32),
                    jax.random.fold_in(key, 2 * rnd + r), 0.025,
                )
            if (rnd + 1) % every == 0:
                exmod.sync_group(exs)
        t = (np.asarray(engines[0].syn0), np.asarray(engines[0].syn1))
        same = all(
            np.array_equal(np.asarray(engines[0].syn0),
                           np.asarray(e.syn0))
            and np.array_equal(np.asarray(engines[0].syn1),
                               np.asarray(e.syn1))
            for e in engines[1:]
        )
        st = engines[0].exchange_stats()
        for e in engines:
            e.destroy()
        return t, same, st

    cap = 512
    (s0, s1), same_sp, st_sp = run("sparse", cap)
    (d0, d1), same_de, st_de = run("dense", cap)
    (o0, o1), same_ov, st_ov = run("sparse", 16)  # forced spill

    # Wire-variant matrix (ISSUE 16): one capacity for every cell so
    # the byte ratios are the encoding, not the buffer size. The
    # coalesced cell accumulates `every` groups of touched rows per
    # round, so the shared capacity leaves it headroom too.
    vcap = 1024
    variants = {}
    vref = None
    for name, kw in [
        ("fp32", {}),
        ("bf16", dict(wire="bf16")),
        ("int8", dict(wire="int8")),
        ("int8_coalesced", dict(wire="int8", every=2)),
        ("int8_twolevel", dict(wire="int8", topology="twolevel")),
    ]:
        t, same, st = run("sparse", vcap, **kw)
        if vref is None:
            vref = t
        drift = max(
            float(np.max(np.abs(t[0] - vref[0]))),
            float(np.max(np.abs(t[1] - vref[1]))),
        )
        variants[name] = {
            "replicas_identical": bool(same),
            "syncs": st["exchange_syncs_total"],
            "dense_syncs": st["exchange_dense_syncs_total"],
            "bytes_total": st["exchange_bytes_total"],
            "bytes_per_sync": st["exchange_bytes_total"]
            // max(st["exchange_syncs_total"], 1),
            # normalized per dispatch group: coalescing's win shows up
            # here (fewer rounds over the same training schedule).
            "bytes_per_group": st["exchange_bytes_total"] // ROUNDS,
            "intra_bytes_total": st["exchange_intra_bytes_total"],
            "inter_bytes_total": st["exchange_inter_bytes_total"],
            "drift_vs_fp32_max_abs": drift,
            "residual_abs": st["exchange_residual_abs"],
        }
    return {
        "vocab": V, "dim": d, "capacity": cap,
        "variant_capacity": vcap,
        "parity_ok": bool(
            np.array_equal(s0, d0) and np.array_equal(s1, d1)
            and same_sp and same_de
        ),
        "spill_parity_ok": bool(
            np.array_equal(o0, d0) and np.array_equal(o1, d1)
            and same_ov and st_ov["exchange_overflow_total"] > 0
        ),
        "sparse_bytes_per_sync": st_sp["exchange_bytes_total"]
        // st_sp["exchange_syncs_total"],
        "dense_bytes_per_sync": st_de["exchange_bytes_total"]
        // st_de["exchange_syncs_total"],
        "sparse_rows_total": st_sp["exchange_rows_total"],
        "overflow_spills": st_ov["exchange_overflow_total"],
        "variants": variants,
    }


# The wire encodings must not cost model quality: one fit per wire
# format over the capital-structure corpus (the same fixture the CI
# quality legs use), each clearing the vienna/berlin gates.
WIRE_DRIFT_BOUND = 1e-2


def _wire_quality() -> dict:
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from conftest import _make_tiny_corpus
    from glint_word2vec_tpu import Word2Vec

    sentences = _make_tiny_corpus()
    out = {}
    # world=1: force the loopback wire so the fits actually run the
    # encode/decode path they are certifying.
    prev = os.environ.get("GLINT_EXCHANGE_FORCE_WIRE")
    os.environ["GLINT_EXCHANGE_FORCE_WIRE"] = "1"
    try:
        for wire, every in [("fp32", 1), ("bf16", 1), ("int8", 2)]:
            t0 = time.time()
            m = Word2Vec(
                vector_size=VEC, window=WINDOW, batch_size=BATCH,
                min_count=5, num_iterations=6, seed=1,
                steps_per_call=SPC, exchange="sparse",
                exchange_wire=wire, exchange_every=every,
            ).fit(sentences)
            syns = m.find_synonyms("austria", 10)
            words = [w for w, _ in syns]
            ana = m.analogy(
                positive=["vienna", "germany"], negative=["austria"],
                num=10,
            )
            vienna = "vienna" in words and dict(syns)["vienna"] > 0.5
            berlin = "berlin" in [w for w, _ in ana]
            st = m.training_metrics["exchange"]
            out[f"{wire}_every{every}"] = {
                "vienna_gate": bool(vienna),
                "berlin_gate": bool(berlin),
                "vienna_sim": round(float(dict(syns).get("vienna", 0)),
                                    4),
                "exchange_syncs_total": st["exchange_syncs_total"],
                "exchange_bytes_total": st["exchange_bytes_total"],
                "wall_seconds": round(time.time() - t0, 1),
            }
            m.stop()
    finally:
        if prev is None:
            os.environ.pop("GLINT_EXCHANGE_FORCE_WIRE", None)
        else:
            os.environ["GLINT_EXCHANGE_FORCE_WIRE"] = prev
    return out


def _kill_one_rank_drill(iterations: int) -> dict:
    """2-process supervised gloo fit with sparse exchange; SIGKILL one
    rank mid-run; assert the supervisor tears down, relaunches, resumes
    from the last committed checkpoint, and the fit completes."""
    tmp = tempfile.mkdtemp(prefix="multichip_drill_")
    corpus = os.path.join(tmp, "corpus.txt")
    # graftlint: ignore[atomic-persist] corpus fixture in the drill's private tmp dir
    with open(corpus, "w") as f:
        f.write("\n".join(_synth_corpus(2 * BASE_SENTENCES)) + "\n")
    report_path = os.path.join(tmp, "report.json")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    argv = [
        sys.executable, "-m", "glint_word2vec_tpu.cli", "supervise",
        "--workers", "2", "--max-restarts", "3",
        "--backoff-base", "0.5", "--backoff-cap", "5",
        "--heartbeat-stale", "300", "--startup-grace", "600",
        "--supervise-dir", os.path.join(tmp, "sup"),
        "--report-out", report_path,
        # SIGKILL rank 0 early in its SECOND epoch (~15 packed groups
        # per epoch at this config, so group 18 lands after ckpt-1's
        # barriered commit); the surviving rank wedges in the exchange
        # allgather — exactly the hang the supervisor's teardown
        # exists for — and the relaunch must resume from ckpt-1.
        "--rank0-env", "GLINT_FAULTS=worker.step:kill@18",
        "train",
        "--corpus", corpus, "--output", os.path.join(tmp, "model"),
        "--vector-size", str(VEC), "--window", str(WINDOW),
        "--batch-size", str(BATCH), "--min-count", str(MIN_COUNT),
        "--iterations", str(iterations), "--seed", "3",
        "--steps-per-call", str(SPC),
        "--exchange", "sparse",
        "--checkpoint-dir", os.path.join(tmp, "ck"),
        "--checkpoint-every", "1",
    ]
    t0 = time.time()
    out = subprocess.run(
        argv, env=env, capture_output=True, text=True, timeout=1500
    )
    wall = time.time() - t0
    report = (
        json.load(open(report_path))
        if os.path.exists(report_path) else {}
    )
    records = report.get("restart_records") or []
    resumed_from = records[0].get("resumed_from") if records else None
    ok = (
        out.returncode == 0
        and report.get("restarts") == 1
        and report.get("completed") is True
        and resumed_from is not None
    )
    if not ok:
        sys.stderr.write(out.stdout[-2000:] + out.stderr[-2000:])
    return {
        "ok": bool(ok),
        "restarts": report.get("restarts"),
        "completed": report.get("completed"),
        "resumed_from": resumed_from,
        "restart_records": records,
        "wall_seconds": round(wall, 1),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--world", type=int, default=1)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--workdir", default=".")
    ap.add_argument("--mode", default="sparse")
    ap.add_argument("--capacity", type=int, default=0)
    ap.add_argument("--wire", default="fp32",
                    choices=["fp32", "bf16", "int8"],
                    help="delta wire encoding for the sweep workers")
    ap.add_argument("--every", type=int, default=1,
                    help="coalesce exchange rounds over N groups")
    ap.add_argument("--iterations", type=int, default=2)
    ap.add_argument("--ranks", default="1,2",
                    help="comma list of world sizes for the sweep")
    ap.add_argument("--quick", action="store_true",
                    help="smaller parity config (CI smoke)")
    ap.add_argument("--drill", action="store_true",
                    help="also run the kill-one-rank supervised drill")
    ap.add_argument("--out",
                    default=os.path.join(ROOT, "MULTICHIP_BENCH.json"))
    args = ap.parse_args()
    if args.worker:
        return worker_main(args)

    ranks = [int(r) for r in args.ranks.split(",")]
    import jax

    platform = jax.default_backend()
    artifact = {
        "platform": platform,
        **(
            {} if platform == "tpu" else {
                "fallback": {
                    "reason": "no TPU in this environment: CPU gloo "
                              "gang (weak-scaling ranks share host "
                              "cores, so weak_efficiency understates "
                              "real multi-chip scaling; bytes/parity/"
                              "skew/checkpoint numbers are "
                              "platform-independent)",
                },
            }
        ),
        "config": {
            "vector_size": VEC, "window": WINDOW, "batch_size": BATCH,
            "steps_per_call": SPC, "iterations": args.iterations,
            "sentences_per_rank": BASE_SENTENCES,
            "vocab_words": VOCAB_WORDS,
            "sweep_wire": args.wire, "sweep_every": args.every,
        },
        "weak_scaling": [],
    }

    print("== in-process parity + bytes gates ==", flush=True)
    parity = _inprocess_parity(args.quick)
    artifact["parity"] = parity
    print(json.dumps(parity, indent=1), flush=True)

    print("== per-wire quality (vienna/berlin) ==", flush=True)
    quality = _wire_quality()
    artifact["wire_quality"] = quality
    print(json.dumps(quality, indent=1), flush=True)

    base_wps = None
    replicas_identical = True
    peak_bounded = True
    world1_bytes_per_sync = None
    world1_skips = None
    for world in ranks:
        print(f"== weak scaling: world={world} (sparse) ==", flush=True)
        results = _run_world(world, "sparse", 0, args.iterations,
                             args.wire, args.every)
        fps = {r["table_fingerprint"] for r in results}
        replicas_identical &= len(fps) == 1
        wps_rank = sum(r["words_per_sec"] for r in results) / world
        if world == 1:
            base_wps = wps_rank
        for r in results:
            shard_bytes = (r["vocab_size"] // max(world, 1) + 1) \
                * r["dim"] * 4
            peak_bounded &= (
                r["checkpoint"]["peak_block_bytes"]
                <= max(shard_bytes * 2, 1 << 20)
            )
        entry = {
            "world": world,
            "words_per_sec_per_rank": round(wps_rank, 1),
            "words_per_sec_total": round(wps_rank * world, 1),
            "weak_efficiency": (
                round(wps_rank / base_wps, 4) if base_wps else None
            ),
            "rank_skew": _rank_skew(results),
            "exchange_bytes_total": sum(
                r["exchange"].get("exchange_bytes_total", 0)
                for r in results
            ),
            "exchange_rows_total": sum(
                r["exchange"].get("exchange_rows_total", 0)
                for r in results
            ),
            "exchange_syncs_total": max(
                r["exchange"].get("exchange_syncs_total", 0)
                for r in results
            ),
            # What the dense schedule would ship per rank per sync at
            # this config (2 tables, fp32 wire) — context for the
            # measured sparse bytes; the >=5x gate rides the parity
            # config, whose vocab/touched ratio is the pod regime.
            "dense_equivalent_bytes_per_sync": (
                2 * results[0]["vocab_size"] * results[0]["dim"] * 4
            ),
            "sparse_bytes_per_sync_per_rank": (
                results[0]["exchange"].get("exchange_bytes_total", 0)
                // max(
                    results[0]["exchange"].get(
                        "exchange_syncs_total", 0
                    ), 1,
                )
            ),
            "checkpoint": {
                "save_seconds_max": max(
                    r["checkpoint"]["write_seconds"] or 0
                    for r in results
                ),
                "shard_write_seconds_max": max(
                    r["checkpoint"]["shard_write_seconds"] or 0
                    for r in results
                ),
                "restore_seconds_max": max(
                    r["checkpoint"]["restore_seconds"] for r in results
                ),
                "peak_block_bytes_max": max(
                    r["checkpoint"]["peak_block_bytes"]
                    for r in results
                ),
            },
            "per_rank": results,
        }
        if world == 1:
            world1_bytes_per_sync = entry["sparse_bytes_per_sync_per_rank"]
            world1_skips = results[0]["exchange"].get(
                "exchange_world1_skips_total", 0
            )
            entry["world1_skips_total"] = world1_skips
        artifact["weak_scaling"].append(entry)
        print(json.dumps(
            {k: v for k, v in entry.items() if k != "per_rank"},
            indent=1,
        ), flush=True)

    if args.drill:
        print("== kill-one-rank drill ==", flush=True)
        artifact["kill_one_rank"] = _kill_one_rank_drill(
            args.iterations + 1
        )
        print(json.dumps(artifact["kill_one_rank"], indent=1),
              flush=True)

    variants = parity["variants"]
    gates = {
        "sparse_bytes_5x": parity["dense_bytes_per_sync"]
        >= 5 * parity["sparse_bytes_per_sync"],
        # ISSUE 16: int8 wire + round coalescing moves >= 3x fewer
        # bytes per dispatch group than fp32 sparse at the same config.
        "int8_coalesced_3x": variants["fp32"]["bytes_per_group"]
        >= 3 * variants["int8_coalesced"]["bytes_per_group"],
        "wire_parity_ok": all(
            v["replicas_identical"] and v["dense_syncs"] == 0
            and v["drift_vs_fp32_max_abs"] <= WIRE_DRIFT_BOUND
            for v in variants.values()
        ),
        "wire_quality_ok": all(
            q["vienna_gate"] and q["berlin_gate"]
            for q in quality.values()
        ),
        "parity_ok": parity["parity_ok"],
        "spill_parity_ok": parity["spill_parity_ok"],
        "replicas_identical": replicas_identical,
        "ckpt_peak_bounded": peak_bounded,
        "weak_efficiency_recorded": all(
            e["weak_efficiency"] is not None
            for e in artifact["weak_scaling"][1:]
        ),
    }
    if world1_bytes_per_sync is not None:
        # one replica never touches the wire: bytes/sync must be 0 and
        # every round must be counted as a short-circuit skip.
        gates["world1_zero_bytes"] = (
            world1_bytes_per_sync == 0 and (world1_skips or 0) > 0
        )
    if args.drill:
        gates["kill_one_rank_ok"] = artifact["kill_one_rank"]["ok"]
    artifact["gates"] = gates
    artifact["all_gates_pass"] = all(gates.values())

    tmp_out = args.out + ".tmp"
    with open(tmp_out, "w") as f:
        json.dump(artifact, f, indent=1)
    os.replace(tmp_out, args.out)
    print(f"\ngates: {json.dumps(gates, indent=1)}")
    print(f"wrote {args.out}; all_gates_pass={artifact['all_gates_pass']}")
    return 0 if artifact["all_gates_pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
