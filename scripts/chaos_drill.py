"""Chaos drill: a supervised fit under a scripted kill schedule.

The executable proof of ISSUE 7's fault-domain layer AND ISSUE 8's
fleet-observability layer: run ``cli supervise`` (the real operator
entry point) over a training gang, arm a deterministic ``GLINT_FAULTS``
kill on rank 0 (``worker.step:kill@G`` — SIGKILL at the G-th dispatch
group, placed early in epoch 2 so at least one checkpoint has
committed), and assert the whole story end to end:

  * the supervisor detects the crash, tears the gang down (the surviving
    rank is wedged in a collective — exactly the hang this layer exists
    for), and relaunches exactly once;
  * the relaunch resumes from the last committed checkpoint
    (integrity-verified through ``utils.integrity.resolve_train_state``);
  * while the gang trains, the supervisor's MERGED ``/metrics`` endpoint
    answers with gang counters that equal the sum of the per-rank values
    and a ``rank_skew`` straggler gauge, and its Prometheus rendering
    lints clean;
  * the kill leaves a ``postmortem-0-0/`` flight-recorder bundle holding
    rank 0's event ring + last heartbeat, referenced from the
    supervisor's JSON report (``--report-out`` — this script consumes
    that report instead of re-deriving anything);
  * the per-rank event JSONLs merge into one rank-laned Chrome trace
    (``trace_summarize.py --merge-ranks``) with one lane per rank;
  * the fit completes and the final model clears the same vienna/berlin
    quality gates the CI smoke jobs use;
  * everything lands in ``FAULT_BENCH.json`` (repo root), comparable
    across PRs.

Env: GLINT_CHAOS_WORKERS (gang size, default 2; 1 = supervised
single-process fit), GLINT_CHAOS_ITERATIONS (default 6),
GLINT_CHAOS_OUT (artifact path override). Exits nonzero if any gate
fails.
"""

import json
import math
import os
import subprocess
import sys
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))
sys.path.insert(0, os.path.join(ROOT, "scripts"))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

from conftest import _make_tiny_corpus  # noqa: E402

# Scrub the virtual-8-device XLA flag the test conftest just installed
# (and anything the harness set): each WORKER must see exactly its own
# real device count, or the gang's (workers, 1) mesh covers only rank
# 0's devices and the cross-process collectives are malformed. The
# supervisor itself never touches a device.
os.environ.pop("XLA_FLAGS", None)

OUT = os.environ.get(
    "GLINT_CHAOS_OUT", os.path.join(ROOT, "FAULT_BENCH.json")
)

BATCH = 256
SPC = 4
WINDOW = 5
MIN_COUNT = 5


def _groups_per_epoch(sentences, workers: int) -> int:
    """Dispatch groups per epoch for this corpus/config — the unit the
    ``worker.step`` injection point counts in. Computed exactly the way
    the fit loops size their epochs so the kill schedule is
    deterministic: single-process runs the device-resident grid scan
    (ceil(positions/B) steps), multi-process runs the host-batcher
    lockstep schedule (ceil(max-shard-words/local-batch) steps)."""
    from glint_word2vec_tpu.corpus.batching import (
        chunk_sentences,
        encode_sentences,
    )
    from glint_word2vec_tpu.corpus.vocab import build_vocab
    from glint_word2vec_tpu.parallel.distributed import (
        per_process_word_counts,
    )

    vocab = build_vocab(sentences, min_count=MIN_COUNT)
    encoded = chunk_sentences(encode_sentences(sentences, vocab), 1000)
    lens = np.array([s.size for s in encoded], dtype=np.int64)
    if workers > 1:
        counts = per_process_word_counts(lens, workers)
        steps = max(1, math.ceil(int(counts.max()) / (BATCH // workers)))
    else:
        steps = max(1, math.ceil(int(lens.sum()) / BATCH))
    return max(1, math.ceil(steps / SPC))


def _fetch(url: str, timeout: float = 2.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def _scrape_merged(port: int, workers: int, proc) -> dict:
    """Poll the supervisor's merged endpoint while the gang trains;
    keep the best sample (all ranks reporting) plus one lint-checked
    Prometheus scrape. Never fails the drill by itself — missing
    samples turn into failed checks downstream."""
    from glint_word2vec_tpu.obs.prometheus import lint_prometheus_text

    best, prom_ok, healthz_seen = None, False, False
    while proc.poll() is None:
        try:
            merged = json.loads(
                _fetch(f"http://127.0.0.1:{port}/metrics")
            )
        except Exception:
            time.sleep(0.25)
            continue
        if merged.get("ranks_reporting"):
            if best is None or (
                merged["ranks_reporting"]
                >= best.get("ranks_reporting", 0)
            ):
                best = merged
        if not healthz_seen:
            try:
                _fetch(f"http://127.0.0.1:{port}/healthz")
                healthz_seen = True
            except Exception:
                pass
        if not prom_ok and merged.get("ranks_reporting") == workers:
            try:
                lint_prometheus_text(_fetch(
                    f"http://127.0.0.1:{port}/metrics?format=prometheus"
                ))
                prom_ok = True
            except Exception as e:
                print(f"prometheus scrape failed lint: {e}",
                      file=sys.stderr)
        time.sleep(0.25)
    return {"sample": best, "prometheus_lint_ok": prom_ok,
            "healthz_ok": healthz_seen}


def main() -> int:
    workers = int(os.environ.get("GLINT_CHAOS_WORKERS", 2))
    iterations = int(os.environ.get("GLINT_CHAOS_ITERATIONS", 6))
    import tempfile

    tmp = tempfile.mkdtemp(prefix="chaos_drill_")
    corpus = os.path.join(tmp, "capitals.txt")
    model_dir = os.path.join(tmp, "model")
    ck_dir = os.path.join(tmp, "ck")
    sup_dir = os.path.join(tmp, "supervisor")
    report_path = os.path.join(tmp, "report.json")
    sentences = _make_tiny_corpus()
    # graftlint: ignore[atomic-persist] corpus fixture in this drill's private tmp dir; nothing reads it across a crash
    with open(corpus, "w") as f:
        for s in sentences:
            f.write(" ".join(s) + "\n")

    gpe = _groups_per_epoch(sentences, workers)
    # Early in epoch 2 for the multi-process gang (its epoch-boundary
    # checkpoints are blocking + barriered, so ckpt-1 is committed
    # before any epoch-2 group dispatches); one epoch later for the
    # single-process async-checkpoint path, giving the background
    # writer a whole epoch of margin to commit.
    from glint_word2vec_tpu.parallel.supervisor import free_port

    kill_at = (gpe if workers > 1 else 2 * gpe) + 2
    fault = f"worker.step:kill@{kill_at}"
    metrics_port = free_port()

    train_rest = [
        "--corpus", corpus, "--output", model_dir,
        "--vector-size", "48", "--window", str(WINDOW),
        "--step-size", "0.025", "--batch-size", str(BATCH),
        "--negatives", "5", "--min-count", str(MIN_COUNT),
        "--iterations", str(iterations), "--seed", "1",
        "--steps-per-call", str(SPC),
        "--checkpoint-dir", ck_dir, "--checkpoint-every", "1",
    ]
    if workers > 1:
        train_rest += [
            "--num-partitions", str(workers), "--num-shards", "1",
        ]

    # The REAL operator entry point: cli supervise persists the report
    # (--report-out) and serves the merged gang endpoint; this script
    # consumes both instead of re-deriving anything in-process.
    argv = [
        sys.executable, "-m", "glint_word2vec_tpu.cli", "supervise",
        "--workers", str(workers),
        "--max-restarts", "3",
        "--backoff-base", "0.5", "--backoff-cap", "5",
        "--heartbeat-stale", "300", "--startup-grace", "600",
        "--supervise-dir", sup_dir,
        "--report-out", report_path,
        "--metrics-port", str(metrics_port),
        # Armed for rank 0's FIRST launch only — a re-armed relaunch
        # would die at the same group forever.
        "--rank0-env", f"GLINT_FAULTS={fault}",
        "train", *train_rest,
    ]

    print(
        f"chaos drill: {workers} worker(s), {gpe} groups/epoch, "
        f"armed {fault!r} on rank 0 generation 0; merged metrics on "
        f"port {metrics_port}",
        flush=True,
    )
    t0 = time.time()
    sup_log = os.path.join(tmp, "supervise.log")
    # graftlint: ignore[atomic-persist] live stdout/stderr sink for the supervise subprocess — a stream, not an artifact
    with open(sup_log, "wb") as logf:
        proc = subprocess.Popen(argv, stdout=logf,
                                stderr=subprocess.STDOUT)
        gang = _scrape_merged(metrics_port, workers, proc)
        rc = proc.wait()
    wall = time.time() - t0
    with open(sup_log, "rb") as f:
        print(f.read()[-4000:].decode(errors="replace"), flush=True)

    report = None
    if os.path.exists(report_path):
        report = json.load(open(report_path))

    out = {
        "metric": "chaos_drill",
        "workers": workers,
        "iterations": iterations,
        "groups_per_epoch": gpe,
        "fault": fault,
        "wall_seconds": round(wall, 2),
        "supervise_rc": rc,
        "supervisor": report,
    }

    checks = {
        "report_written": report is not None,
        "completed": bool(report and report["completed"]),
        "restarts_exactly_one": bool(report and report["restarts"] == 1),
        "resumed_from_committed_checkpoint": bool(
            report
            and report["restart_records"]
            and report["restart_records"][0]["resumed_from"]
        ),
        "merged_healthz_answered": gang["healthz_ok"],
        "merged_prometheus_lints": gang["prometheus_lint_ok"],
    }

    # -- merged gang endpoint: counters are sums, rank_skew present ----
    sample = gang["sample"]
    out["gang_metrics"] = sample
    merged_ok = sums_ok = skew_present = False
    if sample:
        merged_ok = sample.get("ranks_reporting", 0) >= 1
        per_rank = sample.get("per_rank") or {}
        counters = sample.get("counters") or {}
        sums_ok = (
            counters.get("steps_total")
            == sum(r.get("step") or 0 for r in per_rank.values())
            and counters.get("words_done_total")
            == sum(r.get("words_done") or 0 for r in per_rank.values())
        )
        # Not just key presence (the merge always emits the key): a
        # full-gang sample must carry a REAL skew number, or the
        # straggler gauge silently died (e.g. step_time vanished from
        # the heartbeat snapshot).
        skew = sample.get("rank_skew")
        skew_present = (
            isinstance(skew, (int, float)) and skew >= 1.0
            if sample.get("ranks_reporting") == workers
            else skew is not None
        )
    checks["merged_metrics_scraped"] = merged_ok
    checks["merged_counters_equal_rank_sums"] = sums_ok
    checks["rank_skew_present"] = skew_present

    # -- crash flight recorder: the killed rank's bundle ---------------
    bundle_ok = False
    if report and report["restart_records"]:
        bundles = report["restart_records"][0].get("postmortem") or []
        rank0 = [b for b in bundles if b.endswith("-0")]
        if rank0 and os.path.isdir(rank0[0]):
            files = set(os.listdir(rank0[0]))
            bundle_ok = {"heartbeat.json", "events.jsonl",
                         "meta.json"} <= files
            out["postmortem_bundle"] = {
                "path": rank0[0], "files": sorted(files),
            }
    checks["postmortem_bundle_collected"] = bundle_ok

    # -- rank-laned merged Chrome trace --------------------------------
    from trace_summarize import merge_rank_traces

    event_logs = [
        os.path.join(sup_dir, f"events-{r}.jsonl")
        for r in range(workers)
    ]
    trace_lanes_ok = False
    if all(os.path.exists(p) for p in event_logs):
        doc = merge_rank_traces(event_logs)
        lanes = {
            ev["pid"] for ev in doc["traceEvents"]
            if ev.get("ph") != "M"
        }
        trace_lanes_ok = len(lanes) == workers
        out["merged_trace"] = {
            "ranks": doc["otherData"]["ranks"],
            "events": len(doc["traceEvents"]),
            "lanes": sorted(lanes),
        }
    checks["merged_trace_one_lane_per_rank"] = trace_lanes_ok

    quality = {}
    if checks["completed"]:
        from glint_word2vec_tpu import load_model

        m = load_model(model_dir)
        syns = m.find_synonyms("austria", 10)
        ana = m.analogy(
            positive=["vienna", "germany"], negative=["austria"], num=10
        )
        quality = {
            "vienna_in_top10": "vienna" in [w for w, _ in syns],
            "vienna_score": round(dict(syns).get("vienna", 0.0), 4),
            "berlin_in_analogy_top10": "berlin" in [w for w, _ in ana],
        }
        checks["vienna_gate"] = bool(
            quality["vienna_in_top10"] and quality["vienna_score"] > 0.5
        )
        checks["berlin_gate"] = quality["berlin_in_analogy_top10"]
        state = json.load(open(os.path.join(ck_dir, "train_state.json")))
        checks["all_epochs_committed"] = (
            state["epochs_completed"] == iterations
        )
        out["final_train_state"] = {
            "epochs_completed": state["epochs_completed"],
            "ckpt": state["ckpt"],
            "prev_ckpt": (state.get("prev") or {}).get("ckpt"),
        }
        import jax

        dev = jax.devices()[0]
        out["platform"] = dev.platform
        if dev.platform != "tpu":
            out["fallback"] = dev.platform
    out["quality"] = quality
    out["checks"] = checks

    from glint_word2vec_tpu.utils import atomic_write_json

    atomic_write_json(OUT, out, indent=2)
    print(json.dumps(out, indent=2))
    if not all(checks.values()):
        print("chaos drill FAILED gates:", [
            k for k, v in checks.items() if not v
        ], file=sys.stderr)
        return 1
    print("chaos drill ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
