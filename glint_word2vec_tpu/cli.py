"""Command-line interface: train / query / inspect without writing code.

The reference's operational entry points are spark-submit invocations — the
trainer app and the standalone ``glint.Main`` PS-cluster launcher
(README.md:45-57, build.sbt:42-59, SURVEY.md §3.5). On TPU there is no
separate server process to launch (the "cluster" is the device mesh inside
this process), so the CLI collapses to:

  python -m glint_word2vec_tpu.cli train   --corpus c.txt --output m/ [...]
  python -m glint_word2vec_tpu.cli fit-stream --corpus - --publish-dir p/ [...]
  python -m glint_word2vec_tpu.cli synonyms --model m/ --word w [-n 10]
  python -m glint_word2vec_tpu.cli analogy  --model m/ --positive a b --negative c
  python -m glint_word2vec_tpu.cli transform --model m/ --sentence "w1 w2 w3"
  python -m glint_word2vec_tpu.cli info     --model m/
"""

from __future__ import annotations

import argparse
import json
import logging
import sys

from glint_word2vec_tpu.obs.canary import TrainingDiverged


def _add_train(sub):
    p = sub.add_parser("train", help="train a model from a text corpus")
    p.add_argument("--corpus", required=True, help="text file, one sentence per line")
    p.add_argument("--output", required=True, help="model output directory")
    p.add_argument("--lowercase", action="store_true")
    p.add_argument("--vector-size", type=int, default=100)
    p.add_argument("--window", type=int, default=5)
    p.add_argument("--step-size", type=float, default=0.01875)
    p.add_argument("--batch-size", type=int, default=1024)
    p.add_argument("--negatives", type=int, default=5)
    p.add_argument("--subsample-ratio", type=float, default=0.0)
    p.add_argument("--min-count", type=int, default=5)
    p.add_argument("--iterations", type=int, default=1)
    p.add_argument("--max-sentence-length", type=int, default=1000)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--num-partitions", type=int, default=1,
                   help="data-parallel mesh axis (reference numPartitions)")
    p.add_argument("--num-shards", type=int, default=1,
                   help="model-parallel mesh axis (reference numParameterServers)")
    p.add_argument("--dtype", choices=["float32", "bfloat16"], default="float32")
    p.add_argument("--compute-dtype", choices=["float32", "bfloat16"],
                   default=None,
                   help="MXU operand dtype for the step's dense "
                        "contractions (f32 accumulation either way)")
    p.add_argument("--steps-per-call", type=int, default=16,
                   help="minibatches per device dispatch (on-device scan)")
    p.add_argument("--architecture", choices=["skipgram", "cbow"],
                   default="skipgram",
                   help="the model: skip-gram (default) or CBOW with "
                        "negative sampling (word2vec's -cbow 1: the mean "
                        "of a position's context rows predicts its word; "
                        "with --fasttext, `fasttext cbow`: one mean over "
                        "the subword rows of the bag's words). CBOW takes "
                        "the corpus-resident path only and is refused "
                        "with --shared-negatives, --packing grid or "
                        "--exchange; saved with the model")
    p.add_argument("--position-weights", action="store_true",
                   help="CBOW with position weights (Mikolov et al. LREC "
                        "2018, arXiv:1712.09405 s2.2; the recipe of the "
                        "published cc.<lang>.300 fastText tables, Grave "
                        "et al. arXiv:1802.06893): a trained vector for "
                        "each relative position of the window multiplies "
                        "the word a bag holds there before the bag is "
                        "summed; a third table, (2 x window, vector "
                        "size), saved with the model. Needs "
                        "--architecture cbow, with or without --fasttext")
    p.add_argument("--shared-negatives", type=int, default=0,
                   help="shared noise-pool size per step "
                        "(0 = per-pair reference semantics)")
    p.add_argument("--packing", choices=["dense", "grid"], default="dense",
                   help="device-corpus dispatch shape: dense pair "
                        "packing (default — valid pairs compacted into "
                        "dense pair batches on device) or the legacy "
                        "grid window batches (~43%% live lanes at "
                        "window 5)")
    p.add_argument("--exchange", choices=["none", "sparse", "dense"],
                   default="none",
                   help="cross-replica reconciliation for multi-process "
                        "runs (ISSUE 15): sparse ships only touched-row "
                        "(ids, deltas) between data-parallel replicas "
                        "after every dispatch group; dense ships full "
                        "table deltas (parity baseline); none keeps the "
                        "SPMD global-mesh path")
    p.add_argument("--exchange-capacity", type=int, default=0,
                   help="fixed touched-row buffer capacity per exchange "
                        "sync (0 = auto-sized from the dispatch-group "
                        "pair budget, then adapted down from observed "
                        "telemetry; nonzero pins it)")
    p.add_argument("--exchange-wire", choices=["fp32", "bf16", "int8"],
                   default="fp32",
                   help="sparse exchange payload encoding (ISSUE 16): "
                        "fp32 exact, bf16 half-width, or int8 with "
                        "per-row maxabs scales and error-feedback "
                        "residual carry (unbiased update stream); "
                        "dense/spill/flush rounds always ship fp32")
    p.add_argument("--exchange-every", type=int, default=1,
                   help="coalesce this many dispatch groups into one "
                        "exchange round (hot rows repeatedly touched "
                        "in the window cost one wire row); 1 = sync "
                        "every group")
    p.add_argument("--exchange-topology", choices=["flat", "twolevel"],
                   default="flat",
                   help="exchange sync topology: flat allgather, or "
                        "twolevel — exact intra-node hop + leaders-only "
                        "quantized inter-node hop (GLINT_RANKS_PER_NODE "
                        "sets the node size)")
    p.add_argument("--exchange-shard",
                   choices=["roundrobin", "locality"],
                   default="roundrobin",
                   help="replica corpus sharding: roundrobin interleave "
                        "or locality (sentences clustered by rarest "
                        "token to concentrate per-rank touched rows)")
    p.add_argument("--checkpoint-dir", default=None,
                   help="enable epoch-granular checkpoint/resume")
    p.add_argument("--checkpoint-every", type=int, default=1,
                   help="epochs between checkpoints (default 1). Saves "
                        "are asynchronous by default — the fit thread "
                        "blocks only for the device->host snapshot "
                        "copy; write + commit run on a background "
                        "thread (GLINT_SYNC_CKPT=1 forces blocking "
                        "saves)")
    p.add_argument("--metrics-out", default=None,
                   help="write training metrics JSON here (atomic write)")
    obs = p.add_argument_group(
        "observability",
        "run-wide observability: live heartbeat, span event log, "
        "divergence canary (all opt-in, zero overhead when off)",
    )
    obs.add_argument("--status-port", type=int, default=None,
                     help="serve a live training heartbeat on this port: "
                          "GET /healthz and /metrics (JSON by default; "
                          "?format=prometheus for scrape-ready text). "
                          "0 binds an ephemeral port")
    obs.add_argument("--status-host", default="127.0.0.1",
                     help="heartbeat bind address (default 127.0.0.1)")
    obs.add_argument("--status-file", default=None,
                     help="atomically mirror the status snapshot JSON to "
                          "this path (for multihost workers that can't "
                          "bind ports)")
    obs.add_argument("--event-log", default=None,
                     help="JSONL span/event log of the fit's phases "
                          "(subsample-compact, host batching, device "
                          "dispatch, checkpoints) plus engine events "
                          "(table mutations, query-shape compiles)")
    obs.add_argument("--event-capacity", type=int, default=65536,
                     help="in-memory event ring bound; overflow is "
                          "counted, never unbounded (default 65536)")
    obs.add_argument("--steptime-out", default=None,
                     help="write the per-run step-time attribution "
                          "ledger (STEPTIME.json) here at fit end: "
                          "fit-thread wall seconds by phase (dispatch, "
                          "readback_harvest, producer_wait, compact, "
                          "checkpoint, other) + per-phase span-duration "
                          "quantiles")
    obs.add_argument("--chrome-trace", default=None,
                     help="write the event log as chrome://tracing / "
                          "Perfetto JSON at run end (merge with device "
                          "xplane tables via scripts/trace_summarize.py "
                          "--host-spans)")
    obs.add_argument("--canary", choices=["off", "warn", "abort"],
                     default="off",
                     help="divergence canary over a rolling loss window: "
                          "'warn' logs and records an event; 'abort' "
                          "writes a final ckpt-diverged snapshot + "
                          "flushes the event log, then fails the run")
    obs.add_argument("--canary-window", type=int, default=64,
                     help="rolling loss window size (default 64)")
    obs.add_argument("--canary-factor", type=float, default=10.0,
                     help="trip when loss exceeds factor x the window "
                          "median (default 10.0); NaN/Inf always trips")
    obs.add_argument("--canary-check-every", type=int, default=32,
                     help="steps between canary loss syncs; each check "
                          "blocks the async dispatch pipeline for one "
                          "device sync (default 32)")
    dist_g = p.add_argument_group(
        "distributed",
        "multi-process bring-up (the supervise subcommand fills these "
        "in per worker; set them by hand for custom launchers)",
    )
    dist_g.add_argument("--coordinator", default=None,
                        help="host:port of the jax.distributed "
                             "coordinator (process 0 binds it)")
    dist_g.add_argument("--num-processes", type=int, default=None,
                        help="total worker processes in the gang")
    dist_g.add_argument("--process-id", type=int, default=None,
                        help="this worker's rank in [0, num-processes)")
    p.add_argument("--fasttext", action="store_true",
                   help="train the subword (fastText-style) family")
    p.add_argument("--min-n", type=int, default=3,
                   help="min char-ngram length (fastText family)")
    p.add_argument("--max-n", type=int, default=6,
                   help="max char-ngram length (fastText family)")
    p.add_argument("--bucket", type=int, default=2_000_000,
                   help="subword hash-bucket rows (fastText family)")
    p.add_argument("--max-subwords", type=int, default=32,
                   help="max subword rows per word (fastText family)")


def _add_fit_stream(sub):
    p = sub.add_parser(
        "fit-stream",
        help="incremental (ISGNS) training on an unbounded sentence "
             "stream: online vocab growth, adaptive distributions, and "
             "committed generation publishing a `serve "
             "--watch-checkpoint` fleet hot-swaps under load",
        description="Incremental (ISGNS, arXiv:1704.03956) training on a "
                    "sentence stream it sees once. Run on one v5e chip at "
                    "two f32 tables of 2,065,536 x 300 (2M base words and "
                    "65,536 spare rows, --buffer-words 1048576): "
                    "about 1.1M raw words/s (PERF.md, the cell "
                    "w2v-stream-300-2m.train): a round of 1M kept words "
                    "is 1.06 s of the device's work, and the host's half "
                    "of a round (0.55 s: fill, promotion, refresh) runs "
                    "up to two rounds ahead, behind it, so the device "
                    "sets the pace and idles 4% of the time.",
    )
    p.add_argument("--corpus", default="-",
                   help="sentence source, one per line: a file path, or "
                        "'-' (default) for stdin — pipe a live feed in")
    p.add_argument("--follow", action="store_true",
                   help="tail the --corpus file forever (tail -f "
                        "semantics): keep polling for appended lines "
                        "instead of stopping at EOF")
    p.add_argument("--lowercase", action="store_true")
    p.add_argument("--publish-dir", default=None,
                   help="publish committed model generations here "
                        "(gen-NNNNNN dirs + LATEST.json pointer) for "
                        "serving replicas to hot-swap")
    p.add_argument("--publish-every", type=float, default=30.0,
                   help="seconds between generation publishes "
                        "(default 30; whichever of the time/word "
                        "cadences fires first publishes)")
    p.add_argument("--publish-words", type=int, default=None,
                   help="also publish every N trained words")
    p.add_argument("--output", default=None,
                   help="save the final model here when the stream ends")
    p.add_argument("--bootstrap-words", type=int, default=10000,
                   help="stream prefix scanned batch-style to seed the "
                        "base vocabulary (default 10000 words)")
    p.add_argument("--buffer-words", type=int, default=65536,
                   help="mini-epoch buffer capacity in words (fixed "
                        "shape: every round reuses the same compiled "
                        "programs; default 65536)")
    p.add_argument("--extra-rows", type=int, default=1024,
                   help="spare table rows reserved for online vocab "
                        "growth — the promotion budget (default 1024)")
    p.add_argument("--refresh-words", type=int, default=None,
                   help="kept-word cadence for recomputing the adaptive "
                        "noise/subsample distributions (default: one "
                        "buffer, so every round; a promotion refreshes "
                        "at once whatever this says). A refresh re-derives "
                        "an alias table for EVERY base word: about 0.13 s "
                        "of host time at 2M words, behind the device's "
                        "work (PERF.md, the cell w2v-stream-300-2m.train: "
                        "the host's half of a round of 1M kept words is "
                        "0.55 s, the device's 1.06 s); raise it when the "
                        "vocabulary is large and the buffer small, so that "
                        "the host, not the device, would set the pace")
    p.add_argument("--max-words", type=int, default=None,
                   help="stop after training this many words (bounded "
                        "runs/smokes; default: run until the stream ends)")
    p.add_argument("--max-seconds", type=float, default=None,
                   help="stop after this much wall time")
    p.add_argument("--vector-size", type=int, default=100)
    p.add_argument("--window", type=int, default=5)
    p.add_argument("--step-size", type=float, default=0.01875)
    p.add_argument("--batch-size", type=int, default=1024)
    p.add_argument("--negatives", type=int, default=5)
    p.add_argument("--subsample-ratio", type=float, default=0.0)
    p.add_argument("--min-count", type=int, default=5,
                   help="bootstrap admission AND promotion threshold "
                        "(a candidate's guaranteed sketch count must "
                        "clear it)")
    p.add_argument("--max-sentence-length", type=int, default=1000)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--num-partitions", type=int, default=1)
    p.add_argument("--num-shards", type=int, default=1)
    p.add_argument("--steps-per-call", type=int, default=16)
    p.add_argument("--metrics-out", default=None,
                   help="write final training metrics JSON here "
                        "(atomic write)")
    obs = p.add_argument_group(
        "observability",
        "live heartbeat with the streaming gauge set: stream lag, "
        "vocab growth, distribution drift, publish cadence "
        "(glint_stream_* in the Prometheus exposition)",
    )
    obs.add_argument("--status-port", type=int, default=None,
                     help="serve /healthz + /metrics for the stream "
                          "trainer (0 binds an ephemeral port)")
    obs.add_argument("--status-host", default="127.0.0.1")
    obs.add_argument("--status-file", default=None,
                     help="atomically mirror the status snapshot JSON "
                          "to this path")
    obs.add_argument("--event-log", default=None,
                     help="JSONL span/event log (stream_fill, "
                          "device_steps, publish, table mutations)")


def _add_ann_flags(p):
    ann = p.add_argument_group(
        "approximate serving (ANN index)",
        "two-stage device top-k: k-means centroids trained on-device "
        "from the live table, coarse scores pick nprobe clusters, "
        "exact rerank inside them — O(sqrt(V)*d)-ish per query with a "
        "measured recall@10 gate against the exact path (per-request "
        '{"exact": true} always escapes to the exact masked GEMM)',
    )
    ann.add_argument("--ann", action="store_true",
                     help="enable the approximate /synonyms path "
                          "(built + recall-gated before the port "
                          "binds; refreshed on every hot-swap; "
                          "word-level models only: a subword model "
                          "stays exact, with a warning)")
    ann.add_argument("--ann-clusters", type=int, default=-1,
                     help="coarse cluster count (-1 auto: "
                          "next_pow2(sqrt(rows)))")
    ann.add_argument("--ann-nprobe", type=int, default=8,
                     help="clusters probed per query (default 8)")
    ann.add_argument("--ann-iters", type=int, default=6,
                     help="k-means sweeps per index build (default 6)")
    ann.add_argument("--ann-sample", type=int, default=65536,
                     help="rows sampled for centroid training "
                          "(default 65536; the full table is always "
                          "assigned)")
    ann.add_argument("--ann-recall-gate", type=float, default=0.95,
                     help="minimum measured recall@10 vs the exact "
                          "path; below it the exact path keeps "
                          "serving (default 0.95)")
    ann.add_argument("--ann-recall-sample", type=int, default=64,
                     help="query rows sampled per recall measurement "
                          "(default 64)")


def _ann_kwargs(args) -> dict:
    return dict(
        ann=args.ann,
        ann_clusters=args.ann_clusters,
        ann_nprobe=args.ann_nprobe,
        ann_iters=args.ann_iters,
        ann_sample=args.ann_sample,
        ann_recall_gate=args.ann_recall_gate,
        ann_recall_sample=args.ann_recall_sample,
    )


def _add_query(sub):
    p = sub.add_parser("synonyms", help="nearest neighbors of a word")
    p.add_argument("--model", required=True)
    p.add_argument("--word", required=True)
    p.add_argument("-n", "--num", type=int, default=10)

    p = sub.add_parser("analogy", help="a is to b as c is to ?")
    p.add_argument("--model", required=True)
    p.add_argument("--positive", nargs="+", required=True)
    p.add_argument("--negative", nargs="+", default=[])
    p.add_argument("-n", "--num", type=int, default=10)

    p = sub.add_parser("transform", help="embed a sentence (mean of word vectors)")
    p.add_argument("--model", required=True)
    p.add_argument("--sentence", required=True, help="whitespace-tokenized")

    p = sub.add_parser("info", help="model metadata")
    p.add_argument("--model", required=True)

    p = sub.add_parser(
        "serve",
        help="serve a saved model over HTTP (the separate-PS-cluster "
             "deployment analogue: trainers/clients come and go, the "
             "model stays resident); a word-level or a subword "
             "(--fasttext) model, both on the coalesced, cached, "
             "pre-warmed path: a subword model also answers words "
             "that are not in its dictionary, from their n-gram rows",
        description=(
            "Serve a saved model over HTTP. A model saved with "
            "--num-shards n is served where it lies: the saved topology "
            "is re-homed on the live devices (the model axis clamped to "
            "their number), each holds 1/n of the rows of both tables, "
            "and every query is answered by all of them (a shard's own "
            "top-k, merged over the model axis) with no option here. "
            "GET /metrics reports, under the model's entry, `shards`, "
            "`rows_per_shard`, `resident_bytes` (the model's total over "
            "ALL its devices, which --model-memory-budget is held "
            "against) and `resident_bytes_per_device` (what the fullest "
            "device holds of it)."
        ),
    )
    p.add_argument("--model", default=None,
                   help="saved model directory (optional when "
                        "--watch-checkpoint names a publish dir: the "
                        "newest committed generation boots the server)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8801)
    p.add_argument("--watch-checkpoint", default=None, metavar="DIR",
                   help="follow a fit-stream publish directory: each "
                        "new committed generation (LATEST.json) is "
                        "staged off the request path and hot-swapped "
                        "into the live engine — no dropped requests, "
                        "no post-warmup compiles (POST /reload forces "
                        "an immediate poll)")
    p.add_argument("--watch-poll", type=float, default=1.0,
                   help="seconds between LATEST.json polls (default 1)")
    p.add_argument("--max-batch", type=int, default=64,
                   help="coalesced /synonyms dispatch cap (rounded up to "
                        "a power of two; Q shape buckets warm up to it)")
    p.add_argument("--no-warmup", action="store_true",
                   help="skip pre-binding compilation of the serving "
                        "shape family (first requests then pay jit "
                        "compiles)")
    p.add_argument("--cache-size", type=int, default=65536,
                   help="synonym result-cache entries (0 disables); "
                        "invalidated wholesale on any table mutation")
    p.add_argument("--port-file", default=None, metavar="FILE",
                   help="write the bound {host, port} JSON here once "
                        "the server is warmed and listening (the "
                        "fleet launcher's readiness barrier for "
                        "--port 0)")
    p.add_argument("--trace-log", default=None, metavar="FILE",
                   help="record request-path phase spans (tail-sampled "
                        "distributed tracing) to this JSONL sink; "
                        "stitch per-process sinks with `cli "
                        "trace-merge` and open the result in Perfetto")
    p.add_argument("--flight-dir", default=None, metavar="DIR",
                   help="arm the anomaly flight recorder: a shed burst "
                        "or an SLO fast-burn writes a postmortem "
                        "bundle (recent spans + full metrics) here")
    _add_ann_flags(p)
    over = p.add_argument_group(
        "overload protection",
        "bounded admission + per-request deadlines + degraded "
        "cache-only mode, so a traffic spike sheds load instead of "
        "queueing without bound (counters on /metrics)",
    )
    over.add_argument("--max-inflight", type=int, default=256,
                      help="admission high-water mark: device-touching "
                           "requests beyond this many in flight are "
                           "shed with 429 + Retry-After (0 disables; "
                           "default 256)")
    over.add_argument("--request-deadline", type=float, default=30.0,
                      help="per-request deadline seconds: a request "
                           "that cannot reach the device in time is "
                           "answered 504 instead of occupying a "
                           "dispatch slot (0 disables; default 30)")
    over.add_argument("--degraded-after", type=float, default=5.0,
                      help="device-lock hold seconds after which the "
                           "server enters degraded cache-only mode "
                           "(serve cache hits, shed misses with 429) "
                           "until the lock frees (0 disables; "
                           "default 5)")
    mm = p.add_argument_group(
        "multi-model serving (ISSUE 20)",
        "one server hosting N models behind one port: route with the "
        "/m/<id>/ path prefix or the X-Glint-Model header (no id = the "
        "default model, full back-compat); same-shape models share "
        "every compiled program, so model #2..N loads with zero "
        "compiles",
    )
    mm.add_argument("--add-model", action="append", default=[],
                    metavar="ID=DIR",
                    help="load an extra named model into the catalog "
                         "(repeatable); DIR is a saved model dir or a "
                         "committed publish generation")
    mm.add_argument("--model-memory-budget", default=None,
                    metavar="BYTES",
                    help="device-memory budget for resident tables, "
                         "a model's total over ALL its devices "
                         "(suffixes kb/mb/gb); over budget, the "
                         "least-recently-used unpinned model is staged "
                         "out to its committed snapshot and staged "
                         "back in off the request path on first miss")
    mm.add_argument("--watch-models", default=None, metavar="DIR",
                    help="catalog root: each subdirectory with a "
                         "LATEST.json is one model's publish dir "
                         "(subdir name = model id), followed with its "
                         "own hot-swap watcher")

    p = sub.add_parser(
        "serve-fleet",
        help="launch N serving replicas following one model (or one "
             "publish dir) behind a front load balancer: round-robin "
             "spread, overload-aware retry on the replicas' 429/503 "
             "backpressure, one merged fleet /metrics exposition",
    )
    p.add_argument("--model", default=None,
                   help="saved model directory every replica loads")
    p.add_argument("--watch-checkpoint", default=None, metavar="DIR",
                   help="publish dir every replica follows (each "
                        "committed generation hot-swaps the WHOLE "
                        "fleet, index refresh included)")
    p.add_argument("--watch-poll", type=float, default=1.0)
    p.add_argument("--replicas", type=int, default=2,
                   help="serving process count (default 2)")
    p.add_argument("--host", default="127.0.0.1",
                   help="balancer bind address")
    p.add_argument("--port", type=int, default=8800,
                   help="balancer port (0 = ephemeral; replicas "
                        "always bind ephemeral ports)")
    p.add_argument("--port-file", default=None, metavar="FILE",
                   help="write the balancer's bound {host, port} here "
                        "once the fleet is up")
    p.add_argument("--replica-log-dir", default=None, metavar="DIR",
                   help="capture one replica-N.log per process "
                        "(default: replicas inherit stderr)")
    p.add_argument("--trace-dir", default=None, metavar="DIR",
                   help="end-to-end distributed tracing root: the "
                        "balancer records to balancer.jsonl, every "
                        "replica to replica-N.jsonl, anomaly bundles "
                        "land under flight/ — stitch with `cli "
                        "trace-merge <DIR> --out trace.json`")
    p.add_argument("--max-batch", type=int, default=64)
    p.add_argument("--cache-size", type=int, default=65536)
    p.add_argument("--max-inflight", type=int, default=256)
    p.add_argument("--request-deadline", type=float, default=30.0)
    p.add_argument("--degraded-after", type=float, default=5.0)
    _add_ann_flags(p)
    heal = p.add_argument_group(
        "self-healing (ISSUE 14)",
        "replica supervision (waitpid + /healthz probes with a launch-"
        "generation handshake), probe-driven circuit breaking, and — "
        "with --watch-checkpoint — rolling generation rollout behind a "
        "shadow-canary promotion gate",
    )
    heal.add_argument("--max-restarts", type=int, default=3,
                      help="per-replica relaunch budget before the "
                           "replica is left down (fleet serves from "
                           "the survivors; default 3)")
    heal.add_argument("--backoff-base", type=float, default=1.0,
                      help="first relaunch delay seconds (doubles per "
                           "restart, capped at --backoff-cap)")
    heal.add_argument("--backoff-cap", type=float, default=30.0)
    heal.add_argument("--hang-kill-after", type=float, default=10.0,
                      help="continuous probe-failure seconds after "
                           "which a live replica process is killed "
                           "and relaunched (hung-replica detection)")
    heal.add_argument("--probe-interval", type=float, default=0.5,
                      help="seconds between active /healthz probes "
                           "per replica")
    heal.add_argument("--probe-timeout", type=float, default=2.0)
    heal.add_argument("--breaker-failures", type=int, default=3,
                      help="consecutive probe/connect failures that "
                           "eject a replica from rotation (breaker "
                           "opens)")
    heal.add_argument("--breaker-successes", type=int, default=2,
                      help="consecutive half-open trial successes "
                           "that readmit it")
    heal.add_argument("--breaker-open-seconds", type=float, default=2.0,
                      help="open-breaker cooldown before half-open "
                           "trials begin")
    heal.add_argument("--replica0-env", action="append", default=[],
                      metavar="KEY=VAL",
                      help="env var applied to replica 0's FIRST "
                           "launch only (repeatable) — the chaos-drill "
                           "seam for arming a GLINT_FAULTS schedule on "
                           "one replica without re-killing every "
                           "relaunch")
    heal.add_argument("--uncoordinated-watch", action="store_true",
                      help="legacy behavior: every replica follows "
                           "--watch-checkpoint itself (simultaneous "
                           "fleet-wide swaps, no rolling rollout, no "
                           "canary gate)")
    can = p.add_argument_group(
        "shadow-canary promotion gate",
        "before a rolling rollout proceeds, the candidate generation "
        "serves MIRRORED traffic on one ejected replica and must "
        "agree with the live fleet (top-k overlap) — regression means "
        "automatic hold-back, counted on /metrics, with the candidate "
        "left on disk",
    )
    can.add_argument("--no-canary", action="store_true",
                     help="skip the canary gate (rolling rollout "
                          "proceeds directly)")
    can.add_argument("--canary-mirror-every", type=int, default=4,
                     help="mirror every Nth live /synonyms|/analogy "
                          "request to the canary (default 4)")
    can.add_argument("--canary-min-scores", type=int, default=8,
                     help="responses to score before deciding "
                          "(default 8)")
    can.add_argument("--canary-mirror-seconds", type=float, default=10.0,
                     help="max seconds to collect mirrored responses "
                          "(default 10)")
    can.add_argument("--canary-agreement", type=float, default=0.6,
                     help="mean top-k agreement the candidate must "
                          "clear to promote (default 0.6)")
    can.add_argument("--canary-top-k", type=int, default=10)
    can.add_argument("--canary-probes", default=None, metavar="FILE",
                     help="JSON list of deterministic probe requests "
                          '([{"path": "/synonyms", "body": {...}}, '
                          "...]) posted to both the live fleet and "
                          "the canary and scored for agreement — the "
                          "vienna/berlin + capital-of analogy gates, "
                          "restated as live-vs-candidate checks")
    dp = p.add_argument_group(
        "demand-driven fleet (ISSUE 19)",
        "multi-process balancer data plane (N processes sharing one "
        "listen port), warm-spare autoscaling driven by shed-rate/p95/"
        "burn signals, and per-tenant QoS admission at the front door",
    )
    dp.add_argument("--balancer-procs", type=int, default=1,
                    help="balancer process count sharing the listen "
                         "port (SO_REUSEPORT, falling back to an "
                         "inherited listener fd); 1 = the classic "
                         "single in-process balancer (default)")
    dp.add_argument("--warm-spares", type=int, default=0,
                    help="extra replicas launched and fully warmed at "
                         "boot but HELD out of rotation as spares; "
                         "the autoscaler readmits them under load "
                         "(scale-up is never a cold boot)")
    dp.add_argument("--autoscale-interval", type=float, default=0.5,
                    help="autoscaler policy-evaluation period seconds "
                         "(default 0.5)")
    dp.add_argument("--autoscale-up-shed-rate", type=float, default=1.0,
                    help="fleet shed rate (sheds/sec, QoS sheds "
                         "included) that counts as scale-up pressure "
                         "(default 1.0)")
    dp.add_argument("--autoscale-up-p95-ms", type=float, default=None,
                    help="forward-path p95 ms that counts as scale-up "
                         "pressure (default: the SLO latency target, "
                         "GLINT_SLO_LATENCY_MS or 250)")
    dp.add_argument("--autoscale-up-window", type=float, default=1.0,
                    help="seconds pressure must be sustained before a "
                         "readmit (default 1)")
    dp.add_argument("--autoscale-down-window", type=float, default=10.0,
                    help="seconds of idle before a live replica is "
                         "parked back to spare (default 10)")
    dp.add_argument("--autoscale-cooldown", type=float, default=5.0,
                    help="minimum seconds between any two autoscale "
                         "transitions (default 5)")
    dp.add_argument("--qos-tenant-rate", type=float, default=None,
                    help="per-tenant token-bucket refill rate "
                         "(requests/sec, tenant from X-Glint-Tenant, "
                         "'default' bucket otherwise); unset = no "
                         "tenant quotas")
    dp.add_argument("--qos-tenant-burst", type=float, default=None,
                    help="per-tenant bucket depth (default 2x rate)")
    dp.add_argument("--qos-bulk-max-inflight", type=int, default=None,
                    help="concurrent in-flight cap for the bulk "
                         "priority class (X-Glint-Priority: bulk); "
                         "unset = no class cap")
    fmm = p.add_argument_group(
        "multi-model fleet (ISSUE 20)",
        "every replica hosts the same model catalog behind the one "
        "balancer port; each watched model gets its OWN rolling "
        "rollout + canary gate, so one model's LATEST.json move never "
        "touches another model's replica state",
    )
    fmm.add_argument("--add-model", action="append", default=[],
                     metavar="ID=DIR",
                     help="extra named model every replica loads "
                          "(repeatable)")
    fmm.add_argument("--watch-model", action="append", default=[],
                     metavar="ID=DIR",
                     help="publish dir followed for model ID with a "
                          "per-model rolling rollout (repeatable; "
                          "also loads the model at its newest "
                          "committed generation when no --add-model "
                          "pins a boot point)")
    fmm.add_argument("--model-memory-budget", default=None,
                     metavar="BYTES",
                     help="per-replica resident-table budget, a "
                          "model's total over all its devices "
                          "(suffixes kb/mb/gb); LRU stage-out beyond "
                          "it")

    p = sub.add_parser(
        "fleet-shard",
        help="INTERNAL: one balancer data-plane shard of `serve-fleet "
             "--balancer-procs N` — accepts from the shared fleet "
             "port, driven by the supervisor over a private control "
             "channel; never invoke by hand",
    )
    p.add_argument("--config", required=True, metavar="FILE",
                   help="shard config JSON written by the supervisor")

    p = sub.add_parser(
        "supervise",
        help="run a train command under the elastic supervisor: gang "
             "launch, crash/hang detection, teardown, resume from the "
             "last committed checkpoint with capped backoff",
    )
    p.add_argument("--workers", type=int, default=1,
                   help="worker process count (1 = supervised "
                        "single-process fit; >1 launches a "
                        "jax.distributed gang on a local coordinator)")
    p.add_argument("--max-restarts", type=int, default=3,
                   help="gang restart budget before giving up")
    p.add_argument("--backoff-base", type=float, default=1.0,
                   help="first restart delay seconds (doubles per "
                        "restart, capped at --backoff-cap)")
    p.add_argument("--backoff-cap", type=float, default=30.0)
    p.add_argument("--heartbeat-stale", type=float, default=120.0,
                   help="status-file heartbeat age that counts as a "
                        "hang (0 disables hang detection)")
    p.add_argument("--startup-grace", type=float, default=600.0,
                   help="seconds a fresh worker may run without a "
                        "first heartbeat (cold jax compiles are slow)")
    p.add_argument("--supervise-dir", default=None,
                   help="status files + worker logs directory "
                        "(default: <checkpoint-dir>/supervisor)")
    p.add_argument("--report-out", default=None,
                   help="write the supervisor report JSON here too "
                        "(it always prints to stdout): restarts, "
                        "per-restart detect->relaunch/heartbeat "
                        "latency, and the postmortem bundle paths the "
                        "flight recorder collected")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve the MERGED gang observability endpoint "
                        "on this port (0 = ephemeral): one /metrics "
                        "(JSON; ?format=prometheus for scrape-ready "
                        "text) + /healthz for the whole gang — summed "
                        "counters, per-rank words/sec, the rank_skew "
                        "straggler gauge, merged step-time ledger — "
                        "generation-stamped so pre-restart scrapes are "
                        "never mixed in")
    p.add_argument("--metrics-host", default="127.0.0.1",
                   help="merged-endpoint bind address")
    p.add_argument("--join-serving", action="append", default=[],
                   metavar="URL",
                   help="serving-replica JSON /metrics URL to join "
                        "into the merged exposition (repeatable; "
                        "scraped per request, replica failures "
                        "reported, never fatal)")
    p.add_argument("--rank0-env", action="append", default=[],
                   metavar="KEY=VAL",
                   help="env var applied to rank 0's FIRST launch only "
                        "(generation 0, repeatable) — the chaos-drill "
                        "seam for arming a GLINT_FAULTS schedule "
                        "without re-killing every relaunch")
    p.add_argument(
        "train_args", nargs=argparse.REMAINDER,
        help="the train command to supervise: everything after the "
             "supervise flags, e.g. `supervise --workers 2 train "
             "--corpus c.txt --output m/ --checkpoint-dir ck/`. "
             "--checkpoint-dir is REQUIRED (recovery resumes from it); "
             "the supervisor appends per-worker --status-file and "
             "distributed flags itself",
    )

    p = sub.add_parser(
        "transform-file",
        help="bulk-embed a sentence file into resumable .npy vector "
             "shards (the offline DataFrame-transform analogue: "
             "compile-once packed pull-average batches at full device "
             "utilization)",
    )
    p.add_argument("--model", required=True)
    p.add_argument("--input", required=True,
                   help="one whitespace-tokenized sentence per line; "
                        "blank/all-OOV lines become zero vectors — "
                        "output row i always aligns with input line i")
    p.add_argument("--out", required=True,
                   help="shard directory (per-rank rank-NNNN/ subdirs "
                        "when --workers > 1)")
    p.add_argument("--rows", type=int, default=1024,
                   help="sentences per packed device batch — the fixed "
                        "row bucket of the compiled family "
                        "(default 1024)")
    p.add_argument("--max-len", type=int, default=256,
                   help="token cap per sentence (longer tails are "
                        "truncated; bounds the warmed pow2 length "
                        "family, default 256)")
    p.add_argument("--shard-size", type=int, default=8192,
                   help="sentences per output shard, rounded up to a "
                        "--rows multiple (default 8192)")
    p.add_argument("--lowercase", action="store_true")
    p.add_argument("--prefetch", type=int, default=2,
                   help="producer batches buffered ahead of the device "
                        "(default 2: double-buffered)")
    p.add_argument("--no-deep-verify", action="store_true",
                   help="resume scan checks shard sizes only instead "
                        "of re-hashing committed payloads")
    p.add_argument("--no-warmup", action="store_true",
                   help="skip the pre-stream compile warmup (steady "
                        "state then pays the jit compiles)")
    p.add_argument("--metrics-out", default=None,
                   help="write the run stats JSON here too (always "
                        "printed to stdout)")
    p.add_argument("--status-file", default=None,
                   help="atomically mirror the transform heartbeat "
                        "snapshot JSON to this path")
    p.add_argument("--status-port", type=int, default=None,
                   help="serve /healthz + /metrics for this transform "
                        "run (0 binds an ephemeral port)")
    p.add_argument("--event-log", default=None)
    p.add_argument("--rank", type=int, default=None,
                   help=argparse.SUPPRESS)
    p.add_argument("--world", type=int, default=None,
                   help=argparse.SUPPRESS)
    p.add_argument("--workers", type=int, default=1,
                   help="rank-parallel worker count under the elastic "
                        "supervisor: each rank owns a contiguous input "
                        "span and a private shard directory, crashed "
                        "ranks relaunch and resume from their own "
                        "committed shards")
    p.add_argument("--max-restarts", type=int, default=3)
    p.add_argument("--heartbeat-stale", type=float, default=120.0,
                   help="status-file heartbeat age that counts as a "
                        "hang (0 disables hang detection)")
    p.add_argument("--startup-grace", type=float, default=600.0)
    p.add_argument("--supervise-dir", default=None,
                   help="supervisor status/log directory (default "
                        "<out>/supervisor)")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="merged gang /metrics + /healthz endpoint "
                        "(supervisor mode; 0 = ephemeral)")
    p.add_argument("--report-out", default=None,
                   help="write the supervisor report JSON here too")

    p = sub.add_parser(
        "synonyms-dump",
        help="all-vocab top-k neighbor dump (JSONL) and/or k-NN graph "
             "arrays — whole-table batch top-k, the ANN index's best "
             "amortization regime",
    )
    p.add_argument("--model", required=True)
    p.add_argument("--out", default=None,
                   help='JSONL output path (one {"word", "synonyms"} '
                        "object per vocab word, self-match excluded)")
    p.add_argument("--graph-out", default=None, metavar="PREFIX",
                   help="also write <PREFIX>.ids.npy / <PREFIX>.sims"
                        ".npy / <PREFIX>.json k-NN graph arrays "
                        "(int32 neighbor ids padded with -1)")
    p.add_argument("-n", "--num", type=int, default=10)
    p.add_argument("--block", type=int, default=1024,
                   help="vocab rows pulled + queried per device "
                        "dispatch (default 1024)")
    p.add_argument("--metrics-out", default=None)
    _add_ann_flags(p)

    p = sub.add_parser(
        "trace-merge",
        help="stitch per-process request-trace JSONL sinks (a "
             "serve-fleet --trace-dir, or any set of --trace-log / "
             "--event-log files) into one clock-anchored Chrome-trace "
             "/ Perfetto JSON timeline",
    )
    p.add_argument("inputs", nargs="+",
                   help="trace JSONL files, or directories globbed "
                        "for *.jsonl (+ rotated *.jsonl.1)")
    p.add_argument("--out", required=True,
                   help="merged Chrome-trace JSON output path (open "
                        "in ui.perfetto.dev or chrome://tracing)")

    p = sub.add_parser(
        "eval", help="analogy accuracy on a standard question file"
    )
    p.add_argument("--model", required=True)
    p.add_argument("--questions", required=True,
                   help="': section' headers + 'a b c d' rows")
    p.add_argument("--top-k", type=int, default=1)
    p.add_argument("--no-lowercase", action="store_true")


def main(argv=None) -> int:
    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s: %(message)s"
    )
    parser = argparse.ArgumentParser(prog="glint_word2vec_tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)
    _add_train(sub)
    _add_fit_stream(sub)
    _add_query(sub)
    args = parser.parse_args(argv)
    try:
        return _run(args)
    except TrainingDiverged as e:
        # The canary already wrote the final checkpoint and flushed the
        # event log; the operator needs the reason, not a stack trace.
        print(f"error: training diverged: {e}", file=sys.stderr)
        return 2
    except (KeyError, ValueError, FileNotFoundError) as e:
        # Expected user errors (OOV word, bad path, bad params): one clean
        # line, no traceback.
        msg = e.args[0] if e.args else str(e)
        print(f"error: {msg}", file=sys.stderr)
        return 1


def _argv_value(argv, flag):
    """Last value of ``--flag v`` / ``--flag=v`` in a raw argv list."""
    val = None
    for i, a in enumerate(argv):
        if a == flag and i + 1 < len(argv):
            val = argv[i + 1]
        elif a.startswith(flag + "="):
            val = a.split("=", 1)[1]
    return val


def _run_supervise(args) -> int:
    """The supervise subcommand: a thin CLI shell over
    ``parallel.supervisor.Supervisor``. Runs in a jax-free process —
    the workers own the devices; the supervisor only watches pids and
    status files."""
    import os

    train_args = list(args.train_args)
    if train_args and train_args[0] == "--":
        train_args = train_args[1:]
    if not train_args or train_args[0] != "train":
        print(
            "error: supervise expects the train command to run, e.g. "
            "`supervise --workers 2 train --corpus c.txt --output m/ "
            "--checkpoint-dir ck/`",
            file=sys.stderr,
        )
        return 1
    rest = train_args[1:]
    checkpoint_dir = _argv_value(rest, "--checkpoint-dir")
    if checkpoint_dir is None:
        print(
            "error: supervise requires --checkpoint-dir in the train "
            "arguments (recovery relaunches resume from it)",
            file=sys.stderr,
        )
        return 1
    sup_dir = args.supervise_dir or os.path.join(
        checkpoint_dir, "supervisor"
    )

    rank0_env = {}
    for kv in args.rank0_env:
        if "=" not in kv:
            print(
                f"error: --rank0-env expects KEY=VAL, got {kv!r}",
                file=sys.stderr,
            )
            return 1
        k, v = kv.split("=", 1)
        rank0_env[k] = v

    from glint_word2vec_tpu.parallel.supervisor import (
        Supervisor,
        cli_train_build_argv,
    )

    report = Supervisor(
        cli_train_build_argv(rest),
        args.workers,
        status_dir=sup_dir,
        checkpoint_dir=checkpoint_dir,
        rank_env_first_launch={0: rank0_env} if rank0_env else None,
        heartbeat_stale_seconds=(
            args.heartbeat_stale if args.heartbeat_stale > 0 else None
        ),
        startup_grace_seconds=args.startup_grace,
        max_restarts=args.max_restarts,
        backoff_base_seconds=args.backoff_base,
        backoff_cap_seconds=args.backoff_cap,
        metrics_port=args.metrics_port,
        metrics_host=args.metrics_host,
        serving_urls=args.join_serving,
    ).run()
    out = report.to_dict()
    print(json.dumps(out))
    if args.report_out:
        from glint_word2vec_tpu.utils import atomic_write_json

        atomic_write_json(args.report_out, out)
    return 0 if report.completed else 3


def _run_transform_fleet(args) -> int:
    """``transform-file --workers N``: a device-free supervisor shell
    (the parent never imports jax — the serve-fleet discipline). Each
    rank re-enters this CLI with ``--rank``/``--world``, derives its
    contiguous input span, and owns a private ``rank-NNNN/`` shard
    directory; a crashed or hung rank relaunches and resumes from its
    own committed shards."""
    import os

    from glint_word2vec_tpu.parallel.supervisor import (
        Supervisor,
        cli_transform_build_argv,
    )

    rest = [
        "--model", args.model, "--input", args.input, "--out", args.out,
        "--rows", str(args.rows), "--max-len", str(args.max_len),
        "--shard-size", str(args.shard_size),
        "--prefetch", str(args.prefetch),
    ]
    if args.lowercase:
        rest.append("--lowercase")
    if args.no_deep_verify:
        rest.append("--no-deep-verify")
    if args.no_warmup:
        rest.append("--no-warmup")
    sup_dir = args.supervise_dir or os.path.join(args.out, "supervisor")
    report = Supervisor(
        cli_transform_build_argv(rest),
        args.workers,
        status_dir=sup_dir,
        heartbeat_stale_seconds=(
            args.heartbeat_stale if args.heartbeat_stale > 0 else None
        ),
        startup_grace_seconds=args.startup_grace,
        max_restarts=args.max_restarts,
        metrics_port=args.metrics_port,
    ).run()
    out = report.to_dict()
    print(json.dumps(out))
    if args.report_out:
        from glint_word2vec_tpu.utils import atomic_write_json

        atomic_write_json(args.report_out, out)
    return 0 if report.completed else 3


def _run_transform_file(args, model) -> int:
    """One transform rank (or the whole single-process run): derive the
    input span, wire the transform heartbeat, stream the file."""
    import os

    from glint_word2vec_tpu.batch.transform import (
        count_lines,
        transform_file,
    )

    rank = args.rank or 0
    world = args.world or 1
    out_dir = args.out
    start, end = 0, None
    if world > 1:
        from glint_word2vec_tpu.parallel.distributed import shard_span

        start, end = shard_span(count_lines(args.input), rank, world)
        out_dir = os.path.join(args.out, f"rank-{rank:04d}")
    run = None
    if (args.status_file or args.status_port is not None
            or args.event_log):
        from glint_word2vec_tpu.obs import ObsConfig, start_run

        run = start_run(
            ObsConfig(
                status_file=args.status_file,
                status_port=args.status_port,
                event_log=args.event_log,
            ),
            pipeline="transform", engine=model.engine,
        )
    failed = True
    try:
        stats = transform_file(
            model, args.input, out_dir,
            rows=args.rows, max_len=args.max_len,
            shard_size=args.shard_size, start=start, end=end,
            lowercase=args.lowercase, prefetch_depth=args.prefetch,
            deep_verify=not args.no_deep_verify,
            warmup=not args.no_warmup, obs_run=run,
        )
        failed = False
    finally:
        if run is not None:
            run.close(failed=failed)
    stats["rank"], stats["world"] = rank, world
    print(json.dumps(stats))
    if args.metrics_out:
        from glint_word2vec_tpu.utils import atomic_write_json

        atomic_write_json(args.metrics_out, stats)
    return 0


def _run_synonyms_dump(args, model) -> int:
    from glint_word2vec_tpu.batch.transform import synonyms_dump

    if args.out is None and args.graph_out is None:
        print(
            "error: synonyms-dump needs --out and/or --graph-out",
            file=sys.stderr,
        )
        return 1
    if args.ann:
        eng = model._query_engine()
        eng.configure_ann(
            clusters=args.ann_clusters, nprobe=args.ann_nprobe,
            iters=args.ann_iters, sample=args.ann_sample,
        )
        if eng.ann_index is None:
            eng.adopt_ann(eng.ann_build())
    stats = synonyms_dump(
        model, args.out, num=args.num, block=args.block,
        approximate=args.ann, graph_prefix=args.graph_out,
    )
    print(json.dumps(stats))
    if args.metrics_out:
        from glint_word2vec_tpu.utils import atomic_write_json

        atomic_write_json(args.metrics_out, stats)
    return 0


def _stream_sentences(path: str, follow: bool, lowercase: bool):
    """Tokenized sentences from a file, stdin (``-``), or a followed
    (tail -f) file. The generator is pull-based: a bounded trainer
    (--max-words/--max-seconds) simply stops pulling. While a followed
    file is idle it yields ``[]`` heartbeats so the trainer can honor
    its stop bounds and publish cadence instead of blocking in the
    fill loop; a half-written trailing line (the producer's write
    landed between flushes) is held until its newline arrives so
    partial tokens never reach the counts or the candidate sketch."""
    import time as _time

    def _toks(line):
        return (line.lower() if lowercase else line).split()

    if path != "-" and not follow:
        # Plain file: the batch paths' line streamer already does
        # exactly this (same tokenization policy, one implementation).
        from glint_word2vec_tpu.corpus.vocab import iter_text_file

        yield from iter_text_file(path, lowercase)
        return
    if path == "-":
        try:
            fd = sys.stdin.fileno()
        except (OSError, ValueError, AttributeError):
            fd = None
        if fd is None:
            # Not a real descriptor (tests substituting StringIO):
            # plain blocking iteration, no idle heartbeats possible.
            for line in sys.stdin:
                toks = _toks(line)
                if toks:
                    yield toks
            return
        # A quiet pipe must not pin the trainer inside its fill loop:
        # select with a timeout and yield [] heartbeats while idle, so
        # --max-seconds and --publish-every stay live, holding any
        # half-written trailing line until its newline arrives.
        import codecs
        import os as _os
        import select as _select

        dec = codecs.getincrementaldecoder("utf-8")("replace")
        pending = ""
        while True:
            ready, _, _ = _select.select([fd], [], [], 0.2)
            if not ready:
                yield []  # idle heartbeat
                continue
            chunk = _os.read(fd, 65536)
            if not chunk:  # EOF
                pending += dec.decode(b"", final=True)
                toks = _toks(pending)  # final newline-less line
                if toks:
                    yield toks
                return
            pending += dec.decode(chunk)
            *lines, pending = pending.split("\n")
            for line in lines:
                toks = _toks(line)
                if toks:
                    yield toks
    # --follow: tail the file forever, holding a half-written trailing
    # line until its newline lands.
    with open(path, encoding="utf-8") as f:
        pending = ""
        while True:
            line = f.readline()
            if not line:
                _time.sleep(0.2)
                yield []  # idle heartbeat
                continue
            line = pending + line
            pending = ""
            if not line.endswith("\n"):
                pending = line
                continue
            toks = _toks(line)
            if toks:
                yield toks


def _run_fit_stream(args) -> int:
    from glint_word2vec_tpu import Word2Vec

    obs = None
    if args.status_port is not None or args.status_file or args.event_log:
        from glint_word2vec_tpu.obs import ObsConfig

        obs = ObsConfig(
            event_log=args.event_log,
            status_port=args.status_port,
            status_host=args.status_host,
            status_file=args.status_file,
        )
    w2v = Word2Vec(
        vector_size=args.vector_size,
        window=args.window,
        step_size=args.step_size,
        batch_size=args.batch_size,
        num_negatives=args.negatives,
        subsample_ratio=args.subsample_ratio,
        min_count=args.min_count,
        max_sentence_length=args.max_sentence_length,
        seed=args.seed,
        num_partitions=args.num_partitions,
        num_shards=args.num_shards,
        steps_per_call=args.steps_per_call,
        obs=obs,
    )
    model = w2v.fit_stream(
        _stream_sentences(args.corpus, args.follow, args.lowercase),
        publish_dir=args.publish_dir,
        bootstrap_words=args.bootstrap_words,
        buffer_words=args.buffer_words,
        extra_rows=args.extra_rows,
        refresh_words=args.refresh_words,
        publish_seconds=args.publish_every,
        publish_words=args.publish_words,
        max_words=args.max_words,
        max_seconds=args.max_seconds,
    )
    if args.output:
        model.save(args.output)
    print(json.dumps({
        **({"saved": args.output} if args.output else {}),
        **(model.training_metrics or {}),
    }))
    if args.metrics_out:
        from glint_word2vec_tpu.utils import atomic_write_json

        atomic_write_json(args.metrics_out, model.training_metrics)
    return 0


def _run_fleet_shard(args) -> int:
    """``fleet-shard``: one subprocess shard of the multi-process
    balancer data plane. Device-free — it proxies bytes."""
    from glint_word2vec_tpu.fleet import run_balancer_shard

    return run_balancer_shard(args.config)


def _parse_model_specs(pairs, flag: str):
    """``ID=DIR`` repeatable-flag parser shared by serve/serve-fleet.
    Returns a dict, or None after printing an error (ids ride the
    ``/m/<id>/`` routing prefix, so ``/`` and blanks are rejected)."""
    out = {}
    for kv in pairs:
        mid, sep, d = kv.partition("=")
        if not sep or not mid or not d or "/" in mid:
            print(
                f"error: {flag} expects ID=DIR with a /-free id, "
                f"got {kv!r}",
                file=sys.stderr,
            )
            return None
        out[mid] = d
    return out


def _run_serve_fleet(args) -> int:
    from glint_word2vec_tpu.fleet import (
        AutoscaleConfig, CanaryConfig, QosConfig, serve_fleet,
    )

    models = _parse_model_specs(args.add_model, "--add-model")
    if models is None:
        return 1
    model_watch_dirs = _parse_model_specs(
        args.watch_model, "--watch-model"
    )
    if model_watch_dirs is None:
        return 1
    if args.model is None and args.watch_checkpoint is None:
        print(
            "error: serve-fleet needs --model or --watch-checkpoint",
            file=sys.stderr,
        )
        return 1
    flags = [
        "--max-batch", str(args.max_batch),
        "--cache-size", str(args.cache_size),
        "--max-inflight", str(args.max_inflight),
        "--request-deadline", str(args.request_deadline),
        "--degraded-after", str(args.degraded_after),
    ]
    # (--watch-checkpoint/--watch-poll are NOT appended here: in
    # coordinated mode the rollout coordinator owns every swap, and in
    # --uncoordinated-watch mode the FleetSupervisor's replica argv
    # builder supplies both flags itself.)
    if args.ann:
        flags += [
            "--ann",
            "--ann-clusters", str(args.ann_clusters),
            "--ann-nprobe", str(args.ann_nprobe),
            "--ann-iters", str(args.ann_iters),
            "--ann-sample", str(args.ann_sample),
            "--ann-recall-gate", str(args.ann_recall_gate),
            "--ann-recall-sample", str(args.ann_recall_sample),
        ]
    replica0_env = {}
    for kv in args.replica0_env:
        if "=" not in kv:
            print(
                f"error: --replica0-env expects KEY=VAL, got {kv!r}",
                file=sys.stderr,
            )
            return 1
        k, v = kv.split("=", 1)
        replica0_env[k] = v
    canary = None
    if ((args.watch_checkpoint is not None or model_watch_dirs)
            and not args.no_canary
            and not args.uncoordinated_watch):
        probes = None
        if args.canary_probes:
            with open(args.canary_probes) as f:
                probes = json.load(f)
            if not isinstance(probes, list):
                print(
                    "error: --canary-probes must be a JSON list of "
                    '{"path", "body"} objects',
                    file=sys.stderr,
                )
                return 1
        canary = CanaryConfig(
            mirror_every=args.canary_mirror_every,
            min_scores=args.canary_min_scores,
            mirror_seconds=args.canary_mirror_seconds,
            agreement_gate=args.canary_agreement,
            top_k=args.canary_top_k,
            probes=probes,
        )
    qos = None
    if (args.qos_tenant_rate is not None
            or args.qos_bulk_max_inflight is not None):
        qos = QosConfig(
            tenant_rate=args.qos_tenant_rate,
            tenant_burst=args.qos_tenant_burst,
            bulk_max_inflight=args.qos_bulk_max_inflight,
        )
    autoscale = None
    if args.warm_spares > 0:
        autoscale = AutoscaleConfig(
            min_live=args.replicas,
            max_live=args.replicas + args.warm_spares,
            interval=args.autoscale_interval,
            up_shed_per_sec=args.autoscale_up_shed_rate,
            up_p95_ms=args.autoscale_up_p95_ms,
            up_window_seconds=args.autoscale_up_window,
            down_window_seconds=args.autoscale_down_window,
            cooldown_seconds=args.autoscale_cooldown,
        )
    return serve_fleet(
        args.model,
        replicas=args.replicas,
        host=args.host,
        port=args.port,
        watch_dir=args.watch_checkpoint,
        watch_poll=args.watch_poll,
        replica_flags=flags,
        log_dir=args.replica_log_dir,
        trace_dir=args.trace_dir,
        port_file=args.port_file,
        max_restarts=args.max_restarts,
        backoff_base_seconds=args.backoff_base,
        backoff_cap_seconds=args.backoff_cap,
        hang_kill_seconds=args.hang_kill_after,
        probe_interval=args.probe_interval,
        probe_timeout=args.probe_timeout,
        breaker_failures=args.breaker_failures,
        breaker_successes=args.breaker_successes,
        breaker_open_seconds=args.breaker_open_seconds,
        canary=canary,
        coordinated=not args.uncoordinated_watch,
        replica_env_first_launch=(
            {0: replica0_env} if replica0_env else None
        ),
        warm_spares=args.warm_spares,
        autoscale=autoscale,
        balancer_procs=args.balancer_procs,
        qos=qos,
        models=models,
        model_watch_dirs=model_watch_dirs,
        model_memory_budget=args.model_memory_budget,
    )


def _run_trace_merge(args) -> int:
    """``trace-merge``: jax-free stitcher over EventRecorder JSONL
    sinks — directories expand to their (rotated included) .jsonl
    files, the merged document is written atomically, and the summary
    line reports how many trace ids actually stitched across
    processes."""
    import glob
    import os

    from glint_word2vec_tpu.obs.aggregate import merge_trace_logs
    from glint_word2vec_tpu.utils import atomic_write_json

    paths = []
    for inp in args.inputs:
        if os.path.isdir(inp):
            paths += sorted(
                glob.glob(os.path.join(inp, "*.jsonl"))
                + glob.glob(os.path.join(inp, "*.jsonl.1"))
            )
        else:
            paths.append(inp)
    if not paths:
        print("error: no trace JSONL inputs found", file=sys.stderr)
        return 1
    doc = merge_trace_logs(paths)
    atomic_write_json(args.out, doc)
    other = doc["otherData"]
    print(json.dumps({
        "out": args.out,
        "events": len(doc["traceEvents"]),
        "trace_ids": other["trace_ids"],
        "stitched_traces": other["stitched_traces"],
        "sources": other["sources"],
    }))
    return 0


def _run(args) -> int:
    if args.cmd == "supervise":
        # Before anything imports jax: the supervisor process never
        # touches a device.
        return _run_supervise(args)
    if args.cmd == "serve-fleet":
        # Likewise device-free: the balancer proxies; only the replica
        # SUBPROCESSES load tables.
        return _run_serve_fleet(args)
    if args.cmd == "fleet-shard":
        # One balancer data-plane shard: device-free like its parent.
        return _run_fleet_shard(args)
    if args.cmd == "trace-merge":
        # Pure file stitching: no devices, no model loads.
        return _run_trace_merge(args)
    if (args.cmd == "transform-file" and args.workers > 1
            and args.rank is None):
        # Rank-parallel bulk transform: the parent is a device-free
        # supervisor shell; only rank subprocesses load the tables.
        return _run_transform_fleet(args)

    if args.cmd == "train" and (args.coordinator or args.num_processes):
        # First: nothing may initialise the backend before the gang joins,
        # and the cache rule below reads the backend.
        from glint_word2vec_tpu.parallel import distributed as dist

        dist.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )

    from glint_word2vec_tpu.utils.platform import enable_compile_cache

    # A server start compiles ~50 small programs, the same for every start
    # of one table shape: keep them all. Other commands keep JAX's floor.
    enable_compile_cache(0.0 if args.cmd == "serve" else 1.0)

    from glint_word2vec_tpu import FastTextWord2Vec, Word2Vec, load_model

    if args.cmd == "train":
        kw = dict(
            vector_size=args.vector_size,
            window=args.window,
            step_size=args.step_size,
            batch_size=args.batch_size,
            num_negatives=args.negatives,
            subsample_ratio=args.subsample_ratio,
            min_count=args.min_count,
            num_iterations=args.iterations,
            max_sentence_length=args.max_sentence_length,
            seed=args.seed,
            num_partitions=args.num_partitions,
            num_shards=args.num_shards,
            dtype=args.dtype,
            compute_dtype=args.compute_dtype,
            steps_per_call=args.steps_per_call,
            architecture=args.architecture,
            position_weights=args.position_weights,
            shared_negatives=args.shared_negatives,
            batch_packing=args.packing,
            exchange=args.exchange,
            exchange_capacity=args.exchange_capacity,
            exchange_wire=args.exchange_wire,
            exchange_every=args.exchange_every,
            exchange_topology=args.exchange_topology,
            exchange_shard=args.exchange_shard,
        )
        obs = None
        if (args.status_port is not None or args.status_file
                or args.event_log or args.chrome_trace
                or args.steptime_out or args.canary != "off"):
            from glint_word2vec_tpu.obs import ObsConfig

            obs = ObsConfig(
                event_log=args.event_log,
                event_capacity=args.event_capacity,
                chrome_trace=args.chrome_trace,
                status_port=args.status_port,
                status_host=args.status_host,
                status_file=args.status_file,
                canary=args.canary,
                canary_window=args.canary_window,
                canary_factor=args.canary_factor,
                canary_check_every=args.canary_check_every,
                steptime_path=args.steptime_out,
            )
        if args.fasttext:
            w2v = FastTextWord2Vec(
                **kw, obs=obs, min_n=args.min_n, max_n=args.max_n,
                bucket=args.bucket, max_subwords=args.max_subwords,
            )
        else:
            w2v = Word2Vec(**kw, obs=obs)
        # Streaming ingestion (fit_file): two passes over the file, flat
        # int32 encoding — never materializes Python sentence lists.
        model = w2v.fit_file(
            args.corpus, lowercase=args.lowercase,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every_epochs=args.checkpoint_every,
        )
        model.save(args.output)
        print(json.dumps({"saved": args.output, **(model.training_metrics or {})}))
        if args.metrics_out:
            from glint_word2vec_tpu.utils import atomic_write_json

            atomic_write_json(args.metrics_out, model.training_metrics)
        return 0

    if args.cmd == "fit-stream":
        return _run_fit_stream(args)

    if args.cmd == "serve":
        from glint_word2vec_tpu.serving import serve_model_dir

        if args.model is None and args.watch_checkpoint is None:
            print(
                "error: serve needs --model or --watch-checkpoint",
                file=sys.stderr,
            )
            return 1
        models = _parse_model_specs(args.add_model, "--add-model")
        if models is None:
            return 1
        serve_model_dir(
            args.model, host=args.host, port=args.port,
            max_batch=args.max_batch, warmup=not args.no_warmup,
            cache_size=args.cache_size,
            max_inflight=args.max_inflight,
            request_deadline=args.request_deadline,
            degraded_after=args.degraded_after,
            watch_dir=args.watch_checkpoint,
            watch_poll=args.watch_poll,
            port_file=args.port_file,
            trace_log=args.trace_log,
            flight_dir=args.flight_dir,
            models=models,
            model_memory_budget=args.model_memory_budget,
            watch_models=args.watch_models,
            **_ann_kwargs(args),
        )
        return 0

    model = load_model(args.model)
    if args.cmd == "transform-file":
        return _run_transform_file(args, model)
    if args.cmd == "synonyms-dump":
        return _run_synonyms_dump(args, model)
    if args.cmd == "synonyms":
        for w, s in model.find_synonyms(args.word, args.num):
            print(f"{w}\t{s:.4f}")
    elif args.cmd == "analogy":
        for w, s in model.analogy(args.positive, args.negative, args.num):
            print(f"{w}\t{s:.4f}")
    elif args.cmd == "transform":
        vec = model.transform_sentences([args.sentence.split()])[0]
        print(json.dumps([round(float(x), 6) for x in vec]))
    elif args.cmd == "eval":
        from glint_word2vec_tpu.eval import evaluate_analogies, parse_analogy_file

        questions = parse_analogy_file(
            args.questions, lowercase=not args.no_lowercase
        )
        result = evaluate_analogies(model, questions, top_k=args.top_k)
        print(json.dumps(result.to_dict()))
    elif args.cmd == "info":
        print(
            json.dumps(
                {
                    "family": type(model).__name__,
                    "vocab_size": model.vocab.size,
                    "vector_size": model.vector_size,
                    "train_words_count": model.vocab.train_words_count,
                    "params": json.loads(model.params.to_json()),
                }
            )
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
