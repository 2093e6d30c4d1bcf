"""Benchmark: sustained SGNS training throughput on the available device.

Measures the fused train step (the dotprod+adjust equivalent of the
reference's hot loop, mllib/feature/ServerSideGlintWord2Vec.scala:421-425)
in steady state on a realistic large-vocab configuration, reporting trained
words per second per chip plus an MFU estimate. Baseline: the driver
north-star of 50M words/sec on a v5e-32 (BASELINE.json) = 1.5625M
words/sec/chip; the reference itself publishes no throughput numbers
(BASELINE.md).

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": "words/sec/chip", "vs_baseline": N, ...}

The headline "value" is the PER-PAIR estimator (reference semantics: n fresh
negatives per (center, context) pair) so vs_baseline is comparable to the
reference's algorithm; the shared-negative-pool mode (the TPU-shaped
estimator) is reported alongside under "modes". The full config is echoed in
the line so no number is ever ambiguous about what it measured.

It measures an accelerator and nothing else: the measurement runs once, in
one worker subprocess (the parent never imports JAX, so the worker is the
only process that holds the chip). With no accelerator, on a device whose
peak is not in the table below, or when any requested mode fails, the worker
raises and bench.py exits non-zero with the error — there is no retry, no CPU
run under a chip's unit, and no line assembled from partial results.

Every mode reports ``pairs_per_sec`` (measured useful (center, context)
pairs trained per second) and ``effective_words_per_sec`` :=
``pairs_per_sec / context_lanes`` — useful-pair throughput in dense-word
units, the number on which grid-vs-packed dispatch shapes are directly
comparable. (The naive ``words_per_sec / mask_density`` form would
INFLATE a mode by its own masked-lane waste — a grid cell at density
0.43 would score 2.3x its real training rate — so the pair-normalized
form is what the packed-vs-grid gate in BENCH_PACKED.json uses.)

Environment knobs:
  BENCH_VOCAB, BENCH_DIM, BENCH_BATCH, BENCH_SPC (minibatches per device
  dispatch = scan length), BENCH_SHARED_NEG (pool size for the shared mode),
  BENCH_MODES (default
  "per_pair,per_pair_bf16t,per_pair_bf16ct,shared_bf16t,shared_bf16ct,corpus,corpus_subsample,corpus_packed"
  — the `_bf16t` cells are the PROPER mixed-precision regime (bf16
  STORAGE, fp32 compute/accumulate — the fused-kernel target geometry,
  ISSUE 11), distinct from `_bf16ct` which also runs bf16 MXU operands;
  "corpus" is the production fit/fit_file path with minibatches assembled
  on device from the uploaded corpus; "corpus_subsample" is the same path
  with frequency subsampling on (ratio BENCH_SUBSAMPLE, default 1e-3):
  a per-epoch on-device compaction pass, then training over the
  compacted stream — the realistic production config; "corpus_packed" is
  the corpus path under dense pair packing (set_batch_packing("dense"),
  ISSUE 4): valid pairs prefix-sum-compacted into dense pair batches of
  batch*context_lanes slots, reported with packed fill as its
  mask_density; suffixes:
  "_bf16c" = bf16 MXU operands with f32 accumulation, "_bf16t" = bf16
  TABLES for that mode (overriding BENCH_DTYPE; halves gather/scatter
  bytes), "_bf16ct" = both; "stall_overlap" (not in the default set) is
  the ISSUE-5 checkpoint-pause cell — words/sec with checkpointing every
  N groups, blocking vs async saves, gating >= 80% pause removal,
  recorded in BENCH_STALL.json), BENCH_DTYPE (run-level table dtype, default
  float32 so the suffixless per_pair headline stays comparable across
  rounds; each mode's effective table dtype is echoed in its results),
  BENCH_TIMEOUT (seconds the worker may take, default 1800),
  BENCH_MIN_SECONDS (timed-loop floor), BENCH_HOST_INPUTS=1 (feed numpy
  batches per dispatch instead of device-resident arrays — a diagnostic for
  the host->device transfer cost the production device-resident corpus
  pipeline avoids).
"""

import json
import os
import subprocess
import sys
import time

BASELINE_WORDS_PER_SEC_PER_CHIP = 50e6 / 32

# Peak dense bf16 matmul throughput keyed by ``device_kind`` exactly as JAX
# reports it, used only for the MFU *estimate*. Sources: Google Cloud TPU
# documentation, the "System architecture" page of each version (v5e: 197
# TFLOP/s bf16; the 394 listed there is its int8 peak). For modes whose
# contractions run f32 operands the MXU needs multiple bf16 passes; we
# charge those against bf16_peak/2 and record the assumption. A device
# that is not here is an error, not a default: add it with its source.
_PEAK_FLOPS_BF16 = {
    "TPU v5 lite": 197e12,  # v5e
    "TPU v4": 275e12,
    "TPU v3": 123e12,
    "TPU v2": 45e12,
}


def _peak_for(device_kind: str, compute_dtype: str) -> float:
    try:
        peak = _PEAK_FLOPS_BF16[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak on record for device_kind {device_kind!r}; "
            f"known: {sorted(_PEAK_FLOPS_BF16)}. Add it to "
            "_PEAK_FLOPS_BF16 with its source before benchmarking on it."
        ) from None
    return peak if compute_dtype == "bfloat16" else peak / 2


def _config_from_env():
    return {
        "vocab": int(os.environ.get("BENCH_VOCAB", 1_000_000)),
        "dim": int(os.environ.get("BENCH_DIM", 300)),
        "batch": int(os.environ.get("BENCH_BATCH", 8192)),
        "steps_per_call": int(os.environ.get("BENCH_SPC", 32)),
        "shared_negatives": int(os.environ.get("BENCH_SHARED_NEG", 4096)),
        "subsample_ratio": float(os.environ.get("BENCH_SUBSAMPLE", 1e-3)),
        "negatives": 5,
        "context_lanes": 7,
        # Table dtype defaults to float32 (the exactness-tested numerics);
        # the bf16-table geometry is swept by scripts/bench_sweep.py,
        # which sets BENCH_DTYPE explicitly.
        "dtype": os.environ.get("BENCH_DTYPE", "float32"),
        # Mode suffixes: _bf16c = bf16 MXU operands, _bf16t = bf16 tables,
        # _bf16ct = both; no suffix = f32 (exactness-tested numerics).
        # Estimators: per_pair (reference semantics, pre-built batches),
        # shared (pool estimator), corpus (the PRODUCTION fit/fit_file
        # path: minibatch windows assembled ON DEVICE from the uploaded
        # corpus — includes the window-assembly cost the other modes
        # skip), corpus_subsample (corpus + the per-epoch on-device
        # subsample-compact pass — the realistic production config).
        # Defaults: the f32 per-pair headline + the per-pair fast path
        # + the fastest estimator config + both production paths.
        # The _bf16t cells are the mixed-precision surface ISSUE 11
        # cares about: bf16 STORAGE with fp32 compute/accumulation (the
        # fused-kernel regime). _bf16ct additionally runs the MXU
        # contractions on bf16 operands. Both ride _mode_parts.
        "modes": os.environ.get(
            "BENCH_MODES",
            "per_pair,per_pair_bf16t,per_pair_bf16ct,shared_bf16t,"
            "shared_bf16ct,corpus,corpus_subsample,corpus_packed",
        ),
    }


def _flops_per_step(mode: str, cfg, mask_density: float) -> float:
    """USEFUL FLOPs of one minibatch update (matmul-equivalent count).

    Per-pair (ops/sgns.py sgns_grads + rank-1 expansion): f_pos 2BCd,
    f_neg 2BCnd, d_center 2BCd+2BCnd, outer products BCd+BCnd, scatter adds
    BCd+BCnd+Bd  => ~6BCd(1+n) + Bd.
    Shared pool (shared_sgns_grads): f_pos 2BCd, f_pool 2BSd, d_center
    2BCd+2BSd, d_pool 2BSd, outer+scatter 2BCd+Bd+Sd => ~6BCd + 6BSd.

    Every context-lane term is scaled by the mode's MEASURED mask
    density: the MXU executes all C static lanes either way, but masked
    lanes do no useful work, so crediting them would inflate MFU — and
    inflate it unevenly (synthetic masks run ~0.85 dense, the corpus
    mode's shrunk windows ~0.42; round-4 verdict weak #8). The MFU
    reported is therefore useful-work MFU on a consistent basis.
    """
    B, C, d, n = cfg["batch"], cfg["context_lanes"], cfg["dim"], cfg["negatives"]
    estimator, _, _ = _mode_parts(mode)
    if estimator in ("per_pair", "corpus", "corpus_subsample"):
        return 6.0 * B * C * d * (1 + n) * mask_density + B * d
    S = cfg["shared_negatives"]
    return 6.0 * B * C * d * mask_density + 6.0 * B * S * d + B * d + S * d


# ----------------------------------------------------------------------
# Worker: does the measurement, prints the JSON line.
# ----------------------------------------------------------------------


def _bench_obs_overhead(jax, np):
    """ISSUE 3 overhead guard, re-run for ISSUE 8 with the step-time
    attribution ledger in the stack: a fit with the full observability
    suite enabled (event ring + JSONL sink + Chrome-trace export +
    heartbeat server + status file + warn canary + attribution ledger
    with STEPTIME.json dump) must stay within 3% words/sec of the same
    fit with observability off (where the ledger path is the one
    module-global NULL_SPAN read). Runs the real production fit
    (device-resident corpus path) three times — warm-up (compiles,
    discarded), baseline, instrumented — and reports both throughputs,
    the overhead fraction, and the ledger's phase breakdown. Mode name:
    ``obs_overhead`` in BENCH_MODES (not in the default set; words/sec
    here is from a small fit, not comparable to the engine-loop
    modes)."""
    import tempfile

    from glint_word2vec_tpu.models.word2vec import Word2Vec
    from glint_word2vec_tpu.obs import ObsConfig
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(0)
    n_words = int(os.environ.get("BENCH_OBS_WORDS", 400_000))
    vocab = [f"w{i}" for i in range(2000)]
    sent_len = 20
    sentences = [
        [vocab[j] for j in rng.integers(0, len(vocab), sent_len)]
        for _ in range(n_words // sent_len)
    ]

    def run(obs):
        model = Word2Vec(
            mesh=make_mesh(1, 1), obs=obs, vector_size=64, min_count=1,
            batch_size=1024, num_iterations=2, seed=1, steps_per_call=8,
        ).fit(sentences)
        wps = model.training_metrics["words_per_sec"]
        pipeline = model.training_metrics["pipeline"]
        model.stop()
        return wps, pipeline

    run(None)  # compile warm-up fit, discarded
    base, pipeline = run(None)
    with tempfile.TemporaryDirectory() as td:
        obs = ObsConfig(
            event_log=os.path.join(td, "events.jsonl"),
            chrome_trace=os.path.join(td, "trace.json"),
            status_port=0,
            status_file=os.path.join(td, "status.json"),
            canary="warn",
            steptime_path=os.path.join(td, "STEPTIME.json"),
        )
        instrumented, _ = run(obs)
        import json as _json

        with open(os.path.join(td, "STEPTIME.json")) as f:
            steptime = _json.load(f)
    return {
        "words_per_sec": instrumented,
        "words_per_sec_baseline": base,
        "overhead_frac": round(1.0 - instrumented / base, 4),
        "corpus_words": n_words,
        "pipeline": pipeline,
        "steptime_wall_seconds": steptime["wall_seconds"],
        "steptime_phases": {
            p: info["seconds"]
            for p, info in steptime["phases"].items()
        },
        "inputs": "fit_list",
    }


def _bench_stall_overlap(jax, np):
    """ISSUE 5 acceptance cell: words/sec with checkpointing every N
    dispatch groups, blocking vs async saves, over the device-resident
    corpus scan. The gated quantity is the PER-CHECKPOINT WALL-CLOCK
    PAUSE at the fit loop's call site — the time the dispatching thread
    is blocked per save, which is exactly the device-pipeline bubble a
    checkpoint used to cost. Async saves must remove >= 80% of it
    (``ckpt_pause_removed_frac``). Words/sec under each regime is
    reported alongside but NOT gated. Mode name ``stall_overlap`` in
    BENCH_MODES (not in the default set). The committed BENCH_STALL.json
    predates the accelerator-only rule and was taken on a CPU container.

    Knobs: BENCH_STALL_VOCAB/DIM/BATCH/SPC (table + dispatch geometry),
    BENCH_STALL_GROUPS (timed dispatch groups), BENCH_STALL_CKPT_EVERY
    (groups between checkpoints)."""
    import shutil
    import tempfile

    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    V = int(os.environ.get("BENCH_STALL_VOCAB", 200_000))
    d = int(os.environ.get("BENCH_STALL_DIM", 128))
    B = int(os.environ.get("BENCH_STALL_BATCH", 2048))
    spc = int(os.environ.get("BENCH_STALL_SPC", 8))
    groups = int(os.environ.get("BENCH_STALL_GROUPS", 24))
    every = int(os.environ.get("BENCH_STALL_CKPT_EVERY", 4))
    if every <= 0 or groups < every:
        raise ValueError(
            f"BENCH_STALL_CKPT_EVERY={every} must be in [1, "
            f"BENCH_STALL_GROUPS={groups}] or no checkpoint ever fires "
            "and there is no pause to measure"
        )
    W = 5
    ranks = np.arange(1, V + 1, dtype=np.float64)
    counts = np.maximum((1e9 / ranks), 1.0).astype(np.int64)
    p = counts / counts.sum()
    rng = np.random.default_rng(0)
    sent_len = 40
    N = max(2 * spc * B, 1_000_000)
    N -= N % sent_len
    ids = rng.choice(V, size=N, p=p).astype(np.int32)
    offsets = np.arange(0, N + sent_len, sent_len, dtype=np.int64)
    mesh = make_mesh(1, 1, devices=[jax.devices()[0]])
    alphas = np.full(spc, 0.025, np.float32)
    key = jax.random.PRNGKey(0)
    span = max(N - spc * B, 1)

    def run(async_mode: bool):
        eng = EmbeddingEngine(mesh, V, d, counts, seed=0)
        eng.upload_corpus(ids, offsets)
        td = tempfile.mkdtemp(prefix="stall_bench_")
        # Warm every compile (train scan, snapshot copy) and the page
        # cache before timing.
        jax.block_until_ready(
            eng.train_steps_corpus(0, B, W, key, alphas, 0)
        )
        if async_mode:
            eng.save_async(os.path.join(td, "warm"))
            eng.wait_pending_saves()
        else:
            eng.save(os.path.join(td, "warm"))
        pauses = []
        t_start = time.time()
        last = None
        for g in range(groups):
            start = (g * spc * B) % span
            last = eng.train_steps_corpus(start, B, W, key, alphas,
                                          g * spc)
            if (g + 1) % every == 0:
                ck = os.path.join(td, f"ckpt-{g}")
                t0 = time.time()
                if async_mode:
                    eng.save_async(ck)
                else:
                    eng.save(ck)
                pauses.append(time.time() - t0)
        eng.wait_pending_saves()
        jax.block_until_ready(last)
        wall = time.time() - t_start
        stats = eng.checkpoint_stats()
        eng.destroy()
        shutil.rmtree(td, ignore_errors=True)
        wps = groups * spc * B / wall
        return wps, pauses, stats

    sync_wps, sync_pauses, _ = run(False)
    async_wps, async_pauses, async_stats = run(True)
    mean = lambda xs: sum(xs) / max(len(xs), 1)  # noqa: E731
    removed = 1.0 - mean(async_pauses) / max(mean(sync_pauses), 1e-12)
    return {
        "words_per_sec": round(async_wps, 1),
        "words_per_sec_sync_ckpt": round(sync_wps, 1),
        "ckpt_pause_sync_ms": round(mean(sync_pauses) * 1e3, 3),
        "ckpt_pause_sync_max_ms": round(max(sync_pauses) * 1e3, 3),
        "ckpt_pause_async_ms": round(mean(async_pauses) * 1e3, 3),
        "ckpt_pause_async_max_ms": round(max(async_pauses) * 1e3, 3),
        "ckpt_pause_removed_frac": round(removed, 4),
        "gate_pause_removed_min": 0.8,
        "gate_pass": bool(removed >= 0.8),
        "checkpoints_per_run": len(sync_pauses),
        "ckpt_every_groups": every,
        "async_save_waits": async_stats.get("async_save_waits"),
        "vocab": V, "dim": d, "batch": B, "steps_per_call": spc,
        "timed_groups": groups, "window": W,
        "corpus_words_device": int(N),
        "table_bytes_per_copy": int(2 * V * d * 4),
        "inputs": "device_corpus",
        "caveats": (
            "Single-run wall-clock numbers on a host whose cores the "
            "writer thread shares with the dispatch loop. The gated "
            "pause is the call-site blocking time of the identical "
            "snapshot geometry in both modes."
        ),
    }


def _mode_parts(mode: str):
    """Split a mode name into (estimator, compute_dtype, table_dtype).

    Suffixes: "_bf16c" = bf16 MXU operands; "_bf16t" = bf16 tables
    (halves gather/scatter HBM bytes); "_bf16ct" = both. No suffix = f32
    everywhere (the exactness-tested reference numerics). table_dtype is
    None when the mode doesn't override the run-level BENCH_DTYPE.
    """
    for suf, cd, td in (
        ("_bf16ct", "bfloat16", "bfloat16"),
        ("_bf16c", "bfloat16", None),
        ("_bf16t", "float32", "bfloat16"),
    ):
        if mode.endswith(suf):
            return mode[: -len(suf)], cd, td
    return mode, "float32", None


def _bench_mode(jax, mesh, cfg, mode: str, np):
    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine

    V, d, B = cfg["vocab"], cfg["dim"], cfg["batch"]
    spc, C, n = cfg["steps_per_call"], cfg["context_lanes"], cfg["negatives"]
    estimator, compute_dtype, table_dtype = _mode_parts(mode)
    if estimator == "obs_overhead":
        return _bench_obs_overhead(jax, np)
    if estimator == "stall_overlap":
        return _bench_stall_overlap(jax, np)
    shared = cfg["shared_negatives"] if estimator == "shared" else 0

    # Zipf-ish counts: realistic index skew for gathers and the noise table.
    ranks = np.arange(1, V + 1, dtype=np.float64)
    counts = np.maximum((1e9 / ranks), 1.0).astype(np.int64)

    eng = EmbeddingEngine(
        mesh, V, d, counts, num_negatives=n, seed=0,
        shared_negatives=shared, dtype=table_dtype or cfg["dtype"],
        compute_dtype=compute_dtype,
    )

    p = (counts / counts.sum()).astype(np.float64)
    if estimator in ("corpus", "corpus_subsample", "corpus_packed"):
        return _bench_corpus_mode(
            jax, eng, cfg, np, compute_dtype, p,
            subsample=(estimator == "corpus_subsample"),
            packed=(estimator == "corpus_packed"),
        )

    rng = np.random.default_rng(0)
    # Zipf-distributed center/context draws (the hot rows dominate, as in
    # real corpora after subsampling). One stacked group of spc minibatches,
    # dispatched as a single on-device lax.scan — the production hot path.
    centers_k = rng.choice(V, size=(spc, B), p=p).astype(np.int32)
    contexts_k = rng.choice(V, size=(spc, B, C), p=p).astype(np.int32)
    mask_k = (rng.random((spc, B, C)) < 0.85).astype(np.float32)
    # Measured mask density for useful-FLOPs accounting, taken from the
    # host copy BEFORE device_put: pulling the full mask back later
    # would re-pay the device->host transfer the device-input path
    # exists to avoid.
    density = float(mask_k.mean())
    alphas = np.full(spc, 0.025, np.float32)
    host_inputs = bool(int(os.environ.get("BENCH_HOST_INPUTS", "0")))
    if not host_inputs:
        # Device-resident inputs: production fit()/fit_file() assembles
        # batches ON device from the uploaded corpus (ops/device_batching),
        # so steady-state training ships only scalars per dispatch. Feeding
        # numpy here instead would re-measure a host->device transfer the
        # hot path no longer performs. BENCH_HOST_INPUTS=1 restores the
        # old behavior as a diagnostic.
        centers_k, contexts_k, mask_k, alphas = map(
            jax.device_put, (centers_k, contexts_k, mask_k, alphas)
        )
        jax.block_until_ready(alphas)
    key = jax.random.PRNGKey(0)

    # Warm up / compile.
    t0 = time.time()
    losses = eng.train_steps(centers_k, contexts_k, mask_k, key, alphas, 0)
    jax.block_until_ready(losses)
    compile_s = time.time() - t0

    # Timed loop: run dispatches until the floor is reached so one number
    # is never a single-dispatch fluke.
    min_seconds = float(os.environ.get("BENCH_MIN_SECONDS", 2.0))
    max_calls = int(os.environ.get("BENCH_MAX_CALLS", 50))
    t0 = time.time()
    calls = 0
    last = None
    while calls < max_calls:
        last = eng.train_steps(
            centers_k, contexts_k, mask_k, key, alphas, calls * spc
        )
        calls += 1
        if calls >= 2 and time.time() - t0 >= min_seconds:
            break
    jax.block_until_ready(last)
    dt = time.time() - t0

    steps = calls * spc
    words = B * steps  # trained center positions == reference word count
    wps = words / dt
    flops = _flops_per_step(mode, cfg, density) * steps / dt
    del eng  # release the two V x d tables before the next mode runs
    return {
        "words_per_sec": round(wps, 1),
        # Useful-pair throughput + its dense-word normalization (see
        # module docstring): the grid-vs-packed comparable numbers.
        "pairs_per_sec": round(wps * C * density, 1),
        "effective_words_per_sec": round(wps * density, 1),
        "step_time_us": round(dt / steps * 1e6, 1),
        "compile_s": round(compile_s, 1),
        "flops_per_sec": round(flops, 3),
        "mask_density": round(density, 4),
        "timed_steps": steps,
        # Effective dtypes for THIS mode (suffixes override BENCH_DTYPE),
        # so the artifact is self-describing.
        "table_dtype": table_dtype or cfg["dtype"],
        "compute_dtype": compute_dtype,
        "inputs": "host" if host_inputs else "device",
    }


def _bench_corpus_mode(
    jax, eng, cfg, np, compute_dtype, p, subsample=False, packed=False,
):
    """The production fit/fit_file hot path: the flat Zipf corpus uploaded
    to HBM once, every minibatch assembled INSIDE the jitted train scan
    (ops/device_batching window shrinkage + sentence bounds); per-dispatch
    host->device traffic is scalars only. With ``subsample`` the per-epoch
    on-device subsample-compact pass runs first (the realistic production
    config) and training covers the compacted stream. With ``packed`` the
    scan runs the DENSE pair-packing dispatch (ISSUE 4): valid pairs
    compacted into batch*context_lanes pair slots per step — same nominal
    step FLOPs as a grid dispatch, ~1/density more corpus positions
    covered per step; its ``mask_density`` is the packed fill."""
    V, B, spc = cfg["vocab"], cfg["batch"], cfg["steps_per_call"]
    # Window sized so the device batcher's lane count (2W-3) matches the
    # context_lanes the FLOPs formula charges.
    W = (cfg["context_lanes"] + 3) // 2
    assert 2 * W - 3 == cfg["context_lanes"], cfg
    sent_len = 40
    rng = np.random.default_rng(0)
    N = max(4 * spc * B, 2_000_000)
    N -= N % sent_len
    ids = rng.choice(V, size=N, p=p).astype(np.int32)
    offsets = np.arange(0, N + sent_len, sent_len, dtype=np.int64)
    eng.upload_corpus(ids, offsets)
    ratio = cfg["subsample_ratio"]
    n_pos = N
    compact_s = None
    if subsample:
        # Per-word keep probabilities by the exact Vocabulary
        # .keep_probabilities rule; ``p`` IS the normalized frequency the
        # Zipf corpus was drawn from, so no vocab scan is needed.
        with np.errstate(divide="ignore", invalid="ignore"):
            kp = (np.sqrt(p / ratio) + 1.0) * (ratio / p)
        kp = np.clip(np.where(p > 0, kp, 0.0), 0.0, 1.0).astype(np.float32)
        eng.set_keep_probs(kp)
        eng.compact_corpus(jax.random.PRNGKey(1))  # compile warm-up
        t0 = time.time()
        n_pos = eng.compact_corpus(jax.random.PRNGKey(2))
        compact_s = time.time() - t0  # steady-state per-epoch cost
    alphas = np.full(spc, 0.025, np.float32)
    key = jax.random.PRNGKey(0)
    min_seconds = float(os.environ.get("BENCH_MIN_SECONDS", 2.0))
    max_calls = int(os.environ.get("BENCH_MAX_CALLS", 50))
    C = cfg["context_lanes"]
    pairs_done = None

    if packed:
        # Pair slots per step = the grid step's lane count, so one packed
        # dispatch costs the same nominal contraction FLOPs as one grid
        # dispatch; LR params pinned so alpha ~= the grid loop's 0.025.
        P = B * C
        pk = dict(
            step_size=0.025, total_words=10**12, words_base=0,
        )
        t0 = time.time()
        res = eng.train_steps_corpus_packed(
            0, P, W, B, key, spc, step0=0, grid_step0=0, **pk
        )
        jax.block_until_ready(res[0])
        compile_s = time.time() - t0

        t0 = time.time()
        calls, words, pairs_done, pos, live_slots = 0, 0, 0, 0, 0
        while calls < max_calls:
            if pos >= n_pos:
                pos = 0  # epoch wrap
            res = eng.train_steps_corpus_packed(
                pos, P, W, B, key, spc, step0=calls * spc, grid_step0=0,
                **pk,
            )
            # The (K,)-scalar readback the production loop also performs
            # per dispatch — the data-dependent position advance.
            pos_ends = np.asarray(res[2])
            pairs_done += int(np.asarray(res[1]).sum())
            # Fill denominator counts LIVE steps only (same rule as the
            # fit loop's packed_mask_density): steps past the corpus end
            # are zero-pair no-ops, and charging their empty slots would
            # understate the per-dispatch fill on epoch crossings.
            starts = np.concatenate(([pos], pos_ends[:-1]))
            live_slots += int((starts < n_pos).sum()) * P
            words += max(0, min(n_pos, int(pos_ends[-1])) - pos)
            pos = int(pos_ends[-1])
            calls += 1
            if calls >= 2 and time.time() - t0 >= min_seconds:
                break
        dt = time.time() - t0
        steps = calls * spc
    else:
        t0 = time.time()
        losses = eng.train_steps_corpus(0, B, W, key, alphas, 0)
        jax.block_until_ready(losses)
        compile_s = time.time() - t0

        span = max(n_pos - spc * B, 1)  # wrap: no dispatch hits the tail
        t0 = time.time()
        calls, last, words = 0, None, 0
        while calls < max_calls:
            start = (calls * spc * B) % span
            last = eng.train_steps_corpus(
                start, B, W, key, alphas, calls * spc
            )
            # Credit only LIVE positions: an aggressive ratio can compact
            # n_pos below one dispatch's coverage, and the tail rows past
            # n_pos are zero-mask no-ops that must not count as trained
            # words.
            words += max(0, min(n_pos, start + spc * B) - start)
            calls += 1
            if calls >= 2 and time.time() - t0 >= min_seconds:
                break
        jax.block_until_ready(last)
        dt = time.time() - t0
        steps = calls * spc

    # MEASURED mask density of the device-assembled windows (shrink draw
    # + sentence-bound clipping leave ~0.42 of the lanes live at W=5:
    # E[max(2b-1,0)]/7 = 0.457 for b~U[0,5), minus boundary loss):
    # evaluate the actual batcher on one step's B positions, reusing the
    # corpus the engine already holds on device — no re-upload, and only
    # a B*C mask comes back to host.
    from glint_word2vec_tpu.ops.device_batching import device_window_batch

    jnp = jax.numpy
    # Probe the ACTIVE corpus view (the compacted buffers when
    # subsampling): its shrunk-window density is what the scan executed.
    dev_ids, dev_offsets = (
        eng._corpus_compacted if subsample else eng._corpus
    )
    _, _, probe_mask = device_window_batch(
        dev_ids, dev_offsets,
        jnp.arange(B, dtype=jnp.int32),
        jnp.arange(B, dtype=jnp.int32),
        key, W,
        n_valid=jnp.int32(n_pos),
    )
    density = float(np.asarray(probe_mask).mean())
    del probe_mask
    if packed:
        # mask_density for the packed cell is the measured FILL of the
        # dense pair batches (live pairs / dispatched pair slots); the
        # grid density of the same corpus is echoed for context — it is
        # the waste packing removed. pairs/sec is measured directly.
        d_, n_ = cfg["dim"], cfg["negatives"]
        fill = pairs_done / max(live_slots, 1)
        out = {
            "words_per_sec": round(words / dt, 1),
            "pairs_per_sec": round(pairs_done / dt, 1),
            "effective_words_per_sec": round(pairs_done / dt / C, 1),
            "step_time_us": round(dt / steps * 1e6, 1),
            "compile_s": round(compile_s, 1),
            "flops_per_sec": round(
                (6.0 * d_ * (1 + n_) * pairs_done + words * d_) / dt, 3
            ),
            "mask_density": round(fill, 4),
            "grid_mask_density": round(density, 4),
            "pair_batch": B * C,
            "timed_steps": steps,
            "table_dtype": str(eng.syn0.dtype),
            "compute_dtype": compute_dtype,
            "corpus_words_device": int(N),
            "window": W,
            "inputs": "device_corpus_packed",
        }
        return out
    out = {
        "words_per_sec": round(words / dt, 1),
        "pairs_per_sec": round(words / dt * C * density, 1),
        "effective_words_per_sec": round(words / dt * density, 1),
        "step_time_us": round(dt / steps * 1e6, 1),
        "compile_s": round(compile_s, 1),
        "flops_per_sec": round(
            _flops_per_step("corpus", cfg, density) * steps / dt, 3
        ),
        "mask_density": round(density, 4),
        "timed_steps": steps,
        "table_dtype": str(eng.syn0.dtype),
        "compute_dtype": compute_dtype,
        "corpus_words_device": int(N),
        "window": W,
        "inputs": "device_corpus",
    }
    if subsample:
        # The effective ratio + what it kept, so the JSON line is
        # self-describing about what the words/sec number trained over.
        out["subsample_ratio"] = ratio
        out["corpus_words_kept"] = int(n_pos)
        out["kept_fraction"] = round(n_pos / N, 4)
        out["compact_s"] = round(compact_s, 3)
    return out


def worker_main() -> None:
    from glint_word2vec_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    import numpy as np
    import jax

    from glint_word2vec_tpu.parallel.mesh import make_mesh

    cfg = _config_from_env()
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        raise RuntimeError(
            "bench.py measures an accelerator and JAX found only the CPU "
            f"({dev.device_kind!r}); a CPU rate is not written under the "
            "unit words/sec/chip. For a CPU rehearsal of the main path run "
            "`python chip_smoke.py --platform cpu --tiny`."
        )
    mesh = make_mesh(1, 1, devices=[dev])

    modes = [m.strip() for m in cfg.pop("modes").split(",") if m.strip()]
    results = {}
    peaks = {}
    for mode in modes:
        _, compute_dtype, _ = _mode_parts(mode)
        peak = _peak_for(dev.device_kind, compute_dtype)
        r = _bench_mode(jax, mesh, cfg, mode, np)
        if "flops_per_sec" in r:
            r["mfu"] = round(r.pop("flops_per_sec") / peak, 4)
            r["peak_flops_assumed"] = peak
            peaks[mode] = peak
        results[mode] = r
        # Each mode as it lands, for a run that is cut short later.
        print(f"[bench] {mode}: {json.dumps(r)}", file=sys.stderr, flush=True)

    headline_mode = "per_pair" if "per_pair" in results else modes[0]
    headline = results[headline_mode]
    wps = headline["words_per_sec"]
    line = {
        "metric": "sgns_train_throughput",
        "value": wps,
        "unit": "words/sec/chip",
        "vs_baseline": round(wps / BASELINE_WORDS_PER_SEC_PER_CHIP, 4),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "estimator": headline_mode,
        "config": cfg,
        "modes": results,
    }
    if headline_mode in peaks:
        line["peak_flops_assumed"] = peaks[headline_mode]
        line["mfu"] = headline["mfu"]
    print(json.dumps(line), flush=True)


def main() -> int:
    """Run the worker once in a child and hand back its exit code; its
    output goes straight through. This parent never imports JAX, so the
    worker is the one process that touches the chip."""
    timeout = float(os.environ.get("BENCH_TIMEOUT", 1800))
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=dict(os.environ, BENCH_WORKER="1"), timeout=timeout,
        )
    except subprocess.TimeoutExpired:
        print(f"bench.py: worker timed out after {timeout:g}s",
              file=sys.stderr)
        return 124
    return proc.returncode


if __name__ == "__main__":
    if os.environ.get("BENCH_WORKER") == "1":
        worker_main()
    else:
        sys.exit(main())
