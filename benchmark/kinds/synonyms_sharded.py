"""Cell kind ``synonyms_sharded``: a served table that lies on several chips,
a share of its rows on each, under a closed loop of callers.

``kinds/synonyms.py`` for a model no one chip holds: what ``cli serve`` does
after ``Word2VecModel.load`` has re-homed a model saved with ``num_shards``
n. The engine is built on the mesh ``load`` builds (data 1 x model n);
``syn0`` is drawn on the devices from ``--seed`` in row blocks (normal(0,
std); speed and the reference need no trained table), each block read back
for the reference (the check's own read, not set-up) and written with the
program's ``write_rows``; ``ModelServer`` with ``cli serve``'s defaults
warms and binds. The callers are ``benchmark/loadgen.py``, a child that
never imports JAX; each sends ``POST /synonyms {"word", "num"}`` for words
drawn Zipf over the whole vocabulary.

Once the window has closed a seeded sample of the answers the callers
received is compared with ``benchmark/reference_nn_sharded.py`` (numpy
float32, the reference's ``findSynonyms`` block by block over the host's
copy), and the tables' own arrays are asked where they lie: every chip of
the cell holds rows of both tables, none more than its share.

Taken from the program: ``EmbeddingEngine``, ``write_rows``, ``make_mesh``,
``Word2VecModel``, ``ModelServer``, ``GET /metrics``, the recorder of
``obs.events``; from ``kinds/synonyms.py`` its ``http``, ``vocabulary`` and
``Seam`` (the benchmark's annotations around the top-k dispatches).
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def rows_over_share(tables, rows: int, chips: int):
    """(rows the fullest device holds beyond its share, devices that hold
    any) of tables that should lie by rows over ``chips`` devices: the
    share is the rows over the chips, rounded up."""
    held = {}
    for table in tables:
        for shard in table.addressable_shards:
            rows_here = shard.data.shape[0]
            if shard.data.shape[1:] != table.shape[1:]:
                rows_here = table.shape[0]  # split another way: all rows
            held.setdefault(shard.device.id, []).append(rows_here)
    share = -(-rows // chips)
    fullest = max((max(v) for v in held.values()), default=rows)
    return max(0, fullest - share), len(held)


def run(ctx):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmark import reference_nn_sharded
    from benchmark.corpus import zipf_words
    from benchmark.kinds.synonyms import Seam, http, vocabulary
    from glint_word2vec_tpu.corpus.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.obs import events as obs_events
    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    from glint_word2vec_tpu.serving import ModelServer
    from glint_word2vec_tpu.utils.params import Word2VecParams

    cfg, traffic, args = ctx.cfg, ctx.traffic, ctx.args
    m, r = cfg["model"], cfg["run"]
    V, d, shards = m["vocab"], m["vector_size"], r["num_shards"]
    prog_seed = int(args.seed) % (2**31 - 1)
    dtype = ctx.table_dtype or m["table_dtype"]

    t0 = time.perf_counter()
    words, counts = vocabulary(V)
    vocab = Vocabulary.from_sorted(words, counts)
    ctx.say(f"vocabulary: {V} words in {time.perf_counter() - t0:.2f}s")
    # the mesh Word2VecModel.load builds for params.num_shards = shards
    mesh = make_mesh(1, shards)
    engine = EmbeddingEngine(
        mesh, V, d, counts, num_negatives=m["negatives"], seed=prog_seed,
        dtype=dtype)
    ctx.device = ctx.device_of(engine)
    # syn0 a block of rows at a time: drawn on every device of the mesh
    # alike, read back once for the reference, written by each shard.
    block_rows = int(traffic["table_block_rows"])
    draw = jax.jit(
        lambda key, n: jax.random.normal(key, (n, d), dtype=jnp.float32)
        * jnp.float32(traffic["table_std"]), static_argnums=1,
        out_shardings=NamedSharding(mesh, PartitionSpec()))
    key = jax.random.PRNGKey(prog_seed)
    host = reference_nn_sharded.Blocks(d)
    t0 = time.perf_counter()
    for i, s in enumerate(range(0, V, block_rows)):
        block = draw(jax.random.fold_in(key, i), min(block_rows, V - s))
        t_check = time.perf_counter()
        host.add(s, np.asarray(block))
        ctx.check_seconds += time.perf_counter() - t_check
        engine.write_rows(s, block)
        block.delete()
    jax.block_until_ready(engine.syn0)
    over_share, spans_devices = rows_over_share(
        (engine.syn0, engine.syn1), engine.padded_vocab, ctx.cell["chips"])
    model = Word2VecModel(vocab, engine, Word2VecParams(
        vector_size=d, window=m["window"], num_negatives=m["negatives"],
        num_shards=shards, seed=prog_seed, dtype=dtype))
    ctx.say(f"syn0 {V} x {d} {dtype} in blocks of {block_rows} rows over "
            f"{spans_devices} device(s) in {time.perf_counter() - t0:.2f}s "
            f"({ctx.check_seconds:.2f}s of them the check's read-back), "
            f"{time.perf_counter() - ctx.t_start:.2f}s since start")

    seam = Seam()
    seam.install()
    recorder = prev_recorder = None
    if args.trace:
        recorder = obs_events.EventRecorder(capacity=1 << 20)
        prev_recorder = obs_events.get_recorder()
        obs_events.set_recorder(recorder)
    server = None
    try:
        t0 = time.perf_counter()
        server = ModelServer(model, host="127.0.0.1", port=0)
        server.start_background()
        port = server.port
        ctx.say(f"server: port {port}, warm-up {time.perf_counter() - t0:.2f}s")

        def load(name, ranks, **spec):
            """The callers, as a child process over a spec file."""
            n = traffic["callers"]
            spec = dict(spec, port=port, path="/synonyms",
                        num=traffic["num"], keep_every=traffic["keep_every"],
                        callers=[[words[i] for i in ranks[k::n]]
                                 for k in range(n)],
                        out=os.path.join(ctx.work, name + "_out.json"))
            path = os.path.join(ctx.work, name + "_spec.json")
            with open(path, "w") as f:
                json.dump(spec, f)
            child = subprocess.Popen(
                [sys.executable,
                 os.path.join(os.path.dirname(HERE), "loadgen.py"), path],
                stdout=sys.stderr, stderr=sys.stderr)
            return child, spec["out"]

        # A server that has run for hours holds its hottest words' answers.
        t0 = time.perf_counter()
        warm_s = float(traffic["cache_warm_seconds"])
        child, _ = load("warm", np.arange(traffic["cache_warm_words"]),
                        seconds=warm_s, once=True)
        if child.wait(timeout=warm_s + 120) != 0:
            raise RuntimeError("cache warm-up failed")
        ctx.say(f"result cache: the {traffic['cache_warm_words']} most "
                f"frequent words asked for once in "
                f"{time.perf_counter() - t0:.2f}s")
        m0 = http(port, "/metrics")
        per_caller = int(float(args.seconds) * 1500) + 1000
        ranks = zipf_words(V, traffic["callers"] * per_caller,
                           traffic["zipf_exponent"], args.seed)

        tracer = None
        if args.trace:
            lo, hi = traffic["trace_window_s"]
            trace_t = ctx.trace_t = [None, None]

            def trace():
                time.sleep(lo)
                jax.profiler.start_trace(ctx.trace_dir)
                trace_t[0] = time.perf_counter()
                time.sleep(hi - lo)
                trace_t[1] = time.perf_counter()
                jax.profiler.stop_trace()

            tracer = threading.Thread(target=trace, name="bench-trace")
        with ctx.count_compiles() as compiles:
            child, out_path = load("window", ranks,
                                   seconds=float(args.seconds))
            if tracer:
                tracer.start()
            rc = child.wait(timeout=float(args.seconds) + 240)
            t_closed = time.perf_counter()
        if tracer:
            tracer.join()
        if rc != 0:
            raise RuntimeError(f"load generator exited {rc}")
        m1 = ctx.serving_metrics = http(port, "/metrics")
        ctx.serving_metrics_before = m0
        ctx.memory_peak_bytes = ctx.read_memory_peak()
        if recorder is not None:
            ctx.program_spans = recorder.events()
    finally:
        if server is not None:
            server.stop()
        if recorder is not None:
            obs_events.set_recorder(prev_recorder)
        seam.uninstall()
        model.stop()

    with open(out_path) as f:
        out = json.load(f)
    # The child's window: from its first caller's start to its last reply.
    window_s = float(out["window_s"])
    ctx.window = (t_closed - window_s, t_closed)
    req = np.asarray(out["requests"], np.float64).reshape(-1, 3)
    ok = req[:, 2] == 200
    attempted, failed = int(req.shape[0]), int((~ok).sum())
    # A failed or refused request counts as beyond every percentile.
    lat_ms = np.where(ok, req[:, 1] * 1e3, np.inf)
    n_ok = int(ok.sum())
    ctx.say(f"window: {attempted} requests from {traffic['callers']} callers "
            f"in {window_s:.3f}s, {failed} failed, {n_ok / window_s:.2f} "
            f"queries/s completed; p95 over {attempted} samples, "
            f"{int(attempted * 0.05)} beyond it")
    ctx.say(f"server: shards {m1.get('shards')}, rows_per_shard "
            f"{m1.get('rows_per_shard')}, resident_bytes "
            f"{m1.get('resident_bytes')} over all devices, "
            f"{m1.get('resident_bytes_per_device')} on the fullest")

    # -- the reference, once the window has closed ----------------------
    t_ref = time.perf_counter()
    rng = np.random.default_rng(args.seed)
    kept = out["kept"]
    seen, sample = set(), []
    for i in rng.permutation(len(kept)):  # distinct words: a hot one is one
        if kept[i]["word"] not in seen:
            seen.add(kept[i]["word"])
            sample.append(kept[i])
        if len(sample) == traffic["checked_answers"]:
            break
    rows = [vocab.word_index[s["word"]] for s in sample]
    cos = host.cosines(host.rows(rows)) if sample else None
    worst, bad_status = 0.0, 0
    for j, s in enumerate(sample):
        if s["status"] != 200:
            bad_status += 1
            continue
        got = [(vocab.word_index.get(w), sc)
               for w, sc in json.loads(s["body"])]
        worst = max(worst, reference_nn_sharded.gap(
            cos[:, j], got, traffic["num"], ban=rows[j]))
    ctx.say(f"reference: {len(sample)} answers of {len(kept)} kept, over "
            f"{len(host.blocks)} blocks, in "
            f"{time.perf_counter() - t_ref:.2f}s")
    lim = traffic["limits"]
    ctx.numbers = [
        ("answers.score_gap", worst, lim["answers.score_gap"]),
        ("answers.sampled_not_ok", bad_status, 0),
        ("answers.too_few_sampled",
         max(0, min(traffic["checked_answers"], 4) - len(sample)), 0),
        ("server.post_warmup_compiles", m1["compiles"]["post_warmup"], 0),
        ("window.compiles",
         sum(t >= ctx.window[0] for t, _ in compiles), 0),
        ("tables.devices_missing",
         max(0, ctx.cell["chips"] - spans_devices), 0),
        ("tables.rows_on_fullest_device_over_share", over_share, 0),
    ]
    ctx.attempted, ctx.failed = attempted, failed
    # ``synonyms_qps`` reaches the result line only where BENCHMARK.json lists
    # the cell under it (``run.py``); the log carries the rate either way.
    ctx.end_to_end = {
        "synonyms_qps": n_ok / window_s,
        "synonyms_p50_ms": float(np.percentile(lat_ms, 50)),
        "synonyms_p95_ms": float(np.percentile(lat_ms, 95)),
    }
    ctx.notes = {"window_s": window_s, "requests": attempted,
                 "padded_rows": int(engine.padded_vocab),
                 "shards": int(shards)}
