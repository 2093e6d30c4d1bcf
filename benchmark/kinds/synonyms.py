"""Cell kind ``synonyms``: a served table under a closed loop of callers.

The table is made on the device from ``--seed`` (normal(0, std); speed and
the reference need no trained table), written into a fresh engine with the
program's ``write_rows``, and handed to the ``ModelServer`` that
``serve_model_dir`` builds after its load, with ``cli serve``'s defaults
(warm-up included). The server runs on threads of this process, which holds
the chip; the callers are ``benchmark/loadgen.py``, a child that never
imports JAX. Once the window has closed a seeded sample of the answers the
callers received is compared with a numpy float32 cosine top-k.

The seam: ``EmbeddingEngine.top_k_cosine`` / ``top_k_cosine_batch`` are
wrapped with the benchmark's own trace annotations; counters come from ``GET /metrics``, spans from a recorder the
benchmark installs in traced runs.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def http(port, path, body=None, timeout=120.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        headers={"Content-Type": "application/json"} if data else {})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


class Seam:
    """The benchmark's own annotations around the top-k dispatches: they
    label the device's idle gaps in the trace."""

    def install(self):
        import jax
        from glint_word2vec_tpu.parallel.engine import EmbeddingEngine

        self._cls = EmbeddingEngine
        self._orig = {}
        for name in ("top_k_cosine", "top_k_cosine_batch"):
            orig = self._orig[name] = getattr(EmbeddingEngine, name)

            def wrapped(engine, *a, _orig=orig, _name=name, **k):
                with jax.profiler.TraceAnnotation("bench." + _name):
                    return _orig(engine, *a, **k)

            setattr(EmbeddingEngine, name, wrapped)

    def uninstall(self):
        for name, orig in self._orig.items():
            setattr(self._cls, name, orig)


def vocabulary(vocab: int):
    """Words in frequency-rank order and Zipf-shaped counts."""
    from benchmark.corpus import filler_names, special_words

    _, special = special_words()
    words = list(filler_names(vocab - len(special))) + special
    counts = np.maximum(1, (vocab / np.arange(1, vocab + 1))).astype(np.int64)
    return words, counts


def run(ctx):
    import jax
    import jax.numpy as jnp

    from benchmark import reference
    from benchmark.corpus import zipf_words
    from glint_word2vec_tpu.corpus.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    from glint_word2vec_tpu.serving import ModelServer
    from glint_word2vec_tpu.utils.params import Word2VecParams

    cfg, traffic, args = ctx.cfg, ctx.traffic, ctx.args
    m, r = cfg["model"], cfg["run"]
    V, d = m["vocab"], m["vector_size"]
    prog_seed = int(args.seed) % (2**31 - 1)
    dtype = ctx.table_dtype or m["table_dtype"]

    words, counts = vocabulary(V)
    vocab = Vocabulary.from_sorted(words, counts)
    engine = EmbeddingEngine(
        make_mesh(1, r["num_shards"]), V, d, counts,
        num_negatives=m["negatives"], seed=prog_seed, dtype=dtype)
    table = jax.jit(lambda key: jax.random.normal(
        key, (V, d), dtype=jnp.float32) * jnp.float32(traffic["table_std"]))(
            jax.random.PRNGKey(prog_seed))
    t_check = time.perf_counter()
    host_table = np.asarray(table)  # the reference's copy: not set-up
    ctx.check_seconds += time.perf_counter() - t_check
    engine.write_rows(0, table)
    table.delete()
    ctx.device = ctx.device_of(engine)
    spans_devices = len(engine.syn0.sharding.device_set)
    model = Word2VecModel(vocab, engine, Word2VecParams(
        vector_size=d, window=m["window"], num_negatives=m["negatives"],
        num_shards=r["num_shards"], seed=prog_seed, dtype=dtype))
    ctx.say(f"table {V} x {d} {dtype} on the device, "
            f"{time.perf_counter() - ctx.t_start:.2f}s since start")

    seam = Seam()
    seam.install()
    recorder = None
    if args.trace:
        from glint_word2vec_tpu.obs import events as obs_events

        recorder = obs_events.EventRecorder(capacity=1 << 20)
        prev_recorder = obs_events.set_recorder(recorder)
    server = None
    try:
        t0 = time.perf_counter()
        server = ModelServer(model, host="127.0.0.1", port=0)
        server.start_background()
        port = server.port
        ctx.say(f"server: port {port}, warm-up {time.perf_counter() - t0:.2f}s")
        def callers(ranks):
            return [[words[i] for i in ranks[k::traffic["callers"]]]
                    for k in range(traffic["callers"])]

        def load(name, ranks, **spec):
            """The callers, as a child process over a spec file."""
            spec = dict(spec, port=port, path="/synonyms",
                        num=traffic["num"], keep_every=37,
                        callers=callers(ranks),
                        out=os.path.join(ctx.work, name + "_out.json"))
            path = os.path.join(ctx.work, name + "_spec.json")
            with open(path, "w") as f:
                json.dump(spec, f)
            child = subprocess.Popen(
                [sys.executable,
                 os.path.join(os.path.dirname(HERE), "loadgen.py"), path],
                stdout=sys.stderr, stderr=sys.stderr)
            return child, spec["out"]

        # A server that has run for hours holds its hottest words' answers:
        # fill the result cache with the most frequent ones, so that the
        # window sees a steady hit share and not a cold cache filling.
        t0 = time.perf_counter()
        child, _ = load("warm", np.arange(traffic["cache_warm_words"]),
                        seconds=600.0, once=True)
        if child.wait(timeout=600) != 0:
            raise RuntimeError("cache warm-up failed")
        ctx.say(f"result cache warmed with the {traffic['cache_warm_words']} "
                f"most frequent words in {time.perf_counter() - t0:.2f}s")
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
        if args.trace:
            obs_events.set_recorder(prev_recorder)
        seam.uninstall()
        model.stop()

    with open(out_path) as f:
        out = json.load(f)
    # The child's window: from its first caller's start to its last reply.
    # Spawning the child (some 50 ms of interpreter start) is not in it.
    window_s = float(out["window_s"])
    ctx.window = (t_closed - window_s, t_closed)
    req = np.asarray(out["requests"], np.float64).reshape(-1, 3)
    ok = req[:, 2] == 200
    attempted, failed = int(req.shape[0]), int((~ok).sum())
    # A failed or refused request counts as beyond every percentile.
    lat_ms = np.where(ok, req[:, 1] * 1e3, np.inf)
    ctx.say(f"window: {attempted} requests from {traffic['callers']} callers "
            f"in {window_s:.3f}s, {failed} failed; p95 over {attempted} "
            f"samples, {int(attempted * 0.05)} beyond it")

    # -- the reference, once the window has closed ----------------------
    t_ref = time.perf_counter()
    rng = np.random.default_rng(args.seed)
    kept = out["kept"]
    order = rng.permutation(len(kept))
    seen, sample = set(), []
    for i in order:  # distinct words first: a hot word is one answer
        if kept[i]["word"] not in seen:
            seen.add(kept[i]["word"])
            sample.append(kept[i])
        if len(sample) == traffic["checked_answers"]:
            break
    top = reference.TopK(host_table)
    rows = np.asarray([vocab.word_index[s["word"]] for s in sample])
    cos = top.cosines(rows)
    worst, bad_status = 0.0, 0
    for j, s in enumerate(sample):
        if s["status"] != 200:
            bad_status += 1
            continue
        got = [(vocab.word_index.get(w), sc) for w, sc in json.loads(s["body"])]
        worst = max(worst, top.gap(int(rows[j]), cos[:, j], got,
                                   traffic["num"]))
    ctx.say(f"reference: {len(sample)} answers of {len(kept)} kept in "
            f"{time.perf_counter() - t_ref:.2f}s")
    lim = traffic["limits"]
    post = m1["compiles"]["post_warmup"]
    ctx.numbers = [
        ("answers.score_gap", worst, lim["answers.score_gap"]),
        ("answers.sampled_not_ok", bad_status, 0),
        ("answers.too_few_sampled",
         max(0, min(traffic["checked_answers"], 4) - len(sample)), 0),
        ("server.post_warmup_compiles", post, 0),
        ("window.compiles",
         sum(t >= ctx.window[0] for t, _ in compiles), 0),
        ("tables.devices_missing",
         max(0, ctx.cell["chips"] - spans_devices), 0),
    ]
    ctx.attempted, ctx.failed = attempted, failed
    n_ok = int(ok.sum())
    ctx.end_to_end = {
        "synonyms_qps": n_ok / window_s,
        "synonyms_p50_ms": float(np.percentile(lat_ms, 50)),
        "synonyms_p95_ms": float(np.percentile(lat_ms, 95)),
    }
    ctx.notes = {"window_s": window_s, "requests": attempted,
                 "padded_rows": int(engine.padded_vocab)}
