#!/usr/bin/env python3
"""The benchmark's harness: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Driven by data. ``BENCHMARK.json`` names the cell; its configuration is the
file its ``configs`` entry names, its traffic ``benchmark/traffic/<cell>.json``,
whose ``kind`` names ``benchmark/kinds/<kind>.py`` (the code that drives one
kind of job); each per-layer metric is ``benchmark/layers/<metric>.py`` with a
``read(run)`` that returns a number, or None where it finds nothing to read.
A new configuration, traffic mix or per-layer metric is new files plus
entries; no file here needs an edit.

Needs a TPU (``--tiny`` alone lets a CPU rehearse the control flow; such a
run says ``platform: cpu`` and is never a metric), never sets
``JAX_PLATFORMS``, and prints as the LAST line of stdout one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``. Every number compared for ``correct`` is on an earlier line
beside its limit. Everything the program prints goes to stderr.
"""

T_START = __import__("time").perf_counter()

import argparse
import contextlib
import importlib.util
import json
import logging
import math
import os
import shutil
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = sys.stdout


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=OUT, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """A file of the benchmark found by name (names may hold dots)."""
    name = "bench_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, HERE))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if (
            isinstance(v, dict) and isinstance(out.get(k), dict)) else v
    return out


class Run:
    """What one run knows; kinds fill it, layer readers read it."""

    def __init__(self, args, cell, cfg, traffic):
        self.args, self.cell = args, cell
        self.cfg, self.traffic = cfg, traffic
        self.t_start = T_START
        self.say = say
        self.work = os.path.join(ROOT, ".bench_work", cell["name"])
        self.trace_dir = os.path.join(self.work, "trace")
        self.table_dtype = "bfloat16" if args.control == "bf16" else None
        self.check_seconds = 0.0  # the check's reads inside set-up
        self.window = None  # (start, end) on perf_counter
        self.device = None
        self.memory_peak_bytes = 0
        self.numbers = []  # (name, value, limit)
        self.attempted = self.failed = 0
        self.end_to_end = {}
        self.notes = {}
        self.trace = None  # trace_reduce.reduce(...)
        self.trace_t = None  # (start, stop) of the profiler, perf_counter
        self.program_spans = None
        self.program_spans_path = None
        self.training_metrics = None
        self.serving_metrics = self.serving_metrics_before = None

    def device_of(self, engine) -> dict:
        """The device as the tables' own arrays report it."""
        dev = next(iter(engine.syn0.devices()))
        self._devices = sorted(engine.syn0.devices(), key=lambda x: x.id)
        return {"platform": dev.platform, "kind": dev.device_kind,
                "count": int(engine.mesh.devices.size)}

    def read_memory_peak(self) -> int:
        stats = [d.memory_stats() or {} for d in getattr(self, "_devices", [])]
        say(f"memory_stats of the fullest chip: "
            f"{max(stats, key=lambda s: s.get('peak_bytes_in_use', 0), default={})}")
        # The runtime keeps a program's temporaries in a reserved pool that
        # bytes_in_use does not count: the peak is the two together.
        return int(max((s.get("peak_bytes_in_use", 0)
                        + s.get("peak_bytes_reserved", 0) for s in stats),
                       default=0))

    @contextlib.contextmanager
    def count_compiles(self):
        """(perf_counter, event) of every program compiled, or looked up in
        the persistent cache, while the block runs: the window sees none."""
        import jax

        seen, on = [], [True]  # (perf_counter, event)

        def on_event(event, **_):
            if on[0] and event.endswith(("/cache_hits", "/cache_misses")):
                seen.append((time.perf_counter(), event))

        def on_duration(event, seconds, **_):
            if on[0] and event.endswith("/backend_compile_duration"):
                seen.append((time.perf_counter(), event))

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        try:
            yield seen
        finally:
            on[0] = False  # jax.monitoring cannot unregister a listener


def finish(doc) -> int:
    """The one line the contract reads, last; nothing may follow it."""
    sys.stderr.flush()
    print(json.dumps(doc), file=OUT, flush=True)
    os.dup2(os.open(os.devnull, os.O_WRONLY), OUT.fileno())
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal sizes (control flow only; the one way "
                         "to run without a TPU)")
    ap.add_argument("--control", choices=("bf16",), default=None,
                    help="run the cell in the nearest lower precision "
                         "(bfloat16 tables): correct must come out false")
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 2
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(os.path.join(ROOT, config["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", cell["name"] + ".json"))
    if args.tiny:
        cfg = merge(cfg, cfg.get("tiny", {}))
        traffic = merge(traffic, traffic.get("tiny", {}))
    kind = load_module(os.path.join(HERE, "kinds", traffic["kind"] + ".py"))

    sys.stdout = sys.stderr  # the package's prints
    logging.basicConfig(stream=sys.stderr, level=logging.INFO,
                        format="%(asctime)s %(name)s: %(message)s")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    if args.trace:
        os.environ.setdefault("GLINT_TRACE_SAMPLE", "4")
    import jax  # never sets JAX_PLATFORMS: JAX finds what there is

    devs = jax.devices()
    found = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    say(f"jax {jax.__version__} found {found}; cell {cell['name']} "
        f"seed {args.seed} seconds {args.seconds} trace {args.trace}"
        f"{' TINY REHEARSAL' if args.tiny else ''}"
        f"{' CONTROL ' + args.control if args.control else ''}")
    if found["platform"] != "tpu" and not args.tiny:
        print(f"this benchmark needs a TPU and JAX found "
              f"{found['platform']!r}; no fallback", file=sys.stderr)
        return 3
    if found["count"] < cell["chips"]:
        print(f"cell {cell['name']} needs {cell['chips']} chip(s) and JAX "
              f"found {found['count']}", file=sys.stderr)
        return 3

    from glint_word2vec_tpu.utils.platform import enable_compile_cache

    cache_dir = enable_compile_cache(0.0)
    say(f"compile cache: {cache_dir} "
        f"({len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(cache_dir) else 0} entries)")

    run = Run(args, cell, cfg, traffic)
    shutil.rmtree(run.work, ignore_errors=True)
    os.makedirs(run.work)
    try:
        kind.run(run)
        metrics = {}
        setup_s = run.window[0] - T_START - run.check_seconds
        if args.trace:
            from benchmark import trace_reduce

            t0, t1 = run.trace_t
            run.trace = trace_reduce.reduce(run.trace_dir, t1 - t0)
            say(f"trace: {run.trace['op_events']} op events on "
                f"{run.trace['devices']} device(s), busy "
                f"{run.trace['busy_s']:.4f}s of {run.trace['window_s']:.4f}s")
            for name, m in sorted(run.trace["modules"].items(),
                                  key=lambda kv: -kv[1]["seconds"])[:8]:
                say(f"trace module {name}: {m['count']:.1f} runs, "
                    f"{m['seconds']:.4f}s")
            for spec in bench["per_layer"]:
                if cell["name"] not in spec.get("workloads", [cell["name"]]):
                    continue
                value = load_module(os.path.join(
                    HERE, "layers", spec["name"] + ".py")).read(run)
                if value is not None:
                    metrics[spec["name"]] = {"value": float(value),
                                             "unit": spec["unit"]}
        else:
            values = dict(run.end_to_end, setup_s=setup_s)
            for spec in bench["end_to_end"]:
                if cell["name"] in spec.get("workloads", [cell["name"]]):
                    v = float(values[spec["name"]])
                    metrics[spec["name"]] = {
                        "value": v if math.isfinite(v) else 1e12,
                        "unit": spec["unit"]}
        correct = True
        for name, value, limit in run.numbers:
            ok = math.isfinite(value) and value <= limit
            correct = correct and ok
            say(f"compare {name}: {value:.6g} (limit {limit:.6g}) "
                f"{'ok' if ok else 'NOT OK'}")
        say(f"setup_s {setup_s:.3f} (process start to the window, less "
            f"{run.check_seconds:.3f}s of the check's own reads); "
            f"whole run {time.perf_counter() - T_START:.2f}s")
        for k, v in sorted(metrics.items()):
            say(f"metric {k} = {v['value']:.6g} {v['unit']}")
        device = dict(run.device, memory_peak_bytes=run.memory_peak_bytes)
        doc = {"correct": bool(correct), "attempted": int(run.attempted),
               "failed": int(run.failed), "metrics": metrics,
               "device": device}
        if run.trace:
            device.update(busy_s=run.trace["busy_s"],
                          window_s=run.trace["window_s"])
            doc["breakdown"] = run.trace["breakdown"]
    except BaseException:
        traceback.print_exc(file=sys.stderr)
        say("FAILED: " + traceback.format_exc().strip().splitlines()[-1])
        return 1
    finally:
        shutil.rmtree(run.work, ignore_errors=True)
    return finish(doc)


if __name__ == "__main__":
    sys.exit(main())
