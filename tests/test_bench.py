"""bench.py's contract with the device (it measures an accelerator or
fails: no CPU fallback, no unknown peak, a JAX-free parent), its
mask-density-scaled FLOPs accounting, and the trace summarizer."""

import importlib.util
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

spec = importlib.util.spec_from_file_location(
    "bench_mod", os.path.join(ROOT, "bench.py")
)
bench = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bench)


def test_peak_table_is_keyed_by_exact_device_kind():
    assert bench._peak_for("TPU v5 lite", "bfloat16") == 197e12
    assert bench._peak_for("TPU v5 lite", "float32") == 197e12 / 2
    # No substring match ("v5" once also caught v5p) and no default: a
    # device without a published peak on record is an error.
    for kind in ("TPU v5", "TPU v5p", "cpu", "TPU v9 lite"):
        with pytest.raises(ValueError, match="no published peak"):
            bench._peak_for(kind, "bfloat16")


def test_bench_fails_without_an_accelerator():
    """No CPU fallback, no salvaged line, no exit 0: with JAX held to the
    CPU (tests/conftest.py exports JAX_PLATFORMS=cpu) the one worker
    raises and bench.py passes its failure on."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, BENCH_MODES="per_pair", BENCH_VOCAB="1000"),
    )
    assert proc.returncode != 0
    assert "found only the CPU" in proc.stderr
    assert '"metric"' not in proc.stdout


def test_bench_parent_stays_off_jax():
    """The chip belongs to one process: the parent that starts the
    worker must never import JAX (or the package, which does)."""
    code = (
        "import runpy, sys, subprocess\n"
        "subprocess.run = lambda *a, **k: type('P', (), {'returncode': 0})()\n"
        "try:\n"
        f"    runpy.run_path({os.path.join(ROOT, 'bench.py')!r}, run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    assert e.code == 0, e.code\n"
        "assert 'jax' not in sys.modules, 'bench.py parent imported jax'\n"
        "assert 'glint_word2vec_tpu' not in sys.modules\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "BENCH_WORKER"}
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=60, env=env,
    )
    assert proc.returncode == 0, proc.stderr


def test_flops_scale_with_measured_mask_density():
    cfg = {"batch": 8, "context_lanes": 7, "dim": 4, "negatives": 5,
           "shared_negatives": 16}
    full = bench._flops_per_step("per_pair", cfg, 1.0)
    half = bench._flops_per_step("per_pair", cfg, 0.5)
    # Context-lane terms halve; the center-row scatter (B*d) does not.
    assert half == (full - 8 * 4) / 2 + 8 * 4
    sh_full = bench._flops_per_step("shared", cfg, 1.0)
    sh_half = bench._flops_per_step("shared", cfg, 0.5)
    pool_terms = 6.0 * 8 * 16 * 4 + 8 * 4 + 16 * 4
    assert sh_half == (sh_full - pool_terms) / 2 + pool_terms


def _load_trace_summarize():
    spec2 = importlib.util.spec_from_file_location(
        "trace_summarize", os.path.join(ROOT, "scripts", "trace_summarize.py")
    )
    ts = importlib.util.module_from_spec(spec2)
    spec2.loader.exec_module(ts)
    return ts


def test_trace_summarize_op_classes():
    ts = _load_trace_summarize()
    cases = {
        "all-reduce.1": "collective",
        "dynamic-update-slice.7": "scatter",
        "gather.2": "gather",
        "dot_general": "dense_mxu",
        "rng-bit-generator": "rng_sampling",
        "copy.3": "data_movement",
        "infeed": "host_transfer",
        "fusion.12": "fusion_other",
        "custom-call.9": "other",
    }
    for name, want in cases.items():
        assert ts.classify(name) == want, (name, ts.classify(name))


@pytest.mark.slow  # the tensorflow import alone costs ~20s of tier-1 wall
def test_trace_summarize_device_plane_aggregation(tmp_path):
    # Synthetic xplane with the TPU trace shape: a device plane carrying
    # an "XLA Ops" line (must aggregate) plus spanning lines that must be
    # EXCLUDED — "XLA Modules"/"Steps" (fail the ops|stream inclusion)
    # AND a "Steps Ops" line that MATCHES the inclusion regex and is only
    # kept out by the module|step|traceme exclusion — plus a host plane
    # (ignored). Counting any spanning line would double the device time.
    os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")
    pytest.importorskip("tensorflow")
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    ts = _load_trace_summarize()

    xs = xplane_pb2.XSpace()
    dev = xs.planes.add(name="/device:TPU:0")

    def add_line(plane, name, events):  # events: [(op_name, dur_ps)]
        line = plane.lines.add(name=name)
        for op, dur in events:
            mid = len(plane.event_metadata) + 1
            plane.event_metadata[mid].id = mid
            plane.event_metadata[mid].name = op
            ev = line.events.add(metadata_id=mid)
            ev.duration_ps = dur

    add_line(dev, "XLA Ops", [
        ("fusion.1", 3_000_000),          # 3 us -> fusion_other
        ("dot_general.2", 2_000_000),     # dense_mxu
        ("dynamic-update-slice.3", 1_000_000),  # scatter
        ("all-reduce.4", 500_000),        # collective
    ])
    add_line(dev, "XLA Modules", [("jit_train", 6_500_000)])
    add_line(dev, "Steps", [("step0", 6_500_000)])
    # Matches the inclusion regex ("ops") — only the exclusion branch
    # keeps this spanning line out of the aggregate.
    add_line(dev, "Steps Ops", [("step0_span", 6_500_000)])
    host = xs.planes.add(name="/host:CPU")
    add_line(host, "python", [("frame", 9_000_000)])

    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(xs.SerializeToString())

    doc = ts.summarize(str(tmp_path))
    assert len(doc["planes"]) == 1
    p = doc["planes"][0]
    assert p["plane"] == "/device:TPU:0"
    assert p["device_busy_us"] == 6.5  # ops only, no module/step double-count
    assert p["by_class_us"] == {
        "fusion_other": 3.0, "dense_mxu": 2.0, "scatter": 1.0,
        "collective": 0.5,
    }
    assert abs(p["by_class_share"]["fusion_other"] - 3.0 / 6.5) < 1e-3
