"""chip_smoke.py's contract with the driver, rehearsed on the CPU.

The driver reads the LAST line of the script's stdout and nothing else. These
tests run the tiny CPU rehearsal as a child (it keeps the ``JAX_PLATFORMS=cpu``
that tests/conftest.py exports, so it never loads the TPU library beside
tests/test_tpu_compile.py) and pin that line's shape, the failing forms, and
that no chip means no success.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args, timeout=300):
    env = dict(os.environ)
    # One virtual device is what the one-chip form sees; conftest's eight
    # are for the mesh tests.
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
    )


def _last_line(proc):
    """The last stdout line, parsed — after asserting it IS the last thing
    on stdout (one trailing newline, nothing after)."""
    assert proc.stdout.endswith("\n") and not proc.stdout.endswith("\n\n")
    line = proc.stdout.splitlines()[-1]
    doc = json.loads(line)
    assert proc.stdout.rstrip("\n").endswith(line)
    assert isinstance(doc, dict) and set(doc) == {"ok", "device"}, line
    return doc


def test_rehearsal_last_line_is_exactly_the_contract():
    proc = _run("--platform", "cpu", "--tiny")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    doc = _last_line(proc)
    assert doc["ok"] is True
    assert set(doc["device"]) == {"platform", "kind", "count"}
    # Truthful: a CPU rehearsal never reports a chip.
    assert doc["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    # The observations are on EARLIER lines, never in the last one.
    out = proc.stdout
    for needle in ("loss first=", "step_body=rows/per_pair/xla\n",
                   "compile cache:", "native host library:",
                   "phase seconds:", "check serve.zero_post_warmup_compiles"):
        assert needle in out, needle
    # And what the package itself prints stays off stdout.
    assert '"saved"' not in out


def test_failed_check_exits_nonzero_with_ok_false_last():
    proc = _run("--platform", "cpu", "--tiny",
                "--fail-check", "train.loss_fell")
    assert proc.returncode != 0
    doc = _last_line(proc)
    assert doc["ok"] is False
    assert set(doc["device"]) == {"platform", "kind", "count"}
    # The reason is on an earlier line; the run stopped at that check.
    assert "FAILED: train.loss_fell" in proc.stdout
    assert "phase serve" not in proc.stdout


def test_no_chip_means_no_success():
    """Run as the driver runs it (no arguments) where JAX finds no TPU:
    fails at once, no fallback to the CPU it did find."""
    proc = _run(timeout=120)
    assert proc.returncode != 0
    doc = _last_line(proc)
    assert doc["ok"] is False
    assert doc["device"]["platform"] == "cpu"
    assert "phase" not in proc.stdout  # nothing ran
