"""graftlint (glint_word2vec_tpu/analysis): per-checker fixture tests —
a good and a bad snippet each, asserting the bad one is flagged with the
right rule id and the suppressed one is not — plus the whole-repo smoke
test asserting the committed baseline is exactly reproduced, and the
README fault-injection table staying generated-from-registry.

Deliberately jax-free: the analysis pass is the CI lint gate and must
run on a bare interpreter.
"""

import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

from glint_word2vec_tpu.analysis import baseline as bl
from glint_word2vec_tpu.analysis import core

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAULTS_REL = "glint_word2vec_tpu/utils/faults.py"


def run_on(tmp_path, files, rules=None):
    """Write fixture ``files`` (rel -> source) under a fresh root and
    run the pass over them."""
    for rel, src in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
    findings, suppressed = core.run_analysis(
        str(tmp_path), targets=sorted(files), rules=rules
    )
    return findings, suppressed


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ----------------------------------------------------------------------
# sync-point
# ----------------------------------------------------------------------


def test_sync_point_flags_device_cast(tmp_path):
    findings, _ = run_on(tmp_path, {
        "glint_word2vec_tpu/badsync.py": """
            import jax

            def step(loss):
                return float(loss)
        """,
    }, rules=["sync-point"])
    assert [f.rule for f in findings] == ["sync-point"]
    assert findings[0].line == 5
    assert "blessed seam" in findings[0].message


def test_sync_point_good_and_suppressed(tmp_path):
    findings, suppressed = run_on(tmp_path, {
        # Host-rooted casts and jax-free modules are not candidates; a
        # justified inline ignore silences a real candidate.
        "glint_word2vec_tpu/goodsync.py": """
            import os
            import jax

            def config():
                return int(os.environ.get("N", "1")), float("2.5")

            def harvest(loss):
                return float(loss)  # graftlint: ignore[sync-point] test seam
        """,
        "glint_word2vec_tpu/nojax.py": """
            def anything(x):
                return float(x)
        """,
    }, rules=["sync-point"])
    assert findings == []
    assert len(suppressed) == 1


def test_sync_point_flags_dtype_kwarg_asarray(tmp_path):
    """np.asarray(x, dtype=...) — the codebase's dominant sync form —
    must be flagged; int(s, 16)-style string parses must not."""
    findings, _ = run_on(tmp_path, {
        "glint_word2vec_tpu/dtype.py": """
            import jax
            import numpy as np

            def harvest(arr, s):
                a = np.asarray(arr, dtype=np.float32)
                b = np.array(arr, np.float32)
                n = int(s, 16)
                return a, b, n
        """,
    }, rules=["sync-point"])
    assert [f.line for f in findings] == [6, 7]


def test_sync_point_block_until_ready(tmp_path):
    findings, _ = run_on(tmp_path, {
        "glint_word2vec_tpu/bur.py": """
            import jax

            def wait(arr):
                arr.block_until_ready()
        """,
    }, rules=["sync-point"])
    assert [f.rule for f in findings] == ["sync-point"]
    assert "block_until_ready" in findings[0].message


# ----------------------------------------------------------------------
# atomic-persist
# ----------------------------------------------------------------------


def test_atomic_persist_flags_bare_dump(tmp_path):
    findings, _ = run_on(tmp_path, {
        "scripts/bad_persist.py": """
            import json

            def save(path, doc):
                with open(path, "w") as f:
                    json.dump(doc, f)
        """,
    }, rules=["atomic-persist"])
    assert [f.rule for f in findings] == ["atomic-persist"]
    assert "bare write-mode open()" in findings[0].message


def test_atomic_persist_blesses_commit_protocol_and_append(tmp_path):
    findings, _ = run_on(tmp_path, {
        "scripts/good_persist.py": """
            import json
            import os

            def save(path, doc):
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(doc, f)
                os.replace(tmp, path)

            def log(path, line):
                with open(path, "a") as f:
                    f.write(line)
        """,
    }, rules=["atomic-persist"])
    assert findings == []


def test_atomic_persist_flags_np_save(tmp_path):
    findings, _ = run_on(tmp_path, {
        "scripts/badnp.py": """
            import numpy as np

            def save(path, arr):
                np.save(path, arr)
        """,
    }, rules=["atomic-persist"])
    assert [f.rule for f in findings] == ["atomic-persist"]
    assert "np.save" in findings[0].message


# ----------------------------------------------------------------------
# table-tick
# ----------------------------------------------------------------------

_ENGINE_FIXTURE = """
    class Engine:
        def __init__(self):
            self.syn0 = None
            self.syn1 = None

        def _tick_tables(self, reason):
            pass

        def good_mutation(self, t):
            self.syn0 = t
            self._tick_tables("good_mutation")

        def bad_mutation(self, t):
            self.syn1 = t
"""


def test_table_tick_flags_untipped_mutation(tmp_path):
    findings, _ = run_on(tmp_path, {
        "glint_word2vec_tpu/eng.py": _ENGINE_FIXTURE,
    }, rules=["table-tick"])
    assert [f.rule for f in findings] == ["table-tick"]
    assert "bad_mutation" in findings[0].message
    assert "syn1" in findings[0].message


def test_table_tick_ignores_other_classes(tmp_path):
    findings, _ = run_on(tmp_path, {
        "glint_word2vec_tpu/noteng.py": """
            class NotAnEngine:
                def set(self, t):
                    self.syn0 = t
        """,
    }, rules=["table-tick"])
    assert findings == []


# ----------------------------------------------------------------------
# fault-point
# ----------------------------------------------------------------------

_FAULTS_FIXTURE = """
    POINTS = {
        "a.used": "fires in mod",
        "a.unused": "never fired",
    }
"""


def test_fault_point_both_directions(tmp_path):
    findings, _ = run_on(tmp_path, {
        FAULTS_REL: _FAULTS_FIXTURE,
        "glint_word2vec_tpu/mod.py": """
            from glint_word2vec_tpu.utils import faults

            def f():
                faults.fire("a.used")
                faults.fire("a.typo")
        """,
    }, rules=["fault-point"])
    msgs = sorted(f.message for f in findings)
    assert len(findings) == 2
    assert any("a.typo" in m and "undeclared" in m for m in msgs)
    assert any("a.unused" in m and "no faults.fire() call site" in m
               for m in msgs)


def test_fault_point_clean_and_nonliteral(tmp_path):
    findings, _ = run_on(tmp_path, {
        FAULTS_REL: _FAULTS_FIXTURE,
        "glint_word2vec_tpu/mod.py": """
            from glint_word2vec_tpu.utils import faults

            def f(name):
                faults.fire("a.used")
                faults.fire("a.unused")
                faults.fire(name)
        """,
    }, rules=["fault-point"])
    assert len(findings) == 1
    assert "string literal" in findings[0].message


def test_fault_point_registry_matches_runtime():
    """The static extraction and the runtime registry agree."""
    from glint_word2vec_tpu.analysis.checkers.fault_points import (
        declared_points,
    )
    from glint_word2vec_tpu.utils import faults

    cache = core.ModuleCache(REPO, [])
    pts = declared_points(cache)
    assert pts is not None
    assert sorted(pts) == sorted(faults.POINTS)


def test_fire_rejects_undeclared_point_when_armed():
    from glint_word2vec_tpu.utils import faults

    faults.arm("worker.step:delay=0")
    try:
        with pytest.raises(ValueError, match="undeclared injection point"):
            faults.fire("no.such.point")
    finally:
        faults.disarm()


def test_readme_fault_table_matches_registry():
    """The README fault-injection and span tables are generated from
    their registries (faults.POINTS and obs.events.REQUEST_SPANS)."""
    from glint_word2vec_tpu.obs.events import REQUEST_SPANS
    from glint_word2vec_tpu.utils import faults

    readme = open(os.path.join(REPO, "README.md"), encoding="utf-8").read()
    rows = {
        name: doc.replace("\\|", "|")  # markdown-escaped pipes in cells
        for name, doc in re.findall(
            r"^\| `([a-z._]+)` \| (.+?) \|$", readme, re.MULTILINE)
    }
    registry = {**faults.POINTS, **REQUEST_SPANS}
    for name, doc in registry.items():
        assert name in rows, f"README table missing entry {name}"
        assert rows[name] == doc, f"README row for {name} drifted"
    assert set(rows) == set(registry)


# ----------------------------------------------------------------------
# prom-consistency
# ----------------------------------------------------------------------

_RENDERER_REL = "glint_word2vec_tpu/obs/prometheus.py"
_HEARTBEAT_REL = "glint_word2vec_tpu/obs/heartbeat.py"


def test_prom_flags_renderer_only_key_and_bad_names(tmp_path):
    findings, _ = run_on(tmp_path, {
        _RENDERER_REL: """
            def training_to_prometheus(snap):
                p = _Prom()
                p.head("glint_training_x_total", "gauge", "bad suffix")
                p.sample("glint_training_x_total", None, snap.get("x"))
                p.sample("glint_training_orphan", None, snap.get("missing"))
                return p.text()
        """,
        _HEARTBEAT_REL: """
            def snapshot():
                return {"x": 1}
        """,
    }, rules=["prom-consistency"])
    msgs = " | ".join(f.message for f in findings)
    assert "must not end in _total" in msgs          # gauge named _total
    assert "no head" in msgs                         # orphan sample
    assert "'missing'" in msgs and "no producer" in msgs


def test_prom_cross_renderer_type_conflict(tmp_path):
    findings, _ = run_on(tmp_path, {
        _RENDERER_REL: """
            def training_to_prometheus(snap):
                p = _Prom()
                p.head("glint_shared", "gauge", "one type")
                p.sample("glint_shared", None, 1)
                return p.text()

            def serving_to_prometheus(snap):
                p = _Prom()
                p.head("glint_shared", "summary", "another type")
                p.sample("glint_shared", None, 1)
                return p.text()
        """,
    }, rules=["prom-consistency"])
    assert any("disjoint or identical" in f.message for f in findings)


def test_prom_clean_loop_idiom(tmp_path):
    findings, _ = run_on(tmp_path, {
        _RENDERER_REL: """
            def training_to_prometheus(snap):
                p = _Prom()
                gauges = [
                    ("glint_training_epoch", "epoch", "Epoch."),
                    ("glint_training_alpha", "alpha", "LR."),
                ]
                for name, key, help_ in gauges:
                    p.head(name, "gauge", help_)
                    p.sample(name, None, snap.get(key))
                p.head("glint_training_steps_total", "counter", "Steps.")
                p.sample("glint_training_steps_total", None,
                         snap.get("step", 0))
                return p.text()
        """,
        _HEARTBEAT_REL: """
            def snapshot():
                return {"epoch": 0, "alpha": 0.01, "step": 3}
        """,
    }, rules=["prom-consistency"])
    assert findings == []


def test_prom_real_renderers_statically_resolvable():
    """Every metric name the repo's renderers emit resolves statically
    (the gang-counter f-string regression stays fixed)."""
    findings, _ = core.run_analysis(
        REPO, targets=[_RENDERER_REL], rules=["prom-consistency"]
    )
    assert not any("not statically resolvable" in f.message
                   for f in findings)


# ----------------------------------------------------------------------
# lock-discipline
# ----------------------------------------------------------------------

_LOCKED_FIXTURE_BAD = """
    import threading

    class Box:
        def __init__(self):
            self._mu = threading.Lock()
            self.count = 0

        def bump(self):
            with self._mu:
                self.count += 1

        def peek(self):
            return self.count
"""


def test_lock_discipline_flags_unguarded_read(tmp_path):
    findings, _ = run_on(tmp_path, {
        "glint_word2vec_tpu/box.py": _LOCKED_FIXTURE_BAD,
    }, rules=["lock-discipline"])
    assert [f.rule for f in findings] == ["lock-discipline"]
    assert "Box.peek" in findings[0].message
    assert "count" in findings[0].message


def test_lock_discipline_atomic_attrs_and_locked_suffix(tmp_path):
    findings, _ = run_on(tmp_path, {
        "glint_word2vec_tpu/box2.py": """
            import threading

            class Box:
                _ATOMIC_ATTRS = frozenset({"count"})

                def __init__(self):
                    self._mu = threading.Lock()
                    self.count = 0
                    self.state = "idle"

                def bump(self):
                    with self._mu:
                        self.count += 1
                        self._advance_locked()

                def _advance_locked(self):
                    self.state = "running"

                def peek(self):
                    return self.count
        """,
    }, rules=["lock-discipline"])
    assert findings == []


def test_lock_discipline_nested_def_does_not_inherit_lock(tmp_path):
    findings, _ = run_on(tmp_path, {
        "glint_word2vec_tpu/box3.py": """
            import threading

            class Box:
                def __init__(self):
                    self._mu = threading.Lock()
                    self.value = 0

                def start(self):
                    with self._mu:
                        self.value = 1

                        def worker():
                            self.value = 2
                        return worker
        """,
    }, rules=["lock-discipline"])
    # worker() runs after the with block exits: its write is unguarded.
    assert [f.rule for f in findings] == ["lock-discipline"]
    assert findings[0].line == 14


# ----------------------------------------------------------------------
# suppressions + baseline machinery
# ----------------------------------------------------------------------


def test_suppression_requires_reason_and_known_rule(tmp_path):
    findings, suppressed = run_on(tmp_path, {
        "scripts/sup.py": """
            import json

            def a(path, doc):
                # graftlint: ignore[atomic-persist]
                with open(path, "w") as f:
                    json.dump(doc, f)

            def b(path, doc):
                # graftlint: ignore[no-such-rule] because reasons
                with open(path, "w") as f:
                    json.dump(doc, f)
        """,
    }, rules=["atomic-persist"])
    rules = [f.rule for f in findings]
    # Reasonless suppression does not suppress, and both malformed
    # comments are themselves findings.
    assert rules.count("atomic-persist") == 2
    assert rules.count(core.SUPPRESSION_RULE) == 2
    assert suppressed == []


def test_baseline_matching_ignores_line_drift(tmp_path):
    f = core.Finding(rule="r", path="p.py", line=10, message="m",
                     context="x = 1")
    entry = {"rule": "r", "path": "p.py", "line": 99, "context": "x = 1",
             "note": "fine"}
    new, stale, noteless = bl.compare_to_baseline([f], [entry])
    assert new == [] and stale == [] and noteless == []
    # Same identity but no note -> noteless; changed context -> new+stale.
    entry_nonote = dict(entry, note=" ")
    _, _, noteless = bl.compare_to_baseline([f], [entry_nonote])
    assert noteless == [entry_nonote]
    entry_moved = dict(entry, context="x = 2")
    new, stale, _ = bl.compare_to_baseline([f], [entry_moved])
    assert new == [f] and stale == [entry_moved]


def test_meta_rules_cannot_be_baselined(tmp_path):
    """graftlint-suppression / graftlint-parse findings never launder
    through the baseline: write_baseline drops them, and a hand-edited
    entry reads as stale."""
    f = core.Finding(rule=core.SUPPRESSION_RULE, path="p.py", line=3,
                     message="m", context="# graftlint: ignore[x]")
    path = tmp_path / "b.json"
    entries = bl.write_baseline(str(path), [f])
    assert entries == []
    hand = {"rule": core.SUPPRESSION_RULE, "path": "p.py", "line": 3,
            "context": "# graftlint: ignore[x]", "note": "laundered"}
    new, stale, _ = bl.compare_to_baseline([f], [hand])
    assert new == [f]
    assert stale == [hand]


def test_cli_partial_paths_do_not_stale_rest_of_baseline():
    """--check-baseline over an explicit file subset judges only that
    subset: baseline entries for other files are not reported stale."""
    out = subprocess.run(
        [sys.executable, "-m", "glint_word2vec_tpu.analysis",
         "glint_word2vec_tpu/obs/heartbeat.py", "--check-baseline"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 new, 0 stale, 0 noteless" in out.stdout


def test_cli_partial_update_preserves_out_of_scope_entries(tmp_path):
    """--update-baseline scoped to one file must not destroy the other
    files' entries (or their notes)."""
    import shutil
    entries = bl.load_baseline(os.path.join(REPO, bl.BASELINE_REL))
    scratch = tmp_path / "baseline.json"
    shutil.copyfile(os.path.join(REPO, bl.BASELINE_REL), scratch)
    out = subprocess.run(
        [sys.executable, "-m", "glint_word2vec_tpu.analysis",
         "glint_word2vec_tpu/obs/heartbeat.py",
         "--baseline", str(scratch), "--update-baseline"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    after = bl.load_baseline(str(scratch))
    assert len(after) == len(entries)
    assert all(e.get("note", "").strip() for e in after)


def test_cli_normalizes_dot_slash_paths():
    """'./'-prefixed paths must not silently skip path-scoped checks."""
    plain = subprocess.run(
        [sys.executable, "-m", "glint_word2vec_tpu.analysis",
         "glint_word2vec_tpu/obs/heartbeat.py", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    dotted = subprocess.run(
        [sys.executable, "-m", "glint_word2vec_tpu.analysis",
         "./glint_word2vec_tpu/obs/heartbeat.py", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    a = json.loads(plain.stdout)["findings"]
    b = json.loads(dotted.stdout)["findings"]
    assert a and a == b


def test_prom_cross_renderer_help_drift(tmp_path):
    findings, _ = run_on(tmp_path, {
        _RENDERER_REL: """
            def training_to_prometheus(snap):
                p = _Prom()
                p.head("glint_shared", "gauge", "one help")
                p.sample("glint_shared", None, 1)
                return p.text()

            def serving_to_prometheus(snap):
                p = _Prom()
                p.head("glint_shared", "gauge", "another help")
                p.sample("glint_shared", None, 1)
                return p.text()
        """,
    }, rules=["prom-consistency"])
    assert any("HELP text" in f.message for f in findings)


def test_parse_error_is_a_finding(tmp_path):
    findings, _ = run_on(tmp_path, {
        "scripts/broken.py": "def f(:\n",
    }, rules=[])
    assert [f.rule for f in findings] == [core.PARSE_RULE]


# ----------------------------------------------------------------------
# scope-registry
# ----------------------------------------------------------------------

_SCOPES_FIXTURE = """
    DEVICE_SCOPES = {
        "glint.used": "a phase",
        "inner": "an inner name",
        "glint.unused": "registered, opened through a variable",
    }
"""


def test_scope_registry_flags_an_unregistered_literal(tmp_path):
    findings, _ = run_on(tmp_path, {
        "glint_word2vec_tpu/obs/events.py": _SCOPES_FIXTURE,
        "glint_word2vec_tpu/mod.py": """
            import jax

            def f(x):
                with jax.named_scope("glint.used"), jax.named_scope("inner"):
                    with jax.named_scope("glint.typo"):
                        return x
        """,
    }, rules=["scope-registry"])
    msgs = [f.message for f in findings]
    # the typo alone: a registered scope that nothing opens is no finding
    assert len(findings) == 1, msgs
    assert "glint.typo" in msgs[0] and "not a DEVICE_SCOPES" in msgs[0]


def test_scope_registry_clean_and_nonliteral(tmp_path):
    findings, _ = run_on(tmp_path, {
        "glint_word2vec_tpu/obs/events.py": _SCOPES_FIXTURE,
        "glint_word2vec_tpu/mod.py": """
            import jax

            def f(x, wide):
                # a scope chosen among registered literals at its own site
                scope = "glint.unused" if wide else "glint.used"
                with jax.named_scope(scope), jax.named_scope("inner"):
                    return x
        """,
    }, rules=["scope-registry"])
    assert findings == []


def test_scope_registry_holds_the_engines_scopes():
    """Every scope a per-layer reader splits the device trace by is a
    registered literal of the engine, the position table's among them."""
    from glint_word2vec_tpu.obs.events import DEVICE_SCOPES

    findings, _ = core.run_analysis(REPO, rules=["scope-registry"])
    assert findings == [], [f.format() for f in findings]
    for scope in ("glint.compose", "group", "bag", "posgrad", "glint.batch",
                  "glint.scatter", "syn0", "syn1"):
        assert DEVICE_SCOPES[scope], scope


# ----------------------------------------------------------------------
# whole-repo smoke: the committed baseline is exactly reproduced
# ----------------------------------------------------------------------


def test_repo_reproduces_committed_baseline():
    findings, _ = core.run_analysis(REPO)
    entries = bl.load_baseline(os.path.join(REPO, bl.BASELINE_REL))
    assert entries, "committed baseline missing or empty"
    new, stale, noteless = bl.compare_to_baseline(findings, entries)
    assert new == [], f"new findings not in baseline: " \
                      f"{[f.format() for f in new[:5]]}"
    assert stale == [], f"stale baseline entries: {stale[:5]}"
    assert noteless == [], f"baseline entries missing notes: " \
                           f"{noteless[:5]}"


def test_baseline_notes_all_nonempty():
    entries = bl.load_baseline(os.path.join(REPO, bl.BASELINE_REL))
    assert all(e.get("note", "").strip() for e in entries)


def test_cli_check_baseline_jax_free():
    """The CI gate command: exits 0 on the repo and never imports jax
    (asserted via -X importtime would be flaky; instead poison the
    import by pointing jax at a module that raises)."""
    env = dict(os.environ)
    poison = os.path.join(REPO, ".graftlint_poison")
    os.makedirs(poison, exist_ok=True)
    with open(os.path.join(poison, "jax.py"), "w") as f:
        f.write("raise ImportError('graftlint must not import jax')\n")
    try:
        env["PYTHONPATH"] = poison + os.pathsep + env.get("PYTHONPATH", "")
        out = subprocess.run(
            [sys.executable, "-m", "glint_word2vec_tpu.analysis",
             "--check-baseline"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        assert "0 new, 0 stale, 0 noteless" in out.stdout
    finally:
        os.remove(os.path.join(poison, "jax.py"))
        os.rmdir(poison)


def test_cli_list_rules():
    out = subprocess.run(
        [sys.executable, "-m", "glint_word2vec_tpu.analysis",
         "--list-rules"],
        cwd=REPO, capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0
    for rule in ("sync-point", "atomic-persist", "table-tick",
                 "fault-point", "prom-consistency", "lock-discipline",
                 "span-registry", "scope-registry"):
        assert rule in out.stdout


def test_cli_unknown_rule_is_usage_error():
    out = subprocess.run(
        [sys.executable, "-m", "glint_word2vec_tpu.analysis",
         "--rules", "no-such-rule"],
        cwd=REPO, capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 2
    assert "unknown rule" in out.stderr
