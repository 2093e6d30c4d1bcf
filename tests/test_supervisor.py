"""Elastic-supervisor tests (ISSUE 7, parallel/supervisor.py) with
jax-free stub workers: crash detection via waitpid, hang detection via
stale status-file heartbeats, whole-gang teardown, generation-gated
relaunch env, backoff/budget, and the report the chaos drill records."""

import json
import os
import sys
import time

from glint_word2vec_tpu.parallel.supervisor import Supervisor

# Stub worker: writes generation-stamped heartbeats (with the progress
# fields the gang aggregator sums) plus a per-rank event-log JSONL (the
# flight recorder's collection source), then follows the behavior its
# env/generation selects. argv: <status_file> <behavior> [<rank> [<n>]]
_STUB = r"""
import json, os, sys, time

status_file, behavior = sys.argv[1], sys.argv[2]
rank = int(sys.argv[3]) if len(sys.argv) > 3 else 0
n = int(sys.argv[4]) if len(sys.argv) > 4 else 1
gen = int(os.environ.get("GLINT_SUPERVISOR_GEN", "-1"))

events_file = os.path.join(
    os.path.dirname(status_file), "events-%d.jsonl" % rank
)
with open(events_file, "w") as f:
    f.write(json.dumps({"name": "clock_anchor", "ph": "M", "ts": 0,
                        "args": {"wall_t0": time.time()}}) + "\n")
    f.write(json.dumps({"name": "run_start", "ph": "i", "ts": 1.0,
                        "args": {"generation": gen}}) + "\n")


def beat(state="running"):
    tmp = status_file + ".tmp"
    with open(tmp, "w") as f:
        json.dump({
            "state": state, "supervisor_generation": gen,
            "step": 10 * (rank + 1), "words_done": 100 * (rank + 1),
            "words_per_sec_rolling": 5.0 * (rank + 1),
            "step_time": 1.0,
            "events": {"recorded": 2, "dropped": 0},
        }, f)
    os.replace(tmp, status_file)


beat()
if behavior == "ok":
    for _ in range(3):
        time.sleep(0.05)
        beat()
    beat("done")
    sys.exit(0)
if behavior == "crash-env":
    # Crashes only when the first-launch-only env var is present.
    if os.environ.get("GLINT_TEST_CRASH") == "1":
        # not before every peer has a heartbeat for the flight recorder
        # to collect: a loaded host starts them tens of ms apart
        peers = [os.path.join(os.path.dirname(status_file),
                              "status-%d.json" % r) for r in range(n)]
        deadline = time.time() + 30
        while time.time() < deadline and not all(map(os.path.exists, peers)):
            time.sleep(0.01)
        sys.exit(3)
    time.sleep(0.1)
    beat("done")
    sys.exit(0)
if behavior == "crash-always":
    time.sleep(0.05)
    sys.exit(3)
if behavior == "hang-gen0":
    if gen == 0:
        time.sleep(120)  # heartbeat never refreshes -> stale
    time.sleep(0.1)
    beat("done")
    sys.exit(0)
if behavior == "slow-ok":
    # Heartbeats long enough for the test to scrape the merged gang
    # endpoint mid-run.
    for _ in range(60):
        time.sleep(0.05)
        beat()
    beat("done")
    sys.exit(0)
if behavior == "wedge-on-peer":
    # Rank 0 crashes in gen 0; rank 1 "wedges" (keeps heartbeating but
    # never exits) — only the gang teardown can end it.
    if gen == 0 and rank == 0:
        sys.exit(3)
    if gen == 0:
        for _ in range(2400):
            time.sleep(0.05)
            beat()
        sys.exit(0)
    time.sleep(0.1)
    beat("done")
    sys.exit(0)
sys.exit(99)
"""


def _sup(tmp_path, behavior, workers=1, **kw):
    stub = tmp_path / "stub.py"
    stub.write_text(_STUB)

    def build_argv(rank, n, port, status_file, generation):
        return [
            sys.executable, str(stub), status_file, behavior, str(rank),
            str(n),
        ]

    defaults = dict(
        status_dir=str(tmp_path / "sup"),
        poll_interval=0.05,
        max_restarts=2,
        backoff_base_seconds=0.05,
        backoff_cap_seconds=0.2,
        kill_grace_seconds=1.0,
        heartbeat_stale_seconds=1.0,
        startup_grace_seconds=10.0,
    )
    defaults.update(kw)
    return Supervisor(build_argv, workers, **defaults)


def test_clean_completion_no_restarts(tmp_path):
    report = _sup(tmp_path, "ok", workers=2).run()
    assert report.completed
    assert report.restarts == 0
    assert report.generations == 1


def test_crash_detected_restarted_once_env_not_rearmed(tmp_path):
    # The first-launch-only env (the chaos drill's GLINT_FAULTS seam)
    # crashes generation 0; generation 1 runs WITHOUT it and completes.
    report = _sup(
        tmp_path, "crash-env",
        rank_env_first_launch={0: {"GLINT_TEST_CRASH": "1"}},
    ).run()
    assert report.completed
    assert report.restarts == 1
    rec = report.restart_records[0]
    assert "exited with code 3" in rec.reason
    assert rec.detect_to_relaunch_seconds >= rec.backoff_seconds
    d = report.to_dict()
    assert d["restart_records"][0]["reason"] == rec.reason


def test_gang_teardown_kills_wedged_survivor(tmp_path):
    # Rank 0 dies; rank 1 heartbeats forever (the stuck-collective
    # analogue). The supervisor must kill it, relaunch BOTH, complete.
    t0 = time.time()
    report = _sup(tmp_path, "wedge-on-peer", workers=2).run()
    assert report.completed
    assert report.restarts == 1
    assert time.time() - t0 < 60  # the wedged worker did not pin us


def test_restart_budget_exhausted_gives_up(tmp_path):
    report = _sup(tmp_path, "crash-always", max_restarts=2).run()
    assert not report.completed
    assert report.restarts == 2
    assert "budget" in report.gave_up_reason


def test_hang_detected_via_stale_heartbeat(tmp_path):
    report = _sup(
        tmp_path, "hang-gen0", heartbeat_stale_seconds=0.5,
    ).run()
    assert report.completed
    assert report.restarts == 1
    assert "stale" in report.restart_records[0].reason


def test_stale_pre_restart_status_file_not_trusted(tmp_path):
    # A status file stamped with an older generation must read as
    # "no heartbeat yet", not as a live (or stale) current one.
    sup = _sup(tmp_path, "ok")
    os.makedirs(sup.status_dir, exist_ok=True)
    with open(sup._status_file(0), "w") as f:
        json.dump({"state": "running", "supervisor_generation": 0}, f)
    assert sup._read_status(0, generation=1) is None
    assert sup._read_status(0, generation=0) is not None


def test_cli_supervise_validates_arguments(capsys):
    # jax-free: the supervise branch returns before any device setup.
    from glint_word2vec_tpu import cli

    assert cli.main(["supervise", "--workers", "1"]) == 1
    assert "expects the train command" in capsys.readouterr().err
    assert cli.main(["supervise", "train", "--corpus", "x"]) == 1
    assert "--checkpoint-dir" in capsys.readouterr().err


def test_cli_argv_value_forms():
    from glint_word2vec_tpu.cli import _argv_value

    argv = ["--corpus", "c.txt", "--checkpoint-dir", "a",
            "--checkpoint-dir=b"]
    assert _argv_value(argv, "--checkpoint-dir") == "b"  # last wins
    assert _argv_value(argv, "--corpus") == "c.txt"
    assert _argv_value(argv, "--output") is None


def test_crash_collects_postmortem_bundles_referenced_from_report(
    tmp_path,
):
    # ISSUE 8 flight recorder: a crashed generation leaves
    # postmortem-<gen>-<rank>/ bundles holding each rank's last
    # heartbeat + event ring, referenced from the restart record AND
    # the report-level aggregate list.
    report = _sup(
        tmp_path, "crash-env", workers=2,
        rank_env_first_launch={0: {"GLINT_TEST_CRASH": "1"}},
    ).run()
    assert report.completed and report.restarts == 1
    rec = report.restart_records[0]
    assert rec.postmortem, "restart record references no bundles"
    assert set(rec.postmortem) <= set(report.postmortem_bundles)
    d = report.to_dict()
    assert d["restart_records"][0]["postmortem"] == rec.postmortem
    assert d["postmortem_bundles"] == report.postmortem_bundles
    sup_dir = tmp_path / "sup"
    for rank in (0, 1):
        bundle = sup_dir / f"postmortem-0-{rank}"
        assert str(bundle) in rec.postmortem
        files = set(os.listdir(bundle))
        assert {"heartbeat.json", "events.jsonl", "meta.json",
                "log_tail.txt"} <= files
        hb = json.load(open(bundle / "heartbeat.json"))
        assert hb["supervisor_generation"] == 0
        events = [json.loads(line)
                  for line in open(bundle / "events.jsonl")]
        assert any(e["name"] == "run_start" for e in events)
        meta = json.load(open(bundle / "meta.json"))
        assert meta["generation"] == 0 and meta["rank"] == rank
        assert "exited with code 3" in meta["reason"]
    # Generation 1 completed cleanly: no gen-1 bundles.
    assert not [e for e in os.listdir(sup_dir)
                if e.startswith("postmortem-1-")]


def test_give_up_teardown_also_collects_postmortem(tmp_path):
    report = _sup(tmp_path, "crash-always", max_restarts=1).run()
    assert not report.completed
    # Both failed generations (0 and 1) collected bundles.
    gens = {os.path.basename(b).split("-")[1]
            for b in report.postmortem_bundles}
    assert gens == {"0", "1"}


def test_merged_gang_metrics_endpoint_live_during_run(tmp_path):
    # The supervisor's merged /metrics: counters equal the sum of the
    # per-rank heartbeat values (the stub's rank-keyed numbers make a
    # wrong merge visible), rank_skew is present, the view carries the
    # generation stamp, and the Prometheus rendering lints clean.
    import threading
    import urllib.request

    from glint_word2vec_tpu.obs.prometheus import lint_prometheus_text

    sup = _sup(tmp_path, "slow-ok", workers=2, metrics_port=0)
    assert sup.metrics_port  # bound before run() so operators can curl
    base = f"http://127.0.0.1:{sup.metrics_port}"
    result = {}
    t = threading.Thread(target=lambda: result.update(r=sup.run()))
    t.start()
    try:
        merged = None
        for _ in range(200):
            try:
                with urllib.request.urlopen(
                    base + "/metrics", timeout=2
                ) as r:
                    m = json.loads(r.read())
                if m["ranks_reporting"] == 2:
                    merged = m
                    break
            except OSError:
                pass
            time.sleep(0.05)
        assert merged, "merged endpoint never saw both ranks"
        assert merged["generation"] == 0
        assert merged["num_workers"] == 2
        # Stub ranks report step 10*(rank+1), words 100*(rank+1):
        # summed counters must equal the per-rank sums exactly.
        assert merged["counters"]["steps_total"] == 30
        assert merged["counters"]["words_done_total"] == 300
        assert merged["counters"]["events_recorded_total"] == 4
        assert merged["words_per_sec_total"] == 15.0
        assert "rank_skew" in merged and merged["rank_skew"] is not None
        assert set(merged["per_rank"]) == {"0", "1"}
        with urllib.request.urlopen(
            base + "/metrics?format=prometheus", timeout=2
        ) as r:
            text = r.read().decode()
        lint_prometheus_text(text)
        assert "glint_gang_rank_skew" in text
        with urllib.request.urlopen(base + "/healthz", timeout=2) as r:
            h = json.loads(r.read())
        assert h["status"] == "ok" and h["ranks_reporting"] == 2
    finally:
        t.join(timeout=60)
    assert result["r"].completed
    assert result["r"].metrics_port == sup.metrics_port


def test_worker_launch_contract_includes_flight_recorder_paths(
    tmp_path,
):
    # cli_train_build_argv appends the per-rank status/event-log/
    # steptime paths the supervisor's flight recorder collects.
    from glint_word2vec_tpu.parallel.supervisor import (
        cli_train_build_argv,
    )

    argv = cli_train_build_argv(["--corpus", "c.txt"])(
        1, 2, 12345, str(tmp_path / "status-1.json"), 0
    )
    joined = " ".join(argv)
    assert "--status-file" in joined
    assert str(tmp_path / "events-1.jsonl") in argv
    assert str(tmp_path / "steptime-1.json") in argv
    assert "--process-id 1" in joined


def test_gave_up_on_unverifiable_checkpoint(tmp_path):
    # A crash with a train_state.json pointing only at corrupt
    # snapshots must GIVE UP (never silently retrain from scratch).
    ck = tmp_path / "ck"
    os.makedirs(ck / "ckpt-1")
    with open(ck / "train_state.json", "w") as f:
        json.dump({"epochs_completed": 1, "step": 1, "words_done": 1,
                   "ckpt": "ckpt-1"}, f)
    report = _sup(
        tmp_path, "crash-always", checkpoint_dir=str(ck), max_restarts=3,
    ).run()
    assert not report.completed
    assert report.restarts == 0
    assert "no verifiable checkpoint" in report.gave_up_reason
