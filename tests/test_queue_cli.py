"""scripts/tpu_queue.py CLI + the job-side heartbeat/status contract.

The selfcheck test is the one place the WHOLE stack runs with real
subprocesses (spawn, SIGTERM, heartbeat files, journal replay) — on CPU,
with healthy probes injected, in the smoke tier. A hard SIGALRM bounds
every test: nothing here may ever block on a real `jax.devices()`.
"""

import importlib.util
import json
import os
import signal
import subprocess
import sys

import pytest

from real_time_helmet_detection_tpu.runtime import (EXIT_TRANSIENT,
                                                    FileHeartbeat,
                                                    classify_error_text,
                                                    classify_exception,
                                                    heartbeat_age_s,
                                                    maybe_job_heartbeat,
                                                    read_heartbeat,
                                                    run_as_job,
                                                    write_job_status)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _hard_timeout():
    def _fire(signum, frame):
        raise RuntimeError("test exceeded the hard timeout — something "
                           "blocked")

    old = signal.signal(signal.SIGALRM, _fire)
    signal.alarm(300)  # selfcheck spawns ~5 interpreters on a slow box
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


def _load_cli():
    spec = importlib.util.spec_from_file_location(
        "tpu_queue", os.path.join(REPO, "scripts", "tpu_queue.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------
# heartbeat / status primitives
# --------------------------------------------------------------------------

def test_file_heartbeat_roundtrip(tmp_path):
    path = str(tmp_path / "hb" / "job.json")
    hb = FileHeartbeat(path)
    assert heartbeat_age_s(path) is None  # no beat yet
    hb.beat("step 3")
    rec = read_heartbeat(path)
    assert rec["label"] == "step 3" and rec["pid"] == os.getpid()
    assert heartbeat_age_s(path) < 60.0


def test_maybe_job_heartbeat_is_noop_without_env():
    hb = maybe_job_heartbeat(env={})
    hb.beat("anything")  # must not write or raise
    assert hb.path is None


def test_maybe_job_heartbeat_binds_env_path(tmp_path):
    path = str(tmp_path / "hb.json")
    hb = maybe_job_heartbeat(env={"TPU_QUEUE_HEARTBEAT": path})
    hb.beat("bound")
    assert read_heartbeat(path)["label"] == "bound"


def test_write_job_status_roundtrip(tmp_path):
    path = str(tmp_path / "status.json")
    write_job_status(False, error="UNAVAILABLE: socket",
                     error_class="transient",
                     env={"TPU_QUEUE_STATUS": path})
    rec = read_heartbeat(path)
    assert rec == {"ok": False, "error": "UNAVAILABLE: socket",
                   "error_class": "transient", "t": rec["t"],
                   "pid": os.getpid()}
    write_job_status(True, env={})  # no env: must be a silent no-op


def test_classifiers_shared_with_train():
    # train.py re-exports the SAME objects — one classifier, no drift
    from real_time_helmet_detection_tpu import train as train_mod
    from real_time_helmet_detection_tpu.runtime import errors
    assert train_mod.is_transient_backend_error \
        is errors.is_transient_backend_error
    assert classify_exception(RuntimeError("UNAVAILABLE: x")) == "transient"
    assert classify_exception(ValueError("UNAVAILABLE: x")) == "permanent"
    assert classify_error_text("... UNAVAILABLE: TPU backend ...") \
        == "transient"
    # text-only INTERNAL must NOT classify (no type evidence)
    assert classify_error_text("INTERNAL: assertion") == "permanent"


def test_run_as_job_maps_outcomes(tmp_path, monkeypatch):
    status = str(tmp_path / "s.json")
    monkeypatch.setenv("TPU_QUEUE_STATUS", status)

    run_as_job(lambda: None)
    assert read_heartbeat(status)["ok"] is True

    with pytest.raises(SystemExit) as ei:
        run_as_job(lambda: (_ for _ in ()).throw(
            RuntimeError("UNAVAILABLE: socket closed")))
    assert ei.value.code == EXIT_TRANSIENT
    assert read_heartbeat(status)["error_class"] == "transient"

    with pytest.raises(SystemExit) as ei:
        run_as_job(lambda: (_ for _ in ()).throw(ValueError("bad shape")))
    assert ei.value.code == 1
    assert read_heartbeat(status)["error_class"] == "permanent"

    # a script's own string refusal (bad flag value) is permanent
    with pytest.raises(SystemExit) as ei:
        run_as_job(lambda: (_ for _ in ()).throw(
            SystemExit("--only: unknown mode(s) ['x']")))
    assert ei.value.code == 1
    assert read_heartbeat(status)["error_class"] == "permanent"


# --------------------------------------------------------------------------
# CLI surface
# --------------------------------------------------------------------------

def test_cli_enqueue_and_status(tmp_path, capsys):
    cli = _load_cli()
    qdir = str(tmp_path / "q")
    rc = cli.main(["--queue-dir", qdir, "enqueue", "bench",
                   "--artifacts", "artifacts/r08/BENCH_*_local.json",
                   "--heartbeat-timeout", "1200",
                   "--", "python", "bench.py"])
    assert rc == 0
    capsys.readouterr()  # drop the enqueue confirmation
    rc = cli.main(["--queue-dir", qdir, "status"])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["jobs"] == [{
        "job": "bench", "state": "queued", "attempt": 1,
        "not_before": None, "argv": "python bench.py"}]


def test_cli_run_runs_a_queued_job_on_a_bare_machine(tmp_path, capsys):
    """`tpu_queue.py run` with the real default seams: nothing but the
    queue and a python — the job runs (it is the first and only process
    the supervisor starts), it is not parked behind a health triage."""
    cli = _load_cli()
    qdir = str(tmp_path / "queue")
    marker = str(tmp_path / "ran")
    assert cli.main(["--queue-dir", qdir, "enqueue", "touch",
                     "--heartbeat-timeout", "60", "--", sys.executable,
                     "-c", "open(%r, 'w').write('1')" % marker]) == 0
    capsys.readouterr()
    assert cli.main(["--queue-dir", qdir, "run"]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary == {"jobs": {"touch": {"state": "done", "attempt": 1}}}
    assert os.path.exists(marker)


def test_cli_enqueue_rejects_duplicate_and_empty(tmp_path):
    cli = _load_cli()
    qdir = str(tmp_path / "q")
    cli.main(["--queue-dir", qdir, "enqueue", "j", "--", "true"])
    with pytest.raises(ValueError):
        cli.main(["--queue-dir", qdir, "enqueue", "j", "--", "true"])
    with pytest.raises(SystemExit):
        cli.main(["--queue-dir", qdir, "enqueue", "empty"])


def test_cli_default_queue_dir_is_round_scoped(monkeypatch):
    cli = _load_cli()
    monkeypatch.setenv("GRAFT_ROUND", "r99")
    assert cli.default_queue_dir().endswith(
        os.path.join("artifacts", "r99", "queue"))


def test_cli_status_summary_cross_round_census(tmp_path, capsys,
                                               monkeypatch):
    """`status --summary` (ISSUE 16): read-only census across every
    round's journal — last state per job wins, salvage waypoints are
    counted separately, torn tails are dropped, and the journals are
    NEVER rewritten (no Spool tail repair)."""
    cli = _load_cli()
    monkeypatch.setattr(cli, "REPO", str(tmp_path))

    def journal(rnd, lines):
        qdir = tmp_path / "artifacts" / rnd / "queue"
        qdir.mkdir(parents=True)
        path = qdir / "jobs.jsonl"
        path.write_bytes(b"".join(lines))
        return path

    j = json.dumps
    p08 = journal("r08", [
        (j({"kind": "spec", "job": "bench"}) + "\n").encode(),
        (j({"kind": "spec", "job": "sweep"}) + "\n").encode(),
        (j({"kind": "state", "job": "sweep", "state": "salvaged",
            "t": 1.0, "attempt": 1}) + "\n").encode(),
        (j({"kind": "state", "job": "sweep", "state": "failed",
            "t": 2.0, "attempt": 1}) + "\n").encode(),
        b'{"kind": "state", "job": "bench", "sta',  # torn tail
    ])
    p09 = journal("r09", [
        (j({"kind": "spec", "job": "curve"}) + "\n").encode(),
        (j({"kind": "state", "job": "curve", "state": "done",
            "t": 3.0, "attempt": 1}) + "\n").encode(),
        (j({"kind": "note", "msg": "ignored"}) + "\n").encode(),
    ])
    before = (p08.read_bytes(), p09.read_bytes())

    assert cli.main(["status", "--summary"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["summary"] is True
    r08 = payload["rounds"]["r08"]
    assert r08["jobs"] == 2
    assert r08["by_state"] == {"failed": 1, "queued": 1}
    assert r08["salvaged"] == 1
    assert r08["dropped_lines"] == 1
    assert payload["rounds"]["r09"] == {
        "jobs": 1, "by_state": {"done": 1}, "salvaged": 0,
        "dropped_lines": 0}
    # the census must be read-only: journal bytes are untouched
    assert (p08.read_bytes(), p09.read_bytes()) == before


# --------------------------------------------------------------------------
# the end-to-end proof: real subprocesses through the whole state machine
# --------------------------------------------------------------------------

def test_selfcheck_end_to_end():
    """`tpu_queue.py --selfcheck` in a child process, exactly as CI and an
    operator would run it: ok job -> done; transient job -> requeued then
    done; hanging job -> killed, salvaged with its flushed partial,
    requeued, budget exhausted -> failed; journal replay intact."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "tpu_queue.py"),
         "--selfcheck"],
        capture_output=True, text=True, timeout=280, cwd=REPO)
    assert r.returncode == 0, "selfcheck failed:\n%s\n%s" % (r.stdout,
                                                             r.stderr)
    assert "all checks passed" in r.stdout
