"""TPU job queue CLI: a persistent, crash-restartable serial job runner.

The reference has no job supervision of any kind (SURVEY.md §5; its only
recovery is a manual restart, ref train.py:190-199).

Front-end to the crash-restartable supervisor in
`real_time_helmet_detection_tpu/runtime/` (spool + heartbeat
kill-salvage + backoff requeue; see that package and docs/ARCHITECTURE.md
"Failure domains & supervision" for the design). The spool lives under
`artifacts/<round>/queue/` ($GRAFT_ROUND via bench.graft_round), so a
round's queue — including per-attempt logs, heartbeats, status files and
the full transition journal — is committed evidence like every other
artifact.

Usage:

    # queue the round's jobs (does NOT touch the chip):
    python scripts/tpu_queue.py enqueue bench \
        --artifacts 'artifacts/r08/BENCH_*_local.json' \
        --heartbeat-timeout 1800 -- python bench.py
    python scripts/tpu_queue.py enqueue sweep-step-grid \
        --artifacts 'artifacts/r08/sweep.json' \
        -- python scripts/tpu_sweep.py --only step_grid

    # drain it (ONE supervisor owns the chip; jobs run strictly serially):
    python scripts/tpu_queue.py run

    # inspect:
    python scripts/tpu_queue.py status

    # CI/self-diagnosis: exercise the whole spool state machine on CPU
    # with synthetic jobs (ok / transient-retry / hang-kill-salvage):
    python scripts/tpu_queue.py --selfcheck

The supervisor process itself never initializes a JAX backend, and
starts nothing that does before the job: the job is the only process on
the chip (one process per chip).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import graft_round  # noqa: E402 — one shared round default
from real_time_helmet_detection_tpu.runtime import (  # noqa: E402
    JobSpec, Spool, Supervisor)


def default_queue_dir() -> str:
    return os.path.join(REPO, "artifacts", graft_round(), "queue")


def cmd_enqueue(args) -> int:
    if not args.command:
        raise SystemExit("enqueue: no command given (use `-- cmd ...`)")
    spool = Spool(args.queue_dir)
    spec = JobSpec(
        job=args.name, argv=list(args.command),
        artifacts=args.artifacts or [],
        heartbeat_timeout_s=args.heartbeat_timeout,
        max_attempts=args.max_attempts,
        backoff_base_s=args.backoff_base,
        backoff_cap_s=args.backoff_cap,
        cwd=REPO)
    spool.enqueue(spec)
    spool.close()
    print("enqueued %s: %s" % (args.name, " ".join(args.command)))
    return 0


def cmd_run(args) -> int:
    spool = Spool(args.queue_dir)
    summary = Supervisor(spool).run()
    spool.close()
    print(json.dumps(summary))
    states = {j["state"] for j in summary["jobs"].values()}
    return 1 if "failed" in states else 0


def cmd_status(args) -> int:
    if getattr(args, "summary", False):
        return cmd_status_summary(args)
    spool = Spool(args.queue_dir)
    rows = [{"job": js.spec.job, "state": js.state, "attempt": js.attempt,
             "not_before": js.not_before or None,
             "argv": " ".join(js.spec.argv)}
            for js in spool.ordered()]
    spool.close()
    print(json.dumps({"queue_dir": spool.root, "jobs": rows}, indent=1))
    return 0


def _journal_census(path: str):
    """Read-only tolerant census of one queue journal: last state per
    job + salvage evidence. Never opens a Spool (Spool's constructor
    repairs torn tails IN PLACE — a census across other rounds' committed
    queues must not rewrite them); torn/junk lines are dropped, exactly
    like obs_report's journal reader."""
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError:
        return None
    jobs: dict = {}
    salvaged = set()
    dropped = 0
    for ln in data.splitlines():
        if not ln.strip():
            continue
        try:
            rec = json.loads(ln)
        except json.JSONDecodeError:
            dropped += 1
            continue
        kind, job = rec.get("kind"), rec.get("job")
        if kind == "spec" and job:
            jobs.setdefault(job, "queued")
        elif kind == "state" and job in jobs and rec.get("state"):
            jobs[job] = rec["state"]
            if rec["state"] == "salvaged":
                # salvage is a waypoint (salvaged -> failed/queued), so
                # count it separately from the terminal state
                salvaged.add(job)
    by_state: dict = {}
    for st in jobs.values():
        by_state[st] = by_state.get(st, 0) + 1
    return {"jobs": len(jobs), "by_state": dict(sorted(by_state.items())),
            "salvaged": len(salvaged), "dropped_lines": dropped}


def cmd_status_summary(args) -> int:
    """`status --summary` (ISSUE 16): one-screen census across EVERY
    round's queue (artifacts/*/queue/jobs.jsonl) — queued/running/failed/
    done/salvaged counts per round, so a backlog triage (r08-r15 style)
    reads one table instead of N per-round status dumps."""
    import glob as _glob
    rounds = {}
    for path in sorted(_glob.glob(os.path.join(
            REPO, "artifacts", "*", "queue", "jobs.jsonl"))):
        rnd = os.path.basename(os.path.dirname(os.path.dirname(path)))
        census = _journal_census(path)
        if census is not None:
            rounds[rnd] = census
    if rounds:
        states = sorted({s for c in rounds.values() for s in c["by_state"]})
        hdr = ["round", "jobs"] + states + ["salvaged"]
        print("  ".join("%-9s" % h for h in hdr), file=sys.stderr)
        for rnd, c in rounds.items():
            row = [rnd, str(c["jobs"])]
            row += [str(c["by_state"].get(s, 0)) for s in states]
            row += [str(c["salvaged"])]
            print("  ".join("%-9s" % v for v in row), file=sys.stderr)
    else:
        print("no round queues under artifacts/*/queue", file=sys.stderr)
    print(json.dumps({"tool": "tpu_queue", "summary": True,
                      "rounds": rounds}))
    return 0


# ---- selfcheck: the spool state machine end-to-end on CPU ----------------

_OK_JOB = (
    "import json, os, time\n"
    "from real_time_helmet_detection_tpu.runtime import (maybe_job_heartbeat,"
    " write_job_status)\n"
    "hb = maybe_job_heartbeat()\n"
    "for i in range(3):\n"
    "    hb.beat('step %d' % i)\n"
    "    time.sleep(0.05)\n"
    "open(os.environ['SELFCHECK_ARTIFACT'], 'w').write('{\"ok\": true}')\n"
    "write_job_status(True)\n"
)

_TRANSIENT_JOB = (
    "import os, sys\n"
    "from real_time_helmet_detection_tpu.runtime import (EXIT_TRANSIENT,"
    " maybe_job_heartbeat, write_job_status)\n"
    "maybe_job_heartbeat().beat('attempt')\n"
    "marker = os.environ['SELFCHECK_MARKER']\n"
    "if not os.path.exists(marker):\n"
    "    open(marker, 'w').write('1')\n"
    "    write_job_status(False, error='UNAVAILABLE: injected',"
    " error_class='transient')\n"
    "    sys.exit(EXIT_TRANSIENT)\n"
    "write_job_status(True)\n"
)

# flushes one partial artifact, then hangs WITHOUT beating: exercises the
# stale-heartbeat kill + salvage recording
_HANG_JOB = (
    "import os, time\n"
    "from real_time_helmet_detection_tpu.runtime import maybe_job_heartbeat\n"
    "maybe_job_heartbeat().beat('before hang')\n"
    "open(os.environ['SELFCHECK_ARTIFACT'], 'w').write('{\"partial\": 1}')\n"
    "time.sleep(120)\n"
)


def selfcheck() -> int:
    """End-to-end spool exercise with REAL subprocesses on CPU (no jax,
    no chip): the production path — spawn, heartbeat files, SIGTERM kill,
    salvage, backoff requeue, journal replay across a supervisor
    'restart'."""
    failures = []

    def check(name, cond):
        print("selfcheck %-42s %s" % (name, "ok" if cond else "FAIL"),
              flush=True)
        if not cond:
            failures.append(name)

    with tempfile.TemporaryDirectory(prefix="tpu_queue_selfcheck.") as tmp:
        qdir = os.path.join(tmp, "queue")
        env_common = {"PYTHONPATH": os.pathsep.join(
            [REPO] + [p for p in os.environ.get("PYTHONPATH", "").split(
                os.pathsep) if p])}
        py = sys.executable

        spool = Spool(qdir)
        art_ok = os.path.join(tmp, "ok_artifact.json")
        art_hang = os.path.join(tmp, "hang_partial.json")
        marker = os.path.join(tmp, "transient_marker")
        spool.enqueue(JobSpec(
            job="ok", argv=[py, "-c", _OK_JOB], cwd=tmp,
            artifacts=[os.path.basename(art_ok)],
            heartbeat_timeout_s=30.0,
            env=dict(env_common, SELFCHECK_ARTIFACT=art_ok)))
        spool.enqueue(JobSpec(
            job="transient", argv=[py, "-c", _TRANSIENT_JOB], cwd=tmp,
            heartbeat_timeout_s=30.0, max_attempts=3,
            backoff_base_s=0.1, backoff_cap_s=0.2,
            env=dict(env_common, SELFCHECK_MARKER=marker)))
        # hang deadline balances two costs: it must outlive a cold child
        # interpreter start so the pre-hang beat + artifact flush happen,
        # yet keep the whole selfcheck comfortably inside the smoke tier
        spool.enqueue(JobSpec(
            job="hang", argv=[py, "-c", _HANG_JOB], cwd=tmp,
            artifacts=[os.path.basename(art_hang)],
            heartbeat_timeout_s=8.0, max_attempts=2,
            backoff_base_s=0.1, backoff_cap_s=0.2,
            env=dict(env_common, SELFCHECK_ARTIFACT=art_hang)))

        sup = Supervisor(spool, poll_s=0.05, kill_grace_s=1.0)
        t0 = time.time()
        summary = sup.run()
        print("selfcheck drained in %.1fs: %s"
              % (time.time() - t0, json.dumps(summary)), flush=True)

        jobs = summary["jobs"]
        check("ok job done", jobs["ok"]["state"] == "done")
        check("ok artifact written", os.path.exists(art_ok))
        check("transient retried then done",
              jobs["transient"]["state"] == "done"
              and jobs["transient"]["attempt"] == 2)
        check("hang killed, budget exhausted -> failed",
              jobs["hang"]["state"] == "failed")
        # journal truth: hang job passed through salvaged with its flushed
        # partial artifact recorded
        with open(spool.path) as f:
            recs = [json.loads(ln) for ln in f if ln.strip()]
        salv = [r for r in recs if r.get("kind") == "state"
                and r.get("job") == "hang" and r["state"] == "salvaged"]
        check("hang salvaged with partial artifact",
              bool(salv) and any(a["path"] == os.path.basename(art_hang)
                                 for a in salv[0]["salvaged_artifacts"]))
        requeues = [r for r in recs if r.get("kind") == "state"
                    and r.get("job") == "hang" and r["state"] == "queued"
                    and r.get("attempt", 1) > 1]
        check("hang requeued with backoff gate",
              bool(requeues) and requeues[0].get("not_before", 0) > 0)
        spool.close()

        # restart semantics: replay the journal in a fresh Spool — nothing
        # lost, terminal states intact (the kill -9 durability contract)
        spool2 = Spool(qdir)
        check("replay preserves all jobs", len(spool2.jobs) == 3)
        check("replay preserves terminal states",
              spool2.jobs["ok"].state == "done"
              and spool2.jobs["hang"].state == "failed")
        spool2.close()

    if failures:
        print("selfcheck: %d FAILURE(s): %s" % (len(failures), failures),
              flush=True)
        return 1
    print("selfcheck: all checks passed", flush=True)
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--selfcheck" in argv:
        return selfcheck()

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--queue-dir", default=None,
                   help="spool dir (default artifacts/<round>/queue)")
    sub = p.add_subparsers(dest="cmd", required=True)

    pe = sub.add_parser("enqueue", help="append a job to the spool")
    pe.add_argument("name")
    pe.add_argument("--artifacts", action="append", default=[],
                    help="glob (repo-relative) recorded on salvage; repeat")
    pe.add_argument("--heartbeat-timeout", type=float, default=1800.0,
                    help="stale-beat kill deadline, seconds (default 1800: "
                         "a cold compile of every program a job needs "
                         "takes minutes, and not every job beats inside "
                         "it)")
    pe.add_argument("--max-attempts", type=int, default=3)
    pe.add_argument("--backoff-base", type=float, default=60.0)
    pe.add_argument("--backoff-cap", type=float, default=900.0)

    sub.add_parser("run", help="drain the queue (one job on the chip at "
                               "a time)")

    ps = sub.add_parser("status", help="print the spool state as JSON")
    ps.add_argument("--summary", action="store_true",
                    help="one-screen census across ALL rounds' queues "
                         "(read-only; journals are never repaired)")

    # the job command sits after a literal `--` (argparse's REMAINDER is
    # greedy and would swallow enqueue's own options; splitting by hand
    # keeps `enqueue NAME --artifacts G -- python bench.py` working)
    command = []
    if "--" in argv:
        cut = argv.index("--")
        argv, command = argv[:cut], argv[cut + 1:]
    args = p.parse_args(argv)
    args.command = command
    args.queue_dir = args.queue_dir or default_queue_dir()
    return {"enqueue": cmd_enqueue, "run": cmd_run,
            "status": cmd_status}[args.cmd](args)


if __name__ == "__main__":
    raise SystemExit(main())
