#!/usr/bin/env python
"""Replayable MASTER-kill recovery drill (the control-plane twin of
scripts/run_worker_kill_drill.py).

Runs the REAL distributed stack with the master as a subprocess —
`python -m elasticdl_tpu.master.main` with a --job_state_dir journal,
LocalInstanceManager spawning a worker subprocess — and has the MASTER
SIGKILL itself mid-job: EDL_FAULT_SPEC=report_task_result:kill:1:skip=2
(common/fault_injection.py) fires on the worker's THIRD task report,
so two ranges are done and one is in flight whatever the machine's
load (a kill sent by this script after polling the journal raced a
job of a few short tasks, and lost whenever the poll ran late). The
orphaned worker keeps retrying inside its bounded
reconnect window (common/retry.py) instead of exiting; a second master
process started over the same --job_state_dir restores the dispatcher
from the journal (todo ∪ requeued-doing), the worker re-registers, and
the job runs to completion. The drill then audits the two journals:
every record range must be completed exactly once (done ∪ done_recovered
over both master lifetimes), and the recovery gauges (master/restarts,
master/recovery_requeued_tasks, fault/rpc_retries) must appear in the
TensorBoard event stream.

Usage: python scripts/run_master_kill_drill.py
Exit 0 = recovered, exactly-once accounting holds; the transcript
narrates each phase.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def read_journal(path):
    """Parse journal events, tolerating the torn final line a SIGKILL
    can leave behind (same rule as state_store.load)."""
    events = []
    if not os.path.exists(path):
        return events
    with open(path) as f:
        lines = f.readlines()
    for i, line in enumerate(lines):
        line = line.strip()
        if not line:
            continue
        try:
            events.append(json.loads(line))
        except ValueError:
            if i != len(lines) - 1:
                raise
    return events


def completed_ranges(events):
    """(shard, start, end) of every done / done_recovered event."""
    out = []
    for ev in events:
        if ev.get("ev") in ("done", "done_recovered"):
            p = ev["task"]
            out.append((p[0], p[1], p[2]))
    return out


def find_worker_pids(master_addr):
    """PIDs of THIS job's elasticdl_tpu.worker.main processes: those
    started with `--master_addr <master_addr>` (the orphan-worker
    probe: /proc scan, no psutil dependency). Another job's workers on
    the same machine — a test running beside this one — dial another
    master and are not the drill's to assert on or to kill."""
    pids = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open("/proc/%s/cmdline" % pid, "rb") as f:
                argv = f.read().decode("utf-8", "replace").split("\0")
        except OSError:
            continue
        if ("elasticdl_tpu.worker.main" in argv
                and "--master_addr" in argv[:-1]
                and argv[argv.index("--master_addr") + 1] == master_addr):
            pids.append(int(pid))
    return pids


def tb_stream_contains(tb_dir, tags):
    """True when every tag appears in some TensorBoard event file under
    tb_dir (tags are embedded as plain strings in the Event protos, so a
    byte scan needs no TF)."""
    blobs = []
    for root, _, files in os.walk(tb_dir):
        for name in files:
            if "tfevents" in name:
                with open(os.path.join(root, name), "rb") as f:
                    blobs.append(f.read())
    blob = b"".join(blobs)
    return all(tag.encode() in blob for tag in tags)


def master_cmd(port, train_dir, state_dir, status_file, tb_dir,
               num_workers, records_per_task, minibatch_size, num_epochs):
    return [
        sys.executable, "-m", "elasticdl_tpu.master.main",
        "--model_zoo", os.path.join(REPO, "model_zoo"),
        "--model_def",
        "mnist_functional_api.mnist_functional_api.custom_model",
        "--training_data", train_dir,
        "--minibatch_size", str(minibatch_size),
        "--records_per_task", str(records_per_task),
        "--num_epochs", str(num_epochs),
        "--num_workers", str(num_workers),
        "--port", str(port),
        "--job_state_dir", state_dir,
        "--job_status_file", status_file,
        "--need_tensorboard", "true",
        "--tensorboard_log_dir", tb_dir,
    ]


def run_drill(
    workdir=None,
    num_files=4,
    records_per_file=48,
    records_per_task=24,
    minibatch_size=16,
    num_epochs=1,
    reconnect_window_secs=120,
    startup_timeout=180,
    finish_timeout=300,
    log=print,
):
    """Execute the kill/restart/verify sequence; returns a result dict
    (raises AssertionError on drill failure). Shared by the CLI and
    tests/test_master_failover.py."""
    from elasticdl_tpu.data import recordio_gen

    workdir = workdir or tempfile.mkdtemp(prefix="master_kill_drill_")
    train_dir = os.path.join(workdir, "train")
    state_dir = os.path.join(workdir, "job_state")
    tb_dir = os.path.join(workdir, "tb")
    status_file = os.path.join(workdir, "job_status.json")
    total_records = num_files * records_per_file
    log("[drill] generating %dx%d TRec records -> %s"
        % (num_files, records_per_file, train_dir))
    recordio_gen.gen_mnist_like(train_dir, num_files=num_files,
                                records_per_file=records_per_file)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # bounded reconnect window the orphan worker must ride out; huge
    # snapshot threshold so the journal keeps every event for the
    # exactly-once audit below
    env["EDL_RPC_RECONNECT_WINDOW_SECS"] = str(reconnect_window_secs)
    env["EDL_RPC_TIMEOUT_SECS"] = "15"
    env["EDL_STATE_SNAPSHOT_EVERY"] = "100000"

    port = free_port()
    # what master/main.py hands its local workers as --master_addr
    master_addr = "localhost:%d" % port
    journal = os.path.join(state_dir, "journal.jsonl")
    args = (train_dir, state_dir, status_file, tb_dir)
    m2 = None
    # master #1 dies on its third task report, before applying it: two
    # ranges done, one in flight — the kill lands between ranges by
    # construction, proving both replay paths (done stays done, doing
    # gets requeued)
    m1 = subprocess.Popen(
        master_cmd(port, *args, num_workers=1,
                   records_per_task=records_per_task,
                   minibatch_size=minibatch_size, num_epochs=num_epochs),
        env=dict(env, EDL_FAULT_SPEC="report_task_result:kill:1:skip=2"),
    )
    log("[drill] master #1 (pid %d) on :%d, journaling to %s"
        % (m1.pid, port, state_dir))

    try:
        try:
            m1.wait(timeout=startup_timeout)
        except subprocess.TimeoutExpired:
            raise AssertionError(
                "master #1 never reached its third task report "
                "(journal: %s)"
                % [e.get("ev") for e in read_journal(journal)])
        assert m1.returncode == -signal.SIGKILL, (
            "master #1 exited rc=%s, not by the injected SIGKILL"
            % m1.returncode)
        worker_pids = find_worker_pids(master_addr)
        assert worker_pids, "no worker subprocess found"
        log("[drill] master #1 SIGKILLed itself mid-job; worker(s) %s "
            "orphaned" % worker_pids)

        # audit what master #1's lifetime completed, BEFORE the restart
        # compacts the journal
        events1 = read_journal(journal)
        done1 = completed_ranges(events1)
        log("[drill] master #1 journal: %d events, %d ranges done"
            % (len(events1), len(done1)))
        assert len(done1) == 2, (
            "the kill did not land on the third report: %d ranges done"
            % len(done1))

        time.sleep(1.0)
        alive = [p for p in worker_pids
                 if os.path.exists("/proc/%d" % p)]
        assert alive, (
            "worker exited during the master outage — the 'UNAVAILABLE "
            "means job done' bug is back")
        log("[drill] workers %s survived the outage (retrying)" % alive)

        # master #2 over the same journal; the orphan worker reconnects,
        # so no fresh worker fleet (--num_workers 0)
        m2 = subprocess.Popen(
            master_cmd(port, *args, num_workers=0,
                       records_per_task=records_per_task,
                       minibatch_size=minibatch_size,
                       num_epochs=num_epochs),
            env=env,
        )
        log("[drill] master #2 (pid %d) restoring from the journal"
            % m2.pid)

        deadline = time.time() + finish_timeout
        while time.time() < deadline:
            if m2.poll() is not None:
                break
            time.sleep(0.5)
        assert m2.poll() is not None, "master #2 did not finish in time"
        assert m2.returncode == 0, (
            "master #2 exited rc=%d" % m2.returncode)

        with open(status_file) as f:
            status = json.load(f)["status"]
        assert status == "Succeeded", "job status %s" % status

        # exactly-once accounting across both master lifetimes
        events2 = read_journal(journal)
        done2 = completed_ranges(events2)
        all_done = sorted(done1 + done2)
        expected = sorted(
            (shard, start, min(start + records_per_task, records))
            for shard, records in (
                (os.path.join(train_dir, name), records_per_file)
                for name in sorted(os.listdir(train_dir))
            )
            for start in range(0, records, records_per_task)
            for _ in range(num_epochs)
        )
        assert all_done == expected, (
            "record-range accounting mismatch:\n got %s\n want %s"
            % (all_done, expected))
        requeued = [e for e in events2 if e.get("ev") == "done_recovered"]
        log("[drill] exactly-once holds over %d ranges (%d records), "
            "%d reconciled from pre-crash doing"
            % (len(all_done), total_records, len(requeued)))

        # the recovery gauges must be visible in the TensorBoard stream
        tags = ["master/restarts", "master/recovery_requeued_tasks",
                "fault/rpc_retries"]
        assert tb_stream_contains(tb_dir, tags), (
            "recovery gauges missing from the TensorBoard stream: %s"
            % tags)
        log("[drill] recovery gauges present in TB stream: %s" % tags)

        deadline = time.time() + 60
        while time.time() < deadline and any(
            os.path.exists("/proc/%d" % p) for p in alive
        ):
            time.sleep(0.5)
        log("[drill] worker(s) exited after JOB_COMPLETE")
        return {
            "ranges": len(all_done),
            "requeued_reconciled": len(requeued),
            "worker_pids": worker_pids,
        }
    finally:
        for proc in (m1, m2):
            if proc is not None and proc.poll() is None:
                proc.kill()
        for pid in find_worker_pids(master_addr):
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass


def main():
    res = run_drill(num_epochs=2)
    print("[drill] master-kill recovery drill PASSED: %s" % res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
