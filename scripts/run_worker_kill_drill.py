#!/usr/bin/env python
"""Replayable worker-kill recovery drill.

Runs the REAL distributed stack — master gRPC server, task dispatcher,
LocalInstanceManager spawning worker subprocesses — SIGKILLs a worker
mid-task (the exit the reference's benchmark induced by cluster
preemption, report §Elasticity), and verifies the master re-queues the
in-flight task, relaunches a replacement, and finishes the job. The
same sequence runs against a k8s cluster via
scripts/run_cluster_job_smoke.sh (EDL_CLUSTER_FULL=1) with `kubectl
delete pod` as the kill; this script needs nothing but the repo.

Usage: python scripts/run_worker_kill_drill.py
Exit 0 = recovered and finished; the transcript narrates each phase.
"""

import os
import sys
import tempfile
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from elasticdl_tpu.common.model_utils import load_model_spec_from_module
from elasticdl_tpu.data import recordio_gen
from elasticdl_tpu.master.instance_manager import LocalInstanceManager
from elasticdl_tpu.master.master import Master


def main():
    from model_zoo.mnist_functional_api import mnist_functional_api as zoo

    workdir = tempfile.mkdtemp(prefix="kill_drill_")
    train_dir = os.path.join(workdir, "train")
    print("[drill] generating 4x48 TRec records -> %s" % train_dir)
    recordio_gen.gen_mnist_like(train_dir, num_files=4,
                                records_per_file=48)

    master = Master(
        load_model_spec_from_module(zoo),
        training_data=train_dir,
        minibatch_size=16,
        records_per_task=24,
        num_epochs=2,
    )
    master.prepare()
    print("[drill] master gRPC server on :%d, %d tasks queued"
          % (master.port, len(master.task_d._todo)))

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    manager = LocalInstanceManager(
        master.task_d,
        num_workers=1,
        worker_args=[
            "--model_zoo", os.path.join(repo, "model_zoo"),
            "--model_def",
            "mnist_functional_api.mnist_functional_api.custom_model",
            "--training_data", train_dir,
            "--minibatch_size", "16",
            "--records_per_task", "24",
            "--job_type", "training_only",
            "--master_addr", "localhost:%d" % master.port,
        ],
        env=env,
    )
    master.instance_manager = manager
    manager.start_workers()
    print("[drill] worker 0 launched (subprocess)")

    try:
        deadline = time.time() + 120
        while not master.task_d.doing_tasks() and time.time() < deadline:
            time.sleep(0.2)
        if not master.task_d.doing_tasks():
            print("[drill] FAIL: worker never took a task")
            return 1
        doing = dict(master.task_d.doing_tasks())
        print("[drill] worker 0 is mid-task (in-flight: %s) — SIGKILL"
              % sorted(doing))
        manager.remove_worker(0)

        deadline = time.time() + 300
        while not master.task_d.finished() and time.time() < deadline:
            if manager.all_workers_failed():
                print("[drill] FAIL: all workers failed, no relaunch")
                return 1
            time.sleep(0.5)
        if not master.task_d.finished():
            print("[drill] FAIL: job did not finish after the kill")
            return 1
        print("[drill] worker 0 terminal phase: %s"
              % manager.worker_phase(0))
        print("[drill] replacement worker 1 phase: %s"
              % manager.worker_phase(1))
        print("[drill] job finished: every task completed after "
              "re-queue — PASSED")
        return 0
    finally:
        master.stop()


if __name__ == "__main__":
    sys.exit(main())
