"""Elastic mesh re-formation drill (SURVEY
hard part #1): a 2-host SPMD job loses a host mid-training (preemption
SIGKILL, exit 137), the sharded checkpoint carries continuity, and the
job finishes on a RE-FORMED, SMALLER mesh — re-jit, re-shard restore —
with the task queue as the unit of continuity (the reference's key
insight: tasks, not ranks, are the unit of work; its equivalent drill is
report_cn.md:108-120 convergence-invariance under 4<->8 workers +
test_restart_ps fault injection)."""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import jax

from elasticdl_tpu.common.model_utils import load_model_spec_from_module
from elasticdl_tpu.data import recordio_gen
from elasticdl_tpu.master.master import Master
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.worker.worker import JobType, Worker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spec():
    from model_zoo.mnist_functional_api import mnist_functional_api as zoo

    return load_model_spec_from_module(zoo)


@pytest.mark.slow
def test_mesh_reformation_after_host_loss(tmp_path):
    data_dir = str(tmp_path / "train")
    ckpt_dir = str(tmp_path / "ckpt")
    # 192 records, global batch 16 -> 12 full lockstep rounds if nothing
    # fails; checkpoint every 4 steps; host 1 is preempted after step 6,
    # so version-4 is the continuity point.
    recordio_gen.gen_mnist_like(data_dir, num_files=2, records_per_file=96)

    master = Master(
        _spec(),
        training_data=data_dir,
        minibatch_size=8,
        records_per_task=32,
        num_epochs=1,
        port=0,
    )
    master.prepare()
    coord_port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"

    procs = []
    try:
        # ---- phase 1: 2 hosts x 4 devices; host 1 dies after 6 steps
        for pid, die_after in ((0, -1), (1, 6)):
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable,
                        os.path.join(REPO, "tests", "spmd_proc_main.py"),
                        str(pid), "2", str(master.port), str(coord_port),
                        data_dir, "4", str(die_after), ckpt_dir, "4",
                    ],
                    env=env,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                    text=True,
                )
            )
        out1, _ = procs[1].communicate(timeout=300)
        assert procs[1].returncode == 137, (
            "host 1 should die preempted (137):\n%s" % out1[-3000:]
        )
        # The survivor's next collective can only fail or stall without
        # its peer; its failure handler reports in-flight tasks back to
        # the master. Give it a moment, then treat the whole phase-1 job
        # as dead (what the instance manager concludes from pod events).
        try:
            procs[0].communicate(timeout=60)
        except subprocess.TimeoutExpired:
            procs[0].kill()
            procs[0].communicate()

        # Master-side recovery — exactly what InstanceManager._event_cb
        # runs on a pod Failed/DELETED event: requeue the lost workers'
        # in-flight tasks.
        for wid in ("0", "1", 0, 1):
            master.task_d.recover_tasks(wid)
        assert not master.task_d.finished(), (
            "tasks must remain after losing the job mid-training"
        )

        # ---- phase 2: re-formed SMALLER mesh (1 host x 4 devices),
        # restore from the sharded checkpoint (re-shard), finish the job.
        assert os.path.isdir(os.path.join(ckpt_dir, "version-4")), (
            "phase 1 must have checkpointed version-4 before the loss"
        )
        mesh = mesh_lib.build_mesh({"dp": 4}, devices=jax.devices()[:4])
        worker = Worker(
            2,
            _spec(),
            master_addr="localhost:%d" % master.port,
            job_type=JobType.TRAINING_ONLY,
            minibatch_size=8,
            training_data=data_dir,
            wait_sleep_secs=0.1,
            mesh=mesh,
            spmd=True,
            checkpoint_dir_for_init=ckpt_dir,
        )
        state = worker.run()

        # continuity: restored from version-4, then kept stepping
        assert state is not None
        assert int(state.step) > 4
        assert np.isfinite(worker.losses).all()
        # completion: every task accounted for on the re-formed mesh
        assert master.task_d.finished()
        # the checkpoint restore really fed phase 2 (not a fresh init):
        # the final step count must equal restored version 4 + exactly
        # the batches phase 2 ran — a fresh init would start at 0 and
        # give step == len(losses).
        assert len(worker.losses) >= 1
        assert int(state.step) == 4 + len(worker.losses)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        master.stop()
