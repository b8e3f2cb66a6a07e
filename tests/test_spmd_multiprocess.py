"""True multi-host SPMD: 2 OS processes x 4 virtual CPU devices each, gloo
collectives, real gRPC master. The TPU-pod execution model end-to-end —
both hosts run the same compiled step in lockstep while pulling tasks
elastically from the master."""

import os
import socket
import subprocess
import sys

import pytest

from elasticdl_tpu.common.model_utils import load_model_spec_from_module
from elasticdl_tpu.data import recordio_gen
from elasticdl_tpu.master.master import Master

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
def test_two_process_host_embedding_parity(tmp_path):
    """Host-spill embedding tables partitioned
    over 2 real processes (4 virtual devices each) train to parity with
    a single-process run of the identical global batch stream — the
    reference's PS capacity-scales-with-fleet property, TPU-style."""
    import numpy as np

    out_dir = str(tmp_path)
    coord_port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    steps = 4
    procs = [
        subprocess.Popen(
            [
                sys.executable,
                os.path.join(REPO, "tests", "host_spmd_proc_main.py"),
                str(pid), "2", str(coord_port), out_dir, "4", str(steps),
            ],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in range(2)
    ]
    try:
        outs = [p.communicate(timeout=300)[0] for p in procs]
        for i, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, "proc %d failed:\n%s" % (
                i, out[-3000:])
            assert "HOST_SPMD_DONE" in out
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    # single-process baseline over the identical global stream
    from elasticdl_tpu.common.model_utils import (
        load_model_spec_from_module as _load,
    )
    from elasticdl_tpu.embedding.host_bridge import attach_from_spec
    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.training.trainer import Trainer
    from model_zoo.deepfm_host_embedding import deepfm_host_embedding as z

    spec = _load(z)
    trainer = Trainer(spec, mesh=mesh_lib.local_mesh())
    manager = attach_from_spec(trainer, spec)
    rng = np.random.RandomState(7)
    state = None
    base_losses = []
    for _ in range(steps):
        ids = rng.randint(0, 50, size=(16, 10)).astype(np.int32)
        labels = rng.randint(0, 2, size=(16,)).astype(np.int32)
        batch = ({"feature": ids}, labels)
        if state is None:
            state = trainer.init_state(batch)
        state, loss = trainer.train_step(state, batch)
        base_losses.append(float(loss))

    d0 = np.load(os.path.join(out_dir, "proc0.npz"))
    d1 = np.load(os.path.join(out_dir, "proc1.npz"))
    np.testing.assert_allclose(d0["losses"], base_losses, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(d1["losses"], base_losses, rtol=1e-5,
                               atol=1e-6)
    for name, t in manager.tables().items():
        base_ids, base_vals = t.engine.param.export_rows()
        base_map = dict(zip(base_ids.tolist(), base_vals))
        merged = {}
        for d in (d0, d1):
            merged.update(
                zip(d[name + ".ids"].tolist(), d[name + ".values"])
            )
        assert sorted(merged) == sorted(base_map)
        for i in merged:
            np.testing.assert_allclose(
                merged[i], base_map[i], rtol=1e-5, atol=1e-6
            )


class _DrillInfraError(AssertionError):
    """Infra-class drill failure (timeout / dead subprocess) — the
    load-sensitive mode the single retry is allowed to absorb. The
    post-completion correctness assertions (step parity, dispatcher
    drained, eval aggregated) are NOT this class and fail hard."""


@pytest.mark.slow
def test_two_process_spmd_train(tmp_path):
    """Known load-sensitive drill (see .claude/skills/verify/SKILL.md):
    the two jax subprocesses + master can outlast their gRPC deadlines
    under heavily parallel pytest runs. One retry with a fresh master/
    ports absorbs INFRA failures only (timeouts, dead subprocesses);
    correctness assertions fail hard, and a real infra regression
    fails both attempts."""
    import warnings

    try:
        _two_process_spmd_drill(tmp_path / "a")
    except _DrillInfraError as e:
        warnings.warn(
            "two-process SPMD drill retried after infra failure: %s"
            % (str(e)[:500],)
        )
        _two_process_spmd_drill(tmp_path / "b")


def _two_process_spmd_drill(tmp_path):
    tmp_path.mkdir(parents=True, exist_ok=True)
    data_dir = str(tmp_path / "train")
    val_dir = str(tmp_path / "val")
    recordio_gen.gen_mnist_like(data_dir, num_files=2, records_per_file=64)
    recordio_gen.gen_mnist_like(val_dir, num_files=1, records_per_file=32,
                                seed=3)

    master = None
    procs = []
    try:
        master = Master(
            _spec(),
            training_data=data_dir,
            validation_data=val_dir,
            minibatch_size=8,   # per-host; global batch = 16
            records_per_task=32,
            num_epochs=1,
            evaluation_steps=4,
            port=0,
        )
        master.prepare()
        coord_port = _free_port()
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        for pid in range(2):
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable,
                        os.path.join(REPO, "tests", "spmd_proc_main.py"),
                        str(pid), "2", str(master.port), str(coord_port),
                        data_dir, "4",
                    ],
                    env=env,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                    text=True,
                )
            )
        outs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=300)
            except subprocess.TimeoutExpired as e:
                raise _DrillInfraError("subprocess timeout: %s" % (e,))
            outs.append(out)
        for i, (p, out) in enumerate(zip(procs, outs)):
            if p.returncode != 0 or "SPMD_PROC_DONE" not in out:
                raise _DrillInfraError(
                    "proc %d rc=%s:\n%s" % (i, p.returncode, out[-3000:])
                )
        tail = "\n--- proc0 ---\n%s\n--- proc1 ---\n%s" % (
            outs[0][-1500:], outs[1][-1500:])
        assert master.task_d.finished(), (
            "dispatcher not finished; todo=%r doing=%r%s"
            % (master.task_d._todo, master.task_d._doing, tail))
        # both hosts agreed on the same number of global steps
        import re

        steps = [
            int(re.search(r"steps=(\d+)", o).group(1)) for o in outs
        ]
        assert steps[0] == steps[1], (steps, tail)
        # 128 records / 16 global batch = 8 full global rounds minimum;
        # uneven task streams can add padded rounds, never lose records
        assert steps[0] >= 128 // 16, (steps, tail)
        # eval ran and aggregated on the master
        assert master.evaluation_service.completed_job_metrics, (
            "no completed eval jobs%s" % tail)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()  # reap BEFORE any retry adds fresh load
        if master is not None:
            master.stop()
