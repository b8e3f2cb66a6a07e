"""Master fault-tolerance failover tests.

Worker-level (in-process servicer + fault injector): a transient master
outage no longer terminates the worker as "end of job" — it retries
inside the bounded reconnect window; a genuinely finished job shuts the
worker down via the explicit JOB_COMPLETE signal even when the master
disappears right after.

End-to-end drill (subprocess): SIGKILL the master mid-job, restart it
from --job_state_dir, and prove the orphaned worker reconnects with
backoff, the job completes, every record range is processed exactly
once, and the recovery gauges land in the TensorBoard stream
(scripts/run_master_kill_drill.py owns the sequence; CI runs it on
every PR through this test).
"""

import grpc
import pytest

from elasticdl_tpu.common.fault_injection import (
    FaultInjectingServicer,
    FaultInjector,
)
from elasticdl_tpu.common.model_utils import load_model_spec_from_module
from elasticdl_tpu.common.retry import RetryPolicy
from elasticdl_tpu.data import recordio_gen
from elasticdl_tpu.master.master import Master
from elasticdl_tpu.proto import elasticdl_pb2 as pb
from elasticdl_tpu.worker.worker import JobType, Worker

# CI drills shard companion of test_worker_master_integration; tier-1
# ('not slow') includes this file so the failover drill gates every PR.
pytestmark = pytest.mark.integration


def _spec():
    from model_zoo.mnist_functional_api import mnist_functional_api as zoo

    return load_model_spec_from_module(zoo)


def _fast_policy(window=20.0):
    return RetryPolicy(
        rpc_timeout_secs=5.0,
        base_delay_secs=0.005,
        max_delay_secs=0.05,
        reconnect_window_secs=window,
    )


@pytest.fixture()
def train_dir(tmp_path):
    d = str(tmp_path / "train")
    recordio_gen.gen_mnist_like(d, num_files=2, records_per_file=48)
    return d


def _worker(master_servicer, train_dir, **kwargs):
    return Worker(
        0,
        _spec(),
        master_servicer=master_servicer,
        job_type=JobType.TRAINING_ONLY,
        minibatch_size=16,
        training_data=train_dir,
        wait_sleep_secs=0.05,
        retry_policy=_fast_policy(),
        **kwargs,
    )


def test_transient_outage_is_retried_not_end_of_job(train_dir):
    """RPC drops mid-job (the wire signature of a master restart) must
    NOT terminate the worker; it retries and the job completes with
    every record trained."""
    master = Master(
        _spec(), training_data=train_dir, minibatch_size=16,
        records_per_task=24, num_epochs=1,
    )
    injector = FaultInjector(
        # drop three polls mid-job + lose one applied report response
        # (the duplicate-side-effect path)
        spec="get_task:drop:3:skip=2;report_task_result:error:1",
    )
    worker = _worker(
        FaultInjectingServicer(master.servicer, injector), train_dir
    )
    state = worker.run()
    assert master.task_d.finished()
    assert int(state.step) == 96 // 16  # every range trained exactly once
    assert worker.rpc_retry_count >= 4
    assert injector.injected["get_task"] == 3
    assert worker.job_complete  # exited on the explicit signal


def test_clean_completion_via_explicit_signal(train_dir):
    """A finished job shuts the worker down via JOB_COMPLETE even when
    the master becomes unreachable immediately afterwards: post-signal
    RPCs degrade to best-effort instead of retrying a dead master."""
    master = Master(
        _spec(), training_data=train_dir, minibatch_size=16,
        records_per_task=48, num_epochs=1,
    )
    worker = _worker(master.servicer, train_dir)
    state = worker.run()
    assert worker.job_complete
    assert master.task_d.finished()
    assert int(state.step) == 96 // 16
    # master gone now: every further call is best-effort, never raises
    worker._master = FaultInjectingServicer(
        master.servicer, FaultInjector(spec="*:drop:*")
    )
    task = worker.get_task()
    assert task.type == pb.NONE and task.reason == pb.JOB_COMPLETE
    worker.report_task_result(1)
    worker.report_version(3)


def test_reconnect_window_exhaustion_raises(train_dir):
    """A master that never comes back must fail the worker LOUDLY after
    the bounded window — not silently, and not as a fake end-of-job."""
    master = Master(
        _spec(), training_data=train_dir, minibatch_size=16,
        records_per_task=48, num_epochs=1,
    )
    worker = Worker(
        0,
        _spec(),
        master_servicer=FaultInjectingServicer(
            master.servicer, FaultInjector(spec="get_task:drop:*")
        ),
        job_type=JobType.TRAINING_ONLY,
        minibatch_size=16,
        training_data=train_dir,
        retry_policy=RetryPolicy(base_delay_secs=0.005,
                                 max_delay_secs=0.02,
                                 reconnect_window_secs=0.3),
    )
    with pytest.raises(grpc.RpcError):
        worker.get_task()
    assert not worker.job_complete
    assert worker.rpc_retry_count > 0


def test_worker_reregisters_after_master_restart(train_dir):
    """A retried RPC that eventually lands means the master restarted:
    the worker re-registers so the new master's membership is whole."""
    master = Master(
        _spec(), training_data=train_dir, minibatch_size=16,
        records_per_task=24, num_epochs=1,
    )
    worker = _worker(
        FaultInjectingServicer(
            master.servicer, FaultInjector(spec="get_task:drop:2:skip=1")
        ),
        train_dir,
    )
    worker.run()
    assert worker.reconnect_count >= 1
    # re-registration reached the servicer (initial + at least one more)
    assert 0 in master.servicer._workers
    assert master.servicer._cluster_version >= 2


def test_master_recovery_gauges_exported(tmp_path, train_dir):
    """master/restarts + master/recovery_requeued_tasks ride the
    existing TensorBoard gauge path on a recovered master."""
    from elasticdl_tpu.master.tensorboard_service import TensorboardService

    state_dir = str(tmp_path / "state")
    master = Master(
        _spec(), training_data=train_dir, minibatch_size=16,
        records_per_task=24, num_epochs=1, job_state_dir=state_dir,
    )
    tid, _ = master.task_d.get(0)  # leave one task in-flight

    tb_dir = str(tmp_path / "tb")
    master2 = Master(
        _spec(), training_data=train_dir, minibatch_size=16,
        records_per_task=24, num_epochs=1, job_state_dir=state_dir,
        tensorboard_service=TensorboardService(tb_dir),
    )
    assert master2.task_d.requeued_on_recovery == 1
    assert master2.state_store.restart_count == 1
    master2._write_recovery_gauges()
    master2.tensorboard_service.stop()
    from scripts.run_master_kill_drill import tb_stream_contains

    assert tb_stream_contains(
        tb_dir, ["master/restarts", "master/recovery_requeued_tasks"]
    )


def test_drill_worker_discovery_ignores_another_jobs_workers():
    """The drill asserts on, and in its `finally` SIGKILLs, the
    workers it finds: a scan for ANY elasticdl_tpu.worker.main process
    adopted the workers of whichever test ran a job beside it (the
    tier-1 run's flaky failure under `-n 6`). A decoy whose command
    line names the worker module and ANOTHER master's address is found
    for that address only — not for the drill's, and not for one that
    merely starts with the same characters."""
    import subprocess
    import sys
    import time

    from scripts.run_master_kill_drill import find_worker_pids

    decoy = subprocess.Popen([
        sys.executable, "-c", "import time; time.sleep(120)",
        "-m", "elasticdl_tpu.worker.main",
        "--master_addr", "localhost:61", "--worker_id", "0",
    ])
    try:
        deadline = time.time() + 30
        while (decoy.pid not in find_worker_pids("localhost:61")
               and time.time() < deadline):
            time.sleep(0.05)  # exec has to replace the forked cmdline
        assert decoy.pid in find_worker_pids("localhost:61")
        assert decoy.pid not in find_worker_pids("localhost:62")
        assert decoy.pid not in find_worker_pids("localhost:6")
        assert decoy.pid not in find_worker_pids("localhost:611")
    finally:
        decoy.kill()
        decoy.wait()


def test_master_kill_drill_end_to_end(tmp_path):
    """The full SIGKILL drill: master dies mid-job, restarts from the
    journal, the orphan worker reconnects (never exits), the job
    completes with exactly-once range accounting, and the recovery
    gauges appear in the TB stream."""
    from scripts.run_master_kill_drill import run_drill

    result = run_drill(
        workdir=str(tmp_path),
        num_files=2,
        records_per_file=32,
        records_per_task=8,
        minibatch_size=8,
        num_epochs=1,
        reconnect_window_secs=120,
        log=lambda *a: None,
    )
    assert result["ranges"] == 8  # 2 files x 32 records / 8 per task
