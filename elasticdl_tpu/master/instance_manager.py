"""Instance manager: the elasticity core.

Parity with the reference's InstanceManager
(master/k8s_instance_manager.py:52-388) minus PS pods (no parameter
servers on TPU):

* launches the worker fleet (k8s pods or local subprocesses);
* reacts to lifecycle events: a worker that dies has its in-flight tasks
  recovered back to the todo queue (`task_d.recover_tasks`) and is
  relaunched with a NEW worker id (reference :369-378) up to
  `relaunch_on_worker_failure` times; exit code 137 that is NOT an OOM
  kill means preemption and relaunches without burning a retry
  (reference :310-338);
* `all_workers_failed` aborts the job from the master wait loop
  (reference master.py:242-245);
* fractional pod priority: "high=0.5" marks the first half of workers
  high-priority (reference `_parse_worker_pod_priority`).

The k8s watch stream and the local process-waiter thread both funnel
into the same `_handle_worker_exit` path, so elasticity semantics are
identical and unit-testable without a cluster (the reference tests mock
the same boundary — k8s_instance_manager_test.py).
"""

import os
import subprocess
import sys
import threading

from elasticdl_tpu.common import platform_utils
from elasticdl_tpu.common.k8s_client import (
    ELASTICDL_REPLICA_INDEX_KEY,
    ELASTICDL_REPLICA_TYPE_KEY,
)
from elasticdl_tpu.common.log_utils import default_logger as logger

_EXIT_PREEMPTED = 137  # SIGKILL: evicted/preempted unless reason=OOMKilled


def parse_worker_pod_priority(num_workers, priority_spec):
    """'high=0.5' → the first half of worker indices get priority 'high'
    (reference k8s_instance_manager.py `_parse_worker_pod_priority`)."""
    if not priority_spec:
        return {i: None for i in range(num_workers)}
    if "=" in priority_spec:
        name, _, frac = priority_spec.partition("=")
        frac = float(frac)
        n_high = int(num_workers * frac)
        return {
            i: (name if i < n_high else None)
            for i in range(num_workers)
        }
    return {i: priority_spec for i in range(num_workers)}


class _WorkerRecord(object):
    def __init__(self, worker_id, original_index):
        self.worker_id = worker_id
        self.original_index = original_index  # priority slot
        self.phase = "Pending"
        self.relaunch_count = 0


class InstanceManagerBase(object):
    """Shared elasticity state machine over an abstract launch/kill
    backend."""

    def __init__(
        self,
        task_d,
        num_workers,
        relaunch_on_worker_failure=3,
        disable_relaunch=False,
        fault_injector=None,
    ):
        from elasticdl_tpu.common.fault_injection import FaultInjector

        self._task_d = task_d
        self._num_workers = num_workers
        self._max_relaunch = (
            0 if disable_relaunch else relaunch_on_worker_failure
        )
        self._lock = threading.Lock()
        self._workers = {}  # worker_id -> _WorkerRecord
        self._next_worker_id = 0
        self._stopping = False
        # chaos hooks for drill tests: EDL_FAULT_SPEC rules named
        # worker_launch / worker_exit fire here (delay a relaunch, kill
        # the master mid-launch, ...)
        self._fault_injector = (
            fault_injector or FaultInjector.from_env()
        )

    # backend hooks ------------------------------------------------------

    def _launch(self, worker_id, original_index):
        raise NotImplementedError

    def _kill(self, worker_id):
        raise NotImplementedError

    # public API used by Master ------------------------------------------

    def start_workers(self):
        for i in range(self._num_workers):
            self._start_worker(i)

    def _start_worker(self, original_index, relaunch_count=0):
        with self._lock:
            worker_id = self._next_worker_id
            self._next_worker_id += 1
            record = _WorkerRecord(worker_id, original_index)
            record.relaunch_count = relaunch_count
            self._workers[worker_id] = record
        logger.info(
            "Starting worker %d (slot %d)", worker_id, original_index
        )
        if self._fault_injector is not None:
            self._fault_injector.intercept("worker_launch")
        self._launch(worker_id, original_index)
        return worker_id

    def remove_worker(self, worker_id):
        """Kill a straggler (watchdog path, reference master.py:552-556).
        The resulting exit event relaunches it like any failure."""
        logger.info("Removing worker %d", worker_id)
        self._kill(worker_id)

    def all_workers_failed(self):
        with self._lock:
            if not self._workers:
                return False
            return all(
                r.phase in ("Failed", "Deleted")
                for r in self._workers.values()
            )

    def stop(self):
        with self._lock:
            self._stopping = True
            ids = list(self._workers)
        for worker_id in ids:
            try:
                self._kill(worker_id)
            except Exception:
                pass

    # event handling -----------------------------------------------------

    def _handle_worker_exit(
        self, worker_id, *, succeeded, exit_code=None, oom=False,
        deleted=False,
    ):
        """One dead worker: recover its tasks, decide on relaunch."""
        if self._fault_injector is not None:
            self._fault_injector.intercept("worker_exit")
        with self._lock:
            record = self._workers.get(worker_id)
            if self._stopping or record is None or record.phase in (
                "Succeeded", "Failed", "Deleted",
            ):
                return
            if succeeded:
                record.phase = "Succeeded"
                return
            record.phase = "Deleted" if deleted else "Failed"
            preempted = (
                exit_code == _EXIT_PREEMPTED and not oom
            ) or deleted
            relaunch = self._max_relaunch > 0 and (
                preempted or record.relaunch_count < self._max_relaunch
            )
            original_index = record.original_index
            relaunch_count = (
                record.relaunch_count
                if preempted
                else record.relaunch_count + 1
            )
        self._task_d.recover_tasks(worker_id)
        if relaunch:
            logger.info(
                "Relaunching worker %d (slot %d, relaunches used %d/%d%s)",
                worker_id, original_index, relaunch_count,
                self._max_relaunch,
                ", preempted" if preempted else "",
            )
            self._start_worker(
                original_index, relaunch_count=relaunch_count
            )
        else:
            logger.info("Worker %d will not be relaunched", worker_id)

    def worker_phase(self, worker_id):
        with self._lock:
            record = self._workers.get(worker_id)
            return record.phase if record else None


class K8sInstanceManager(InstanceManagerBase):
    """Workers are Kubernetes pods; events come from the watch stream."""

    def __init__(
        self,
        task_d,
        *,
        num_workers,
        worker_command,
        worker_args,
        k8s_client,
        resource_request=None,
        resource_limit=None,
        pod_priority="",
        restart_policy="Never",
        image_pull_policy="Always",
        envs=None,
        volume=None,
        relaunch_on_worker_failure=3,
        disable_relaunch=False,
    ):
        super().__init__(
            task_d,
            num_workers,
            relaunch_on_worker_failure=relaunch_on_worker_failure,
            disable_relaunch=disable_relaunch,
        )
        self._client = k8s_client
        self._image_pull_policy = image_pull_policy
        self._worker_command = list(worker_command)
        self._worker_args = list(worker_args)
        self._resource_request = resource_request or {}
        self._resource_limit = resource_limit or {}
        self._priorities = parse_worker_pod_priority(
            num_workers, pod_priority
        )
        self._restart_policy = restart_policy
        self._envs = envs or {}
        self._volume = volume

    def _launch(self, worker_id, original_index):
        self._client.create_worker_pod(
            worker_id,
            command=self._worker_command,
            args=self._worker_args + ["--worker_id", str(worker_id)],
            resource_requests=self._resource_request,
            resource_limits=self._resource_limit,
            priority_class=self._priorities.get(original_index),
            restart_policy=self._restart_policy,
            image_pull_policy=self._image_pull_policy,
            envs=self._envs,
            volume=self._volume,
        )

    def _kill(self, worker_id):
        self._client.delete_worker(worker_id)

    def stop(self):
        super().stop()
        self._client.stop()

    # ---- k8s event plumbing

    def event_cb(self, event):
        """Pod watch callback (reference `_event_cb`,
        k8s_instance_manager.py:284-384). Accepts kubernetes objects or
        plain dicts (tests)."""
        evt_type = _get(event, "type")
        pod = _get(event, "object")
        labels = _get(pod, "metadata", "labels") or {}
        if _get(labels, ELASTICDL_REPLICA_TYPE_KEY) != "worker":
            return
        worker_id = int(_get(labels, ELASTICDL_REPLICA_INDEX_KEY))
        phase = _get(pod, "status", "phase")
        if evt_type == "DELETED":
            self._handle_worker_exit(worker_id, succeeded=False,
                                     deleted=True)
            return
        if phase == "Succeeded":
            self._handle_worker_exit(worker_id, succeeded=True)
        elif phase == "Failed":
            exit_code, reason = _terminated_state(pod)
            self._handle_worker_exit(
                worker_id,
                succeeded=False,
                exit_code=exit_code,
                oom=(reason == "OOMKilled"),
            )


class LocalInstanceManager(InstanceManagerBase):
    """Workers are local subprocesses running
    `python -m elasticdl_tpu.worker.main` — the no-cluster elastic path
    (and the fault-injection surface the integration tests use).

    On a TPU host each worker slot owns one chip (a chip belongs to one
    process at a time): with more than one worker, slot i's process is
    confined to chip i through its environment, and a job asking for
    more workers than the host has chips is refused at start instead of
    left to fail — or hang — at the second worker's backend start. A
    relaunched worker keeps its slot, hence its chip. One worker keeps
    the whole host: it may drive every chip through a mesh."""

    def __init__(
        self,
        task_d,
        *,
        num_workers,
        worker_args,
        relaunch_on_worker_failure=3,
        disable_relaunch=False,
        env=None,
    ):
        super().__init__(
            task_d,
            num_workers,
            relaunch_on_worker_failure=relaunch_on_worker_failure,
            disable_relaunch=disable_relaunch,
        )
        self._worker_args = list(worker_args)
        self._procs = {}
        self._env = env
        self._chips = (
            platform_utils.tpu_chip_paths() if num_workers > 1 else []
        )
        if self._chips and num_workers > len(self._chips):
            raise ValueError(
                "%d workers asked for, but this host has %d TPU chip(s) "
                "(%s) and a chip belongs to one process: lower "
                "--num_workers, or give one worker several chips with "
                "--distribution_strategy AllreduceStrategy --mesh_spec"
                % (num_workers, len(self._chips), ", ".join(self._chips))
            )

    def _launch(self, worker_id, original_index):
        cmd = (
            [sys.executable, "-m", "elasticdl_tpu.worker.main"]
            + self._worker_args
            + ["--worker_id", str(worker_id)]
        )
        env = self._env
        if self._chips:
            chip = self._chips[original_index]
            env = dict(os.environ if env is None else env,
                       **platform_utils.one_chip_env(chip))
            logger.info("Worker %d (slot %d) is given chip %s",
                        worker_id, original_index, chip)
        proc = subprocess.Popen(cmd, env=env)
        with self._lock:
            self._procs[worker_id] = proc
        threading.Thread(
            target=self._wait_proc,
            args=(worker_id, proc),
            daemon=True,
        ).start()

    def _wait_proc(self, worker_id, proc):
        code = proc.wait()
        if code == 0:
            self._handle_worker_exit(worker_id, succeeded=True)
        else:
            self._handle_worker_exit(
                worker_id,
                succeeded=False,
                exit_code=(
                    _EXIT_PREEMPTED if code == -9 else code
                ),
            )

    def _kill(self, worker_id):
        with self._lock:
            proc = self._procs.get(worker_id)
        if proc is not None and proc.poll() is None:
            proc.kill()

    def stop(self):
        """Kill the workers and wait until they are gone: a worker
        holds its chip until its process has exited, and whoever runs
        next on this host needs the chip."""
        super().stop()
        with self._lock:
            procs = list(self._procs.values())
        for proc in procs:
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                logger.warning("worker pid %d did not exit after kill",
                               proc.pid)


# ---------------------------------------------------------------- helpers


def _get(obj, *path):
    """Attribute/key access that works for kubernetes models and dicts."""
    for key in path:
        if obj is None:
            return None
        if isinstance(obj, dict):
            obj = obj.get(key)
        else:
            obj = getattr(obj, key, None)
    return obj


def _terminated_state(pod):
    """(exit_code, reason) of the first terminated container, if any."""
    statuses = _get(pod, "status", "container_statuses") or _get(
        pod, "status", "containerStatuses"
    )
    if not statuses:
        return None, None
    st = statuses[0]
    term = _get(st, "state", "terminated")
    if term is None:
        return None, None
    return _get(term, "exit_code") or _get(term, "exitCode"), _get(
        term, "reason"
    )
