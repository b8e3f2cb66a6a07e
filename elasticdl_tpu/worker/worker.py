"""Worker: the compute-plane process driven by master-dispatched tasks.

Replaces the reference's worker/worker.py:72-1147. What's gone, by design:
all PS plumbing (pull_dense_parameters / report_gradient / embedding RPC —
~700 of those 1147 lines). The TPU worker's gradient path is the jit-compiled
Trainer step; gradient aggregation across hosts is XLA collectives inside
that step (multi-host wiring in parallel/), not RPC.

What's preserved, behavior-for-behavior:
* task-driven training with batches spanning task boundaries,
* interleaved evaluation during training (TRAINING_WITH_EVALUATION pulls an
  eval task before each minibatch — reference :1041-1047, :1091-1110),
* minibatch retry up to MAX_MINIBATCH_RETRY_NUM (=64, reference :62),
* version reporting to the master for step-based eval triggers (in the
  reference the PS did this every eval_steps; the PS is gone, so the worker
  reports after each completed minibatch),
* TRAIN_END_CALLBACK processing (train-end callbacks e.g. model export),
* predict-only mode with a prediction outputs processor.
"""

import os
import time
import traceback

import numpy as np

from elasticdl_tpu.common.constants import (
    MAX_MINIBATCH_RETRY_NUM,
    Mode,
)
from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.common.model_utils import resolve_dataset_fn
from elasticdl_tpu.common.retry import (
    RetryPolicy,
    is_transient_rpc_error,
    retry_call,
)
from elasticdl_tpu.common.tensor_utils import serialize_ndarray_dict
from elasticdl_tpu.common.timing_utils import Timing
from elasticdl_tpu.data.dataset import pad_batch
from elasticdl_tpu.master.task_dispatcher import Task
from elasticdl_tpu.proto import elasticdl_pb2 as pb
from elasticdl_tpu.proto.service import MasterStub, build_channel
from elasticdl_tpu.training.trainer import Trainer
from elasticdl_tpu.worker.task_data_service import TaskDataService


def _default_retry_policy():
    """Worker RPC retry knobs, env-overridable so subprocess drills can
    shrink the reconnect window without new CLI flags."""
    return RetryPolicy(
        rpc_timeout_secs=float(
            os.environ.get("EDL_RPC_TIMEOUT_SECS", 30.0)
        ),
        reconnect_window_secs=float(
            os.environ.get("EDL_RPC_RECONNECT_WINDOW_SECS", 120.0)
        ),
    )


class JobType(object):
    TRAINING_ONLY = "training_only"
    TRAINING_WITH_EVALUATION = "training_with_evaluation"
    EVALUATION_ONLY = "evaluation_only"
    PREDICTION_ONLY = "prediction_only"


class Worker(object):
    def __init__(
        self,
        worker_id,
        model_spec,
        master_addr=None,
        master_servicer=None,
        job_type=JobType.TRAINING_ONLY,
        minibatch_size=32,
        training_data=None,
        data_reader_params=None,
        records_per_task=None,
        mesh=None,
        model_params="",
        seed=0,
        callbacks=None,
        wait_sleep_secs=0.5,
        spmd=False,
        checkpoint_saver=None,
        checkpoint_dir_for_init=None,
        grad_accum_steps=1,
        retry_policy=None,
    ):
        """Connect either over gRPC (master_addr) or in-process
        (master_servicer — the test harness path, mirroring the reference's
        InProcessMaster in tests/in_process_master.py)."""
        self.worker_id = worker_id
        self.spec = model_spec
        self.job_type = job_type
        self.minibatch_size = minibatch_size
        self._channel = None
        self._master_addr = master_addr
        if master_servicer is not None:
            self._master = master_servicer
        elif master_addr:
            self._channel = build_channel(master_addr)
            self._master = MasterStub(self._channel)
        else:
            raise ValueError("need master_addr or master_servicer")
        self.trainer = Trainer(
            model_spec, mesh=mesh, model_params=model_params, seed=seed,
            grad_accum_steps=grad_accum_steps,
        )
        from elasticdl_tpu.embedding.host_bridge import attach_from_spec

        self._host_manager = attach_from_spec(self.trainer, model_spec)
        self.state = None
        self._task_data_service = TaskDataService(
            self,
            data_origin=training_data,
            data_reader_params=data_reader_params,
            custom_data_reader=model_spec.custom_data_reader,
            records_per_task=records_per_task,
            wait_sleep_secs=wait_sleep_secs,
        )
        self._timing = Timing(enabled=True, logger=logger)
        self._callbacks = callbacks or []
        self._minibatch_retry_count = 0
        self._retry_policy = retry_policy or _default_retry_policy()
        # set ONLY by the master's explicit JOB_COMPLETE signal — never
        # inferred from a transport error (see _call_master)
        self.job_complete = False
        self.rpc_retry_count = 0
        self.reconnect_count = 0
        # training-plane tracing: task_id -> the worker's `worker_task`
        # span (fetch -> report), parented under the master's
        # task_dispatch span via the Task proto's trace fields. The
        # worker's task loop is single-threaded; no lock needed.
        self._task_spans = {}
        self.losses = []
        # The reference's PS owns checkpointing (ps/servicer.py:255-270);
        # with the PS gone the worker that owns the jit state does, on the
        # same every-checkpoint_steps cadence.
        self._checkpoint_saver = checkpoint_saver
        if checkpoint_saver is not None and self._host_manager:
            checkpoint_saver.extra_state_fn = self._host_manager.flat_state
        self._checkpoint_dir_for_init = checkpoint_dir_for_init
        self.spmd = spmd
        self._spmd_ctx = None
        self._template_batch = None
        self._train_iter = None
        self._eval_iter = None
        self._eval_task_pb = None
        if spmd:
            from elasticdl_tpu.parallel.spmd import SPMDContext

            self._spmd_ctx = SPMDContext(self.trainer.mesh)
            if self._host_manager:
                # Multi-host host-spill: partition the id space over
                # hosts (embedding/host_bridge.py enable_spmd) so table
                # capacity scales with the fleet, like the reference's
                # PS pods (docs/designs/parameter_server.md:42-78).
                self._host_manager.enable_spmd(self._spmd_ctx)

    # ----------------------------------------------------------- RPC layer
    #
    # Every worker->master RPC goes through _call_master: per-RPC
    # deadlines, exponential backoff with jitter, and a bounded reconnect
    # window (common/retry.py). The old heuristic — "UNAVAILABLE from an
    # ever-connected master means the job finished" — is GONE: a
    # transient master outage (pod reschedule, journal replay) looks
    # identical to shutdown on the wire, and the heuristic silently
    # terminated every worker mid-epoch. Workers now exit only on the
    # servicer's explicit JOB_COMPLETE reason; transport errors retry
    # within the window and then fail loudly.

    def _rebuild_channel(self):
        """Drop the broken channel and dial the master fresh. A stale
        channel's subchannel can sit in connect-backoff long after a
        restarted master is serving again; a new channel connects
        immediately."""
        if self._master_addr is None:
            return
        try:
            self._channel.close()
        except Exception:
            pass
        self._channel = build_channel(self._master_addr)
        self._master = MasterStub(self._channel)

    def _call_master(self, rpc_name, request, default_after_complete=None):
        if self._channel is not None:
            def attempt():
                # resolve through self._master each attempt: a retry may
                # have rebuilt the channel and stub underneath us
                return getattr(self._master, rpc_name)(
                    request, timeout=self._retry_policy.rpc_timeout_secs
                )
        else:
            def attempt():
                return getattr(self._master, rpc_name)(request)

        if self.job_complete and default_after_complete is not None:
            # after the explicit end-of-job signal the master is ALLOWED
            # to be gone — remaining reports/polls are best-effort
            try:
                return attempt()
            except Exception as e:
                if is_transient_rpc_error(e):
                    logger.info(
                        "Master gone after JOB_COMPLETE; dropping %s",
                        rpc_name,
                    )
                    return default_after_complete
                raise

        def on_retry(attempt_idx, exc):
            self.rpc_retry_count += 1
            if self._channel is not None:
                self._rebuild_channel()

        result, attempts = retry_call(
            attempt,
            policy=self._retry_policy,
            is_retryable=is_transient_rpc_error,
            on_retry=on_retry,
            what="%s(worker %s)" % (rpc_name, self.worker_id),
        )
        if attempts and rpc_name != "register_worker":
            # the call only succeeded after transport failures: the
            # master (re)started and lost in-memory membership, so
            # re-register before continuing the task loop
            self.reconnect_count += 1
            logger.info(
                "Reconnected to master after %d retries; re-registering",
                attempts,
            )
            self.register()
        return result

    def register(self):
        try:
            self._call_master(
                "register_worker",
                pb.RegisterWorkerRequest(
                    worker_id=self.worker_id, address="",
                    num_devices=self.trainer.mesh.size,
                ),
            )
        except Exception:
            logger.warning("register_worker failed", exc_info=True)

    def get_task(self, task_type=None):
        req = pb.GetTaskRequest(worker_id=self.worker_id)
        if task_type is not None:
            req.task_type = task_type
        task = self._call_master(
            "get_task",
            req,
            default_after_complete=pb.Task(
                type=pb.NONE, reason=pb.JOB_COMPLETE
            ),
        )
        if task.type == pb.NONE and task.reason == pb.JOB_COMPLETE:
            if not self.job_complete:
                logger.info("Master signaled JOB_COMPLETE")
            self.job_complete = True
        if task.task_id and task.trace_id:
            # open this task's span under the master's dispatch span;
            # report_task_result seals it, so the span's duration IS
            # the fetch->report task execution time
            from elasticdl_tpu.observability.tracing import recorder

            span = recorder().start_span(
                "worker_task", trace_id=task.trace_id,
                parent_span_id=task.span_id, task_id=task.task_id,
                worker_id=self.worker_id,
            )
            span.event("fetched", shard=task.shard_name,
                       start=task.start, end=task.end)
            self._task_spans[task.task_id] = span
        return task

    def report_task_result(self, task_id, err_msg="", exec_counters=None):
        req = pb.ReportTaskResultRequest(
            task_id=task_id, err_message=err_msg or ""
        )
        if exec_counters:
            for k, v in exec_counters.items():
                req.exec_counters[k] = int(v)
        # piggyback the trainer's tier-health gauges (cumulative host-
        # tier drop counters) on every task report — the master turns
        # tier/-prefixed counters into TensorBoard scalars
        tier = getattr(self.trainer, "tier_health", None)
        if tier and any(tier.values()):
            for k, v in tier.items():
                req.exec_counters["tier/" + k] = int(v)
        # ... and the RPC-resilience counters as fault/ gauges, so a
        # master outage leaves a visible trace in TensorBoard
        if self.rpc_retry_count:
            req.exec_counters["fault/rpc_retries"] = self.rpc_retry_count
        if self.reconnect_count:
            req.exec_counters["fault/reconnects"] = self.reconnect_count
        span = self._task_spans.pop(task_id, None)
        if span is not None:
            span.event("reported", ok=not err_msg)
        try:
            return self._call_master(
                "report_task_result", req,
                default_after_complete=pb.Empty(),
            )
        finally:
            if span is not None:
                span.finish("ok" if not err_msg else "error")

    def report_version(self, version):
        self._call_master(
            "report_version",
            pb.ReportVersionRequest(
                worker_id=self.worker_id, model_version=int(version)
            ),
            default_after_complete=pb.Empty(),
        )

    def report_evaluation_metrics(self, outputs, labels, version):
        if not isinstance(outputs, dict):
            outputs = {"output": outputs}
        self._call_master(
            "report_evaluation_metrics",
            pb.ReportEvaluationMetricsRequest(
                worker_id=self.worker_id,
                model_version=int(version),
                model_outputs=serialize_ndarray_dict(outputs),
                labels=serialize_ndarray_dict({"labels": labels}),
            ),
            default_after_complete=pb.Empty(),
        )

    # --------------------------------------------------------- train loop

    def _task_from_pb(self, task_pb):
        from elasticdl_tpu.proto.convert import task_type_from_pb

        return Task(
            task_pb.shard_name,
            task_pb.start,
            task_pb.end,
            task_type_from_pb(task_pb.type),
            model_version=task_pb.model_version,
        )

    def _ensure_state(self, batch):
        if self.state is None:
            self.state = self.trainer.init_state(batch)
            if self._checkpoint_dir_for_init:
                from elasticdl_tpu.embedding.host_bridge import (
                    restore_with_host_state,
                )

                self.state, version = restore_with_host_state(
                    self.state,
                    self._host_manager,
                    self._checkpoint_dir_for_init,
                )
                logger.info(
                    "Restored model version %d from %s",
                    version, self._checkpoint_dir_for_init,
                )

    def _log_batch_placement(self, labels):
        """Once, before the first step: where the assembled global
        batch landed — one shard per device of the mesh's batch axes."""
        shards = labels.addressable_shards
        logger.info(
            "Worker %d global batch of %d rows placed as %d shard(s) "
            "of %d rows on devices %s", self.worker_id, labels.shape[0],
            len(shards), shards[0].data.shape[0],
            [s.device.id for s in shards],
        )

    def _record_loss(self, loss, t0):
        """Keep and log one step's loss. float() is where the host
        waits for the step, so the seconds are the step's own — the
        first one carries state init and the compile."""
        loss = float(loss)
        self.losses.append(loss)
        logger.info(
            "Worker %d step %d loss %.6f (%.3f s)", self.worker_id,
            len(self.losses), loss, time.perf_counter() - t0,
        )

    def _maybe_checkpoint(self):
        """Save on the checkpoint_steps cadence. Never raises: a transient
        save failure must not fail (or retry) the already-applied step."""
        if self._checkpoint_saver is None or self.state is None:
            return
        try:
            self._checkpoint_saver.maybe_save(self.state)
        except Exception:
            logger.warning("checkpoint save failed", exc_info=True)

    def _process_minibatch(self, batch, true_count):
        """Train one minibatch with retry (reference :870-922: up to 64
        retries; there a retry refetched the PS model after a stale-version
        reject — here retries only guard transient runtime failures)."""
        err = ""
        for attempt in range(MAX_MINIBATCH_RETRY_NUM):
            try:
                t0 = time.perf_counter()
                self._ensure_state(batch)
                self.state, loss = self.trainer.train_step(
                    self.state, batch, true_count
                )
                self._record_loss(loss, t0)
                break
            except (ValueError, TypeError):
                # deterministic failures don't heal with retries
                raise
            except Exception as e:
                err = "%s" % e
                logger.warning(
                    "minibatch failed (attempt %d): %s", attempt + 1, err
                )
                self._minibatch_retry_count += 1
        else:
            return err or "minibatch failed"
        # outside the retry region by design (see _maybe_checkpoint)
        self._maybe_checkpoint()
        return ""

    def _train_and_evaluate(self):
        evaluation_task_executed = False
        while True:
            dataset = self._task_data_service.get_dataset()
            if dataset is None:
                self._process_train_end_callback_task_if_needed()
                break
            dataset = resolve_dataset_fn(
                self.spec, self._task_data_service.data_reader
            )(
                dataset,
                Mode.TRAINING,
                self._task_data_service.data_reader.metadata,
            )
            dataset = dataset.batch(self.minibatch_size).prefetch(1)
            self._timing.start_record_time("task_process")
            stream_err = ""
            for batch in dataset:
                if self.job_type == JobType.TRAINING_WITH_EVALUATION:
                    evaluation_task_executed = (
                        self._evaluate_only() or evaluation_task_executed
                    )
                padded, n = pad_batch(batch, self.minibatch_size)
                with self._timing.record("batch_process"):
                    err_msg = self._process_minibatch(padded, n)
                if err_msg:
                    stream_err = err_msg
                else:
                    self.report_version(int(self.state.step))
                if self._task_data_service.report_record_done(n, err_msg):
                    self._timing.end_record_time("task_process")
                    self._timing.report_timing(reset=True)
                    self._timing.start_record_time("task_process")
            # stream exhausted normally: complete any tasks row-based
            # counting could not cover (cardinality-changing
            # dataset_fns, e.g. sequence packing); 1:1 families no-op.
            # Any failure in the stream propagates so those tasks are
            # retried, not silently marked successful.
            self._task_data_service.flush_record_accounting(stream_err)
            if self.job_type == JobType.TRAINING_WITH_EVALUATION:
                evaluation_task_executed = self._evaluate_only()
            self._process_train_end_callback_task_if_needed()

    def _evaluate_only(self):
        """Drain the master's eval queue (reference :1091-1110)."""
        executed = False
        while True:
            task_pb = self.get_task(pb.EVALUATION)
            if not task_pb.shard_name:
                break
            self._process_eval_task(task_pb)
            executed = True
        return executed

    def _process_eval_task(self, task_pb):
        ds = self._task_dataset(self._task_from_pb(task_pb), Mode.EVALUATION)
        err = ""
        try:
            for batch in ds:
                padded, n = pad_batch(batch, self.minibatch_size)
                self._ensure_state(padded)
                outputs, labels = self.trainer.evaluate_batch(
                    self.state, padded, n
                )
                self.report_evaluation_metrics(
                    outputs, labels, task_pb.model_version
                )
        except Exception as e:
            err = "%s" % e
            logger.error("eval task failed: %s", traceback.format_exc())
        self.report_task_result(task_pb.task_id, err)

    def _predict_only(self):
        from elasticdl_tpu.worker.prediction_outputs_processor import (
            resolve_processor,
        )

        process_outputs = resolve_processor(
            self.spec.prediction_outputs_processor
        )
        results = []
        while True:
            task_pb = self.get_task()
            if not task_pb.shard_name:
                if task_pb.type == pb.WAIT:
                    time.sleep(self._task_data_service._wait_sleep_secs)
                    continue
                break
            ds = self._task_dataset(
                self._task_from_pb(task_pb), Mode.PREDICTION
            )
            err = ""
            try:
                for batch in ds:
                    padded, n = pad_batch(batch, self.minibatch_size)
                    self._ensure_state(padded)
                    preds, _ = self.trainer.evaluate_batch(
                        self.state, padded, n
                    )
                    results.append(preds)
                    if process_outputs is not None:
                        process_outputs(preds, self.worker_id)
            except Exception as e:
                err = "%s" % e
                logger.error(
                    "prediction task failed: %s", traceback.format_exc()
                )
            self.report_task_result(task_pb.task_id, err)
        return (
            np.concatenate(results, axis=0) if results else np.array([])
        )

    def _process_train_end_callback_task_if_needed(self):
        task_pb = self._task_data_service.get_train_end_callback_task()
        if task_pb is None:
            return
        err = ""
        try:
            for cb in self._callbacks:
                if hasattr(cb, "on_train_end"):
                    cb.on_train_end(self)
        except Exception as e:
            err = "%s" % e
            logger.error(
                "train-end callback failed: %s", traceback.format_exc()
            )
        self._task_data_service.clear_train_end_callback_task()
        self.report_task_result(task_pb.task_id, err)

    # ------------------------------------------------------ SPMD lockstep

    def _poll_train(self):
        """One tri-state train poll for the ElasticSPMDLoop:
        ("item", (padded, n)) | ("wait",) | ("done",)."""
        while True:
            if self._train_iter is None:
                dataset = self._task_data_service.get_dataset()
                if dataset is None:
                    return ("done",)
                dataset = resolve_dataset_fn(
                    self.spec, self._task_data_service.data_reader
                )(
                    dataset,
                    Mode.TRAINING,
                    self._task_data_service.data_reader.metadata,
                )
                self._train_iter = iter(
                    dataset.batch(self.minibatch_size).prefetch(1)
                )
            batch = next(self._train_iter, None)
            if batch is not None:
                return ("item", pad_batch(batch, self.minibatch_size))
            self._train_iter = None
            # per-stream flush: every emitted row was already processed
            # (the loop polls the next item only after the previous
            # round ran), so tasks row-counting could not cover are
            # complete — and MUST be reported before the WAIT resume,
            # or get_dataset()'s pending-tasks guard would wedge the
            # job. Step failures raise out of loop.run() instead, so
            # success reporting is correct here.
            self._task_data_service.flush_record_accounting()
            if self._task_data_service._pending_dataset:
                return ("wait",)
            # stream ended for good: loop once more; get_dataset -> None

    def _poll_eval(self):
        """Next eval batch, fetching new eval tasks as needed. Reports a
        task's result when refilled past its last batch (the loop only
        refills after the previous item's round executed)."""
        while True:
            if self._eval_iter is not None:
                batch = next(self._eval_iter, None)
                if batch is not None:
                    return (
                        pad_batch(batch, self.minibatch_size),
                        self._eval_task_pb,
                    )
                self.report_task_result(self._eval_task_pb.task_id, "")
                self._eval_iter = None
                self._eval_task_pb = None
            task_pb = self.get_task(pb.EVALUATION)
            if not task_pb.shard_name:
                return None
            self._eval_iter = iter(
                self._task_dataset(
                    self._task_from_pb(task_pb), Mode.EVALUATION
                )
            )
            self._eval_task_pb = task_pb

    def _zero_weight_item(self):
        """A template batch with weight 0 — keeps a starved host inside the
        collective without contributing to the global weighted loss."""
        if self._template_batch is None:
            raise RuntimeError(
                "host has no batch template: it never received any data, so "
                "it cannot synthesize a padding batch for the collective"
            )
        return self._template_batch, 0

    def _task_dataset(self, task, mode):
        """Batched dataset over one task's records (shared by the eval /
        predict paths)."""
        reader = self._task_data_service.data_reader
        from elasticdl_tpu.data.dataset import Dataset

        ds = Dataset.from_generator(lambda: reader.read_records(task))
        ds = resolve_dataset_fn(self.spec, reader)(
            ds, mode, reader.metadata
        )
        return ds.batch(self.minibatch_size)

    def _spmd_step(self, item):
        from elasticdl_tpu.training.trainer import _split_label

        if item is None:
            item = self._zero_weight_item()
        padded, n = item
        features, labels = _split_label(padded)
        weights = self.trainer.make_weights(self.minibatch_size, n)
        # Host-spill prepare runs on the LOCAL features before assembly
        # (the multi-host prepare is itself a host-level collective that
        # every host must enter this round — the lockstep loop ensures
        # every host is in this call).
        prepped = self.trainer._host_prepare(features)
        gf, gl, gw = self._spmd_ctx.assemble((prepped, labels, weights))
        if not self.losses:
            self._log_batch_placement(gl)
        t0 = time.perf_counter()
        self._ensure_state(padded)
        self.state, loss = self.trainer.train_step_assembled(
            self.state, gf, gl, gw
        )
        self._maybe_checkpoint()
        if n > 0:
            self._template_batch = (features, labels)
            self._record_loss(loss, t0)
            if self._spmd_ctx.process_index == 0:
                self.report_version(int(self.state.step))
            self._task_data_service.report_record_done(n, "")

    def _run_spmd_job(self, with_train):
        """Unified lockstep job loop: eval-priority mode consensus every
        round (parallel/spmd.py ElasticSPMDLoop)."""
        from elasticdl_tpu.parallel.spmd import ElasticSPMDLoop

        with_eval = self.job_type in (
            JobType.TRAINING_WITH_EVALUATION,
            JobType.EVALUATION_ONLY,
        )
        loop = ElasticSPMDLoop(
            self._spmd_ctx,
            poll_train=self._poll_train if with_train else None,
            poll_eval=self._poll_eval if with_eval else None,
            train_step=self._spmd_step,
            eval_step=self._spmd_eval_step,
            idle_sleep_secs=min(0.2, self._task_data_service._wait_sleep_secs),
        )
        try:
            loop.run()
        except Exception as e:
            # Report in-flight tasks as failed so the master requeues them
            # promptly instead of waiting out the straggler watchdog, then
            # re-raise: a failed step desyncs the lockstep, so the job-level
            # answer is restart with a re-formed mesh (elastic recovery).
            err = "spmd step failed: %s" % e
            logger.error("%s\n%s", err, traceback.format_exc())
            if self._eval_task_pb is not None:
                self.report_task_result(self._eval_task_pb.task_id, err)
                self._eval_task_pb = None
            for task in list(
                self._task_data_service._pending_tasks
            ):
                self.report_task_result(task.task_id, err)
            raise
        self._process_train_end_callback_task_if_needed()

    def _spmd_eval_step(self, item):
        from elasticdl_tpu.training.trainer import _split_label

        if item is None:
            padded, n = self._zero_weight_item()
            task_pb = None
        else:
            (padded, n), task_pb = item
        features, labels = _split_label(padded)
        gf = self._spmd_ctx.assemble(self.trainer._host_prepare(features))
        self._ensure_state(padded)
        global_out = self.trainer.forward_assembled(self.state, gf)
        if task_pb is None:
            return
        self._template_batch = (features, labels)
        # slice the replicated global output back to this host's rows
        global_bsz = self.minibatch_size * self._spmd_ctx.num_processes
        rows = self._spmd_ctx.local_rows(global_bsz)

        def to_local(x):
            return np.asarray(x)[rows][:n]

        if isinstance(global_out, dict):
            outputs = {k: to_local(v) for k, v in global_out.items()}
        else:
            outputs = to_local(global_out)
        self.report_evaluation_metrics(
            outputs, np.asarray(labels)[:n], task_pb.model_version
        )


    def run(self):
        self.register()
        if self.job_type in (
            JobType.TRAINING_ONLY,
            JobType.TRAINING_WITH_EVALUATION,
        ):
            if self.spmd:
                self._run_spmd_job(with_train=True)
            else:
                self._train_and_evaluate()
            return self.state
        if self.job_type == JobType.EVALUATION_ONLY:
            if self.spmd:
                self._run_spmd_job(with_train=False)
            else:
                self._evaluate_only()
            return self.state
        if self.job_type == JobType.PREDICTION_ONLY:
            return self._predict_only()
        raise ValueError("Unknown job type %s" % self.job_type)

    def close(self):
        if self._channel is not None:
            self._channel.close()
