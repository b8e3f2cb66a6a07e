"""LocalExecutor: single-process train/eval/predict over a model-zoo spec.

Parity with the reference's elasticdl/python/elasticdl/local_executor.py
(debug path without master/PS pods) — but TPU-native: it drives the same
in-process TaskDispatcher the master uses (tasks stay the unit of work, so
local and distributed runs share semantics) and the same jit-compiled Trainer
(so "local" already means "all local TPU chips via the mesh").
"""

import json

import numpy as np

from elasticdl_tpu.common.constants import Mode
from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.data.dataset import Dataset, pad_batch
from elasticdl_tpu.common.model_utils import resolve_dataset_fn
from elasticdl_tpu.data.reader.data_reader_factory import create_data_reader
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher, TaskType
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.observability.phase_watch import PhaseWatcher
from elasticdl_tpu.training.metrics import MetricsAggregator
from elasticdl_tpu.training.trainer import Trainer


class LocalExecutor(object):
    def __init__(
        self,
        model_spec,
        training_data=None,
        validation_data=None,
        prediction_data=None,
        minibatch_size=32,
        num_epochs=1,
        records_per_task=256,
        evaluation_steps=0,
        mesh=None,
        model_params="",
        data_reader_params=None,
        seed=0,
        max_steps=None,
        checkpoint_dir=None,
        checkpoint_steps=0,
        keep_checkpoint_max=0,
        checkpoint_dir_for_init=None,
        grad_accum_steps=1,
        trainable_pattern=None,
        job_state_dir=None,
        fault_injector=None,
    ):
        from elasticdl_tpu.common.platform_utils import (
            configure_compile_cache,
        )

        # before the first backend use (Trainer builds the mesh below)
        configure_compile_cache()
        self.spec = model_spec
        self.minibatch_size = minibatch_size
        self.num_epochs = num_epochs
        self.records_per_task = records_per_task
        self.evaluation_steps = evaluation_steps
        self.max_steps = max_steps
        self._reader_params = data_reader_params or {}
        self.training_data = training_data
        self.validation_data = validation_data
        self.prediction_data = prediction_data
        self.trainer = Trainer(
            model_spec, mesh=mesh, model_params=model_params, seed=seed,
            grad_accum_steps=grad_accum_steps,
            trainable_pattern=trainable_pattern,
        )
        from elasticdl_tpu.embedding.host_bridge import attach_from_spec

        self._host_manager = attach_from_spec(self.trainer, model_spec)
        self.state = None
        self.losses = []
        # same crash-recovery machinery as the distributed master: with
        # a job_state_dir the in-process dispatcher journals task
        # lifecycle, so a killed local run resumes from where it died
        # instead of re-training completed ranges
        self._job_state_dir = job_state_dir
        # fault hooks (common/fault_injection.py): local_get_task /
        # local_report rules let drill tests delay, drop, or SIGKILL the
        # local run at the dispatch boundary
        from elasticdl_tpu.common.fault_injection import FaultInjector

        self._fault_injector = (
            fault_injector or FaultInjector.from_env()
        )
        self._checkpoint_dir_for_init = checkpoint_dir_for_init
        self._checkpoint_saver = None
        if checkpoint_dir and checkpoint_steps:
            from elasticdl_tpu.checkpoint import CheckpointSaver

            self._checkpoint_saver = CheckpointSaver(
                checkpoint_dir,
                checkpoint_steps=checkpoint_steps,
                keep_max_version=keep_checkpoint_max,
                extra_state_fn=(
                    self._host_manager.flat_state
                    if self._host_manager
                    else None
                ),
            )

    def _reader(self, data_origin):
        return create_data_reader(
            data_origin, self.records_per_task, **dict(self._reader_params)
        )

    def _make_dispatcher(self):
        def shards_of(data):
            return self._reader(data).create_shards() if data else {}

        state_store = None
        if self._job_state_dir:
            from elasticdl_tpu.master.state_store import JobStateStore

            state_store = JobStateStore(self._job_state_dir)

        return TaskDispatcher(
            shards_of(self.training_data),
            shards_of(self.validation_data),
            shards_of(self.prediction_data),
            self.records_per_task,
            self.num_epochs,
            state_store=state_store,
        )

    def _task_dataset(self, reader, task, mode):
        ds = Dataset.from_generator(lambda: reader.read_records(task))
        ds = resolve_dataset_fn(self.spec, reader)(
            ds, mode, reader.metadata
        )
        # background-thread prefetch overlaps host parsing with the
        # device step (the worker does the same — worker.py)
        return ds.batch(self.minibatch_size).prefetch(1)

    def _ensure_state(self, batch):
        if self.state is None:
            padded, _ = pad_batch(batch, self.minibatch_size)
            self.state = self.trainer.init_state(padded)
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

    def run(self):
        if self.training_data:
            return self.train()
        if self.validation_data:
            return self.evaluate()
        if self.prediction_data:
            return self.predict()
        raise ValueError("No data configured")

    def train(self):
        """The train loop, with a watcher of its open phases beside it
        (observability/phase_watch.py: this process has no health
        plane) and, at its end, what its phases cost in one line."""
        watcher = PhaseWatcher().start()
        try:
            return self._train()
        finally:
            watcher.stop()
            rec = tracing.recorder()
            logger.info("train phases: %s; gc %s",
                        json.dumps(rec.phase_snapshot(), sort_keys=True),
                        json.dumps(rec.gc_pauses()))

    def _train(self):
        dispatcher = self._make_dispatcher()
        reader = self._reader(self.training_data)
        eval_reader = (
            self._reader(self.validation_data)
            if self.validation_data
            else None
        )
        stop = False
        while not stop:
            with tracing.phase("train.task_get"):
                if self._fault_injector is not None:
                    self._fault_injector.intercept("local_get_task")
                task_id, task = dispatcher.get("local")
            if task is None:
                break
            batches = iter(self._task_dataset(reader, task, Mode.TRAINING))
            while True:
                # the loop's phases carry the step they lead up to
                seq = len(self.losses)
                with tracing.phase("train.next_batch", seq=seq):
                    batch = next(batches, None)
                if batch is None:
                    break
                with tracing.phase("train.pad", seq=seq):
                    padded, n = pad_batch(batch, self.minibatch_size)
                    self._ensure_state(padded)
                with tracing.phase("train.step", seq=seq):
                    self.state, loss = self.trainer.train_step(
                        self.state, padded, n
                    )
                with tracing.phase("train.loss_fetch", seq=seq):
                    self.losses.append(float(loss))
                if self._checkpoint_saver is not None:
                    with tracing.phase("train.checkpoint", seq=seq):
                        self._checkpoint_saver.maybe_save(self.state)
                step = int(self.state.step)
                if (
                    self.evaluation_steps
                    and eval_reader
                    and step % self.evaluation_steps == 0
                ):
                    with tracing.phase("train.eval", seq=seq):
                        metrics = self._evaluate_with_reader(eval_reader)
                    logger.info("Eval at step %d: %s", step, metrics)
                if self.max_steps and step >= self.max_steps:
                    dispatcher.stop_training = True
                    stop = True
                    break
            with tracing.phase("train.task_report"):
                if self._fault_injector is not None:
                    self._fault_injector.intercept("local_report")
                dispatcher.report(task_id, True)
        final_metrics = (
            self._evaluate_with_reader(eval_reader) if eval_reader else {}
        )
        if final_metrics:
            logger.info("Final eval: %s", final_metrics)
        return self.state, final_metrics

    def _evaluate_with_reader(self, reader):
        agg = MetricsAggregator(self.spec.eval_metrics_fn())
        for shard_name, (start, n) in reader.create_shards().items():
            from elasticdl_tpu.master.task_dispatcher import Task

            task = Task(shard_name, start, start + n, TaskType.EVALUATION)
            for batch in self._task_dataset(reader, task, Mode.EVALUATION):
                padded, n_true = pad_batch(batch, self.minibatch_size)
                self._ensure_state(padded)
                outputs, labels = self.trainer.evaluate_batch(
                    self.state, padded, n_true
                )
                agg.update(labels, outputs)
        return agg.result()

    def evaluate(self):
        reader = self._reader(self.validation_data)
        return self._evaluate_with_reader(reader)

    def predict(self):
        reader = self._reader(self.prediction_data)
        outputs = []
        for shard_name, (start, n) in reader.create_shards().items():
            from elasticdl_tpu.master.task_dispatcher import Task

            task = Task(shard_name, start, start + n, TaskType.PREDICTION)
            for batch in self._task_dataset(reader, task, Mode.PREDICTION):
                padded, n_true = pad_batch(batch, self.minibatch_size)
                self._ensure_state(padded)
                preds, _ = self.trainer.evaluate_batch(
                    self.state, padded, n_true
                )
                outputs.append(preds)
        result = np.concatenate(outputs, axis=0) if outputs else np.array([])
        if self.spec.prediction_outputs_processor is not None:
            from elasticdl_tpu.worker.prediction_outputs_processor import (
                invoke_processor,
            )

            invoke_processor(self.spec.prediction_outputs_processor, result)
        return result
