"""Master process entrypoint (reference master/main.py:20-24 +
Master._create_instance_manager, master.py:387-534): parse flags, build
the Master with an instance manager, reconstruct worker command lines
from the parsed args, serve until the job finishes."""

import sys

from elasticdl_tpu.common.args import (
    MASTER_ONLY_ARGS,
    build_arguments_from_parsed_result,
    parse_master_args,
    parse_resource_spec,
)
from elasticdl_tpu.common import job_status
from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.common.model_utils import get_model_spec
from elasticdl_tpu.master.master import Master

def _infer_job_type(args):
    if args.prediction_data and not args.training_data:
        return "prediction_only"
    if args.validation_data and not args.training_data:
        return "evaluation_only"
    if args.validation_data:
        return "training_with_evaluation"
    return "training_only"


def build_worker_args(args, master_addr):
    worker_args = build_arguments_from_parsed_result(
        args, filter_args=MASTER_ONLY_ARGS
    )
    worker_args += [
        "--master_addr", master_addr,
        "--job_type", _infer_job_type(args),
    ]
    return worker_args


def create_instance_manager(args, task_d, master_port):
    """K8s pods when a worker image is configured, local subprocesses
    otherwise (the no-cluster path)."""
    if args.num_workers <= 0:
        return None
    if args.worker_image:
        from elasticdl_tpu.common.k8s_client import (
            Client,
            get_master_pod_name,
        )
        from elasticdl_tpu.master.instance_manager import (
            K8sInstanceManager,
        )

        # worker pods dial the master pod by its stable in-cluster name,
        # never localhost (that would be the worker's own netns)
        worker_args = build_worker_args(
            args,
            "%s:%d" % (get_master_pod_name(args.job_name), master_port),
        )
        manager_holder = {}

        def event_cb(event):
            manager = manager_holder.get("m")
            if manager is not None:
                manager.event_cb(event)

        client = Client(
            image_name=args.worker_image,
            namespace=args.namespace,
            job_name=args.job_name,
            event_callback=event_cb,
            cluster_spec=args.cluster_spec,
        )
        volume = None
        if args.volume:
            volume = parse_resource_spec(args.volume)
        manager = K8sInstanceManager(
            task_d,
            num_workers=args.num_workers,
            worker_command=["python", "-m", "elasticdl_tpu.worker.main"],
            worker_args=worker_args,
            k8s_client=client,
            resource_request=parse_resource_spec(
                args.worker_resource_request
            ),
            resource_limit=parse_resource_spec(args.worker_resource_limit),
            pod_priority=args.worker_pod_priority,
            restart_policy=args.restart_policy,
            image_pull_policy=args.image_pull_policy,
            volume=volume,
            relaunch_on_worker_failure=args.relaunch_on_worker_failure,
            disable_relaunch=args.disable_relaunch,
        )
        manager_holder["m"] = manager
        return manager
    from elasticdl_tpu.master.instance_manager import LocalInstanceManager

    return LocalInstanceManager(
        task_d,
        num_workers=args.num_workers,
        worker_args=build_worker_args(
            args, "localhost:%d" % master_port
        ),
        relaunch_on_worker_failure=args.relaunch_on_worker_failure,
        disable_relaunch=args.disable_relaunch,
    )


def main(argv=None):
    from elasticdl_tpu.common.platform_utils import (
        configure_compile_cache,
    )

    # the master itself never touches a device; this fixes the
    # directory its worker subprocesses inherit
    configure_compile_cache()
    # SIGUSR2 -> all-thread stack dump: a live wedged master can
    # always be interrogated without killing the job
    from elasticdl_tpu.observability.runtime_health import (
        install_sigusr2_dump,
    )

    install_sigusr2_dump()
    args = parse_master_args(argv)
    status_file = getattr(args, "job_status_file", "")
    job_status.write_job_status(status_file, job_status.PENDING)
    try:
        rc = _run_master(args, status_file)
    except BaseException:
        job_status.write_job_status(status_file, job_status.FAILED)
        raise
    job_status.write_job_status(
        status_file,
        job_status.SUCCEEDED if rc == 0 else job_status.FAILED,
    )
    return rc


def _validate_dataset_fn(spec, args):
    """Specs may omit dataset_fn only when the configured data reader
    derives one from its schema (model_utils.resolve_dataset_fn). Check
    at SUBMISSION time — the reader type is already known here — so a
    misconfiguration fails the master fast instead of crash-looping
    every worker on its first task."""
    if spec.dataset_fn is not None:
        return
    from elasticdl_tpu.common.model_utils import resolve_dataset_fn
    from elasticdl_tpu.data.reader.data_reader_factory import (
        build_data_reader,
    )

    data = (args.training_data or args.validation_data
            or args.prediction_data)
    reader = build_data_reader(
        data, args.records_per_task, args.data_reader_params,
        custom_data_reader=spec.custom_data_reader,
    )
    resolve_dataset_fn(spec, reader)


def _expose_tensorboard(instance_manager):
    """Cluster path only: publish the master's TensorBoard through a
    LoadBalancer service (reference k8s_tensorboard_client.py), waiting
    for the ingress IP on a daemon thread so master startup is not
    blocked."""
    import threading

    from elasticdl_tpu.common.k8s_tensorboard_client import (
        TensorBoardClient,
    )

    k8s_cli = getattr(instance_manager, "_client", None)
    if k8s_cli is None:
        return
    threading.Thread(
        target=lambda: TensorBoardClient(
            client=k8s_cli
        ).start_tensorboard_service(),
        daemon=True,
        name="tensorboard-exposure",
    ).start()


def _run_master(args, status_file=""):
    spec = get_model_spec(args.model_zoo, args.model_def)
    _validate_dataset_fn(spec, args)
    callbacks_list = None
    if spec.callbacks_fn is not None:
        from elasticdl_tpu.api.callbacks import CallbackList

        callbacks_list = CallbackList(spec.callbacks_fn())

    tensorboard_service = None
    if args.need_tensorboard:
        from elasticdl_tpu.master.tensorboard_service import (
            TensorboardService,
        )

        tensorboard_service = TensorboardService(
            args.tensorboard_log_dir or "/tmp/elasticdl_tb"
        )

    master = Master(
        spec,
        training_data=args.training_data or None,
        validation_data=args.validation_data or None,
        prediction_data=args.prediction_data or None,
        minibatch_size=args.minibatch_size,
        records_per_task=args.records_per_task,
        num_epochs=args.num_epochs,
        evaluation_steps=args.evaluation_steps,
        eval_start_delay_secs=args.eval_start_delay_secs,
        eval_throttle_secs=args.eval_throttle_secs,
        port=args.port,
        task_timeout_check_interval=args.task_timeout_check_interval,
        callbacks_list=callbacks_list,
        export_saved_model=args.export_saved_model,
        tensorboard_service=tensorboard_service,
        checkpoint_dir_for_init=args.checkpoint_dir_for_init,
        job_state_dir=args.job_state_dir or None,
    )
    if master.state_store and master.state_store.is_job_complete():
        # a relaunched master over a finished job: report success and
        # exit instead of re-serving an empty dispatcher
        logger.info("Job already complete per %s; nothing to do",
                    args.job_state_dir)
        return 0
    # gRPC port is bound in prepare(); the instance manager needs the
    # final address, so wire it afterwards.
    master.prepare()
    instance_manager = create_instance_manager(
        args, master.task_d, master.port
    )
    master.instance_manager = instance_manager
    if instance_manager:
        instance_manager.start_workers()
    if tensorboard_service is not None and args.worker_image:
        _expose_tensorboard(instance_manager)
    logger.info("Master ready on port %d", master.port)
    # name this process's span recorder; dispatch spans export to
    # $EDL_TRACE_DIR on exit (atexit) when tracing is armed
    from elasticdl_tpu.observability.tracing import configure

    configure(service="master:%d" % master.port)
    metrics = _start_metrics(args, master)
    job_status.write_job_status(status_file, job_status.RUNNING)
    try:
        return master.run()
    finally:
        if metrics is not None:
            metrics.close()


def _start_metrics(args, master):
    """The master's /metrics exposition (--metrics_port /
    EDL_METRICS_PORT, off by default): task-queue pressure, model
    version and the crash-recovery counters — the training-plane
    corner of the same scrape surface the serving fleet exposes."""
    from elasticdl_tpu.observability.metrics import (
        MetricsServer,
        counter_family,
        gauge_family,
        metrics_port_default,
    )

    port = (metrics_port_default() if args.metrics_port < 0
            else args.metrics_port)
    if port is None:
        return None

    def collect():
        todo, doing, eval_todo = master.task_d.queue_depths()
        restarts = (master.state_store.restart_count
                    if master.state_store else 0)
        return [
            gauge_family("edl_master_tasks_todo",
                         "training tasks queued", [({}, todo)]),
            gauge_family("edl_master_tasks_doing",
                         "training tasks dispatched and in flight",
                         [({}, doing)]),
            gauge_family("edl_master_eval_tasks_todo",
                         "evaluation tasks queued", [({}, eval_todo)]),
            gauge_family("edl_master_model_version",
                         "dispatcher model version",
                         [({}, master.task_d.model_version)]),
            counter_family("edl_master_restarts_total",
                           "master crash recoveries", restarts),
            counter_family(
                "edl_master_recovery_requeued_tasks_total",
                "doing-tasks requeued by journal recovery",
                master.task_d.requeued_on_recovery,
            ),
        ]

    server = MetricsServer(collect, port=port)
    logger.info("Master /metrics exposition on port %d", server.port)
    print("METRICS_READY port=%d" % server.port, flush=True)
    return server


if __name__ == "__main__":
    sys.exit(main())
