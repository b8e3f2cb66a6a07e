"""Worker process entrypoint (reference worker/main.py:33-88): parse
flags, connect to the master, run the task-driven loop. Launched by the
instance manager (k8s pod or local subprocess)."""

import sys

from elasticdl_tpu.common.args import parse_worker_args
from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.common.model_utils import (
    get_dict_from_params_str,
    get_model_spec,
)
from elasticdl_tpu.worker.worker import JobType, Worker


def build_worker(args):
    spec = get_model_spec(args.model_zoo, args.model_def)
    mesh = None
    spmd = False
    if args.distribution_strategy == "AllreduceStrategy":
        from elasticdl_tpu.parallel import mesh as mesh_lib
        from elasticdl_tpu.parallel.spmd import initialize_distributed

        initialize_distributed(
            coordinator_addr=args.coordinator_addr or None,
            num_processes=args.num_processes or None,
            process_id=args.process_id,
        )
        mesh = mesh_lib.build_mesh(args.mesh_spec or None)
        spmd = True

    checkpoint_saver = None
    if args.checkpoint_dir and args.checkpoint_steps:
        from elasticdl_tpu.checkpoint import CheckpointSaver

        checkpoint_saver = CheckpointSaver(
            args.checkpoint_dir,
            checkpoint_steps=args.checkpoint_steps,
            keep_max_version=args.keep_checkpoint_max,
        )

    job_type = {
        "training_only": JobType.TRAINING_ONLY,
        "training_with_evaluation": JobType.TRAINING_WITH_EVALUATION,
        "evaluation_only": JobType.EVALUATION_ONLY,
        "prediction_only": JobType.PREDICTION_ONLY,
    }[args.job_type]

    return Worker(
        args.worker_id,
        spec,
        master_addr=args.master_addr,
        job_type=job_type,
        minibatch_size=args.minibatch_size,
        training_data=args.training_data or None,
        data_reader_params=get_dict_from_params_str(
            args.data_reader_params
        ),
        records_per_task=args.records_per_task,
        mesh=mesh,
        model_params=args.model_params,
        seed=args.seed,
        spmd=spmd,
        checkpoint_saver=checkpoint_saver,
        checkpoint_dir_for_init=args.checkpoint_dir_for_init or None,
        grad_accum_steps=args.grad_accum_steps,
    )


def main(argv=None):
    from elasticdl_tpu.common.platform_utils import (
        configure_compile_cache,
        log_device_memory,
        log_startup,
    )

    configure_compile_cache()
    # SIGUSR2 -> all-thread stack dump: a live wedged worker can
    # always be interrogated without killing its task
    from elasticdl_tpu.observability.runtime_health import (
        install_sigusr2_dump,
    )

    install_sigusr2_dump()
    args = parse_worker_args(argv)
    logger.info(
        "Worker %d starting, master=%s", args.worker_id, args.master_addr
    )
    # name this process's span recorder; task spans export to
    # $EDL_TRACE_DIR on exit (atexit) when tracing is armed
    from elasticdl_tpu.observability.tracing import configure

    configure(service="worker:%d" % args.worker_id)
    worker = build_worker(args)
    role = "Worker %d" % args.worker_id
    devices = list(worker.trainer.mesh.devices.flat)
    # the default strategy trains on the first visible device only
    # (Trainer -> local_mesh()); the line below is where that shows
    log_startup(
        role, devices,
        mesh={k: v for k, v in worker.trainer.mesh.shape.items() if v > 1},
    )
    worker.run()
    log_device_memory(role, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
