"""The `elasticdl-tpu` client CLI (reference elasticdl_client/main.py:
29-80): `zoo init|build|push`, `train`, `evaluate`, `predict`.

`train/evaluate/predict` submit a master — as a Kubernetes pod when
`--image_name` is given (reference api.train → master pod via the k8s
API), or as a local in-process master otherwise (the no-cluster path the
TPU build adds so a laptop run needs zero infra)."""

import argparse
import sys

from elasticdl_tpu.client import api
from elasticdl_tpu.common.args import (
    add_common_params,
    add_master_params,
)


def _add_zoo_init_params(parser):
    parser.add_argument(
        "--base_image", default="python:3.10",
        help="Base docker image for the zoo",
    )
    parser.add_argument(
        "--extra_pypi_index", default="", help="Extra pip index URL"
    )
    parser.add_argument(
        "--cluster_spec", default="",
        help="Cluster spec module copied into the image",
    )
    parser.add_argument("--path", default=".", help="Zoo directory")


def _add_zoo_build_params(parser):
    parser.add_argument(
        "path", nargs="?", default=".", help="Zoo directory to build"
    )
    parser.add_argument(
        "--image", required=True, help="Target docker image name"
    )


def _add_zoo_push_params(parser):
    parser.add_argument("image", help="Docker image to push")


def _add_job_params(parser):
    add_common_params(parser)
    add_master_params(parser)
    parser.add_argument(
        "--image_name", default="",
        help="Job image; empty = run the master locally (no cluster)",
    )
    parser.add_argument(
        "--master_resource_request", default="cpu=0.1,memory=1024Mi"
    )
    parser.add_argument("--master_resource_limit", default="")
    parser.add_argument("--master_pod_priority", default="")
    parser.add_argument(
        "--detach", action="store_true",
        help="Don't monitor the submitted job",
    )


def build_argument_parser():
    parser = argparse.ArgumentParser(prog="elasticdl-tpu")
    subparsers = parser.add_subparsers(dest="command")
    subparsers.required = True

    zoo_parser = subparsers.add_parser(
        "zoo", help="Manage model-zoo images"
    )
    zoo_sub = zoo_parser.add_subparsers(dest="zoo_command")
    zoo_sub.required = True
    init_p = zoo_sub.add_parser("init", help="Initialize a model zoo")
    _add_zoo_init_params(init_p)
    init_p.set_defaults(func=api.init_zoo)
    build_p = zoo_sub.add_parser("build", help="Build the zoo image")
    _add_zoo_build_params(build_p)
    build_p.set_defaults(func=api.build_zoo)
    push_p = zoo_sub.add_parser("push", help="Push the zoo image")
    _add_zoo_push_params(push_p)
    push_p.set_defaults(func=api.push_zoo)

    train_p = subparsers.add_parser("train", help="Submit a training job")
    _add_job_params(train_p)
    train_p.set_defaults(func=api.train)

    eval_p = subparsers.add_parser(
        "evaluate", help="Submit an evaluation job"
    )
    _add_job_params(eval_p)
    eval_p.set_defaults(func=api.evaluate)

    pred_p = subparsers.add_parser(
        "predict", help="Submit a prediction job"
    )
    _add_job_params(pred_p)
    pred_p.set_defaults(func=api.predict)
    return parser


def main(argv=None):
    from elasticdl_tpu.common.platform_utils import (
        configure_compile_cache,
    )

    configure_compile_cache()
    parser = build_argument_parser()
    args, extra = parser.parse_known_args(args=argv)
    return args.func(args, extra) or 0


if __name__ == "__main__":
    sys.exit(main())
