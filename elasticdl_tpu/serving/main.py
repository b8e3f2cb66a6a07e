"""Serving process entrypoint.

Builds the sequence-family model from the zoo spec, restores the newest
checkpoint when one exists, and serves Generate/GenerateStream/
ServerStatus until SIGTERM/SIGINT — which trigger the graceful path:
admission closes (queued requests get RESOURCE_EXHAUSTED), in-flight
slots drain to completion, then the transport stops. With
--checkpoint_dir the server keeps following the directory and
hot-reloads newer versions between decode steps.

    python -m elasticdl_tpu.serving.main \\
        --model_zoo model_zoo \\
        --model_def transformer_lm.transformer_lm.custom_model \\
        --model_params "vocab_size=256; seq_len=128" \\
        --checkpoint_dir /ckpt --port 50051 --num_slots 8
"""

import argparse
import signal
import sys
import threading

import numpy as np

from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.common.model_utils import get_model_spec


def _kv_paged_flag(value):
    if value != "1":
        raise argparse.ArgumentTypeError(
            "the dense KV pool was removed in PR 30: the block-paged "
            "pool is the server's only layout (drop the flag, or pass 1)"
        )
    return 1


def parse_serving_args(args=None):
    parser = argparse.ArgumentParser(
        description="elasticdl-tpu generation server"
    )
    parser.add_argument("--model_zoo", required=True)
    parser.add_argument("--model_def", required=True)
    parser.add_argument("--model_params", default="")
    parser.add_argument("--port", type=int, default=50051)
    parser.add_argument("--num_slots", type=int, default=4)
    parser.add_argument("--queue_capacity", type=int, default=64)
    parser.add_argument("--top_k", type=int, default=0)
    parser.add_argument("--top_p", type=float, default=1.0)
    parser.add_argument("--checkpoint_dir", default="")
    parser.add_argument("--max_workers", type=int, default=64,
                        help="gRPC handler threads; size ABOVE the "
                             "expected concurrent in-flight RPCs — a "
                             "pool full of blocked generate handlers "
                             "starves server_status and the router "
                             "reads the silence as lease decay")
    parser.add_argument("--reload_poll_secs", type=float, default=2.0,
                        help="0 disables the watcher's self-upgrade "
                             "poll: checkpoints load only through the "
                             "explicit reload_checkpoint RPC (the "
                             "rollout-managed fleet mode)")
    parser.add_argument("--tensorboard_log_dir", default="")
    # selects nothing: the block-paged pool (serving/kv_pool.py) is the
    # only KV layout. Still parsed because the benchmark's files pass
    # `--kv_paged 1` (chipbench/configs/sc2-3b-serve.json and
    # tests/chipbench/test_chipbench_dropin.py, through
    # chipbench/drivers/open_loop.py) and only a `benchmark` PR may
    # edit those (ROADMAP D1b); any other value is refused here
    parser.add_argument("--kv_paged", type=_kv_paged_flag, default=1)
    parser.add_argument("--kv_block_size", type=int, default=16)
    parser.add_argument("--kv_num_blocks", type=int, default=0,
                        help="block budget; 0 = the rows of num_slots "
                             "sequences of seq_len tokens")
    # prefix sharing: -1 resolves from EDL_KV_SHARED (default on) —
    # refcounted dedupe of matching prompt prefixes
    parser.add_argument("--kv_shared", type=int, default=-1,
                        choices=(-1, 0, 1))
    # tiered host spill: byte budget for evicted prefix
    # chains demoted to host RAM and revived by upload instead of
    # re-prefill; -1 resolves from EDL_KV_HOST_BYTES, 0 = off
    parser.add_argument("--kv_host_bytes", type=int, default=-1)
    # speculative decode: a small DRAFT model proposes draft_k tokens
    # per tick, verified in one target step (token-exact with plain
    # decode)
    parser.add_argument("--draft_k", type=int, default=0)
    parser.add_argument("--draft_model_def", default="",
                        help="zoo model_def for the draft; empty = "
                             "speculative decode off")
    parser.add_argument("--draft_model_params", default="")
    # a block-diffusion model (block_causal > 1) only: the denoising
    # passes a block takes before its commit pass; 0 = the block
    # length (one position a pass). Must divide the block length
    parser.add_argument("--denoise_steps", type=int, default=0)
    # pre-READY warmup: generate this many tokens in-process before
    # printing the readiness line, so the jit compile is paid BEFORE a
    # router/autoscaler routes live traffic here (a freshly adopted
    # replica must not serve its first request cold)
    parser.add_argument("--warmup_tokens", type=int, default=0)
    # live metrics plane: Prometheus-text /metrics exposition (stdlib
    # http.server thread, observability/metrics.py); -1 resolves from
    # EDL_METRICS_PORT (unset = off), 0 = ephemeral port — the bound
    # port prints as `METRICS_READY port=N` next to the serving line
    parser.add_argument("--metrics_port", type=int, default=-1)
    # tail-forensics plane (histogram exemplars + tail-based trace
    # retention + slow-cause attribution): -1 resolves from
    # EDL_FORENSICS, default ON — priced by the bench overhead A/B
    parser.add_argument("--forensics", type=int, default=-1,
                        choices=(-1, 0, 1))
    # runtime health plane (observability/runtime_health.py):
    # recompile sentry + device-memory ledger reconciliation +
    # progress watchdog with flight recorder, self-reported through
    # ServerStatus health_state/last_progress_age_ms; -1 resolves
    # from EDL_RUNTIME_HEALTH, default ON — priced by the same bench
    # overhead A/B as the rest of the observability stack
    parser.add_argument("--runtime_health", type=int, default=-1,
                        choices=(-1, 0, 1))
    # watchdog budget: work seated but no progress (tokens OR jit
    # compiles) for this long = stalled; -1 resolves from
    # EDL_STALL_AFTER_SECS (default 10 s). Stall bundles dump to
    # $EDL_HEALTH_DIR when set.
    parser.add_argument("--stall_after_secs", type=float, default=-1.0)
    # disaggregated serving (serving/disagg.py): the phase this
    # replica advertises through ServerStatus.role — "prefill"
    # replicas are kept out of the router's normal rotation and serve
    # cache-warming handoffs only; "" resolves from EDL_SERVING_ROLE
    # (default "unified")
    parser.add_argument("--role", default="",
                        choices=("", "prefill", "decode", "unified"))
    # chunked prefill: tile size in tokens (long prompts prefill in tiles interleaved with decode steps instead
    # of monopolizing a tick); -1 resolves from
    # EDL_PREFILL_CHUNK_TOKENS, 0 = monolithic prefill
    parser.add_argument("--prefill_chunk_tokens", type=int, default=-1)
    # SLO-aware per-tick prefill budget in milliseconds (at least one
    # tile always runs; the EWMA tile price decides whether the NEXT
    # one fits); -1 resolves from EDL_PREFILL_BUDGET_MS (default 8),
    # 0 = unbounded
    parser.add_argument("--prefill_budget_ms", type=float, default=-1.0)
    return parser.parse_args(args)


def _paged_decode_choice(engine):
    """Which paged-decode implementation dispatch picks for this
    engine (the start-up log line)."""
    from elasticdl_tpu.ops.attention import paged_decode_impl

    kv = engine.kv
    arenas = kv.row_arenas()
    if not arenas:  # a model without attention layers pages nothing
        return "none"
    arena = max(arenas, key=lambda leaf: leaf.shape[-1])
    return paged_decode_impl(kv.max_blocks_per_slot, arena,
                             kv.kv_cache_dtype == "int8")


def build_server(args):
    # imports deferred so --help works without jax initialized
    import jax

    from elasticdl_tpu.checkpoint.saver import (
        get_latest_checkpoint_version,
        restore_state_from_checkpoint,
    )
    from elasticdl_tpu.common.platform_utils import (
        configure_compile_cache,
        log_startup,
    )
    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.serving.server import (
        GenerationServer,
        ServingConfig,
    )
    from elasticdl_tpu.training.trainer import Trainer

    configure_compile_cache()
    spec = get_model_spec(args.model_zoo, args.model_def)
    mesh = mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = Trainer(spec, mesh=mesh, model_params=args.model_params)
    seq_len = int(trainer.model.seq_len)
    dummy = np.zeros((1, seq_len), np.int32)
    state = trainer.init_state(({"tokens": dummy}, dummy))
    version = 0
    if args.checkpoint_dir:
        if get_latest_checkpoint_version(args.checkpoint_dir) >= 0:
            state, version = restore_state_from_checkpoint(
                state, args.checkpoint_dir, strict=False
            )
            logger.info("serving checkpoint version-%d", version)
        else:
            logger.warning(
                "no checkpoint under %r yet; serving fresh params "
                "until one lands", args.checkpoint_dir,
            )
    draft = None
    draft_k = int(args.draft_k)
    if args.draft_model_def and draft_k > 0:
        d_spec = get_model_spec(args.model_zoo, args.draft_model_def)
        d_trainer = Trainer(d_spec, mesh=mesh,
                            model_params=args.draft_model_params)
        d_len = int(d_trainer.model.seq_len)
        d_state = d_trainer.init_state(
            ({"tokens": np.zeros((1, d_len), np.int32)},
             np.zeros((1, d_len), np.int32))
        )
        draft = (d_trainer, d_state)
    server = GenerationServer(
        trainer, state,
        ServingConfig(
            num_slots=args.num_slots,
            queue_capacity=args.queue_capacity,
            top_k=args.top_k, top_p=args.top_p,
            checkpoint_dir=args.checkpoint_dir,
            reload_poll_secs=args.reload_poll_secs,
            telemetry_dir=args.tensorboard_log_dir,
            port=args.port,
            max_workers=args.max_workers,
            kv_block_size=args.kv_block_size,
            kv_num_blocks=args.kv_num_blocks,
            kv_shared=(None if args.kv_shared < 0
                       else bool(args.kv_shared)),
            kv_host_bytes=(None if args.kv_host_bytes < 0
                           else args.kv_host_bytes),
            draft_k=draft_k if draft is not None else 0,
            denoise_steps=args.denoise_steps,
            metrics_port=(None if args.metrics_port < 0
                          else args.metrics_port),
            forensics=(None if args.forensics < 0
                       else bool(args.forensics)),
            runtime_health=(None if args.runtime_health < 0
                            else bool(args.runtime_health)),
            stall_after_secs=(None if args.stall_after_secs < 0
                              else args.stall_after_secs),
            role=args.role or None,
            prefill_chunk_tokens=(None if args.prefill_chunk_tokens < 0
                                  else args.prefill_chunk_tokens),
            prefill_budget_ms=(None if args.prefill_budget_ms < 0
                               else args.prefill_budget_ms),
        ),
        draft=draft,
    )
    server.engine.model_version = version
    if server.watcher is not None:
        server.watcher.version = version
    log_startup("Serving", mesh.devices.flat,
                paged_decode=_paged_decode_choice(server.engine))
    return server


def warmup(server, tokens):
    """One in-process generate through the UNWRAPPED servicer: pays
    the jit compile (and records nothing against armed fault rules)
    before the process advertises readiness."""
    from elasticdl_tpu.proto import elasticdl_pb2 as pb

    server.raw_servicer.generate(
        pb.GenerateRequest(prompt=[1, 2], max_new_tokens=tokens)
    )
    # the compile-heavy warmup latency must never surface in the
    # percentiles a router/autoscaler SLOs on
    server.telemetry.reset_latency()
    # the runtime-health steady boundary: from here on a recompile is
    # a counted anomaly and the memory baseline is anchored
    server.mark_steady()
    logger.info("warmup complete (%d tokens)", tokens)


def main(argv=None):
    args = parse_serving_args(argv)
    # SIGUSR2 -> all-thread stack dump: a live wedged replica can
    # always be interrogated without killing it
    from elasticdl_tpu.observability.runtime_health import (
        install_sigusr2_dump,
    )

    install_sigusr2_dump()
    server = build_server(args).start()
    if args.warmup_tokens > 0:
        warmup(server, args.warmup_tokens)
    # name this process's span recorder after the bound port; spans
    # export to $EDL_TRACE_DIR on stop (plus an atexit backstop)
    from elasticdl_tpu.observability.tracing import configure

    configure(service="replica:%d" % server.port)
    done = threading.Event()

    def _graceful(_signum, _frame):
        logger.info("signal received: draining and stopping")
        done.set()

    signal.signal(signal.SIGTERM, _graceful)
    signal.signal(signal.SIGINT, _graceful)
    if server.metrics is not None:
        # same log-line discovery contract as SERVING_READY: a scraper
        # (or the supervisor's log re-read) learns the bound port here
        print("METRICS_READY port=%d" % server.metrics.port,
              flush=True)
    print("SERVING_READY port=%d" % server.port, flush=True)
    done.wait()
    server.stop(drain=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
