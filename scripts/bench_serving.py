#!/usr/bin/env python
"""Closed-loop serving load generator: the serving-throughput entry in
the bench trajectory (BENCH_* record family).

Runs the real stack in one process — GenerationServer (continuous-
batching engine + gRPC transport) and a Poisson open-loop arrival
process of streaming clients with mixed prompt/output lengths — and
emits ONE JSON line:

    {"metric": "serving_goodput_tokens_per_sec", "value": ...,
     "ttft_ms": {"p50": ..., "p99": ...}, "latency_ms": {...},
     "tokens_per_sec": ..., "goodput_rps": ..., "rejected": ...,
     "expired": ..., "kv": {...}, ...}

* TTFT is measured at the FIRST streamed chunk (prefill + queueing);
  all percentiles run through the shared log-linear histogram code
  (elasticdl_tpu/observability/histogram.py) — the same definition
  the live ServerStatus/router_status percentile fields report, whose
  server-side view of the run is echoed under "server_ttft_ms" /
  "server_queue_wait_ms";
* tokens_per_sec counts only tokens of COMPLETED requests over the
  measurement wall; goodput_rps is completed requests per second —
  rejected (backpressure) and expired (deadline) requests score zero,
  which is what makes overload visible as a goodput plateau;
* arrivals are open-loop Poisson (exponential gaps at --rate), so
  backpressure actually engages instead of the clients self-throttling;
* the "kv" block records the memory-efficiency trajectory: bytes
  resident in the pool at peak, average KV bytes per generated token,
  block budget and admitted-vs-rejected under it.

--compare_paged runs the SAME arrival plan several ways — the dense
pool, the block-paged pool (serving/kv_pool.py) with prefix sharing
OFF, the paged pool with prefix sharing ON (plus speculative decode
when --draft_k > 0), and with --kv_cache_dtype int8 an INT8-ARENA leg
(quantized block storage, deferred dequantize in the paged scan) —
all holding the SAME total KV bytes (the int8 leg pays its budget in
~2-3x as many smaller blocks) — and nests the records plus headline
ratios under "paged" / "paged_shared" / "paged_shared_spec" /
"paged_int8" / "paged_vs_dense" / "shared_vs_paged" /
"spec_vs_shared" / "int8_vs_shared" (the last with a greedy-match
rate against the int8 DENSE oracle). That A/B is the
`make serve-smoke` shape: equal HBM, more admissible concurrency,
deduped prefixes converting into admitted slots, and quantized
arenas compounding on top.

--shared_prefix switches the workload to the system-prompt shape the
sharing is FOR: every prompt = one of --prefix_pool common prefixes of
--prefix_len tokens + a random --suffix_len suffix. --draft_k k seats
a draft model (--draft_params; default = the target's params, i.e.
self-draft — the acceptance ceiling) and verifies k drafted tokens
per tick. --shared_prefix also runs the ROUTER-tier prefix-affinity
A/B ("affinity_ab"): the same shape through a real two-replica fleet
behind the Router, fingerprint-affine dispatch ON vs OFF — fleet
re-paid prefix prefill tokens and warm TTFT percentiles.

Defaults are CPU-smoke sized; on hardware raise --requests/--rate and
the model dims.

Usage:
    python scripts/bench_serving.py --requests 32 --rate 16 \
        --num_slots 4 --compare_paged --out BENCH_SERVING.json
"""

import argparse
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--rate", type=float, default=16.0,
                   help="mean arrival rate, requests/sec (Poisson)")
    p.add_argument("--ramp", default="",
                   help="piecewise-Poisson load profile r1:t1,r2:t2,"
                        "... (rate req/s : duration secs per phase); "
                        "overrides --rate/--requests and records "
                        "per-phase percentiles — the SAME generator "
                        "the autoscale drill ramps with")
    p.add_argument("--num_slots", type=int, default=4)
    p.add_argument("--queue_capacity", type=int, default=16)
    p.add_argument("--prompt_len", default="2:6",
                   help="min:max prompt tokens (uniform)")
    p.add_argument("--out_len", default="4:12",
                   help="min:max generated tokens (uniform)")
    p.add_argument("--deadline_ms", type=int, default=0,
                   help="per-request deadline budget; 0 = none")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--model_params", default=(
        "vocab_size=32; seq_len=32; embed_dim=32; num_heads=2; "
        "num_layers=1"
    ))
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default="",
                   help="also write the JSON record to this path")
    # KV pool layout (serving/kv_pool.py)
    p.add_argument("--kv_paged", type=int, default=0,
                   help="1 = serve from the block-paged KV pool")
    p.add_argument("--kv_block_size", type=int, default=4)
    p.add_argument("--kv_num_blocks", type=int, default=0,
                   help="block budget; 0 = dense-equivalent bytes for "
                        "--num_slots")
    p.add_argument("--paged_slots", type=int, default=0,
                   help="slot count for the paged side of "
                        "--compare_paged; 0 = 2x --num_slots")
    p.add_argument("--compare_paged", action="store_true",
                   help="A/B the dense pool vs the paged pool (shared "
                        "off AND on) at EQUAL total KV bytes; nests "
                        "the paged/paged_shared records")
    p.add_argument("--kv_shared", type=int, default=1,
                   help="1 = refcounted prefix sharing in the paged "
                        "pool (single-run mode; --compare_paged runs "
                        "both)")
    # shared-prefix workload: common system prompts + random suffixes
    p.add_argument("--shared_prefix", action="store_true",
                   help="draw prompts as <common prefix> + <random "
                        "suffix> instead of fully random")
    p.add_argument("--prefix_len", type=int, default=16,
                   help="tokens in each common system prompt")
    p.add_argument("--prefix_pool", type=int, default=2,
                   help="distinct system prompts in the pool")
    p.add_argument("--suffix_len", default="1:4",
                   help="min:max per-request suffix tokens (uniform)")
    # speculative decode (paged+shared leg / single paged run)
    p.add_argument("--draft_k", type=int, default=0,
                   help="draft tokens per tick; 0 = speculative "
                        "decode off")
    p.add_argument("--draft_params", default="",
                   help="draft model_params; empty = the target's "
                        "(self-draft: the acceptance ceiling)")
    # int8 KV arenas (model kv_cache_dtype): single-run mode serves
    # the whole run quantized; with --compare_paged this adds an
    # int8-arena leg at EQUAL KV BYTES (more blocks, not fewer bytes)
    # plus an int8_vs_shared ratio block with a greedy-match rate
    # against the int8 DENSE oracle (offline decode on the same
    # quantized model)
    p.add_argument("--kv_cache_dtype", default="",
                   choices=("", "int8"))
    # the loop's phase spans (observability/tracing.py `phase`; always
    # on in the server): the run records each phase's p50/p99/count
    # under "profile" — tick.upload / tick.dispatch / tick.fetch /
    # prefill / prompt_write / suffix_tile / draft / revive_upload ...
    # — and scrapes /metrics once
    p.add_argument("--profile", action="store_true")
    # observability overhead A/B: run the paged+shared leg twice —
    # plane OFF (no /metrics server, no forensics, no runtime health)
    # vs ON (all three; the live exposition being scraped is the serve
    # path under test) — and assert the ON leg's tokens/sec within
    # OVERHEAD_BOUND of OFF. The phase spans ride both legs: they have
    # no switch
    p.add_argument("--overhead_ab", action="store_true")
    # tiered host spill (serving/kv_pool.py): host-tier capacity in
    # BLOCKS (converted to bytes at the serving rig's exact
    # block_bytes). Single-run mode arms the tier directly; with
    # --compare_paged AND --shared_prefix it also runs the
    # EVICTION-PRESSURE A/B: the same shared-prefix plan over a
    # device pool deliberately sized below the prefix working set,
    # once with the host tier off (every evicted chain re-pays
    # prefill) and once on (evicted chains revive by upload), at
    # equal DEVICE KV bytes — the "host_vs_evict" ratio block
    p.add_argument("--kv_host_blocks", type=int, default=0)
    # the disaggregation A/B (serving/disagg.py): the same open-loop
    # plan of long COLD prompts through a real two-replica in-process
    # fleet behind the Router, three ways at EQUAL FLEET KV BYTES —
    # monolithic prefill, chunked prefill, and chunked + phase-split
    # (dedicated prefill replica handing chains to the decode replica
    # over TransferChain) — each leg with its own slowest-TTFT-decile
    # cause breakdown (the "disagg_ab" record block)
    p.add_argument("--disagg", action="store_true")
    return p.parse_args(argv)


def _span(text):
    lo, _, hi = text.partition(":")
    lo, hi = int(lo), int(hi or lo)
    if not 1 <= lo <= hi:
        raise ValueError("bad span %r" % text)
    return lo, hi


def parse_ramp(spec):
    """'r1:t1,r2:t2,...' -> [(rate_rps, duration_secs), ...]. The one
    ramp grammar the bench and scripts/run_autoscale_drill.py share —
    one load generator, so a drill phase and a bench phase mean the
    same arrival process."""
    phases = []
    for part in spec.split(","):
        rate_text, _, secs_text = part.strip().partition(":")
        rate, secs = float(rate_text), float(secs_text)
        if rate <= 0 or secs <= 0:
            raise ValueError("bad ramp phase %r in %r" % (part, spec))
        phases.append((rate, secs))
    if not phases:
        raise ValueError("empty ramp spec %r" % spec)
    return phases


def ramp_arrivals(phases, rs):
    """Open-loop piecewise-Poisson arrival plan: [(offset_secs,
    phase_index), ...] with exponential gaps at each phase's rate,
    phase boundaries at the cumulative durations."""
    out = []
    t0 = 0.0
    for idx, (rate, secs) in enumerate(phases):
        t = t0 + float(rs.exponential(1.0 / rate))
        while t < t0 + secs:
            out.append((t, idx))
            t += float(rs.exponential(1.0 / rate))
        t0 += secs
    return out


# percentiles go through the SAME log-linear histogram code the live
# telemetry and the status RPCs use (observability/histogram.py), so a
# bench p99 and a ServerStatus p99 are definitionally the same number
# — not a sorted-list math that drifts from the serving-side buckets
from elasticdl_tpu.observability.histogram import percentiles  # noqa: E402


def build_rig(args, model_params=None):
    """The trainer/state every A/B side shares (same params -> the
    dense and paged runs serve identical token streams), plus the
    draft rig when --draft_k asks for speculative decode.
    `model_params` overrides args.model_params (the int8-arena leg
    builds a second rig with kv_cache_dtype='int8' — the knob changes
    only the cache buffers, so the same seed yields the same
    weights)."""
    import jax
    import numpy as np

    from elasticdl_tpu.common.model_utils import (
        load_model_spec_from_module,
    )
    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.training.trainer import Trainer
    from model_zoo.transformer_lm import transformer_lm as zoo

    mesh = mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1])

    def one(params):
        trainer = Trainer(
            load_model_spec_from_module(zoo), mesh=mesh,
            model_params=params,
        )
        seq_len = int(trainer.model.seq_len)
        dummy = np.zeros((1, seq_len), np.int32)
        return trainer, trainer.init_state(({"tokens": dummy}, dummy))

    trainer, state = one(model_params or args.model_params)
    draft = None
    if args.draft_k > 0:
        draft = one(args.draft_params or args.model_params)
    return trainer, state, draft


def block_bytes_for(trainer, block_size):
    """Per-block arena bytes for this model's KV row leaves at their
    OWN dtypes — the same sum PagedKVPool computes, so the equal-byte
    block budgets below are exact (int8 rows + f32 scale leaves, not a
    homogeneous-dtype guess)."""
    import jax
    import numpy as np

    from elasticdl_tpu.api.generation import (
        _decode_cache,
        _kv_shapes_for,
        kv_row_leaf,
    )

    seq_len = int(trainer.model.seq_len)
    kv_shapes = _kv_shapes_for(
        _decode_cache(trainer), trainer.model, 1
    )
    return int(sum(
        np.dtype(leaf.dtype).itemsize * block_size
        * leaf.shape[1] * leaf.shape[3]
        for leaf in jax.tree.leaves(kv_shapes)
        if kv_row_leaf(leaf, seq_len)
    ))


def build_plan(args, seq_len, vocab):
    import numpy as np

    o_lo, o_hi = _span(args.out_len)
    rs = np.random.RandomState(args.seed)
    if args.shared_prefix:
        # the system-prompt workload: every request = one of a small
        # pool of common prefixes + a short random suffix — what the
        # refcounted prefix index dedupes to one resident chain
        s_lo, s_hi = _span(args.suffix_len)
        if args.prefix_len + s_hi + o_hi > seq_len:
            raise SystemExit(
                "prefix_len %d + suffix max %d + out max %d exceeds "
                "seq_len %d"
                % (args.prefix_len, s_hi, o_hi, seq_len)
            )
        pool = [
            rs.randint(0, vocab, size=args.prefix_len)
            for _ in range(max(1, args.prefix_pool))
        ]

        def prompt(i):
            suffix = rs.randint(0, vocab,
                                size=rs.randint(s_lo, s_hi + 1))
            return np.concatenate([pool[i % len(pool)], suffix])
    else:
        p_lo, p_hi = _span(args.prompt_len)
        if p_hi + o_hi > seq_len:
            raise SystemExit(
                "prompt_len max %d + out_len max %d exceeds seq_len %d"
                % (p_hi, o_hi, seq_len)
            )

        def prompt(i):
            return rs.randint(0, vocab,
                              size=rs.randint(p_lo, p_hi + 1))

    if args.ramp:
        # piecewise-Poisson ramp: the arrival schedule fixes both the
        # request count and each request's phase tag
        arrivals = ramp_arrivals(parse_ramp(args.ramp), rs)
        gaps = [
            at - (arrivals[i - 1][0] if i else 0.0)
            for i, (at, _phase) in enumerate(arrivals)
        ]
        return [
            {
                "prompt": prompt(i),
                "new": int(rs.randint(o_lo, o_hi + 1)),
                "gap": float(gaps[i]),
                "seed": int(i),
                "phase": int(arrivals[i][1]),
            }
            for i in range(len(arrivals))
        ]
    return [
        {
            "prompt": prompt(i),
            "new": int(rs.randint(o_lo, o_hi + 1)),
            "gap": float(rs.exponential(1.0 / args.rate)),
            "seed": int(i),
            "phase": None,
        }
        for i in range(args.requests)
    ]


def run_load(args, trainer, state, plan, num_slots, kv_paged,
             kv_block_size, kv_num_blocks, kv_shared=False,
             draft=None, draft_k=0, kv_host_bytes=0, profile=False,
             metrics_port=None, forensics=True, runtime_health=True):
    import jax

    from elasticdl_tpu.observability.tracing import (
        new_trace_id,
        recorder,
    )
    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.proto.service import ServingStub, build_channel
    from elasticdl_tpu.serving import GenerationServer, ServingConfig

    if profile:
        # the phase histograms are the process's, cumulative: this
        # leg's block starts from zero
        recorder().clear_phases()
    server = GenerationServer(
        trainer, state,
        ServingConfig(
            num_slots=num_slots,
            queue_capacity=args.queue_capacity,
            kv_paged=kv_paged,
            kv_block_size=kv_block_size,
            kv_num_blocks=kv_num_blocks,
            kv_shared=kv_shared,
            draft_k=draft_k if draft is not None else 0,
            kv_host_bytes=kv_host_bytes,
            metrics_port=metrics_port,
            forensics=forensics,
            runtime_health=runtime_health,
        ),
        draft=draft,
    ).start()
    stub = ServingStub(build_channel("localhost:%d" % server.port))

    # one warmup request outside the measurement: pays the jit compiles
    stub.generate(
        pb.GenerateRequest(prompt=[1, 2], max_new_tokens=2), timeout=300
    )
    # the runtime-health steady boundary: every compile from here on
    # of an ALREADY-COMPILED executable is a counted anomaly — the
    # "churn never recompiles" invariant this bench asserts at zero.
    # (First compiles of new bucket names mid-run are the cold path
    # working as designed and stay legal.)
    server.mark_steady()

    results = []
    lock = threading.Lock()

    def one(spec):
        t0 = time.monotonic()
        # mint the trace client-side (the server adopts inbound trace
        # context), so the bench can join its own latency rows back to
        # the in-process span trees — the --ramp tail_report path
        trace_id = new_trace_id()
        row = {"status": "OK", "tokens": 0, "ttft_ms": None,
               "phase": spec.get("phase"), "spec": spec,
               "out_tokens": [], "trace_id": trace_id}
        try:
            stream = stub.generate_stream(
                pb.GenerateRequest(
                    prompt=[int(t) for t in spec["prompt"]],
                    max_new_tokens=spec["new"],
                    temperature=args.temperature,
                    seed=spec["seed"],
                    deadline_ms=args.deadline_ms,
                    trace_id=trace_id,
                ),
                timeout=300,
            )
            for chunk in stream:
                if row["ttft_ms"] is None and chunk.tokens:
                    row["ttft_ms"] = (time.monotonic() - t0) * 1000.0
                row["tokens"] += len(chunk.tokens)
                row["out_tokens"].extend(int(t) for t in chunk.tokens)
        except Exception as e:  # noqa: BLE001 - status is the datum
            code = getattr(e, "code", None)
            row["status"] = (
                code().name if callable(code) else type(e).__name__
            )
        row["latency_ms"] = (time.monotonic() - t0) * 1000.0
        with lock:
            results.append(row)

    threads = []
    bench_t0 = time.monotonic()
    for spec in plan:
        time.sleep(spec["gap"])
        t = threading.Thread(target=one, args=(spec,))
        t.start()
        threads.append(t)
    for t in threads:
        t.join(timeout=600)
    wall = time.monotonic() - bench_t0

    status = stub.server_status(pb.ServerStatusRequest(), timeout=30)
    health_snap = (server.health.snapshot()
                   if server.health is not None else None)
    profile_snap = recorder().phase_snapshot() if profile else None
    scrape = None
    if server.metrics is not None:
        # one real scrape through the stdlib HTTP server, validated by
        # the INDEPENDENT parser — the exposition is part of the path
        # under test, not a decoration
        import urllib.request

        from elasticdl_tpu.observability.promparse import (
            parse_prometheus_text,
        )

        text = urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % server.metrics.port,
            timeout=10,
        ).read().decode("utf-8")
        fams = parse_prometheus_text(text)
        scrape = {
            "families": len(fams),
            "samples": sum(len(f["samples"]) for f in fams.values()),
        }
    server.stop()

    ok = [r for r in results if r["status"] == "OK"]
    ttfts = [r["ttft_ms"] for r in ok if r["ttft_ms"] is not None]
    lats = [r["latency_ms"] for r in ok]
    tokens_ok = sum(r["tokens"] for r in ok)
    record = {
        "metric": "serving_goodput_tokens_per_sec",
        "value": round(tokens_ok / wall, 3) if wall else None,
        "unit": "tokens/sec",
        "platform": jax.default_backend(),
        "requests": len(plan),
        "rate_rps": args.rate,
        "ramp": args.ramp or None,
        "num_slots": num_slots,
        "queue_capacity": args.queue_capacity,
        "completed": len(ok),
        "rejected": sum(
            1 for r in results if r["status"] == "RESOURCE_EXHAUSTED"
        ),
        "expired": sum(
            1 for r in results if r["status"] == "DEADLINE_EXCEEDED"
        ),
        "goodput_rps": round(len(ok) / wall, 3) if wall else None,
        "tokens_per_sec": round(tokens_ok / wall, 3) if wall else None,
        "ttft_ms": percentiles(ttfts, (50, 90, 99)),
        "latency_ms": percentiles(lats, (50, 90, 99)),
        # the server's own histogram view of the same run (ServerStatus
        # percentile fields) — same bucket scheme as the client-side
        # numbers above
        "server_ttft_ms": {
            "p50": round(status.ttft_p50_ms, 3),
            "p90": round(status.ttft_p90_ms, 3),
            "p99": round(status.ttft_p99_ms, 3),
        },
        "server_queue_wait_ms": {
            "p50": round(status.queue_wait_p50_ms, 3),
            "p90": round(status.queue_wait_p90_ms, 3),
            "p99": round(status.queue_wait_p99_ms, 3),
        },
        "wall_secs": round(wall, 3),
        "max_active_slots": status.max_active_slots,
        "server_tokens_generated": status.tokens_generated,
        # memory-efficiency fields: the paged-vs-dense trajectory
        "kv": {
            "paged": bool(status.kv_paged),
            "shared": bool(status.kv_shared),
            "cache_dtype": status.kv_cache_dtype,
            "block_size": status.kv_block_size,
            "blocks_total": status.kv_blocks_total,
            "bytes_total": status.kv_bytes_total,
            "bytes_in_use_peak": status.kv_bytes_in_use_peak,
            "bytes_per_token": round(status.kv_bytes_per_token, 1),
            "admitted": status.admitted,
            "rejected": status.rejected,
            "prefix_hit_tokens": status.prefix_hit_tokens,
            "cow_copies": status.cow_copies,
            # tiered host spill (zeros with the tier off)
            "host_blocks": status.kv_host_blocks,
            "host_bytes": status.kv_host_bytes,
            "revive_uploads": status.revive_uploads,
            "prefill_tokens_revived": status.prefill_tokens_revived,
            "host_drops": status.host_drops,
            # windowed warm-capacity signal (time-series ring)
            "prefix_hit_rate_window": round(
                status.prefix_hit_rate_window, 4
            ),
        },
        # speculative-decode economy (zeros when --draft_k is off)
        "draft": {
            "k": status.draft_k,
            "proposed": status.draft_proposed,
            "accepted": status.draft_accepted,
            "accept_rate": round(
                status.draft_accepted / status.draft_proposed, 3
            ) if status.draft_proposed else 0.0,
        },
    }
    if health_snap is not None:
        # the runtime health plane's own verdict on the run: total
        # compiles, post-boundary recompiles (must be 0 — main()
        # gates on it), the watchdog state and the accountant's peak
        # unaccounted drift
        record["health"] = {
            "jit_compiles": health_snap["jit_compiles"],
            "recompiles": health_snap["recompiles"],
            "steady_recompiles": health_snap["steady_recompiles"],
            "health_state": health_snap["health_state"],
            "stalls": health_snap["stalls"],
            "memory_unaccounted_bytes":
                health_snap["memory_unaccounted_bytes"],
        }
    if profile_snap is not None:
        # the loop's phase spans: p50/p99/count per phase
        # (tracing.SpanRecorder.phase_snapshot shape)
        record["profile"] = profile_snap
    if scrape is not None:
        record["metrics_scrape"] = scrape
    if args.ramp:
        # per-phase percentiles: one entry per ramp phase, same
        # histogram code as everything else — the autoscale drill's
        # per-transition SLO reads exactly this shape
        record["phases"] = []
        for idx, (rate, secs) in enumerate(parse_ramp(args.ramp)):
            rows = [r for r in results if r["phase"] == idx]
            rows_ok = [r for r in rows if r["status"] == "OK"]
            record["phases"].append({
                "phase": idx,
                "rate_rps": rate,
                "secs": secs,
                "requests": len(rows),
                "completed": len(rows_ok),
                "rejected": sum(1 for r in rows
                                if r["status"] == "RESOURCE_EXHAUSTED"),
                "expired": sum(1 for r in rows
                               if r["status"] == "DEADLINE_EXCEEDED"),
                "ttft_ms": percentiles(
                    [r["ttft_ms"] for r in rows_ok
                     if r["ttft_ms"] is not None], (50, 90, 99)
                ),
                "latency_ms": percentiles(
                    [r["latency_ms"] for r in rows_ok], (50, 90, 99)
                ),
            })
    return record, results


def tail_report(results, phases):
    """Forensics over the RAMP's slowest requests: per phase, take the
    slowest TTFT decile of completed requests, pull their span trees
    from the in-process recorder, run forensics.attribute() on each,
    and histogram the dominant causes. The output is the quantified
    tail-latency evidence the disaggregated-prefill ROADMAP item asks
    for BEFORE scheduling work starts: "N% of the p99 TTFT tail is
    prefill monopolization" is a number here, not a hunch."""
    from elasticdl_tpu.observability import forensics
    from elasticdl_tpu.observability.tracing import (
        group_by_trace,
        recorder,
    )

    by_trace = group_by_trace(
        [s.to_dict() for s in recorder().snapshot()]
    )
    per_phase = []
    all_verdicts = []
    agg_ms = {c: 0.0 for c in forensics.CAUSES}
    for idx in range(len(phases)):
        rows = [
            r for r in results
            if r["phase"] == idx and r["status"] == "OK"
            and r["ttft_ms"] is not None and r["trace_id"] in by_trace
        ]
        rows.sort(key=lambda r: r["ttft_ms"], reverse=True)
        decile = rows[:max(1, len(rows) // 10)] if rows else []
        verdicts = [
            forensics.attribute(by_trace[r["trace_id"]])
            for r in decile
        ]
        for v in verdicts:
            for part in v["breakdown"]:
                agg_ms[part["cause"]] += part["ms"]
        all_verdicts.extend(verdicts)
        per_phase.append({
            "phase": idx,
            "rate_rps": phases[idx][0],
            "analyzed": len(verdicts),
            "dominant_causes": forensics.cause_histogram(verdicts),
        })
    total = forensics.cause_histogram(all_verdicts)
    total_ms = sum(agg_ms.values()) or 1e-9
    return {
        "decile": "slowest 10% by TTFT, per phase, completed only",
        "analyzed": len(all_verdicts),
        "per_phase": per_phase,
        "dominant_causes": total,
        "top_cause": max(total, key=total.get) if total else None,
        # aggregate wall-ms breakdown over the analyzed tail — the
        # shares the scheduler items cite (e.g. what fraction of the
        # tail is prefill_blocked_by_other)
        "breakdown_ms": {c: round(agg_ms[c], 3)
                         for c in forensics.CAUSES},
        "breakdown_share": {c: round(agg_ms[c] / total_ms, 4)
                            for c in forensics.CAUSES},
        "evidence_complete": all(
            v["evidence_complete"] for v in all_verdicts
        ) if all_verdicts else False,
    }


def greedy_match_rate(trainer, state, results, temperature):
    """Fraction of completed GREEDY streams whose tokens equal the
    offline `autoregressive_generate(use_cache=True)` oracle on
    `trainer` — for the int8 leg that oracle is the int8 DENSE decode
    (same quantizer), so a miss means the paged deferred scan diverged,
    not that quantization rounded differently."""
    import numpy as np

    from elasticdl_tpu.api.generation import autoregressive_generate

    if temperature > 0.0:
        return None  # sampled runs have no greedy oracle
    compared = matched = 0
    for row in results:
        if row["status"] != "OK" or not row["out_tokens"]:
            continue
        spec = row["spec"]
        off = np.asarray(autoregressive_generate(
            trainer, state,
            np.asarray([spec["prompt"]], np.int32), spec["new"],
            use_cache=True,
        ))[0]
        compared += 1
        if list(off[len(spec["prompt"]):]) == row["out_tokens"]:
            matched += 1
    return round(matched / compared, 4) if compared else None


#: the eviction-pressure A/B's own serving rig: long system prompts
#: over a real-ish context, so a re-paid prefill is real compute (the
#: tiny smoke model's 32-token prefill costs ~2 ms — cheaper than any
#: measurement overhead, so TTFT could not see the difference). At
#: this scale a full re-prefill seat measures ~29 ms vs ~13 ms for a
#: revive-by-upload seat on the CPU rig.
PRESS_MODEL_PARAMS = (
    "vocab_size=32; seq_len=256; embed_dim=256; num_heads=4; "
    "num_layers=4"
)
PRESS_PREFIX_LEN = 224
PRESS_BLOCK_SIZE = 16


def run_host_evict_ab(args):
    """The tiered-KV eviction-pressure A/B: a shared-prefix workload
    whose prefix WORKING SET deliberately exceeds the device pool, so
    reclaimable chains are forced out between hits — run twice at
    EQUAL DEVICE KV BYTES, host tier off (every evicted chain re-pays
    its prefill on the next hit) vs on (evicted chains spill and
    revive by upload). The headline ratio: what fraction of the
    prefill tokens the baseline re-pays after eviction does the host
    tier recover (`prefill_tokens_revived` vs the baseline's
    repeated-prefix re-prefill tokens)? Runs its own rig
    (PRESS_MODEL_PARAMS, int8 arenas when --kv_cache_dtype says so)
    with 96-token system prompts: long enough that a re-paid prefill
    costs real compute, which is what the TTFT comparison measures."""
    import numpy as np

    model_params = PRESS_MODEL_PARAMS
    if args.kv_cache_dtype:
        model_params += "; kv_cache_dtype=%r" % args.kv_cache_dtype
    trainer, state, _ = build_rig(args, model_params=model_params)
    vocab = int(trainer.model.vocab_size)
    bs = PRESS_BLOCK_SIZE
    o_lo, o_hi = _span(args.out_len)
    s_lo, s_hi = _span(args.suffix_len)
    prefix_len = (PRESS_PREFIX_LEN // bs) * bs  # full blocks only
    press_pool = 6   # distinct system prompts in the pressure pool
    passes = 4       # times each prompt comes back around
    # a seat's full commitment, in blocks — the device pool holds two
    # concurrent seats and nothing more, far below the working set
    seat_blocks = -(-(prefix_len + s_hi + o_hi - 1) // bs)
    device_blocks = 2 * seat_blocks
    working_set = press_pool * (prefix_len // bs)
    if working_set <= device_blocks:
        raise SystemExit(
            "eviction-pressure A/B needs the prefix working set "
            "(%d blocks) above the device pool (%d)"
            % (working_set, device_blocks)
        )
    host_blocks = working_set  # the tier holds the whole working set
    host_bytes = host_blocks * block_bytes_for(trainer, bs)
    rs = np.random.RandomState(args.seed + 17)
    pool = [rs.randint(0, vocab, size=prefix_len)
            for _ in range(press_pool)]
    # arrivals slow enough that TTFT is seat latency (prefill vs
    # revive), not queueing — the quantity under test
    rate = 1.5
    plan = []
    for i in range(passes * press_pool):
        # round-robin: consecutive hits of one prefix are press_pool
        # requests apart, so the tight pool has evicted it in between
        suffix = rs.randint(0, vocab,
                            size=rs.randint(s_lo, s_hi + 1))
        plan.append({
            "prompt": np.concatenate([pool[i % press_pool], suffix]),
            "new": int(rs.randint(o_lo, o_hi + 1)),
            "gap": float(rs.exponential(1.0 / rate)),
            "seed": int(i),
            "phase": None,
        })
    legs, rows = {}, {}
    for name, bytes_budget in (("baseline", 0), ("host", host_bytes)):
        legs[name], rows[name] = run_load(
            args, trainer, state, plan, 2,
            kv_paged=True,
            kv_block_size=bs,
            kv_num_blocks=device_blocks,
            kv_shared=True,
            kv_host_bytes=bytes_budget,
        )

    def post_evict_ttft(leg_rows):
        """TTFT percentiles over the STEADY post-eviction hits: the
        last two passes, by which point every compile (either leg's)
        is paid and every seat of a pooled prompt finds its chain
        evicted — re-prefilled by the baseline, revived by the host
        tier. Same histogram code as every other percentile."""
        steady = [
            r["ttft_ms"] for r in leg_rows
            if r["status"] == "OK" and r["ttft_ms"] is not None
            and r["spec"]["seed"] >= 2 * press_pool
        ]
        return percentiles(steady, (50, 90, 99))

    base, host = legs["baseline"], legs["host"]
    base_steady = post_evict_ttft(rows["baseline"]) or {}
    host_steady = post_evict_ttft(rows["host"]) or {}
    offered = len(plan) * prefix_len   # full-block prefix tokens sent
    cold = press_pool * prefix_len     # first-touch: unavoidable
    repaid_base = max(
        0, offered - base["kv"]["prefix_hit_tokens"] - cold
    )
    recovered = host["kv"]["prefill_tokens_revived"]
    return {
        "model_params": model_params,
        "block_size": bs,
        "device_blocks": device_blocks,
        "host_blocks": host_blocks,
        "prefix_pool": press_pool,
        "passes": passes,
        "prefix_working_set_blocks": working_set,
        "equal_device_kv_bytes": (
            base["kv"]["bytes_total"] == host["kv"]["bytes_total"]
        ),
        "prefix_tokens_offered": offered,
        "cold_prefix_tokens": cold,
        "baseline_repaid_prefix_tokens": repaid_base,
        "prefill_tokens_revived": recovered,
        "recovered_ratio": round(recovered / max(1, repaid_base), 3),
        "revive_uploads": host["kv"]["revive_uploads"],
        "host_drops": host["kv"]["host_drops"],
        "prefix_hit_tokens": [base["kv"]["prefix_hit_tokens"],
                              host["kv"]["prefix_hit_tokens"]],
        # steady-state post-eviction TTFT: the headline the tier buys
        "post_evict_ttft_ms": [base_steady, host_steady],
        "ttft_p50_improved": (
            (host_steady.get("p50") or 0.0)
            < (base_steady.get("p50") or 0.0)
        ),
        "ttft_p99_improved": (
            (host_steady.get("p99") or 0.0)
            < (base_steady.get("p99") or 0.0)
        ),
        "goodput_rps": [base["goodput_rps"], host["goodput_rps"]],
        "goodput_ratio": round(
            (host["goodput_rps"] or 0.0)
            / (base["goodput_rps"] or 1e-9), 3,
        ),
        "baseline": base,
        "host": host,
    }


def run_affinity_ab(args):
    """The prefix-affinity A/B at the ROUTER tier: the same
    shared-prefix Poisson plan dispatched through a real two-replica
    in-process fleet behind the real Router, affinity ON vs OFF.

    The off-leg's pathology is structural, not statistical: with
    load scores tied, the least-loaded order tie-breaks on free
    blocks, and the replica that just cached a family's prefix chain
    has FEWER free blocks — so consecutive hits of one family
    ping-pong between replicas and each bounce re-pays the family's
    prefill cold. The on-leg pins each family to the replica already
    holding its chain (the fingerprint ladder), so the fleet pays
    each family's prefill once. The headline: fleet re-paid prefix
    prefill tokens (offered minus hits minus the one unavoidable
    first touch per family) and the warm-pass TTFT percentiles."""
    import numpy as np

    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.proto.service import ServingStub, build_channel
    from elasticdl_tpu.serving import GenerationServer, ServingConfig
    from elasticdl_tpu.serving.router import (
        Router,
        RouterConfig,
        RouterError,
    )

    trainer, state, _ = build_rig(args, model_params=PRESS_MODEL_PARAMS)
    vocab = int(trainer.model.vocab_size)
    bs = PRESS_BLOCK_SIZE
    o_lo, o_hi = _span(args.out_len)
    s_lo, s_hi = _span(args.suffix_len)
    prefix_len = (PRESS_PREFIX_LEN // bs) * bs  # full blocks only
    families = 4  # distinct system prompts
    passes = 6    # times each family comes back around
    # arrivals BELOW fleet capacity: with slots idle, load scores sit
    # near zero and the affinity_load_margin can hold — the A/B
    # measures placement, not saturation (under which the ladder's
    # load rung decays affinity to least-loaded, by design)
    rate = 1.0
    # roomy per-replica pools: every family's chain fits on BOTH
    # replicas plus full seats — zero eviction pressure, so the A/B
    # isolates WHERE a family lands, not whether its chain survives
    seat_blocks = -(-(prefix_len + s_hi + o_hi - 1) // bs)
    # +1 family of room for the full-shape warmup chain each replica
    # seats outside the measurement window
    num_blocks = ((families + 1) * (prefix_len // bs)
                  + 2 * seat_blocks + 8)
    rs = np.random.RandomState(args.seed + 29)
    pool = [rs.randint(0, vocab, size=prefix_len)
            for _ in range(families)]
    plan = []
    for i in range(passes * families):
        suffix = rs.randint(0, vocab,
                            size=rs.randint(s_lo, s_hi + 1))
        plan.append({
            "prompt": np.concatenate([pool[i % families], suffix]),
            "new": int(rs.randint(o_lo, o_hi + 1)),
            "gap": float(rs.exponential(1.0 / rate)),
            "seed": int(i),
        })

    def run_leg(affinity_on):
        servers, router = [], None
        try:
            for _ in range(2):
                srv = GenerationServer(
                    trainer, state,
                    ServingConfig(
                        num_slots=2,
                        queue_capacity=args.queue_capacity,
                        kv_paged=True, kv_block_size=bs,
                        kv_num_blocks=num_blocks, kv_shared=True,
                    ),
                ).start()
                servers.append(srv)
            warm_prompt = [0] * prefix_len + [1, 2]
            for srv in servers:
                # pay each replica's jit compiles outside the window
                # with a FULL-SHAPE request (block-aligned prefix +
                # suffix + decode): a cold family inside the window
                # must cost one prefill, never a multi-second compile
                # stall that blows the load margin and cascades
                ServingStub(
                    build_channel("localhost:%d" % srv.port)
                ).generate(
                    pb.GenerateRequest(prompt=warm_prompt,
                                       max_new_tokens=4),
                    timeout=600,
                )
                srv.mark_steady()
            router = Router(
                ["localhost:%d" % s.port for s in servers],
                config=RouterConfig(
                    poll_secs=0.2, lease_secs=2.0,
                    affinity=affinity_on,
                    affinity_block_tokens=bs,
                    # a couple of cold prefills stacked on the
                    # affine target (queue+slots+inflight) must not
                    # decay the whole family off its warm replica:
                    # the A/B's on-leg expresses "placement first",
                    # and the off-leg ignores the knob entirely
                    affinity_load_margin=8.0,
                ),
            )
            router.start(grpc_server=False)
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                if router.status_response().healthy >= len(servers):
                    break
                time.sleep(0.1)

            rows = []
            lock = threading.Lock()

            def one(spec):
                t0 = time.monotonic()
                row = {"status": "OK", "ttft_ms": None, "spec": spec}
                try:
                    for chunk in router.dispatch_stream(
                        pb.GenerateRequest(
                            prompt=[int(t) for t in spec["prompt"]],
                            max_new_tokens=spec["new"],
                            temperature=args.temperature,
                            seed=spec["seed"],
                        )
                    ):
                        if row["ttft_ms"] is None and chunk.tokens:
                            row["ttft_ms"] = (
                                (time.monotonic() - t0) * 1000.0
                            )
                except RouterError as e:
                    row["status"] = e.code
                with lock:
                    rows.append(row)

            threads = []
            for spec in plan:
                time.sleep(spec["gap"])
                t = threading.Thread(target=one, args=(spec,))
                t.start()
                threads.append(t)
            for t in threads:
                t.join(timeout=600)

            hits = sum(
                s.engine.kv_stats()["prefix_hit_tokens"]
                for s in servers
            )
            snap = router.telemetry.snapshot()
            warm = [
                r["ttft_ms"] for r in rows
                if r["status"] == "OK" and r["ttft_ms"] is not None
                and r["spec"]["seed"] >= families  # pass 2 onward
            ]
            offered = len(plan) * prefix_len
            cold = families * prefix_len  # first touch: unavoidable
            return {
                "completed": sum(
                    1 for r in rows if r["status"] == "OK"
                ),
                "prefix_hit_tokens": hits,
                "repaid_prefix_tokens": max(
                    0, offered - hits - cold
                ),
                "warm_ttft_ms": percentiles(warm, (50, 90, 99)) or {},
                "affinity_hits": snap["affinity_hits"],
                "affinity_misses": snap["affinity_misses"],
            }
        finally:
            if router is not None:
                router.stop()
            for srv in servers:
                srv.stop()

    on, off = run_leg(True), run_leg(False)
    return {
        "model_params": PRESS_MODEL_PARAMS,
        "block_size": bs,
        "replicas": 2,
        "prefix_families": families,
        "passes": passes,
        "prefix_tokens_offered": len(plan) * prefix_len,
        "cold_prefix_tokens": families * prefix_len,
        # the headline: prefill the FLEET re-pays because requests
        # landed away from the replica already holding their chain
        "repaid_prefix_tokens": [on["repaid_prefix_tokens"],
                                 off["repaid_prefix_tokens"]],
        "repaid_drop": (
            off["repaid_prefix_tokens"] - on["repaid_prefix_tokens"]
        ),
        "repaid_improved": (
            on["repaid_prefix_tokens"] < off["repaid_prefix_tokens"]
        ),
        "prefix_hit_tokens": [on["prefix_hit_tokens"],
                              off["prefix_hit_tokens"]],
        "affinity_hit_rate": round(
            on["affinity_hits"]
            / max(1, on["affinity_hits"] + on["affinity_misses"]), 3,
        ),
        "warm_ttft_ms": [on["warm_ttft_ms"], off["warm_ttft_ms"]],
        "warm_ttft_p99_improved": (
            (on["warm_ttft_ms"].get("p99") or 0.0)
            < (off["warm_ttft_ms"].get("p99") or 0.0)
        ),
        "completed": [on["completed"], off["completed"]],
        "affinity_on": on,
        "affinity_off": off,
    }


def run_disagg_ab(args):
    """The disaggregation A/B at EQUAL FLEET KV BYTES: one open-loop
    plan of long COLD prompts (every prompt unique — every prefill is
    paid inside the window) through a real two-replica in-process
    fleet behind the Router, three ways:

      monolithic      two unified replicas, chunking OFF — a 224-token
                      prefill monopolizes its scheduler tick, and
                      requests admitted meanwhile wait it out
                      (prefill_blocked_by_other)
      chunked         same fleet, prefill tiled (PRESS_BLOCK_SIZE
                      tokens per tile) under the per-tick budget —
                      decode steps and other admissions interleave
                      between tiles
      chunked_disagg  chunked + phase-split: replica 0 re-roles as a
                      dedicated PREFILL replica (out of rotation), the
                      router runs every cold prompt through a
                      prefill->TransferChain handoff, and the decode
                      replica seats the imported chain by prefix hit —
                      its scheduler never runs a cold prompt's prefill

    Every leg fires the SAME plan and holds the same fleet KV bytes
    (2 pools x num_blocks x block_bytes). Per leg, tail_report runs
    the slowest-TTFT-decile forensics — the headline is the
    prefill_blocked_by_other share of the tail breakdown, which
    chunking must REDUCE vs monolithic at goodput >= 0.95x."""
    import numpy as np

    from elasticdl_tpu.observability.tracing import new_trace_id
    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.proto.service import ServingStub, build_channel
    from elasticdl_tpu.serving import GenerationServer, ServingConfig
    from elasticdl_tpu.serving.router import (
        Router,
        RouterConfig,
        RouterError,
    )

    trainer, state, _ = build_rig(args, model_params=PRESS_MODEL_PARAMS)
    vocab = int(trainer.model.vocab_size)
    bs = PRESS_BLOCK_SIZE
    o_lo, o_hi = _span(args.out_len)
    s_lo, s_hi = _span(args.suffix_len)
    prompt_len = (PRESS_PREFIX_LEN // bs) * bs  # full blocks
    # 64-token tiles: big enough that per-tile dispatch overhead stays
    # noise on the CPU rig (4 tiles per prompt), small enough that a
    # cold prompt's monopolization window shrinks 4x
    chunk_tokens = 4 * bs
    # BURSTY arrivals — the contention is structural, not Poisson
    # luck: each burst lands burst_size cold prompts on 2 replicas at
    # once, so at least two share a replica and the later one's
    # admission waits out the earlier one's prefill (monolithic) or
    # only its current tile (chunked). Bursts are spaced so the fleet
    # drains between them — the A/B measures scheduling, not
    # saturation.
    bursts, burst_size, burst_gap = 8, 4, 1.2
    requests = bursts * burst_size
    rate = burst_size / burst_gap
    seat_blocks = -(-(prompt_len + s_hi + o_hi - 1) // bs)
    # pools hold EVERY chain the window creates (plus warmup and
    # seats): eviction must never clip a chain between its register
    # and its export, or between its import and its seat — a clipped
    # chain re-prefills an odd-length suffix whose tile bucket would
    # COMPILE inside the measurement window and swamp the tail with
    # compile stalls instead of scheduling
    num_blocks = (requests + 3) * seat_blocks
    rs = np.random.RandomState(args.seed + 43)
    plan = []
    for i in range(requests):
        suffix = rs.randint(0, vocab,
                            size=rs.randint(s_lo, s_hi + 1))
        plan.append({
            "prompt": np.concatenate([
                rs.randint(0, vocab, size=prompt_len), suffix,
            ]),
            "new": int(rs.randint(o_lo, o_hi + 1)),
            "gap": (burst_gap if i and i % burst_size == 0 else 0.0),
            "seed": int(i),
        })

    def run_leg(chunk_tokens, disagg):
        servers, router = [], None
        roles = ("prefill", "decode") if disagg else (None, None)
        try:
            for role in roles:
                srv = GenerationServer(
                    trainer, state,
                    ServingConfig(
                        num_slots=2, queue_capacity=32,
                        kv_paged=True, kv_block_size=bs,
                        kv_num_blocks=num_blocks, kv_shared=True,
                        role=role,
                        prefill_chunk_tokens=chunk_tokens,
                    ),
                ).start()
                servers.append(srv)
            warm_prompt = [0] * prompt_len + [1, 2]
            for srv in servers:
                # pay each replica's compiles outside the measurement
                # window: the full prefill (or its tiles) + decode
                # step first, then a same-prefix request whose short
                # suffix compiles the prefix-hit tile — the path every
                # imported chain's request runs on the decode side
                stub = ServingStub(
                    build_channel("localhost:%d" % srv.port)
                )
                stub.generate(
                    pb.GenerateRequest(prompt=warm_prompt,
                                       max_new_tokens=4),
                    timeout=600,
                )
                stub.generate(
                    pb.GenerateRequest(
                        prompt=[0] * prompt_len + [3],
                        max_new_tokens=4,
                    ),
                    timeout=600,
                )
                srv.mark_steady()
            router = Router(
                ["localhost:%d" % s.port for s in servers],
                config=RouterConfig(
                    poll_secs=0.2, lease_secs=2.0,
                    affinity=True, affinity_block_tokens=bs,
                    affinity_load_margin=8.0, disagg=disagg,
                ),
            )
            router.start(grpc_server=False)
            want = 1 if disagg else 2
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                st = router.status_response()
                roles_seen = sum(
                    1 for r in router.replicas() if r.role
                )
                if st.healthy >= want and (
                    not disagg or roles_seen >= 2
                ):
                    break
                time.sleep(0.1)

            rows = []
            lock = threading.Lock()

            def one(spec):
                t0 = time.monotonic()
                trace_id = new_trace_id()
                row = {"status": "OK", "ttft_ms": None, "phase": 0,
                       "tokens": 0, "trace_id": trace_id,
                       "spec": spec}
                try:
                    for chunk in router.dispatch_stream(
                        pb.GenerateRequest(
                            prompt=[int(t) for t in spec["prompt"]],
                            max_new_tokens=spec["new"],
                            temperature=args.temperature,
                            seed=spec["seed"],
                            trace_id=trace_id,
                        )
                    ):
                        if row["ttft_ms"] is None and chunk.tokens:
                            row["ttft_ms"] = (
                                (time.monotonic() - t0) * 1000.0
                            )
                        row["tokens"] += len(chunk.tokens)
                except RouterError as e:
                    row["status"] = e.code
                row["latency_ms"] = (time.monotonic() - t0) * 1000.0
                with lock:
                    rows.append(row)

            threads = []
            t_start = time.monotonic()
            for spec in plan:
                time.sleep(spec["gap"])
                t = threading.Thread(target=one, args=(spec,))
                t.start()
                threads.append(t)
            for t in threads:
                t.join(timeout=600)
            wall = time.monotonic() - t_start

            ok = [r for r in rows if r["status"] == "OK"]
            snap = router.telemetry.snapshot()
            pools = [s.engine.kv_stats() for s in servers]
            tail = tail_report(rows, [(rate, wall)])
            return {
                "chunk_tokens": chunk_tokens,
                "disagg": disagg,
                "completed": len(ok),
                "goodput_rps": round(len(ok) / wall, 3),
                "tokens_per_sec": round(
                    sum(r["tokens"] for r in ok) / wall, 3
                ),
                "ttft_ms": percentiles(
                    [r["ttft_ms"] for r in ok
                     if r["ttft_ms"] is not None], (50, 90, 99)
                ) or {},
                "fleet_kv_bytes": sum(
                    p["kv_bytes_total"] for p in pools
                ),
                "disagg_handoffs": snap.get("disagg_handoffs", 0),
                "disagg_fallbacks": snap.get("disagg_fallbacks", 0),
                "chain_exports": sum(
                    p.get("chain_exports", 0) for p in pools
                ),
                "chain_imports": sum(
                    p.get("chain_imports", 0) for p in pools
                ),
                # the two-pool post-drain ledger (drill-grade)
                "pools_clean": all(
                    p["kv_blocks_free"] == p["kv_blocks_total"]
                    for p in pools
                ),
                "tail_report": tail,
                "tail_blocked_share": tail["breakdown_share"][
                    "prefill_blocked_by_other"
                ],
                "tail_blocked_ms": tail["breakdown_ms"][
                    "prefill_blocked_by_other"
                ],
            }
        finally:
            if router is not None:
                router.stop()
            for srv in servers:
                srv.stop()

    mono = run_leg(0, False)
    chunked = run_leg(chunk_tokens, False)
    split = run_leg(chunk_tokens, True)
    mono_good = mono["goodput_rps"] or 1e-9
    return {
        "model_params": PRESS_MODEL_PARAMS,
        "block_size": bs,
        "prompt_len": prompt_len,
        "requests": requests,
        "rate_rps": rate,
        "replicas": 2,
        "equal_fleet_kv_bytes": (
            mono["fleet_kv_bytes"] == chunked["fleet_kv_bytes"]
            == split["fleet_kv_bytes"]
        ),
        # the headline: what share of the slowest-TTFT-decile wall is
        # sitting behind ANOTHER request's prefill, per leg
        "tail_blocked_share": [mono["tail_blocked_share"],
                               chunked["tail_blocked_share"],
                               split["tail_blocked_share"]],
        "tail_blocked_ms": [mono["tail_blocked_ms"],
                            chunked["tail_blocked_ms"],
                            split["tail_blocked_ms"]],
        "blocked_reduced_chunked_vs_mono": (
            chunked["tail_blocked_ms"] < mono["tail_blocked_ms"]
        ),
        "goodput_rps": [mono["goodput_rps"], chunked["goodput_rps"],
                        split["goodput_rps"]],
        "chunked_goodput_ratio": round(
            (chunked["goodput_rps"] or 0.0) / mono_good, 3
        ),
        "disagg_goodput_ratio": round(
            (split["goodput_rps"] or 0.0) / mono_good, 3
        ),
        "ttft_ms": [mono["ttft_ms"], chunked["ttft_ms"],
                    split["ttft_ms"]],
        "disagg_handoffs": split["disagg_handoffs"],
        "disagg_fallbacks": split["disagg_fallbacks"],
        "pools_clean": [mono["pools_clean"], chunked["pools_clean"],
                        split["pools_clean"]],
        "monolithic": mono,
        "chunked": chunked,
        "chunked_disagg": split,
    }


#: the enabled observability plane may cost at most this fraction of
#: the disabled plane's tokens/sec (the PR 6 tracing bound, kept)
OVERHEAD_BOUND = 0.05


def run_overhead_ab(args, trainer, state, plan, num_slots,
                    num_blocks, draft):
    """The observability overhead A/B: the SAME arrival plan on the
    paged+shared pool, plane OFF (no exposition, no forensics —
    exemplars, tail retention and slow-cause attribution all disarmed
    — and no runtime health: sentry, accountant and watchdog all
    absent) vs ON (a live /metrics server that gets scraped at the
    end, the full forensics plane AND the runtime health plane:
    recompile sentry on every executable, ledger reconciliation,
    progress watchdog). The phase spans have no switch and ride both
    legs (the ON leg records their block). The key stays
    "profiler_overhead": bench_compare.py reads it from older
    records. tokens/sec must stay within OVERHEAD_BOUND; one
    retry forgives a scheduler hiccup on a noisy CI box, but two
    misses fail the bench (a >5% observability tax is a regression,
    not noise)."""
    ratios = []
    for _attempt in range(2):
        off, _ = run_load(
            args, trainer, state, plan, num_slots,
            kv_paged=True, kv_block_size=args.kv_block_size,
            kv_num_blocks=num_blocks, kv_shared=True,
            draft=draft, draft_k=args.draft_k,
            forensics=False, runtime_health=False,
        )
        on, _ = run_load(
            args, trainer, state, plan, num_slots,
            kv_paged=True, kv_block_size=args.kv_block_size,
            kv_num_blocks=num_blocks, kv_shared=True,
            draft=draft, draft_k=args.draft_k,
            profile=True, metrics_port=0, forensics=True,
            runtime_health=True,
        )
        ratio = ((on["tokens_per_sec"] or 0.0)
                 / (off["tokens_per_sec"] or 1e-9))
        ratios.append(round(ratio, 4))
        if ratio >= 1.0 - OVERHEAD_BOUND:
            break
    return {
        "bound": OVERHEAD_BOUND,
        "tokens_per_sec": [off["tokens_per_sec"],
                           on["tokens_per_sec"]],
        "goodput_rps": [off["goodput_rps"], on["goodput_rps"]],
        "ratios": ratios,
        "tokens_per_sec_ratio": ratios[-1],
        "within_bound": ratios[-1] >= 1.0 - OVERHEAD_BOUND,
        "profile": on.get("profile"),
        "metrics_scrape": on.get("metrics_scrape"),
    }


def run_bench(args):
    if args.kv_cache_dtype and not args.compare_paged:
        # single-run mode: the whole run serves quantized arenas
        args.model_params += (
            "; kv_cache_dtype=%r" % args.kv_cache_dtype
        )
    trainer, state, draft = build_rig(args)
    seq_len = int(trainer.model.seq_len)
    vocab = int(trainer.model.vocab_size)
    plan = build_plan(args, seq_len, vocab)
    if args.kv_block_size < 1 or seq_len % args.kv_block_size:
        raise SystemExit(
            "kv_block_size %d must divide seq_len %d"
            % (args.kv_block_size, seq_len)
        )
    # dense-equivalent block budget: the SAME KV bytes the dense pool
    # pins for --num_slots, expressed in blocks
    dense_blocks = args.num_slots * (seq_len // args.kv_block_size)
    num_blocks = args.kv_num_blocks or dense_blocks
    host_bytes = (
        args.kv_host_blocks * block_bytes_for(trainer,
                                              args.kv_block_size)
        if args.kv_host_blocks > 0 else 0
    )

    record, results = run_load(
        args, trainer, state, plan, args.num_slots,
        kv_paged=bool(args.kv_paged),
        kv_block_size=args.kv_block_size,
        kv_num_blocks=num_blocks if args.kv_paged else 0,
        kv_shared=bool(args.kv_paged and args.kv_shared),
        draft=draft if args.kv_paged else None,
        draft_k=args.draft_k,
        kv_host_bytes=host_bytes if args.kv_paged else 0,
        profile=args.profile,
        metrics_port=0 if args.profile else None,
    )
    if args.ramp:
        # forensics over the ramp's slow tail: which cause dominates
        # the slowest decile, per phase (the in-process span trees are
        # still in the recorder — the bench minted the trace ids)
        record["tail_report"] = tail_report(
            results, parse_ramp(args.ramp)
        )
    if args.overhead_ab:
        # observability overhead A/B on the paged+shared shape (the
        # path with the most instrumented phases)
        record["profiler_overhead"] = run_overhead_ab(
            args, trainer, state, plan,
            args.paged_slots or 2 * args.num_slots, dense_blocks,
            draft,
        )
    if args.disagg:
        # the disaggregation A/B: monolithic vs chunked prefill vs
        # chunked + phase-split fleet at equal fleet KV bytes, with
        # the slowest-TTFT-decile cause breakdown per leg — its own
        # long-prompt rig, so it runs with or without --compare_paged
        record["disagg_ab"] = run_disagg_ab(args)
    if not args.compare_paged:
        return record

    # the A/B legs: equal KV bytes (the dense pool's budget), spread
    # over more slots — first the private paged pool (the concurrency
    # block granularity alone admits), then the prefix-SHARED pool
    # (+ speculative decode when --draft_k is on): what dedup converts
    # the same bytes into
    paged_slots = args.paged_slots or 2 * args.num_slots
    paged, _ = run_load(
        args, trainer, state, plan, paged_slots,
        kv_paged=True,
        kv_block_size=args.kv_block_size,
        kv_num_blocks=dense_blocks,
        kv_shared=False,
    )
    shared, _ = run_load(
        args, trainer, state, plan, paged_slots,
        kv_paged=True,
        kv_block_size=args.kv_block_size,
        kv_num_blocks=dense_blocks,
        kv_shared=True,
    )
    record["paged"] = paged
    record["paged_shared"] = shared
    if draft is not None:
        # the draft on/off A/B rides the shared leg: same plan, same
        # pool, plus the speculative draft-verify tick
        spec, _ = run_load(
            args, trainer, state, plan, paged_slots,
            kv_paged=True,
            kv_block_size=args.kv_block_size,
            kv_num_blocks=dense_blocks,
            kv_shared=True,
            draft=draft,
            draft_k=args.draft_k,
        )
        record["paged_shared_spec"] = spec
        shared_tok = shared["tokens_per_sec"] or 1e-9
        record["spec_vs_shared"] = {
            "draft_k": args.draft_k,
            "tokens_per_sec": [shared["tokens_per_sec"],
                               spec["tokens_per_sec"]],
            "tokens_per_sec_ratio": round(
                (spec["tokens_per_sec"] or 0.0) / shared_tok, 3
            ),
            "draft_accept_rate": spec["draft"]["accept_rate"],
        }
    if args.kv_cache_dtype == "int8":
        # the int8-arena leg: SAME byte budget, paid in ~2-3x as many
        # int8 blocks (block bytes shrink to int8 rows + f32 scales),
        # with slots raised to let the extra blocks become extra
        # concurrency; sharing (and the draft, when on) ride along —
        # the compounding the arenas exist for
        i8_trainer, i8_state, _ = build_rig(
            args,
            model_params=(args.model_params
                          + "; kv_cache_dtype='int8'"),
        )
        fp_bb = block_bytes_for(trainer, args.kv_block_size)
        i8_bb = block_bytes_for(i8_trainer, args.kv_block_size)
        i8_blocks = max(1, (dense_blocks * fp_bb) // i8_bb)
        i8_slots = 2 * paged_slots
        int8, i8_results = run_load(
            args, i8_trainer, i8_state, plan, i8_slots,
            kv_paged=True,
            kv_block_size=args.kv_block_size,
            kv_num_blocks=i8_blocks,
            kv_shared=True,
            draft=draft,
            draft_k=args.draft_k,
            profile=args.profile,
            metrics_port=0 if args.profile else None,
        )
        record["paged_int8"] = int8
        shared_tok = shared["tokens_per_sec"] or 1e-9
        shared_bpt = shared["kv"]["bytes_per_token"] or 1e-9
        record["int8_vs_shared"] = {
            # equal BYTES, not equal blocks: the whole point
            "equal_kv_bytes": abs(
                int8["kv"]["bytes_total"]
                - shared["kv"]["bytes_total"]
            ) <= i8_bb,
            "blocks": [shared["kv"]["blocks_total"],
                       int8["kv"]["blocks_total"]],
            "bytes_per_token": [shared["kv"]["bytes_per_token"],
                                int8["kv"]["bytes_per_token"]],
            "bytes_per_token_improvement": round(
                1.0 - (int8["kv"]["bytes_per_token"] or 0.0)
                / shared_bpt, 3,
            ),
            "max_active_slots": [shared["max_active_slots"],
                                 int8["max_active_slots"]],
            "goodput_rps": [shared["goodput_rps"],
                            int8["goodput_rps"]],
            "tokens_per_sec_ratio": round(
                (int8["tokens_per_sec"] or 0.0) / shared_tok, 3
            ),
            # token-level correctness of the quantized serving path:
            # completed greedy streams vs the int8 dense oracle
            "greedy_match_rate_vs_int8_dense": greedy_match_rate(
                i8_trainer, i8_state, i8_results, args.temperature
            ),
        }
    if args.kv_host_blocks > 0 and args.shared_prefix:
        # the tiered-KV eviction-pressure A/B: its own long-prefix
        # rig (int8 arenas when --kv_cache_dtype says so — the
        # serve-smoke shape, where one host GB buys ~3x the chains)
        record["host_vs_evict"] = run_host_evict_ab(args)
    if args.shared_prefix:
        # the router-tier prefix-affinity A/B: the same shared-prefix
        # shape one tier up — does fingerprint-affine dispatch stop
        # the fleet re-paying prefills it already holds?
        record["affinity_ab"] = run_affinity_ab(args)
    base_good = record["goodput_rps"] or 1e-9
    base_tok = record["tokens_per_sec"] or 1e-9
    record["paged_vs_dense"] = {
        "equal_kv_bytes": paged["kv"]["bytes_total"]
        == record["kv"]["bytes_total"],
        "goodput_ratio": round((paged["goodput_rps"] or 0.0)
                               / base_good, 3),
        "tokens_per_sec_ratio": round((paged["tokens_per_sec"] or 0.0)
                                      / base_tok, 3),
        "max_active_slots": [record["max_active_slots"],
                             paged["max_active_slots"]],
        "bytes_per_token": [record["kv"]["bytes_per_token"],
                            paged["kv"]["bytes_per_token"]],
    }
    paged_tok = paged["tokens_per_sec"] or 1e-9
    paged_bpt = paged["kv"]["bytes_per_token"] or 1e-9
    record["shared_vs_paged"] = {
        "equal_kv_bytes": shared["kv"]["bytes_total"]
        == paged["kv"]["bytes_total"],
        "tokens_per_sec_ratio": round(
            (shared["tokens_per_sec"] or 0.0) / paged_tok, 3
        ),
        "max_active_slots": [paged["max_active_slots"],
                             shared["max_active_slots"]],
        "bytes_per_token": [paged["kv"]["bytes_per_token"],
                            shared["kv"]["bytes_per_token"]],
        "bytes_per_token_improvement": round(
            1.0 - (shared["kv"]["bytes_per_token"] or 0.0) / paged_bpt,
            3,
        ),
        "prefix_hit_tokens": shared["kv"]["prefix_hit_tokens"],
    }
    return record


def main(argv=None):
    args = parse_args(argv)
    record = run_bench(args)
    line = json.dumps(record)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    # a bench run that completed nothing is a failure, not a datum;
    # an observability plane that taxes the serve path past the bound
    # is one too
    overhead = record.get("profiler_overhead")
    if overhead is not None and not overhead["within_bound"]:
        print("profiler overhead A/B OUT OF BOUND: ratio %.4f < %.4f"
              % (overhead["tokens_per_sec_ratio"],
                 1.0 - OVERHEAD_BOUND), file=sys.stderr)
        return 1
    # the recompile sentry's steady-state invariant: once the warmup
    # boundary is marked, membership churn must never recompile an
    # existing executable — a nonzero count here is the compile-storm
    # failure class the health plane exists to catch, and it fails
    # the bench on every leg that carried the plane
    steady_violations = [
        (leg, rec["health"]["steady_recompiles"])
        for leg, rec in [("base", record)] + [
            (k, record[k]) for k in ("paged", "paged_shared",
                                     "paged_shared_spec", "paged_int8")
            if isinstance(record.get(k), dict)
        ]
        if isinstance(rec.get("health"), dict)
        and rec["health"]["steady_recompiles"]
    ]
    if steady_violations:
        print("STEADY-STATE RECOMPILES detected: %r (the zero-"
              "recompile invariant is broken)" % steady_violations,
              file=sys.stderr)
        return 1
    return 0 if record["completed"] > 0 else 1


if __name__ == "__main__":
    sys.exit(main())
