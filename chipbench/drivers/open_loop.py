"""Driver of the traffic kind `open_loop`: the real serving stack
(`serving.main.build_server`: GenerationServer, scheduler, paged pool)
with its gRPC transport, in the process that holds the chip, under an
open-loop schedule of streaming requests made from the seed. Times are
taken at the client from each request's due time. Once the window has
closed and every request has drained, the server is freed and a seeded
sample of the finished requests, the longest among them, is compared
with the plain reference."""

import concurrent.futures
import hashlib
import json
import math
import time

import numpy as np

from chipbench import correct, probes, stats, traffic

# what this driver uses of a family's reference and of a configuration
# file (refs/__init__.py holds every family to it)
NEEDS = {
    "reference": ["all_leaves", "outer_leaves", "layer_leaves",
                  "make_leaves", "block_weights", "embed", "layer",
                  "head_logits", "matmul", "matmul_fp8"],
    "config": ["model.model_zoo", "model.model_def",
               "model.params.vocab_size", "model.params.num_layers",
               "server.num_slots"],
}


def _unflatten(flat):
    tree = {}
    for path, value in flat.items():
        node = tree
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = value
    return tree


def _build_server(run, ref, rcfg, spans):
    """`serving.main.build_server` with the flags of the configuration;
    every other option keeps the program's default. The state it asks
    the Trainer for is the benchmark's: weights from the seed and no
    optimizer slots (the program's own init would also allocate
    adamw's, 12 bytes a parameter that serving never reads)."""
    import jax
    import jax.numpy as jnp
    from flax.core import FrozenDict

    from elasticdl_tpu.serving import main as serving_main
    from elasticdl_tpu.training import trainer as trainer_mod

    model = run.cfg["model"]
    flags = [
        "--model_zoo", "%s/%s" % (run.root, model["model_zoo"]),
        "--model_def", model["model_def"],
        "--model_params", "; ".join(
            "%s=%r" % kv for kv in sorted(model["params"].items())),
        "--port", "0",
    ]
    for key, value in sorted(run.cfg["server"].items()):
        flags += ["--" + key, str(value)]

    def init_state(_trainer, _example_batch):
        t0 = time.perf_counter()
        params = _unflatten(
            ref.make_leaves(rcfg, run.seed, ref.all_leaves(rcfg)))
        jax.block_until_ready(params)
        spans["state_init_s"].append(time.perf_counter() - t0)
        return trainer_mod.TrainState(
            step=jnp.zeros((), jnp.int32), params=params, opt_state=(),
            model_state=FrozenDict({}), rng=jax.random.PRNGKey(0))

    original = trainer_mod.Trainer.init_state
    trainer_mod.Trainer.init_state = init_state
    try:
        return serving_main.build_server(
            serving_main.parse_serving_args(flags))
    finally:
        trainer_mod.Trainer.init_state = original


def _tap(obj, name, sink, pick):
    """Keep raw values of a telemetry call beside the program's own
    histogram: `pick(args, kwargs)` -> the value to keep."""
    inner = getattr(obj, name)

    def tapped(*args, **kwargs):
        sink.append((time.time(), pick(args, kwargs)))
        return inner(*args, **kwargs)

    setattr(obj, name, tapped)


class _Client(object):
    """One streaming request: arrival time of every chunk."""

    def __init__(self, stub, pb, spec, t_due):
        self.stub, self.pb, self.spec, self.t_due = stub, pb, spec, t_due
        self.t_sent = None
        self.arrivals, self.tokens = [], []
        self.error = None
        self.t_done = None

    def __call__(self):
        self.t_sent = time.time()
        try:
            for chunk in self.stub.generate_stream(self.pb.GenerateRequest(
                    prompt=self.spec["prompt"],
                    max_new_tokens=self.spec["max_new_tokens"]),
                    timeout=600):
                now = time.time()
                if chunk.tokens:
                    self.arrivals.append((now, len(chunk.tokens)))
                    self.tokens.extend(chunk.tokens)
            if len(self.tokens) != self.spec["max_new_tokens"]:
                self.error = "got %d of %d tokens" % (
                    len(self.tokens), self.spec["max_new_tokens"])
        except Exception as e:  # a failed request is counted, not fatal
            self.error = "%s: %s" % (type(e).__name__, e)
        self.t_done = time.time()
        return self


def _send(pool, stub, pb, schedule, t_open, tracer):
    """Offer the schedule in real time; returns the clients."""
    clients, futures = [], []
    for spec in schedule:
        t_due = t_open + spec["due_s"]
        while True:
            tracer.poll()
            wait = t_due - time.time()
            if wait <= 0:
                break
            time.sleep(min(wait, 0.05))
        client = _Client(stub, pb, spec, t_due)
        clients.append(client)
        futures.append(pool.submit(client))
    return clients, futures


def _warm_up(pool, stub, pb, mix, vocab, slots, say):
    """Every prefill shape of the mix and the decode step, first one at
    a time, then all slots at once."""
    rng = stats.rng_for(0, "warmup")
    lens = [p for p, _ in mix["prompt_lens"]]

    def one(p):
        spec = {"prompt": [rng.randrange(vocab) for _ in range(p)],
                "max_new_tokens": 4}
        return _Client(stub, pb, spec, time.time())

    for p in lens:
        c = one(p)()
        if c.error:
            raise RuntimeError("warm-up request failed: %s" % c.error)
    burst = [one(lens[i % len(lens)]) for i in range(slots)]
    for f in [pool.submit(c) for c in burst]:
        if f.result().error:
            raise RuntimeError("warm-up burst failed: %s" % f.result().error)
    say("warm-up: %d prompt lengths, then %d at once" % (len(lens), slots))


def _window(run, mix, pool, stub, pb, vocab, tracer, seed):
    """Offer one window's schedule, wait for its close and for the
    drain, and reduce the clients' clocks."""
    schedule = traffic.open_loop_schedule(mix, seed, run.seconds, vocab)
    run.say("inputs: %d requests, sha1 %s" % (len(schedule), hashlib.sha1(
        json.dumps(schedule).encode()).hexdigest()))
    tracer.start()
    t_open = time.time()
    t_close = t_open + run.seconds
    clients, futures = _send(pool, stub, pb, schedule, t_open, tracer)
    while time.time() < t_close:
        tracer.poll()
        time.sleep(0.02)
    tracer.poll(force=True)
    in_flight = sum(1 for c in clients if c.t_done is None)
    concurrent.futures.wait(futures, timeout=mix.get("drain_s", 120))
    finished = [c for c in clients if c.t_done is not None and not c.error]
    done_in = [c for c in finished if c.t_done <= t_close]
    return {
        "clients": clients, "t_open": t_open, "t_close": t_close,
        "finished": finished, "failed": len(clients) - len(finished),
        "in_flight": in_flight, "done_in": len(done_in),
        "undrained": sum(1 for c in clients if c.t_done is None),
        "out_tokens": sum(len(c.tokens) for c in done_in),
        # token by token: what was delivered, and which prompts got
        # their first token, between the window's two ends
        "out_delivered": sum(n for c in clients for t, n in c.arrivals
                             if t_open <= t <= t_close),
        "prompts_served": sum(len(c.spec["prompt"]) for c in clients
                              if c.arrivals
                              and t_open <= c.arrivals[0][0] <= t_close),
        "ttft": [(c.arrivals[0][0] - c.t_due) if c.arrivals else math.inf
                 for c in clients],
        "itl": [b[0] - a[0] for c in clients
                for a, b in zip(c.arrivals, c.arrivals[1:])],
        "late": [c.t_sent - c.t_due for c in clients],
    }


def _say_window(run, w, tag):
    ms = lambda xs, q: 1e3 * (stats.percentile(xs, q) or math.nan)
    run.say("%s%d offered, %d finished (%d inside the window, %.1f tokens/s)"
            ", %d failed, %d in flight at its close, %d never drained; "
            "delivered inside it %.1f output tokens/s, %.1f prompt + output "
            "tokens/s"
            % (tag, len(w["clients"]), len(w["finished"]), w["done_in"],
               w["out_tokens"] / run.seconds, w["failed"], w["in_flight"],
               w["undrained"], w["out_delivered"] / run.seconds,
               (w["out_delivered"] + w["prompts_served"]) / run.seconds))
    run.say("%sttft ms p50 %.1f p95 %.1f over %d; token gap ms p50 %.1f "
            "p95 %.1f over %d; generator late ms p50 %.2f worst %.2f"
            % (tag, ms(w["ttft"], 50), ms(w["ttft"], 95), len(w["ttft"]),
               ms(w["itl"], 50), ms(w["itl"], 95), len(w["itl"]),
               ms(w["late"], 50), 1e3 * max(w["late"])))


def _ticks_within(steps, t0, t1):
    """(lanes seated, step seconds) of the tapped decode steps that
    ended in [t0, t1]."""
    inside = [v for (t, v) in steps if t0 <= t <= t1]
    return [v[0] for v in inside], [v[1] for v in inside]


def token_reaches(clients, t0, t1):
    """The cached tokens behind every token that arrived in [t0, t1]
    (prompt + what the request had generated before it), raw: a
    kernel's reader folds them by what it knows of the layers (a
    window, a top-k) into the bytes the kernel needed."""
    out = []
    for c in clients:
        seen = len(c.spec["prompt"])
        for t, n in c.arrivals:
            if t0 <= t <= t1:
                out.extend(range(seen, seen + n))
            seen += n
    return out


def reference_logits(run, ref, rcfg, sample, mm, rows=512):
    """float32 logits of the reference at the positions that produced
    each served token: one full forward over prompt + served tokens,
    layer by layer, each layer's weights made from the seed and dropped
    after use."""
    import jax
    import jax.numpy as jnp

    outer = ref.make_leaves(rcfg, run.seed, ref.outer_leaves(rcfg))
    # the reference is told which layer it computes, statically: a
    # family's layers may differ in kind (window, full, sparse)
    layer = jax.jit(lambda w, x, i: ref.layer(rcfg, w, x, mm, rows, i),
                    static_argnums=2)
    xs = []
    for c in sample:
        seq = list(c.spec["prompt"]) + list(c.tokens)
        pad = -len(seq) % rows
        xs.append(ref.embed(outer, jnp.asarray(seq + [0] * pad)[None]))
    for i in range(rcfg["num_layers"]):
        w = ref.block_weights(
            ref.make_leaves(rcfg, run.seed, ref.layer_leaves(rcfg, i)), i)
        xs = [layer(w, x, i) for x in xs]
        del w
    head = jax.jit(lambda w, x: ref.head_logits(w, x, mm))
    out = []
    for c, x in zip(sample, xs):
        p, n = len(c.spec["prompt"]), len(c.tokens)
        out.append(np.asarray(head(outer, x[0, p - 1:p - 1 + n])))
    return out


def pick_sample(run, finished, k):
    """k finished requests drawn from the seed, and the longest."""
    rng = stats.rng_for(run.seed, "sample")
    longest = max(finished, key=lambda c: (
        len(c.spec["prompt"]) + len(c.tokens), -c.t_due))
    rest = [c for c in finished if c is not longest]
    return [longest] + rng.sample(rest, min(k, len(rest)))


def check(run, ref, rcfg, sample):
    t0 = time.time()
    served = [np.asarray(c.tokens) for c in sample]
    want = reference_logits(run, ref, rcfg, sample, ref.matmul)
    numbers, info = correct.serve_numbers(want, served)
    run.say("reference: %d requests, %d served tokens, longest %d, in "
            "%.1f s; agreement with reference-greedy %.4f" % (
                len(sample), info["tokens"],
                max(len(c.spec["prompt"]) + len(c.tokens) for c in sample),
                time.time() - t0, info["agreement"]))
    if run.control:
        low = reference_logits(run, ref, rcfg, sample, ref.matmul_fp8)
        ctl, _ = correct.serve_numbers(
            want, [np.asarray(x).argmax(-1) for x in low])
        for name, value in sorted(ctl.items()):
            run.say("control(fp8): %-22s %.6g" % (name, value))
    return numbers


def run_cell(run):
    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.proto.service import ServingStub, build_channel

    mix, ref = run.mix, run.reference
    params = run.cfg["model"]["params"]
    rcfg = dict(params, **run.cfg.get("weights", {}))
    vocab = params["vocab_size"]
    slots = int(run.cfg["server"]["num_slots"])
    spans = {"state_init_s": []}
    compiles = probes.CompileCounter()
    tracer = probes.WindowTrace(run.trace, mix.get("trace_seconds", 3),
                                run.workdir)
    server = _build_server(run, ref, rcfg, spans).start()
    queue_wait, steps = [], []
    _tap(server.telemetry, "record_queue_wait", queue_wait,
         lambda a, k: a[0])
    _tap(server.telemetry, "record_step", steps,
         lambda a, k: (a[1], a[2]))  # active slots, step seconds
    channel = build_channel("localhost:%d" % server.port)
    stub = ServingStub(channel)
    pool = concurrent.futures.ThreadPoolExecutor(max_workers=128)
    try:
        _warm_up(pool, stub, pb, mix, vocab, slots, run.say)
        server.telemetry.reset_latency()
        server.mark_steady()
        for i, rate in enumerate(run.sweep):  # the builder's knee sweep
            w = _window(run, dict(mix, rate_per_s=rate), pool, stub, pb,
                        vocab, probes.WindowTrace(False, 0, run.workdir),
                        run.seed + 1000 * (i + 1))
            _say_window(run, w, "sweep rate %.3g/s: " % rate)
            lanes, step_s = _ticks_within(steps, w["t_open"], w["t_close"])
            run.say("sweep rate %.3g/s: lanes seated a tick median %s p95 "
                    "%s, step ms median %.3f over %d ticks" % (
                        rate, stats.percentile(lanes, 50),
                        stats.percentile(lanes, 95),
                        1e3 * (stats.percentile(step_s, 50) or math.nan),
                        len(lanes)))
        compiles_at_open = compiles.count
        run.mark_window_open()
        w = _window(run, mix, pool, stub, pb, vocab, tracer, run.seed)
        window_compiles = compiles.count - compiles_at_open
        device = run.describe_devices()
        trace = tracer.reduce(run.say)
    finally:
        server.stop(drain=False)
        channel.close()
        pool.shutdown(wait=True)
    clients, t_open, t_close = w["clients"], w["t_open"], w["t_close"]
    _say_window(run, w, "window: ")
    for c in clients:
        if c.error:
            run.say("request failed: %s" % c.error)
    finished, failed = w["finished"], w["failed"]
    active_slots, decode_step_s = _ticks_within(steps, t_open, t_close)
    traced_reach = ([] if tracer.t1 is None
                    else token_reaches(clients, tracer.t0, tracer.t1))

    # the server is gone; the reference takes its place
    del server, stub
    probes.free_device_memory()
    if not finished:
        raise RuntimeError("no request finished")
    numbers = check(run, ref, rcfg,
                    pick_sample(run, finished, run.cell["sample"]))
    numbers["failed_requests"] = float(failed)
    ok = correct.judge(numbers, run.cell["limits"], run.say)
    return {
        "correct": ok, "numbers": numbers, "attempted": len(clients),
        "failed": failed,
        "device": device, "trace": trace, "window_s": run.seconds,
        "samples": {
            "ttft_s": w["ttft"], "itl_s": w["itl"],
            "state_init_s": spans["state_init_s"],
            "queue_wait_s": [v for (t, v) in queue_wait
                             if t_open <= t <= t_close],
            "active_slots": active_slots,
            "decode_step_s": decode_step_s,
            "traced_token_reach": traced_reach,
        },
        "counters": {
            "window_s": run.seconds,
            "out_tokens_delivered": w["out_delivered"],
            "total_tokens_served": w["out_delivered"] + w["prompts_served"],
            "requests": len(clients), "window_compiles": window_compiles,
            "memory_peak_bytes": device["memory_peak_bytes"],
        },
    }
