#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py          # needs a TPU; exits nonzero without
    python3 chip_smoke.py --tiny   # rehearsal on the CPU, toy widths

Drives the main path once, through the entry points a user runs, at the
full width of the flagship (transformer_lm, d=1024, 8 heads x 128, 8
layers, vocab 32000, seq 1024, bf16; weights random from a seed):

  train-1chip    `elasticdl_tpu.client.main train`: local master ->
                 gRPC -> ONE worker process -> Trainer.train_step
  serve-1chip    `elasticdl_tpu.serving.main` with the paged KV pool,
                 Generate x3 + GenerateStream + ServerStatus over gRPC
  kernels        the lowered train step and the lowered paged decode
                 step hold the Mosaic kernels; each kernel agrees with
                 its jnp oracle on a small input
  too-many       more workers than chips is refused at start
  train-dp4      the same CLI, one worker driving four chips (dp=4)
  train-2workers the same CLI, two workers, one chip each

One process holds a chip at a time, so every leg is a child process and
this parent stays off JAX until the last child has exited. A leg this
machine cannot run (fewer chips than it needs) is printed as not run,
by name. Any leg that fails ends the run with a nonzero exit code and
its reason on the last line. A passing run ends with one JSON object on
the last line of stdout:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

`--tiny` exists so the command can be rehearsed without a chip
(JAX_PLATFORMS=cpu, four virtual devices, kernels interpreted, toy
widths) and pinned by a tier-1 test. Its last line says
"platform": "cpu" and "rehearsal": "tiny".

Logs and data go to <checkout>/chiprun_out/chip_smoke/ (git-ignored).
"""

import argparse
import concurrent.futures
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, "chiprun_out", "chip_smoke")

MODEL_DEF = "transformer_lm.transformer_lm.custom_model"


class Sizes(object):
    """One run's widths. `full` is the flagship — the one configuration
    with chip lineage; `tiny` only rehearses the command."""

    def __init__(self, tiny):
        self.tiny = tiny
        if tiny:
            self.model = dict(vocab_size=64, seq_len=256, embed_dim=32,
                              num_heads=2, num_layers=1)
            self.minibatch, self.block_records = 8, 16
            self.prompts, self.new_tokens = (17, 70, 130), 8
            self.stream_prompt = 40
        else:
            self.model = dict(vocab_size=32000, seq_len=1024,
                              embed_dim=1024, num_heads=8, num_layers=8,
                              dtype="bf16")
            self.minibatch, self.block_records = 32, 64
            # 64-token prefill buckets: 17 -> 64, 130 -> 192, 600 -> 640
            self.prompts, self.new_tokens = (17, 130, 600), 32
            self.stream_prompt = 45
        # four identical shards: the master shuffles tasks, and every
        # order must feed the same batches so that legs are comparable
        self.shards = 4
        self.records = self.shards * self.block_records
        self.steps = self.records // self.minibatch
        self.num_slots, self.kv_block = 4, 16

    @property
    def model_params(self):
        return "; ".join(
            "%s=%r" % kv for kv in sorted(self.model.items())
        )

    def serving_flags(self):
        return [
            "--model_zoo", os.path.join(HERE, "model_zoo"),
            "--model_def", MODEL_DEF,
            "--model_params", self.model_params,
            "--port", "0", "--num_slots", str(self.num_slots),
            "--kv_block_size", str(self.kv_block),
        ]


class LegFailed(Exception):
    pass


def check(cond, why):
    if not cond:
        raise LegFailed(why)


def say(msg=""):
    print(msg, flush=True)


# ----------------------------------------------------------- processes

_LIVE = []


def spawn(cmd, env, log_path):
    """Start a child in its own process group (the CLI starts workers
    of its own; the whole group is what must be gone at exit)."""
    log = open(log_path, "w")
    proc = subprocess.Popen(
        cmd, cwd=HERE, env=env, stdout=log, stderr=subprocess.STDOUT,
        start_new_session=True,
    )
    proc.log_file = log
    _LIVE.append(proc)
    return proc


def reap(proc, timeout, what):
    """Wait for a child, then make sure nothing of its process group
    is left: a straggler would still hold its chip. On timeout the
    group is killed and the leg fails."""
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        rc = None
    finally:
        proc.log_file.close()
    kill_group(proc)
    if rc is None:
        raise LegFailed("%s did not finish within %d s" % (what, timeout))
    return rc


def group_members(pgid):
    """Pids of the live (non-zombie) processes in a process group."""
    alive = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open("/proc/%s/stat" % pid) as f:
                # pid (comm) state ppid pgrp ...; comm may hold spaces
                state, _, pgrp = f.read().rsplit(")", 1)[1].split()[:3]
        except (OSError, ValueError):
            continue  # it exited while we looked
        if int(pgrp) == pgid and state != "Z":
            alive.append(int(pid))
    return alive


def kill_group(proc):
    """SIGKILL whatever is left of the child's process group and wait
    until nothing of it lives."""
    deadline = time.time() + 60
    while group_members(proc.pid) and time.time() < deadline:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            break
        proc.poll()
        time.sleep(0.1)
    proc.wait()


def kill_everything():
    for proc in _LIVE:
        kill_group(proc)


def child_env(sizes):
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    # the compiler module logs every persistent-cache hit and miss
    env["JAX_DEBUG_LOG_MODULES"] = "jax._src.compiler"
    if sizes.tiny:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip()
        env["ELASTICDL_TPU_FORCE_INTERPRET"] = "1"
    return env


def read(path):
    with open(path, errors="replace") as f:
        return f.read()


def tail(path, n=25):
    return "\n".join(read(path).splitlines()[-n:])


# ------------------------------------------------------------- parsing


def startups(log):
    """{role: payload} of the `<role> startup: {json}` lines."""
    out = {}
    for role, payload in re.findall(r"\] (\w+(?: \d+)?) startup: (\{.*\})",
                                    log):
        out[role] = json.loads(payload)
    return out


def worker_steps(log):
    """{worker_id: [(step, loss, seconds), ...]}"""
    out = {}
    for wid, step, loss, secs in re.findall(
            r"Worker (\d+) step (\d+) loss (\S+) \((\S+) s\)", log):
        out.setdefault(int(wid), []).append(
            (int(step), float(loss), float(secs)))
    return out


def cache_use(log):
    hits = re.findall(r"Persistent compilation cache hit for '([^']+)'",
                      log)
    misses = re.findall(
        r"PERSISTENT COMPILATION CACHE MISS for '([^']+)'", log)
    return {"hits": len(hits), "misses": len(misses),
            "hit_names": sorted(set(hits)),
            "miss_names": sorted(set(misses))}


def codec(log):
    found = sorted(set(re.findall(r"TRec codec: (native|python)", log)))
    return "/".join(found) or "not logged"


def describe_device(info):
    return ("platform: %(platform)s, device_kind: %(device_kind)s, "
            "devices used: %(count)d of %(visible)d visible, "
            "jax %(jax)s / jaxlib %(jaxlib)s / libtpu %(libtpu)s" % info)


def print_cache(info, use):
    say("  compile cache: %s; hits %d, misses %d%s" % (
        info.get("compile_cache"), use["hits"], use["misses"],
        (" (hit: %s)" % ", ".join(use["hit_names"])
         if use["hit_names"] else "")))


# ---------------------------------------------------------------- legs


def probe_device(sizes):
    """Ask JAX, in a child, what the first device is — before anything
    at the flagship's width is attempted."""
    code = (
        "import json, jax\n"
        "d = jax.devices()\n"
        "print('DEVICE ' + json.dumps({'platform': d[0].platform, "
        "'kind': d[0].device_kind, 'count': len(d)}))\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=HERE, env=child_env(sizes),
        capture_output=True, text=True, timeout=300,
    )
    found = re.search(r"^DEVICE (\{.*\})$", r.stdout, re.M)
    check(r.returncode == 0 and found,
          "JAX could not list its devices: %s"
          % (r.stderr.strip().splitlines() or ["no output"])[-1])
    return json.loads(found.group(1))


def make_data(sizes):
    """One seeded shard of token records, copied into identical
    shards (see Sizes.shards)."""
    from elasticdl_tpu.data import recordio_gen

    data_dir = os.path.join(WORK, "tokens")
    (first,) = recordio_gen.gen_tokens_like(
        data_dir, num_files=1, records_per_file=sizes.block_records,
        seed=0, seq_len=sizes.model["seq_len"] + 1,
        vocab_size=sizes.model["vocab_size"],
    )
    for i in range(1, sizes.shards):
        shutil.copyfile(first, first.replace("-0000.", "-%04d." % i))
    return data_dir


def run_train_cli(sizes, name, data_dir, extra, timeout):
    log_path = os.path.join(WORK, name + ".log")
    cmd = [
        sys.executable, "-m", "elasticdl_tpu.client.main", "train",
        "--model_zoo", os.path.join(HERE, "model_zoo"),
        "--model_def", MODEL_DEF,
        "--model_params", sizes.model_params,
        "--training_data", data_dir,
        "--minibatch_size", str(sizes.minibatch),
        "--records_per_task", str(sizes.block_records),
        "--port", "0",
    ] + extra
    t0 = time.time()
    rc = reap(spawn(cmd, child_env(sizes), log_path), timeout, name)
    return rc, read(log_path), log_path, time.time() - t0


def check_training(sizes, name, rc, log, log_path, workers, platform,
                   epochs=1):
    """What every train leg must show: exit 0, every task completed,
    the master's model version, and per worker the start-up line and
    finite losses. `workers` are the ids that must have taken steps."""
    check(rc == 0, "%s: the train CLI exited with %s; log tail:\n%s"
          % (name, rc, tail(log_path)))
    tasks = len(re.findall(r"Task:\d+ completed", log))
    check(tasks == epochs * sizes.shards,
          "%s: the master completed %d tasks, the records imply %d"
          % (name, tasks, epochs * sizes.shards))
    version = re.search(r"All tasks finished at model version (\d+)", log)
    check(version, "%s: the master did not report its final version"
          % name)
    steps = worker_steps(log)
    starts = startups(log)
    check(set(workers) <= set(steps),
          "%s: expected steps from workers %s, got them from %s"
          % (name, list(workers), sorted(steps)))
    for wid, rows in sorted(steps.items()):
        info = starts.get("Worker %d" % wid)
        check(info, "%s: worker %d logged no start-up line" % (name, wid))
        check(info["platform"] == platform,
              "%s: worker %d reports platform %r, not %r"
              % (name, wid, info["platform"], platform))
        check(all(math.isfinite(loss) for _, loss, _ in rows),
              "%s: worker %d has a non-finite loss: %s"
              % (name, wid, [loss for _, loss, _ in rows]))
        say("  worker %d: %s" % (wid, describe_device(info)))
        say("  worker %d: attention %s; mesh %s; chip %s" % (
            wid, info["attention"], info.get("mesh") or "one device",
            info.get("chip_paths") or "all visible"))
        losses = ["%.4f" % l for _, l, _ in rows]
        if len(losses) > 10:
            losses = losses[:5] + ["..."] + losses[-4:]
        say("  worker %d: %d steps, all losses finite: %s"
            % (wid, len(rows), " ".join(losses)))
        say("  worker %d: first step (state init + compile) %.1f s, "
            "the other %d steps %.2f s" % (
                wid, rows[0][2], len(rows) - 1,
                sum(s for _, _, s in rows[1:])))
    total = sum(len(rows) for rows in steps.values())
    check(total == epochs * sizes.steps,
          "%s: %d steps were taken, the records imply %d"
          % (name, total, epochs * sizes.steps))
    say("  master: %d/%d tasks completed, final model version %s; "
        "record codec %s" % (tasks, epochs * sizes.shards,
                             version.group(1), codec(log)))
    print_cache(next(iter(starts.values())), cache_use(log))
    return steps, starts, int(version.group(1))


def leg_train_1chip(sizes, data_dir, platform):
    rc, log, path, wall = run_train_cli(
        sizes, "train-1chip", data_dir, ["--num_workers", "1"], 900)
    steps, starts, version = check_training(
        sizes, "train-1chip", rc, log, path, [0], platform)
    check(starts["Worker 0"]["count"] == 1,
          "train-1chip: the default strategy should use one device")
    check(version == sizes.steps,
          "train-1chip: the master's version %d is not the step count"
          % version)
    say("  wall %.1f s" % wall)
    return steps[0]


def leg_serve(sizes, platform):
    import grpc  # noqa: F401  (the parent speaks gRPC, never JAX)

    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.proto.service import ServingStub, build_channel

    log_path = os.path.join(WORK, "serve-1chip.log")
    t0 = time.time()
    proc = spawn(
        [sys.executable, "-m", "elasticdl_tpu.serving.main"]
        + sizes.serving_flags(), child_env(sizes), log_path)
    port = None
    while port is None:
        found = re.search(r"SERVING_READY port=(\d+)", read(log_path))
        if found:
            port = int(found.group(1))
            break
        if proc.poll() is not None or time.time() - t0 > 600:
            kill_group(proc)
            raise LegFailed("serve-1chip: the server did not become "
                            "ready; log tail:\n%s" % tail(log_path))
        time.sleep(0.5)
    ready_s = time.time() - t0
    vocab = sizes.model["vocab_size"]
    stub = ServingStub(build_channel("localhost:%d" % port))

    def prompt(n, salt):
        return [(7 * i + salt) % vocab for i in range(n)]

    def generate(n):
        t = time.time()
        reply = stub.generate(pb.GenerateRequest(
            prompt=prompt(n, n), max_new_tokens=sizes.new_tokens),
            timeout=600)
        return n, list(reply.tokens), time.time() - t

    try:
        # together, so the decode step runs with several slots seated
        with concurrent.futures.ThreadPoolExecutor(4) as pool:
            jobs = [pool.submit(generate, n) for n in sizes.prompts]
            stream_t = time.time()
            chunks = list(stub.generate_stream(pb.GenerateRequest(
                prompt=prompt(sizes.stream_prompt, 3),
                max_new_tokens=sizes.new_tokens), timeout=600))
            stream_s = time.time() - stream_t
            replies = [job.result() for job in jobs]
        status = stub.server_status(pb.Empty(), timeout=30)
    finally:
        proc.send_signal(signal.SIGTERM)
    rc = reap(proc, 120, "serve-1chip shutdown")
    log = read(log_path)
    check(rc == 0, "serve-1chip: the server exited with %s; log tail:\n%s"
          % (rc, tail(log_path)))
    info = startups(log).get("Serving")
    check(info, "serve-1chip: the server logged no start-up line")
    check(info["platform"] == platform,
          "serve-1chip: the server reports platform %r, not %r"
          % (info["platform"], platform))
    say("  server: %s" % describe_device(info))
    say("  server: attention %s; paged decode %s" % (
        info["attention"], info["paged_decode"]))
    want = "pallas-interpret" if sizes.tiny else "pallas"
    check(info["paged_decode"] == want,
          "serve-1chip: paged decode took %r, not the %s kernel"
          % (info["paged_decode"], want))
    for n, tokens, secs in replies:
        new = tokens[n:]
        check(tokens[:n] == prompt(n, n) and len(new) == sizes.new_tokens
              and all(0 <= t < vocab for t in new),
              "serve-1chip: Generate(%d-token prompt) returned %d new "
              "tokens, ids in range: %s" % (
                  n, len(new), all(0 <= t < vocab for t in new)))
        say("  Generate: prompt %d -> %d new tokens in %.2f s"
            % (n, len(new), secs))
    streamed = [t for c in chunks for t in c.tokens]
    check(len(streamed) == sizes.new_tokens and chunks[-1].done
          and all(0 <= t < vocab for t in streamed),
          "serve-1chip: GenerateStream returned %d tokens in %d chunks"
          % (len(streamed), len(chunks)))
    say("  GenerateStream: prompt %d -> %d tokens in %d chunks, %.2f s"
        % (sizes.stream_prompt, len(streamed), len(chunks), stream_s))
    check(status.completed == len(sizes.prompts) + 1 and status.kv_paged,
          "serve-1chip: ServerStatus counts %d completed (paged=%s)"
          % (status.completed, status.kv_paged))
    check(status.health_state == "ok",
          "serve-1chip: health_state is %r" % status.health_state)
    say("  ServerStatus: completed %d, tokens %d, health_state %r, "
        "memory ledger drift %d bytes, KV blocks %d x %d tokens" % (
            status.completed, status.tokens_generated,
            status.health_state, status.memory_unaccounted_bytes,
            status.kv_blocks_total, status.kv_block_size))
    fallbacks = re.findall(r"\] (flash_attention takes .*)", log)
    for line in sorted(set(fallbacks)):
        say("  server: %s (x%d, once per layer)"
            % (line, fallbacks.count(line)))
    say("  ready after %.1f s (state init); %d tokens generated"
        % (ready_s, status.tokens_generated))
    print_cache(info, cache_use(log))


def leg_kernels(sizes, platform):
    log_path = os.path.join(WORK, "kernels.log")
    cmd = [sys.executable, os.path.abspath(__file__), "--child-kernels"]
    if sizes.tiny:
        cmd.append("--tiny")
    rc = reap(spawn(cmd, child_env(sizes), log_path), 900, "kernels")
    log = read(log_path)
    found = re.search(r"^KERNELS (\{.*\})$", log, re.M)
    check(rc == 0 and found,
          "kernels: the child exited with %s; log tail:\n%s"
          % (rc, tail(log_path)))
    rep = json.loads(found.group(1))
    check(rep["device"]["platform"] == platform,
          "kernels: ran on %r" % rep["device"]["platform"])
    say("  %s" % describe_device(rep["device"]))
    say("  interpret_mode(): %s" % rep["interpret_mode"])
    check(rep["interpret_mode"] == sizes.tiny,
          "kernels: interpret_mode() is %s" % rep["interpret_mode"])
    layers = sizes.model["num_layers"]
    train, serve = rep["train_step"], rep["paged_step"]
    say("  train step: pallas_call in the traced program: %s"
        % json.dumps(train["pallas_calls"], sort_keys=True))
    for kernel in ("_flash_kernel", "_flash_bwd_dq_kernel",
                   "_flash_bwd_dkv_kernel"):
        check(train["pallas_calls"].get(kernel) == layers,
              "kernels: the train step holds %s x%s, expected x%d"
              % (kernel, train["pallas_calls"].get(kernel), layers))
    say("  paged decode step: pallas_call in the traced program: %s"
        % json.dumps(serve["pallas_calls"], sort_keys=True))
    check(serve["pallas_calls"].get("_paged_kernel") == layers,
          "kernels: the paged step holds _paged_kernel x%s, expected x%d"
          % (serve["pallas_calls"].get("_paged_kernel"), layers))
    say("  paged decode step: the kernel sits inside %s — %s" % (
        serve["enclosing"] or "no loop",
        "jax.vmap over slots became a per-slot loop, one launch per "
        "slot per layer" if serve["enclosing"] else
        "one launch per layer for all slots"))
    if not sizes.tiny:
        # compiled, not interpreted: the lowered module names a Mosaic
        # custom call per kernel, and the executable keeps them
        for part, want in ((train, 3 * layers), (serve, layers)):
            say("  %s: Mosaic custom calls in the lowered module %s; "
                "tpu_custom_call in the executable: %d; compile %.1f s"
                % (part["name"], json.dumps(part["mosaic"],
                                            sort_keys=True),
                   part["compiled_custom_calls"], part["compile_s"]))
            check(sum(part["mosaic"].values()) == want
                  and part["compiled_custom_calls"] == want,
                  "kernels: %s should hold %d Mosaic custom calls"
                  % (part["name"], want))
    for name, err in sorted(rep["oracle_errors"].items()):
        say("  %s vs its jnp oracle: max error %.2e (bound %.0e)"
            % (name, err, rep["oracle_bound"]))
        check(err <= rep["oracle_bound"],
              "kernels: %s is %.3g from its oracle" % (name, err))
    print_cache({"compile_cache": rep["compile_cache"]}, cache_use(log))


def leg_too_many_workers(sizes, data_dir, chips):
    """A job asking for more workers than chips must fail at start,
    with a message, before any worker exists."""
    name = "too-many"
    rc, log, _, wall = run_train_cli(
        sizes, name, data_dir, ["--num_workers", str(chips + 1)], 120)
    refusal = re.search(r"\d+ workers asked for, but this host has .*",
                        log)
    check(rc != 0 and refusal and "Starting worker" not in log,
          "%s: %d workers on %d chip(s) exited with %s and no refusal"
          % (name, chips + 1, chips, rc))
    say("  refused in %.1f s: %s" % (wall, refusal.group(0)[:160]))


def leg_train_dp4(sizes, data_dir, platform, one_chip_steps):
    name = "train-dp4"
    rc, log, path, wall = run_train_cli(
        sizes, name, data_dir,
        ["--num_workers", "1", "--distribution_strategy",
         "AllreduceStrategy", "--mesh_spec", "dp=4"], 900)
    steps, starts, _ = check_training(sizes, name, rc, log, path, [0],
                                      platform)
    info = starts["Worker 0"]
    check(info["count"] == 4 and info["mesh"] == {"dp": 4},
          "%s: the worker uses %d device(s), mesh %s"
          % (name, info["count"], info["mesh"]))
    placed = re.search(r"placed as (\d+) shard\(s\) of (\d+) rows on "
                       r"devices (\[.*\])", log)
    check(placed and int(placed.group(1)) == 4,
          "%s: the batch was not placed as four shards" % name)
    say("  batch: %s shards of %s rows on devices %s"
        % placed.groups())
    if not sizes.tiny:
        mem = json.loads(re.search(
            r"Worker 0 device memory: (\{.*\})", log).group(1))
        say("  peak bytes in use per device: %s" % json.dumps(
            {k: v.get("peak_bytes_in_use") for k, v in mem.items()},
            sort_keys=True))
        check(len(mem) == 4 and all(
            v.get("peak_bytes_in_use", 0) > 0 for v in mem.values()),
            "%s: not every device held memory: %s" % (name, mem))
    # same seed, same first batch; the four shards are reduced in
    # another order and each runs bf16 matmuls on a quarter of the rows
    tol = 0.05
    first, ref = steps[0][0][1], one_chip_steps[0][1]
    say("  first-step loss %.4f vs one chip %.4f (tolerance %.2f)"
        % (first, ref, tol))
    check(abs(first - ref) <= tol,
          "%s: first-step loss %.4f is not within %.2f of the one-chip "
          "leg's %.4f" % (name, first, tol, ref))
    say("  wall %.1f s" % wall)


def leg_train_2workers(sizes, data_dir, platform):
    name = "train-2workers"
    # A worker's input pipeline shuffles through a 1024-record buffer
    # and pulls tasks until it is full, so the first worker to ask
    # takes 1024 records' worth of tasks at once. Twelve epochs leave
    # twice that for the second worker, which asks while the first is
    # still in state init and compile. The toy rehearsal's tasks last
    # milliseconds, so there only the start-up of both workers is
    # required, not steps from both.
    epochs = 2 if sizes.tiny else 12
    rc, log, path, wall = run_train_cli(
        sizes, name, data_dir,
        ["--num_workers", "2", "--num_epochs", str(epochs)], 900)
    _, starts, _ = check_training(
        sizes, name, rc, log, path, [] if sizes.tiny else [0, 1],
        platform, epochs=epochs)
    check({"Worker 0", "Worker 1"} <= set(starts),
          "%s: start-up lines came from %s" % (name, sorted(starts)))
    if not sizes.tiny:
        chips = [starts["Worker %d" % w].get("chip_paths") for w in (0, 1)]
        check(all(chips) and chips[0] != chips[1],
              "%s: the workers were given chips %s" % (name, chips))
        say("  chips: worker 0 on %s, worker 1 on %s" % tuple(chips))
    say("  wall %.1f s" % wall)


# ------------------------------------------------- the `kernels` child


def child_kernels(sizes):
    """Holds the chip. Lowers the two steps the other legs ran, counts
    the kernels in them, and checks each kernel against its oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from elasticdl_tpu.common.model_utils import get_model_spec
    from elasticdl_tpu.common.platform_utils import (
        configure_compile_cache,
        device_summary,
    )
    from elasticdl_tpu.ops import attention
    from elasticdl_tpu.serving.main import build_server, parse_serving_args
    from elasticdl_tpu.training.trainer import Trainer

    report = {
        "compile_cache": configure_compile_cache(),
        "device": device_summary(jax.devices()[:1]),
        "interpret_mode": attention.interpret_mode(),
    }

    def kernels_in(jaxpr, path=()):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["jaxpr"].debug_info.func_name, path
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from kernels_in(sub, path + (eqn.primitive.name,))

    def describe(name, traced):
        found = list(kernels_in(traced.jaxpr.jaxpr))
        calls = {}
        for kernel, _ in found:
            calls[kernel] = calls.get(kernel, 0) + 1
        loops = sorted({p for _, path in found for p in path
                        if p in ("scan", "while")})
        out = {"name": name, "pallas_calls": calls,
               "enclosing": "/".join(loops)}
        if not sizes.tiny:
            lowered = traced.lower()
            names = re.findall(r'kernel_name = "([^"]+)"',
                               lowered.as_text())
            out["mosaic"] = {k: names.count(k) for k in set(names)}
            t0 = time.time()
            compiled = lowered.compile()
            out["compile_s"] = time.time() - t0
            out["compiled_custom_calls"] = compiled.as_text().count(
                'custom_call_target="tpu_custom_call"')
        return out

    # -- the train step, as the worker builds it
    mb, seq = sizes.minibatch, sizes.model["seq_len"]
    spec = get_model_spec(os.path.join(HERE, "model_zoo"), MODEL_DEF)
    trainer = Trainer(spec, model_params=sizes.model_params)
    tokens = np.random.RandomState(0).randint(
        0, sizes.model["vocab_size"], size=(mb, seq + 1)).astype(np.int32)
    batch = ({"tokens": tokens[:, :-1]}, tokens[:, 1:])
    state = trainer.init_state(batch)
    with trainer.mesh:
        report["train_step"] = describe(
            "train step", trainer._build_train_step().trace(
                state, batch[0], batch[1], np.ones((mb,), np.float32)))
    del state

    # -- the paged decode step, as the server builds it
    server = build_server(parse_serving_args(sizes.serving_flags()))
    engine = server.engine
    with engine.trainer.mesh:
        report["paged_step"] = describe(
            "paged decode step", engine._build_paged_step().trace(
                # traced and compiled, never run: the step donates
                # the pool it is handed, and this one stays the engine's
                engine.kv.pools, engine._exec_variables,
                engine._lanes_spec()))

    # -- each kernel against its jnp oracle, on a small input
    rng = np.random.default_rng(0)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    errors = {}
    q, k, v = rand(2, 4, 256, 128), rand(2, 4, 256, 128), rand(2, 4, 256,
                                                              128)

    def flash_loss(fn):
        def loss(q, k, v):
            return (fn(q, k, v, causal=True).astype(jnp.float32)
                    ** 2).sum()
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    val, grads = flash_loss(attention.flash_attention)(q, k, v)
    ref_val, ref_grads = flash_loss(attention.naive_attention)(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    errors["flash forward"] = abs(float(val) - float(ref_val)) / abs(
        float(ref_val))
    errors["flash backward"] = max(
        float(jnp.abs(g.astype(jnp.float32) - r.astype(jnp.float32)).max()
              / jnp.abs(r.astype(jnp.float32)).max())
        for g, r in zip(grads, ref_grads))
    # the server's pool shape: hkv 8, d 128, block 16; a table with an
    # unallocated tail and a length that ends inside a block
    b, hkv, d, bs, m, nb = 4, 8, 128, sizes.kv_block, 6, 32
    table = np.full((b, m), -1, np.int32)
    table[:, :4] = rng.permutation(nb)[:b * 4].reshape(b, 4)
    args = (rand(b, hkv, 1, d), rand(b, hkv, 1, d), rand(b, hkv, 1, d),
            rand(nb, bs, hkv, d), rand(nb, bs, hkv, d),
            jnp.asarray(table), jnp.full((b,), 4 * bs - 5, jnp.int32))
    paged = [
        jax.jit(lambda *a, use=use: attention.paged_decode_attention(
            *a, use_kernel=use))(*args) for use in (True, False)
    ]
    errors["paged decode"] = float(jnp.abs(paged[0] - paged[1]).max())
    report["oracle_errors"] = errors
    # bf16 inputs against fp32 oracles: errors are relative to the
    # largest oracle value (flash) or absolute on O(1) outputs (paged)
    report["oracle_bound"] = 3e-2
    print("KERNELS " + json.dumps(report), flush=True)


# ---------------------------------------------------------------- main


def run(sizes):
    check(os.path.isdir(os.path.join(HERE, "elasticdl_tpu"))
          and os.path.isdir(os.path.join(HERE, "model_zoo")),
          "chip_smoke.py must sit at the root of an elasticdl-tpu "
          "checkout; %s holds no elasticdl_tpu/ and model_zoo/" % HERE)
    sys.path.insert(0, HERE)
    from elasticdl_tpu.common.platform_utils import (
        configure_compile_cache,
        tpu_chip_paths,
    )

    device = probe_device(sizes)
    platform = "cpu" if sizes.tiny else "tpu"
    check(device["platform"] == platform,
          "jax.devices()[0].platform is %r, not %r: %s" % (
              device["platform"], platform,
              "this run needs a TPU (rehearse with --tiny)"
              if not sizes.tiny else "--tiny runs on the CPU"))
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    cache_dir = configure_compile_cache()
    chips = device["count"]
    say("chip_smoke%s: platform: %s, device_kind: %s, device count: %d"
        % (" --tiny (REHEARSAL, not a chip run)" if sizes.tiny else "",
           device["platform"], device["kind"], chips))
    say("compile cache: %s (%d entries before this run); chip files: %s"
        % (cache_dir,
           len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0,
           tpu_chip_paths() or "none"))
    say("model: transformer_lm %s; %d records -> %d steps of %d"
        % (sizes.model_params, sizes.records, sizes.steps,
           sizes.minibatch))
    data_dir = make_data(sizes)

    not_run = []

    def leg(name, needs, fn, *args):
        if chips < needs:
            not_run.append(name)
            say("\n== %s: NOT RUN — needs %d chips, this machine has %d"
                % (name, needs, chips))
            return None
        say("\n== %s" % name)
        t0 = time.time()
        out = fn(*args)
        say("== %s: passed in %.1f s" % (name, time.time() - t0))
        return out

    first = leg("train-1chip", 1, leg_train_1chip, sizes, data_dir,
                platform)
    leg("serve-1chip", 1, leg_serve, sizes, platform)
    leg("kernels", 1, leg_kernels, sizes, platform)
    if not sizes.tiny:
        leg("too-many", 1, leg_too_many_workers, sizes, data_dir, chips)
    leg("train-dp4", 4, leg_train_dp4, sizes, data_dir, platform, first)
    leg("train-2workers", 2, leg_train_2workers, sizes, data_dir,
        platform)
    say("\nnot run: %s" % (", ".join(not_run) or "none"))

    # every child is gone: the parent may now ask JAX itself
    if sizes.tiny:
        os.environ.update(JAX_PLATFORMS="cpu")
    import jax

    devices = jax.devices()
    result = {"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}
    check(result["device"]["platform"] == platform,
          "the parent sees platform %r" % result["device"]["platform"])
    if sizes.tiny:
        result["rehearsal"] = "tiny"
    return result


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--tiny", action="store_true",
                        help="rehearse on the CPU at toy widths")
    parser.add_argument("--child-kernels", action="store_true",
                        help=argparse.SUPPRESS)
    args = parser.parse_args()
    sizes = Sizes(args.tiny)
    if args.child_kernels:
        sys.path.insert(0, HERE)
        child_kernels(sizes)
        return 0
    t0 = time.time()
    try:
        result = run(sizes)
    except LegFailed as failure:
        say("\nchip_smoke: FAILED after %.0f s: %s"
            % (time.time() - t0, failure))
        return 1
    finally:
        kill_everything()
    say("chip_smoke: all legs that ran passed in %.0f s"
        % (time.time() - t0))
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
