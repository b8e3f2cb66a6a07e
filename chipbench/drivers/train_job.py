"""Driver of the traffic kind `train_job`: one `LocalExecutor.train()`
call over TRec data made from the seed. The harness wraps the two calls
into the layers below the executor — the batch iterator and
`Trainer.train_step` — to time them, to read the first three steps for
`correct`, to open and close the window at step boundaries, and to end
the job when the window closes. The object that `correct` reads is the
one the window then drives."""

import hashlib
import os
import time

import numpy as np

from chipbench import correct, probes


def _flatten(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        if hasattr(v, "items"):
            out.update(_flatten(v, prefix + k + "/"))
        else:
            out[prefix + k] = v
    return out


def _write_data(run, mix, vocab):
    from elasticdl_tpu.data.example_codec import encode_example
    from elasticdl_tpu.data.record_format import RecordWriter

    rng = np.random.default_rng(run.seed)
    rows = rng.integers(0, vocab, (mix["records"], mix["seq_len"] + 1),
                        dtype=np.int32)
    run.say("inputs: %d rows of %d tokens, sha1 %s" % (
        rows.shape[0], rows.shape[1], hashlib.sha1(rows.tobytes()).hexdigest()))
    data_dir = os.path.join(run.workdir, "data")
    os.makedirs(data_dir, exist_ok=True)
    with RecordWriter(os.path.join(data_dir, "tokens-0000.trec")) as w:
        for row in rows:
            w.write(encode_example({"tokens": row}))
    return data_dir, rows


def _adam_mu(opt_state):
    import jax
    import optax

    found = [x for x in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: isinstance(x, optax.ScaleByAdamState))
        if isinstance(x, optax.ScaleByAdamState)]
    if len(found) != 1:
        raise RuntimeError("expected one Adam state, found %d" % len(found))
    return found[0].mu


class _Probe(object):
    """Stands where `Trainer.train_step` stood."""

    def __init__(self, run, mix, executor, tracer, compiles):
        self.run, self.mix, self.executor = run, mix, executor
        self.tracer, self.compiles = tracer, compiles
        self.inner = executor.trainer.train_step
        self.calls = 0
        self.check = {"loss": [], "batches": []}
        self.step_s, self.window_losses = [], []
        self.t_open = self.t_close = None
        self.compiles_at_open = None
        self.check_s = 0.0

    def __call__(self, state, batch, true_count=None):
        k = self.calls
        self.calls += 1
        t0 = time.perf_counter()
        state, loss = self.inner(state, batch, true_count)
        loss = float(loss)  # the step's end: the host has the loss
        now = time.perf_counter()
        if k < 3:
            t = time.time()
            self._read_check_step(k, state, batch, loss)
            self.check_s += time.time() - t
        elif self.t_open is not None:
            self.step_s.append(now - t0)
            self.window_losses.append(loss)
            self.tracer.poll()
            if now - self.t_open >= self.run.seconds:
                self.t_close = now
                self.tracer.poll(force=True)
                self.executor.max_steps = int(state.step)  # ends the job
        if self.t_open is None and k + 1 >= 3 + self.mix["warmup_steps"]:
            self.compiles_at_open = self.compiles.count
            self.tracer.start()
            self.t_open = time.perf_counter()
            self.run.mark_window_open(self.check_s)
        return state, loss

    def _read_check_step(self, k, state, batch, loss):
        features, labels = batch
        self.check["loss"].append(loss)
        self.check["batches"].append(
            (np.asarray(features["tokens"]), np.asarray(labels)))
        if k == 0:
            mu = _flatten(_adam_mu(state.opt_state))
            self.check["grad"] = {
                p: (n * 10.0, v * 10.0)  # g = mu / (1 - b1)
                for p, (n, v) in correct.probe(mu, self.run.seed).items()}
        if k == 2:
            self.check["p3"] = correct.probe(
                _flatten(state.params), self.run.seed)


class _TimedIter(object):
    def __init__(self, it, sink):
        self.it, self.sink = iter(it), sink

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        item = next(self.it)
        now = time.perf_counter()
        self.sink.append((now, now - t0))
        return item


def _fill_params(run, ref, cfg, state):
    """Replace the program's own initial weights by the benchmark's,
    leaf by leaf, each in the place and sharding of the leaf it
    replaces. Returns the new state and the sampled initial values."""
    import jax

    flat = _flatten(state.params)
    spec = ref.all_leaves(cfg)
    if set(flat) != set(spec):
        raise RuntimeError("parameter paths differ: %s"
                           % sorted(set(flat) ^ set(spec)))
    p0 = {}

    def fill(path, old):
        if tuple(old.shape) != tuple(spec[path][0]):
            raise RuntimeError("%s is %s, the reference has %s"
                               % (path, old.shape, spec[path][0]))
        sharding = old.sharding
        old.delete()
        new = ref.make_leaves(cfg, run.seed, {path: spec[path]})[path]
        p0[path] = np.asarray(
            new.reshape(-1)[correct.sample_index(run.seed, path, new.size)])
        return jax.device_put(new, sharding)

    params = jax.tree_util.tree_map_with_path(
        lambda kp, x: fill("/".join(k.key for k in kp), x), state.params)
    return state.replace(params=params), p0


def _reference(run, ref, cfg, opt, batches, mm):
    """The plain reference over the same three batches: losses, the
    first gradient and the weights after three steps."""
    import jax
    import jax.numpy as jnp

    w = ref.make_leaves(cfg, run.seed, ref.all_leaves(cfg))
    p0 = {p: np.asarray(x.reshape(-1)[
        correct.sample_index(run.seed, p, x.size)]) for p, x in w.items()}
    mu = {p: jnp.zeros_like(x) for p, x in w.items()}
    nu = {p: jnp.zeros_like(x) for p, x in w.items()}
    grad = jax.jit(jax.value_and_grad(
        lambda ww, t, y: ref.loss(cfg, ww, t, y, mm)))
    update = jax.jit(
        lambda ww, g, m, n, step: ref.adamw(
            ww, g, m, n, step, opt["lr"], opt["weight_decay"]),
        donate_argnums=(0, 2, 3))
    out = {"loss": [], "p0": p0}
    for i, (tokens, labels) in enumerate(batches):
        loss, g = grad(w, jnp.asarray(tokens), jnp.asarray(labels))
        out["loss"].append(float(loss))
        if i == 0:
            out["grad"] = correct.probe(g, run.seed)
        w, mu, nu = update(w, g, mu, nu, jnp.float32(i + 1))
    out["p3"] = correct.probe(w, run.seed)
    return out


def run_cell(run):
    import jax

    from elasticdl_tpu.api.local_executor import LocalExecutor
    from elasticdl_tpu.common.model_utils import get_model_spec
    from elasticdl_tpu.parallel import mesh as mesh_lib

    mix, model = run.mix, run.cfg["model"]
    ref = run.reference
    params = dict(model["params"])
    rcfg = dict(params, **run.cfg.get("weights", {}))
    chips = len(run.devices)
    batch = mix["per_chip_batch"] * chips
    data_dir, rows = _write_data(run, mix, params["vocab_size"])
    mesh = None
    if mix.get("mesh"):
        mesh = mesh_lib.build_mesh(dict(mix["mesh"]), devices=run.devices)
    executor = LocalExecutor(
        get_model_spec(os.path.join(run.root, model["model_zoo"]),
                       model["model_def"]),
        training_data=data_dir, minibatch_size=batch,
        num_epochs=10 ** 6, records_per_task=mix["records_per_task"],
        mesh=mesh, seed=0,
        model_params="; ".join("%s=%r" % kv for kv in sorted(params.items())),
    )
    compiles = probes.CompileCounter()
    tracer = probes.WindowTrace(run.trace, mix.get("trace_seconds", 5),
                                run.workdir)
    spans = {"state_init_s": [], "input_wait_s": []}

    init_state = executor.trainer.init_state
    p0 = {}

    def timed_init(example_batch):
        t0 = time.perf_counter()
        state = init_state(example_batch)
        jax.block_until_ready(state.params)
        spans["state_init_s"].append(time.perf_counter() - t0)
        state, sampled = _fill_params(run, ref, rcfg, state)
        p0.update(sampled)
        return state

    executor.trainer.init_state = timed_init
    probe = _Probe(run, mix, executor, tracer, compiles)
    executor.trainer.train_step = probe
    task_dataset = executor._task_dataset
    executor._task_dataset = lambda *a: _TimedIter(
        task_dataset(*a), spans["input_wait_s"])

    executor.train()
    if probe.t_close is None:
        raise RuntimeError("the data ran out before the window closed")
    window_s = probe.t_close - probe.t_open
    steps = len(probe.step_s)
    window_compiles = compiles.count - probe.compiles_at_open
    device = run.describe_devices()
    trace = tracer.reduce()

    # everything of the program is freed; the reference takes its place
    check = probe.check
    check["p0"] = p0
    executor.state = None
    del executor, probe.inner, init_state, task_dataset
    probes.free_device_memory()
    known = {bytes(r[:16]) for r in rows}
    for tokens, _ in check["batches"]:
        if not all(bytes(t[:16]) in known for t in tokens):
            raise RuntimeError("a fed row is not one of the seed's rows")
    t0 = time.time()
    want = _reference(run, ref, rcfg, run.cfg["optimizer"],
                      check["batches"], ref.matmul)
    run.say("reference: three float32 steps in %.1f s" % (time.time() - t0))
    numbers = correct.train_numbers(check, want, run.say)
    if run.control:
        low = _reference(run, ref, rcfg, run.cfg["optimizer"],
                         check["batches"], ref.matmul_fp8)
        for name, value in sorted(correct.train_numbers(low, want).items()):
            run.say("control(fp8): %-22s %.6g" % (name, value))
    nonfinite = sum(1 for x in probe.window_losses if not np.isfinite(x))
    numbers["window_nonfinite_losses"] = float(nonfinite)
    ok = correct.judge(numbers, run.cell["limits"], run.say)

    tokens = steps * batch * mix["seq_len"]
    waits = [dt for (t, dt) in spans["input_wait_s"]
             if probe.t_open <= t <= probe.t_close]
    return {
        "correct": ok, "attempted": steps, "failed": nonfinite,
        "device": device, "trace": trace, "window_s": window_s,
        "check_s": probe.check_s,
        "samples": {
            "train_step_s": probe.step_s,
            "state_init_s": spans["state_init_s"],
            "input_wait_s": waits,  # the iterator's, inside the window
        },
        "counters": {
            "window_s": window_s, "tokens": tokens, "steps": steps,
            "tokens_per_chip": tokens / chips,
            "memory_peak_bytes": device["memory_peak_bytes"],
            "chips": chips, "seq_len": mix["seq_len"],
            "batch": batch, "window_compiles": window_compiles,
        },
    }
