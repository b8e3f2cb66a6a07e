"""Driver of the traffic kind `open_loop_blocks`: `open_loop`'s served
stack, schedule, warm-up, window and clocks, for a model that generates
by DIFFUSION OVER BLOCKS (a streamed chunk is a committed block of the
model's block length, and carries the denoising pass that revealed
each of its tokens, `TokenChunk.reveal_steps`). Two things are this
driver's own:

* the client also keeps each chunk's `reveal_steps`;
* `check`. `open_loop`'s reads the reference's NEXT-token logits at
  the position before each served token, over the finished sequence:
  nothing this model computed (a position's token comes from the
  logits AT that position, under a block-causal mask, of a block whose
  other positions were masked or revealed as the pass found them). So
  the sampled finished requests (the longest and `sample` from the
  seed) are run through the family's `denoise_plan`: one forward over
  the clean sequence followed by S noisy copies of every generated
  block, layer by layer, each layer's weights made from the seed and
  dropped after use. Compared, AT THE PASS THAT REVEALED each served
  token: `deficit_max` and `deficit_mean_sigma` as
  `correct.serve_numbers` defines them; and `reveal_deficit_max`, over
  every pass: the reference's highest probability (of its greedy
  token) among the positions the pass left masked, less its lowest
  among those it revealed, not below 0: 0 where the server revealed
  what the reference holds most certain. And `failed_requests`.

Beyond `open_loop`'s NEEDS the family's reference exports
`denoise_plan(cfg, prompt, tokens, reveal_steps, S, rows)` and its
`layer` takes the plan as `plan=`; the configuration states
`server.denoise_steps` and `model.params.block_causal` (checked when
the cell starts)."""

import time

import numpy as np

from chipbench import correct
from chipbench.drivers import open_loop as base

NEEDS = base.NEEDS
#: ... and of the reference, for `check`
ALSO_NEEDS = ["denoise_plan"]


class _Client(base._Client):
    """One streaming request: arrival time of every chunk, and the
    reveal step of every token."""

    def __init__(self, stub, pb, spec, t_due):
        # the stream passes through `generate_stream` below on its way
        # to `open_loop`'s client
        super().__init__(self, pb, spec, t_due)
        self._server, self.reveal_steps = stub, []

    def generate_stream(self, request, timeout):
        for chunk in self._server.generate_stream(request, timeout=timeout):
            self.reveal_steps.extend(chunk.reveal_steps)
            yield chunk

    def __call__(self):
        super().__call__()
        if not self.error and len(self.reveal_steps) != len(self.tokens):
            self.error = "got %d reveal steps for %d tokens" % (
                len(self.reveal_steps), len(self.tokens))
        return self


def reference_passes(run, ref, rcfg, sample, mm, steps, rows=512):
    """[(logits [blocks, S, B, vocab] float32, reveal [blocks, B])] of
    the reference for each sampled request: every denoising pass of
    every generated block (`denoise_plan`), layer by layer."""
    import jax
    import jax.numpy as jnp

    outer = ref.make_leaves(rcfg, run.seed, ref.outer_leaves(rcfg))
    # every layer is of the one kind: one compile a length
    layer = jax.jit(
        lambda w, x, plan: ref.layer(rcfg, w, x, mm, rows, 0, plan))
    plans = [ref.denoise_plan(rcfg, c.spec["prompt"], c.tokens,
                              c.reveal_steps, steps, rows) for c in sample]
    xs = [ref.embed(outer, jnp.asarray(dp["ids"])[None]) for dp in plans]
    for i in range(rcfg["num_layers"]):
        w = ref.block_weights(
            ref.make_leaves(rcfg, run.seed, ref.layer_leaves(rcfg, i)), i)
        xs = [layer(w, x, tuple(jnp.asarray(a) for a in dp["plan"]))
              for x, dp in zip(xs, plans)]
        del w
    head = jax.jit(lambda w, x: ref.head_logits(w, x, mm))
    out = []
    for x, dp in zip(xs, plans):
        at = dp["at"]
        logits = np.asarray(head(outer, x[0, at.reshape(-1)]))
        out.append((logits.reshape(at.shape + (-1,)), dp["reveal"]))
    return out


def _confidence(logits):
    """The softmax probability of the greedy token, float64."""
    z = logits.astype(np.float64)
    z = z - z.max(-1, keepdims=True)
    return 1.0 / np.exp(z).sum(-1)


def block_numbers(passes, revealed_by=None, least_certain=False):
    """What `check` compares, from `reference_passes`. `revealed_by`
    (the control): [blocks, S, B, vocab] logits whose greedy tokens and
    reveal order stand in for the server's; `least_certain`: a server
    that reveals what they hold LEAST certain first, the fault that
    `reveal_deficit_max` is there to catch."""
    at_reveal, served, worst = [], [], 0.0
    for k, (logits, reveal) in enumerate(passes):
        blocks, steps, width = logits.shape[:3]
        prob = _confidence(logits)
        theirs = None if revealed_by is None else revealed_by[k]
        for b in range(blocks):
            for s in range(steps):
                masked = reveal[b] >= s  # not given, not revealed before
                now = reveal[b] == s
                if theirs is not None:
                    # as many as the server revealed, by THEIR order
                    sure = _confidence(theirs[b, s])
                    order = np.argsort(-np.where(
                        masked, 1.0 - sure if least_certain else sure,
                        -1.0), kind="stable")
                    count, now = int(now.sum()), np.zeros(width, bool)
                    now[order[:count]] = True
                    now &= masked
                left = masked & ~now
                if now.any() and left.any():
                    worst = max(worst, float(prob[b, s][left].max()
                                             - prob[b, s][now].min()))
            for j in np.flatnonzero(reveal[b] >= 0):
                at_reveal.append(logits[b, reveal[b, j], j])
                served.append(None if theirs is None else int(
                    theirs[b, reveal[b, j], j].argmax()))
    return np.stack(at_reveal), served, worst


def check(run, ref, rcfg, sample):
    t0 = time.time()
    steps = int(run.cfg["server"]["denoise_steps"])
    want = reference_passes(run, ref, rcfg, sample, ref.matmul, steps)
    at_reveal, _, worst = block_numbers(want)
    served = np.concatenate([np.asarray(c.tokens) for c in sample])
    numbers, info = correct.serve_numbers([at_reveal], [served])
    numbers["reveal_deficit_max"] = worst
    run.say("reference: %d requests, %d served tokens, longest %d, %d "
            "passes a block, in %.1f s; agreement with reference-greedy "
            "%.4f" % (
                len(sample), info["tokens"],
                max(len(c.spec["prompt"]) + len(c.tokens) for c in sample),
                steps, time.time() - t0, info["agreement"]))
    if run.control:
        low = [x for x, _ in reference_passes(
            run, ref, rcfg, sample, ref.matmul_fp8, steps)]
        _, theirs, ctl_worst = block_numbers(want, revealed_by=low)
        ctl, _ = correct.serve_numbers([at_reveal], [np.asarray(theirs)])
        ctl["reveal_deficit_max"] = ctl_worst
        # the control goes through the cell's comparison too: it has to
        # come out as not correct, by one limit at least
        limits = run.cell["limits"]
        over = [name for name in sorted(ctl) if not ctl[name] <= limits[name]]
        for name, value in sorted(ctl.items()):
            run.say("control(fp8): %-22s %.6g  limit %.6g  %s" % (
                name, value, limits[name],
                "OVER" if name in over else "ok"))
        run.say("control(fp8): %s" % (
            "not correct, by %s" % ", ".join(over) if over
            else "WITHIN every limit: the limits tell nothing"))
        # the precision hardly moves the order of the reveal; the fault
        # that number is there for is an order that is wrong
        _, _, wrong = block_numbers(want, revealed_by=[x for x, _ in want],
                                    least_certain=True)
        run.say("control(least certain first): reveal_deficit_max %.6g  "
                "limit %.6g  %s" % (
                    wrong, limits["reveal_deficit_max"],
                    "OVER" if wrong > limits["reveal_deficit_max"]
                    else "ok"))
    return numbers


def run_cell(run):
    """`open_loop.run_cell` with this module's client and check in the
    places of its own."""
    lacking = [name for name in ALSO_NEEDS
               if not callable(getattr(run.reference, name, None))]
    if lacking or "denoise_steps" not in run.cfg["server"]:
        raise RuntimeError(
            "open_loop_blocks: the reference exports no %s, or the "
            "configuration has no server.denoise_steps" % lacking)
    theirs = {name: getattr(base, name) for name in REPLACED}
    for name, value in REPLACED.items():
        setattr(base, name, value)
    try:
        return base.run_cell(run)
    finally:
        for name, value in theirs.items():
            setattr(base, name, value)


#: the names of `open_loop`'s module that `run_cell` looks up as globals
#: while it runs, and what stands in their places here. `open_loop.py`
#: is the accepted benchmark's and may not be edited by the PR that
#: brings this file; a `benchmark` issue that gives its `run_cell` a
#: `client=` and a `check=` makes this table two arguments
REPLACED = {"_Client": _Client, "check": check}
assert all(callable(getattr(base, name, None)) for name in REPLACED), (
    "open_loop no longer has %s: open_loop_blocks replaces them by name"
    % sorted(REPLACED))
