"""How `correct` is decided: numbers of the timed path against the
plain reference, each with a limit of its own from the cell's file
(`cells/<workload>.json`). Every number compared is printed beside its
limit, so a `correct: false` can be read from the log."""

import statistics

import jax
import jax.numpy as jnp
import numpy as np

SAMPLE = 16384  # elements of each leaf compared one by one


def sample_index(seed, path, size):
    """Fixed positions of a flattened leaf, from the seed and the
    leaf's path (the same on both sides)."""
    rng = np.random.default_rng([int(seed), sum(path.encode())])
    return jnp.asarray(rng.integers(0, size, SAMPLE), jnp.int32)


@jax.jit
def _probe(x, idx):
    flat = x.reshape(-1).astype(jnp.float32)
    return jnp.sqrt(jnp.sum(flat * flat)), flat[idx]


def probe(leaves, seed):
    """{path: (norm, sample values)} of a {path: array} tree, as numpy."""
    out = {}
    for path, x in leaves.items():
        norm, vals = _probe(x, sample_index(seed, path, x.size))
        out[path] = (float(norm), np.asarray(vals))
    return out


def _worst(per_leaf, say, what):
    if say:
        top = sorted(per_leaf.items(), key=lambda kv: -kv[1])[:3]
        say("detail: %s, worst leaves: %s" % (
            what, ", ".join("%s %.4g" % kv for kv in top)))
    return max(per_leaf.values())


def _worst_norm_gap(got, want, say=None, what="norm gap"):
    """Largest |norm_got - norm_want| over leaves, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    median = statistics.median(want[p] for p in want)
    return _worst({p: abs(got[p] - want[p]) / max(want[p], median)
                   for p in want}, say, what)


def _mean_rel_rms(got, want, say=None, what="rel rms"):
    """Mean over leaves of rms(got - want) / rms(want) on the sampled
    positions; leaves whose reference sample is all zero are skipped."""
    rel = {}
    for p in want:
        denom = float(np.sqrt(np.mean(np.square(want[p]))))
        if denom > 0:
            diff = float(np.sqrt(np.mean(np.square(got[p] - want[p]))))
            rel[p] = diff / denom
    _worst(rel, say, what)
    return float(np.mean(list(rel.values())))


def train_numbers(prog, ref, say=None):
    """`prog` and `ref`: {"loss": [3], "grad": probe of the first
    gradient, "p0": sample of the initial weights, "p3": probe of the
    weights after three steps}."""
    def delta(side):
        return {p: side["p3"][p][1] - side["p0"][p] for p in side["p0"]}

    def norms(d):
        return {p: float(np.linalg.norm(v)) for p, v in d.items()}

    out = {}
    for i in range(3):
        out["loss_gap.step%d" % (i + 1)] = abs(
            prog["loss"][i] - ref["loss"][i])
    out["grad_norm_gap"] = _worst_norm_gap(
        {p: v[0] for p, v in prog["grad"].items()},
        {p: v[0] for p, v in ref["grad"].items()}, say, "grad_norm_gap")
    out["grad_rel_rms"] = _mean_rel_rms(
        {p: v[1] for p, v in prog["grad"].items()},
        {p: v[1] for p, v in ref["grad"].items()}, say, "grad_rel_rms")
    out["update_norm_gap"] = _worst_norm_gap(
        norms(delta(prog)), norms(delta(ref)), say, "update_norm_gap")
    return out


def serve_numbers(ref_logits, served):
    """`ref_logits`: list of [n_i, vocab] float32 reference logits at
    the positions that produced each served token; `served`: list of
    [n_i] served token ids. The deficit of a served token is how far
    its reference logit lies below the reference's best."""
    gaps, rel, agree = [], [], []
    for logits, toks in zip(ref_logits, served):
        logits = np.asarray(logits, np.float32)
        toks = np.asarray(toks)
        best = logits.max(-1)
        gap = best - logits[np.arange(len(toks)), toks]
        gaps.append(gap)
        rel.append(gap / logits.std(-1))
        agree.append(logits.argmax(-1) == toks)
    gaps, rel = np.concatenate(gaps), np.concatenate(rel)
    return {
        "deficit_max": float(gaps.max()),
        "deficit_mean_sigma": float(rel.mean()),
    }, {"tokens": int(gaps.size),
        "agreement": float(np.concatenate(agree).mean())}


def judge(numbers, limits, say):
    """True iff every number that has a limit is within it (an exact
    comparison has the limit 0). A number without a limit, or a limit
    without a number, is an error in the cell's file."""
    if set(numbers) != set(limits):
        raise KeyError("numbers %s but limits %s"
                       % (sorted(numbers), sorted(limits)))
    ok = True
    for name in sorted(numbers):
        good = bool(numbers[name] <= limits[name])  # nan fails
        ok = ok and good
        say("correct: %-22s %.6g  limit %.6g  %s"
            % (name, numbers[name], limits[name],
               "ok" if good else "OVER"))
    return ok
