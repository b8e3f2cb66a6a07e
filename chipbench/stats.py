"""Arithmetic on samples: exact percentiles (no histogram), the
quartile spread the bounds are set from, the spread of a set of runs as
the driver takes it, and weighted multisets that every seed draws in
another order."""

import math
import random
import statistics


def percentile(values, q):
    """Exact q-th percentile (0..100) of raw samples, linear between
    order statistics; None for no samples; inf if any is inf there."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if xs[hi] == math.inf:
        return math.inf if hi != lo or xs[lo] == math.inf else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def spread(values):
    """Distance between the first and third quartile as a share of the
    median (statistics.quantiles, n=4)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def run_spread(values):
    """The driver's spread of a set of runs of one cell on one tree:
    the largest less the smallest, leaving out the run farthest from
    the set's median where that narrows it (a set of three or more),
    as (absolute, share of the set's median); None for no run."""
    xs = sorted(values)
    if not xs:
        return None
    median = statistics.median(xs)
    if len(xs) >= 3:
        below, above = median - xs[0], xs[-1] - median
        if below != above:
            xs = xs[1:] if below > above else xs[:-1]
        else:  # both ends as far: the one whose going narrows it more
            xs = min(xs[1:], xs[:-1], key=lambda k: k[-1] - k[0])
    width = xs[-1] - xs[0]
    return width, width / median


def weighted_counts(pairs, n):
    """Split n into whole counts proportional to the weights of
    [(value, weight), ...] by largest remainder, so that every seed
    gets the same multiset."""
    total = float(sum(w for _, w in pairs))
    exact = [n * w / total for _, w in pairs]
    counts = [int(math.floor(e)) for e in exact]
    order = sorted(range(len(pairs)), key=lambda i: exact[i] - counts[i],
                   reverse=True)
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return [(v, c) for (v, _), c in zip(pairs, counts)]


def shuffled_multiset(pairs, n, rng):
    out = [v for v, c in weighted_counts(pairs, n) for _ in range(c)]
    rng.shuffle(out)
    return out


def rng_for(seed, salt):
    """A `random.Random` for one purpose of one run; any whole-number
    seed."""
    return random.Random("%d/%s" % (int(seed), salt))
