"""The one general traffic generator. A traffic mix is a data file
(`traffic/<name>.json`); this module turns `open_loop` parameters and a
seed into a request schedule. Every seed gets the same multiset of
prompt lengths, answer lengths and inter-arrival gaps, in another
order, so that seeds change the order of the work and not its amount.
Where the order IS the amount (a server that batches what is resident
together: which requests meet in the lanes sets every tick's cost), a
mix states `deal_seed`: the lengths and gaps are then dealt in the one
order that seed gives, for every `--seed`, and `--seed` draws only the
tokens of each prompt (and, in the driver, the weights)."""

import math

from chipbench import stats


def arrival_gaps(n, rate_per_s, kind):
    """n inter-arrival gaps with mean 1/rate. `poisson`: the n
    mid-quantiles of the exponential distribution (a stratified sample
    of a Poisson process, rescaled to the exact mean); `uniform`: even
    spacing."""
    if kind == "uniform":
        return [1.0 / rate_per_s] * n
    if kind != "poisson":
        raise ValueError("unknown arrivals %r" % (kind,))
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n / (rate_per_s * sum(gaps))
    return [g * scale for g in gaps]


def open_loop_schedule(mix, seed, seconds, vocab_size):
    """[{due_s, prompt, max_new_tokens}] for one window. The first
    request is due at 0; all are due before `seconds`."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    rng = stats.rng_for(seed, "open_loop")
    deal = rng  # without `deal_seed`: one stream, the order then the tokens
    if mix.get("deal_seed") is not None:
        deal = stats.rng_for(mix["deal_seed"], "open_loop")
    prompts = stats.shuffled_multiset(mix["prompt_lens"], n, deal)
    news = stats.shuffled_multiset(mix["max_new_tokens"], n, deal)
    gaps = arrival_gaps(n, mix["rate_per_s"], mix.get("arrivals", "poisson"))
    deal.shuffle(gaps)
    out, due = [], 0.0
    for p, m, g in zip(prompts, news, gaps):
        out.append({
            "due_s": due, "max_new_tokens": m,
            "prompt": [rng.randrange(vocab_size) for _ in range(p)],
        })
        due += g
    return out
