"""The general readers that metric files name (`"reader": "<function>"`,
or `"<module>:<function>"` for one kept elsewhere under `paths`). Each
takes what a driver measured —

    samples   {name: [numbers]}      raw values from the harness's spans
    counters  {name: number}         counts, window_s, setup_s
    trace     trace_reduce.summarize(...) of a --trace 1 run, or None
    cfg       the model's parameters; peaks: the chip's (None off-chip)

— and returns a number, or None when it finds nothing to read."""

from chipbench import flops, stats, trace_reduce


def counter(m, name, scale=1.0):
    value = m["counters"].get(name)
    return None if value is None else value * scale


def ratio(m, num, den, scale=1.0):
    n, d = m["counters"].get(num), m["counters"].get(den)
    return None if n is None or not d else scale * n / d


def percentile(m, samples, q, scale=1.0):
    value = stats.percentile(m["samples"].get(samples, []), q)
    return None if value is None else value * scale


def mean(m, samples, scale=1.0):
    xs = m["samples"].get(samples)
    return scale * sum(xs) / len(xs) if xs else None


def sample_share(m, samples, of):
    """sum(samples) / counters[of], in %."""
    xs, total = m["samples"].get(samples), m["counters"].get(of)
    return 100.0 * sum(xs) / total if xs is not None and total else None


def train_mfu(m):
    """Model FLOP/s utilization in %: tokens/s/chip times the FLOPs a
    token needs forward and backward (causal, windowed, GQA-aware;
    recomputation not counted) over the chip's bf16 peak."""
    if not m["peaks"]:
        return None
    c = m["counters"]
    per_token = flops.train_flops_per_token(m["cfg"], c["seq_len"])
    rate = c["tokens"] / c["window_s"] / c["chips"]
    return 100.0 * rate * per_token / m["peaks"]["bf16_flops_per_s"]


def trace_idle_share(m):
    t = m["trace"]
    return None if not t else 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def trace_share(m, match, of_programs=None):
    """Time of the operations matching `match` as a share (%) of device
    busy time, or of the time of the programs matching `of_programs`."""
    t = m["trace"]
    if not t:
        return None
    secs, count = trace_reduce.seconds_matching(t, match)
    base = t["busy_s"]
    if of_programs:
        base, _ = trace_reduce.seconds_matching(t, of_programs, "programs")
    return 100.0 * secs / base if count and base else None


def trace_ms_per_execution(m, programs):
    t = m["trace"]
    if not t:
        return None
    secs, count = trace_reduce.seconds_matching(t, programs, "programs")
    return 1e3 * secs / count if count else None


def flash_train_roofline(m, match, program):
    """The three flash kernels' share of their roofline over the traced
    train steps: the FLOPs and bytes `flops.flash_train_cost` says one
    step needs, times the steps traced, against the kernels' time."""
    t = m["trace"]
    if not t or not m["peaks"]:
        return None
    secs, count = trace_reduce.seconds_matching(t, match)
    _, steps = trace_reduce.seconds_matching(t, program, "programs")
    if not count or not steps:
        return None
    c = m["counters"]
    need_flops, need_bytes = flops.flash_train_cost(
        m["cfg"], c["seq_len"], c["batch"] // c["chips"])
    share, _ = flops.roofline_share(
        need_flops * steps, need_bytes * steps, secs,
        m["peaks"]["bf16_flops_per_s"], m["peaks"]["hbm_bytes_per_s"])
    return share


def paged_decode_roofline(m, match):
    """The paged decode kernel's share of its (bytes-bound) roofline:
    the bytes the ticks inside the traced part of the window had to
    stream from the pool — `decode_context_tokens`, the cached tokens
    in reach of each slot summed over those ticks — against the
    kernel's time."""
    t = m["trace"]
    ctx = m["counters"].get("traced_decode_context_tokens")
    if not t or not m["peaks"] or not ctx:
        return None
    secs, count = trace_reduce.seconds_matching(t, match)
    if not count:
        return None
    need_flops, need_bytes = flops.paged_decode_cost(m["cfg"], 1)
    share, _ = flops.roofline_share(
        need_flops * ctx, need_bytes * ctx, secs,
        m["peaks"]["bf16_flops_per_s"], m["peaks"]["hbm_bytes_per_s"])
    return share


def collective_exposed_share(m):
    """Collective time during which nothing else ran on the device, as
    a share (%) of device busy time."""
    t = m["trace"]
    if not t or not t["collective_s"]:
        return None
    return 100.0 * t["collective_exposed_s"] / t["busy_s"]


def ratio_to_constant(m, num, den, constant, scale=1.0):
    """(counters[num] / counters[den]) / constant: a rate against one
    recorded elsewhere, such as the one-chip cell's."""
    value = ratio(m, num, den)
    return None if value is None else scale * value / constant
