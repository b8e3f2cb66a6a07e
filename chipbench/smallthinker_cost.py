"""Cost functions and readers of the `smallthinker` family's layers
(`"reader": "chipbench.smallthinker_cost:<function>"`): what the expert
products and the paged decode attention of a stack with layers of two
kinds NEED, from shapes and counts, against the time a trace shows.
Kept with the benchmark so that no PR that claims a gain can change the
count. Matmul FLOPs are 2 per multiply-add.

The family's parameters are read from the configuration as it is run
(`m["cfg"]` = its `model.params`): `embed_dim`, `moe_hidden`,
`num_heads`, `num_kv_heads`, `head_dim`, `num_layers`, `attn_window`,
`window_layout`, `dtype`."""

from chipbench import flops, span_readers, trace_reduce

_BYTES = {"bf16": 2, "bfloat16": 2, "fp16": 2, "float16": 2}


def dtype_bytes(cfg):
    return _BYTES.get(str(cfg.get("dtype", "fp32")).lower(), 4)


def expert_bytes(cfg):
    """Bytes of ONE expert's three matrices in the compute dtype."""
    return 3 * cfg["embed_dim"] * cfg["moe_hidden"] * dtype_bytes(cfg)


def expert_flops_per_pair(cfg):
    """FLOPs of one (row, held expert) pair: three products of
    embed_dim x moe_hidden."""
    return 2 * 3 * cfg["embed_dim"] * cfg["moe_hidden"]


def moe_decode_cost(cfg, experts_hit, pairs_held):
    """(flops, bytes) the decode ticks' expert layers need: each expert
    some lane chose is read once a tick a layer (`experts_hit`, summed
    over ticks and layers), and each held (row, choice) pair is three
    products (`pairs_held`). The rows and the results are a few KB a
    tick and are left out."""
    return (pairs_held * expert_flops_per_pair(cfg),
            experts_hit * expert_bytes(cfg))


def layer_windows(cfg):
    """Each layer's window, 0 where it sees every earlier key."""
    layout = cfg.get("window_layout") or [1] * cfg["num_layers"]
    return [cfg.get("attn_window", 0) if on else 0 for on in layout]


def keys_in_reach_by_kind(cfg, reaches):
    """Cached keys ALL layers' decode attention has to read for tokens
    generated with `reaches` tokens behind them: a window layer reads
    at most its window, a global layer all of them."""
    total = 0
    for window in layer_windows(cfg):
        total += sum(min(r, window) if window else r for r in reaches)
    return total


def paged_decode_cost_by_kind(cfg, reaches):
    """(flops, bytes) of paged decode attention over all layers for
    tokens generated at `reaches`: K and V streamed once, 4 * head_dim
    FLOPs per (query head, key)."""
    h = cfg["num_heads"]
    hkv = cfg.get("num_kv_heads") or h
    hd = cfg.get("head_dim") or cfg["embed_dim"] // h
    keys = keys_in_reach_by_kind(cfg, reaches)
    return 4 * h * hd * keys, 2 * keys * hkv * hd * dtype_bytes(cfg)


def traced_tick_counts(m, names, program):
    """{name: sum of the counter over the traced decode ticks}, or None.
    The trace starts while the server idles, a moment before the window
    opens, and covers its first seconds: the ticks it holds are the
    window's first N, N the launches of `program` in the trace. Each
    tick counts each name once, in order."""
    t, phases = m["trace"], span_readers._in_window(m)
    if not t or phases is None:
        return None
    _, launches = trace_reduce.seconds_matching(t, program, "programs")
    out = {}
    for name in names:
        mine = sorted((p for p in phases if p.name == name),
                      key=lambda p: p.start_ns)[:int(round(launches))]
        if not mine:
            return None
        out[name] = sum(p.attrs.get("n", 0) for p in mine)
    return out


def moe_decode_roofline(m, match, program):
    """The expert products' share of their roofline over the traced
    decode ticks: the weights of the experts the ticks hit and the
    products of the pairs held (the program's `moe.experts_hit` and
    `moe.pairs_held`, of the ticks in the trace) against the kernel's
    time inside the step."""
    t = m["trace"]
    if not t or not m["peaks"]:
        return None
    secs, count = trace_reduce.seconds_matching(t, match)
    counts = traced_tick_counts(
        m, ("moe.experts_hit", "moe.pairs_held"), program)
    if not count or not counts:
        return None
    need_flops, need_bytes = moe_decode_cost(
        m["cfg"], counts["moe.experts_hit"], counts["moe.pairs_held"])
    share, _ = flops.roofline_share(
        need_flops, need_bytes, secs, m["peaks"]["bf16_flops_per_s"],
        m["peaks"]["hbm_bytes_per_s"])
    return share


def paged_roofline_by_kind(m, match):
    """`readers.paged_decode_roofline` for a stack whose layers differ:
    the raw reaches the serve driver hands over, folded a layer by its
    own window, and head_dim as the configuration gives it."""
    t = m["trace"]
    reaches = m["samples"].get("traced_token_reach", [])
    if not t or not m["peaks"] or not reaches or "head_dim" not in m["cfg"]:
        return None
    secs, count = trace_reduce.seconds_matching(t, match)
    if not count:
        return None
    need_flops, need_bytes = paged_decode_cost_by_kind(m["cfg"], reaches)
    share, _ = flops.roofline_share(
        need_flops, need_bytes, secs, m["peaks"]["bf16_flops_per_s"],
        m["peaks"]["hbm_bytes_per_s"])
    return share
