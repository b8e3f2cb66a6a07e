"""Cost functions and readers of the `sdar_moe` family's layers
(`"reader": "chipbench.sdar_cost:<function>"`): what the SwiGLU expert
products and the paged attention of a BLOCK tick (a decode step that
runs a tile of `block_causal` positions a lane, `denoise_steps` + 1
passes a block) NEED, from shapes and counts, against the time a trace
shows; and the block tick's own pace. Kept with the benchmark so that
no PR that claims a gain can change the count. Matmul FLOPs are 2 per
multiply-add.

The family's parameters are read from the configuration as it is run
(`m["cfg"]` = its `model.params`: `embed_dim`, `moe_hidden`,
`num_heads`, `num_kv_heads`, `head_dim`, `num_layers`, `block_causal`,
`dtype`; `m["config"]["server"]["denoise_steps"]`)."""

from chipbench import flops, span_readers, stats, trace_reduce
from chipbench.smallthinker_cost import dtype_bytes, traced_tick_counts


def expert_bytes(cfg):
    """Bytes of ONE SwiGLU expert's three matrices in the compute
    dtype."""
    return 3 * cfg["embed_dim"] * cfg["moe_hidden"] * dtype_bytes(cfg)


def expert_flops_per_pair(cfg):
    """FLOPs of one (row, held expert) pair: three products of
    embed_dim x moe_hidden (the silu and the gate product are a few
    operations a hidden unit and are left out)."""
    return 2 * 3 * cfg["embed_dim"] * cfg["moe_hidden"]


def moe_pass_cost(cfg, experts_hit, pairs_held):
    """(flops, bytes) the passes' expert layers need: each expert some
    row chose is read once a pass a layer (`experts_hit`, summed over
    passes and layers), and each held (row, choice) pair is three
    products (`pairs_held`; a lane's `block_causal` rows are as many
    rows). The rows and the results are left out."""
    return (pairs_held * expert_flops_per_pair(cfg),
            experts_hit * expert_bytes(cfg))


def passes_a_block(config):
    """Passes that yield one block: the denoising passes and the
    commit pass."""
    return int(config["server"]["denoise_steps"]) + 1


def keys_streamed(cfg, config, reaches):
    """Cached keys ONE layer's paged attention streams for the tokens
    served with `reaches` tokens behind them: a token's block starts
    at `reach // B * B`, every pass of the block streams the keys
    before that start once for its B rows, and a token is a B-th of
    its block."""
    block = cfg["block_causal"]
    passes = passes_a_block(config)
    return sum(r // block * block for r in reaches) * passes / block


def paged_tile_cost(cfg, config, reaches):
    """(flops, bytes) of the tile's paged attention over all layers: K
    and V of the keys streamed once a pass, 4 * head_dim FLOPs per
    (query head, tile row, key), `block_causal` rows a tile; the
    tile's own B keys are in registers and left out."""
    h = cfg["num_heads"]
    hkv = cfg.get("num_kv_heads") or h
    hd = cfg.get("head_dim") or cfg["embed_dim"] // h
    keys = keys_streamed(cfg, config, reaches) * cfg["num_layers"]
    return (4 * h * hd * cfg["block_causal"] * keys,
            2 * keys * hkv * hd * dtype_bytes(cfg))


def _share(m, need, match):
    secs, count = trace_reduce.seconds_matching(m["trace"], match)
    if not count:
        return None
    share, _ = flops.roofline_share(
        need[0], need[1], secs, m["peaks"]["bf16_flops_per_s"],
        m["peaks"]["hbm_bytes_per_s"])
    return share


def moe_pass_roofline(m, match, program):
    """The SwiGLU expert products' share of their roofline over the
    traced ticks: the weights of the experts the passes hit and the
    products of the pairs held (the program's `moe.experts_hit` and
    `moe.pairs_held`, of the ticks in the trace) against the kernel's
    time inside the step."""
    if not m["trace"] or not m["peaks"]:
        return None
    counts = traced_tick_counts(
        m, ("moe.experts_hit", "moe.pairs_held"), program)
    if not counts:
        return None
    return _share(m, moe_pass_cost(
        m["cfg"], counts["moe.experts_hit"], counts["moe.pairs_held"]),
        match)


def paged_tile_roofline(m, match):
    """The paged attention's share of its roofline under the block
    tick: what the passes of the blocks served inside the traced part
    of the window had to stream (`traced_token_reach`, folded by
    `keys_streamed`) against the time of the per-slot body that holds
    the kernel."""
    reaches = m["samples"].get("traced_token_reach", [])
    if (not m["trace"] or not m["peaks"] or not reaches
            or "block_causal" not in m["cfg"]
            or "denoise_steps" not in m["config"].get("server", {})):
        return None
    return _share(m, paged_tile_cost(m["cfg"], m["config"], reaches), match)


def ms_per_token(m, q=50):
    """The q-th percentile, over the window's decode ticks, of a whole
    tick (its root phase) times the passes a block takes, over the
    block's length: what a token costs a lane, in ms."""
    phases = span_readers._in_window(m)
    cfg, config = m["cfg"], m["config"]
    if (not phases or "block_causal" not in cfg
            or "denoise_steps" not in config.get("server", {})):
        return None
    stepped = {p.seq for p in phases if p.name == "tick.dispatch"}
    ticks = [p.end_ns - p.start_ns for p in phases
             if p.name == "tick" and p.seq in stepped]
    if not ticks:
        return None
    return (1e-6 * stats.percentile(ticks, q) * passes_a_block(config)
            / cfg["block_causal"])
