"""Cost functions and readers of the `nemotron_h` family's layers
(`"reader": "chipbench.nemotron_h_cost:<function>"`): what the Mamba-2
decode update and the two-matrix (relu^2) expert products NEED, from
shapes and counts, against the time a trace shows. Kept with the
benchmark so that no PR that claims a gain can change the count. Matmul
FLOPs are 2 per multiply-add.

The family's parameters are read from the configuration as it is run
(`m["cfg"]` = its `model.params`): `embed_dim`, `moe_hidden`,
`ssm_heads`, `ssm_head_dim`, `ssm_state`, `dtype`; the lanes a decode
tick updates are `m["config"]["server"]["num_slots"]` (a free lane is
updated like a seated one)."""

from chipbench import flops, trace_reduce
from chipbench.smallthinker_cost import dtype_bytes, traced_tick_counts


def ssm_update_cost(cfg, lane_layers):
    """(flops, bytes) of `lane_layers` one-token state updates (a lane
    of one layer each): the float32 state [H, P, N] read once and
    written once, and a decay, an outer product and a readout of it,
    6 H P N operations. The token's x, B, C, Δ and y are a few KB a
    lane and are left out."""
    state = cfg["ssm_heads"] * cfg["ssm_head_dim"] * cfg["ssm_state"]
    return lane_layers * 6 * state, lane_layers * 2 * 4 * state


def ssm_roofline(m, match):
    """The state update kernel's share of its (bytes-bound) roofline:
    each of its events in the trace is one layer's update of all
    `num_slots` lanes, against the kernel's time."""
    t = m["trace"]
    if not t or not m["peaks"] or "ssm_state" not in m["cfg"]:
        return None
    secs, count = trace_reduce.seconds_matching(t, match)
    if not count:
        return None
    need_flops, need_bytes = ssm_update_cost(
        m["cfg"], count * m["config"]["server"]["num_slots"])
    share, _ = flops.roofline_share(
        need_flops, need_bytes, secs, m["peaks"]["bf16_flops_per_s"],
        m["peaks"]["hbm_bytes_per_s"])
    return share


def expert_bytes(cfg):
    """Bytes of ONE routed expert's two matrices in the compute dtype."""
    return 2 * cfg["embed_dim"] * cfg["moe_hidden"] * dtype_bytes(cfg)


def expert_flops_per_pair(cfg):
    """FLOPs of one (row, held expert) pair: two products of
    embed_dim x moe_hidden."""
    return 2 * 2 * cfg["embed_dim"] * cfg["moe_hidden"]


def moe_relu2_cost(cfg, experts_hit, pairs_held):
    """(flops, bytes) the decode ticks' routed experts need: each
    expert some lane chose is read once a tick a layer, each held
    (row, choice) pair is two products. The shared expert is a dense
    product outside the kernel and is not in it."""
    return (pairs_held * expert_flops_per_pair(cfg),
            experts_hit * expert_bytes(cfg))


def moe_relu2_roofline(m, match, program):
    """The relu^2 expert products' share of their roofline over the
    traced decode ticks: the program's `moe.experts_hit` and
    `moe.pairs_held` of the ticks in the trace against the kernel's
    time inside the step."""
    t = m["trace"]
    if not t or not m["peaks"] or "moe_hidden" not in m["cfg"]:
        return None
    secs, count = trace_reduce.seconds_matching(t, match)
    counts = traced_tick_counts(
        m, ("moe.experts_hit", "moe.pairs_held"), program)
    if not count or not counts:
        return None
    need_flops, need_bytes = moe_relu2_cost(
        m["cfg"], counts["moe.experts_hit"], counts["moe.pairs_held"])
    share, _ = flops.roofline_share(
        need_flops, need_bytes, secs, m["peaks"]["bf16_flops_per_s"],
        m["peaks"]["hbm_bytes_per_s"])
    return share
