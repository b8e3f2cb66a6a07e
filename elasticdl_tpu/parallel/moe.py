"""Mixture-of-experts over the ``ep`` mesh axis (expert parallelism).

Net-new beyond the reference (which has no expert axis — SURVEY.md §2.5;
``ep`` existed for embedding-row sharding only). The design is the
GShard/Switch static-shape formulation, which is what XLA wants:

* top-k routing (k=1 Switch, k=2 GShard) with a CAPACITY per expert
  (round(k * tokens * capacity_factor / E), expert_capacity()): every
  tensor keeps a static shape; choices over capacity are dropped from the expert path (their
  combine weight is 0, so over-capacity tokens pass through the
  residual only);
* dispatch and combine are one-hot einsums — no gather/scatter with
  dynamic shapes;
* expert weights are stacked [E, ...] and annotated over ``ep``
  (nn.with_partitioning); GSPMD inserts the all-to-alls when the einsums
  cross the token (dp-sharded) and expert (ep-sharded) dims;
* the load-balancing auxiliary loss is the standard fraction*prob dot
  (Switch Transformer eq. 4), returned to the caller to add to the task
  loss.

Two dispatch implementations share those semantics:

* :func:`moe_mlp_apply` — sharding-annotated einsums; GSPMD infers the
  collectives (the default; single-chip and small meshes);
* :func:`moe_mlp_apply_a2a` — EXPLICIT shard_map dispatch: tokens are
  sharded into (dp, fsdp, ep) groups, each group routes locally into a
  capacity-bounded [E, C, D] send buffer, one ``all_to_all`` over
  ``ep`` delivers each expert its ep receive buffers, the expert FFNs
  run on their [E/ep, ep*C, D] batch, and a reverse ``all_to_all``
  brings outputs home for the combine. Capacity is per GROUP
  (GShard's groups: round(k * T_group * cf / E)) rather than global-T,
  so the a2a cost is bounded at 2 * E * C * D * itemsize bytes per
  group regardless of routing skew. Drop-free configurations produce
  exactly the einsum path's outputs (the aux loss is assembled from
  pmean'd fraction/prob so it matches the global formula); under
  saturation the paths differ only in WHICH over-capacity choices drop
  (global queue vs per-group queues).
"""

import functools

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common.constants import MeshAxis
from elasticdl_tpu.ops.expert_ffn import expert_tiles
from elasticdl_tpu.parallel.pipeline import shard_map


def top1_dispatch(router_logits, capacity):
    """Static-shape top-1 routing (Switch). See topk_dispatch."""
    return topk_dispatch(router_logits, capacity, k=1)


def topk_dispatch(router_logits, capacity, k=1):
    """Static-shape top-k routing (k=1 Switch, k=2 GShard).

    router_logits: [T, E]; capacity: int C per expert.
    Returns (dispatch [T, E, C] 0/1, combine [T, E, C] float, aux_loss
    scalar, stats dict). Each token routes to its k highest-probability
    experts; capacity queues fill primary choices first (all rank-0
    picks, then rank-1, ...), so under load the second choices are the
    ones dropped — GShard's policy. Combine weights follow GShard's
    g1/g2 normalization: each chosen expert's router prob is normalized
    over ALL k chosen experts BEFORE capacity drops, so a dropped choice
    contributes zero while the surviving choice keeps its pre-drop
    weight (e.g. p2/(p1+p2) — never amplified to 1.0). A token whose
    every choice was dropped has an all-zero combine row and rides the
    residual only.
    """
    t, e = router_logits.shape
    if not 1 <= k <= e:
        raise ValueError("top-k k=%d must be in [1, %d experts]" % (k, e))
    probs = jax.nn.softmax(router_logits, axis=-1)

    # lax.top_k guarantees k DISTINCT indices per token (an iterative
    # mask-and-argmax can pick an expert twice when the masked row
    # underflows to all zeros under a saturated router)
    _, topk_idx = jax.lax.top_k(probs, k)  # [T, k]
    onehots = [
        jax.nn.one_hot(topk_idx[:, r], e, dtype=probs.dtype)
        for r in range(k)
    ]

    # queue positions over (rank, arrival) order: rank-0 choices claim
    # capacity before any rank-1 choice
    flat = jnp.concatenate(onehots, axis=0)  # [k*T, E], rank-major
    position = jnp.cumsum(flat, axis=0) * flat - 1.0  # [k*T, E]
    within = (position >= 0) & (position < capacity)
    kept_flat = flat * within.astype(probs.dtype)
    pos_onehot = jax.nn.one_hot(
        jnp.clip(position, 0, capacity - 1).astype(jnp.int32),
        capacity,
        dtype=probs.dtype,
    )  # [k*T, E, C]
    dispatch_flat = kept_flat[..., None] * pos_onehot
    dispatch = dispatch_flat.reshape(k, t, e, capacity).sum(0)  # [T,E,C]

    # combine weights: k=1 keeps the raw chosen prob (Switch eq. 2 — the
    # magnitude is the router's gradient path); k>1 normalizes each
    # chosen prob over the CHOSEN set before capacity drops (GShard
    # g1/g2): a capacity-dropped primary zeroes its own weight but does
    # not inflate the secondary's.
    kept = kept_flat.reshape(k, t, e).sum(0)  # [T, E] post-drop
    chosen = flat.reshape(k, t, e).sum(0)     # [T, E] pre-drop
    if k == 1:
        combine = dispatch * (probs * kept)[..., None]
    else:
        denom = jnp.maximum(
            jnp.sum(probs * chosen, axis=-1, keepdims=True), 1e-9
        )
        combine = dispatch * (probs * kept / denom)[..., None]

    # Switch aux loss on the primary choice: E * sum_e frac_e * prob_e
    fraction = jnp.mean(onehots[0], axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux_loss = e * jnp.sum(fraction * mean_prob)
    stats = {
        "dropped_fraction": 1.0 - jnp.sum(kept) / (k * t),
        "expert_fraction": fraction,
    }
    return dispatch, combine, aux_loss, stats


def expert_capacity(num_tokens, num_experts, capacity_factor):
    return max(1, int(num_tokens * capacity_factor / num_experts + 0.5))


def moe_mlp_apply(params, x, capacity_factor=1.25, activation=jax.nn.gelu,
                  router_top_k=1):
    """Functional MoE MLP: x [T, D] through E expert FFNs.

    params: {"router": [D, E], "w_up": [E, D, H], "b_up": [E, H],
             "w_down": [E, H, D], "b_down": [E, D]} — stacked expert
    leaves sharded over ep by the caller's annotations.
    Returns (y [T, D], aux_loss, stats).
    """
    t = x.shape[0]
    e = params["router"].shape[-1]
    capacity = expert_capacity(
        t * router_top_k, e, capacity_factor
    )
    logits = x @ params["router"]
    dispatch, combine, aux_loss, stats = topk_dispatch(
        logits, capacity, k=router_top_k
    )
    # [T,E,C] x [T,D] -> [E,C,D]: the all-to-all boundary under GSPMD
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)
    h = activation(
        jnp.einsum("ecd,edh->ech", expert_in, params["w_up"])
        + params["b_up"][:, None, :]
    )
    expert_out = (
        jnp.einsum("ech,ehd->ecd", h, params["w_down"])
        + params["b_down"][:, None, :]
    )
    y = jnp.einsum("tec,ecd->td", combine, expert_out)
    return y, aux_loss, stats


def moe_mlp_apply_a2a(params, x, mesh, capacity_factor=1.25,
                      activation=jax.nn.gelu, router_top_k=1):
    """Explicit expert-parallel dispatch (module docstring): shard_map
    over (dp, fsdp, ep) token groups with capacity-bounded all_to_all
    send/recv buffers over ``ep``.

    Same signature/result contract as :func:`moe_mlp_apply` plus the
    mesh. x [T, D] may arrive with any sharding — the shard_map in_spec
    reshards rows over (dp, fsdp, ep). Requires T % (dp*fsdp*ep) == 0
    and E % ep == 0.
    """
    dp = mesh.shape[MeshAxis.DP]
    fsdp = mesh.shape[MeshAxis.FSDP]
    ep = mesh.shape[MeshAxis.EP]
    shards = dp * fsdp * ep
    t, d = x.shape
    e = params["router"].shape[-1]
    if t % shards:
        raise ValueError(
            "a2a dispatch: %d tokens not divisible by dp*fsdp*ep=%d"
            % (t, shards)
        )
    if e % ep:
        raise ValueError(
            "a2a dispatch: %d experts not divisible by ep=%d" % (e, ep)
        )
    t_loc = t // shards
    e_loc = e // ep
    cap = expert_capacity(t_loc * router_top_k, e, capacity_factor)
    token_spec = P((MeshAxis.DP, MeshAxis.FSDP, MeshAxis.EP))
    param_specs = {
        "router": P(None, None),
        "w_up": P(MeshAxis.EP, None, None),
        "b_up": P(MeshAxis.EP, None),
        "w_down": P(MeshAxis.EP, None, None),
        "b_down": P(MeshAxis.EP, None),
    }
    token_axes = (MeshAxis.DP, MeshAxis.FSDP, MeshAxis.EP)

    def body(p, xl):
        logits = xl @ p["router"]
        dispatch, combine, _, stats = topk_dispatch(
            logits, cap, k=router_top_k
        )
        # capacity-bounded send buffers: [E, C, D] -> [ep(dst), E/ep, C, D]
        send = jnp.einsum("tec,td->ecd", dispatch, xl)
        send = send.reshape(ep, e_loc, cap, d)
        recv = jax.lax.all_to_all(
            send, MeshAxis.EP, split_axis=0, concat_axis=0
        )  # [ep(src), E/ep, C, D]
        # each local expert's batch: its C-slot buffer from every peer
        xin = recv.transpose(1, 0, 2, 3).reshape(e_loc, ep * cap, d)
        h = activation(
            jnp.einsum("egd,edh->egh", xin, p["w_up"])
            + p["b_up"][:, None, :]
        )
        out = (
            jnp.einsum("egh,ehd->egd", h, p["w_down"])
            + p["b_down"][:, None, :]
        )
        out = out.reshape(e_loc, ep, cap, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(
            out, MeshAxis.EP, split_axis=0, concat_axis=0
        )  # [ep(expert group), E/ep, C, D] == local [E, C, D] order
        y = jnp.einsum("tec,ecd->td", combine,
                       back.reshape(e, cap, d))
        # aux loss assembled GLOBALLY (equal-size groups: the mean of
        # group means IS the global mean), so drop-free runs match the
        # einsum path's aux bit-for-bit up to reduction order
        probs = jax.nn.softmax(logits, axis=-1)
        fraction = jax.lax.pmean(
            stats["expert_fraction"], token_axes)
        mean_prob = jax.lax.pmean(jnp.mean(probs, axis=0), token_axes)
        aux = e * jnp.sum(fraction * mean_prob)
        out_stats = {
            "dropped_fraction": jax.lax.pmean(
                stats["dropped_fraction"], token_axes),
            "expert_fraction": fraction,
        }
        return y, aux, out_stats

    return shard_map(
        body,
        mesh,
        ({k: param_specs[k] for k in params}, token_spec),
        (token_spec, P(), {"dropped_fraction": P(),
                           "expert_fraction": P()}),
    )(dict(params), x)


def _router_gates(params, x, k):
    """Shared drop-free routing for the inference formulations: f32
    softmax router probs, top-k choice, and the combine-weight rule —
    raw chosen prob for k=1 (Switch), chosen-set-normalized for k>1
    (GShard g1/g2). Returns (gates [T, k] f32, top_i [T, k])."""
    probs = jax.nn.softmax(
        (x @ params["router"]).astype(jnp.float32), axis=-1
    )
    top_v, top_i = jax.lax.top_k(probs, k)
    if k == 1:
        gates = top_v
    else:
        gates = top_v / jnp.maximum(top_v.sum(-1, keepdims=True), 1e-9)
    return gates, top_i


def moe_mlp_infer(params, x, activation=jax.nn.gelu, router_top_k=1):
    """Drop-free top-k MoE MLP for DECODE/PREFILL: every token reaches
    all k chosen experts, no capacity queues, no [T, E, C] dispatch
    tensor (whose drop-free form is O(T^2 E) memory — unusable for a
    long-prompt prefill). Instead each expert runs densely over all T
    tokens and the combine mask zeroes non-chosen pairs: E-times the
    dense-MLP FLOPs, O(T*H) memory. The right trade exactly where this
    is used — decode steps (T = batch, tiny) and the one-time prefill
    pass — and the reason cached MoE decode is deterministic: a token's
    routing can't depend on which other tokens share its pass.

    Combine weights match topk_dispatch with no drops (shared
    _router_gates). Returns y [T, D]."""
    e = params["router"].shape[-1]
    gates, top_i = _router_gates(params, x, router_top_k)
    # f32 gates and accumulator, like moe_mlp_apply's combine — the
    # bit-parity of the two formulations (and so cached-vs-uncached
    # decode equality) must hold for bf16-configured models too
    y = jnp.zeros(x.shape, jnp.float32)
    for ei in range(e):  # static unroll; E is a model-size constant
        h = activation(
            x @ params["w_up"][ei] + params["b_up"][ei]
        )
        out = h @ params["w_down"][ei] + params["b_down"][ei]
        w_e = jnp.sum(jnp.where(top_i == ei, gates, 0.0), axis=-1)
        y = y + w_e[:, None] * out.astype(jnp.float32)
    return y


def moe_mlp_infer_gather(params, x, activation=jax.nn.gelu,
                         router_top_k=1):
    """Drop-free top-k MoE MLP via sort + ``jax.lax.ragged_dot``
    (MegaBlocks-style dropless dispatch): the (token, choice) pairs are
    sorted by expert, each expert multiplies exactly its own
    contiguous row group against its weights, and outputs scatter-add
    home weighted by the gates.

    Same routing/combine semantics as :func:`moe_mlp_infer` (raw
    chosen prob for k=1, chosen-set-normalized for k>1, f32
    accumulator) at k/E of its FLOPs — moe_mlp_infer runs EVERY expert
    densely over all T tokens (E x dense-MLP), this runs each token
    through only its k experts: the right prefill path once expert
    counts grow. Opt-in via the model knob ``moe_infer_impl='gather'``
    (dense stays the default: for tiny decode batches the sort/gather
    overhead outweighs the FLOP win, and the dense form is the
    long-standing determinism baseline)."""
    t, d = x.shape
    e = params["router"].shape[-1]
    k = router_top_k
    gates, top_i = _router_gates(params, x, k)
    flat_e = top_i.reshape(-1)                      # [T*k]
    flat_t = jnp.repeat(jnp.arange(t), k)           # token of each pair
    order = jnp.argsort(flat_e)                     # stable: ties keep
    sorted_e = flat_e[order]                        # token order
    sorted_t = flat_t[order]
    xs = x[sorted_t]                                # [T*k, D]
    group_sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)
    h = activation(
        jax.lax.ragged_dot(xs, params["w_up"], group_sizes)
        + params["b_up"][sorted_e]
    )
    out = (
        jax.lax.ragged_dot(h, params["w_down"], group_sizes)
        + params["b_down"][sorted_e]
    )
    gate_sorted = gates.reshape(-1)[order]
    return jnp.zeros((t, d), jnp.float32).at[sorted_t].add(
        gate_sorted[:, None] * out.astype(jnp.float32)
    )


def moe_reference(params, x, capacity_factor=1.25,
                  activation=jax.nn.gelu, router_top_k=1):
    """Oracle: loop over tokens/experts in plain numpy-style code (tests
    compare the einsum formulation against this). Mirrors topk_dispatch:
    rank-0 choices claim capacity before rank-1, combine weights are raw
    probs for k=1 and, for k>1, normalized over the CHOSEN (pre-drop)
    experts — GShard g1/g2, drops zero their own weight only."""
    import numpy as np

    x = np.asarray(x, np.float32)
    router = np.asarray(params["router"], np.float32)
    t, _ = x.shape
    e = router.shape[-1]
    k = router_top_k
    capacity = expert_capacity(t * k, e, capacity_factor)
    logits = x @ router
    exps = np.exp(logits - logits.max(-1, keepdims=True))
    probs = exps / exps.sum(-1, keepdims=True)
    order = np.argsort(-probs, axis=-1)[:, :k]  # [T, k]
    counts = {i: 0 for i in range(e)}
    kept = [[] for _ in range(t)]  # (expert, prob) kept per token
    for rank in range(k):
        for ti in range(t):
            ei = int(order[ti, rank])
            if counts[ei] >= capacity:
                continue
            counts[ei] += 1
            kept[ti].append((ei, probs[ti, ei]))

    def expert_out(ti, ei):
        h = np.asarray(activation(
            jnp.asarray(x[ti] @ np.asarray(params["w_up"][ei])
                        + np.asarray(params["b_up"][ei]))
        ))
        return h @ np.asarray(params["w_down"][ei]) + np.asarray(
            params["b_down"][ei]
        )

    y = np.zeros_like(x)
    for ti in range(t):
        if not kept[ti]:
            continue
        # g1/g2: normalize over the CHOSEN experts, drops excluded from
        # the numerator only
        denom = (
            sum(probs[ti, int(order[ti, r])] for r in range(k))
            if k > 1 else 1.0
        )
        for ei, p in kept[ti]:
            y[ti] += (p / denom) * expert_out(ti, ei)
    return y


# ---------------------------------------------- drop-free gated experts
#
# The serving-path expert layer: top-k of E by router score (a softmax
# over the k chosen logits, or sigmoid scores chosen with a selection
# bias and renormalised), experts of three matrices (gated, ReGLU) or
# two (relu^2), NO capacity and NO drop, and told which experts it
# holds. Built on ops/expert_ffn.expert_tiles; this half decides which
# tiles there are.

#: up to this many rows the layer reads each HIT expert once for all
#: rows (a decode tick of few lanes: the experts' weights are the cost,
#: so the rows ride along as ONE tile of this height a hit expert);
#: above it the (row, choice) pairs are sorted by expert and each row
#: is multiplied by its own experts only, in tiles whose height
#: follows the call's shapes (`sorted_tile_rows`). Chosen from T, no
#: knob.
DECODE_ROWS = 16
#: the tallest sorted tile, a prefill's: at 256 rows a tile's products
#: (3 GFLOP at widths 2560 x 768) take about as long as its expert's
#: weights (11.8 MB). A call whose experts' runs are shorter takes a
#: shorter step of the ladder below
PREFILL_TILE_ROWS = 256
#: the heights a sorted tile may have: whole sublane tiles of bf16 (16
#: rows) and of float32 (8), each twice the one before
_TILE_LADDER = (16, 32, 64, 128, PREFILL_TILE_ROWS)


def sorted_tile_rows(t, k, count):
    """Rows of a sorted tile, from the call's static shapes alone: the
    lowest step of the ladder that is not under `t * k / count`, the
    run a held expert would have on average if every one of `t` rows'
    `k` choices were one of the `count` experts held here. A tile is
    multiplied whole and written whole, so one much taller than its
    expert's run multiplies and writes padding: a decode step of 32
    rows x 6 choices over 32 held experts takes 16 rows, one of 128
    rows x 8 choices 32, a prefill of 1,024 rows or more the 256 it
    always had. A run longer than the tile takes further tiles
    (`_grouped_tiles`), so the height costs time and never a pair."""
    run = -(-t * k // count)
    return next((tm for tm in _TILE_LADDER if tm >= run),
                PREFILL_TILE_ROWS)


def route_top_k(logits, k):
    """(gates [T, k] float32, experts [T, k] int32): the k largest of
    the router's logits [T, E] and a softmax over those k (the same
    numbers as a softmax over all E renormalised over the chosen)."""
    top_v, top_i = jax.lax.top_k(logits.astype(jnp.float32), k)
    return jax.nn.softmax(top_v, axis=-1), top_i.astype(jnp.int32)


def route_sigmoid_top_k(logits, k, bias, scale=1.0):
    """(gates [T, k] float32, experts [T, k] int32) of a sigmoid
    router: scores s = sigmoid(logits [T, E]); the k experts with the
    largest s + bias (`bias` [E] only selects); weights
    scale * s / (the chosen scores' sum + 1e-20)."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, top_i = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(scores, top_i, axis=-1)
    gates = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                              + 1e-20)
    return gates, top_i.astype(jnp.int32)


def _held_choices(experts, first, count):
    """(local [T, k], held [T, k]): each choice's index among the
    `count` experts held here from `first` on, and whether it is one.
    A choice below 0 is NO choice (held_experts) and is held nowhere."""
    local = experts - first
    return local, (local >= 0) & (local < count)


def _hit_tiles(h, gates, experts, first, *weights_and_use_kernel,
               activation=None):
    """Few rows: one tile per HIT expert over all the rows. Returns
    (y, held, hit, rows of the tiles computed)."""
    *weights, use_kernel = weights_and_use_kernel
    t = h.shape[0]
    count = weights[0].shape[0]
    local, held = _held_choices(experts, first, count)
    onehot = (local[..., None] == jnp.arange(count)) & held[..., None]
    gate_te = jnp.sum(jnp.where(onehot, gates[..., None], 0.0), axis=1)
    hit = jnp.any(onehot, axis=(0, 1))  # [count]
    n_live = jnp.sum(hit.astype(jnp.int32))
    # hit experts first, in order; the dead tail repeats the last live
    order = jnp.argsort(~hit, stable=True).astype(jnp.int32)
    order = jnp.where(jnp.arange(count) < n_live, order,
                      order[jnp.maximum(n_live - 1, 0)])
    tm = -(-t // DECODE_ROWS) * DECODE_ROWS
    x = jnp.pad(h, ((0, tm - t), (0, 0)))[None]
    tile_gates = jnp.pad(gate_te.T[order], ((0, 0), (0, tm - t)))
    y = expert_tiles(x, jnp.zeros((count,), jnp.int32),
                     tile_gates[..., None], order, n_live, *weights,
                     use_kernel=use_kernel, activation=activation)
    return jnp.sum(y, axis=0)[:t], held, hit, n_live * tm


def _grouped_tiles(h, gates, experts, first, *weights_and_use_kernel,
                   activation=None):
    """Many rows: the (row, choice) pairs sorted by expert, each held
    expert's run padded to whole tiles of `sorted_tile_rows` rows;
    gathers only, no scatter. `n_tiles` is the static bound: every
    pair in a full tile, and a part-filled last tile a held expert.
    Returns (y, held, hit, rows of the tiles computed)."""
    *weights, use_kernel = weights_and_use_kernel
    t, d = h.shape
    k = experts.shape[1]
    count = weights[0].shape[0]
    tm = sorted_tile_rows(t, k, count)
    n_tiles = -(-t * k // tm) + count
    local, held = _held_choices(experts, first, count)
    # pairs of experts held elsewhere, and pairs that are no choice
    # (below 0), sort behind every held expert and are in no tile
    key = jnp.where(held, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True)
    rank = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
    sizes = jnp.sum(key[:, None] == jnp.arange(count), axis=0)
    start = jnp.cumsum(sizes) - sizes  # first sorted pair of each expert
    tiles_of = -(-sizes // tm)
    tile_end = jnp.cumsum(tiles_of)
    tile_start = tile_end - tiles_of
    n_live = tile_end[-1]
    expert_of = jnp.searchsorted(tile_end, jnp.arange(n_tiles),
                                 side="right")
    expert_of = jnp.where(jnp.arange(n_tiles) < n_live, expert_of,
                          expert_of[jnp.maximum(n_live - 1, 0)])
    expert_of = jnp.minimum(expert_of, count - 1).astype(jnp.int32)
    # row j of tile i is sorted pair start[e] + (row - first row of e)
    row = jnp.arange(n_tiles * tm).reshape(n_tiles, tm)
    e_row = expert_of[:, None]
    offset = row - tile_start[e_row] * tm
    live_row = ((jnp.arange(n_tiles) < n_live)[:, None]
                & (offset < sizes[e_row]))
    pair = order[jnp.clip(start[e_row] + offset, 0, t * k - 1)]
    x_tiles = h[pair // k]  # [n_tiles, tm, d]
    tile_gates = jnp.where(live_row, gates.reshape(-1)[pair], 0.0)
    # a dead tile names the last live tile's rows, as it names its
    # expert: the kernel then fetches nothing for it
    x_of = jnp.minimum(jnp.arange(n_tiles), jnp.maximum(n_live - 1, 0))
    y = expert_tiles(x_tiles, x_of, tile_gates[..., None], expert_of,
                     n_live, *weights, use_kernel=use_kernel,
                     activation=activation)
    # each pair's row of the tiles; a pair held elsewhere reads none
    e_pair = jnp.minimum(key, count - 1)
    dest = tile_start[e_pair] * tm + rank - start[e_pair]
    rows = y.reshape(n_tiles * tm, d)[jnp.where(key < count, dest, 0)]
    rows = jnp.where((key < count)[:, None], rows, 0.0)
    hit = sizes > 0
    return jnp.sum(rows.reshape(t, k, d), axis=1), held, hit, n_live * tm


def _held_experts(first, use_kernel, activation, h, gates, experts,
                  *weights):
    path = _hit_tiles if h.shape[0] <= DECODE_ROWS else _grouped_tiles
    y, held, hit, tile_rows = path(h, gates, experts, first, *weights,
                                   use_kernel, activation=activation)
    return (y, jnp.sum(held, axis=1).astype(jnp.int32),
            hit.astype(jnp.int32), tile_rows.astype(jnp.int32))


@functools.lru_cache(maxsize=None)
def _lanes_as_one_call(first, use_kernel, activation=None):
    """_held_experts(first, use_kernel, activation, ...) with a
    batching rule:
    mapped over rows with the weights shared, the lanes are laid side
    by side and computed as ONE call (itself mappable again)."""
    plain = functools.partial(_held_experts, first, use_kernel,
                              activation)
    call = custom_vmap(plain)

    @call.def_vmap
    def _lanes(axis_size, in_batched, h, gates, experts, *weights):
        if any(in_batched[3:]) or not all(in_batched[:3]):
            axes = [0 if b else None for b in in_batched]
            out = jax.vmap(plain, in_axes=axes)(h, gates, experts,
                                                *weights)
            return out, (True, True, True, True)
        t = h.shape[1]
        flat = [a.reshape((axis_size * t,) + a.shape[2:])
                for a in (h, gates, experts)]
        y, held, hit, tile_rows = call(*flat, *weights)
        return ((y.reshape((axis_size, t) + y.shape[1:]),
                 held.reshape(axis_size, t), hit, tile_rows),
                (True, True, False, False))

    return call


def held_experts(h, gates, experts, weights, first=0, use_kernel=None,
                 activation=None):
    """This chip's part of a drop-free expert layer.

    h [T, D] (the compute dtype), gates [T, k] float32 and experts
    [T, k] (a router's choice over ALL the layer's experts), and the
    `weights` of the `count = weights[0].shape[0]` experts held here,
    experts `first .. first + count` of the layer, in h's dtype: three
    matrices an expert for gated (ReGLU) experts, (w_gate, w_up
    [count, D, H], w_down [count, H, D]), or two for relu^2 experts,
    (w_up, w_down), both [count, H, D], a hidden unit a row
    (ops/expert_ffn.py says why). `activation` names the form where
    the count does not ("swiglu": gated, with silu in relu's place;
    None: "reglu" for three matrices, "relu2" for two). Returns

        y [T, D] float32     sum over a row's HELD choices of
                             gate * ((relu(h W_gate) * (h W_up)) W_down)
                             or gate * (relu(h W_up^T)^2 W_down)
        held [T] int32       how many of a row's k choices are held
        hit [count] int32    1 for each held expert some row chose
        tile_rows int32      the rows of the tiles that were computed
                             (live tiles x their height): what the
                             kernel multiplied and wrote for the held
                             pairs, padding included

    No choice is dropped and no expert is computed that no row chose;
    what the experts held elsewhere would add is left out (on one chip
    there is no exchange, and nothing stands in for one). With `first`
    0 and all the experts it is the whole layer.

    A CHOICE BELOW 0 IS NO CHOICE: the caller writes -1 over the
    choices of a row that carries no sequence (a free lane of the
    decode step), and such a pair is held nowhere: it makes no tile,
    marks no expert in `hit`, is not in `held`, and adds exactly 0.0
    to its row, whatever its gate. The tiles that are left keep their
    order (hit experts by index), so every other row's `y` is bit for
    bit what it would be had the row chosen like the others. With no
    choice at all the kernel is handed `n_live` 0 and `y` is zeros.

    The path and the tiles' height are chosen from the call's static
    shapes, with no option: up to DECODE_ROWS rows every hit expert is
    one tile over the same rows; above it the pairs are sorted by
    expert into tiles of `sorted_tile_rows(T, k, count)` rows, the
    lowest step of 16 .. PREFILL_TILE_ROWS not under the run an expert
    has on average (a decode step of many lanes takes short tiles, a
    long prefill the tallest). Under `jax.vmap` over rows with the
    weights shared (the serving step maps one lane a sequence) the
    lanes are laid side by side and computed as ONE call, so a tick
    reads a hit expert once and not once a lane and T is the tick's
    rows; `hit` and `tile_rows` are then the tick's."""
    if activation == ("reglu" if len(weights) == 3 else "relu2"):
        activation = None  # what the count says: one call for both names
    return _lanes_as_one_call(int(first), use_kernel, activation)(
        h, gates, experts, *weights)


def held_experts_reglu(h, gates, experts, w_gate, w_up, w_down, first=0,
                       use_kernel=None):
    """`held_experts` of gated (ReGLU) experts."""
    return held_experts(h, gates, experts, (w_gate, w_up, w_down),
                        first=first, use_kernel=use_kernel)
