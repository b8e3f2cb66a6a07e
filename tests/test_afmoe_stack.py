"""The `afmoe` family (Trinity-Large-Preview: gated, QK-normed GQA under
sandwich norms, sliding and full layers mixed, a leading dense SwiGLU
layer before sigmoid-routed SwiGLU experts with a shared expert)
through the ordinary model and the ONE serving engine, against its
plain reference (chipbench/refs/afmoe.py), at tiny widths on the CPU;
and the KV pool's block CLASSES by layer window (serving/kv_pool.py),
which this model is served over.

Tolerances. float32 (`TOL`): model and reference compute the same
products in another order; the stream is of unit scale and five layers
deep, and the worst logit gap seen over the seeds here is 2e-5. bf16
(`TOL_BF16_*`): every product's operands are rounded to 8 bits of
mantissa, so a logit of unit scale moves by several hundredths on
average and, where a rounding flips one of a token's 3 of 8 experts (the
router itself is float32, what it reads is not), by about one at one
position. This family reads higher than the other expert families' bf16
cases (0.05 and 1.0 in tests/test_sdar_block_stack.py): a sandwich norm
brings every sublayer's output back to unit scale, its rounding with
it, where a plain residual adds a sublayer's small output to a large
stream; at 48 wide a flipped expert is a third of a token's routed
part. Seen over three seeds: mean 0.075-0.092, worst 1.07-1.38; held to
0.15 and 2.5 (float32 reads 2e-5 on both).
"""

import functools
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import FrozenDict

from chipbench.drivers.open_loop import _unflatten
from chipbench.refs import afmoe as ref
from elasticdl_tpu.common.log_utils import default_logger
from elasticdl_tpu.common.model_utils import load_model_spec_from_module
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.serving import kv_pool
from elasticdl_tpu.serving.admission import ServingRequest
from elasticdl_tpu.serving.engine import PagedContinuousBatchingEngine
from elasticdl_tpu.training import trainer as trainer_mod
from model_zoo.transformer_lm import transformer_lm as zoo

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
TOL_BF16_MEAN, TOL_BF16_WORST = 0.15, 2.5
#: the cell's proportions at a tiny size: one leading dense layer, then
#: a period S S S F of expert layers; window 8 under 72 positions
PARAMS = {
    "vocab_size": 96, "seq_len": 72, "embed_dim": 48, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 16, "num_layers": 5, "pos_emb": "rope",
    "rope_theta": 10000, "norm": "rms", "norm_eps": 1e-5,
    "attn_window": 8, "rope_layout": [1, 1, 1, 1, 0],
    "window_layout": [1, 1, 1, 1, 0], "qk_norm": True, "attn_gate": True,
    "sandwich_norm": True, "embed_scale": True,
    "mlp": "moe_reglu", "mlp_layout": [0, 1, 1, 1, 1], "dense_hidden": 96,
    "moe_activation": "swiglu", "moe_scoring": "sigmoid",
    "moe_route_scale": 2.448, "moe_route_from": "mlp", "moe_experts": 8,
    "moe_top_k": 3, "moe_hidden": 24, "moe_shared_hidden": 24,
    "experts_held": [0, 4],
}
WEIGHTS = {"qk_gain": 2.0, "router_gain": 1.0, "sel_bias_std": 0.2}


def _cfg(**over):
    return dict(PARAMS, **WEIGHTS, **over)


@functools.lru_cache(maxsize=None)
def _weights(seed=0):
    cfg = _cfg()
    return ref.make_leaves(cfg, seed, ref.all_leaves(cfg))


def _engine(slots=2, params=PARAMS, seed=0, **kwargs):
    trainer = trainer_mod.Trainer(
        load_model_spec_from_module(zoo),
        mesh=mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1]),
        model_params="; ".join(
            "%s=%r" % kv for kv in sorted(params.items())))
    state = trainer_mod.TrainState(
        step=jnp.zeros((), jnp.int32), params=_unflatten(_weights(seed)),
        opt_state=(), model_state=FrozenDict({}),
        rng=jax.random.PRNGKey(0))
    kwargs.setdefault("share_prefix", False)
    return PagedContinuousBatchingEngine(
        trainer, state, slots, block_size=4, **kwargs)


def _model(**over):
    return zoo.custom_model(**dict(PARAMS, **over))


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 96, n)]


def _counts_since(before):
    after = tracing.recorder().counts()
    return {k: after[k] - before.get(k, 0) for k in after}


# ------------------------------------------------ (a) the full forward


@pytest.mark.parametrize("seed,held", [(0, [0, 4]), (1, [4, 4]),
                                       (2, [0, 8])])
def test_full_forward_matches_the_reference(seed, held):
    cfg = _cfg(experts_held=held)
    w = ref.make_leaves(cfg, seed, ref.all_leaves(cfg))
    tokens = jnp.asarray([_prompt(seed, 40)])
    got = _model(experts_held=held).apply({"params": _unflatten(w)},
                                          {"tokens": tokens})
    want = ref.forward(cfg, w, tokens, rows=8)
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    assert 0.5 < float(jnp.std(want)) < 2.0  # a logit of unit scale


def test_the_full_forward_in_bfloat16_is_within_its_own_tolerance():
    tokens = jnp.asarray([_prompt(3, 40)])
    got = _model(dtype="bf16").apply({"params": _unflatten(_weights())},
                                     {"tokens": tokens})
    gap = np.abs(np.asarray(got) - np.asarray(
        ref.forward(_cfg(), _weights(), tokens, rows=8)))
    assert gap.mean() < TOL_BF16_MEAN and gap.max() < TOL_BF16_WORST
    assert gap.max() > TOL  # and it is not the float32 program


def test_the_names_of_the_parameters_are_the_references():
    tree = _model().init(jax.random.PRNGKey(0),
                         {"tokens": jnp.zeros((1, 8), jnp.int32)})["params"]
    flat = {"/".join(k.key for k in path): np.shape(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                jax.tree.map(lambda x: getattr(x, "value", x), tree,
                             is_leaf=lambda x: hasattr(x, "value")))[0]}
    assert flat == {p: tuple(s) for p, (s, _) in
                    ref.all_leaves(_cfg()).items()}
    assert set(tree["block_0"]) == {
        "RMSNorm_0", "RMSNorm_1", "attn", "post_attn_norm",
        "post_mlp_norm", "mlp_gate", "mlp_up", "mlp_down"}
    assert set(tree["block_1"]["moe"]) == {
        "router", "router_bias", "w_gate", "w_up", "w_down",
        "shared_gate", "shared_up", "shared_down"}
    assert set(tree["block_1"]["attn"]) == {
        "qkv", "gate", "q_norm", "k_norm", "proj"}
    assert _model().layer_windows() == (8, 8, 8, 8, 0)


@pytest.mark.parametrize("over", [
    {"attn_gate": False}, {"sandwich_norm": False}, {"embed_scale": False},
    {"moe_shared_hidden": 0}, {"moe_route_scale": 1.0},
    {"window_layout": [1, 1, 1, 1, 1]}, {"rope_layout": [1, 1, 1, 1, 1]},
    {"moe_scoring": "softmax"}], ids=lambda o: "%s=%s" % next(iter(o.items())))
def test_each_of_its_mechanisms_makes_another_model(over):
    """Leave one mechanism out and the logits are no longer the
    reference's: none of them is decoration."""
    tokens = jnp.asarray([_prompt(5, 24)])
    model = _model(**over)
    tree = model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]
    tree = jax.tree.map(lambda x: getattr(x, "value", x), tree,
                        is_leaf=lambda x: hasattr(x, "value"))
    given = _unflatten(_weights())
    # what the cut model still has, from the reference's leaves
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: functools.reduce(
            lambda t, k: t[k.key], path, given), tree)
    got = model.apply({"params": params}, {"tokens": tokens})
    want = ref.forward(_cfg(), _weights(), tokens, rows=8)
    assert float(jnp.max(jnp.abs(got - want))) > 100 * TOL


def test_a_model_without_the_new_parameters_has_none_of_their_leaves():
    tree = zoo.custom_model(
        vocab_size=32, seq_len=16, embed_dim=16, num_heads=2,
        num_layers=2, pos_emb="rope", norm="rms").init(
            jax.random.PRNGKey(0),
            {"tokens": jnp.zeros((1, 8), jnp.int32)})["params"]
    assert set(tree["block_1"]) == {"RMSNorm_0", "RMSNorm_1", "attn",
                                    "mlp_up", "mlp_down"}
    assert set(tree["block_1"]["attn"]) == {"qkv", "proj"}
    with pytest.raises(ValueError, match="needs dense_hidden"):
        zoo.custom_model(vocab_size=32, seq_len=16, embed_dim=16,
                         num_heads=2, num_layers=1, mlp="swiglu").init(
            jax.random.PRNGKey(0), {"tokens": jnp.zeros((1, 8), jnp.int32)})
    with pytest.raises(ValueError, match="sandwich_norm is built for"):
        _model(layer_kinds="*****").init(
            jax.random.PRNGKey(0), {"tokens": jnp.zeros((1, 8), jnp.int32)})


# ------------------------- (b) prefill, then decode through the classes


_CALLS = {}  # id(engine) -> its jitted decode call


def _served_logits(eng, slot, request, program=_CALLS):
    """The logits of the model's own paged decode call at the lane's
    position, read from the engine's pool as the next step will (the
    call jitted once an engine: the tables' layout is static)."""
    if id(eng) not in program:
        table_of = eng.kv.table_of

        def call(variables, pools, table, pos, tok):
            out, _ = eng.model.apply(
                dict(variables, cache={"pos": pos}),
                {"tokens": tok[None, None]}, training=False, decode=True,
                mutable=["cache", "kv_out"],
                paged={"pools": pools, "table_of": table_of,
                       "table": table[None]})
            return out[0, 0]

        program[id(eng)] = jax.jit(call)
    return np.asarray(program[id(eng)](
        eng._exec_variables, eng.kv.pools,
        jnp.asarray(eng.kv.tables[slot]),
        jnp.asarray(int(eng._positions[slot])),
        jnp.asarray(request.generated[-1])))


@functools.lru_cache(maxsize=None)
def _served(dtype="", prompt_len=21, new=18):
    """A prompt longer than the window, decoded across several
    releases, in line (launched and committed, nothing ahead): the
    served logits at every decoded position, the tokens, the counters,
    and the class tables as they stood at the end."""
    eng = _engine(params=dict(PARAMS, **({"dtype": dtype} if dtype else {})))
    prompt = _prompt(7, prompt_len)
    request = ServingRequest(prompt, new)
    before = dict(tracing.recorder().counts())
    slot, _, _ = eng.insert(request)
    seated = eng.kv.tables[slot].copy()
    logits, holes = [], []
    while eng.active_count():
        # the book first: it is the table the step reads
        eng.kv.ensure_blocks(slot, int(eng._positions[slot]))
        logits.append(_served_logits(eng, slot, request))
        holes.append(eng.kv.holes[:, slot].copy())
        assert eng._launch() and eng._collect()
    return (prompt, list(request.generated), logits,
            _counts_since(before), seated, np.stack(holes), eng)


def _reference_logits(prompt, generated):
    seq = prompt + generated
    pad = -len(seq) % 8
    out = ref.forward(_cfg(), _weights(),
                      jnp.asarray([seq + [0] * pad]), rows=8)[0]
    return np.asarray(out[len(prompt) - 1:len(seq) - 1])


def test_prefill_then_decode_through_the_classes_matches_the_reference():
    prompt, generated, logits, _, seated, holes, eng = _served()
    assert len(prompt) > PARAMS["attn_window"] * 2
    want = _reference_logits(prompt, generated)
    assert generated[0] == int(want[0].argmax())
    got = np.stack(logits)
    assert got.shape == want[1:].shape
    assert np.abs(got - want[1:]).max() < TOL
    assert generated[1:] == [int(r.argmax()) for r in got]
    # two classes, the whole-length first; a table a class side by side
    m = eng.kv.max_blocks_per_slot
    assert eng.kv.class_windows == [0, 8] and m == 18
    assert eng.kv.class_layers == [1, 4]
    assert eng.kv.tables.shape == (2, 2 * m)
    assert eng.kv.table_of["block_4"] == (0, m)
    assert eng.kv.table_of["block_0"] == (m, 2 * m)
    # the prompt of 21 was seated with blocks 0..5 in the whole-length
    # class and, of the window class, only those the first step (at
    # position 21: keys 14..21) has in reach: 3, 4, 5
    assert (seated[:6] >= 0).all() and (seated[6:m] < 0).all()
    assert (seated[m:m + 3] < 0).all() and (seated[m + 3:m + 6] >= 0).all()
    # and the lane decoded across several releases
    assert holes[0, 1] == 3 and holes[-1, 1] == 7
    assert (holes[:, 0] == 0).all()


def test_the_same_in_bfloat16_is_within_its_own_tolerance():
    prompt, generated, logits, _, _, _, _ = _served("bf16")
    gap = np.abs(np.stack(logits) - _reference_logits(prompt,
                                                      generated)[1:])
    assert gap.mean() < TOL_BF16_MEAN and gap.max() < TOL_BF16_WORST


def test_one_table_for_every_layer_serves_the_same_tokens():
    """With prefix sharing on the pool is the parent's (one table,
    every layer whole) and the stream is the same."""
    prompt, generated, _, _, _, _, _ = _served()
    eng = _engine(share_prefix=True)
    assert eng.kv.table_of is None and len(eng.kv.allocators) == 1
    assert eng.kv.allocator.window == 0
    request = ServingRequest(prompt, len(generated))
    eng.insert(request)
    while eng.active_count():
        eng.step()
    assert request.generated == generated
    assert (eng.kv.holes == 0).all()


def test_the_counters_say_what_the_classes_hold():
    prompt, generated, _, counts, _, _, _ = _served()
    # blocks x layers: one table would hold every block of five layers
    assert 0 < counts["kv.blocks_held"] < 0.6 * counts["kv.blocks_whole"]
    # a window layer holds at most one dead block a lane between two
    # releases: 4 layers of a tick's 5
    ticks = len(generated) - 1
    assert counts["kv.window_dead_blocks"] <= 4 * ticks
    assert counts["kv.window_dead_blocks"] < 0.25 * counts["kv.blocks_held"]
    # 21 + 18 tokens: blocks 3 .. 6 of the window class released while
    # it decoded, in each of its 4 layers
    assert counts["kv.window_blocks_released"] == 4 * 4
    # the prompt's blocks 0, 1, 2 were written to the full layer only
    assert counts["prompt_write.blocks_skipped"] == 4 * 3
    assert counts["prompt_write.launches"] == 6
    assert counts["pool.inplace_launches"] == counts["pool.launches"]
    # the reach is what the kernel streams, whatever the pool holds
    assert counts["paged.blocks_streamed"] > 0
    assert counts["moe.pairs_routed"] == ticks * 3 * 4  # 4 expert layers


# ---------------- (c) lanes seated and freed with a step in flight


def _stream(eng, requests, order):
    """Drive `eng.step()` with `order[tick]` naming the requests seated
    before that tick; returns when all have finished."""
    tick, done = 0, set()
    while len(done) < len(requests):
        for i in order.get(tick, ()):
            eng.insert(requests[i])
        for _slot, request, _tokens, finished in eng.step():
            if finished:
                done.add(id(request))
        tick += 1
        assert tick < 400


def test_two_lanes_of_different_length_with_a_step_in_flight():
    """A long and a short request seated ticks apart, the short one
    freed while the long decodes on with a step in flight, and a third
    seated into the freed lane: each stream is what the request gets
    alone, and is the reference's greedy stream."""
    specs = [(_prompt(11, 33), 24), (_prompt(12, 9), 6),
             (_prompt(13, 18), 14)]
    alone = []
    for prompt, new in specs:
        eng = _engine(slots=1)
        request = ServingRequest(prompt, new)
        _stream(eng, [request], {0: [0]})
        alone.append(list(request.generated))
    eng = _engine(slots=2)
    requests = [ServingRequest(prompt, new) for prompt, new in specs]
    before = dict(tracing.recorder().counts())
    _stream(eng, requests, {0: [0], 3: [1], 12: [2]})
    counts = _counts_since(before)
    assert [r.generated for r in requests] == alone
    assert counts["tick.ahead"] > 0.8 * counts["tick.transfers"] > 0
    for (prompt, _new), request in zip(specs, requests):
        want = _reference_logits(prompt, request.generated)
        assert request.generated == [int(r.argmax()) for r in want]
    # everything went back: both classes whole again
    for alloc in eng.kv.allocators:
        assert alloc.num_free() == alloc.num_blocks
        assert alloc.available() == alloc.num_blocks
    assert (eng.kv.tables == -1).all() and (eng.kv.holes == 0).all()


# ------------------------------------------- (d) the shares add up


def _module_share(w, u, first, count):
    """The program's expert layer holding `count` experts from `first`,
    over the reference's leaves: its routed part plus the shared one."""
    module = zoo.ExpertFFN(
        num_experts=8, top_k=3, hidden=24, held=(first, count),
        activation="swiglu", scoring="sigmoid", route_scale=2.448,
        shared_hidden=24)
    params = {k[len("moe/"):]: (v[first:first + count]
                                if k.startswith("moe/w_") else v)
              for k, v in w.items() if k.startswith("moe/")}
    return module.apply({"params": params}, u[None], u[None])[0]


def test_the_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """32 shares of 8 of Trinity's 256 experts, here 4 shares of 2 of
    8: what every chip's held experts add, and the shared expert, which
    every chip computes alike, counted once, are the uncut reference's
    expert layer (before its output norm: a deployment sums there)."""
    cfg = _cfg(experts_held=[0, 8])
    w = ref.block_weights(ref.make_leaves(cfg, 4, ref.layer_leaves(cfg, 2)),
                          2)
    u = jax.random.normal(jax.random.PRNGKey(9), (12, 48))
    shared = ref.shared_expert(w, u)
    uncut = ref.expert_mlp(cfg, w, u)
    parts = [_module_share(w, u, first, 2) - shared
             for first in (0, 2, 4, 6)]
    # float32 rounding: the shares sum the same products in four parts
    assert float(jnp.max(jnp.abs(sum(parts) + shared - uncut))) < 5e-6
    assert float(jnp.max(jnp.abs(_module_share(w, u, 0, 8) - uncut))) < 5e-6
    # a share alone is the reference given that share, and is not the
    # whole (each part carries weight)
    held = dict(cfg, experts_held=[2, 2])
    w_held = dict(w, **{k: w[k][2:4] for k in w if k.startswith("moe/w_")})
    assert float(jnp.max(jnp.abs(
        parts[1] - ref.expert_mlp(held, w_held, u, shared=False)))) < 5e-6
    assert min(float(jnp.max(jnp.abs(p))) for p in parts) > 0.05


# ------------------------------------------- (e) the class charge


def test_the_allocator_charges_a_window_class_its_window():
    whole = kv_pool.BlockAllocator(40, 4)
    ring = kv_pool.BlockAllocator(40, 4, window=8)
    assert ring.window_blocks == 4
    assert [ring.charge(n) for n in (1, 8, 16, 17, 70)] == [1, 2, 4, 4, 4]
    assert whole.charge(70) == 18
    # a row at 21 sees keys 14 .. 21: block 3 on
    assert [ring.live_from(p) for p in (0, 7, 8, 11, 21)] == [0, 0, 0, 1, 3]
    assert ring.plan(None, 21, 70) == ([], 4) and whole.plan(
        None, 21, 70) == ([], 18)
    ring.alloc("a", 21, commit_tokens=70)
    assert ring.table("a")[:3] == [-1] * 3 and ring.holes("a") == 3
    assert ring.blocks_in_use() == 3 and ring.available() == 36
    for pos in range(21, 70):  # decode to the end: never more than 4
        ring.extend("a", pos + 1)
        assert ring.blocks_in_use() <= 4 and ring.available() == 36
        held = [j for j, b in enumerate(ring.table("a")) if b >= 0]
        assert held[0] <= ring.live_from(pos) <= held[-1] == pos // 4
    assert ring.released == 18 - 3 - 3
    assert ring.free("a") == 18 and ring.available() == 40
    assert ring.num_free() == 40
    with pytest.raises(ValueError, match="no shared or spilled chain"):
        kv_pool.BlockAllocator(40, 4, share_prefix=True, window=8)


def test_the_classes_seat_every_lane_where_one_table_seats_a_quarter():
    """The cell's proportions: 4 lanes, each with the longest request
    (72 positions, a window of 8), one full layer and four window
    layers. The classes hold 72 + 4 x 16 = 136 layer-blocks; one table
    for five layers in the same bytes has 27 blocks of 18 a lane."""
    eng = _engine(slots=4)
    kv = eng.kv
    assert [a.num_blocks for a in kv.allocators] == [4 * 18, 4 * (2 + 2)]
    per_block = 2 * 4 * 2 * 16 * 4  # K and V, 4 rows, 2 heads of 16, f32
    assert kv.class_block_bytes == [per_block, 4 * per_block]
    assert kv.bytes_total == (72 + 4 * 16) * per_block
    one_table = kv_pool.BlockAllocator(kv.bytes_total // (5 * per_block), 4)
    assert one_table.num_blocks == 27
    longest = [ServingRequest(_prompt(20 + i, 40), 32) for i in range(4)]
    seated = 0
    for i, request in enumerate(longest):
        assert eng.can_seat(request)
        eng.insert(request)
        if one_table.can_seat(None, 40, 71):
            one_table.alloc(i, 40, commit_tokens=71)
            seated += 1
    assert seated == 1  # a quarter of the four
    assert [a.available() for a in kv.allocators] == [0, 0]
    stats = eng.kv_stats()
    assert stats["kv_classes"][1][:3] == [8, 4, 16]
    assert stats["kv_blocks_total"] == 72 + 16
    assert stats["kv_bytes_in_use"] == (4 * 10 + 4 * 4 * 2) * per_block
    while eng.active_count():
        eng.step()
    assert all(len(r.generated) == 32 for r in longest)
    assert [a.available() for a in kv.allocators] == [72, 16]


def test_a_full_window_class_refuses_by_name():
    """A window class capped below what its lanes need: admission says
    no (backpressure), and a seat past it names the class."""
    eng = _engine(slots=4, num_blocks=10)
    assert [a.num_blocks for a in eng.kv.allocators] == [10, 10]
    first = ServingRequest(_prompt(30, 20), 4)   # 6 whole, 4 window
    second = ServingRequest(_prompt(31, 9), 4)   # 3 whole, 3 window
    third = ServingRequest(_prompt(32, 4), 4)    # 2 whole, 2 window
    eng.insert(first)
    assert eng.can_seat(second)
    eng.insert(second)
    assert [a.available() for a in eng.kv.allocators] == [1, 3]
    assert not eng.can_seat(third)  # the whole-length class is full
    with pytest.raises(kv_pool.OutOfBlocks, match="2 shared|need 2"):
        eng.insert(third)
    eng.evict(0)
    eng.kv.allocators[1]._reserved += 6  # the window class alone full
    assert not eng.can_seat(third)
    with pytest.raises(kv_pool.OutOfBlocks,
                       match="class of blocks of window 8 needs 2"):
        eng.insert(third)
    assert eng.kv.allocators[0].table(eng.free_slots()[0]) == []


# ------------------------------ (f) what the classes cannot hold


def test_a_classed_pool_refuses_a_chain_export_and_a_copy_by_name():
    eng = _engine()
    eng.insert(ServingRequest(_prompt(40, 12), 4))
    with pytest.raises(ValueError, match="chain export .* 2 classes by "
                       "attention window .*--kv_shared 1"):
        eng.kv.export_chain(_prompt(40, 12))
    with pytest.raises(ValueError, match="a copy on write needs every"):
        eng.kv.cow_for_write(0, 3)
    with pytest.raises(ValueError, match="requires a prefix-shared pool"):
        eng.kv.import_chain([])
    with pytest.raises(ValueError, match="leaf_windows with share_prefix"):
        kv_pool.PagedKVPool(eng._kv_shapes, 72, 2, 36, 4, share_prefix=True,
                            leaf_windows=[0] * len(eng.kv.kinds))


@pytest.mark.parametrize("kwargs", [
    {"share_prefix": True}, {"host_bytes": 1 << 20, "share_prefix": True},
    {"prefill_chunk_tokens": 8}], ids=lambda k: "+".join(sorted(k)))
def test_what_reads_behind_a_window_keeps_the_one_table_pool(kwargs, caplog):
    """A shared or spilled chain, and a chunked prefill's tiles, need
    every block of every layer: with any of them on the pool is what it
    was, one table, every sequence charged whole, and the server says
    which option gave the classes up and what that costs a lane."""
    default_logger.addHandler(caplog.handler)  # it does not propagate
    try:
        eng = _engine(**kwargs)
    finally:
        default_logger.removeHandler(caplog.handler)
    assert eng.kv.table_of is None and eng.kv.class_windows == [0]
    assert eng.kv.tables.shape == (2, 18)
    assert eng._lanes_spec().shape == (2, 4 + 18)
    assert eng.kv.allocator.charge(70) == 18
    given_up = eng.kv_stats()["kv_classes_given_up"]
    assert len(given_up) == len(kwargs)
    assert all(any(key in name for name in given_up)
               for key in ("kv_shared" if k == "share_prefix"
                           else "kv_host_bytes" if k == "host_bytes" else k
                           for k in kwargs))
    said = [r.getMessage() for r in caplog.records
            if "ONE table for every layer" in r.getMessage()]
    # 72 tokens in blocks of 4: 18 blocks a window layer where the
    # class of window 8 would charge 2 + 2
    assert len(said) == 1 and all(name in said[0] for name in given_up)
    assert "charged 18 blocks in each of the 4 window layers" in said[0]
    assert "window 8 would charge 4" in said[0]
    assert _engine().kv_stats()["kv_classes_given_up"] == []


def test_a_draft_keeps_the_one_table_pool_too():
    draft_params = dict(PARAMS, num_layers=1, mlp_layout=[0],
                        rope_layout=[1], window_layout=[1])
    cfg = dict(draft_params, **WEIGHTS)
    d_trainer = trainer_mod.Trainer(
        load_model_spec_from_module(zoo),
        mesh=mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1]),
        model_params="; ".join(
            "%s=%r" % kv for kv in sorted(draft_params.items())))
    d_state = trainer_mod.TrainState(
        step=jnp.zeros((), jnp.int32), opt_state=(),
        params=_unflatten(ref.make_leaves(cfg, 0, ref.all_leaves(cfg))),
        model_state=FrozenDict({}), rng=jax.random.PRNGKey(0))
    eng = _engine(draft=(d_trainer, d_state), draft_k=2)
    assert eng.kv.table_of is None and eng.kv.class_windows == [0]
    assert eng.kv_stats()["kv_classes_given_up"] == [
        "speculative decode (draft, draft_k)"]


def test_one_window_for_every_layer_is_one_class_of_that_window():
    """A model whose layers all have the one window, served without
    sharing: one class, one table, charged the window."""
    params = {"vocab_size": 64, "seq_len": 32, "embed_dim": 32,
              "num_heads": 2, "num_layers": 2, "pos_emb": "rope",
              "attn_window": 8}
    trainer = trainer_mod.Trainer(
        load_model_spec_from_module(zoo),
        mesh=mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1]),
        model_params="; ".join("%s=%r" % kv for kv in sorted(params.items())))
    state = trainer.init_state(({"tokens": np.zeros((1, 32), np.int32)},
                                np.zeros((1, 32), np.int32)))
    out = {}
    for shared in (False, True):
        eng = PagedContinuousBatchingEngine(trainer, state, 2, block_size=4,
                                            share_prefix=shared)
        request = ServingRequest(_prompt(50, 13)[:13], 16)
        request.prompt = [t % 64 for t in request.prompt]
        before = dict(tracing.recorder().counts())
        eng.insert(request)
        while eng.active_count():
            eng.step()
        out[shared] = (list(request.generated), _counts_since(before), eng)
    assert out[False][0] == out[True][0]
    eng = out[False][2]
    assert eng.kv.table_of is None and eng.kv.class_windows == [8]
    assert eng.kv.allocator.num_blocks == 2 * (2 + 2)
    assert eng.kv.tables.shape == (2, 8)
    assert out[False][1]["kv.window_blocks_released"] > 0
    assert out[False][1]["kv.blocks_held"] < out[True][1]["kv.blocks_held"]
    assert out[True][1].get("kv.window_blocks_released", 0) == 0
    assert (out[True][1]["kv.blocks_held"]
            == out[True][1]["kv.blocks_whole"]
            == out[False][1]["kv.blocks_whole"])


# ------------- (g) a token a step: this family's program, pinned

#: sha1 of the sorted (operation, count) pairs of this family's lowered
#: paged step at the tiny size above (tests/test_sdar_block_stack.py
#: pins the dense, expert and state families' the same way, and they
#: did not move with this family's parameters): taken on this PR's tree
_STEP_OPS = "84d9be956e93057ee1bc503db9415c0dc98ba3cf"


def test_this_familys_step_is_pinned_and_reads_a_table_a_class():
    eng = _engine()
    with eng.trainer.mesh:
        text = jax.jit(eng._paged_step_program()).lower(
            eng.kv.pools, eng._exec_variables, eng._lanes_spec()).as_text()
    ops = {}
    for op in re.findall(r"= \"?([a-z_]+\.[a-z_.]+)\"?[ (]", text):
        ops[op] = ops.get(op, 0) + 1
    digest = hashlib.sha1(repr(sorted(ops.items())).encode()).hexdigest()
    assert sum(ops.values()) > 100
    assert digest == _STEP_OPS, (digest, sorted(ops.items()))


# ------------- (h) the cell's programs, for a described v5e


@pytest.fixture(scope="module")
def one_chip():
    from jax.sharding import SingleDeviceSharding

    from chipbench import offchip

    try:
        topo = offchip.describe()
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cell_engine(one_chip):
    from unittest import mock

    from elasticdl_tpu.ops import dispatch
    from scripts import check_pool_donation as check

    with open(os.path.join(ROOT, "chipbench", "configs",
                           "trinity-large-serve.json")) as f:
        cfg = json.load(f)
    with mock.patch.object(dispatch, "is_tpu_backend", lambda: True):
        eng, _handed_in = check.build_engine(cfg)
    return eng, check


def test_the_cells_pool_is_the_issues_arithmetic(cell_engine):
    eng, _check = cell_engine
    kv = eng.kv
    assert kv.class_windows == [0, 4096] and kv.class_layers == [1, 4]
    assert kv.max_blocks_per_slot == 2113
    assert [a.num_blocks for a in kv.allocators] == [33808, 16 * 258]
    # 4,096 B a token a layer: 2.22 GB + 1.08 GB
    assert kv.class_block_bytes == [16 * 4096, 4 * 16 * 4096]
    assert kv.bytes_total == (33808 + 4 * 4128) * 16 * 4096
    assert round(kv.bytes_total / 1e9, 2) == 3.3
    assert eng._lanes_spec().shape == (16, 4 + 2 * 2113)


@pytest.mark.parametrize("name", ["paged_step", "prompt_write",
                                  "prompt_write[whole]"])
def test_the_cells_programs_compile_for_a_v5e_and_alias_the_pool(
        cell_engine, one_chip, name):
    """chipbench/configs/trinity-large-serve.json at its real widths
    and depth, over shapes: the Mosaic compiler takes the 3072 x 3072
    SwiGLU expert tiles and the paged kernel over a table of 2,113
    blocks a class, and every class's arenas are updated in place."""
    from unittest import mock

    from elasticdl_tpu.ops import dispatch

    eng, check = cell_engine
    todo = check.programs(eng, tile=16, upload_blocks=4)
    assert sorted(todo) == ["paged_step", "prompt_write",
                            "prompt_write[whole]"]
    with mock.patch.object(dispatch, "is_tpu_backend", lambda: True):
        compiled, pools = check.compile_program(eng, todo[name], one_chip)
    got = kv_pool.pool_aliasing(compiled, pools)
    assert got["pool_bytes"] - eng.kv.bytes_total in (0, 4)  # + `pos`
    assert 0 <= got["alias_bytes"] - got["pool_bytes"] <= 512, got
    assert got["pool_shaped_copies"] == 0, got
    if name == "paged_step":
        hlo = compiled.as_text()
        assert hlo.count("moe_expert_tiles/pallas_call") >= 4
        assert hlo.count("paged_decode") >= 5
        assert not re.search(
            r"= bf16\[8,3072,3072\]\S* copy\(", hlo)


def test_a_prefill_bucket_of_the_cell_compiles_for_a_v5e(cell_engine,
                                                         one_chip):
    """The second of the cell's four prompt lengths (8,200 -> bucket
    8,256), at the real widths: the flash kernel under a window and
    without one, the expert tiles over 8k rows, within the chip's
    memory beside the weights and the pool."""
    from unittest import mock

    from chipbench import offchip
    from elasticdl_tpu.ops import dispatch

    eng, _check = cell_engine

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    f32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    with mock.patch.object(dispatch, "is_tpu_backend", lambda: True), \
            eng.trainer.mesh:
        compiled = jax.jit(eng._prefill_program(8256)).lower(
            jax.tree.map(spec, eng._exec_variables),
            jax.ShapeDtypeStruct((1, eng.seq_len), jnp.int32,
                                 sharding=one_chip),
            i32, i32, f32).compile()
    # beside the 3.21 GB of weights (an argument here) and the 3.30 GB
    # pool (not one): under the chip's 16 GB with room
    assert offchip.device_bytes(compiled) + eng.kv.bytes_total < 12e9
