"""The `transformer_lm` stack configured as SmallThinker-21BA3B (RMSNorm,
NoPE-global and RoPE-window layers, a drop-free ReGLU expert layer fed
by the block's input, a share of the experts held) against the plain
reference chipbench/refs/smallthinker.py, at small size on the CPU with
seeded random weights, float32 compute:

(a) the program's full forward, logits;
(b) prefill, then decode through the paged pool, logits at every
    decoded position, with a request longer than the (small) window,
    and the same logits against references whose layers all have ONE
    window: they must differ;
(c) the shares add up: two halves of a layer's experts sum to the
    uncut layer and to the reference, for ReGLU experts and for SwiGLU
    ones (chipbench/refs/sdar_moe.py: silu in relu's place);
(d) no token is dropped under a routing skewed onto one expert, and
    the decode path and the prefill path of the layer agree;
and what the serving step hands back for the counters.

Tolerances: both sides are float32 and sum in different orders (block
attention against the program's blockwise scan, experts one by one
against tiles), through 4 layers: 2e-4 on logits of unit scale is 100x
the rounding seen and 100x under the smallest effect tested (one
layer's window: 0.03 and more).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import FrozenDict

from chipbench.drivers.open_loop import _unflatten
from chipbench.refs import sdar_moe as swiglu_ref
from chipbench.refs import smallthinker as ref
from elasticdl_tpu.common.model_utils import load_model_spec_from_module
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.ops import expert_ffn
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.parallel import moe
from elasticdl_tpu.serving.admission import ServingRequest
from elasticdl_tpu.serving.engine import (
    PagedContinuousBatchingEngine,
    _tick_counts,
)
from elasticdl_tpu.training import trainer as trainer_mod
from model_zoo.transformer_lm import transformer_lm as zoo

TOL = 2e-4
PARAMS = {
    "vocab_size": 96, "seq_len": 64, "embed_dim": 48, "num_heads": 3,
    "num_kv_heads": 1, "head_dim": 32, "num_layers": 4, "pos_emb": "rope",
    "rope_theta": 1500000, "rope_layout": [0, 1, 1, 1], "attn_window": 8,
    "window_layout": [0, 1, 1, 1], "norm": "rms", "norm_eps": 1e-6,
    "mlp": "moe_reglu", "moe_experts": 8, "moe_top_k": 3, "moe_hidden": 24,
    "experts_held": [0, 4],
}
WEIGHTS = {"qk_gain": 2.0, "router_gain": 1.0}


def _cfg(**over):
    return dict(PARAMS, **WEIGHTS, **over)


def _engine(params, leaves, slots=2, **kwargs):
    """The paged engine over `leaves` (the reference's, by path),
    `slots` lanes, blocks of four; prefix sharing on, as the engine's
    default has it: one table for every layer."""
    trainer = trainer_mod.Trainer(
        load_model_spec_from_module(zoo),
        mesh=mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1]),
        model_params="; ".join(
            "%s=%r" % kv for kv in sorted(params.items())))
    state = trainer_mod.TrainState(
        step=jnp.zeros((), jnp.int32), params=_unflatten(leaves),
        opt_state=(), model_state=FrozenDict({}),
        rng=jax.random.PRNGKey(0))
    return PagedContinuousBatchingEngine(trainer, state, slots,
                                         block_size=4, **kwargs)


def _model(cfg):
    return zoo.custom_model(**{k: v for k, v in cfg.items()
                               if k not in WEIGHTS})


def _tokens(seed, n, vocab=96):
    return np.random.default_rng(seed).integers(0, vocab, (1, n))


# ------------------------------------------------ (a) the full forward


@pytest.mark.parametrize("seed,held", [(0, [0, 4]), (1, [4, 4]),
                                       (2, [0, 8]), (3, [2, 3])])
def test_full_forward_matches_the_reference(seed, held):
    cfg = _cfg(experts_held=held)
    w = ref.make_leaves(cfg, seed, ref.all_leaves(cfg))
    tokens = _tokens(seed, 40)
    got = _model(cfg).apply({"params": _unflatten(w)},
                            {"tokens": jnp.asarray(tokens)})
    want = ref.forward(cfg, w, jnp.asarray(tokens), rows=8)
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_the_sc2_stack_keeps_its_parameter_names_and_its_one_window():
    model = zoo.custom_model(vocab_size=32, seq_len=16, embed_dim=16,
                             num_heads=2, num_layers=2, pos_emb="rope",
                             attn_window=4)
    tree = model.init(jax.random.PRNGKey(0),
                      {"tokens": jnp.zeros((1, 8), jnp.int32)})["params"]
    assert set(tree["block_1"]) == {"LayerNorm_0", "LayerNorm_1", "attn",
                                    "mlp_up", "mlp_down"}
    assert set(tree["ln_f"]) == {"scale", "bias"}
    assert model.layer_windows() == (4, 4)
    assert _model(_cfg()).layer_windows() == (0, 8, 8, 8)
    with pytest.raises(ValueError, match="3 entries for 4 layers"):
        _model(_cfg(window_layout=[0, 1, 1])).layer_windows()


# ------------------------------- (b) prefill, then the paged pool


@functools.lru_cache(maxsize=None)
def _served(seed=0, prompt_len=20, new=12):
    """A request longer than the window through the engine: the logits
    of the model's own paged decode call at every decoded position
    (read from the engine's pool before each step), the tokens the
    engine streamed, and the counters its steps handed back."""
    cfg = _cfg()
    w = ref.make_leaves(cfg, seed, ref.all_leaves(cfg))
    eng = _engine(PARAMS, w)
    prompt = [int(t) for t in _tokens(seed + 7, prompt_len)[0]]
    request = ServingRequest(prompt, new)
    before = dict(tracing.recorder().counts())
    slot, _, _ = eng.insert(request)
    logits = []
    while eng.active_count():
        pos, tok = int(eng._positions[slot]), request.generated[-1]
        out, _ = eng.model.apply(
            dict(eng._exec_variables, cache={"pos": jnp.asarray(pos)}),
            {"tokens": jnp.asarray([[tok]])}, training=False, decode=True,
            mutable=["cache", "kv_out"],
            paged={"pools": eng.kv.pools,
                   "table": jnp.asarray(eng.kv.tables[slot])[None]})
        logits.append(np.asarray(out[0, 0]))
        # in line (launched and committed, nothing ahead of it): the
        # book between two steps is that of the tokens committed
        assert eng._launch() and eng._collect()
    after = tracing.recorder().counts()
    counts = {k: after[k] - before.get(k, 0) for k in after}
    return cfg, w, prompt, list(request.generated), logits, counts


def _reference_logits(cfg, w, prompt, generated):
    seq = prompt + generated
    pad = -len(seq) % 8
    out = ref.forward(cfg, w, jnp.asarray([seq + [0] * pad]), rows=8)[0]
    return np.asarray(out[len(prompt) - 1:len(seq) - 1])


def test_prefill_then_paged_decode_matches_the_reference_past_the_window():
    cfg, w, prompt, generated, logits, _ = _served()
    assert len(prompt) + len(generated) > cfg["attn_window"] * 3
    want = _reference_logits(cfg, w, prompt, generated)
    # want[0] is the prefill's own logit row (the first token); the
    # paged steps produced tokens 1..n-1 from positions p..p+n-2
    assert generated[0] == int(want[0].argmax())
    got = np.stack(logits)
    assert got.shape == want[1:].shape
    assert np.abs(got - want[1:]).max() < TOL
    assert generated[1:] == [int(r.argmax()) for r in got]


@pytest.mark.parametrize("layout", [[1, 1, 1, 1], [0, 0, 0, 0]],
                         ids=["every-layer-windowed", "no-layer-windowed"])
def test_one_window_for_every_layer_is_another_model(layout):
    """Were every layer given the same window (the stack before this
    configuration had one `attn_window`), the served logits would be
    those of a reference with that layout: they are not."""
    cfg, w, prompt, generated, logits, _ = _served()
    other = _reference_logits(dict(cfg, window_layout=layout), w, prompt,
                              generated)
    assert np.abs(np.stack(logits) - other[1:]).max() > 100 * TOL


def test_a_rotary_global_layer_is_another_model_too():
    cfg, w, prompt, generated, logits, _ = _served()
    other = _reference_logits(dict(cfg, rope_layout=[1, 1, 1, 1]), w,
                              prompt, generated)
    assert np.abs(np.stack(logits) - other[1:]).max() > 100 * TOL


def test_the_step_hands_back_what_the_expert_layers_did():
    cfg, _, prompt, generated, _, counts = _served()
    ticks = len(generated) - 1
    layers, k = cfg["num_layers"], cfg["moe_top_k"]
    # both lanes ride every tick; the free one makes no choice
    assert counts["moe.pairs_routed"] == ticks * 1 * k * layers
    assert counts["moe.lanes"] == ticks * 2 * layers
    assert counts["moe.lanes_live"] == ticks * 1 * layers
    assert 0 < counts["moe.pairs_held"] < counts["moe.pairs_routed"]
    assert counts["moe.expert_slots"] == ticks * 4 * layers
    # one lane's three choices hit three experts a layer at the most
    assert 0 < counts["moe.experts_hit"] <= ticks * k * layers
    # two lanes take the 16-row path: a tile of 16 rows a hit expert,
    # counted once a tick and not once a lane
    assert counts["moe.tile_rows"] == moe.DECODE_ROWS * counts[
        "moe.experts_hit"]
    # 3 of 4 layers have a window of 8 = 2 blocks of 4: a lane at 20..31
    # holds 5..8 blocks a layer, the oldest of them dead in those three
    assert counts["kv.blocks_held"] > 0
    dead = counts["kv.window_dead_blocks"] / counts["kv.blocks_held"]
    assert 0.3 < dead < 0.75
    # one table for every layer (the engine shares prefixes): what it
    # holds is what it would hold, and it gives nothing back
    assert counts["kv.blocks_whole"] == counts["kv.blocks_held"]
    assert counts.get("kv.window_blocks_released", 0) == 0


def test_without_sharing_the_window_layers_hold_their_window_only():
    """The same request through an engine that shares no prefix: the
    three window layers' blocks are a class of their own
    (serving/kv_pool.py, BLOCK CLASSES), which holds at most one dead
    block a lane a layer between two releases, and the tokens are the
    same."""
    _, w, prompt, generated, _, shared = _served()
    eng = _engine(PARAMS, w, share_prefix=False)
    assert eng.kv.class_windows == [0, 8] and eng.kv.class_layers == [1, 3]
    request = ServingRequest(prompt, len(generated))
    before = dict(tracing.recorder().counts())
    eng.insert(request)
    while eng.active_count():
        assert eng._launch() and eng._collect()
    after = tracing.recorder().counts()
    counts = {k: after[k] - before.get(k, 0) for k in after}
    assert request.generated == generated
    ticks = len(generated) - 1
    assert counts["kv.blocks_whole"] == shared["kv.blocks_held"]
    assert counts["kv.blocks_held"] < 0.75 * counts["kv.blocks_whole"]
    assert counts["kv.window_dead_blocks"] <= 3 * ticks  # one a layer
    assert (counts["kv.window_dead_blocks"]
            < 0.2 * counts["kv.blocks_held"])
    assert counts["kv.window_blocks_released"] > 0
    assert counts["prompt_write.blocks_skipped"] == 3 * 3  # blocks 0-2
    assert counts["paged.blocks_streamed"] == shared[
        "paged.blocks_streamed"]
    assert counts["paged.blocks_streamed"] > 0


def test_tick_counts_sums_scalars_and_counts_a_marked_item_once():
    lanes = {"block_0": {"moe": {
        "n": (jnp.asarray([3, 4, 5]),),
        "hit": (jnp.asarray([[1, 0, 0, 1], [1, 0, 1, 0], [0, 0, 0, 0]]),),
    }}, "block_1": {"moe": {"n": (jnp.asarray([1, 1, 1]),),
                            "hit": (jnp.asarray([[0, 1, 0, 0]] * 3),)}}}
    out = _tick_counts(lanes)
    assert {k: int(v) for k, v in out.items()} == {"n": 15, "hit": 4}
    assert _tick_counts({}) == {}


# --------------------------------------- (c), (d) the expert layer


def _layer(seed, t, experts=8, d=32, hidden=16, k=3, skew=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    w = {
        "moe/router": jax.random.normal(ks[0], (d, experts)) * d ** -0.5,
        "moe/w_gate": jax.random.normal(ks[1], (experts, d, hidden))
        * d ** -0.5,
        "moe/w_up": jax.random.normal(ks[2], (experts, d, hidden))
        * d ** -0.5,
        "moe/w_down": jax.random.normal(ks[3], (experts, hidden, d))
        * hidden ** -0.5,
    }
    if skew is not None:  # every token's first choice is expert `skew`
        w["moe/router"] = w["moe/router"].at[:, skew].set(0.0)
    h = jax.random.normal(ks[4], (t, d))
    x = jax.random.normal(ks[5], (t, d))
    logits = ref.matmul(x, w["moe/router"])
    if skew is not None:
        logits = logits.at[:, skew].add(50.0)
    return w, h, x, logits


def _share(w, h, logits, first, count, k=3, form="reglu", **kwargs):
    gates, experts = moe.route_top_k(logits, k)
    return moe.held_experts(
        h, gates, experts, [w[n][first:first + count] for n in
                            ("moe/w_gate", "moe/w_up", "moe/w_down")],
        first=first, activation=form, **kwargs)


def _reference_layer(w, h, logits, first, count, k=3, form="reglu"):
    cfg = {"moe_experts": 8, "moe_top_k": k, "experts_held": [first, count]}
    top_v, top_i = jax.lax.top_k(logits, k)
    weights = jnp.sum(jnp.where(
        top_i[..., None] == jnp.arange(8),
        jax.nn.softmax(top_v, -1)[..., None], 0.0), axis=-2)
    held = {n: v[first:first + count] for n, v in w.items()
            if n != "moe/router"}
    family = {"reglu": ref, "swiglu": swiglu_ref}[form]
    return family.experts(cfg, held, h, weights)


@pytest.mark.parametrize("form", ["reglu", "swiglu"])
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("t", [1, 16, 17, 300],
                         ids=["one-row", "decode-rows", "first-prefill",
                              "two-tiles"])
def test_the_shares_add_up_to_the_whole_layer_and_to_the_reference(
        seed, t, form):
    w, h, _, logits = _layer(seed, t)
    whole, held_all, hit_all, _ = _share(w, h, logits, 0, 8, form=form)
    low, held_low, hit_low, _ = _share(w, h, logits, 0, 4, form=form)
    high, held_high, hit_high, _ = _share(w, h, logits, 4, 4, form=form)
    # float32 rounding: the halves sum the same products in two parts
    assert float(jnp.max(jnp.abs(low + high - whole))) < 2e-6
    assert float(jnp.max(jnp.abs(
        whole - _reference_layer(w, h, logits, 0, 8, form=form)))) < 2e-6
    assert float(jnp.max(jnp.abs(
        high - _reference_layer(w, h, logits, 4, 4, form=form)))) < 2e-6
    # the two gated forms are two layers
    other = {"reglu": "swiglu", "swiglu": "reglu"}[form]
    assert float(jnp.max(jnp.abs(
        whole - _reference_layer(w, h, logits, 0, 8, form=other)))) > 1e-3
    assert (held_low + held_high == held_all).all()
    assert (held_all == 3).all()
    assert hit_low.tolist() + hit_high.tolist() == hit_all.tolist()


@pytest.mark.parametrize("t", [8, 16, 40, 600])
def test_no_token_is_dropped_when_every_token_chooses_one_expert(t):
    """A capacity of 1.25 would drop most of these rows' first choice;
    here every row reaches expert 2 with its full weight."""
    w, h, _, logits = _layer(5, t, skew=2)
    got, held, hit, _ = _share(w, h, logits, 0, 8)
    assert hit[2] == 1 and (held == 3).all()
    want = _reference_layer(w, h, logits, 0, 8)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    gates, experts = moe.route_top_k(logits, 3)
    assert (experts[:, 0] == 2).all() and float(gates[:, 0].min()) > 0.99
    # and the expert alone gives nearly all of every row
    alone = _share(w, h, logits, 2, 1)[0]
    assert float(jnp.max(jnp.abs(alone - want))) < 0.05 * float(
        jnp.max(jnp.abs(want)))
    assert float(jnp.min(jnp.max(jnp.abs(alone), axis=1))) > 0


@pytest.mark.parametrize("first,count", [(0, 8), (0, 4), (4, 4), (3, 2)])
def test_the_decode_path_and_the_prefill_path_agree(first, count):
    w, h, _, logits = _layer(9, 12)
    gates, experts = moe.route_top_k(logits, 3)
    weights = [w[n][first:first + count]
               for n in ("moe/w_gate", "moe/w_up", "moe/w_down")]
    assert h.shape[0] <= moe.DECODE_ROWS
    hit_path = moe._hit_tiles(h, gates, experts, first, *weights, False)
    grouped = moe._grouped_tiles(h, gates, experts, first, *weights, False)
    assert float(jnp.max(jnp.abs(hit_path[0] - grouped[0]))) < 2e-6
    assert (hit_path[1] == grouped[1]).all()
    assert (hit_path[2] == grouped[2]).all()


#: (rows, choices a row, experts held, experts of the layer, form) ->
#: the sorted tile's height by the rule: the decode step of the
#: benchmark's expert cells above DECODE_ROWS (nm3n's 32 lanes, sdar's
#: 32 lanes x 4 rows, a fused pass of twice that), a short prefill
#: bucket, and a prefill of 2,048 rows
_TILE_SHAPES = {
    "nm3n-tick": ((32, 6, 32, 128, "relu2"), 16),
    "sdar-pass": ((128, 8, 32, 128, "swiglu"), 32),
    "fused-pass": ((256, 8, 32, 128, "swiglu"), 64),
    "short-prefill": ((512, 6, 32, 64, "reglu"), 128),
    "prefill-2048": ((2048, 6, 32, 64, "reglu"), 256),
}


@pytest.mark.parametrize("t,k,count,want", [
    (32, 6, 32, 16), (128, 8, 32, 32), (256, 8, 32, 64),
    (17, 6, 32, 16), (64, 6, 32, 16), (128, 6, 32, 32),
    (512, 8, 32, 128), (1024, 6, 32, 256), (1024, 8, 32, 256),
    (2048, 6, 32, 256), (2048, 8, 32, 256), (6208, 6, 32, 256),
    (40, 3, 8, 16), (40, 3, 1, 128), (4096, 8, 128, 256)])
def test_a_sorted_tile_is_as_tall_as_an_experts_run(t, k, count, want):
    """The height comes from the static shapes alone: the lowest step
    of the ladder not under t * k / count, never over the prefill's."""
    assert moe.sorted_tile_rows(t, k, count) == want
    assert want in moe._TILE_LADDER and want <= moe.PREFILL_TILE_ROWS
    assert want >= min(t * k / count, moe.PREFILL_TILE_ROWS)
    lower = [tm for tm in moe._TILE_LADDER if tm < want]
    assert all(tm < t * k / count for tm in lower)
    # more rows never take a shorter tile
    assert moe.sorted_tile_rows(2 * t, k, count) >= want


def _pairs_one_by_one(h, gates, experts, first, weights, form):
    """Every held (row, choice) pair's product on its own: each expert
    over every row densely, a row's k results added in order."""
    count = weights[0].shape[0]
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    if form == "relu2":
        dense = [dot(jnp.square(jnp.maximum(dot(h, weights[0][e].T), 0.0)),
                     weights[1][e]) for e in range(count)]
    else:
        act = {"reglu": lambda a: jnp.maximum(a, 0.0),
               "swiglu": jax.nn.silu}[form]
        dense = [dot(act(dot(h, weights[0][e])) * dot(h, weights[1][e]),
                     weights[2][e]) for e in range(count)]
    dense = jnp.stack(dense)  # [count, t, d]
    local = np.asarray(experts) - first
    ok = (local >= 0) & (local < count)
    rows = np.arange(h.shape[0])
    y = jnp.zeros_like(h)
    for j in range(experts.shape[1]):
        picked = dense[np.clip(local[:, j], 0, count - 1), rows]
        y = y + jnp.where(ok[:, j, None], gates[:, j, None] * picked, 0.0)
    return y, ok


@pytest.mark.parametrize("skew", [
    "spread", "long-run", "exact-run", "dead-lanes", "held-elsewhere"])
@pytest.mark.parametrize("shape", sorted(_TILE_SHAPES))
def test_sorted_tiles_of_every_height_give_each_pair_its_product(
        shape, skew, monkeypatch):
    """`_grouped_tiles` at every height of the ladder against the pairs
    one by one: choices spread over the layer's experts with one held
    expert nobody chose; a run longer than the tile (every row's first
    choice is one expert: further tiles, no pair dropped); a run of
    exactly the tile; rows of free lanes (-1: no choice); and every
    choice held elsewhere (no tile at all). The rows the kernel is
    handed are the tile list's."""
    (t, k, count, total, form), tm = _TILE_SHAPES[shape]
    assert moe.sorted_tile_rows(t, k, count) == tm
    first, d, hidden = 3, 16, 8
    rng = np.random.default_rng(sum(map(ord, shape + skew)))
    quiet, busy = first + 5, first + 1  # held experts: nobody's / skewed
    # each row's k choices: distinct experts, never the two reserved
    free = np.setdiff1d(np.arange(total), [quiet, busy])
    experts = np.stack([rng.choice(free, size=k, replace=False)
                        for _ in range(t)])
    if skew == "long-run":
        experts[:, 0] = busy  # a run of t rows: more than one tile
    elif skew == "exact-run":
        experts[:tm, 0] = busy  # t >= tm for every shape: one full tile
    elif skew == "dead-lanes":
        experts[rng.random(t) < 0.4] = -1
        experts[0] = -1
    elif skew == "held-elsewhere":
        experts = np.where(experts < first + count, experts + count,
                           experts) % total
        experts = np.where((experts >= first) & (experts < first + count),
                           first + count, experts)
    gates = jax.nn.softmax(jnp.asarray(rng.normal(size=(t, k)),
                                       jnp.float32), axis=-1)
    into, back = (count, d, hidden), (count, hidden, d)
    shapes = [back, back] if form == "relu2" else [into, into, back]
    weights = [jnp.asarray(rng.normal(size=sh) * sh[1] ** -0.5, jnp.float32)
               for sh in shapes]
    h = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    experts = jnp.asarray(experts, jnp.int32)
    handed = []
    real = moe.expert_tiles

    def spy(x_tiles, x_of, tile_gates, expert_of, n, *w, **kw):
        handed.append((x_tiles.shape, int(n), np.asarray(expert_of)))
        return real(x_tiles, x_of, tile_gates, expert_of, n, *w, **kw)

    with monkeypatch.context() as patch:
        patch.setattr(moe, "expert_tiles", spy)
        y, held, hit, tile_rows = moe._grouped_tiles(
            h, gates, experts, first, *weights, False, activation=form)
    want, ok = _pairs_one_by_one(h, gates, experts, first, weights, form)
    assert float(jnp.max(jnp.abs(y - want))) < 2e-5
    assert (np.asarray(held) == ok).all()
    sizes = np.bincount(np.asarray(experts)[ok] - first, minlength=count)
    assert np.asarray(hit).astype(bool).tolist() == (sizes > 0).tolist()
    assert not hit[quiet - first]
    # the tile list: the static bound, and whole tiles an expert's run
    (x_shape, n_live, expert_of), = handed
    assert x_shape == (-(-t * k // tm) + count, tm, d)
    tiles_of = -(-sizes // tm)
    assert n_live == tiles_of.sum() <= x_shape[0]
    assert expert_of[:n_live].tolist() == np.repeat(
        np.arange(count), tiles_of).tolist()
    assert int(tile_rows) == n_live * tm >= ok.sum()
    if skew == "long-run":
        assert sizes[busy - first] == t > tm
        assert tiles_of[busy - first] == -(-t // tm) > 1
    elif skew == "exact-run":
        assert sizes[busy - first] == tm and tiles_of[busy - first] == 1
    elif skew == "dead-lanes":
        dead = (np.asarray(experts) < 0).all(axis=1)
        assert dead.any() and not np.asarray(y)[dead].any()
    elif skew == "held-elsewhere":
        assert n_live == 0 == int(tile_rows) and not np.asarray(y).any()
    # the layer itself takes this path and height for these shapes
    if skew == "spread":
        whole = moe.held_experts(h, gates, experts, weights, first=first,
                                 use_kernel=False, activation=form)
        np.testing.assert_array_equal(np.asarray(whole[0]), np.asarray(y))
        assert int(whole[3]) == int(tile_rows)


def _dead_rows_case(seed, t, dead, form, d=32, hidden=16):
    """A layer of 8 experts (the `form`'s banks), `t` rows routed top-3
    so that expert 7 is the first choice of every row in `dead` and of
    no other: (h, gates, experts, weights, live)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    into, back = (8, d, hidden), (8, hidden, d)
    shapes = [into, into, back] if form == "reglu" else [back, back]
    weights = [jax.random.normal(k, shape) * shape[1] ** -0.5
               for k, shape in zip(ks, shapes)]
    h = jax.random.normal(ks[3], (t, d))
    live = np.ones(t, bool)
    live[list(dead)] = False
    logits = jax.random.normal(ks[4], (t, 8)).at[:, 7].set(
        jnp.where(jnp.asarray(live), -50.0, 50.0))
    gates, experts = moe.route_top_k(logits, 3)
    return h, gates, experts, weights, live


def _some(seed, t):
    rng = np.random.default_rng(seed)
    return sorted(rng.choice(t, size=rng.integers(1, t), replace=False))


@pytest.mark.parametrize("form", ["reglu", "relu2"])
@pytest.mark.parametrize("first,count", [(0, 8), (4, 4)])
@pytest.mark.parametrize("t,dead", [
    (4, [2]), (4, [0, 1, 3]), (4, range(4)), (16, _some(1, 16)),
    (40, _some(2, 40)), (40, _some(3, 40)), (40, range(40)),
    (300, _some(4, 300))], ids=[
    "hit-one-dead", "hit-one-live", "hit-none-live", "hit-16-rows",
    "grouped-a", "grouped-b", "grouped-none-live", "grouped-two-tiles"])
def test_a_row_that_makes_no_choice_reads_no_expert(
        t, dead, first, count, form, monkeypatch):
    """A choice below 0 is no choice, on both paths: the rows that
    chose keep their numbers bit for bit, the others get exactly 0,
    an expert only they chose is not hit, and the kernel is handed
    fewer live tiles: none at all when no row chose (a launch whose
    lanes were all freed at the one before)."""
    h, gates, experts, weights, live = _dead_rows_case(t, t, dead, form)
    weights = [w[first:first + count] for w in weights]
    masked = jnp.where(jnp.asarray(live)[:, None], experts, -1)
    path = moe._hit_tiles if t <= moe.DECODE_ROWS else moe._grouped_tiles
    n_live = []
    real = moe.expert_tiles

    def spy(x_tiles, x_of, tile_gates, expert_of, n, *w, **kw):
        n_live.append(int(n))
        return real(x_tiles, x_of, tile_gates, expert_of, n, *w, **kw)

    with monkeypatch.context() as patch:
        patch.setattr(moe, "expert_tiles", spy)
        y_all, held_all, hit_all, _ = path(h, gates, experts, first,
                                           *weights, False)
        y, held, hit, tile_rows = path(h, gates, masked, first, *weights,
                                       False)
    y, y_all = np.asarray(y), np.asarray(y_all)
    np.testing.assert_array_equal(y[live], y_all[live])
    assert (y[~live] == 0.0).all() and np.isfinite(y).all()
    held = np.asarray(held).sum(-1)
    assert (held[~live] == 0).all()
    assert (held[live] == np.asarray(held_all).sum(-1)[live]).all()
    chosen = np.zeros(8, bool)
    chosen[np.asarray(experts)[live].reshape(-1)] = True
    assert np.asarray(hit).astype(bool).tolist() == chosen[
        first:first + count].tolist()
    # expert 7 is every dead row's first choice and no live row's
    if first + count == 8:
        assert hit_all[-1] and not hit[-1]
    sizes = np.bincount(np.asarray(experts)[live].reshape(-1),
                        minlength=8)[first:first + count]
    tm = (moe.DECODE_ROWS if t <= moe.DECODE_ROWS
          else moe.sorted_tile_rows(t, 3, count))
    want_live = (int((sizes > 0).sum()) if t <= moe.DECODE_ROWS
                 else int((-(-sizes // tm)).sum()))
    assert n_live[1] == want_live <= n_live[0] - (first + count == 8)
    # the rows that were multiplied are the tile list's, padding and all
    assert int(tile_rows) == want_live * tm >= int(held.sum())
    if not live.any():
        assert n_live[1] == 0 and not y.any() and not np.asarray(hit).any()
    # the layer itself (its batching rule takes no new operand), lane
    # by lane as the serving step maps it: the same rows, the tick's
    # marks
    if t <= moe.DECODE_ROWS:
        by_lane = jax.vmap(lambda hh, gg, ee: moe.held_experts(
            hh, gg, ee, weights, first=first, use_kernel=False))(
            h[:, None], gates[:, None], masked[:, None])
        np.testing.assert_array_equal(np.asarray(by_lane[0][:, 0]), y)
        assert (np.asarray(by_lane[1][:, 0]) == held).all()
        assert (np.asarray(by_lane[2]) == np.asarray(hit)[None]).all()


@pytest.mark.parametrize("t", [4, 40])
def test_the_kernel_hands_a_dead_row_zeros_when_interpreted(t, monkeypatch):
    monkeypatch.setenv("ELASTICDL_TPU_FORCE_INTERPRET", "1")
    for dead in ([1], range(t)):
        h, gates, experts, weights, live = _dead_rows_case(
            t, t, dead, "reglu", d=128, hidden=256)
        masked = jnp.where(jnp.asarray(live)[:, None], experts, -1)
        kernel = moe.held_experts(h, gates, masked, weights,
                                  use_kernel=True)
        plain = moe.held_experts(h, gates, masked, weights,
                                 use_kernel=False)
        whole = moe.held_experts(h, gates, experts, weights,
                                 use_kernel=True)
        assert float(jnp.max(jnp.abs(kernel[0] - plain[0]))) < 1e-5
        np.testing.assert_array_equal(np.asarray(kernel[0])[live],
                                      np.asarray(whole[0])[live])
        assert not np.asarray(kernel[0])[~live].any()
        assert (kernel[1] == plain[1]).all()
        assert (kernel[2] == plain[2]).all() and not kernel[2][7]


def _layer_as_it_was(params, h, route_from, form):
    """ExpertFFN(8, 3, 24, held=(2, 4)) written out as it was before it
    could be told which rows are live: the router at full precision,
    the top 3, the held experts' tiles, the shared expert."""
    b, l, d = h.shape
    weights = [params[n] for n in (
        ("w_gate", "w_up", "w_down") if form == "reglu"
        else ("w_up", "w_down"))]
    logits = jnp.matmul(route_from.reshape(b * l, d), params["router"],
                        precision=jax.lax.Precision.HIGHEST)
    if form == "reglu":
        gates, experts = moe.route_top_k(logits, 3)
    else:
        gates, experts = moe.route_sigmoid_top_k(
            logits, 3, params["router_bias"], 2.5)
    rows = h.reshape(b * l, d)
    y = moe.held_experts(rows, gates, experts, weights, first=2)[0]
    if form == "relu2":
        act = jnp.square(jnp.maximum(jnp.dot(
            rows, params["shared_up"],
            preferred_element_type=jnp.float32), 0.0))
        y = y + jnp.dot(act, params["shared_down"],
                        preferred_element_type=jnp.float32)
    return y.reshape(b, l, d)


@pytest.mark.parametrize("form", ["reglu", "relu2"])
def test_told_nothing_the_layer_is_the_operations_it_always_was(form):
    """`live=None` adds no operation: prefill, the tiles and the
    training forward lower to what they did. Told which rows are live,
    the layer is those operations and the few that write -1."""
    layer = zoo.ExpertFFN(8, 3, 24, held=(2, 4), **(
        {} if form == "reglu" else dict(
            activation="relu2", scoring="sigmoid", route_scale=2.5,
            shared_hidden=40)))
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 20, 32))
    params = jax.tree.map(
        lambda x: getattr(x, "value", x),
        layer.init(jax.random.PRNGKey(1), h, h)["params"],
        is_leaf=lambda x: hasattr(x, "value"))

    def program(**kwargs):
        return str(jax.make_jaxpr(lambda p, hh, rr: layer.apply(
            {"params": p}, hh, rr, **kwargs))(params, h, 0.5 * h))

    was = str(jax.make_jaxpr(
        lambda p, hh, rr: _layer_as_it_was(p, hh, rr, form))(
            params, h, 0.5 * h))
    assert program() == program(live=None) == was
    assert program(live=jnp.ones((2,), bool)) != was
    # every row live: the same numbers, whoever says so
    every = [layer.apply({"params": params}, h, 0.5 * h, live=live)
             for live in (None, jnp.ones((2,), bool),
                          jnp.ones((40,), bool))]
    np.testing.assert_array_equal(every[0], every[1])
    np.testing.assert_array_equal(every[0], every[2])
    # a dead sequence's routed part is exactly 0 (relu2: the shared
    # expert alone is left), a live one's bit for bit what it was
    half = layer.apply({"params": params}, h, 0.5 * h,
                       live=jnp.asarray([True, False]))
    np.testing.assert_array_equal(half[0], every[0][0])
    if form == "reglu":
        assert not np.asarray(half[1]).any()
    else:
        assert np.asarray(half[1]).any()
        assert not np.array_equal(half[1], every[0][1])


def test_only_the_tick_says_which_rows_are_live(monkeypatch):
    """Of the engine's programs the decode step alone hands `live`
    down; prefill and the suffix tile leave it out, and are traced to
    the programs they were."""
    cfg = _cfg()
    eng = _engine(PARAMS, ref.make_leaves(cfg, 0, ref.all_leaves(cfg)))
    told = []
    real = zoo.ExpertFFN.__call__

    def spy(self, h, route_from, training=False, live=None):
        told.append(None if live is None else live.shape)
        return real(self, h, route_from, training, live)

    monkeypatch.setattr(zoo.ExpertFFN, "__call__", spy)
    by_program = []
    for program, args, _ in eng._weight_programs(
            eng._exec_variables, None):
        del told[:]
        jax.eval_shape(program, *args)
        by_program.append(list(told))
    layers = cfg["num_layers"]
    assert by_program == [[None] * layers, [None] * layers,
                          [(1,)] * layers]


def test_whatever_token_a_free_lane_holds_it_hits_no_expert():
    """The free lane is given another token every tick: the seated
    lane streams the tokens it streams alone, and the experts hit and
    the pairs held are those of a server with no free lane at all."""
    cfg, _, prompt, generated, _, counts = _served()
    w = ref.make_leaves(cfg, 0, ref.all_leaves(cfg))

    def run(eng, poison):
        request = ServingRequest(prompt, len(generated))
        before = dict(tracing.recorder().counts())
        slot, _, _ = eng.insert(request)
        tick = 0
        while eng.active_count():
            if poison:
                eng._last_tokens[1 - slot] = (7 * tick + 3) % 96
                eng._lanes_dirty = True
            assert eng._launch() and eng._collect()
            tick += 1
        after = tracing.recorder().counts()
        return list(request.generated), {
            k: after[k] - before.get(k, 0) for k in after
            if k.startswith("moe.")}

    poisoned, counted = run(_engine(PARAMS, w), True)
    alone, counted_alone = run(_engine(PARAMS, w, slots=1), False)
    assert poisoned == alone == generated
    for name in ("moe.pairs_routed", "moe.pairs_held", "moe.experts_hit",
                 "moe.lanes_live", "moe.tile_rows"):
        assert counted[name] == counted_alone[name] == counts[name], name
    assert counted["moe.lanes"] == 2 * counted_alone["moe.lanes"]
    assert counted_alone["moe.lanes"] == counted_alone["moe.lanes_live"]


def test_lanes_mapped_one_by_one_are_computed_as_one_call():
    """The serving step maps a lane a sequence: the layer lays them side
    by side (one read of a hit expert a tick) and marks the tick's hit
    experts; a lane alone gives the same rows."""
    w, h, _, logits = _layer(4, 6)

    def lane(hh, ll):
        return _share(w, hh, ll, 0, 4)

    y, held, hit, tile_rows = jax.vmap(lane)(h[:, None], logits[:, None])
    together, held_t, hit_t, tile_rows_t = _share(w, h, logits, 0, 4)
    assert float(jnp.max(jnp.abs(y[:, 0] - together))) < 2e-6
    assert (held[:, 0] == held_t).all()
    assert (hit == hit_t[None]).all()  # the tick's mark, on every lane
    assert (tile_rows == tile_rows_t).all()  # and the tick's tiles
    assert int(tile_rows_t) == moe.DECODE_ROWS * int(hit_t.sum())
    jaxpr = str(jax.make_jaxpr(jax.vmap(lane))(h[:, None], logits[:, None]))
    assert jaxpr.count("custom_vmap_call") == 1  # one call for all lanes


@pytest.mark.parametrize("hidden,expert_bytes,want", [
    (768, 3 * 2048 * 768 * 2, 768), (768, 3 * 2560 * 768 * 2, 768),
    (1856, 2 * 2688 * 1856 * 2, 1856), (14336, 3 * 4096 * 14336 * 2, 512),
    (768, 2 ** 40, 256), (256, 2 ** 40, 128), (192, 2 ** 40, 192)])
def test_the_hidden_width_is_one_slice_where_an_expert_fits_twice(
        hidden, expert_bytes, want):
    """One slice makes a second tile of an expert repeat its block
    index (no second fetch): taken where the pipeline's two buffers of
    an expert's matrices fit half of the kernel's VMEM, as at the three
    serve configurations' widths; a larger expert goes by slices."""
    assert expert_ffn.hidden_slice(hidden, expert_bytes) == want
    assert hidden % want == 0
    assert (want == hidden) == (
        2 * expert_bytes <= expert_ffn._VMEM_LIMIT // 2
        or not any(hidden % th == 0 and hidden > th
                   for th in (512, 256, 128)))


@pytest.mark.parametrize("slices", [1, 2], ids=["one-slice", "two-slices"])
@pytest.mark.parametrize("form", ["reglu", "swiglu"])
def test_the_kernel_agrees_with_the_plain_tiles_when_interpreted(
        monkeypatch, form, slices):
    monkeypatch.setenv("ELASTICDL_TPU_FORCE_INTERPRET", "1")
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    e, d, hidden = 4, 128, 256
    if slices == 2:  # as an expert too large for the VMEM is multiplied
        monkeypatch.setattr(expert_ffn, "_VMEM_LIMIT", 0)
    assert expert_ffn.hidden_slice(hidden, 3 * d * hidden * 4) \
        == hidden // slices
    w = [jax.random.normal(ks[0], (e, d, hidden)) * d ** -0.5,
         jax.random.normal(ks[1], (e, d, hidden)) * d ** -0.5,
         jax.random.normal(ks[2], (e, hidden, d)) * hidden ** -0.5]
    # 5 rows: a tile a hit expert; 40: sorted tiles of 32 rows; 41:
    # every row chooses expert 3, whose run takes a second tile (the
    # weights' block index repeats under one slice and not under two)
    for t in (5, 40, 41):
        h = jax.random.normal(ks[3], (t, d))
        gates, experts = moe.route_top_k(
            jax.random.normal(ks[4], (t, 8)).at[:, 3].add(
                50.0 * (t == 41)), 3)
        kernel = moe.held_experts(h, gates, experts, w, first=2,
                                  use_kernel=True, activation=form)
        plain = moe.held_experts(h, gates, experts, w, first=2,
                                 use_kernel=False, activation=form)
        assert float(jnp.max(jnp.abs(kernel[0] - plain[0]))) < 1e-5
        assert (kernel[2] == plain[2]).all()
        if form == "reglu":  # the name it has always had
            named = moe.held_experts_reglu(h, gates, experts, *w, first=2,
                                           use_kernel=True)
            assert (named[0] == kernel[0]).all()
    with pytest.raises(ValueError, match="activation 'relu2' with 3"):
        moe.held_experts(h, gates, experts, w, use_kernel=False,
                         activation="relu2")


def test_the_router_is_kept_float32_and_the_experts_are_served_as_bf16():
    """serving/exec_weights.py, by the programs' jaxprs: the expert
    banks are consumed through one narrowing cast, the router raw."""
    w = ref.make_leaves(_cfg(), 0, ref.all_leaves(_cfg()))
    before = dict(tracing.recorder().counts())
    eng = _engine(dict(PARAMS, dtype="bf16"), w)
    moe_leaves = eng._exec_variables["params"]["block_2"]["moe"]
    assert moe_leaves["router"].dtype == jnp.float32
    for name in ("w_gate", "w_up", "w_down"):
        assert jax.tree.leaves(moe_leaves[name])[0].dtype == jnp.bfloat16
    after = tracing.recorder().counts()
    # a layer: qkv, proj and three banks cast; two norms and a router kept
    assert after["weights.leaves_cast"] - before["weights.leaves_cast"] \
        == 4 * 5 + 2
    assert after["weights.leaves_kept"] - before["weights.leaves_kept"] \
        == 4 * 3 + 1
