"""The `transformer_lm` stack configured as NVIDIA-Nemotron-3-Nano (one
mixer a layer behind one RMSNorm: Mamba-2, a relu^2 expert layer with a
shared expert and a sigmoid router, attention without positions)
against the plain reference chipbench/refs/nemotron_h.py, at small size
on the CPU with seeded random weights, float32 compute, on LOGITS:

(a) the program's full forward;
(b) prefill of a prompt that does not fill its bucket, then 44 decode
    steps through the paged ENGINE (the state seated in a slot, carried
    by the step in place);
(c) a slot used twice, lanes seated and released beside each other, a
    free lane whose state is not finite, a release that launches
    nothing;
(d) the chunked scan against the reference's token-by-token scan, both
    kernels (interpreted) against their jax.numpy twins, the four
    shares of a layer's experts plus the shared expert once against the
    uncut reference layer;
(e) the pool tells rows from state by the declared kind, the engine
    refuses what would need a state snapshot, and the weights that are
    read as they are stay float32.

Tolerances. Both sides are float32 and sum in different orders (the
chunked scan's decay-weighted products against a recurrence, tiles
against experts one by one), through 5 layers: 2e-4 on logits of unit
scale is 50x the rounding seen (4e-6) and far under what a fault moves:
a bfloat16 state moves them by 2e-3 and more, a dropped `D_skip` term by
0.1 and more (both tried below). Where two runs of the PROGRAM are
compared (a slot reused, churn, a poisoned free lane) the tokens and
the state are equal exactly: the same program on the same numbers.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import FrozenDict

from chipbench.drivers.open_loop import _unflatten
from chipbench.refs import nemotron_h as ref
from elasticdl_tpu.api.generation import ROWS, SCALAR, STATE
from elasticdl_tpu.common.model_utils import load_model_spec_from_module
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.ops import expert_ffn, ssm
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.parallel import moe
from elasticdl_tpu.serving import kv_pool
from elasticdl_tpu.serving.admission import ServingRequest
from elasticdl_tpu.serving.engine import PagedContinuousBatchingEngine
from elasticdl_tpu.training import trainer as trainer_mod
from model_zoo.transformer_lm import mamba2
from model_zoo.transformer_lm import transformer_lm as zoo

TOL = 2e-4
PARAMS = {
    "vocab_size": 96, "seq_len": 128, "embed_dim": 48, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 16, "num_layers": 5, "pos_emb": "rope",
    "rope_layout": [0] * 5, "norm": "rms", "norm_eps": 1e-5,
    "layer_kinds": "MEM*E", "moe_experts": 8, "moe_top_k": 3,
    "moe_hidden": 24, "experts_held": [0, 4], "moe_activation": "relu2",
    "moe_scoring": "sigmoid", "moe_route_scale": 2.5,
    "moe_shared_hidden": 40, "ssm_heads": 4, "ssm_head_dim": 8,
    "ssm_groups": 2, "ssm_state": 16, "ssm_conv": 4, "ssm_chunk": 16,
}
WEIGHTS = {"qk_gain": 2.0, "router_gain": 1.0, "sel_bias_std": 0.1}


def _cfg(**over):
    return dict(PARAMS, **WEIGHTS, **over)


@functools.lru_cache(maxsize=None)
def _leaves(seed=0):
    return ref.make_leaves(_cfg(), seed, ref.all_leaves(_cfg()))


def _engine(leaves=None, slots=3, params=PARAMS, **kwargs):
    """The paged engine over the reference's leaves, blocks of four."""
    trainer = trainer_mod.Trainer(
        load_model_spec_from_module(zoo),
        mesh=mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1]),
        model_params="; ".join(
            "%s=%r" % kv for kv in sorted(params.items())))
    state = trainer_mod.TrainState(
        step=jnp.zeros((), jnp.int32),
        params=_unflatten(_leaves() if leaves is None else leaves),
        opt_state=(), model_state=FrozenDict({}),
        rng=jax.random.PRNGKey(0))
    kwargs.setdefault("share_prefix", False)
    return PagedContinuousBatchingEngine(trainer, state, slots,
                                         block_size=4, **kwargs)


def _model(cfg):
    return zoo.custom_model(**{k: v for k, v in cfg.items()
                               if k not in WEIGHTS})


def _prompt(seed, n, vocab=96):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, vocab, n)]


def _slot_state(eng, slot):
    """{leaf path: numpy} of a slot's per-sequence state."""
    flat = jax.tree_util.tree_flatten_with_path(eng.kv.pools)[0]
    return {jax.tree_util.keystr(path): np.array(leaf[slot])
            for (path, leaf), kind in zip(flat, eng.kv.kinds)
            if kind == STATE}


def _step(eng):
    """One decode step in line: launched and committed with nothing
    ahead of it (engine.step() keeps one in flight), so that between
    two of them the pool, the state arenas and the book are those of
    the tokens committed, which is what _lane_logits reads."""
    assert not eng._flights
    return eng._collect() if eng._launch() else []


def _lane_logits(eng, slot):
    """The logits of the model's own paged decode call for a seated
    lane, read from the engine's pool before its next step (_step)."""
    read = eng.__dict__.get("_test_logits")
    if read is None:
        def logits(variables, pools, slot, pos, token, table):
            cache = {"pos": pos}
            for name, layer in pools.items():
                if "ssm" in getattr(layer, "keys", lambda: ())():
                    cache[name] = {"ssm": {k: v[slot][None]
                                           for k, v in layer["ssm"].items()}}
            out, _ = eng.model.apply(
                dict(variables, cache=cache), {"tokens": token[None, None]},
                training=False, decode=True, mutable=["cache", "kv_out"],
                paged={"pools": pools, "table": table[None]})
            return out[0, 0]

        read = eng.__dict__["_test_logits"] = jax.jit(logits)
    return np.asarray(read(
        eng._exec_variables, eng.kv.pools, slot,
        jnp.asarray(eng._positions[slot]),
        jnp.asarray(eng._slots[slot].request.generated[-1]),
        jnp.asarray(eng.kv.tables[slot])))


def _reference_logits(cfg, w, prompt, generated):
    seq = prompt + generated
    pad = -len(seq) % 8
    out = ref.forward(cfg, w, jnp.asarray([seq + [0] * pad]), rows=8)[0]
    return np.asarray(out[len(prompt) - 1:len(seq) - 1])


# ------------------------------------------------ (a) the full forward


@pytest.mark.parametrize("seed,held", [(0, [0, 4]), (1, [4, 4]),
                                       (2, [0, 8]), (3, [2, 3])])
def test_full_forward_matches_the_reference(seed, held):
    cfg = _cfg(experts_held=held)
    w = ref.make_leaves(cfg, seed, ref.all_leaves(cfg))
    tokens = jnp.asarray([_prompt(seed, 40)])
    got = _model(cfg).apply({"params": _unflatten(w)}, {"tokens": tokens})
    want = ref.forward(cfg, w, tokens, rows=8)
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_a_layer_is_one_mixer_and_the_old_stacks_keep_their_names():
    tree = _model(_cfg()).init(
        jax.random.PRNGKey(0), {"tokens": jnp.zeros((1, 8), jnp.int32)}
    )["params"]
    assert [sorted(tree["block_%d" % i]) for i in range(5)] == [
        ["RMSNorm_0", "ssm"], ["RMSNorm_0", "moe"], ["RMSNorm_0", "ssm"],
        ["RMSNorm_0", "attn"], ["RMSNorm_0", "moe"]]
    assert sorted(tree["block_1"]["moe"]) == [
        "router", "router_bias", "shared_down", "shared_up", "w_down",
        "w_up"]  # two matrices an expert: no w_gate
    assert sorted(tree["block_0"]["ssm"]) == [
        "A_log", "D_skip", "conv_bias", "conv_kernel", "dt_bias",
        "in_proj", "norm_scale", "out_proj"]
    # the engine counts reach by the attention layers alone
    assert _model(_cfg()).layer_windows() == (0,)
    dense = zoo.custom_model(vocab_size=32, seq_len=16, embed_dim=16,
                             num_heads=2, num_layers=2)
    old = dense.init(jax.random.PRNGKey(0),
                     {"tokens": jnp.zeros((1, 8), jnp.int32)})["params"]
    assert set(old["block_1"]) == {"LayerNorm_0", "LayerNorm_1", "attn",
                                   "mlp_up", "mlp_down"}
    assert dense.layer_windows() == (0, 0)
    with pytest.raises(ValueError, match="is not 5 of 'M', 'E'"):
        _model(_cfg(layer_kinds="MEM")).layer_windows()


# --------------------- (b) prefill, then the engine, on logits


@functools.lru_cache(maxsize=None)
def _served(prompt_len=21, new=45):
    """One request through the engine: the logits at every decoded
    position, the tokens streamed, and the counters of its steps."""
    eng = _engine()
    prompt = _prompt(7, prompt_len)
    request = ServingRequest(prompt, new)
    before = dict(tracing.recorder().counts())
    slot, _, _ = eng.insert(request)
    logits = []
    while eng.active_count():
        logits.append(_lane_logits(eng, slot))
        _step(eng)
    after = tracing.recorder().counts()
    counts = {k: after[k] - before.get(k, 0) for k in after}
    return prompt, list(request.generated), logits, counts, eng


def test_prefill_then_44_engine_steps_match_the_reference_on_logits():
    prompt, generated, logits, _, _ = _served()
    assert len(prompt) % 64 and len(logits) == 44  # a part-filled bucket
    want = _reference_logits(_cfg(), _leaves(), prompt, generated)
    # want[0] is the prefill's own row (the first token); the steps
    # produced tokens 1..n-1 from positions p..p+n-2
    assert generated[0] == int(want[0].argmax())
    got = np.stack(logits)
    assert np.abs(got - want[1:]).max() < TOL
    assert generated[1:] == [int(r.argmax()) for r in got]


@pytest.mark.parametrize("fault", ["bf16-state", "no-skip", "no-stop"])
def test_the_tolerance_catches_what_it_is_there_for(fault, monkeypatch):
    """A bfloat16 state, a dropped D_skip term, a scan that runs on
    over the bucket's padding: each moves the logits past TOL."""
    prompt, generated, _, _, _ = _served()
    leaves = dict(_leaves())
    if fault == "bf16-state":
        real = ssm.ssm_state_update_reference

        def rounded(state, *rest):
            y, new = real(state, *rest)
            return y, new.astype(jnp.bfloat16).astype(jnp.float32)

        monkeypatch.setattr(ssm, "ssm_state_update_reference", rounded)
        ssm._lanes_as_one_call.cache_clear()
    elif fault == "no-skip":
        for path in leaves:
            if path.endswith("D_skip"):
                leaves[path] = jnp.zeros_like(leaves[path])
    else:
        real_scan = mamba2.ssm_chunked_scan
        monkeypatch.setattr(
            mamba2, "ssm_chunked_scan",
            lambda x, delta, *rest, **kw: real_scan(
                x, jnp.where(delta == 0, 0.05, delta), *rest, **kw))
    eng = _engine(leaves)
    request = ServingRequest(prompt, len(generated))
    slot, _, _ = eng.insert(request)
    got = []
    for token in generated[1:]:  # the sound run's tokens, forced
        got.append(_lane_logits(eng, slot))
        _step(eng)
        # the host says which token the lane holds: the mirror's value
        # goes over the one the device carries
        request.generated[-1] = token
        eng._last_tokens[slot] = token
        eng._lanes_dirty = True
    ssm._lanes_as_one_call.cache_clear()
    want = _reference_logits(_cfg(), _leaves(), prompt, generated)
    assert np.abs(np.stack(got) - want[1:]).max() > 5 * TOL


def test_the_step_counts_state_updates_and_a_seating_writes_once():
    prompt, generated, _, counts, eng = _served()
    ticks = len(generated) - 1
    # three lanes ride every tick over two state layers; one is seated
    assert counts["ssm.lanes"] == ticks * 3 * 2
    assert counts["ssm.lanes_live"] == ticks * 1 * 2
    assert counts["state_write.launches"] == 1
    # the prompt's blocks are written for the attention layer's rows,
    # and that is all a sequence is charged: ceil(21 / 4) blocks
    assert counts["prompt_write.launches"] == 6
    assert counts["pool.inplace_launches"] == counts["pool.launches"]
    # two expert layers; of the three lanes the seated one chooses
    assert counts["moe.pairs_routed"] == ticks * 1 * 3 * 2
    assert counts["moe.lanes"] == ticks * 3 * 2
    assert counts["moe.lanes_live"] == ticks * 1 * 2
    assert 0 < counts["moe.pairs_held"] < counts["moe.pairs_routed"]
    assert 0 < counts["moe.experts_hit"] <= ticks * 3 * 2
    stats = eng.kv.stats()
    assert stats["kv_state_bytes"] == 3 * 2 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert stats["kv_bytes_total"] == eng.kv.num_blocks * 4 * 2 * 2 * 16 * 4


# -------------------- (c) slots reused, churn, free lanes, release


_one_slot = functools.lru_cache(maxsize=None)(lambda: _engine(slots=1))


def _alone(prompt, new):
    """A request's tokens with the server to itself (one engine of one
    slot for all of them: the next test proves a used slot is as good
    as a fresh server)."""
    eng = _one_slot()
    request = ServingRequest(prompt, new)
    eng.insert(request)
    while eng.active_count():
        eng.step()
    return list(request.generated)


def test_a_second_request_in_a_used_slot_is_served_as_by_a_fresh_server():
    eng = _engine(slots=1)
    first = ServingRequest(_prompt(11, 30), 9)
    eng.insert(first)
    while eng.active_count():
        eng.step()
    launches = tracing.recorder().counts()["pool.launches"]
    second = ServingRequest(_prompt(12, 13), 20)
    slot, _, _ = eng.insert(second)
    assert slot == 0
    fresh = _engine(slots=1)
    twin = ServingRequest(_prompt(12, 13), 20)
    fresh.insert(twin)
    # seating overwrote the WHOLE of the slot's state: nothing of the
    # first request is left, bit for bit
    for path, value in _slot_state(eng, 0).items():
        np.testing.assert_array_equal(value, _slot_state(fresh, 0)[path])
    assert tracing.recorder().counts()["pool.launches"] > launches
    while eng.active_count():
        np.testing.assert_array_equal(_lane_logits(eng, 0),
                                      _lane_logits(fresh, 0))
        _step(eng)
        _step(fresh)
    assert second.generated == twin.generated == _alone(_prompt(12, 13), 20)


def test_release_launches_nothing_on_the_device():
    eng = _engine(slots=2)
    eng.insert(ServingRequest(_prompt(3, 10), 5))
    eng.step()
    before = dict(tracing.recorder().counts())
    held = _slot_state(eng, 0)
    eng.evict(0)
    after = tracing.recorder().counts()
    assert after["pool.launches"] == before["pool.launches"]
    assert after.get("state_write.launches") == before.get(
        "state_write.launches")
    # what the slot held is still there, unread, until the next seating
    for path, value in _slot_state(eng, 0).items():
        np.testing.assert_array_equal(value, held[path])
    assert eng.kv.allocator.blocks_in_use() == 0


def test_lanes_seated_and_released_beside_each_other_keep_their_tokens():
    """Churn over three slots: five requests of different lengths come
    and go while others decode; each one's tokens are those it gets
    alone on a fresh server, and the reference's greedy ones."""
    work = [(_prompt(20 + i, p), n) for i, (p, n) in enumerate(
        [(9, 14), (33, 6), (5, 25), (17, 11), (70, 8)])]
    eng = _engine(slots=3)
    waiting = [ServingRequest(p, n) for p, n in work]
    requests = list(waiting)
    steps = 0
    while waiting or eng.active_count():
        # one seating every other tick, as slots free up
        if waiting and eng.free_slots() and steps % 2 == 0:
            eng.insert(waiting.pop(0))
        if eng.active_count():
            eng.step()
        steps += 1
    for (prompt, new), request in zip(work, requests):
        assert list(request.generated) == _alone(prompt, new)
        want = _reference_logits(_cfg(), _leaves(), prompt,
                                 list(request.generated))
        assert list(request.generated) == [int(r.argmax()) for r in want]


@pytest.mark.parametrize("junk", [np.nan, np.inf, -1e30])
def test_whatever_a_free_lanes_state_holds_no_seated_lane_changes(junk):
    prompt, generated, logits, _, _ = _served()
    eng = _engine()
    request = ServingRequest(prompt, len(generated))
    slot, _, _ = eng.insert(request)
    free = [s for s in range(3) if s != slot]
    flat, treedef = jax.tree.flatten(eng.kv.pools)
    eng.kv.pools = jax.tree.unflatten(treedef, [
        leaf.at[jnp.asarray(free)].set(junk) if kind == STATE else leaf
        for leaf, kind in zip(flat, eng.kv.kinds)])
    for want in logits[:12]:
        np.testing.assert_array_equal(_lane_logits(eng, slot), want)
        _step(eng)
    while eng.active_count():
        eng.step()
    assert list(request.generated) == generated
    # the free lanes were updated like the seated one, and stay junk
    assert not np.isfinite(
        _slot_state(eng, free[0])["['block_0']['ssm']['state']"]).all() \
        or junk == -1e30


@pytest.mark.parametrize("slots", [3, 18], ids=["hit-tiles",
                                                "grouped-tiles"])
def test_whatever_token_a_free_lane_holds_it_hits_no_expert(slots):
    """Every free lane is given another token every tick, on both
    paths of the expert layer (18 lanes are over DECODE_ROWS): the
    seated lane streams what it streams beside quiet lanes, and the
    experts hit and the pairs held are one lane's."""
    prompt, generated, _, counts, _ = _served()
    assert (slots > moe.DECODE_ROWS) == (slots == 18)
    eng = _engine(slots=slots)
    request = ServingRequest(prompt, len(generated))
    before = dict(tracing.recorder().counts())
    slot, _, _ = eng.insert(request)
    free = [s for s in range(slots) if s != slot]
    tick = 0
    while eng.active_count():
        eng._last_tokens[free] = (np.arange(len(free)) * 5 + tick) % 96
        eng._lanes_dirty = True
        _step(eng)
        tick += 1
    after = tracing.recorder().counts()
    assert list(request.generated) == generated
    for name in ("moe.pairs_routed", "moe.pairs_held", "moe.experts_hit",
                 "moe.lanes_live"):
        assert after[name] - before.get(name, 0) == counts[name], name
    assert after["moe.lanes"] - before.get("moe.lanes", 0) \
        == slots * counts["moe.lanes_live"]
    # one seated lane: a tile a hit expert on either path, of 16 rows up
    # to 16 lanes and of the sorted tile's height above (18 lanes x 3
    # choices over 4 held experts, a run of 14: 16 too, where the
    # prefill's 256 were multiplied before)
    tm = (moe.DECODE_ROWS if slots <= moe.DECODE_ROWS
          else moe.sorted_tile_rows(slots, 3, 4))
    assert tm == 16
    assert after["moe.tile_rows"] - before.get("moe.tile_rows", 0) \
        == tm * counts["moe.experts_hit"]


# ------------------------------ (d) the scans, the kernels, the shares


def _scan_inputs(seed, l, h=4, p=8, g=2, n=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (l, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (l, h)) - 2.0),
            -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0,
                                        maxval=2.7)),
            jax.random.normal(ks[3], (l, g, n)),
            jax.random.normal(ks[4], (l, g, n)))


@pytest.mark.parametrize("l,chunk", [(1, 16), (16, 16), (40, 16),
                                     (150, 32), (64, 128)])
def test_the_chunked_scan_equals_the_sequential_one(l, chunk):
    x, dt, a, b, c = _scan_inputs(l, l)
    got, last = ssm.ssm_chunked_scan(x[None], dt[None], a, b[None],
                                     c[None], chunk=chunk)
    want = ref.ssm_scan(x, dt, a, b, c)
    # float32 both; the chunked form sums a chunk's products at once
    assert float(jnp.max(jnp.abs(got[0] - want))) < 1e-4 * (
        1 + float(jnp.max(jnp.abs(want))))
    # and carrying its last state on equals one scan over both halves
    more = _scan_inputs(l + 1, 24)
    both = ref.ssm_scan(*(jnp.concatenate([u, v]) if u.ndim > 1 else u
                          for u, v in zip((x, dt, a, b, c), more)))
    tail, _ = ssm.ssm_chunked_scan(more[0][None], more[1][None], a,
                                   more[3][None], more[4][None],
                                   chunk=chunk, state=last)
    assert float(jnp.max(jnp.abs(tail[0] - both[l:]))) < 1e-4 * (
        1 + float(jnp.max(jnp.abs(both))))


@pytest.mark.parametrize("stop", [1, 15, 16, 17, 39])
def test_the_scan_stops_at_the_prompts_true_length(stop):
    """Δ = 0 from `stop` on: the state is the one AT `stop`, whatever
    the bucket's padding holds."""
    x, dt, a, b, c = _scan_inputs(5, 40)
    live = (jnp.arange(40) < stop)[:, None]
    _, padded = ssm.ssm_chunked_scan(
        x[None], (dt * live)[None], a, b[None], c[None], chunk=16)
    _, exact = ssm.ssm_chunked_scan(
        x[None, :stop], dt[None, :stop], a, b[None, :stop],
        c[None, :stop], chunk=16)
    assert float(jnp.max(jnp.abs(padded - exact))) < 1e-5


@pytest.mark.parametrize("lanes,h,p,g,n", [(3, 16, 16, 4, 128),
                                           (1, 8, 64, 8, 128),
                                           (5, 4, 8, 2, 128)])
def test_the_state_update_kernel_equals_its_twin_when_interpreted(
        lanes, h, p, g, n, monkeypatch):
    monkeypatch.setenv("ELASTICDL_TPU_FORCE_INTERPRET", "1")
    ks = jax.random.split(jax.random.PRNGKey(lanes), 3)
    state = jax.random.normal(ks[0], (lanes, h, p, n))
    x, dt, a, b, c = (v for v in _scan_inputs(lanes, lanes, h, p, g, n))
    assert ssm.kernel_supported(h, p, n, g)
    kernel = ssm.ssm_state_update(state, x, dt, a, b, c, use_kernel=True)
    plain = ssm.ssm_state_update(state, x, dt, a, b, c, use_kernel=False)
    assert float(jnp.max(jnp.abs(kernel[0] - plain[0]))) < 2e-5
    assert float(jnp.max(jnp.abs(kernel[1] - plain[1]))) < 2e-6

    # mapped a lane at a time (the serving step) it is ONE call
    def lane(s, xx, dd, bb, cc):
        return ssm.ssm_state_update(s[None], xx[None], dd[None], a,
                                    bb[None], cc[None], use_kernel=True)

    y, new = jax.vmap(lane)(state, x, dt, b, c)
    assert float(jnp.max(jnp.abs(y[:, 0] - plain[0]))) < 2e-5
    assert float(jnp.max(jnp.abs(new[:, 0] - plain[1]))) < 2e-6
    jaxpr = str(jax.make_jaxpr(jax.vmap(lane))(state, x, dt, b, c))
    assert jaxpr.count("custom_vmap_call") == 1
    # a lane that is not finite stays alone
    poisoned = ssm.ssm_state_update(state.at[0].set(jnp.nan), x, dt, a,
                                    b, c, use_kernel=True)
    assert lanes == 1 or bool(jnp.isfinite(poisoned[0][1:]).all())
    assert not ssm.kernel_supported(4, 8, 16, 2)  # 16 is no lane tile


def _relu2_layer(seed, t, experts=8, d=32, hidden=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    w = {
        "moe/router": jax.random.normal(ks[0], (d, experts)) * d ** -0.5,
        "moe/router_bias": 0.2 * jax.random.normal(ks[1], (experts,)),
        "moe/w_up": jax.random.normal(ks[2], (experts, hidden, d))
        * d ** -0.5,
        "moe/w_down": jax.random.normal(ks[3], (experts, hidden, d))
        * hidden ** -0.5,
        "moe/shared_up": jax.random.normal(ks[4], (d, 2 * hidden))
        * d ** -0.5,
        "moe/shared_down": jax.random.normal(ks[5], (2 * hidden, d))
        * (2 * hidden) ** -0.5,
    }
    return w, jax.random.normal(ks[6], (t, d))


def _module_share(w, u, first, count, experts=8, hidden=16):
    """ExpertFFN holding experts first .. first + count: its routed
    part plus the shared expert."""
    layer = zoo.ExpertFFN(
        experts, 3, hidden, held=(first, count), activation="relu2",
        scoring="sigmoid", route_scale=2.5, shared_hidden=2 * hidden)
    params = {k.split("/")[1]: (v[first:first + count]
                                if k in ("moe/w_up", "moe/w_down") else v)
              for k, v in w.items()}
    return layer.apply({"params": params}, u[None], u[None])[0]


@pytest.mark.parametrize("t", [1, 16, 17, 300],
                         ids=["one-row", "decode-rows", "first-prefill",
                              "two-tiles"])
def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer(t):
    w, u = _relu2_layer(t, t)
    cfg = {"moe_experts": 8, "moe_top_k": 3, "moe_route_scale": 2.5,
           "experts_held": [0, 8]}
    weights = ref.router_weights(cfg, w, u)
    # selection and weighting differ: the bias only selects
    scores = jax.nn.sigmoid(ref.matmul(u, w["moe/router"]))
    assert not (jnp.argsort(-scores)[:, :3] == jnp.argsort(
        -(scores + w["moe/router_bias"]))[:, :3]).all() or t == 1
    assert np.allclose(np.asarray(weights.sum(-1)), 2.5, atol=1e-5)
    shared = ref.shared_expert(w, u)
    uncut = ref.routed_experts(cfg, w, u, weights) + shared
    parts = [_module_share(w, u, first, 2) - shared
             for first in (0, 2, 4, 6)]
    # float32 rounding: the shares sum the same products in four parts
    assert float(jnp.max(jnp.abs(sum(parts) + shared - uncut))) < 5e-6
    assert float(jnp.max(jnp.abs(
        _module_share(w, u, 0, 8) - uncut))) < 5e-6
    # and a share alone is the reference given that share
    for first, part in zip((0, 2, 4, 6), parts):
        held = {k: v[first:first + 2] if k in ("moe/w_up", "moe/w_down")
                else v for k, v in w.items()}
        want = ref.routed_experts(dict(cfg, experts_held=[first, 2]),
                                  held, u, weights)
        assert float(jnp.max(jnp.abs(part - want))) < 5e-6


def test_the_relu2_tiles_kernel_equals_the_plain_tiles_when_interpreted(
        monkeypatch):
    monkeypatch.setenv("ELASTICDL_TPU_FORCE_INTERPRET", "1")
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    e, d, hidden = 4, 128, 192  # no 128-multiple divides 192 but itself
    assert expert_ffn.hidden_slice(hidden, 2 ** 40) == hidden
    assert expert_ffn.kernel_supported(16, d, hidden, jnp.float32)
    w = [jax.random.normal(ks[0], (e, hidden, d)) * d ** -0.5,
         jax.random.normal(ks[1], (e, hidden, d)) * hidden ** -0.5]
    bias = 0.1 * jax.random.normal(ks[2], (8,))
    for t in (5, 40):
        h = jax.random.normal(ks[3], (t, d))
        gates, experts = moe.route_sigmoid_top_k(
            jax.random.normal(ks[4], (t, 8)), 3, bias, 2.5)
        kernel = moe.held_experts(h, gates, experts, w, first=2,
                                  use_kernel=True)
        plain = moe.held_experts(h, gates, experts, w, first=2,
                                 use_kernel=False)
        assert float(jnp.max(jnp.abs(kernel[0] - plain[0]))) < 1e-5
        assert (kernel[1] == plain[1]).all()
        assert (kernel[2] == plain[2]).all()
    with pytest.raises(ValueError, match="two matrices"):
        expert_ffn.expert_tiles(h[None], [0], gates[None, :, :1], [0], 1,
                                w[0])


# ------------- (e) leaves by kind, refusals, what stays float32


def test_a_4d_state_leaf_is_never_sliced_copied_or_counted_as_rows():
    """A state-space layer's state [1, 4, 8, 16] is 4-d like K rows
    [1, 2, 128, 16]: the pool goes by the kind the model declares."""
    eng = _engine()
    kinds = dict(zip(
        (jax.tree_util.keystr(p) for p, _ in
         jax.tree_util.tree_flatten_with_path(eng.kv.pools)[0]),
        eng.kv.kinds))
    assert kinds == {
        "['block_0']['ssm']['conv']": STATE,
        "['block_0']['ssm']['state']": STATE,
        "['block_2']['ssm']['conv']": STATE,
        "['block_2']['ssm']['state']": STATE,
        "['block_3']['attn']['k']": ROWS,
        "['block_3']['attn']['v']": ROWS, "['pos']": SCALAR}
    assert eng.kv.pools["block_0"]["ssm"]["state"].shape == (3, 4, 8, 16)
    assert eng.kv.pools["block_3"]["attn"]["k"].ndim == 4
    assert len(eng.kv.row_arenas()) == len(eng.kv.row_shapes) == 2
    # rows are counted, the state beside them
    assert eng.kv.bytes_total == 2 * eng.kv.num_blocks * 4 * 2 * 16 * 4
    assert eng.kv.block_bytes == 2 * 4 * 2 * 16 * 4
    marked = jax.tree.map(lambda x: jnp.full(x.shape, 7, x.dtype),
                          eng.kv.pools)
    kv = jax.tree.map(lambda s: jnp.ones(s.shape, s.dtype),
                      eng._kv_shapes)
    wrote = kv_pool.write_prompt_block(marked, kv, 0, 1, 4, eng.kv.kinds)
    copied = kv_pool.copy_block(marked, 0, 2, eng.kv.kinds)
    for tree in (wrote, copied):
        for name in ("block_0", "block_2"):
            for leaf in tree[name]["ssm"].values():
                assert bool((leaf == 7).all())
    assert bool((wrote["block_3"]["attn"]["k"][1] == 1).all())
    seated = kv_pool.write_state(marked, kv, 2, eng.kv.kinds)
    assert bool((seated["block_0"]["ssm"]["state"][2] == 1).all())
    assert bool((seated["block_0"]["ssm"]["state"][:2] == 7).all())
    assert bool((seated["block_3"]["attn"]["k"] == 7).all())
    with pytest.raises(ValueError, match="kinds for a pool"):
        kv_pool.copy_block(marked, 0, 1, eng.kv.kinds[:-1])


@pytest.mark.parametrize("option,kwargs", [
    ("share_prefix", {"share_prefix": True}),
    ("host_bytes", {"host_bytes": 1 << 20}),
    ("draft_k", {"draft": "self", "draft_k": 2}),
    ("prefill_chunk_tokens", {"prefill_chunk_tokens": 16}),
])
def test_what_needs_a_state_snapshot_refuses_to_start(option, kwargs):
    if kwargs.get("draft") == "self":
        donor = _engine()
        kwargs = dict(kwargs, draft=(donor.trainer, trainer_mod.TrainState(
            step=jnp.zeros((), jnp.int32), params=_unflatten(_leaves()),
            opt_state=(), model_state=FrozenDict({}),
            rng=jax.random.PRNGKey(0))))
    with pytest.raises(ValueError, match="per-sequence state") as e:
        _engine(**kwargs)
    assert option in str(e.value)


def test_chain_export_and_a_prefill_only_seat_are_refused_by_name():
    eng = _engine()
    with pytest.raises(ValueError, match="chain export"):
        eng.kv.export_chain(_prompt(1, 12))
    request = ServingRequest(_prompt(1, 12), 1)
    request.prefill_only = True
    with pytest.raises(ValueError, match="prefill-only seat"):
        eng.insert(request)
    assert eng.kv.has_state and eng._state_layers == 2


def test_what_is_read_as_it_is_stays_float32_and_the_rest_is_cast_once():
    """serving/exec_weights.py, by the programs' jaxprs."""
    eng = _engine(params=dict(PARAMS, dtype="bf16"))
    served = eng._exec_variables["params"]
    mixer, experts = served["block_0"]["ssm"], served["block_1"]["moe"]
    for name in ("A_log", "D_skip", "dt_bias", "norm_scale"):
        assert mixer[name].dtype == jnp.float32, name
    for name in ("conv_kernel", "conv_bias"):
        assert mixer[name].dtype == jnp.bfloat16, name
    for name in ("in_proj", "out_proj"):
        assert mixer[name]["kernel"].dtype == jnp.bfloat16, name
    for name in ("router", "router_bias"):
        assert experts[name].dtype == jnp.float32, name
    for name in ("w_up", "w_down", "shared_up", "shared_down"):
        assert jax.tree.leaves(experts[name])[0].dtype == jnp.bfloat16
    assert served["block_3"]["RMSNorm_0"]["scale"].dtype == jnp.float32
    # the state arena is float32, the convolution's tail the compute's
    assert eng.kv.pools["block_0"]["ssm"]["state"].dtype == jnp.float32
    assert eng.kv.pools["block_0"]["ssm"]["conv"].dtype == jnp.bfloat16
