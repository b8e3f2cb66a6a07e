"""The `transformer_lm` stack configured as SDAR-30B-A3B-Chat (RMSNorm,
QK-normed GQA under a BLOCK-CAUSAL mask, a drop-free SwiGLU expert
layer routed from the MLP's normed input, a share of the experts held)
and the serving engine's BLOCK TICK (a decode step that denoises a
block of 4 positions a lane: reveal by confidence, a commit pass)
against the plain reference chipbench/refs/sdar_moe.py, at small size
on the CPU with seeded random weights:

(a) the program's full forward, logits, and that the block-causal mask
    and the head norm each make another model;
(b) prefill, then every denoising pass and commit pass through the
    paged pool: the logits of the model's own paged call at every pass
    against `denoise_logits`, float32 and then bf16;
(c) the engine's streams against `generate`, the published loop by
    full recomputation: S in {1, 2, 4}, prompts with p mod 4 in
    {0, 1, 3} and shorter than a block, `max_new_tokens` no multiple
    of 4, lanes seated and freed mid-block, one step in flight;
    `reveal_steps` parallel to the tokens; the counters handed back;
(d) the masks: block-causal flash (kernel interpreted) and blockwise
    against plain attention, the paged tile's block mask in the kernel
    (interpreted) and the scan;
(e) what a block model refuses, by name;
(f) the step programs of the families that yield a token a step are
    the operations they were before the tile was a parameter.

Tolerances. float32: both sides are float32 and sum in different
orders (block attention against the program's paged scan, experts one
by one against tiles) through 3 layers: 2e-4 on logits of unit scale
is 50x the rounding seen (4e-6) and far under any effect tested (a
mask: 0.1 and more). bf16: the program computes in bfloat16 (8 bits of
mantissa) what the reference computes in float32, through 3 layers of
peaked attention (scores of spread 4) and experts chosen by a float32
router over bf16 activations: logits of unit scale differ by 0.014 to
0.019 in the mean over three requests, and by up to 0.42 at single
entries (a row whose last expert is a near-tie reads another one). The
mean is held to 0.05, three times what was seen, which a wrong mask or
position (0.1 and more everywhere) cannot pass; single entries to 1.
"""

import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import FrozenDict

from chipbench.drivers.open_loop import _unflatten
from chipbench.refs import sdar_moe as ref
from elasticdl_tpu.common.model_utils import load_model_spec_from_module
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.ops import attention
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.serving import engine as engine_mod
from elasticdl_tpu.serving.admission import (
    AdmissionError,
    RequestQueue,
    ServingRequest,
)
from elasticdl_tpu.serving.engine import PagedContinuousBatchingEngine
from elasticdl_tpu.training import trainer as trainer_mod
from model_zoo.transformer_lm import transformer_lm as zoo

TOL = 2e-4
TOL_BF16_MEAN, TOL_BF16_WORST = 0.05, 1.0
PARAMS = {
    "vocab_size": 96, "seq_len": 64, "embed_dim": 48, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 16, "num_layers": 3, "pos_emb": "rope",
    "rope_theta": 1000000, "norm": "rms", "norm_eps": 1e-6,
    "mlp": "moe_reglu", "moe_activation": "swiglu",
    "moe_route_from": "mlp", "moe_experts": 8, "moe_top_k": 3,
    "moe_hidden": 24, "experts_held": [0, 4], "qk_norm": True,
    "block_causal": 4, "mask_token": 95,
}
WEIGHTS = {"qk_gain": 2.0, "router_gain": 1.0}
B = PARAMS["block_causal"]


def _cfg(**over):
    return dict(PARAMS, **WEIGHTS, **over)


@functools.lru_cache(maxsize=None)
def _weights(seed=0):
    cfg = _cfg()
    return ref.make_leaves(cfg, seed, ref.all_leaves(cfg))


def _engine(S, slots=2, params=PARAMS, seed=0, **kwargs):
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
        trainer, state, slots, block_size=4, denoise_steps=S, **kwargs)


def _model(**over):
    return zoo.custom_model(**dict(PARAMS, **over))


def _prompt(seed, n):
    # the mask token (the vocabulary's last row) is never a prompt's
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, 95, n)]


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


def test_the_names_of_the_parameters_are_the_references():
    tree = _model().init(jax.random.PRNGKey(0),
                         {"tokens": jnp.zeros((1, 8), jnp.int32)})["params"]
    flat = {"/".join(k.key for k in path): np.shape(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                jax.tree.map(lambda x: getattr(x, "value", x), tree,
                             is_leaf=lambda x: hasattr(x, "value")))[0]}
    assert flat == {p: tuple(s) for p, (s, _) in
                    ref.all_leaves(_cfg()).items()}
    assert flat["block_0/attn/q_norm/scale"] == (16,)


@pytest.mark.parametrize("over", [{"block_causal": 0}, {"block_causal": 8},
                                  {"qk_norm": False},
                                  {"moe_route_from": "input"},
                                  {"moe_activation": "reglu"}],
                         ids=lambda o: "%s=%s" % next(iter(o.items())))
def test_each_of_its_mechanisms_makes_another_model(over):
    w = {k: v for k, v in _weights().items() if "_norm/" not in k
         or over.get("qk_norm", True)}
    tokens = jnp.asarray([_prompt(3, 40)])
    got = _model(**over).apply({"params": _unflatten(w)},
                               {"tokens": tokens})
    want = ref.forward(_cfg(), _weights(), tokens, rows=8)
    assert float(jnp.max(jnp.abs(got - want))) > 0.05


# ------------------- (b) prefill, denoise and commit through the pool


def _served_passes(S, prompt, new, dtype=None, seed=0):
    """One request through the engine, its steps in line: before each
    launch the logits of the model's own paged call over the lane's
    open block as the device holds it (rows of a denoising pass), and
    the request."""
    params = dict(PARAMS, **({"dtype": dtype} if dtype else {}))
    eng = _engine(S, params=params, seed=seed)
    request = ServingRequest(prompt, new)
    slot, first, finished = eng.insert(request)
    assert first is None and not finished and request.generated == []
    passes = []
    while eng.active_count():
        if eng._lanes is None:  # the first launch sends the mirror
            block, reveal = eng._block_tokens[slot], eng._block_reveal[slot]
        else:
            block, reveal, _ = (np.asarray(x)[slot] for x in
                                engine_mod.block_fields(eng._lanes, B))
        pos, step = int(eng._positions[slot]), int(eng._passes[slot])
        if step < S:
            toks = np.where(reveal == engine_mod.MASKED, 95, block)
            out, _ = eng.model.apply(
                dict(eng._exec_variables,
                     cache={"pos": jnp.asarray(pos)}),
                {"tokens": jnp.asarray(toks)[None]}, training=False,
                decode=True, mutable=["cache", "kv_out", "counters"],
                paged={"pools": eng.kv.pools,
                       "table": jnp.asarray(eng.kv.tables[slot])[None]})
            passes.append(((pos - len(prompt) // B * B) // B, step,
                           np.asarray(out[0])))
        assert eng._launch() and eng._collect() is not None
    return request, passes


@pytest.mark.parametrize("S,p,new", [(2, 8, 12), (4, 9, 11), (1, 11, 9),
                                     (2, 3, 9)])
def test_every_pass_through_the_pool_matches_denoise_logits(S, p, new):
    prompt = _prompt(p, p)
    request, passes = _served_passes(S, prompt, new)
    assert len(request.generated) == new == len(request.reveal_steps)
    want = ref.denoise_logits(_cfg(), _weights(), prompt, request.generated,
                              request.reveal_steps, S, rows=8)
    assert len(passes) == want.shape[0] * S
    for b, s, got in passes:
        assert float(np.max(np.abs(got - want[b, s]))) < TOL, (b, s)
    # the passes are not all alike: a copy sees what was revealed
    assert S == 1 or float(jnp.max(jnp.abs(want[:, 0] - want[:, -1]))) > 0.05


def test_every_pass_in_bfloat16_is_within_its_own_tolerance():
    prompt = _prompt(8, 8)
    request, passes = _served_passes(2, prompt, 12, dtype="bf16")
    want = ref.denoise_logits(_cfg(), _weights(), prompt, request.generated,
                              request.reveal_steps, 2, rows=8)
    errs = np.concatenate([np.abs(got - np.asarray(want[b, s])).ravel()
                           for b, s, got in passes])
    assert 1e-3 < float(errs.mean()) < TOL_BF16_MEAN
    assert float(errs.max()) < TOL_BF16_WORST


def test_denoise_logits_asks_for_whole_blocks():
    with pytest.raises(ValueError, match="do not end a block"):
        ref.denoise_plan(_cfg(), _prompt(0, 8), [1, 2, 3], [0, 0, 0], 1)


def test_a_noisy_copy_sees_the_clean_past_and_itself_only():
    dp = ref.denoise_plan(_cfg(), _prompt(0, 6), [5, 6, 7, 8, 9, 10],
                          [0, 1, 1, 0, 0, 1], 2, rows=8)
    pos, blk, copy = dp["plan"]
    assert dp["at"].shape == (2, 2, 4) and len(dp["ids"]) % 8 == 0
    # block 1 (positions 4..7): two given, then reveal steps 0, 1
    assert dp["reveal"].tolist() == [[-2, -2, 0, 1], [1, 0, 0, 1]]
    first, second = dp["ids"][dp["at"][0, 0]], dp["ids"][dp["at"][0, 1]]
    assert first.tolist()[2:] == [95, 95] and second.tolist()[2:] == [5, 95]
    assert pos[dp["at"][1, 1]].tolist() == [8, 9, 10, 11]
    assert set(copy[dp["at"][1, 0]]) == {3} and set(blk[dp["at"][1, 0]]) == {2}


# --------------------------------- (c) the engine against `generate`


@pytest.mark.parametrize("S", [1, 2, 4])
@pytest.mark.parametrize("p,new", [(8, 8), (9, 7), (11, 6), (3, 5),
                                   (12, 1)],
                         ids=["whole", "one-over", "three-over",
                              "under-a-block", "one-token"])
def test_a_stream_is_the_published_loops(S, p, new):
    prompt = _prompt(p, p)
    eng = _engine(S)
    request = ServingRequest(prompt, new)
    eng.insert(request)
    chunks = []
    while eng.active_count():
        for _slot, req, tokens, _last in eng.step():
            assert req is request
            chunks.append(len(tokens))
    want, steps = ref.generate(_cfg(), _weights(), prompt, new, S)
    assert request.generated == want
    assert request.reveal_steps == steps
    assert all(0 <= s < S for s in steps)
    # a lane yields a block at its commit pass and nothing else
    blocks = -(-(p % B + new) // B)
    assert [n for n in chunks if n] == (
        [min(B - p % B, new)] + [B] * (blocks - 2)
        + [(p + new - 1) % B + 1])[:blocks] or blocks == 1
    assert sum(chunks) == new and len(chunks) == blocks * (S + 1)


def test_lanes_seated_and_freed_mid_block_with_a_step_in_flight():
    """Three requests over two lanes: the second is seated while the
    first is mid-block, the third into the lane the first frees; each
    stream is its own whatever shares the pool, and the loop ran ahead
    of its fetches."""
    S = 2
    eng = _engine(S)
    specs = [(_prompt(21, 9), 10), (_prompt(22, 6), 7), (_prompt(23, 4), 9)]
    requests = [ServingRequest(p, n) for p, n in specs]
    before = dict(tracing.recorder().counts())
    eng.insert(requests[0])
    eng.step()  # launches two passes, commits one: mid-block
    assert eng._flights and int(eng._passes[0]) == 2
    eng.insert(requests[1])
    waiting = [requests[2]]
    seen_mid_block = False
    while eng.active_count() or waiting:
        if waiting and eng.free_slots():
            seen_mid_block |= any(0 < p <= S for p in eng._passes)
            eng.insert(waiting.pop())
        eng.step()
    assert seen_mid_block
    for request, (prompt, new) in zip(requests, specs):
        want, steps = ref.generate(_cfg(), _weights(), prompt, new, S)
        assert request.generated == want and request.reveal_steps == steps
    after = tracing.recorder().counts()
    count = lambda name: after.get(name, 0) - before.get(name, 0)
    blocks = sum(-(-(len(p) % B + n) // B) for p, n in specs)
    assert count("diffusion.blocks_committed") == blocks
    assert count("diffusion.commit_passes") == blocks
    assert count("diffusion.lane_passes") == blocks * (S + 1)
    # every position of every block is revealed but the prompt's own
    assert count("diffusion.tokens_revealed") == blocks * B - sum(
        len(p) % B for p, _ in specs)
    # the expert layers count ROWS: a lane's four are four
    assert count("moe.lanes_live") == blocks * (S + 1) * B * 3
    # 2 lanes x 4 rows ride the 16-row path: 16 rows a hit expert
    assert count("moe.tile_rows") == 16 * count("moe.experts_hit") > 0
    assert count("tick.ahead") >= count("diffusion.lane_passes") // 2 - 3


def test_after_a_launch_that_raised_the_open_block_starts_anew():
    S = 4
    eng = _engine(S)
    prompt = _prompt(31, 9)
    request = ServingRequest(prompt, 6)
    eng.insert(request)
    eng.step()
    eng.step()
    assert eng._flights and int(eng._passes[0]) > 1
    eng._lanes = None  # what a launch that raised leaves behind
    while eng.active_count():
        eng.step()
    want, steps = ref.generate(_cfg(), _weights(), prompt, 6, S)
    assert request.generated == want and request.reveal_steps == steps


def test_the_lane_state_names_its_block_columns():
    eng = _engine(4)
    eng.insert(ServingRequest(_prompt(1, 10), 4))
    assert eng._launch() and eng._collect() is not None
    lanes = np.asarray(eng._lanes)
    assert lanes.shape == tuple(eng._lanes_spec().shape)
    fields = engine_mod.lane_fields(lanes, B)
    assert fields.tables.shape == (2, eng.kv.max_blocks_per_slot)
    block, reveal, passes = engine_mod.block_fields(lanes, B)
    assert fields.positions.tolist() == [8, 0]
    assert passes.tolist() == [1, engine_mod._NO_PASS]
    assert reveal[0].tolist()[:2] == [engine_mod.GIVEN] * 2
    assert sorted(reveal[0].tolist()[2:]) == [engine_mod.MASKED, 0]
    assert block[0].tolist()[:2] == _prompt(1, 10)[8:]


def test_the_reveal_takes_the_most_confident_and_the_lower_on_a_tie():
    prob = jnp.asarray([[0.2, 0.9, 0.9, 0.1], [0.5, 0.5, 0.5, 0.5]])
    masked = jnp.asarray([[True, True, True, False], [True] * 4])
    got = engine_mod._reveal_by_confidence(prob, masked, 2)
    assert got.tolist() == [[False, True, True, False],
                            [True, True, False, False]]
    for row, mask in zip(np.asarray(prob), np.asarray(masked)):
        assert ref.reveal_now(row, mask, 2).tolist() in got.tolist()


# ------------------------------------------------------- (d) the masks


def _qkv(seed, l, h=4, hkv=2, d=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (1, h, l, d)),
            jax.random.normal(ks[1], (1, hkv, l, d)),
            jax.random.normal(ks[2], (1, hkv, l, d)))


def _plain_block_causal(q, k, v, block):
    """softmax(q k^T) v with row i seeing key j iff j // B <= i // B,
    written out."""
    k, v = (jnp.repeat(x, q.shape[1] // x.shape[1], axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    i, j = jnp.arange(q.shape[2])[:, None], jnp.arange(k.shape[2])[None, :]
    s = jnp.where(j // block <= i // block, s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("path", ["naive", "blockwise", "flash-interpreted"])
@pytest.mark.parametrize("block", [4, 8])
def test_block_causal_attention_against_the_mask_written_out(
        path, block, monkeypatch):
    q, k, v = _qkv(block, 64)
    if path == "naive":
        got = attention.naive_attention(q, k, v, causal=True,
                                        block_causal=block)
    elif path == "blockwise":
        got = attention.blockwise_attention(
            q, k, v, causal=True, block_size=16, block_causal=block)
    else:
        monkeypatch.setenv("ELASTICDL_TPU_FORCE_INTERPRET", "1")
        got = attention.flash_attention(q, k, v, causal=True, block_q=16,
                                        block_k=16, block_causal=block)
    want = _plain_block_causal(q, k, v, block)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    causal = attention.naive_attention(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(causal - want))) > 0.05


def test_block_causal_needs_causal_and_falls_back_off_the_kernels_blocks(
        monkeypatch):
    q, k, v = _qkv(1, 24)
    with pytest.raises(ValueError, match="needs causal"):
        attention.flash_attention(q, k, v, block_causal=4)
    monkeypatch.setenv("ELASTICDL_TPU_FORCE_INTERPRET", "1")
    # kernel blocks of 8 are no whole multiple of 3: the blockwise path
    got = attention.flash_attention(q, k, v, causal=True, block_q=8,
                                    block_k=8, block_causal=3)
    assert float(jnp.max(jnp.abs(
        got - _plain_block_causal(q, k, v, 3)))) < 2e-5


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["scan", "kernel-interpreted"])
@pytest.mark.parametrize("length,t", [(8, 4), (12, 4), (8, 8), (6, 4)],
                         ids=["one-block", "later-block", "two-blocks",
                              "unaligned"])
def test_the_paged_tile_sees_its_own_block_whole(use_kernel, length, t,
                                                 monkeypatch):
    """A tile at `length` over the pool under the block-causal mask
    equals plain block-causal attention over the whole sequence, rows
    [length, length + t); under the causal tile mask it does not."""
    if use_kernel:
        monkeypatch.setenv("ELASTICDL_TPU_FORCE_INTERPRET", "1")
    q, k, v = _qkv(length, 16, d=128 if use_kernel else 16)
    bs, hkv = 4, k.shape[1]
    pool = lambda x: jnp.concatenate(
        [x[0].transpose(1, 0, 2).reshape(4, bs, hkv, -1),
         jnp.zeros((1, bs, hkv, x.shape[-1]))])[jnp.asarray([4, 2, 0, 1, 3])]
    table = jnp.asarray([[2, 3, 1, 4]])  # where blocks 0..3 now lie
    cut = lambda x: x[:, :, length:length + t]
    call = functools.partial(
        attention.paged_decode_attention, cut(q), cut(k), cut(v), pool(k),
        pool(v), table, jnp.asarray([length]), use_kernel=use_kernel)
    want = cut(_plain_block_causal(q[:, :, :length + t], k[:, :, :length + t],
                                   v[:, :, :length + t], 4))
    assert float(jnp.max(jnp.abs(call(block_causal=4) - want))) < 2e-5
    assert float(jnp.max(jnp.abs(call() - want))) > 0.01


# ---------------------------------------------------- (e) the refusals


@pytest.mark.parametrize("kwargs,names", [
    ({"denoise_steps": 3}, "denoise_steps 3 does not divide"),
    ({"share_prefix": True}, "--kv_shared 0"),
    ({"prefill_chunk_tokens": 8}, "--prefill_chunk_tokens 0"),
    ({"host_bytes": 1 << 20}, "--kv_host_bytes 0"),
    ({"top_k": 5}, "--top_k 0 --top_p 1.0"),
    ({"block_size": 6}, "whole multiples of the model's block"),
], ids=lambda x: x if isinstance(x, str) else "")
def test_what_a_block_model_cannot_hold_is_refused_by_name(kwargs, names):
    kwargs = dict({"denoise_steps": 2, "share_prefix": False,
                   "block_size": 4}, **kwargs)
    trainer = _engine(2).trainer
    state = trainer_mod.TrainState(
        step=jnp.zeros((), jnp.int32), params=_unflatten(_weights()),
        opt_state=(), model_state=FrozenDict({}),
        rng=jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match=re.escape(names)):
        PagedContinuousBatchingEngine(trainer, state, 2, **kwargs)
    with pytest.raises(ValueError, match="--draft_k 0"):
        PagedContinuousBatchingEngine(
            trainer, state, 2, block_size=4, share_prefix=False,
            draft=(trainer, state), draft_k=2)


def test_a_block_model_without_a_mask_token_is_refused():
    with pytest.raises(ValueError, match="mask_token is not set"):
        _engine(2, params={k: v for k, v in PARAMS.items()
                           if k != "mask_token"})


def test_denoise_steps_is_refused_for_a_model_that_yields_a_token_a_step():
    params = dict(PARAMS, block_causal=0)
    with pytest.raises(ValueError, match="--denoise_steps 0"):
        _engine(2, params=params)
    assert _engine(0, params=params).denoise_steps == 0
    assert _engine(0).denoise_steps == B  # a position a pass


def test_a_sampled_or_overlong_request_is_turned_away_at_admission():
    eng = _engine(2)
    queue = RequestQueue(4, eng.seq_len, refuse=eng.refuse_request)
    # 8 + 53 rounds up to 64 = seq_len, a multiple of the block
    queue.submit(ServingRequest(_prompt(0, 8), 53))
    with pytest.raises(AdmissionError, match="served greedy"):
        queue.submit(ServingRequest(_prompt(0, 8), 4, temperature=0.7))
    with pytest.raises(AdmissionError, match="prefill-only"):
        queue.submit(ServingRequest(_prompt(0, 8), 4, prefill_only=True))
    with pytest.raises(AdmissionError, match="exceeds"):
        queue.submit(ServingRequest(_prompt(0, 8), 57))
    plain = _engine(0, params=dict(PARAMS, block_causal=0))
    assert plain.refuse_request(
        ServingRequest(_prompt(0, 8), 4, temperature=0.7)) is None


def test_the_sharded_paths_have_no_block_mask():
    with pytest.raises(NotImplementedError, match="block_causal"):
        _model(attn_impl="jax_flash").apply(
            {"params": _unflatten(_weights())},
            {"tokens": jnp.zeros((1, 8), jnp.int32)})


# ------------- the block step at the cell's widths, for a described v5e


@pytest.fixture(scope="module")
def one_chip():
    from jax.sharding import SingleDeviceSharding

    from chipbench import offchip

    try:
        topo = offchip.describe()
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def test_the_block_step_compiles_for_a_v5e_at_the_cells_widths(one_chip):
    """chipbench/configs/sdar-30b-serve.json two layers deep, over
    shapes: the Mosaic compiler takes the SwiGLU tile kernel and the
    paged kernel under a tile of four rows a lane, the pool is updated
    in place, and no expert bank is copied to suit the kernel."""
    import json
    import os
    from unittest import mock

    from elasticdl_tpu.ops import dispatch
    from elasticdl_tpu.serving import kv_pool
    from scripts import check_pool_donation as check

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "chipbench", "configs",
            "sdar-30b-serve.json")) as f:
        cfg = json.load(f)
    cfg["model"]["params"]["num_layers"] = 2
    eng, _handed_in = check.build_engine(cfg)
    assert (eng._tile, eng.denoise_steps, eng._mask_token) == (4, 2, 37983)
    assert eng._lanes_spec().shape == (32, 4 + 145 + 2 * 4 + 1)
    todo = check.programs(eng, tile=16, upload_blocks=4)
    with mock.patch.object(dispatch, "is_tpu_backend", lambda: True):
        compiled, pools = check.compile_program(eng, todo["paged_step"],
                                                one_chip)
    got = kv_pool.pool_aliasing(compiled, pools)
    assert eng.kv.bytes_total == 2 * 2 * 4640 * 16 * 4 * 128 * 2
    assert 0 <= got["alias_bytes"] - got["pool_bytes"] <= 512, got
    assert got["pool_shaped_copies"] == 0, got
    hlo = compiled.as_text()
    assert hlo.count("moe_expert_tiles/pallas_call") >= 2
    assert not re.search(r"= bf16\[32,(2048,768|768,2048)\]\S* copy\(", hlo)


# ---------------- (f) a token a step: the programs they always were

#: sha1 of the sorted (operation, count) pairs of the lowered paged
#: step of each family at a tiny size, taken on the parent commit
#: (27fd91b, jax 0.9.0) by this very function: making the tile a
#: parameter of the one step program changed no operation of theirs.
#: The two families with expert layers were taken again when the layer
#: began to count `moe.tile_rows` (3c28db8 -> the commit after it): ten
#: small operations an expert layer more (the live tiles times their
#: height, the mark's reduce and sum: 1,474 -> 1,494 and 1,108 ->
#: 1,119) and none changed; the dense family is as it was
_STEP_OPS = {
    "dense": "48da383e02e3f43877968d632397ab62c47b144f",
    "experts": "6af399f77b7ea1b47b5adc053a65064dc3ddf23e",
    "state": "b50da8a582432bba7e869e18761c7455b5ea4937",
}
_FAMILIES = {
    "dense": {"vocab_size": 64, "seq_len": 32, "embed_dim": 32,
              "num_heads": 2, "num_layers": 2, "pos_emb": "rope",
              "attn_window": 8},
    "experts": {"vocab_size": 64, "seq_len": 32, "embed_dim": 32,
                "num_heads": 2, "num_kv_heads": 1, "head_dim": 16,
                "num_layers": 2, "pos_emb": "rope", "norm": "rms",
                "mlp": "moe_reglu", "moe_experts": 4, "moe_top_k": 2,
                "moe_hidden": 16, "experts_held": [0, 2]},
    "state": {"vocab_size": 64, "seq_len": 32, "embed_dim": 32,
              "num_heads": 2, "num_kv_heads": 1, "head_dim": 16,
              "num_layers": 3, "pos_emb": "rope", "rope_layout": [0, 0, 0],
              "norm": "rms", "layer_kinds": "ME*", "mlp": "moe_reglu",
              "moe_activation": "relu2", "moe_scoring": "sigmoid",
              "moe_experts": 4, "moe_top_k": 2, "moe_hidden": 16,
              "moe_shared_hidden": 16, "experts_held": [0, 2],
              "ssm_heads": 4, "ssm_head_dim": 8, "ssm_groups": 2,
              "ssm_state": 8, "ssm_chunk": 8},
}


def step_operations(family):
    """(digest, {operation: count}) of the family's lowered paged step
    at a tiny size."""
    trainer = trainer_mod.Trainer(
        load_model_spec_from_module(zoo),
        mesh=mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1]),
        model_params="; ".join(
            "%s=%r" % kv for kv in sorted(_FAMILIES[family].items())))
    state = trainer.init_state(({"tokens": np.zeros((1, 32), np.int32)},
                                np.zeros((1, 32), np.int32)))
    eng = PagedContinuousBatchingEngine(trainer, state, 2, block_size=4,
                                        share_prefix=False)
    with trainer.mesh:
        text = jax.jit(eng._paged_step_program()).lower(
            eng.kv.pools, eng._exec_variables, eng._lanes_spec()).as_text()
    ops = {}
    for op in re.findall(r"= \"?([a-z_]+\.[a-z_.]+)\"?[ (]", text):
        ops[op] = ops.get(op, 0) + 1
    digest = hashlib.sha1(repr(sorted(ops.items())).encode()).hexdigest()
    return digest, ops


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_a_token_a_step_is_the_program_it_was_before_the_tile(family):
    digest, ops = step_operations(family)
    assert sum(ops.values()) > 100
    assert digest == _STEP_OPS[family], sorted(ops.items())
