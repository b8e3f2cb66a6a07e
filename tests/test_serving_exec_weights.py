"""The server casts its weights to the compute dtype ONCE a (re)load
(serving/exec_weights.py, engine._load_params), not once a program:

* which leaves: decided from the traced programs. Every leaf the
  serving programs consume only through one narrowing cast is served
  as that cast (bf16 compute over fp32 weights: the matmul kernels,
  the MLP biases, the head, the embedding table); every leaf any
  program reads as it is stays as handed in (LayerNorm, a router);
* same result: the engine's greedy tokens equal offline decode's and
  those of an engine forced back to the tree it was handed;
* with fp32 compute nothing is a cast and the tree served IS the tree
  handed in;
* a hot reload runs the same program, compiles nothing, and the engine
  holds neither the old nor the new fp32 kernels afterwards;
* int8 params: dequantize-then-cast in the one load program;
* the four `weights.*` counters read what the trees say.
"""

import functools
import gc
import weakref

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.api import generation
from elasticdl_tpu.api.quantization import (
    dequantize_params,
    quantize_params,
)
from elasticdl_tpu.common.model_utils import load_model_spec_from_module
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.observability.runtime_health import RecompileSentry
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.serving import exec_weights
from elasticdl_tpu.serving.admission import ServingRequest
from elasticdl_tpu.serving.engine import PagedContinuousBatchingEngine
from elasticdl_tpu.training.trainer import Trainer
from model_zoo.transformer_lm import transformer_lm as zoo

VOCAB, SEQ = 64, 64
SIZES = ("vocab_size=%d; seq_len=%d; embed_dim=64; num_heads=2; "
         "num_layers=2; pos_emb='rope'" % (VOCAB, SEQ))
BF16, F32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)

PROMPTS = (
    tuple(range(1, 9)),
    tuple((7 * i + 3) % VOCAB for i in range(21)),
    (5,),
    tuple((11 * i + 2) % VOCAB for i in range(33)),
)


@functools.lru_cache(maxsize=None)
def _rig(dtype="bf16", seed=0):
    params = SIZES + ("; dtype=%r" % dtype if dtype else "")
    trainer = Trainer(
        load_model_spec_from_module(zoo),
        mesh=mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1]),
        model_params=params, seed=seed)
    toks = (np.arange(SEQ + 1)[None, :] % VOCAB).astype(np.int32)
    return trainer, trainer.init_state(
        ({"tokens": toks[:, :-1]}, toks[:, 1:]))


def _engine(trainer, state, **kwargs):
    return PagedContinuousBatchingEngine(
        trainer, state, 2, block_size=4, **kwargs)


def _serve(eng, prompts=PROMPTS, new=6):
    """Greedy streams of `prompts`, two seated at a time."""
    reqs = [ServingRequest(list(p), new) for p in prompts]
    for r in reqs:
        while not (eng.free_slots() and eng.can_seat(r)):
            assert eng.step()
        eng.insert(r)
    while eng.active_count():
        eng.step()
    return [list(r.generated) for r in reqs]


def _offline(trainer, state, prompts=PROMPTS, new=6):
    return [
        [int(t) for t in np.asarray(generation.autoregressive_generate(
            trainer, state, np.asarray([p], np.int32), new,
            use_cache=True))[0, len(p):]]
        for p in prompts
    ]


def _flat(tree):
    """{"block_0/attn/qkv/kernel": leaf} of a variables tree's params."""
    return {
        "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                 for k in path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            tree["params"])[0]
    }


def _counts():
    return {k: v for k, v in tracing.recorder().counts().items()
            if k.startswith("weights.")}


def _delta(before):
    return {k: v - before[k] for k, v in _counts().items()}


# ------------------------------------------- (1) which leaves are cast


@functools.lru_cache(maxsize=None)
def _bf16_engine():
    return _engine(*_rig())


@pytest.mark.parametrize("leaf,dtype", [
    ("block_0/attn/qkv/kernel", BF16),
    ("block_1/attn/proj/kernel", BF16),
    ("block_0/mlp_up/kernel", BF16),
    ("block_1/mlp_down/kernel", BF16),
    ("block_0/mlp_up/bias", BF16),
    ("block_1/mlp_down/bias", BF16),
    ("head/kernel", BF16),
    ("wte/embedding", BF16),
    ("block_0/LayerNorm_0/scale", F32),
    ("block_0/LayerNorm_0/bias", F32),
    ("block_1/LayerNorm_1/scale", F32),
    ("block_1/LayerNorm_1/bias", F32),
    ("ln_f/scale", F32),
    ("ln_f/bias", F32),
])
def test_bf16_compute_serves_the_cast_of_what_is_only_ever_cast(
        leaf, dtype):
    """Kernels, MLP biases, head and embedding are consumed through
    `astype(bf16)` alone and are served as that; LayerNorm multiplies
    its scale and adds its bias in fp32, so both stay fp32."""
    eng = _bf16_engine()
    _trainer, state = _rig()
    served = _flat(eng._exec_variables)[leaf]
    handed = _flat({"params": state.params})[leaf]
    assert served.dtype == dtype and handed.dtype == F32
    assert served.shape == handed.shape
    np.testing.assert_array_equal(
        np.asarray(served), np.asarray(handed.astype(dtype)))


def test_only_the_layer_norms_are_kept_and_there_is_one_tree():
    eng = _bf16_engine()
    _trainer, state = _rig()
    served = _flat(eng._exec_variables)
    handed = _flat({"params": state.params})
    assert sorted(served) == sorted(handed)
    cast = {k for k in served if served[k].dtype == BF16}
    assert {k.rsplit("/", 2)[-2] for k in set(served) - cast} == {
        "LayerNorm_0", "LayerNorm_1", "ln_f"}
    assert eng.variables is eng._exec_variables  # there is one tree


def _handed(state):
    return {"params": state.params, **state.model_state}


@pytest.mark.parametrize("tick", ["paged_step", "spec_step"])
def test_every_program_that_takes_a_tree_is_walked(tick):
    """The prompt prefill, the decode tile and the tick's own
    program for the target's tree; with a draft seated, the
    speculative step for both trees and the draft's prefill."""
    trainer, state = _rig()
    if tick == "spec_step":
        eng = _engine(trainer, state, draft=(trainer, state), draft_k=2)
        d_handed = _handed(state)
    else:
        eng, d_handed = _bf16_engine(), None
    programs = eng._weight_programs(_handed(state), d_handed)
    walked = {fn.__qualname__.split(".")[1]: argnums
              for fn, _args, argnums in programs}
    assert walked == {
        "paged_step": {"_prefill_program": (0, None),
                       "_suffix_prefill_program": (1, None),
                       "_paged_step_program": (1, None)},
        "spec_step": {"_prefill_program": (0, None),
                      "_suffix_prefill_program": (1, None),
                      "_spec_step_program": (2, 3),
                      "_draft_prefill_program": (None, 0)},
    }[tick]
    # and no other builder of a program takes a weight tree
    assert {name for name in dir(eng) if name.endswith("_program")} == {
        "_prefill_program", "_suffix_prefill_program",
        "_paged_step_program", "_spec_step_program",
        "_draft_prefill_program"}
    # where it says a tree is, the tree is
    for _fn, args, (place, d_place) in programs:
        assert place is None or args[place]["params"] is state.params
        assert d_place is None or args[d_place] is d_handed


@pytest.mark.parametrize("tick", ["paged_step", "spec_step"])
def test_a_leaf_only_the_ticks_program_reads_raw_is_kept(
        tick, monkeypatch):
    """A model may do with a weight in the tick's narrow call what it
    does in no other program: here the head adds its fp32 kernel's
    sum below eight positions (the step's one, the speculative
    step's k + 1; the tile is eight wide, the prompt a bucket). The
    prefill and the tile alone would serve the cast; the engine walks
    the tick too and keeps the leaf."""
    class Head(zoo.LMHead):
        @nn.compact
        def __call__(self, x, fused=False):
            kernel = self.param(
                "kernel", self.kernel_init,
                (x.shape[-1], self.vocab_size), jnp.float32)
            logits = (x @ kernel.astype(x.dtype)).astype(jnp.float32)
            return logits + (0.0 if x.shape[-2] >= 8 else 0 * kernel.sum())

    monkeypatch.setattr(zoo, "LMHead", Head)
    trainer, state = _rig.__wrapped__(seed=1)
    draft = {"draft": (trainer, state), "draft_k": 2}
    eng = _engine(trainer, state, **(
        draft if tick == "spec_step" else {}))
    handed = _handed(state)
    for tree in [eng._exec_variables] + (
            [eng._d_variables] if tick == "spec_step" else []):
        served = {k: v.dtype for k, v in _flat(tree).items()}
        assert served["head/kernel"] == F32
        assert served["wte/embedding"] == BF16
    with trainer.mesh:
        others = exec_weights.narrowing_casts(handed, [
            (fn, args, place) for fn, args, (place, _d)
            in eng._weight_programs(handed, None)[:2]])
    assert dict(zip(_flat(handed), others))["head/kernel"] == BF16
    assert _serve(eng, PROMPTS[:2]) == _offline(trainer, state, PROMPTS[:2])


def test_boxes_and_their_sharding_names_are_kept():
    """A kernel handed in as an `nn.Partitioned` box is served as the
    same box, same names, around the cast."""
    trainer, state = _rig()

    def box(path, leaf):
        if leaf.ndim != 2:
            return leaf
        return nn.Partitioned(leaf, names=(None, "tp"))

    boxed = jax.tree_util.tree_map_with_path(box, state.params)
    eng = _engine(trainer, state.replace(params=boxed))
    def is_box(x):
        return isinstance(x, nn.Partitioned)

    served = jax.tree.leaves(eng._exec_variables, is_leaf=is_box)
    boxes = [x for x in served if is_box(x)]
    assert len(boxes) == 2 * 4 + 2  # four kernels a layer, head, wte
    assert all(b.names == (None, "tp") and b.value.dtype == BF16
               for b in boxes)
    assert _serve(eng, PROMPTS[:2]) == _serve(_bf16_engine(), PROMPTS[:2])


def test_the_draft_tree_goes_through_the_same_rule():
    trainer, state = _rig()
    eng = _engine(trainer, state, draft=(trainer, state), draft_k=2)
    want = {k: v.dtype for k, v in _flat(eng._exec_variables).items()}
    assert {k: v.dtype
            for k, v in _flat(eng._d_variables).items()} == want
    assert BF16 in want.values() and F32 in want.values()
    # greedy speculative decode commits the plain step's tokens
    assert _serve(eng) == _serve(_bf16_engine())
    assert eng.draft_accepted > 0


# --------------------------------------------------- (2) same result


@pytest.mark.parametrize("i", range(len(PROMPTS)))
def test_greedy_tokens_equal_offline_decode_and_the_fp32_tree(i):
    """Offline decode casts inside its own programs; an engine whose
    `_exec_variables` is forced back to the tree it was handed does
    what the engine did before. All three agree token for token."""
    trainer, state = _rig()
    prompts = PROMPTS[i:i + 1]
    got = _serve(_bf16_engine(), prompts)
    forced = _engine(trainer, state)
    forced._exec_variables = {"params": state.params,
                              **state.model_state}
    assert got == _serve(forced, prompts)
    assert got == _offline(trainer, state, prompts)
    assert len(got[0]) == 6


# --------------------------------- (3) fp32 compute: nothing is a cast


def test_fp32_compute_serves_the_tree_handed_in():
    trainer, state = _rig(dtype="")
    before = _counts()
    eng = _engine(trainer, state)
    assert eng._exec_variables is eng.variables
    assert eng._exec_variables["params"] is state.params
    assert eng._loader[0] is None  # and no program to run at a reload
    n = len(jax.tree.leaves(state.params))
    size = exec_weights.tree_bytes(state.params)
    assert _delta(before) == {
        "weights.source_bytes": size, "weights.exec_bytes": size,
        "weights.leaves_cast": 0, "weights.leaves_kept": n}
    assert _serve(eng, PROMPTS[:2]) == _offline(trainer, state,
                                                PROMPTS[:2])


# ----------------------------- (4) the rule, on programs made to try it


class _Routed(nn.Module):
    """A bf16 Dense beside a router that multiplies in fp32."""

    @nn.compact
    def __call__(self, x):
        gate = self.param("router", nn.initializers.normal(1.0), (8, 4))
        probs = jax.nn.softmax(x.astype(jnp.float32) @ gate)
        y = nn.Dense(4, dtype=jnp.bfloat16, name="expert")(x)
        return (y.astype(jnp.float32) * probs).sum(-1)


def test_a_two_dimensional_leaf_consumed_raw_stays_fp32():
    model = _Routed()
    x = jnp.ones((2, 8), jnp.bfloat16)
    variables = model.init(jax.random.PRNGKey(0), x)
    plan = exec_weights.narrowing_casts(
        variables, [(model.apply, (variables, x), 0)])
    served = exec_weights.cast_leaves(variables, plan)["params"]
    assert served["router"] is variables["params"]["router"]
    assert served["expert"]["kernel"].dtype == BF16
    assert served["expert"]["bias"].dtype == BF16
    np.testing.assert_array_equal(
        np.asarray(model.apply(variables, x)),
        np.asarray(model.apply({"params": served}, x)))


def _bf16(w):
    return w.astype(jnp.bfloat16)


def _loop(body):
    return lambda w: jax.lax.while_loop(
        lambda c: c < 3.0, lambda c: c + body(w),
        jnp.zeros((), jnp.float32))


_W = jax.ShapeDtypeStruct((4, 4), jnp.float32)
_Q = jax.ShapeDtypeStruct((4, 4), jnp.int8)

RULE = {
    # name: (programs over one leaf, the leaf, the dtype it is served in)
    "one_cast": ([lambda w: _bf16(w).sum()], _W, BF16),
    "the_same_cast_twice": (
        [lambda w: _bf16(w).sum() + (_bf16(w) * 2).sum()], _W, BF16),
    "the_same_cast_in_two_programs": (
        [lambda w: _bf16(w).sum(), lambda w: _bf16(w) @ _bf16(w)],
        _W, BF16),
    "cast_inside_a_jitted_call": (
        [lambda w: jax.jit(lambda v: _bf16(v).sum())(w)], _W, BF16),
    "cast_inside_a_remat": (
        [lambda w: jax.checkpoint(lambda v: _bf16(v).sum())(w)],
        _W, BF16),
    "cast_inside_a_scan_that_closes_over_it": (
        [lambda w: jax.lax.scan(
            lambda c, x: (c + (_bf16(w) * x).sum(), None),
            jnp.zeros((), jnp.bfloat16),
            jnp.ones((3,), jnp.bfloat16))[0]], _W, BF16),
    "under_vmap_unbatched": (
        [lambda w: jax.vmap(lambda x: _bf16(w) @ x)(
            jnp.ones((3, 4), jnp.bfloat16))], _W, BF16),
    "consumed_raw": ([lambda w: w.sum()], _W, None),
    "cast_and_consumed_raw": (
        [lambda w: _bf16(w).sum() + w.sum()], _W, None),
    "raw_in_another_program": (
        [lambda w: _bf16(w).sum(), lambda w: w @ w], _W, None),
    "cast_to_two_dtypes": (
        [lambda w: _bf16(w).sum() + w.astype(jnp.float16).sum()],
        _W, None),
    "two_dtypes_over_two_programs": (
        [lambda w: _bf16(w).sum(), lambda w: w.astype(jnp.float16)],
        _W, None),
    "returned": ([lambda w: (_bf16(w).sum(), w)], _W, None),
    "carried_by_a_scan": (
        [lambda w: jax.lax.scan(
            lambda c, _x: (c * 2, _bf16(c).sum()), w, None,
            length=2)[1]], _W, None),
    "inside_a_loop_the_walk_has_no_rule_for": (
        [_loop(lambda w: _bf16(w).sum().astype(jnp.float32))], _W, None),
    "raw_inside_a_jitted_call": (
        [lambda w: _bf16(w).sum() + jax.jit(lambda v: v.sum())(w)],
        _W, None),
    "a_widening_cast": (
        [lambda q: q.astype(jnp.float32).sum()], _Q, None),
    "not_consumed_at_all": ([lambda w: jnp.zeros(())], _W, None),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_a_leaf_is_replaced_iff_every_consumer_is_one_narrowing_cast(
        case):
    programs, leaf, want = RULE[case]
    tree = {"other": jax.ShapeDtypeStruct((2,), jnp.float32), "w": leaf}
    plan = exec_weights.narrowing_casts(tree, [
        (lambda other, t, fn=fn: (fn(t["w"]), other * 2),
         (tree["other"], tree), 1)
        for fn in programs])
    assert plan == [None, want]


# ------------------------------------------------- (5) the hot reload


def _copy(state, scale=1.0):
    """`state` with params of its own (fresh arrays)."""
    return state.replace(
        params=jax.tree.map(lambda x: x * scale, state.params))


def _matrices(params):
    return [x for x in jax.tree.leaves(params) if x.ndim == 2]


def test_hot_reload_serves_the_new_cast_compiles_nothing_holds_no_fp32():
    trainer, state = _rig()
    _t2, other = _rig(seed=1)
    old, new = _copy(state), _copy(other)
    sentry = RecompileSentry()
    eng = _engine(trainer, old)
    eng.sentry = sentry
    first = _serve(eng)
    assert first == _serve(_bf16_engine())
    compiled, recompiles = dict(sentry.compiles), sentry.recompiles
    assert compiled and "load_weights" not in compiled  # at construction

    refs = {"old": [weakref.ref(x) for x in _matrices(old.params)],
            "new": [weakref.ref(x) for x in _matrices(new.params)]}
    before = _counts()
    eng.set_params(new, 7)
    assert _delta(before)["weights.leaves_cast"] == 2 * 6 + 2
    del old, new
    gc.collect()
    # the caller let go; the engine never held on: every fp32 matrix
    # of either tree is gone
    assert [r() for r in refs["old"] + refs["new"]] == [None] * 20
    assert all(x.dtype == BF16
               for x in _matrices(eng._exec_variables["params"]))

    assert eng.model_version == 7
    second = _serve(eng)
    assert second == _offline(trainer, other)
    assert second != first
    assert dict(sentry.compiles) == compiled
    assert sentry.recompiles == recompiles


# ------------------------------------------------------- (6) int8 params


def test_int8_params_dequantize_then_cast_in_the_one_load_program():
    trainer, state = _rig()
    q = state.replace(params=quantize_params(state.params, 1024))
    before = _counts()
    eng = _engine(trainer, q)
    counted = _delta(before)
    served = _flat(eng._exec_variables)
    assert all(x.dtype == BF16 for x in served.values() if x.ndim == 2)
    assert served["ln_f/scale"].dtype == F32
    # what the engine served before: the dequantized floats, cast by
    # each program
    forced = _engine(trainer, q)
    forced._exec_variables = {
        "params": jax.jit(dequantize_params)(q.params), **q.model_state}
    got = _serve(eng)
    assert got == _serve(forced)
    assert got == _offline(trainer, q)
    assert counted["weights.source_bytes"] == exec_weights.tree_bytes(
        q.params)
    assert counted["weights.exec_bytes"] == exec_weights.tree_bytes(
        eng._exec_variables)
    assert counted["weights.leaves_cast"] == 2 * 6 + 2
    assert counted["weights.leaves_kept"] == 2 * 4 + 2


# ------------------------------------------------------- (7) the counters


@pytest.mark.parametrize("dtype", ["bf16", ""])
def test_the_four_counters_read_what_the_trees_say(dtype):
    trainer, state = _rig(dtype=dtype)
    before = _counts()
    eng = _engine(trainer, _copy(state))
    got = _delta(before)
    handed = jax.tree.leaves(state.params)
    served = jax.tree.leaves(eng._exec_variables)
    cast = sum(a.dtype != b.dtype for a, b in zip(handed, served))
    assert got == {
        "weights.source_bytes": sum(x.nbytes for x in handed),
        "weights.exec_bytes": sum(x.nbytes for x in served),
        "weights.leaves_cast": cast,
        "weights.leaves_kept": len(handed) - cast,
    }
    assert cast == (2 * 6 + 2 if dtype else 0)
    if dtype:  # d = 64: the matrices are all but 3 % of the bytes
        ratio = got["weights.exec_bytes"] / got["weights.source_bytes"]
        assert 0.5 < ratio < 0.52
    again = _counts()
    eng.set_params(_copy(state, 0.5), 1)  # counted once a load
    assert _delta(again) == got


# ------------------------------- (8) the decision, remembered on disk


@pytest.fixture
def cache_dir(tmp_path):
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", before)


def _entries(cache_dir):
    return sorted(p.name for p in cache_dir.iterdir()
                  if p.name.startswith("edl-weight-casts-"))


def test_a_warm_start_reads_the_decision_and_traces_nothing(
        cache_dir, monkeypatch):
    trainer, state = _rig()
    cold = _engine(trainer, state)
    (entry,) = _entries(cache_dir)
    monkeypatch.setattr(
        exec_weights, "narrowing_casts",
        lambda *_a: pytest.fail("a warm start traced the programs"))
    warm = _engine(trainer, state)
    want = [x.dtype for x in jax.tree.leaves(cold._exec_variables)]
    assert [x.dtype
            for x in jax.tree.leaves(warm._exec_variables)] == want
    assert BF16 in want and F32 in want
    assert _entries(cache_dir) == [entry]
    assert _serve(warm, PROMPTS[:2]) == _serve(_bf16_engine(), PROMPTS[:2])


@pytest.mark.parametrize("what", [
    "another model", "another setting", "another source",
    "another environment", "an unreadable entry", "a short entry"])
def test_anything_else_traces_again(what, cache_dir, monkeypatch):
    trainer, state = _rig()
    _engine(trainer, state)
    (entry,) = _entries(cache_dir)
    traced = []
    walk = exec_weights.narrowing_casts
    monkeypatch.setattr(
        exec_weights, "narrowing_casts",
        lambda *a: traced.append(1) or walk(*a))
    kwargs = {}
    if what == "another model":
        trainer, state = _rig(dtype="")
    elif what == "another setting":
        kwargs["top_k"] = 3
    elif what == "another source":
        monkeypatch.setattr(exec_weights, "_sources",
                            lambda _closed_over: "edited")
    elif what == "another environment":
        monkeypatch.setenv("EDL_FLASH_BLOCK_Q", "256")
    elif what == "an unreadable entry":
        (cache_dir / entry).write_text("{not json")
    elif what == "a short entry":
        (cache_dir / entry).write_text("[null]")
    eng = _engine(trainer, state, **kwargs)
    assert traced == [1]
    rewritten = what in ("an unreadable entry", "a short entry")
    assert len(_entries(cache_dir)) == (1 if rewritten else 2)
    served = {x.dtype for x in jax.tree.leaves(eng._exec_variables)}
    assert served == ({F32} if what == "another model" else {BF16, F32})


def test_without_a_cache_directory_nothing_is_written(
        tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        eng = _engine(*_rig())
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert BF16 in {x.dtype for x in jax.tree.leaves(eng._exec_variables)}
    assert list(tmp_path.iterdir()) == []


def _zoo_copy(tmp_path):
    """A copy of the transformer_lm zoo under `tmp_path`, and a rig
    over it loaded the way a job loads its model: by path
    (model_utils.get_model_spec), into no module table."""
    import os
    import shutil

    from elasticdl_tpu.common.model_utils import get_model_spec

    os.makedirs(tmp_path / "zoo" / "lm", exist_ok=True)
    path = tmp_path / "zoo" / "lm" / "lm.py"
    if not path.exists():
        shutil.copy(zoo.__file__, path)

    def rig():
        trainer = Trainer(
            get_model_spec(str(tmp_path / "zoo"), "lm.lm.custom_model"),
            mesh=mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1]),
            model_params=SIZES + "; dtype='bf16'", seed=0)
        toks = (np.arange(SEQ + 1)[None, :] % VOCAB).astype(np.int32)
        return trainer, trainer.init_state(
            ({"tokens": toks[:, :-1]}, toks[:, 1:]))

    return path, rig


def test_an_edit_to_a_model_file_loaded_by_path_traces_again(
        cache_dir, tmp_path):
    """The remembered decision decides numerics, so an edit to the
    model's own file must miss, however the file was loaded: here the
    head starts reading its kernel raw, and the next start serves the
    kernel as handed in, not the cast it remembered."""
    import sys

    path, rig = _zoo_copy(tmp_path)
    trainer, state = rig()
    assert type(trainer.model).__module__ not in sys.modules
    served = _flat(_engine(trainer, state)._exec_variables)
    assert served["head/kernel"].dtype == BF16
    assert len(_entries(cache_dir)) == 1
    _engine(trainer, state)
    assert len(_entries(cache_dir)) == 1  # the same files: a hit

    matmul = "logits = x @ jnp.asarray(kernel, self.dtype or x.dtype)"
    source = path.read_text()
    assert source.count(matmul) == 1
    path.write_text(source.replace(
        matmul, matmul + " + 0 * kernel.sum()"))
    trainer, state = rig()
    served = _flat(_engine(trainer, state)._exec_variables)
    assert served["head/kernel"].dtype == F32
    assert served["wte/embedding"].dtype == BF16
    assert len(_entries(cache_dir)) == 2


@pytest.mark.parametrize("what", [
    "an imported module", "a model loaded by path",
    "a class whose method is wrapped", "a file that is gone"])
def test_the_sources_digest_moves_with_what_a_trace_can_run(
        what, tmp_path, monkeypatch):
    import sys

    from elasticdl_tpu.common.model_utils import load_module

    if what == "a model loaded by path":
        path, rig = _zoo_copy(tmp_path)
        closed_over = (rig()[0].model, 3, "name")
    else:
        path = tmp_path / "edl_probe_mod.py"
        path.write_text(
            "import functools\n"
            "def logged(f):\n"
            "    return functools.wraps(f)(lambda *a: f(*a))\n"
            "class Probe:\n"
            "    @property\n"
            "    def size(self): return 1\n"
            "    @logged\n"
            "    def __call__(self, x): return x\n")
        module = load_module(str(path))  # into no module table
        closed_over = (module.Probe(),)
        if what == "an imported module":
            monkeypatch.setitem(sys.modules, "edl_probe_mod", module)
            closed_over = ()
    one = exec_weights._sources(closed_over)
    assert one == exec_weights._sources(closed_over)
    if what == "a file that is gone":
        path.unlink()
    else:
        path.write_text(path.read_text() + "# edited\n")
    two = exec_weights._sources(closed_over)
    assert two != one and two == exec_weights._sources(closed_over)
    assert exec_weights._sources(()) != two or not closed_over


def test_the_key_holds_the_jax_configuration(cache_dir):
    """A flag that changes what a trace computes (x64, a matmul
    precision) is another key."""
    trainer, state = _rig()
    _engine(trainer, state)
    assert len(_entries(cache_dir)) == 1
    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "float32")
    try:
        _engine(trainer, state)
    finally:
        jax.config.update("jax_default_matmul_precision", before)
    assert len(_entries(cache_dir)) == 2
