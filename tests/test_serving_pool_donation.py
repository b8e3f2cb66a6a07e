"""The serving executables update the KV pool in place (tier-1).

Every compiled program that takes the pool tree and hands one back
donates it (serving/kv_pool.py, UPDATED IN PLACE). What that obliges
the program to, shown here at the benchmark's rehearsal widths
(chipbench/configs/sc2-3b-serve.json `rehearsal`):

* each pool-updating site consumes the tree it is handed, binds the
  new one, and leaves in the arenas exactly what the same program
  leaves when it donates nothing — the rows written, every other block
  untouched;
* whole greedy requests stream the same tokens as through programs
  that donate nothing, with and without prefix sharing, with the host
  spill tier, with a draft model, with int8 arenas;
* `pool.inplace_launches == pool.launches` over those runs;
* a donating call that raises after consuming the pool is KVPoolLost,
  then and at every later use, never a "buffer has been deleted" out
  of a later tick; the scheduler dies of it and aborts its requests;
* compiled for a described v5e (nothing runs), each program aliases
  every byte of the pool it takes and holds no copy of an arena's
  shape.
"""

import functools
import json
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.common.model_utils import get_model_spec
from elasticdl_tpu.observability import runtime_health, tracing
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.serving import engine as engine_mod
from elasticdl_tpu.serving import kv_pool
from elasticdl_tpu.serving.admission import RequestQueue, ServingRequest
from elasticdl_tpu.serving.engine import PagedContinuousBatchingEngine
from elasticdl_tpu.serving.kv_pool import KVPoolLost
from elasticdl_tpu.training.trainer import Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "chipbench", "configs",
                       "sc2-3b-serve.json")) as _f:
    REHEARSAL = json.load(_f)["rehearsal"]
SERVER = REHEARSAL["server"]


@functools.lru_cache(maxsize=None)
def _rig(kv_cache_dtype=""):
    params = dict(REHEARSAL["model"]["params"])
    if kv_cache_dtype:
        params["kv_cache_dtype"] = kv_cache_dtype
    trainer = Trainer(
        get_model_spec(os.path.join(ROOT, "model_zoo"),
                       "transformer_lm.transformer_lm.custom_model"),
        mesh=mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1]),
        model_params="; ".join(
            "%s=%r" % kv for kv in sorted(params.items())),
    )
    dummy = np.zeros((1, params["seq_len"]), np.int32)
    return trainer, trainer.init_state(({"tokens": dummy}, dummy))


def _engine(kv_cache_dtype="", draft=False, **kwargs):
    trainer, state = _rig(kv_cache_dtype)
    kwargs.setdefault("num_blocks", SERVER["kv_num_blocks"])
    if draft:  # the target as its own draft: every proposal accepted
        kwargs.update(draft=(trainer, state), draft_k=2)
    return PagedContinuousBatchingEngine(
        trainer, state, num_slots=SERVER["num_slots"],
        block_size=SERVER["kv_block_size"], **kwargs)


def _req(prompt, new):
    return ServingRequest([int(t) for t in prompt], new)


def _host(tree):
    # copies: on the CPU `np.asarray` of a jax array is a view of its
    # buffer, and a buffer that numpy holds is not donated
    return jax.tree.map(np.array, tree)


def _leaves(tree):
    return jax.tree.leaves(tree)


def _same(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------ (a) site by site


class _Spy(object):
    """Stands in for kv_pool.run_inplace: before each pool-updating
    call, runs the same program WITHOUT donation on a copy of every
    argument, then lets the real call through and keeps both."""

    def __init__(self):
        self.calls = []
        self._twins = {}
        self._real = kv_pool.run_inplace

    def __call__(self, program, pools, *args, **kwargs):
        twin = self._twins.get(program)
        if twin is None:
            twin = self._twins[program] = jax.jit(
                program.__wrapped__, static_argnames=tuple(kwargs))
        copies = jax.tree.map(
            lambda x: jnp.array(x) if isinstance(x, jax.Array) else x,
            (pools, args))
        want = twin(copies[0], *copies[1], **kwargs)
        want = want[0] if isinstance(want, tuple) else want
        call = {"name": program.__name__, "pools": pools, "args": args,
                "before": _host(pools), "want": _host(want)}
        out = self._real(program, pools, *args, **kwargs)
        call["out"] = out[0] if isinstance(out, tuple) else out
        call["got"] = _host(call["out"])
        self.calls.append(call)
        return out


def _site_prompt_write():
    eng = _engine(share_prefix=False)
    return eng, lambda: eng.insert(_req(range(1, 41), 6))


def _site_paged_step():
    eng = _engine()
    eng.insert(_req(range(1, 41), 6))
    eng.insert(_req(range(50, 60), 6))
    return eng, eng.step


def _site_suffix_prefill():
    eng = _engine()
    eng.insert(_req(range(1, 41), 6))
    return eng, lambda: eng.insert(
        _req(list(range(1, 33)) + [5, 6, 7], 6))


def _site_cow_copy():
    eng = _engine()
    eng.insert(_req(range(1, 33), 6))
    # the same two full blocks: a full-prompt match, whose re-run of
    # the last token faults the shared tail block
    return eng, lambda: eng.insert(_req(range(1, 33), 6))


def _site_prefill_tile():
    eng = _engine(share_prefix=False, prefill_chunk_tokens=16)

    def act():
        job = eng.begin_insert(_req(range(1, 41), 6))
        while not eng.advance_prefill(job):
            pass
        assert job.tiles == 3

    return eng, act


def _site_revive_upload():
    eng = _engine(num_blocks=4, host_bytes=1 << 24)
    for prompt in (range(1, 33), range(40, 88)):  # the second evicts
        eng.insert(_req(prompt, 2))
        while eng.active_count():
            eng.step()
    assert eng.kv.allocator.num_spilled() == 2
    return eng, lambda: eng.insert(_req(range(1, 33), 2))


def _site_spec_step():
    eng = _engine(draft=True)
    eng.insert(_req(range(1, 21), 8))
    return eng, eng.step


def _site_draft_slot_write():
    eng = _engine(draft=True)
    return eng, lambda: eng.insert(_req(range(1, 21), 8))


#: site -> (the program's name, its set-up, how many leading arguments
#: are pool trees). Seven sites take the arenas; the draft's slot write
#: takes the draft's dense pool alone
SITES = {
    "prompt_write": ("write_prompt_block", _site_prompt_write, 1),
    "paged_step": ("step", _site_paged_step, 1),
    "suffix_prefill": ("fn", _site_suffix_prefill, 1),
    "prefill_tile": ("fn", _site_prefill_tile, 1),
    "cow_copy": ("copy_block", _site_cow_copy, 1),
    "revive_upload": ("upload", _site_revive_upload, 1),
    "spec_step": ("step", _site_spec_step, 2),
    "draft_slot_write": ("write", _site_draft_slot_write, 0),
}


@pytest.mark.parametrize("site", sorted(SITES))
def test_site_consumes_its_pool_and_writes_what_the_undonated_does(
        site, monkeypatch):
    name, setup, n_pools = SITES[site]
    eng, act = setup()
    spy = _Spy()
    monkeypatch.setattr(kv_pool, "run_inplace", spy)
    act()
    calls = [c for c in spy.calls if c["name"] == name]
    assert calls, [c["name"] for c in spy.calls]
    blocks_written = set()
    for c in calls:
        # the trees that went in are gone: every leaf that holds rows
        # (a position counter the program returns untouched, or never
        # reads, is handed straight back by jit)
        donated = (c["pools"],) + tuple(c["args"][:max(0, n_pools - 1)])
        rows = [leaf for leaf in _leaves(donated) if leaf.ndim >= 4]
        assert rows and all(leaf.is_deleted() for leaf in rows)
        # and what came out is what the undonated program writes
        _same(c["got"], c["want"])
        if n_pools:
            for was, now in zip(_leaves(c["before"]), _leaves(c["got"])):
                if now.ndim == 4:
                    moved = (was != now).reshape(len(was), -1).any(1)
                    blocks_written.update(np.flatnonzero(moved).tolist())
    if n_pools:
        # rows landed, and only in blocks some slot's table holds
        held = {b for s in range(eng.num_slots)
                for b in eng.kv.allocator.table(s)}
        assert blocks_written and blocks_written <= held
        bound = eng.kv.pools
        last = [c for c in spy.calls if c["name"] != "write"][-1]
    else:
        bound, last = eng._d_pool, calls[-1]
    # the engine holds the tree the last call returned, alive
    assert all(a is b for a, b in zip(_leaves(bound),
                                      _leaves(last["out"])))
    assert not any(leaf.is_deleted() for leaf in _leaves(bound))
    if n_pools == 2:
        assert not any(leaf.is_deleted()
                       for leaf in _leaves(eng._d_pool))


# ------------------------------------- (b), (c) whole greedy requests

VARIANTS = {
    "shared": {},
    "private": {"share_prefix": False},
    "spill": {"num_blocks": 4, "host_bytes": 1 << 24},
    "draft": {"draft": True},
    "int8": {"kv_cache_dtype": "int8"},
}
WORKLOAD = (
    (range(1, 41), 6),
    (list(range(1, 33)) + [5, 6, 7], 5),  # shares two blocks
    (range(1, 33), 4),                    # full-prompt match: CoW
    (range(40, 88), 3),                   # the spill variant's evictor
    (range(1, 41), 6),                    # seats on what is cached
)


def _serve(eng):
    """Every request of WORKLOAD, greedy, seated as soon as a slot and
    its blocks are free; the streams, in order."""
    reqs = [_req(p, n) for p, n in WORKLOAD]
    for r in reqs:
        while not (eng.free_slots() and eng.can_seat(r)):
            assert eng.step()
        eng.insert(r)
        eng.step()
    while eng.active_count():
        eng.step()
    return [list(r.generated) for r in reqs]


_TRACKED_JIT = runtime_health.tracked_jit


def _undonated_jit(fn, name, sentry, **jit_kwargs):
    jit_kwargs.pop("donate_argnums", None)
    return _TRACKED_JIT(fn, name, sentry, **jit_kwargs)


@functools.lru_cache(maxsize=None)
def _runs(variant):
    """The workload through the programs as they are and through the
    same programs with `donate_argnums` struck: streams, the two pool
    counters over each run, the engine that donated."""
    out = {}
    for side in ("donated", "undonated"):
        patch = _undonated_jit if side == "undonated" else _TRACKED_JIT
        with mock.patch.object(engine_mod, "tracked_jit", patch), \
                mock.patch.object(runtime_health, "tracked_jit", patch):
            eng = _engine(**VARIANTS[variant])
            before = tracing.recorder().counts()
            streams = _serve(eng)
            after = tracing.recorder().counts()
        out[side] = {
            "streams": streams, "engine": eng,
            "launches": after["pool.launches"] - before["pool.launches"],
            "inplace": (after["pool.inplace_launches"]
                        - before["pool.inplace_launches"]),
        }
    return out


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_greedy_streams_equal_those_of_undonated_programs(variant):
    runs = _runs(variant)
    streams = runs["donated"]["streams"]
    assert [len(s) for s in streams] == [n for _, n in WORKLOAD]
    assert streams == runs["undonated"]["streams"]
    # and the variant's own machinery was in the run
    eng = runs["donated"]["engine"]
    stats = eng.kv_stats()
    if variant == "private":
        assert stats["prefix_hit_tokens"] == 0
    else:
        assert stats["prefix_hit_tokens"] > 0
    if variant == "shared":
        assert stats["cow_copies"] > 0
    if variant == "spill":
        assert stats["revive_uploads"] > 0
    if variant == "draft":
        assert eng.draft_accepted > 0
    if variant == "int8":
        assert stats["kv_cache_dtype"] == "int8"


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_every_pool_launch_of_a_run_was_in_place(variant):
    runs = _runs(variant)
    assert runs["donated"]["launches"] > len(WORKLOAD)
    assert runs["donated"]["inplace"] == runs["donated"]["launches"]
    # the counter tells the two apart: same launches, none in place
    assert runs["undonated"]["launches"] == runs["donated"]["launches"]
    assert runs["undonated"]["inplace"] == 0


# ------------------------------------- (d) a donating call that raises


def _consume_then_raise(pools, *_args, **_kwargs):
    for leaf in _leaves(pools):
        leaf.delete()  # what a donation that got as far as running does
    raise RuntimeError("device fault")


def _seated_engine():
    eng, req = _engine(), _req(range(1, 41), 6)
    eng.insert(req)
    eng.step()
    return eng, req


@pytest.mark.parametrize("next_use", ["step", "insert", "export"])
def test_pool_lost_is_raised_then_and_at_every_later_use(next_use):
    eng, _ = _seated_engine()
    step_fn = eng._step_fn
    eng._step_fn = _consume_then_raise
    with pytest.raises(KVPoolLost, match="device fault"):
        eng.step()
    assert eng.kv.pools is None  # nothing points at deleted arenas
    eng._step_fn = step_fn
    use = {
        "step": eng.step,
        "insert": lambda: eng.insert(_req(range(50, 70), 4)),
        "export": lambda: eng.kv.export_chain(list(range(1, 41))),
    }[next_use]
    with pytest.raises(KVPoolLost, match="serves no more"):
        use()
    # shapes and sizes are still there for who reports them
    assert eng.kv_stats()["kv_bytes_total"] == eng.kv.bytes_total
    assert eng.kv.leaf_dtypes() and eng.kv.row_shapes


def test_a_call_that_raises_before_consuming_leaves_the_pool():
    (eng, req), (clean, clean_req) = _seated_engine(), _seated_engine()
    step_fn = eng._step_fn

    def refuses(_pools, *_args):
        raise ValueError("bad shapes")

    eng._step_fn = refuses
    before = tracing.recorder().counts()["pool.launches"]
    with pytest.raises(ValueError, match="bad shapes"):
        eng.step()
    assert tracing.recorder().counts()["pool.launches"] == before
    assert not any(leaf.is_deleted() for leaf in _leaves(eng.kv.pools))
    eng._step_fn = step_fn
    for e in (eng, clean):
        while e.active_count():
            e.step()
    assert len(req.generated) == 6
    assert req.generated == clean_req.generated


def test_scheduler_dies_of_a_lost_pool_and_aborts_its_requests():
    from elasticdl_tpu.serving.server import _Scheduler
    from elasticdl_tpu.serving.telemetry import ServingTelemetry

    eng = _engine()
    queue = RequestQueue(capacity=4, seq_len=eng.seq_len)
    sched = _Scheduler(eng, queue, ServingTelemetry(log_dir=None),
                       idle_wait_secs=0.001)
    seated, queued = _req(range(1, 41), 6), _req(range(50, 70), 4)
    queue.submit(seated)
    sched._iterate()  # seats it, first token, one decode tick
    queue.submit(queued)
    eng.free_slots = lambda: []  # keep the second one queued
    eng._step_fn = _consume_then_raise
    sched.run()  # returns: the loop caught what the step raised
    assert isinstance(sched.crashed, KVPoolLost)
    for req in (seated, queued):
        events = []
        while True:
            ev = req.next_event(timeout=0)
            if ev is None:
                break
            events.append(ev)
        assert events[-1][:2] == ("error", "RESOURCE_EXHAUSTED")
        assert "KVPoolLost" in events[-1][2]


# ---------------------- (e) compiled for a described v5e: nothing runs


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
def cell_programs():
    """Every kind of pool-updating program at the serving cell's own
    widths and pool (8,448 blocks x 16 x 2 x 128), two layers deep,
    over shapes: no weight and no arena exists. At the rehearsal's
    sizes the chip's compiler moves the 64 KB arenas whole into fast
    memory and back (`copy-start` to `S(1)`) and lays them out
    blocks-minor, so "no copy of an arena's shape" says nothing there."""
    from scripts import check_pool_donation as check

    with open(os.path.join(ROOT, "chipbench", "configs",
                           "sc2-3b-serve.json")) as f:
        cfg = json.load(f)
    cfg["model"]["params"]["num_layers"] = 2
    eng, handed_in = check.build_engine(cfg)
    eng.handed_in = handed_in
    return eng, check.programs(eng, tile=16, upload_blocks=4)


@pytest.mark.parametrize("program", [
    "paged_step", "prompt_write", "cow_copy", "suffix_prefill[16]",
    "revive_upload[4]"])
def test_compiled_for_a_v5e_the_program_aliases_its_whole_pool(
        program, one_chip, cell_programs):
    from elasticdl_tpu.ops import dispatch
    from scripts import check_pool_donation as check

    eng, todo = cell_programs
    with mock.patch.object(dispatch, "is_tpu_backend", lambda: True):
        compiled, pools = check.compile_program(eng, todo[program],
                                                one_chip)
    got = kv_pool.pool_aliasing(compiled, pools)
    assert got["pool_bytes"] > eng.kv.bytes_total > 2.7e8
    # every byte of the pool is reused by a result (the zero-d position
    # placeholder takes a 512-byte tile on the chip), and no arena is
    # copied on the way
    assert 0 <= got["alias_bytes"] - got["pool_bytes"] <= 512, got
    assert got["pool_shaped_copies"] == 0, got


@pytest.mark.parametrize("program", ["paged_step", "suffix_prefill[16]"])
def test_compiled_for_a_v5e_the_program_takes_its_weights_in_bf16(
        program, one_chip, cell_programs):
    """The cell computes in bf16: the engine hands each program that
    takes the weights the cast of every kernel, made once a load
    (serving/exec_weights.py). No float32 matrix is an argument, and
    the optimized HLO casts no `[vocab, d]` table to gather 16 rows."""
    from elasticdl_tpu.ops import dispatch
    from scripts import check_pool_donation as check

    eng, todo = cell_programs
    assert todo[program][1][0] is eng._exec_variables
    with mock.patch.object(dispatch, "is_tpu_backend", lambda: True):
        compiled, _pools = check.compile_program(eng, todo[program],
                                                 one_chip)
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "sc2-3b-serve.json")) as f:
        model = json.load(f)["model"]["params"]
    table = (model["vocab_size"], model["embed_dim"])
    got = check.weight_report(eng._exec_variables, compiled.as_text(),
                              table)
    assert got["f32_matrices"] == 0 and got["table_converts"] == 0, got
    # two layers: kernels, MLP biases, head and table in bf16; the
    # five LayerNorms' scales and biases as handed in
    assert got["weight_bytes"] == {
        "bfloat16": 987820032, "float32": 5 * 2 * 3072 * 4}
    # and the report does see a cast table where there is one, and
    # counts the float32 matrices the HLO casts (here the table and the
    # head), not those a program reads as they are (a router)
    hlo = ("%c = bf16[49152,3072]{1,0} convert(f32[49152,3072]{1,0} %p)"
           "\n%d = bf16[3072,49152]{1,0} convert(f32[3072,49152] %q)")
    fp32 = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32),
        eng._exec_variables)
    assert check.weight_report(fp32, hlo, table) == {
        "weight_bytes": {"float32": 2 * 987820032 + 5 * 2 * 3072 * 4},
        "f32_matrices": 2, "table_converts": 1}


def test_at_the_cells_widths_the_decision_is_the_steps_too(
        cell_programs):
    """The engine decides which leaves to serve as a cast from every
    program that takes them, the tick's own among them: at the cell's
    widths, two layers deep, the four kernels and two MLP biases of a
    layer, the head and the table; the five LayerNorms stay."""
    eng, _todo = cell_programs
    handed = jax.tree.leaves(eng.handed_in)
    assert {x.dtype for x in handed} == {jnp.dtype(jnp.float32)}
    assert [fn.__qualname__.split(".")[1] for fn, _args, _where
            in eng._weight_programs(eng.handed_in, None)] == [
        "_prefill_program", "_suffix_prefill_program",
        "_paged_step_program"]
    served = [x.dtype for x in jax.tree.leaves(eng._exec_variables)]
    assert served.count(jnp.dtype(jnp.bfloat16)) == 2 * 6 + 2
    assert served.count(jnp.dtype(jnp.float32)) == 5 * 2
    assert [x.shape for x in handed] == [
        x.shape for x in jax.tree.leaves(eng._exec_variables)]


# -------- (f) a pool with per-slot state leaves, at the nm3n cell's widths


@pytest.fixture(scope="module")
def state_programs():
    """The pool-updating programs of a model with state-space layers
    (chipbench/configs/nm3n-30b-serve.json: its widths, 32 slots, its
    pool), the first six layers `MEMEM*` deep, over shapes: the decode
    step, the prompt's block write and the seating's state write."""
    from scripts import check_pool_donation as check

    with open(os.path.join(ROOT, "chipbench", "configs",
                           "nm3n-30b-serve.json")) as f:
        cfg = json.load(f)
    params = cfg["model"]["params"]
    params.update(num_layers=6, layer_kinds=params["layer_kinds"][:6],
                  rope_layout=params["rope_layout"][:6])
    eng, _handed_in = check.build_engine(cfg)
    return eng, check.programs(eng, tile=16, upload_blocks=4)


@pytest.mark.parametrize("program", ["paged_step", "prompt_write",
                                     "state_write"])
def test_a_pool_with_state_leaves_is_aliased_whole_on_a_v5e_too(
        program, one_chip, state_programs):
    from elasticdl_tpu.ops import dispatch
    from scripts import check_pool_donation as check

    eng, todo = state_programs
    assert sorted(todo) == ["paged_step", "prompt_write", "state_write"]
    with mock.patch.object(dispatch, "is_tpu_backend", lambda: True):
        compiled, pools = check.compile_program(eng, todo[program],
                                                one_chip)
    got = kv_pool.pool_aliasing(compiled, pools)
    # three Mamba-2 layers' float32 state and tails for 32 slots beside
    # one attention layer's arenas: the state is most of the pool
    assert eng.kv.state_bytes == 32 * 3 * (64 * 64 * 128 * 4
                                           + 3 * 6144 * 2)
    assert eng.kv.bytes_total == 2 * 2688 * 16 * 2 * 128 * 2
    assert got["pool_bytes"] >= eng.kv.state_bytes + eng.kv.bytes_total
    assert 0 <= got["alias_bytes"] - got["pool_bytes"] <= 512, got
    assert got["pool_shaped_copies"] == 0, got
    if program == "paged_step":
        hlo = compiled.as_text()
        # both new kernels are in the step, under their names, one a
        # layer, and the state update writes over its input
        assert hlo.count("ssm_state_update/pallas_call") >= 3
        assert hlo.count("moe_expert_tiles/pallas_call") >= 2
        assert "output_to_operand_aliasing={{1}: (0, {})}" in hlo
        # no expert bank is copied to suit the kernel (a [d, 1856] bank
        # would be: ops/expert_ffn.py)
        assert not re.search(
            r"= bf16\[32,(2688,1856|1856,2688)\]\S* copy\(", hlo)
