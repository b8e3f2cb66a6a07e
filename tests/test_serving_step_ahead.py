"""The decode loop runs one step ahead, and serves what the in-line
order serves (tier-1; serving/engine.py, ONE STEP IN FLIGHT).

A seeded run of the real scheduler, driven tick by tick on a hand-held
clock, over three tiny models: the dense `transformer_lm`, the
SmallThinker stack (layers of two kinds, experts) and the Nemotron-H
stack (a per-slot state carried by the step in place). The run has
seatings into used slots, releases, rows that grow by a block, a
deadline that evicts a lane while its step is in flight, a hot reload
between a launch and its commit and, where the model allows them,
chunked prefill tiles between decode ticks. It is made twice: with
`engine.step()` as it is (launch the next step, then fetch the older),
and with every step launched and committed in line. Every request
streams the same tokens in both, chunk for chunk one token a tick.

Then the server's ends: a scheduler that empties, and one told to
drain, stream the last token of every lane though the lane was freed a
tick earlier; and what reads or updates the pool between ticks with a
step in flight (a chain exported and imported, a chain spilled to the
host tier and revived; the copy-on-write case is in
test_serving_resident_lanes.py's churn) is ordered behind that step.
"""

import functools
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import FrozenDict

import test_nemotron_h_stack as nm
import test_smallthinker_stack as st
from chipbench.drivers.open_loop import _unflatten
from elasticdl_tpu.common.model_utils import load_model_spec_from_module
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.serving.admission import RequestQueue, ServingRequest
from elasticdl_tpu.serving.engine import PagedContinuousBatchingEngine
from elasticdl_tpu.serving.server import _Scheduler
from elasticdl_tpu.serving.telemetry import ServingTelemetry
from elasticdl_tpu.training import trainer as trainer_mod
from model_zoo.transformer_lm import transformer_lm as zoo

DENSE = {"vocab_size": 96, "seq_len": 64, "embed_dim": 32, "num_heads": 2,
         "num_layers": 2}


@functools.lru_cache(maxsize=None)
def _rig(kind):
    """(trainer, state, what the model lets the engine do) of a tiny
    model, its weights seeded."""
    if kind == "dense":
        params, leaves = DENSE, None
        options = {"share_prefix": True, "prefill_chunk_tokens": 4}
    elif kind == "smallthinker":
        cfg = st._cfg()
        params = st.PARAMS
        leaves = st.ref.make_leaves(cfg, 0, st.ref.all_leaves(cfg))
        options = {"share_prefix": False, "prefill_chunk_tokens": 4}
    else:  # a state beside the rows: no tiles, no shared prefixes
        params, leaves = nm.PARAMS, nm._leaves()
        options = {"share_prefix": False}
    trainer = trainer_mod.Trainer(
        load_model_spec_from_module(zoo),
        mesh=mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1]),
        model_params="; ".join(
            "%s=%r" % kv for kv in sorted(params.items())), seed=3)
    if leaves is None:
        dummy = np.zeros((1, params["seq_len"]), np.int32)
        state = trainer.init_state(({"tokens": dummy}, dummy))
    else:
        state = trainer_mod.TrainState(
            step=jnp.zeros((), jnp.int32), params=_unflatten(leaves),
            opt_state=(), model_state=FrozenDict({}),
            rng=jax.random.PRNGKey(0))
    return trainer, state, options


def _engine(kind, slots=3, **over):
    trainer, state, options = _rig(kind)
    return PagedContinuousBatchingEngine(
        trainer, state, slots, block_size=4, **dict(options, **over))


def _in_line(eng):
    """`eng.step` as the order before this one: each step launched and
    committed before the call returns, nothing ever in flight."""
    def step():
        assert not eng._flights
        return eng._collect() if eng._launch() else []

    eng.step = step
    return eng


class _Clock(object):
    """Seconds the test moves by hand, plus a little at every reading
    so that a prefill tile is seen to cost something and the budget
    lets one through a tick."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 1e-4
        return self.t


class _Reloads(object):
    """The checkpoint watcher's surface: hands over what it was armed
    with, once."""

    def __init__(self):
        self.armed = None

    def poll(self):
        got, self.armed = self.armed, None
        return got


def _scheduler(eng, clock=None, watcher=None):
    clock = clock or _Clock()
    queue = RequestQueue(capacity=32, seq_len=eng.seq_len, clock=clock)
    sched = _Scheduler(eng, queue, ServingTelemetry(log_dir=None,
                                                    clock=clock),
                       watcher=watcher, idle_wait_secs=0.0, clock=clock,
                       prefill_budget_ms=0.05)
    return sched, queue, clock


def _events(request):
    out = []
    while True:
        event = request.next_event(timeout=0)
        if event is None:
            return out
        out.append(event)


def _seeded_work(clock, vocab=96):
    """[(tick it arrives at, request)]: nine requests of a seeded
    shape, greedy and sampled, one with a deadline it cannot meet; the
    lengths make rows grow by a block mid-decode (blocks of four) and
    three slots serve them, so slots are used again."""
    rng = random.Random(20260101)
    work = []
    for i in range(9):
        prompt = [rng.randrange(vocab) for _ in range(rng.randint(2, 13))]
        new = rng.randint(2, 11)
        sampled = i % 3 == 1
        work.append((i + i // 2, ServingRequest(
            prompt, new, temperature=0.8 if sampled else 0.0,
            seed=rng.randrange(1000) if sampled else 0, clock=clock)))
    doomed = ServingRequest([7, 7, 3, 1, 2], 40, deadline_ms=5500,
                            clock=clock)
    return work[:2] + [(2, doomed)] + work[2:], doomed


def _serve(kind, in_line):
    """One run of the scenario: what every request was streamed, and
    what was seen on the way."""
    trainer, state, _options = _rig(kind)
    eng = _engine(kind)
    if in_line:
        _in_line(eng)
    watcher = _Reloads()
    sched, queue, clock = _scheduler(eng, watcher=watcher)
    work, doomed = _seeded_work(clock)
    seen = {"evicted_in_flight": None, "reloaded_in_flight": None,
            "tiles_in_flight": 0, "ahead": 0}
    evict, reload_, tile = (eng.evict_expired, eng.set_params,
                            eng.advance_prefill)

    def evict_expired(now):
        flying = {st_.request for f in eng._flights
                  for _slot, st_, _last in f.ran}
        out = evict(now)
        if out:
            seen["evicted_in_flight"] = all(r in flying for r in out)
        return out

    def set_params(new_state, version):
        seen["reloaded_in_flight"] = bool(eng._flights)
        return reload_(new_state, version)

    def advance_prefill(job):
        seen["tiles_in_flight"] += bool(eng._flights)
        return tile(job)

    eng.evict_expired, eng.set_params = evict_expired, set_params
    eng.advance_prefill = advance_prefill
    before = tracing.recorder().counts().get("tick.ahead", 0)
    requests = [r for _at, r in work]
    tick = 0
    while tick < 200 and (tick < 14 or eng.active_count() or len(queue)
                          or sched._pending_prefills):
        for at, request in work:
            if at == tick:
                queue.submit(request)
        if tick == 6:
            watcher.armed = (state, 5)  # the same weights, renamed
        clock.t += 1.0
        sched._iterate()
        tick += 1
    assert tick < 200 and not eng._flights and eng._landing == 0
    seen["ahead"] = tracing.recorder().counts()["tick.ahead"] - before
    seen["blocks_in_use"] = eng.kv.allocator.blocks_in_use()
    return [(r, _events(r)) for r in requests], doomed, seen


@pytest.mark.parametrize("kind", ["dense", "smallthinker", "nemotron_h"])
def test_a_seeded_server_run_streams_what_the_in_line_order_streams(kind):
    ahead, doomed_a, seen_a = _serve(kind, in_line=False)
    inline, doomed_i, seen_i = _serve(kind, in_line=True)
    # the scenario happened: the doomed lane's step was in flight when
    # its deadline evicted it, a step was in flight across the reload,
    # and (where the model allows tiles) across prefill tiles; in line
    # nothing ever is
    assert seen_a["evicted_in_flight"] is True
    assert seen_a["reloaded_in_flight"] is True
    assert seen_i["reloaded_in_flight"] is False
    assert seen_i["tiles_in_flight"] == 0 == seen_i["ahead"]
    if kind != "nemotron_h":
        assert seen_a["tiles_in_flight"] >= 3
    assert seen_a["ahead"] >= 20
    assert seen_a["blocks_in_use"] == 0 == seen_i["blocks_in_use"]
    for (ra, ea), (ri, ei) in zip(ahead, inline):
        assert ra.prompt == ri.prompt
        if ra is doomed_a:
            # evicted at the same tick with the same tokens streamed:
            # the step that was in flight gives the lane nothing, as
            # the step the in-line order had not yet launched
            assert ea[-1][:2] == ("error", "DEADLINE_EXCEEDED")
            assert 2 <= len(ra.generated) < ra.max_new_tokens
            assert ra.generated == ri.generated and ea == ei
            continue
        assert ra.generated == ri.generated, ra.prompt
        assert len(ra.generated) == ra.max_new_tokens
        # streamed as committed: the first token, then one a tick, then
        # `done`, every chunk the tail of what was generated so far
        tokens = [t for e in ea if e[0] == "tokens" for t in e[1]]
        assert tokens == ra.generated
        assert [e[0] for e in ea] == ["tokens"] * len(tokens) + ["done"]
        assert [e[:2] for e in ea] == [e[:2] for e in ei]
        # the version a chunk carries is that of the weights its step
        # was launched under: never newer in the run-ahead order
        assert all(va[2] <= vi[2] for va, vi in zip(ea, ei)
                   if va[0] == "tokens")
    versions = {e[2] for _r, events in ahead for e in events
                if e[0] == "tokens"}
    assert versions == {0, 5}


# ------------------------------------------------------ the server's ends


def _submit(queue, clock, specs):
    out = [ServingRequest(p, n, clock=clock) for p, n in specs]
    for r in out:
        queue.submit(r)
    return out


def _alone(kind, request):
    """The request's tokens with a one-slot server to itself, every
    step in line."""
    eng = _in_line(_engine(kind, slots=1))
    twin = ServingRequest(request.prompt, request.max_new_tokens,
                          temperature=request.temperature,
                          seed=request.seed)
    eng.insert(twin)
    while eng.active_count():
        eng.step()
    return twin.generated


def test_an_emptying_server_streams_the_last_token_of_every_lane():
    eng = _engine("dense", share_prefix=False, prefill_chunk_tokens=0)
    sched, queue, clock = _scheduler(eng)
    reqs = _submit(queue, clock, [([1, 2, 3], 5), ([4, 5], 2), ([6], 3)])
    ticks = 0
    while eng.active_count() or len(queue):
        sched._iterate()
        ticks += 1
        # freed at their last launch, the lanes stay active until
        # their last token is committed and streamed
        seated = sum(s is not None for s in eng._slots)
        assert eng.active_count() == seated + eng._landing
    assert ticks == 4  # the longest: one seating tick + three more
    for r in reqs:
        events = _events(r)
        assert [e[0] for e in events] == (
            ["tokens"] * r.max_new_tokens + ["done"])
        assert r.generated == _alone("dense", r)
    # a tick's root span says what it left SEATED (what the benchmark's
    # holds are read from): a lane whose last token is in flight holds
    # no slot, though it keeps the scheduler ticking
    roots = [p for p in tracing.recorder().phases() if p.name == "tick"]
    assert [p.attrs["active"] for p in roots[-4:]] == [1, 1, 0, 0]
    sched._iterate()  # and then it idles
    assert tracing.recorder().phases()[-2].name == "idle"


def test_a_draining_shutdown_streams_the_last_token_of_every_lane():
    eng = _engine("nemotron_h")
    sched, queue, clock = _scheduler(eng)
    reqs = _submit(queue, clock, [([9, 8, 7, 6, 5], 6), ([1, 2], 9),
                                  ([3], 4)])
    queued = ServingRequest([5, 5], 3, clock=clock)
    sched._iterate()
    sched._iterate()
    queue.submit(queued)
    assert eng._flights and eng.active_count() == 3
    sched.stop(drain=True)
    sched._shutdown()
    assert not eng._flights and eng.active_count() == 0
    for r in reqs:
        events = _events(r)
        assert [e[0] for e in events] == (
            ["tokens"] * r.max_new_tokens + ["done"])
        assert r.generated == _alone("nemotron_h", r)
    assert _events(queued)[-1][:2] == ("error", "RESOURCE_EXHAUSTED")


def test_an_abort_reaches_a_lane_whose_last_token_is_in_flight():
    eng = _engine("dense", share_prefix=False, prefill_chunk_tokens=0)
    sched, queue, clock = _scheduler(eng)
    short, long_ = _submit(queue, clock, [([1, 2, 3], 3), ([4, 5], 9)])
    sched._iterate()  # the short lane's last step is launched: freed
    assert eng._slots[0] is None and short in eng.active_requests()
    sched.stop(drain=False)
    sched._shutdown()
    for r in (short, long_):
        assert _events(r)[-1][:2] == ("error", "RESOURCE_EXHAUSTED")


# ------------------------- the pool between ticks, with a step in flight


def test_a_chain_is_exported_and_imported_behind_the_step_in_flight():
    src, dst = _engine("dense"), _engine("dense")
    busy = [ServingRequest([2, 9, 4], 12, temperature=0.7, seed=i + 1)
            for i in range(2)]
    for eng, request in zip((src, dst), busy):
        eng.insert(request)
        eng.step()
        eng.step()
    prompt = [11, 12, 13, 14, 21, 22, 23, 24, 31]  # two full blocks
    warm = ServingRequest(prompt, 1, prefill_only=True)
    assert src._flights and src.insert(warm)[2]
    chain = src.kv.export_chain(prompt)  # reads the pool: new arrays
    assert len(chain) == 2 and src._flights
    src.step()
    assert dst._flights
    assert dst.kv.import_chain(chain, leaf_dtypes=dst.kv.leaf_dtypes()) == (
        2, 8)
    hits = dst.kv.allocator.prefix_hit_tokens
    asked = ServingRequest(prompt, 6)
    dst.insert(asked)  # seats on the imported blocks: a suffix tile
    assert dst.kv.allocator.prefix_hit_tokens - hits == 8
    for eng in (src, dst):
        while eng.active_count():
            eng.step()
    assert asked.generated == _alone("dense", asked)
    for request in busy:
        assert request.generated == _alone("dense", request)


def test_a_chain_spills_to_the_host_and_is_revived_behind_the_step_in_flight():
    # eight blocks of four: the first prompt's two full blocks park in
    # the cache when it ends; a lane reserves two and a long prompt
    # needs six at once, so its seating takes a cached one back (a
    # spill: a gather out of the pool) while the lane's step is in
    # flight, and the first prompt, asked again, seats by upload
    eng = _engine("dense", slots=3, num_blocks=8, host_bytes=1 << 20)
    first = ServingRequest([1, 2, 3, 4, 5, 6, 7, 8, 9], 2)
    eng.insert(first)
    while eng.active_count():
        eng.step()
    cached = eng.kv.allocator.num_cached()
    assert cached == 2
    busy = ServingRequest([3, 3, 3], 5, temperature=0.9, seed=5)
    eng.insert(busy)
    eng.step()
    long_ = ServingRequest(list(range(20, 41)), 2)
    assert eng._flights and eng.can_seat(long_)
    eng.insert(long_)
    assert eng.kv.allocator.num_spilled() >= 1
    while long_ in eng.active_requests():
        eng.step()
    again = ServingRequest(first.prompt, 4)
    before = eng.kv.stats()["revive_uploads"]
    other = ServingRequest([8, 8], 6)
    eng.insert(other)
    eng.step()
    assert eng._flights
    eng.insert(again)
    assert eng.kv.stats()["revive_uploads"] > before
    while eng.active_count():
        eng.step()
    assert again.generated[:2] == first.generated
    assert again.generated == _alone("dense", again)
    assert long_.generated == _alone("dense", long_)
    assert busy.generated == _alone("dense", busy)
    assert other.generated == _alone("dense", other)
    assert eng.kv.allocator.blocks_in_use() == 0
