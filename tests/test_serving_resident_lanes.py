"""The decode tick keeps its lane state on the device, and one step in
flight (tier-1).

Block tables, positions, last tokens, seeds and temperatures are one
array the step takes and hands back advanced (serving/engine.py, LANE
STATE); the host's numpy mirror stays the book and a launch sends it
only when the host changed it (`_tick_lanes`). `engine.step()` launches
step n+1 before it fetches step n's tokens (ONE STEP IN FLIGHT), so
the book moves on at the LAUNCH and the mirror's token column says
`_KEEP` wherever the host has not seen the token. Shown here on a toy
model:

* through a churn of seatings, block-boundary crossings, completions,
  a deadline eviction with the lane's step in flight, a chunked
  prefill finishing between decode ticks and a shared-prefix seating
  with copy-on-write, every launch runs on exactly the book, each
  seated lane holding the token the offline oracle has at its
  position (the host never having sent it), and the streamed tokens
  are the oracle's, greedy and sampled;
* from the second call on a step is dispatched before the older one is
  fetched, what a call commits is what the call before launched, and
  `tick.ahead` / `tick.transfers` are counted once a launch;
* a launch whose lanes did not change makes no host-to-device transfer
  (and passes with explicit transfers disallowed too), a launch after
  a seating, after a release and one that grows a block make exactly
  one, ahead of the fetch all the same;
* a lane is freed at the launch of its last step: it is never launched
  again, `ensure_blocks` is never asked past its reservation, its
  blocks are released once, and its slot can be seated before its last
  token is committed;
* a lane evicted with its step in flight gets none of that step's
  tokens; a hot reload between launch and commit tags the tokens with
  the version that made them;
* after a launch that raises, with one in flight, the device's state
  is not trusted: what was in flight is committed, the next launch
  sends every lane with its real token, and the tokens are still the
  oracle's;
* the speculative tick stays in line, one transfer a tick, and still
  streams its oracle's tokens;
* the pool says when a row of its tables was written.
"""

import functools

import jax
import numpy as np
import pytest

from elasticdl_tpu.api.generation import autoregressive_generate
from elasticdl_tpu.common.model_utils import get_model_spec
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.serving import engine as engine_mod
from elasticdl_tpu.serving.admission import ServingRequest
from elasticdl_tpu.serving.engine import (
    PagedContinuousBatchingEngine,
    lane_fields,
)
from elasticdl_tpu.training.trainer import Trainer

SLOTS, BLOCK, SEQ = 4, 4, 64


@functools.lru_cache(maxsize=None)
def _rig(seed=0):
    trainer = Trainer(
        get_model_spec("model_zoo",
                       "transformer_lm.transformer_lm.custom_model"),
        mesh=mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1]),
        model_params="vocab_size=32; seq_len=%d; embed_dim=32; "
                     "num_heads=2; num_layers=2" % SEQ, seed=seed,
    )
    dummy = np.zeros((1, SEQ), np.int32)
    return trainer, trainer.init_state(({"tokens": dummy}, dummy))


def _engine(**kwargs):
    trainer, state = _rig()
    return PagedContinuousBatchingEngine(
        trainer, state, num_slots=SLOTS, block_size=BLOCK, **kwargs)


@functools.lru_cache(maxsize=None)
def _oracle_tokens(prompt, new, temperature, seed, weights=0):
    trainer, state = _rig(weights)
    out = np.asarray(autoregressive_generate(
        trainer, state, np.asarray([prompt], np.int32), new,
        temperature=temperature, seed=seed, use_cache=True))[0]
    return [int(t) for t in out[len(prompt):]]


def _oracle(request):
    return _oracle_tokens(tuple(request.prompt), request.max_new_tokens,
                          request.temperature, request.seed)


def _count(name):
    return tracing.recorder().counts().get(name, 0)


class _Watched(object):
    """The engine's step program, checked at every launch: the state
    it runs on is the host's book on EVERY lane (tables, positions,
    seeds, temperatures), and each seated lane holds the token the
    oracle has at its position, which the host may never have seen:
    its step may still be in flight."""

    def __init__(self, eng):
        self.eng, self.launches, self.kept = eng, 0, 0
        self.real = eng._step_fn or eng._build_paged_step()
        eng._step_fn = self

    def __call__(self, pools, variables, lanes):
        eng = self.eng
        got = lane_fields(np.asarray(lanes))
        book = engine_mod.Lanes(eng.kv.tables, eng._positions, None,
                                eng._seeds, eng._temps)
        for name, have, want in zip(got._fields, got, book):
            if want is not None:
                np.testing.assert_array_equal(have, want, err_msg=name)
        for slot, st in enumerate(eng._slots):
            if st is None:
                continue
            r = st.request
            at = int(eng._positions[slot]) - len(r.prompt)
            assert got.last_tokens[slot] == _oracle(r)[at], (slot, at)
            # more often than not the host could not have sent it
            self.kept += len(r.generated) <= at
        self.launches += 1
        return self.real(pools, variables, lanes)


def _tick(eng):
    """One call of step(), then: one step is in flight while a lane
    is seated, the book is one launch ahead of what is committed, and
    a free lane sits at position 0 with an all-(-1) row."""
    out = eng.step()
    seated = [s for s in eng._slots if s is not None]
    assert len(eng._flights) == (1 if seated or eng._landing else 0)
    assert eng._lanes is not None
    for slot, st in enumerate(eng._slots):
        if st is not None:
            r = st.request
            assert eng._positions[slot] == len(r.prompt) + len(r.generated)
    for slot in eng.free_slots():
        assert eng._positions[slot] == 0
        assert (eng.kv.tables[slot] == -1).all()
    for _slot, request, tokens, _finished in out:
        assert request.generated[-len(tokens):] == tokens
    return out


def _transfers(eng, tick=_tick):
    """The host-to-device transfers one call's launches count."""
    before = _count("tick.transfers")
    tick(eng)
    return _count("tick.transfers") - before


# ------------------------------------------------------------ the churn


def test_through_a_churn_every_launch_runs_on_the_book_and_streams_the_oracle():
    now = [0.0]
    eng = _engine(share_prefix=True, prefill_chunk_tokens=4)
    watch = _Watched(eng)
    before = _count("tick.transfers")
    # A: greedy, six prompt tokens: its third launch writes position 8,
    # the first row of a block it does not have yet
    a = ServingRequest([5, 6, 7, 8, 9, 10], 9)
    eng.insert(a)
    # the first call launches twice: the mirror, then nothing
    assert _transfers(eng) == 1
    assert not eng.kv.tables_dirty
    assert _transfers(eng) == 1  # grew a block
    assert _transfers(eng) == 0
    # B: sampled; C: a deadline it will not meet
    b = ServingRequest([3, 1, 2], 7, temperature=0.8, seed=11)
    c = ServingRequest([9, 9, 4, 2, 7], 30, deadline_ms=50,
                       clock=lambda: now[0])
    eng.insert(b)
    eng.insert(c)
    _tick(eng)
    # D: sampled, eleven prompt tokens in tiles of four, decode ticks
    # between its tiles (its row is seated, its position still 0) and
    # a step in flight while each tile runs
    d = ServingRequest(list(range(1, 12)), 6, temperature=1.3, seed=5)
    job = eng.begin_insert(d)
    while not job.done():
        _tick(eng)
        assert eng._flights
        eng.advance_prefill(job)
    _tick(eng)
    now[0] = 1.0
    in_flight = [st.request for _slot, st, _last in eng._flights[0].ran]
    assert c in in_flight
    assert eng.evict_expired(now[0]) == [c]
    given = len(c.generated)
    assert 1 < given < c.max_new_tokens
    assert all(r is not c for _s, r, _t, _f in _tick(eng))
    assert len(c.generated) == given  # the step in flight gave it none
    while eng.active_count():
        _tick(eng)  # A, B and D complete
    # E, F: the same two full blocks of prompt: F seats on E's blocks
    # and its last row's re-write copies the shared tail block, with
    # E's step in flight
    e = ServingRequest([4, 3, 2, 1, 8, 7, 6, 5], 5)
    f = ServingRequest(e.prompt, 6, temperature=0.5, seed=3)
    eng.insert(e)
    _tick(eng)
    cows = eng.kv.allocator.cow_copies
    assert eng._flights and eng.begin_insert(f).done()
    assert eng.kv.allocator.cow_copies == cows + 1
    while eng.active_count():
        _tick(eng)
    for request in (a, b, d, e, f):
        assert request.generated == _oracle(request), request.prompt
    assert c.generated == _oracle(c)[:given]
    # every launch was checked, and the mechanism engaged both ways:
    # some launches sent the mirror, most sent nothing, and most ran
    # on tokens the host had not seen
    sent = _count("tick.transfers") - before
    assert 0 < sent < watch.launches
    assert watch.kept > watch.launches // 2


# ---------------------------------------- one step ahead, from the ring


def test_from_the_second_call_on_a_step_is_dispatched_before_the_older_is_fetched():
    eng = _engine(share_prefix=False)
    a = ServingRequest([1, 2, 3, 4, 5], 12)
    b = ServingRequest([7, 8], 9, temperature=0.7, seed=4)
    eng.insert(a)
    eng.insert(b)
    rec = tracing.recorder()
    calls = 0
    while eng.active_count():
        rec.clear_phases()
        older = eng._flights[0] if eng._flights else None
        launched = len(eng._flights)
        seated = [s for s in eng._slots if s is not None]
        out = eng.step()
        calls += 1
        ring = rec.phases()
        names = [p.name for p in ring if p.name.startswith("tick.")
                 and p.parent == ""]
        launch = ["tick.ensure", "tick.upload", "tick.dispatch"]
        if older is None:
            # nothing in flight: launch, launch ahead, collect
            assert names == launch * 2 + ["tick.fetch", "tick.commit"]
            ahead = [0, 1]
        else:
            # what this call commits is what the call before launched
            assert launched == 1
            want = np.asarray(older.tokens)
            assert [(slot, tokens) for slot, _r, tokens, _f in out] == [
                (slot, [int(want[slot])]) for slot, _st, _last in older.ran]
            assert names == (launch if seated else []) + [
                "tick.fetch", "tick.commit"]
            ahead = [1] if seated else []
        by_name = {p.name: p for p in ring}
        if seated:
            assert (by_name["tick.dispatch"].end_ns
                    <= by_name["tick.fetch"].start_ns)
        # the counters, once a launch, each inside its phase
        assert [p.attrs["n"] for p in ring
                if p.name == "tick.ahead"] == ahead
        assert len([p for p in ring
                    if p.name == "tick.transfers"]) == len(ahead)
        assert {p.parent for p in ring if p.name == "tick.ahead"} <= {
            "tick.dispatch"}
        assert {p.parent for p in ring if p.name == "tick.transfers"} <= {
            "tick.upload"}
    # 11 + 8 steps over two lanes: the first call launched two
    assert calls == 11 and not eng._flights
    assert a.generated == _oracle(a) and b.generated == _oracle(b)


# ------------------------------------------------- what a launch transfers


#: refuses `jax.device_put` and `jnp.asarray` of host data as well as
#: an argument converted on the way into a program ("disallow" alone
#: lets the first two through)
GUARD = "disallow_explicit"


def _guard_is_honoured():
    try:
        with jax.transfer_guard_host_to_device(GUARD):
            jax.device_put(np.ones(2, np.int32)).block_until_ready()
    except Exception:
        return True
    return False


def test_a_clean_launch_transfers_nothing_and_a_changed_one_once_ahead_of_the_fetch():
    eng = _engine(share_prefix=False)
    a, b = ServingRequest([1, 2, 3, 4, 5], 13), ServingRequest([7, 8], 7)
    eng.insert(a)
    eng.insert(b)
    # after the seatings: one transfer, the mirror whole; the launch
    # ahead (rows 6 and 3) sends nothing
    assert _transfers(eng) == 1

    def guarded(eng):
        with jax.transfer_guard_host_to_device(GUARD):
            _tick(eng)

    # row 4 of B, then row 8 of A: a row grew by a block, one transfer
    for _grows in (b, a):
        assert _transfers(eng) == 1
    # rows 9 and 6: neither lane needs a block, nothing is sent, and
    # nothing COULD be: the call runs with transfers refused (a
    # backend that does not enforce the guard still counts 0)
    assert _transfers(eng, guarded) == 0
    # a dirty launch goes ahead of the fetch like a clean one: one
    # transfer, counted inside its upload, and no fetch before it
    rec = tracing.recorder()
    rec.clear_phases()
    eng._lanes_dirty = True
    assert _transfers(eng) == 1
    order = [p.name for p in rec.phases()
             if p.name in ("tick.transfers", "tick.dispatch", "tick.fetch")]
    assert order == ["tick.transfers", "tick.dispatch", "tick.fetch"]
    ring = [p for p in rec.phases() if p.name == "tick.transfers"]
    assert {p.parent for p in ring} == {"tick.upload"}
    assert ring[-1].attrs == {"n": 1}
    # that launch (rows 10 and 7) was B's last: B is freed at it, its
    # seventh token still in flight, and the release is owed to the
    # next launch: lane 1's scalars and its row
    assert eng._slots[1] is None and len(b.generated) == 6
    assert eng.active_count() == 2
    assert _transfers(eng) == 1
    assert len(b.generated) == 7 and eng.active_count() == 1
    assert _transfers(eng) == 1  # row 12 of A
    assert _transfers(eng) == 0
    if _guard_is_honoured():
        eng._lanes_dirty = True  # a launch that sends does trip it
        with pytest.raises(Exception, match="[Dd]isallowed"):
            guarded(eng)
    while eng.active_count():
        eng.step()
    assert a.generated == _oracle(a) and b.generated == _oracle(b)


# ------------------------------------------------------ a lane's last step


def test_a_lane_is_freed_at_the_launch_of_its_last_step_and_never_launched_again():
    eng = _engine(share_prefix=False)
    asked, released = [], []
    ensure, release = eng.kv.ensure_blocks, eng.kv.release
    eng.kv.ensure_blocks = lambda slot, pos: (
        asked.append((slot, pos)), ensure(slot, pos))[1]
    eng.kv.release = lambda slot: (
        released.append(slot), release(slot))[1]
    a = ServingRequest([1, 2, 3, 4, 5, 6], 4)  # three steps
    eng.insert(a)
    reserved = len(a.prompt) + a.max_new_tokens - 1  # rows 0..8
    blocks = eng.kv.allocator.blocks_in_use()
    out = _tick(eng)  # launches rows 6 and 7, commits row 6's token
    assert [(t, f) for _s, _r, t, f in out] == [([a.generated[1]], False)]
    out = _tick(eng)  # launches row 8, the last: the lane is freed
    assert eng._slots[0] is None and released == [0]
    assert eng.kv.allocator.blocks_in_use() == 0 < blocks
    assert eng.active_count() == 1 and eng.free_slots()[0] == 0
    assert a in eng.active_requests()
    # its slot is seated again before its last token is committed, and
    # the step in flight still writes its old blocks: the new prompt's
    # rows come after it
    b = ServingRequest([9, 8, 7], 3, temperature=0.6, seed=2)
    assert eng.insert(b)[0] == 0
    out = _tick(eng)  # launches B; commits A's last
    assert [(r, t, f) for _s, r, t, f in out] == [
        (a, [a.generated[-1]], True)]
    assert len(a.generated) == 4
    while eng.active_count():
        out = _tick(eng)
    assert out[-1][1] is b and out[-1][3] is True
    assert a.generated == _oracle(a) and b.generated == _oracle(b)
    # never asked past a reservation, never launched after the last
    assert [pos for slot, pos in asked] == [6, 7, 8, 3, 4]
    assert max(pos for _slot, pos in asked[:3]) == reserved - 1
    assert released == [0, 0]  # once a request
    assert not eng._flights and eng._landing == 0
    assert eng.step() == []


# --------------------------------------- evicted, reloaded while in flight


def test_a_lane_evicted_with_its_step_in_flight_gets_none_of_its_tokens():
    eng = _engine(share_prefix=False)
    a = ServingRequest([1, 2, 3], 10)
    b = ServingRequest([4, 5, 6, 7], 10, temperature=0.9, seed=8)
    eng.insert(a)
    eng.insert(b)
    _tick(eng)
    _tick(eng)
    assert [slot for slot, _st, _last in eng._flights[0].ran] == [0, 1]
    eng.evict(0)
    given = list(a.generated)
    # the slot is seated again at once: the new lane is not the old
    c = ServingRequest([2, 2, 2, 2, 2], 5)
    assert eng.insert(c)[0] == 0
    out = _tick(eng)
    assert [r for _s, r, _t, _f in out] == [b]
    assert a.generated == given == _oracle(a)[:len(given)]
    while eng.active_count():
        _tick(eng)
    assert b.generated == _oracle(b) and c.generated == _oracle(c)


def test_a_reload_between_launch_and_commit_tags_the_tokens_with_their_version():
    trainer, state = _rig()
    _other, newer = _rig(1)
    eng = PagedContinuousBatchingEngine(
        trainer, state, num_slots=SLOTS, block_size=BLOCK,
        share_prefix=False)
    a = ServingRequest([3, 4, 5, 6], 8)
    eng.insert(a)
    _tick(eng)  # two tokens in, the third in flight under version 0
    eng.set_params(newer, 7)
    out = _tick(eng)  # launches the fourth under 7, commits the third
    assert a.model_version == 0 and len(a.generated) == 3
    assert out[0][2] == [_oracle(a)[2]]
    out = _tick(eng)
    assert a.model_version == 7 and len(a.generated) == 4
    while eng.active_count():
        _tick(eng)
    # the old weights' three tokens, then the new weights' over them
    assert a.generated[:3] == _oracle(a)[:3]
    assert a.generated != _oracle(a)


# ------------------------------------------------------ a launch that raises


def test_after_a_launch_that_raises_the_one_in_flight_is_committed_and_every_lane_sent_again():
    eng = _engine(share_prefix=False)
    a = ServingRequest([2, 4, 6, 8, 1], 9, temperature=0.9, seed=2)
    eng.insert(a)
    _tick(eng)
    _tick(eng)  # row 7 launched: the next launch grows a block
    step_fn = eng._step_fn

    def refuses(_pools, *_args):
        raise ValueError("bad shapes")

    eng._step_fn = refuses
    committed = list(a.generated)
    with pytest.raises(ValueError, match="bad shapes"):
        eng.step()
    # nothing on the device is trusted; what was in flight still is,
    # the book stands where the last launch that ran left it, and no
    # token was given or lost
    assert eng._lanes is None and len(eng._flights) == 1
    assert a.generated == committed
    assert eng._positions[0] == len(a.prompt) + len(committed)
    eng._step_fn = step_fn
    watch = _Watched(eng)
    rec = tracing.recorder()
    rec.clear_phases()
    # the next call commits first and then sends the whole mirror with
    # the tokens the host now knows: nothing runs ahead of it
    assert _transfers(eng) == 1
    assert a.generated == committed + [_oracle(a)[len(committed)]]
    assert eng._last_tokens[0] == engine_mod._KEEP  # launched since
    order = [p.name for p in rec.phases() if p.name in (
        "tick.fetch", "tick.dispatch", "tick.ahead")]
    assert order == ["tick.fetch", "tick.ahead", "tick.dispatch"]
    assert [p.attrs["n"] for p in rec.phases()
            if p.name == "tick.ahead"] == [0]
    assert watch.launches == 1 and watch.kept == 0
    assert _transfers(eng) == 0  # and runs ahead again
    assert _count("tick.ahead") and len(eng._flights) == 1
    while eng.active_count():
        _tick(eng)
    assert a.generated == _oracle(a)


# ------------------------------------------------------ the speculative tick


def test_the_speculative_tick_sends_once_a_tick_and_matches_its_oracle():
    trainer, state = _rig()
    eng = _engine(share_prefix=False, draft=(trainer, state), draft_k=2,
                  prefill_chunk_tokens=4)
    a = ServingRequest([5, 6, 7, 8, 9, 10], 9)
    b = ServingRequest([3, 1, 2], 7, temperature=0.8, seed=11)
    d = ServingRequest(list(range(1, 12)), 6)
    eng.insert(a)
    eng.insert(b)
    job = eng.begin_insert(d)
    ahead = _count("tick.ahead")
    while eng.active_count() or not job.done():
        if not job.done():
            eng.advance_prefill(job)
        assert _transfers(eng, lambda e: e.step()) == 1
        # it hands no state back and stays in line
        assert eng._lanes is None and not eng._flights
    assert eng.draft_accepted > 0 and _count("tick.ahead") == ahead
    for request in (a, b, d):
        assert request.generated == _oracle(request), request.prompt


# ------------------------------------------------------- the pool's book


def test_the_pool_says_when_a_row_of_its_tables_was_written():
    kv = _engine(share_prefix=False).kv
    assert not kv.tables_dirty

    def written(change, *args):
        kv.tables_dirty = False  # as the engine does when it sends
        before = kv.tables.copy()
        change(*args)
        assert kv.tables_dirty == bool((kv.tables != before).any())
        return kv.tables_dirty

    assert written(kv.seat, 0, list(range(9)), 20)  # three blocks
    assert written(kv.seat, 1, [1, 2], 20)
    assert not written(kv.ensure_blocks, 0, 11)  # in reach already
    assert written(kv.ensure_blocks, 0, 12)
    assert kv.tables[0, 3] >= 0 and kv.tables[0, 4] == -1
    assert written(kv.release, 1)
    assert (kv.tables[1] == -1).all()
    assert not written(kv.release, 1)  # nothing left to free
