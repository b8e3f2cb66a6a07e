"""The decode tick keeps its lane state on the device (tier-1).

Block tables, positions, last tokens, seeds and temperatures are one
array the step takes and hands back advanced (serving/engine.py, LANE
STATE); the host's numpy mirror stays the book and the tick sends it
only when the host changed it (`_tick_lanes`). Shown here on a toy
model:

* through a churn of seatings, block-boundary crossings, completions,
  a deadline eviction, a chunked prefill finishing between decode
  ticks and a shared-prefix seating with copy-on-write, every step
  runs on exactly the mirror, what it hands back is the mirror again
  on every lane the host has not written since (free lanes at position
  0), and the streamed tokens are the offline oracle's, greedy and
  sampled;
* a tick whose lanes did not change makes no host-to-device transfer
  (and passes with explicit transfers disallowed too, which is more
  than `jax.transfer_guard_host_to_device("disallow")` asks),
  a tick after a seating, after an eviction and one that grows a block
  make exactly one;
* after a step that raises the device's state is not trusted: the next
  tick sends every lane and the tokens are still the oracle's;
* the speculative tick goes through the same helper, one transfer a
  tick, and still streams its oracle's tokens;
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
def _rig():
    trainer = Trainer(
        get_model_spec("model_zoo",
                       "transformer_lm.transformer_lm.custom_model"),
        mesh=mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1]),
        model_params="vocab_size=32; seq_len=%d; embed_dim=32; "
                     "num_heads=2; num_layers=2" % SEQ,
    )
    dummy = np.zeros((1, SEQ), np.int32)
    return trainer, trainer.init_state(({"tokens": dummy}, dummy))


def _engine(**kwargs):
    trainer, state = _rig()
    return PagedContinuousBatchingEngine(
        trainer, state, num_slots=SLOTS, block_size=BLOCK, **kwargs)


def _oracle(request):
    trainer, state = _rig()
    out = np.asarray(autoregressive_generate(
        trainer, state, np.asarray([request.prompt], np.int32),
        request.max_new_tokens, temperature=request.temperature,
        seed=request.seed, use_cache=True))[0]
    return [int(t) for t in out[len(request.prompt):]]


def _mirror(eng):
    return engine_mod.Lanes(eng.kv.tables, eng._positions,
                            eng._last_tokens, eng._seeds, eng._temps)


def _same_lanes(device, mirror, lanes=slice(None)):
    for name, got, want in zip(device._fields, device, mirror):
        np.testing.assert_array_equal(
            np.asarray(got)[lanes], want[lanes], err_msg=name)


class _Watched(object):
    """The engine's step program, checked at every launch: the state
    it runs on is the host's mirror on EVERY lane."""

    def __init__(self, eng):
        self.eng, self.launches = eng, 0
        self.real = eng._step_fn or eng._build_paged_step()
        eng._step_fn = self

    def __call__(self, pools, variables, lanes):
        _same_lanes(lane_fields(np.asarray(lanes)), _mirror(self.eng))
        self.launches += 1
        return self.real(pools, variables, lanes)


def _tick(eng):
    """One decode tick, then: what the step handed back is the mirror
    on every lane the host has not written since (the commit loop
    frees the lanes that finished, and then owes the device the
    mirror), and a free lane sits at position 0 with an all-(-1)
    row."""
    out = eng.step()
    device = lane_fields(np.asarray(eng._lanes))
    freed = {slot for slot, _request, _tokens, finished in out if finished}
    assert (eng._lanes_dirty and eng.kv.tables_dirty) == bool(freed)
    clean = [i for i in range(eng.num_slots) if i not in freed]
    _same_lanes(device, _mirror(eng), clean)
    for slot in eng.free_slots():
        assert eng._positions[slot] == 0
        assert (eng.kv.tables[slot] == -1).all()
    return out


def _transfers(eng, tick=_tick):
    """The host-to-device transfers one tick counts."""
    before = tracing.recorder().counts()["tick.transfers"]
    tick(eng)
    return tracing.recorder().counts()["tick.transfers"] - before


# ------------------------------------------------------------ the churn


def test_through_a_churn_every_step_runs_on_the_mirror_and_streams_the_oracle():
    now = [0.0]
    eng = _engine(share_prefix=True, prefill_chunk_tokens=4)
    watch = _Watched(eng)
    before = tracing.recorder().counts()["tick.transfers"]
    # A: greedy, six prompt tokens: its second tick writes position 8,
    # the first row of a block it does not have yet
    a = ServingRequest([5, 6, 7, 8, 9, 10], 9)
    eng.insert(a)
    assert _transfers(eng) == 1  # the first tick
    assert _transfers(eng) == 0
    assert not eng.kv.tables_dirty
    assert _transfers(eng) == 1  # grew a block
    # B: sampled; C: a deadline it will not meet
    b = ServingRequest([3, 1, 2], 7, temperature=0.8, seed=11)
    c = ServingRequest([9, 9, 4, 2, 7], 30, deadline_ms=50,
                       clock=lambda: now[0])
    eng.insert(b)
    eng.insert(c)
    _tick(eng)
    # D: sampled, eleven prompt tokens in tiles of four, decode ticks
    # between its tiles (its row is seated, its position still 0)
    d = ServingRequest(list(range(1, 12)), 6, temperature=1.3, seed=5)
    job = eng.begin_insert(d)
    while not job.done():
        _tick(eng)
        eng.advance_prefill(job)
    _tick(eng)
    now[0] = 1.0
    assert eng.evict_expired(now[0]) == [c]
    assert 1 < len(c.generated) < c.max_new_tokens
    while eng.active_count():
        _tick(eng)  # A, B and D complete
    # E, F: the same two full blocks of prompt: F seats on E's blocks
    # and its last row's re-write copies the shared tail block
    e = ServingRequest([4, 3, 2, 1, 8, 7, 6, 5], 5)
    f = ServingRequest(e.prompt, 6, temperature=0.5, seed=3)
    eng.insert(e)
    _tick(eng)
    cows = eng.kv.allocator.cow_copies
    assert eng.begin_insert(f).done()
    assert eng.kv.allocator.cow_copies == cows + 1
    while eng.active_count():
        _tick(eng)
    for request in (a, b, d, e, f):
        assert request.generated == _oracle(request), request.prompt
    assert c.generated == _oracle(c)[:len(c.generated)]
    # every launch was checked, and the mechanism engaged both ways
    sent = tracing.recorder().counts()["tick.transfers"] - before
    assert 0 < sent < watch.launches


# ------------------------------------------------- what a tick transfers


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


def test_a_clean_tick_transfers_nothing_and_a_changed_one_once():
    eng = _engine(share_prefix=False)
    a, b = ServingRequest([1, 2, 3, 4, 5], 12), ServingRequest([7, 8], 6)
    eng.insert(a)
    eng.insert(b)
    # after the seatings: one transfer, the mirror whole
    assert _transfers(eng) == 1

    def guarded(eng):
        with jax.transfer_guard_host_to_device(GUARD):
            _tick(eng)

    # rows 6 and 3 are written: neither lane needs a block, nothing is
    # sent, and nothing COULD be: the tick runs with transfers refused
    # (a backend that does not enforce the guard still counts 0)
    assert _transfers(eng, guarded) == 0
    # row 4 of B, then row 8 of A: a row grew by a block, one transfer
    for _grows in (b, a):
        assert _transfers(eng) == 1
    # B's last token comes out of a clean tick; its eviction is owed
    # to the next one: lane 1's scalars and its row
    assert _transfers(eng) == 0
    assert len(b.generated) == 6 and eng._slots[1] is None
    assert _transfers(eng) == 1
    assert _transfers(eng) == 0
    # in the ring the count is an entry inside the tick's upload
    ring = [p for p in tracing.recorder().phases()
            if p.name == "tick.transfers"]
    assert {p.parent for p in ring} == {"tick.upload"}
    assert ring[-1].attrs == {"n": 0}
    if _guard_is_honoured():
        eng._lanes_dirty = True  # a tick that sends does trip it
        with pytest.raises(Exception, match="[Dd]isallowed"):
            guarded(eng)
    while eng.active_count():
        _tick(eng)
    assert a.generated == _oracle(a) and b.generated == _oracle(b)


# ------------------------------------------------------ a step that raises


def test_after_a_step_that_raises_every_lane_is_sent_again():
    eng = _engine(share_prefix=False)
    a = ServingRequest([2, 4, 6, 8], 8, temperature=0.9, seed=2)
    eng.insert(a)
    _tick(eng)
    _tick(eng)  # position 5: the next block is due at 8
    step_fn = eng._step_fn

    def refuses(_pools, *_args):
        raise ValueError("bad shapes")

    eng._step_fn = refuses
    with pytest.raises(ValueError, match="bad shapes"):
        eng.step()
    assert eng._lanes is None  # nothing on the device is trusted
    eng._step_fn = step_fn
    # the state is rebuilt from the mirror, which the failed tick left
    # as it was
    assert not (eng._lanes_dirty or eng.kv.tables_dirty)
    assert _transfers(eng) == 1
    assert _transfers(eng) == 0
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
    while eng.active_count() or not job.done():
        if not job.done():
            eng.advance_prefill(job)
        assert _transfers(eng, lambda e: e.step()) == 1
        assert eng._lanes is None  # it hands no state back
    assert eng.draft_accepted > 0
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
