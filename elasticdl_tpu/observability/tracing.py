"""Request-scoped distributed tracing: spans, the ring-buffer
recorder, and Chrome-trace/Perfetto export.

The context model is deliberately tiny — three ids, all hex strings:

* ``trace_id``        one per REQUEST (or per training task), minted
                      at admission wherever the request first enters
                      the system (router, direct client, or the
                      master handing out a task);
* ``span_id``         one per span;
* ``parent_span_id``  the causal edge. Crossing a process boundary
                      means copying ``(trace_id, span_id)`` into the
                      RPC's trace fields; the receiver starts its span
                      with ``parent_span_id = <sender's span_id>``.

That is enough to reassemble ONE tree per request across any number
of processes and retries: a hedge or a re-dispatch creates SIBLING
spans under the same parent, a mid-stream replica loss shows as a
failed child next to the replacement — causality survives exactly the
hops the router/master elasticity story creates.

Standard span events (attach with ``span.event(name, **attrs)``):
``queued``, ``seated``, ``prefill``, ``first_token``, ``completed``,
``expired``, ``rejected``, ``redispatched``, ``hedged``,
``hedge_win``, ``breaker_trip``, ``shed``, ``fault_injected``,
``fetched``, ``reported``. Nothing enforces the vocabulary — but the
chaos drill's structural assertions and the dump tool's summary key
on these names, so stick to them.

Recording is ALWAYS on and bounded: finished spans land in a
lock-guarded ring buffer (drop-OLDEST on overflow, with a ``dropped``
counter — a traced process can never grow without bound, and the drop
is visible).

TAIL-BASED RETENTION: the ring alone has a forensic blind spot — under
pressure, drop-oldest evicts exactly the traces that explain a latency
spike, because slow requests are by definition OLD by the time anyone
looks. The recorder therefore runs TWO tiers: classifier hooks
(``add_classifier``) judge each finished span — ``True`` moves the
span AND every recorded span of its trace into a separately-bounded
RETAINED tier (and pins later-finishing spans of that trace there
too), ``False`` marks a healthy root that is kept only with
probability ``sample_rate`` (below it, the root and its trace's spans
leave the ring — counted in ``sampled_out``), ``None`` means "not
mine" and falls through to the next hook / the plain ring. The
router installs a hook judging its request roots against the declared
SLO thresholds (RouterConfig.slo_*), the replica one judging `serve`
spans against each request's OWN deadline — retention policy reuses
the thresholds the system already declares, no new config surface.
With no hooks installed, behavior is exactly the PR 6 single ring.

Export to disk happens only when ``EDL_TRACE_DIR`` is set: each process writes ``spans-<service>-<pid>.json`` there
(explicitly via ``flush()`` on clean shutdown, plus an atexit
backstop), and ``python -m elasticdl_tpu.observability.dump`` merges
every per-process export into one Chrome-trace JSON that loads in
Perfetto (ui.perfetto.dev) or chrome://tracing.

PHASE SPANS: below the request there is the loop. `phase(name)` (or
the `begin`/`end` pair) times one host-visible region of a hot loop —
a scheduler tick's upload, dispatch, fetch and bookkeeping, a train
step's batch wait, dispatch and loss fetch — from a CLOSED set of
names (`PHASES`; an unknown name raises). It is always on and never
touches the device: no `block_until_ready`, no second program. Each
phase is recorded twice, on two clocks that agree:

* as ``(name, start_ns, end_ns, seq, parent, trace_id, attrs)`` with
  ``time.time_ns()`` in a SECOND bounded ring of the same recorder
  (`phases()`; its own ``phases_dropped``), so a long window of ticks
  never evicts request spans and request spans never evict phases.
  ``seq`` is the tick or step number (children inherit their
  parent's), ``parent`` the enclosing phase's name on this thread;
* as ``jax.profiler.TraceAnnotation("edl/" + name)`` when jax is
  already imported: with a profiler session running the phase is an
  event of the calling thread in the xplane, beside the device's
  lines; with none it costs a flag check.

THE RING IS COLUMNS (`SpanRecorder`, "phase ring"): an entry is six
64-bit integers at one place of one private anonymous mapping (name,
parent and two attribute keys packed into the first as indexes into
`PHASES + COUNTERS` and a table of keys, then start_ns, end_ns, seq
and the two attributes' values), written by `struct.pack_into` at one
write position under the ring's lock. The mapping is made whole
(``48 * phase_capacity`` bytes) and a page of it is the process's only
once an entry is written there; nothing of it is an object, so the
garbage collector walks none of it. At the default bound of 2^20
entries (over 250 s of the fastest serving cell's 4,160 entries a
second: a benchmark window, the end of its warm-up and its drain) a
full ring is 48 MB, where 2^20 tuples with a dict each were 327 MB and
117 ms of every full collection. Only a trace id, an attribute that is
no 64-bit integer and a phase's third attribute go to a side table
keyed by the entry's number. Entries are sealed as they end, so the end
column is in order to within a lock's wait (`_disorder_ns` is the most
it was ever out by) and `phases(since_ns, until_ns)` searches it;
`phases()` without bounds hands back the list it last built while
nothing has been sealed since.

Every phase end also feeds that name's cumulative log-linear histogram
(`phase_snapshot()`, the ``edl_serving_phase_ms{phase=}`` family): the
ring drops its oldest, a cumulative family cannot. `count(name, n)`
records work done where it happens (`COUNTERS`, closed too) as a
zero-length entry of the same ring, so a reader can cut counts to a
window like phases.

SLOW PHASES AND WHAT LAY BENEATH THEM. One rule, in `_seal`: a phase
that ends having lasted over 0.25 s of its own (less the slow phases
already kept from inside it) and over three times its name's median so
far (no median yet counts as 0) is SLOW; `idle`, `train.checkpoint`,
`train.eval` and the names below are exempt. A slow phase, every ring
entry inside its interval on any thread, and the watcher's samples of
it go to a RETAINED tier of the phase store (`slow_phases()`; 256
records, drop-oldest with ``slow_dropped``) that the ring's wrap
cannot evict; it is logged once, in one line at warning level, and
counted by name (`slow_counts()`, ``edl_serving_slow_phases_total``).
What may lie beneath is recorded always, as phases of its own:

* ``gc`` (`generation`, `collected`): `gc.callbacks`, from "start" to
  "stop" on the thread that collected. A collection under 1 ms, or
  one while no thread has a phase open (it lies beneath nothing),
  writes no entry and feeds `gc_pauses()` (collections, total and
  longest) only; a full collection is an ``edl/gc`` annotation too;
* ``compile`` (`backend` 0 = lowering, 1 = backend compile or the
  cache's read) and the counter ``compile.programs`` (lowerings): one
  `jax.monitoring` duration listener, registered when this module
  first sees jax; the phase is stamped from the duration it is handed,
  when the compile ends, so it carries no annotation;
* ``watch.sample`` and ``watch.late``: `observability/phase_watch.py`.
  Every thread's stack of open phases is readable from any other
  (`open_phases()`), so a watcher thread sees a phase that has not
  ended: it samples the frames of a thread whose innermost open phase
  is already slow (kept in the tier, not the ring) and stamps how late
  it woke itself, with the CPU time the process used meanwhile (the
  whole process stood still, not the device).

Timestamps are ``time.time()`` (wall clock): spans from different
processes must land on one timeline, which monotonic clocks cannot
give across processes. Good enough for the single-host drills this
serves; cross-host skew shifts whole processes, never re-orders one
process's spans.
"""

import atexit
import collections
import gc
import json
import mmap
import os
import random
import struct
import sys
import threading
import time
from collections import deque

from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.observability.histogram import LogLinearHistogram

TRACE_DIR_ENV = "EDL_TRACE_DIR"

_DEFAULT_CAPACITY = 4096
#: the retained tier's own bound (slow/failed traces); deliberately
#: smaller than the ring — retention is for the tail, not a second
#: copy of everything
_DEFAULT_RETAINED_CAPACITY = 2048
#: the phase ring's bound: over 250 s at the fastest serving cell's
#: 4,160 entries a second (a 4.5 ms tick of 16 to 22 entries), so a
#: 51 s window, the end of its warm-up and its drain fit with room for
#: ticks twice as fast; 48 bytes an entry (module docstring, THE RING
#: IS COLUMNS)
_DEFAULT_PHASE_CAPACITY = 1 << 20
#: the retained tier's bound, in slow phases, and the most ring
#: entries one of them keeps from beneath it (the rest are counted)
_SLOW_CAPACITY = 256
_SLOW_BENEATH = 1024
#: the slow rule: over this long, and over this many medians of its name
_SLOW_NS = 250 * 10**6
_SLOW_MEDIANS = 3
#: a collection shorter than this writes no ring entry
_GC_ENTRY_NS = 10**6

#: the closed set of phase names, declared once: `begin` raises on
#: anything else. One line per loop, outermost first.
PHASES = (
    # serving/server.py _Scheduler._iterate
    "tick", "tick.admit", "tick.prefill_tile", "tick.stream", "idle",
    # serving/engine.py step() / _spec_step()
    "tick.ensure", "tick.upload", "tick.dispatch", "tick.fetch",
    "tick.commit",
    # serving/engine.py insert() and friends, serving/kv_pool.py
    "prefill", "prefill_tile", "suffix_tile", "draft", "reload_swap",
    "prompt_write", "state_write", "revive_upload",
    # api/local_executor.py train()
    "train.task_get", "train.next_batch", "train.pad", "train.step",
    "train.loss_fetch", "train.checkpoint", "train.eval",
    "train.task_report",
    # training/trainer.py train_step()
    "trainer.host_prepare", "trainer.dispatch", "trainer.post_tiers",
    # what may lie beneath any of them (module docstring, SLOW PHASES):
    # this module's gc callback and jax.monitoring listener, and
    # observability/phase_watch.py
    "gc", "compile", "watch.sample", "watch.late",
)
#: never slow themselves: waits and work that is long by design, and
#: the causes
_SLOW_EXEMPT = ("idle", "train.checkpoint", "train.eval",
                "gc", "compile", "watch.sample", "watch.late")
#: the closed set of `count` names
COUNTERS = ("prompt_write.launches", "prompt_write.tokens",
            "prompts_prefilled",
            # serving/kv_pool.py write_prompt(): (block, layer) writes
            # NOT made, a prompt block behind a window class's reach of
            # the prompt's end (kv_pool.py, BLOCK CLASSES)
            "prompt_write.blocks_skipped",
            # serving/engine.py step() / _spec_step(), a decode tick:
            # table slots in reach of the lanes' sequences (what the
            # paged kernel streams a layer) of lanes x table width
            "paged.blocks_streamed", "paged.table_slots",
            # the same tick, in blocks x layers: what the pool's block
            # classes HOLD of the rows the seated lanes have written,
            # those of them wholly behind their layer's window (held,
            # never read again), and what one table for every layer
            # would hold of the same lanes (every block in every layer:
            # what `held` is where the pool has one table)
            "kv.blocks_held", "kv.window_dead_blocks", "kv.blocks_whole",
            # serving/kv_pool.py ensure_blocks(): blocks x layers a
            # window class gave back because they fell behind the
            # window of the position being written
            "kv.window_blocks_released",
            # what the model's expert layers sow into "counters" in a
            # decode step (model_zoo/transformer_lm ExpertFFN), handed
            # back behind the tick's tokens and summed over layers:
            # (row, choice) pairs routed / those whose expert is held
            # here / held experts some lane chose / held experts, all
            # of SEATED lanes only (a free lane makes no choice); the
            # rows the layers were handed / those that chose; and the
            # rows of the expert tiles that were computed (live tiles x
            # their height: pairs_held over it is how full they were)
            "moe.pairs_routed", "moe.pairs_held", "moe.experts_hit",
            "moe.expert_slots", "moe.lanes", "moe.lanes_live",
            "moe.tile_rows",
            # serving/kv_pool.py run_inplace(): calls of a program that
            # takes a KV pool and hands one back, and those of them
            # that consumed the pool they were handed (donation: the
            # update ran in place). The ratio is 1.0 or some caller
            # kept the pool from being donated
            "pool.launches", "pool.inplace_launches",
            # serving/engine.py _tick_lanes(), once a launch of the
            # decode step (one a tick; two in the tick that starts
            # with none in flight) inside `tick.upload`:
            # host-to-device transfers the launch made for its lane
            # state (0 when no lane changed since the last launch,
            # else 1)
            "tick.transfers",
            # serving/engine.py _launch(), once a launch inside
            # `tick.dispatch`: 1 when the step was launched while the
            # previous step's tokens were still unfetched (the device
            # goes from one step to the next without the host), else 0.
            # Over ticks: the share that ran ahead; beside
            # tick.transfers, the share of those that sent the mirror
            "tick.ahead",
            # serving/kv_pool.py write_prompt(): launches that seated a
            # prompt's per-sequence state (one for all the state layers
            # of a model that has any)
            "state_write.launches",
            # serving/engine.py step(), a decode tick inside
            # `tick.ensure`: (lane, state layer) updates the step makes
            # (every lane rides every tick, so lanes x state layers)
            # and those of them of seated lanes
            "ssm.lanes", "ssm.lanes_live",
            # serving/engine.py, a block-diffusion model's decode step
            # (BLOCK TICK), handed back behind the tick's tokens like
            # the moe.* ones and counted under `tick.commit`: passes of
            # seated lanes (a lane a tick), those of them commit passes
            # (which reveal nothing and write the block's rows), and
            # positions revealed; and, on the host, blocks handed to
            # their requests
            "diffusion.lane_passes", "diffusion.commit_passes",
            "diffusion.tokens_revealed", "diffusion.blocks_committed",
            # serving/engine.py _load_params(), once a (re)load of a
            # weight tree: the bytes of the tree handed in and of the
            # tree the programs are served, and the leaves replaced by
            # their cast to the compute dtype / kept as handed in
            # (serving/exec_weights.py). bf16 compute over fp32
            # weights: exec / source is about 0.5
            "weights.source_bytes", "weights.exec_bytes",
            "weights.leaves_cast", "weights.leaves_kept",
            # this module's jax.monitoring listener: programs lowered
            # (every jit miss lowers, whether or not the persistent
            # cache then has the executable), each inside the `compile`
            # phase of its lowering
            "compile.programs")

Phase = collections.namedtuple(
    "Phase", "name start_ns end_ns seq parent trace_id attrs")

# how the ring's columns hold an entry (SpanRecorder._seal): a name or a
# parent is its index here, an integer attribute the index of its key
_NAMES = PHASES + COUNTERS
_NAME_INDEX = {name: i for i, name in enumerate(_NAMES)}
_NO_KEY = 255  # no parent; no attribute in this slot
_NO_SEQ = -(1 << 63)  # seq is None
_INT_MAX = 1 << 62
_KEYS = ["n", "active", "queue_depth", "prompt_tokens", "bucket", "blocks",
         "slot", "version", "generation", "collected", "backend", "cpu_ms"]
_KEY_INDEX = {key: i for i, key in enumerate(_KEYS)}
_keys_lock = threading.Lock()
_NO_KEYS = _NO_KEY << 16 | _NO_KEY << 24  # packed: neither slot used
_COUNT_KEYS = _KEY_INDEX["n"] << 16 | _NO_KEY << 24  # packed: {"n": v0}
_PACK = struct.Struct("<6q").pack_into


def _intern_key(key):
    """The index of an attribute key seen for the first time, or
    `_NO_KEY` when the column's 255 are taken."""
    with _keys_lock:
        if key not in _KEY_INDEX and len(_KEYS) < _NO_KEY:
            _KEYS.append(key)
            _KEY_INDEX[key] = len(_KEYS) - 1
        return _KEY_INDEX.get(key, _NO_KEY)


def _build(columns, number, side, since_ns, until_ns):
    """`Phase` tuples from the copied columns (`SpanRecorder._columns`),
    those that overlap [since_ns, until_ns]; `number` is the first
    entry's, for the side tables. The collector is held off meanwhile:
    a million new tuples would start it a thousand times."""
    names, keys, new = _NAMES, _KEYS, tuple.__new__
    out = []
    collecting = gc.isenabled()
    gc.disable()
    try:
        for meta, start, end, seq, v0, v1 in zip(*columns):
            if ((since_ns is None or end >= since_ns)
                    and (until_ns is None or start <= until_ns)):
                pi, k0, k1 = meta >> 8 & 255, meta >> 16 & 255, meta >> 24
                if k0 == _NO_KEY:
                    attrs = {}
                elif k1 == _NO_KEY:
                    attrs = {keys[k0]: v0}
                else:
                    attrs = {keys[k0]: v0, keys[k1]: v1}
                trace_id = ""
                if side is not None and number in side:
                    trace_id, extra = side[number]
                    if extra:
                        attrs.update(extra)
                out.append(new(Phase, (
                    names[meta & 255], start, end,
                    None if seq == _NO_SEQ else seq,
                    "" if pi == _NO_KEY else names[pi], trace_id, attrs)))
            number += 1
    finally:
        if collecting:
            gc.enable()
    return out


def new_trace_id():
    return os.urandom(8).hex()


def new_span_id():
    return os.urandom(8).hex()


class Span(object):
    """One timed operation. Created by ``SpanRecorder.start_span``;
    call ``finish()`` (or use as a context manager) to seal it into
    the recorder's ring. Unfinished spans are never exported.

    Cross-thread use is the NORM here (a serving request's span is
    touched by the gRPC handler thread and the scheduler thread):
    ``event``/``set`` are plain appends/updates — atomic under the
    GIL — and ``finish`` is idempotent under the recorder's lock, so
    a terminal race records the span exactly once."""

    __slots__ = ("name", "trace_id", "span_id", "parent_span_id",
                 "service", "start", "end", "status", "attrs",
                 "events", "_recorder")

    def __init__(self, recorder, name, trace_id, parent_span_id,
                 attrs, start):
        self._recorder = recorder
        self.name = name
        self.trace_id = trace_id
        self.span_id = new_span_id()
        self.parent_span_id = parent_span_id or ""
        self.service = recorder.service
        self.start = start
        self.end = None
        self.status = None
        self.attrs = dict(attrs)
        self.events = []

    def event(self, name, **attrs):
        """Timestamped point annotation inside the span."""
        self.events.append((self._recorder.clock(), name, attrs))
        return self

    def set(self, **attrs):
        self.attrs.update(attrs)
        return self

    def finish(self, status="ok"):
        """Seal the span into the recorder's ring (idempotent: the
        first finish wins; later calls are no-ops)."""
        self._recorder._finish(self, status)
        return self

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, _tb):
        self.finish("ok" if exc_type is None else "error")
        return False

    def to_dict(self):
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_span_id": self.parent_span_id,
            "service": self.service,
            "start": self.start,
            "end": self.end,
            "status": self.status,
            "attrs": self.attrs,
            "events": [
                {"ts": ts, "name": name, "attrs": attrs}
                for ts, name, attrs in list(self.events)
            ],
        }


class SpanRecorder(object):
    """Per-process bounded store of FINISHED spans.

    Memory is bounded by construction: `capacity` spans, drop-oldest
    with a monotone ``dropped`` counter (never drop-newest — the most
    recent spans are the ones a post-incident export wants). All
    mutation under one lock; `start_span` allocates outside it (span
    construction is lock-free), so tracing adds one short critical
    section per REQUEST, not per token."""

    def __init__(self, service="proc", capacity=_DEFAULT_CAPACITY,
                 clock=time.time,
                 retained_capacity=_DEFAULT_RETAINED_CAPACITY,
                 sample_rate=1.0, seed=None,
                 phase_capacity=_DEFAULT_PHASE_CAPACITY):
        self.service = service
        self.capacity = int(capacity)
        self.clock = clock
        self.dropped = 0
        self._lock = threading.Lock()
        self._spans = deque()
        # tail-based retention: verdict hooks + the retained tier
        self.retained_capacity = int(retained_capacity)
        self.retained_dropped = 0
        self.sampled_out = 0
        self.sample_rate = float(sample_rate)
        self._retained = deque()
        self._retained_traces = set()
        self._classifiers = []
        self._rand = random.Random(seed)
        # the phase ring (module docstring, THE RING IS COLUMNS): its
        # own bound, lock and drop count, so neither ring evicts the
        # other. The lock is re-entrant only so that the gc callback
        # can tell when its own thread holds it (`_gc_pause`)
        self.phase_capacity = max(1, int(phase_capacity))
        self._phase_lock = threading.RLock()
        self._reset_phases()

    # ------------------------------------------------------- phase ring

    def _reset_phases(self):
        # the columns, interleaved: entry i is six 64-bit ints at byte
        # 48 * i = packed name / parent / two attribute keys, start_ns,
        # end_ns, seq, two attribute values. Mapped whole and private;
        # a page is the process's only once an entry is written to it
        self._ring = mmap.mmap(
            -1, 48 * self.phase_capacity,
            flags=mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS)
        self._appended = 0  # entries ever sealed: the next one's number
        # by entry number: (trace id, what the columns cannot hold)
        self._side = {}
        # how far out of order the end column ever was: what a search
        # by end has to allow for (`phases`)
        self._max_end = self._disorder_ns = 0
        self._built = (-1, None)  # (entries sealed, the list built then)
        self._phase_hists = {p: LogLinearHistogram() for p in PHASES}
        self._counts = dict.fromkeys(COUNTERS, 0)
        # the retained tier of slow phases, and the watcher's samples of
        # phases that have not ended yet
        self.slow_dropped = 0
        self._slow = deque(maxlen=_SLOW_CAPACITY)
        self._slow_counts = {}
        self._samples = deque(maxlen=_SLOW_CAPACITY)
        self._gc = [0, 0, 0]  # collections, their ns, the longest's
        self._gc_deferred = deque()

    @property
    def phases_dropped(self):
        """Entries the ring's wrap has overwritten."""
        return max(0, self._appended - self.phase_capacity)

    def _held(self):
        """Entries the ring holds."""
        return min(self._appended, self.phase_capacity)

    def _beside(self, trace_id, extra):
        """Keep, for the entry about to be written, what the columns
        cannot hold; what the wrap has left behind goes first."""
        side, gone = self._side, self._appended - self.phase_capacity
        while side:  # numbers only grow, so the oldest is the first
            oldest = next(iter(side))
            if oldest >= gone:
                break
            del side[oldest]
        side[self._appended] = (trace_id, extra)

    def _seal_count(self, name, ni, now_ns, seq, pi, n):
        """Seal one count: the short critical section of `count`."""
        if seq is None:
            seq = _NO_SEQ
        with self._phase_lock:
            at = 48 * (self._appended % self.phase_capacity)
            try:
                _PACK(self._ring, at, ni | pi << 8 | _COUNT_KEYS, now_ns,
                      now_ns, seq, n, 0)
            except struct.error:  # `n` is no 64-bit integer
                self._beside("", {"n": n})
                _PACK(self._ring, at, ni | pi << 8 | _NO_KEYS, now_ns,
                      now_ns, seq, 0, 0)
            self._appended += 1
            if now_ns < self._max_end:
                self._disorder_ns = max(self._disorder_ns,
                                        self._max_end - now_ns)
            else:
                self._max_end = now_ns
            self._counts[name] += n

    def _seal(self, name, ni, start_ns, end_ns, seq, parent, pi, trace_id,
              attrs, own_ns=0):
        """Seal one phase (`ni`, `pi`: the indexes of `name` and
        `parent`): one short critical section. With `own_ns` (the
        phase's length less its slow children's, when that is over the
        slow rule's floor) the rule is applied, and True is returned
        if the phase was kept as slow."""
        if self._gc_deferred:
            self._flush_gc()
        meta = ni | pi << 8 | _NO_KEYS
        v0 = v1 = 0
        extra = None
        if attrs:
            k0 = k1 = _NO_KEY
            for key, value in attrs.items():
                ki = _KEY_INDEX.get(key)
                if ki is None:
                    ki = _intern_key(key)
                if (type(value) is not int or ki == _NO_KEY
                        or not -_INT_MAX < value < _INT_MAX):
                    if extra is None:
                        extra = {}
                    extra[key] = value
                elif k0 == _NO_KEY:
                    k0, v0 = ki, value
                elif k1 == _NO_KEY:
                    k1, v1 = ki, value
                else:
                    if extra is None:
                        extra = {}
                    extra[key] = value
            meta = ni | pi << 8 | k0 << 16 | k1 << 24
        if seq is None:
            seq = _NO_SEQ
        median = None
        with self._phase_lock:
            if trace_id or extra is not None:
                self._beside(trace_id, extra)
            _PACK(self._ring, 48 * (self._appended % self.phase_capacity),
                  meta, start_ns, end_ns, seq, v0, v1)
            self._appended += 1
            if end_ns < self._max_end:
                self._disorder_ns = max(self._disorder_ns,
                                        self._max_end - end_ns)
            else:
                self._max_end = end_ns
            hist = self._phase_hists[name]
            if own_ns:
                median = hist.percentile(50)
            hist.record((end_ns - start_ns) * 1e-6)
        if median is None:
            return False
        if name in _SLOW_EXEMPT or own_ns <= _SLOW_MEDIANS * median * 1e6:
            return False
        self._keep_slow(
            Phase(name, start_ns, end_ns, None if seq == _NO_SEQ else seq,
                  parent, trace_id, attrs or {}), own_ns, median)
        return True

    def is_slow(self, name, lasted_ns):
        """The slow rule for a phase `name` that has lasted `lasted_ns`
        so far (the watcher asks it of phases still open)."""
        if lasted_ns <= _SLOW_NS or name in _SLOW_EXEMPT:
            return False
        with self._phase_lock:
            median = self._phase_hists[name].percentile(50)
        return lasted_ns > _SLOW_MEDIANS * median * 1e6

    def _first(self):
        """Position of the oldest entry."""
        return (self._appended % self.phase_capacity
                if self._appended >= self.phase_capacity else 0)

    def _search_end(self, x, after):
        """Where a binary search puts `x` in the end column, as a
        count of entries from the oldest (`after`: behind the ends
        equal to it). The column is in order only to within
        `_disorder_ns`, so what is sure is: every entry before that
        place ends under x + `_disorder_ns`, and every one from it on
        at or over x - `_disorder_ns`. `phases` widens its bounds by
        as much and filters what it copies."""
        first, cap = self._first(), self.phase_capacity
        ends = memoryview(self._ring).cast("q")[2::6]
        lo, hi = 0, self._held()
        while lo < hi:
            mid = (lo + hi) // 2
            e = ends[(first + mid) % cap]
            if e < x or (after and e == x):
                lo = mid + 1
            else:
                hi = mid
        return lo

    def _columns(self, lo, hi):
        """The entries `lo` to `hi` (counted from the oldest) as six
        lists, copied: the caller builds from them outside the lock."""
        ring, cap = self._ring, self.phase_capacity
        a, b = self._first() + lo, self._first() + hi
        if b <= cap:
            part = ring[48 * a:48 * b]
        elif a >= cap:
            part = ring[48 * (a - cap):48 * (b - cap)]
        else:
            part = ring[48 * a:48 * cap] + ring[:48 * (b - cap)]
        part = memoryview(part).cast("q")
        return [part[field::6].tolist() for field in range(6)]

    def phases(self, since_ns=None, until_ns=None):
        """The raw ring, oldest first, as `Phase` tuples (counts are
        zero-length entries whose attrs hold ``n``); with bounds, the
        entries that overlap [since_ns, until_ns], found by a search of
        the end column and not by building the ring. Without bounds,
        and with nothing sealed since the last such call, the list
        built then: do not change it."""
        bounded = since_ns is not None or until_ns is not None
        with self._phase_lock:
            sealed, built = self._built
            if not bounded and sealed == self._appended:
                return built
            lo, hi = 0, self._held()
            if since_ns is not None:
                lo = self._search_end(since_ns - self._disorder_ns, False)
            if until_ns is not None:
                # an entry that starts by `until_ns` ends no later than
                # that plus the longest phase there ever was (the
                # histograms keep each name's, in ms)
                longest = 1000 + int(1e6 * max(
                    h.max for h in self._phase_hists.values()))
                hi = max(lo, self._search_end(
                    until_ns + longest + self._disorder_ns, True))
            columns = self._columns(lo, hi)
            sealed = self._appended
            number = sealed - self._held() + lo  # of the first copied
            side = dict(self._side) if self._side else None
        out = _build(columns, number, side, since_ns, until_ns)
        if not bounded:
            with self._phase_lock:
                if sealed >= self._built[0]:
                    self._built = (sealed, out)
        return out

    def counts(self):
        """{counter: cumulative total} since start (or clear)."""
        with self._phase_lock:
            return dict(self._counts)

    def phase_snapshot(self):
        """{phase: {count, p50_ms, p99_ms, total_ms}} for phases that
        recorded anything, cumulative."""
        with self._phase_lock:
            return {
                name: {
                    "count": h.count,
                    "p50_ms": round(h.percentile(50), 3),
                    "p99_ms": round(h.percentile(99), 3),
                    "total_ms": round(h.sum, 3),
                }
                for name, h in self._phase_hists.items() if h.count
            }

    def phase_hist_series(self):
        """[({"phase": name}, bucket counts, sum)] per phase that
        recorded samples: the series of one labeled histogram family
        (metrics.hist_family)."""
        with self._phase_lock:
            return [
                ({"phase": name}, h.to_counts(), h.sum)
                for name, h in self._phase_hists.items() if h.count
            ]

    def clear_phases(self):
        with self._phase_lock:
            self._reset_phases()

    # ------------------------------------- slow phases, and beneath them

    def _keep_slow(self, ph, own_ns, median_ms):
        """Move the slow phase `ph`, every ring entry inside its
        interval (they were sealed before it, so they are the ring's
        newest) and the watcher's samples of it into the retained
        tier, and say so in one line."""
        beneath, cut = [], 0
        sums = {"gc": 0, "compile": 0, "watch.late": 0}
        programs = late_cpu = 0
        with self._phase_lock:
            samples = [s for s in self._samples
                       if s.parent == ph.name
                       and s.attrs["phase_start_ns"] == ph.start_ns]
            for s in samples:
                self._samples.remove(s)
        for p in reversed(self.phases(ph.start_ns, ph.end_ns)):
            if (p.start_ns < ph.start_ns or p.end_ns > ph.end_ns
                    or p[:3] == ph[:3]):
                continue  # reaches out of the interval, or is `ph`
            if p.name in sums:
                sums[p.name] += p.end_ns - p.start_ns
                late_cpu += p.attrs.get("cpu_ms", 0)
            elif p.name == "compile.programs":
                programs += p.attrs.get("n", 0)
            if len(beneath) < _SLOW_BENEATH:
                beneath.append(p)
            else:
                cut += 1
        beneath.reverse()
        lasted = ph.end_ns - ph.start_ns
        line = (
            "slow phase %s seq %s: %.0f ms (%smedian %.3g); gc %.0f ms; "
            "compile %.0f ms, %d programs; watcher late %.0f ms (cpu %d); "
            "at %s" % (
                ph.name, ph.seq, lasted * 1e-6,
                "" if own_ns == lasted else "%.0f its own, " % (own_ns * 1e-6),
                median_ms, sums["gc"] * 1e-6, sums["compile"] * 1e-6,
                programs, sums["watch.late"] * 1e-6, late_cpu,
                " <- ".join(samples[-1].attrs["frames"][:4])
                if samples else "(no sample)"))
        record = {
            "phase": ph, "own_ms": own_ns * 1e-6, "median_ms": median_ms,
            "gc_ms": sums["gc"] * 1e-6, "compile_ms": sums["compile"] * 1e-6,
            "compile_programs": programs,
            "late_ms": sums["watch.late"] * 1e-6, "late_cpu_ms": late_cpu,
            "beneath": beneath, "beneath_cut": cut, "samples": samples,
            "line": line,
        }
        with self._phase_lock:
            if len(self._slow) == _SLOW_CAPACITY:
                self.slow_dropped += 1
            self._slow.append(record)
            self._slow_counts[ph.name] = (
                self._slow_counts.get(ph.name, 0) + 1)
        logger.warning(line)

    def slow_phases(self):
        """The retained tier, oldest first: one dict a slow phase
        (`phase`, `own_ms`, `median_ms`, `gc_ms`, `compile_ms`,
        `compile_programs`, `late_ms`, `beneath` = the ring's entries
        inside it, `beneath_cut` = how many more there were,
        `samples` = the watcher's, `line` = what was logged)."""
        with self._phase_lock:
            return list(self._slow)

    def slow_json(self):
        """The retained tier and the samples of phases still open with
        their tuples as dicts: what `export()` and the health bundle
        carry."""
        def plain(record):
            return dict(
                record, phase=record["phase"]._asdict(),
                beneath=[p._asdict() for p in record["beneath"]],
                samples=[p._asdict() for p in record["samples"]])

        with self._phase_lock:
            return {
                "dropped": self.slow_dropped,
                "slow": [plain(r) for r in self._slow],
                "open_samples": [p._asdict() for p in self._samples],
            }

    def slow_counts(self):
        """{phase name: slow phases so far}, cumulative."""
        with self._phase_lock:
            return dict(self._slow_counts)

    def watch_samples(self):
        """The watcher's samples of phases that have not ended (those
        of an ended slow phase are in its record)."""
        with self._phase_lock:
            return list(self._samples)

    def _note_sample(self, sample):
        with self._phase_lock:
            self._samples.append(sample)

    def gc_pauses(self):
        """{collections, total_ms, longest_ms} of every garbage
        collection since start (or clear), short ones included."""
        n, ns, longest = self._gc
        return {"collections": n, "total_ms": ns * 1e-6,
                "longest_ms": longest * 1e-6}

    def _gc_pause(self, start_ns, end_ns, seq, parent, info, beneath):
        """One collection (the gc callback, on the thread that
        collected; collections do not overlap). Only one of 1 ms or
        more that lies `beneath` something (some thread has a phase
        open) is a ring entry, and where this thread already holds
        the ring's lock (the collection began inside `_seal`) the
        entry waits for the next seal."""
        dur = end_ns - start_ns
        tally = self._gc
        tally[0] += 1
        tally[1] += dur
        if dur > tally[2]:
            tally[2] = dur
        if dur < _GC_ENTRY_NS or not beneath:
            return
        entry = ("gc", _NAME_INDEX["gc"], start_ns, end_ns, seq, parent,
                 _NAME_INDEX[parent] if parent else _NO_KEY, "",
                 {"generation": info.get("generation", -1),
                  "collected": info.get("collected", 0)})
        if self._phase_lock._is_owned():
            self._gc_deferred.append(entry)
        else:
            self._seal(*entry)

    def _flush_gc(self):
        while True:
            try:
                entry = self._gc_deferred.popleft()
            except IndexError:
                return
            self._seal(*entry)

    # ---------------------------------------------------- request spans

    def add_classifier(self, fn):
        """Register a verdict hook `fn(span) -> True | False | None`:
        True = retain the span's whole trace in the retained tier,
        False = healthy root (probabilistic sample), None = not this
        hook's span (fall through). Hooks run under the recorder lock
        at finish time — keep them pure and cheap. Idempotent per
        function object."""
        with self._lock:
            if fn not in self._classifiers:
                self._classifiers.append(fn)
        return fn

    def remove_classifier(self, fn):
        """Unregister a hook (no-op if absent) — lifecycle owners
        (e.g. a stopping Router) drop their hook so a long-lived test
        process never accumulates stale verdicts."""
        with self._lock:
            self._classifiers = [
                f for f in self._classifiers if f != fn
            ]

    def clear_classifiers(self):
        with self._lock:
            self._classifiers = []

    def start_span(self, name, trace_id=None, parent_span_id="",
                   **attrs):
        """New span; mints a fresh trace when `trace_id` is falsy
        (this IS admission: the point a request first gets traced)."""
        return Span(self, name, trace_id or new_trace_id(),
                    parent_span_id, attrs, self.clock())

    def _verdict_locked(self, span):
        """First non-None hook verdict, or None. A hook that raises is
        treated as abstaining — observability must never take the
        serving path down with it."""
        for fn in self._classifiers:
            try:
                verdict = fn(span)
            except Exception:  # noqa: BLE001 - hooks must not crash us
                verdict = None
            if verdict is not None:
                return bool(verdict)
        return None

    def _retain_locked(self, span):
        """Move `span` — and every already-recorded span of its trace —
        into the retained tier, pinning the trace so stragglers follow.
        The tier is bounded drop-oldest with its own counter."""
        self._retained_traces.add(span.trace_id)
        moved = [s for s in self._spans
                 if s.trace_id == span.trace_id]
        if moved:
            self._spans = deque(
                s for s in self._spans
                if s.trace_id != span.trace_id
            )
        for s in moved:
            self._retained.append(s)
        self._retained.append(span)
        while len(self._retained) > self.retained_capacity:
            victim = self._retained.popleft()
            self.retained_dropped += 1
            if not any(s.trace_id == victim.trace_id
                       for s in self._retained):
                self._retained_traces.discard(victim.trace_id)

    def _finish(self, span, status):
        with self._lock:
            if span.end is not None:  # idempotent terminal
                return
            span.end = self.clock()
            span.status = status
            if span.trace_id in self._retained_traces:
                self._retain_locked(span)
                return
            verdict = self._verdict_locked(span)
            if verdict is True:
                self._retain_locked(span)
                return
            if verdict is False and self._rand.random() >= self.sample_rate:
                # healthy root sampled OUT: its trace's spans leave the
                # ring too — pressure relief is the whole point
                before = len(self._spans)
                self._spans = deque(
                    s for s in self._spans
                    if s.trace_id != span.trace_id
                )
                self.sampled_out += 1 + (before - len(self._spans))
                return
            self._spans.append(span)
            while len(self._spans) > self.capacity:
                self._spans.popleft()
                self.dropped += 1

    def __len__(self):
        with self._lock:
            return len(self._retained) + len(self._spans)

    def snapshot(self):
        """Every recorded span, retained tier first (it holds the
        oldest surviving evidence)."""
        with self._lock:
            return list(self._retained) + list(self._spans)

    def clear(self):
        with self._lock:
            self._spans.clear()
            self._retained.clear()
            self._retained_traces.clear()
            self.dropped = 0
            self.retained_dropped = 0
            self.sampled_out = 0

    def export(self):
        """The on-disk per-process document the dump tool merges."""
        with self._lock:
            spans = list(self._retained) + list(self._spans)
            retained = len(self._retained)
            dropped = self.dropped
            retained_dropped = self.retained_dropped
            sampled_out = self.sampled_out
        with self._phase_lock:
            phases_dropped = self.phases_dropped
        return {
            "service": self.service,
            "pid": os.getpid(),
            "dropped": dropped,
            "retained": retained,
            "retained_dropped": retained_dropped,
            "sampled_out": sampled_out,
            "spans": [s.to_dict() for s in spans],
            "phases_dropped": phases_dropped,
            "phases": [dict(p._asdict(), service=self.service)
                       for p in self.phases()],
            "slow_phases": self.slow_json(),
        }

    def write(self, path):
        """Atomic JSON write (tmp + rename): a process dying mid-write
        can never leave a torn file for the merger to choke on."""
        tmp = "%s.tmp.%d" % (path, os.getpid())
        with open(tmp, "w") as f:
            json.dump(self.export(), f)
        os.replace(tmp, path)
        return path

    def flush(self, trace_dir=None):
        """Write this process's spans into the trace directory
        (EDL_TRACE_DIR unless given). No-op returning None when no
        directory is configured — the zero-config production default
        keeps spans in memory only."""
        trace_dir = trace_dir or os.environ.get(TRACE_DIR_ENV, "")
        if not trace_dir:
            return None
        os.makedirs(trace_dir, exist_ok=True)
        safe = "".join(
            c if c.isalnum() or c in "-_." else "-"
            for c in self.service
        )
        return self.write(os.path.join(
            trace_dir, "spans-%s-%d.json" % (safe, os.getpid())
        ))


# ------------------------------------------------- process-global recorder

_RECORDER = SpanRecorder()
_ATEXIT_ARMED = False


def recorder():
    """The process-global recorder every subsystem records into (one
    file per process at export time). Tests may swap service/capacity
    via configure() or construct private SpanRecorders."""
    return _RECORDER


def configure(service=None, capacity=None):
    """Name this process's recorder (e.g. ``replica:50051``,
    ``router``, ``master``) and arm the atexit flush backstop. Called
    by the process entrypoints; safe to call repeatedly."""
    global _ATEXIT_ARMED
    if service:
        _RECORDER.service = service
    if capacity:
        _RECORDER.capacity = int(capacity)
    if not _ATEXIT_ARMED:
        _ATEXIT_ARMED = True
        atexit.register(lambda: _RECORDER.flush())
    return _RECORDER


# ------------------------------------------------------------ phase spans

_LABELS = {p: "edl/" + p for p in PHASES}  # the xplane's event names
_COUNTERS = frozenset(COUNTERS)
_PHASE_INDEX = {name: _NAME_INDEX[name] for name in PHASES}
_COUNTER_INDEX = {name: _NAME_INDEX[name] for name in COUNTERS}
_tls = threading.local()
_annotation = None  # jax.profiler.TraceAnnotation, once jax is imported
# every thread's stack of open phases, where another thread can read
# it: {thread ident: (thread name, the stack `_tls.stack` also names)}
_stacks = {}
_stacks_lock = threading.Lock()


class _OpenPhase(object):
    """A phase between `begin` and `end`; also the context manager
    `phase()` returns. `slow_ns` is the time of the slow phases already
    kept from inside it, `sample_at_ns` how long it has to have been
    open for the watcher's next sample of it."""

    __slots__ = ("name", "ni", "seq", "parent", "pi", "trace_id", "attrs",
                 "start_ns", "slow_ns", "sample_at_ns", "_ann", "_stack")

    def __init__(self, name, ni, seq, parent, pi, trace_id, attrs, stack):
        self.name, self.ni, self.seq = name, ni, seq
        self.parent, self.pi = parent, pi
        self.trace_id, self.attrs, self._stack = trace_id, attrs, stack
        self._ann = None
        # start_ns comes with the start; slow_ns and sample_at_ns only
        # once the phase is slow (`getattr` with a default reads them)

    def __enter__(self):
        return self

    def __exit__(self, _exc_type, _exc, _tb):
        end(self)
        return False


def _my_stack():
    stack = _tls.stack = []
    me = threading.current_thread()
    with _stacks_lock:
        _stacks[me.ident] = (me.name, stack)
    return stack


def open_threads():
    """[(thread ident, thread name, [open phases, outermost first])] of
    the live threads that have a phase open: what `open_phases` and the
    watcher read. The phases are the live objects: read, do not write."""
    alive = {t.ident for t in threading.enumerate()}
    with _stacks_lock:
        for ident in [i for i in _stacks if i not in alive]:
            del _stacks[ident]
        threads = list(_stacks.items())
    out = []
    for ident, (name, stack) in threads:
        opened = [ph for ph in list(stack) if getattr(ph, "start_ns", 0)]
        if opened:
            out.append((ident, name, opened))
    return out


def open_phases():
    """{thread name: [(name, seq, start_ns), ...]} of the phases open
    right now, outermost first, on every thread that has one: a phase
    that never ends is in here and nowhere else."""
    return {
        name: [(ph.name, ph.seq, ph.start_ns) for ph in opened]
        for _ident, name, opened in open_threads()
    }


def _see_jax():
    """Once jax is imported: its annotation for `begin`, and the one
    listener that stamps `compile` and counts `compile.programs`."""
    global _annotation
    jax = sys.modules["jax"]
    if not hasattr(jax, "profiler") or not hasattr(jax, "monitoring"):
        return  # jax is still being imported
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
    _annotation = jax.profiler.TraceAnnotation


#: the two stages of a jit miss (jax._src.dispatch); the first is the
#: event `chipbench.probes.CompileCounter` counts
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": 0,
    "/jax/core/compile/backend_compile_duration": 1,
}


def _on_jax_duration(event, duration_secs, **_):
    backend = _COMPILE_EVENTS.get(event)
    if backend is None:
        return
    now = time.time_ns()
    stamp("compile", now - int(duration_secs * 1e9), now, backend=backend)
    if not backend:
        count("compile.programs")


_gc_open = None  # (start_ns, annotation or None) of the running collection


def _on_gc(when, info):
    """`gc.callbacks`: a collection as the phase `gc` on the thread
    that collected. Only a full collection is annotated: the length is
    not known at its start, and a young one is over in microseconds."""
    global _gc_open
    if when == "start":
        ann = None
        if (info.get("generation") == 2 and _annotation is not None
                and _annotation.is_enabled()):
            ann = _annotation(_LABELS["gc"])
            ann.__enter__()
        _gc_open = (time.time_ns(), ann)
    elif _gc_open is not None:
        end_ns = time.time_ns()
        (start_ns, ann), _gc_open = _gc_open, None
        if ann is not None:
            ann.__exit__(None, None, None)
        stack = getattr(_tls, "stack", None)
        top = stack[-1] if stack else None
        # (a test may have put another object in the recorder's place)
        pause = getattr(_RECORDER, "_gc_pause", None)
        if pause is not None:
            pause(start_ns, end_ns, top.seq if top else None,
                  top.name if top else "", info,
                  any(stack for _name, stack in list(_stacks.values())))


gc.callbacks.append(_on_gc)
atexit.register(gc.callbacks.remove, _on_gc)
if "jax" in sys.modules:
    _see_jax()


def begin(name, seq=None, trace_id="", **attrs):
    """Open the phase `name` on this thread; close it with `end`.
    `seq` (the tick or step number) and `trace_id` (the request's,
    where the phase serves one request) are inherited from the
    enclosing phase when not given."""
    ni = _PHASE_INDEX.get(name)
    if ni is None:
        raise ValueError(
            "unknown phase %r (declared: %s)" % (name, ", ".join(PHASES))
        )
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _my_stack()
    if stack:
        top = stack[-1]
        ph = _OpenPhase(name, ni, top.seq if seq is None else seq,
                        top.name, top.ni, trace_id or top.trace_id, attrs,
                        stack)
    else:
        ph = _OpenPhase(name, ni, seq, "", _NO_KEY, trace_id, attrs, stack)
    stack.append(ph)
    if _annotation is None and "jax" in sys.modules:
        _see_jax()
    # with no profiler session the annotation is this flag check
    if _annotation is not None and _annotation.is_enabled():
        ph._ann = _annotation(_LABELS[name])
        ph._ann.__enter__()
    ph.start_ns = time.time_ns()
    return ph


def end(ph, **attrs):
    """Close a phase opened by `begin` (with what is only known now as
    `attrs`) and seal it into the recorder's phase ring. Phases left
    open above it on its thread's stack (an exception skipped their
    `end`) are dropped, so one failure cannot mis-parent what
    follows."""
    end_ns = time.time_ns()
    if ph._ann is not None:
        ph._ann.__exit__(None, None, None)
    stack = ph._stack
    if stack and stack[-1] is ph:
        stack.pop()
    elif ph in stack:
        while stack.pop() is not ph:
            pass
    if attrs:
        ph.attrs.update(attrs)
    lasted = end_ns - ph.start_ns
    if lasted <= _SLOW_NS:
        _RECORDER._seal(ph.name, ph.ni, ph.start_ns, end_ns, ph.seq,
                        ph.parent, ph.pi, ph.trace_id, ph.attrs)
        return
    # the slow rule (module docstring): on the phase's own time, and
    # what is kept, or was kept beneath it, is not its parent's own
    beneath = getattr(ph, "slow_ns", 0)
    own = lasted - beneath
    kept = _RECORDER._seal(ph.name, ph.ni, ph.start_ns, end_ns, ph.seq,
                           ph.parent, ph.pi, ph.trace_id, ph.attrs,
                           own if own > _SLOW_NS else 0)
    if stack:
        # (a wait that is long by design is not its parent's own either)
        if kept or ph.name in _SLOW_EXEMPT:
            beneath = lasted
        if beneath:
            stack[-1].slow_ns = getattr(stack[-1], "slow_ns", 0) + beneath


#: `with phase("tick.upload"): ...` — `begin` and `end` around a block
phase = begin


def stamp(name, start_ns, end_ns, **attrs):
    """Seal a phase that has already happened (a length handed over by
    whoever timed it), under this thread's innermost open phase."""
    if name not in _PHASE_INDEX:
        raise ValueError(
            "unknown phase %r (declared: %s)" % (name, ", ".join(PHASES))
        )
    stack = getattr(_tls, "stack", None)
    if stack:
        top = stack[-1]
        _RECORDER._seal(name, _PHASE_INDEX[name], start_ns, end_ns, top.seq,
                        top.name, top.ni, "", attrs)
    else:
        _RECORDER._seal(name, _PHASE_INDEX[name], start_ns, end_ns, None,
                        "", _NO_KEY, "", attrs)


def count(name, n=1):
    """Count `n` units of work under the closed counter `name`, where
    the work happens."""
    ni = _COUNTER_INDEX.get(name)
    if ni is None:
        raise ValueError(
            "unknown counter %r (declared: %s)"
            % (name, ", ".join(COUNTERS))
        )
    if _annotation is None and "jax" in sys.modules:
        _see_jax()
    stack = getattr(_tls, "stack", None)
    now = time.time_ns()
    if stack:
        top = stack[-1]
        _RECORDER._seal_count(name, ni, now, top.seq, top.ni, n)
    else:
        _RECORDER._seal_count(name, ni, now, None, _NO_KEY, n)


# ------------------------------------------------------ chrome conversion


def group_by_trace(span_dicts):
    """{trace_id: [span dicts]} — the structural-assertion entry the
    tests and the chaos drill use."""
    by_trace = {}
    for s in span_dicts:
        by_trace.setdefault(s["trace_id"], []).append(s)
    return by_trace


def trace_roots(span_dicts):
    """Spans with no parent IN the set (cross-process parents that
    were never exported — e.g. a SIGKILLed process — leave their
    children as roots rather than hiding them)."""
    ids = {s["span_id"] for s in span_dicts}
    return [s for s in span_dicts
            if not s["parent_span_id"] or s["parent_span_id"] not in ids]


def children_of(span_dicts, parent_span_id):
    return [s for s in span_dicts
            if s["parent_span_id"] == parent_span_id]


def _chrome_phases(phase_dicts, pid_of):
    """Phase dicts (`SpanRecorder.export()["phases"]`) as slices on
    one "phases" row per service — they nest by time, as they did on
    the thread that ran them — named as in the xplane (``edl/<name>``);
    counts become instant events."""
    events = []
    for pid in sorted({pid_of[p["service"]] for p in phase_dicts}):
        events.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": "phases"},
        })
    for p in sorted(phase_dicts, key=lambda d: (d["start_ns"],
                                                -d["end_ns"])):
        args = dict(p["attrs"], seq=p["seq"], parent=p["parent"])
        if p["trace_id"]:
            args["trace_id"] = p["trace_id"]
        ev = {"name": "edl/" + p["name"], "cat": p["service"],
              "pid": pid_of[p["service"]], "tid": 0,
              "ts": p["start_ns"] / 1e3, "args": args}
        if p["name"] in _COUNTERS:
            ev.update(ph="i", s="t")
        else:
            ev.update(ph="X", dur=(p["end_ns"] - p["start_ns"]) / 1e3)
        events.append(ev)
    return events


def chrome_trace(span_dicts, phase_dicts=()):
    """Convert merged span dicts into Chrome-trace JSON (the "JSON
    Array Format" both chrome://tracing and Perfetto ingest).

    Layout: one Chrome "process" per service (process_name metadata),
    one "thread" per trace within it — so opening the file shows each
    request's spans stacked on one row, per tier. Every slice carries
    trace_id/span_id/parent_span_id (plus the span attrs and status)
    in ``args``; span events become instant events on the same row.
    `phase_dicts` (the same processes' phase rings, on the same wall
    clock) land on a "phases" row of their service."""
    services = sorted({s["service"] for s in span_dicts}
                      | {p["service"] for p in phase_dicts})
    pid_of = {svc: i + 1 for i, svc in enumerate(services)}
    tid_of = {}
    events = []
    for svc, pid in pid_of.items():
        events.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": svc},
        })
    for s in sorted(span_dicts, key=lambda d: (d["start"], d["name"])):
        pid = pid_of[s["service"]]
        tid = tid_of.setdefault((pid, s["trace_id"]),
                                len(tid_of) + 1)
        end = s["end"] if s["end"] is not None else s["start"]
        args = dict(s["attrs"])
        args.update({
            "trace_id": s["trace_id"],
            "span_id": s["span_id"],
            "parent_span_id": s["parent_span_id"],
            "status": s["status"],
        })
        events.append({
            "name": s["name"], "cat": s["service"], "ph": "X",
            "pid": pid, "tid": tid,
            "ts": s["start"] * 1e6,
            "dur": max(0.0, (end - s["start"])) * 1e6,
            "args": args,
        })
        for ev in s["events"]:
            events.append({
                "name": ev["name"], "cat": s["service"], "ph": "i",
                "s": "t", "pid": pid, "tid": tid,
                "ts": ev["ts"] * 1e6,
                "args": dict(ev["attrs"],
                             trace_id=s["trace_id"],
                             span_id=s["span_id"]),
            })
    events.extend(_chrome_phases(phase_dicts, pid_of))
    return {"traceEvents": events, "displayTimeUnit": "ms"}
