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

Every phase end also feeds that name's cumulative log-linear histogram
(`phase_snapshot()`, the ``edl_serving_phase_ms{phase=}`` family): the
ring drops its oldest, a cumulative family cannot. `count(name, n)`
records work done where it happens (`COUNTERS`, closed too) as a
zero-length entry of the same ring, so a reader can cut counts to a
window like phases.

Timestamps are ``time.time()`` (wall clock): spans from different
processes must land on one timeline, which monotonic clocks cannot
give across processes. Good enough for the single-host drills this
serves; cross-host skew shifts whole processes, never re-orders one
process's spans.
"""

import atexit
import collections
import json
import os
import random
import sys
import threading
import time
from collections import deque

from elasticdl_tpu.observability.histogram import LogLinearHistogram

TRACE_DIR_ENV = "EDL_TRACE_DIR"

_DEFAULT_CAPACITY = 4096
#: the retained tier's own bound (slow/failed traces); deliberately
#: smaller than the ring — retention is for the tail, not a second
#: copy of everything
_DEFAULT_RETAINED_CAPACITY = 2048
#: the phase ring's bound: a 51 s window of 10 ticks/s x ~10 phases,
#: its warm-up and its drain fit several times over
_DEFAULT_PHASE_CAPACITY = 65536

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
)
#: the closed set of `count` names
COUNTERS = ("prompt_write.launches", "prompt_write.tokens",
            "prompts_prefilled",
            # serving/engine.py step() / _spec_step(), a decode tick:
            # table slots in reach of the lanes' sequences (what the
            # paged kernel streams a layer) of lanes x table width
            "paged.blocks_streamed", "paged.table_slots",
            # the same tick, over ALL layers: blocks a seated lane has
            # written, and those of them wholly behind their layer's
            # window (held by the pool, never read again)
            "kv.blocks_held", "kv.window_dead_blocks",
            # what the model's expert layers sow into "counters" in a
            # decode step (model_zoo/transformer_lm ExpertFFN), handed
            # back behind the tick's tokens and summed over layers:
            # (row, choice) pairs routed / those whose expert is held
            # here / held experts some lane chose / held experts
            "moe.pairs_routed", "moe.pairs_held", "moe.experts_hit",
            "moe.expert_slots",
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
            # serving/engine.py _load_params(), once a (re)load of a
            # weight tree: the bytes of the tree handed in and of the
            # tree the programs are served, and the leaves replaced by
            # their cast to the compute dtype / kept as handed in
            # (serving/exec_weights.py). bf16 compute over fp32
            # weights: exec / source is about 0.5
            "weights.source_bytes", "weights.exec_bytes",
            "weights.leaves_cast", "weights.leaves_kept")

Phase = collections.namedtuple(
    "Phase", "name start_ns end_ns seq parent trace_id attrs")


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
        # the phase ring (module docstring, PHASE SPANS): its own
        # bound, lock and drop count, so neither ring evicts the other
        self.phase_capacity = int(phase_capacity)
        self.phases_dropped = 0
        self._phase_lock = threading.Lock()
        self._phases = deque(maxlen=self.phase_capacity)
        self._phase_hists = {p: LogLinearHistogram() for p in PHASES}
        self._counts = dict.fromkeys(COUNTERS, 0)

    # ------------------------------------------------------- phase ring

    def _record_phase(self, record):
        """Seal one phase or count: one short critical section."""
        with self._phase_lock:
            if len(self._phases) == self.phase_capacity:
                self.phases_dropped += 1
            self._phases.append(record)
            hist = self._phase_hists.get(record[0])
            if hist is not None:
                hist.record((record[2] - record[1]) * 1e-6)
            else:
                self._counts[record[0]] += record[6]["n"]

    def phases(self, since_ns=None, until_ns=None):
        """The raw ring, oldest first, as `Phase` tuples (counts are
        zero-length entries whose attrs hold ``n``); with bounds, the
        entries that overlap [since_ns, until_ns]."""
        with self._phase_lock:
            raw = list(self._phases)
        return [
            Phase(*r) for r in raw
            if (since_ns is None or r[2] >= since_ns)
            and (until_ns is None or r[1] <= until_ns)
        ]

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
            self._phases.clear()
            self.phases_dropped = 0
            self._phase_hists = {p: LogLinearHistogram() for p in PHASES}
            self._counts = dict.fromkeys(COUNTERS, 0)

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
_tls = threading.local()
_annotation = None  # jax.profiler.TraceAnnotation, once jax is imported


class _OpenPhase(object):
    """A phase between `begin` and `end`; also the context manager
    `phase()` returns."""

    __slots__ = ("name", "seq", "parent", "trace_id", "attrs",
                 "start_ns", "_ann", "_stack")

    def __init__(self, name, seq, parent, trace_id, attrs, stack):
        self.name, self.seq, self.parent = name, seq, parent
        self.trace_id, self.attrs, self._stack = trace_id, attrs, stack
        self._ann = None

    def __enter__(self):
        return self

    def __exit__(self, _exc_type, _exc, _tb):
        end(self)
        return False


def begin(name, seq=None, trace_id="", **attrs):
    """Open the phase `name` on this thread; close it with `end`.
    `seq` (the tick or step number) and `trace_id` (the request's,
    where the phase serves one request) are inherited from the
    enclosing phase when not given."""
    global _annotation
    label = _LABELS.get(name)
    if label is None:
        raise ValueError(
            "unknown phase %r (declared: %s)" % (name, ", ".join(PHASES))
        )
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    if stack:
        top = stack[-1]
        ph = _OpenPhase(name, top.seq if seq is None else seq, top.name,
                        trace_id or top.trace_id, attrs, stack)
    else:
        ph = _OpenPhase(name, seq, "", trace_id, attrs, stack)
    stack.append(ph)
    if _annotation is None and "jax" in sys.modules:
        _annotation = sys.modules["jax"].profiler.TraceAnnotation
    # with no profiler session the annotation is this flag check
    if _annotation is not None and _annotation.is_enabled():
        ph._ann = _annotation(label)
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
    _RECORDER._record_phase((ph.name, ph.start_ns, end_ns, ph.seq,
                             ph.parent, ph.trace_id, ph.attrs))


#: `with phase("tick.upload"): ...` — `begin` and `end` around a block
phase = begin


def count(name, n=1):
    """Count `n` units of work under the closed counter `name`, where
    the work happens."""
    if name not in _COUNTERS:
        raise ValueError(
            "unknown counter %r (declared: %s)"
            % (name, ", ".join(COUNTERS))
        )
    stack = getattr(_tls, "stack", None)
    top = stack[-1] if stack else None
    now = time.time_ns()
    _RECORDER._record_phase((
        name, now, now, top.seq if top else None,
        top.name if top else "", "", {"n": n},
    ))


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
