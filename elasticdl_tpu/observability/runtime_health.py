"""Runtime health plane: recompile sentry, device-memory ledger
reconciliation, and a progress watchdog with a flight recorder.

PRs 6/12/13 built the observability stack from traces up — spans,
histograms, a live /metrics plane, tail forensics — but everything in
it observes REQUESTS. Nothing observes the RUNTIME: the engine's
"churn never recompiles" invariant is asserted by design and measured
nowhere, the paged pool's byte ledger is self-reported and never
reconciled against what the device actually holds, and a wedged
scheduler is detected from the OUTSIDE by lease decay plus a
deliberately conservative 30 s heuristic (serving/autoscaler.py
`wedged_after_secs`). This module makes the runtime self-report, three
layers behind one `ServingConfig.runtime_health` switch:

* **RecompileSentry** — every `jax.jit` call site in the serving
  engine, the paged KV pool and the offline decode paths is adopted
  through `tracked_jit`, which counts COMPILATIONS per named
  executable (the wrapped python fn runs exactly once per trace, i.e.
  per compile-cache miss — the lowering-hook variant of
  `_cache_size()` probing, with no jax-version coupling). First
  compiles of a name are the cold path by design (one executable per
  prefill/suffix bucket); a SECOND compile of the same name is a
  RECOMPILE, and after `mark_steady()` (the post-warmup boundary) a
  recompile is a counted, trace-evented ANOMALY — the invariant
  tests/test_runtime_health.py holds a live server to at zero. Exposed as the closed labeled family
  `edl_serving_recompiles_total{fn=...}`.

* **DeviceMemoryAccountant** — periodic reconciliation of the
  runtime's own ledger (pool `bytes_total` + host-tier bytes + param
  bytes + draft-pool bytes) against JAX's live-buffer view
  (`jax.live_arrays()` byte sum, plus `device.memory_stats()` where
  the backend provides it). Drift since the baseline —
  device bytes the ledger cannot name — lands in the
  `memory_unaccounted_bytes` gauge with a monotone peak watermark, so
  a leaked donated buffer or an executable cache growing without
  bound is VISIBLE before it is fatal. The `health_leak` fault hook
  leaks a buffer on purpose so the drill can prove the accountant
  convicts it.

* **ProgressWatchdog + FlightRecorder** — a bounded ring of per-tick
  engine snapshots (seated slots, queue depth, blocks
  free/cached/host, tokens committed, step ms) fed by the scheduler,
  and a watchdog that runs on its OWN thread (the whole point: the
  scheduler being wedged is the failure under observation) and
  declares `stalled` only when work is seated/queued but the progress
  counter — tokens committed PLUS jit compiles, so a long cold
  compile is progress, not a stall — has not moved for
  `stall_after_secs`. Idle is healthy. On the transition to stalled
  it atomically dumps a DIAGNOSTIC BUNDLE to `$EDL_HEALTH_DIR`:
  all-thread stacks (faulthandler), the snapshot ring, the two-tier
  pool ledger, the reconciliation view and the recompile counters —
  the flight recorder of the crash. `last_progress_age_ms` +
  `health_state` ride ServerStatus/ReplicaStatus so the autoscaler
  can replace a self-reported stalled replica in seconds instead of
  the 30 s lease heuristic (scripts/run_stall_drill.py proves the
  latency gap).

Thread model: the scheduler thread feeds (`record_tick`, and compiles
happen on it), the health thread checks/reconciles, gRPC status
threads read snapshots — every structure carries its own lock, and no
health lock is ever held while taking the telemetry lock's callbacks
(the mirror pattern: read under own lock, count deltas outside).

`install_sigusr2_dump()` is the standalone escape hatch every
long-running entrypoint registers: SIGUSR2 -> faulthandler all-thread
stack dump to stderr (or `$EDL_HEALTH_DIR/sigusr2-<pid>.txt`), so a
live wedged process can always be interrogated without killing it.

Design doc: docs/designs/observability.md ("Runtime health").
"""

import faulthandler
import io
import json
import os
import signal
import threading
import time
import traceback
from collections import deque

from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.observability.phase_watch import PhaseWatcher

HEALTH_DIR_ENV = "EDL_HEALTH_DIR"
HEALTH_ENV = "EDL_RUNTIME_HEALTH"
STALL_AFTER_ENV = "EDL_STALL_AFTER_SECS"

#: the closed health-state set (ServerStatus.health_state); "" on the
#: wire means the replica predates the health plane (or runs with it
#: off) — the autoscaler's cue to fall back to lease decay
HEALTH_STATES = ("ok", "stalled")

BUNDLE_SCHEMA = "edl-health-bundle/1"


def runtime_health_default():
    """EDL_RUNTIME_HEALTH resolves the health plane when the config
    leaves it unset: on unless explicitly '0' (the plane's cost is
    inside the benchmark's `tick.host_ms`, like forensics)."""
    return os.environ.get(HEALTH_ENV, "1") != "0"


def stall_after_default():
    """EDL_STALL_AFTER_SECS resolves the watchdog budget when the
    config leaves it unset (default 10 s: far above any healthy step,
    far below the 30 s lease heuristic it exists to beat)."""
    try:
        return float(os.environ.get(STALL_AFTER_ENV, "") or 10.0)
    except ValueError:
        return 10.0


def health_dir_default():
    """$EDL_HEALTH_DIR, or "" = bundles off (stalls still count and
    advertise; only the on-disk dump is skipped)."""
    return os.environ.get(HEALTH_DIR_ENV, "")


# ------------------------------------------------------ recompile sentry


class RecompileSentry(object):
    """Per-named-executable compilation counts, with a steady-state
    boundary. `record_compile` is called from inside the traced
    function (tracked_jit), i.e. on whatever thread triggered the
    compile; reads come from the health/status threads — one lock.

    Vocabulary: a COMPILE is any cache-miss trace of a tracked jit; a
    RECOMPILE is a compile of a name that was already compiled once
    (the engine's call sites all carry fixed shapes per name, so a
    recompile is never legitimate); a STEADY RECOMPILE is a recompile
    after `mark_steady()` — the anomaly class the live-server test
    pins at zero. First compiles of a NEW name after the boundary are fine:
    a prefill bucket first exercised mid-serve is the cold path
    working as designed, not churn recompiling."""

    #: anomaly ring bound (each entry is tiny; 256 outlives any drill)
    MAX_ANOMALIES = 256

    def __init__(self, clock=time.monotonic):
        self._lock = threading.Lock()
        self._clock = clock
        self.compiles = {}  # fn name -> compile count
        self.recompiles = 0
        self.steady_recompiles = 0
        self.steady_at = None
        self.anomalies = deque(maxlen=self.MAX_ANOMALIES)

    def record_compile(self, name):
        anomaly = False
        with self._lock:
            n = self.compiles.get(name, 0) + 1
            self.compiles[name] = n
            if n > 1:
                self.recompiles += 1
                if self.steady_at is not None:
                    self.steady_recompiles += 1
                    self.anomalies.append(
                        {"fn": name, "count": n, "at": self._clock()}
                    )
                    anomaly = True
        if anomaly:
            # trace-evented: the anomaly is a causal node operators
            # can see next to the requests it slowed (best-effort —
            # the sentry must never make a compile fail)
            try:
                from elasticdl_tpu.observability.tracing import (
                    recorder,
                )

                recorder().start_span(
                    "recompile_anomaly", fn=name, compile_count=n,
                ).finish("anomaly")
            except Exception:  # pragma: no cover - never block
                pass
            logger.warning(
                "runtime health: STEADY-STATE RECOMPILE of %r "
                "(compile #%d) — the zero-recompile invariant is "
                "broken", name, n,
            )

    def mark_steady(self):
        """Declare the warmup over: from here on a recompile is an
        anomaly, not a cold start. Idempotent (the first mark wins, so
        a second warmup pass cannot move the boundary forward past
        real anomalies)."""
        with self._lock:
            if self.steady_at is None:
                self.steady_at = self._clock()

    def total_compiles(self):
        with self._lock:
            return sum(self.compiles.values())

    def snapshot(self):
        with self._lock:
            return {
                "compiles": dict(self.compiles),
                "total_compiles": sum(self.compiles.values()),
                "recompiles": self.recompiles,
                "steady_recompiles": self.steady_recompiles,
                "steady_marked": self.steady_at is not None,
                "anomalies": list(self.anomalies),
            }

    def prometheus(self):
        """The closed labeled family: one `fn` label per tracked
        executable name that compiled at least once."""
        from elasticdl_tpu.observability.metrics import (
            labeled_counter_family,
        )

        with self._lock:
            series = [({"fn": name}, n)
                      for name, n in sorted(self.compiles.items())]
        return [labeled_counter_family(
            "edl_serving_recompiles_total",
            "jit compilations per named executable (recompile sentry; "
            "count > 1 for any fn = the zero-recompile invariant is "
            "broken)",
            series,
        )]


def tracked_jit(fn, name, sentry, **jit_kwargs):
    """`jax.jit(fn)` with compilation counting: the wrapped python
    function body runs exactly once per compile-cache miss (trace =
    compile for pjit), so a trace-time callback IS the compile
    counter — no dependence on private jit internals. `sentry` may be
    a RecompileSentry, None (counting off, still jitted), or a
    zero-arg callable resolving to either at trace time — the lazy
    form lets an engine wrap executables in __init__ and attach the
    sentry afterwards without losing later compiles."""
    import functools

    import jax

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        s = sentry() if callable(sentry) else sentry
        if s is not None:
            s.record_compile(name)
        return fn(*args, **kwargs)

    # wraps() keeps fn's inspectable signature, so jit options that
    # resolve parameter NAMES (static_argnames) still bind correctly
    return jax.jit(traced, **jit_kwargs)


# -------------------------------------------------- memory accountant


def _jax_live_bytes():
    """JAX's view of resident array bytes in this process, plus the
    backend allocator's own stats where the platform provides them
    (TPU/GPU `memory_stats`; CPU returns None)."""
    import jax

    live = 0
    for arr in jax.live_arrays():
        try:
            live += int(arr.nbytes)
        except Exception:  # noqa: BLE001 - a deleted array mid-walk
            continue
    stats = None
    try:
        raw = jax.devices()[0].memory_stats()
        if raw:
            stats = {k: int(v) for k, v in raw.items()
                     if isinstance(v, (int, float))}
    except Exception:  # noqa: BLE001 - CPU backends: no stats
        stats = None
    return live, stats


class DeviceMemoryAccountant(object):
    """Reconciles the runtime's self-reported byte ledger against the
    device's actual holdings.

    Ledger side (what the runtime can NAME): the KV pool's
    `kv_bytes_total` + host-tier bytes + param bytes (the tree the
    engine serves: it keeps no other, and the draft's) + the draft
    pool. Device side: `jax.live_arrays()` byte sum. The difference
    can never be zero — executables pin constants, prefill buffers
    come and go — so the accountant BASELINES at `rebase()` (the
    steady boundary) and reports DRIFT since then:

        unaccounted = max(0, (live - ledger) - baseline)

    A healthy steady-state serve oscillates near zero; a leaked
    buffer (or an executable cache growing per-request) climbs and
    never comes back — which is what the monotone peak watermark
    `memory_unaccounted_peak_bytes` records. `live_bytes_fn` is
    injectable for tests."""

    def __init__(self, engine, live_bytes_fn=None):
        self._engine = engine
        self._live_bytes = live_bytes_fn or _jax_live_bytes
        self._lock = threading.Lock()
        self._baseline = None
        self.unaccounted_bytes = 0
        self.unaccounted_peak_bytes = 0
        self.reconciles = 0
        self.last = {}
        # the drill's deliberate leak: buffers held here are device-
        # resident and absent from every ledger line by construction
        self._leaked = []

    def _param_bytes(self):
        import jax

        seen = set()
        total = 0
        for attr in ("_exec_variables", "_d_variables"):
            tree = getattr(self._engine, attr, None)
            if tree is None:
                continue
            for leaf in jax.tree.leaves(tree):
                nbytes = getattr(leaf, "nbytes", None)
                if nbytes is None:
                    continue
                key = id(leaf)
                if key in seen:
                    continue  # a draft that shares the target's leaves
                seen.add(key)
                total += int(nbytes)
        return total

    def _draft_pool_bytes(self):
        import jax

        pool = getattr(self._engine, "_d_pool", None)
        if pool is None:
            return 0
        # `nbytes` is shape x itemsize, never a buffer read: the
        # scheduler thread donates this tree to the speculative step,
        # and a leaf seen here may already be deleted
        return sum(int(getattr(leaf, "nbytes", 0))
                   for leaf in jax.tree.leaves(pool))

    def ledger(self):
        """The bytes the runtime can account for, by line item."""
        kv = self._engine.kv_stats()
        return {
            "kv_bytes_total": int(kv.get("kv_bytes_total", 0)),
            "kv_host_bytes": int(kv.get("kv_host_bytes", 0)),
            "param_bytes": self._param_bytes(),
            "draft_pool_bytes": self._draft_pool_bytes(),
        }

    def reconcile(self, now=None):
        """One reconciliation pass (health thread cadence). Returns
        the current view dict; updates the drift gauge + peak."""
        ledger = self.ledger()
        ledger_total = sum(ledger.values())
        live, device_stats = self._live_bytes()
        raw_gap = live - ledger_total
        with self._lock:
            if self._baseline is None:
                self._baseline = raw_gap
            unaccounted = max(0, raw_gap - self._baseline)
            self.unaccounted_bytes = unaccounted
            self.unaccounted_peak_bytes = max(
                self.unaccounted_peak_bytes, unaccounted
            )
            self.reconciles += 1
            self.last = {
                "ledger": ledger,
                "ledger_bytes": ledger_total,
                "live_bytes": live,
                "baseline_gap_bytes": self._baseline,
                "unaccounted_bytes": unaccounted,
                "unaccounted_peak_bytes": self.unaccounted_peak_bytes,
                "device_stats": device_stats,
            }
            return dict(self.last)

    def rebase(self):
        """Re-baseline the drift at the CURRENT gap — the steady
        boundary calls this so warmup's executable constants never
        masquerade as a leak. The peak resets too: pre-steady drift
        is definitionally not a leak, and the watermark must answer
        'has it drifted SINCE steady'."""
        ledger_total = sum(self.ledger().values())
        live, _ = self._live_bytes()
        with self._lock:
            self._baseline = live - ledger_total
            self.unaccounted_bytes = 0
            self.unaccounted_peak_bytes = 0

    def leak_for_drill(self, nbytes):
        """Allocate and HOLD a device buffer the ledger cannot name —
        the fault-injection payload that proves reconciliation
        convicts a real leak (never called outside the health_leak
        hook)."""
        import jax.numpy as jnp

        buf = jnp.zeros((max(1, int(nbytes)),), jnp.int8)
        buf.block_until_ready()
        with self._lock:
            self._leaked.append(buf)
        logger.warning(
            "runtime health: health_leak fault leaked %d device "
            "bytes on purpose", buf.nbytes,
        )
        return int(buf.nbytes)

    def snapshot(self):
        with self._lock:
            return {
                "unaccounted_bytes": self.unaccounted_bytes,
                "unaccounted_peak_bytes": self.unaccounted_peak_bytes,
                "reconciles": self.reconciles,
                "leaked_buffers": len(self._leaked),
                "last": dict(self.last),
            }


# ---------------------------------------------- watchdog + flight ring


class FlightRecorder(object):
    """Bounded ring of per-tick engine snapshots — the drop-OLDEST +
    monotone `dropped` contract every ring in the system keeps. The
    scheduler records; the bundle dump and status threads snapshot."""

    def __init__(self, capacity=256):
        self.capacity = max(1, int(capacity))
        self._lock = threading.Lock()
        self._ring = deque(maxlen=self.capacity)
        self.recorded = 0
        self.dropped = 0

    def record(self, snap):
        with self._lock:
            if len(self._ring) == self.capacity:
                self.dropped += 1
            self._ring.append(dict(snap))
            self.recorded += 1

    def snapshot(self):
        with self._lock:
            return [dict(s) for s in self._ring]


class ProgressWatchdog(object):
    """Stall = work is seated (or queued) but the progress counter has
    not moved for `stall_after_secs`. Idle (no work anywhere) is
    healthy by definition, and the caller folds jit compiles into the
    progress counter so a cold compile can never read as a stall.
    `observe()` returns True exactly on the ok->stalled transition
    (the bundle-dump edge); recovery (tokens flow again) returns to
    "ok" silently."""

    def __init__(self, stall_after_secs=10.0, clock=time.monotonic):
        self.stall_after_secs = float(stall_after_secs)
        self._clock = clock
        self._lock = threading.Lock()
        self.state = "ok"
        self.stalls = 0
        self._last_progress_at = clock()
        self._last_counter = None
        self._last_work = 0

    def observe(self, work, progress_counter, now=None):
        now = self._clock() if now is None else now
        with self._lock:
            if (self._last_counter is None
                    or progress_counter != self._last_counter
                    or not work):
                self._last_progress_at = now
            self._last_counter = progress_counter
            self._last_work = work
            age = now - self._last_progress_at
            stalled = bool(work) and age >= self.stall_after_secs
            transition = stalled and self.state != "stalled"
            self.state = "stalled" if stalled else "ok"
            if transition:
                self.stalls += 1
            return transition

    def last_progress_age_ms(self, now=None):
        """Ms since progress last moved WITH work present; an idle
        watchdog reads 0 (the wire contract: 0 = idle or moving)."""
        now = self._clock() if now is None else now
        with self._lock:
            if not self._last_work:
                return 0.0
            return max(0.0, (now - self._last_progress_at) * 1000.0)

    def snapshot(self, now=None):
        now = self._clock() if now is None else now
        with self._lock:
            age = (
                max(0.0, (now - self._last_progress_at) * 1000.0)
                if self._last_work else 0.0
            )
            return {
                "state": self.state,
                "stalls": self.stalls,
                "last_progress_age_ms": age,
                "stall_after_secs": self.stall_after_secs,
            }


# ------------------------------------------------------- bundle writer


def _all_thread_stacks():
    """All-thread stacks, twice over: faulthandler's raw dump (the
    signal-safe ground truth — it shows frames even for threads the
    interpreter-level walk cannot name) plus a python-level walk with
    thread NAMES, which is what makes the bundle readable."""
    fh = ""
    try:
        buf = io.StringIO()
        faulthandler.dump_traceback(file=buf, all_threads=True)
        fh = buf.getvalue()
    except Exception:  # noqa: BLE001 - some files reject dump
        fh = ""
    names = {t.ident: t.name for t in threading.enumerate()}
    frames = {}
    try:
        import sys

        frames = sys._current_frames()
    except Exception:  # noqa: BLE001 - best effort
        frames = {}
    threads = []
    for ident, frame in frames.items():
        threads.append({
            "thread": names.get(ident, "ident-%s" % ident),
            "stack": traceback.format_stack(frame),
        })
    return {"faulthandler": fh, "threads": threads}


#: required bundle keys -> required type (the drill's schema gate)
_BUNDLE_SCHEMA_KEYS = {
    "schema": str,
    "reason": str,
    "pid": int,
    "unix_ts": float,
    "health": dict,
    "ring": list,
    "kv_ledger": dict,
    "memory": dict,
    "recompiles": dict,
    "stacks": dict,
}


def validate_bundle(bundle):
    """Schema-gate a diagnostic bundle dict; returns a list of
    problems ([] = valid). The drill and the unit tests call this so
    'a bundle was written' always means 'a bundle a human can read'."""
    problems = []
    if not isinstance(bundle, dict):
        return ["bundle is not a dict"]
    for key, typ in _BUNDLE_SCHEMA_KEYS.items():
        if key not in bundle:
            problems.append("missing key %r" % key)
        elif not isinstance(bundle[key], typ):
            problems.append(
                "key %r: expected %s, got %s"
                % (key, typ.__name__, type(bundle[key]).__name__)
            )
    if bundle.get("schema") != BUNDLE_SCHEMA:
        problems.append("schema %r != %r"
                        % (bundle.get("schema"), BUNDLE_SCHEMA))
    stacks = bundle.get("stacks")
    if isinstance(stacks, dict) and not (
            stacks.get("faulthandler") or stacks.get("threads")):
        problems.append("stacks carry neither faulthandler text nor "
                        "a thread walk")
    return problems


def write_bundle(health_dir, bundle):
    """Atomic (tmp+rename) JSON dump — the span-export contract: a
    reader never sees a torn bundle. Returns the final path."""
    os.makedirs(health_dir, exist_ok=True)
    name = "health-bundle-%d-%d.json" % (
        bundle.get("pid", os.getpid()), bundle.get("seq", 0),
    )
    path = os.path.join(health_dir, name)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(bundle, f, indent=1, default=str)
    os.replace(tmp, path)
    return path


# --------------------------------------------------------- the owner


class RuntimeHealth(object):
    """The health plane's owner: one sentry + accountant + watchdog +
    flight ring, and the daemon thread that drives checks/reconciles
    INDEPENDENTLY of the scheduler (whose failure is the thing under
    observation).

    Wiring: GenerationServer constructs it when
    `ServingConfig.runtime_health` is on, attaches `self.sentry` to
    the engine (which forwards it to the KV pool and the offline
    decode caches), hands `record_tick` to the scheduler loop, and
    reads `snapshot()` for ServerStatus. The telemetry mirror follows
    the PR 11 pattern (the pool's `_sync_host_telemetry`): the sentry
    and watchdog are the single source of truth; the closed telemetry
    counters/gauges receive DELTAS so the scrape plane can never
    drift from them."""

    def __init__(self, engine, queue, telemetry,
                 stall_after_secs=None, check_secs=0.25,
                 reconcile_secs=2.0, ring_capacity=256,
                 health_dir=None, injector=None,
                 clock=time.monotonic, live_bytes_fn=None):
        self._engine = engine
        self._queue = queue
        self._telemetry = telemetry
        self._clock = clock
        self.check_secs = float(check_secs)
        self.reconcile_secs = float(reconcile_secs)
        self.health_dir = (
            health_dir_default() if health_dir is None else health_dir
        )
        self._injector = injector
        self.sentry = RecompileSentry(clock=clock)
        self.accountant = DeviceMemoryAccountant(
            engine, live_bytes_fn=live_bytes_fn
        )
        self.watchdog = ProgressWatchdog(
            stall_after_default() if stall_after_secs is None
            else stall_after_secs,
            clock=clock,
        )
        self.recorder = FlightRecorder(capacity=ring_capacity)
        self.phase_watch = PhaseWatcher(period_secs=check_secs, clock=clock)
        self.bundles = []  # paths written (drill/status introspection)
        self._bundle_seq = 0
        self._leak_checked = False
        self._steady_seen = 0  # steady_recompiles mirrored so far
        self._stalls_seen = 0
        self._last_reconcile = 0.0
        self._stop = threading.Event()
        self._thread = None

    # ------------------------------------------------------ lifecycle

    def start(self):
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="runtime-health"
        )
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None

    def _run(self):
        while not self._stop.is_set():
            try:
                self.phase_watch.wake()
                now = self._clock()
                self.check(now)
                if now - self._last_reconcile >= self.reconcile_secs:
                    self.reconcile(now)
                    self._last_reconcile = now
            except Exception:  # noqa: BLE001 - the loop must survive
                logger.exception("runtime health tick failed")
            self.phase_watch.sleep(self._stop, self.check_secs)

    # ------------------------------------------------------- feeding

    def mark_steady(self):
        """The post-warmup boundary: recompiles become anomalies and
        the memory baseline re-anchors past the warmup's executable
        constants."""
        self.sentry.mark_steady()
        try:
            self.accountant.rebase()
        except Exception:  # noqa: BLE001 - a bare test engine
            logger.exception("runtime health: rebase failed")

    def record_tick(self, queue_depth, active_slots, step_secs,
                    tokens_committed, kv=None):
        """One scheduler tick into the flight ring (scheduler thread).
        `kv` is the `engine.kv_stats()` the tick has just read (the
        ring shows the pool the way the stalled step last saw it);
        a caller without one has it read here."""
        if kv is None:
            try:
                kv = self._engine.kv_stats()
            except Exception:  # noqa: BLE001 - mid-teardown
                kv = {}
        self.recorder.record({
            "t": self._clock(),
            "queue_depth": int(queue_depth),
            "active_slots": int(active_slots),
            "step_ms": round(float(step_secs) * 1000.0, 3),
            "tokens_committed": int(tokens_committed),
            "kv_blocks_free": kv.get("kv_blocks_free", 0),
            "kv_blocks_cached": kv.get("kv_blocks_cached", 0),
            "kv_host_blocks": kv.get("kv_host_blocks", 0),
            "kv_bytes_in_use": kv.get("kv_bytes_in_use", 0),
        })

    # ------------------------------------------------------- checking

    def _progress_counter(self):
        """Tokens committed + compiles finished: either moving means
        the scheduler is ALIVE. The counter dict read is a GIL-atomic
        int fetch — deliberately lock-free (a stale read delays
        detection by one check period, never fabricates a stall)."""
        tokens = self._telemetry.counters.get("tokens_generated", 0)
        return tokens + self.sentry.total_compiles()

    def _work_present(self):
        try:
            seated = self._engine.active_count()
        except Exception:  # noqa: BLE001 - mid-teardown
            seated = 0
        try:
            queued = len(self._queue)
        except Exception:  # noqa: BLE001
            queued = 0
        return seated + queued

    def check(self, now=None):
        """One watchdog evaluation (health thread, or any thread —
        the drill's status reads converge on the same state). On the
        ok->stalled transition: count the stall, dump the bundle."""
        now = self._clock() if now is None else now
        transition = self.watchdog.observe(
            self._work_present(), self._progress_counter(), now=now
        )
        if transition:
            self._telemetry.count("stalls")
            try:
                from elasticdl_tpu.observability.tracing import (
                    recorder,
                )

                recorder().start_span(
                    "progress_stall",
                    age_ms=self.watchdog.last_progress_age_ms(now),
                ).finish("stalled")
            except Exception:  # pragma: no cover - best effort
                pass
            self.dump_bundle("progress_stall")
        return transition

    def reconcile(self, now=None):
        """One ledger reconciliation + telemetry mirror pass (health
        thread cadence). The health_leak fault hook fires here — the
        drill's deliberate leak happens exactly once per armed rule,
        then the next reconcile convicts it."""
        self._maybe_leak()
        try:
            self.accountant.reconcile(now)
        except Exception:  # noqa: BLE001 - bare test engines
            logger.exception("runtime health: reconcile failed")
        snap = self.accountant.snapshot()
        self._telemetry.gauge("memory_unaccounted_bytes",
                              snap["unaccounted_peak_bytes"])
        self._telemetry.gauge(
            "last_progress_age_ms",
            self.watchdog.last_progress_age_ms(now),
        )
        # mirror the sentry's anomaly count by delta (single source
        # of truth stays the sentry)
        steady = self.sentry.snapshot()["steady_recompiles"]
        if steady > self._steady_seen:
            self._telemetry.count("steady_recompiles",
                                  steady - self._steady_seen)
            self._steady_seen = steady

    def _maybe_leak(self):
        # the drill's leak tests STEADY-STATE reconciliation: firing
        # before the warmup boundary would be absorbed by the rebase
        if (self._injector is None
                or not self.sentry.snapshot()["steady_marked"]):
            return
        try:
            self._injector.intercept("health_leak")
        except Exception:  # noqa: BLE001 - the armed rule fired
            self.accountant.leak_for_drill(8 << 20)

    # ------------------------------------------------------- reading

    def health_state(self, now=None):
        return self.watchdog.state

    def snapshot(self, now=None):
        now = self._clock() if now is None else now
        wd = self.watchdog.snapshot(now)
        sentry = self.sentry.snapshot()
        mem = self.accountant.snapshot()
        return {
            "health_state": wd["state"],
            "last_progress_age_ms": wd["last_progress_age_ms"],
            "stalls": wd["stalls"],
            "jit_compiles": sentry["total_compiles"],
            "recompiles": sentry["recompiles"],
            "steady_recompiles": sentry["steady_recompiles"],
            "steady_marked": sentry["steady_marked"],
            "memory_unaccounted_bytes": mem["unaccounted_peak_bytes"],
            "bundles": list(self.bundles),
            "ring_recorded": self.recorder.recorded,
        }

    def prometheus(self):
        """Exposition families only the health plane can render: the
        per-fn recompile family. (The scalar gauges/counters ride the
        closed telemetry sets via the mirror.)"""
        return self.sentry.prometheus()

    # --------------------------------------------------------- bundle

    def dump_bundle(self, reason, now=None):
        """Atomically dump the diagnostic bundle; returns the path or
        None (no EDL_HEALTH_DIR = advertise-only mode)."""
        now = self._clock() if now is None else now
        if not self.health_dir:
            return None
        try:
            kv = self._engine.kv_stats()
        except Exception:  # noqa: BLE001
            kv = {}
        self._bundle_seq += 1
        bundle = {
            "schema": BUNDLE_SCHEMA,
            "reason": reason,
            "pid": os.getpid(),
            "seq": self._bundle_seq,
            "unix_ts": time.time(),
            "health": self.watchdog.snapshot(now),
            "ring": self.recorder.snapshot(),
            "ring_dropped": self.recorder.dropped,
            "kv_ledger": kv,
            "memory": self.accountant.snapshot(),
            "recompiles": self.sentry.snapshot(),
            "stacks": _all_thread_stacks(),
            # the phases open right now on every thread, and the slow
            # phases kept with what lay beneath them (tracing.py)
            "open_phases": tracing.open_phases(),
            "slow_phases": tracing.recorder().slow_json(),
        }
        try:
            path = write_bundle(self.health_dir, bundle)
        except OSError:
            logger.exception("runtime health: bundle dump failed")
            return None
        self.bundles.append(path)
        logger.warning("runtime health: %s bundle dumped to %s",
                       reason, path)
        return path


# ------------------------------------------------------------- SIGUSR2


def install_sigusr2_dump(to_health_dir=True):
    """Register SIGUSR2 -> faulthandler all-thread stack dump, so a
    live wedged process can always be interrogated without killing
    it:

        kill -USR2 <pid>

    With $EDL_HEALTH_DIR set (and to_health_dir), stacks append to
    `sigusr2-<pid>.txt` there — interrogation survives a rotated or
    discarded stderr; otherwise they go to stderr. Returns the dump
    file path ("" = stderr). Idempotent and best-effort: entrypoints
    call it unconditionally, and a platform without SIGUSR2 or
    faulthandler registration (threads, exotic runtimes) is a no-op,
    never a crash."""
    try:
        target = ""
        stream = None
        if to_health_dir and health_dir_default():
            os.makedirs(health_dir_default(), exist_ok=True)
            target = os.path.join(
                health_dir_default(), "sigusr2-%d.txt" % os.getpid()
            )
            stream = open(target, "a")  # noqa: SIM115 - lives forever
        faulthandler.register(
            signal.SIGUSR2, all_threads=True, chain=False,
            **({"file": stream} if stream is not None else {}),
        )
        logger.info(
            "SIGUSR2 stack dump armed (-> %s)", target or "stderr"
        )
        return target
    except (AttributeError, ValueError, OSError):
        # no SIGUSR2 (platform) / not the main thread / bad dir
        logger.warning("SIGUSR2 stack dump could not be registered")
        return None
