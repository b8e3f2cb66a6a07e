"""Serving telemetry on the dependency-free TensorBoard event path.

Same substrate as the master's training gauges (common/tb_events.py —
the recovery gauges ride it too), so one TensorBoard logdir shows the
whole system. Gauges, stepped by decode-step index:

    serving/queue_depth        queued backlog at the step
    serving/active_slots       slots decoding at the step
    serving/step_ms            wall time of the decode step
    serving/tokens_per_sec     tokens committed / wall over the window
    serving/ttft_ms            per-request time-to-first-token (written
                               at each request's first token)
    serving/kv_bytes_in_use    KV bytes live requests pin at the step
    serving/kv_blocks_free     paged pool's free blocks at the step
    serving/kv_host_blocks     spilled chain blocks parked host-side
    serving/kv_host_bytes      host spill-tier bytes at the step
    serving/queue_wait_ms      EWMA of time-queued-before-seating (the
                               router's load signal; ServerStatus field)
    serving/ttft_p99           histogram percentiles, one scalar per
    serving/e2e_p99            flush window (see below)
    serving/prefix_hit_rate_window  windowed share of prompt tokens
                               seated by prefix incref/revival — the
                               warm-capacity signal (ring-derived)
    serving/admitted_total     monotone counters, one scalar per flush
    serving/rejected_total
    serving/expired_total
    serving/completed_total
    serving/reloads_total

Latency distributions live in fixed-bucket log-linear histograms
(observability/histogram.py) — TTFT, queue wait, step time and
end-to-end latency — NOT in point-gauges: the status RPCs report
p50/p90/p99 from them, the router merges the raw bucket counts across
replicas, and the drills compute their client-side percentiles with
the same histogram code, so drill numbers and live numbers are
definitionally identical.

The LIVE signal plane (observability/metrics.py): every telemetry
object also feeds a windowed **TimeSeriesRing** — fixed-interval
snapshots of counter deltas, last gauges and histogram BUCKET deltas —
which is what the Prometheus `/metrics` exposition, the windowed
prefix-hit-rate and the router's SLO burn-rate engine read. The ring
and the tb_events path flush through the SAME lock at the SAME points,
and `close()` lands the final partial window in BOTH: a server stopped
mid-window reports identical totals to the event file and to the last
ring window (pinned by a regression test).

The snapshot derives the memory-efficiency headline
`kv_bytes_per_token` = sum-over-steps(kv_bytes_in_use) /
tokens_generated: the average KV bytes RESIDENT per generated token.

Counters also back the ServerStatus RPC via snapshot() — the RPC must
work with telemetry disabled (no log_dir), so counters live here and
the event writer is optional. The counter NAME SET is closed
(`COUNTERS`): count() raises on anything undeclared, because a typo'd
name would silently fork a fresh counter and under-report the real
one forever. The GAUGE set is closed the same way (`GAUGES` /
`gauge()`) — a typo'd gauge tag would fork a dead TensorBoard series
and a dead Prometheus series just as silently. edl-lint EDL401 flags
literal call sites of BOTH statically; the raises catch dynamic names.

Thread-safety: the scheduler thread writes step gauges; gRPC threads
bump admission counters and read snapshots; the metrics-exposition
thread reads `prometheus()` — everything under one lock (the writes
are tiny appends; contention is negligible next to a decode step)."""

import threading
import time

from elasticdl_tpu.common.tb_events import EventFileWriter
from elasticdl_tpu.observability.forensics import CAUSES
from elasticdl_tpu.observability.histogram import LogLinearHistogram
from elasticdl_tpu.observability.metrics import (
    TimeSeriesRing,
    counter_family,
    gauge_family,
    hist_family,
    labeled_counter_family,
)


class ServingTelemetry(object):
    #: the closed counter set — count() REJECTS anything else.
    #: prefix_hit_tokens counts prompt tokens seated by shared-prefix
    #: incref (never re-prefilled), prompt_tokens EVERY prompt token
    #: seated (the hit-rate denominator), cow_copies the copy-on-write
    #: faults, draft_proposed/draft_accepted the speculative-decode
    #: proposal economy (accept rate = accepted / proposed).
    #: The tiered-KV trio: revive_uploads counts batched host->device
    #: revival scatters, prefill_tokens_revived the prompt tokens
    #: those uploads seated WITHOUT re-running prefill, host_drops the
    #: spilled entries the bounded host LRU (or a reload flush)
    #: discarded.
    #: The runtime-health pair (observability/runtime_health.py):
    #: steady_recompiles counts post-warmup-boundary recompiles of an
    #: already-compiled executable (the zero-recompile anomaly class;
    #: the per-fn distribution is the sentry's own labeled
    #: edl_serving_recompiles_total{fn=} family), stalls the
    #: ok->stalled watchdog transitions (work seated, no progress for
    #: the budget — each one also dumps a diagnostic bundle).
    COUNTERS = ("admitted", "rejected", "expired", "completed",
                "tokens_generated", "reloads", "prefix_hit_tokens",
                "prompt_tokens", "cow_copies", "draft_proposed",
                "draft_accepted", "revive_uploads",
                "prefill_tokens_revived", "host_drops",
                "steady_recompiles", "stalls")
    #: the closed gauge set — gauge()/_gauge_locked REJECT anything
    #: else, exactly like the counters (EDL401 is the static twin for
    #: both). These are the serving/<name> TensorBoard tags and the
    #: edl_serving_<name> Prometheus series.
    #: last_progress_age_ms / memory_unaccounted_bytes are the
    #: runtime-health plane's scrape surface (watchdog age at the
    #: last reconcile; the memory accountant's monotone PEAK
    #: unaccounted-drift watermark)
    GAUGES = ("queue_depth", "active_slots", "step_ms",
              "tokens_per_sec", "ttft_ms", "queue_wait_ms",
              "kv_bytes_in_use", "kv_blocks_free", "kv_host_blocks",
              "kv_host_bytes", "ttft_p99", "e2e_p99",
              "prefix_hit_rate_window", "last_progress_age_ms",
              "memory_unaccounted_bytes")
    #: latency histograms (ms), all on the shared bucket scheme
    HISTOGRAMS = ("ttft_ms", "queue_wait_ms", "step_ms", "e2e_ms")
    #: the closed slow-cause label set (observability/forensics.py
    #: CAUSES — single source of truth): count_slow_cause() REJECTS
    #: anything else, exactly like count()/gauge(), and EDL401 is the
    #: static twin. One labeled Prometheus counter family
    #: (edl_serving_slow_cause_total{cause=...}) makes the
    #: DISTRIBUTION OF WHY terminally-slow requests were slow
    #: scrapeable, not just the that.
    SLOW_CAUSES = CAUSES
    #: the windowed prefix-hit-rate's trailing horizon (secs): long
    #: enough to smooth a single burst, short enough that a router
    #: reading it sees the CURRENT warm-capacity regime
    PREFIX_HIT_HORIZON_SECS = 30.0

    def __init__(self, log_dir=None, flush_every=50, clock=time.monotonic,
                 ring_secs=1.0, ring_windows=240, exemplars=True):
        self._log_dir = log_dir
        self._flush_every = max(1, int(flush_every))
        self._clock = clock
        self._lock = threading.Lock()
        self._writer = None
        self._started = clock()
        # exemplars=False drops trace ids at the record sites (the
        # overhead A/B's OFF leg); the histograms themselves are
        # unchanged either way
        self._exemplars = bool(exemplars)
        self.counters = {name: 0 for name in self.COUNTERS}
        self.gauges = {name: 0.0 for name in self.GAUGES}
        self.slow_causes = {name: 0 for name in self.SLOW_CAUSES}
        self.hists = {name: LogLinearHistogram()
                      for name in self.HISTOGRAMS}
        # the live metrics plane: windowed counter/bucket deltas
        # (observability/metrics.py), fed under this lock at flush
        # cadence; /metrics, the SLO engine and the windowed
        # prefix-hit-rate all read it
        self.ring = TimeSeriesRing(interval_secs=ring_secs,
                                   capacity=ring_windows, clock=clock)
        self.max_active_slots = 0
        self.kv_bytes_in_use_peak = 0
        self._kv_byte_steps = 0  # sum of kv_bytes_in_use over steps
        self._queue_wait_ewma_ms = 0.0
        self._queue_waits_seen = 0
        self._step = 0
        self._window_tokens = 0
        self._window_t0 = clock()
        self._counters_flushed_at = 0  # step of the last counter flush
        self._dirty = False  # anything recorded since the last flush

    def _ensure_writer(self):
        if self._writer is None and self._log_dir:
            self._writer = EventFileWriter(
                self._log_dir, filename_suffix=".serving"
            )
        return self._writer

    def _scalar(self, tag, value, step):
        writer = self._ensure_writer()
        if writer is not None:
            writer.add_scalar(tag, float(value), step)

    def _gauge_locked(self, name, value, step=None):
        """One gauge write: last-value for the ring/exposition + a
        TensorBoard scalar. Closed set — see the class docstring.
        Caller holds the lock."""
        if name not in self.gauges:
            raise ValueError(
                "unknown serving gauge %r (declared: %s) — a typo "
                "here would fork a dead series"
                % (name, ", ".join(self.GAUGES))
            )
        self.gauges[name] = float(value)
        self._scalar("serving/%s" % name, value,
                     self._step if step is None else step)

    def gauge(self, name, value):
        """Public gauge entry for callers outside this class (the
        engine, the supervisor); internal call sites already hold the
        lock and use _gauge_locked."""
        with self._lock:
            self._gauge_locked(name, value)

    def _ring_observe_locked(self, roll=True):
        """Feed the ring one CUMULATIVE snapshot (it differences at
        window boundaries). Caller holds the lock. Copying the trimmed
        bucket lists is the whole cost, so hot paths gate this behind
        ring.due(). Slow-cause counts ride as `slow_cause.<cause>`
        counters so window deltas carry the why-distribution too."""
        counters = dict(self.counters)
        for cause, n in self.slow_causes.items():
            counters["slow_cause.%s" % cause] = n
        self.ring.observe(
            counters=counters,
            gauges=self.gauges,
            hists={name: h.to_counts()
                   for name, h in self.hists.items()},
            exemplars={name: h.exemplars
                       for name, h in self.hists.items()
                       if h.exemplars},
            roll=roll,
        )

    # ------------------------------------------------------------ events

    def count(self, name, n=1):
        with self._lock:
            if name not in self.counters:
                raise ValueError(
                    "unknown serving counter %r (declared: %s) — a "
                    "typo here would silently fork a new counter"
                    % (name, ", ".join(self.COUNTERS))
                )
            self.counters[name] += n
            self._dirty = True

    def count_slow_cause(self, cause, n=1):
        """One terminally-slow request attributed to `cause` — the
        dominant label forensics.attribute() produced. Closed set,
        same contract as count(): a typo'd cause would silently fork a
        dead series."""
        with self._lock:
            if cause not in self.slow_causes:
                raise ValueError(
                    "unknown slow cause %r (declared: %s) — a typo "
                    "here would silently fork a new series"
                    % (cause, ", ".join(self.SLOW_CAUSES))
                )
            self.slow_causes[cause] += n
            self._dirty = True

    def reset_latency(self):
        """Drop the latency DISTRIBUTIONS (histograms + the queue-wait
        EWMA) without touching the monotone counters. The pre-ready
        warmup path (serving/main.py --warmup_tokens) calls this so
        the jit-compile latency of a request no client ever sent can
        never surface in the percentiles a router/autoscaler SLOs on.
        The ring restarts with the histograms: a warmup window must
        not seed the burn-rate horizon either."""
        with self._lock:
            for name in self.hists:
                self.hists[name] = LogLinearHistogram()
            self._queue_wait_ewma_ms = 0.0
            self._queue_waits_seen = 0
            self.ring = TimeSeriesRing(
                interval_secs=self.ring.interval_secs,
                capacity=self.ring.capacity, clock=self._clock,
            )

    def record_ttft(self, request):
        """Time-to-first-token for one request, at its first token.
        The request's trace_id rides into the TTFT histogram as a
        bucket exemplar, so a scraped p99 bucket names a real trace."""
        ttft_ms = (self._clock() - request.submitted_at) * 1000.0
        trace_id = (getattr(request, "trace_id", "")
                    if self._exemplars else "")
        with self._lock:
            self._dirty = True
            self.hists["ttft_ms"].record(ttft_ms,
                                         trace_id=trace_id or None)
            self._gauge_locked("ttft_ms", ttft_ms)
            if self.ring.due():
                self._ring_observe_locked()
        return ttft_ms

    def record_e2e(self, latency_ms, trace_id=None):
        """End-to-end latency of one COMPLETED request (admission ->
        final token). Expired/rejected requests don't land here — the
        histogram answers "how long does a successful request take",
        the counters answer how many weren't."""
        with self._lock:
            self._dirty = True
            self.hists["e2e_ms"].record(
                latency_ms,
                trace_id=trace_id if self._exemplars else None,
            )

    # EWMA, not a running mean: the router reads this as a LOAD signal,
    # so it must track the current regime, not the lifetime average
    QUEUE_WAIT_ALPHA = 0.3

    def record_queue_wait(self, wait_secs, trace_id=None):
        """Time one request spent queued before seating. Feeds the
        queue_wait_ms EWMA the router folds into least-loaded routing
        (ServerStatus.queue_wait_ms) and the queue-wait histogram
        behind the percentile fields."""
        wait_ms = wait_secs * 1000.0
        with self._lock:
            if self._queue_waits_seen == 0:
                self._queue_wait_ewma_ms = wait_ms
            else:
                a = self.QUEUE_WAIT_ALPHA
                self._queue_wait_ewma_ms = (
                    a * wait_ms + (1.0 - a) * self._queue_wait_ewma_ms
                )
            self._queue_waits_seen += 1
            self.hists["queue_wait_ms"].record(
                wait_ms,
                trace_id=trace_id if self._exemplars else None,
            )
            self._gauge_locked("queue_wait_ms",
                               self._queue_wait_ewma_ms)
        return wait_ms

    def record_step(self, queue_depth, active_slots, step_secs,
                    tokens_committed, kv_bytes_in_use=None,
                    kv_blocks_free=None, kv_host_blocks=None,
                    kv_host_bytes=None):
        """Per-decode-step gauges; counters flush every flush_every
        steps so the event file stays O(steps / flush_every)."""
        with self._lock:
            self._dirty = True
            self._step += 1
            self.max_active_slots = max(
                self.max_active_slots, active_slots
            )
            self.counters["tokens_generated"] += tokens_committed
            self._window_tokens += tokens_committed
            self.hists["step_ms"].record(step_secs * 1000.0)
            if kv_bytes_in_use is not None:
                self.kv_bytes_in_use_peak = max(
                    self.kv_bytes_in_use_peak, kv_bytes_in_use
                )
                self._kv_byte_steps += kv_bytes_in_use
                self._gauge_locked("kv_bytes_in_use", kv_bytes_in_use)
            if kv_blocks_free is not None:
                self._gauge_locked("kv_blocks_free", kv_blocks_free)
            if kv_host_blocks is not None:
                self._gauge_locked("kv_host_blocks", kv_host_blocks)
            if kv_host_bytes is not None:
                self._gauge_locked("kv_host_bytes", kv_host_bytes)
            self._gauge_locked("queue_depth", queue_depth)
            self._gauge_locked("active_slots", active_slots)
            self._gauge_locked("step_ms", step_secs * 1000.0)
            if self._step % self._flush_every == 0:
                self._flush_window_locked()
            if self.ring.due():
                self._ring_observe_locked()

    def _prefix_hit_rate_locked(self):
        """Windowed warm-capacity signal: the share of prompt tokens
        seated WITHOUT paying prefill compute (prefix incref + spilled
        revival) over the trailing horizon — closed ring windows plus
        the open partial, so the first seconds of a burst already
        register. Caller holds the lock."""
        horizon = self.PREFIX_HIT_HORIZON_SECS
        # the live partial comes from the COUNTERS directly (the ring
        # only learns cumulative values at observe points, which the
        # hot path gates behind ring.due()) — live minus the open
        # window's baseline is the pending delta
        hit = (self.ring.sum_counter("prefix_hit_tokens", horizon)
               + self.counters["prefix_hit_tokens"]
               - self.ring.baseline_counter("prefix_hit_tokens"))
        total = (self.ring.sum_counter("prompt_tokens", horizon)
                 + self.counters["prompt_tokens"]
                 - self.ring.baseline_counter("prompt_tokens"))
        return hit / total if total > 0 else 0.0

    def _flush_window_locked(self):
        """Close the tokens/sec window and write the counter totals +
        headline percentiles. Caller holds the lock."""
        now = self._clock()
        window = max(now - self._window_t0, 1e-9)
        self._gauge_locked(
            "tokens_per_sec", self._window_tokens / window
        )
        self._window_tokens = 0
        self._window_t0 = now
        for name, value in self.counters.items():
            self._scalar(
                "serving/%s_total" % name, value, self._step
            )
        for hist_name in ("ttft_ms", "e2e_ms"):
            hist = self.hists[hist_name]
            if hist.count:
                self._gauge_locked(
                    "%s_p99" % hist_name.replace("_ms", ""),
                    hist.percentile(99),
                )
        self._gauge_locked("prefix_hit_rate_window",
                           self._prefix_hit_rate_locked())
        self._counters_flushed_at = self._step
        self._dirty = False

    # ---------------------------------------------------------- snapshot

    def snapshot(self):
        with self._lock:
            snap = dict(self.counters)
            snap["max_active_slots"] = self.max_active_slots
            snap["uptime_secs"] = self._clock() - self._started
            snap["steps"] = self._step
            snap["kv_bytes_in_use_peak"] = self.kv_bytes_in_use_peak
            snap["kv_bytes_per_token"] = (
                self._kv_byte_steps
                / max(1, self.counters["tokens_generated"])
            )
            snap["queue_wait_ms"] = self._queue_wait_ewma_ms
            snap["prefix_hit_rate_window"] = (
                self._prefix_hit_rate_locked()
            )
            for prefix in ("ttft", "queue_wait", "e2e", "step"):
                hist = self.hists[prefix + "_ms"]
                for q in (50, 90, 99):
                    snap["%s_p%d_ms" % (prefix, q)] = hist.percentile(q)
            snap["ttft_hist"] = self.hists["ttft_ms"].to_counts()
            snap["queue_wait_hist"] = (
                self.hists["queue_wait_ms"].to_counts()
            )
            # the slow-cause distribution, in declared order (the
            # ServerStatus slow_cause_counts repeated field's contract)
            snap["slow_cause_counts"] = [
                self.slow_causes[c] for c in self.SLOW_CAUSES
            ]
            snap["slow_requests"] = sum(self.slow_causes.values())
            return snap

    def prometheus(self):
        """The exposition families (observability/metrics.py shapes):
        every closed counter as edl_serving_<name>_total, every closed
        gauge as edl_serving_<name>, every histogram with
        _bucket/_sum/_count on the shared bucket scheme, plus the
        ring's drop accounting. Called from the metrics HTTP thread —
        snapshots under the telemetry lock."""
        with self._lock:
            fams = []
            for name in self.COUNTERS:
                fams.append(counter_family(
                    "edl_serving_%s_total" % name,
                    "serving counter %s" % name,
                    self.counters[name],
                ))
            gauges = dict(self.gauges)
            gauges["prefix_hit_rate_window"] = (
                self._prefix_hit_rate_locked()
            )
            for name in self.GAUGES:
                fams.append(gauge_family(
                    "edl_serving_%s" % name,
                    "serving gauge %s" % name,
                    [({}, gauges[name])],
                ))
            for name in self.HISTOGRAMS:
                h = self.hists[name]
                fams.append(hist_family(
                    "edl_serving_%s" % name,
                    "serving latency histogram %s (shared log-linear "
                    "scheme)" % name,
                    [({}, h.to_counts(), h.sum, h.exemplars)],
                ))
            fams.append(labeled_counter_family(
                "edl_serving_slow_cause_total",
                "terminally-slow requests by dominant attributed "
                "cause (observability/forensics.py taxonomy)",
                [({"cause": c}, self.slow_causes[c])
                 for c in self.SLOW_CAUSES],
            ))
            fams.append(gauge_family(
                "edl_serving_ring_windows_dropped",
                "time-series ring windows evicted by the bound",
                [({}, self.ring.dropped)],
            ))
            return fams

    def close(self):
        """Flush the tail, then close the writer. Without this a
        server stopped mid-window under-reported in TensorBoard: the
        partial tokens/sec window and every counter bump since the
        last flush_every boundary never reached the event file. The
        RING flushes at the same point with the same totals — the
        tb_events path and the last ring window must agree on the
        window boundary (regression-pinned), or the scrape plane and
        the event file would tell different stories about the same
        shutdown."""
        with self._lock:
            if self._log_dir and self._dirty:
                # _flush_window_locked creates the writer on demand, so
                # even a server that never reached a flush boundary
                # leaves its final counters on disk
                self._flush_window_locked()
            # final cumulative observation + force-close of the open
            # partial ring window: sum(ring deltas) == final counters
            # == the tb totals written above, by construction
            self._ring_observe_locked(roll=False)
            self.ring.flush()
            if self._writer is not None:
                self._writer.close()
                self._writer = None


class RouterTelemetry(object):
    """The routing tier's gauges/counters on the same event path.

    Gauges, stepped by heartbeat-poll index (the router has no decode
    steps — its clock is the lease-renewal loop):

        router/healthy_replicas   replicas in rotation at the poll
        router/replicas           registered replicas
        router/routed_total       monotone counters, one scalar per
        router/completed_total    flush (routed = accepted dispatches,
        router/redispatched_total completed = returned OK, redispatched
        router/hedges_total       = re-sent after a replica failure,
        router/hedge_wins_total   shed = RESOURCE_EXHAUSTED with no
        router/shed_total         healthy replica, breaker_trips =
        router/breaker_trips_total  closed->open transitions,
        router/affinity_hits_total  affinity_hits/misses = requests
        router/affinity_misses_total  with a prefix fingerprint that
                                  did / did not land on their learned
                                  replica — the decay-ladder telemetry)

    The cell gauges (`router/cell_id`, `router/cells`) identify this
    process inside a multi-cell router tier (serving/router_cell.py);
    a single-cell router reports cell_id=0, cells=1.

    Counters back the router_status RPC via snapshot() — like the
    replica telemetry, the RPC must work with the writer disabled.
    The counter AND gauge name sets are closed (count()/gauge() raise
    on unknowns; edl-lint EDL401 is the static twin for both). The
    router's end-to-end dispatch latency (accept -> terminal outcome,
    re-dispatches and hedges included) rides the shared log-linear
    histogram behind the e2e_p* router_status fields, and snapshot()
    carries the last-observed rotation gauges so operators aren't left
    scraping the event file for fleet size.

    The ring: every heartbeat poll feeds one cumulative observation —
    the router's own counters + e2e buckets PLUS the fleet-merged
    replica histograms the router hands in (`fleet_hists`: last-seen
    cumulative buckets per address, bucket-added — a killed replica's
    history stays in the sum). The SLO burn-rate engine
    (observability/slo.py) reads exactly this ring."""

    COUNTERS = ("routed", "completed", "redispatched", "hedges",
                "hedge_wins", "shed", "breaker_trips", "errors",
                "affinity_hits", "affinity_misses",
                # disaggregated prefill->decode handoffs (serving/
                # disagg.py): a fallback means the request dispatched
                # cold, not that it failed
                "disagg_handoffs", "disagg_fallbacks")
    GAUGES = ("healthy_replicas", "replicas", "cell_id", "cells")

    def __init__(self, log_dir=None, flush_every=20, clock=time.monotonic,
                 ring_secs=2.0, ring_windows=300):
        self._log_dir = log_dir
        self._flush_every = max(1, int(flush_every))
        self._clock = clock
        self._lock = threading.Lock()
        self._writer = None
        self._started = clock()
        self._poll = 0
        self._dirty = False
        self.counters = {name: 0 for name in self.COUNTERS}
        self.gauges = {name: 0.0 for name in self.GAUGES}
        self.hists = {"e2e_ms": LogLinearHistogram()}
        self.ring = TimeSeriesRing(interval_secs=ring_secs,
                                   capacity=ring_windows, clock=clock)

    def _ensure_writer(self):
        if self._writer is None and self._log_dir:
            self._writer = EventFileWriter(
                self._log_dir, filename_suffix=".router"
            )
        return self._writer

    def _scalar(self, tag, value, step):
        writer = self._ensure_writer()
        if writer is not None:
            writer.add_scalar(tag, float(value), step)

    def _gauge_locked(self, name, value, step=None):
        if name not in self.gauges:
            raise ValueError(
                "unknown router gauge %r (declared: %s)"
                % (name, ", ".join(self.GAUGES))
            )
        self.gauges[name] = float(value)
        self._scalar("router/%s" % name, value,
                     self._poll if step is None else step)

    def gauge(self, name, value):
        with self._lock:
            self._gauge_locked(name, value)

    def count(self, name, n=1):
        with self._lock:
            if name not in self.counters:
                raise ValueError(
                    "unknown router counter %r (declared: %s)"
                    % (name, ", ".join(self.COUNTERS))
                )
            self.counters[name] += n
            self._dirty = True

    def record_e2e(self, latency_ms, trace_id=None):
        """Router-observed end-to-end latency of one dispatch that
        reached a terminal outcome. The request's trace_id becomes a
        bucket exemplar on the e2e histogram — the metrics->traces
        join the fleet collector walks."""
        with self._lock:
            self.hists["e2e_ms"].record(latency_ms,
                                        trace_id=trace_id)

    def record_poll(self, healthy, replicas, fleet_hists=None):
        """One heartbeat sweep: rotation-size gauges now, counters
        every flush_every polls, and one cumulative ring observation
        carrying the router's own counters/buckets plus the
        fleet-merged replica histograms (`fleet_hists`, e.g.
        {"fleet_ttft_ms": cumulative bucket counts}) the burn-rate
        engine windows over."""
        with self._lock:
            self._poll += 1
            self._gauge_locked("healthy_replicas", healthy)
            self._gauge_locked("replicas", replicas)
            if self._poll % self._flush_every == 0:
                for name, value in self.counters.items():
                    self._scalar(
                        "router/%s_total" % name, value, self._poll
                    )
            hists = {"e2e_ms": self.hists["e2e_ms"].to_counts()}
            if fleet_hists:
                hists.update(fleet_hists)
            self.ring.observe(
                counters=self.counters, gauges=self.gauges,
                hists=hists,
                exemplars={"e2e_ms": self.hists["e2e_ms"].exemplars},
            )

    def evaluate_slos(self, engine, now=None):
        """Run a BurnRateEngine over this telemetry's ring UNDER the
        telemetry lock (the ring itself is unlocked by design) — the
        router calls this each heartbeat and caches the reports."""
        with self._lock:
            return engine.evaluate(self.ring, now)

    def snapshot(self):
        with self._lock:
            snap = dict(self.counters)
            snap["uptime_secs"] = self._clock() - self._started
            snap["polls"] = self._poll
            snap["healthy_replicas"] = int(
                self.gauges["healthy_replicas"]
            )
            snap["replicas"] = int(self.gauges["replicas"])
            for q in (50, 90, 99):
                snap["e2e_p%d_ms" % q] = (
                    self.hists["e2e_ms"].percentile(q)
                )
            return snap

    def prometheus(self):
        """Exposition families for the routing tier: closed counters
        and gauges, the router's own e2e histogram, plus every
        fleet-merged histogram the ring carries (the cumulative
        last-seen sums record_poll fed) — so one scrape of the router
        answers fleet-wide TTFT without touching a replica."""
        with self._lock:
            fams = []
            for name in self.COUNTERS:
                fams.append(counter_family(
                    "edl_router_%s_total" % name,
                    "router counter %s" % name,
                    self.counters[name],
                ))
            for name in self.GAUGES:
                fams.append(gauge_family(
                    "edl_router_%s" % name,
                    "router gauge %s" % name,
                    [({}, self.gauges[name])],
                ))
            h = self.hists["e2e_ms"]
            fams.append(hist_family(
                "edl_router_e2e_ms",
                "router end-to-end dispatch latency (shared "
                "log-linear scheme)",
                [({}, h.to_counts(), h.sum, h.exemplars)],
            ))
            for name, counts in sorted(
                    self.ring.latest()["hists"].items()):
                if name == "e2e_ms":
                    continue  # rendered from the live hist above
                fams.append(hist_family(
                    "edl_router_%s" % name,
                    "fleet-merged replica histogram %s (bucket "
                    "addition across the roster)" % name,
                    [({}, counts, None)],
                ))
            fams.append(gauge_family(
                "edl_router_ring_windows_dropped",
                "time-series ring windows evicted by the bound",
                [({}, self.ring.dropped)],
            ))
            return fams

    def close(self):
        with self._lock:
            if self._writer is not None:
                for name, value in self.counters.items():
                    self._scalar(
                        "router/%s_total" % name, value, self._poll
                    )
            # same shutdown contract as the serving telemetry: the
            # final partial window lands in the ring too
            self.ring.observe(counters=self.counters,
                              gauges=self.gauges,
                              hists={"e2e_ms":
                                     self.hists["e2e_ms"].to_counts()},
                              roll=False)
            self.ring.flush()
            if self._writer is not None:
                self._writer.close()
                self._writer = None
