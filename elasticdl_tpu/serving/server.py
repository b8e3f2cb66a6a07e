"""The generation server: scheduler thread + gRPC front-end.

Wiring (one process):

    gRPC threads ──submit──> RequestQueue ──pop──┐
         ^                                       v
         └──events (tokens/done/error)── _Scheduler thread
                                           │ engine.insert / engine.step
                                           │ watcher.poll  (hot reload)
                                           │ telemetry gauges
                                           v
                              PagedContinuousBatchingEngine (jit decode pool)

All jax work happens on the single scheduler thread; gRPC handler
threads only touch the admission queue and their request's event queue,
and block with a LIVENESS BOUND: every wait re-checks the request's
deadline and the scheduler's pulse, so a killed or wedged scheduler
turns into a clean RESOURCE_EXHAUSTED/DEADLINE_EXCEEDED, never a hung
client (the kill-drill's invariant).

Fault injection: the servicer is wrapped at the same choke point the
master uses (common/fault_injection.py, EDL_FAULT_SPEC) with the
serving RPC names — overload and kill drills are spec-driven, e.g.
``generate:error:3`` or ``generate:kill:1:skip=8``.
"""

import collections
import json
import os
import threading
import time
from concurrent import futures

from elasticdl_tpu.common.fault_injection import (
    SERVING_RPCS,
    FaultInjector,
    InjectedRpcError,
    maybe_wrap_servicer,
)
from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.observability import forensics, tracing
from elasticdl_tpu.observability.tracing import recorder
from elasticdl_tpu.proto import elasticdl_pb2 as pb
from elasticdl_tpu.serving.admission import (
    AdmissionError,
    RequestQueue,
    ServingRequest,
)
from elasticdl_tpu.observability.metrics import (
    MetricsServer,
    gauge_family,
    hist_family,
    labeled_counter_family,
    metrics_port_default,
)
from elasticdl_tpu.serving.engine import (
    PagedContinuousBatchingEngine,
    kv_host_bytes_default,
    kv_shared_default,
    prefill_budget_default,
    prefill_chunk_default,
    role_default,
)
from elasticdl_tpu.observability.runtime_health import (
    RuntimeHealth,
    runtime_health_default,
    stall_after_default,
)
from elasticdl_tpu.serving.hot_reload import CheckpointWatcher, ReloadError
from elasticdl_tpu.serving.telemetry import ServingTelemetry


def forensics_default():
    """EDL_FORENSICS resolves the tail-forensics plane (histogram
    exemplars + tail-based trace retention + slow-cause attribution)
    when the config leaves it unset: on unless explicitly '0' — the
    plane's cost is bounded by the bench overhead A/B."""
    return os.environ.get("EDL_FORENSICS", "1") != "0"


def serve_span_classifier(span):
    """Tail-retention verdict for replica `serve` spans (installed on
    the process recorder when forensics is on): a span that expired,
    was rejected or errored is RETAINED — and so is a completed one
    that burned most of its own deadline budget (the replica's
    deadline IS the classifier; no new config surface). Healthy serves
    are sampled."""
    if span.name != "serve":
        return None
    if span.status != "ok":
        return True
    deadline_ms = span.attrs.get("deadline_ms") or 0
    if deadline_ms and span.end is not None:
        e2e_ms = (span.end - span.start) * 1000.0
        if forensics.is_terminally_slow("ok", e2e_ms, deadline_ms):
            return True
    return False


class ServingConfig(object):
    """Server knobs. num_slots sizes the decode pool (the compiled step);
    queue_capacity bounds the admitted backlog (backpressure beyond it);
    top_k/top_p are static server-level sampling filters (per-request
    temperature/seed select greedy vs sampling).

    KV layout: the block-paged pool (serving/kv_pool.py) stores KV
    rows in kv_num_blocks blocks of kv_block_size tokens (0 blocks =
    the rows of num_slots sequences of `seq_len` tokens); with a fixed
    block budget, num_slots can be raised beyond that — short requests
    pack densely instead of pinning `seq_len` rows each.

    kv_shared (None resolves from EDL_KV_SHARED, default
    on) refcounts blocks and dedupes matching prompt prefixes to one
    resident chain (copy-on-write on divergence) — N requests with the
    same system prompt pay for its cache once. draft_k > 0 (with a
    draft model handed to GenerationServer) turns each scheduler tick
    into a speculative draft-verify step committing up to draft_k + 1
    tokens, token-exact with plain decode.

    denoise_steps (a block-diffusion model only, `block_causal` > 1):
    the denoising passes S a block of B positions takes before its
    commit pass (0 = B, one position a pass); B % S == 0 or the server
    refuses to start. A streamed chunk is then a committed block.

    kv_host_bytes (None resolves from EDL_KV_HOST_BYTES,
    default 0 = off) bounds the host-RAM spill tier: evicted prefix
    chains demote to host buffers and revive by device upload instead
    of re-paying prefill — a cell's system-prompt working set survives
    device pressure.

    metrics_port (None resolves from EDL_METRICS_PORT; unset = OFF)
    arms the Prometheus-text /metrics exposition on a stdlib HTTP
    thread (observability/metrics.py): the closed telemetry sets, the
    latency histograms and the loop's phase spans (tracing.phase,
    always on), scrapeable by anything that speaks the text format
    (0 = ephemeral port, for drills/tests)."""

    def __init__(self, num_slots=4, queue_capacity=64, top_k=0,
                 top_p=1.0, checkpoint_dir="", reload_poll_secs=2.0,
                 telemetry_dir="", telemetry_flush_every=50,
                 idle_wait_secs=0.05, handler_poll_secs=0.25,
                 port=0, max_workers=64, kv_block_size=16, kv_num_blocks=0, kv_shared=None,
                 draft_k=0, kv_host_bytes=None, metrics_port=None,
                 forensics=None, runtime_health=None,
                 stall_after_secs=None, health_reconcile_secs=2.0,
                 health_dir=None, role=None, prefill_chunk_tokens=None,
                 prefill_budget_ms=None, denoise_steps=0):
        self.num_slots = int(num_slots)
        self.queue_capacity = int(queue_capacity)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.checkpoint_dir = checkpoint_dir
        self.reload_poll_secs = float(reload_poll_secs)
        self.telemetry_dir = telemetry_dir
        self.telemetry_flush_every = int(telemetry_flush_every)
        self.idle_wait_secs = float(idle_wait_secs)
        self.handler_poll_secs = float(handler_poll_secs)
        self.port = int(port)
        self.max_workers = int(max_workers)
        self.kv_block_size = int(kv_block_size)
        self.kv_num_blocks = int(kv_num_blocks)
        self.kv_shared = (
            kv_shared_default() if kv_shared is None
            else bool(kv_shared)
        )
        self.draft_k = int(draft_k)
        self.denoise_steps = int(denoise_steps)
        self.kv_host_bytes = (
            kv_host_bytes_default() if kv_host_bytes is None
            else int(kv_host_bytes)
        )
        self.metrics_port = (
            metrics_port_default() if metrics_port is None
            else int(metrics_port)
        )
        # the tail-forensics plane (None resolves from EDL_FORENSICS,
        # default on): histogram exemplars at the latency record
        # sites, the serve-span tail-retention classifier, and
        # slow-cause attribution into the slow_cause counter family —
        # one switch so the bench overhead A/B can price all of it
        self.forensics = (
            forensics_default() if forensics is None
            else bool(forensics)
        )
        # the runtime health plane (observability/runtime_health.py;
        # None resolves from EDL_RUNTIME_HEALTH, default on): the
        # recompile sentry on every engine/pool/decode jit site, the
        # device-memory ledger reconciliation, and the progress
        # watchdog + flight recorder behind ServerStatus
        # health_state/last_progress_age_ms — one switch so the bench
        # overhead A/B can price all three layers together
        self.runtime_health = (
            runtime_health_default() if runtime_health is None
            else bool(runtime_health)
        )
        # watchdog budget: work seated but no progress (tokens OR jit
        # compiles) for this long = stalled (None -> EDL_STALL_AFTER_
        # SECS -> 10 s: far above a healthy step, far below the 30 s
        # lease heuristic the self-report exists to beat)
        self.stall_after_secs = (
            stall_after_default() if stall_after_secs is None
            else float(stall_after_secs)
        )
        self.health_reconcile_secs = float(health_reconcile_secs)
        # bundle directory (None resolves from EDL_HEALTH_DIR; "" =
        # advertise-only: stalls count and self-report, no dump)
        self.health_dir = health_dir
        # disaggregated serving (serving/disagg.py). role (None
        # resolves from EDL_SERVING_ROLE, default "unified") is the
        # replica's advertised phase: a router keeps "prefill"
        # replicas out of normal rotation and targets them only for
        # cache-warming handoffs. prefill_chunk_tokens (None resolves
        # from EDL_PREFILL_CHUNK_TOKENS, 0 = off) splits
        # prompt prefill into fixed-token tiles the scheduler
        # interleaves with decode ticks; prefill_budget_ms (None
        # resolves from EDL_PREFILL_BUDGET_MS, default 8.0, <= 0 =
        # unbounded) caps the tile time one tick may spend while
        # decode slots are waiting — at least one tile always runs.
        self.role = role_default() if role is None else str(role)
        if self.role not in ("prefill", "decode", "unified"):
            raise ValueError(
                "role must be prefill|decode|unified, got %r"
                % (self.role,)
            )
        self.prefill_chunk_tokens = (
            prefill_chunk_default() if prefill_chunk_tokens is None
            else int(prefill_chunk_tokens)
        )
        self.prefill_budget_ms = (
            prefill_budget_default() if prefill_budget_ms is None
            else float(prefill_budget_ms)
        )


class _Scheduler(threading.Thread):
    """The continuous-batching loop. Each iteration: reload params if a
    newer checkpoint landed, evict expired sequences, seat queued
    prompts into free slots (prefill), call engine.step() ONCE and
    push the tokens it hands back. The engine keeps one decode step in
    flight: the call launches the next step and then commits the
    older one, so what a tick streams is what the tick before
    launched, and the device computes while this thread streams,
    admits and launches. `active_count()` stays above 0 while a lane's
    last token is still in flight, so an emptying server and a
    draining shutdown keep ticking until every lane has been given
    its last token. Idle (nothing seated, nothing in flight) it parks
    on the queue's condition with a short timeout so reload polling
    stays live."""

    def __init__(self, engine, queue, telemetry, watcher=None,
                 idle_wait_secs=0.05, clock=time.monotonic,
                 forensics_on=True, injector=None, health=None,
                 prefill_budget_ms=0.0):
        super().__init__(daemon=True, name="serving-scheduler")
        self.engine = engine
        self.queue = queue
        self.telemetry = telemetry
        self.watcher = watcher
        self.idle_wait_secs = idle_wait_secs
        # chunked prefill: seated-but-prefilling jobs advance one tile
        # per visit, budgeted per tick while decode slots are waiting
        # (engine.prefill_chunk_tokens = 0 keeps the monolithic insert
        # path)
        self._chunked = bool(engine.prefill_chunk_tokens)
        self.prefill_budget_ms = float(prefill_budget_ms)
        self._pending_prefills = []
        self._tile_ms = 0.0  # EWMA tile cost; prices the budget check
        self._tick_seq = 0  # the `tick` phase span's seq
        # scheduler-thread work submitted by gRPC handlers (chain
        # export/import touch the jax pool, and ALL jax work belongs
        # to this thread); submit_job blocks with a liveness bound
        self._jobs = collections.deque()
        # runtime-health plane (RuntimeHealth or None): the loop feeds
        # its flight ring one snapshot per decode tick
        self.health = health
        # the engine_step fault hook (HEALTH_RPCS): drills inject a
        # scheduler stall (delay) or a dropped tick exactly here —
        # the choke point every decode tick passes through
        self._injector = injector
        # slow-cause attribution at terminal paths (forensics plane)
        self.forensics_on = bool(forensics_on)
        self._clock = clock
        self._stop_requested = threading.Event()
        self._drain = True
        self.crashed = None
        # drain advertisement (ServerStatus.draining): a router takes a
        # draining replica out of rotation for NEW requests while
        # in-flight streams finish. Two independent sources, tracked
        # SEPARATELY so they cannot clobber each other: _stopping is
        # set for good on SIGTERM drain, _reloading only spans a
        # hot-reload swap — a reload finishing while stop() lands
        # concurrently must not clear the permanent advertisement.
        self._stopping = threading.Event()
        self._reloading = threading.Event()

    def is_draining(self):
        return self._stopping.is_set() or self._reloading.is_set()

    def run(self):
        try:
            while not self._stop_requested.is_set():
                self._iterate()
            self._shutdown()
        except BaseException as e:  # noqa: BLE001 - surfaced to handlers
            self.crashed = e
            logger.error("serving scheduler crashed: %r", e)
            self._abort_all("RESOURCE_EXHAUSTED",
                            "scheduler crashed: %r" % (e,))

    def submit_job(self, fn, timeout=30.0):
        """Run `fn` on the scheduler thread and return its result (or
        re-raise its exception). Called from gRPC handler threads for
        work that must serialize with the decode loop — chain export/
        import mutate the jax pool. Liveness-bounded like _events: a
        dead scheduler turns into a clean error, never a hang."""
        done = threading.Event()
        cell = {}

        def job():
            try:
                cell["result"] = fn()
            except BaseException as e:  # noqa: BLE001 - re-raised below
                cell["error"] = e
            done.set()

        self._jobs.append(job)
        self.queue.wake()
        deadline = self._clock() + timeout
        while not done.wait(0.05):
            if self.crashed is not None or not self.is_alive():
                raise AdmissionError(
                    "RESOURCE_EXHAUSTED",
                    "serving scheduler is not running",
                )
            if self._clock() > deadline:
                raise AdmissionError(
                    "DEADLINE_EXCEEDED", "scheduler job timed out"
                )
        if "error" in cell:
            raise cell["error"]
        return cell["result"]

    def _run_jobs(self):
        while self._jobs:
            self._jobs.popleft()()

    def reload_to(self, version):
        """Explicit checkpoint swap (the rollout controller's
        reload_checkpoint handshake). MUST run on the scheduler thread
        — handlers reach it through submit_job — because set_params is
        jax work that serializes with the decode loop. Unlike the poll
        path this accepts any target version, older included (that is
        what a rollback is). Raises ReloadError with the old params
        still serving when the watcher's retry ladder is exhausted.
        Returns the version now serving."""
        if self.watcher is None:
            raise ReloadError("no checkpoint watcher configured")
        # same flag discipline as the poll reload: only the reload's
        # OWN transient flag clears, so a SIGTERM drain that starts
        # mid-swap stays advertised
        self._reloading.set()
        try:
            got = self.watcher.load_version(version)
            if got is not None:
                state, ver = got
                self.engine.set_params(state, ver)
                self.telemetry.count("reloads")
        finally:
            self._reloading.clear()
        return int(self.engine.model_version)

    def _iterate(self):
        """One tick, under its root phase span (`tick`: seq = the tick
        number; active = slots still seated when it ends: a lane freed
        at the launch of its last step is not, though active_count()
        counts it until its last token is committed)."""
        tick = tracing.begin("tick", seq=self._tick_seq)
        self._tick_seq += 1
        try:
            self._tick()
        finally:
            # getattr keeps bare test engines valid
            seated = getattr(self.engine, "seated_count",
                             self.engine.active_count)
            # a block engine's tick also says how many lanes' passes
            # the step it committed ran
            more = ({"passes": self._tick_passes}
                    if getattr(self.engine, "denoise_steps", 0) else {})
            tracing.end(tick, active=seated(),
                        queue_depth=len(self.queue), **more)

    def _tick(self):
        self._tick_passes = 0
        self._run_jobs()
        if self.watcher is not None:
            reloaded = self.watcher.poll()
            if reloaded is not None:
                state, version = reloaded
                # advertise draining across the swap so routers route
                # new work elsewhere while the reload applies; only the
                # reload's OWN flag clears, so a SIGTERM drain that
                # starts mid-swap stays advertised
                self._reloading.set()
                try:
                    self.engine.set_params(state, version)
                    self.telemetry.count("reloads")
                finally:
                    self._reloading.clear()
        now = self._clock()
        for req in self.engine.evict_expired(now):
            self.telemetry.count("expired")
            req.trace_event("expired", where="mid-decode")
            req.finish_span("DEADLINE_EXCEEDED")
            self._count_slow(req)
            req.push(("error", "DEADLINE_EXCEEDED",
                      "deadline expired mid-decode"))
        with tracing.phase("tick.admit"):
            self._fill_slots()
        if self._pending_prefills:
            with tracing.phase("tick.prefill_tile"):
                self._advance_prefills()
        if self.engine.active_count():
            if self._injector is not None:
                # the stall drill's injection point: a delay rule
                # wedges THIS thread mid-loop (work stays seated, no
                # tokens commit — exactly the failure the watchdog
                # must catch from its own thread); a drop rule skips
                # one tick
                try:
                    self._injector.intercept("engine_step")
                except InjectedRpcError:
                    return
            t0 = self._clock()
            results = self.engine.step()
            dt = self._clock() - t0
            committed = 0
            self._tick_passes = len(results)
            with tracing.phase("tick.stream"):
                for _slot, req, tokens, finished in results:
                    self._stream(req, tokens)
                    committed += len(tokens)
                    if finished:
                        self._complete(req)
                kv = self.engine.kv_stats()
                self.telemetry.record_step(
                    len(self.queue), len(results), dt, committed,
                    kv_bytes_in_use=kv["kv_bytes_in_use"],
                    kv_blocks_free=kv["kv_blocks_free"],
                    kv_host_blocks=kv.get("kv_host_blocks"),
                    kv_host_bytes=kv.get("kv_host_bytes"),
                )
                if self.health is not None:
                    self.health.record_tick(
                        len(self.queue), len(results), dt, committed,
                        kv=kv,
                    )
        elif not self._pending_prefills:
            with tracing.phase("idle"):
                self.queue.wait_for_work(self.idle_wait_secs)
        # pending prefills and no decode: loop again immediately —
        # the next tick runs another budget's worth of tiles and
        # still polls admission between them

    def _stream(self, req, tokens):
        """Push what a step gave `req`: a token (or a speculative
        step's few), or a block-diffusion model's committed block with
        the pass that revealed each token; nothing for a denoising
        pass, which yields none. The first tokens of a request that
        got none at its seating (a block model's) mark its TTFT."""
        if not tokens:
            return
        if req.first_token_at is None:
            req.first_token_at = self._clock()
            ttft_ms = self.telemetry.record_ttft(req)
            req.trace_event("first_token", ttft_ms=round(ttft_ms, 3))
        req.push(("tokens", list(tokens), req.model_version,
                  req.reveal_steps[-len(tokens):]))

    def _advance_prefills(self):
        """Run pending chunked-prefill tiles, round-robin, under the
        per-tick budget. The budget bites only while decode slots are
        waiting (that is the latency being protected); at least one
        tile always runs, so prefill can never starve. Tile cost is
        priced by an EWMA of measured tile time — the host's, as the
        `prefill_tile` phase span records it."""
        budget = self.prefill_budget_ms
        spent, ran = 0.0, 0
        while self._pending_prefills:
            job = self._pending_prefills[0]
            req = job.request
            if req.expired(self._clock()):
                self._pending_prefills.pop(0)
                self.engine.abort_prefill(job)
                self.telemetry.count("expired")
                req.trace_event("expired", where="mid-prefill",
                                tiles=job.tiles)
                req.finish_span("DEADLINE_EXCEEDED")
                self._count_slow(req)
                req.push(("error", "DEADLINE_EXCEEDED",
                          "deadline expired mid-prefill"))
                continue
            if (ran and budget > 0.0 and self.engine.active_count()
                    and spent + self._tile_ms > budget):
                break
            t0 = self._clock()
            finished = self.engine.advance_prefill(job)
            dt_ms = (self._clock() - t0) * 1000.0
            # the tile held the scheduler: same busy clock insert()
            # advances, so prefill_blocked_by_other attribution and
            # the chunked A/B read one ledger
            self.engine.prefill_busy_ms = (
                getattr(self.engine, "prefill_busy_ms", 0.0) + dt_ms
            )
            spent += dt_ms
            self._tile_ms = (
                0.8 * self._tile_ms + 0.2 * dt_ms
                if self._tile_ms else dt_ms
            )
            ran += 1
            # rotate for fairness: concurrent prompts share the budget
            self._pending_prefills.append(self._pending_prefills.pop(0))
            if finished:
                self._pending_prefills.remove(job)
                self._first_token(job)

    def _first_token(self, job):
        """Prefill-completion bookkeeping shared by the monolithic and
        chunked paths: TTFT record, first-token push, and terminal
        completion for one-shot (max_new_tokens <= 1 / prefill-only)
        requests."""
        req = job.request
        req.first_token_at = self._clock()
        ttft_ms = self.telemetry.record_ttft(req)
        req.trace_event("first_token", slot=job.slot,
                        ttft_ms=round(ttft_ms, 3))
        # the prefill produced this token; step() only counts the
        # decode-loop tokens
        self.telemetry.count("tokens_generated")
        req.push(("tokens", [job.first], req.model_version, []))
        if job.finished:
            self._complete(req)

    def _complete(self, req):
        """Terminal success bookkeeping: completion counter, e2e
        histogram, span seal, done event — one definition for the
        decode loop, the prefill-only fast path and the drain loop."""
        self.telemetry.count("completed")
        self.telemetry.record_e2e(
            (self._clock() - req.submitted_at) * 1000.0,
            trace_id=req.trace_id,
        )
        req.trace_event("completed", tokens=len(req.generated))
        req.finish_span("ok")
        self._count_slow(req)
        req.push(("done", req.model_version))

    def _count_slow(self, req):
        """Attribute one TERMINALLY-SLOW request (deadline breach, or
        a completion that burned most of its own deadline budget) to
        its dominant cause and bump the closed slow_cause counter
        family — the scrapeable distribution of WHY, next to the
        expired/completed that."""
        if not self.forensics_on:
            return
        span = req.span
        if span is None or span.end is None:
            return
        deadline_ms = (
            (req.deadline - req.submitted_at) * 1000.0
            if req.deadline is not None else 0.0
        )
        e2e_ms = (span.end - span.start) * 1000.0
        if not forensics.is_terminally_slow(
                span.status, e2e_ms, deadline_ms):
            return
        verdict = forensics.attribute([span.to_dict()])
        if verdict["dominant_cause"]:
            self.telemetry.count_slow_cause(verdict["dominant_cause"])

    def _blocked_ms(self, req):
        """Wall ms other requests' prefills held the scheduler while
        `req` waited: the engine's cumulative prefill-busy clock now
        minus its value when the servicer admitted the request. The
        forensics `prefill_blocked_by_other` component."""
        stamp = getattr(req, "prefill_busy_at_queued", None)
        if stamp is None:
            return 0.0
        busy = getattr(self.engine, "prefill_busy_ms", 0.0)
        return max(0.0, busy - stamp)

    def _fill_slots(self):
        while self.engine.free_slots():
            # the fit predicate is the paged pool's block budget: a
            # head-of-line request that cannot seat yet stays queued
            # (backpressure), and completions free the blocks it waits
            # for — out-of-blocks is never an insert-time crash
            req, expired = self.queue.pop_ready(fit=self.engine.can_seat)
            for e in expired:
                self.telemetry.count("expired")
                e.trace_event("expired", where="queued",
                              prefill_blocked_ms=round(
                                  self._blocked_ms(e), 3))
                e.finish_span("DEADLINE_EXCEEDED")
                self._count_slow(e)
                e.push(("error", "DEADLINE_EXCEEDED",
                        "deadline expired while queued"))
            if req is None:
                break
            req.seated_at = self._clock()
            wait_ms = self.telemetry.record_queue_wait(
                req.queue_wait_secs(), trace_id=req.trace_id
            )
            # the windowed prefix-hit-rate's denominator: EVERY prompt
            # token seated (the engine counts the prefix_hit_tokens
            # numerator — the ones seated without prefill compute)
            self.telemetry.count("prompt_tokens", len(req.prompt))
            req.trace_event("seated", queue_wait_ms=round(wait_ms, 3),
                            prefill_blocked_ms=round(
                                self._blocked_ms(req), 3))
            t0 = self._clock()
            if self._chunked:
                job = self.engine.begin_insert(req)
            else:
                job = None
                slot, first, finished = self.engine.insert(req)
            # advance the prefill-busy clock (insert = this request's
            # prefill / suffix tile / draft prefill on this thread);
            # getattr keeps bare test/bench engines valid
            self.engine.prefill_busy_ms = (
                getattr(self.engine, "prefill_busy_ms", 0.0)
                + (self._clock() - t0) * 1000.0
            )
            if job is not None:
                # chunked admission: a short/fully-shared prompt
                # completes inside begin_insert; a long one queues
                # for tile-at-a-time advancement between decode ticks
                if job.done():
                    self._first_token(job)
                else:
                    self._pending_prefills.append(job)
                continue
            if first is None:
                # a block-diffusion model: the prompt's blocks are
                # cached and no token has come of it; the first come
                # with its first block's commit pass (_stream)
                continue
            req.first_token_at = self._clock()
            ttft_ms = self.telemetry.record_ttft(req)
            req.trace_event("first_token", slot=slot,
                            ttft_ms=round(ttft_ms, 3))
            # the prefill produced this token; step() only counts the
            # decode-loop tokens
            self.telemetry.count("tokens_generated")
            req.push(("tokens", [first], req.model_version, []))
            if finished:
                self._complete(req)

    def _shutdown(self):
        """Graceful stop: reject the queued backlog immediately; with
        drain=True finish the in-flight slots first (they hold real
        compute progress), else abort them too. Either way every request
        terminates with done or a clean error — never silence."""
        for req in self.queue.close():
            self.telemetry.count("rejected")
            req.trace_event("rejected", why="shutdown")
            req.finish_span("RESOURCE_EXHAUSTED")
            req.push(("error", "RESOURCE_EXHAUSTED",
                      "server shutting down"))
        if not self._drain:
            self._abort_all("RESOURCE_EXHAUSTED", "server shutting down")
            return
        while self.engine.active_count() or self._pending_prefills:
            now = self._clock()
            for req in self.engine.evict_expired(now):
                self.telemetry.count("expired")
                req.trace_event("expired", where="mid-decode")
                req.finish_span("DEADLINE_EXCEEDED")
                self._count_slow(req)
                req.push(("error", "DEADLINE_EXCEEDED",
                          "deadline expired mid-decode"))
            # mid-prefill jobs hold real compute progress too: run
            # their remaining tiles (budget still paced by the loop)
            self._advance_prefills()
            if not self.engine.active_count():
                continue
            for _slot, req, tokens, finished in self.engine.step():
                self._stream(req, tokens)
                if finished:
                    self._complete(req)

    def _abort_all(self, code, message):
        # active_requests covers seated-but-prefilling jobs too (the
        # paged engine's override); the pending list just drops
        self._pending_prefills = []
        for req in self.engine.active_requests():
            req.finish_span(code)
            req.push(("error", code, message))
        for req in self.queue.close():
            req.finish_span(code)
            req.push(("error", code, message))

    def stop(self, drain=True):
        self._drain = drain
        self._stopping.set()  # advertise BEFORE admission closes
        self._stop_requested.set()
        self.queue.wake()  # wake the idle wait so shutdown is prompt


class ServingServicer(object):
    """gRPC handlers (proto/service.py Serving table). Works both over
    real gRPC (context aborts) and in-process (AdmissionError raised to
    the caller) — the same duality the master servicer tests use."""

    def __init__(self, queue, engine, telemetry, scheduler_alive,
                 handler_poll_secs=0.25, clock=time.monotonic,
                 draining=None, health=None, role="unified",
                 submit_job=None, watcher=None, reload_fn=None):
        self._queue = queue
        self._engine = engine
        self._telemetry = telemetry
        self._scheduler_alive = scheduler_alive
        self._poll = handler_poll_secs
        self._clock = clock
        self._draining = draining or (lambda: False)
        # runtime-health plane (RuntimeHealth or None): the status
        # RPC stamps its self-report onto ServerStatus — served from
        # gRPC threads, deliberately NOT the scheduler, so a wedged
        # scheduler can still confess
        self._health = health
        # disaggregated serving: the advertised phase role, and the
        # scheduler-thread executor for chain export/import (jax work
        # may not run on gRPC threads; None = run inline, which only
        # bare single-threaded tests use)
        self._role = role
        self._submit_job = submit_job or (lambda fn, timeout=30.0: fn())
        # explicit checkpoint handshake (serving/rollout.py): the
        # watcher is read for the reload_failed advertisement on
        # ServerStatus; reload_fn (scheduler.reload_to) runs through
        # submit_job because the swap is scheduler-thread jax work
        self._watcher = watcher
        self._reload_fn = reload_fn
        # transfer-family RPCs currently executing here; 0 after a
        # drain is the kill-drill's clean-handoff-ledger assertion
        self._transfers_inflight = 0
        self._transfer_aborts = 0
        self._transfers_lock = threading.Lock()

    # ------------------------------------------------------------- RPCs

    def generate(self, request, context=None):
        req = self._admit(request, context)
        for _chunk, _version, _steps in self._events(req, context):
            pass  # unary: accumulate; req.generated holds the tokens
        return pb.GenerateResponse(
            tokens=req.prompt + req.generated,
            model_version=req.model_version,
        )

    def generate_stream(self, request, context=None):
        req = self._admit(request, context)

        def stream():
            for chunk, version, steps in self._events(req, context):
                yield pb.TokenChunk(
                    tokens=chunk, done=False, model_version=version,
                    reveal_steps=steps,
                )
            yield pb.TokenChunk(
                tokens=[], done=True, model_version=req.model_version
            )

        return stream()

    def export_chain(self, request, context=None):
        """Disaggregated handoff, exporter side: gather the prompt's
        resident chain (int8 rows + scale leaves, the same tree-
        generic gather the host spill tier reads through) into a dense
        TransferChainRequest the decode side imports verbatim. Holds
        NO references — exported chains park refcount-0 cached, so a
        crash mid-transfer leaks nothing (abort_transfer is the
        coordinator's accounting obligation, not a resource release)."""
        from elasticdl_tpu.serving import disagg

        kv = self._engine.kv
        if not kv.allocator.share_prefix:
            self._fail(context, "FAILED_PRECONDITION",
                       "chain export needs prefix sharing (kv_shared)")
        prompt = list(request.prompt)
        with self._transfers_lock:
            self._transfers_inflight += 1
        try:
            chain, dtypes = self._submit_job(
                lambda: (kv.export_chain(prompt), kv.leaf_dtypes())
            )
            if not chain:
                self._fail(context, "NOT_FOUND",
                           "no resident chain for prompt")
            return disagg.chain_to_proto(
                chain, kv.block_size, dtypes, request.transfer_id
            )
        finally:
            with self._transfers_lock:
                self._transfers_inflight -= 1

    def transfer_chain(self, request, context=None):
        """Disaggregated handoff, importer side: one batched upload of
        the payload's blocks into fresh pool blocks, re-keyed into the
        content-addressed trie — the next generate with this prompt
        seats by prefix hit, exactly as if the chain were computed
        here. The response reports the chain's RESOLVED coverage on
        this pool (imported + already-resident levels): a fully
        deduped import is a success — the chain is warm either way —
        so blocks=0 means only that nothing of the chain landed
        (pool exhausted). Layout mismatches come back ok=False (the
        coordinator falls back to a plain dispatch), not as an RPC
        failure."""
        from elasticdl_tpu.serving import disagg

        kv = self._engine.kv
        if not kv.allocator.share_prefix:
            self._fail(context, "FAILED_PRECONDITION",
                       "chain import needs prefix sharing (kv_shared)")
        with self._transfers_lock:
            self._transfers_inflight += 1
        try:
            blocks, dtypes = disagg.proto_to_blocks(request, kv)

            def _import_and_resolve():
                kv.import_chain(blocks, leaf_dtypes=dtypes)
                flat = [t for toks, _ in blocks for t in toks]
                return len(kv.allocator.match_prefix(flat))

            resolved = self._submit_job(_import_and_resolve)
            return pb.TransferChainResponse(
                transfer_id=request.transfer_id, ok=True,
                blocks=resolved, tokens=resolved * kv.block_size,
            )
        except AdmissionError:
            raise
        except ValueError as e:
            return pb.TransferChainResponse(
                transfer_id=request.transfer_id, ok=False,
                error=str(e),
            )
        finally:
            with self._transfers_lock:
                self._transfers_inflight -= 1

    def abort_transfer(self, request, context=None):
        """Close a failed handoff's obligation (EDL501 pairs every
        export_chain with import_chain or this). Structurally there is
        nothing to release — exports hold no references — so this is
        the failure's accounting record."""
        with self._transfers_lock:
            self._transfer_aborts += 1
        return pb.TransferChainResponse(
            transfer_id=request.transfer_id, ok=True
        )

    def reload_checkpoint(self, request, context=None):
        """Explicit checkpoint swap (the rollout controller's
        handshake): load exactly request.version — newer or older — on
        the scheduler thread, draining advertised for the duration.
        Load failures come back as a structured ok=False verdict (old
        params still serving, reload_failed latched on ServerStatus);
        only scheduler-liveness problems surface as RPC errors."""
        if self._reload_fn is None:
            self._fail(context, "FAILED_PRECONDITION",
                       "no checkpoint watcher configured")
        version = int(request.version)
        try:
            now_serving = self._submit_job(
                lambda: self._reload_fn(version), timeout=120.0
            )
        except AdmissionError:
            raise
        except Exception as e:  # noqa: BLE001 - structured verdict
            return pb.ReloadCheckpointResponse(
                ok=False,
                model_version=int(self._engine.model_version),
                error="%s" % (e,),
            )
        return pb.ReloadCheckpointResponse(
            ok=bool(now_serving == version), model_version=now_serving,
            error="" if now_serving == version else
            "serving version-%d after reload" % now_serving,
        )

    def server_status(self, request, context=None):
        snap = self._telemetry.snapshot()
        kv = self._engine.kv_stats()
        with self._transfers_lock:
            transfer_aborts = self._transfer_aborts
            transfers_inflight = self._transfers_inflight
        return pb.ServerStatusResponse(
            queue_depth=len(self._queue),
            active_slots=self._engine.active_count(),
            num_slots=self._engine.num_slots,
            model_version=self._engine.model_version,
            admitted=snap["admitted"],
            rejected=snap["rejected"],
            expired=snap["expired"],
            completed=snap["completed"],
            tokens_generated=snap["tokens_generated"],
            reloads=snap["reloads"],
            uptime_secs=snap["uptime_secs"],
            max_active_slots=snap["max_active_slots"],
            kv_paged=kv["kv_paged"],
            kv_shared=kv["kv_shared"],
            kv_cache_dtype=kv["kv_cache_dtype"],
            kv_block_size=kv["kv_block_size"],
            kv_blocks_total=kv["kv_blocks_total"],
            kv_blocks_free=kv["kv_blocks_free"],
            kv_blocks_cached=kv["kv_blocks_cached"],
            kv_blocks_shared=kv["kv_blocks_shared"],
            kv_bytes_total=kv["kv_bytes_total"],
            kv_bytes_in_use=kv["kv_bytes_in_use"],
            kv_bytes_in_use_peak=snap["kv_bytes_in_use_peak"],
            kv_bytes_per_token=snap["kv_bytes_per_token"],
            prefix_hit_tokens=kv["prefix_hit_tokens"],
            cow_copies=kv["cow_copies"],
            # tiered host spill: occupancy gauges + the monotone
            # revival economy (tokens seated by upload instead of
            # re-prefill)
            kv_host_blocks=kv["kv_host_blocks"],
            kv_host_bytes=kv["kv_host_bytes"],
            revive_uploads=kv["revive_uploads"],
            prefill_tokens_revived=kv["prefill_tokens_revived"],
            host_drops=kv["host_drops"],
            draft_k=self._engine.draft_k,
            draft_proposed=self._engine.draft_proposed,
            draft_accepted=self._engine.draft_accepted,
            draining=self._draining(),
            queue_wait_ms=snap["queue_wait_ms"],
            # windowed warm-capacity signal (time-series ring): prompt
            # tokens seated without prefill compute over the trailing
            # horizon / all prompt tokens seated
            prefix_hit_rate_window=snap["prefix_hit_rate_window"],
            # percentiles + raw mergeable buckets from the shared
            # log-linear histograms (observability/histogram.py)
            ttft_p50_ms=snap["ttft_p50_ms"],
            ttft_p90_ms=snap["ttft_p90_ms"],
            ttft_p99_ms=snap["ttft_p99_ms"],
            queue_wait_p50_ms=snap["queue_wait_p50_ms"],
            queue_wait_p90_ms=snap["queue_wait_p90_ms"],
            queue_wait_p99_ms=snap["queue_wait_p99_ms"],
            ttft_hist=snap["ttft_hist"],
            queue_wait_hist=snap["queue_wait_hist"],
            # terminally-slow requests by dominant attributed cause,
            # aligned with ServingTelemetry.SLOW_CAUSES declared order
            slow_cause_counts=snap["slow_cause_counts"],
            # disaggregated serving: the advertised phase role plus
            # the handoff ledger (pool-side chain counters, the
            # transfer RPCs executing right now, and closed-out
            # failures)
            role=self._role,
            chain_exports=kv["chain_exports"],
            chain_imports=kv["chain_imports"],
            chain_import_tokens=kv["chain_import_tokens"],
            transfer_aborts=transfer_aborts,
            transfers_inflight=transfers_inflight,
            # hot-reload failure latch: the watcher exhausted its retry
            # ladder — old params still serving, error carried verbatim
            reload_failed=(
                bool(self._watcher.reload_failed) if self._watcher
                else False
            ),
            reload_error=(
                self._watcher.last_error if self._watcher else ""
            ),
            # runtime health self-report (observability/
            # runtime_health.py); all-zero/"" with the plane off —
            # the wire signal routers/autoscalers key the fallback on
            **self._health_fields(),
        )

    def _health_fields(self):
        if self._health is None:
            return {}
        # a status read is also a watchdog evaluation: detection
        # cannot lag the poll that would have reported it
        self._health.check()
        h = self._health.snapshot()
        return {
            "last_progress_age_ms": h["last_progress_age_ms"],
            "health_state": h["health_state"],
            "jit_compiles": h["jit_compiles"],
            "steady_recompiles": h["steady_recompiles"],
            "memory_unaccounted_bytes":
                h["memory_unaccounted_bytes"],
        }

    # --------------------------------------------------------- internals

    def _admit(self, proto_req, context):
        req = ServingRequest(
            prompt=list(proto_req.prompt),
            max_new_tokens=proto_req.max_new_tokens,
            temperature=proto_req.temperature,
            seed=proto_req.seed,
            deadline_ms=proto_req.deadline_ms,
            trace_id=getattr(proto_req, "trace_id", ""),
            parent_span_id=getattr(proto_req, "parent_span_id", ""),
            prefill_only=getattr(proto_req, "prefill_only", False),
        )
        # the serve span: parented under the caller's dispatch span
        # when the RPC carried trace context (router/traced client),
        # a fresh root trace otherwise — either way THIS is where a
        # request's causal record on the replica begins
        req.span = recorder().start_span(
            "serve",
            trace_id=req.trace_id or None,
            parent_span_id=req.parent_span_id,
            request_id=req.request_id,
            prompt_len=len(req.prompt),
            max_new_tokens=req.max_new_tokens,
            # the tail-retention classifier and forensics read the
            # request's OWN deadline budget off the span
            deadline_ms=int(proto_req.deadline_ms or 0),
        )
        req.trace_id = req.span.trace_id
        # stamp the engine's cumulative prefill-busy clock: seating
        # reads it back to report how long OTHER requests' prefills
        # held the scheduler while this one queued (forensics:
        # prefill_blocked_by_other)
        req.prefill_busy_at_queued = getattr(
            self._engine, "prefill_busy_ms", 0.0
        )
        try:
            self._queue.submit(req)
        except AdmissionError as e:
            self._telemetry.count(
                "expired" if e.code == "DEADLINE_EXCEEDED" else "rejected"
            )
            req.trace_event(
                "expired" if e.code == "DEADLINE_EXCEEDED"
                else "rejected", why=str(e),
            )
            req.finish_span(e.code)
            self._fail(context, e.code, str(e))
        req.trace_event("queued", queue_depth=len(self._queue))
        self._telemetry.count("admitted")
        return req

    def _events(self, req, context):
        """Yield ("tokens" chunks, version, reveal steps) until done
        (the last empty but for a block-diffusion model, whose chunk is
        a committed block); terminate with a
        clean status on error/expiry/scheduler loss. The timeout'd wait
        is the no-hang backstop: even if the scheduler vanishes without
        pushing a terminal event, the handler notices within one poll."""
        while True:
            ev = req.next_event(timeout=self._poll)
            if ev is None:
                now = self._clock()
                if req.expired(now):
                    # backstop only: the scheduler normally evicts and
                    # counts the expiry before this wait times out
                    self._fail(context, "DEADLINE_EXCEEDED",
                               "deadline expired")
                if not self._scheduler_alive():
                    self._fail(context, "RESOURCE_EXHAUSTED",
                               "serving scheduler is not running")
                continue
            kind = ev[0]
            if kind == "tokens":
                yield ev[1], ev[2], ev[3]
            elif kind == "done":
                return
            else:  # ("error", code, message)
                self._fail(context, ev[1], ev[2])

    def _fail(self, context, code_name, message):
        if context is not None:
            import grpc

            context.abort(
                getattr(grpc.StatusCode, code_name,
                        grpc.StatusCode.UNKNOWN),
                message,
            )
        raise AdmissionError(code_name, message)


class GenerationServer(object):
    """Owns the engine, queue, scheduler thread and (optionally) the
    gRPC server. start(grpc_server=False) runs everything in-process —
    the servicer is callable directly, which is what the unit tests and
    the in-process bench mode use."""

    def __init__(self, trainer, state, config=None, injector=None,
                 draft=None):
        self.config = config or ServingConfig()
        cfg = self.config
        self.engine = PagedContinuousBatchingEngine(
            trainer, state, cfg.num_slots,
            top_k=cfg.top_k, top_p=cfg.top_p,
            block_size=cfg.kv_block_size,
            num_blocks=cfg.kv_num_blocks,
            share_prefix=cfg.kv_shared,
            draft=draft, draft_k=cfg.draft_k,
            host_bytes=cfg.kv_host_bytes,
            prefill_chunk_tokens=cfg.prefill_chunk_tokens,
            denoise_steps=cfg.denoise_steps,
        )
        if self.engine.denoise_steps and cfg.role != "unified":
            raise ValueError(
                "this model generates by diffusion over blocks, and a %r "
                "replica (role) hands a prompt's KV chain to a sibling "
                "that would decode it a token a step. Start it unified "
                "(--role unified / EDL_SERVING_ROLE unset)" % (cfg.role,))
        if self.engine.kv.has_state and cfg.role != "unified":
            raise ValueError(
                "this model keeps a per-sequence state (a state-space "
                "layer) beside its KV rows, and a %r replica (role) "
                "hands a prompt's KV chain to a sibling, which carries "
                "no state yet. Start it unified (--role unified / "
                "EDL_SERVING_ROLE unset)" % (cfg.role,))
        self.queue = RequestQueue(
            cfg.queue_capacity, self.engine.seq_len,
            max_cached_tokens=self.engine.max_cached_tokens(),
            refuse=self.engine.refuse_request,
        )
        self.telemetry = ServingTelemetry(
            log_dir=cfg.telemetry_dir or None,
            flush_every=cfg.telemetry_flush_every,
            exemplars=cfg.forensics,
        )
        if cfg.forensics:
            # tail-based trace retention: slow/failed serve spans
            # survive ring pressure (idempotent per function object)
            recorder().add_classifier(serve_span_classifier)
        # the engine reports the events only it can see (prefix hits,
        # CoW faults, draft accepts) through the same closed counters
        self.engine.telemetry = self.telemetry
        # one injector serves the servicer wrapper AND the health/
        # scheduler hooks, so a single EDL_FAULT_SPEC drives a drill
        # end-to-end (rule state is shared, as it must be)
        self._injector = injector or FaultInjector.from_env()
        # the runtime health plane (observability/runtime_health.py):
        # recompile sentry adopted by the engine (which forwards it to
        # the KV pool and the offline decode caches), device-memory
        # ledger reconciliation, progress watchdog + flight recorder —
        # driven by its OWN daemon thread, because the scheduler being
        # wedged is the failure under observation
        self.health = None
        if cfg.runtime_health:
            self.health = RuntimeHealth(
                self.engine, self.queue, self.telemetry,
                stall_after_secs=cfg.stall_after_secs,
                reconcile_secs=cfg.health_reconcile_secs,
                health_dir=cfg.health_dir,
                injector=self._injector,
            )
            self.engine.sentry = self.health.sentry
        watcher = None
        if cfg.checkpoint_dir:
            watcher = CheckpointWatcher(
                cfg.checkpoint_dir, state,
                poll_secs=cfg.reload_poll_secs,
                start_version=self.engine.model_version,
                injector=self._injector,
            )
        self.watcher = watcher
        self.scheduler = _Scheduler(
            self.engine, self.queue, self.telemetry, watcher=watcher,
            idle_wait_secs=cfg.idle_wait_secs,
            forensics_on=cfg.forensics,
            injector=self._injector, health=self.health,
            prefill_budget_ms=cfg.prefill_budget_ms,
        )
        servicer = ServingServicer(
            self.queue, self.engine, self.telemetry,
            scheduler_alive=self.scheduler.is_alive,
            handler_poll_secs=cfg.handler_poll_secs,
            draining=self.scheduler.is_draining,
            health=self.health,
            role=cfg.role,
            submit_job=self.scheduler.submit_job,
            watcher=watcher,
            reload_fn=self.scheduler.reload_to if watcher else None,
        )
        # the unwrapped servicer: in-process warmup (serving/main.py
        # --warmup_tokens) goes through it so a warmup request can
        # never consume an armed fault rule meant for real traffic
        self.raw_servicer = servicer
        # EDL_FAULT_SPEC (or an explicit injector) arms drop/error/
        # delay/kill at the RPC boundary, exactly like the master
        self.servicer = maybe_wrap_servicer(
            servicer, self._injector, rpcs=SERVING_RPCS
        )
        self._server = None
        self.port = None
        self.metrics = None  # MetricsServer when cfg.metrics_port set

    def _metrics_families(self):
        """One replica scrape: the closed telemetry sets + latency
        histograms, plus the loop's phase spans as one labeled
        histogram family, its work counters as one labeled counter
        family, and their ring's drop count (called on the
        exposition HTTP thread; each collector locks itself)."""
        fams = self.telemetry.prometheus()
        fams.append(hist_family(
            "edl_serving_phase_ms",
            "phase spans of the scheduler tick (tracing.phase): wall "
            "ms per phase, cumulative (shared log-linear scheme)",
            recorder().phase_hist_series(),
        ))
        fams.append(labeled_counter_family(
            "edl_serving_work_total",
            "work counted where it happens (tracing.count), cumulative",
            [({"counter": name}, n)
             for name, n in sorted(recorder().counts().items())],
        ))
        fams.append(gauge_family(
            "edl_serving_phase_ring_dropped",
            "phase spans evicted from the bounded phase ring",
            [({}, recorder().phases_dropped)],
        ))
        fams.append(labeled_counter_family(
            "edl_serving_slow_phases_total",
            "phases that lasted over 0.25 s and over three times their "
            "name's median (tracing.py: kept with what lay beneath them)",
            [({"phase": name}, n)
             for name, n in sorted(recorder().slow_counts().items())],
        ))
        if self.health is not None:
            # the per-fn recompile family (the scalar health gauges/
            # counters already ride the closed telemetry sets)
            fams.extend(self.health.prometheus())
        return fams

    def mark_steady(self):
        """Declare warmup over (runtime health): recompiles become
        counted anomalies and the memory baseline re-anchors. No-op
        with the plane off — warmup call sites never need to care."""
        if self.health is not None:
            self.health.mark_steady()

    def start(self, grpc_server=True):
        self.scheduler.start()
        if self.health is not None:
            self.health.start()
        if self.config.metrics_port is not None:
            self.metrics = MetricsServer(
                self._metrics_families, port=self.config.metrics_port
            )
            logger.info(
                "Serving /metrics exposition on port %d",
                self.metrics.port,
            )
        if grpc_server:
            from elasticdl_tpu.proto.service import (
                add_serving_servicer_to_server,
                build_server,
            )

            server = build_server(
                futures.ThreadPoolExecutor(
                    max_workers=self.config.max_workers
                )
            )
            add_serving_servicer_to_server(self.servicer, server)
            self.port = server.add_insecure_port(
                "[::]:%d" % self.config.port
            )
            server.start()
            self._server = server
            logger.info(
                "Serving gRPC server started on port %d (slots=%d, "
                "queue=%d)", self.port, self.config.num_slots,
                self.config.queue_capacity,
            )
        return self

    def stop(self, drain=True, grace=5.0):
        """Graceful: stop admission, drain (or abort) in-flight work,
        then stop the transport. Safe to call twice."""
        self.scheduler.stop(drain=drain)
        self.scheduler.join(timeout=60.0)
        if self.health is not None:
            self.health.stop()
        if self._server is not None:
            self._server.stop(grace).wait()
            self._server = None
        if self.metrics is not None:
            self.metrics.close()
            self.metrics = None
        self.telemetry.close()
        # what the loop's phases cost, for a run nothing scraped
        logger.info("serving phases: %s; gc %s",
                    json.dumps(recorder().phase_snapshot(), sort_keys=True),
                    json.dumps(recorder().gc_pauses()))
        # export this process's span ring when EDL_TRACE_DIR is set
        # (no-op otherwise) — the dump tool merges per-process files
        recorder().flush()
