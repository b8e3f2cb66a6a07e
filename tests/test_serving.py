"""Serving subsystem unit tests (tier-1: sub-second, no model compile).

Admission queue policy, request event plumbing, telemetry counters and
event-file output, serving proto round-trips/service table, and fault
injection at the serving servicer boundary. The decode-pool e2e tests
(compiled engine, gRPC server, hot reload) live in
tests/test_serving_e2e.py on the drills shard."""

import os

import pytest

from elasticdl_tpu.common.fault_injection import (
    SERVING_RPCS,
    FaultInjector,
    InjectedRpcError,
    maybe_wrap_servicer,
)
from elasticdl_tpu.proto import elasticdl_pb2 as pb
from elasticdl_tpu.serving.admission import (
    AdmissionError,
    RequestQueue,
    ServingRequest,
)
from elasticdl_tpu.serving.server import ServingServicer, _Scheduler
from elasticdl_tpu.serving.telemetry import ServingTelemetry


class FakeClock(object):
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def _req(prompt=(1, 2), new=4, deadline_ms=0, clock=None):
    kwargs = {} if clock is None else {"clock": clock}
    return ServingRequest(list(prompt), new, deadline_ms=deadline_ms,
                          **kwargs)


# ------------------------------------------------------------ admission


def test_queue_admits_and_pops_fifo():
    q = RequestQueue(capacity=4, seq_len=16)
    a, b = _req(), _req()
    q.submit(a)
    q.submit(b)
    assert len(q) == 2
    got, expired = q.pop_ready()
    assert got is a and not expired
    got, _ = q.pop_ready()
    assert got is b
    got, _ = q.pop_ready()
    assert got is None


def test_queue_full_rejects_resource_exhausted():
    q = RequestQueue(capacity=2, seq_len=16)
    q.submit(_req())
    q.submit(_req())
    with pytest.raises(AdmissionError) as e:
        q.submit(_req())
    assert e.value.code == "RESOURCE_EXHAUSTED"
    # backpressure frees as the scheduler pops
    q.pop_ready()
    q.submit(_req())  # admitted again


def test_queue_validates_budget_and_args():
    q = RequestQueue(capacity=4, seq_len=16)
    with pytest.raises(AdmissionError) as e:
        q.submit(_req(prompt=[], new=4))
    assert e.value.code == "INVALID_ARGUMENT"
    with pytest.raises(AdmissionError) as e:
        q.submit(_req(new=0))
    assert e.value.code == "INVALID_ARGUMENT"
    # prompt + new must fit the model's cache
    with pytest.raises(AdmissionError) as e:
        q.submit(_req(prompt=list(range(10)), new=7))
    assert e.value.code == "INVALID_ARGUMENT"
    q.submit(_req(prompt=list(range(10)), new=6))  # == seq_len fits


def test_queue_deadline_expiry_at_admission_and_in_queue():
    clock = FakeClock()
    q = RequestQueue(capacity=4, seq_len=16, clock=clock)
    # expired before admission -> DEADLINE_EXCEEDED, never queued
    stale = _req(deadline_ms=50, clock=clock)
    clock.t += 1.0
    with pytest.raises(AdmissionError) as e:
        q.submit(stale)
    assert e.value.code == "DEADLINE_EXCEEDED"
    assert len(q) == 0
    # expires while queued -> surfaced by pop_ready as expired, the
    # next live request is returned
    doomed = _req(deadline_ms=100, clock=clock)
    q.submit(doomed)
    live = _req(deadline_ms=0, clock=clock)
    q.submit(live)
    clock.t += 10.0
    got, expired = q.pop_ready()
    assert got is live and expired == [doomed]


def test_queue_token_budget_rejects_never_fits():
    """A request whose KV footprint exceeds the paged pool's WHOLE
    block budget can never seat — INVALID_ARGUMENT at submit, not an
    eternal queue residence."""
    q = RequestQueue(capacity=4, seq_len=16, max_cached_tokens=8)
    # cached rows = prompt + new - 1 = 9 > 8
    with pytest.raises(AdmissionError) as e:
        q.submit(_req(prompt=list(range(4)), new=6))
    assert e.value.code == "INVALID_ARGUMENT"
    q.submit(_req(prompt=list(range(4)), new=5))  # 8 rows fits
    # prefill-only requests never touch the pool: always admissible
    q.submit(_req(prompt=list(range(15)), new=1))


def test_queue_pop_ready_fit_predicate_preserves_fifo():
    """pop_ready(fit=...) is the paged pool's backpressure point: an
    unseatable head STAYS at the head (no skip-ahead starvation), and
    seats once capacity frees."""
    q = RequestQueue(capacity=4, seq_len=16)
    big, small = _req(prompt=[1, 2, 3], new=8), _req(new=2)
    q.submit(big)
    q.submit(small)
    got, expired = q.pop_ready(fit=lambda r: r is not big)
    assert got is None and not expired and len(q) == 2
    # capacity frees -> the SAME head pops first, FIFO intact
    got, _ = q.pop_ready(fit=lambda r: True)
    assert got is big
    got, _ = q.pop_ready()
    assert got is small
    # expired requests still drain out even when the head doesn't fit
    clock = FakeClock()
    q2 = RequestQueue(capacity=4, seq_len=16, clock=clock)
    doomed = _req(deadline_ms=100, clock=clock)
    q2.submit(doomed)
    q2.submit(_req(clock=clock))
    clock.t += 10.0
    got, expired = q2.pop_ready(fit=lambda r: False)
    assert got is None and expired == [doomed] and len(q2) == 1


def test_queue_close_rejects_backlog_and_new_submits():
    q = RequestQueue(capacity=4, seq_len=16)
    a = _req()
    q.submit(a)
    backlog = q.close()
    assert backlog == [a] and len(q) == 0
    with pytest.raises(AdmissionError) as e:
        q.submit(_req())
    assert e.value.code == "RESOURCE_EXHAUSTED"


def test_request_event_plumbing():
    r = _req()
    assert r.next_event(timeout=0.01) is None  # timeout, no hang
    r.push(("tokens", [5], 1))
    r.push(("done", 1))
    assert r.next_event() == ("tokens", [5], 1)
    assert r.next_event() == ("done", 1)
    # ids are unique across requests
    assert _req().request_id != _req().request_id


# ------------------------------------------- scheduler deadline semantics


class FakeEngine(object):
    """One-slot engine stand-in: enough surface for _Scheduler and
    ServingServicer without jax or a compiled step."""

    def __init__(self):
        self.num_slots = 1
        self.seq_len = 16
        self.model_version = 0
        self.reloaded = []
        self._slot = None

    def free_slots(self):
        return [] if self._slot is not None else [0]

    def can_seat(self, request):
        return True

    def insert(self, request):
        self._slot = request
        return 0, 11, False

    def evict_expired(self, now):
        if self._slot is not None and self._slot.expired(now):
            req, self._slot = self._slot, None
            return [req]
        return []

    def active_count(self):
        return 0 if self._slot is None else 1

    def active_requests(self):
        return [] if self._slot is None else [self._slot]

    def step(self):
        if self._slot is None:
            return []
        return [(0, self._slot, [12], False)]

    def set_params(self, state, version):
        self.reloaded.append(version)
        self.model_version = version

    def max_cached_tokens(self):
        return self.seq_len

    draft_k = 0
    draft_proposed = 0
    draft_accepted = 0
    prefill_chunk_tokens = 0

    def kv_stats(self):
        return {"kv_paged": False, "kv_shared": False,
                "kv_cache_dtype": "",
                "kv_block_size": 0,
                "kv_blocks_total": 0, "kv_blocks_free": 0,
                "kv_blocks_cached": 0, "kv_blocks_shared": 0,
                "kv_bytes_total": 0, "kv_bytes_in_use": 0,
                "prefix_hit_tokens": 0, "cow_copies": 0,
                "kv_host_blocks": 0, "kv_host_bytes": 0,
                "revive_uploads": 0, "prefill_tokens_revived": 0,
                "host_drops": 0, "chain_exports": 0,
                "chain_imports": 0, "chain_import_tokens": 0}


def _rig(clock):
    engine = FakeEngine()
    queue = RequestQueue(capacity=4, seq_len=16, clock=clock)
    telemetry = ServingTelemetry(log_dir=None, clock=clock)
    sched = _Scheduler(engine, queue, telemetry, idle_wait_secs=0.001,
                       clock=clock)
    return engine, queue, telemetry, sched


def test_deadline_expired_while_queued_gets_explicit_error():
    """Expiry path 1: the request never seats — the scheduler must
    push DEADLINE_EXCEEDED when it pops the corpse, so the handler
    terminates with an explicit status."""
    clock = FakeClock()
    engine, queue, telemetry, sched = _rig(clock)
    doomed = _req(deadline_ms=100, clock=clock)
    queue.submit(doomed)
    clock.t += 1.0  # expires in the queue, before any slot frees
    sched._iterate()
    ev = doomed.next_event(timeout=0)
    assert ev == ("error", "DEADLINE_EXCEEDED",
                  "deadline expired while queued")
    assert telemetry.snapshot()["expired"] == 1
    assert engine.active_count() == 0  # never seated


def test_deadline_expired_while_executing_gets_explicit_error():
    """Expiry path 2: the request seats, decodes, and expires
    mid-flight — the scheduler evicts it between steps with
    DEADLINE_EXCEEDED; delivered tokens stand."""
    clock = FakeClock()
    engine, queue, telemetry, sched = _rig(clock)
    req = _req(deadline_ms=500, clock=clock)
    queue.submit(req)
    sched._iterate()  # seats + prefill token + one decode step
    assert engine.active_count() == 1
    assert req.next_event(timeout=0)[0] == "tokens"
    clock.t += 1.0  # deadline passes mid-decode
    sched._iterate()
    assert engine.active_count() == 0  # slot freed for live work
    events = []
    while True:
        ev = req.next_event(timeout=0)
        if ev is None:
            break
        events.append(ev)
    assert ("error", "DEADLINE_EXCEEDED",
            "deadline expired mid-decode") in events
    assert telemetry.snapshot()["expired"] == 1


def test_scheduler_records_queue_wait_and_snapshot_surfaces_it():
    clock = FakeClock()
    engine, queue, telemetry, sched = _rig(clock)
    req = _req(clock=clock)
    queue.submit(req)
    clock.t += 0.2  # 200 ms queued before the scheduler seats it
    sched._iterate()
    assert req.seated_at == clock.t
    assert req.queue_wait_secs() == pytest.approx(0.2)
    snap = telemetry.snapshot()
    assert snap["queue_wait_ms"] == pytest.approx(200.0)
    # the servicer surfaces the same number on the status RPC —
    # the router's load signal
    servicer = ServingServicer(queue, engine, telemetry,
                               scheduler_alive=lambda: True,
                               clock=clock,
                               draining=sched.is_draining)
    st = servicer.server_status(pb.ServerStatusRequest())
    assert st.queue_wait_ms == pytest.approx(200.0)
    assert not st.draining


def test_scheduler_advertises_draining_on_stop_and_reload():
    clock = FakeClock()
    engine, queue, telemetry, sched = _rig(clock)

    class OneShotWatcher(object):
        def __init__(self):
            self.pending = ("new-state", 7)

        def poll(self):
            out, self.pending = self.pending, None
            return out

    sched.watcher = OneShotWatcher()
    seen = []
    engine.set_params = lambda state, version: seen.append(
        (version, sched.is_draining())
    )
    assert not sched.is_draining()
    sched._iterate()  # reload applies WITH draining advertised
    assert seen == [(7, True)]
    assert not sched.is_draining()  # transient: cleared after the swap
    sched.stop(drain=True)  # SIGTERM path: advertised for good
    assert sched.is_draining()


def test_sigterm_drain_survives_concurrent_reload():
    """Regression: stop() landing while a hot-reload swap is mid-flight
    must not lose the permanent drain advertisement — the reload's
    cleanup used to clear the shared flag, and routers would keep
    routing new work to a terminating replica."""
    clock = FakeClock()
    engine, queue, telemetry, sched = _rig(clock)

    class OneShotWatcher(object):
        def __init__(self):
            self.pending = ("new-state", 7)

        def poll(self):
            out, self.pending = self.pending, None
            return out

    sched.watcher = OneShotWatcher()
    seen = []

    def swap(state, version):
        sched.stop(drain=True)  # SIGTERM arrives mid-swap
        seen.append((version, sched.is_draining()))

    engine.set_params = swap
    sched._iterate()
    assert seen == [(7, True)]
    # the reload's cleanup cleared only its OWN transient flag: the
    # SIGTERM advertisement stays up for good
    assert sched.is_draining()


def test_telemetry_counters_and_snapshot():
    clock = FakeClock()
    t = ServingTelemetry(log_dir=None, flush_every=2, clock=clock)
    t.count("admitted")
    t.count("rejected", 2)
    t.record_step(queue_depth=3, active_slots=2, step_secs=0.01,
                  tokens_committed=2)
    t.record_step(queue_depth=1, active_slots=4, step_secs=0.01,
                  tokens_committed=4)
    snap = t.snapshot()
    assert snap["admitted"] == 1 and snap["rejected"] == 2
    assert snap["tokens_generated"] == 6
    assert snap["max_active_slots"] == 4
    assert snap["steps"] == 2


def test_telemetry_ttft_and_event_file(tmp_path):
    clock = FakeClock()
    t = ServingTelemetry(log_dir=str(tmp_path), flush_every=1,
                         clock=clock)
    r = _req(clock=clock)
    clock.t += 0.25
    ttft = t.record_ttft(r)
    assert abs(ttft - 250.0) < 1e-6
    t.record_step(queue_depth=0, active_slots=1, step_secs=0.002,
                  tokens_committed=1)
    t.close()
    files = [f for f in os.listdir(str(tmp_path))
             if f.startswith("events.out.tfevents")]
    assert len(files) == 1
    assert os.path.getsize(os.path.join(str(tmp_path), files[0])) > 0


# ---------------------------------------------------------------- proto


def test_serving_proto_round_trip():
    req = pb.GenerateRequest(
        prompt=[1, 2, 3], max_new_tokens=5, temperature=0.5, seed=9,
        deadline_ms=2500,
    )
    req2 = pb.GenerateRequest.FromString(req.SerializeToString())
    assert list(req2.prompt) == [1, 2, 3]
    assert req2.max_new_tokens == 5 and req2.seed == 9
    assert req2.deadline_ms == 2500
    chunk = pb.TokenChunk(tokens=[7, 8], done=True, model_version=3)
    chunk2 = pb.TokenChunk.FromString(chunk.SerializeToString())
    assert list(chunk2.tokens) == [7, 8] and chunk2.done
    st = pb.ServerStatusResponse(
        queue_depth=1, active_slots=2, num_slots=4, admitted=10,
        tokens_generated=123, uptime_secs=1.5, max_active_slots=3,
        kv_paged=True, kv_block_size=16, kv_blocks_total=32,
        kv_blocks_free=7, kv_bytes_total=1 << 20,
        kv_bytes_in_use=4096, kv_bytes_in_use_peak=8192,
        kv_bytes_per_token=96.5,
        kv_host_blocks=5, kv_host_bytes=5 << 10,
        revive_uploads=3, prefill_tokens_revived=80, host_drops=2,
    )
    st2 = pb.ServerStatusResponse.FromString(st.SerializeToString())
    assert st2.num_slots == 4 and st2.tokens_generated == 123
    assert abs(st2.uptime_secs - 1.5) < 1e-9
    assert st2.kv_paged and st2.kv_blocks_free == 7
    assert st2.kv_bytes_total == 1 << 20
    assert st2.kv_bytes_in_use_peak == 8192
    assert abs(st2.kv_bytes_per_token - 96.5) < 1e-9
    # the tiered-host-spill fields survive the wire
    assert st2.kv_host_blocks == 5 and st2.kv_host_bytes == 5 << 10
    assert st2.revive_uploads == 3
    assert st2.prefill_tokens_revived == 80 and st2.host_drops == 2


def test_serving_service_descriptor():
    svc = pb.DESCRIPTOR.services_by_name["Serving"]
    names = [m.name for m in svc.methods]
    assert names == ["generate", "generate_stream", "server_status",
                     "export_chain", "transfer_chain",
                     "abort_transfer", "reload_checkpoint"]
    assert svc.methods_by_name["generate_stream"].server_streaming
    assert not svc.methods_by_name["generate"].server_streaming
    # the rollout swap handshake is unary
    assert not svc.methods_by_name["reload_checkpoint"].server_streaming
    # the disagg transfer RPCs are all unary
    assert not svc.methods_by_name["transfer_chain"].server_streaming
    # the hand-rolled binding table mirrors the descriptor
    from elasticdl_tpu.proto.service import _SERVING_METHODS

    assert set(_SERVING_METHODS) == set(names)
    assert _SERVING_METHODS["generate_stream"][2] is True


# ------------------------------------------------------ fault injection


class _EchoServicer(object):
    def generate(self, request, _context=None):
        return pb.GenerateResponse(tokens=list(request.prompt))

    def generate_stream(self, request, _context=None):
        return iter([pb.TokenChunk(tokens=list(request.prompt))])

    def server_status(self, request, _context=None):
        return pb.ServerStatusResponse(num_slots=1)


def test_fault_injection_wraps_serving_rpcs():
    inj = FaultInjector(spec="generate:drop:1;server_status:error:1")
    wrapped = maybe_wrap_servicer(_EchoServicer(), inj, rpcs=SERVING_RPCS)
    req = pb.GenerateRequest(prompt=[1])
    # first generate call is dropped (pre-handler)
    with pytest.raises(InjectedRpcError):
        wrapped.generate(req)
    # second goes through
    assert list(wrapped.generate(req).tokens) == [1]
    # error fires AFTER the handler ran
    with pytest.raises(InjectedRpcError):
        wrapped.server_status(pb.ServerStatusRequest())
    assert wrapped.server_status(pb.ServerStatusRequest()).num_slots == 1
    assert inj.injected == {"generate": 1, "server_status": 1}


def test_fault_injection_inactive_returns_servicer_unwrapped():
    s = _EchoServicer()
    assert maybe_wrap_servicer(s, None, rpcs=SERVING_RPCS) is s
