"""Observability subsystem tests (tier-1: no jax, no sockets).

Locks the ISSUE's tentpole semantics: the log-linear histogram against
a sorted-list oracle (within bucket resolution), bucket-count merge =
recording the union, the span ring buffer's drop-OLDEST bound, one
request = ONE span tree across router re-dispatch and hedging with the
legs as SIBLING spans, the replica serve span parenting under the
router's dispatch span (cross-process merge via the dump tool), the
percentile fields on ServerStatus/router_status, the list-entry
`percentiles` against the same oracle, the closed telemetry counter
sets, telemetry tail-flush on close(), and the tb_events binary format
round-tripped through an independent record/CRC parser."""

import json
import os
import random
import struct
import threading
import time

import grpc
import pytest

from elasticdl_tpu.common.fault_injection import InjectedRpcError
from elasticdl_tpu.observability import dump as dump_mod
from elasticdl_tpu.observability.histogram import (
    NUM_BUCKETS,
    LogLinearHistogram,
    bucket_bounds,
    bucket_index,
    percentiles,
)
from elasticdl_tpu.observability.tracing import (
    SpanRecorder,
    children_of,
    chrome_trace,
    group_by_trace,
    recorder,
    trace_roots,
)
from elasticdl_tpu.proto import elasticdl_pb2 as pb
from elasticdl_tpu.serving.admission import RequestQueue, ServingRequest
from elasticdl_tpu.serving.router import Router, RouterConfig
from elasticdl_tpu.serving.server import ServingServicer, _Scheduler
from elasticdl_tpu.serving.telemetry import (
    RouterTelemetry,
    ServingTelemetry,
)

# ------------------------------------------------------------- histogram


def _sorted_oracle(values, q):
    vs = sorted(values)
    return vs[min(len(vs) - 1, int(round(q / 100.0 * (len(vs) - 1))))]


def test_histogram_matches_sorted_oracle_within_resolution():
    """The acceptance pin: histogram percentiles equal the sorted-list
    oracle within the scheme's relative bucket resolution (2/SUBBUCKETS
    = ~3.1%), across magnitudes from sub-ms to minutes."""
    rng = random.Random(7)
    values = [rng.lognormvariate(3.0, 2.0) for _ in range(4000)]
    h = LogLinearHistogram()
    for v in values:
        h.record(v)
    for q in (50, 90, 99):
        oracle = _sorted_oracle(values, q)
        assert h.percentile(q) == pytest.approx(oracle, rel=0.04)
    assert h.count == len(values)
    assert h.min == min(values) and h.max == max(values)


def test_histogram_merge_equals_union_recording():
    rng = random.Random(11)
    values = [rng.expovariate(0.01) for _ in range(1000)]
    whole, a, b = (LogLinearHistogram() for _ in range(3))
    for v in values:
        whole.record(v)
    for v in values[:500]:
        a.record(v)
    for v in values[500:]:
        b.record(v)
    a.merge(b)
    assert a.counts == whole.counts and a.count == whole.count
    assert a.percentile(99) == whole.percentile(99)


def test_histogram_wire_round_trip_preserves_percentiles():
    h = LogLinearHistogram()
    for v in (0.5, 3.0, 3.0, 40.0, 900.0):
        h.record(v)
    counts = h.to_counts()
    assert counts and counts[-1] != 0  # trailing zeros trimmed
    back = LogLinearHistogram.from_counts(counts)
    assert back.count == h.count
    for q in (50, 90, 99):
        assert back.percentile(q) == pytest.approx(
            h.percentile(q), rel=0.04
        )


def test_histogram_edges():
    h = LogLinearHistogram()
    assert h.percentile(99) == 0.0  # empty -> proto-friendly 0
    for bad in (-1.0, float("nan"), float("inf")):
        h.record(bad)
    assert h.count == 0
    h.record(0.0)
    assert h.percentile(50) == 0.0
    # indexes stay in range across the whole magnitude span
    for v in (0.0, 0.005, 0.64, 1.0, 1e3, 1e7, 1e12, float("inf")):
        assert 0 <= bucket_index(v) < NUM_BUCKETS
    for i in (0, 63, 64, NUM_BUCKETS - 1):
        lo, hi = bucket_bounds(i)
        assert lo < hi


def test_percentiles_of_a_list_match_the_sorted_oracle():
    """`percentiles` — the list entry point the drills report client
    latencies through — answers from the same buckets as the live
    histograms: within bucket resolution of the sorted oracle, and
    None per quantile for an empty list."""
    rng = random.Random(3)
    values = [rng.uniform(1.0, 500.0) for _ in range(500)]
    out = percentiles(values, (50, 90, 99))
    for q in (50, 90, 99):
        assert out["p%d" % q] == pytest.approx(
            _sorted_oracle(values, q), rel=0.04
        )
    assert percentiles([], (50,)) == {"p50": None}


# ------------------------------------------------------ span ring buffer


def test_span_ring_drops_oldest_under_overflow():
    rec = SpanRecorder(service="t", capacity=3)
    spans = [rec.start_span("s%d" % i) for i in range(8)]
    for s in spans:
        s.finish()
    assert len(rec) == 3 and rec.dropped == 5
    kept = [s.name for s in rec.snapshot()]
    assert kept == ["s5", "s6", "s7"]  # newest survive
    assert rec.export()["dropped"] == 5


def test_span_finish_is_idempotent_and_unfinished_never_exports():
    rec = SpanRecorder(service="t")
    a = rec.start_span("a")
    rec.start_span("never-finished")
    a.finish("ok")
    a.finish("error")  # second finish is a no-op
    exported = rec.export()["spans"]
    assert [s["name"] for s in exported] == ["a"]
    assert exported[0]["status"] == "ok"


# ----------------------------------------------- replica-side span tree


class FinishingEngine(object):
    """Jax-free engine stand-in that completes every request at its
    second token, so the scheduler walks the full span lifecycle."""

    def __init__(self):
        self.num_slots = 2
        self.seq_len = 16
        self.model_version = 0
        self._slots = {}

    def free_slots(self):
        return [i for i in range(self.num_slots)
                if i not in self._slots]

    def can_seat(self, request):
        return True

    def insert(self, request):
        slot = self.free_slots()[0]
        if hasattr(request, "trace_event"):
            request.trace_event("prefill", bucket=16, slot=slot)
        if request.max_new_tokens == 1:
            return slot, 11, True
        self._slots[slot] = request
        return slot, 11, False

    def evict_expired(self, now):
        out = [r for r in self._slots.values() if r.expired(now)]
        self._slots = {s: r for s, r in self._slots.items()
                       if not r.expired(now)}
        return out

    def active_count(self):
        return len(self._slots)

    def active_requests(self):
        return list(self._slots.values())

    def step(self):
        out = []
        for slot, req in list(self._slots.items()):
            req.generated.append(12)
            finished = len(req.generated) >= req.max_new_tokens
            if finished:
                del self._slots[slot]
            out.append((slot, req, [12], finished))
        return out

    def set_params(self, state, version):
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


def _replica_rig():
    engine = FinishingEngine()
    queue = RequestQueue(capacity=8, seq_len=16)
    telemetry = ServingTelemetry(log_dir=None)
    sched = _Scheduler(engine, queue, telemetry, idle_wait_secs=0.001)
    servicer = ServingServicer(
        queue, engine, telemetry, scheduler_alive=lambda: True,
        handler_poll_secs=0.02, draining=lambda: False,
    )
    return engine, queue, telemetry, sched, servicer


def test_replica_serve_span_lifecycle_and_parenting():
    recorder().clear()
    engine, queue, telemetry, sched, servicer = _replica_rig()
    req_pb = pb.GenerateRequest(
        prompt=[1, 2], max_new_tokens=3,
        trace_id="feedc0de00000001", parent_span_id="dad0000000000001",
    )
    done = {}

    def call():
        done["resp"] = servicer.generate(req_pb)

    t = threading.Thread(target=call)
    t.start()
    deadline = time.monotonic() + 5.0
    while "resp" not in done and time.monotonic() < deadline:
        sched._iterate()
    t.join(timeout=5.0)
    assert not t.is_alive() and list(done["resp"].tokens)[:2] == [1, 2]

    serve = [s for s in recorder().snapshot()
             if s.name == "serve"
             and s.trace_id == "feedc0de00000001"]
    assert len(serve) == 1
    span = serve[0].to_dict()
    # parented under the caller's (router's) dispatch span: the
    # cross-process tree edge
    assert span["parent_span_id"] == "dad0000000000001"
    assert span["status"] == "ok"
    names = [e["name"] for e in span["events"]]
    assert names == ["queued", "seated", "prefill", "first_token",
                     "completed"]
    # e2e completion landed in the histogram + snapshot percentiles
    snap = telemetry.snapshot()
    assert snap["e2e_p50_ms"] >= 0 and snap["ttft_p99_ms"] >= 0
    assert telemetry.hists["e2e_ms"].count == 1


def test_replica_rejection_finishes_span_with_status():
    recorder().clear()
    engine, queue, telemetry, sched, servicer = _replica_rig()
    # overflow the queue without a scheduler: capacity 8
    for _ in range(8):
        queue.submit(ServingRequest([1], 2))
    from elasticdl_tpu.serving.admission import AdmissionError

    with pytest.raises(AdmissionError):
        servicer.generate(pb.GenerateRequest(
            prompt=[1], max_new_tokens=2, trace_id="feedc0de00000002",
        ))
    spans = [s for s in recorder().snapshot()
             if s.trace_id == "feedc0de00000002"]
    assert len(spans) == 1
    assert spans[0].status == "RESOURCE_EXHAUSTED"
    assert [e[1] for e in spans[0].events] == ["rejected"]


# ------------------------------------------------- router-side span tree


class ForwardingStub(object):
    """ServingStub-shaped fake that forwards unary generates into a
    REAL in-process replica rig (servicer + scheduler thread), so the
    router's dispatch spans and the replica's serve spans land in one
    recorder exactly as one merged trace would."""

    def __init__(self, servicer, fail_first=0):
        self._servicer = servicer
        self.fail_first = fail_first
        self.block_until = None

    def server_status(self, request, timeout=None):
        return self._servicer.server_status(request)

    def generate(self, request, timeout=None):
        if self.block_until is not None:
            assert self.block_until.wait(5.0)
        if self.fail_first > 0:
            self.fail_first -= 1
            raise InjectedRpcError(
                grpc.StatusCode.UNAVAILABLE, "replica down"
            )
        return self._servicer.generate(request)


def _router_over_real_replica(fail_first=0, n=1, **cfg_kwargs):
    rigs = [_replica_rig() for _ in range(n)]
    for rig in rigs:
        rig[3].start()  # scheduler thread (daemon, jax-free)
    stubs = {}
    for i, rig in enumerate(rigs):
        stubs["rep%d" % i] = ForwardingStub(
            rig[4], fail_first=fail_first if i == 0 else 0
        )
    cfg = RouterConfig(lease_secs=30.0, redispatch_window_secs=8.0,
                       base_delay_secs=0.001, max_delay_secs=0.002,
                       **cfg_kwargs)
    router = Router(sorted(stubs), config=cfg,
                    stub_factory=lambda a: stubs[a])
    router.poll_once()
    return router, rigs, stubs


def _tree(trace_id):
    spans = [s.to_dict() for s in recorder().snapshot()
             if s.trace_id == trace_id]
    return spans


def test_one_routed_request_is_one_span_tree():
    """The acceptance pin: router dispatch -> replica admission ->
    seated -> first_token -> completion, one tree, parsed back from
    the exported Chrome-trace JSON."""
    recorder().clear()
    router, rigs, stubs = _router_over_real_replica()
    try:
        resp = router.dispatch_generate(pb.GenerateRequest(
            prompt=[1, 2], max_new_tokens=3,
        ))
        assert len(resp.tokens) == 5
        roots = [s for s in recorder().snapshot()
                 if s.name == "router_generate"]
        assert len(roots) == 1
        spans = _tree(roots[0].trace_id)
        assert len(spans) == 3  # root + dispatch + serve
        root = [s for s in spans if s["name"] == "router_generate"][0]
        dispatch = children_of(spans, root["span_id"])
        assert [d["name"] for d in dispatch] == ["dispatch"]
        serve = children_of(spans, dispatch[0]["span_id"])
        assert [s["name"] for s in serve] == ["serve"]
        assert [e["name"] for e in serve[0]["events"]] == [
            "queued", "seated", "prefill", "first_token", "completed"
        ]
        # and it round-trips through the chrome export
        ct = chrome_trace(spans)
        slices = [e for e in ct["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in slices} == {
            "router_generate", "dispatch", "serve"
        }
        args = [e["args"] for e in slices]
        assert all(a["trace_id"] == root["trace_id"] for a in args)
        # router e2e histogram fed the status RPC fields
        status = router.status_response()
        assert status.e2e_p50_ms > 0
    finally:
        router._stop.set()
        for rig in rigs:
            rig[3].stop()


def test_redispatched_request_yields_sibling_dispatch_spans():
    recorder().clear()
    router, rigs, stubs = _router_over_real_replica(fail_first=1, n=2)
    try:
        resp = router.dispatch_generate(pb.GenerateRequest(
            prompt=[3], max_new_tokens=2,
        ))
        assert len(resp.tokens) == 3
        roots = [s for s in recorder().snapshot()
                 if s.name == "router_generate"]
        assert len(roots) == 1
        root = roots[0].to_dict()
        spans = _tree(root["trace_id"])
        legs = children_of(spans, root["span_id"])
        # both legs are SIBLINGS under the one root: the failed
        # dispatch and its replacement
        assert sorted(leg["status"] for leg in legs) == ["error", "ok"]
        assert {leg["name"] for leg in legs} == {"dispatch"}
        assert any(e["name"] == "redispatched" for e in root["events"])
        # the serve span hangs under the SUCCESSFUL leg only
        ok_leg = [leg for leg in legs if leg["status"] == "ok"][0]
        assert [s["name"] for s in children_of(
            spans, ok_leg["span_id"])] == ["serve"]
        bad_leg = [leg for leg in legs if leg["status"] == "error"][0]
        assert children_of(spans, bad_leg["span_id"]) == []
    finally:
        router._stop.set()
        for rig in rigs:
            rig[3].stop()


def test_hedged_request_yields_sibling_legs_in_one_tree():
    recorder().clear()
    router, rigs, stubs = _router_over_real_replica(
        n=2, hedge_delay_secs=0.05
    )
    try:
        # make rep0 primary and stall it so the hedge fires
        gate = threading.Event()
        stubs["rep0"].block_until = gate
        try:
            resp = router.dispatch_generate(pb.GenerateRequest(
                prompt=[1], max_new_tokens=2,
            ))
        finally:
            gate.set()
        assert len(resp.tokens) == 3
        time.sleep(0.1)  # let the released primary leg finish its span
        roots = [s for s in recorder().snapshot()
                 if s.name == "router_generate"]
        assert len(roots) == 1
        root = roots[0].to_dict()
        assert any(e["name"] == "hedged" for e in root["events"])
        assert any(e["name"] == "hedge_win" for e in root["events"])
        legs = children_of(_tree(root["trace_id"]), root["span_id"])
        assert len(legs) == 2  # primary + hedge, SIBLINGS
        assert sorted(leg["attrs"]["hedge"] for leg in legs) == [
            False, True
        ]
    finally:
        router._stop.set()
        for rig in rigs:
            rig[3].stop()


# ------------------------------------------------- cross-process merge


def test_dump_merges_per_process_exports_into_one_trace(tmp_path):
    """Two recorders standing in for two processes: the merged export
    reassembles the parent/child edge across the 'process' boundary,
    and the CLI writes loadable Chrome-trace JSON."""
    router_rec = SpanRecorder(service="router:1")
    replica_rec = SpanRecorder(service="replica:2")
    root = router_rec.start_span("router_generate")
    leg = router_rec.start_span("dispatch", trace_id=root.trace_id,
                                parent_span_id=root.span_id,
                                replica="localhost:2")
    serve = replica_rec.start_span("serve", trace_id=root.trace_id,
                                   parent_span_id=leg.span_id)
    serve.event("first_token").finish("ok")
    leg.finish("ok")
    root.finish("ok")
    router_rec.flush(str(tmp_path))
    replica_rec.flush(str(tmp_path))

    spans, meta = dump_mod.merge_dir(str(tmp_path))
    assert len(spans) == 3 and len(meta) == 2
    assert len(group_by_trace(spans)) == 1
    roots = trace_roots(spans)
    assert [r["name"] for r in roots] == ["router_generate"]
    serve_spans = [s for s in spans if s["name"] == "serve"]
    assert serve_spans[0]["service"] == "replica:2"
    assert serve_spans[0]["parent_span_id"] == leg.span_id

    out = str(tmp_path / "trace.json")
    assert dump_mod.main(["--dir", str(tmp_path), "--out", out]) == 0
    with open(out) as f:
        doc = json.load(f)
    slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(slices) == 3
    # services map to separate chrome pids with name metadata
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {m["args"]["name"] for m in metas} == {
        "router:1", "replica:2"
    }


# ------------------------------------------------ status RPC percentiles


def test_server_status_reports_histogram_percentiles():
    engine, queue, telemetry, sched, servicer = _replica_rig()
    for wait in (0.010, 0.020, 0.100):
        telemetry.record_queue_wait(wait)
    req = ServingRequest([1], 2)
    req.submitted_at -= 0.050  # 50 ms ago
    telemetry.record_ttft(req)
    st = servicer.server_status(pb.ServerStatusRequest())
    assert st.ttft_p50_ms == pytest.approx(50.0, rel=0.05)
    assert st.queue_wait_p99_ms == pytest.approx(100.0, rel=0.05)
    assert st.queue_wait_p50_ms <= st.queue_wait_p99_ms
    assert list(st.ttft_hist) and list(st.queue_wait_hist)


def test_router_status_merges_replica_histograms():
    """Fleet-wide percentiles come from BUCKET addition across
    replicas — percentiles of the merged counts, never averages of
    per-replica percentiles."""
    h1, h2 = LogLinearHistogram(), LogLinearHistogram()
    for v in (10.0, 12.0, 14.0):
        h1.record(v)
    for v in (200.0, 220.0, 240.0):
        h2.record(v)

    class HistStub(object):
        def __init__(self, hist):
            self._hist = hist

        def server_status(self, request, timeout=None):
            return pb.ServerStatusResponse(
                ttft_hist=self._hist.to_counts(),
                queue_wait_hist=self._hist.to_counts(),
            )

    stubs = {"rep0": HistStub(h1), "rep1": HistStub(h2)}
    router = Router(sorted(stubs), config=RouterConfig(),
                    stub_factory=lambda a: stubs[a])
    router.poll_once()
    st = router.status_response()
    merged = LogLinearHistogram()
    merged.merge(h1)
    merged.merge(h2)
    assert st.ttft_p50_ms == pytest.approx(merged.percentile(50))
    assert st.ttft_p99_ms == pytest.approx(merged.percentile(99))
    assert st.ttft_p99_ms == pytest.approx(240.0, rel=0.05)
    router._stop.set()


# ------------------------------------------------- closed counter sets


def test_serving_counter_set_is_closed():
    t = ServingTelemetry(log_dir=None)
    t.count("admitted")
    with pytest.raises(ValueError, match="unknown serving counter"):
        t.count("admittd")
    assert set(t.counters) == set(ServingTelemetry.COUNTERS)


def test_router_counter_set_is_closed():
    t = RouterTelemetry(log_dir=None)
    t.count("routed")
    with pytest.raises(ValueError, match="unknown router counter"):
        t.count("routd")


def test_router_snapshot_carries_rotation_gauges():
    t = RouterTelemetry(log_dir=None)
    snap = t.snapshot()
    assert snap["healthy_replicas"] == 0 and snap["replicas"] == 0
    t.record_poll(2, 3)
    snap = t.snapshot()
    assert snap["healthy_replicas"] == 2 and snap["replicas"] == 3


# ------------------------- tb_events round-trip + telemetry tail flush


def _crc32c_bitwise(data):
    """Independent (table-free) CRC32C for the round-trip pin — NOT
    the implementation under test."""
    crc = 0xFFFFFFFF
    for b in data:
        crc ^= b
        for _ in range(8):
            crc = (crc >> 1) ^ 0x82F63B78 if crc & 1 else crc >> 1
    return crc ^ 0xFFFFFFFF


def _unmask_check(masked, data):
    expect = ((_crc32c_bitwise(data) >> 15)
              | (_crc32c_bitwise(data) << 17)) + 0xA282EAD8
    return masked == (expect & 0xFFFFFFFF)


def _parse_event_file(path):
    """Minimal TFRecord + Event-proto parser: verifies both masked
    CRCs per record and decodes scalar summaries."""
    records = []
    with open(path, "rb") as f:
        blob = f.read()
    off = 0
    while off < len(blob):
        (length,) = struct.unpack_from("<Q", blob, off)
        header = blob[off:off + 8]
        (len_crc,) = struct.unpack_from("<I", blob, off + 8)
        payload = blob[off + 12:off + 12 + length]
        (data_crc,) = struct.unpack_from("<I", blob, off + 12 + length)
        assert _unmask_check(len_crc, header), "length CRC mismatch"
        assert _unmask_check(data_crc, payload), "payload CRC mismatch"
        records.append(payload)
        off += 12 + length + 4
    assert off == len(blob), "trailing garbage after last record"
    return [_parse_event(r) for r in records]


def _read_varint(buf, off):
    out = shift = 0
    while True:
        b = buf[off]
        off += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, off
        shift += 7


def _parse_fields(buf):
    """[(field_number, wire_type, value)] for one message level."""
    fields = []
    off = 0
    while off < len(buf):
        key, off = _read_varint(buf, off)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, off = _read_varint(buf, off)
        elif wt == 1:
            (val,) = struct.unpack_from("<d", buf, off)
            off += 8
        elif wt == 5:
            (val,) = struct.unpack_from("<f", buf, off)
            off += 4
        elif wt == 2:
            ln, off = _read_varint(buf, off)
            val = buf[off:off + ln]
            off += ln
        else:
            raise AssertionError("unexpected wire type %d" % wt)
        fields.append((num, wt, val))
    return fields


def _parse_event(payload):
    """Event{1: wall_time, 2: step, 3: file_version, 5: summary}."""
    out = {"tags": {}}
    for num, _wt, val in _parse_fields(payload):
        if num == 1:
            out["wall_time"] = val
        elif num == 2:
            out["step"] = val
        elif num == 3:
            out["file_version"] = bytes(val)
        elif num == 5:
            for snum, _swt, sval in _parse_fields(val):
                if snum != 1:
                    continue
                tag, value = None, None
                for vnum, _vwt, vval in _parse_fields(sval):
                    if vnum == 1:
                        tag = bytes(vval).decode("utf-8")
                    elif vnum == 2:
                        value = vval
                out["tags"][tag] = value
    return out


def test_event_file_round_trips_through_independent_parser(tmp_path):
    """Pins the binary format the whole observability stack rides on:
    TFRecord framing with masked CRC32C + Event/Summary protobuf wire
    format, parsed back by an implementation-independent decoder."""
    from elasticdl_tpu.common.tb_events import EventFileWriter

    w = EventFileWriter(str(tmp_path))
    w.add_scalar("serving/ttft_ms", 12.5, 3)
    w.add_scalar("router/shed_total", 7.0, 4)
    w.close()
    events = _parse_event_file(w.path)
    assert events[0]["file_version"] == b"brain.Event:2"
    assert events[1]["tags"] == {
        "serving/ttft_ms": pytest.approx(12.5)
    }
    assert events[1]["step"] == 3
    assert events[2]["tags"] == {
        "router/shed_total": pytest.approx(7.0)
    }
    assert events[2]["step"] == 4
    assert all("wall_time" in e for e in events)


def test_telemetry_close_flushes_partial_window(tmp_path):
    """The satellite fix: a server stopped mid-window must still land
    its tokens/sec tail and final counter totals in the event file."""
    t = ServingTelemetry(log_dir=str(tmp_path), flush_every=50)
    t.count("admitted", 3)
    t.count("completed", 2)
    t.record_step(queue_depth=1, active_slots=2, step_secs=0.01,
                  tokens_committed=5)
    t.close()  # step 1 of 50: nothing flushed without the tail fix
    files = [f for f in os.listdir(str(tmp_path))
             if f.startswith("events.out.tfevents")]
    assert len(files) == 1
    events = _parse_event_file(os.path.join(str(tmp_path), files[0]))
    tags = {}
    for e in events:
        tags.update(e["tags"])
    assert tags["serving/admitted_total"] == pytest.approx(3.0)
    assert tags["serving/completed_total"] == pytest.approx(2.0)
    assert tags["serving/tokens_generated_total"] == pytest.approx(5.0)
    assert "serving/tokens_per_sec" in tags


# --------------------------------------------- training-plane span tree


class _FakeDispatcher(object):
    """Duck-typed task dispatcher for MasterServicer: one task, then
    re-dispatch of the same id, then reports."""

    def __init__(self):
        from elasticdl_tpu.master.task_dispatcher import Task, TaskType

        self._task = Task("shard", 0, 10, TaskType.TRAINING)
        self.model_version = 0

    def get(self, worker_id):
        return 1, self._task

    def get_eval_task(self, worker_id):
        return -1, None

    def finished(self):
        return False

    def invoke_deferred_callback(self):
        return False

    def report(self, task_id, success, exec_counters=None):
        return 0.5, self._task, 0


def test_master_task_dispatch_span_tree():
    from elasticdl_tpu.master.servicer import MasterServicer

    recorder().clear()
    servicer = MasterServicer(32, _FakeDispatcher())
    task = servicer.get_task(pb.GetTaskRequest(worker_id=0))
    assert task.trace_id and task.span_id  # context rides the proto

    # the worker-side span a real worker would open from those fields
    wspan = recorder().start_span(
        "worker_task", trace_id=task.trace_id,
        parent_span_id=task.span_id, task_id=task.task_id,
    )
    wspan.event("fetched")
    wspan.event("reported", ok=True)
    wspan.finish("ok")

    servicer.report_task_result(
        pb.ReportTaskResultRequest(task_id=task.task_id)
    )
    spans = [s.to_dict() for s in recorder().snapshot()
             if s.trace_id == task.trace_id]
    dispatch = [s for s in spans if s["name"] == "task_dispatch"]
    worker = [s for s in spans if s["name"] == "worker_task"]
    assert len(dispatch) == 1 and len(worker) == 1
    assert dispatch[0]["status"] == "ok"
    assert any(e["name"] == "reported" for e in dispatch[0]["events"])
    # one tree: worker span parents under the dispatch span
    assert worker[0]["parent_span_id"] == dispatch[0]["span_id"]
    assert trace_roots(spans)[0]["name"] == "task_dispatch"


def test_master_redispatch_seals_previous_task_span():
    from elasticdl_tpu.master.servicer import MasterServicer

    recorder().clear()
    servicer = MasterServicer(32, _FakeDispatcher())
    first = servicer.get_task(pb.GetTaskRequest(worker_id=0))
    second = servicer.get_task(pb.GetTaskRequest(worker_id=1))
    assert first.trace_id != second.trace_id
    sealed = [s for s in recorder().snapshot()
              if s.trace_id == first.trace_id]
    assert len(sealed) == 1 and sealed[0].status == "redispatched"
    # a late report for the sealed dispatch is simply untraced
    servicer.report_task_result(
        pb.ReportTaskResultRequest(task_id=first.task_id)
    )
