"""Tail-latency forensics battery: exemplar-linked histograms
(record/merge/wire vs a brute-force oracle, OpenMetrics render +
independent parse, malformed-exemplar rejection), tail-based trace
retention (a breaching root survives ring pressure that evicts healthy
siblings), cause attribution pinned on hand-built span trees, the
replica's slow_cause counter family, and the fleet collector's
scrape -> merge -> re-evaluate -> join -> attribute -> report pipeline
over a real 2-endpoint in-process rig (real exposition HTTP servers,
real independent parser, real span exports on disk)."""

import json
import os
import threading
import time

import pytest

from elasticdl_tpu.observability import collector, forensics
from elasticdl_tpu.observability.dump import drops_by_service, merge_dir
from elasticdl_tpu.observability.histogram import (
    EXEMPLAR_SLOTS,
    LogLinearHistogram,
    bucket_index,
)
from elasticdl_tpu.observability.metrics import (
    MetricsServer,
    TimeSeriesRing,
    hist_family,
    merge_window_deltas,
    render_prometheus,
)
from elasticdl_tpu.observability.promparse import parse_prometheus_text
from elasticdl_tpu.observability.slo import default_router_slos
from elasticdl_tpu.observability.tracing import SpanRecorder
from elasticdl_tpu.serving.admission import RequestQueue, ServingRequest
from elasticdl_tpu.serving.server import (
    ServingServicer,
    _Scheduler,
    serve_span_classifier,
)
from elasticdl_tpu.serving.telemetry import ServingTelemetry


# ------------------------------------------------------------ exemplars


def _exemplar_oracle(samples):
    """Brute force: best (max-value) exemplar per bucket, then keep
    only the EXEMPLAR_SLOTS highest buckets."""
    best = {}
    for tid, value, ts in samples:
        idx = bucket_index(value)
        cur = best.get(idx)
        if cur is None or value >= cur[1]:
            best[idx] = (tid, value, ts)
    keep = sorted(best)[-EXEMPLAR_SLOTS:]
    return {i: best[i] for i in keep}


def test_exemplar_record_and_merge_match_bruteforce_oracle():
    import random

    rng = random.Random(7)
    samples = [
        ("t%04d" % i, rng.uniform(0.05, 5000.0), 1000.0 + i)
        for i in range(400)
    ]
    # one histogram recording everything...
    whole = LogLinearHistogram()
    for tid, value, ts in samples:
        whole.record(value, trace_id=tid, ts=ts)
    assert whole.exemplars == _exemplar_oracle(samples)
    # ...must agree with a merge of disjoint shards (associativity —
    # the property fleet bucket-addition relies on). The shard split
    # can transiently evict a bucket one shard would have kept, so
    # compare against the oracle of what the SHARDS retained.
    shards = [LogLinearHistogram() for _ in range(4)]
    for n, (tid, value, ts) in enumerate(samples):
        shards[n % 4].record(value, trace_id=tid, ts=ts)
    merged = LogLinearHistogram()
    for s in shards:
        merged.merge(s)
    surviving = [
        ex for s in shards for ex in
        ((tid, value, ts)
         for tid, value, ts in s.exemplars.values())
    ]
    assert merged.exemplars == _exemplar_oracle(surviving)
    # bounded, highest buckets win, max-value-per-bucket wins
    assert len(whole.exemplars) <= EXEMPLAR_SLOTS
    assert min(whole.exemplars) >= sorted(
        {bucket_index(v) for _t, v, _s in samples}
    )[-EXEMPLAR_SLOTS]


def test_exemplar_wire_round_trip():
    h = LogLinearHistogram()
    h.record(3.0, trace_id="aa", ts=10.0)
    h.record(700.0, trace_id="bb", ts=11.0)
    h.record(0.5)  # no trace: counts, no exemplar
    wire_counts = h.to_counts()
    wire_ex = h.exemplars_wire()
    # JSON round trip stringifies the keys; from_counts re-accepts
    wire_ex = json.loads(json.dumps(wire_ex))
    back = LogLinearHistogram.from_counts(wire_counts, wire_ex)
    assert back.count == 3
    assert back.exemplars == h.exemplars


def test_exemplar_renders_and_reparses_through_independent_parser():
    h = LogLinearHistogram()
    h.record(12.3, trace_id="abc", ts=1722800000.0)
    h.record(456.0, trace_id="tail", ts=1722800001.0)
    text = render_prometheus([hist_family(
        "edl_serving_ttft_ms", "ttft",
        [({}, h.to_counts(), h.sum, h.exemplars)],
    )])
    assert "# {" in text.split("\n")[2]
    fams = parse_prometheus_text(text)
    exes = fams["edl_serving_ttft_ms"]["exemplars"]
    got = {ex_labels["trace_id"]: (value, ts)
           for _n, _l, ex_labels, value, ts in exes}
    assert got == {"abc": (12.3, 1722800000.0),
                   "tail": (456.0, 1722800001.0)}
    # exemplar value must sit inside its bucket's bound
    for _n, labels, _el, value, _ts in exes:
        assert value <= float(labels["le"])


@pytest.mark.parametrize("bad, why", [
    # exemplar on a counter sample
    ('# TYPE edl_x_total counter\nedl_x_total 1 '
     '# {trace_id="t"} 1 1\n', "counter"),
    # exemplar on a gauge sample
    ('# TYPE edl_g gauge\nedl_g 1 # {trace_id="t"} 1 1\n', "gauge"),
    # empty label set
    ('# TYPE h histogram\nh_bucket{le="+Inf"} 1 # {} 0.5 1\n'
     'h_sum 1\nh_count 1\n', "no labels"),
    # value above the bucket bound
    ('# TYPE h histogram\nh_bucket{le="1"} 1 # {trace_id="t"} 5 1\n'
     'h_bucket{le="+Inf"} 1\nh_sum 1\nh_count 1\n', "above le"),
    # non-finite exemplar value
    ('# TYPE h histogram\nh_bucket{le="+Inf"} 1 '
     '# {trace_id="t"} +Inf 1\nh_sum 1\nh_count 1\n', "not finite"),
    # junk after the exemplar timestamp
    ('# TYPE h histogram\nh_bucket{le="+Inf"} 1 '
     '# {trace_id="t"} 0.5 1 junk\nh_sum 1\nh_count 1\n', "junk"),
    # missing value
    ('# TYPE h histogram\nh_bucket{le="+Inf"} 1 '
     '# {trace_id="t"}\nh_sum 1\nh_count 1\n', "no value"),
    # bad label grammar inside the exemplar
    ('# TYPE h histogram\nh_bucket{le="+Inf"} 1 '
     '# {trace id="t"} 0.5\nh_sum 1\nh_count 1\n', "bad label"),
])
def test_promparse_rejects_malformed_exemplars(bad, why):
    with pytest.raises(ValueError):
        parse_prometheus_text(bad)


def test_promparse_hash_inside_label_value_is_not_an_exemplar():
    text = ('# TYPE g gauge\ng{tag="a # b"} 1\n')
    fams = parse_prometheus_text(text)
    assert fams["g"]["samples"] == [("g", {"tag": "a # b"}, 1.0)]
    assert fams["g"]["exemplars"] == []


def test_ring_windows_carry_new_exemplars_and_merge_keeps_max():
    clock = [0.0]
    ring = TimeSeriesRing(interval_secs=1.0, clock=lambda: clock[0])
    ring.observe(hists={"ttft_ms": [1]},
                 exemplars={"ttft_ms": {3: ("a", 0.03, 1.0)}})
    clock[0] = 1.1
    ring.observe(hists={"ttft_ms": [1, 1]},
                 exemplars={"ttft_ms": {3: ("a", 0.03, 1.0),
                                        9: ("b", 0.09, 2.0)}})
    w1 = ring.windows()[0]
    # the first window carries the exemplars recorded up to its close
    # (the boundary observation folds in, same as the counter deltas)
    assert w1["exemplars"]["ttft_ms"] == {3: ("a", 0.03, 1.0),
                                          9: ("b", 0.09, 2.0)}
    clock[0] = 2.2
    ring.observe(hists={"ttft_ms": [1, 1, 1]},
                 exemplars={"ttft_ms": {9: ("c", 0.095, 3.0)}})
    w2 = ring.windows()[1]
    # only the CHANGED exemplar (bucket 9's new max) is in window 2
    assert w2["exemplars"]["ttft_ms"] == {9: ("c", 0.095, 3.0)}
    merged = merge_window_deltas(w1, w2)
    assert merged["exemplars"]["ttft_ms"] == {
        3: ("a", 0.03, 1.0), 9: ("c", 0.095, 3.0),
    }
    # horizon query merges max-value per bucket
    got = ring.merged_exemplars("ttft_ms", now=clock[0])
    assert got[9] == ("c", 0.095, 3.0) or got[9] == ("b", 0.09, 2.0)


# ------------------------------------------------- tail-based retention


def test_tail_retention_keeps_breaching_root_under_ring_pressure():
    rec = SpanRecorder(service="t", capacity=8, retained_capacity=16)

    def classify(span):
        if span.name != "root":
            return None
        return span.status != "ok"

    rec.add_classifier(classify)
    # one breaching trace with a child, finished EARLY
    child = rec.start_span("serve", trace_id="bad1",
                           parent_span_id="x")
    child.finish("ok")
    bad = rec.start_span("root", trace_id="bad1")
    bad.finish("DEADLINE_EXCEEDED")
    # flood with healthy siblings far past the ring bound
    for i in range(50):
        s = rec.start_span("root", trace_id="h%d" % i)
        s.finish("ok")
    assert rec.dropped > 0  # the ring DID evict
    kept = {s.trace_id for s in rec.snapshot()}
    assert "bad1" in kept  # ...but the breaching trace survived
    # the WHOLE trace moved: both its spans are present
    assert sum(1 for s in rec.snapshot()
               if s.trace_id == "bad1") == 2
    doc = rec.export()
    assert doc["retained"] == 2
    assert doc["dropped"] == rec.dropped


def test_tail_retention_straggler_spans_follow_their_trace():
    rec = SpanRecorder(service="t", capacity=4, retained_capacity=8)
    rec.add_classifier(
        lambda s: (s.status != "ok") if s.name == "root" else None
    )
    root = rec.start_span("root", trace_id="late")
    root.finish("error")
    # a child finishing AFTER the root was retained pins to the tier
    child = rec.start_span("serve", trace_id="late",
                           parent_span_id=root.span_id)
    child.finish("ok")
    for i in range(10):
        rec.start_span("root", trace_id="h%d" % i).finish("ok")
    assert sum(1 for s in rec.snapshot()
               if s.trace_id == "late") == 2


def test_probabilistic_sampling_drops_healthy_roots():
    rec = SpanRecorder(service="t", capacity=64, sample_rate=0.0,
                       seed=1)
    rec.add_classifier(
        lambda s: (s.status != "ok") if s.name == "root" else None
    )
    for i in range(10):
        rec.start_span("root", trace_id="h%d" % i).finish("ok")
    bad = rec.start_span("root", trace_id="bad")
    bad.finish("error")
    kept = {s.trace_id for s in rec.snapshot()}
    assert kept == {"bad"}  # every healthy root sampled out
    assert rec.sampled_out == 10


def test_classifier_exception_never_loses_the_span():
    rec = SpanRecorder(service="t", capacity=8)

    def broken(_span):
        raise RuntimeError("hook bug")

    rec.add_classifier(broken)
    rec.start_span("root", trace_id="x").finish("ok")
    assert len(rec) == 1  # abstained, landed in the plain ring


# ------------------------------------------------------ attribute()


def _span(name, trace_id, start, end, status="ok", parent="",
          span_id=None, events=(), attrs=None):
    return {
        "name": name, "trace_id": trace_id,
        "span_id": span_id or ("%s-%s" % (name, start)),
        "parent_span_id": parent, "service": "t",
        "start": start, "end": end, "status": status,
        "attrs": attrs or {},
        "events": [
            {"ts": ts, "name": n, "attrs": a} for ts, n, a in events
        ],
    }


def _serve(trace_id="T", start=10.0, end=10.5, queued=10.0,
           seated=10.1, first=10.2, parent="", blocked=0.0,
           revive_ms=0.0, status="ok"):
    events = [
        (queued, "queued", {}),
        (seated, "seated", {
            "queue_wait_ms": (seated - queued) * 1000.0,
            "prefill_blocked_ms": blocked,
        }),
    ]
    if revive_ms:
        events.append((seated, "revive_upload", {"ms": revive_ms}))
    events.append((first, "first_token", {}))
    events.append((end, "completed", {}))
    return _span("serve", trace_id, start, end, status=status,
                 parent=parent, events=events)


def test_attribute_queue_wait_dominant():
    v = forensics.attribute([_serve(
        start=10.0, end=10.65, queued=10.0, seated=10.5,
        first=10.55,
    )])
    assert v["dominant_cause"] == "queue_wait"
    by = {p["cause"]: p["ms"] for p in v["breakdown"]}
    assert by["queue_wait"] == pytest.approx(500.0, abs=1.0)
    assert v["evidence_complete"]


def test_attribute_prefill_blocked_by_other_dominant():
    # 400ms queued, 380 of them while another slot's prefill ran
    v = forensics.attribute([_serve(
        start=10.0, end=10.5, queued=10.0, seated=10.4,
        first=10.45, blocked=380.0,
    )])
    assert v["dominant_cause"] == "prefill_blocked_by_other"
    by = {p["cause"]: p["ms"] for p in v["breakdown"]}
    assert by["prefill_blocked_by_other"] == pytest.approx(380.0)
    assert by["queue_wait"] == pytest.approx(20.0, abs=1.0)


def test_attribute_prefill_own_dominant():
    v = forensics.attribute([_serve(
        start=10.0, end=10.75, queued=10.0, seated=10.01,
        first=10.7,
    )])
    assert v["dominant_cause"] == "prefill_own"


def test_attribute_revive_upload_split_from_prefill():
    v = forensics.attribute([_serve(
        start=10.0, end=10.8, queued=10.0, seated=10.01,
        first=10.7, revive_ms=600.0,
    )])
    assert v["dominant_cause"] == "revive_upload"
    by = {p["cause"]: p["ms"] for p in v["breakdown"]}
    assert by["revive_upload"] == pytest.approx(600.0)
    assert by["prefill_own"] == pytest.approx(90.0, abs=2.0)


def test_attribute_decode_dominant():
    v = forensics.attribute([_serve(
        start=10.0, end=11.0, queued=10.0, seated=10.01,
        first=10.05,
    )])
    assert v["dominant_cause"] == "decode"


def test_attribute_dispatch_retries_and_stream_stall():
    # router tree: root with a failed leg, then the winning leg whose
    # serve span is much shorter than the dispatch (transport stall)
    root = _span("router_generate", "T", 10.0, 11.5, span_id="root",
                 events=[(10.4, "redispatched", {})])
    failed = _span("dispatch", "T", 10.0, 10.4, status="error",
                   parent="root", span_id="d0")
    win = _span("dispatch", "T", 10.6, 11.5, parent="root",
                span_id="d1")
    serve = _serve(start=10.6, end=10.9, queued=10.6, seated=10.61,
                   first=10.65, parent="d1")
    v = forensics.attribute([root, failed, win, serve])
    by = {p["cause"]: p["ms"] for p in v["breakdown"]}
    assert by["dispatch_retries"] == pytest.approx(600.0, abs=1.0)
    assert by["stream_stall"] == pytest.approx(600.0, abs=1.0)
    assert v["dominant_cause"] in ("dispatch_retries", "stream_stall")
    assert v["total_ms"] == pytest.approx(1500.0)


def test_attribute_expired_in_queue():
    # queued, never seated, expired: the whole wait is queue_wait
    # (minus the blocked share stamped on the expired event)
    span = _span("serve", "T", 10.0, 10.4,
                 status="DEADLINE_EXCEEDED", events=[
                     (10.0, "queued", {}),
                     (10.4, "expired", {"where": "queued",
                                        "prefill_blocked_ms": 150.0}),
                 ])
    v = forensics.attribute([span])
    by = {p["cause"]: p["ms"] for p in v["breakdown"]}
    assert by["queue_wait"] == pytest.approx(250.0, abs=1.0)
    assert by["prefill_blocked_by_other"] == pytest.approx(150.0)
    assert v["dominant_cause"] == "queue_wait"


def test_attribute_degrades_without_serve_span():
    root = _span("router_generate", "T", 10.0, 10.3, span_id="root",
                 status="UNAVAILABLE")
    v = forensics.attribute([root])
    assert not v["evidence_complete"]
    assert v["total_ms"] == pytest.approx(300.0)
    v_empty = forensics.attribute([])
    assert v_empty["dominant_cause"] is None


def test_is_terminally_slow():
    assert forensics.is_terminally_slow("DEADLINE_EXCEEDED", 10.0, 0)
    assert forensics.is_terminally_slow("ok", 90.0, 100.0)
    assert not forensics.is_terminally_slow("ok", 10.0, 100.0)
    assert not forensics.is_terminally_slow("ok", 90.0, 0)
    # errors are fast-and-wrong, not slow
    assert not forensics.is_terminally_slow("RESOURCE_EXHAUSTED",
                                            90.0, 100.0)


# -------------------------------------- replica slow_cause integration


class _SlowSeatEngine(object):
    """Stub engine whose insert() seats instantly; the slowness under
    test comes from the queue (a single slot + a held first request)."""

    num_slots = 1
    model_version = 0
    seq_len = 64
    draft_k = 0
    draft_proposed = 0
    draft_accepted = 0
    prefill_chunk_tokens = 0

    def __init__(self):
        self._slots = {}
        self.prefill_busy_ms = 0.0

    def free_slots(self):
        return [] if self._slots else [0]

    def can_seat(self, _req):
        return True

    def insert(self, request):
        self._slots[0] = request
        return 0, 11, False

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

    def max_cached_tokens(self):
        return self.seq_len

    def kv_stats(self):
        return {"kv_paged": False, "kv_shared": False,
                "kv_cache_dtype": "", "kv_block_size": 0,
                "kv_blocks_total": 0, "kv_blocks_free": 0,
                "kv_blocks_cached": 0, "kv_blocks_shared": 0,
                "kv_bytes_total": 0, "kv_bytes_in_use": 0,
                "prefix_hit_tokens": 0, "cow_copies": 0,
                "kv_host_blocks": 0, "kv_host_bytes": 0,
                "revive_uploads": 0, "prefill_tokens_revived": 0,
                "host_drops": 0, "chain_exports": 0,
                "chain_imports": 0, "chain_import_tokens": 0}


def test_scheduler_counts_slow_cause_for_expired_queued_request():
    from elasticdl_tpu.observability.tracing import recorder

    recorder().clear()
    engine = _SlowSeatEngine()
    queue = RequestQueue(capacity=8, seq_len=64)
    telemetry = ServingTelemetry(log_dir=None)
    sched = _Scheduler(engine, queue, telemetry,
                       idle_wait_secs=0.001, forensics_on=True)
    servicer = ServingServicer(
        queue, engine, telemetry, scheduler_alive=lambda: True,
        handler_poll_secs=0.02, draining=lambda: False,
    )
    import elasticdl_tpu.proto.elasticdl_pb2 as pb

    # request 1 occupies the single slot for a while
    done = {}

    def call(key, deadline_ms):
        try:
            done[key] = servicer.generate(pb.GenerateRequest(
                prompt=[1, 2], max_new_tokens=50,
                deadline_ms=deadline_ms,
            ))
        except Exception as e:  # noqa: BLE001 - the datum
            done[key] = e
    t1 = threading.Thread(target=call, args=("a", 0))
    t1.start()
    deadline = time.monotonic() + 5.0
    while not engine.active_count() and time.monotonic() < deadline:
        sched._iterate()
    # request 2 has a deadline too short to outlive the queue
    t2 = threading.Thread(target=call, args=("b", 60))
    t2.start()
    while "b" not in done and time.monotonic() < deadline:
        time.sleep(0.08)  # let the deadline lapse while queued
        sched._iterate()
    while "a" not in done and time.monotonic() < deadline:
        sched._iterate()
    t1.join(timeout=5)
    t2.join(timeout=5)
    snap = telemetry.snapshot()
    assert snap["expired"] >= 1
    causes = dict(zip(ServingTelemetry.SLOW_CAUSES,
                      snap["slow_cause_counts"]))
    assert causes["queue_wait"] >= 1, causes
    assert snap["slow_requests"] >= 1
    # the slow_cause family renders and re-parses as labeled counters
    fams = parse_prometheus_text(
        render_prometheus(telemetry.prometheus())
    )
    samples = {
        labels["cause"]: value
        for _n, labels, value in (
            fams["edl_serving_slow_cause_total"]["samples"]
        )
    }
    assert set(samples) == set(ServingTelemetry.SLOW_CAUSES)
    assert samples["queue_wait"] >= 1


def test_serve_span_classifier_retains_breach_and_slow_completion():
    class S(object):
        pass

    ok = S()
    ok.name, ok.status = "serve", "ok"
    ok.attrs = {"deadline_ms": 1000}
    ok.start, ok.end = 10.0, 10.1
    assert serve_span_classifier(ok) is False
    slow = S()
    slow.name, slow.status = "serve", "ok"
    slow.attrs = {"deadline_ms": 1000}
    slow.start, slow.end = 10.0, 10.9
    assert serve_span_classifier(slow) is True
    breach = S()
    breach.name, breach.status = "serve", "DEADLINE_EXCEEDED"
    breach.attrs = {}
    breach.start, breach.end = 10.0, 10.1
    assert serve_span_classifier(breach) is True
    other = S()
    other.name = "dispatch"
    assert serve_span_classifier(other) is None


# ------------------------------------------------------- dump drops


def test_dump_surfaces_drops_by_service(tmp_path):
    rec = SpanRecorder(service="tiny", capacity=2)
    for i in range(5):
        rec.start_span("root", trace_id="t%d" % i).finish("ok")
    rec.flush(str(tmp_path))
    rec2 = SpanRecorder(service="fine", capacity=64)
    rec2.start_span("root", trace_id="x").finish("ok")
    rec2.flush(str(tmp_path))
    spans, meta = merge_dir(str(tmp_path))
    drops = drops_by_service(meta)
    assert drops == {"tiny": 3}
    # the CLI embeds the accounting in the artifact
    from elasticdl_tpu.observability.dump import main as dump_main

    out = str(tmp_path / "trace.json")
    assert dump_main(["--dir", str(tmp_path), "--out", out]) == 0
    doc = json.load(open(out))
    assert doc["otherData"]["drops_by_service"] == {"tiny": 3}
    assert doc["otherData"]["evidence_complete"] is False


# ------------------------------------------------------- the collector


class _Req(object):
    def __init__(self, tid, ago, clock=time.monotonic):
        self.trace_id = tid
        self.submitted_at = clock() - ago


def _fleet_rig(tmp_path, n=2):
    """Two 'replicas': real ServingTelemetry + real MetricsServer +
    real span exports on disk — everything the collector consumes,
    minus the jax engine it never talks to anyway."""
    servers, tels = [], []
    for k in range(n):
        tel = ServingTelemetry(log_dir=None, ring_secs=0.05)
        rec = SpanRecorder(service="replica%d" % k)
        for i in range(15):
            tid = "r%d_%04d" % (k, i)
            sp = rec.start_span("serve", trace_id=tid,
                                deadline_ms=200)
            sp.event("queued")
            sp.event("seated", queue_wait_ms=2.0,
                     prefill_blocked_ms=1.0)
            sp.event("first_token")
            sp.event("completed")
            sp.finish("ok")
            tel.record_ttft(_Req(tid, 0.010 + 0.015 * i))
            tel.count("admitted")
            tel.count("completed")
            tel.record_e2e(30.0 + 15 * i, trace_id=tid)
        rec.flush(str(tmp_path))
        srv = MetricsServer(tel.prometheus, port=0, host="127.0.0.1")
        servers.append(srv)
        tels.append(tel)
    return servers, tels


def test_collector_scrape_merge_report_two_replica_rig(tmp_path):
    servers, tels = _fleet_rig(tmp_path)
    try:
        endpoints = ["127.0.0.1:%d" % s.port for s in servers]

        def sleep_and_feed(secs):
            time.sleep(secs)
            for tel in tels:
                tel.count("admitted")
                tel.record_ttft(_Req("hot", 0.450))
                tel.record_e2e(600.0, trace_id="hot")

        bundle = collector.scrape_fleet(
            endpoints, scrapes=3, interval_secs=0.15,
            sleep=sleep_and_feed,
        )
        assert len(bundle["rounds"]) == 3
        # fleet merge: round counters are the SUM across endpoints
        assert bundle["rounds"][0]["counters"]["admitted"] == 30
        specs = default_router_slos(50.0, 100.0, 0.02,
                                    latency_goal=0.01)
        report = collector.build_report(bundle, specs,
                                        trace_dir=str(tmp_path))
        collector.validate_report(report)
        # the tight thresholds + between-scrape hot traffic alert
        assert "ttft_p99" in report["alerting"]
        # exemplars resolved against the on-disk span exports and
        # attributed through the cause taxonomy
        resolved = [e for e in report["exemplars"] if e["resolved"]]
        assert resolved
        assert report["cause_histogram"]
        for cause in report["cause_histogram"]:
            assert cause in forensics.CAUSES
        assert report["span_evidence"]["complete"]
        # the renderer produces a summary naming the dominant cause
        text = collector.render_text(report)
        assert "ALERTING" in text
        assert report["dominant_cause"] in text
        # schema gate rejects tampering
        broken = dict(report, schema="bogus/9")
        with pytest.raises(ValueError):
            collector.validate_report(broken)
        broken = json.loads(json.dumps(report))
        broken["cause_histogram"] = {"made_up_cause": 3}
        with pytest.raises(ValueError):
            collector.validate_report(broken)
    finally:
        for s in servers:
            s.close()


def test_collector_main_cli(tmp_path):
    servers, _tels = _fleet_rig(tmp_path, n=1)
    try:
        out = str(tmp_path / "incident.json")
        txt = str(tmp_path / "incident.txt")
        rc = collector.main([
            "--endpoints", "127.0.0.1:%d" % servers[0].port,
            "--scrapes", "2", "--interval", "0.1",
            "--trace_dir", str(tmp_path),
            "--out", out, "--text", txt,
            "--slo_ttft_p99_ms", "50",
        ])
        assert rc == 0
        report = json.load(open(out))
        collector.validate_report(report)
        assert os.path.exists(txt)
    finally:
        for s in servers:
            s.close()


def test_collector_requires_two_scrapes():
    with pytest.raises(ValueError):
        collector.scrape_fleet(["x"], scrapes=1)
