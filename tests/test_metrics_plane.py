"""Live-metrics-plane tests (tier-1: no jax compute, loopback-only
sockets for the scrape server).

Locks the ISSUE 12 tentpole semantics: the windowed time-series ring
against a brute-force oracle under churn (window deltas, conservation,
bound + drop accounting), cross-replica window merge = bucket/counter
addition, the Prometheus exposition round-tripped through the
INDEPENDENT text-format parser (and that parser rejecting malformed
documents), the stdlib scrape server, the closed GAUGE sets (the
counter-set contract, extended), the snapshot()/close() vs ring
window-boundary regression (identical totals on both paths), the
windowed prefix-hit-rate, the SLO burn-rate math (multi-window rule,
finiteness), and the router's SloObjective blocks + /metrics
endpoint."""

import math
import os
import random
import urllib.error
import urllib.request

import pytest

from elasticdl_tpu.observability.histogram import (
    LogLinearHistogram,
    bucket_index,
)
from elasticdl_tpu.observability.metrics import (
    MetricsServer,
    TimeSeriesRing,
    counter_family,
    gauge_family,
    hist_family,
    merge_window_deltas,
    render_prometheus,
)
from elasticdl_tpu.observability.promparse import parse_prometheus_text
from elasticdl_tpu.observability.slo import BurnRateEngine, SloSpec
from elasticdl_tpu.proto import elasticdl_pb2 as pb
from elasticdl_tpu.serving.router import Router, RouterConfig
from elasticdl_tpu.serving.telemetry import (
    RouterTelemetry,
    ServingTelemetry,
)


class FakeClock(object):
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ------------------------------------------------------------------ ring


def _trim(counts):
    out = list(counts)
    while out and not out[-1]:
        out.pop()
    return out


def _sub(cur, base):
    return _trim([
        c - (base[i] if i < len(base) else 0)
        for i, c in enumerate(cur)
    ])


def test_ring_window_deltas_match_brute_force_oracle_under_churn():
    """Randomized churn (new counter names appearing, the histogram
    growing, irregular observation gaps) against a straight-line
    reference implementation of the close rule: a window closes at the
    first observation >= interval past the window start and carries
    cumulative-difference deltas."""
    rng = random.Random(7)
    clock = FakeClock()
    ring = TimeSeriesRing(interval_secs=1.0, capacity=10_000,
                          clock=clock)
    counters, hist = {}, []
    observations = []
    for _ in range(400):
        clock.t += rng.random() * 0.4
        for name in rng.sample("abcd", rng.randint(0, 3)):
            counters[name] = counters.get(name, 0) + rng.randint(1, 5)
        if rng.random() < 0.7:
            idx = rng.randint(0, 40)
            while len(hist) <= idx:
                hist.append(0)
            hist[idx] += rng.randint(1, 3)
        ring.observe(counters=counters, gauges={"g": clock.t},
                     hists={"h": hist})
        observations.append((clock.t, dict(counters), list(hist)))
    clock.t += 0.01
    ring.flush()

    # the oracle: replay the rule with plain loops
    expected = []
    t0, base_c, base_h, seen = 0.0, {}, [], False
    for t, cs, hs in observations:
        seen = True
        if t - t0 >= 1.0:
            expected.append((t0, t,
                             {k: v - base_c.get(k, 0)
                              for k, v in cs.items()},
                             _sub(hs, base_h), t))
            t0, base_c, base_h, seen = t, dict(cs), list(hs), False
    if seen:
        t, cs, hs = observations[-1]
        expected.append((t0, clock.t,
                         {k: v - base_c.get(k, 0)
                          for k, v in cs.items()},
                         _sub(hs, base_h), t))

    windows = ring.windows()
    assert len(windows) == len(expected) > 50
    for w, (et0, et1, ec, eh, _tc) in zip(windows, expected):
        assert w["t0"] == pytest.approx(et0)
        assert w["t1"] == pytest.approx(et1)
        assert w["counters"] == ec
        assert w["hists"]["h"] == eh
    # conservation: sum of window deltas == final cumulative, exactly
    for name, total in counters.items():
        assert sum(w["counters"].get(name, 0) for w in windows) == total
    merged = []
    for w in windows:
        for i, c in enumerate(w["hists"].get("h", [])):
            while len(merged) <= i:
                merged.append(0)
            merged[i] += c
    assert _trim(merged) == _trim(hist)


def test_ring_cross_replica_merge_is_bucket_addition():
    """Two replicas' window deltas merge exactly like router_status
    merges lifetime histograms: counter addition + elementwise bucket
    addition — and percentiles of the merged counts equal percentiles
    of union recording."""
    h1, h2 = LogLinearHistogram(), LogLinearHistogram()
    for v in (10.0, 12.0, 14.0):
        h1.record(v)
    for v in (200.0, 220.0):
        h2.record(v)
    a = {"t0": 0.0, "t1": 1.0, "counters": {"x": 2},
         "gauges": {"g": 1}, "hists": {"h": h1.to_counts()}}
    b = {"t0": 0.0, "t1": 1.0, "counters": {"x": 3, "y": 1},
         "gauges": {"g": 2}, "hists": {"h": h2.to_counts()}}
    m = merge_window_deltas(a, b)
    assert m["counters"] == {"x": 5, "y": 1}
    assert m["gauges"] == {"g": 3}
    union = LogLinearHistogram()
    union.merge(h1)
    union.merge(h2)
    merged_hist = LogLinearHistogram.from_counts(m["hists"]["h"])
    for q in (50, 90, 99):
        assert merged_hist.percentile(q) == pytest.approx(
            union.percentile(q), rel=0.05
        )
    # inputs untouched
    assert a["counters"] == {"x": 2} and b["counters"] == {"x": 3,
                                                          "y": 1}


def test_ring_bound_and_drop_accounting():
    clock = FakeClock()
    ring = TimeSeriesRing(interval_secs=1.0, capacity=5, clock=clock)
    for i in range(12):
        clock.t += 1.0
        ring.observe(counters={"n": i + 1})
    assert len(ring.windows()) == 5
    assert ring.dropped == 7  # 12 closed - 5 retained
    # the RETAINED windows are the newest; conservation now holds only
    # over retained + dropped, which is the point of the counter
    kept = sum(w["counters"]["n"] for w in ring.windows())
    assert kept < 12  # old deltas genuinely gone...
    assert ring.windows()[-1]["counters"]["n"] == 1  # ...newest kept


def test_ring_flush_closes_partial_window_and_horizon_queries():
    clock = FakeClock()
    ring = TimeSeriesRing(interval_secs=10.0, capacity=100,
                          clock=clock)
    clock.t = 1.0
    ring.observe(counters={"n": 4})
    assert ring.windows() == []  # interval not elapsed
    assert ring.pending_counter("n") == 4
    ring.flush()
    assert len(ring.windows()) == 1  # partial window force-closed
    assert ring.windows()[0]["counters"]["n"] == 4
    assert ring.pending_counter("n") == 0
    clock.t = 50.0
    ring.observe(counters={"n": 10})
    clock.t = 61.0
    ring.observe(counters={"n": 16})
    # horizon: only windows ENDING inside the trailing span count
    assert ring.sum_counter("n", horizon_secs=5.0, now=61.0) == 6
    assert ring.sum_counter("n") == 16


# ------------------------------------------------------------ exposition


def test_render_parse_round_trip():
    """The renderer's output through the INDEPENDENT parser: families,
    types, labels (escapes included), values and histogram structure
    all survive."""
    h = LogLinearHistogram()
    for v in (0.5, 3.0, 250.0):
        h.record(v)
    fams = [
        counter_family("edl_test_requests_total", "requests", 42),
        gauge_family("edl_test_depth", "queue depth",
                     [({"shard": 'a"b\\c'}, 3.5), ({"shard": "d"}, 0)]),
        hist_family("edl_test_latency_ms", "latency",
                    [({"phase": "prefill"}, h.to_counts(), h.sum)]),
    ]
    text = render_prometheus(fams)
    parsed = parse_prometheus_text(text)
    assert set(parsed) == {"edl_test_requests_total", "edl_test_depth",
                           "edl_test_latency_ms"}
    assert parsed["edl_test_requests_total"]["type"] == "counter"
    [(name, labels, value)] = [
        s for s in parsed["edl_test_requests_total"]["samples"]
    ]
    assert (name, labels, value) == ("edl_test_requests_total", {}, 42)
    depth = {tuple(sorted(s[1].items())): s[2]
             for s in parsed["edl_test_depth"]["samples"]}
    assert depth[(("shard", 'a"b\\c'),)] == 3.5
    hist_samples = parsed["edl_test_latency_ms"]["samples"]
    count = [v for n, lab, v in hist_samples
             if n.endswith("_count")]
    assert count == [3]
    sums = [v for n, lab, v in hist_samples if n.endswith("_sum")]
    assert sums[0] == pytest.approx(h.sum)
    inf_bucket = [v for n, lab, v in hist_samples
                  if n.endswith("_bucket") and lab.get("le") == "+Inf"]
    assert inf_bucket == [3]


def test_parser_rejects_malformed_expositions():
    ok_head = "# HELP f help\n# TYPE f histogram\n"
    cases = [
        # histogram buckets not monotone
        ok_head + 'f_bucket{le="1"} 5\nf_bucket{le="2"} 3\n'
        'f_bucket{le="+Inf"} 5\n',
        # no +Inf bucket
        ok_head + 'f_bucket{le="1"} 1\n',
        # _count disagrees with +Inf
        ok_head + 'f_bucket{le="+Inf"} 3\nf_count 4\n',
        # counter not ending in _total
        "# HELP c help\n# TYPE c counter\nc 1\n",
        # sample with no announced family
        "orphan_metric 1\n",
        # sample with no value
        "# HELP g help\n# TYPE g gauge\ng\n",
    ]
    for text in cases:
        with pytest.raises(ValueError):
            parse_prometheus_text(text)
    # and the happy path really is happy
    parse_prometheus_text(
        ok_head + 'f_bucket{le="1"} 3\nf_bucket{le="+Inf"} 5\n'
        "f_sum 9.5\nf_count 5\n"
    )


def test_metrics_server_serves_scrape_and_404():
    calls = []

    def collect():
        calls.append(1)
        return [counter_family("edl_t_total", "t", len(calls))]

    server = MetricsServer(collect, port=0)
    try:
        base = "http://127.0.0.1:%d" % server.port
        text = urllib.request.urlopen(
            base + "/metrics", timeout=5
        ).read().decode()
        fams = parse_prometheus_text(text)
        assert fams["edl_t_total"]["samples"][0][2] == 1
        # collect runs per scrape (live values, not a cached page)
        text = urllib.request.urlopen(
            base + "/metrics", timeout=5
        ).read().decode()
        assert parse_prometheus_text(
            text
        )["edl_t_total"]["samples"][0][2] == 2
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/other", timeout=5)
    finally:
        server.close()


# ----------------------------------------------------- closed gauge sets


def test_serving_gauge_set_is_closed():
    t = ServingTelemetry(log_dir=None)
    t.gauge("queue_depth", 5)
    with pytest.raises(ValueError, match="unknown serving gauge"):
        t.gauge("queue_dept", 5)
    assert set(t.gauges) == set(ServingTelemetry.GAUGES)


def test_router_gauge_set_is_closed():
    t = RouterTelemetry(log_dir=None)
    t.gauge("healthy_replicas", 2)
    with pytest.raises(ValueError, match="unknown router gauge"):
        t.gauge("healthy_replica", 2)


# --------------------------- snapshot()/close() vs ring window boundary


def test_close_flushes_identical_totals_to_tb_events_and_ring(tmp_path):
    """The satellite FIX pin: a server stopped mid-window must flush
    the SAME totals to the tb_events path and to the last ring window
    — for every counter, final event-file total == telemetry counter
    == sum of ring window deltas (the partial window included)."""
    from test_observability import _parse_event_file

    t = ServingTelemetry(log_dir=str(tmp_path), flush_every=50,
                         ring_secs=3600.0)  # ring window stays OPEN
    t.count("admitted", 3)
    t.count("completed", 2)
    t.count("prompt_tokens", 11)
    t.record_step(queue_depth=1, active_slots=2, step_secs=0.01,
                  tokens_committed=5)
    t.count("admitted", 1)  # after the last step: close() must see it
    snap = t.snapshot()  # == the totals both flush paths must land
    t.close()  # mid-window on BOTH paths (step 1/50, ring 0/3600s)

    files = [f for f in os.listdir(str(tmp_path))
             if f.startswith("events.out.tfevents")]
    assert len(files) == 1
    tags = {}
    for e in _parse_event_file(os.path.join(str(tmp_path), files[0])):
        tags.update(e["tags"])
    windows = t.ring.windows()
    assert windows, "close() did not flush the partial ring window"
    for name in ServingTelemetry.COUNTERS:
        ring_total = sum(w["counters"].get(name, 0) for w in windows)
        assert tags["serving/%s_total" % name] == pytest.approx(
            ring_total
        ), name
        assert ring_total == snap[name], name
    # the histogram bucket deltas land too (step_ms recorded once)
    assert sum(sum(w["hists"].get("step_ms", [])) for w in windows) == 1


def test_windowed_prefix_hit_rate():
    clock = FakeClock()
    t = ServingTelemetry(log_dir=None, clock=clock, ring_secs=1.0)
    t.count("prompt_tokens", 80)
    t.count("prefix_hit_tokens", 60)
    # live partial window already answers (pending deltas)
    assert t.snapshot()["prefix_hit_rate_window"] == pytest.approx(
        0.75
    )
    clock.t += 2.0
    t.record_step(0, 1, 0.001, 1)  # rolls the ring window
    assert t.snapshot()["prefix_hit_rate_window"] == pytest.approx(
        0.75
    )
    # a cold burst shifts the WINDOWED rate while the lifetime ratio
    # would lag: new window, all-miss traffic
    clock.t += 40.0  # previous window ages out of the 30s horizon
    t.count("prompt_tokens", 50)
    clock.t += 2.0
    t.record_step(0, 1, 0.001, 1)
    assert t.snapshot()["prefix_hit_rate_window"] == pytest.approx(
        0.0
    )


def test_serving_telemetry_exposition_parses_with_live_values():
    t = ServingTelemetry(log_dir=None)
    t.count("admitted", 4)
    t.record_e2e(12.0)
    t.record_step(1, 1, 0.004, 2)
    fams = parse_prometheus_text(render_prometheus(t.prometheus()))
    admitted = fams["edl_serving_admitted_total"]["samples"][0][2]
    assert admitted == 4
    e2e_count = [v for n, lab, v in
                 fams["edl_serving_e2e_ms"]["samples"]
                 if n.endswith("_count")]
    assert e2e_count == [1]
    assert "edl_serving_prefix_hit_rate_window" in fams
    assert "edl_serving_ring_windows_dropped" in fams


# ------------------------------------------------------- SLO burn rates


def _ring_with_hist(values, clock, name="ttft_ms", counters=None):
    ring = TimeSeriesRing(interval_secs=1.0, capacity=100, clock=clock)
    h = LogLinearHistogram()
    for v in values:
        h.record(v)
    clock.t += 5.0
    ring.observe(counters=counters or {}, hists={name: h.to_counts()})
    clock.t += 0.1
    ring.flush()
    return ring


def test_latency_burn_rate_math_and_multiwindow_rule():
    clock = FakeClock()
    # 8 good (50 ms), 2 bad (500 ms) against a 100 ms threshold with a
    # 1% budget: bad fraction 0.2 => burn 20x on both windows
    ring = _ring_with_hist([50.0] * 8 + [500.0] * 2, clock)
    engine = BurnRateEngine(
        [SloSpec("ttft_p99", "latency", 0.01, hist="ttft_ms",
                 threshold_ms=100.0)],
        fast_window_secs=30.0, slow_window_secs=120.0,
    )
    [r] = engine.evaluate(ring, now=clock.t)
    assert r["fast_burn"] == pytest.approx(20.0)
    assert r["slow_burn"] == pytest.approx(20.0)
    assert r["fast_samples"] == 10
    assert r["alerting"] is True

    # fast-only burn is a blip, not an alert: age the bad window out
    # of the fast horizon, then record fresh good-only traffic
    clock2 = FakeClock()
    ring2 = TimeSeriesRing(interval_secs=1.0, capacity=100,
                           clock=clock2)
    bad = LogLinearHistogram()
    for v in [500.0] * 2 + [50.0] * 8:
        bad.record(v)
    clock2.t = 5.0
    ring2.observe(hists={"ttft_ms": bad.to_counts()})
    clock2.t = 100.0  # bad window now outside fast=30, inside slow=120
    ring2.observe(hists={"ttft_ms": bad.to_counts()})
    ring2.flush()
    [r2] = engine.evaluate(ring2, now=clock2.t)
    assert r2["fast_burn"] == 0.0  # no fresh samples
    assert r2["slow_burn"] == pytest.approx(20.0)
    assert r2["alerting"] is False


def test_threshold_bucket_counts_as_good_within_resolution():
    clock = FakeClock()
    ring = _ring_with_hist([100.0] * 10, clock)
    engine = BurnRateEngine(
        [SloSpec("ttft_p99", "latency", 0.01, hist="ttft_ms",
                 threshold_ms=100.0)],
    )
    [r] = engine.evaluate(ring, now=clock.t)
    assert r["fast_burn"] == 0.0  # the threshold's own bucket is good
    assert bucket_index(100.0) == bucket_index(100.0)  # tautology pin


def test_availability_burn_and_finiteness_on_empty_ring():
    clock = FakeClock()
    ring = _ring_with_hist([], clock,
                           counters={"routed": 100, "shed": 3,
                                     "errors": 1})
    engine = BurnRateEngine(
        [SloSpec("goodput", "availability", 0.02,
                 bad_counters=("shed", "errors"),
                 total_counters=("routed",))],
    )
    [r] = engine.evaluate(ring, now=clock.t)
    assert r["fast_burn"] == pytest.approx((4 / 100) / 0.02)  # 2x
    # empty ring: burns are 0.0 and FINITE, never NaN/inf
    empty = TimeSeriesRing(clock=clock)
    [r0] = engine.evaluate(empty, now=clock.t)
    assert r0["fast_burn"] == 0.0 and r0["slow_burn"] == 0.0
    assert math.isfinite(r0["fast_burn"])
    assert r0["alerting"] is False


def test_slo_spec_validation():
    with pytest.raises(ValueError):
        SloSpec("x", "latency", 0.01)  # no hist/threshold
    with pytest.raises(ValueError):
        SloSpec("x", "availability", 0.01)  # no counters
    with pytest.raises(ValueError):
        SloSpec("x", "latency", 0.0, hist="h", threshold_ms=1.0)
    with pytest.raises(ValueError):
        SloSpec("x", "nonsense", 0.01)


# --------------------------------------------- router SLO + /metrics


class _HistStub(object):
    """Replica stub answering server_status with fixed histogram
    buckets + a windowed hit rate."""

    def __init__(self, hist, hit_rate=0.0):
        self._hist = hist
        self._hit = hit_rate

    def server_status(self, request, timeout=None):
        return pb.ServerStatusResponse(
            ttft_hist=self._hist.to_counts(),
            queue_wait_hist=self._hist.to_counts(),
            prefix_hit_rate_window=self._hit,
        )


def _slo_router(**cfg_kwargs):
    h = LogLinearHistogram()
    for v in (10.0, 50_000.0, 60_000.0):
        h.record(v)
    stub = _HistStub(h, hit_rate=0.4)
    router = Router(
        ["rep0"],
        RouterConfig(slo_ttft_p99_ms=100.0, **cfg_kwargs),
        stub_factory=lambda a: stub,
    )
    router.telemetry.count("routed", 10)
    router.poll_once()
    router.telemetry.ring.interval_secs = 0.0  # close on next poll
    router.poll_once()
    return router


def test_router_status_carries_slo_blocks_and_hit_rate():
    router = _slo_router()
    try:
        st = router.status_response()
        by_name = {s.name: s for s in st.slo}
        assert set(by_name) == {"ttft_p99", "e2e_p99", "goodput"}
        ttft = by_name["ttft_p99"]
        # 2 of 3 samples above 100 ms with a 1% budget: ~66.7x burn
        assert ttft.fast_burn == pytest.approx(66.67, rel=0.01)
        assert ttft.alerting
        assert ttft.fast_samples == 3
        for s in st.slo:
            assert math.isfinite(s.fast_burn)
            assert math.isfinite(s.slow_burn)
        assert st.replica[0].prefix_hit_rate_window == pytest.approx(
            0.4
        )
    finally:
        router._stop.set()


def test_router_metrics_endpoint_exposes_burn_rates():
    router = _slo_router(metrics_port=0)
    router.start(grpc_server=False)
    try:
        text = urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % router.metrics.port,
            timeout=5,
        ).read().decode()
        fams = parse_prometheus_text(text)
        assert "edl_router_routed_total" in fams
        assert "edl_router_fleet_ttft_ms" in fams  # fleet-merged hist
        burns = {
            (lab["slo"], lab["window"]): v
            for n, lab, v in fams["edl_router_slo_burn"]["samples"]
        }
        assert burns[("ttft_p99", "fast")] == pytest.approx(
            66.67, rel=0.01
        )
        assert ("goodput", "slow") in burns
        alerting = {
            lab["slo"]: v
            for n, lab, v in
            fams["edl_router_slo_alerting"]["samples"]
        }
        assert alerting["ttft_p99"] == 1.0
    finally:
        router.stop()
