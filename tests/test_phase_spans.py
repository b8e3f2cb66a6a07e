"""Phase spans (observability/tracing.py `phase` / `begin` / `end` /
`count`): the closed set, nesting, the second ring and its bound, the
cumulative histograms behind /metrics, the profiler's clock, and the
two hot loops that record them (the scheduler tick, the local train
loop) — with the program's outputs unchanged."""

import glob
import json
import threading
import time

import numpy as np
import pytest

from elasticdl_tpu.observability import tracing
from elasticdl_tpu.observability.metrics import (
    hist_family,
    render_prometheus,
)
from elasticdl_tpu.observability.promparse import parse_prometheus_text
from elasticdl_tpu.observability.tracing import SpanRecorder


@pytest.fixture(autouse=True)
def fresh_ring():
    tracing.recorder().clear_phases()
    yield
    tracing.recorder().clear_phases()


def _names(phases):
    return [p.name for p in phases]


# ----------------------------------------------------------- the primitive


def test_nesting_records_parent_and_inherits_seq_and_trace_id():
    tick = tracing.begin("tick", seq=7)
    with tracing.phase("tick.admit"):
        with tracing.phase("prefill", trace_id="abc", prompt_tokens=5):
            with tracing.phase("prompt_write", blocks=2):
                pass
    with tracing.phase("tick.stream"):
        pass
    tracing.end(tick, active=3, queue_depth=1)
    got = {p.name: p for p in tracing.recorder().phases()}
    # children seal before their parent: the root is last
    assert _names(tracing.recorder().phases()) == [
        "prompt_write", "prefill", "tick.admit", "tick.stream", "tick"]
    assert got["tick"].parent == "" and got["tick"].seq == 7
    assert got["tick"].attrs == {"active": 3, "queue_depth": 1}
    assert got["tick.admit"].parent == "tick"
    assert got["prefill"].parent == "tick.admit"
    assert got["prompt_write"].parent == "prefill"
    assert {p.seq for p in got.values()} == {7}
    assert got["prefill"].trace_id == got["prompt_write"].trace_id == "abc"
    assert got["tick.stream"].trace_id == ""  # a sibling inherits nothing
    assert got["prefill"].attrs == {"prompt_tokens": 5}
    for child, parent in (("prompt_write", "prefill"),
                          ("prefill", "tick.admit"), ("tick.admit", "tick")):
        assert got[parent].start_ns <= got[child].start_ns
        assert got[child].end_ns <= got[parent].end_ns


def test_names_are_a_closed_set():
    with pytest.raises(ValueError, match="unknown phase"):
        tracing.begin("tick.uplaod")
    with pytest.raises(ValueError, match="unknown counter"):
        tracing.count("prompt_write.launch")
    assert tracing.recorder().phases() == []
    assert len(set(tracing.PHASES)) == len(tracing.PHASES)
    assert not set(tracing.PHASES) & set(tracing.COUNTERS)


def test_an_exception_between_begin_and_end_cannot_misparent_the_next():
    tick = tracing.begin("tick", seq=1)
    tracing.begin("tick.admit")  # never ended: its body raised
    tracing.end(tick)
    with tracing.phase("idle"):
        pass
    got = {p.name: p for p in tracing.recorder().phases()}
    assert set(got) == {"tick", "idle"}
    assert got["idle"].parent == "" and got["idle"].seq is None


def test_counts_are_windowable_entries_and_cumulative_totals():
    with tracing.phase("prefill", seq=3):
        tracing.count("prompt_write.launches", 4)
        tracing.count("prompt_write.tokens", 61)
    tracing.count("prompts_prefilled")
    rec = tracing.recorder()
    assert rec.counts() == dict(dict.fromkeys(tracing.COUNTERS, 0), **{
        "prompt_write.launches": 4, "prompt_write.tokens": 61,
        "prompts_prefilled": 1})
    launches = [p for p in rec.phases() if p.name == "prompt_write.launches"]
    assert len(launches) == 1 and launches[0].attrs == {"n": 4}
    assert launches[0].start_ns == launches[0].end_ns
    assert launches[0].parent == "prefill" and launches[0].seq == 3
    # `since`/`until` keep what overlaps the interval
    t = launches[0].start_ns
    assert launches[0] in rec.phases(since_ns=t, until_ns=t)
    assert rec.phases(since_ns=t + 10**12) == []
    assert rec.phases(until_ns=t - 10**12) == []


def test_the_phase_ring_is_bounded_and_counts_what_it_drops(monkeypatch):
    small = SpanRecorder(phase_capacity=8)
    monkeypatch.setattr(tracing, "_RECORDER", small)
    for i in range(20):
        with tracing.phase("idle", seq=i):
            pass
    assert len(small.phases()) == 8
    assert small.phases_dropped == 12
    assert [p.seq for p in small.phases()] == list(range(12, 20))
    # the histogram is cumulative: it saw all twenty
    assert small.phase_snapshot()["idle"]["count"] == 20


def test_neither_ring_evicts_the_other(monkeypatch):
    small = SpanRecorder(capacity=4, phase_capacity=4)
    monkeypatch.setattr(tracing, "_RECORDER", small)
    for _ in range(3):
        small.start_span("serve").finish()
    for i in range(50):
        with tracing.phase("idle", seq=i):
            pass
    assert len(small.snapshot()) == 3 and small.dropped == 0
    for _ in range(50):
        small.start_span("serve").finish()
    assert len(small.phases()) == 4 and small.phases_dropped == 46
    assert len(small.snapshot()) == 4 and small.dropped == 49
    small.clear()  # request spans only
    assert len(small.phases()) == 4


def test_histograms_back_the_snapshot_and_the_metrics_family():
    for ms in (1, 2, 40):
        ph = tracing.begin("tick.upload")
        ph.start_ns -= ms * 10**6  # a phase of about `ms` ms
        tracing.end(ph)
    with tracing.phase("tick.fetch"):
        pass
    rec = tracing.recorder()
    snap = rec.phase_snapshot()
    assert set(snap) == {"tick.upload", "tick.fetch"}
    assert snap["tick.upload"]["count"] == 3
    assert snap["tick.upload"]["p50_ms"] == pytest.approx(2.0, rel=0.05)
    assert snap["tick.upload"]["total_ms"] == pytest.approx(43.0, rel=0.05)
    text = render_prometheus([hist_family(
        "edl_serving_phase_ms", "phases", rec.phase_hist_series())])
    fams = parse_prometheus_text(text)  # raises on malformation
    labels = {lab["phase"] for _n, lab, _v in
              fams["edl_serving_phase_ms"]["samples"]}
    assert labels == {"tick.upload", "tick.fetch"}


def test_export_and_chrome_trace_carry_phases_beside_request_spans(
        monkeypatch, tmp_path):
    from elasticdl_tpu.observability import dump

    rec = SpanRecorder(service="replica:1")
    monkeypatch.setattr(tracing, "_RECORDER", rec)
    rec.start_span("serve", trace_id="t1").finish()
    with tracing.phase("tick", seq=0):
        with tracing.phase("prefill", trace_id="t1"):
            tracing.count("prompts_prefilled")
    rec.flush(str(tmp_path))
    assert dump.main(["--dir", str(tmp_path),
                      "--out", str(tmp_path / "trace.json")]) == 0
    doc = json.load(open(tmp_path / "trace.json"))
    events = doc["traceEvents"]
    slices = {e["name"]: e for e in events if e["ph"] == "X"}
    assert {"serve", "edl/tick", "edl/prefill"} <= set(slices)
    assert slices["edl/prefill"]["args"]["trace_id"] == "t1"
    assert slices["edl/prefill"]["pid"] == slices["serve"]["pid"]
    assert slices["edl/tick"]["ts"] <= slices["edl/prefill"]["ts"]
    assert [e["name"] for e in events if e["ph"] == "i"] == [
        "edl/prompts_prefilled"]
    assert doc["otherData"]["exports"][0]["phases"] == 3
    assert doc["otherData"]["exports"][0]["phases_dropped"] == 0
    spans, meta = dump.merge_dir(str(tmp_path))  # its old contract
    assert len(spans) == 1 and meta[0]["spans"] == 1


# ------------------------------------------------------ the profiler's clock


def test_annotations_land_in_the_xplane_on_the_rings_clock(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        for seq in range(3):
            with tracing.phase("train.step", seq=seq):
                with tracing.phase("trainer.dispatch"):
                    jnp.ones((64, 64)).sum().block_until_ready()
                    time.sleep(0.003)
    finally:
        jax.profiler.stop_trace()
    with tracing.phase("train.step", seq=99):  # no session: ring only
        pass
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("edl/"):
                    events.setdefault(ev.name, []).append(
                        (line.name, ev.start_ns, ev.duration_ns))
    assert set(events) == {"edl/train.step", "edl/trainer.dispatch"}
    assert len(events["edl/train.step"]) == 3
    # one thread line holds them all: they nest there
    assert len({line for evs in events.values() for line, _, _ in evs}) == 1
    ring = [p for p in tracing.recorder().phases()
            if p.name == "train.step" and p.seq != 99]
    # the xplane counts from its session's start, the ring from the
    # epoch: one origin apart, and the same clock — the offset is the
    # same for every event to within 1 ms, and so is every duration
    traced = sorted(events["edl/train.step"], key=lambda e: e[1])
    origin = ring[0].start_ns - traced[0][1]
    for (_, start, dur), p in zip(traced, ring):
        assert abs(start + origin - p.start_ns) < 1e6
        assert abs(dur - (p.end_ns - p.start_ns)) < 1e6
        assert dur >= 3e6


# ------------------------------------------------------- the scheduler tick


def test_a_scripted_iterate_yields_the_expected_tree():
    from test_serving import FakeClock, _req, _rig

    clock = FakeClock()
    engine, queue, _telemetry, sched = _rig(clock)
    sched._iterate()  # nothing to do: admit polls, then the idle wait
    queue.submit(_req(clock=clock))
    sched._iterate()  # seats the request, one decode step, streams it
    sched._iterate()  # decode only
    by_seq = {}
    for p in tracing.recorder().phases():
        by_seq.setdefault(p.seq, []).append(p)
    assert sorted(by_seq) == [0, 1, 2]
    assert _names(by_seq[0]) == ["tick.admit", "idle", "tick"]
    for seq in (1, 2):
        assert _names(by_seq[seq]) == ["tick.admit", "tick.stream", "tick"]
        assert all(p.parent == "tick" for p in by_seq[seq][:-1])
    roots = [ps[-1] for _seq, ps in sorted(by_seq.items())]
    assert [r.attrs["active"] for r in roots] == [0, 1, 1]
    assert [r.attrs["queue_depth"] for r in roots] == [0, 0, 0]
    assert all(r.parent == "" for r in roots)


def test_a_tick_that_raises_still_seals_its_root():
    from test_serving import FakeClock, _rig

    _engine, _queue, _telemetry, sched = _rig(FakeClock())
    sched._fill_slots = lambda: 1 / 0
    with pytest.raises(ZeroDivisionError):
        sched._iterate()
    sched._fill_slots = lambda: None
    sched._iterate()
    roots = [p for p in tracing.recorder().phases() if p.name == "tick"]
    assert [r.seq for r in roots] == [0, 1]
    assert all(r.parent == "" for r in roots)


# ------------------------------- the ring as columns, at its default bound


def _seal(rec, name, start_ns, end_ns, seq, parent, trace_id, attrs):
    """One entry with every field given, as `end` and `count` seal it."""
    index = tracing._NAME_INDEX
    pi = index[parent] if parent else tracing._NO_KEY
    if name in tracing.COUNTERS:
        rec._seal_count(name, index[name], start_ns, seq, pi, attrs["n"])
    else:
        rec._seal(name, index[name], start_ns, end_ns, seq, parent, pi,
                  trace_id, attrs)


def _fill(rec, entries, t0=10**18):
    """`entries` of a decode loop's mix, a microsecond apart: one root
    with its two attributes and fifteen counts a tick."""
    for i in range(entries):
        t = t0 + i * 1000
        if i % 16 == 15:
            _seal(rec, "tick", t - 15000, t, i // 16, "", "",
                  {"active": 3, "queue_depth": 1})
        else:
            _seal(rec, "tick.ahead", t, t, i // 16, "tick.dispatch", "",
                  {"n": 1})


def test_the_ring_at_its_default_bound_holds_a_window_in_order(monkeypatch):
    rec = SpanRecorder()
    assert rec.phase_capacity == 2**20
    monkeypatch.setattr(tracing, "_RECORDER", rec)
    for i in range(300000):
        tracing.count("tick.transfers", i & 1)
    got = rec.phases()
    assert len(got) == 300000 and rec.phases_dropped == 0
    assert [p.attrs["n"] for p in got[:4]] == [0, 1, 0, 1]
    assert all(a.end_ns <= b.end_ns for a, b in zip(got, got[1:]))
    assert rec.counts()["tick.transfers"] == 150000
    # nothing was sealed since: the same list, not one built again
    assert rec.phases() is got
    tracing.count("tick.transfers")
    again = rec.phases()
    assert again is not got and len(again) == 300001


def test_a_full_ring_is_small_and_nothing_the_collector_walks():
    import gc
    import tracemalloc

    gc.collect()
    before = len(gc.get_objects())
    tracemalloc.start()
    try:
        rec = SpanRecorder()
        _fill(rec, 2**20 + 1024)
        held, _peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    gc.collect()
    assert rec.phases_dropped == 1024
    assert len(gc.get_objects()) - before < 10000
    # the columns are a mapping of their own, which tracemalloc does
    # not see: 48 bytes an entry
    assert len(rec._ring) == 48 * 2**20
    assert held + len(rec._ring) < 64 * 2**20
    # and what it holds is still the mix that went in
    newest = rec.phases(since_ns=10**18 + (2**20 + 1008) * 1000)
    assert [p.name for p in newest] == ["tick.ahead"] * 15 + ["tick"]
    assert newest[-1].attrs == {"active": 3, "queue_depth": 1}
    assert newest[-1].seq == (2**20 + 1023) // 16
    assert [p.attrs for p in newest[:2]] == [{"n": 1}] * 2


def test_bounds_search_the_ring_and_equal_the_filtered_whole(monkeypatch):
    """A small ring that has wrapped, entries of many lengths, some
    sealed out of order (a stamped phase ends in the past): with
    bounds, exactly what a filter of the whole list keeps."""
    import random

    rng = random.Random(7)
    rec = SpanRecorder(phase_capacity=500)
    monkeypatch.setattr(tracing, "_RECORDER", rec)
    t = 10**15
    for i in range(1300):
        t += rng.randrange(1, 2000)
        lasted = rng.choice((0, 10, 500, 40000))
        late = rng.choice((0, 0, 0, 3000))  # sealed after a younger one
        _seal(rec, "tick.fetch", t - late - lasted, t - late, i, "tick", "",
              {})
    whole = rec.phases()
    assert len(whole) == 500 and rec.phases_dropped == 800
    assert rec._disorder_ns > 0
    lo, hi = whole[0].end_ns, whole[-1].end_ns
    for _ in range(200):
        since = rng.randrange(lo - 5000, hi + 5000)
        until = since + rng.choice((0, 1, 700, 90000))
        for a, b in ((since, until), (since, None), (None, until)):
            want = [p for p in whole
                    if (a is None or p.end_ns >= a)
                    and (b is None or p.start_ns <= b)]
            assert rec.phases(since_ns=a, until_ns=b) == want
    # a search, not a scan: a bound near the end copies a few entries
    copied = []
    columns = rec._columns
    monkeypatch.setattr(
        rec, "_columns",
        lambda a, b: copied.append(b - a) or columns(a, b))
    assert len(rec.phases(since_ns=whole[-3].end_ns)) >= 3
    assert copied[0] < 60


def test_attributes_the_columns_cannot_hold_go_beside_them():
    rec = SpanRecorder(phase_capacity=4)
    _seal(rec, "prefill", 1, 2, 0, "tick.admit", "t1",
          {"prompt_tokens": 5, "bucket": 8, "shared": 3, "why": "x"})
    _seal(rec, "tick.transfers", 3, 3, 0, "", "", {"n": 2.5})
    _seal(rec, "reload_swap", 4, 5, None, "", "", {"version": 2**70})
    got = rec.phases()
    assert got[0].attrs == {"prompt_tokens": 5, "bucket": 8, "shared": 3,
                            "why": "x"}
    assert got[0].trace_id == "t1" and got[1].trace_id == ""
    assert got[1].attrs == {"n": 2.5} and rec.counts()[
        "tick.transfers"] == 2.5
    assert got[2].attrs == {"version": 2**70} and got[2].seq is None
    for i in range(4):  # what the wrap leaves behind is not handed out
        _seal(rec, "idle", 10 + i, 11 + i, i, "", "", {})
    assert [(p.attrs, p.trace_id) for p in rec.phases()] == [({}, "")] * 4


# ------------------------------------- slow phases and what lay beneath them


@pytest.fixture
def warnings_logged(monkeypatch):
    lines = []
    monkeypatch.setattr(tracing.logger, "warning",
                        lambda msg, *args: lines.append(msg % args))
    return lines


def test_a_slow_phase_is_kept_with_the_collection_and_the_compile_inside_it(
        monkeypatch, warnings_logged):
    import gc

    import jax

    rec = SpanRecorder()
    monkeypatch.setattr(tracing, "_RECORDER", rec)
    for seq in range(20):
        with tracing.phase("tick.fetch", seq=seq):
            time.sleep(0.001)
    assert rec.slow_phases() == [] and warnings_logged == []
    with tracing.phase("tick", seq=20):
        with tracing.phase("tick.fetch"):
            time.sleep(0.3)
            gc.collect()
            jax.jit(lambda x: x * 3 + 1)(np.arange(7.0))
    [kept] = rec.slow_phases()
    assert kept["phase"].name == "tick.fetch" and kept["phase"].seq == 20
    assert 300 <= kept["own_ms"] and 0.9 <= kept["median_ms"] <= 3.0
    beneath = {}
    for p in kept["beneath"]:
        beneath.setdefault(p.name, []).append(p)
        assert kept["phase"].start_ns <= p.start_ns
        assert p.end_ns <= kept["phase"].end_ns
    assert beneath["gc"][-1].parent == "tick.fetch"
    assert beneath["gc"][-1].attrs["generation"] == 2
    assert kept["gc_ms"] >= 1.0
    # one program: a lowering and a backend compile, and the count
    assert [p.attrs["backend"] for p in beneath["compile"]] == [0, 1]
    assert [p.attrs["n"] for p in beneath["compile.programs"]] == [1]
    assert kept["compile_programs"] == 1 and kept["compile_ms"] > 0
    # the root took as long, but none of it was its own: one record,
    # one line, one count
    assert rec.slow_counts() == {"tick.fetch": 1}
    assert len(warnings_logged) == 1
    assert warnings_logged[0].startswith("slow phase tick.fetch seq 20: ")
    assert "; gc " in warnings_logged[0] and "; compile " in warnings_logged[0]
    assert warnings_logged[0] == kept["line"]
    # the ring's wrap cannot evict it
    _fill(rec, 2**20)
    assert rec.phases_dropped > 0
    assert not [p for p in rec.phases() if p.name == "tick.fetch"]
    assert rec.slow_phases() == [kept] and rec.slow_dropped == 0
    doc = rec.export()["slow_phases"]
    assert doc == rec.slow_json() and doc["dropped"] == 0
    assert doc["slow"][0]["phase"]["name"] == "tick.fetch"
    assert doc["slow"][0]["line"] == kept["line"]
    json.dumps(doc)


def test_waits_and_the_causes_themselves_are_never_slow(
        monkeypatch, warnings_logged):
    rec = SpanRecorder()
    monkeypatch.setattr(tracing, "_RECORDER", rec)
    now = time.time_ns()
    for name in ("idle", "train.checkpoint", "train.eval"):
        ph = tracing.begin(name)
        ph.start_ns -= 10**9
        tracing.end(ph)
    tracing.stamp("compile", now - 2 * 10**9, now, backend=1)
    tracing.stamp("watch.late", now - 10**9, now)
    assert rec.slow_phases() == [] and warnings_logged == []
    # nor is a wait its parent's own time
    tick = tracing.begin("tick", seq=3)
    idle = tracing.begin("idle")
    idle.start_ns -= 10**9
    tracing.end(idle)
    tick.start_ns -= 10**9
    tracing.end(tick)
    assert rec.slow_phases() == []
    assert not rec.is_slow("idle", 10**10)
    # a name with no median yet has nothing to be three times of
    assert rec.is_slow("train.task_get", 3 * 10**8)
    assert not rec.is_slow("train.task_get", 2 * 10**8)
    ph = tracing.begin("train.task_get")
    ph.start_ns -= 4 * 10**8
    tracing.end(ph)
    assert [r["phase"].name for r in rec.slow_phases()] == ["train.task_get"]
    assert "(no sample)" in warnings_logged[0]
    # and one that is always this long is not slow either
    for _ in range(5):
        ph = tracing.begin("train.task_get")
        ph.start_ns -= 4 * 10**8
        tracing.end(ph)
    assert len(rec.slow_phases()) == 1


def test_the_retained_tier_is_bounded_and_counts_what_it_drops(
        monkeypatch, warnings_logged):
    rec = SpanRecorder()
    monkeypatch.setattr(tracing, "_RECORDER", rec)
    with tracing.phase("tick.commit"):
        pass
    for seq in range(260):
        ph = tracing.begin("tick.commit", seq=seq)
        ph.start_ns -= 3 * 10**8
        tracing.end(ph)
        rec._phase_hists["tick.commit"] = type(
            rec._phase_hists["tick.commit"])()
        rec._phase_hists["tick.commit"].record(0.001)
    assert len(rec.slow_phases()) == 256 and rec.slow_dropped == 4
    assert rec.slow_phases()[0]["phase"].seq == 4
    assert rec.slow_counts() == {"tick.commit": 260}
    rec.clear_phases()
    assert rec.slow_phases() == [] and rec.slow_counts() == {}


def test_a_collection_that_starts_inside_the_rings_lock_waits_its_turn(
        monkeypatch):
    """The gc callback runs wherever a collection starts, and that may
    be inside `_seal` with the ring's lock held by this very thread:
    the entry is kept aside and sealed by the next seal."""
    rec = SpanRecorder()
    monkeypatch.setattr(tracing, "_RECORDER", rec)
    with tracing.phase("tick", seq=1):
        with rec._phase_lock:
            rec._gc_pause(10, 10 + 5 * 10**6, 1, "tick",
                          {"generation": 2, "collected": 9}, True)
            assert len(rec._gc_deferred) == 1
        # short ones, and one beneath nothing, are tallied only
        rec._gc_pause(20, 20 + 10**5, 1, "tick", {"generation": 0}, True)
        rec._gc_pause(30, 30 + 10**7, None, "", {"generation": 2}, False)
    got = rec.phases()
    assert [p.name for p in got] == ["gc", "tick"]
    assert got[0].attrs == {"generation": 2, "collected": 9}
    assert got[0].parent == "tick" and got[0].seq == 1
    pauses = rec.gc_pauses()
    assert pauses["collections"] == 3
    assert pauses["longest_ms"] == 10.0
    assert pauses["total_ms"] == pytest.approx(15.1)
    assert rec.phase_snapshot()["gc"]["count"] == 1


def _sleeps_in_a_phase(opened, release):
    with tracing.phase("tick", seq=41):
        with tracing.phase("tick.fetch"):
            opened.set()
            release.wait(20)


def test_an_open_phase_is_seen_and_sampled_from_another_thread(
        monkeypatch, warnings_logged):
    from elasticdl_tpu.observability.phase_watch import PhaseWatcher

    rec = SpanRecorder()
    monkeypatch.setattr(tracing, "_RECORDER", rec)
    with tracing.phase("tick.fetch"):
        pass  # a median to be slow against
    opened, release = threading.Event(), threading.Event()
    worker = threading.Thread(target=_sleeps_in_a_phase, name="scheduler-x",
                              args=(opened, release))
    worker.start()
    try:
        assert opened.wait(10)
        seen = tracing.open_phases()["scheduler-x"]
        assert [(name, seq) for name, seq, _start in seen] == [
            ("tick", 41), ("tick.fetch", 41)]
        assert abs(seen[1][2] - time.time_ns()) < 5 * 10**9
        assert "scheduler-x" not in {
            p.parent for p in rec.phases()}  # nothing has ended
        watcher = PhaseWatcher()
        assert watcher.wake() is None  # not yet slow
        time.sleep(0.3)
        sample = watcher.wake()
        assert sample.name == "watch.sample" and sample.parent == "tick.fetch"
        assert sample.seq == 41 and sample.attrs["thread"] == "scheduler-x"
        assert sample.attrs["open_ms"] >= 300
        assert len(sample.attrs["frames"]) <= 10
        assert any("_sleeps_in_a_phase" in f for f in sample.attrs["frames"])
        assert sample.attrs["frames"][0].startswith("threading.py:")
        assert rec.watch_samples() == [sample]
        # at most one of a phase until it has been open twice as long
        assert watcher.wake() is None
        assert not [p for p in rec.phases() if p.name == "watch.sample"]
    finally:
        release.set()
        worker.join(20)
    assert not worker.is_alive()
    assert "scheduler-x" not in tracing.open_phases()
    [kept] = rec.slow_phases()
    assert kept["phase"].name == "tick.fetch" and kept["samples"] == [sample]
    assert rec.watch_samples() == []
    assert "_sleeps_in_a_phase" in warnings_logged[0].split("; at ")[1]


def test_watch_late_is_stamped_when_the_watcher_itself_wakes_late(
        monkeypatch):
    from elasticdl_tpu.observability.phase_watch import PhaseWatcher

    rec = SpanRecorder()
    monkeypatch.setattr(tracing, "_RECORDER", rec)
    now = [100.0]
    watcher = PhaseWatcher(clock=lambda: now[0])
    waited = threading.Event()
    waited.set()  # the wait returns at once; the clock is stepped by hand
    watcher.sleep(waited, 0.25)
    now[0] += 0.30  # 50 ms over: a busy machine, not a standstill
    watcher.wake()
    assert rec.phases() == []
    watcher.sleep(waited, 0.25)
    now[0] += 0.25 + 1.5
    watcher.wake()
    [late] = rec.phases()
    assert late.name == "watch.late"
    assert late.end_ns - late.start_ns == pytest.approx(1.5e9, rel=1e-6)
    assert 0 <= late.attrs["cpu_ms"] < 1000  # the process's, meanwhile
    assert abs(late.end_ns - time.time_ns()) < 5 * 10**9
    watcher.wake()  # no wait before it: nothing to be late for
    assert len(rec.phases()) == 1


def test_local_executor_train_runs_a_watcher_for_as_long_as_it_trains(
        tmp_path, monkeypatch):
    from elasticdl_tpu.api.local_executor import LocalExecutor
    from elasticdl_tpu.common.model_utils import get_model_spec
    from elasticdl_tpu.data import recordio_gen
    from elasticdl_tpu.observability import phase_watch

    train_dir = str(tmp_path / "train")
    recordio_gen.gen_mnist_like(train_dir, num_files=1, records_per_file=32)
    executor = LocalExecutor(
        get_model_spec(
            "model_zoo",
            "mnist_functional_api.mnist_functional_api.custom_model"),
        training_data=train_dir, minibatch_size=16, num_epochs=1,
        records_per_task=32,
    )
    watching = []
    step = executor.trainer.train_step

    def stepped(*args, **kwargs):
        watching.append([t.name for t in threading.enumerate()
                         if t.name == "phase-watch" and t.is_alive()])
        return step(*args, **kwargs)

    executor.trainer.train_step = stepped
    said = []
    monkeypatch.setattr("elasticdl_tpu.api.local_executor.logger.info",
                        lambda msg, *args: said.append(msg % args))
    assert "phase-watch" not in [t.name for t in threading.enumerate()]
    executor.run()
    assert watching == [["phase-watch"]] * 2
    assert "phase-watch" not in [t.name for t in threading.enumerate()]
    [line] = [s for s in said if s.startswith("train phases: ")]
    snap = json.loads(line[len("train phases: "):].split("; gc ")[0])
    assert snap["train.step"]["count"] == 2
    assert {"p50_ms", "p99_ms"} <= set(snap["train.step"])
    # the first step compiled inside its phases: the trainer's counter
    assert tracing.recorder().counts()["compile.programs"] >= 1
    assert phase_watch.LATE_SECS == 0.1


# ------------------------------------------- a tiny paged server, for real


@pytest.fixture(scope="module")
def paged_server():
    import jax

    from elasticdl_tpu.common.model_utils import get_model_spec
    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.serving.server import (
        GenerationServer,
        ServingConfig,
    )
    from elasticdl_tpu.training.trainer import Trainer

    spec = get_model_spec("model_zoo",
                          "transformer_lm.transformer_lm.custom_model")
    mesh = mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = Trainer(
        spec, mesh=mesh,
        model_params="vocab_size=16; seq_len=32; embed_dim=32; "
                     "num_heads=2; num_layers=1",
    )
    dummy = np.zeros((1, 32), np.int32)
    state = trainer.init_state(({"tokens": dummy}, dummy))
    server = GenerationServer(
        trainer, state,
        ServingConfig(num_slots=3, kv_block_size=4,
                      kv_shared=False, idle_wait_secs=0.01,
                      handler_poll_secs=0.05),
    ).start(grpc_server=False)
    yield trainer, state, server
    server.stop()


def _serve(server, specs):
    from elasticdl_tpu.proto import elasticdl_pb2 as pb

    results = {}

    def call(i, prompt, new):
        r = server.raw_servicer.generate(
            pb.GenerateRequest(prompt=prompt, max_new_tokens=new))
        results[i] = list(r.tokens)

    threads = [threading.Thread(target=call, args=(i, p, n))
               for i, (p, n) in enumerate(specs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    return results


SPECS = [([1, 2, 3, 4, 5, 6], 5), ([7, 8, 9], 4), ([3, 1], 6),
         ([2, 4, 6, 8, 10, 12, 14, 1, 3], 3), ([5], 7)]


def test_tokens_equal_the_offline_oracle_with_the_spans_recording(
        paged_server):
    from elasticdl_tpu.api.generation import autoregressive_generate

    trainer, state, server = paged_server
    results = _serve(server, SPECS)
    assert len(results) == len(SPECS)
    for i, (prompt, new) in enumerate(SPECS):
        want = np.asarray(autoregressive_generate(
            trainer, state, np.asarray([prompt], np.int32), new,
            use_cache=True))[0]
        assert list(want) == results[i], (i, prompt)
    rec = tracing.recorder()
    assert rec.phases_dropped == 0
    ticks = {}
    for p in rec.phases():
        if p.parent == "tick":
            ticks.setdefault(p.seq, set()).add(p.name)
    decode = [names for names in ticks.values() if "tick.dispatch" in names]
    assert decode and all(
        {"tick.admit", "tick.ensure", "tick.upload", "tick.dispatch",
         "tick.fetch", "tick.commit", "tick.stream"} <= names
        for names in decode)
    prefills = [p for p in rec.phases() if p.name == "prefill"]
    assert len(prefills) == len(SPECS)
    assert all(p.parent == "tick.admit" and p.trace_id for p in prefills)
    assert sorted(p.attrs["prompt_tokens"] for p in prefills) == sorted(
        len(prompt) for prompt, _ in SPECS)
    writes = [p for p in rec.phases() if p.name == "prompt_write"]
    assert {p.parent for p in writes} == {"prefill"}
    counts = rec.counts()
    assert counts["prompts_prefilled"] == len(SPECS)
    assert counts["prompt_write.tokens"] == sum(len(p) for p, _ in SPECS)
    assert counts["prompt_write.launches"] == sum(
        -(-len(p) // 4) for p, _ in SPECS)  # one per 4-token block
    assert sum(p.attrs["blocks"] for p in writes) == counts[
        "prompt_write.launches"]
    # the engine compiled one step program: there is no split twin
    assert not hasattr(server.engine, "_step_fns_split")
    assert not hasattr(server.engine, "profiler")


def test_a_decode_tick_keeps_its_six_phases_and_counts_what_it_sends(
        paged_server):
    """The tick's lane state lives on the device and one step stays in
    flight: every decode tick keeps its six phases under the root, in
    the order launch (`tick.ensure`, `tick.upload`, `tick.dispatch`)
    then collect (`tick.fetch`, `tick.commit`) then `tick.stream`, so
    the dispatch ends before the fetch of the OLDER step starts.
    `tick.upload` counts inside itself what the launch sends
    (`tick.transfers`, 0 or 1) and `tick.dispatch` whether it ran
    ahead of unfetched tokens (`tick.ahead`). Only the tick that
    starts with nothing in flight launches twice: 0, then 1."""
    _trainer, _state, server = paged_server
    _serve(server, [([1, 2, 3], 14)])
    first = max(p.seq for p in tracing.recorder().phases()
                if p.name == "tick")
    _serve(server, [([1, 2, 3, 4, 5], 14), ([6, 7], 3)])
    ticks = {}
    for p in tracing.recorder().phases():
        if p.seq is not None and p.seq > first and (
                p.parent in ("tick", "tick.upload")
                or p.name == "tick.ahead"):
            ticks.setdefault(p.seq, []).append(p)
    decode = [sorted(t, key=lambda p: p.start_ns) for t in ticks.values()
              if any(p.name == "tick.dispatch" for p in t)]
    assert len(decode) >= 12
    launch = ["tick.ensure", "tick.upload", "tick.dispatch"]
    collect = ["tick.fetch", "tick.commit", "tick.stream"]
    sent, twice = [], 0
    for tick in decode:
        spans = [p for p in tick if p.parent == "tick"
                 and p.name != "tick.admit"]
        ahead = [p.attrs["n"] for p in tick if p.name == "tick.ahead"]
        if len(spans) == 9:  # nothing was in flight
            assert [p.name for p in spans] == launch * 2 + collect
            assert ahead == [0, 1]
            twice += 1
        else:
            assert [p.name for p in spans] == launch + collect
            assert ahead == [1]
        assert {p.parent for p in tick if p.name == "tick.ahead"} == {
            "tick.dispatch"}
        by_name = {p.name: p for p in spans}  # a tick's last launch
        assert (by_name["tick.dispatch"].end_ns
                <= by_name["tick.fetch"].start_ns)
        counts = [p for p in tick if p.parent == "tick.upload"]
        assert [p.name for p in counts] == ["tick.transfers"] * len(ahead)
        uploads = [p for p in spans if p.name == "tick.upload"]
        assert all(u.start_ns <= p.start_ns <= u.end_ns
                   for u, p in zip(uploads, counts))
        sent += [p.attrs["n"] for p in counts]
    # seatings, grown blocks and releases send once; the other
    # launches, most of them, send nothing; a run starts with nothing
    # in flight, and so may the tick after a lone lane's last launch
    assert set(sent) == {0, 1} and sent.count(0) > sent.count(1)
    assert 1 <= twice <= 3
    # the last tokens of a run come out of ticks that launch nothing:
    # they fetch, commit and stream all the same
    tails = [t for t in ticks.values()
             if not any(p.name == "tick.dispatch" for p in t)
             and any(p.name == "tick.fetch" for p in t)]
    assert tails and all(
        [p.name for p in sorted(t, key=lambda p: p.start_ns)
         if p.parent == "tick" and p.name != "tick.admit"] == collect
        for t in tails)


@pytest.mark.parametrize("window,want", [(0, 4), (6, 3)],
                         ids=("full", "window6"))
def test_a_decode_tick_counts_the_blocks_its_lanes_have_in_reach(
        window, want):
    """One tick of a 3-lane engine over 4-token blocks (table width
    8) with lanes at positions 9, 3 and free: the paged kernel's live
    range is [0, 3) / [0, 1) / empty, and a window of 6 moves the
    first to [1, 3)."""
    import jax

    from elasticdl_tpu.common.model_utils import get_model_spec
    from elasticdl_tpu.ops.attention import paged_live_blocks
    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.serving.admission import ServingRequest
    from elasticdl_tpu.serving.engine import (
        PagedContinuousBatchingEngine,
    )
    from elasticdl_tpu.training.trainer import Trainer

    spec = get_model_spec("model_zoo",
                          "transformer_lm.transformer_lm.custom_model")
    trainer = Trainer(
        spec, mesh=mesh_lib.build_mesh({"dp": 1},
                                       devices=jax.devices()[:1]),
        model_params="vocab_size=16; seq_len=32; embed_dim=32; "
                     "num_heads=2; num_layers=2; attn_window=%d" % window,
    )
    dummy = np.zeros((1, 32), np.int32)
    state = trainer.init_state(({"tokens": dummy}, dummy))
    eng = PagedContinuousBatchingEngine(
        trainer, state, num_slots=3, block_size=4, share_prefix=False)
    eng.insert(ServingRequest(list(range(1, 10)), 4))
    eng.insert(ServingRequest([3, 1, 2], 4))
    assert list(eng._positions) == [9, 3, 0]
    tracing.recorder().clear_phases()
    assert len(eng.step()) == 2
    lo, hi = paged_live_blocks(np.array([9, 3, 0]), window or None, 4, 8,
                               xp=np)
    assert int((hi - lo).sum()) == want
    counts = tracing.recorder().counts()
    entries = {p.name: p for p in tracing.recorder().phases()
               if p.name.startswith("paged.")}
    assert entries["paged.blocks_streamed"].attrs == {"n": want}
    assert entries["paged.table_slots"].attrs == {"n": 3 * 8}
    assert {p.parent for p in entries.values()} == {"tick.ensure"}
    # once a tick whatever the depth, and cumulative like the others
    before = counts["paged.table_slots"]
    eng.step()  # positions 10, 4, 0: lane 1 now reaches its second block
    counts = tracing.recorder().counts()
    assert counts["paged.table_slots"] - before == 3 * 8
    with pytest.raises(ValueError, match="unknown counter"):
        tracing.count("paged.blocks")


def test_the_queued_stream_share_file_reads_the_two_counters(monkeypatch):
    """chipbench/layers/paged.stream_share.json (a file with no
    BENCHMARK.json entry yet): blocks streamed over table slots, from
    the ring, inside the window; nothing where the program counts
    neither (the parent commit)."""
    import importlib
    import os

    from chipbench import run as cb_run

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "layers",
                           "paged.stream_share.json")) as f:
        spec = json.load(f)
    module, _, fn = spec["reader"].rpartition(":")
    read = getattr(importlib.import_module(module), fn)
    monkeypatch.setattr(cb_run, "_T0", time.time() - 1.0, raising=False)
    measured = {"counters": {"setup_s": 0.5}, "window_s": 60.0}
    with tracing.phase("tick", seq=1):
        pass
    assert read(measured, **spec["args"]) is None
    for streamed in (3, 5):
        tracing.count("paged.blocks_streamed", streamed)
        tracing.count("paged.table_slots", 24)
    assert read(measured, **spec["args"]) == 8 / 48


def test_metrics_exposition_still_carries_the_phase_family(paged_server):
    _trainer, _state, server = paged_server
    _serve(server, SPECS[:2])
    fams = parse_prometheus_text(
        render_prometheus(server._metrics_families()))
    phases = {lab["phase"] for _n, lab, _v in
              fams["edl_serving_phase_ms"]["samples"]}
    assert {"tick", "tick.upload", "tick.dispatch", "tick.fetch",
            "tick.commit", "tick.stream", "prefill",
            "prompt_write"} <= phases
    assert phases <= set(tracing.PHASES)
    dropped = fams["edl_serving_phase_ring_dropped"]["samples"]
    assert [v for _n, _lab, v in dropped] == [0]
    work = {lab["counter"]: v for _n, lab, v in
            fams["edl_serving_work_total"]["samples"]}
    assert set(work) == set(tracing.COUNTERS)
    assert work["pool.inplace_launches"] == work["pool.launches"] > 0
    assert "edl_serving_ttft_ms" in fams


# ---------------------------------------------------------- the train loop


def test_local_executor_train_yields_one_train_step_per_step(tmp_path):
    from elasticdl_tpu.api.local_executor import LocalExecutor
    from elasticdl_tpu.common.model_utils import get_model_spec
    from elasticdl_tpu.data import recordio_gen

    train_dir = str(tmp_path / "train")
    recordio_gen.gen_mnist_like(train_dir, num_files=1, records_per_file=48)
    executor = LocalExecutor(
        get_model_spec(
            "model_zoo",
            "mnist_functional_api.mnist_functional_api.custom_model"),
        training_data=train_dir, minibatch_size=16, num_epochs=1,
        records_per_task=32,
    )
    state, _ = executor.run()
    steps = int(state.step)
    assert steps == 3 and len(executor.losses) == 3
    rec = tracing.recorder()
    by_name = {}
    for p in rec.phases():
        by_name.setdefault(p.name, []).append(p)
    assert [p.seq for p in by_name["train.step"]] == [0, 1, 2]
    assert [p.seq for p in by_name["train.loss_fetch"]] == [0, 1, 2]
    assert [p.seq for p in by_name["train.pad"]] == [0, 1, 2]
    # two tasks (32 + 16 records): each ends with the wait that found
    # its iterator empty, and is fetched and reported once; the third
    # task_get finds the queue empty
    assert len(by_name["train.next_batch"]) == 3 + 2
    assert len(by_name["train.task_report"]) == 2
    assert len(by_name["train.task_get"]) == 3
    for p in by_name["trainer.dispatch"] + by_name["trainer.host_prepare"]:
        assert p.parent == "train.step"
    assert [p.seq for p in by_name["trainer.dispatch"]] == [0, 1, 2]
    assert "trainer.post_tiers" not in by_name  # a dense model has none
    # the loop's phases do not overlap: they tile the thread's time
    loop = sorted((p for p in rec.phases()
                   if p.parent == "" and p.name.startswith("train.")),
                  key=lambda p: p.start_ns)
    for a, b in zip(loop, loop[1:]):
        assert a.end_ns <= b.start_ns


def test_a_stopped_server_says_what_its_phases_cost(paged_server,
                                                   monkeypatch):
    """`GenerationServer.stop()` logs the cumulative snapshot in one
    line (stop is safe to call twice; the fixture calls it again),
    and /metrics counts slow phases by name."""
    _trainer, _state, server = paged_server
    _serve(server, SPECS[:1])
    ph = tracing.begin("tick.commit", seq=10**6)
    ph.start_ns -= 10**9
    tracing.end(ph)
    fams = parse_prometheus_text(
        render_prometheus(server._metrics_families()))
    slow = {lab["phase"]: v for _n, lab, v in
            fams["edl_serving_slow_phases_total"]["samples"]}
    assert slow["tick.commit"] == 1
    said = []
    monkeypatch.setattr("elasticdl_tpu.serving.server.logger.info",
                        lambda msg, *args: said.append(msg % args))
    server.stop()
    [line] = [s for s in said if s.startswith("serving phases: ")]
    snap = json.loads(line[len("serving phases: "):].split("; gc ")[0])
    assert snap["tick.dispatch"]["count"] >= 4
    assert snap["tick"]["p99_ms"] >= snap["tick"]["p50_ms"]
    assert json.loads(line.split("; gc ")[1])["collections"] >= 0
