"""The readers of what the program keeps about its stalls
(chipbench/stall_readers.py) on rings and retained tiers made by hand:
how much of the window the ring holds, the worst slow phase inside the
window and what covered it, collections and compiles as phases, the
mean of a once-a-launch counter — and the expert roofline's counts,
which are the traced launches' own once the ring holds the window."""

import pytest

from cbhelp import ROOT
from chipbench import run as cb_run
from chipbench import smallthinker_cost
from chipbench import span_readers as sr
from chipbench import stall_readers as st
from chipbench.manifest import Manifest
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.observability.tracing import Phase

M = Manifest(ROOT)
T0, SETUP_S, WINDOW_S = 1000.0, 12.0, 5.0
OPEN = int((T0 + SETUP_S) * 1e9)  # the window: 1012 s .. 1017 s
MS = 10**6
SERVE = M.metric_spec("layers", "stall.worst_ms.serve")["args"]["prefixes"]
TRAIN = M.metric_spec("layers", "stall.worst_ms.train")["args"]["prefixes"]


def _measured():
    return {"counters": {"setup_s": SETUP_S}, "window_s": WINDOW_S}


class _Tier(object):
    """A recorder that has the retained tier, with these records."""

    def __init__(self, records):
        self.records = records

    def slow_phases(self):
        return list(self.records)


@pytest.fixture
def ring(monkeypatch):
    """Hand the readers this list for the program's ring, and a
    recorder whose retained tier is `ring.slow`."""
    monkeypatch.setattr(cb_run, "_T0", T0)
    phases = []
    monkeypatch.setattr(sr, "_ring", lambda: phases)
    tier = _Tier([])
    monkeypatch.setattr(tracing, "_RECORDER", tier)
    return phases, tier.records


def _phase(name, start_ms, ms, parent="", seq=1, **attrs):
    t = OPEN + int(start_ms * MS)
    return Phase(name, t, t + int(ms * MS), seq, parent, "", attrs)


def _slow(name, start_ms, ms, beneath=()):
    ph = _phase(name, start_ms, ms)
    return {"phase": ph, "beneath": list(beneath), "samples": [],
            "line": "slow phase %s seq 1: %d ms" % (name, ms)}


def test_coverage_is_the_share_of_the_window_behind_the_oldest_entry(ring):
    phases, _ = ring
    assert st.window_coverage(_measured()) is None  # an empty ring
    # the ring still holds what was sealed before the window opened
    phases.append(_phase("tick", -3000, 5))
    phases.append(_phase("tick", 4000, 5))
    assert st.window_coverage(_measured()) == 1.0
    # the oldest entry it holds ended 3 s into a window of 5
    del phases[0]
    phases.insert(0, _phase("tick", 2995, 5))
    assert st.window_coverage(_measured()) == pytest.approx(0.4)
    # and one that holds nothing of the window
    phases[:] = [_phase("tick", 6000, 5)]
    assert st.window_coverage(_measured()) == 0.0


def test_the_worst_slow_phase_is_one_that_starts_inside_the_window(
        ring, capsys):
    phases, slow = ring
    phases.append(_phase("tick", 10, 5))
    assert st.worst_slow_ms(_measured(), SERVE) == 0.0  # none was slow
    slow.append(_slow("prefill", -9000, 8000))     # warm-up's compile
    slow.append(_slow("tick.fetch", 1000, 700))
    slow.append(_slow("tick.fetch", 2000, 2070))
    slow.append(_slow("train.step", 2500, 3000))   # another loop's
    slow.append(_slow("tick.commit", 5001, 4000))  # after the close
    assert st.worst_slow_ms(_measured(), SERVE) == 2070.0
    assert "worst of 2: slow phase tick.fetch seq 1: 2070 ms" in (
        capsys.readouterr().out)
    assert st.worst_slow_ms(_measured(), TRAIN) == 3000.0
    slow[:] = slow[:1]
    assert st.worst_slow_ms(_measured(), SERVE) == 0.0
    assert st.worst_slow_ms(_measured(), TRAIN) == 0.0


def test_beneath_share_is_the_union_of_the_causes_inside_the_slow_time(
        ring):
    phases, slow = ring
    phases.append(_phase("tick", 10, 5))
    assert st.beneath_share(_measured(), SERVE) == 100.0  # none was slow
    # 1000 ms: a collection of 100, a compile of 300 that holds a
    # second collection of 50 (counted once), the watcher 200 late
    slow.append(_slow("tick.dispatch", 100, 1000, beneath=[
        _phase("gc", 150, 100, "tick.dispatch"),
        _phase("compile", 400, 300, "tick.dispatch", backend=1),
        _phase("gc", 500, 50, "tick.dispatch"),
        _phase("compile.programs", 400, 0, "tick.dispatch", n=1),
        _phase("watch.late", 800, 200),
        _phase("tick.ahead", 900, 0, "tick.dispatch", n=1)]))
    assert st.beneath_share(_measured(), SERVE) == pytest.approx(60.0)
    # a second one with nothing beneath it: the device's, or the runtime's
    slow.append(_slow("tick.fetch", 2000, 2000))
    assert st.beneath_share(_measured(), SERVE) == pytest.approx(20.0)
    assert st.beneath_share(_measured(), TRAIN) == 100.0
    # one before the window is not the window's
    slow[:] = [_slow("tick.fetch", -2500, 2000)]
    assert st.beneath_share(_measured(), SERVE) == 100.0


def test_collections_inside_the_window_and_compiles_before_it(ring):
    phases, _ = ring
    phases.append(_phase("compile", -9000, 4000, backend=0))
    phases.append(_phase("compile", -5000, 2500, "prefill", backend=1))
    phases.append(_phase("gc", -100, 40))
    phases.append(_phase("gc", 1000, 30, "tick.commit"))
    phases.append(_phase("gc", 3000, 20, "idle"))
    phases.append(_phase("compile", 4000, 800, "tick.dispatch"))
    phases.append(_phase("gc", 5500, 60))
    assert st.phase_share(_measured(), "gc") == pytest.approx(1.0)  # %
    assert st.before_window_s(_measured(), "compile") == pytest.approx(6.5)
    # a ring with neither: no collection took a millisecond, nothing
    # compiled before the window as far as the ring goes back
    phases[:] = [_phase("tick", 10, 5)]
    assert st.phase_share(_measured(), "gc") == 0.0
    assert st.before_window_s(_measured(), "compile") == 0.0


def test_the_mean_of_a_counter_written_once_a_launch(ring):
    phases, _ = ring
    phases.append(_phase("tick", 10, 5))
    assert st.mean_count(_measured(), "tick.ahead") is None
    phases.append(_phase("tick.ahead", -10, 0, "tick.dispatch", n=0))
    for i, n in enumerate([0, 1, 1, 1]):
        phases.append(_phase("tick.ahead", 20 + i, 0, "tick.dispatch", n=n))
        phases.append(_phase("tick.transfers", 20 + i, 0, "tick.upload",
                             n=int(i == 0)))
    assert st.mean_count(_measured(), "tick.ahead") == 0.75
    assert st.mean_count(_measured(), "tick.transfers") == 0.25


NEW = [m["name"] for m in M.bench["per_layer"]
       if M.metric_spec("layers", m["name"])["reader"].startswith(
           "chipbench.stall_readers:")]


def test_the_entries_this_reader_file_serves():
    assert sorted(NEW) == [
        "gc.pause_share.serve", "gc.pause_share.train",
        "ring.window_coverage", "setup.compile_s",
        "stall.beneath_share.serve", "stall.beneath_share.train",
        "stall.worst_ms.serve", "stall.worst_ms.train",
        "tick.ahead_share", "tick.send_share"]
    by_name = {m["name"]: m for m in M.bench["per_layer"]}
    assert "workloads" not in by_name["setup.compile_s"]
    assert by_name["stall.worst_ms.train"]["workloads"] == ["train-4k"]
    assert len(by_name["ring.window_coverage"]["workloads"]) == 3


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_on_an_empty_ring_or_an_older_program(
        name, ring, monkeypatch):
    phases, _ = ring
    spec = M.metric_spec("layers", name)
    read = getattr(st, spec["reader"].rpartition(":")[2])
    args = spec.get("args", {})
    assert read(_measured(), **args) is None  # an empty ring
    # a program from before the retained tier, the `gc` and `compile`
    # phases (the parent commit): its ring still answers the readers
    # that only need a ring
    phases.append(_phase("tick", -100, 5))
    phases.append(_phase("tick.ahead", 11, 0, "tick.dispatch", n=1))
    phases.append(_phase("tick.transfers", 11, 0, "tick.upload", n=0))
    monkeypatch.setattr(tracing, "_RECORDER", object())
    monkeypatch.setattr(tracing, "PHASES", tuple(
        p for p in tracing.PHASES if p not in ("gc", "compile")))
    want = {"ring.window_coverage": 1.0, "tick.ahead_share": 1.0,
            "tick.send_share": 0.0}
    assert read(_measured(), **args) == want.get(name)
    # no ring at all, and no clock of process start
    monkeypatch.setattr(sr, "_ring", lambda: None)
    assert read(_measured(), **args) is None
    monkeypatch.setattr(sr, "_ring", lambda: phases)
    monkeypatch.delattr(cb_run, "_T0")
    assert read(_measured(), **args) is None


def test_traced_tick_counts_are_the_traced_launches_own(ring):
    """The trace covers the window's first seconds. A ring that holds
    the whole window gives `traced_tick_counts` those launches' own
    entries; the ring of 65,536 had dropped them by the time the
    readers ran, and the first N it still held were from the window's
    second half."""
    phases, _ = ring
    for tick in range(10):  # hits fall as the lanes drain
        phases.append(_phase("moe.experts_hit", 100 * tick, 0, "tick.commit",
                             seq=tick, n=30 - 2 * tick))
        phases.append(_phase("moe.pairs_held", 100 * tick, 0, "tick.commit",
                             seq=tick, n=50))
    m = dict(_measured(), trace={
        "planes": 1, "ops": {}, "programs": {"jit_step": [0.02, 4]}})
    names = ("moe.experts_hit", "moe.pairs_held")
    assert smallthinker_cost.traced_tick_counts(m, names, "^jit_step$") == {
        "moe.experts_hit": 30 + 28 + 26 + 24, "moe.pairs_held": 200}
    assert st.window_coverage(m) == 1.0
    # what a ring that kept only the last six ticks would have paired
    # with the same kernel time
    del phases[:8]
    assert smallthinker_cost.traced_tick_counts(m, names, "^jit_step$")[
        "moe.experts_hit"] == 22 + 20 + 18 + 16
    assert st.window_coverage(m) == pytest.approx(0.92)
