"""The readers of the program's phase spans (chipbench/span_readers.py)
on rings made by hand — the window cut, which ticks count, where a hold
is — and both cells under --rehearsal --trace 1 reporting every metric
that reads the ring."""

import pytest

from cbhelp import ROOT, last_json, run_cell
from chipbench import run as cb_run
from chipbench import span_readers as sr
from chipbench.manifest import Manifest
from elasticdl_tpu.observability.tracing import Phase

M = Manifest(ROOT)
T0, SETUP_S, CHECK_S, WINDOW_S = 1000.0, 10.0, 2.0, 5.0
OPEN = int((T0 + SETUP_S + CHECK_S) * 1e9)  # the window: 1012 s .. 1017 s
MS = 10**6


def _measured():
    return {"counters": {"setup_s": SETUP_S}, "check_s": CHECK_S,
            "window_s": WINDOW_S}


@pytest.fixture
def ring(monkeypatch):
    """Hand a reader this list of phases for the program's ring."""
    monkeypatch.setattr(cb_run, "_T0", T0)
    phases = []
    monkeypatch.setattr(sr, "_ring", lambda: phases)
    return phases


def _tick(seq, start_ms, active, upload=1, dispatch=2, fetch=90, commit=1,
          stream=3, ensure=1, admit=0, decode=True):
    """One tick's phases, laid end to end from `start_ms` after the
    window opens; `admit` ms of seating come first."""
    out, t = [], OPEN + start_ms * MS
    root_start = t

    def child(name, ms):
        nonlocal t
        out.append(Phase(name, t, t + ms * MS, seq, "tick", "", {}))
        t += ms * MS

    child("tick.admit", admit)
    if decode:
        for name, ms in (("tick.ensure", ensure), ("tick.upload", upload),
                         ("tick.dispatch", dispatch), ("tick.fetch", fetch),
                         ("tick.commit", commit), ("tick.stream", stream)):
            child(name, ms)
    else:
        child("idle", 50)
    out.append(Phase("tick", root_start, t, seq, "", "",
                     {"active": active, "queue_depth": 0}))
    return out


def test_the_window_is_cut_from_the_harness_clock_exactly_to_a_tick(ring):
    assert sr._window_ns(_measured()) == (OPEN, OPEN + int(WINDOW_S * 1e9))
    # a tick that starts 1 ms before the window opens, three inside,
    # one that starts 1 ms before it closes (it counts: it started
    # inside) and one that starts as it closes (it does not)
    starts = [-1, 0, 100, 200, 4999, 5000]
    for seq, start in enumerate(starts):
        ring.extend(_tick(seq, start, active=2, upload=seq + 1))
    ticks, window = sr._window_ticks(_measured())
    assert window == (OPEN, OPEN + int(WINDOW_S * 1e9))
    assert [t["seq"] for t in ticks] == [1, 2, 3, 4]
    # the last one's phases run past the close, and stay with it
    assert ticks[-1]["tick.stream"][1] > window[1]
    # uploads of 2, 3, 4, 5 ms: the median is exact, not a bucket's
    assert sr.tick_phase_ms(_measured(), ["tick.upload"]) == 3.5
    assert sr.tick_phase_ms(_measured(), ["tick.upload"], q=100) == 5.0


def test_tick_host_ms_sums_the_host_phases_of_decode_ticks_only(ring):
    ring.extend(_tick(0, 0, active=1))                  # 1+1+2+1+3 = 8 ms
    ring.extend(_tick(1, 200, active=0, decode=False))  # idle: no decode
    ring.extend(_tick(2, 400, active=1, upload=5))      # 12 ms
    ring.extend(_tick(3, 600, active=1, stream=10))     # 15 ms
    host = ["tick.ensure", "tick.upload", "tick.dispatch", "tick.commit",
            "tick.stream"]
    assert sr.tick_phase_ms(_measured(), host) == 12.0
    assert sr.tick_phase_ms(_measured(), ["tick.fetch"]) == 90.0


def test_holds_are_only_between_ticks_that_left_slots_seated(ring):
    # a tick is 98 ms of phases; the next starts at a round 100 ms:
    # 2 ms between one's stream and the next one's ensure, plus the
    # next one's seating
    ring.extend(_tick(0, 0, active=2))
    ring.extend(_tick(1, 100, active=2, admit=40))   # hold 2 + 40 ms
    ring.extend(_tick(2, 240, active=0))             # hold 2 ms; then empty
    ring.extend(_tick(3, 2000, active=1, admit=30))  # nothing was seated
    ring.extend(_tick(4, 2130, active=1))            # hold 2 ms
    ticks, _ = sr._window_ticks(_measured())
    assert [h / MS for h in sr.holds_ns(ticks)] == [42.0, 2.0, 2.0]
    assert sr.hold_share(_measured()) == pytest.approx(100 * 0.046 / 5.0)
    assert sr.hold_ms(_measured(), q=100) == 42.0
    assert sr.hold_ms(_measured(), q=50) == 2.0


def test_counts_are_cut_to_the_window_before_they_are_divided(ring):
    def count(name, at_ms, n):
        t = OPEN + at_ms * MS
        ring.append(Phase(name, t, t, 0, "prefill", "", {"n": n}))

    count("prompt_write.launches", -5, 100)  # before the window
    count("prompts_prefilled", -5, 1)
    count("prompt_write.launches", 10, 27)
    count("prompts_prefilled", 10, 1)
    count("prompt_write.launches", 900, 5)
    count("prompts_prefilled", 900, 1)
    count("prompt_write.launches", 6000, 64)  # after it
    count("prompts_prefilled", 6000, 1)
    assert sr.count_ratio(_measured(), "prompt_write.launches",
                          "prompts_prefilled") == 16.0
    assert sr.count_ratio(_measured(), "prompt_write.tokens",
                          "prompt_write.launches") == 0.0
    assert sr.count_ratio(_measured(), "prompts_prefilled",
                          "prompt_write.tokens") is None


def test_train_loop_time_is_what_lies_between_steps(ring, capsys):
    t = OPEN - 150 * MS
    for seq, (wait, step) in enumerate([(1, 288), (1, 288), (2, 288),
                                        (40, 288), (3, 288)]):
        ring.append(Phase("train.next_batch", t, t + wait * MS, seq, "", "",
                          {}))
        t += wait * MS
        ring.append(Phase("train.step", t, t + step * MS, seq, "", "", {}))
        ring.append(Phase("trainer.dispatch", t, t + step * MS, seq,
                          "train.step", "", {}))
        t += step * MS
    # the first step began before the window; between the four steps
    # inside it lie 2, 40 and 3 ms
    assert sr.between_ms(_measured(), "train.step") == 3.0
    assert sr.longest_ms(_measured(), "train.", "train.step") == 40.0
    assert "longest phase: train.next_batch seq 3, 40.000 ms" in (
        capsys.readouterr().out)


@pytest.mark.parametrize("name", [
    m["name"] for m in M.bench["per_layer"]
    if M.metric_spec("layers", m["name"])["reader"].startswith(
        "chipbench.span_readers:")])
def test_a_reader_finds_nothing_on_an_empty_ring_or_without_one(
        name, ring, monkeypatch):
    spec = M.metric_spec("layers", name)
    read = getattr(sr, spec["reader"].rpartition(":")[2])
    assert read(_measured(), **spec.get("args", {})) is None  # empty
    # only phases of another loop, or before the window
    ring.extend(_tick(0, -500, active=1))
    ring.append(Phase("idle", OPEN + MS, OPEN + 2 * MS, None, "", "", {}))
    assert read(_measured(), **spec.get("args", {})) is None
    # a program from before the phase ring
    monkeypatch.setattr(sr, "_ring", lambda: None)
    assert read(_measured(), **spec.get("args", {})) is None
    # no clock of process start
    monkeypatch.undo()
    monkeypatch.setattr(sr, "_ring", lambda: _tick(0, 0, active=1))
    monkeypatch.delattr(cb_run, "_T0")
    assert read(_measured(), **spec.get("args", {})) is None


def test_a_program_without_the_ring_reads_as_nothing(monkeypatch):
    from elasticdl_tpu.observability import tracing

    class Old(object):
        """The recorder of a checkout from before the phase ring."""

    monkeypatch.setattr(tracing, "_RECORDER", Old())
    assert sr._ring() is None


@pytest.mark.parametrize("cell", ["train-4k", "serve-gen-steady"])
def test_both_cells_report_every_span_metric_under_rehearsal(cell):
    rc, lines, err = run_cell(cell, 2**31 + 24, trace=1)
    assert rc == 0, err[-2000:]
    got = last_json(lines)["metrics"]
    mine = [m for m in M.metrics_of("per_layer", cell)
            if m["source"] in ("program_span", "program_counter")
            and M.metric_spec("layers", m["name"])["reader"].startswith(
                "chipbench.span_readers:")]
    assert len(mine) == {"train-4k": 2, "serve-gen-steady": 6}[cell]
    for m in mine:
        assert m["name"] in got, (m["name"], sorted(got))
        assert got[m["name"]]["unit"] == m["unit"]
        assert got[m["name"]]["value"] >= 0
    if cell == "train-4k":
        assert any(line.startswith("longest phase: train.") for line in lines)
    else:
        # what the spans see of a tick fits inside what the harness
        # times around engine.step()
        assert (got["tick.upload_ms"]["value"]
                <= got["tick.host_ms"]["value"])
