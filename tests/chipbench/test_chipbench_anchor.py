"""The serve cells' anchors: each open-loop cell offers a stated share
of a knee that its traffic file records, keeps its traced part inside
the window, and runs from its files; a file that was anchored again
names the tick of each sweep and keeps the earlier one whole; and the
spread of a set of runs as the driver takes it (`stats.run_spread`),
from which the bound of `itl_p50_ms` is set. The rules read what a
file holds and name no cell: a later cell brings its own file."""

import collections
import re

import pytest

from cbhelp import ROOT, last_json, run_cell
from chipbench import stats, traffic
from chipbench.manifest import Manifest

M = Manifest(ROOT)
NAMES = [w["name"] for w in M.bench["workloads"]
         if M.traffic(w["traffic"])["kind"] == "open_loop"]
# the two cells PR 41 anchored again: only what is about THEIR files
# and their `why` is held to these names
RE_ANCHORED = ["serve-st21b-mixed-len", "serve-nm3n-short-chat"]


def _mix(cell):
    return M.traffic(M.workload(cell)["traffic"])


def _recorded_sets():
    """(cell, index) of every set of runs that an open-loop cell's
    traffic file records under `knee.sets`; a file may record none."""
    return [(cell, i) for cell in NAMES
            for i in range(len(_mix(cell)["knee"].get("sets", ())))]


@pytest.mark.parametrize("cell", NAMES)
def test_rate_is_a_stated_share_of_a_knee_swept_at_a_stated_tick(cell):
    mix = _mix(cell)
    knee = mix["knee"]
    share = knee.get("share", 0.8)
    if share != 0.8:  # a cell off four fifths of its knee says why
        assert len(knee["why_not"]) > 40
    # the stated share of the knee, to the 0.1 req/s a rate is rounded to
    assert abs(mix["rate_per_s"] - share * knee["req_per_s"]) <= 0.05 + 1e-9
    # a cell that a latency judges stands under its knee (over it the
    # queue grows through the window, and only a rate completed can judge)
    by_latency = any(m["unit"] == "ms" and cell in m.get("workloads", NAMES)
                     for m in M.bench["end_to_end"])
    assert 0 < share and (share < 1 or not by_latency)
    # the traced part lies inside the window
    assert 0 < mix["trace_seconds"] <= M.bench["run_seconds"]
    assert knee["how"]
    if "tick_ms" in knee:  # a sweep that states its tick says so in words
        assert knee["tick_ms"] > 0 and "tick" in knee["how"]
        assert ("%g ms" % knee["tick_ms"]) in knee["how"]
    if "earlier" in knee:
        # anchored again: the earlier sweep is kept whole beside the new
        # one, each with the tick it was made at
        earlier = knee["earlier"]
        assert earlier["tick_ms"] > 0 and knee["tick_ms"] > 0
        assert earlier["rate_per_s"] == pytest.approx(
            earlier.get("share", 0.8) * earlier["req_per_s"], abs=0.05)
        assert earlier["how"] and "tick" in earlier["how"]


@pytest.mark.parametrize("cell", RE_ANCHORED)
def test_the_cells_why_states_the_rate_its_file_offers(cell):
    why = M.workload(cell)["why"]
    assert len(why) <= 200
    stated = re.search(r"([0-9.]+) req/s", why)
    assert stated, why
    assert float(stated.group(1)) == _mix(cell)["rate_per_s"]
    share = _mix(cell)["knee"].get("share", 0.8)
    assert ("%g of the knee" % share) in why


@pytest.mark.parametrize("cell", RE_ANCHORED)
def test_every_seed_deals_one_multiset_at_the_new_rate(cell):
    mix, seconds = _mix(cell), M.bench["run_seconds"]
    a = traffic.open_loop_schedule(mix, 2**31 + 41, seconds, 97)
    b = traffic.open_loop_schedule(mix, 41, seconds, 97)
    assert len(a) == len(b) == int(round(mix["rate_per_s"] * seconds))
    lens = lambda s, k: collections.Counter(
        len(r[k]) if k == "prompt" else r[k] for r in s)
    for key in ("prompt", "max_new_tokens"):
        assert lens(a, key) == lens(b, key)
    # a file that states `deal_seed` deals every seed the one order
    fixed = mix.get("deal_seed") is not None
    assert ([r["due_s"] for r in a] == [r["due_s"] for r in b]) == fixed
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert max(r["due_s"] for r in a) < seconds
    # the pool holds every lane at the mix's longest request
    server = M.config(M.workload(cell)["config"])["server"]
    longest = max(p for p, _ in mix["prompt_lens"]) + max(
        n for n, _ in mix["max_new_tokens"])
    per_slot = -(-longest // server["kv_block_size"])
    assert server["kv_num_blocks"] >= server["num_slots"] * per_slot


_SMALL = {"rate_per_s": 5.0, "arrivals": "poisson",
          "prompt_lens": [[72, .5], [136, .3], [264, .2]],
          "max_new_tokens": [[32, .5], [64, .5]]}


def _shape(schedule):
    """What a schedule asks of the server, without the tokens."""
    return [(r["due_s"], len(r["prompt"]), r["max_new_tokens"])
            for r in schedule]


@pytest.mark.parametrize("seed, sha1", [
    (1, "1601f71d94c1f46cda72a2831c10a02823a2c4ae"),
    (2**31 + 11, "64a841c77dde44f022afba353fcd146e5adb9965"),
])
def test_a_mix_without_deal_seed_is_dealt_as_it_always_was(seed, sha1):
    # the generator of PRs 23 to 40, to the last token: the cells whose
    # files state no `deal_seed` read what their ledger lines read
    import hashlib
    import json

    got = traffic.open_loop_schedule(_SMALL, seed, 20, 1000)
    assert hashlib.sha1(json.dumps(got).encode()).hexdigest() == sha1
    assert got == traffic.open_loop_schedule(
        dict(_SMALL, deal_seed=None), seed, 20, 1000)


@pytest.mark.parametrize("seed", [0, 7, 41, 2**31 + 41])
def test_a_deal_seed_fixes_the_order_and_leaves_the_tokens_to_the_seed(seed):
    mix = dict(_SMALL, deal_seed=7)
    got = traffic.open_loop_schedule(mix, seed, 20, 1000)
    # the order is the one that seed 7 deals a mix without the key
    assert _shape(got) == _shape(
        traffic.open_loop_schedule(_SMALL, 7, 20, 1000))
    assert got == traffic.open_loop_schedule(mix, seed, 20, 1000)
    other = traffic.open_loop_schedule(mix, seed + 1, 20, 1000)
    assert _shape(other) == _shape(got)
    assert [r["prompt"] for r in other] != [r["prompt"] for r in got]
    assert all(0 <= t < 1000 for r in got for t in r["prompt"])
    # another deal seed, another order of the same multiset
    moved = traffic.open_loop_schedule(dict(mix, deal_seed=8), seed, 20, 1000)
    assert _shape(moved) != _shape(got)
    for k in (1, 2):  # prompt lengths, answer lengths
        assert sorted(s[k] for s in _shape(moved)) == sorted(
            s[k] for s in _shape(got))


@pytest.mark.parametrize("cell", NAMES)
def test_a_cell_that_fixes_its_order_says_why_and_from_which_runs(cell):
    mix = _mix(cell)
    if mix.get("deal_seed") is None:
        assert "deal" not in mix["knee"]
        return
    deal = mix["knee"]["deal"]
    # the seed whose order every run gets was itself run, at this rate
    assert deal["seed"] == mix["deal_seed"]
    assert any(mix["deal_seed"] in s["seeds"]
               and s["rate_per_s"] == mix["rate_per_s"]
               for s in mix["knee"]["sets"])
    assert len(deal["why"]) > 40
    # something arrives inside the traced part of the window
    dues = [r["due_s"] for r in traffic.open_loop_schedule(
        mix, 0, M.bench["run_seconds"], 97)]
    assert sum(d < mix["trace_seconds"] for d in dues) >= 2


@pytest.mark.parametrize("cell", RE_ANCHORED)
def test_the_cell_rehearses_from_its_edited_files_with_a_sweep(cell):
    rc, lines, err = run_cell(cell, 2**31 + 4100 + NAMES.index(cell),
                              extra=("--sweep", "2,4"))
    assert rc == 0, err[-2000:]
    result = last_json(lines)
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"]["itl_p50_ms"]["value"] > 0
    swept = [l for l in lines if l.startswith("sweep rate")]
    # three lines a swept rate: the window, its clocks, the lanes seated
    assert len(swept) == 6
    assert sum("lanes seated a tick median" in l for l in swept) == 2
    assert sum("in flight at its close" in l for l in swept) == 2


@pytest.mark.parametrize("cell, i", _recorded_sets())
def test_a_recorded_sets_spread_is_what_run_spread_gives_of_its_runs(cell, i):
    recorded = _mix(cell)["knee"]["sets"][i]
    runs = recorded["itl_p50_ms"]
    assert len(runs) >= 3 and recorded["rate_per_s"] > 0
    width, share = stats.run_spread(runs)
    assert recorded["run_spread"] == pytest.approx(share, abs=5e-4)
    assert recorded["quartile_spread"] == pytest.approx(
        stats.spread(runs), abs=5e-4)


@pytest.mark.parametrize("values, width, share", [
    # an odd set: the median is a run; 2.9 is farthest and goes
    ([2.5, 2.6, 2.9, 2.55, 2.65], 0.15, 0.15 / 2.6),
    # an even set: the median lies between two runs
    ([4.0, 4.1, 4.2, 4.3, 4.4, 5.0], 0.4, 0.4 / 4.25),
    # the farthest run widens the set: it goes, from below as well
    ([10.0, 10.1, 10.2, 10.3, 10.4, 30.0], 0.4, 0.4 / 10.25),
    ([1.0, 10.1, 10.2, 10.3, 10.4, 10.5], 0.4, 0.4 / 10.25),
    # both ends as far: the one whose going narrows the set more
    ([1.0, 2.0, 4.0, 5.0], 3.0, 3.0 / 3.0),
    # a set of equal values
    ([7.0] * 6, 0.0, 0.0),
    # fewer than three runs: nothing is left out
    ([3.0, 3.3], 0.3, 0.3 / 3.15),
    ([3.0], 0.0, 0.0),
])
def test_run_spread_is_the_range_less_the_farthest_run(values, width, share):
    got = stats.run_spread(values)
    assert got == pytest.approx((width, share))
    assert stats.run_spread(list(reversed(values))) == pytest.approx(got)


def test_run_spread_of_no_run_is_none_and_six_runs_lose_their_farthest():
    assert stats.run_spread([]) is None
    # never wider than the whole range, never under the quartiles'
    # distance of the runs that stay
    runs = [2.6322, 2.55, 2.70, 2.61, 2.75, 2.58]
    width, share = stats.run_spread(runs)
    assert width <= max(runs) - min(runs)
    assert width == pytest.approx(2.70 - 2.55)
    assert share == pytest.approx(width / 2.6211)
