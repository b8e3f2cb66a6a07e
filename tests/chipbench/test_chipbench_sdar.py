"""The `sdar_moe` family as the benchmark holds it: the configuration
against the catalog row it was drawn from, the cut, its floors and its
arithmetic, what the reference lists as set by its author, the cost
functions and readers of chipbench/sdar_cost.py on handmade counts and
a handmade trace, what `open_loop_blocks` compares on handmade passes,
and the rehearsal of the cell with every metric that reads the
program's counters."""

import collections
import json
import os

import numpy as np
import pytest

from cbhelp import ROOT, last_json, run_cell
from chipbench import sdar_cost as cost
from chipbench import span_readers, stats, trace_reduce, traffic
from chipbench.drivers import open_loop, open_loop_blocks
from chipbench.manifest import Manifest
from chipbench.refs import sdar_moe as ref

M = Manifest(ROOT)
CELL = "serve-sdar-block-gen"
CFG = M.config("sdar-30b-serve")
PARAMS = CFG["model"]["params"]
# the catalog row's `config` (model-configs guide), as published
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_key_of_the_source_is_as_published_or_listed_as_reduced(key):
    assert CFG["source_config"][key] == PUBLISHED[key]
    if key in REDUCED:
        assert CFG["published"][key] == PUBLISHED[key]
        assert CFG[key] != PUBLISHED[key]
    else:
        assert CFG[key] == PUBLISHED[key]
    assert CFG["reduced"] == REDUCED
    assert CFG["source"].startswith("https://huggingface.co/JetLM/SDAR-30B")


def test_the_cut_is_the_deployments_share_and_keeps_the_floors():
    # one kind of layer: at least four; an eighth of the experts and of
    # the vocabulary at the least; every width as published
    assert PARAMS["num_layers"] == CFG["num_hidden_layers"] == 11 >= 4
    assert PARAMS["experts_held"] == [0, CFG["num_experts"]] == [0, 32]
    assert PARAMS["moe_experts"] == 128 and PARAMS["moe_top_k"] == 8
    assert PARAMS["vocab_size"] * 4 == 151936
    assert PARAMS["mask_token"] == PARAMS["vocab_size"] - 1
    assert (PARAMS["embed_dim"], PARAMS["num_heads"], PARAMS["head_dim"],
            PARAMS["num_kv_heads"], PARAMS["moe_hidden"]) == (
        2048, 32, 128, 4, 768)
    assert (PARAMS["qk_norm"], PARAMS["block_causal"],
            PARAMS["moe_activation"], PARAMS["moe_route_from"]) == (
        True, 4, "swiglu", "mlp")
    assert "v5e-8" in CFG["deployment"] and "over its share" in CFG[
        "deployment"]
    for key in ("block_length", "mask_token", "same_position_logits",
                "noise_schedule", "commit_pass", "prompt_remainder",
                "weights", "seq_len"):
        assert CFG["assumed"][key]
    assert len(CFG["departures"]) >= 4


def test_the_sizing_notes_arithmetic_is_the_leaves():
    cfg = dict(PARAMS, **CFG["weights"])
    sizes = {p: int(np.prod(s)) for p, (s, _) in ref.all_leaves(cfg).items()}
    layer = sum(n for p, n in sizes.items() if p.startswith("block_0/"))
    assert layer == 18874368 + 2 * 2048 + 2 * 128 + 262144 + 32 * 4718592
    total = sum(sizes.values())
    assert total == 11 * layer + 2 * 37984 * 2048 + 2048
    assert round(total / 1e9, 3) == 2.027
    server = CFG["server"]
    tokens = server["kv_num_blocks"] * server["kv_block_size"]
    assert tokens == 32 * 2320 == 74240
    assert PARAMS["seq_len"] == 2320 == -(-(2056 + 256) // 16) * 16
    kv_bytes = tokens * 11 * 2 * 4 * 128 * 2
    assert round(kv_bytes / 1e9, 2) == 1.67
    # the load's peak, 6 bytes a parameter, beside the pool: under 16 GB
    assert 13.5e9 < 6 * total + kv_bytes < 14.2e9
    assert server["denoise_steps"] == 2 and 4 % 2 == 0
    mix = M.traffic("block-gen")
    assert mix["kind"] == "open_loop_blocks" and mix["deal_seed"]
    assert all(p % 4 == 0 for p, _ in mix["prompt_lens"])
    assert mix["max_new_tokens"] == [[256, 1.0]]
    assert round(sum(p * w for p, w in mix["prompt_lens"])) == 427
    assert max(p for p, _ in mix["prompt_lens"]) + 256 <= PARAMS["seq_len"]


def test_a_fixed_order_deals_every_seed_the_same_lengths_and_dues():
    mix = M.traffic("block-gen")
    a, b = (traffic.open_loop_schedule(mix, seed, 20.0, 96)
            for seed in (1, 2**31 + 5))
    assert [(r["due_s"], len(r["prompt"])) for r in a] == [
        (r["due_s"], len(r["prompt"])) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


# ------------- the anchor and the limits, as the chip runs set them


def test_the_rate_is_four_fifths_of_a_knee_swept_at_a_stated_tick():
    mix = M.traffic("block-gen")
    knee = mix["knee"]
    assert abs(mix["rate_per_s"] - 0.8 * knee["req_per_s"]) <= 0.05 + 1e-9
    assert knee["tick_ms"] > 0 and ("%g ms" % knee["tick_ms"]) in knee["how"]
    assert 0 < mix["trace_seconds"] <= M.bench["run_seconds"]
    why = M.workload(CELL)["why"]
    assert ("%g req/s" % mix["rate_per_s"]) in why
    assert "0.8 of the knee" in why and len(why) <= 200
    # the seed whose order every run gets was itself run, at this rate
    deal = knee["deal"]
    assert deal["seed"] == mix["deal_seed"] and len(deal["why"]) > 40
    assert "not among" in deal["why"] or any(
        mix["deal_seed"] in s["seeds"]
        and s["rate_per_s"] == mix["rate_per_s"] for s in knee["sets"])
    # something arrives inside the traced part of the window
    dues = [r["due_s"] for r in traffic.open_loop_schedule(
        mix, 0, M.bench["run_seconds"], 97)]
    assert sum(d < mix["trace_seconds"] for d in dues) >= 2


@pytest.mark.parametrize("i", range(len(
    M.traffic("block-gen")["knee"].get("sets", ()))))
def test_a_recorded_sets_spread_is_what_the_driver_would_take_of_it(i):
    mix = M.traffic("block-gen")
    recorded = mix["knee"]["sets"][i]
    runs = recorded["itl_p50_ms"]
    assert len(runs) == len(recorded["seeds"]) >= 3
    _, share = stats.run_spread(runs)
    assert recorded["run_spread"] == pytest.approx(share, abs=5e-4)
    assert recorded["quartile_spread"] == pytest.approx(
        stats.spread(runs), abs=5e-4)
    if recorded["rate_per_s"] == mix["rate_per_s"] and len(runs) >= 6:
        # a new cell is admitted if a set spreads by under half the bound
        bound = next(m["bound"] for m in M.bench["end_to_end"]
                     if m["name"] == "itl_p50_ms")
        assert recorded["quartile_spread"] < bound / 2


@pytest.mark.parametrize("name", ["deficit_max", "deficit_mean_sigma",
                                  "reveal_deficit_max"])
def test_a_limit_lies_between_its_two_readings_with_room_on_both_sides(name):
    cell = M.cell(CELL)
    limit, read = cell["limits"][name], cell["readings"]
    sound = read["sound"][name]
    assert len(sound) >= 3 and len(read["seeds"]) == len(sound)
    assert max(sound) * 1.25 <= limit
    if name in read["control_fails"]:
        # the fp8 control comes out as not correct by this limit
        assert limit * 1.25 <= min(read["control_fp8"][name])
    else:
        # the precision hardly moves this number (its two readings
        # overlap or nearly): its limit is held by the fault it is
        # there for, an order of reveal that is wrong
        wrong = read["least_certain_first"][name]
        if wrong:
            assert limit * 1.25 <= min(wrong)
        else:  # not read on the chip yet: the file says so
            assert "no chip reading yet" in cell["note"]
    assert {"deficit_max", "deficit_mean_sigma"} <= set(read["control_fails"])


def test_the_reference_lists_what_its_author_set_and_the_file_does_too():
    assert any("block length 4" in line for line in ref.assumed)
    assert any("no shift by one" in line for line in ref.assumed)
    assert any("static" in line for line in ref.departures)
    assert PARAMS["mask_token"] == 37983
    assert open_loop_blocks.NEEDS == open_loop.NEEDS
    assert all(callable(getattr(ref, n)) for n in open_loop_blocks.ALSO_NEEDS)


# ------------------------------------------------- the cost functions


def test_expert_and_tile_costs_on_handmade_counts():
    assert cost.expert_bytes(PARAMS) == 3 * 2048 * 768 * 2 == 9437184
    assert cost.expert_flops_per_pair(PARAMS) == 6 * 2048 * 768
    assert cost.moe_pass_cost(PARAMS, experts_hit=10, pairs_held=7) == (
        7 * 6 * 2048 * 768, 10 * 9437184)
    assert cost.expert_bytes(dict(PARAMS, dtype="fp32")) == 2 * 9437184
    assert cost.passes_a_block(CFG) == 3
    # a block of four tokens served behind 40 cached ones: three passes
    # each stream 40 keys; tokens of the next block stream 44
    assert cost.keys_streamed(PARAMS, CFG, [40, 41, 42, 43]) == 3 * 40
    assert cost.keys_streamed(PARAMS, CFG, [44, 45, 46, 47, 40]) == (
        3 * 44 + 3 * 40 / 4)
    flops, bytes_ = cost.paged_tile_cost(PARAMS, CFG, [40, 41, 42, 43])
    keys = 3 * 40 * 11
    assert bytes_ == 2 * keys * 4 * 128 * 2  # K and V, head_dim 128
    assert flops == 4 * 32 * 128 * 4 * keys  # four query rows a lane


Phase = collections.namedtuple("Phase", "name start_ns end_ns seq attrs")


def _measured(monkeypatch, hit, held, tiles_us=400, slot_us=20,
              reaches=(512, 513, 514, 515)):
    """Three traced ticks of a step that holds two expert kernels and
    four per-slot paged bodies, and a ring of five ticks' counters and
    root phases."""
    events = []
    for tick in range(3):
        t0 = 4000000 * tick
        events.append({"plane": "/device:TPU:0", "line": "XLA Modules",
                       "name": "jit_step(7)", "meta": "", "start_ns": t0,
                       "dur_ns": 3000000})
        for j, (name, us) in enumerate([
                ("moe_expert_tiles.5", tiles_us),
                ("moe_expert_tiles.6", tiles_us),
                ("closed_call.11", slot_us), ("closed_call.12", slot_us),
                ("closed_call.13", slot_us), ("closed_call.14", slot_us)]):
            events.append({"plane": "/device:TPU:0", "line": "XLA Ops",
                           "name": name, "meta": "tpu_custom_call",
                           "start_ns": t0 + 450000 * j,
                           "dur_ns": 1000 * us})
    ring = []
    for i, (h, p) in enumerate(zip(hit, held)):
        ring += [Phase("moe.experts_hit", 10 * i, 10 * i, None, {"n": h}),
                 Phase("moe.pairs_held", 10 * i, 10 * i, None, {"n": p}),
                 Phase("tick", 10000000 * i, 10000000 * i + 6000000 + i, i,
                       {"active": 2, "passes": 2}),
                 Phase("tick.dispatch", 10000000 * i + 5, 10000000 * i + 9,
                       i, {})]
    ring.append(Phase("tick", 5, 99000000, 77, {"active": 0}))  # no step
    monkeypatch.setattr(span_readers, "_in_window", lambda m: ring)
    return {
        "trace": trace_reduce.summarize(events, 12e-3), "cfg": dict(PARAMS),
        "config": CFG, "samples": {"traced_token_reach": list(reaches)},
        "counters": {},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }


def test_rooflines_and_the_ticks_pace_on_a_handmade_trace(monkeypatch):
    m = _measured(monkeypatch, hit=[20, 25, 30, 999, 999],
                  held=[60, 64, 64, 9999, 9999])
    # 75 experts' three matrices against the six kernel events
    assert cost.moe_pass_roofline(
        m, r"^jit_step\|moe_expert_tiles", "^jit_step$") == pytest.approx(
        100 * (75 * 9437184 / 819e9) / 2400e-6)
    # one block behind 512 tokens: three passes x 11 layers x 512 keys
    need = 2 * 3 * 512 * 11 * 4 * 128 * 2
    assert cost.paged_tile_roofline(
        m, r"^jit_step\|closed_call\.") == pytest.approx(
        100 * (need / 819e9) / 240e-6)
    # neither passes 100 % at the memory's rate
    fast = _measured(monkeypatch, hit=[32] * 5, held=[64] * 5,
                     tiles_us=0.5e6 * 32 * 9437184 / 819e9,
                     slot_us=1e6 * need / 819e9 / 12)
    assert 99 < cost.moe_pass_roofline(
        fast, "moe_expert_tiles", "^jit_step$") <= 100.1
    assert 99 < cost.paged_tile_roofline(fast, r"closed_call\.") <= 100.1
    # the median of the five ticks that ran a step, three passes a
    # block of four
    assert cost.ms_per_token(m) == pytest.approx(6.000002 * 3 / 4)


def test_readers_return_nothing_where_there_is_nothing_to_read(
        monkeypatch):
    """On the parent commit, or in another family's cell: no such
    counter in the ring, no such key in the file, no trace."""
    m = _measured(monkeypatch, hit=[], held=[])
    assert cost.moe_pass_roofline(m, "moe_expert_tiles",
                                  "^jit_step$") is None
    assert cost.ms_per_token(m) is None
    assert cost.paged_tile_roofline(m, "no_such_kernel") is None
    other = dict(_measured(monkeypatch, hit=[1], held=[1]),
                 cfg={"embed_dim": 2560}, config={"server": {}})
    assert cost.paged_tile_roofline(other, r"closed_call\.") is None
    assert cost.ms_per_token(other) is None
    m["trace"] = None
    assert cost.moe_pass_roofline(m, "x", "y") is None
    assert cost.paged_tile_roofline(m, "x") is None
    monkeypatch.setattr(span_readers, "_in_window", lambda m: None)
    assert cost.ms_per_token(m) is None


# -------------------------------- what `open_loop_blocks` compares


def _passes(probs, reveal, vocab=6):
    """Reference logits [blocks, S, B, vocab] whose greedy token at
    (b, s, j) is j with probability `probs[b][s][j]`."""
    probs = np.asarray(probs, np.float64)
    logits = np.zeros(probs.shape + (vocab,), np.float32)
    for idx in np.ndindex(probs.shape):
        p = probs[idx]  # softmax([x, 0, ..]) has max p
        logits[idx][idx[-1]] = np.log(p * (vocab - 1) / (1 - p))
    return logits, np.asarray(reveal)


def test_the_numbers_are_read_at_the_pass_that_revealed_each_token():
    # one block of four, two passes; positions 1 and 3 revealed first
    logits, reveal = _passes(
        [[[0.5, 0.9, 0.6, 0.8], [0.7, 0.99, 0.7, 0.99]]], [[1, 0, 1, 0]])
    at_reveal, _, worst = open_loop_blocks.block_numbers([(logits, reveal)])
    assert at_reveal.shape == (4, 6)
    assert [int(x.argmax()) for x in at_reveal] == [0, 1, 2, 3]
    # rows 0 and 2 from pass 1, rows 1 and 3 from pass 0
    assert at_reveal[0, 0] == logits[0, 1, 0, 0]
    assert at_reveal[1, 1] == logits[0, 0, 1, 1]
    assert worst == 0.0  # the most certain went first
    # the server revealed 0 and 2 first instead: 0.9 was left masked
    # while 0.5 was revealed
    _, _, worst = open_loop_blocks.block_numbers(
        [(logits, np.asarray([[0, 1, 0, 1]]))])
    assert worst == pytest.approx(0.9 - 0.5)
    # a given position is compared nowhere and counts in no pass
    at_reveal, _, worst = open_loop_blocks.block_numbers(
        [(logits, np.asarray([[ref.GIVEN, 0, 1, 0]]))])
    assert at_reveal.shape == (3, 6) and worst == 0.0


def test_the_control_reveals_by_its_own_order_and_serves_its_own_tokens():
    logits, reveal = _passes(
        [[[0.5, 0.9, 0.6, 0.8], [0.7, 0.99, 0.7, 0.99]]], [[1, 0, 1, 0]])
    low = np.roll(logits, 1, axis=2)  # another model: all one to the right
    _, theirs, worst = open_loop_blocks.block_numbers(
        [(logits, reveal)], revealed_by=[low])
    assert theirs == [int(low[0, 1, 0].argmax()), int(low[0, 0, 1].argmax()),
                      int(low[0, 1, 2].argmax()), int(low[0, 0, 3].argmax())]
    # it took 0 and 2 first (its 0.8 and 0.9) and left the reference's 0.9
    assert worst == pytest.approx(0.9 - 0.5)


# --------------------------------------------- the cell, rehearsed


@pytest.fixture(scope="module")
def traced():
    rc, lines, err = run_cell(CELL, 3000000019, trace=1)
    assert rc == 0, err[-2000:]
    return lines, last_json(lines)


@pytest.mark.parametrize("metric,low,high", [
    ("diffusion.tokens_per_pass", 4 / 3 - 1e-9, 4 / 3 + 1e-9),
    ("diffusion.commit_share", 1 / 3 - 1e-9, 1 / 3 + 1e-9),
    ("diffusion.ms_per_token", 1e-3, 1e4),
    ("moe.held_share", 0.25, 0.75),
    ("moe.experts_hit_share", 0.2, 1.0),
    ("moe.live_share", 0.05, 1.0),
    ("paged.stream_share", 0.0, 1.0),
    ("prompt_write.launches_per_prompt", 0.5, 5.0),
    ("tick.ahead_share", 0.3, 1.0),
])
def test_rehearsal_reports_what_the_programs_counters_give(
        traced, metric, low, high):
    _, result = traced
    assert result["correct"] is True and result["failed"] == 0
    assert low <= result["metrics"][metric]["value"] <= high


def test_rehearsal_lacks_only_what_a_device_trace_gives(traced):
    lines, result = traced
    declared = {m["name"]: m["source"]
                for m in M.metrics_of("per_layer", CELL)}
    missing = set(declared) - set(result["metrics"])
    assert missing and all(declared[m] == "device_trace" for m in missing)
    assert {"moe_roofline.swiglu", "paged_roofline.tile",
            "moe.time_share"} <= missing
    assert set(result["compared"]) == {
        "deficit_max", "deficit_mean_sigma", "reveal_deficit_max",
        "failed_requests"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # another family's cost functions are not this cell's
    for name in ("moe_roofline", "moe_roofline.relu2", "paged_roofline",
                 "paged_roofline.by_kind", "kv.window_dead_share",
                 "ssm_roofline"):
        assert CELL not in next(m for m in bench["per_layer"]
                                if m["name"] == name)["workloads"]
    assert next(w for w in bench["workloads"]
                if w["name"] == CELL)["chips"] == 1
    assert any("passes a block" in ln for ln in lines)


def test_the_same_seed_gives_the_same_inputs(traced):
    lines, _ = traced
    rc, again, err = run_cell(CELL, 3000000019)
    assert rc == 0, err[-2000:]
    inputs = [ln for ln in lines if ln.startswith("inputs:")]
    assert inputs and inputs == [ln for ln in again
                                 if ln.startswith("inputs:")]
