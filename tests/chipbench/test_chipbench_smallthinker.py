"""The `smallthinker` family as the benchmark holds it: the
configuration against the catalog row it was drawn from, the reference's
two kinds of layer, the cost functions and readers of
chipbench/smallthinker_cost.py on handmade reaches, counters and a
handmade trace, and the rehearsal of the cell with every metric that
reads the program's counters."""

import collections
import json
import os

import pytest

from cbhelp import ROOT, last_json, run_cell
from chipbench import smallthinker_cost as cost
from chipbench import span_readers, trace_reduce
from chipbench.manifest import Manifest
from chipbench.refs import smallthinker as ref

M = Manifest(ROOT)
CELL = "serve-st21b-mixed-len"
CFG = M.config("st21b-serve")
PARAMS = CFG["model"]["params"]
# the catalog row's `config` (model-configs guide), as published
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936,
}
REDUCED = ["num_hidden_layers", "moe_num_primary_experts", "vocab_size",
           "rope_layout", "sliding_window_layout"]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_key_of_the_source_is_as_published_or_listed_as_reduced(key):
    if key in REDUCED:
        assert CFG["published"][key] == PUBLISHED[key]
        assert CFG[key] != PUBLISHED[key]
    else:
        assert CFG[key] == PUBLISHED[key]
    assert CFG["reduced"] == REDUCED


def test_the_cut_is_the_deployments_share_and_keeps_the_floors():
    assert PARAMS["moe_experts"] == 64 and PARAMS["moe_top_k"] == 6
    assert PARAMS["experts_held"] == [0, CFG["moe_num_primary_experts"]]
    assert CFG["moe_num_primary_experts"] == 32 >= 8
    assert PARAMS["num_layers"] == 8 and PARAMS["num_layers"] % 4 == 0
    assert PARAMS["rope_layout"] == PUBLISHED["rope_layout"][:8]
    assert PARAMS["window_layout"] == PUBLISHED["sliding_window_layout"][:8]
    assert PARAMS["vocab_size"] * 2 == PUBLISHED["vocab_size"]
    assert "2 pipeline stages" in CFG["deployment"]
    server = CFG["server"]
    longest = max(p for p, _ in M.traffic("mixed-len")["prompt_lens"]) + max(
        n for n, _ in M.traffic("mixed-len")["max_new_tokens"])
    per_slot = -(-longest // server["kv_block_size"])
    assert server["kv_num_blocks"] >= server["num_slots"] * per_slot
    # the rehearsal: one period, half its experts, a window shorter
    # than its longest request
    small = CFG["rehearsal"]["model"]["params"]
    mix = M.traffic("mixed-len")["rehearsal"]
    assert small["num_layers"] == 4 and small["experts_held"] == [0, 4]
    assert small["moe_experts"] == 8
    assert small["attn_window"] < max(p for p, _ in mix["prompt_lens"])


def test_the_reference_tells_the_two_kinds_of_layer_and_lists_its_choices():
    cfg = dict(PARAMS, **CFG["weights"])
    assert [ref.layer_kind(cfg, i) for i in range(8)] == [
        (0, 0), (1500000, 4096), (1500000, 4096), (1500000, 4096)] * 2
    assert len(ref.departures) >= 3 and len(ref.assumed) >= 4
    assert any("router" in line for line in ref.assumed)
    leaves = ref.layer_leaves(cfg, 3)
    assert leaves["block_3/moe/router"][0] == (2560, 64)
    assert leaves["block_3/moe/w_gate"][0] == (32, 2560, 768)
    assert leaves["block_3/moe/w_down"][0] == (32, 768, 2560)
    assert leaves["block_3/attn/qkv/kernel"][0] == (2560, 36 * 128)
    total = sum(_size(s) for s, _ in ref.all_leaves(cfg).values())
    assert round(total / 1e6) == 2068  # the configuration's sizing_note


def _size(shape):
    n = 1
    for s in shape:
        n *= s
    return n


# --------------------------------------------------- (f) the costs


def test_expert_cost_on_handmade_counters():
    assert cost.expert_bytes(PARAMS) == 3 * 2560 * 768 * 2 == 11796480
    assert cost.expert_flops_per_pair(PARAMS) == 6 * 2560 * 768
    assert cost.moe_decode_cost(PARAMS, experts_hit=10, pairs_held=7) == (
        7 * 11796480, 10 * 11796480)
    assert cost.expert_bytes(dict(PARAMS, dtype="fp32")) == 2 * 11796480


@pytest.mark.parametrize("reaches,keys", [
    ([100], 8 * 100),
    ([4096], 8 * 4096),
    ([5000], 2 * 5000 + 6 * 4096),
    ([10, 6000, 4097], 2 * 10107 + 6 * (10 + 4096 + 4096)),
    ([], 0),
])
def test_reach_is_folded_by_each_layers_own_window(reaches, keys):
    assert cost.layer_windows(PARAMS) == [0, 4096, 4096, 4096] * 2
    assert cost.keys_in_reach_by_kind(PARAMS, reaches) == keys
    flops, nbytes = cost.paged_decode_cost_by_kind(PARAMS, reaches)
    assert flops == 4 * 28 * 128 * keys
    assert nbytes == 2 * keys * 4 * 128 * 2
    # one window for every layer is the accepted count
    one = dict(PARAMS, window_layout=[1] * 8)
    assert cost.keys_in_reach_by_kind(one, reaches) == 8 * sum(
        min(r, 4096) for r in reaches)


Phase = collections.namedtuple("Phase", "name start_ns attrs")


def _measured(monkeypatch, hit, held, kernel_us=100, body_us=40):
    """Three traced ticks of a step that holds two expert kernels and
    two per-slot paged bodies, and a ring of five ticks' counters."""
    events = []
    for tick in range(3):
        t0 = 1000000 * tick
        events.append({"plane": "/device:TPU:0", "line": "XLA Modules",
                       "name": "jit_step(7)", "meta": "", "start_ns": t0,
                       "dur_ns": 500000})
        for j, (name, meta, us) in enumerate([
                ("moe_expert_tiles.8", "tpu_custom_call", kernel_us),
                ("closed_call.3", "", body_us),
                ("moe_expert_tiles.9", "tpu_custom_call", kernel_us),
                ("closed_call.4", "", body_us)]):
            events.append({"plane": "/device:TPU:0", "line": "XLA Ops",
                           "name": name, "meta": meta,
                           "start_ns": t0 + 100000 * j,
                           "dur_ns": 1000 * us})
    events.append({"plane": "/device:TPU:0", "line": "XLA Modules",
                   "name": "jit_prefill(9)", "meta": "", "start_ns": 3500000,
                   "dur_ns": 100000})
    events.append({"plane": "/device:TPU:0", "line": "XLA Ops",
                   "name": "moe_expert_tiles.2", "meta": "tpu_custom_call",
                   "start_ns": 3500000, "dur_ns": 90000})
    ring = [Phase(name, 10 * i, {"n": n})
            for i, (h, p) in enumerate(zip(hit, held))
            for name, n in (("moe.experts_hit", h), ("moe.pairs_held", p))]
    monkeypatch.setattr(span_readers, "_in_window", lambda m: ring)
    return {
        "trace": trace_reduce.summarize(events, 4e-3), "cfg": dict(PARAMS),
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "samples": {"traced_token_reach": [100, 5000]}, "counters": {},
    }


def test_moe_roofline_reads_the_traced_ticks_counters(monkeypatch):
    m = _measured(monkeypatch, hit=[50, 60, 70, 1000, 1000],
                  held=[300, 310, 320, 9999, 9999])
    counts = cost.traced_tick_counts(
        m, ("moe.experts_hit", "moe.pairs_held"), "^jit_step$")
    assert counts == {"moe.experts_hit": 180, "moe.pairs_held": 930}
    share = cost.moe_decode_roofline(
        m, match=r"^jit_step\|moe_expert_tiles", program="^jit_step$")
    # bytes-bound: 180 experts x 11.8 MB at 819 GB/s against the six
    # kernel events inside the step (the prefill's is not counted)
    assert share == pytest.approx(
        100 * (180 * 11796480 / 819e9) / 600e-6)
    m["trace"] = None
    assert cost.moe_decode_roofline(m, "x", "y") is None


def test_paged_roofline_by_kind_on_a_handmade_trace(monkeypatch):
    m = _measured(monkeypatch, hit=[1] * 5, held=[1] * 5)
    keys = 8 * 100 + 2 * 5000 + 6 * 4096
    share = cost.paged_roofline_by_kind(m, r"^jit_step\|closed_call\.")
    assert share == pytest.approx(
        100 * (2 * keys * 4 * 128 * 2 / 819e9) / 240e-6)
    # a family whose file gives no head_dim is not this reader's
    del m["cfg"]["head_dim"]
    assert cost.paged_roofline_by_kind(m, "closed_call") is None


def test_readers_return_nothing_where_the_program_counts_nothing(
        monkeypatch):
    m = _measured(monkeypatch, hit=[], held=[])
    assert cost.traced_tick_counts(m, ("moe.experts_hit",),
                                   "^jit_step$") is None
    assert cost.moe_decode_roofline(m, "moe_expert_tiles",
                                    "^jit_step$") is None
    monkeypatch.setattr(span_readers, "_in_window", lambda m: None)
    assert cost.traced_tick_counts(m, ("moe.experts_hit",),
                                   "^jit_step$") is None


# ------------------------------------------- (e) the cell, rehearsed


@pytest.fixture(scope="module")
def traced():
    rc, lines, err = run_cell(CELL, 816325893, trace=1)
    assert rc == 0, err[-2000:]
    return lines, last_json(lines)


@pytest.mark.parametrize("metric,low,high", [
    ("moe.held_share", 0.25, 0.75),
    ("moe.experts_hit_share", 0.2, 1.0),
    ("kv.window_dead_share", 0.0, 0.6),
    ("paged.stream_share", 0.0, 1.0),
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
    assert {"moe.time_share", "moe_roofline",
            "paged_roofline.by_kind"} <= missing
    assert 0 <= result["device"]["busy_s"] <= result["device"]["window_s"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert CELL not in next(m for m in bench["per_layer"]
                            if m["name"] == "paged_roofline")["workloads"]
