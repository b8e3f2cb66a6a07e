"""The `nemotron_h` family as the benchmark holds it: the configuration
against the catalog row it was drawn from, the cut and its floors, the
reference's three kinds of layer and the sizes its leaves add up to, the
cost functions and readers of chipbench/nemotron_h_cost.py on handmade
counters and a handmade trace, and the rehearsal of the cell with every
metric that reads the program's counters."""

import collections
import json
import os

import pytest

from cbhelp import ROOT, last_json, run_cell
from chipbench import nemotron_h_cost as cost
from chipbench import span_readers, trace_reduce
from chipbench.manifest import Manifest
from chipbench.refs import nemotron_h as ref

M = Manifest(ROOT)
CELL = "serve-nm3n-short-chat"
CFG = M.config("nm3n-30b-serve")
PARAMS = CFG["model"]["params"]
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# the catalog row's `config` (model-configs guide), as published
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072,
}
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size", "max_position_embeddings"]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_key_of_the_source_is_as_published_or_listed_as_reduced(key):
    assert CFG["source_config"][key] == PUBLISHED[key]
    if key in REDUCED:
        assert CFG["published"][key] == PUBLISHED[key]
        assert CFG[key] != PUBLISHED[key]
    else:
        assert CFG[key] == PUBLISHED[key]
    assert CFG["reduced"] == REDUCED
    assert len(PATTERN) == 52 and (PATTERN.count("M"), PATTERN.count("E"),
                                   PATTERN.count("*")) == (23, 23, 6)


def test_the_cut_is_the_deployments_share_and_keeps_the_floors():
    kinds = PARAMS["layer_kinds"]
    assert kinds == PATTERN[:13] == CFG["hybrid_override_pattern"]
    assert PARAMS["num_layers"] == 13 > 9  # longer than the period
    assert (kinds.count("M"), kinds.count("E"), kinds.count("*")) == (
        6, 5, 2)
    assert PARAMS["moe_experts"] == 128 and PARAMS["moe_top_k"] == 6
    assert PARAMS["experts_held"] == [0, CFG["n_routed_experts"]]
    assert CFG["n_routed_experts"] == 32 >= 8
    assert PARAMS["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert PARAMS["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    # no positional encoding: a rotary layout of zeros, so that a
    # correction is a change of data
    assert PARAMS["rope_layout"] == [0] * 13
    assert "no positional encoding" in CFG["assumed"]["positional_encoding"]
    assert "2 pipeline stages x 4-way" in CFG["deployment"]
    assert "v5e-8" in CFG["deployment"]
    # every width as published
    for key, param in CFG["param_of"].items():
        assert PARAMS[param] == CFG[key], key
    server, mix = CFG["server"], M.traffic("short-chat")
    longest = max(p for p, _ in mix["prompt_lens"]) + max(
        n for n, _ in mix["max_new_tokens"])
    assert longest == 1288 <= PARAMS["seq_len"]
    per_slot = -(-longest // server["kv_block_size"])
    assert server["kv_num_blocks"] >= server["num_slots"] * per_slot
    assert server["kv_shared"] == 0  # a state cannot be shared by prefix
    # the rate is a fraction of the knee the file records
    assert mix["rate_per_s"] == pytest.approx(
        0.8 * mix["knee"]["req_per_s"])
    # the rehearsal keeps every kind of layer and a share of the experts
    small = CFG["rehearsal"]["model"]["params"]
    assert set(small["layer_kinds"]) == set("ME*")
    assert small["experts_held"] == [0, small["moe_experts"] // 2]


def _size(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def test_the_reference_tells_the_kinds_apart_and_its_sizes_are_the_models():
    cfg = dict(PARAMS, **CFG["weights"])
    per_kind = {}
    for i, kind in enumerate(cfg["layer_kinds"]):
        leaves = ref.layer_leaves(cfg, i)
        mixer = sum(_size(s) for p, (s, _) in leaves.items()
                    if "RMSNorm" not in p)
        per_kind.setdefault(kind, set()).add(mixer)
    # ISSUE.md's sizes: 38.74 M a Mamba-2 mixer, 23.40 M attention,
    # 339.6 M an expert layer at 32 of 128 experts
    assert per_kind == {"M": {38742208}, "*": {23396352},
                        "E": {32 * 2 * 2688 * 1856 + 2 * 2688 * 3712
                              + 2688 * 128 + 128}}
    total = sum(_size(s) for s, _ in ref.all_leaves(cfg).values())
    assert round(total / 1e6) == 2153  # the configuration's sizing_note
    # uncut, the whole model: the published 31.6 B
    whole = dict(cfg, layer_kinds=PATTERN, num_layers=52,
                 experts_held=[0, 128], vocab_size=131072)
    assert round(sum(_size(s) for s, _ in
                     ref.all_leaves(whole).values()) / 1e9, 1) == 31.6
    mixer = ref.layer_leaves(cfg, 0)
    assert mixer["block_0/ssm/in_proj/kernel"][0] == (2688, 10304)
    assert mixer["block_0/ssm/conv_kernel"][0] == (6144, 4)
    experts = ref.layer_leaves(cfg, 1)
    assert experts["block_1/moe/w_up"][0] == (32, 1856, 2688)
    assert "block_1/moe/w_gate" not in experts
    assert len(ref.departures) >= 3 and len(ref.assumed) >= 4
    assert any("positional" in line for line in ref.assumed)
    # what the seed gives the recurrence (`assumed`)
    w = ref.make_leaves(dict(cfg, ssm_heads=512), 3, {
        "a": ((512,), "a_log"), "dt": ((512,), "dt_bias"),
        "d": ((512,), "one"), "b": ((128,), "sel_bias")})
    import numpy as np

    a, dt0 = np.exp(np.asarray(w["a"])), np.log1p(np.exp(np.asarray(w["dt"])))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 3
    assert 0.001 * 0.99 <= dt0.min() and dt0.max() <= 0.1 * 1.01
    assert (np.asarray(w["d"]) == 1).all()
    assert 0.05 < np.asarray(w["b"]).std() < 0.2


# ------------------------------------------------------- the costs


def test_state_update_and_expert_costs_on_handmade_counts():
    state = 64 * 64 * 128
    assert cost.ssm_update_cost(PARAMS, 1) == (6 * state, 2 * 4 * state)
    assert cost.ssm_update_cost(PARAMS, 32)[1] == 32 * 2 * 2097152
    assert cost.expert_bytes(PARAMS) == 2 * 2688 * 1856 * 2 == 19955712
    assert cost.expert_flops_per_pair(PARAMS) == 4 * 2688 * 1856
    assert cost.moe_relu2_cost(PARAMS, experts_hit=10, pairs_held=7) == (
        7 * 4 * 2688 * 1856, 10 * 19955712)
    assert cost.expert_bytes(dict(PARAMS, dtype="fp32")) == 2 * 19955712


Phase = collections.namedtuple("Phase", "name start_ns attrs")


def _measured(monkeypatch, hit, held, update_us=200, tiles_us=700):
    """Three traced ticks of a step that holds two state updates and
    two expert kernels, and a ring of five ticks' counters."""
    events = []
    for tick in range(3):
        t0 = 4000000 * tick
        events.append({"plane": "/device:TPU:0", "line": "XLA Modules",
                       "name": "jit_step(7)", "meta": "", "start_ns": t0,
                       "dur_ns": 3000000})
        for j, (name, us) in enumerate([
                ("ssm_state_update.6", update_us),
                ("moe_expert_tiles.5", tiles_us),
                ("ssm_state_update.7", update_us),
                ("moe_expert_tiles.6", tiles_us)]):
            events.append({"plane": "/device:TPU:0", "line": "XLA Ops",
                           "name": name, "meta": "tpu_custom_call",
                           "start_ns": t0 + 750000 * j,
                           "dur_ns": 1000 * us})
    ring = [Phase(name, 10 * i, {"n": n})
            for i, (h, p) in enumerate(zip(hit, held))
            for name, n in (("moe.experts_hit", h), ("moe.pairs_held", p))]
    monkeypatch.setattr(span_readers, "_in_window", lambda m: ring)
    return {
        "trace": trace_reduce.summarize(events, 12e-3), "cfg": dict(PARAMS),
        "config": CFG, "samples": {}, "counters": {},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }


def test_rooflines_on_a_handmade_trace(monkeypatch):
    m = _measured(monkeypatch, hit=[40, 50, 60, 999, 999],
                  held=[90, 95, 100, 9999, 9999])
    # six update events, each all 32 lanes of one layer, bytes-bound
    assert cost.ssm_roofline(m, r"^jit_step\|ssm_state_update") == (
        pytest.approx(100 * (6 * 32 * 2 * 2097152 / 819e9) / 1200e-6))
    # 150 experts' two matrices against the six kernel events
    assert cost.moe_relu2_roofline(
        m, r"^jit_step\|moe_expert_tiles", "^jit_step$") == pytest.approx(
        100 * (150 * 19955712 / 819e9) / 4200e-6)
    # both stay under 100 % at the memory's rate: a 32-lane update
    # cannot be faster than 0.164 ms, nor 25 experts than 0.61 ms
    fast = _measured(monkeypatch, hit=[25] * 5, held=[48] * 5,
                     update_us=164, tiles_us=610 / 2)
    assert 99 < cost.ssm_roofline(fast, "ssm_state_update") <= 100.1
    assert 99 < cost.moe_relu2_roofline(
        fast, "moe_expert_tiles", "^jit_step$") <= 100.1


def test_readers_return_nothing_where_there_is_nothing_to_read(
        monkeypatch):
    """On the parent commit, or in another family's cell: no such
    kernel in the trace, no such counter in the ring, no such key."""
    m = _measured(monkeypatch, hit=[], held=[])
    assert cost.moe_relu2_roofline(m, "moe_expert_tiles",
                                   "^jit_step$") is None
    assert cost.ssm_roofline(m, "no_such_kernel") is None
    other = dict(m, cfg={"embed_dim": 2560})
    assert cost.ssm_roofline(other, "ssm_state_update") is None
    assert cost.moe_relu2_roofline(other, "moe_expert_tiles",
                                   "^jit_step$") is None
    m["trace"] = None
    assert cost.ssm_roofline(m, "ssm_state_update") is None
    assert cost.moe_relu2_roofline(m, "x", "y") is None


# --------------------------------------------- the cell, rehearsed


@pytest.fixture(scope="module")
def traced():
    rc, lines, err = run_cell(CELL, 3000000019, trace=1)
    assert rc == 0, err[-2000:]
    return lines, last_json(lines)


@pytest.mark.parametrize("metric,low,high", [
    ("ssm.live_share", 0.05, 1.0),
    ("moe.held_share", 0.25, 0.75),
    ("moe.experts_hit_share", 0.2, 1.0),
    ("paged.stream_share", 0.0, 1.0),
    ("prompt_write.launches_per_prompt", 1.0, 5.0),
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
    assert {"ssm.time_share", "ssm_roofline", "moe_roofline.relu2",
            "moe.time_share"} <= missing
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # another family's cost functions are not this cell's
    for name in ("moe_roofline", "paged_roofline", "paged_roofline.by_kind",
                 "kv.window_dead_share"):
        assert CELL not in next(m for m in bench["per_layer"]
                                if m["name"] == name)["workloads"]
    assert len(bench["workloads"]) == 4
    assert all(w["chips"] == 1 for w in bench["workloads"])


def test_the_same_seed_gives_the_same_inputs(traced):
    lines, _ = traced
    rc, again, err = run_cell(CELL, 3000000019)
    assert rc == 0, err[-2000:]
    inputs = [ln for ln in lines if ln.startswith("inputs:")]
    assert inputs and inputs == [ln for ln in again
                                 if ln.startswith("inputs:")]
