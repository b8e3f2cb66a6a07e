"""The `afmoe` family as the benchmark holds it: the configuration
against the catalog row it was drawn from, the cut, its floors and its
arithmetic (the weights and the pool's two block classes), what the
reference lists as set by its author, the accepted cost functions and
readers the cell is listed under (`paged_roofline.by_kind`,
`moe_roofline.swiglu`: the family brings no kernel of its own, so it
brings no cost module either) on handmade counts and a handmade trace
with THIS configuration's widths, the anchor and the limits as the chip
runs set them, and the rehearsal of the cell with every metric that
reads the program's counters."""

import collections
import importlib
import json
import os

import numpy as np
import pytest

from cbhelp import ROOT, last_json, run_cell
from chipbench import refs, sdar_cost, span_readers, stats, trace_reduce
from chipbench import smallthinker_cost as cost
from chipbench import traffic
from chipbench.drivers import open_loop
from chipbench.manifest import Manifest
from chipbench.refs import afmoe as ref

M = Manifest(ROOT)
CELL = "serve-trinity-long-context"
CFG = M.config("trinity-large-serve")
PARAMS = CFG["model"]["params"]
MIX = M.traffic("long-context")
SLIDING, FULL = "sliding_attention", "full_attention"
# the catalog row's `config` (model-configs guide), as published
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 3072, "intermediate_size": 12288,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 15,
    "load_balance_coeff": 5e-05, "max_position_embeddings": 262144,
    "model_type": "afmoe", "moe_intermediate_size": 3072,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 48,
    "num_dense_layers": 6, "num_expert_groups": 1, "num_experts": 256,
    "num_experts_per_tok": 4, "num_hidden_layers": 60,
    "num_key_value_heads": 8, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
    "score_func": "sigmoid", "sliding_window": 4096,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192,
}
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types",
           "num_experts", "vocab_size", "max_position_embeddings"]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_key_of_the_source_is_as_published_or_listed_as_reduced(key):
    assert CFG["source_config"][key] == PUBLISHED[key]
    if key in REDUCED:
        assert CFG["published"][key] == PUBLISHED[key]
        assert CFG[key] != PUBLISHED[key]
    else:
        assert CFG[key] == PUBLISHED[key]
    assert CFG["reduced"] == REDUCED
    assert CFG["source"] == ("https://huggingface.co/arcee-ai/"
                             "Trinity-Large-Preview/blob/main/config.json")
    entry = next(c for c in M.bench["configs"]
                 if c["name"] == "trinity-large-serve")
    assert entry["reduced"] == REDUCED and entry["source"] == CFG["source"]


def test_the_cut_is_the_deployments_share_and_keeps_the_floors():
    # a leading dense layer, then one whole period and four expert
    # layers; 8 routed experts a layer; an eighth of the vocabulary;
    # every width as published
    assert PARAMS["num_layers"] == CFG["num_hidden_layers"] == 5
    assert PARAMS["mlp_layout"] == [0, 1, 1, 1, 1]
    assert CFG["num_dense_layers"] == 1
    assert CFG["layer_types"] == [SLIDING] * 4 + [FULL]
    assert PARAMS["window_layout"] == PARAMS["rope_layout"] == [1, 1, 1, 1, 0]
    assert CFG["layer_types"][1:] == PUBLISHED["layer_types"][:4]
    assert PARAMS["experts_held"] == [0, CFG["num_experts"]] == [0, 8]
    assert PARAMS["moe_experts"] == 256 and PARAMS["moe_top_k"] == 4
    assert PARAMS["vocab_size"] * 8 == 200192
    assert (PARAMS["embed_dim"], PARAMS["num_heads"], PARAMS["head_dim"],
            PARAMS["num_kv_heads"], PARAMS["moe_hidden"],
            PARAMS["moe_shared_hidden"], PARAMS["dense_hidden"],
            PARAMS["attn_window"]) == (
        3072, 48, 128, 8, 3072, 3072, 12288, 4096)
    assert (PARAMS["qk_norm"], PARAMS["attn_gate"], PARAMS["sandwich_norm"],
            PARAMS["embed_scale"], PARAMS["moe_activation"],
            PARAMS["moe_scoring"], PARAMS["moe_route_from"],
            PARAMS["moe_route_scale"], PARAMS["norm_eps"],
            PARAMS["rope_theta"]) == (
        True, True, True, True, "swiglu", "sigmoid", "mlp", 2.448, 1e-05,
        10000)
    assert "v5e-128" in CFG["deployment"]
    assert "32-way expert split" in CFG["deployment"]
    assert "under their share" in CFG["deployment"]
    for key in ("mup_multiplier", "gate_proj", "head_norms", "nope_global",
                "window", "norm_sites", "expert_bias", "bias", "weights"):
        assert CFG["assumed"][key]
    assert len(CFG["departures"]) >= 4
    assert CFG["server"]["kv_shared"] == 0  # the block classes need it


def test_the_sizing_notes_arithmetic_is_the_leaves():
    cfg = dict(PARAMS, **CFG["weights"])
    sizes = {p: int(np.prod(s)) for p, (s, _) in ref.all_leaves(cfg).items()}

    def of(prefix):
        return sum(n for p, n in sizes.items() if p.startswith(prefix))

    attention = of("block_1/attn/")
    assert attention == 3072 * 64 * 128 + 2 * 3072 * 6144 + 2 * 128
    assert round(attention / 1e6, 2) == 62.91
    assert sizes["block_1/moe/w_up"] // 8 * 3 == 28311552  # one expert
    assert of("block_1/moe/shared_") == 28311552
    assert sizes["block_1/moe/router"] == 786432
    assert of("block_0/mlp_") == 3 * 3072 * 12288 == 113246208
    assert round(of("block_1/") / 1e6, 1) == 318.5
    assert round(of("block_0/") / 1e6, 1) == 176.2
    total = sum(sizes.values())
    assert total == of("block_0/") + 4 * of("block_1/") + (
        2 * 25024 * 3072 + 3072)
    assert round(total / 1e9, 3) == 1.604
    server = CFG["server"]
    assert server["kv_block_size"] == 16 and server["num_slots"] == 16
    # a lane's table: the longest request, rounded up to blocks
    assert PARAMS["seq_len"] == 33808 == -(-(32776 + 1024) // 16) * 16
    assert PARAMS["seq_len"] % 16 == 0
    assert CFG["max_position_embeddings"] == PARAMS["seq_len"]
    whole = server["kv_num_blocks"]
    window = 16 * (4096 // 16 + 2)
    assert whole == 16 * 2113 == 33808 and window == 4128
    per_layer_block = 16 * 2 * 8 * 128 * 2  # 4,096 B a token a layer
    kv_bytes = (whole + 4 * window) * per_layer_block
    assert round(whole * per_layer_block / 1e9, 2) == 2.22
    assert round(4 * window * per_layer_block / 1e9, 2) == 1.08
    one_table = 5 * whole * per_layer_block
    assert round(one_table / 1e9, 1) == 11.1
    # in the classes' bytes one table seats 4 of the 16
    assert kv_bytes // (5 * 2113 * per_layer_block) == 4
    # the load's peak, 6 bytes a parameter, beside the pool: under 16 GB
    assert 12.7e9 < 6 * total + kv_bytes < 13.1e9
    assert 6 * total + one_table > 16e9
    assert MIX["kind"] == "open_loop" and MIX["deal_seed"]
    assert MIX["prompt_lens"] == [[4104, 0.3], [8200, 0.35], [16392, 0.25],
                                  [32776, 0.1]]
    assert MIX["max_new_tokens"] == [[512, 0.5], [1024, 0.5]]
    assert round(sum(p * w for p, w in MIX["prompt_lens"])) == 11477
    assert min(p for p, _ in MIX["prompt_lens"]) > PARAMS["attn_window"]
    assert max(p for p, _ in MIX["prompt_lens"]) + 1024 <= PARAMS["seq_len"]


def test_a_fixed_order_deals_every_seed_the_same_lengths_and_dues():
    a, b = (traffic.open_loop_schedule(MIX, seed, 40.0, 96)
            for seed in (1, 2**31 + 5))
    assert [(r["due_s"], len(r["prompt"])) for r in a] == [
        (r["due_s"], len(r["prompt"])) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


# ------------- the anchor and the limits, as the chip runs set them


def test_the_rate_is_four_fifths_of_a_knee_swept_on_the_chip():
    knee = MIX["knee"]
    assert knee["share"] == 0.8
    assert MIX["rate_per_s"] == round(0.8 * knee["req_per_s"], 2) == 0.29
    assert knee["tick_ms"] > 0 and "v5e" in knee["how"]
    # "sustains": everything drains and the TTFT median stays within
    # twice a seating's own time on an empty server, stated in the file
    assert knee["ttft_limit_ms"] == pytest.approx(
        2 * knee["empty_server_seating_ms"])
    assert 0 < MIX["trace_seconds"] <= M.bench["run_seconds"]
    why = M.workload(CELL)["why"]
    assert ("%g req/s" % MIX["rate_per_s"]) in why
    assert "0.8 of the knee" in why and len(why) <= 200
    assert "under their share" in why or "under share" in why
    # what the median gap is at this rate, and where the classes show
    assert "1-lane tick" in why and "kv." in why
    deal = knee["deal"]
    assert deal["seed"] == MIX["deal_seed"] and len(deal["why"]) > 40
    # something arrives inside the traced part of the window
    dues = [r["due_s"] for r in traffic.open_loop_schedule(
        MIX, 0, M.bench["run_seconds"], 97)]
    assert sum(d < MIX["trace_seconds"] for d in dues) >= 1


def test_the_cells_own_set_is_six_runs_at_its_rate_each_a_run_of_the_cell():
    """The set that stands for the cell is six whole runs of the cell
    at the committed rate, a seed each; a window inside a sweep's
    process is not one (it draws the deal seed + 1000 a rate)."""
    mine = [s for s in MIX["knee"]["sets"]
            if s["rate_per_s"] == MIX["rate_per_s"]]
    assert len(mine) == 1
    recorded = mine[0]
    assert len(recorded["itl_p50_ms"]) == len(set(recorded["seeds"])) >= 6
    assert "sweep" not in recorded["what"]
    bound = next(m["bound"] for m in M.bench["end_to_end"]
                 if m["name"] == "itl_p50_ms")
    # a new cell is admitted if a set spreads by under half the bound
    assert recorded["quartile_spread"] < bound / 2
    assert recorded["median"] == pytest.approx(
        float(np.median(recorded["itl_p50_ms"])), abs=5e-4)


@pytest.mark.parametrize("i", range(len(MIX["knee"].get("sets", ()))))
def test_a_recorded_sets_spread_is_what_the_driver_would_take_of_it(i):
    recorded = MIX["knee"]["sets"][i]
    runs = recorded["itl_p50_ms"]
    assert len(runs) == len(recorded["seeds"]) >= 3
    _, share = stats.run_spread(runs)
    assert recorded["run_spread"] == pytest.approx(share, abs=5e-4)
    assert recorded["quartile_spread"] == pytest.approx(
        stats.spread(runs), abs=5e-4)


@pytest.mark.parametrize("name", ["deficit_max", "deficit_mean_sigma"])
def test_a_limit_lies_between_its_two_readings_with_room_on_both_sides(name):
    cell = M.cell(CELL)
    limit, read = cell["limits"][name], cell["readings"]
    sound = read["sound"][name]
    assert len(sound) >= 3 and len(read["seeds"]) == len(sound)
    assert max(sound) * 1.25 <= limit
    if name in read["control_fails"]:
        # the fp8 control comes out as not correct by this limit
        assert limit * 1.25 <= min(read["control_fp8"][name])
    assert read["control_fails"]  # by one of the limits at the least
    assert cell["limits"]["failed_requests"] == 0 and cell["sample"] == 6


def test_the_reference_lists_what_its_author_set_and_the_file_does_too():
    assert any("sqrt(hidden_size)" in line for line in ref.assumed)
    assert any("gate_proj" in line for line in ref.assumed)
    assert any("NoPE" in line for line in ref.assumed)
    assert any("SELECTION only" in line for line in ref.assumed)
    assert any("four norm sites" in line for line in ref.assumed)
    assert any("N(0, 1/D)" in line for line in ref.assumed)
    assert any("left out before" in line for line in ref.departures)
    assert "N(0, 1/3072)" in CFG["assumed"]["weights"]
    assert refs.unmet(open_loop, ref, CFG) == []
    assert CFG["family"] == "afmoe"
    # the layer tells dense from expert and sliding from full by `i`
    cfg = dict(PARAMS, **CFG["weights"])
    assert ref.layer_kind(cfg, 0) == (10000, 4096, True)
    assert ref.layer_kind(cfg, 3) == (10000, 4096, False)
    assert ref.layer_kind(cfg, 4) == (0, 0, False)


# ------------------------------------------------- the cost functions


def _reader(name):
    """The reader of per-layer metric `name` as the harness resolves it
    for this cell (chipbench/run.py `_metrics`), its file's args bound."""
    assert name in {m["name"] for m in M.metrics_of("per_layer", CELL)}
    spec = M.metric_spec("layers", name)
    module, _, fn = spec["reader"].rpartition(":")
    read = getattr(importlib.import_module(module), fn)
    return lambda m, **over: read(m, **dict(spec.get("args", {}), **over))


def test_expert_and_paged_costs_on_handmade_counts():
    assert sdar_cost.expert_bytes(PARAMS) == 3 * 3072 * 3072 * 2 == 56623104
    assert sdar_cost.expert_flops_per_pair(PARAMS) == 6 * 3072 * 3072
    assert sdar_cost.moe_pass_cost(PARAMS, experts_hit=10, pairs_held=7) == (
        7 * 6 * 3072 * 3072, 10 * 56623104)
    assert sdar_cost.expert_bytes(dict(PARAMS, dtype="fp32")) == 2 * 56623104
    assert cost.layer_windows(PARAMS) == [4096, 4096, 4096, 4096, 0]
    # a token behind 14,000 cached ones: four layers read their window,
    # the full layer all of them; one behind 1,000 reads all in each
    assert cost.keys_in_reach_by_kind(PARAMS, [14000]) == 4 * 4096 + 14000
    assert cost.keys_in_reach_by_kind(PARAMS, [1000, 14000]) == (
        5 * 1000 + 4 * 4096 + 14000)
    flops, bytes_ = cost.paged_decode_cost_by_kind(PARAMS, [14000])
    keys = 4 * 4096 + 14000
    assert bytes_ == 2 * keys * 8 * 128 * 2  # K and V, 8 heads of 128
    assert flops == 4 * 48 * 128 * keys
    # the issue's reckoning: 57 MB of the full layer, 67 MB of the four
    assert round(2 * 14000 * 8 * 128 * 2 / 1e6) == 57
    assert round(2 * 4 * 4096 * 8 * 128 * 2 / 1e6) == 67


Phase = collections.namedtuple("Phase", "name start_ns end_ns seq attrs")


def _measured(monkeypatch, hit, held, tiles_us=400, slot_us=20,
              reaches=(14000, 5000)):
    """Three traced ticks of a step that holds two expert kernels and
    four per-slot paged bodies, and a ring of five ticks' counters."""
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
                 Phase("moe.pairs_held", 10 * i, 10 * i, None, {"n": p})]
    monkeypatch.setattr(span_readers, "_in_window", lambda m: ring)
    return {
        "trace": trace_reduce.summarize(events, 12e-3), "cfg": dict(PARAMS),
        "config": CFG, "samples": {"traced_token_reach": list(reaches)},
        "counters": {},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }


def test_rooflines_on_a_handmade_trace(monkeypatch):
    moe, paged = _reader("moe_roofline.swiglu"), _reader(
        "paged_roofline.by_kind")
    m = _measured(monkeypatch, hit=[2, 3, 4, 999, 999],
                  held=[6, 8, 8, 9999, 9999])
    # 9 experts' three matrices against the six kernel events
    assert moe(m) == pytest.approx(
        100 * (9 * 56623104 / 819e9) / 2400e-6)
    need = 2 * (4 * 4096 + 14000 + 4 * 4096 + 5000) * 8 * 128 * 2
    assert paged(m) == pytest.approx(100 * (need / 819e9) / 240e-6)
    # neither passes 100 % at the memory's rate
    fast = _measured(monkeypatch, hit=[8] * 5, held=[64] * 5,
                     tiles_us=0.5e6 * 8 * 56623104 / 819e9,
                     slot_us=1e6 * need / 819e9 / 12)
    assert 99 < moe(fast) <= 100.1
    assert 99 < paged(fast) <= 100.1


def test_readers_return_nothing_where_there_is_nothing_to_read(
        monkeypatch):
    """On the parent commit: no such counter in the ring, no such
    kernel in the trace, no trace."""
    moe, paged = _reader("moe_roofline.swiglu"), _reader(
        "paged_roofline.by_kind")
    m = _measured(monkeypatch, hit=[], held=[])
    assert moe(m) is None
    assert paged(m, match="no_such_kernel") is None
    assert paged(dict(m, samples={})) is None
    m["trace"] = None
    assert moe(m) is None
    assert paged(m) is None
    # a program without the counter kv.blocks_whole (the parent's)
    ring = [Phase("kv.blocks_held", 1, 1, None, {"n": 5})]
    monkeypatch.setattr(span_readers, "_in_window", lambda m: ring)
    assert _reader("kv.class_charge_share")(m) is None


# --------------------------------------------- the cell, rehearsed


@pytest.fixture(scope="module")
def traced():
    rc, lines, err = run_cell(CELL, 3000000019, trace=1, seconds=3)
    assert rc == 0, err[-2000:]
    return lines, last_json(lines)


@pytest.mark.parametrize("metric,low,high", [
    ("kv.class_charge_share", 0.3, 0.95),
    ("kv.window_dead_share", 0.0, 0.1),
    ("moe.held_share", 0.25, 0.75),
    ("moe.experts_hit_share", 0.1, 1.0),
    ("moe.live_share", 0.05, 1.0),
    ("moe.tile_fill", 0.0, 1.0),
    ("paged.stream_share", 0.0, 1.0),
    ("prompt_write.launches_per_prompt", 3.0, 17.0),
    ("tick.ahead_share", 0.3, 1.0),
    ("setup.window_compiles", 0, 0),
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
    assert {"moe_roofline.swiglu", "paged_roofline.by_kind",
            "moe.time_share", "paged.time_share"} <= missing
    assert set(result["compared"]) == {
        "deficit_max", "deficit_mean_sigma", "failed_requests"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # another family's cost functions are not this cell's, nor the
    # pinned ring.window_coverage
    for name in ("moe_roofline", "moe_roofline.relu2", "paged_roofline",
                 "paged_roofline.tile", "ssm_roofline",
                 "ring.window_coverage"):
        assert CELL not in next(m for m in bench["per_layer"]
                                if m["name"] == name)["workloads"]
    # the same kernels under the same counts are ONE series each: the
    # cell is appended to the accepted lists, it mints no second name
    for name, before in (
            ("paged_roofline.by_kind", ["serve-st21b-mixed-len"]),
            ("moe_roofline.swiglu", ["serve-sdar-block-gen"])):
        assert next(m for m in bench["per_layer"]
                    if m["name"] == name)["workloads"] == before + [CELL]
    assert not [m["name"] for m in bench["per_layer"] if "afmoe" in m["name"]]
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "kv.class_charge_share")
    assert entry["workloads"] == [CELL] and entry["moves"] == "itl_p50_ms"
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "itl_p50_ms")["workloads"]
    assert next(w for w in bench["workloads"]
                if w["name"] == CELL)["chips"] == 1
    # the sample holds the longest request, and every request's decode
    # crosses a release of the window class (every prompt is past it)
    assert any("longest 2" in ln for ln in lines if "reference:" in ln)
    window = CFG["rehearsal"]["model"]["params"]["attn_window"]
    assert min(p for p, _ in MIX["rehearsal"]["prompt_lens"]) > window


def test_the_same_seed_gives_the_same_inputs(traced):
    lines, _ = traced
    rc, again, err = run_cell(CELL, 3000000019, seconds=3)
    assert rc == 0, err[-2000:]
    inputs = [ln for ln in lines if ln.startswith("inputs:")]
    assert inputs and inputs == [ln for ln in again
                                 if ln.startswith("inputs:")]
