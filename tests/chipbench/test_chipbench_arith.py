"""The yardstick's arithmetic: percentiles, due-time TTFT, the traffic
generator, the FLOP and byte functions, the trace reduction."""

import math
import os
import sys

import pytest

from cbhelp import ROOT
from chipbench import flops, readers, stats, trace_reduce, traffic

SC2 = {"embed_dim": 3072, "num_heads": 24, "num_kv_heads": 2,
       "num_layers": 4, "vocab_size": 49152, "attn_window": 4096}


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 95, 95.05),
    ([5.0], 95, 5.0),
    ([], 95, None),
    ([1, 2, math.inf], 50, 2.0),
    ([1, 2, math.inf], 95, math.inf),
])
def test_percentile_is_exact(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_is_not_a_histogram():
    # the program's log-linear histogram is off by up to 3.1 %; two
    # samples 1 % apart must stay apart
    assert stats.percentile([100.0, 101.0], 100) == 101.0
    assert stats.percentile([100.0, 101.0], 0) == 100.0


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.spread([10, 10, 10, 10, 10, 10]) == 0
    assert stats.spread([98, 99, 100, 100, 101, 102]) == pytest.approx(
        (101.25 - 98.75) / 100)


@pytest.mark.parametrize("n", [1, 7, 10, 80, 203])
def test_weighted_counts_are_whole_and_sum_to_n(n):
    pairs = [(72, .15), (136, .25), (264, .25), (520, .20), (1032, .10),
             (2056, .05)]
    counts = stats.weighted_counts(pairs, n)
    assert sum(c for _, c in counts) == n
    for (_, w), (_, c) in zip(pairs, counts):
        assert abs(c - n * w) < 1


def test_ttft_counts_from_the_due_time_so_a_stall_shows():
    """Requests due every 100 ms; the server stalls 1 s at t=0.2 and
    then answers each request 10 ms after it is free. Timed from the
    send after the stall, later requests would look fast; timed from
    their due time they carry the stall."""
    due = [0.1 * i for i in range(10)]
    first_token = [d + 0.01 if d < 0.2 else max(d, 1.2) + 0.01 for d in due]
    ttft = [f - d for f, d in zip(first_token, due)]
    assert ttft[0] == pytest.approx(0.01)
    assert ttft[2] == pytest.approx(1.01)      # due at 0.2, the stall
    assert ttft[5] == pytest.approx(0.71)      # due at 0.5, still waits
    m = {"samples": {"ttft_s": ttft}, "counters": {}}
    assert readers.percentile(m, "ttft_s", 95, 1000.0) > 900
    assert readers.percentile(m, "ttft_s", 50, 1000.0) == pytest.approx(
        560.0)


MIX = {"rate_per_s": 5.0, "arrivals": "poisson",
       "prompt_lens": [[72, .5], [136, .3], [264, .2]],
       "max_new_tokens": [[32, .5], [64, .5]]}


def test_schedule_same_seed_same_inputs():
    a = traffic.open_loop_schedule(MIX, 2**31 + 11, 20, 1000)
    b = traffic.open_loop_schedule(MIX, 2**31 + 11, 20, 1000)
    assert a == b and len(a) == 100 and a[0]["due_s"] == 0.0
    assert all(0 <= t < 1000 for r in a for t in r["prompt"])


def test_schedule_other_seed_same_work_in_another_order():
    a = traffic.open_loop_schedule(MIX, 1, 20, 1000)
    b = traffic.open_loop_schedule(MIX, 2, 20, 1000)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert sorted(len(r["prompt"]) for r in a) == sorted(
        len(r["prompt"]) for r in b)
    assert sorted(r["max_new_tokens"] for r in a) == sorted(
        r["max_new_tokens"] for r in b)
    gaps = lambda s: sorted(round(y["due_s"] - x["due_s"], 9)
                            for x, y in zip(s, s[1:]))
    # the same gaps but one: the last gap of each order is never used
    assert len(set(gaps(a)) ^ set(gaps(b))) <= 2
    assert a[-1]["due_s"] < 20 and b[-1]["due_s"] < 20


@pytest.mark.parametrize("kind", ["poisson", "uniform"])
def test_arrival_gaps_have_the_exact_mean(kind):
    gaps = traffic.arrival_gaps(200, 4.0, kind)
    assert sum(gaps) == pytest.approx(50.0)
    if kind == "poisson":  # exponential: median = ln2 * mean
        assert sorted(gaps)[100] == pytest.approx(0.25 * math.log(2),
                                                  rel=0.02)


@pytest.mark.parametrize("seq,window,want", [
    (4, 0, 10), (4, 8, 10), (4096, 0, 4096 * 4097 // 2),
    (8, 4, 10 + 4 * 4), (16384, 4096, 4096 * 4097 // 2 + 12288 * 4096),
])
def test_attended_keys(seq, window, want):
    assert flops.attended_keys(seq, window) == want
    brute = sum(min(i + 1, window) if window else i + 1
                for i in range(seq))
    assert want == brute


def test_dense_flops_are_gqa_aware_hand_count():
    d, hd = 3072, 128
    qkv = 2 * d * (24 + 2 * 2) * hd       # 28 heads' worth, not 72
    proj = 2 * 24 * hd * d
    mlp = 2 * d * 4 * d * 2
    head = 2 * d * 49152
    assert flops.dense_flops_per_token(SC2) == 4 * (qkv + proj + mlp) + head
    mha = dict(SC2, num_kv_heads=24)
    assert flops.dense_flops_per_token(mha) > flops.dense_flops_per_token(SC2)


def test_attention_flops_causal_and_windowed():
    full = dict(SC2, attn_window=0)
    pairs = 4096 * 4097 // 2
    assert flops.attention_flops(full, 4096) == 4 * 4 * 24 * 128 * pairs
    assert flops.attention_flops(SC2, 4096) == flops.attention_flops(
        full, 4096)  # the window is the sequence: nothing skipped
    assert flops.attention_flops(SC2, 16384) < flops.attention_flops(
        full, 16384) / 2


def test_our_count_differs_from_bench_py_where_that_one_is_wrong():
    sys.path.insert(0, ROOT)
    import bench

    theirs = bench.transformer_flops_per_step(2, 4096, 3072, 4, 49152)
    ours = flops.train_flops_per_token(SC2, 4096) * 2 * 4096
    # bench.py counts non-causal attention and MHA projections
    assert theirs > ours * 1.1
    mha_noncausal = 3 * 2 * 4096 * (
        flops.dense_flops_per_token(dict(SC2, num_kv_heads=24))
        + 2 * flops.attention_flops(dict(SC2, attn_window=0), 4096) / 4096)
    assert theirs == pytest.approx(mha_noncausal, rel=0.002)


def test_flash_and_paged_costs():
    f, b = flops.flash_train_cost(SC2, 4096, 2)
    assert f == flops.attention_flops(SC2, 4096) * 2 * 7 // 2
    qo, kv = 2 * 4096 * 24 * 128 * 2, 2 * 4096 * 2 * 128 * 2
    assert b == 4 * (8 * qo + 8 * kv)
    pf, pb = flops.paged_decode_cost(SC2, 1000)
    assert pb == 4 * 2 * 1000 * 2 * 128 * 2
    assert flops.paged_decode_cost(SC2, 9000) == flops.paged_decode_cost(
        SC2, 4096)


def test_roofline_share_names_its_bound():
    share, bound = flops.roofline_share(197e12, 1e9, 2.0, 197e12, 819e9)
    assert (share, bound) == (50.0, "flops")
    share, bound = flops.roofline_share(1e9, 819e9, 4.0, 197e12, 819e9)
    assert (share, bound) == (25.0, "bytes")


@pytest.fixture(scope="module")
def handmade():
    events = trace_reduce.load_events(os.path.join(
        ROOT, "chipbench", "testdata", "handmade_trace.json"))
    return trace_reduce.summarize(events, window_s=40e-6)


def test_trace_busy_is_a_union_not_a_sum(handmade):
    # ops cover [1000, 9000] and [20000, 22000], [24000, 29000];
    # fusion/flash/all-reduce overlap inside the first
    assert handmade["busy_s"] == pytest.approx(15e-6)
    assert handmade["planes"] == 1


def test_trace_sums_by_name_and_program(handmade):
    secs, count = trace_reduce.seconds_matching(
        handmade, r"^jit_train_step\|.*\|tpu_custom_call$")
    assert (secs, count) == (pytest.approx(9e-6), 2)
    secs, count = trace_reduce.seconds_matching(
        handmade, "jit_train_step", "programs")
    assert (secs, count) == (pytest.approx(18e-6), 2)
    assert handmade["device_ops"][0] == ["fusion.1", pytest.approx(5e-6)]


def test_trace_gaps_go_to_what_the_host_was_doing(handmade):
    gaps = dict(handmade["idle_gaps"])
    assert gaps["float(loss)"] == pytest.approx(11e-6)   # 9000 -> 20000
    assert gaps["next(batch)"] == pytest.approx(2e-6)    # 22000 -> 24000


def test_trace_exposed_collective_time(handmade):
    # all-reduce [6000, 9000]; flash runs until 7000: 2000 ns exposed
    assert handmade["collective_s"] == pytest.approx(3e-6)
    assert handmade["collective_exposed_s"] == pytest.approx(2e-6)
    m = {"trace": handmade}
    assert readers.collective_exposed_share(m) == pytest.approx(
        100 * 2 / 15)
    assert readers.trace_idle_share(m) == pytest.approx(100 * 25 / 40)
    assert readers.trace_share(m, "tpu_custom_call$") == pytest.approx(
        100 * 9 / 15)
    assert readers.trace_share(
        m, "tpu_custom_call$",
        of_programs="jit_train_step") == pytest.approx(50.0)
    assert readers.trace_ms_per_execution(
        m, "jit_train_step") == pytest.approx(9e-3)


def test_hlo_event_names_are_cut_to_the_instruction():
    name = ('%attn.16 = (bf16[48,4096,128]{2,1,0}, f32[48,4096,1]) '
            'custom-call(bf16[48,4096,128] %pad), '
            'custom_call_target="tpu_custom_call", frontend_attributes={}')
    assert trace_reduce.short_name(name) == "attn.16"
    assert trace_reduce.kind_of(name) == "tpu_custom_call"
    assert trace_reduce.kind_of("%fusion.1 = f32[8] fusion(...)") == ""


@pytest.fixture(scope="module")
def recorded():
    """One train-4k step on a v5e chip (my chip run, PR 23), cut from
    the profiler's trace to the reduction's own event format."""
    events = trace_reduce.load_events(os.path.join(
        ROOT, "chipbench", "testdata", "train_step_v5e.json"))
    return trace_reduce.summarize(events, window_s=0.290909365)


def test_recorded_trace_busy_and_programs(recorded):
    assert recorded["planes"] == 1
    assert recorded["busy_s"] == pytest.approx(0.287925927, rel=1e-6)
    assert recorded["programs"]["jit_train_step"][1] == 2
    assert recorded["collective_s"] == 0.0
    assert 0 < readers.trace_idle_share({"trace": recorded}) < 2


def test_recorded_trace_finds_the_sixteen_flash_kernel_calls(recorded):
    files = os.path.join(ROOT, "chipbench", "layers")
    import json
    spec = json.load(open(os.path.join(files, "flash.time_share.train.json")))
    secs, count = trace_reduce.seconds_matching(
        recorded, spec["args"]["match"])
    # 4 layers x (forward, its recomputation, dq, dkv)
    assert count == 16 and secs == pytest.approx(0.040830205, rel=1e-6)
    share = readers.trace_share({"trace": recorded}, **spec["args"])
    assert share == pytest.approx(100 * 0.040830205 / 0.287925927, rel=1e-6)


def test_recorded_trace_roofline_is_under_its_ceiling(recorded):
    import json
    spec = json.load(open(os.path.join(
        ROOT, "chipbench", "layers", "flash_roofline.train.json")))
    m = {"trace": recorded, "cfg": SC2,
         "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
         "counters": {"seq_len": 4096, "batch": 2, "chips": 1}}
    # the trace holds one whole step's kernels but counts two module
    # launches: the share over two steps' need is an upper bound
    share = readers.flash_train_roofline(m, **spec["args"])
    assert 0 < share < 100


def test_recorded_trace_gaps_are_the_hosts_wait_for_the_loss(recorded):
    name, secs = recorded["idle_gaps"][0]
    assert "_value" in name and secs == pytest.approx(0.00376, rel=0.01)
    assert all(not n.startswith("while") for n, _ in recorded["device_ops"])


def test_readers_return_nothing_when_there_is_nothing_to_read():
    m = {"trace": None, "samples": {}, "counters": {}, "peaks": None,
         "cfg": SC2}
    assert readers.trace_idle_share(m) is None
    assert readers.trace_share(m, "x") is None
    assert readers.mean(m, "absent") is None
    assert readers.percentile(m, "absent", 95) is None
    assert readers.counter(m, "absent") is None
    assert readers.ratio(m, "a", "b") is None
    assert readers.train_mfu(m) is None
    assert readers.flash_train_roofline(m, "x", "y") is None
    assert readers.paged_decode_roofline(m, "x") is None


def test_mfu_and_rooflines_from_hand_numbers():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    per_token = flops.train_flops_per_token(SC2, 4096)
    m = {"peaks": peaks, "cfg": SC2, "trace": None, "samples": {},
         "counters": {"tokens": 20000.0 * 10, "window_s": 10.0, "chips": 1,
                      "seq_len": 4096, "batch": 2}}
    assert readers.train_mfu(m) == pytest.approx(
        100 * 20000 * per_token / 197e12)
    assert 3.4e9 < per_token < 3.7e9   # the issue's 3.5 GFLOP/token
