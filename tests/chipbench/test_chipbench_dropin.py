"""The harness is driven by data: a second configuration, a traffic
file, a cell and a metric of each kind dropped into a copy of the
benchmark are found by name, with no file of the copy edited but
BENCHMARK.json; and the queued cells (the four-chip one on four virtual
devices) run from their files as they are committed."""

import json
import os

import pytest

from cbhelp import RESULT_KEYS, copy_benchmark, last_json, run_cell


def _write(path, data):
    with open(path, "w") as f:
        json.dump(data, f)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench"))
    copy_benchmark(root)
    cb = os.path.join(root, "chipbench")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))

    # a second configuration: other sizes, same family
    cfg = json.load(open(os.path.join(cb, "configs", "sc2-3b-serve.json")))
    cfg["rehearsal"]["model"]["params"].update(embed_dim=32, num_heads=2,
                                               num_kv_heads=1)
    _write(os.path.join(cb, "configs", "other-serve.json"), cfg)
    bench["configs"].append(dict(bench["configs"][1], name="other-serve",
                                 file="chipbench/configs/other-serve.json"))
    # a traffic mix: data only
    _write(os.path.join(cb, "traffic", "even.json"), {
        "kind": "open_loop", "arrivals": "uniform", "rate_per_s": 4.0,
        "prompt_lens": [[11, 1.0]], "max_new_tokens": [[5, 1.0]]})
    # its cell, and one metric of each kind with a reader of its own
    _write(os.path.join(cb, "cells", "other-even.json"), {
        "sample": 2, "limits": {"deficit_max": 0.5,
                                "deficit_mean_sigma": 0.05,
                                "failed_requests": 0}})
    with open(os.path.join(cb, "layers", "requests_seen.py"), "w") as f:
        f.write("def read(m, times):\n"
                "    return m['counters']['requests'] * times\n")
    _write(os.path.join(cb, "layers", "requests_seen.json"),
           {"reader": "chipbench.layers.requests_seen:read",
            "args": {"times": 2}})
    _write(os.path.join(cb, "metrics", "ttft_p50_ms.json"),
           {"reader": "percentile",
            "args": {"samples": "ttft_s", "q": 50, "scale": 1000.0}})
    bench["workloads"].append({
        "name": "other-even", "config": "other-serve", "traffic": "even",
        "chips": 1, "why": "a test's"})
    bench["end_to_end"].append({
        "name": "ttft_p50_ms", "unit": "ms", "better": "lower",
        "bound": 0.1, "source": "host_clock", "workloads": ["other-even"]})
    bench["per_layer"].append({
        "name": "requests_seen", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "serving loop",
        "moves": "ttft_p50_ms", "workloads": ["other-even"]})
    # the queued cells: an entry is all each lacks
    bench["workloads"].append({
        "name": "serve-complete-long", "config": "sc2-3b-serve",
        "traffic": "complete-long", "chips": 1, "why": "queued"})
    for metric in bench["end_to_end"]:
        if metric["name"] == "itl_p50_ms":
            metric["workloads"].append("serve-complete-long")
    bench["workloads"].append({
        "name": "train-4k-dp4", "config": "sc2-3b-train",
        "traffic": "train-packed-4k-dp4", "chips": 4, "why": "queued"})
    bench["end_to_end"][0]["workloads"].append("train-4k-dp4")
    for name in ("allreduce.exposed_share", "scaling.efficiency_vs_1chip"):
        spec = json.load(open(os.path.join(cb, "layers", name + ".json")))
        bench["per_layer"].append({
            "name": name, "unit": spec["unit"], "better": spec["better"],
            "source": spec["source"], "layer": spec["layer"],
            "moves": spec["moves"], "workloads": ["train-4k-dp4"]})
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_a_dropped_in_cell_is_found_by_name(copy, trace):
    rc, lines, err = run_cell("other-even", 9, trace=trace, cwd=copy,
                              seconds=2)
    assert rc == 0, err[-2000:]
    result = last_json(lines)
    assert result["correct"] is True and result["attempted"] == 8
    if trace:
        assert result["metrics"]["requests_seen"] == {
            "value": 16, "unit": "requests"}
        assert "sched.queue_wait_ms" not in result["metrics"]
    else:
        assert set(result["metrics"]) == {"ttft_p50_ms", "setup_s"}


def test_the_queued_below_knee_cell_runs_from_its_files(copy):
    rc, lines, err = run_cell("serve-complete-long", 10, cwd=copy, seconds=2)
    assert rc == 0, err[-2000:]
    result = last_json(lines)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"itl_p50_ms", "setup_s"}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_queued_dp4_cell_runs_on_four_virtual_devices(copy, trace):
    rc, lines, err = run_cell(
        "train-4k-dp4", 2**31 + 77, trace=trace, cwd=copy,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert rc == 0, err[-2000:]
    result = last_json(lines)
    assert RESULT_KEYS <= set(result)
    assert result["correct"] is True and result["device"]["count"] == 4
    assert lines[0].startswith("chipbench: train-4k-dp4") and "4 x" in lines[0]
    if not trace:
        assert result["metrics"]["train_tokens_per_s_chip"]["value"] > 0


def test_an_unknown_workload_is_an_error(copy):
    rc, lines, err = run_cell("no-such-cell", 1, cwd=copy)
    assert rc != 0 and "no-such-cell" in err
