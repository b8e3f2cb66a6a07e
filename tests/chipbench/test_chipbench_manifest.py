"""BENCHMARK.json against the benchmark's contract, and every data
file it names: all found by name, all cross-referenced."""

import importlib
import json
import os
import re

import pytest

from cbhelp import ROOT
from chipbench import readers
from chipbench.manifest import NAME, Manifest

M = Manifest(ROOT)
B = M.bench
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_dim|expansion|experts_per_tok")
E2E = {m["name"]: m for m in B["end_to_end"]}
CELLS = [w["name"] for w in B["workloads"]]


def _line(text, limit=200):
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def test_top_level_keys_and_sizes():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert 1 <= len(B["command"]) <= 32 and all(map(_line, B["command"]))
    assert 1 <= len(B["paths"]) <= 16
    for p in B["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) and ".." not in p
        assert not p.startswith("/") and os.path.isdir(os.path.join(ROOT, p))


def test_a_full_check_fits_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (B["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_files_under_paths_have_plain_names():
    for p in B["paths"]:
        for folder, _, files in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in folder:
                continue
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


@pytest.mark.parametrize("cfg", B["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and _line(cfg["source"])
    assert _line(cfg["why"])
    assert any(cfg["file"].startswith(p + "/") for p in B["paths"])
    assert len(cfg["reduced"]) <= 16
    data = M.config(cfg["name"])
    assert data["source"] == cfg["source"]
    assert data["reduced"] == cfg["reduced"]
    for key in cfg["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
        assert key in data and key in data["published"]
    assert any(w["config"] == cfg["name"] for w in B["workloads"])
    # the reference of its family is there and imports nothing of the
    # program
    ref = M.reference(data)
    src = open(ref.__file__).read()
    assert "elasticdl_tpu" not in src.split('"""', 2)[2]
    assert "model_zoo" not in src.split('"""', 2)[2]


def test_config_files_are_distinct():
    files = [c["file"] for c in B["configs"]]
    assert len(set(files)) == len(files)


def test_widths_are_the_published_ones():
    """StarCoder2-3B's config.json; no width is cut in either file."""
    published = {"hidden_size": 3072, "intermediate_size": 12288,
                 "num_attention_heads": 24, "num_key_value_heads": 2,
                 "vocab_size": 49152, "max_position_embeddings": 16384,
                 "sliding_window": 4096}
    for cfg in B["configs"]:
        data = M.config(cfg["name"])
        for key, value in published.items():
            assert data[key] == value, (cfg["name"], key)
        p = data["model"]["params"]
        assert p["embed_dim"] == 3072 and p["num_heads"] == 24
        assert p["num_kv_heads"] == 2 and p["attn_window"] == 4096
        assert p["num_layers"] == data["num_hidden_layers"]
        assert p["vocab_size"] == 49152 and p["seq_len"] == 16384


@pytest.mark.parametrize("wl", B["workloads"], ids=lambda w: w["name"])
def test_workload_entry_and_files(wl):
    assert set(wl) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(wl[k]) for k in ("name", "config", "traffic"))
    assert wl["chips"] in (1, 4) and _line(wl["why"])
    M.config(wl["config"])
    mix = M.traffic(wl["traffic"])
    M.driver(mix)
    cell = M.cell(wl["name"])
    assert cell["limits"] and all(v >= 0 for v in cell["limits"].values())
    mine = M.metrics_of("end_to_end", wl["name"])
    assert "setup_s" in [m["name"] for m in mine] and len(mine) >= 2
    assert M.metrics_of("per_layer", wl["name"])


def test_cells_are_distinct_and_few_take_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(set(pairs)) == len(pairs) == len(set(CELLS))
    four = sum(1 for w in B["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize(
    "kind,folder,metric",
    [("end_to_end", "metrics", m) for m in B["end_to_end"]]
    + [("per_layer", "layers", m) for m in B["per_layer"]],
    ids=lambda x: x["name"] if isinstance(x, dict) else x)
def test_metric_entry_and_reader(kind, folder, metric):
    keys = {"name", "unit", "better", "source"}
    keys |= {"bound"} if kind == "end_to_end" else {"layer", "moves"}
    assert keys <= set(metric) <= keys | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = metric.get("workloads", CELLS)
    assert cells and set(cells) <= set(CELLS)
    if kind == "end_to_end":
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert _line(metric["layer"]) and metric["moves"] in E2E
        moved = E2E[metric["moves"]].get("workloads", CELLS)
        assert set(cells) <= set(moved)
        if "roofline" in metric["name"] or "mfu" in metric["name"]:
            assert metric["unit"] == "%"
    spec = M.metric_spec(folder, metric["name"])
    module, _, fn = spec["reader"].rpartition(":")
    reader = getattr(importlib.import_module(module) if module else readers,
                     fn)
    assert callable(reader)


def test_names_are_unique():
    for key in ("configs", "workloads"):
        names = [e["name"] for e in B[key]]
        assert len(set(names)) == len(names)
    names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.1
    assert "workloads" not in E2E["setup_s"]


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(ROOT, "chipbench", "traffic"))))
def test_traffic_files_are_data_for_a_known_driver(name):
    mix = M.traffic(name)
    assert NAME.match(name) and hasattr(M.driver(mix), "run_cell")
    if mix["kind"] == "open_loop":
        assert mix["rate_per_s"] > 0
        for key in ("prompt_lens", "max_new_tokens"):
            assert abs(sum(w for _, w in mix[key]) - 1.0) < 1e-9


def test_queued_dp4_cell_has_its_files_and_no_entry():
    mix = M.traffic("train-packed-4k-dp4")
    assert mix["mesh"] == {"dp": 4}
    one = M.traffic("train-packed-4k")
    assert mix["per_chip_batch"] == one["per_chip_batch"]
    assert mix["seq_len"] == one["seq_len"]
    assert "train-4k-dp4" not in CELLS
    for metric in ("allreduce.exposed_share", "scaling.efficiency_vs_1chip"):
        assert "reader" in M.metric_spec("layers", metric)
    assert "limits" in M.cell("train-4k-dp4")


def test_queued_below_knee_long_cell_has_its_files_and_no_entry():
    below, over = M.traffic("complete-long"), M.traffic("complete-long-over")
    assert "serve-complete-long" not in CELLS
    assert below["prompt_lens"] == over["prompt_lens"]
    assert below["rate_per_s"] < below["knee"]["req_per_s"] < over[
        "rate_per_s"]
    assert "limits" in M.cell("serve-complete-long")
    for metric in ("ttft_p95_ms", "itl_p95_ms", "serve_total_tokens_per_s"):
        assert "reader" in M.metric_spec("metrics", metric)
    assert "reader" in M.metric_spec("layers", "sched.queue_wait_ms")
    assert "limits" in M.cell("serve-complete-long-over")


def test_peaks_name_their_source():
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
