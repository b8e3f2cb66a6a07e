"""Every cell of BENCHMARK.json end to end at tiny size, in a child
process started the way the driver starts it (the rehearsal flag stands
in for the chip), and what the command does without a chip."""

import os
import shutil

import pytest

from cbhelp import RESULT_KEYS, ROOT, last_json, run_cell
from chipbench import device
from chipbench.manifest import Manifest

M = Manifest(ROOT)
CELLS = [w["name"] for w in M.bench["workloads"]]


@pytest.fixture(scope="module")
def runs():
    """One untraced and one traced run of each cell, made once."""
    out = {}
    for i, cell in enumerate(CELLS):
        for trace in (0, 1):
            seed = 2**31 + 100 * i + trace
            out[cell, trace] = run_cell(cell, seed, trace=trace)
    return out


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_its_last_line_has_the_contracts_keys(
        runs, cell, trace):
    rc, lines, err = runs[cell, trace]
    assert rc == 0, err[-2000:]
    result = last_json(lines)
    allowed = RESULT_KEYS | {"rehearsal"} | ({"breakdown"} if trace else set())
    assert RESULT_KEYS <= set(result) <= allowed
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu" and result["rehearsal"]
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    kind = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in M.metrics_of(kind, cell)}
    assert set(result["metrics"]) <= set(declared)
    for name, got in result["metrics"].items():
        assert set(got) == {"value", "unit"}
        assert got["unit"] == declared[name]["unit"]
        assert isinstance(got["value"], (int, float))
    if trace:
        assert {"busy_s", "window_s"} <= set(result["device"])
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert result["metrics"]["setup.window_compiles"]["value"] == 0
    else:
        # every end-to-end metric of the cell, none of them zero
        assert set(result["metrics"]) == set(declared)
        assert all(v["value"] > 0 for v in result["metrics"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_every_number_compared_is_printed_beside_its_limit(runs, cell):
    _, lines, _ = runs[cell, 0]
    limits = M.cell(cell)["limits"]
    printed = [l.split()[1] for l in lines if l.startswith("correct:")]
    assert sorted(printed) == sorted(limits)
    assert any(l.startswith("reference:") for l in lines)


@pytest.mark.parametrize("cell", CELLS[:2])
def test_same_seed_same_inputs_and_bench_run_is_ignored(runs, cell):
    seed = 2**31 + 100 * CELLS.index(cell)
    again = run_cell(cell, seed, env={"BENCH_RUN": "2"})
    other = run_cell(cell, seed + 5)
    pick = lambda lines: [l for l in lines if l.startswith("inputs:")]
    assert again[0] == other[0] == 0
    assert pick(runs[cell, 0][1]) == pick(again[1]) != pick(other[1])


def test_without_a_chip_and_without_the_flag_it_fails_and_prints_no_result():
    rc, lines, err = run_cell(CELLS[0], 1, rehearsal=False)
    assert rc != 0 and "not a TPU" in err
    assert not any(l.startswith("{") for l in lines)


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in M.bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    rc, lines, err = run_cell(CELLS[0], 1, cwd=str(tmp_path))
    assert rc != 0 and "not in this checkout" in err
    assert not any(l.startswith("{") for l in lines)


def test_an_unknown_device_kind_raises():
    with pytest.raises(device.NoChip, match="TPU v9"):
        device.peaks_for("TPU v9")
    assert device.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_fewer_chips_than_the_cell_asks_for_is_refused(monkeypatch):
    import jax

    with pytest.raises(device.NoChip, match="needs 64 chips"):
        device.claim(64, rehearsal=True)
    with pytest.raises(device.NoChip, match="not a TPU"):
        device.claim(1, rehearsal=False)
    devices, peaks = device.claim(4, rehearsal=True)
    assert len(devices) == 4 and peaks is None
    assert jax.devices()[0].platform == "cpu"
