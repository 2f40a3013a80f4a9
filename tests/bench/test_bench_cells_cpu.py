"""Each cell of BENCHMARK.json, at a toy size on the CPU, runs through the
harness (all but its look for a chip) and its checks pass."""
import pytest
from toycells import UNLISTED, run_toy, run_toy_subprocess, toy_cell

from bench import manifest

WORKLOADS = [w["name"] for w in manifest.load_manifest()["workloads"]]


def run(workload, **kw):
    chips = toy_cell(workload).chips
    if chips > 1:
        return run_toy_subprocess(workload, devices=chips, **kw)
    return run_toy(workload, **kw)


@pytest.mark.parametrize("workload", WORKLOADS + sorted(UNLISTED))
def test_toy_cell_is_correct(workload):
    line = run(workload)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert list(line)[-1] == "checks"
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name
    assert {"color_ms", "colors", "setup_s"} <= set(line["metrics"])
    assert line["device"]["platform"] == "cpu"


def test_box_timesteps_keep_frozen_cells_and_stay_in_the_mask():
    line = run_toy("hex128-d1-box10", seconds=0.5)
    assert line["correct"] and line["attempted"] >= 2
    assert line["checks"]["frozen_changed"]["value"] == 0
    assert line["window"]["compiles"] == 0
    slowest = line["window"]["slowest"]
    assert slowest["ms"] == max(line["window"]["request_ms"])
    assert 0 <= slowest["gc_ms"] <= slowest["ms"]


def test_traced_toy_run_reports_set_up_layers():
    line = run_toy("hex128-d1-full", trace=True)
    assert line["correct"]
    assert {"graph_build_s", "plan_build_s", "compile_s"} <= set(
        line["metrics"])
    assert "breakdown" in line and "window_s" in line["device"]
