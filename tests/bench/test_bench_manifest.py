"""BENCHMARK.json resolves, by name, to the files of each cell and metric,
and keeps to the shape the benchmark's contract gives it."""
import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import manifest  # noqa: E402

M = manifest.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = M["end_to_end"] + M["per_layer"]
WORKLOADS = [w["name"] for w in M["workloads"]]


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["command"][1] == "bench/run.py"
    assert all(not w.startswith("/") and ".." not in w for w in M["command"])
    for p in M["paths"]:
        assert (ROOT / p).is_dir() and re.fullmatch(r"[A-Za-z0-9_./-]+", p)


def test_run_seconds_fit_a_full_check_of_24_cells():
    rs = M["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_to_its_files(workload):
    cell = manifest.resolve(workload)
    w = next(x for x in M["workloads"] if x["name"] == workload)
    assert manifest.config_path(w["config"]).is_file()
    assert manifest.traffic_path(w["traffic"]).is_file()
    for kind, name in (("masks", cell.mix["mask"]),
                       ("references", cell.config["problem"]),
                       ("graphs", cell.config["graph"]["kind"])):
        assert (ROOT / "bench" / kind / f"{name}.py").is_file()
    assert cell.chips in (1, 4) and cell.config["chips"] == cell.chips
    assert NAME.match(workload) and len(w["why"]) <= 200
    names = {m.name for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(m.reader.read)


@pytest.mark.parametrize("config", M["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    path = ROOT / config["file"]
    assert path == manifest.config_path(config["name"])
    assert any(config["file"].startswith(p + "/") for p in M["paths"])
    data = json.loads(path.read_text())
    assert data["name"] == config["name"]
    assert set(config["reduced"]) <= set(data)
    assert config["source"].startswith("https://")
    used = [w for w in M["workloads"] if w["config"] == config["name"]]
    assert used


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert (ROOT / "bench" / "metrics" / f"{metric['name']}.py").is_file()
    for w in metric.get("workloads", []):
        assert w in WORKLOADS
    if metric in M["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert metric["moves"] in {m["name"] for m in M["end_to_end"]}
        assert metric["layer"] and "\n" not in metric["layer"]


def test_names_are_unique_and_four_chip_cells_few():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in M[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 2)


def test_peaks_of_an_unknown_kind_are_an_error():
    assert manifest.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        manifest.peaks("cpu")


def test_bench_modules_do_not_load_jax_at_import():
    code = ("import sys; sys.path.insert(0, %r); import bench.run, "
            "bench.system, bench.metrics.local_roofline; "
            "print('jax' in sys.modules)" % str(ROOT))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


def test_without_the_program_the_harness_exits_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in M["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, *M["command"][1:], "--workload", WORKLOADS[0],
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_without_a_tpu_the_harness_exits_without_a_result():
    env = {k: v for k, v in __import__("os").environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, *M["command"][1:], "--workload", WORKLOADS[0],
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 3 and out.stdout.strip() == ""
    assert "no TPU" in out.stderr
