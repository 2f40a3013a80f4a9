"""Resolve a workload of ``BENCHMARK.json`` to the files it names.

A workload names a configuration and a traffic mix; each metric names a
reader.  Each lives in a file of its own, found by name:

* configuration ``<c>`` — ``bench/configs/<c>.json``;
* traffic mix ``<t>``   — ``bench/traffic/<t>.json``;
* metric ``<m>``        — ``bench/metrics/<m>.py`` (a ``read(run)``);

and inside those files, a graph ``kind`` is ``bench/graphs/<kind>.py``,
a mix's ``mask`` kind ``bench/masks/<mask>.py`` and a configuration's
``problem`` ``bench/references/<problem>.py``.

So a later cell or metric is a manifest entry plus new files.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    reader: object          # module with read(run) -> float | None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    return json.loads(path.read_text())


def config_path(name: str) -> Path:
    return BENCH / "configs" / f"{name}.json"


def traffic_path(name: str) -> Path:
    return BENCH / "traffic" / f"{name}.json"


def reader(name: str):
    return importlib.import_module(f"bench.metrics.{name}")


def _metrics(entries, workload: str) -> tuple[Metric, ...]:
    return tuple(Metric(e["name"], e["unit"], reader(e["name"]))
                 for e in entries
                 if "workloads" not in e or workload in e["workloads"])


def resolve(workload: str, manifest: dict | None = None) -> Cell:
    """The cell named ``workload``, with its files loaded."""
    m = load_manifest() if manifest is None else manifest
    cells = {w["name"]: w for w in m["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have: {', '.join(sorted(cells))})")
    w = cells[workload]
    return cell(workload, w["config"], w["traffic"], int(w["chips"]), m)


def cell(name: str, config: str, traffic: str, chips: int,
         manifest: dict) -> Cell:
    """A cell of ``config`` under ``traffic``, with the metrics that
    ``manifest`` gives a workload of this name."""
    return Cell(
        name=name,
        chips=chips,
        config=_json(config_path(config)),
        mix=_json(traffic_path(traffic)),
        end_to_end=_metrics(manifest["end_to_end"], name),
        per_layer=_metrics(manifest["per_layer"], name),
    )


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind`` (``bench/peaks.json``).

    A kind that is not in the table is an error, never a default.
    """
    table = _json(BENCH / "peaks.json")["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have: {', '.join(table)})")
    return table[device_kind]
