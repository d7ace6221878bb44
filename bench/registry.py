"""Finds everything a cell needs by name, so that a new cell, configuration,
traffic mix or per-layer metric is new files and new entries, and never an
edit here.

    BENCHMARK.json                  cells, configurations, metrics
    bench/configs/<config>.json     a deployment: sizes, law, guarantees
    bench/traffic/<mix>.json        a traffic mix, read by bench/traffic.py
    bench/limits/<cell>.json        the limit on each number ``correct`` compares
    bench/metrics/<metric>.py       a per-layer metric: ``read(run) -> float | None``
    bench/peaks.json                device peaks, keyed by JAX's device_kind
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

#: the checkout: BENCHMARK.json sits here, the benchmark under bench/
ROOT = Path(__file__).resolve().parent.parent


@dataclass(frozen=True)
class Cell:
    """One entry of BENCHMARK.json's ``workloads`` with its files read."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple  # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def reports(metric: dict, cell: str) -> bool:
    """A metric without ``workloads`` is reported by every cell."""
    return "workloads" not in metric or cell in metric["workloads"]


def _one(entries, name, what):
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise KeyError(f"BENCHMARK.json has {len(found)} {what} named {name!r}")
    return found[0]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bm = load_benchmark(root)
    w = _one(bm["workloads"], name, "workloads")
    cfg = _one(bm["configs"], w["config"], "configs")
    bench = root / "bench"
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=json.loads((root / cfg["file"]).read_text()),
        traffic=json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((bench / "limits" / f"{name}.json").read_text()),
        end_to_end=tuple(m for m in bm["end_to_end"] if reports(m, name)),
        per_layer=tuple(m for m in bm["per_layer"] if reports(m, name)),
    )


def load_module(path: Path, name: str):
    """Import a file of the benchmark by path (metrics, references, laws)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    """The ``read`` function of bench/metrics/<name>.py."""
    return load_module(root / "bench" / "metrics" / f"{name}.py",
                       f"bench_metric_{name.replace('.', '_')}").read


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"bench/peaks.json has no peaks for device {device_kind!r}")
    return table["devices"][device_kind]
