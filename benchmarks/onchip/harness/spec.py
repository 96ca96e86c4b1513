"""Find the benchmark's pieces by name.

``BENCHMARK.json`` (at the checkout root) pairs configurations, traffic
mixes and metrics into cells.  Each piece lives in a file of its own
under ``benchmarks/onchip`` and is found by the name the cell gives it:

  configs/<config>.json   the model as it is run (sizes, slots, limits)
  traffic/<mix>.json      parameters of one traffic mix
  metrics/<metric>.py     a reader with ``read(run) -> float | None``
  mixers/<kind>.py        reference maths and cost model of a mixer kind

So a new cell, mix, config, metric or mixer kind is new files and
entries, never an edit of an existing file.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parents[1]      # benchmarks/onchip
ROOT = HERE.parents[1]                           # checkout root


def _names(directory: Path, suffix: str):
    return sorted(p.name[:-len(suffix)] for p in directory.glob(f"*{suffix}"))


def _find(directory: Path, name: str, suffix: str) -> Path:
    path = directory / f"{name}{suffix}"
    if not path.is_file():
        raise KeyError(f"nothing named {name!r} in {directory}; have "
                       f"{_names(directory, suffix)}")
    return path


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find_workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r}; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def load_config(name: str, base: Path = HERE) -> dict:
    with open(_find(base / "configs", name, ".json")) as f:
        cfg = json.load(f)
    cfg.setdefault("name", name)
    return cfg


def load_traffic(name: str, base: Path = HERE) -> dict:
    with open(_find(base / "traffic", name, ".json")) as f:
        mix = json.load(f)
    mix.setdefault("name", name)
    return mix


def _load_module(path: Path, prefix: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{path.stem.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str, base: Path = HERE) -> ModuleType:
    mod = _load_module(_find(base / "metrics", name, ".py"), "metric")
    if not callable(getattr(mod, "read", None)):
        raise TypeError(f"metric {name!r} has no read(run) function")
    return mod


def load_mixer(kind: str, base: Path = HERE) -> ModuleType:
    return _load_module(_find(base / "mixers", kind, ".py"), "mixer")


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    """The metric entries a run of ``workload`` reports: the end-to-end
    ones without tracing, the per-layer ones with it.  An entry with a
    ``workloads`` list applies to those cells only."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in bench[key]
            if workload in m.get("workloads", [workload])]
