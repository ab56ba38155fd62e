"""Find the benchmark's pieces by name.

``BENCHMARK.json`` at the checkout's root lists the cells and metrics.
Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own under ``bench/``, found by the name the cell
or metric gives:

- ``bench/configs/<config>.json``: one model configuration;
- ``bench/traffic/<traffic>.json``: one traffic mix, whose ``kind``
  names its generator in ``harness.traffic``, with its parameters, an
  open loop's rate among them;
- ``bench/metrics/<metric>.py``, or ``<metric up to its first dot>.py``
  where several metrics share a reader: a ``read(ctx)`` function that
  returns the metric's value, or None where the run holds nothing to
  read;
- ``bench/reference/<reference>.py``: the plain reference a
  configuration names.

A later cell, mix or metric is added by adding files and entries; no
file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bm['workloads']]}")


def config(bm: dict, name: str) -> dict:
    """The configuration's file, with its BENCHMARK.json entry under
    ``"entry"``."""
    for c in bm["configs"]:
        if c["name"] == name:
            out = _load_json(ROOT / c["file"])
            out["entry"] = c
            return out
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return _load_json(BENCH / "traffic" / f"{name}.json")


def _module(path: pathlib.Path, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader_path(metric: str) -> pathlib.Path:
    own = BENCH / "metrics" / f"{metric}.py"
    if own.exists():
        return own
    return BENCH / "metrics" / f"{metric.split('.')[0]}.py"


def metric_reader(metric: str):
    """The ``read(ctx)`` function of a per-layer metric."""
    path = reader_path(metric)
    if not path.exists():
        raise KeyError(f"no reader for metric {metric!r} ({path})")
    return _module(path, f"bench_metric_{path.stem.replace('.', '_')}").read


def reference(name: str):
    """The plain reference module a configuration names."""
    path = BENCH / "reference" / f"{name}.py"
    if not path.exists():
        raise KeyError(f"no reference {name!r} ({path})")
    return _module(path, f"bench_reference_{name}")


def metrics_for(bm: dict, workload_name: str, per_layer: bool) -> list:
    """The metrics a cell reports: its end-to-end ones with --trace 0,
    its per-layer ones with --trace 1."""
    key = "per_layer" if per_layer else "end_to_end"
    return [m for m in bm[key]
            if "workloads" not in m or workload_name in m["workloads"]]
