"""Find a cell's pieces by name.

``BENCHMARK.json`` at the root names the cells; everything that belongs to
one configuration, one traffic mix, one cell's limits or one per-layer
metric sits in a file of its own under ``bench/``:

* ``bench/configs/<config>.json``   the model's sizes and its cut
* ``bench/traffic/<traffic>.json``  the FL job (workers, steps, channel, mesh)
* ``bench/limits/<workload>.json``  the limits the ``correct`` check holds
* ``bench/metrics/<metric>.py``     a reader with ``read(ctx)``
* ``bench/models/<model_type>.py``  the plain model of a configuration's
  ``model_type``: its weights, its loss and its FLOP count
* ``bench/systems/<system>.py``     the program's trainer that a traffic
  file's ``system`` names, built for the cell
* ``bench/references/<reference>.py``  the plain rounds that a traffic
  file's ``reference`` names, which ``correct`` compares with

A later change adds a cell, a mix, a metric, an architecture or a trainer
by adding files and entries.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    """A name that the benchmark's files do not resolve."""


def _load_json(path: str) -> Dict[str, Any]:
    if not os.path.isfile(path):
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files read."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    run_seconds: int


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_config(name: str, bench: Optional[Dict[str, Any]] = None,
                root: str = ROOT) -> Dict[str, Any]:
    bench = bench if bench is not None else load_benchmark(root)
    for c in bench["configs"]:
        if c["name"] == name:
            return _load_json(os.path.join(root, c["file"]))
    raise SpecError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    return _load_json(os.path.join(bench_dir, "traffic", f"{name}.json"))


def load_limits(workload: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    return _load_json(os.path.join(bench_dir, "limits", f"{workload}.json"))


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, bench: Optional[Dict[str, Any]] = None,
              root: str = ROOT) -> Cell:
    """The workload's cell; ``root`` holds ``BENCHMARK.json`` and
    ``bench/``."""
    bench = bench if bench is not None else load_benchmark(root)
    bench_dir = os.path.join(root, "bench")
    for w in bench["workloads"]:
        if w["name"] == workload:
            break
    else:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json; known: "
                        f"{[w['name'] for w in bench['workloads']]}")
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=load_config(w["config"], bench, root),
        traffic=load_traffic(w["traffic"], bench_dir),
        limits=load_limits(workload, bench_dir),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        run_seconds=int(bench["run_seconds"]))


@functools.lru_cache(maxsize=None)
def _module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded once."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise SpecError(f"no file bench/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """``bench/metrics/<metric>.py``; its ``read(ctx)`` returns the
    metric's value, or None where the run gave it nothing to read."""
    return _module("metrics", metric)


def load_model(config: Dict[str, Any]):
    """``bench/models/<model_type>.py`` for a configuration."""
    return _module("models", config["model_type"])


def load_system(traffic: Dict[str, Any]):
    """``bench/systems/<system>.py``; its ``System`` is the program."""
    return _module("systems", traffic["system"])


def load_reference(traffic: Dict[str, Any]):
    """``bench/references/<reference>.py``; its ``run`` the plain rounds."""
    return _module("references", traffic["reference"])
