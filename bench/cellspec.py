"""Find a cell's configuration, traffic mix, settings and metric readers by name.

Everything a cell needs lives in files of its own, found from the names in
``BENCHMARK.json``:

- ``bench/configs/<config>.json``: the model configuration as it is run;
- ``bench/mixes/<traffic>.json``: the traffic mix (lengths, arrivals);
- ``bench/cells/<workload>.json``: the server's settings for the cell and
  the limits that decide ``correct``;
- ``bench/metrics/<metric>.py``: one reader per metric.  A metric named
  ``<base>.<suffix>`` may share ``bench/metrics/<base>.py`` with its siblings.

Adding a configuration, a mix, a cell or a metric adds files and entries; no
existing file changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


class SpecError(ValueError):
    """A name in BENCHMARK.json has no file, or a file is malformed."""


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    moves: Optional[str]          # per-layer metrics only
    layer: Optional[str]
    workloads: Optional[List[str]]
    end_to_end: bool


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic: str
    config: dict
    mix: dict
    settings: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]
    run_seconds: int


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {os.path.relpath(path)}") from None


def benchmark_path(bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json")


def _metrics(entries: List[dict], end_to_end: bool) -> List[Metric]:
    return [Metric(name=m["name"], unit=m["unit"], better=m["better"],
                   source=m["source"], moves=m.get("moves"),
                   layer=m.get("layer"), workloads=m.get("workloads"),
                   end_to_end=end_to_end)
            for m in entries]


def load_cell(workload: str, bench_dir: str = BENCH_DIR) -> Cell:
    """Everything the run of one cell needs, found by the cell's name."""
    bench = _read_json(benchmark_path(bench_dir))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json; "
                        f"known: {', '.join(sorted(cells))}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    conf_entry = configs[w["config"]]
    config = _read_json(os.path.join(os.path.dirname(bench_dir),
                                     conf_entry["file"]))
    mix = _read_json(os.path.join(bench_dir, "mixes", f"{w['traffic']}.json"))
    settings = _read_json(os.path.join(bench_dir, "cells", f"{workload}.json"))

    e2e = [m for m in _metrics(bench["end_to_end"], True)
           if m.workloads is None or workload in m.workloads]
    e2e_names = {m.name for m in e2e}
    per_layer = [m for m in _metrics(bench["per_layer"], False)
                 if (workload in m.workloads if m.workloads is not None
                     else m.moves in e2e_names)]
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], traffic=w["traffic"],
                config=config, mix=mix, settings=settings,
                end_to_end=e2e, per_layer=per_layer,
                run_seconds=int(bench["run_seconds"]))


def metric_reader(name: str, bench_dir: str = BENCH_DIR
                  ) -> Callable[[object, Metric], Optional[float]]:
    """The ``read(run, metric)`` function of ``bench/metrics/<name>.py``, or
    of ``bench/metrics/<base>.py`` for a metric named ``<base>.<suffix>``."""
    mdir = os.path.join(bench_dir, "metrics")
    for stem in (name, name.split(".", 1)[0]):
        path = os.path.join(mdir, f"{stem}.py")
        if os.path.exists(path):
            mod_name = "bench_metric_" + "".join(
                ch if ch.isalnum() else "_" for ch in stem)
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise SpecError(f"no reader bench/metrics/{name}.py for metric {name!r}")


def readers_for(metrics: List[Metric], bench_dir: str = BENCH_DIR
                ) -> Dict[str, Callable]:
    return {m.name: metric_reader(m.name, bench_dir) for m in metrics}
