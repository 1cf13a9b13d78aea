"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

A cell names a configuration (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<traffic>.json``), and is served by one one-chip
replica per chip it asks for (``bench/run.py``); each metric is read by
``bench/metrics/<module>.py``, where the module is the metric's name with
``.`` and ``-`` written as ``_``; each kernel's work count is
``bench/work/<kernel>.py``; the correctness limits of a cell are in
``bench/limits/<cell>.json``. Adding a cell or a metric adds files and
entries and edits none.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"


class SpecError(Exception):
    pass


def _load(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def benchmark() -> Dict[str, Any]:
    return _load(ROOT / "BENCHMARK.json")


def workload(name: str) -> Dict[str, Any]:
    for cell in benchmark()["workloads"]:
        if cell["name"] == name:
            return cell
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> Dict[str, Any]:
    return _load(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> Dict[str, Any]:
    return _load(BENCH / "traffic" / f"{name}.json")


def limits(cell: str) -> Dict[str, float]:
    return _load(BENCH / "limits" / f"{cell}.json")["limits"]


def module_name(metric: str) -> str:
    return metric.replace(".", "_").replace("-", "_")


def reader(metric: str):
    """The ``read(run)`` function of a metric's own module."""
    mod = importlib.import_module(f"bench.metrics.{module_name(metric)}")
    return mod.read


def cell_metrics(cell: str, kind: str) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell`` reports:
    those without a ``workloads`` key, and those that list it."""
    return [m for m in benchmark()[kind]
            if "workloads" not in m or cell in m["workloads"]]


def kernels() -> Dict[str, tuple]:
    """Each kernel with a work count (``bench/work/<kernel>.py``), and the
    names its events carry in a device trace."""
    out = {}
    for path in sorted((BENCH / "work").glob("*.py")):
        if path.stem != "__init__":
            mod = importlib.import_module(f"bench.work.{path.stem}")
            out[path.stem] = tuple(mod.TRACE_NAMES)
    return out


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of ``device_kind``; an unknown device is an error."""
    table = _load(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        "bench/peaks.json")
    return table[device_kind]


# ------------------------------------------------------- program objects


def search_space(cfg: Dict[str, Any]):
    from repro.core import Categorical, Continuous, Integer, SearchSpace

    params = []
    for p in cfg["space"]:
        if p["kind"] == "categorical":
            params.append(Categorical(p["name"], p["choices"]))
        elif p["kind"] == "integer":
            params.append(Integer(p["name"], int(p["low"]), int(p["high"]),
                                  scaling=p["scaling"]))
        else:
            params.append(Continuous(p["name"], float(p["low"]),
                                     float(p["high"]), scaling=p["scaling"]))
    return SearchSpace(params)


def bo_config(cfg: Dict[str, Any]):
    from repro.core import BOConfig
    from repro.core.gp.slice_sampler import SliceSamplerConfig
    from repro.core.optimize_acq import AcqOptConfig

    engine = dict(cfg["engine"])
    engine["slice_config"] = SliceSamplerConfig(**engine["slice_config"])
    engine["acq"] = AcqOptConfig(**engine["acq"])
    return BOConfig(**engine)


def service_config(cfg: Dict[str, Any]):
    from repro.core import ServiceConfig

    return ServiceConfig(default_bo_config=bo_config(cfg), **cfg["service"])
