"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name the
manifest gives it, so a cell or a metric is added with files and
entries alone:

* a configuration's file is the entry's ``file``;
* a traffic mix ``<t>`` is ``traffic/<t>.json``;
* a per-layer metric ``<m>`` is read by ``metrics/<m>.py``, whose
  ``read(ctx)`` returns the value or ``None`` where there is nothing to
  read.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def config(bench: dict, cell: dict, root: str = ROOT) -> dict:
    with open(os.path.join(root, config_entry(bench, cell["config"])
                           ["file"])) as f:
        return json.load(f)


def traffic_path(name: str) -> str:
    return os.path.join(HERE, "traffic", f"{name}.json")


def traffic(cell: dict) -> dict:
    with open(traffic_path(cell["traffic"])) as f:
        return json.load(f)


def reader_path(metric: str) -> str:
    return os.path.join(HERE, "metrics", f"{metric}.py")


def _load_reader(metric: str) -> Callable:
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}",
        reader_path(metric))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def applies(metric: dict, cell: dict, bench: dict) -> bool:
    """A metric with ``workloads`` is read in those cells; one without
    in every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}[metric["moves"]]
    return "workloads" not in e2e or cell["name"] in e2e["workloads"]


def readers(bench: dict, cell: dict
            ) -> Dict[str, Tuple[str, Callable[..., Optional[float]]]]:
    """``{metric: (unit, read)}`` for the per-layer metrics of ``cell``."""
    return {m["name"]: (m["unit"], _load_reader(m["name"]))
            for m in bench["per_layer"] if applies(m, cell, bench)}
