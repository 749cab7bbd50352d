"""Find what a cell of ``BENCHMARK.json`` is made of, by the names it gives.

Each piece sits in a file of its own under ``chipbench/``:

  configs/<config>.json     the model configuration as it is run
  traffic/<traffic>.json    the traffic mix, read by ``traffic.py``
  limits/<workload>.json    the correctness limit of the cell and its sample
  metrics/<metric>.py       one reader per per-layer metric (``read(ctx)``)
  references/<name>.py      the plain reference a configuration names

A cell, a configuration or a metric is added by adding files and entries;
nothing here changes.  An unknown name raises ``KeyError``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"unknown workload {name!r}; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def _path(kind: str, name: str, ext: str) -> str:
    path = os.path.join(HERE, kind, name + ext)
    if not os.path.isfile(path):
        raise KeyError(f"no {kind} file for {name!r} ({path})")
    return path


def _json(kind: str, name: str) -> dict:
    with open(_path(kind, name, ".json")) as f:
        return json.load(f)


def config(name: str) -> dict:
    return _json("configs", name)


def traffic(name: str) -> dict:
    return _json("traffic", name)


def limits(workload_name: str) -> dict:
    return _json("limits", workload_name)


def _module(kind: str, name: str):
    path = _path(kind, name, ".py")
    mod_name = f"chipbench_{kind}_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``read(ctx) -> float | None`` of per-layer metric ``name``."""
    return _module("metrics", name).read


def reference(name: str):
    return _module("references", name)


def cell(bench: dict, workload_name: str) -> dict:
    """Everything one run of ``workload_name`` reads, loaded by name."""
    w = workload(bench, workload_name)
    return {"workload": w, "config": config(w["config"]),
            "traffic": traffic(w["traffic"]),
            "limits": limits(workload_name)}
