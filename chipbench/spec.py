"""Find what a cell of ``BENCHMARK.json`` is made of, by the names it gives.

Each piece sits in a file of its own under ``chipbench/``:

  configs/<config>.json     the model configuration as it is run
  traffic/<traffic>.json    the traffic mix, read by ``traffic.py``
  limits/<workload>.json    the correctness limit of the cell and its sample
  metrics/<metric>.py       one reader per per-layer metric (``read(ctx)``)
  references/<name>.py      the plain reference a configuration names

A cell, a configuration or a metric is added by adding files and entries;
nothing here changes.  An unknown name raises ``KeyError``.
``listing_faults`` holds a benchmark to the rules its listing keeps, and
``use_root`` reads another benchmark than this checkout's (a copy that
holds a candidate cell).
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# per-layer metrics whose readers find something in every cell: they carry
# no ``workloads`` list, so a cell added later reports them too
EVERY_CELL = ("device_idle_share", "decode_row_util", "decode_step_ms",
              "prefill_us_per_tok", "mfu", "decode_weight_bw_share",
              "decode_linear_ms", "decode_attn_ms", "decode_linear_bw_share",
              "decode_attn_bw_share", "sched_host_ms_per_chunk",
              "prefill_pad_share")
# read only where the decode loop runs collectives: listed for exactly the
# cells of more than one chip
MESH_ONLY = "decode_collective_ms"


def load_benchmark(root: str = None) -> dict:
    with open(os.path.join(ROOT if root is None else root,
                           "BENCHMARK.json")) as f:
        return json.load(f)


def use_root(root: str) -> dict:
    """Find every piece under ``root`` from here on (a checkout, or a copy
    of its ``BENCHMARK.json`` and ``chipbench/`` with a candidate cell
    added), and return its ``BENCHMARK.json``.  The program stays the one
    this checkout holds."""
    global HERE, ROOT
    ROOT = os.path.abspath(root)
    HERE = os.path.join(ROOT, os.path.basename(HERE))
    return load_benchmark()


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


def listing_faults(bench: dict) -> list:
    """What breaks the rules the benchmark's listing keeps, one line
    each (none: it keeps them).  Every name is unique and every cell
    loads by name; each configuration's ``file`` is the file read for it,
    and some cell runs it; each per-layer metric has a reader and moves
    an end-to-end metric; a ``workloads`` list names cells of the
    benchmark; the metrics of ``EVERY_CELL`` carry no such list, and
    ``MESH_ONLY``, where listed, lists exactly the cells of more than one
    chip (and is listed where there is one)."""
    faults = []
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    faults += [f"{n!r} names two entries" for n in sorted(set(names))
               if names.count(n) > 1]
    cells = [w["name"] for w in bench["workloads"]]
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        try:
            path = _path("configs", c["name"], ".json")
        except KeyError as e:
            faults.append(str(e))
            continue
        if os.path.normpath(os.path.join(ROOT, c["file"])) != path:
            faults.append(f"config {c['name']!r}: file {c['file']!r} is "
                          f"not the one read, {os.path.relpath(path, ROOT)}")
        if c["name"] not in used:
            faults.append(f"config {c['name']!r} is run by no cell")
    for name in cells:
        try:
            c = cell(bench, name)
            if c["config"]["name"] != c["workload"]["config"]:
                faults.append(f"cell {name!r}: its configuration file "
                              f"names {c['config']['name']!r}")
            ref = reference(c["config"]["reference"])
            if not (callable(getattr(ref, "served_logits", None))
                    and callable(getattr(ref, "affine_params", None))):
                faults.append(f"cell {name!r}: reference "
                              f"{c['config']['reference']!r} lacks "
                              f"served_logits or affine_params")
            if not int(c["traffic"]["batch_size"]) >= 1:
                faults.append(f"cell {name!r}: batch_size under 1")
            if not (float(c["limits"]["max_logit_gap"]) > 0
                    and int(c["limits"]["sample_requests"]) >= 1):
                faults.append(f"cell {name!r}: limits hold no positive "
                              f"max_logit_gap and sample")
        except KeyError as e:
            faults.append(f"cell {name!r}: {e}")
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        name, listed = m["name"], m.get("workloads")
        try:
            if not callable(metric_reader(name)):
                faults.append(f"metric {name!r}: its reader is no function")
        except KeyError as e:
            faults.append(str(e))
        if m["moves"] not in end_to_end:
            faults.append(f"metric {name!r} moves {m['moves']!r}, no "
                          f"end-to-end metric")
        if listed is not None:
            faults += [f"metric {name!r} lists {w!r}, no cell"
                       for w in listed if w not in cells]
        if name in EVERY_CELL and listed is not None:
            faults.append(f"metric {name!r} reads every cell and carries "
                          f"a workloads list")
    mesh_cells = [w["name"] for w in bench["workloads"] if w["chips"] > 1]
    mesh_only = [m.get("workloads") for m in bench["per_layer"]
                 if m["name"] == MESH_ONLY]
    if mesh_only != ([mesh_cells] if mesh_cells else []):
        faults.append(f"{MESH_ONLY} is listed for {mesh_only}, not for "
                      f"the cells of more than one chip {mesh_cells}")
    return faults
