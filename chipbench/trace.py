"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The traced window is the harness's host span ``job`` (one job of the
cell's traffic).  On each TPU plane:

* busy time is the union of the intervals of the ``XLA Ops`` line (the
  ``XLA Modules`` line where a plane has no op line), clipped to the
  window; idle time is the rest of the window;
* each executable's device time is the sum of its ``XLA Modules`` events
  in the window, found by the names in ``executables.json`` (the part
  of an event name before its ``(`` id);
* the top device ops are the ``XLA Ops`` with the most self time (an op
  such as a ``while`` loop contains the ops of its body; their time is
  taken out of its own), named by the HLO op name and result shape, and
  the longest idle gaps are named after the innermost host span of the
  harness (``job``, ``prefill``, ``chunk``) open at the gap's middle.

Times are averaged over the TPU planes found (one per chip used).
"""
from __future__ import annotations

import gc
import glob
import json
import os
from collections import defaultdict
from contextlib import contextmanager
from functools import lru_cache
from typing import NamedTuple

HERE = os.path.dirname(os.path.abspath(__file__))
WINDOW_SPAN = "job"
HOST_SPANS = ("job", "prefill", "chunk")
DEVICE_LINES = ("XLA Ops", "XLA Modules")     # the lines of a TPU plane read
TOP = 10


def executables() -> dict:
    """Kind (``prefill``, ``decode_loop``) -> executable names."""
    with open(os.path.join(HERE, "executables.json")) as f:
        return {k: v for k, v in json.load(f).items() if k != "about"}


class Event(NamedTuple):
    """An event of a trace line, read out of the profiler's object once
    (each read of a profiler event's field crosses into C++)."""
    name: str
    start_ns: float
    end_ns: float


@contextmanager
def no_gc():
    """The cyclic garbage collector off: a trace is read into millions of
    tuples, none of them in a cycle, which its passes would walk again and
    again (more than half the time of a reduction)."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


@lru_cache(maxsize=1)
def load(path: str, mtime_ns: int):
    """The ``jax.profiler.ProfileData`` of the trace file ``path`` (at
    its version ``mtime_ns``), read once for both reductions of it."""
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


@lru_cache(maxsize=1)
def device_planes(pd, w0, w1) -> list:
    """Each TPU plane of ``pd`` read out once for both reductions of it:
    ``({line name: [Event]}, [(event, self time)])``, the self times (in
    the window [w0, w1)) of its ``XLA Ops`` events.  A four-chip job's
    trace holds millions of op events."""
    out = []
    with no_gc():
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                lines = {line.name: [Event(ev.name, ev.start_ns, ev.end_ns)
                                     for ev in line.events]
                         for line in plane.lines if line.name in DEVICE_LINES}
                out.append((lines, _self_times(lines.get("XLA Ops", []),
                                               w0, w1)))
    return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint union."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def _exec_name(event_name: str) -> str:
    return event_name.split("(")[0].strip()


def _op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[8,128]{1,0...} fusion(...)`` -> ``fusion.3 =
    bf16[8,128]``."""
    return event_name.split("{")[0].split(" fusion(")[0].lstrip("%").strip()


def _self_times(events, w0, w1):
    """(event, self time in the window) of each ``Event`` of one line,
    less the time of the events nested in it."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out = []
    ends, idx = [], []               # the stack of open events
    for _, start, end in evs:
        while ends and ends[-1] <= start:
            ends.pop()
            idx.pop()
        dur = (end if end < w1 else w1) - (start if start > w0 else w0)
        if dur < 0.0:
            dur = 0.0
        if ends and end <= ends[-1]:            # nested, not overlapping
            out[idx[-1]] -= dur
        ends.append(end)
        idx.append(len(out))
        out.append(dur)
    return list(zip(evs, out))


def _gap_name(spans, s, e):
    """Name of the innermost of ``spans`` open at the middle of the idle
    gap [s, e), or ``outside``."""
    mid = (s + e) / 2
    open_ = [sp for sp in spans if sp[1] <= mid <= sp[2]]
    return min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_ else "outside"


def reduce(pd, execs: dict = None) -> dict:
    """Numbers of one traced window from a ``jax.profiler.ProfileData``."""
    with no_gc():
        return _reduce(pd, executables() if execs is None else execs)


def _reduce(pd, execs: dict) -> dict:
    spans = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.end_ns)
                          for ev in line.events if ev.name in HOST_SPANS]
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows or not devices:
        raise ValueError(f"trace holds {len(windows)} '{WINDOW_SPAN}' "
                         f"spans and {len(devices)} TPU planes")
    _, w0, w1 = windows[0]
    inner = [s for s in spans if s[1] < w1 and s[2] > w0]

    busy_total = 0.0
    exec_s = defaultdict(float)
    exec_n = defaultdict(int)
    op_s = defaultdict(float)
    op_names = {}
    modules = defaultdict(float)
    gaps = []
    for lines, self_times in device_planes(pd, w0, w1):
        ops = lines.get("XLA Ops") or lines.get("XLA Modules") or []
        busy = _union([(s if s > w0 else w0, e if e < w1 else w1)
                       for _, s, e in ops if e > w0 and s < w1])
        busy_total += sum(e - s for s, e in busy)
        for ev, t in self_times:
            if t > 0:
                name = ev.name
                op = op_names.get(name)
                if op is None:         # an op runs once a layer and step
                    op = op_names[name] = _op_name(name)
                op_s[op] += t
        for ev in lines.get("XLA Modules", []):
            s, e = _clip(ev.start_ns, ev.end_ns, w0, w1)
            if e <= s:
                continue
            name = _exec_name(ev.name)
            modules[name] += e - s
            for kind, names in execs.items():
                if name in names:
                    exec_s[kind] += e - s
                    exec_n[kind] += 1
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps += [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    n = len(devices)
    ns = 1e-9
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": busy_total / n * ns,
        "chips": n,
        "exec": {k: {"seconds": exec_s[k] / n * ns, "count": exec_n[k] // n}
                 for k in execs},
        "device_ops": [[name, t / n * ns] for name, t in sorted(
            op_s.items(), key=lambda kv: (-kv[1], kv[0]))[:TOP]],
        "idle_gaps": [[_gap_name(inner, s, e), (e - s) * ns] for s, e in
                      sorted(gaps, key=lambda g: -(g[1] - g[0]))[:TOP]],
        "modules": {k: t / n * ns for k, t in modules.items()},
    }


def reduce_dir(trace_dir: str, execs: dict = None) -> dict:
    path = find_xplane(trace_dir)
    return reduce(load(path, os.stat(path).st_mtime_ns), execs)
