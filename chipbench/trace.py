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

import glob
import json
import os
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
WINDOW_SPAN = "job"
HOST_SPANS = ("job", "prefill", "chunk")
TOP = 10


def executables() -> dict:
    """Kind (``prefill``, ``decode_loop``) -> executable names."""
    with open(os.path.join(HERE, "executables.json")) as f:
        return {k: v for k, v in json.load(f).items() if k != "about"}


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
    """(name, self time in the window) of each event of one line, less the
    time of the events nested in it."""
    out = []
    stack = []                       # [end, index in out]
    for ev in sorted(events, key=lambda ev: (ev.start_ns, -ev.end_ns)):
        while stack and stack[-1][0] <= ev.start_ns:
            stack.pop()
        s, e = _clip(ev.start_ns, ev.end_ns, w0, w1)
        dur = max(e - s, 0.0)
        if stack and ev.end_ns <= stack[-1][0]:       # nested, not overlapping
            out[stack[-1][1]][1] -= dur
        out.append([ev.name, dur])
        stack.append([ev.end_ns, len(out) - 1])
    return out


def reduce(pd, execs: dict = None) -> dict:
    """Numbers of one traced window from a ``jax.profiler.ProfileData``."""
    execs = executables() if execs is None else execs
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
    modules = defaultdict(float)
    gaps = []
    for plane in devices:
        lines = {line.name: list(line.events) for line in plane.lines}
        ops = lines.get("XLA Ops") or lines.get("XLA Modules") or []
        busy = _union([_clip(ev.start_ns, ev.end_ns, w0, w1) for ev in ops
                       if ev.end_ns > w0 and ev.start_ns < w1])
        busy_total += sum(e - s for s, e in busy)
        for name, t in _self_times(lines.get("XLA Ops", []), w0, w1):
            if t > 0:
                op_s[_op_name(name)] += t
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
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                mid = (s + e) / 2
                open_ = [sp for sp in inner if sp[1] <= mid <= sp[2]]
                name = (min(open_, key=lambda sp: sp[2] - sp[1])[0]
                        if open_ else "outside")
                gaps.append((name, e - s))
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
        "idle_gaps": [[name, t * ns] for name, t in sorted(
            gaps, key=lambda g: -g[1])[:TOP]],
        "modules": {k: t / n * ns for k, t in modules.items()},
    }


def reduce_dir(trace_dir: str, execs: dict = None) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(trace_dir)), execs)
