"""Reduce a traced job to the names the program gives its work.

  python chipbench/scopes.py [trace_dir]     # the reduction, as JSON

Device side: the self time of every op of the ``XLA Ops`` line inside
the traced job (the harness's host span ``job``), summed by executable
and by the path of the program's named scopes (``SCOPES``) in the op's
``op_name`` metadata, outermost first (``qlinear/matmul``), and the
self time of the collective ops (``COLLECTIVES``, with their async
``-start`` and ``-done`` halves) by executable, collective and scope
path.  A TPU
trace's op events carry no ``op_name``: it is read from the HLO text of
the executable the op ran in, keyed by executable and op name.  The
profiler keeps each executable's compiled HLO (the text
``compiled.as_text()`` prints) in the trace's ``/host:metadata`` plane
(``hlo_texts``).  An op with no scope of its own (a copy or an async
transfer the compiler put in) takes the scope of the op that produced
its first operand.

Host side: the program's spans (``serve.*``, ``ServeLoop``'s) inside the
job: the host time of each wave outside ``serve.wait``, and the args the
``serve.chunk`` spans (counter, steps, rows) and ``serve.prefill`` spans
(real and padded tokens) carry.  The busy intervals, self times and
executable names are ``trace.py``'s; the idle gaps are left to it.

Every number is ``None`` or empty where the trace holds none of the
program's names (a program that does not name its work): the readers in
``metrics/`` then report nothing.  They take the reduction through
``for_ctx``, which holds it to the window of the trace the harness
reduced.  Times are averaged over TPU planes.
"""
from __future__ import annotations

import bisect
import json
import math
import os
import re
import sys
from collections import defaultdict
from functools import lru_cache

import trace as trace_lib

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")  # run.py writes it
WINDOW_SPAN = trace_lib.WINDOW_SPAN
SPAN_PREFIX = "serve."
WAIT_SPAN = "serve.wait"
STACK = "layers"  # the layer scan: every block's work runs inside it
SCOPES = ("embed", STACK, "norm", "qlinear", "act_quant", "weight_dequant",
          "matmul", "attention", "kv_gather", "attend", "kv_append",
          "kv_convert", "mlp_act", "residual", "head", "sample")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
TOP_OPS = 5       # ops listed per executable and scope path
FOLLOW = 8        # operand links followed to find an unscoped op's scope

# "[ROOT ][%]name = shape opcode(operands)...": a shape is one token, or
# a tuple in parentheses whose layouts hold parentheses of their own
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = "
                    r"(?:\((?:[^()]|\([^()]*\))*\)|\S+) [\w\-]+\((.*)$")
_META_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# an op's opcode in its instruction text; a collective's op name or
# opcode starts with its kind (``all-reduce.3``, ``all-gather-start.1``,
# ``all-reduce-done``, a fusion named after the collective it holds)
_OPCODE = re.compile(r" = (?:\((?:[^()]|\([^()]*\))*\)|\S+) ([\w\-]+)\(")
_COLLECTIVE = re.compile("|".join(COLLECTIVES))
# a computation's first line, and the computation an op calls
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def scope_path(op_name: str) -> tuple:
    """The program's scopes in an ``op_name``, outermost first."""
    return tuple(p for p in op_name.split("/") if p in SCOPES)


def _split_instr(line: str):
    """(name, op_name or None, operand names) of one HLO instruction
    line, or None."""
    m = _INSTR.match(line)
    if not m:
        return None
    name, rest = m.groups()
    meta = _META_OP_NAME.search(rest)
    # operands: inside the call's parentheses (attributes follow "), "),
    # each "[shape ]name"
    args = rest.split("), ")[0].rstrip(")")
    operands = [a.split(" ")[-1].lstrip("%") for a in args.split(", ") if a]
    return name, meta.group(1) if meta else None, operands


def hlo_ops(text: str) -> dict:
    """{op name: (scope path, operand names)} of an HLO module's text."""
    out = {}
    for line in text.splitlines():
        got = _split_instr(line)
        if got:
            name, op_name, operands = got
            out[name] = (scope_path(op_name or ""), operands)
    return out


def _fields(buf):
    """(field number, value) of one protobuf message: an int, or a
    memoryview for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            value, i = buf[i:i + width], i + width
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


def _varint(buf, i):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def hlo_texts(xplane_path: str) -> dict:
    """{executable, named as its ``XLA Modules`` events are (``jit_f(5)``):
    HLO text} of the HLO protos the profiler keeps in the ``/host:metadata``
    plane of an ``.xplane.pb`` (XSpace: planes 1; XPlane: name 2, event
    metadata 4, stat metadata 5; XEventMetadata: name 2, stats 5; XStat:
    metadata id 1, bytes 6; HloProto: module 1)."""
    from jax._src.lib import xla_client
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        parts = list(_fields(plane))
        if not any(f == 2 and bytes(v) == b"/host:metadata" for f, v in parts):
            continue
        stat_ids = set()
        for f, entry in parts:
            if f == 5:
                meta = dict(_fields(dict(_fields(entry))[2]))
                if bytes(meta.get(2, b"")) == b"Hlo Proto":
                    stat_ids.add(meta[1])
        for f, entry in parts:
            if f != 4:
                continue
            meta = list(_fields(dict(_fields(entry))[2]))
            name = next(bytes(v).decode() for k, v in meta if k == 2)
            for k, stat in meta:
                stat = dict(_fields(stat)) if k == 5 else {}
                if stat.get(1) in stat_ids and 6 in stat:
                    module = dict(_fields(stat[6]))[1]
                    out[name] = xla_client._xla.HloModule.\
                        from_serialized_hlo_module_proto(
                            bytes(module)).to_string()
    return out


def _resolve(ops: dict, name: str) -> tuple:
    """Scope path of op ``name``; unscoped ops take their first operand's."""
    for _ in range(FOLLOW):
        path, operands = ops.get(name, ((), []))
        if path or not operands:
            return path
        name = operands[0]
    return ()


def hlo_collectives(text: str) -> dict:
    """{op name: collective kind} of the ops of an HLO module's text that
    run a collective: a collective op, or an op that calls a computation
    holding one.  (On v5e an all-reduce whose result each chip keeps a
    slice of is a ``kCustom`` fusion, named ``fusion.N``, that calls an
    ``all-reduce-scatter`` computation.)"""
    held, calls, out = {}, {}, {}
    comp = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        got = _split_instr(line)
        op = _OPCODE.search(line)
        if not got or not op:
            continue
        kind = _COLLECTIVE.match(op.group(1))
        if kind:
            out[got[0]] = held[comp] = kind.group(0)
        callee = _CALLS.search(line)
        if callee:
            calls[got[0]] = (comp, callee.group(1))
    for _ in range(FOLLOW):             # callers of callers, a few deep
        new = {name: held[callee] for name, (_, callee) in calls.items()
               if callee in held and name not in out}
        if not new:
            break
        out.update(new)
        for name in new:
            held.setdefault(calls[name][0], new[name])
    return out


def collective(event_name: str):
    """The collective (one of ``COLLECTIVES``) an ``XLA Ops`` event runs,
    read from its op name or from the opcode in its instruction text, or
    ``None``."""
    m = _COLLECTIVE.match(_short(event_name))
    if m is None:
        op = _OPCODE.search(event_name)
        m = op and _COLLECTIVE.match(op.group(1))
    return m.group(0) if m else None


def _short(event_name: str) -> str:
    """``%fusion.3 = bf16[8]{0} fusion(...)`` -> ``fusion.3``."""
    return event_name.split("=")[0].strip().lstrip("%").split(" ")[0]


def _program_spans(pd):
    """The harness's window span and the program's spans: (name, start,
    end, args)."""
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN or ev.name.startswith(
                            SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.end_ns,
                                      dict(ev.stats)))
    return spans


def _args(spans, name, keys):
    """The ``keys`` args of the ``name`` spans, in time order."""
    return [{k: int(s[3][k]) for k in keys}
            for s in sorted(spans, key=lambda s: s[1])
            if s[0] == name and all(k in s[3] for k in keys)]


def reduce(pd, texts: dict) -> dict:
    """Numbers of the traced job in a ``jax.profiler.ProfileData``, whose
    executables' HLO texts are ``texts`` (``hlo_texts``)."""
    with trace_lib.no_gc():
        return _reduce(pd, texts)


def _reduce(pd, texts: dict) -> dict:
    tables = {k: hlo_ops(t) for k, t in texts.items()}
    coll_tables = {k: hlo_collectives(t) for k, t in texts.items()}
    spans = _program_spans(pd)
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    n = sum(p.name.startswith("/device:TPU:") for p in pd.planes)
    if not windows or not n:
        raise ValueError(f"trace holds {len(windows)} '{WINDOW_SPAN}' "
                         f"spans and {n} TPU planes")
    _, w0, w1, _ = windows[0]
    spans = [s for s in spans if s[0] != WINDOW_SPAN
             and s[1] < w1 and s[2] > w0]

    scopes = defaultdict(lambda: defaultdict(float))   # module -> path -> ns
    ops = defaultdict(float)                  # (module, path, op) -> ns
    colls = defaultdict(float)                # (module, kind, path) -> ns
    named = {}     # (executable, event name) -> (module, path, op, kind)
    for lines, self_times in trace_lib.device_planes(pd, w0, w1):
        mods = sorted((ev.start_ns, ev.end_ns, ev.name.strip())
                      for ev in lines.get("XLA Modules", []))
        for ev, t in self_times:
            if t > 0:
                exe = _enclosing(mods, ev) or ""
                name = ev.name
                got = named.get((exe, name))
                if got is None:        # an op runs once a layer and step
                    got = named[exe, name] = (
                        trace_lib._exec_name(exe),
                        "/".join(_resolve(tables.get(exe, {}),
                                          _short(name))),
                        trace_lib._op_name(name),
                        collective(name) or coll_tables.get(exe, {}).get(
                            _short(name)))
                module, path, op, kind = got
                scopes[module][path] += t
                ops[module, path, op] += t
                if kind:
                    colls[module, kind, path] += t

    ns = 1e-9
    waves = [s for s in spans if s[0] == "serve.wave"]
    waits = trace_lib._union([(s[1], s[2]) for s in spans
                              if s[0] == WAIT_SPAN])
    sched = sum(s[2] - s[1] - _overlap(waits, s[1], s[2]) for s in waves)
    by_kind = defaultdict(lambda: defaultdict(dict))
    for (m, kind, p), t in colls.items():
        by_kind[m][kind][p] = t / n * ns
    top = defaultdict(lambda: defaultdict(list))
    for (m, p, op), t in sorted(ops.items(), key=lambda kv: -kv[1]):
        if len(top[m][p]) < TOP_OPS:
            top[m][p].append([op, t / n * ns])
    return {
        "window_s": (w1 - w0) * ns,
        "scopes": {m: {p: t / n * ns for p, t in d.items()}
                   for m, d in scopes.items()},
        "top_ops": {m: dict(d) for m, d in top.items()},
        "collectives": {m: dict(d) for m, d in by_kind.items()},
        "spans": {name: sum(s[2] - s[1] for s in spans if s[0] == name) * ns
                  for name in sorted({s[0] for s in spans})},
        "sched_host_s": sched * ns if waves else None,
        "chunks": _args(spans, "serve.chunk", ("pos", "steps", "rows")),
        "prefills": _args(spans, "serve.prefill", ("tokens", "padded")),
    }


def _enclosing(mods, ev):
    """Name of the ``XLA Modules`` event (sorted (start, end, name))
    that holds ``ev`` in time."""
    i = bisect.bisect_right(mods, (ev.start_ns, float("inf"), "")) - 1
    if i >= 0 and mods[i][1] >= ev.end_ns:
        return mods[i][2]
    return None


def _overlap(union, s, e):
    return sum(max(0, min(e, b) - max(s, a)) for a, b in union)


def _paths(reduced: dict, modules) -> dict:
    paths = defaultdict(float)
    for m in modules:
        for p, t in reduced["scopes"].get(m, {}).items():
            paths[p] += t
    return paths


def scoped_seconds(reduced: dict, modules, scope: str):
    """Device seconds, in the executables ``modules``, of the ops under
    ``scope`` (its children included) that run no collective (those are
    ``collective_seconds``'); ``None`` where those executables ran no op
    under any of the program's scopes."""
    paths = _paths(reduced, modules)
    if not any(p for p in paths):
        return None
    coll = sum(t for m in modules
               for by_path in reduced["collectives"].get(m, {}).values()
               for p, t in by_path.items() if scope in p.split("/"))
    return sum(t for p, t in paths.items() if scope in p.split("/")) - coll


def collective_seconds(reduced: dict, modules):
    """Device seconds of the collective ops in the executables
    ``modules``; ``None`` where those executables ran none."""
    total = sum(t for m in modules
                for paths in reduced["collectives"].get(m, {}).values()
                for t in paths.values())
    return total or None


def coverage(reduced: dict, modules) -> dict:
    """Shares, in %, of the device time of the executables ``modules``:
    ops under a scope of the layer's own work (``named``), ops under the
    layer stack alone (``stack``: the scan slicing each layer's weights
    and cache out of the stacks and writing the cache back), and ops under
    no scope (``unscoped``)."""
    paths = _paths(reduced, modules)
    total = sum(paths.values())
    if not total:
        return {}
    stack = paths.get(STACK, 0.0)
    unscoped = paths.get("", 0.0)
    return {"named": 100.0 * (total - stack - unscoped) / total,
            "stack": 100.0 * stack / total,
            "unscoped": 100.0 * unscoped / total}


@lru_cache(maxsize=4)
def _read(path: str, mtime_ns: int):
    return reduce(trace_lib.load(path, mtime_ns), hlo_texts(path))


def read_dir(trace_dir: str = TRACE_DIR):
    """The reduction of the newest trace under ``trace_dir`` (read once
    per file and version), or ``None`` where there is none."""
    try:
        path = trace_lib.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    return _read(path, os.stat(path).st_mtime_ns)


def for_ctx(ctx: dict):
    """The reduction of the trace the harness reduced into ``ctx`` (the
    one whose window is ``ctx["trace"]``'s), or ``None``."""
    r = read_dir()
    if r is None or not math.isclose(r["window_s"],
                                     ctx["trace"]["window_s"],
                                     rel_tol=1e-9):
        return None
    return r


def decode_modules() -> list:
    """The fused decode loop's executables (``executables.json``)."""
    return trace_lib.executables()["decode_loop"]


def decode_steps(reduced: dict):
    """(steps, row-steps) the traced job's ``serve.chunk`` spans carry."""
    return (sum(c["steps"] for c in reduced["chunks"]),
            sum(c["steps"] * c["rows"] for c in reduced["chunks"]))


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    r = read_dir(argv[0] if argv else TRACE_DIR)
    if r is not None:
        r["coverage"] = {kind: coverage(r, names) for kind, names
                         in trace_lib.executables().items()}
    print(json.dumps(r, indent=1))


if __name__ == "__main__":
    main()
