"""The trace reducer on a small recorded trace with known numbers."""
import pytest
from jax.profiler import ProfileData

import trace as trace_lib

EXECS = {"prefill": ["jit__lambda"], "decode_loop": ["jit_f"]}


def _line(lid, name, events):
    ev = "\n".join(f"    events {{ metadata_id: {m} offset_ps: {s * 1000} "
                   f"duration_ps: {(e - s) * 1000} }}" for m, s, e in events)
    return f"  lines {{ id: {lid} name: \"{name}\" timestamp_ns: 0\n{ev}\n  }}"


def _plane(pid, name, lines, names):
    meta = "\n".join(f"  event_metadata {{ key: {i} value {{ id: {i} "
                     f"name: \"{n}\" }} }}" for i, n in names.items())
    return f"planes {{\n  id: {pid}\n  name: \"{name}\"\n" + \
        "\n".join(lines) + "\n" + meta + "\n}"


def recorded():
    """One job [0, 1000) ns: a prefill and a decode loop on the device,
    an executable the metrics do not read, and host spans."""
    dev_names = {1: "jit__lambda(7)", 2: "jit_f(9)", 3: "jit_other(2)",
                 4: "%fusion.1 = bf16[8,128]{1,0:T(8,128)} fusion(%p)",
                 5: "fusion.2", 6: "dot.3", 7: "copy.4", 8: "%while.5 = (s32[])",
                 9: "add.6"}
    dev = _plane(1, "/device:TPU:0", [
        _line(1, "XLA Modules", [(1, 100, 300), (2, 500, 900),
                                 (3, 950, 990)]),
        _line(2, "XLA Ops", [(4, 100, 200), (5, 150, 300), (8, 500, 900),
                             (6, 520, 800), (9, 800, 900), (7, 950, 990)]),
    ], dev_names)
    host = _plane(2, "/host:CPU", [
        _line(1, "python", [(1, 0, 1000), (2, 50, 120), (3, 400, 450)]),
    ], {1: "job", 2: "prefill", 3: "chunk"})
    xspace = ProfileData.text_proto_to_serialized_xspace(dev + "\n" + host)
    return ProfileData.from_serialized_xspace(xspace)


def test_busy_idle_and_executables():
    r = trace_lib.reduce(recorded(), EXECS)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(640e-9)          # 200 + 400 + 40
    assert r["exec"]["prefill"] == {"seconds": pytest.approx(200e-9),
                                    "count": 1}
    assert r["exec"]["decode_loop"] == {"seconds": pytest.approx(400e-9),
                                        "count": 1}


def test_breakdown_names_ops_and_gaps():
    r = trace_lib.reduce(recorded(), EXECS)
    # the while loop's own time is what its body ops leave: 400-280-100
    assert [n for n, _ in r["device_ops"]] == [
        "dot.3", "fusion.2", "add.6", "fusion.1 = bf16[8,128]", "copy.4",
        "while.5 = (s32[])"]
    assert [t for _, t in r["device_ops"]] == pytest.approx(
        [280e-9, 150e-9, 100e-9, 100e-9, 40e-9, 20e-9])
    assert r["modules"]["jit_other"] == pytest.approx(40e-9)
    gaps = [(n, pytest.approx(t)) for n, t in r["idle_gaps"]]
    assert gaps == [("chunk", 200e-9), ("prefill", 100e-9),
                    ("job", 50e-9), ("job", 10e-9)]


def test_no_window_raises():
    xspace = ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 1 name: "/device:TPU:0" }')
    with pytest.raises(ValueError, match="job"):
        trace_lib.reduce(ProfileData.from_serialized_xspace(xspace), EXECS)


def test_executable_names_are_data():
    execs = trace_lib.executables()
    assert set(execs) == {"prefill", "decode_loop"}
