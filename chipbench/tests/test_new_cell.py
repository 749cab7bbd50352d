"""A cell added as data files alone: in a copy of the benchmark that holds
``candidate.CELL`` (new files, and entries appended), the cell loads by
name, the copy keeps every rule of the listing, the harness serves the
cell correct on four devices, and every per-layer metric that covers it
reads a value from a trace of four chips."""
import os

import jax
import pytest
from jax.profiler import ProfileData

import candidate
import run
import scopes
import spec
import trace as trace_lib
import traffic
from test_scopes import _line, _plane

SEED = 2 ** 31 + 41
REPO = spec.ROOT


def _listed(bench, name):
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", [name])]


def test_the_cell_adds_files_and_entries_only(candidate_root):
    for dirpath, dirs, files in os.walk(os.path.join(REPO, "chipbench")):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", "tests")]
        for name in files:
            rel = os.path.relpath(os.path.join(dirpath, name), REPO)
            with open(os.path.join(REPO, rel), "rb") as a, \
                    open(os.path.join(candidate_root, rel), "rb") as b:
                assert a.read() == b.read(), rel
    before = spec.load_benchmark(REPO)
    after = spec.load_benchmark(candidate_root)
    assert set(after) == set(before)
    for key, value in before.items():
        if key in candidate.ENTRIES:
            assert after[key] == value + [candidate.ENTRIES[key]]
        else:
            assert after[key] == value


def test_the_cell_loads_by_name(candidate_root):
    c = spec.cell(spec.load_benchmark(), candidate.CELL)
    assert c["workload"]["chips"] == 4
    assert c["config"]["model"] == candidate.MODEL
    assert c["limits"]["max_logit_gap"] > 0


def test_the_copy_keeps_the_listing_rules(candidate_root):
    bench = spec.load_benchmark()
    assert spec.listing_faults(bench) == []
    names = {m["name"] for m in _listed(bench, candidate.CELL)}
    assert set(spec.EVERY_CELL) | {spec.MESH_ONLY} <= names


def test_the_cell_serves_correct_on_four_devices(candidate_root):
    bench = spec.load_benchmark()
    c = spec.cell(bench, candidate.CELL)
    r = run.run_cell(bench, c, SEED, 0.5, False, devices=jax.devices(),
                     log=lambda m: None)
    assert r["correct"], r["check"]
    assert r["attempted"] >= 4 and r["failed"] == 0
    assert r["device"]["count"] == 4


# -- a traced job on four chips -----------------------------------------

# (op, op_name metadata, HLO opcode) of the prefill and the decode loop
PREFILL = [("fusion.10", "jit(_lambda)/layers/qlinear/matmul/dot_general",
            "fusion")]
LOOP = [("fusion.1", "jit(f)/while/body/layers/qlinear/matmul/dot_general",
         "fusion"),
        ("all-reduce.2", None, "all-reduce"),
        ("fusion.3", "jit(f)/while/body/layers/attention/kv_gather/mul",
         "fusion"),
        ("add.4", None, "add")]
OP_TIMES = [(100, 300), (500, 600), (600, 650), (650, 800), (800, 900)]


def _text(module, ops):
    return f"HloModule {module}\n" + "\n".join(
        f"  %{op} = bf16[8]{{0}} {code}()"
        + (f", metadata={{op_name=\"{on}\"}}" if on else "")
        for op, on, code in ops)


TEXTS = {"jit__lambda(7)": _text("jit__lambda", PREFILL),
         "jit_f(9)": _text("jit_f", LOOP)}


def four_chip_trace(mix):
    """One job [0, 1000) ns on four chips: on each, the prefill
    ``jit__lambda(7)`` [100, 300) and the decode loop ``jit_f(9)`` [500,
    900) (a matmul, an all-reduce, a cache gather, an unscoped add).
    Host: a wave holding the prefill dispatch of the mix's batch, one
    chunk of 32 steps and one wait."""
    names = {1: "jit__lambda(7)", 2: "jit_f(9)"}
    ops = []
    for i, ((op, _, code), (s, e)) in enumerate(zip(PREFILL + LOOP,
                                                    OP_TIMES)):
        names[i + 3] = f"%{op} = bf16[8]{{0}} {code}()"
        ops.append((i + 3, s, e, []))
    planes = [_plane(chip + 1, f"/device:TPU:{chip}", [
        _line(1, "XLA Modules", [(1, 100, 300, []), (2, 500, 900, [])]),
        _line(2, "XLA Ops", ops)], names, {}) for chip in range(4)]
    B = int(mix["batch_size"])
    S = int(mix["prompt_tokens"]["max"])
    host = _plane(9, "/host:CPU", [_line(1, "python", [
        (1, 0, 1000, []), (2, 0, 1000, []),
        (5, 10, 40, [(4, B * S), (5, B * S)]),
        (3, 450, 470, [(1, S), (2, 32), (3, B)]),
        (4, 470, 950, [])])],
        {1: "job", 2: "serve.wave", 3: "serve.chunk", 4: "serve.wait",
         5: "serve.prefill"},
        {1: "pos", 2: "steps", 3: "rows", 4: "tokens", 5: "padded"})
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(
            "\n".join(planes + [host])))


def test_every_metric_that_covers_the_cell_reads_it(candidate_root,
                                                    monkeypatch):
    bench = spec.load_benchmark()
    c = spec.cell(bench, candidate.CELL)
    conf, mix = c["config"], c["traffic"]
    pd = four_chip_trace(mix)
    monkeypatch.setattr(scopes, "read_dir", lambda: scopes.reduce(pd, TEXTS))
    prompts, budgets = traffic.job(mix, SEED)
    B = int(mix["batch_size"])
    job = {"counts": {"prefills": 1, "chunks": 1, "decode_steps": 32,
                      "decode_row_steps": 32 * B,
                      "prefill_padded_tokens": B * 64},
           "served": [[1] * n for n in budgets]}
    ctx = run.layer_ctx(trace_lib.reduce(pd),
                        run.job_numbers(conf, mix, prompts, job), conf, mix,
                        "TPU v5 lite", 4)
    read = {m["name"]: spec.metric_reader(m["name"])(ctx)
            for m in _listed(bench, candidate.CELL)}
    assert [n for n, v in read.items() if v is None] == []
    assert read[spec.MESH_ONLY] == pytest.approx(1e3 * 50e-9 / 32)
    assert read["decode_linear_ms"] == pytest.approx(1e3 * 100e-9 / 32)
    assert read["decode_attn_ms"] == pytest.approx(1e3 * 150e-9 / 32)
    assert read["prefill_pad_share"] == 0.0

