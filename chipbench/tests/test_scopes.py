"""The scope and span reducer and the readers of the program's names, on
small recorded traces with known numbers."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

import layer_counts
import scopes
import spec

TOY = {"n_layers": 1, "d_model": 256, "d_ff": 512, "n_heads": 2,
       "n_kv_heads": 1, "head_dim": 32, "vocab_size": 1024,
       "mlp_style": "gated", "tie_embeddings": False, "norm_type": "rms"}
PEAKS = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}

# the ops of one decode executable: (name, op_name metadata, operands)
OPS = [("fusion.1", "jit(f)/while/body/qlinear/matmul/dot_general", []),
       ("copy.2", None, ["fusion.1"]),
       ("fusion.3", "jit(f)/while/body/attention/kv_gather/mul", []),
       ("add.4", "jit(f)/while/body/add", []),
       ("dynamic-slice.5", "jit(f)/while/body/layers/while/dynamic_slice",
        [])]
TIMES = [(500, 600), (600, 650), (650, 800), (800, 850), (850, 900)]


def _stats(st):
    return "".join(f" stats {{ metadata_id: {k} str_value: \"{v}\" }}"
                   if isinstance(v, str) else
                   f" stats {{ metadata_id: {k} int64_value: {v} }}"
                   for k, v in st)


def _line(lid, name, events):
    ev = "\n".join(f"    events {{ metadata_id: {m} offset_ps: {s * 1000} "
                   f"duration_ps: {(e - s) * 1000}{_stats(st)} }}"
                   for m, s, e, st in events)
    return f"  lines {{ id: {lid} name: \"{name}\" timestamp_ns: 0\n{ev}\n  }}"


def _plane(pid, name, lines, names, stat_names):
    meta = "\n".join(f"  event_metadata {{ key: {i} value {{ id: {i} "
                     f"name: \"{n}\" }} }}" for i, n in names.items())
    smeta = "\n".join(f"  stat_metadata {{ key: {i} value {{ id: {i} "
                      f"name: \"{n}\" }} }}" for i, n in stat_names.items())
    return (f"planes {{\n  id: {pid}\n  name: \"{name}\"\n"
            + "\n".join(lines) + "\n" + meta + "\n" + smeta + "\n}")


def xspace(program_spans=True):
    """One job [0, 1000) ns.  The decode executable ``jit_f(9)`` runs
    [500, 900): a matmul, a copy of it with no metadata, a cache gather,
    an unscoped add and a slice of the layer stack, its ops named as a
    TPU trace names them (no ``op_name``).  Host: a wave [0, 1000)
    holding a prefill dispatch [10, 40) (100 real of 128 tokens), one
    chunk dispatch [450, 470) (counter 100, 32 steps, 4 rows) and one
    wait [470, 950).  ``program_spans=False`` keeps only the harness's
    window."""
    names = {1: "jit_f(9)"}
    ops = []
    for i, (op, _, operands) in enumerate(OPS):
        args = ", ".join("bf16[8]{0} %" + o for o in operands)
        names[i + 2] = f"%{op} = bf16[8]{{0}} fusion({args})"
        ops.append((i + 2, *TIMES[i], []))
    dev = _plane(1, "/device:TPU:0", [
        _line(1, "XLA Modules", [(1, 500, 900, [])]),
        _line(2, "XLA Ops", ops)], names, {})
    spans = [(1, 0, 1000, [])]
    if program_spans:
        spans += [(2, 0, 1000, []),
                  (5, 10, 40, [(4, 100), (5, 128)]),
                  (3, 450, 470, [(1, 100), (2, 32), (3, 4)]),
                  (4, 470, 950, [])]
    host = _plane(2, "/host:CPU", [_line(1, "python", spans)],
                  {1: "job", 2: "serve.wave", 3: "serve.chunk",
                   4: "serve.wait", 5: "serve.prefill"},
                  {1: "pos", 2: "steps", 3: "rows", 4: "tokens",
                   5: "padded"})
    return ProfileData.text_proto_to_serialized_xspace(dev + "\n" + host)


def recorded(program_spans=True):
    return ProfileData.from_serialized_xspace(xspace(program_spans))


# the executable's HLO text, as the trace keeps it
TEXTS = {"jit_f(9)": "HloModule jit_f\n" + "\n".join(
    f"  %{op} = bf16[8]{{0}} fusion({', '.join('%' + o for o in args)})"
    + (f", metadata={{op_name=\"{on}\"}}" if on else "")
    for op, on, args in OPS)}


def _check(r):
    s = r["scopes"]["jit_f"]
    assert s == {"qlinear/matmul": pytest.approx(150e-9),
                 "attention/kv_gather": pytest.approx(150e-9),
                 "": pytest.approx(50e-9), "layers": pytest.approx(50e-9)}
    assert scopes.scoped_seconds(r, ["jit_f"], "qlinear") == \
        pytest.approx(150e-9)
    assert scopes.scoped_seconds(r, ["jit_f"], "kv_gather") == \
        pytest.approx(150e-9)
    # the layer stack's own ops are counted apart from the named work
    assert scopes.coverage(r, ["jit_f"]) == {
        "named": pytest.approx(75.0), "stack": pytest.approx(12.5),
        "unscoped": pytest.approx(12.5)}
    assert r["top_ops"]["jit_f"]["layers"] == [
        ["dynamic-slice.5 = bf16[8]", pytest.approx(50e-9)]]


def test_scopes_from_the_executables_hlo_text():
    _check(scopes.reduce(recorded(), TEXTS))


def test_without_the_hlo_text_every_op_is_unscoped():
    r = scopes.reduce(recorded(), {})
    assert r["scopes"]["jit_f"] == {"": pytest.approx(400e-9)}
    assert scopes.scoped_seconds(r, ["jit_f"], "qlinear") is None
    assert scopes.coverage(r, ["jit_f"])["unscoped"] == pytest.approx(100)


def test_host_spans_chunks_and_prefills():
    r = scopes.reduce(recorded(), TEXTS)
    assert r["sched_host_s"] == pytest.approx(520e-9)     # 1000 - 480
    assert r["chunks"] == [{"pos": 100, "steps": 32, "rows": 4}]
    assert r["prefills"] == [{"tokens": 100, "padded": 128}]
    assert scopes.decode_steps(r) == (32, 128)


def test_a_program_that_names_nothing_reads_nothing():
    r = scopes.reduce(recorded(program_spans=False), {})
    assert scopes.scoped_seconds(r, ["jit_f"], "qlinear") is None
    assert r["sched_host_s"] is None and r["chunks"] == []
    assert r["prefills"] == []


def test_hlo_texts_from_a_recorded_cpu_trace(tmp_path):
    """The profiler keeps each executable's compiled HLO in the trace: the
    parser finds the function's scopes there, under the names the trace's
    op events give (the CPU trace has op events on a host thread)."""
    def serve_x(x, w):
        with jax.named_scope("qlinear"):
            with jax.named_scope("matmul"):
                y = x @ w
        with jax.named_scope("norm"):
            return y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True))
    x = jnp.ones((8, 64))
    f = jax.jit(serve_x)
    f(x, x.T).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(x, x.T).block_until_ready()
    import trace as trace_lib
    path = trace_lib.find_xplane(str(tmp_path))
    texts = scopes.hlo_texts(path)
    (exe, text), = [(k, v) for k, v in texts.items()
                    if k.startswith("jit_serve_x(")]
    ops = scopes.hlo_ops(text)
    assert ops == scopes.hlo_ops(f.lower(x, x.T).compile().as_text())
    assert {("qlinear", "matmul"), ("norm",)} <= {p for p, _ in ops.values()}
    ran = [dict(ev.stats)["hlo_op"]
           for plane in ProfileData.from_file(path).planes
           for line in plane.lines for ev in line.events
           if dict(ev.stats).get("hlo_module") == "jit_serve_x"]
    assert ran and all(op in ops for op in ran)
    assert {scopes._resolve(ops, op) for op in ran} >= {
        ("qlinear", "matmul")}


def test_cache_row_bytes_by_hand():
    m = dict(TOY, head_dim=32)
    # 100 positions: K 96 at 8 bits (33 B) + 4 at 4 bits (17 B); V 3
    # groups at 8 bits (1056 B); 4 raw float32 residual tokens (128 B)
    assert layer_counts.cache_row_bytes(m, 100) == 96 * 33 + 4 * 17 + \
        3 * 1056 + 4 * 128
    # 200: K 96 + 104 at 4 bits; V groups 0, 4, 5 at 8 bits, 1-3 at 4
    assert layer_counts.cache_row_bytes(m, 200) == 96 * 33 + 104 * 17 + \
        3 * 1056 + 3 * 544 + 8 * 128
    assert layer_counts.chunk_cache_bytes(m, 99, 2, 3) == 3 * (
        layer_counts.cache_row_bytes(m, 100)
        + layer_counts.cache_row_bytes(m, 101))


@pytest.mark.parametrize("config", ["deepseek-7b", "starcoder2-15b"])
def test_linear_bytes_are_the_weights_less_norms_and_tied_head(config):
    import counts
    m = spec.config(config)["model"]
    norms = 2 * m["d_model"] * (m["n_layers"] * (2 if m["norm_type"] == "rms"
                                                 else 4)
                                + (1 if m["norm_type"] == "rms" else 2))
    tied = 2 * m["vocab_size"] * m["d_model"] if m["tie_embeddings"] else 0
    assert layer_counts.decode_linear_bytes(m) == \
        counts.decode_weight_bytes(m) - norms - tied


def _ctx(window_s=1000e-9, chips=1):
    """What the harness hands a reader: the readers here take nothing of
    the harness's own job counts."""
    return {"model": TOY, "peaks": PEAKS, "trace": {"window_s": window_s},
            "chips": chips}


READS = {
    "decode_linear_ms": 1e3 * 150e-9 / 32,
    "decode_attn_ms": 1e3 * 150e-9 / 32,
    "decode_linear_bw_share": 100 * max(
        32 * layer_counts.decode_linear_bytes(TOY) / 1e9,
        layer_counts.decode_linear_flops(TOY, 128) / 1e12) / 150e-9,
    "decode_attn_bw_share": 100 * layer_counts.chunk_cache_bytes(
        TOY, 100, 32, 4) / 1e9 / 150e-9,
    "sched_host_ms_per_chunk": 1e3 * 520e-9,
    "prefill_pad_share": 100 * 28 / 128,
}


@pytest.mark.parametrize("name", sorted(READS))
def test_readers_on_the_recorded_trace(monkeypatch, name):
    monkeypatch.setattr(scopes, "read_dir",
                        lambda: scopes.reduce(recorded(), TEXTS))
    monkeypatch.setattr(scopes, "decode_modules", lambda: ["jit_f"])
    assert spec.metric_reader(name)(_ctx()) == pytest.approx(READS[name])


@pytest.mark.parametrize("name", sorted(READS))
def test_readers_find_nothing_in_a_program_without_names(monkeypatch, name):
    monkeypatch.setattr(scopes, "read_dir", lambda: scopes.reduce(
        recorded(program_spans=False), {}))
    monkeypatch.setattr(scopes, "decode_modules", lambda: ["jit_f"])
    assert spec.metric_reader(name)(_ctx()) is None


@pytest.mark.parametrize("name", sorted(READS))
def test_readers_take_no_trace_but_the_harnesss(monkeypatch, name):
    """A trace whose window is not the one the harness reduced (one left
    from another run) is not read."""
    monkeypatch.setattr(scopes, "read_dir",
                        lambda: scopes.reduce(recorded(), TEXTS))
    monkeypatch.setattr(scopes, "decode_modules", lambda: ["jit_f"])
    assert spec.metric_reader(name)(_ctx(window_s=2000e-9)) is None


def test_read_dir_reads_each_trace_file_once(monkeypatch, tmp_path):
    reduce, calls = scopes.reduce, []
    monkeypatch.setattr(scopes, "reduce",
                        lambda pd, texts: calls.append(1) or reduce(pd, texts))
    assert scopes.read_dir(str(tmp_path)) is None         # no trace yet
    path = tmp_path / "x.xplane.pb"
    path.write_bytes(xspace())
    first = scopes.read_dir(str(tmp_path))
    assert first["chunks"] == [{"pos": 100, "steps": 32, "rows": 4}]
    assert scopes.read_dir(str(tmp_path)) is first and len(calls) == 1
    os.utime(path, ns=(1, 1))                             # a new trace
    scopes.read_dir(str(tmp_path))
    assert len(calls) == 2


def test_new_metrics_are_listed_with_their_cells():
    bench = spec.load_benchmark()
    listed = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    for name in READS:
        assert listed[name]["moves"] == "output_tok_s"
        assert listed[name]["workloads"] == cells


def mesh_xspace():
    """One job [0, 1000) ns on two chips.  On each, the decode executable
    ``jit_f(9)`` runs [500, 900): a matmul, an all-reduce after it (an op
    named by its instruction text), the sampler's all-gather as async
    halves, and a fusion that calls an all-reduce-scatter computation (as
    v5e runs a row-shard all-reduce); chip 1's collectives take twice as
    long.  Host: one chunk of 32 steps."""
    names = {1: "jit_f(9)",
             2: "%fusion.1 = f32[128,16]{1,0} fusion(%p)",
             3: "%all-reduce.2 = f32[128,16]{1,0} all-reduce(f32[128,16]{1,0} "
                "%fusion.1), channel_id=1, to_apply=%add",
             4: "all-gather-start.3", 5: "all-gather-done.3",
             6: "%add.4 = f32[16]{0} add(%a, %b)",
             7: "%fusion.5 = f32[32,16]{1,0} fusion(%fusion.1), kind=kCustom"}
    planes = []
    for chip, k in ((0, 1), (1, 2)):
        ops = [(2, 500, 600, []), (3, 600, 600 + 40 * k, []),
               (4, 700, 700 + 10 * k, []), (6, 750, 800, []),
               (5, 800, 800 + 20 * k, []), (7, 850, 850 + 15 * k, [])]
        planes.append(_plane(chip + 1, f"/device:TPU:{chip}", [
            _line(1, "XLA Modules", [(1, 500, 900, [])]),
            _line(2, "XLA Ops", ops)], names, {}))
    host = _plane(9, "/host:CPU", [_line(1, "python", [
        (1, 0, 1000, []), (2, 0, 1000, []),
        (3, 450, 470, [(1, 0), (2, 32), (3, 16)])])],
        {1: "job", 2: "serve.wave", 3: "serve.chunk"},
        {1: "pos", 2: "steps", 3: "rows"})
    text = "\n".join(planes + [host])
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


MESH_TEXTS = {"jit_f(9)": "HloModule jit_f\n\n"
              "%all-reduce-scatter.clone (input.4: f32[128,16]) -> f32[32,16] {\n"
              "  %input.4 = f32[128,16]{1,0} parameter(0)\n"
              "  ROOT %all-reduce.9 = f32[128,16]{1,0} all-reduce(%input.4)\n"
              "}\n\n"
              "ENTRY %main.2 (p: f32[16]) -> f32[16] {\n"
              "  %fusion.1 = f32[128,16]{1,0} fusion(), metadata={op_name="
              "\"jit(f)/while/body/layers/qlinear/matmul/dot_general\"}\n"
              "  %all-reduce.2 = f32[128,16]{1,0} all-reduce(%fusion.1)\n"
              "  %all-gather-start.3 = (f32[16,8]{1,0}, f32[16,16]{1,0}) "
              "all-gather-start(), metadata={op_name="
              "\"jit(f)/while/body/squeeze\"}\n"
              "  %fusion.5 = f32[32,16]{1,0} fusion(%fusion.1), kind=kCustom, "
              "calls=%all-reduce-scatter.clone\n}\n"}


@pytest.mark.parametrize("name,kind", [
    ("all-reduce.2", "all-reduce"), ("all-gather-start.3", "all-gather"),
    ("all-reduce-done", "all-reduce"), ("reduce-scatter.1", "reduce-scatter"),
    ("collective-permute-start.4", "collective-permute"),
    ("all-to-all.5", "all-to-all"),
    ("%x.7 = (s32[8]{0:T(256)S(1)}, s32[8]) all-gather-start(%a)",
     "all-gather"),
    ("%fusion.3 = bf16[8]{0} fusion(%all-reduce.3)", None),
    ("%reduce.6 = f32[8]{0} reduce(%a, %b)", None), ("copy.4", None)])
def test_collectives_by_op_name_or_opcode(name, kind):
    assert scopes.collective(name) == kind


def test_collectives_by_kind_and_scope_averaged_over_chips():
    r = scopes.reduce(mesh_xspace(), MESH_TEXTS)
    # chip 0: all-reduce 40 and the fusion that calls one 15, gather
    # halves 10 + 20; chip 1: twice that
    assert r["collectives"]["jit_f"] == {
        "all-reduce": {"layers/qlinear/matmul": pytest.approx(82.5e-9)},
        "all-gather": {"": pytest.approx(45e-9)}}
    assert scopes.collective_seconds(r, ["jit_f"]) == \
        pytest.approx(127.5e-9)
    assert scopes.collective_seconds(r, ["jit__lambda"]) is None


def test_collectives_in_the_computations_an_op_calls():
    text = "\n".join([
        "HloModule m", "",
        "%ar (a: f32[8]) -> f32[8] {",
        "  %a = f32[8]{0} parameter(0)",
        "  ROOT %all-reduce.1 = f32[8]{0} all-reduce(%a), to_apply=%add",
        "}", "",
        "%outer (b: f32[8]) -> f32[8] {",
        "  %b = f32[8]{0} parameter(0)",
        "  ROOT %fusion.2 = f32[8]{0} fusion(%b), kind=kCustom, calls=%ar",
        "}", "",
        "%plain (c: f32[8]) -> f32[8] {",
        "  %c = f32[8]{0} parameter(0)",
        "  ROOT %add.3 = f32[8]{0} add(%c, %c)",
        "}", "",
        "ENTRY %main (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        "  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, calls=%plain",
        "  %fusion.5 = f32[8]{0} fusion(%fusion.4), kind=kCustom, "
        "calls=%outer",
        "  ROOT %all-gather-start.6 = (f32[8]{0}, f32[32]{0}) "
        "all-gather-start(%fusion.5), dimensions={0}",
        "}"])
    assert scopes.hlo_collectives(text) == {
        "all-reduce.1": "all-reduce", "fusion.2": "all-reduce",
        "fusion.5": "all-reduce", "all-gather-start.6": "all-gather"}


def test_decode_collective_ms_on_a_recorded_mesh_trace(monkeypatch):
    monkeypatch.setattr(scopes, "read_dir",
                        lambda: scopes.reduce(mesh_xspace(), MESH_TEXTS))
    monkeypatch.setattr(scopes, "decode_modules", lambda: ["jit_f"])
    read = spec.metric_reader("decode_collective_ms")
    assert read(_ctx(chips=2)) == pytest.approx(1e3 * 127.5e-9 / 32)
    assert read(_ctx(window_s=2000e-9, chips=2)) is None


def test_decode_collective_ms_reads_nothing_on_one_chip(monkeypatch):
    monkeypatch.setattr(scopes, "read_dir",
                        lambda: scopes.reduce(recorded(), TEXTS))
    monkeypatch.setattr(scopes, "decode_modules", lambda: ["jit_f"])
    assert spec.metric_reader("decode_collective_ms")(_ctx()) is None


def test_decode_collective_ms_is_listed_for_the_four_chip_cells():
    """Listed for every cell of more than one chip and for no other; the
    reader is ready for the first such cell."""
    bench = spec.load_benchmark()
    mesh_cells = [w["name"] for w in bench["workloads"] if w["chips"] > 1]
    listed = [m for m in bench["per_layer"]
              if m["name"] == "decode_collective_ms"]
    assert [m["workloads"] for m in listed] == (
        [mesh_cells] if mesh_cells else [])
    for m in listed:
        assert m["moves"] == "output_tok_s" and m["layer"] == "collectives"
    assert callable(spec.metric_reader("decode_collective_ms"))


def test_scoped_seconds_leave_the_collectives_out():
    """On a mesh the row-shard all-reduces fused under ``qlinear/matmul``
    are ``collective_seconds``', not the linears': only the matmul's 100
    ns a chip stays."""
    r = scopes.reduce(mesh_xspace(), MESH_TEXTS)
    assert r["scopes"]["jit_f"]["layers/qlinear/matmul"] == \
        pytest.approx(182.5e-9)
    assert scopes.scoped_seconds(r, ["jit_f"], "qlinear") == \
        pytest.approx(100e-9)


@pytest.mark.parametrize("scope", ["qlinear", "attention", "kv_gather"])
def test_scoped_seconds_on_one_chip_are_the_plain_sum(scope):
    """Where no collective ran, a scope's seconds are the same float sum
    as before collectives were left out."""
    r = scopes.reduce(recorded(), TEXTS)
    assert r["collectives"] == {}
    assert scopes.scoped_seconds(r, ["jit_f"], scope) == sum(
        t for p, t in r["scopes"]["jit_f"].items() if scope in p.split("/"))
