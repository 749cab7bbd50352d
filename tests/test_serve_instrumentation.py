"""The served path's own names: named scopes in the compiled programs,
named executables, ``ServeLoop``'s host spans in a profiler trace, its
counters and its per-request records."""
import contextlib
import dataclasses
import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.models.config import ModelConfig
from repro.models.init import init_params
from repro.quant.int4 import pack_params
from repro.serving.engine import (SERVE_STATS, Engine, EngineConfig,
                                  ServeLoop)

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=128,
                  n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256,
                  vocab_size=259, param_dtype="float32")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# a padded first wave (5 and 26 prompt tokens), a short budget that frees
# a row, and a queued request that is swapped into it
PROMPTS = ["four", "a longer prompt of twenty", "third", "fourth one"]
BUDGETS = [3, 70, 12, 9]
DECODE_SCOPES = {"embed", "layers", "norm", "qlinear", "act_quant",
                 "weight_dequant", "matmul", "attention", "kv_gather",
                 "attend", "kv_append", "mlp_act", "residual", "head",
                 "sample"}
PREFILL_SCOPES = {"embed", "layers", "norm", "qlinear", "act_quant",
                  "weight_dequant", "matmul", "attention", "attend",
                  "kv_convert", "mlp_act", "residual", "head"}


@pytest.fixture(scope="module")
def engine():
    params = pack_params(init_params(CFG, jax.random.PRNGKey(0)))
    return Engine(params, CFG, EngineConfig(max_seq=256, max_new_tokens=8))


def _scopes(text: str) -> set:
    names = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        names.update(op_name.split("/"))
    return names


def _programs(engine):
    """(jitted, arguments) of the engine's prefill, fused decode chunk
    and host-loop step at batch 2."""
    toks, pp = engine._prepare(["hello", "hi"])
    logits, caches = engine.prefill(toks)
    key = jax.random.PRNGKey(0)
    tok = jnp.zeros((2,), jnp.int32)
    return {
        "prefill": (engine._prefill, (engine.params, toks), PREFILL_SCOPES),
        "decode": (engine._fused(32, start=False),
                   (engine.params, tok, caches, pp, key,
                    jnp.zeros((2,), bool)), DECODE_SCOPES),
        "step": (engine._decode, (engine.params, tok, caches, pp),
                 DECODE_SCOPES - {"sample"}),
    }


@pytest.mark.parametrize("program", ["prefill", "decode", "step"])
def test_compiled_programs_carry_every_named_scope(engine, program):
    fn, args, want = _programs(engine)[program]
    text = fn.lower(*args).as_text(dialect="hlo", debug_info=True)
    assert want <= _scopes(text), want - _scopes(text)


def _named(engine, program):
    """(jitted, arguments) of the host-loop step and, on a one-device
    mesh, of the mesh step and row swap."""
    if program == "step":
        return _programs(engine)["step"][:2]
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    eng = Engine(engine.params, CFG,
                 dataclasses.replace(engine.ecfg, mesh=mesh))
    toks, pp = eng._prepare(["hello", "hi"])
    _, caches = eng.prefill(toks)
    if program == "mesh_step":
        eng.decode(jnp.zeros((2,), jnp.int32), caches, pp)
        return eng._mesh_jits[("decode", 2)], (
            eng.params, jnp.zeros((2,), jnp.int32), caches, pp)
    _, sub = eng.prefill(eng._prepare(["yo"])[0])
    rows = jnp.asarray([1])
    eng.scatter_cache_rows(caches, sub, [1], 2)
    return eng._mesh_jits[("scatter", 2, 1)], (caches, sub, rows)


@pytest.mark.parametrize("program,module", [
    ("step", "jit_serve_step"), ("mesh_step", "jit_serve_step"),
    ("mesh_swap_rows", "jit_serve_swap_rows")])
def test_executables_are_named(engine, program, module):
    fn, args = _named(engine, program)
    text = fn.lower(*args).as_text(dialect="hlo")
    assert text.split(",")[0] == f"HloModule {module}"


@pytest.mark.parametrize("program,kind", [("prefill", "prefill"),
                                          ("decode", "decode_loop")])
def test_served_executables_carry_the_names_the_benchmark_reads(
        engine, program, kind):
    """The chip benchmark finds the prefill and the fused decode loop in
    the device trace by the names in ``chipbench/executables.json``: a
    renamed executable must come with a new entry there."""
    with open(os.path.join(ROOT, "chipbench", "executables.json")) as f:
        names = json.load(f)[kind]
    fn, args, _ = _programs(engine)[program]
    module = fn.lower(*args).as_text(dialect="hlo").split(",")[0].split()[1]
    assert module in names


def test_named_scopes_leave_the_program_unchanged(engine, monkeypatch):
    """A scope is metadata: without it the optimized program has the same
    instructions (XLA numbers their names in another order)."""
    fn, args, _ = _programs(engine)["decode"]

    def body(text):
        lines = [re.sub(r", metadata=\{[^}]*\}", "", line)
                 for line in text.splitlines() if " = " in line]
        return sorted(re.sub(r"%[\w.\-]+", "%", line) for line in lines)
    scoped = body(fn.lower(*args).compile().as_text())
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain_engine = Engine(engine.params, CFG, engine.ecfg)   # fresh jits
    plain = body(plain_engine._fused(32, start=False).lower(*args)
                 .compile().as_text())
    assert "qlinear" not in "".join(plain)
    assert scoped == plain


def _spans(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                    for ev in line.events if ev.name.startswith("serve.")]
    return out


def test_serve_writes_its_spans_into_a_profiler_trace(engine, tmp_path):
    loop = ServeLoop(engine, batch_size=2, max_steps=32)
    loop.serve(PROMPTS, BUDGETS)                      # compile outside
    with jax.profiler.trace(str(tmp_path)):
        loop.serve(PROMPTS, BUDGETS)
    spans = _spans(str(tmp_path))
    names = {s[0] for s in spans}
    assert {"serve.wave", "serve.prepare", "serve.prefill", "serve.chunk",
            "serve.swap_in", "serve.wait", "serve.finalize"} <= names
    waves = [s for s in spans if s[0] == "serve.wave"]
    assert len(waves) == loop.stats["waves"]
    for name, s, e, _ in spans:
        if name != "serve.wave":           # everything sits in a wave
            assert any(w[1] <= s and e <= w[2] for w in waves), name
    chunks = [s for s in spans if s[0] == "serve.chunk"]
    assert len(chunks) == loop.stats["chunks"]
    assert sum(c[3]["steps"] for c in chunks) == loop.stats["decode_steps"]
    assert all(c[3]["rows"] == 2 and c[3]["pos"] % 32 == 0 for c in chunks)
    prefills = [s for s in spans if s[0] == "serve.prefill"]
    assert len(prefills) == loop.stats["prefills"]
    assert sum(p[3]["tokens"] for p in prefills) == \
        loop.stats["prefill_tokens"]
    assert sum(p[3]["padded"] for p in prefills) == \
        loop.stats["prefill_padded_tokens"]


def _counted(engine, counts):
    """``engine`` with its prefill and fused-loop dispatches counted from
    outside, into ``counts``."""
    prefill, fused = engine.prefill, engine._fused

    def counted_prefill(toks):
        counts["prefills"] += 1
        counts["prefill_padded_tokens"] += int(toks.size)
        return prefill(toks)

    def counted_fused(num_steps, start, batch=None):
        fn = fused(num_steps, start, batch=batch)

        def run(*args):
            counts["chunks"] += 1
            counts["decode_steps"] += num_steps
            counts["decode_row_steps"] += num_steps * int(args[1].shape[0])
            return fn(*args)
        return run
    engine.prefill, engine._fused = counted_prefill, counted_fused
    return engine


def test_stats_equal_the_dispatches_counted_around_the_engine(engine):
    counts = dict.fromkeys(("prefills", "prefill_padded_tokens", "chunks",
                            "decode_steps", "decode_row_steps"), 0)
    fresh = _counted(Engine(engine.params, CFG, engine.ecfg), counts)
    loop = ServeLoop(fresh, batch_size=2, max_steps=32)
    loop.serve(PROMPTS, BUDGETS)
    st = loop.stats
    assert set(st) == set(SERVE_STATS)
    assert st["swaps"] >= 1                           # a swap-in ran
    assert st["prefill_padded_tokens"] > st["prefill_tokens"]   # padded
    assert {k: st[k] for k in counts} == counts
    assert st["prefill_tokens"] == sum(len(fresh._encode(p))
                                       for p in PROMPTS)


def test_records_hold_each_requests_served_ids_and_times(engine):
    loop = ServeLoop(engine, batch_size=2, max_steps=32)
    texts = loop.serve(PROMPTS, BUDGETS)
    eos = engine.tok.eos_id
    assert len(loop.records) == len(PROMPTS)
    for rec, text, budget in zip(loop.records, texts, BUDGETS):
        assert rec.admitted <= rec.first_token <= rec.finished
        assert 1 <= len(rec.tokens) <= budget and eos not in rec.tokens
        assert engine.tok.decode(rec.tokens) == text
        if all(t < 128 for t in rec.tokens):           # ASCII bytes
            assert engine.tok.encode(text, add_bos=False) == rec.tokens
    # the swapped-in request was admitted after the first wave's
    assert loop.records[2].admitted > loop.records[0].admitted
    assert np.all(np.diff([r.admitted for r in loop.records[:2]]) == 0)


def test_stats_and_records_reset_per_serve(engine):
    loop = ServeLoop(engine, batch_size=2, max_steps=32)
    loop.serve(PROMPTS, BUDGETS)
    first = dict(loop.stats)
    loop.serve(PROMPTS[:1], BUDGETS[:1])
    assert loop.stats["waves"] == 1 and loop.stats["prefills"] == 1
    assert loop.stats["decode_steps"] <= first["decode_steps"]
    assert len(loop.records) == 1
