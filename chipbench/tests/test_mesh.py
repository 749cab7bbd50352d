"""A cell on the chips it names, at a small size on four CPU devices: a
cell of four chips serves on a (data=1, model=4) mesh whose weights lie
in their tensor-parallel shardings and stays correct; a one-chip cell
builds no mesh and the program it draws its weights with is the one it
always was; rooflines of shared work are the chips' together."""

import jax
import numpy as np
import pytest

import counts
import run
import spec
import tiny

ONE_CHIP = ["deepseek7b.longdoc_bucket", "starcoder2.chat_bucket"]
# each cell served on four chips: StarCoder2 (GQA, QKV and MLP biases,
# LayerNorm, a tied embedding sharded by vocabulary) and DeepSeek (MHA,
# RMSNorm, SwiGLU, an untied INT4 head)
MESH_CELL = "starcoder2.chat_bucket"
ON_FOUR = ONE_CHIP
SEED = 2 ** 31 + 21


def _on_four(name):
    bench, c = tiny.cell(name)
    c["workload"] = dict(c["workload"], chips=4)
    return bench, c


def _weights(c, mesh):
    conf = c["config"]
    return run._init_weights(run.model_config(conf), conf["reference"],
                             tuple(sorted(conf["model"].items())), mesh)(
                                 run.seed_key(SEED))


def test_the_mesh_cell_is_a_gqa_model_with_qkv_biases():
    _, c = _on_four(MESH_CELL)
    m = c["config"]["model"]
    assert c["workload"]["chips"] == 4
    assert m["n_kv_heads"] < m["n_heads"] and m["qkv_bias"]


@pytest.mark.parametrize("name", ON_FOUR)
def test_the_mesh_draw_is_the_one_chip_draw(name):
    """On a mesh the weights are the one-chip cell's weights: the
    program's own draw, placed in shards, and the same affine parameters.
    The INT4 values agree bit for bit; group scales and the embedding,
    computed op by op there and in one program here, to an ulp or two."""
    _, c = _on_four(name)
    got = _weights(c, run.serve_mesh(jax.devices(), 4))
    want = _weights(c, None)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        a, b = np.asarray(a), np.asarray(b)
        if np.issubdtype(a.dtype, np.floating):
            np.testing.assert_allclose(a, b, rtol=4 * np.finfo(a.dtype).eps,
                                       atol=0)
        else:
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("name", ON_FOUR)
def test_weights_lie_in_their_shardings(name):
    from repro.distributed.sharding import param_pspecs, to_named
    _, c = _on_four(name)
    mesh = run.serve_mesh(jax.devices(), 4)
    assert dict(mesh.shape) == {"data": 1, "model": 4}
    params = _weights(c, mesh)
    want = to_named(param_pspecs(run.model_config(c["config"]), params,
                                 mesh), mesh)
    leaves = jax.tree.leaves(params)
    sharded = 0
    for leaf, sh in zip(leaves, jax.tree.leaves(want)):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)
        assert len(leaf.sharding.device_set) == 4      # none whole on one
        held = sum(s.data.nbytes for s in leaf.addressable_shards)
        if any(sh.spec):
            sharded += 1
            assert held == leaf.nbytes                 # a quarter each
    assert sharded >= len(leaves) // 2
    # the linears and the head are all split over the chips
    blocks = params["blocks"]["attn"]
    for w in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        assert w not in blocks or any(blocks[w].packed.sharding.spec)
    head = params.get("lm_head")
    head = params["embed"] if head is None else head.packed
    assert any(head.sharding.spec)


@pytest.mark.parametrize("name", ON_FOUR)
def test_a_four_chip_cell_serves_correctly_on_the_mesh(name):
    bench, c = _on_four(name)
    r = run.run_cell(bench, c, SEED, 0.5, False, devices=jax.devices(),
                     log=lambda m: None)
    assert r["correct"], r["check"]
    assert r["attempted"] >= 4 and r["failed"] == 0
    assert set(r["metrics"]) == {"output_tok_s", "setup_s"}


def test_fewer_devices_than_chips_raise():
    with pytest.raises(ValueError, match="needs 4 devices"):
        run.serve_mesh(jax.devices()[:2], 4)


@pytest.mark.parametrize("name", ONE_CHIP)
def test_a_one_chip_cell_builds_no_mesh(name):
    _, c = tiny.cell(name)
    assert run.serve_mesh(jax.devices(), c["workload"]["chips"]) is None
    loop = run.build(c["config"], c["traffic"], SEED)
    assert loop.engine.mesh is None
    dev = {d for x in jax.tree.leaves(loop.engine.params)
           for d in x.sharding.device_set}
    assert dev == {jax.devices()[0]}


@pytest.mark.parametrize("name", ONE_CHIP)
def test_a_one_chip_cell_draws_its_weights_with_the_same_program(name):
    """The jitted draw of a one-chip cell lowers to the program the
    harness drew its weights with before cells could name more chips."""
    from repro.models.init import init_packed_params
    _, c = tiny.cell(name)
    conf = c["config"]
    cfg, model = run.model_config(conf), dict(conf["model"])
    affine = spec.reference(conf["reference"]).affine_params

    def init(key):
        return run.put_affine(init_packed_params(cfg, key),
                              affine(model, key))
    key = run.seed_key(SEED)
    now = run._init_weights(cfg, conf["reference"],
                            tuple(sorted(model.items())), None)
    assert now.lower(key).as_text() == jax.jit(init).lower(key).as_text()


# -- rooflines over the chips that share the work ------------------------

TRACE_CHIPS = {"window_s": 2.0, "busy_s": 1.9,
               "exec": {"decode_loop": {"seconds": 1.5, "count": 4}}}
NUMBERS = {"flops": 3.0e15, "decode_steps": 128,
           "decode_weight_bytes": 18.4e9}


def _ctx(chips, trace_chips=None):
    conf = spec.config("deepseek-7b")
    reduced = dict(TRACE_CHIPS, chips=chips if trace_chips is None
                   else trace_chips)
    return run.layer_ctx(reduced, dict(NUMBERS), conf, {}, "TPU v5 lite",
                         chips)


def test_a_trace_of_other_chips_than_the_cells_raises():
    with pytest.raises(ValueError, match="4 chips"):
        _ctx(4, trace_chips=1)
    with pytest.raises(ValueError, match="1 chips"):
        _ctx(1, trace_chips=4)


def test_one_chip_rooflines_keep_their_arithmetic():
    """On one chip each share reads exactly what it read when the harness
    knew one chip only (the same operations in the same order)."""
    ctx = _ctx(1)
    peaks = counts.peaks("TPU v5 lite")
    step = 1.5 / 128
    assert spec.metric_reader("mfu")(ctx) == 100.0 * 3.0e15 / (
        2.0 * peaks["bf16_flops_per_s"])
    assert spec.metric_reader("decode_weight_bw_share")(ctx) == \
        100.0 * (18.4e9 / peaks["hbm_bytes_per_s"]) / step


SCOPED = ["decode_linear_bw_share", "decode_attn_bw_share"]


@pytest.mark.parametrize("name", ["mfu", "decode_weight_bw_share"]
                         + SCOPED)
def test_rooflines_are_the_chips_together(monkeypatch, name):
    import scopes
    from test_scopes import TEXTS, recorded
    r = scopes.reduce(recorded(), TEXTS)
    monkeypatch.setattr(scopes, "read_dir", lambda: dict(r, window_s=2.0))
    monkeypatch.setattr(scopes, "decode_modules", lambda: ["jit_f"])
    read = spec.metric_reader(name)
    one, four = read(_ctx(1)), read(_ctx(4))
    assert one > 0
    assert four == pytest.approx(one / 4, rel=1e-12)


def test_one_chip_scoped_rooflines_keep_their_arithmetic(monkeypatch):
    import layer_counts
    import scopes
    from test_scopes import TEXTS, recorded
    r = dict(scopes.reduce(recorded(), TEXTS), window_s=2.0)
    monkeypatch.setattr(scopes, "read_dir", lambda: r)
    monkeypatch.setattr(scopes, "decode_modules", lambda: ["jit_f"])
    ctx = _ctx(1)
    m, peaks = ctx["model"], ctx["peaks"]
    s = scopes.scoped_seconds(r, ["jit_f"], "qlinear")
    floor_s = max(32 * layer_counts.decode_linear_bytes(m)
                  / peaks["hbm_bytes_per_s"],
                  layer_counts.decode_linear_flops(m, 128)
                  / peaks["bf16_flops_per_s"])
    assert spec.metric_reader("decode_linear_bw_share")(ctx) == \
        100.0 * floor_s / s
    s = scopes.scoped_seconds(r, ["jit_f"], "attention")
    total = layer_counts.chunk_cache_bytes(m, 100, 32, 4)
    assert spec.metric_reader("decode_attn_bw_share")(ctx) == \
        100.0 * total / peaks["hbm_bytes_per_s"] / s


def test_mesh_executables_carry_the_names_the_trace_is_read_by():
    """On a mesh the prefill and the fused decode loop keep the names
    ``executables.json`` gives them."""
    import trace as trace_lib
    _, c = tiny.cell(MESH_CELL)
    loop = run.build(c["config"], c["traffic"], SEED,
                     mesh=run.serve_mesh(jax.devices(), 4))
    eng, B = loop.engine, c["traffic"]["batch_size"]
    toks = jax.numpy.zeros((B, 64), jax.numpy.int32)
    logits, caches = eng.prefill(toks)
    row = jax.numpy.zeros((B,), jax.numpy.int32)
    from repro.serving.engine import Engine
    loop_fn = Engine._fused(eng, 32, start=False, batch=B)
    lowered = {
        "prefill": eng._mesh_jits[("prefill", B, 64)].lower(eng.params, toks),
        "decode_loop": loop_fn.lower(eng.params, row, caches, row,
                                     jax.random.PRNGKey(0),
                                     jax.numpy.zeros((B,), bool))}
    names = trace_lib.executables()
    for kind, low in lowered.items():
        module = low.as_text(dialect="hlo").split(",")[0].split()[1]
        assert module in names[kind]
