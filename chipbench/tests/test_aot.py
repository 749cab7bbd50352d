"""``aot.py`` compiles the programs a cell's engine dispatches: for a cell
of one chip the programs it compiled before it knew meshes, and for a
cell of four chips the mesh's programs, whose memory is one chip's: a
quarter of what is split, and all of what is replicated."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import aot
import candidate
import run
import spec
from repro.distributed.sharding import param_pspecs, to_named

ONE_CHIP = ["deepseek7b.longdoc_bucket", "starcoder2.chat_bucket"]


def _shapes(c):
    B = int(c["traffic"]["batch_size"])
    return [("prefill", B, 64), ("chunk", 32, B)]


def _before(c, shapes):
    """The one-chip programs as ``aot.py`` built them before it compiled
    on meshes: the engine over abstract weights, its plain prefill and
    its fused loop."""
    from functools import partial
    from repro.models.init import init_packed_params
    from repro.serving.engine import Engine, EngineConfig
    conf, mix = c["config"], c["traffic"]
    cfg = run.model_config(conf)
    params = jax.eval_shape(partial(init_packed_params, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    eng = Engine(params, cfg, EngineConfig(
        max_seq=int(mix["max_seq"]), quant=run.quant_config(conf["numerics"])))
    sds = jax.ShapeDtypeStruct
    for kind, a, b in shapes:
        if kind == "prefill":
            yield eng._prefill, (params, sds((a, b), jnp.int32))
        else:
            steps, B = a, b
            _, caches = jax.eval_shape(eng._prefill, params,
                                       sds((B, 32), jnp.int32))
            key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
            yield eng._fused(steps, start=False), (
                params, sds((B,), jnp.int32), caches, sds((B,), jnp.int32),
                key, sds((B,), jnp.bool_))


@pytest.mark.parametrize("name", ONE_CHIP)
def test_a_one_chip_cell_lowers_the_programs_it_always_did(name):
    """At the architecture's smoke widths, with the cell's own mix."""
    from repro.configs import get_arch
    c = spec.cell(spec.load_benchmark(), name)
    smoke = get_arch(c["config"]["arch"]).smoke
    c["config"]["model"] = {k: getattr(smoke, k)
                            for k in c["config"]["model"]
                            if hasattr(smoke, k)}
    now = list(aot.programs(c, _shapes(c)))
    before = list(_before(c, _shapes(c)))
    assert [n for n, _, _ in now] == [
        f"prefill B={c['traffic']['batch_size']} S=64",
        f"decode loop 32 steps B={c['traffic']['batch_size']}"]
    for (_, fn, args), (fn0, args0) in zip(now, before, strict=True):
        assert fn.lower(*args).as_text() == fn0.lower(*args0).as_text()


def _replicated_bytes(c):
    """Bytes of the weights ``param_pspecs`` splits over no axis (norms,
    biases): each chip holds all of them."""
    from functools import partial
    from repro.models.init import init_packed_params
    cfg = run.model_config(c["config"])
    params = jax.eval_shape(partial(init_packed_params, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    mesh = run.serve_mesh(jax.devices(), 4)
    shardings = to_named(param_pspecs(cfg, params, mesh), mesh)
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x, sh in zip(jax.tree.leaves(params),
                                jax.tree.leaves(shardings), strict=True)
               if not any(sh.spec))


def test_a_four_chip_cell_compiles_on_its_mesh(candidate_root):
    c = spec.cell(spec.load_benchmark(), candidate.CELL)
    shapes = _shapes(c)
    four = {n: aot.memory(fn, args) for n, fn, args in aot.programs(c, shapes)}
    one_c = dict(c, workload=dict(c["workload"], chips=1))
    one = {n: aot.memory(fn, args)
           for n, fn, args in aot.programs(one_c, shapes)}
    assert set(four) == set(one) and len(four) == 2
    rep = _replicated_bytes(c)
    assert 0 < rep < 0.1 * one[next(iter(one))]["arguments_gb"] * 1e9
    for name, got in four.items():
        assert "refused" not in got, got
        per_chip = got["arguments_gb"] * 1e9
        assert per_chip <= one[name]["arguments_gb"] * 1e9 / 4 + rep, name
    for _, fn, args in aot.programs(c, shapes):
        params = args[0]
        assert all(len(x.sharding.device_set) == 4
                   for x in jax.tree.leaves(params))


def test_aot_main_reads_another_benchmark_per_chip(candidate_root, tmp_path,
                                                   capsys):
    """``aot.py <plan> --root <copy>`` compiles the copy's four-chip cell
    on the attached devices and says so on every line."""
    plan = tmp_path / "plan.jsonl"
    plan.write_text(json.dumps({"workload": candidate.CELL,
                                "shapes": [["prefill", 4, 64],
                                           ["chunk", 32, 4]]}) + "\n")
    aot.main([str(plan), "--root", candidate_root])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["program"] for x in lines] == [
        "prefill B=4 S=64", "decode loop 32 steps B=4"]
    for x in lines:
        assert x["chips"] == 4 and x["target"] == "attached cpu"
        assert {"arguments_gb", "temporaries_gb", "outputs_gb",
                "aliased_gb"} <= set(x)


def test_a_described_topology_takes_no_one_chip_cell(tmp_path):
    """A one-chip cell compiles on the attached backend only, so the
    plan is refused before any topology is described."""
    plan = tmp_path / "plan.jsonl"
    plan.write_text(json.dumps({"workload": ONE_CHIP[0],
                                "shapes": [["prefill", 4, 64]]}) + "\n")
    with pytest.raises(SystemExit):
        aot.main([str(plan), "--topology", "v5e:2x2"])
