"""Compile each program a cell's job uses at full width, and report its
device memory: arguments and temporaries, as the compiler counts them.

  JAX_PLATFORMS=cpu python chipbench/run.py --plan > plan.jsonl
  python chipbench/aot.py plan.jsonl          # on the machine with the chip
  JAX_PLATFORMS=cpu python chipbench/aot.py plan.jsonl --topology v5e:2x2
  python chipbench/aot.py plan.jsonl --root <copy>    # a candidate cell

The shapes come from ``run.py --plan`` (or are written by hand: a line
names a cell and lists its ``["prefill", B, S]`` and ``["chunk", steps,
B]`` shapes); nothing is run or allocated (the weights are shapes only).
One JSON line per program.

A cell of more than one chip is compiled on its (data=1, model=chips)
mesh (``run.serve_mesh``), as the engine dispatches it there: the weights
in the shardings ``distributed/sharding.py:param_pspecs`` gives,
``Engine.prefill``'s mesh program with its pinned shardings, and the
fused decode loop with the cache's.  A sharded program's memory is one
chip's, and its line says so (``chips``) and names what it was compiled
for (``target``): the chips attached, or, with ``--topology``, a
described TPU topology, which needs no chip.  A cell of one chip compiles
its programs on the attached backend, as it always did.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402  (sets the paths of the program)
import spec  # noqa: E402


def engine(params, cfg, ecfg):
    """The program's ``Engine`` over abstract weights.  On a mesh its
    constructor places the weights (``jax.device_put``, which takes arrays
    only), so it runs traced, and the engine then holds the abstract
    weights in the shardings it placed them in."""
    import jax
    from repro.serving.engine import Engine
    if ecfg.mesh is None:
        return Engine(params, cfg, ecfg)
    built = []
    jax.eval_shape(lambda p: built.append(Engine(p, cfg, ecfg)), params)
    eng, = built
    eng.params = _placed(params, eng._param_sh)
    return eng


def _placed(tree, shardings):
    import jax
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings)


def mesh_prefill(eng, toks):
    """The program ``Engine.prefill`` dispatches for ``toks`` on the mesh,
    built by tracing one dispatch with the weights as tracers (nothing
    runs)."""
    import jax
    held = eng.params

    def dispatch(p, t):
        eng.params = p
        return eng.prefill(t)
    try:
        jax.eval_shape(dispatch, held, toks)
    finally:
        eng.params = held
    return eng._mesh_jits[("prefill", *toks.shape)]


def programs(cell: dict, shapes: list, devices=None):
    """(name, jitted, abstract arguments) of each planned shape; a cell of
    more than one chip on its mesh of ``devices`` (by default
    ``jax.devices()``)."""
    import jax
    import jax.numpy as jnp
    from repro.models.init import init_packed_params
    from repro.serving.engine import EngineConfig
    conf, mix = cell["config"], cell["traffic"]
    chips = int(cell["workload"]["chips"])
    cfg = run.model_config(conf)
    params = jax.eval_shape(partial(init_packed_params, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    mesh = None if chips == 1 else run.serve_mesh(
        jax.devices() if devices is None else devices, chips)
    eng = engine(params, cfg, EngineConfig(
        max_seq=int(mix["max_seq"]), quant=run.quant_config(conf["numerics"]),
        mesh=mesh))
    params = eng.params
    sds = jax.ShapeDtypeStruct
    for kind, a, b in shapes:
        if kind == "prefill":
            toks = sds((a, b), jnp.int32)
            fn = eng._prefill if mesh is None else mesh_prefill(eng, toks)
            yield f"prefill B={a} S={b}", fn, (params, toks)
        else:
            steps, B = a, b
            _, caches = jax.eval_shape(eng._prefill, params,
                                       sds((B, 32), jnp.int32))
            if mesh is None:
                fn = eng._fused(steps, start=False)
            else:
                caches = _placed(caches, eng.cache_shardings(B))
                fn = eng._fused(steps, start=False, batch=B)
            # the token, pad and finished rows and the key: small, and
            # pinned to no sharding, as the engine pins none
            key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
            args = (params, sds((B,), jnp.int32), caches,
                    sds((B,), jnp.int32), key, sds((B,), jnp.bool_))
            yield f"decode loop {steps} steps B={B}", fn, args


def memory(fn, args) -> dict:
    """The compiled program's device memory, in GB (one chip's where it is
    sharded), or why the compiler refused it."""
    try:
        ma = fn.lower(*args).compile().memory_analysis()
    except Exception as e:  # noqa: BLE001 - a refusal is a finding
        return {"refused": str(e).splitlines()[0][:300]}
    return {"arguments_gb": ma.argument_size_in_bytes / 1e9,
            "temporaries_gb": ma.temp_size_in_bytes / 1e9,
            "outputs_gb": ma.output_size_in_bytes / 1e9,
            "aliased_gb": ma.alias_size_in_bytes / 1e9}


def described(topology: str) -> list:
    """The devices of a described TPU topology (``v5e:2x2``).  A program
    compiled for them would be written to the compile cache but cannot be
    read back without a chip, so the cache is turned off."""
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    return list(topologies.get_topology_desc(
        platform="tpu", topology_name=topology).devices)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("plan", help="the output of run.py --plan")
    ap.add_argument("--root", help="read the cells from the benchmark at "
                    "ROOT (a copy of BENCHMARK.json and chipbench/ that "
                    "holds a candidate cell)")
    ap.add_argument("--topology", help="compile the cells of more than one "
                    "chip for this described TPU topology (v5e:2x2) in "
                    "place of the chips attached")
    args = ap.parse_args(argv)
    import jax
    bench = spec.use_root(args.root) if args.root else spec.load_benchmark()
    with open(args.plan) as f:
        plans = [json.loads(line) for line in f if line.strip()]
    if args.topology:
        one = [p["workload"] for p in plans
               if spec.workload(bench, p["workload"])["chips"] == 1]
        if one:
            ap.error(f"--topology compiles cells of more than one chip; "
                     f"{one} run on one")
        devices, target = described(args.topology), f"described {args.topology}"
    else:
        devices = jax.devices()
        target = f"attached {devices[0].device_kind}"
    for p in plans:
        cell = spec.cell(bench, p["workload"])
        chips = int(cell["workload"]["chips"])
        for name, fn, args_ in programs(cell, p["shapes"], devices):
            t = time.perf_counter()
            out = memory(fn, args_)
            out.update(workload=p["workload"], program=name,
                       compile_s=time.perf_counter() - t)
            if chips > 1:
                out.update(chips=chips, target=target)
            print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
