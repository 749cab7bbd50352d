"""Compile each program a cell's job uses at full width, and report its
device memory: arguments and temporaries, as the compiler counts them.

  JAX_PLATFORMS=cpu python chipbench/run.py --plan > plan.jsonl
  python chipbench/aot.py plan.jsonl          # on the machine with the chip

The shapes come from ``run.py --plan``; nothing is run or allocated
(the weights are shapes only).  One JSON line per program.
"""
from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402  (sets the paths of the program)
import spec  # noqa: E402


def programs(cell: dict, shapes: list):
    """(name, jitted, abstract arguments) of each planned shape."""
    import jax
    import jax.numpy as jnp
    from repro.models.init import init_packed_params
    from repro.serving.engine import Engine, EngineConfig
    conf, mix = cell["config"], cell["traffic"]
    cfg = run.model_config(conf)
    params = jax.eval_shape(partial(init_packed_params, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    eng = Engine(params, cfg, EngineConfig(
        max_seq=int(mix["max_seq"]), quant=run.quant_config(conf["numerics"])))
    sds = jax.ShapeDtypeStruct
    for kind, a, b in shapes:
        if kind == "prefill":
            toks = sds((a, b), jnp.int32)
            yield f"prefill B={a} S={b}", eng._prefill, (params, toks)
        else:
            steps, B = a, b
            _, caches = jax.eval_shape(eng._prefill, params,
                                       sds((B, 32), jnp.int32))
            key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
            args = (params, sds((B,), jnp.int32), caches,
                    sds((B,), jnp.int32), key, sds((B,), jnp.bool_))
            yield (f"decode loop {steps} steps B={B}",
                   eng._fused(steps, start=False), args)


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    bench = spec.load_benchmark()
    with open(argv[0]) as f:
        plans = [json.loads(line) for line in f if line.strip()]
    for p in plans:
        cell = spec.cell(bench, p["workload"])
        for name, fn, args in programs(cell, p["shapes"]):
            t = time.perf_counter()
            try:
                ma = fn.lower(*args).compile().memory_analysis()
                out = {"arguments_gb": ma.argument_size_in_bytes / 1e9,
                       "temporaries_gb": ma.temp_size_in_bytes / 1e9,
                       "outputs_gb": ma.output_size_in_bytes / 1e9,
                       "aliased_gb": ma.alias_size_in_bytes / 1e9}
            except Exception as e:  # noqa: BLE001 - a refusal is a finding
                out = {"refused": str(e).splitlines()[0][:300]}
            out.update(workload=p["workload"], program=name,
                       compile_s=time.perf_counter() - t)
            print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
