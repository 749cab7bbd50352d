"""Readings a cell's correctness limit is set from, on the chip.

  python chipbench/control.py --workload <cell> --seeds 101-112 \\
      --control-seeds 201-203

For each ``--seeds`` seed the program as the configuration states it,
and for each ``--control-seeds`` seed the control (the program's own
lower-precision path: linear inputs, Q, K and fresh V at 4-bit BFP where
the configuration states 8), each plays the cell's job once at the
cell's batch and sizes, on the cell's chips (``run.serve_mesh``), and
the sampled requests are held to the reference as in a benchmark run.
One JSON line per seed, then a summary: the largest gap of the program's
seeds (the lower reading) and the smallest of the control's (the upper
one).  Everything runs in this
one process, so the programs compile once.  The benchmark's own runs do
not run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402  (sets the paths of the program)
import spec  # noqa: E402
import traffic  # noqa: E402

CONTROL_ACT_BITS = 4


def seeds(text: str) -> list:
    out = []
    for part in filter(None, text.split(",")):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def reading(cell: dict, seed: int, quant=None, mesh=None) -> dict:
    from loop import play
    conf, mix, lim = cell["config"], cell["traffic"], cell["limits"]
    loop = run.build(conf, mix, seed, quant, mesh)
    prompts, budgets = traffic.job(mix, seed)
    job = play(loop, prompts, budgets)
    failed = sum(s is None for s in job["served"])
    del loop
    gc.collect()
    checked, (_, n_tok) = run.check(conf, mix, lim, seed, prompts, [job],
                                    failed)
    return {"seed": seed, "max_logit_gap": checked["max_logit_gap"]["value"],
            "failed": failed, "served": [len(s or []) for s in job["served"]],
            "tokens_compared": n_tok}


def main(argv=None):
    from repro.launch.device import enable_compile_cache, require_tpu
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="101-112")
    ap.add_argument("--control-seeds", default="201-203")
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    chips = int(cell["workload"]["chips"])
    mesh = run.serve_mesh(require_tpu(chips), chips)
    enable_compile_cache()
    control = run.quant_config(dict(cell["config"]["numerics"],
                                    act_bits=CONTROL_ACT_BITS))
    lower, upper = [], []
    for kind, seed_list, quant, out in (
            ("program", seeds(args.seeds), None, lower),
            ("control", seeds(args.control_seeds), control, upper)):
        for seed in seed_list:
            r = dict(reading(cell, seed, quant, mesh), kind=kind)
            out.append(r["max_logit_gap"])
            print(json.dumps(r), flush=True)
    print(json.dumps({"workload": args.workload,
                      "lower": max(lower) if lower else None,
                      "upper": min(upper) if upper else None,
                      "program": lower, "control": upper}), flush=True)


if __name__ == "__main__":
    main()
