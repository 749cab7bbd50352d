"""Serving driver CLI.

  PYTHONPATH=src python -m repro.launch.serve --arch <id> --smoke \
      --prompts "hello" "world" --max-new 32

Initializes (or loads) weights, INT4-packs them, and serves batched
requests through the Harmonia engine (BFP activations + packed
asymmetric KV cache).  Generation runs through the fused on-device loop
(single jitted scan, donated in-place cache) unless ``--host-loop`` is
given; ``--continuous`` serves the prompts through the
continuous-batching ``ServeLoop`` (finished rows swapped for queued
requests at chunk boundaries) instead of one batched ``generate`` call.

``--mesh DxM`` serves mesh-sharded: a (data=D, model=M) mesh over
``jax.devices()`` with Megatron tensor parallelism on ``model`` and the
batch + KV-cache rows on ``data`` (see ``distributed/sharding.py``).
``--force-host-devices N`` forces N host CPU devices *before* jax
initializes — the CI / laptop way to exercise a real multi-device mesh:

  PYTHONPATH=src python -m repro.launch.serve --smoke \
      --force-host-devices 8 --mesh 2x2

Serving weights are drawn and INT4-packed one layer at a time
(``init_packed_params``), so the full-precision tree never exists.
Compiled programs persist in ``$JAX_COMPILATION_CACHE_DIR``, or else in
``.jax_cache/`` at the root of the checkout (``launch/device.py``).
"""
from __future__ import annotations

import argparse
import os
import re
import time


def _parse_mesh(s: str):
    m = re.fullmatch(r"(\d+)x(\d+)", s)
    if not m:
        raise argparse.ArgumentTypeError(
            f"--mesh wants DATAxMODEL (e.g. 2x2), got {s!r}")
    return int(m.group(1)), int(m.group(2))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="harmonia-llama3.1-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompts", nargs="+",
                    default=["the shared exponent", "attention is"])
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--recipe", default="harmonia_kv4")
    ap.add_argument("--ckpt")
    ap.add_argument("--sampler", default="greedy")
    ap.add_argument("--mesh", type=_parse_mesh, default=None,
                    metavar="DxM",
                    help="mesh-sharded serving over a (data=D, model=M) "
                         "device mesh (e.g. 2x2)")
    ap.add_argument("--force-host-devices", type=int, default=0,
                    metavar="N",
                    help="force N host CPU devices (XLA_FLAGS) before jax "
                         "initializes — debug/CI meshes on one machine")
    ap.add_argument("--pallas", action="store_true",
                    help="serve through the grid-fused Pallas kernels "
                         "(prefill + 4-bit bulk decode)")
    ap.add_argument("--host-loop", action="store_true",
                    help="legacy per-token host loop instead of the "
                         "fused on-device generation loop")
    ap.add_argument("--continuous", action="store_true",
                    help="serve through the continuous-batching ServeLoop "
                         "(row swap at chunk boundaries)")
    ap.add_argument("--batch-size", type=int, default=4,
                    help="continuous-batching batch width")
    ap.add_argument("--max-steps", type=int, default=32,
                    help="continuous-batching chunk length (rounded up "
                         "to a multiple of 32)")
    args = ap.parse_args()
    if args.continuous and args.host_loop:
        ap.error("--continuous drives the fused continuation loop and "
                 "cannot run with --host-loop")
    if args.force_host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count="
            f"{args.force_host_devices} " + os.environ.get("XLA_FLAGS", ""))

    import jax

    from repro.configs import get_arch
    from repro.core.quant_config import get_recipe
    from repro.launch.device import enable_compile_cache
    from repro.models.init import abstract_params, init_packed_params
    from repro.quant.int4 import pack_params
    from repro.serving.engine import Engine, EngineConfig, ServeLoop

    enable_compile_cache()

    mesh = None
    if args.mesh is not None:
        from repro.launch.mesh import make_debug_mesh
        d, m = args.mesh
        mesh = make_debug_mesh(d, m)
        print(f"[serve] mesh-sharded: (data={d}, model={m}) over "
              f"{len(jax.devices())} {jax.default_backend()} devices")

    spec = get_arch(args.arch)
    cfg = spec.smoke if args.smoke else spec.config
    restored = None
    if args.ckpt:
        from repro.checkpoint.manager import CheckpointManager
        restored = CheckpointManager(args.ckpt).restore_latest(
            {"params": abstract_params(cfg)})
    if restored:
        print(f"[serve] restored step {restored[1]}")
        params = pack_params(restored[0]["params"])   # Engine places it
    else:
        # drawn and packed layer by layer (straight into the mesh
        # shardings): the full-precision tree never exists
        params = init_packed_params(cfg, jax.random.PRNGKey(0), mesh=mesh)

    eng = Engine(params, cfg, EngineConfig(
        max_seq=args.max_seq, max_new_tokens=args.max_new,
        quant=get_recipe(args.recipe), sampler=args.sampler,
        use_pallas_kernels=args.pallas,
        fused_loop=not args.host_loop, mesh=mesh))

    if args.continuous:
        loop = ServeLoop(eng, batch_size=args.batch_size,
                         max_steps=args.max_steps)
        t0 = time.perf_counter()
        texts = loop.serve(args.prompts)
        for p, t, rec in zip(args.prompts, texts, loop.records):
            print(f"[serve] {p!r} -> {t!r} ({len(rec.tokens)} tokens, "
                  f"first token {rec.first_token - t0:.3f} s, "
                  f"done {rec.finished - t0:.3f} s)")
        print("[serve] continuous batching: " + ", ".join(
            f"{k} {v}" for k, v in loop.stats.items()))
        return

    out = eng.generate(args.prompts)
    for p, t in zip(args.prompts, out["texts"]):
        print(f"[serve] {p!r} -> {t!r}")
    print(f"[serve] {out['tokens_per_s']:.1f} tok/s raw, "
          f"{out['useful_tokens_per_s']:.1f} tok/s useful "
          f"(EOS-truncated), KV storage fraction "
          f"{out['cache_stats']['storage_fraction']:.3f}")


if __name__ == "__main__":
    main()
