"""Kernel microbenchmarks: correctness vs the jnp oracles plus wall-clock
of the grid-fused batched Pallas paths against the legacy per-head vmap
towers, at serving shapes — plus the two regression gates of the
converter/single-launch rework:

  * single-launch asymmetric-cache decode (one grid over bulk + init +
    local window, in-kernel merge) must beat the legacy bulk-kernel +
    XLA-epilogue path on wall-clock (a Pallas-vs-Pallas comparison, so
    interpret overhead cancels), with outputs that agree within
    float32 reduction-order noise (twice ``ref.DECODE_ATOL``),
  * the in-kernel FP->BFP converter prefill (the one-launch K+V pair
    kernel feeding the attention kernel, and the single-launch
    prefill-cache region converter) must be bit-exact against the
    XLA-quantize-then-kernel formulation and structurally eliminate its
    data movement: zero re-layout transposes and zero scatter/update
    chains (wall-clock recorded alongside; see the bench docstring for
    why a Pallas-vs-pure-XLA wall-clock gate would measure the
    interpreter, not the kernels).

Everything runs the interpret-mode kernels on CPU, jitted, min-of-reps.
Interpret mode executes the grid as a sequential scan, so CPU wall-clock
is dominated by per-grid-step overhead — which is exactly the quantity
the grid fusion attacks (fewer, larger grid steps and no vmap towers or
moveaxis copies; DESIGN.md §3).  Causal tile skipping is additionally
verified structurally: the traced kernel must contain a ``cond`` whose
skip branch performs no ``dot_general`` (so on TPU the skipped tiles
really skip the MXU work), and the live/total tile counts are reported.

Full runs write ``BENCH_kernels.json`` at the repo root so later PRs
have a perf trajectory; ``--fast`` (CI) runs a trimmed sweep — which
still includes both regression gates — and does not write the file.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bfp, kvcache
from repro.kernels import ops, ref
from repro.kernels.bfp_attention import (bfp_attention_prefill_batched,
                                         prefill_tile_counts)
from repro.layers import attention as attn_lib
from repro.quant.int4 import quantize_weight

from benchmarks._shared import csv

REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")
BENCH_JSON = os.path.join(REPO_ROOT, "BENCH_kernels.json")


def timeit(fn, *args, n=5):
    """(min-of-n microseconds, output) — min is robust to CPU contention
    spikes."""
    out = fn(*args)  # compile
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(n):
        t0 = time.time()
        jax.block_until_ready(fn(*args))
        best = min(best, time.time() - t0)
    return best * 1e6, out


# ---------------------------------------------------------------------------
# Tile-skip probe
# ---------------------------------------------------------------------------

def _count_dots(jaxpr) -> int:
    from jax._src import core as jcore
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            n += 1
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for x in vs:
                if isinstance(x, jcore.ClosedJaxpr):
                    n += _count_dots(x.jaxpr)
                elif isinstance(x, jcore.Jaxpr):
                    n += _count_dots(x)
    return n


def _guarded_conds(jaxpr):
    """All (branch_dot_counts) of cond eqns anywhere in ``jaxpr``."""
    from jax._src import core as jcore
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            found.append(tuple(_count_dots(b.jaxpr)
                               for b in eqn.params["branches"]))
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for x in vs:
                if isinstance(x, jcore.ClosedJaxpr):
                    found.extend(_guarded_conds(x.jaxpr))
                elif isinstance(x, jcore.Jaxpr):
                    found.extend(_guarded_conds(x))
    return found


def verify_tile_skip_guard() -> bool:
    """Trace the fused prefill kernel and check the causal guard is a
    real branch: one arm runs the QK+PV dots, the other runs none."""
    B, S, Hkv, hd = 1, 128, 1, 64
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    km, ke = ops.bfp_quantize(k)
    vm, ve = ops.quantize_v_token_grouped_batched(v)
    jaxpr = jax.make_jaxpr(
        lambda *a: bfp_attention_prefill_batched(
            *a, causal=True, block_q=64, block_s=64, interpret=True)
    )(q, km, ke, vm, ve)
    conds = _guarded_conds(jaxpr.jaxpr)
    return any(min(c) == 0 and max(c) >= 2 for c in conds if len(c) >= 2)


# ---------------------------------------------------------------------------
# Benchmarks
# ---------------------------------------------------------------------------

def _attention_inputs(rng, B, Hkv, S, hd):
    q = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    km, ke = ops.bfp_quantize(k)
    vm, ve = ops.quantize_v_token_grouped_batched(v)
    return q, km, ke, vm, ve


def bench_prefill(rng, B, Hkv, S, hd=64, n=1):
    q, km, ke, vm, ve = _attention_inputs(rng, B, Hkv, S, hd)
    legacy_us, o_l = timeit(
        lambda *a: ops.bfp_attention_prefill(*a, legacy=True),
        q, km, ke, vm, ve, n=n)
    fused_us, o_f = timeit(
        lambda *a: ops.bfp_attention_prefill(*a),
        q, km, ke, vm, ve, n=n)
    rel = (float(jnp.abs(o_f - o_l).max())
           / max(float(jnp.abs(o_l).max()), 1e-9))
    live, total = prefill_tile_counts(S)
    rec = {"B": B, "Hkv": Hkv, "S": S, "hd": hd,
           "legacy_us": round(legacy_us, 1), "fused_us": round(fused_us, 1),
           "speedup": round(legacy_us / fused_us, 2), "max_rel_err": rel,
           "tiles_live": live, "tiles_total": total}
    csv(f"kernels.prefill.B{B}.Hkv{Hkv}.S{S}", fused_us,
        f"legacy_us={legacy_us:.0f},speedup={rec['speedup']},"
        f"relerr={rel:.1e},tiles={live}/{total}")
    assert rel < 1e-5, rec
    return rec


def bench_decode(rng, B, Hkv, S, hd=64, n=3):
    H = Hkv  # rep=1 at serving shapes; GQA covered by tests
    q = jnp.asarray(rng.normal(size=(B, H, hd)).astype(np.float32))
    kb = rng.normal(size=(B, S, Hkv, hd)).astype(np.float32)
    vb = rng.normal(size=(B, S, Hkv, hd)).astype(np.float32)
    km4, ke4 = bfp.bfp_quantize(jnp.asarray(kb), 32, 4, axis=-1)
    km4 = bfp.pack_int4(km4.reshape(B, S, Hkv, hd), axis=-1)
    vm4, ve4 = bfp.bfp_quantize(jnp.asarray(vb), 32, 4, axis=1)
    vm4 = jnp.moveaxis(vm4.reshape(B, Hkv, hd, S), -1, 1)
    ve4 = jnp.moveaxis(ve4, -1, 1)
    vm4 = bfp.pack_int4(vm4, axis=1)
    vl = jnp.asarray(S // 2, jnp.int32)  # half-full cache: tiles skippable
    legacy_us, t_l = timeit(
        lambda *a: ops.bfp_attention_decode_bulk(*a, legacy=True),
        q, km4, ke4, vm4, ve4, vl, n=n)
    fused_us, t_f = timeit(
        lambda *a: ops.bfp_attention_decode_bulk(*a),
        q, km4, ke4, vm4, ve4, vl, n=n)
    o_l = t_l[0] / jnp.maximum(t_l[2], 1e-30)
    o_f = t_f[0] / jnp.maximum(t_f[2], 1e-30)
    rel = (float(jnp.abs(o_f - o_l).max())
           / max(float(jnp.abs(o_l).max()), 1e-9))
    rec = {"B": B, "Hkv": Hkv, "S": S, "hd": hd,
           "legacy_us": round(legacy_us, 1), "fused_us": round(fused_us, 1),
           "speedup": round(legacy_us / fused_us, 2), "max_rel_err": rel}
    csv(f"kernels.decode.B{B}.Hkv{Hkv}.S{S}", fused_us,
        f"legacy_us={legacy_us:.0f},speedup={rec['speedup']},"
        f"relerr={rel:.1e}")
    assert rel < 1e-5, rec
    return rec


def bench_decode_single_launch(rng, B, Hkv, S, hd=64, rep=2, n=6):
    """Single-launch asymmetric-cache decode vs the legacy bulk-kernel +
    XLA-epilogue path, on a real packed cache (jitted; outputs agree
    within twice ``ref.DECODE_ATOL``:
    each is within it of the dense oracle).  The two paths are timed *interleaved* (min of
    alternating reps) so a drifting machine load cannot flip the gate's
    sign the way back-to-back min-of-reps can."""
    H = Hkv * rep
    cache = kvcache.init_cache(B, Hkv, hd, max_seq=S)
    k = jnp.asarray(rng.normal(size=(B, S - 32, Hkv, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S - 32, Hkv, hd)).astype(np.float32))
    cache = kvcache.prefill_cache(cache, k, v)
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)).astype(np.float32))
    legacy_fn = jax.jit(lambda q, c: attn_lib.attention_decode_packed(
        q, c, use_pallas=True, single_launch=False))
    fused_fn = jax.jit(lambda q, c: attn_lib.attention_decode_packed(
        q, c, use_pallas=True, single_launch=True))
    o_l = legacy_fn(q, cache)                              # compile both
    o_f = fused_fn(q, cache)
    jax.block_until_ready((o_l, o_f))
    max_diff = float(jnp.abs(o_l - o_f).max())
    legacy_s = fused_s = float("inf")
    for _ in range(n):
        t0 = time.time()
        jax.block_until_ready(legacy_fn(q, cache))
        legacy_s = min(legacy_s, time.time() - t0)
        t0 = time.time()
        jax.block_until_ready(fused_fn(q, cache))
        fused_s = min(fused_s, time.time() - t0)
    legacy_us, fused_us = legacy_s * 1e6, fused_s * 1e6
    rec = {"B": B, "Hkv": Hkv, "rep": rep, "S": S, "hd": hd,
           "legacy_us": round(legacy_us, 1), "fused_us": round(fused_us, 1),
           "speedup": round(legacy_us / fused_us, 2), "max_abs_diff": max_diff}
    csv(f"kernels.decode_single_launch.B{B}.Hkv{Hkv}.S{S}", fused_us,
        f"legacy_us={legacy_us:.0f},speedup={rec['speedup']},"
        f"max_abs_diff={max_diff:.2e}")
    assert max_diff <= 2 * ref.DECODE_ATOL, rec
    return rec


def _count_eqns(jaxpr, names) -> int:
    """Top-level + nested eqn count, excluding pallas_call bodies (in-
    kernel ops run on the VMEM tile — they are the point)."""
    from jax._src import core as jcore
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name in names:
            total += 1
        for val in eqn.params.values():
            vs = val if isinstance(val, (tuple, list)) else (val,)
            for x in vs:
                if isinstance(x, jcore.ClosedJaxpr):
                    total += _count_eqns(x.jaxpr, names)
                elif isinstance(x, jcore.Jaxpr):
                    total += _count_eqns(x, names)
    return total


def bench_prefill_convert(rng, B, Hkv, S, hd=64, rep=2, n=3):
    """In-kernel FP->BFP converter prefill vs XLA-quantize-then-kernel:
    same attention kernel, quantize pass swapped — plus the packed-cache
    build (single-launch region converter vs the `.at[].set` chains).

    Like the causal tile skip (DESIGN.md §3), the converter's win is
    verified *structurally*, with wall-clock recorded alongside: the
    interpret-mode grid loop copies the full output buffers once per
    grid step, so CPU wall-clock charges a Pallas kernel O(grid·bytes)
    that the XLA pass never pays and real hardware never sees — it
    measures the interpreter, not the data movement the converter
    removes.  The gates assert what the converter actually eliminates:
    the whole quantize pass is ONE launch with ZERO re-layout transposes
    (the XLA pass moveaxis-copies V twice), and the cache build is ONE
    launch with ZERO scatter/`.at[].set` update chains — bit-exact on
    every output either way.
    """
    H = Hkv * rep
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)).astype(np.float32))

    def quant_xla(k, v):
        km, ke = ops.bfp_quantize(k, interpret=True)
        vm, ve = ops.quantize_v_token_grouped_batched_xla(v)
        return km, ke, vm, ve

    def quant_kernel(k, v):
        return ops.bfp_quantize_kv_pair(k, v)

    def attn_xla_quant(q, k, v):
        return ops.bfp_attention_prefill(q, *quant_xla(k, v))

    def attn_kernel_quant(q, k, v):
        return ops.bfp_attention_prefill(q, *quant_kernel(k, v))

    xla_us, o_x = timeit(jax.jit(attn_xla_quant), q, k, v, n=n)
    ker_us, o_k = timeit(jax.jit(attn_kernel_quant), q, k, v, n=n)
    exact = bool(jnp.all(o_x == o_k))

    # structural gates: re-layout copies of the quantize pass
    jx = jax.make_jaxpr(quant_xla)(k, v)
    jk = jax.make_jaxpr(quant_kernel)(k, v)
    probes = {
        "xla_transposes": _count_eqns(jx.jaxpr, {"transpose"}),
        "kernel_transposes": _count_eqns(jk.jaxpr, {"transpose"}),
    }

    cache = kvcache.init_cache(B, Hkv, hd, max_seq=S)
    cache_xla_us, c_x = timeit(
        jax.jit(lambda c, k, v: kvcache.prefill_cache(c, k, v)),
        cache, k, v, n=n)
    cache_ker_us, c_k = timeit(
        jax.jit(lambda c, k, v: kvcache.prefill_cache(c, k, v,
                                                      use_pallas=True)),
        cache, k, v, n=n)
    cache_exact = all(bool(jnp.all(a == b))
                      for a, b in zip(jax.tree.leaves(c_x),
                                      jax.tree.leaves(c_k)))
    j_cx = jax.make_jaxpr(
        lambda c, k, v: kvcache.prefill_cache(c, k, v))(cache, k, v)
    j_ck = jax.make_jaxpr(
        lambda c, k, v: kvcache.prefill_cache(c, k, v, use_pallas=True)
    )(cache, k, v)
    scatters = {"scatter", "dynamic_update_slice"}
    probes["cache_xla_updates"] = _count_eqns(j_cx.jaxpr, scatters)
    probes["cache_kernel_updates"] = _count_eqns(j_ck.jaxpr, scatters)

    rec = {"B": B, "Hkv": Hkv, "rep": rep, "S": S, "hd": hd,
           "attn_xla_quant_us": round(xla_us, 1),
           "attn_kernel_quant_us": round(ker_us, 1),
           "attn_bit_exact": exact,
           "cache_xla_us": round(cache_xla_us, 1),
           "cache_kernel_us": round(cache_ker_us, 1),
           "cache_bit_exact": cache_exact, **probes}
    csv(f"kernels.prefill_convert.B{B}.Hkv{Hkv}.S{S}", ker_us,
        f"xla_us={xla_us:.0f},relayouts={probes['xla_transposes']}->"
        f"{probes['kernel_transposes']},cache_updates="
        f"{probes['cache_xla_updates']}->{probes['cache_kernel_updates']},"
        f"bit_exact={exact}")
    assert exact and cache_exact, rec
    assert probes["kernel_transposes"] == 0 \
        and probes["xla_transposes"] >= 2, probes
    assert probes["cache_kernel_updates"] == 0 \
        and probes["cache_xla_updates"] >= 4, probes
    return rec


def bench_matmul(rng, M, K, N, block_k=None, n=3):
    a = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32)) * .05
    am, ae = ref.ref_bfp_quantize(a)
    qw = quantize_weight(w, 128)
    oracle = ref.ref_bfp_matmul(am, ae, qw.packed, qw.scale)
    us, out = timeit(
        lambda *x: ops.bfp_matmul(*x, block_k=block_k),
        am, ae, qw.packed, qw.scale, n=n)
    rel = (float(jnp.abs(out - oracle).max())
           / max(float(jnp.abs(oracle).max()), 1e-9))
    tag = f"bk{block_k}" if block_k else "fullK"
    csv(f"kernels.bfp_matmul.{M}x{K}x{N}.{tag}", us, f"relerr={rel:.2e}")
    assert rel < 1e-5
    return {"M": M, "K": K, "N": N, "block_k": block_k,
            "us": round(us, 1), "max_rel_err": rel}


def main(fast: bool = False) -> dict:
    rng = np.random.default_rng(0)
    out = {"meta": {"backend": jax.default_backend(), "interpret": True,
                    "note": "interpret-mode Pallas on CPU; wall-clock is "
                            "grid-step bound (see module docstring)"},
           "prefill": [], "decode": [], "matmul": []}

    # -- correctness spot checks (seed behavior, kept) --
    mm_shapes = [(256, 512, 256)] if fast else [(256, 512, 256),
                                               (512, 1024, 512)]
    for (M, K, N) in mm_shapes:
        out["matmul"].append(bench_matmul(rng, M, K, N))
        out["matmul"].append(bench_matmul(rng, M, K, N, block_k=128))

    x = jnp.asarray(rng.normal(size=(128, 256)).astype(np.float32))
    mk, ek = ops.bfp_quantize(x, interpret=True)
    mr, er = ref.ref_bfp_quantize(x)
    exact = bool(jnp.all(mk == mr) and jnp.all(ek == er))
    csv("kernels.bfp_quantize.128x256", 0.0, f"bit_exact={exact}")
    assert exact

    # -- tile-skip structural probe --
    skip_ok = verify_tile_skip_guard()
    csv("kernels.prefill.tile_skip_guard", 0.0, f"verified={skip_ok}")
    assert skip_ok, "causal tile-skip cond guard not found in kernel jaxpr"
    out["tile_skip_guard_verified"] = skip_ok

    # -- fused vs legacy at serving shapes --
    # single-launch gate shapes: multi-tile / multi-head, where the
    # grid-step reduction is structural (one step per batch row vs one
    # per (b, h); at tiny S=512/Hkv=2 the two paths are within CPU noise)
    if fast:
        prefill_shapes = [(1, 4, 512, 2)]
        decode_shapes = [(1, 4, 512, 3)]
        single_launch_shapes = [(2, 2, 2048, 3)]
        convert_shapes = [(2, 2, 512, 2)]
    else:
        prefill_shapes = [(1, 4, 512, 3), (1, 8, 512, 3), (8, 4, 512, 2),
                          (8, 8, 512, 2), (1, 4, 2048, 1), (8, 8, 2048, 1)]
        decode_shapes = [(1, 4, 512, 3), (8, 4, 512, 3), (1, 8, 2048, 3),
                         (8, 8, 2048, 3)]
        single_launch_shapes = [(2, 2, 2048, 3), (8, 8, 512, 3),
                                (8, 4, 2048, 2)]
        convert_shapes = [(2, 2, 512, 3), (8, 4, 512, 2), (2, 4, 2048, 2)]
    for (B, Hkv, S, n) in prefill_shapes:
        out["prefill"].append(bench_prefill(rng, B, Hkv, S, n=n))
    for (B, Hkv, S, n) in decode_shapes:
        out["decode"].append(bench_decode(rng, B, Hkv, S, n=n))
    out["decode_single_launch"] = [
        bench_decode_single_launch(rng, B, Hkv, S, n=n)
        for (B, Hkv, S, n) in single_launch_shapes]
    out["prefill_convert"] = [bench_prefill_convert(rng, B, Hkv, S, n=n)
                              for (B, Hkv, S, n) in convert_shapes]

    # -- regression gates (run in --fast too: the CI kernel gate) --
    for r in out["decode_single_launch"]:
        assert r["speedup"] >= 1.0, (
            f"single-launch decode slower than the legacy kernel+epilogue "
            f"path at {r}")

    if not fast:
        key = next(r for r in out["prefill"]
                   if (r["B"], r["Hkv"], r["S"]) == (8, 8, 2048))
        assert key["speedup"] >= 1.5, (
            f"grid-fused prefill speedup {key['speedup']} < 1.5x at "
            f"(B=8, Hkv=8, S=2048)")
        with open(BENCH_JSON, "w") as f:
            json.dump(out, f, indent=1)
        print(f"# wrote {os.path.normpath(BENCH_JSON)}")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    main(fast=ap.parse_args().fast)
