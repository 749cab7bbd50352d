"""Jitted public wrappers around the Pallas kernels.

``interpret`` defaults to True on the CPU backend (tests validate kernel
numerics in the interpreter) and False on a TPU (real Mosaic kernels);
any other backend raises.

The attention wrappers default to the grid-fused batched kernels
(one ``pallas_call`` over the (batch × kv-head) grid, zero layout
copies).  ``legacy=True`` selects the original per-head kernels driven
by ``jax.vmap`` towers plus four ``moveaxis`` transposes per call —
kept as a numerical-comparison escape hatch and as the baseline for
``benchmarks/kernels_micro.py``.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import bfp
from repro.kernels.bfp_attention import (BLOCK_Q_BATCHED, BLOCK_S_BATCHED,
                                         BLOCK_S_DECODE,
                                         bfp_attention_decode_asym_batched,
                                         bfp_attention_decode_batched,
                                         bfp_attention_decode_kernel,
                                         bfp_attention_prefill_batched,
                                         bfp_attention_prefill_kernel)
from repro.kernels.bfp_matmul import bfp_matmul_kernel, choose_dataflow
from repro.kernels.bfp_quant import (bfp_quantize_kernel,
                                     bfp_quantize_kv_batched_kernel,
                                     bfp_quantize_kv_pair_kernel,
                                     bfp_quantize_v_batched_kernel,
                                     convert_prefill_cache_kernel)

GROUP = 32

# seed-era defaults of the per-head kernels, kept for the legacy path
LEGACY_BLOCK_Q = 128
LEGACY_BLOCK_S = 128


def _default_interpret() -> bool:
    """Mosaic on a TPU, the Pallas interpreter on the CPU (tests), and
    an error anywhere else: these kernels are written for the TPU, and
    interpreting them on another accelerator would hide that."""
    backend = jax.default_backend()
    if backend in ("tpu", "cpu"):
        return backend == "cpu"
    raise RuntimeError(f"Pallas kernels need a TPU (or the CPU "
                       f"interpreter); backend is {backend!r}")


@partial(jax.jit, static_argnames=("mantissa_bits", "rounding", "interpret"))
def bfp_quantize(x, mantissa_bits: int = 8, rounding: str = "trunc",
                 interpret: Optional[bool] = None):
    """(..., K) fp -> (mant int8 (..., K), exp int8 (..., K/32))."""
    interpret = _default_interpret() if interpret is None else interpret
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m, e = bfp_quantize_kernel(x2, mantissa_bits=mantissa_bits,
                               rounding=rounding, interpret=interpret)
    return (m.reshape(lead + (x.shape[-1],)),
            e.reshape(lead + (x.shape[-1] // GROUP,)))


@partial(jax.jit, static_argnames=("mantissa_bits", "dataflow", "block_k",
                                   "int_path", "interpret"))
def bfp_matmul(a_mant, a_exp, w_packed, w_scale, mantissa_bits: int = 8,
               dataflow: str = "auto", block_k: Optional[int] = None,
               int_path: bool = False,
               interpret: Optional[bool] = None):
    """Packed BFP-INT GEMM; leading activation dims are flattened to M.

    ``block_k``: contraction tile for the K-blocked grid (VMEM-bounded
    K); None keeps the whole contraction dim resident."""
    interpret = _default_interpret() if interpret is None else interpret
    lead = a_mant.shape[:-1]
    K = a_mant.shape[-1]
    am = a_mant.reshape(-1, K)
    ae = a_exp.reshape(-1, K // GROUP)
    out = bfp_matmul_kernel(am, ae, w_packed, w_scale,
                            mantissa_bits=mantissa_bits, dataflow=dataflow,
                            block_k=block_k, int_path=int_path,
                            interpret=interpret)
    return out.reshape(lead + (w_packed.shape[-1],))


@partial(jax.jit, static_argnames=("mantissa_bits", "dataflow", "block_k",
                                   "interpret"))
def bfp_linear(x, w_packed, w_scale, mantissa_bits: int = 8,
               dataflow: str = "auto", block_k: Optional[int] = None,
               interpret: Optional[bool] = None):
    """Fused convenience: FP activations -> BFP (kernel) -> BFP-INT GEMM.

    This is the full Harmonia linear-layer path: the converter keeps x
    compressed between layers; the GEMM consumes packed operands."""
    am, ae = bfp_quantize(x, mantissa_bits, interpret=interpret)
    return bfp_matmul(am, ae, w_packed, w_scale, mantissa_bits,
                      dataflow, block_k, interpret=interpret)


def quantize_v_token_grouped(v, mantissa_bits: int = 8):
    """(S, hd) fp -> token-grouped packed V: (mant (S, hd), exp (S/32, hd))."""
    S, hd = v.shape
    m, e = bfp.bfp_quantize(v, GROUP, mantissa_bits, axis=0)
    # bfp_quantize moves axis 0 last: m (hd, S/32, 32), e (hd, S/32)
    m = jnp.moveaxis(m, (0, 1, 2), (2, 0, 1)).reshape(S, hd)
    return m, e.T


def quantize_v_token_grouped_batched_xla(v, mantissa_bits: int = 8):
    """XLA reference for :func:`quantize_v_token_grouped_batched` (the
    pre-converter-kernel formulation: quantize along axis 1, then two
    ``moveaxis`` re-layout copies) — kept as the converter benchmark
    baseline and bit-exactness oracle."""
    B, S, Hkv, hd = v.shape
    m, e = bfp.bfp_quantize(v, GROUP, mantissa_bits, axis=1)
    # token axis moved last: m (B, Hkv, hd, S/32, 32), e (B, Hkv, hd, S/32)
    m = jnp.moveaxis(m.reshape(B, Hkv, hd, S), -1, 1)
    e = jnp.moveaxis(e, -1, 1)
    return m, e


@partial(jax.jit, static_argnames=("mantissa_bits", "pack", "interpret"))
def quantize_v_token_grouped_batched(v, mantissa_bits: int = 8,
                                     pack: bool = False,
                                     interpret: Optional[bool] = None):
    """(B, S, Hkv, hd) fp -> token-grouped packed V in the batched kernel
    layout: (mant (B, S, Hkv, hd), exp (B, S/32, Hkv, hd)) — through the
    grid-fused converter kernel (the token-group reduction and optional
    int4 token-pair packing run on the VMEM tile; no moveaxis copies).
    """
    interpret = _default_interpret() if interpret is None else interpret
    return bfp_quantize_v_batched_kernel(
        v, mantissa_bits=mantissa_bits, pack=pack, interpret=interpret)


@partial(jax.jit, static_argnames=("mantissa_bits", "pack", "interpret"))
def bfp_quantize_kv_batched(x, mantissa_bits: int = 8, pack: bool = False,
                            interpret: Optional[bool] = None):
    """(B, S, Hkv, hd) fp -> per-token-grouped packed K in the batched
    kernel layout: (mant (B, S, Hkv, hd) — nibble-packed (B, S, Hkv,
    hd/2) when ``pack`` — , exp (B, S, Hkv, hd/32))."""
    interpret = _default_interpret() if interpret is None else interpret
    return bfp_quantize_kv_batched_kernel(
        x, mantissa_bits=mantissa_bits, pack=pack, interpret=interpret)


@partial(jax.jit, static_argnames=("mantissa_bits", "interpret"))
def bfp_quantize_kv_pair(k, v, mantissa_bits: int = 8,
                         interpret: Optional[bool] = None):
    """One-launch FP->BFP conversion of fresh K and V for the prefill
    attention kernel: per-token K groups + token-grouped V share one
    (B·Hkv, S/bs) grid.  Returns (k_mant, k_exp, v_mant, v_exp)."""
    interpret = _default_interpret() if interpret is None else interpret
    return bfp_quantize_kv_pair_kernel(
        k, v, mantissa_bits=mantissa_bits, interpret=interpret)


@partial(jax.jit, static_argnames=("s_bulk", "interpret"))
def convert_prefill_cache(k, v, k_offsets, s_bulk: int,
                          interpret: Optional[bool] = None):
    """Single-launch FP->BFP conversion of a dense prefill chunk into all
    packed asymmetric-cache regions (dict keyed by ``AsymKVCache`` field
    names) — see ``bfp_quant.convert_prefill_cache_kernel``."""
    interpret = _default_interpret() if interpret is None else interpret
    return convert_prefill_cache_kernel(k, v, k_offsets, s_bulk=s_bulk,
                                        interpret=interpret)


@partial(jax.jit, static_argnames=("mantissa_bits", "causal", "logit_cap",
                                   "window", "legacy", "block_q", "block_s",
                                   "interpret"))
def bfp_attention_prefill(q, k_mant, k_exp, v_mant, v_exp,
                          mantissa_bits: int = 8, causal: bool = True,
                          logit_cap: float = 0.0, window: int = 0,
                          legacy: bool = False,
                          block_q: Optional[int] = None,
                          block_s: Optional[int] = None,
                          interpret: Optional[bool] = None):
    """Batched GQA prefill attention on packed K/V.

    q: (B, S, H, hd); K: (B, S, Hkv, hd)+(B, S, Hkv, hd/32);
    V token-grouped: (B, S, Hkv, hd)+(B, S/32, Hkv, hd).
    Returns (B, S, H, hd) f32.

    Default path: one grid-fused ``pallas_call`` (grid (B·Hkv, S/bq,
    S/bs), rep folded into the q tile, causal tiles skipped).
    ``legacy=True``: the original per-head kernel under a triple vmap
    tower with moveaxis layout copies."""
    interpret = _default_interpret() if interpret is None else interpret
    B, S, H, hd = q.shape
    Hkv = k_mant.shape[2]
    rep = H // Hkv

    if not legacy:
        # scale the default q tile down by the folded query group: the
        # (bq*rep, bs) score tile and (bq*rep, hd) accumulator grow with
        # rep, and high-rep GQA/MQA configs (rep 12-16) would otherwise
        # blow the TPU VMEM budget at the 512 default
        bq_default = max(BLOCK_Q_BATCHED // rep, 128)
        return bfp_attention_prefill_batched(
            q, k_mant, k_exp, v_mant, v_exp, mantissa_bits=mantissa_bits,
            causal=causal, logit_cap=logit_cap, window=window,
            block_q=block_q or bq_default,
            block_s=block_s or BLOCK_S_BATCHED, interpret=interpret)

    single = partial(bfp_attention_prefill_kernel,
                     mantissa_bits=mantissa_bits, causal=causal,
                     logit_cap=logit_cap, window=window,
                     block_q=block_q or LEGACY_BLOCK_Q,
                     block_s=block_s or LEGACY_BLOCK_S,
                     interpret=interpret)
    # vmap: rep (q only) -> kv head -> batch
    f = jax.vmap(single, in_axes=(0, None, None, None, None))
    f = jax.vmap(f, in_axes=(0, 0, 0, 0, 0))
    f = jax.vmap(f, in_axes=(0, 0, 0, 0, 0))
    qg = jnp.moveaxis(q.reshape(B, S, Hkv, rep, hd), 1, 3)   # B,Hkv,rep,S,hd
    km = jnp.moveaxis(k_mant, 1, 2)                          # B,Hkv,S,hd
    ke = jnp.moveaxis(k_exp, 1, 2)
    vm = jnp.moveaxis(v_mant, 1, 2)
    ve = jnp.moveaxis(v_exp, 1, 2)                           # B,Hkv,S/32,hd
    o = f(qg, km, ke, vm, ve)                                # B,Hkv,rep,S,hd
    return jnp.moveaxis(o, 3, 1).reshape(B, S, H, hd)


@partial(jax.jit, static_argnames=("logit_cap", "legacy", "block_s",
                                   "interpret"))
def bfp_attention_decode_bulk(q, k_mant4, k_exp, v_mant4, v_exp, valid_len,
                              start=None, logit_cap: float = 0.0,
                              legacy: bool = False,
                              block_s: Optional[int] = None,
                              interpret: Optional[bool] = None):
    """Batched GQA decode over the 4-bit bulk cache region.

    q: (B, H, hd) (one token); k_mant4: (B, S, Hkv, hd/2);
    k_exp: (B, S, Hkv, hd/32); v_mant4: (B, S/2, Hkv, hd);
    v_exp: (B, S/32, Hkv, hd); valid_len: () int32;
    start: optional (B,) int32 first valid slot per row (left-pad mask —
    fused path only).
    Returns flash triple (o (B,H,hd), m (B,H,1), l (B,H,1)).

    Default path: one grid-fused ``pallas_call`` over (B·Hkv, S/bs) with
    dead key tiles skipped.  ``legacy=True``: per-head kernel under a
    double vmap tower."""
    interpret = _default_interpret() if interpret is None else interpret
    B, H, hd = q.shape
    Hkv = k_mant4.shape[2]
    rep = H // Hkv

    if not legacy:
        return bfp_attention_decode_batched(
            q, k_mant4, k_exp, v_mant4, v_exp, valid_len, start=start,
            logit_cap=logit_cap, block_s=block_s or BLOCK_S_DECODE,
            interpret=interpret)

    if start is not None:
        raise ValueError("per-row start masking requires the fused path")
    if logit_cap > 0:
        raise ValueError("logit_cap requires the fused path")
    single = partial(bfp_attention_decode_kernel, interpret=interpret,
                     **({"block_s": block_s} if block_s else {}))
    f = jax.vmap(single, in_axes=(0, 0, 0, 0, 0, None))      # kv heads
    f = jax.vmap(f, in_axes=(0, 0, 0, 0, 0, None))           # batch
    qg = q.reshape(B, Hkv, rep, hd)
    km = jnp.moveaxis(k_mant4, 1, 2)
    ke = jnp.moveaxis(k_exp, 1, 2)
    vm = jnp.moveaxis(v_mant4, 1, 2)
    ve = jnp.moveaxis(v_exp, 1, 2)
    o, m, l = f(qg, km, ke, vm, ve, valid_len)
    return (o.reshape(B, H, hd), m.reshape(B, H, 1), l.reshape(B, H, 1))


@partial(jax.jit, static_argnames=("logit_cap", "block_s", "interpret"))
def bfp_attention_decode_cache(q, cache, start=None, logit_cap: float = 0.0,
                               block_s: Optional[int] = None,
                               interpret: Optional[bool] = None):
    """Single-launch batched GQA decode of q (B, H, hd) against a packed
    ``AsymKVCache``: one grid covers the 4-bit bulk region, the 8-bit
    init block and the recent local window (K ring + freshly-demoted
    band, V group ring + residual), with per-region dequant in the tile
    body and the flash triples merged in-kernel.  Returns normalized
    (B, H, hd) f32 — no XLA epilogue, no extra launches.
    """
    interpret = _default_interpret() if interpret is None else interpret
    return bfp_attention_decode_asym_batched(
        q, cache.k_bulk_mant, cache.k_bulk_exp,
        cache.v_bulk_mant, cache.v_bulk_exp,
        cache.k_init_mant, cache.k_init_exp,
        cache.k_local_mant, cache.k_local_exp,
        cache.v_init_mant, cache.v_init_exp,
        cache.v_local_mant, cache.v_local_exp, cache.v_resid,
        cache.length, start=start, logit_cap=logit_cap,
        block_s=block_s or BLOCK_S_DECODE, interpret=interpret)


__all__ = ["bfp_quantize", "bfp_matmul", "bfp_linear",
           "bfp_attention_prefill", "bfp_attention_decode_bulk",
           "bfp_attention_decode_cache", "bfp_quantize_kv_batched",
           "bfp_quantize_kv_pair",
           "quantize_v_token_grouped", "quantize_v_token_grouped_batched",
           "quantize_v_token_grouped_batched_xla", "convert_prefill_cache",
           "choose_dataflow"]
