"""Pure-jnp oracles for every Pallas kernel (the correctness references).

All packed layouts match the kernels exactly:
  * activations: mant int8 (M, K) + shared exps int8 (M, K/32),
  * weights: INT4 nibbles packed 2-per-byte along K (K/2, N) + per-group-128
    fp32 scales (K/128, N),
  * V cache (attention): mant int8 grouped along the token dim,
    exps (S/32, hd).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bfp

GROUP_A = 32     # activation BFP group (contraction dim)
GROUP_W = 128    # weight INT4 group (contraction dim)


def dequant_act(a_mant: jax.Array, a_exp: jax.Array,
                mantissa_bits: int = 8) -> jax.Array:
    """(M, K) int8 + (M, K/32) int8 -> (M, K) f32."""
    M, K = a_mant.shape
    g = a_mant.reshape(M, K // GROUP_A, GROUP_A).astype(jnp.float32)
    step = bfp.pow2(a_exp.astype(jnp.float32) - (mantissa_bits - 2))
    return (g * step[..., None]).reshape(M, K)


def dequant_weight(w_packed: jax.Array, w_scale: jax.Array) -> jax.Array:
    """(K/2, N) int8 nibbles + (K/128, N) f32 -> (K, N) f32."""
    w_int = bfp.unpack_int4(w_packed, axis=0).astype(jnp.float32)  # (K, N)
    K, N = w_int.shape
    g = w_int.reshape(K // GROUP_W, GROUP_W, N)
    return (g * w_scale[:, None, :]).reshape(K, N)


def ref_bfp_quantize(x: jax.Array, mantissa_bits: int = 8,
                     rounding: str = "trunc"):
    """(M, K) fp -> (mant int8 (M, K), exp int8 (M, K/32))."""
    mant, exp = bfp.bfp_quantize(x, GROUP_A, mantissa_bits, rounding,
                                 axis=-1)
    return mant.reshape(x.shape), exp


def ref_bfp_matmul(a_mant, a_exp, w_packed, w_scale,
                   mantissa_bits: int = 8, out_dtype=jnp.float32):
    """The M8W4 GEMM oracle: dequantize then fp32 matmul."""
    a = dequant_act(a_mant, a_exp, mantissa_bits)
    w = dequant_weight(w_packed, w_scale)
    return jnp.dot(a, w, preferred_element_type=jnp.float32).astype(out_dtype)


def ref_bfp_matmul_int(a_mant, a_exp, w_packed, w_scale,
                       mantissa_bits: int = 8, out_dtype=jnp.float32):
    """Integer-subdot oracle (the literal Harmonia PE dataflow): per-32
    group int dot-products accumulated in fp32 with 2^e * scale factors.
    Numerically identical to ``ref_bfp_matmul`` up to fp accumulation
    order."""
    M, K = a_mant.shape
    w_int = bfp.unpack_int4(w_packed, axis=0).astype(jnp.int32)  # (K, N)
    N = w_int.shape[1]
    nga = K // GROUP_A
    a_g = a_mant.reshape(M, nga, GROUP_A).astype(jnp.int32)
    w_g = w_int.reshape(nga, GROUP_A, N)
    # int dot per group -> (M, nga, N)
    pp = jnp.einsum("mgk,gkn->mgn", a_g, w_g).astype(jnp.float32)
    a_step = bfp.pow2(a_exp.astype(jnp.float32) - (mantissa_bits - 2))
    rep = GROUP_W // GROUP_A
    ws = jnp.repeat(w_scale, rep, axis=0)            # (nga, N)
    return jnp.einsum("mgn,mg,gn->mn", pp, a_step, ws).astype(out_dtype)


def ref_bfp_attention_prefill(q, k_mant, k_exp, v_mant, v_exp, *,
                              mantissa_bits: int = 8, causal: bool = True,
                              logit_cap: float = 0.0, window: int = 0,
                              out_dtype=jnp.float32):
    """Single-head oracle.

    q: (S, hd) fp; K per-token BFP (S, hd)+(S, hd/32);
    V token-grouped BFP (S, hd) + (S/32, hd)."""
    S, hd = q.shape
    k = dequant_act(k_mant, k_exp, mantissa_bits)            # (S, hd)
    vg = v_mant.reshape(S // GROUP_A, GROUP_A, hd).astype(jnp.float32)
    vstep = bfp.pow2(v_exp.astype(jnp.float32) - (mantissa_bits - 2))
    v = (vg * vstep[:, None, :]).reshape(S, hd)

    s = (q.astype(jnp.float32) @ k.T) / jnp.sqrt(float(hd))
    if logit_cap > 0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    pos = jnp.arange(S)
    m = jnp.ones((S, S), bool)
    if causal:
        d = pos[:, None] - pos[None, :]
        m = d >= 0
        if window > 0:
            m &= d < window
    s = jnp.where(m, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return (p @ v).astype(out_dtype)


def ref_bfp_decode_bulk(q, k_mant4, k_exp, v_mant4, v_exp,
                        valid_len: int):
    """Decode partial-attention oracle over the 4-bit bulk region.

    q: (H, hd); k_mant4: (S, hd/2) packed; v_mant4: (S/2, hd) packed along
    tokens; returns un-normalized (o (H, hd), m (H,), l (H,)) flash triple
    so callers can merge with other regions."""
    S2 = k_mant4.shape[0]
    hd = q.shape[-1]
    k_int = bfp.unpack_int4(k_mant4, axis=-1).astype(jnp.float32)
    kstep = bfp.pow2(k_exp.astype(jnp.float32) - 2.0)        # m=4
    k = (k_int.reshape(S2, hd // GROUP_A, GROUP_A)
         * kstep[..., None]).reshape(S2, hd)
    v_int = bfp.unpack_int4(v_mant4, axis=0).astype(jnp.float32)  # (S, hd)
    S = v_int.shape[0]
    vstep = bfp.pow2(v_exp.astype(jnp.float32) - 2.0)        # (S/32, hd)
    v = (v_int.reshape(S // GROUP_A, GROUP_A, hd)
         * vstep[:, None, :]).reshape(S, hd)

    s = (q.astype(jnp.float32) @ k.T) / jnp.sqrt(float(hd))  # (H, S)
    valid = jnp.arange(S2) < valid_len
    s = jnp.where(valid[None], s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[:, None])
    p = jnp.where(valid[None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = p @ v
    return o, m, l


# Decode outputs are O(1) softmax averages of O(1) values over at most a
# few thousand tokens; a decode path differs from ``ref_attention_decode``
# only in f32 reduction order (<= 2.4e-7 measured at 512 tokens on CPU),
# so 2e-6 (~16 ulp at 1.0) tells reduction order from a defect (a wrong
# mask or region is off by ~1e-2).
DECODE_ATOL = 2e-6


def ref_attention_decode(q, k, v, valid, *, logit_cap: float = 0.0,
                         start=None):
    """Dense one-token GQA decode oracle, all in float32.

    q: (B, H, hd); k, v: (B, S, Hkv, hd) dequantized cache in position
    order (``kvcache.gather_kv``); valid: (S,) bool; start: optional (B,)
    left-pad count — positions below it are masked.  Returns normalized
    (B, H, hd)."""
    B, H, hd = q.shape
    Hkv = k.shape[2]
    qg = q.astype(jnp.float32).reshape(B, Hkv, H // Hkv, hd)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bgrd,btgd->bgrt", qg, k.astype(jnp.float32))
        s = s / jnp.sqrt(float(hd))
        if logit_cap > 0:
            s = logit_cap * jnp.tanh(s / logit_cap)
        mask = jnp.broadcast_to(valid[None], (B, k.shape[1]))
        if start is not None:
            mask = mask & (jnp.arange(k.shape[1])[None] >= start[:, None])
        s = jnp.where(mask[:, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bgrt,btgd->bgrd", p, v.astype(jnp.float32))
    return o.reshape(B, H, hd)


__all__ = ["dequant_act", "dequant_weight", "ref_bfp_quantize",
           "ref_bfp_matmul", "ref_bfp_matmul_int",
           "ref_bfp_attention_prefill", "ref_bfp_decode_bulk",
           "ref_attention_decode", "DECODE_ATOL",
           "GROUP_A", "GROUP_W"]
