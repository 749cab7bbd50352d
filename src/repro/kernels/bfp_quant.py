"""Real-time FP->BFP converter kernels (paper Sec. IV-C, TPU-adapted).

The ASIC converter sits on the PE-array output path; on TPU the same role
is a VMEM-tiled Pallas kernel that streams an fp tile, reduces the
per-group max exponent, shifts/truncates mantissas, and writes the packed
(mant, exp) pair — used to keep activations BFP-compressed in HBM.

Three converter generations live here:

* ``bfp_quantize_kernel`` — flat (M, K) per-token groups along K
  (grid (M/bm, K/bk)); the linear-layer activation converter.
* ``bfp_quantize_kv_batched_kernel`` / ``bfp_quantize_v_batched_kernel``
  — grid-fused batched converters in the cache-native (B, S, Hkv, hd)
  layout (grid (B·Hkv, S/bs), all (batch, head) selection in BlockSpec
  index maps).  K groups run along head_dim per token; V groups along the
  token dim per channel (paper Fig. 6a).  ``pack=True`` nibble-packs
  4-bit mantissas two-per-byte *in VMEM* (pairs along head_dim for K,
  pairs along the token axis for V), so only packed bytes ever reach HBM.
* ``convert_prefill_cache_kernel`` — the single-launch asymmetric-cache
  builder: one ``pallas_call`` over (B·Hkv,) converts a dense prefill
  K/V chunk into *all* packed cache regions (8-bit init, 8-bit K local
  ring / V group ring in ring-slot order, 4-bit nibble-packed bulk with
  bulk-relative exponents) — replacing ``kvcache.prefill_cache``'s XLA
  quantize + ``.at[].set`` chains.  The 8-bit and 4-bit mantissas share
  one exponent reduction (the shared exponent depends only on the group
  absmax, not the mantissa width).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bfp import pow2, shared_exponent

GROUP = 32


def _mantissa(g, e, mantissa_bits: int, rounding: str = "trunc"):
    """g: (..., n_groups, GROUP) fp32 with exps e (..., n_groups) -> f32
    mantissa values in [-(2^(m-1)-1), 2^(m-1)-1]."""
    step = pow2(e - (mantissa_bits - 2))
    scaled = g / step[..., None]
    m = jnp.trunc(scaled) if rounding == "trunc" else jnp.round(scaled)
    lim = float(2 ** (mantissa_bits - 1) - 1)
    return jnp.clip(m, -lim, lim)


def _pack_nibbles(m, axis: int):
    """Pack int4-valued f32/int8 mantissas two-per-byte along ``axis``
    (low nibble = even index) — mirrors ``bfp.pack_int4``."""
    m = jnp.moveaxis(m, axis, -1).astype(jnp.int8)
    lo = m[..., 0::2].astype(jnp.uint8) & 0xF
    hi = m[..., 1::2].astype(jnp.uint8) & 0xF
    packed = (lo | (hi << 4)).astype(jnp.int8)
    return jnp.moveaxis(packed, -1, axis)


def _quant_kernel(x_ref, mant_ref, exp_ref, *, mantissa_bits: int,
                  rounding: str):
    x = x_ref[...].astype(jnp.float32)                 # (bm, bk)
    bm, bk = x.shape
    g = x.reshape(bm, bk // GROUP, GROUP)
    e = shared_exponent(jnp.max(jnp.abs(g), axis=-1))  # (bm, bk/32)
    m = _mantissa(g, e, mantissa_bits, rounding)
    mant_ref[...] = m.reshape(bm, bk).astype(jnp.int8)
    exp_ref[...] = e.astype(jnp.int8)


def bfp_quantize_kernel(x: jax.Array, *, mantissa_bits: int = 8,
                        rounding: str = "trunc", block_m: int = 256,
                        block_k: int = 512, interpret: bool = False):
    """x: (M, K) fp -> (mant int8 (M, K), exp int8 (M, K/32)).

    K must be a multiple of 32; blocks are clamped to the array."""
    M, K = x.shape
    if K % GROUP:
        raise ValueError(f"K={K} must be a multiple of {GROUP}")
    bm = min(block_m, M)
    bk = min(block_k, K)
    if K % bk:
        bk = K  # fall back to one K block when not divisible
    if M % bm:
        bm = M
    grid = (M // bm, K // bk)
    kernel = functools.partial(_quant_kernel, mantissa_bits=mantissa_bits,
                               rounding=rounding)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bk // GROUP), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, K), jnp.int8),
            jax.ShapeDtypeStruct((M, K // GROUP), jnp.int8),
        ],
        interpret=interpret,
    )(x)


# ---------------------------------------------------------------------------
# Grid-fused batched converters (cache-native (B, S, Hkv, hd) layout)
# ---------------------------------------------------------------------------

def _aligned_block(S: int, block: int) -> int:
    b = min(block, S)
    b -= b % GROUP
    while b >= GROUP:
        if S % b == 0:
            return b
        b -= GROUP
    return S


def _quant_kv_batched_kernel(x_ref, mant_ref, exp_ref, *, mantissa_bits,
                             rounding, pack):
    x = x_ref[0, :, 0].astype(jnp.float32)             # (bs, hd)
    bs, hd = x.shape
    g = x.reshape(bs, hd // GROUP, GROUP)
    e = shared_exponent(jnp.max(jnp.abs(g), axis=-1))  # (bs, hd/32)
    m = _mantissa(g, e, mantissa_bits, rounding).reshape(bs, hd)
    if pack:
        mant_ref[0, :, 0] = _pack_nibbles(m, axis=-1)
    else:
        mant_ref[0, :, 0] = m.astype(jnp.int8)
    exp_ref[0, :, 0] = e.astype(jnp.int8)


def bfp_quantize_kv_batched_kernel(x: jax.Array, *, mantissa_bits: int = 8,
                                   rounding: str = "trunc",
                                   pack: bool = False,
                                   block_s: int = 512,
                                   interpret: bool = False):
    """Batched K-style converter: per-token groups along head_dim.

    x: (B, S, Hkv, hd) fp -> (mant (B, S, Hkv, hd) i8 — or nibble-packed
    (B, S, Hkv, hd/2) when ``pack`` — , exp (B, S, Hkv, hd/32) i8).
    Grid (B·Hkv, S/bs); no operand is ever transposed or copied.
    """
    B, S, Hkv, hd = x.shape
    if hd % GROUP:
        raise ValueError(f"head_dim {hd} must be a multiple of {GROUP}")
    if pack and mantissa_bits != 4:
        raise ValueError("nibble packing requires mantissa_bits=4")
    bs = _aligned_block(S, block_s) if S % GROUP == 0 else S
    hd_out = hd // 2 if pack else hd
    kernel = functools.partial(_quant_kv_batched_kernel,
                               mantissa_bits=mantissa_bits,
                               rounding=rounding, pack=pack)
    return pl.pallas_call(
        kernel,
        grid=(B * Hkv, S // bs),
        in_specs=[pl.BlockSpec((1, bs, 1, hd),
                               lambda b, j: (b // Hkv, j, b % Hkv, 0))],
        out_specs=[
            pl.BlockSpec((1, bs, 1, hd_out),
                         lambda b, j: (b // Hkv, j, b % Hkv, 0)),
            pl.BlockSpec((1, bs, 1, hd // GROUP),
                         lambda b, j: (b // Hkv, j, b % Hkv, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, Hkv, hd_out), jnp.int8),
            jax.ShapeDtypeStruct((B, S, Hkv, hd // GROUP), jnp.int8),
        ],
        interpret=interpret,
    )(x)


def _quant_v_batched_kernel(x_ref, mant_ref, exp_ref, *, mantissa_bits,
                            rounding, pack):
    x = x_ref[0, :, 0].astype(jnp.float32)             # (bs, hd)
    bs, hd = x.shape
    g = jnp.moveaxis(x.reshape(bs // GROUP, GROUP, hd), 1, 2)
    e = shared_exponent(jnp.max(jnp.abs(g), axis=-1))  # (bs/32, hd)
    m = _mantissa(g, e, mantissa_bits, rounding)       # (bs/32, hd, 32)
    m = jnp.moveaxis(m, 2, 1).reshape(bs, hd)
    if pack:
        mant_ref[0, :, 0] = _pack_nibbles(m, axis=0)
    else:
        mant_ref[0, :, 0] = m.astype(jnp.int8)
    exp_ref[0, :, 0] = e.astype(jnp.int8)


def bfp_quantize_v_batched_kernel(v: jax.Array, *, mantissa_bits: int = 8,
                                  rounding: str = "trunc",
                                  pack: bool = False,
                                  block_s: int = 512,
                                  interpret: bool = False):
    """Batched V-style converter: 32-token groups along the token axis
    (the P·V contraction direction, paper Fig. 6a).

    v: (B, S, Hkv, hd) fp, S % 32 == 0 -> (mant (B, S, Hkv, hd) i8 — or
    token-packed (B, S/2, Hkv, hd) when ``pack`` — , exp (B, S/32, Hkv,
    hd) i8).  Replaces the XLA moveaxis re-layout chain of the old
    ``quantize_v_token_grouped_batched``: the token-group reduction and
    the (optional) nibble packing happen on the VMEM tile.
    """
    B, S, Hkv, hd = v.shape
    if S % GROUP:
        raise ValueError(f"token extent {S} must be a multiple of {GROUP}")
    if pack and mantissa_bits != 4:
        raise ValueError("nibble packing requires mantissa_bits=4")
    bs = _aligned_block(S, block_s)
    s_out = S // 2 if pack else S
    bs_out = bs // 2 if pack else bs
    kernel = functools.partial(_quant_v_batched_kernel,
                               mantissa_bits=mantissa_bits,
                               rounding=rounding, pack=pack)
    return pl.pallas_call(
        kernel,
        grid=(B * Hkv, S // bs),
        in_specs=[pl.BlockSpec((1, bs, 1, hd),
                               lambda b, j: (b // Hkv, j, b % Hkv, 0))],
        out_specs=[
            pl.BlockSpec((1, bs_out, 1, hd),
                         lambda b, j: (b // Hkv, j, b % Hkv, 0)),
            pl.BlockSpec((1, bs // GROUP, 1, hd),
                         lambda b, j: (b // Hkv, j, b % Hkv, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, s_out, Hkv, hd), jnp.int8),
            jax.ShapeDtypeStruct((B, S // GROUP, Hkv, hd), jnp.int8),
        ],
        interpret=interpret,
    )(v)


def _quant_kv_pair_kernel(k_ref, v_ref, km_ref, ke_ref, vm_ref, ve_ref, *,
                          mantissa_bits, rounding):
    _quant_kv_batched_kernel(k_ref, km_ref, ke_ref,
                             mantissa_bits=mantissa_bits,
                             rounding=rounding, pack=False)
    _quant_v_batched_kernel(v_ref, vm_ref, ve_ref,
                            mantissa_bits=mantissa_bits,
                            rounding=rounding, pack=False)


def bfp_quantize_kv_pair_kernel(k: jax.Array, v: jax.Array, *,
                                mantissa_bits: int = 8,
                                rounding: str = "trunc",
                                block_s: int = 2048,
                                interpret: bool = False):
    """One-launch K+V converter for the attention-prefill quantize pass:
    per-token K groups and token-grouped V share the (B·Hkv, S/bs) grid,
    so the whole FP->BFP pass is a single ``pallas_call`` (the old XLA
    pass was two quantizes plus two ``moveaxis`` re-layout copies of V).

    k, v: (B, S, Hkv, hd) fp, S % 32 == 0 -> (k_mant, k_exp, v_mant,
    v_exp) in the batched attention-kernel layouts.
    """
    B, S, Hkv, hd = k.shape
    if S % GROUP or hd % GROUP:
        raise ValueError("S and head_dim must be multiples of 32")
    bs = _aligned_block(S, block_s)
    kernel = functools.partial(_quant_kv_pair_kernel,
                               mantissa_bits=mantissa_bits,
                               rounding=rounding)

    def spec(T, d):
        return pl.BlockSpec((1, T, 1, d),
                            lambda b, j: (b // Hkv, j, b % Hkv, 0))

    return pl.pallas_call(
        kernel,
        grid=(B * Hkv, S // bs),
        in_specs=[spec(bs, hd), spec(bs, hd)],
        out_specs=[spec(bs, hd), spec(bs, hd // GROUP),
                   spec(bs, hd), spec(bs // GROUP, hd)],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, Hkv, hd), jnp.int8),
            jax.ShapeDtypeStruct((B, S, Hkv, hd // GROUP), jnp.int8),
            jax.ShapeDtypeStruct((B, S, Hkv, hd), jnp.int8),
            jax.ShapeDtypeStruct((B, S // GROUP, Hkv, hd), jnp.int8),
        ],
        interpret=interpret,
    )(k, v)


# ---------------------------------------------------------------------------
# Single-launch prefill-cache converter (all asymmetric regions)
# ---------------------------------------------------------------------------

from repro.core.kvcache import (INIT_TOKENS, LOCAL_TOKENS,  # noqa: E402
                                V_LOCAL_GROUPS)


def _prefill_cache_kernel(k_ref, v_ref, off_ref,
                          kim_ref, kie_ref, klm_ref, kle_ref,
                          kbm_ref, kbe_ref, vim_ref, vie_ref,
                          vlm_ref, vle_ref, vbm_ref, vbe_ref, *,
                          S, s_bulk):
    hd = k_ref.shape[-1]
    i8 = jnp.int8
    cg = S // GROUP

    # ---- K: one shared-exponent reduction feeds the 8b and 4b paths ----
    k = k_ref[0, :, 0].astype(jnp.float32) - off_ref[0, 0][None, :]
    kg = k.reshape(S, hd // GROUP, GROUP)
    ke = shared_exponent(jnp.max(jnp.abs(kg), axis=-1))  # (S, hd/32)
    km8 = _mantissa(kg, ke, 8).reshape(S, hd)

    kim_ref[0, :, 0] = km8[:INIT_TOKENS].astype(i8)
    kie_ref[0, :, 0] = ke[:INIT_TOKENS].astype(i8)

    # local ring: tokens [max(32, S-64), S) at slot (t-32)%64
    ring_lo = max(INIT_TOKENS, S - LOCAL_TOKENS)
    if S <= INIT_TOKENS:
        klm = jnp.zeros((LOCAL_TOKENS, hd), i8)
        kle = jnp.zeros((LOCAL_TOKENS, hd // GROUP), i8)
    elif S - INIT_TOKENS < LOCAL_TOKENS:
        pad = LOCAL_TOKENS - (S - ring_lo)
        klm = jnp.concatenate(
            [km8[ring_lo:].astype(i8), jnp.zeros((pad, hd), i8)])
        kle = jnp.concatenate(
            [ke[ring_lo:].astype(i8),
             jnp.zeros((pad, hd // GROUP), i8)])
    else:
        shift = (ring_lo - INIT_TOKENS) % LOCAL_TOKENS
        klm = jnp.roll(km8[ring_lo:].astype(i8), shift, axis=0)
        kle = jnp.roll(ke[ring_lo:].astype(i8), shift, axis=0)
    klm_ref[0, :, 0] = klm
    kle_ref[0, :, 0] = kle

    # bulk: tokens [32, S-64) at 4-bit, nibble-packed along head_dim
    n_bulk = max(0, S - LOCAL_TOKENS - INIT_TOKENS)
    kbm = jnp.zeros((s_bulk, hd // 2), i8)
    kbe = jnp.zeros((s_bulk, hd // GROUP), i8)
    if n_bulk > 0:
        km4 = _mantissa(kg[INIT_TOKENS:INIT_TOKENS + n_bulk],
                        ke[INIT_TOKENS:INIT_TOKENS + n_bulk],
                        4).reshape(n_bulk, hd)
        kbm = jnp.concatenate(
            [_pack_nibbles(km4, axis=-1),
             jnp.zeros((s_bulk - n_bulk, hd // 2), i8)])
        kbe = jnp.concatenate(
            [ke[INIT_TOKENS:INIT_TOKENS + n_bulk].astype(i8),
             jnp.zeros((s_bulk - n_bulk, hd // GROUP), i8)])
    kbm_ref[0, :, 0] = kbm
    kbe_ref[0, :, 0] = kbe

    # ---- V: token groups, again one exponent reduction for both widths ----
    v = v_ref[0, :, 0].astype(jnp.float32)
    vg = jnp.moveaxis(v.reshape(cg, GROUP, hd), 1, 2)  # (cg, hd, 32)
    ve = shared_exponent(jnp.max(jnp.abs(vg), axis=-1))  # (cg, hd)
    vm8 = jnp.moveaxis(_mantissa(vg, ve, 8), 2, 1)     # (cg, 32, hd)

    vim_ref[0, :, 0] = vm8[0].astype(i8)
    vie_ref[0, :, 0] = ve[:1].astype(i8)

    # local group ring: groups {cg-2, cg-1} (>= 1) at slot g%2
    ring = [None] * V_LOCAL_GROUPS
    for g in (cg - V_LOCAL_GROUPS, cg - 1):
        if g >= 1:
            ring[g % V_LOCAL_GROUPS] = g
    vlm_ref[0, :, 0] = jnp.concatenate(
        [vm8[g].astype(i8) if g is not None
         else jnp.zeros((GROUP, hd), i8) for g in ring])
    vle_ref[0, :, 0] = jnp.concatenate(
        [ve[g:g + 1].astype(i8) if g is not None
         else jnp.zeros((1, hd), i8) for g in ring])

    # bulk: groups 1..cg-3 at 4-bit, nibble-packed along the token axis,
    # exponents in bulk-relative slots (group g at slot g-1)
    n_bulk_g = max(0, cg - V_LOCAL_GROUPS - 1)
    vbm = jnp.zeros((s_bulk // 2, hd), i8)
    vbe = jnp.zeros((s_bulk // GROUP, hd), i8)
    if n_bulk_g > 0:
        vm4 = jnp.moveaxis(_mantissa(vg[1:1 + n_bulk_g],
                                     ve[1:1 + n_bulk_g], 4), 2, 1)
        vm4 = vm4.reshape(n_bulk_g * GROUP, hd)
        vbm = jnp.concatenate(
            [_pack_nibbles(vm4, axis=0),
             jnp.zeros((s_bulk // 2 - n_bulk_g * GROUP // 2, hd), i8)])
        vbe = jnp.concatenate(
            [ve[1:1 + n_bulk_g].astype(i8),
             jnp.zeros((s_bulk // GROUP - n_bulk_g, hd), i8)])
    vbm_ref[0, :, 0] = vbm
    vbe_ref[0, :, 0] = vbe


def convert_prefill_cache_kernel(k: jax.Array, v: jax.Array,
                                 k_offsets: jax.Array, *, s_bulk: int,
                                 interpret: bool = False):
    """Single-launch converter: dense prefill K/V -> every packed region.

    k, v: (B, S, Hkv, hd) fp32 (S % 32 == 0, S <= s_bulk + 32);
    k_offsets: (B, Hkv, hd) online-smoothing offsets (subtracted from K
    before quantization).  Returns a dict of the 12 packed region arrays
    keyed by ``AsymKVCache`` field names — bit-identical to the XLA
    ``kvcache.prefill_cache`` construction.

    One ``pallas_call`` over (B·Hkv,): each grid step streams one head's
    dense (S, hd) K/V tiles into VMEM, reduces the shared exponents once,
    derives the 8-bit (init/ring) and 4-bit (bulk) mantissas from the
    same reduction, nibble-packs in VMEM and writes only packed bytes.
    """
    B, S, Hkv, hd = k.shape
    if S % GROUP or hd % GROUP:
        raise ValueError("S and head_dim must be multiples of 32")
    if S > s_bulk + INIT_TOKENS:
        raise ValueError(f"prefill length {S} exceeds capacity")
    kernel = functools.partial(_prefill_cache_kernel, S=S, s_bulk=s_bulk)
    ng = hd // GROUP

    def tok_spec(T, d):
        return pl.BlockSpec((1, T, 1, d), lambda b: (b // Hkv, 0, b % Hkv, 0))

    outs = pl.pallas_call(
        kernel,
        grid=(B * Hkv,),
        in_specs=[
            tok_spec(S, hd), tok_spec(S, hd),
            pl.BlockSpec((1, 1, hd), lambda b: (b // Hkv, b % Hkv, 0)),
        ],
        out_specs=[
            tok_spec(INIT_TOKENS, hd), tok_spec(INIT_TOKENS, ng),
            tok_spec(LOCAL_TOKENS, hd), tok_spec(LOCAL_TOKENS, ng),
            tok_spec(s_bulk, hd // 2), tok_spec(s_bulk, ng),
            tok_spec(GROUP, hd), tok_spec(1, hd),
            tok_spec(V_LOCAL_GROUPS * GROUP, hd),
            tok_spec(V_LOCAL_GROUPS, hd),
            tok_spec(s_bulk // 2, hd), tok_spec(s_bulk // GROUP, hd),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, INIT_TOKENS, Hkv, hd), jnp.int8),
            jax.ShapeDtypeStruct((B, INIT_TOKENS, Hkv, ng), jnp.int8),
            jax.ShapeDtypeStruct((B, LOCAL_TOKENS, Hkv, hd), jnp.int8),
            jax.ShapeDtypeStruct((B, LOCAL_TOKENS, Hkv, ng), jnp.int8),
            jax.ShapeDtypeStruct((B, s_bulk, Hkv, hd // 2), jnp.int8),
            jax.ShapeDtypeStruct((B, s_bulk, Hkv, ng), jnp.int8),
            jax.ShapeDtypeStruct((B, GROUP, Hkv, hd), jnp.int8),
            jax.ShapeDtypeStruct((B, 1, Hkv, hd), jnp.int8),
            jax.ShapeDtypeStruct((B, V_LOCAL_GROUPS * GROUP, Hkv, hd),
                                 jnp.int8),
            jax.ShapeDtypeStruct((B, V_LOCAL_GROUPS, Hkv, hd), jnp.int8),
            jax.ShapeDtypeStruct((B, s_bulk // 2, Hkv, hd), jnp.int8),
            jax.ShapeDtypeStruct((B, s_bulk // GROUP, Hkv, hd), jnp.int8),
        ],
        interpret=interpret,
    )(k, v, k_offsets)
    names = ["k_init_mant", "k_init_exp", "k_local_mant", "k_local_exp",
             "k_bulk_mant", "k_bulk_exp", "v_init_mant", "v_init_exp",
             "v_local_mant", "v_local_exp", "v_bulk_mant", "v_bulk_exp"]
    return dict(zip(names, outs))


__all__ = ["bfp_quantize_kernel", "bfp_quantize_kv_batched_kernel",
           "bfp_quantize_v_batched_kernel", "bfp_quantize_kv_pair_kernel",
           "convert_prefill_cache_kernel"]
