"""BFP-BFP attention kernels — the paper's M8M8 / M8M4 PE modes on TPU.

Prefill: flash-attention (online softmax) over BFP-compressed K/V tiles,
dequantized in VMEM right before the MXU dots.  K is per-token grouped
along head_dim; V is token-grouped (the P.V contraction direction,
paper Fig. 6a) so its shared exponents index (S/32, hd).

Decode: one-step attention of a kv-head's query group against the 4-bit
*bulk* region of the asymmetric cache (the big, bandwidth-critical read:
4.25 bits/value instead of 16).  Returns the unnormalized flash triple
(o, m, l) so the XLA epilogue merges it with the small 8-bit init/local/
residual regions.

Two generations of each kernel live here:

* ``*_kernel`` — the original single-head kernels.  Batch and kv-head are
  supplied by ``jax.vmap`` towers in ops.py (the ``legacy=True`` path),
  which costs four ``moveaxis`` layout copies per call and prevents any
  cross-head scheduling.
* ``*_batched`` — grid-fused kernels: the (batch × kv-head) product is a
  leading grid dimension and the GQA query group ``rep`` is folded into
  the q tile, so one ``pallas_call`` covers the whole batched GQA op with
  zero layout copies (all slicing happens in BlockSpec index maps).
  Prefill additionally skips fully-masked causal/window tiles with a
  ``pl.when`` guard (see ``prefill_tile_counts``); decode skips tiles
  fully outside [start, valid_len).

Grid-order note: Pallas executes the grid sequentially on a TPU core,
last dimension fastest.  Both batched kernels keep the key-tile dimension
innermost, so for a fixed (batch·kv-head, q-tile) the flash accumulator
scratch is swept over key tiles exactly like the legacy kernels — and a
``pl.when``-guarded body is a real branch in the Mosaic lowering (and a
``lax.cond`` in interpret mode), so skipped tiles genuinely skip the QK
dot, the softmax update and the PV dot rather than masking them after
the fact.

P is kept fp32 inside the kernels: on TPU the MXU consumes fp natively, so
the ASIC's P->BFP conversion (which exists to feed integer PEs) would only
lose accuracy without a perf win — recorded in DESIGN.md §2.  The P-BFP
numerics are exercised by the fake-quant eval path instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bfp import pow2, shared_exponent

GROUP = 32
NEG_INF = -1e30

# Default tile sizes for the grid-fused kernels.  Larger than the legacy
# 128 defaults: with (batch x kv-head) amortizing the grid, a 512-tile
# keeps every operand block plus the fp32 accumulator comfortably inside
# TPU VMEM (~1.5 MiB at hd=128, rep=4) while cutting grid-step overhead
# 16x vs 128-tiles (DESIGN.md §3).
BLOCK_Q_BATCHED = 512
BLOCK_S_BATCHED = 512
BLOCK_S_DECODE = 512


def _dq_k_tile(k_mant, k_exp, mantissa_bits):
    """(bs, hd) int8 + (bs, hd/32) -> (bs, hd) f32 (per-token groups)."""
    bs, hd = k_mant.shape
    step = pow2(k_exp.astype(jnp.float32) - (mantissa_bits - 2))
    return (k_mant.astype(jnp.float32).reshape(bs, hd // GROUP, GROUP)
            * step[..., None]).reshape(bs, hd)


def _dq_v_tile(v_mant, v_exp, mantissa_bits):
    """(bs, hd) int8 + (bs/32, hd) -> (bs, hd) f32 (token groups)."""
    bs, hd = v_mant.shape
    step = pow2(v_exp.astype(jnp.float32) - (mantissa_bits - 2))
    return (v_mant.astype(jnp.float32).reshape(bs // GROUP, GROUP, hd)
            * step[:, None, :]).reshape(bs, hd)


def _dq_k4_tile(km, ke, hd):
    """(bs, hd/2) int8 nibble pairs + (bs, hd/32) exps -> (bs, hd) f32."""
    kmu = km.astype(jnp.uint8)
    lo = (kmu & 0xF).astype(jnp.int32)
    hi = ((kmu >> 4) & 0xF).astype(jnp.int32)
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    k_int = jnp.stack([lo, hi], axis=-1).reshape(km.shape[0], hd)
    kstep = pow2(ke.astype(jnp.float32) - 2.0)  # m=4
    return (k_int.astype(jnp.float32).reshape(-1, hd // GROUP, GROUP)
            * kstep[..., None]).reshape(-1, hd)


def _dq_v4_tile(vm, ve, hd):
    """(bs/2, hd) token-packed nibbles + (bs/32, hd) exps -> (bs, hd) f32."""
    vmu = vm.astype(jnp.uint8)
    vlo = (vmu & 0xF).astype(jnp.int32)
    vhi = ((vmu >> 4) & 0xF).astype(jnp.int32)
    vlo = jnp.where(vlo >= 8, vlo - 16, vlo)
    vhi = jnp.where(vhi >= 8, vhi - 16, vhi)
    v_int = jnp.stack([vlo, vhi], axis=1).reshape(-1, hd)
    vstep = pow2(ve.astype(jnp.float32) - 2.0)  # (bs/32, hd)
    return (v_int.astype(jnp.float32).reshape(-1, GROUP, hd)
            * vstep[:, None, :]).reshape(-1, hd)


def _aligned_block(S: int, block: int) -> int:
    """Largest GROUP-aligned divisor of S that is <= block.

    Keeps the grid tiled (so causal/dead tile skipping stays active)
    for any S that is a multiple of GROUP — e.g. the decode bulk
    region's S = max_seq - 32 is rarely a multiple of the 512 default,
    but always of 32.  Truly ragged S (not a multiple of GROUP) degrades
    to a single tile — padding packed K/V would break the S/32 exponent
    layouts."""
    b = min(block, S)
    b -= b % GROUP
    while b >= GROUP:
        if S % b == 0:
            return b
        b -= GROUP
    return S


def _resolve_blocks(S, block_q, block_s):
    bq = min(block_q, S)
    if S % bq:
        bq = _aligned_block(S, block_q)
    bs = min(block_s, S)
    if S % bs or bs % GROUP:
        bs = _aligned_block(S, block_s)
    return bq, bs


# ---------------------------------------------------------------------------
# Prefill (flash)
# ---------------------------------------------------------------------------

def _prefill_kernel(q_ref, km_ref, ke_ref, vm_ref, ve_ref, o_ref,
                    acc_ref, m_ref, l_ref, *, mantissa_bits, causal,
                    logit_cap, window, block_q, block_s, n_s):
    iq, ik = pl.program_id(0), pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32)                     # (bq, hd)
    hd = q.shape[-1]
    k = _dq_k_tile(km_ref[...], ke_ref[...], mantissa_bits)
    v = _dq_v_tile(vm_ref[...], ve_ref[...], mantissa_bits)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) \
        / jnp.sqrt(float(hd))                              # (bq, bs)
    if logit_cap > 0:
        s = logit_cap * jnp.tanh(s / logit_cap)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    s.shape, 0)
    k_pos = ik * block_s + jax.lax.broadcasted_iota(jnp.int32,
                                                    s.shape, 1)
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        d = q_pos - k_pos
        mask = d >= 0
        if window > 0:
            mask &= d < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                    # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_s - 1)
    def _fin():
        l = l_ref[...]
        o_ref[...] = jnp.where(l > 0, acc_ref[...] / jnp.maximum(l, 1e-30),
                               0.0).astype(o_ref.dtype)


def bfp_attention_prefill_kernel(q, k_mant, k_exp, v_mant, v_exp, *,
                                 mantissa_bits: int = 8,
                                 causal: bool = True,
                                 logit_cap: float = 0.0, window: int = 0,
                                 block_q: int = 128, block_s: int = 128,
                                 out_dtype=jnp.float32,
                                 interpret: bool = False):
    """Single-head: q (S, hd) fp; K (S, hd)+(S, hd/32); V (S, hd)+(S/32, hd).

    Legacy entry point: vmapped over (batch, head) in ops.py.  New callers
    should use ``bfp_attention_prefill_batched``.
    """
    from jax.experimental.pallas import tpu as pltpu
    S, hd = q.shape
    bq, bs = _resolve_blocks(S, block_q, block_s)
    n_s = S // bs
    kernel = functools.partial(
        _prefill_kernel, mantissa_bits=mantissa_bits, causal=causal,
        logit_cap=logit_cap, window=window, block_q=bq, block_s=bs, n_s=n_s)
    return pl.pallas_call(
        kernel,
        grid=(S // bq, n_s),
        in_specs=[
            pl.BlockSpec((bq, hd), lambda i, j: (i, 0)),
            pl.BlockSpec((bs, hd), lambda i, j: (j, 0)),
            pl.BlockSpec((bs, hd // GROUP), lambda i, j: (j, 0)),
            pl.BlockSpec((bs, hd), lambda i, j: (j, 0)),
            pl.BlockSpec((bs // GROUP, hd), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, hd), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, hd), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k_mant, k_exp, v_mant, v_exp)


# ---------------------------------------------------------------------------
# Prefill (grid-fused batched)
# ---------------------------------------------------------------------------

def _tile_live(iq, ik, *, block_q, block_s, causal, window):
    """Whether causal/window masking leaves anything alive in tile
    (iq, ik).  Shared between the kernel's ``pl.when`` guard and the
    ``prefill_tile_counts`` probe so benchmarks count exactly what the
    kernel skips.  Works on both Python ints and traced scalars."""
    if not causal:
        return True
    first_q, last_q = iq * block_q, iq * block_q + block_q - 1
    first_k, last_k = ik * block_s, ik * block_s + block_s - 1
    live = first_k <= last_q                       # below/on the diagonal
    if window > 0:
        live = live & (first_q - last_k < window)  # not fully out-of-window
    return live


def prefill_tile_counts(S: int, block_q: int = BLOCK_Q_BATCHED,
                        block_s: int = BLOCK_S_BATCHED,
                        causal: bool = True, window: int = 0):
    """(live, total) per-head tile counts for the batched prefill grid.

    ``live/total`` is the fraction of (QK dot + softmax + PV dot) tile
    bodies the fused kernel actually executes; the rest are skipped by the
    ``pl.when`` guard."""
    bq, bs = _resolve_blocks(S, block_q, block_s)
    n_q, n_s = S // bq, S // bs
    live = sum(bool(_tile_live(iq, ik, block_q=bq, block_s=bs,
                               causal=causal, window=window))
               for iq in range(n_q) for ik in range(n_s))
    return live, n_q * n_s


def _prefill_batched_kernel(q_ref, km_ref, ke_ref, vm_ref, ve_ref, o_ref,
                            acc_ref, m_ref, l_ref, *, mantissa_bits,
                            causal, logit_cap, window, block_q, block_s,
                            n_s, rep):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body():
        q = q_ref[0, :, 0].reshape(block_q * rep, -1).astype(jnp.float32)
        hd = q.shape[-1]
        k = _dq_k_tile(km_ref[0, :, 0], ke_ref[0, :, 0], mantissa_bits)
        v = _dq_v_tile(vm_ref[0, :, 0], ve_ref[0, :, 0], mantissa_bits)

        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) \
            / jnp.sqrt(float(hd))                  # (bq*rep, bs)
        if logit_cap > 0:
            s = logit_cap * jnp.tanh(s / logit_cap)

        # row r of the folded q tile is query position iq*bq + r//rep
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) // rep
        k_pos = ik * block_s + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = jnp.ones(s.shape, jnp.bool_)
        if causal:
            d = q_pos - k_pos
            mask = d >= 0
            if window > 0:
                mask &= d < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                        # (bq*rep, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        pl.when(_tile_live(iq, ik, block_q=block_q, block_s=block_s,
                           causal=True, window=window))(_body)
    else:
        _body()

    @pl.when(ik == n_s - 1)
    def _fin():
        l = l_ref[...]
        out = jnp.where(l > 0, acc_ref[...] / jnp.maximum(l, 1e-30), 0.0)
        o_ref[0, :, 0] = out.reshape(block_q, rep, -1).astype(o_ref.dtype)


def bfp_attention_prefill_batched(q, k_mant, k_exp, v_mant, v_exp, *,
                                  mantissa_bits: int = 8,
                                  causal: bool = True,
                                  logit_cap: float = 0.0, window: int = 0,
                                  block_q: int = BLOCK_Q_BATCHED,
                                  block_s: int = BLOCK_S_BATCHED,
                                  out_dtype=jnp.float32,
                                  interpret: bool = False):
    """Grid-fused batched GQA prefill on packed K/V.

    q: (B, S, H, hd) fp; K (B, S, Hkv, hd) + (B, S, Hkv, hd/32);
    V token-grouped (B, S, Hkv, hd) + (B, S/32, Hkv, hd).
    Returns (B, S, H, hd).

    Grid is (B·Hkv, S/bq, S/bs) with the query group rep = H/Hkv folded
    into the q tile; all (batch, head) slicing happens in BlockSpec index
    maps so no operand is ever transposed or copied.  Fully-masked causal
    tiles are skipped (see ``prefill_tile_counts``).
    """
    from jax.experimental.pallas import tpu as pltpu
    B, S, H, hd = q.shape
    Hkv = k_mant.shape[2]
    rep = H // Hkv
    if H % Hkv:
        raise ValueError(f"H={H} must be a multiple of Hkv={Hkv}")
    bq, bs = _resolve_blocks(S, block_q, block_s)
    n_q, n_s = S // bq, S // bs
    q5 = q.reshape(B, S, Hkv, rep, hd)
    kernel = functools.partial(
        _prefill_batched_kernel, mantissa_bits=mantissa_bits, causal=causal,
        logit_cap=logit_cap, window=window, block_q=bq, block_s=bs,
        n_s=n_s, rep=rep)
    out = pl.pallas_call(
        kernel,
        grid=(B * Hkv, n_q, n_s),
        in_specs=[
            pl.BlockSpec((1, bq, 1, rep, hd),
                         lambda b, i, j: (b // Hkv, i, b % Hkv, 0, 0)),
            pl.BlockSpec((1, bs, 1, hd),
                         lambda b, i, j: (b // Hkv, j, b % Hkv, 0)),
            pl.BlockSpec((1, bs, 1, hd // GROUP),
                         lambda b, i, j: (b // Hkv, j, b % Hkv, 0)),
            pl.BlockSpec((1, bs, 1, hd),
                         lambda b, i, j: (b // Hkv, j, b % Hkv, 0)),
            pl.BlockSpec((1, bs // GROUP, 1, hd),
                         lambda b, i, j: (b // Hkv, j, b % Hkv, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, rep, hd),
                               lambda b, i, j: (b // Hkv, i, b % Hkv, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, Hkv, rep, hd), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bq * rep, hd), jnp.float32),
            pltpu.VMEM((bq * rep, 1), jnp.float32),
            pltpu.VMEM((bq * rep, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q5, k_mant, k_exp, v_mant, v_exp)
    return out.reshape(B, S, H, hd)


# ---------------------------------------------------------------------------
# Decode (bulk region, 4-bit)
# ---------------------------------------------------------------------------

def _decode_kernel(len_ref, q_ref, km_ref, ke_ref, vm_ref, ve_ref,
                   o_ref, m_out_ref, l_out_ref, acc_ref, m_ref, l_ref, *,
                   block_s, n_s):
    ik = pl.program_id(0)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32)                     # (rep, hd)
    hd = q.shape[-1]
    k = _dq_k4_tile(km_ref[...], ke_ref[...], hd)          # (bs, hd)
    v = _dq_v4_tile(vm_ref[...], ve_ref[...], hd)          # (bs, hd)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) \
        / jnp.sqrt(float(hd))                              # (rep, bs)
    pos = ik * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = pos < len_ref[0]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_s - 1)
    def _fin():
        o_ref[...] = acc_ref[...]
        m_out_ref[...] = m_ref[...]
        l_out_ref[...] = l_ref[...]


def bfp_attention_decode_kernel(q, k_mant4, k_exp, v_mant4, v_exp,
                                valid_len, *, block_s: int = 512,
                                interpret: bool = False):
    """One kv-head decode over the 4-bit bulk region (legacy entry).

    q: (rep, hd) — the query-head group of this kv head;
    k_mant4: (S, hd/2) int8 nibbles (packed along hd);
    k_exp: (S, hd/32); v_mant4: (S/2, hd) nibbles (packed along tokens);
    v_exp: (S/32, hd); valid_len: () int32.

    Returns the flash triple (o (rep, hd) unnormalized, m (rep, 1),
    l (rep, 1)) for merging with the 8-bit regions.
    """
    from jax.experimental.pallas import tpu as pltpu
    S = k_mant4.shape[0]
    rep, hd = q.shape
    bs = min(block_s, S)
    if S % bs:
        bs = S
    n_s = S // bs
    kernel = functools.partial(_decode_kernel, block_s=bs, n_s=n_s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_s,),
        in_specs=[
            pl.BlockSpec((rep, hd), lambda j, *_: (0, 0)),
            pl.BlockSpec((bs, hd // 2), lambda j, *_: (j, 0)),
            pl.BlockSpec((bs, hd // GROUP), lambda j, *_: (j, 0)),
            pl.BlockSpec((bs // 2, hd), lambda j, *_: (j, 0)),
            pl.BlockSpec((bs // GROUP, hd), lambda j, *_: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rep, hd), lambda j, *_: (0, 0)),
            pl.BlockSpec((rep, 1), lambda j, *_: (0, 0)),
            pl.BlockSpec((rep, 1), lambda j, *_: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((rep, hd), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rep, hd), jnp.float32),
            jax.ShapeDtypeStruct((rep, 1), jnp.float32),
            jax.ShapeDtypeStruct((rep, 1), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray(valid_len, jnp.int32).reshape(1), q, k_mant4, k_exp,
      v_mant4, v_exp)


# ---------------------------------------------------------------------------
# Decode (grid-fused batched)
# ---------------------------------------------------------------------------

def _decode_batched_kernel(len_ref, q_ref, km_ref, ke_ref, vm_ref, ve_ref,
                           o_ref, m_out_ref, l_out_ref,
                           acc_ref, m_ref, l_ref, *, block_s, n_s, n_kv,
                           logit_cap):
    bh, ik = pl.program_id(0), pl.program_id(1)
    b = bh // n_kv
    valid_len = len_ref[0]
    start = len_ref[1 + b]        # first valid slot of this batch row

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # tile is dead when it lies entirely beyond valid_len or entirely
    # before this row's left-pad start
    live = (ik * block_s < valid_len) & (ik * block_s + block_s > start)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                # (rep, hd)
        hd = q.shape[-1]
        k = _dq_k4_tile(km_ref[0, :, 0], ke_ref[0, :, 0], hd)
        v = _dq_v4_tile(vm_ref[0, :, 0], ve_ref[0, :, 0], hd)

        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) \
            / jnp.sqrt(float(hd))                          # (rep, bs)
        if logit_cap > 0:
            s = logit_cap * jnp.tanh(s / logit_cap)
        pos = ik * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = (pos < valid_len) & (pos >= start)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == n_s - 1)
    def _fin():
        o_ref[0, 0] = acc_ref[...]
        m_out_ref[0, 0] = m_ref[...]
        l_out_ref[0, 0] = l_ref[...]


def bfp_attention_decode_batched(q, k_mant4, k_exp, v_mant4, v_exp,
                                 valid_len, *, start=None,
                                 logit_cap: float = 0.0,
                                 block_s: int = BLOCK_S_DECODE,
                                 interpret: bool = False):
    """Grid-fused batched GQA decode over the 4-bit bulk region.

    q: (B, H, hd); k_mant4: (B, S, Hkv, hd/2); k_exp: (B, S, Hkv, hd/32);
    v_mant4: (B, S/2, Hkv, hd); v_exp: (B, S/32, Hkv, hd);
    valid_len: () int32 shared upper bound; start: optional (B,) int32
    first-valid slot per row (left-pad masking — the serving engine's
    ``pad_prefix`` shifted into bulk-slot space).

    Grid is (B·Hkv, S/bs); key tiles fully outside [start, valid_len) are
    skipped.  Returns the flash triple (o (B, H, hd) unnormalized,
    m (B, H, 1), l (B, H, 1)).
    """
    from jax.experimental.pallas import tpu as pltpu
    B, H, hd = q.shape
    S, Hkv = k_mant4.shape[1], k_mant4.shape[2]
    rep = H // Hkv
    if H % Hkv:
        raise ValueError(f"H={H} must be a multiple of Hkv={Hkv}")
    bs = min(block_s, S)
    if S % bs or bs % GROUP:
        bs = _aligned_block(S, block_s)
    n_s = S // bs
    q4 = q.reshape(B, Hkv, rep, hd)
    if start is None:
        start = jnp.zeros((B,), jnp.int32)
    prefetch = jnp.concatenate(
        [jnp.asarray(valid_len, jnp.int32).reshape(1),
         jnp.asarray(start, jnp.int32).reshape(B)])
    kernel = functools.partial(_decode_batched_kernel, block_s=bs, n_s=n_s,
                               n_kv=Hkv, logit_cap=logit_cap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * Hkv, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd),
                         lambda b, j, *_: (b // Hkv, b % Hkv, 0, 0)),
            pl.BlockSpec((1, bs, 1, hd // 2),
                         lambda b, j, *_: (b // Hkv, j, b % Hkv, 0)),
            pl.BlockSpec((1, bs, 1, hd // GROUP),
                         lambda b, j, *_: (b // Hkv, j, b % Hkv, 0)),
            pl.BlockSpec((1, bs // 2, 1, hd),
                         lambda b, j, *_: (b // Hkv, j, b % Hkv, 0)),
            pl.BlockSpec((1, bs // GROUP, 1, hd),
                         lambda b, j, *_: (b // Hkv, j, b % Hkv, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, rep, hd),
                         lambda b, j, *_: (b // Hkv, b % Hkv, 0, 0)),
            pl.BlockSpec((1, 1, rep, 1),
                         lambda b, j, *_: (b // Hkv, b % Hkv, 0, 0)),
            pl.BlockSpec((1, 1, rep, 1),
                         lambda b, j, *_: (b // Hkv, b % Hkv, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((rep, hd), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
        ],
    )
    o, m, l = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, rep, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, rep, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, rep, 1), jnp.float32),
        ],
        interpret=interpret,
    )(prefetch, q4, k_mant4, k_exp, v_mant4, v_exp)
    return (o.reshape(B, H, hd), m.reshape(B, H, 1), l.reshape(B, H, 1))


# ---------------------------------------------------------------------------
# Decode (single-launch: bulk + init + local window in one grid)
# ---------------------------------------------------------------------------

# canonical cache-layout / shared-exponent parameters — the decode
# kernel must index exactly the regions the cache writes
from repro.core.kvcache import (INIT_TOKENS, LOCAL_TOKENS,  # noqa: E402
                                V_LOCAL_GROUPS as V_LOCAL_GROUPS_K)


def _dq_k8_batched(mant, exp):
    """(B, T, H, hd) int8 + (B, T, H, hd/32) -> f32 — op-for-op the same
    math as ``kvcache._dq_k(..., 8)`` (elementwise, so bitwise equal)."""
    shp = mant.shape
    g = mant.astype(jnp.float32).reshape(shp[:-1] + (shp[-1] // GROUP,
                                                     GROUP))
    step = pow2(exp.astype(jnp.float32) - 6.0)[..., None]
    return (g * step).reshape(shp)


def _dq_k4_batched(packed, exp, hd):
    """(B, T, H, hd/2) int8 nibble pairs + (B, T, H, hd/32) -> f32,
    mirroring ``bfp.unpack_int4`` + ``kvcache._dq_k(..., 4)``."""
    u = packed.astype(jnp.uint8)
    lo = (u & 0xF).astype(jnp.int32)
    hi = ((u >> 4) & 0xF).astype(jnp.int32)
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    m = jnp.stack([lo, hi], axis=-1).reshape(packed.shape[:-1] + (hd,))
    g = m.astype(jnp.float32).reshape(packed.shape[:-1] + (hd // GROUP,
                                                           GROUP))
    step = pow2(exp.astype(jnp.float32) - 2.0)[..., None]
    return (g * step).reshape(packed.shape[:-1] + (hd,))


def _decode_asym_kernel(pf, qb_ref, q_ref, kbm_ref, kbe_ref, vbm_ref,
                        vbe_ref, kwm_ref, kwe_ref, kim_ref, kie_ref,
                        klm_ref, kle_ref, vim_ref, vie_ref, vlm_ref,
                        vle_ref, vr_ref, o_ref, acc_ref, m_ref, l_ref, *,
                        block_s, n_s, n_kv, n_b, rep, logit_cap):
    t = pl.program_id(0)
    b = t // n_s                   # batch row during the bulk sweep
    j = t % n_s
    valid_len = pf[1]              # bulk-relative valid slots

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # ---- bulk tiles: one grid step covers ALL kv heads of a batch row
    # (Hkv× fewer steps than the per-(b,h) legacy grid).  The dequant
    # is vectorized over heads; the QK and PV contractions are per-head
    # dots ----
    start_abs = pf[3 + jnp.minimum(b, n_b - 1)]
    start = jnp.maximum(start_abs - INIT_TOKENS, 0)
    live = (t < n_b * n_s) & (j * block_s < valid_len) \
        & (j * block_s + block_s > start)

    @pl.when(live)
    def _bulk():
        q3 = qb_ref[0].astype(jnp.float32)             # (Hkv, rep, hd)
        hd = q3.shape[-1]
        km = kbm_ref[0].astype(jnp.uint8)              # (bs, Hkv, hd/2)
        lo = (km & 0xF).astype(jnp.int32)
        hi = ((km >> 4) & 0xF).astype(jnp.int32)
        lo = jnp.where(lo >= 8, lo - 16, lo)
        hi = jnp.where(hi >= 8, hi - 16, hi)
        k_int = jnp.stack([lo, hi], axis=-1).reshape(block_s, n_kv, hd)
        kstep = pow2(kbe_ref[0].astype(jnp.float32) - 2.0)
        k = (k_int.astype(jnp.float32)
             .reshape(block_s, n_kv, hd // GROUP, GROUP)
             * kstep[..., None]).reshape(block_s, n_kv, hd)
        vm = vbm_ref[0].astype(jnp.uint8)              # (bs/2, Hkv, hd)
        vlo = (vm & 0xF).astype(jnp.int32)
        vhi = ((vm >> 4) & 0xF).astype(jnp.int32)
        vlo = jnp.where(vlo >= 8, vlo - 16, vlo)
        vhi = jnp.where(vhi >= 8, vhi - 16, vhi)
        v_int = jnp.stack([vlo, vhi], axis=1).reshape(block_s, n_kv, hd)
        vstep = pow2(vbe_ref[0].astype(jnp.float32) - 2.0)
        v = (v_int.astype(jnp.float32)
             .reshape(block_s // GROUP, GROUP, n_kv, hd)
             * vstep[:, None]).reshape(block_s, n_kv, hd)

        # per-head flash updates on (rep, bs) tiles, each head's triple
        # in its own scratch slab
        for h in range(n_kv):
            s = jnp.dot(q3[h], k[:, h].T,
                        preferred_element_type=jnp.float32) \
                / jnp.sqrt(float(hd))                  # (rep, bs)
            if logit_cap > 0:
                s = logit_cap * jnp.tanh(s / logit_cap)
            pos = j * block_s + jax.lax.broadcasted_iota(jnp.int32,
                                                         s.shape, 1)
            valid = (pos < valid_len) & (pos >= start)
            s = jnp.where(valid, s, NEG_INF)

            slab = pl.ds(b * n_kv * rep + h * rep, rep)
            m_prev = m_ref[slab]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[slab] = l_ref[slab] * corr \
                + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[slab] = acc_ref[slab] * corr + jnp.dot(
                p, v[:, h], preferred_element_type=jnp.float32)
            m_ref[slab] = m_new

    # ---- final grid step: the 8-bit init block + recent window for
    # *all* (batch, head) at once — one vectorized tile body instead of
    # the per-step XLA epilogue, in its batched einsum formulation ----
    @pl.when(t == n_b * n_s)
    def _epilogue():
        L = pf[0]
        B = n_b
        band = pf[2]                   # bulk 32-slot block index (cg-3)
        q5 = q_ref[...].astype(jnp.float32)            # (B, Hkv, rep, hd)
        hd = q5.shape[-1]
        cg = L // GROUP
        r = L % GROUP
        R0 = GROUP * jnp.maximum(cg - 2, 1)
        W = LOCAL_TOKENS + GROUP                       # 96-slot window

        # K: init block + window (local ring in position order via a
        # 2-phase select; the <=32 freshly-demoted tokens from the 4-bit
        # band block fetched at bulk slot cg-3)
        k_init = _dq_k8_batched(kim_ref[...], kie_ref[...])
        k_loc = _dq_k8_batched(klm_ref[...], kle_ref[...])
        kl2 = jnp.concatenate([k_loc, k_loc], axis=1)  # (B, 128, Hkv, hd)
        phase = (R0 - INIT_TOKENS) % LOCAL_TOKENS      # 0 or 32
        k_from_local = jnp.where(phase == 0, kl2[:, :W],
                                 kl2[:, GROUP:GROUP + W])
        k_band = _dq_k4_batched(kwm_ref[:, pl.ds(band * GROUP, GROUP)],
                                kwe_ref[:, pl.ds(band * GROUP, GROUP)], hd)
        k_from_bulk = jnp.concatenate([k_band, k_from_local[:, GROUP:]],
                                      axis=1)
        t_win = R0 + jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)[:, 0]
        use_local = t_win >= jnp.maximum(INIT_TOKENS, L - LOCAL_TOKENS)
        k_win = jnp.where(use_local[None, :, None, None], k_from_local,
                          k_from_bulk)
        k_ep = jnp.concatenate([k_init, k_win], axis=1)    # (B,128,Hkv,hd)

        # V: init group + groups {a0, a0+1, a0+2} from the 8-bit group
        # ring / the residual group re-converted at its current size
        vie = pow2(vie_ref[...].astype(jnp.float32) - 6.0)
        v_init = vim_ref[...].astype(jnp.float32).reshape(
            B, 1, GROUP, n_kv, hd) * vie[:, :, None]
        v_init = v_init.reshape(B, GROUP, n_kv, hd)
        vle = pow2(vle_ref[...].astype(jnp.float32) - 6.0)
        v_loc = vlm_ref[...].astype(jnp.float32)
        ring0 = v_loc[:, :GROUP] * vle[:, 0:1]
        ring1 = v_loc[:, GROUP:] * vle[:, 1:2]
        resid_raw = vr_ref[...].astype(jnp.float32)    # (B, 32, Hkv, hd)
        tok32 = jax.lax.broadcasted_iota(jnp.int32, (GROUP, 1), 0)[:, 0]
        resid = jnp.where((tok32 < r)[None, :, None, None], resid_raw, 0.0)
        e = shared_exponent(jnp.max(jnp.abs(resid), axis=1))  # (B,Hkv,hd)
        step = pow2(e - 6.0)[:, None]
        resid_q = jnp.clip(jnp.trunc(resid / step), -127.0, 127.0) * step
        a0 = jnp.maximum(cg - 2, 1)
        parts = []
        for off in range(W // GROUP):
            gg = a0 + off
            from_ring = jnp.where(gg % V_LOCAL_GROUPS_K == 0, ring0, ring1)
            parts.append(jnp.where(gg == cg, resid_q, from_ring))
        v_win = jnp.concatenate(parts, axis=1)         # (B, 96, Hkv, hd)
        v_ep = jnp.concatenate([v_init, v_win], axis=1)

        pos_ep = jnp.concatenate([tok32, t_win])       # (128,)
        starts = jnp.stack([pf[3 + i] for i in range(B)])
        valid_ep = (pos_ep[None, :] < L) \
            & (pos_ep[None, :] >= starts[:, None])     # (B, 128)

        # scores/softmax/PV as batched einsums over (b, g)
        qg = q5.reshape(B, 1, n_kv, rep, hd)
        s_e = jnp.einsum("bsgrd,btgd->bgrst", qg, k_ep,
                         preferred_element_type=jnp.float32) \
            * (1.0 / jnp.sqrt(float(hd)))              # (B,Hkv,rep,1,128)
        if logit_cap > 0:
            s_e = logit_cap * jnp.tanh(s_e / logit_cap)
        s_e = jnp.where(valid_ep[:, None, None, None], s_e, NEG_INF)
        m_e = jnp.max(s_e, axis=-1)                    # (B,Hkv,rep,1)
        p_e = jnp.where(valid_ep[:, None, None, None],
                        jnp.exp(s_e - m_e[..., None]), 0.0)
        l_e = jnp.sum(p_e, axis=-1)
        o_e = jnp.einsum("bgrst,btgd->bgrsd", p_e, v_ep,
                         preferred_element_type=jnp.float32)[:, :, :, 0]

        # two-way merge — same expression as the legacy XLA epilogue
        m_e, l_e = m_e[..., 0], l_e[..., 0]            # (B,Hkv,rep)
        o_b = acc_ref[...].reshape(B, n_kv, rep, hd)
        m_b = m_ref[...].reshape(B, n_kv, rep)
        l_b = l_ref[...].reshape(B, n_kv, rep)
        m = jnp.maximum(m_e, m_b)
        a_e = jnp.exp(m_e - m)
        a_b = jnp.exp(m_b - m)
        l = l_e * a_e + l_b * a_b
        o = o_e * a_e[..., None] + o_b * a_b[..., None]
        o_ref[...] = jnp.where(l[..., None] > 0,
                               o / jnp.maximum(l[..., None], 1e-30), 0.0)


def bfp_attention_decode_asym_batched(q, k_bulk_mant, k_bulk_exp,
                                      v_bulk_mant, v_bulk_exp,
                                      k_init_mant, k_init_exp,
                                      k_local_mant, k_local_exp,
                                      v_init_mant, v_init_exp,
                                      v_local_mant, v_local_exp, v_resid,
                                      length, *, start=None,
                                      logit_cap: float = 0.0,
                                      block_s: int = BLOCK_S_DECODE,
                                      interpret: bool = False):
    """Single-launch batched GQA decode over the *whole* asymmetric cache.

    One ``pallas_call`` over a flattened grid of B·(S_bulk/bs) + 1
    steps: the bulk sweep walks the 4-bit nibble-packed region with one
    step per batch row covering all kv heads (Hkv× fewer grid steps
    than the per-(b,h) legacy grid; dequant vectorized over heads,
    QK/PV contractions as per-head dots, each head's flash triple in its
    own scratch slab, same dead-tile skip rule),
    and the *single* final step dequantizes the three small 8-bit
    regions for every (batch, head) at once (init block, local K ring
    rolled into position order via a 2-phase select, the ≤32 freshly
    demoted K tokens from a scalar-prefetch-indexed bulk band block, the
    V group ring and the residual group re-converted at its current
    size) and merges the flash triples in-kernel — eliminating the two
    extra launches and the XLA dynamic-slice/select epilogue per layer
    per step.  ``v_bulk_exp`` is indexed directly (bulk-relative layout:
    slot j = group j+1) — no per-step exponent shift exists on this
    path.  Tests hold it to ``kernels/ref.py``'s dense decode under a
    float32 tolerance.

    q: (B, H, hd); cache regions in their ``AsymKVCache`` layouts;
    length: () int32 cache length; start: optional (B,) int32 left-pad
    prefix (absolute positions).  Returns normalized (B, H, hd) f32.
    """
    from jax.experimental.pallas import tpu as pltpu
    B, H, hd = q.shape
    s_bulk, Hkv = k_bulk_mant.shape[1], k_bulk_mant.shape[2]
    rep = H // Hkv
    if H % Hkv:
        raise ValueError(f"H={H} must be a multiple of Hkv={Hkv}")
    bs = min(block_s, s_bulk)
    if s_bulk % bs or bs % GROUP:
        bs = _aligned_block(s_bulk, block_s)
    n_s = s_bulk // bs
    n_bh = B * Hkv
    q4 = q.reshape(B, Hkv, rep, hd)
    L = jnp.asarray(length, jnp.int32).reshape(())
    cg = L // GROUP
    vl_bulk = jnp.maximum(GROUP * (cg - 2) - INIT_TOKENS, 0)
    band = jnp.clip(cg - 3, 0, s_bulk // GROUP - 1)
    if start is None:
        start = jnp.zeros((B,), jnp.int32)
    prefetch = jnp.concatenate(
        [L.reshape(1), vl_bulk.reshape(1), band.reshape(1),
         jnp.asarray(start, jnp.int32).reshape(B)])
    ng = hd // GROUP
    kernel = functools.partial(_decode_asym_kernel, block_s=bs, n_s=n_s,
                               n_kv=Hkv, n_b=B, rep=rep,
                               logit_cap=logit_cap)

    def fixed(T, d):
        # whole-array refs, read once in the final (epilogue) step: a
        # blocked spec would re-fetch every region every grid step (the
        # interpreter re-slices per step; on TPU the revisit cache would
        # hide it, but ANY also lets Mosaic keep these small buffers
        # resident instead of streaming them through the block machinery)
        del T, d
        return pl.BlockSpec(memory_space=pltpu.ANY)

    def bulk(T, d):
        # (b, j) of the bulk sweep, all kv heads per block; the final
        # (epilogue) step re-fetches the last row's first block, which
        # it never reads
        return pl.BlockSpec(
            (1, T, Hkv, d),
            lambda t, *_: (jnp.minimum(t // n_s, B - 1), t % n_s, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * n_s + 1,),
        in_specs=[
            # q twice: a per-batch-row block for the bulk sweep, and the
            # whole ref for the one vectorized epilogue step
            pl.BlockSpec(
                (1, Hkv, rep, hd),
                lambda t, *_: (jnp.minimum(t // n_s, B - 1), 0, 0, 0)),
            fixed(0, 0),
            bulk(bs, hd // 2), bulk(bs, ng),
            bulk(bs // 2, hd), bulk(bs // GROUP, hd),
            # freshly-demoted K band: the bulk arrays again as whole
            # refs; the epilogue slices one 32-slot block at the
            # prefetched index (cg-3), once
            fixed(0, 0), fixed(0, 0),
            fixed(INIT_TOKENS, hd), fixed(INIT_TOKENS, ng),
            fixed(LOCAL_TOKENS, hd), fixed(LOCAL_TOKENS, ng),
            fixed(GROUP, hd), fixed(1, hd),
            fixed(V_LOCAL_GROUPS_K * GROUP, hd), fixed(V_LOCAL_GROUPS_K, hd),
            fixed(GROUP, hd),
        ],
        out_specs=[
            pl.BlockSpec((B, Hkv, rep, hd), lambda t, *_: (0, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_bh * rep, hd), jnp.float32),
            pltpu.VMEM((n_bh * rep, 1), jnp.float32),
            pltpu.VMEM((n_bh * rep, 1), jnp.float32),
        ],
    )
    (o,) = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, rep, hd), jnp.float32)],
        interpret=interpret,
    )(prefetch, q4, q4, k_bulk_mant, k_bulk_exp, v_bulk_mant, v_bulk_exp,
      k_bulk_mant, k_bulk_exp, k_init_mant, k_init_exp,
      k_local_mant, k_local_exp, v_init_mant, v_init_exp,
      v_local_mant, v_local_exp, v_resid)
    return o.reshape(B, H, hd)


__all__ = ["bfp_attention_prefill_kernel", "bfp_attention_prefill_batched",
           "bfp_attention_decode_kernel", "bfp_attention_decode_batched",
           "bfp_attention_decode_asym_batched",
           "prefill_tile_counts", "BLOCK_Q_BATCHED", "BLOCK_S_BATCHED",
           "BLOCK_S_DECODE"]
