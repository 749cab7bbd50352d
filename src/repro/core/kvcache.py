"""Asymmetric BFP KV cache (paper Sec. III-B + Fig. 6b).

Two implementations, used at different layers of the system:

1. ``fake_quant_kv`` — position-masked fake quantization over flat fp K/V
   tensors.  Differentiable-ish, vmap/scan-friendly; used inside full-model
   accuracy experiments (Tables I/II, Fig. 5/8 analogues).

2. ``AsymKVCache`` — the *packed* production cache used by the serving
   engine, the decode dry-run and the Pallas decode kernel.  Real int4/int8
   storage, so ``memory_analysis()`` of the compiled decode step shows the
   paper's 31.25 % footprint:

   K (grouped per token along head_dim, hd/32 groups):
     * ``k_init``  — first INIT=32 tokens, 8-bit mantissas ("attention sink")
     * ``k_local`` — ring of LOCAL=64 most recent tokens, 8-bit
     * ``k_bulk``  — everything older, 4-bit mantissas packed 2/byte;
       a token is *demoted* (requantized 8b -> 4b) when it falls out of the
       local ring.

   V (grouped along the token dim per channel, 32-token groups — the P·V
   contraction direction):
     * ``v_resid`` — the residual (incomplete) group kept raw; re-converted
       at its current size every step (paper's incremental grouping) by the
       attention consumer,
     * ``v_init``  — group 0 at 8-bit,
     * ``v_local`` — ring of the 2 most recent complete groups at 8-bit,
     * ``v_bulk``  — older groups demoted to 4-bit.

   The cache uses a single scalar ``length`` (the serving engine left-pads
   batches so all rows share the position counter; per-row validity is
   handled by attention masks).

Token-to-region map at length L (0-indexed token t):
  K: t < 32 -> init;  t in [max(32, L-64), L) -> local ring slot (t-32)%64;
     t in [32, L-64) -> bulk slot t-32.
  V: group g = t//32; g == 0 -> init; complete groups {cg-1, cg-2} (>=1)
     -> local ring slot g%2; groups [1, cg-3] -> bulk; tokens >= 32*cg
     -> resid, where cg = L//32.

Every bulk buffer is *bulk-relative*: K slot j holds token 32+j, V mantissa
slot j holds token 32+j (nibble-packed in pairs along the token axis) and
``v_bulk_exp`` slot j holds group j+1 — the layout the decode kernels index
directly, so no per-step shift/concat re-layout of exponents exists
anywhere on the decode path (it used to cost an O(B.S/32.H.hd) copy per
layer per step).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import bfp
from repro.core.quant_config import KvQuantConfig

INIT_TOKENS = 32
LOCAL_TOKENS = 64
GROUP = 32
V_LOCAL_GROUPS = 2


# ---------------------------------------------------------------------------
# 1. Fake-quant path (model accuracy experiments)
# ---------------------------------------------------------------------------

def fake_quant_kv(k: jax.Array, v: jax.Array, cfg: KvQuantConfig,
                  length=None) -> Tuple[jax.Array, jax.Array]:
    """Apply the asymmetric BFP policy to flat (B, S, n_kv, hd) K/V.

    ``length``: optional scalar true sequence length; defaults to S.  The
    local window is the last ``cfg.local_tokens`` *valid* positions.
    K quantizes along head_dim per token; V along the token dim per channel.
    ``mantissa_bits >= 16`` means "leave FP" (used by FP16-KV baselines).
    """
    S = k.shape[1]
    length = S if length is None else length
    pos = jnp.arange(S)

    def _q(x, bits, axis):
        if bits >= 16:
            return x
        return bfp.bfp_fake_quant(x, cfg.group_size, bits, "trunc", axis=axis)

    if not cfg.asymmetric:
        return _q(k, cfg.mantissa_bits, -1), _q(v, cfg.mantissa_bits, 1)

    hi_mask = (pos < cfg.initial_tokens) | (pos >= length - cfg.local_tokens)
    hi_mask_k = hi_mask[None, :, None, None]

    k_hi = _q(k, cfg.high_mantissa_bits, -1)
    k_lo = _q(k, cfg.mantissa_bits, -1)
    k_out = jnp.where(hi_mask_k, k_hi, k_lo)

    # V groups run along tokens; a group is high-precision iff any of its
    # tokens is in the high region (hardware stores whole groups per mode).
    grp = pos // cfg.group_size
    grp_hi = jax.ops.segment_max(hi_mask.astype(jnp.int32), grp,
                                 num_segments=-(-S // cfg.group_size))
    v_hi_mask = grp_hi[grp].astype(bool)[None, :, None, None]
    v_hi = _q(v, cfg.high_mantissa_bits, 1)
    v_lo = _q(v, cfg.mantissa_bits, 1)
    v_out = jnp.where(v_hi_mask, v_hi, v_lo)
    return k_out, v_out


# ---------------------------------------------------------------------------
# 2. Packed asymmetric cache
# ---------------------------------------------------------------------------

class AsymKVCache(NamedTuple):
    """Packed per-layer KV cache.  All token axes are axis 1."""

    # --- K: per-token groups along head_dim ---
    k_init_mant: jax.Array   # (B, INIT, n_kv, hd)        int8
    k_init_exp: jax.Array    # (B, INIT, n_kv, hd//G)     int8
    k_local_mant: jax.Array  # (B, LOCAL, n_kv, hd)       int8 (ring)
    k_local_exp: jax.Array   # (B, LOCAL, n_kv, hd//G)    int8
    k_bulk_mant: jax.Array   # (B, S_bulk, n_kv, hd//2)   int8 (4b pairs)
    k_bulk_exp: jax.Array    # (B, S_bulk, n_kv, hd//G)   int8
    # --- V: per-channel groups along tokens ---
    v_resid: jax.Array       # (B, G, n_kv, hd)           bf16/f32 raw
    v_init_mant: jax.Array   # (B, G, n_kv, hd)           int8 (group 0)
    v_init_exp: jax.Array    # (B, 1, n_kv, hd)           int8
    v_local_mant: jax.Array  # (B, 2*G, n_kv, hd)         int8 (2-group ring)
    v_local_exp: jax.Array   # (B, 2, n_kv, hd)           int8
    v_bulk_mant: jax.Array   # (B, S_bulk//2, n_kv, hd)   int8 (4b pairs,
                             #   packed along the token axis inside a group)
    v_bulk_exp: jax.Array    # (B, S_bulk//G, n_kv, hd)   int8 (slot j =
                             #   group j+1: bulk-relative, kernel-indexable)
    # --- online-smoothing offsets for K (subtracted before quantization) ---
    k_offsets: jax.Array     # (B, n_kv, hd)              f32
    length: jax.Array        # ()                          int32

    @property
    def max_seq(self) -> int:
        return INIT_TOKENS + self.k_bulk_mant.shape[1]


def init_cache(batch: int, n_kv: int, head_dim: int, max_seq: int,
               resid_dtype=jnp.float32) -> AsymKVCache:
    if head_dim % GROUP != 0:
        raise ValueError(f"head_dim {head_dim} must be a multiple of {GROUP}")
    if max_seq % GROUP != 0 or max_seq < INIT_TOKENS + LOCAL_TOKENS + GROUP:
        raise ValueError(f"max_seq {max_seq} must be a multiple of {GROUP} "
                         f"and >= {INIT_TOKENS + LOCAL_TOKENS + GROUP}")
    s_bulk = max_seq - INIT_TOKENS
    ng = head_dim // GROUP
    i8, f = jnp.int8, resid_dtype
    z = jnp.zeros
    return AsymKVCache(
        k_init_mant=z((batch, INIT_TOKENS, n_kv, head_dim), i8),
        k_init_exp=z((batch, INIT_TOKENS, n_kv, ng), i8),
        k_local_mant=z((batch, LOCAL_TOKENS, n_kv, head_dim), i8),
        k_local_exp=z((batch, LOCAL_TOKENS, n_kv, ng), i8),
        k_bulk_mant=z((batch, s_bulk, n_kv, head_dim // 2), i8),
        k_bulk_exp=z((batch, s_bulk, n_kv, ng), i8),
        v_resid=z((batch, GROUP, n_kv, head_dim), f),
        v_init_mant=z((batch, GROUP, n_kv, head_dim), i8),
        v_init_exp=z((batch, 1, n_kv, head_dim), i8),
        v_local_mant=z((batch, V_LOCAL_GROUPS * GROUP, n_kv, head_dim), i8),
        v_local_exp=z((batch, V_LOCAL_GROUPS, n_kv, head_dim), i8),
        v_bulk_mant=z((batch, s_bulk // 2, n_kv, head_dim), i8),
        v_bulk_exp=z((batch, s_bulk // GROUP, n_kv, head_dim), i8),
        k_offsets=z((batch, n_kv, head_dim), jnp.float32),
        length=jnp.zeros((), jnp.int32),
    )


# -- quantization helpers on (B, T, n_kv, hd) slabs --

def _q_k(x, bits):
    """Quantize K tokens along head_dim.  Returns (mant i8 (..., hd),
    exp i8 (..., hd//G)) in the original layout."""
    mant, exp = bfp.bfp_quantize(x, GROUP, bits, axis=-1)
    mant = mant.reshape(x.shape)
    return mant, exp


def _dq_k(mant, exp, bits, dtype=jnp.float32):
    g = mant.reshape(mant.shape[:-1] + (mant.shape[-1] // GROUP, GROUP))
    step = bfp.pow2(exp.astype(jnp.float32) - (bits - 2))[..., None]
    return (g.astype(jnp.float32) * step).reshape(mant.shape).astype(dtype)


def _q_v_group(x, bits):
    """Quantize one (or more) complete V group(s) along the token axis.

    x: (B, n*G, n_kv, hd) -> mant (B, n*G, n_kv, hd) i8, exp (B, n, n_kv, hd).
    """
    B, T, H, D = x.shape
    xg = x.reshape(B, T // GROUP, GROUP, H, D)
    mant, exp = bfp.bfp_quantize(xg, GROUP, bits, axis=2)
    # bfp_quantize moved axis 2 last: mant (B, n, H, D, 1, G); restore.
    mant = jnp.moveaxis(mant.reshape(B, T // GROUP, H, D, GROUP), -1, 2)
    exp = exp.reshape(B, T // GROUP, H, D)
    return mant.reshape(B, T, H, D), exp


def _dq_v_group(mant, exp, bits, dtype=jnp.float32):
    B, T, H, D = mant.shape
    g = mant.reshape(B, T // GROUP, GROUP, H, D).astype(jnp.float32)
    step = bfp.pow2(exp.astype(jnp.float32) - (bits - 2))[:, :, None]
    return (g * step).reshape(B, T, H, D).astype(dtype)


def _pack4_lastdim(mant8):
    return bfp.pack_int4(mant8, axis=-1)


def _pack4_tokendim(mant8):
    return bfp.pack_int4(mant8, axis=1)


def predicated_write(buf: jax.Array, update: jax.Array, cond,
                     idx, axis: int = 1) -> jax.Array:
    """Write ``update`` into ``buf`` at ``idx`` iff ``cond``, else rewrite
    the slab's current contents.

    The write itself is unconditional — the predicate selects the *slab*
    (O(slab) work), never the whole buffer.  The alternative
    ``jnp.where(cond, dynamic_update_slice(buf, ...), buf)`` pattern keeps
    both the updated and the original buffer live through the select, so
    XLA must materialize a second O(buf) copy every step even when ``buf``
    is donated.  This form lowers to a single dynamic-update-slice, which
    XLA aliases in place under donation (and inside ``lax.scan`` carries).
    """
    n = update.shape[axis]
    cur = jax.lax.dynamic_slice_in_dim(buf, idx, n, axis=axis)
    slab = jnp.where(cond, update.astype(buf.dtype), cur)
    return jax.lax.dynamic_update_slice_in_dim(buf, slab, idx, axis=axis)


# ---------------------------------------------------------------------------
# Prefill: build all regions from (B, S, n_kv, hd) fp K/V
# ---------------------------------------------------------------------------

def prefill_cache(cache: AsymKVCache, k: jax.Array, v: jax.Array,
                  k_offsets: jax.Array | None = None, *,
                  use_pallas: bool = False,
                  interpret: bool | None = None) -> AsymKVCache:
    """Vectorized construction of the packed cache from a prefill chunk.

    ``k``/``v``: (B, S, n_kv, hd) with S a multiple of GROUP, S <= max_seq.
    ``k_offsets``: optional (B, n_kv, hd) online-smoothing offsets; they are
    subtracted from *all* keys before quantization (softmax-invariant).

    ``use_pallas=True`` builds every packed region through the grid-fused
    FP->BFP converter kernel (``kernels.ops.convert_prefill_cache``): the
    dense K/V tiles are quantized, demoted and nibble-packed in VMEM and
    only packed bytes are written to HBM — replacing this function's
    quantize + ``.at[].set`` XLA chains.  Bit-identical output.
    """
    B, S, H, D = k.shape
    if S % GROUP != 0:
        raise ValueError(f"prefill length {S} must be a multiple of {GROUP}")
    if k_offsets is None:
        k_offsets = jnp.zeros((B, H, D), jnp.float32)
    if use_pallas and D % GROUP == 0:
        from repro.kernels import ops as kernel_ops
        regions = kernel_ops.convert_prefill_cache(
            k.astype(jnp.float32), v.astype(jnp.float32),
            k_offsets.astype(jnp.float32),
            s_bulk=cache.k_bulk_mant.shape[1], interpret=interpret)
        return cache._replace(
            **regions, k_offsets=k_offsets.astype(jnp.float32),
            length=jnp.asarray(S, jnp.int32))
    k = k - k_offsets[:, None].astype(k.dtype)

    s_bulk = cache.k_bulk_mant.shape[1]

    # --- K regions ---
    k_init = k[:, :INIT_TOKENS]
    kim, kie = _q_k(k_init, 8)

    # local ring holds tokens [max(32, S-64), S) at slot (t-32)%64
    ring_lo = max(INIT_TOKENS, S - LOCAL_TOKENS)
    klm = jnp.zeros_like(cache.k_local_mant)
    kle = jnp.zeros_like(cache.k_local_exp)
    if S > INIT_TOKENS:
        toks = jnp.arange(ring_lo, S)
        slots = (toks - INIT_TOKENS) % LOCAL_TOKENS
        m, e = _q_k(k[:, ring_lo:S], 8)
        klm = klm.at[:, slots].set(m)
        kle = kle.at[:, slots].set(e)

    # bulk holds tokens [32, S-64) at 4-bit, slot t-32
    kbm = jnp.zeros_like(cache.k_bulk_mant)
    kbe = jnp.zeros_like(cache.k_bulk_exp)
    n_bulk = max(0, S - LOCAL_TOKENS - INIT_TOKENS)
    if n_bulk > 0:
        m, e = _q_k(k[:, INIT_TOKENS:INIT_TOKENS + n_bulk], 4)
        kbm = kbm.at[:, :n_bulk].set(_pack4_lastdim(m))
        kbe = kbe.at[:, :n_bulk].set(e)

    # --- V regions ---
    cg = S // GROUP
    v_init = v[:, :GROUP]
    vim, vie = _q_v_group(v_init, 8)

    vlm = jnp.zeros_like(cache.v_local_mant)
    vle = jnp.zeros_like(cache.v_local_exp)
    local_groups = [g for g in (cg - 2, cg - 1) if g >= 1]
    for g in local_groups:
        m, e = _q_v_group(v[:, g * GROUP:(g + 1) * GROUP], 8)
        slot = g % V_LOCAL_GROUPS
        vlm = vlm.at[:, slot * GROUP:(slot + 1) * GROUP].set(m)
        vle = vle.at[:, slot:slot + 1].set(e)

    vbm = jnp.zeros_like(cache.v_bulk_mant)
    vbe = jnp.zeros_like(cache.v_bulk_exp)
    n_bulk_g = max(0, cg - 2 - 1)  # groups 1 .. cg-3
    if n_bulk_g > 0:
        vb = v[:, GROUP:(1 + n_bulk_g) * GROUP]
        m, e = _q_v_group(vb, 4)
        # pack along token axis (pairs inside a group); exps bulk-relative
        vbm = vbm.at[:, : n_bulk_g * GROUP // 2].set(_pack4_tokendim(m))
        vbe = vbe.at[:, :n_bulk_g].set(e)
    del s_bulk

    # residual group: raw copy of the incomplete trailing group (none when
    # S is a multiple of GROUP, which prefill requires; kept zeroed).
    return cache._replace(
        k_init_mant=kim, k_init_exp=kie, k_local_mant=klm, k_local_exp=kle,
        k_bulk_mant=kbm, k_bulk_exp=kbe,
        v_init_mant=vim, v_init_exp=vie, v_local_mant=vlm, v_local_exp=vle,
        v_bulk_mant=vbm, v_bulk_exp=vbe,
        k_offsets=k_offsets.astype(jnp.float32),
        length=jnp.asarray(S, jnp.int32))


# ---------------------------------------------------------------------------
# Decode append: one token, with demotion
# ---------------------------------------------------------------------------

def append_token(cache: AsymKVCache, k_new: jax.Array,
                 v_new: jax.Array, *, legacy: bool = False) -> AsymKVCache:
    """Append one (B, n_kv, hd) K/V token at position t = length.

    jit-safe: all branches via lax.cond-free masking.  Every region is
    updated with :func:`predicated_write` — an unconditional slab-sized
    dynamic-update-slice whose *contents* are selected by the predicate —
    never with a whole-buffer ``jnp.where`` select, so a donated (or
    scan-carried) cache is mutated in place instead of copied per step.
    Demotes K token t-64 (8b->4b) and, when a V group completes, demotes
    V group g-2.

    ``legacy=True`` dispatches to the pre-fused-loop select-based
    formulation (the reference of ``tests/test_kvcache.py``): bit-identical
    values, whole-buffer ``jnp.where`` data movement.
    """
    if legacy:
        return _append_token_select(cache, k_new, v_new)
    t = cache.length
    B, _, H, D = cache.k_init_mant.shape
    k_new = (k_new.astype(jnp.float32)
             - cache.k_offsets).astype(jnp.float32)
    v_new = v_new.astype(cache.v_resid.dtype)

    # ---- K: init region ----
    km, ke = _q_k(k_new[:, None], 8)        # (B,1,H,D)/(B,1,H,D//G)
    in_init = t < INIT_TOKENS
    idx_init = jnp.clip(t, 0, INIT_TOKENS - 1)
    kim = predicated_write(cache.k_init_mant, km, in_init, idx_init)
    kie = predicated_write(cache.k_init_exp, ke, in_init, idx_init)

    # ---- K: local ring (tokens >= 32) + demotion of token t-64 ----
    in_ring = t >= INIT_TOKENS
    slot = jnp.clip((t - INIT_TOKENS) % LOCAL_TOKENS, 0, LOCAL_TOKENS - 1)
    # demote current occupant of `slot` (token t - 64) if it is a real token
    old_m = jax.lax.dynamic_slice_in_dim(cache.k_local_mant, slot, 1, axis=1)
    old_e = jax.lax.dynamic_slice_in_dim(cache.k_local_exp, slot, 1, axis=1)
    demote_tok = t - LOCAL_TOKENS
    do_demote = in_ring & (demote_tok >= INIT_TOKENS)
    old_fp = _dq_k(old_m, old_e, 8)
    dm, de = _q_k(old_fp, 4)
    bulk_idx = jnp.clip(demote_tok - INIT_TOKENS, 0,
                        cache.k_bulk_mant.shape[1] - 1)
    kbm = predicated_write(cache.k_bulk_mant, _pack4_lastdim(dm),
                           do_demote, bulk_idx)
    kbe = predicated_write(cache.k_bulk_exp, de, do_demote, bulk_idx)
    klm = predicated_write(cache.k_local_mant, km, in_ring, slot)
    kle = predicated_write(cache.k_local_exp, ke, in_ring, slot)

    # ---- V: residual group append ----
    r = t % GROUP
    v_resid = jax.lax.dynamic_update_slice_in_dim(
        cache.v_resid, v_new[:, None], r, axis=1)

    # group completes when r == GROUP-1; committed group index g = t//GROUP
    completes = r == GROUP - 1
    g = t // GROUP
    gm, ge = _q_v_group(v_resid, 8)         # quantize the full group @8b
    # -- commit to init (g == 0) --
    vim = predicated_write(cache.v_init_mant, gm, completes & (g == 0), 0)
    vie = predicated_write(cache.v_init_exp, ge, completes & (g == 0), 0)
    # -- commit to local ring (g >= 1) + demote group g-2 --
    vslot = jnp.clip(g % V_LOCAL_GROUPS, 0, V_LOCAL_GROUPS - 1)
    old_vm = jax.lax.dynamic_slice_in_dim(
        cache.v_local_mant, vslot * GROUP, GROUP, axis=1)
    old_ve = jax.lax.dynamic_slice_in_dim(cache.v_local_exp, vslot, 1, axis=1)
    old_vfp = _dq_v_group(old_vm, old_ve, 8)
    dvm, dve = _q_v_group(old_vfp, 4)
    gd = g - V_LOCAL_GROUPS
    do_vdemote = completes & (g >= 1) & (gd >= 1)
    vb_idx = jnp.clip((gd - 1) * (GROUP // 2), 0,
                      cache.v_bulk_mant.shape[1] - GROUP // 2)
    vbm = predicated_write(cache.v_bulk_mant, _pack4_tokendim(dvm),
                           do_vdemote, vb_idx)
    vbe_idx = jnp.clip(gd - 1, 0, cache.v_bulk_exp.shape[1] - 1)
    vbe = predicated_write(cache.v_bulk_exp, dve, do_vdemote, vbe_idx)
    do_vlocal = completes & (g >= 1)
    vlm = predicated_write(cache.v_local_mant, gm, do_vlocal, vslot * GROUP)
    vle = predicated_write(cache.v_local_exp, ge, do_vlocal, vslot)
    # clear residual after commit so stale values never leak into the next
    # group's shared exponent (elementwise select — aliasable in place)
    v_resid = jnp.where(completes, jnp.zeros_like(v_resid), v_resid)

    return cache._replace(
        k_init_mant=kim, k_init_exp=kie, k_local_mant=klm, k_local_exp=kle,
        k_bulk_mant=kbm, k_bulk_exp=kbe,
        v_resid=v_resid, v_init_mant=vim, v_init_exp=vie,
        v_local_mant=vlm, v_local_exp=vle, v_bulk_mant=vbm, v_bulk_exp=vbe,
        length=t + 1)


# ---------------------------------------------------------------------------
# Gather: dequantize to positionally-ordered (B, S_cap, n_kv, hd) + mask
# ---------------------------------------------------------------------------

def gather_kv(cache: AsymKVCache, dtype=jnp.float32, *,
              legacy: bool = False):
    """Dequantize the full cache into position order.

    ``legacy=True`` dispatches to the scatter/`.at[].set` formulation (the
    reference of ``tests/test_kvcache.py``) — bit-identical values.

    Returns (k, v, valid) where k/v: (B, max_seq, n_kv, hd) and
    valid: (max_seq,) bool (position < length).  The k_offsets are *not*
    added back — softmax shift-invariance makes that unnecessary (and the
    paper's hardware never undoes the shift).

    Overlay-based: the init and bulk regions already sit in position
    order (bulk slot j holds token 32+j), so their dequants concatenate
    straight into the output, and only the recent window is patched in
    with slab-sized read-modify-write overlays — a rolled 64-token K ring
    window and a 96-token V window (two complete ring groups + the
    residual group re-converted at its current size).  The previous
    scatter formulation (a chain of full-buffer ``.at[].set`` overlays
    and position scatters) materialized the O(B·S·hd) output several
    times per call — on the decode hot path that was the dominant
    per-step cost on CPU; XLA also lowers position scatters/gathers to
    scalar loops there.  Invalid positions (>= length) keep whatever the
    bulk region holds (freshly-demoted garbage), exactly like the scatter
    formulation — masked by ``valid`` downstream.
    """
    if legacy:
        return _gather_kv_select(cache, dtype)
    L = cache.length
    B, _, H, D = cache.k_init_mant.shape
    S = cache.max_seq
    pos = jnp.arange(S)

    # --- K: [init | bulk] in position order + rolled local-ring window ---
    k_init = _dq_k(cache.k_init_mant, cache.k_init_exp, 8, dtype)
    k_bulk = _dq_k(bfp.unpack_int4(cache.k_bulk_mant, axis=-1),
                   cache.k_bulk_exp, 4, dtype)
    k = jnp.concatenate([k_init, k_bulk], axis=1)
    k_local = _dq_k(cache.k_local_mant, cache.k_local_exp, 8, dtype)
    # window [w0, w0+64) with w0 = max(L-64, 32): position p lives at ring
    # slot (p-32)%64, so position order is the ring rolled by -(w0-32)
    w0 = jnp.clip(L - LOCAL_TOKENS, INIT_TOKENS, S - LOCAL_TOKENS)
    k_win = jax.lax.dynamic_slice_in_dim(        # ring rolled into position
        jnp.concatenate([k_local, k_local], axis=1),  # order, O(64) work
        (w0 - INIT_TOKENS) % LOCAL_TOKENS, LOCAL_TOKENS, axis=1)
    w_pos = w0 + jnp.arange(LOCAL_TOKENS)
    base = jax.lax.dynamic_slice_in_dim(k, w0, LOCAL_TOKENS, axis=1)
    merged = jnp.where((w_pos < L)[None, :, None, None], k_win, base)
    k = jax.lax.dynamic_update_slice_in_dim(k, merged, w0, axis=1)

    # --- V: [init | bulk | zero tail] in position order + a 3-group
    # window covering the complete ring groups {cg-2, cg-1} and the
    # residual group cg (incremental grouping: padded residual slots are
    # zero and never raise the shared max-exponent) ---
    cg = L // GROUP
    r = L % GROUP
    v_init = _dq_v_group(cache.v_init_mant, cache.v_init_exp, 8, dtype)
    vb_unpacked = bfp.unpack_int4(cache.v_bulk_mant, axis=1)
    n_bulk_groups = cache.v_bulk_exp.shape[1]
    v_bulk = _dq_v_group(
        vb_unpacked[:, : (n_bulk_groups - 1) * GROUP],
        cache.v_bulk_exp[:, : n_bulk_groups - 1], 4, dtype)
    v = jnp.concatenate(
        [v_init, v_bulk, jnp.zeros((B, GROUP, H, D), dtype)], axis=1)
    v_local = _dq_v_group(cache.v_local_mant, cache.v_local_exp, 8, dtype)
    resid_valid = jnp.arange(GROUP) < r
    resid = jnp.where(resid_valid[None, :, None, None],
                      cache.v_resid.astype(jnp.float32), 0.0)
    resid_q = bfp.bfp_fake_quant(resid, GROUP, 8, "trunc",
                                 axis=1).astype(dtype)
    n_win = V_LOCAL_GROUPS + 1
    g0 = jnp.clip((cg - V_LOCAL_GROUPS) * GROUP, 0,
                  S - n_win * GROUP) // GROUP
    parts, masks = [], []
    for i in range(n_win):
        gi = g0 + i
        from_ring = jnp.where(gi % V_LOCAL_GROUPS == 0,
                              v_local[:, :GROUP], v_local[:, GROUP:])
        parts.append(jnp.where(gi == cg, resid_q, from_ring))
        is_local = (gi >= 1) & (gi >= cg - V_LOCAL_GROUPS) & (gi < cg)
        masks.append(jnp.where(gi == cg, resid_valid,
                               jnp.broadcast_to(is_local, (GROUP,))))
    v_win = jnp.concatenate(parts, axis=1)          # (B, 96, H, D)
    v_mask = jnp.concatenate(masks)                 # (96,)
    base = jax.lax.dynamic_slice_in_dim(v, g0 * GROUP, n_win * GROUP,
                                        axis=1)
    merged = jnp.where(v_mask[None, :, None, None], v_win, base)
    v = jax.lax.dynamic_update_slice_in_dim(v, merged, g0 * GROUP, axis=1)

    valid = pos < L
    return k, v, valid


# ---------------------------------------------------------------------------
# Legacy (pre-fused-loop) formulations, kept as the reference of
# tests/test_kvcache.py (same values bit-for-bit, different data movement),
# reached through ``append_token(..., legacy=True)`` /
# ``gather_kv(..., legacy=True)``:
#   * _append_token_select — whole-buffer jnp.where selects around every
#     dynamic_update_slice (no in-place aliasing under donation),
#   * _gather_kv_select — position scatters / .at[].set overlay chains.
# ---------------------------------------------------------------------------

def _append_token_select(cache: AsymKVCache, k_new: jax.Array,
                         v_new: jax.Array) -> AsymKVCache:
    """Legacy append: ``jnp.where(cond, dynamic_update_slice(...), x)`` on
    every region (the pattern the predicated-write rewrite replaced)."""
    t = cache.length
    k_new = (k_new.astype(jnp.float32)
             - cache.k_offsets).astype(jnp.float32)
    v_new = v_new.astype(cache.v_resid.dtype)

    km, ke = _q_k(k_new[:, None], 8)
    in_init = t < INIT_TOKENS
    idx_init = jnp.clip(t, 0, INIT_TOKENS - 1)
    dus = jax.lax.dynamic_update_slice_in_dim
    kim = jnp.where(in_init, dus(cache.k_init_mant, km, idx_init, axis=1),
                    cache.k_init_mant)
    kie = jnp.where(in_init, dus(cache.k_init_exp, ke, idx_init, axis=1),
                    cache.k_init_exp)

    in_ring = t >= INIT_TOKENS
    slot = jnp.clip((t - INIT_TOKENS) % LOCAL_TOKENS, 0, LOCAL_TOKENS - 1)
    old_m = jax.lax.dynamic_slice_in_dim(cache.k_local_mant, slot, 1, axis=1)
    old_e = jax.lax.dynamic_slice_in_dim(cache.k_local_exp, slot, 1, axis=1)
    demote_tok = t - LOCAL_TOKENS
    do_demote = in_ring & (demote_tok >= INIT_TOKENS)
    dm, de = _q_k(_dq_k(old_m, old_e, 8), 4)
    bulk_idx = jnp.clip(demote_tok - INIT_TOKENS, 0,
                        cache.k_bulk_mant.shape[1] - 1)
    kbm = jnp.where(do_demote, dus(cache.k_bulk_mant, _pack4_lastdim(dm),
                                   bulk_idx, axis=1), cache.k_bulk_mant)
    kbe = jnp.where(do_demote, dus(cache.k_bulk_exp, de, bulk_idx, axis=1),
                    cache.k_bulk_exp)
    klm = jnp.where(in_ring, dus(cache.k_local_mant, km, slot, axis=1),
                    cache.k_local_mant)
    kle = jnp.where(in_ring, dus(cache.k_local_exp, ke, slot, axis=1),
                    cache.k_local_exp)

    r = t % GROUP
    v_resid = dus(cache.v_resid, v_new[:, None], r, axis=1)
    completes = r == GROUP - 1
    g = t // GROUP
    gm, ge = _q_v_group(v_resid, 8)
    vim = jnp.where(completes & (g == 0), gm, cache.v_init_mant)
    vie = jnp.where(completes & (g == 0), ge, cache.v_init_exp)
    vslot = jnp.clip(g % V_LOCAL_GROUPS, 0, V_LOCAL_GROUPS - 1)
    old_vm = jax.lax.dynamic_slice_in_dim(
        cache.v_local_mant, vslot * GROUP, GROUP, axis=1)
    old_ve = jax.lax.dynamic_slice_in_dim(cache.v_local_exp, vslot, 1,
                                          axis=1)
    dvm, dve = _q_v_group(_dq_v_group(old_vm, old_ve, 8), 4)
    gd = g - V_LOCAL_GROUPS
    do_vdemote = completes & (g >= 1) & (gd >= 1)
    vb_idx = jnp.clip((gd - 1) * (GROUP // 2), 0,
                      cache.v_bulk_mant.shape[1] - GROUP // 2)
    vbm = jnp.where(do_vdemote, dus(cache.v_bulk_mant,
                                    _pack4_tokendim(dvm), vb_idx, axis=1),
                    cache.v_bulk_mant)
    vbe_idx = jnp.clip(gd - 1, 0, cache.v_bulk_exp.shape[1] - 1)
    vbe = jnp.where(do_vdemote, dus(cache.v_bulk_exp, dve, vbe_idx, axis=1),
                    cache.v_bulk_exp)
    do_vlocal = completes & (g >= 1)
    vlm = jnp.where(do_vlocal, dus(cache.v_local_mant, gm, vslot * GROUP,
                                   axis=1), cache.v_local_mant)
    vle = jnp.where(do_vlocal, dus(cache.v_local_exp, ge, vslot, axis=1),
                    cache.v_local_exp)
    v_resid = jnp.where(completes, jnp.zeros_like(v_resid), v_resid)

    return cache._replace(
        k_init_mant=kim, k_init_exp=kie, k_local_mant=klm, k_local_exp=kle,
        k_bulk_mant=kbm, k_bulk_exp=kbe,
        v_resid=v_resid, v_init_mant=vim, v_init_exp=vie,
        v_local_mant=vlm, v_local_exp=vle, v_bulk_mant=vbm, v_bulk_exp=vbe,
        length=t + 1)


def _gather_kv_select(cache: AsymKVCache, dtype=jnp.float32):
    """Legacy gather: scatter the ring/local/residual regions into
    position order through ``.at[].set`` overlay chains (each one
    materializes the O(B·S·hd) output again)."""
    L = cache.length
    B, _, H, D = cache.k_init_mant.shape
    S = cache.max_seq
    pos = jnp.arange(S)

    k = jnp.zeros((B, S + 1, H, D), dtype)
    k = k.at[:, :INIT_TOKENS].set(_dq_k(cache.k_init_mant,
                                        cache.k_init_exp, 8, dtype))
    kb = _dq_k(bfp.unpack_int4(cache.k_bulk_mant, axis=-1),
               cache.k_bulk_exp, 4, dtype)
    k = k.at[:, INIT_TOKENS:S].set(kb)
    s_idx = jnp.arange(LOCAL_TOKENS)
    t_s = INIT_TOKENS + s_idx + LOCAL_TOKENS * (
        (L - 1 - INIT_TOKENS - s_idx) // LOCAL_TOKENS)
    ring_valid = (t_s >= INIT_TOKENS) & (t_s < L) & (L > INIT_TOKENS)
    t_safe = jnp.where(ring_valid, jnp.clip(t_s, 0, S - 1), S)
    kl = _dq_k(cache.k_local_mant, cache.k_local_exp, 8, dtype)
    k = k.at[:, t_safe].set(kl)
    k = k[:, :S]

    v = jnp.zeros((B, S + GROUP, H, D), dtype)
    v = v.at[:, :GROUP].set(_dq_v_group(cache.v_init_mant,
                                        cache.v_init_exp, 8, dtype))
    vb_unpacked = bfp.unpack_int4(cache.v_bulk_mant, axis=1)
    n_bulk_groups = cache.v_bulk_exp.shape[1]
    vb = _dq_v_group(
        vb_unpacked[:, : (n_bulk_groups - 1) * GROUP],
        cache.v_bulk_exp[:, : n_bulk_groups - 1], 4, dtype)
    v = v.at[:, GROUP:GROUP + vb.shape[1]].set(vb)
    cg = L // GROUP
    sg = jnp.arange(V_LOCAL_GROUPS)
    g_s = sg + V_LOCAL_GROUPS * ((cg - 1 - sg) // V_LOCAL_GROUPS)
    g_valid = (g_s >= 1) & (g_s < cg)
    vl = _dq_v_group(cache.v_local_mant, cache.v_local_exp, 8, dtype)
    g_safe = jnp.where(g_valid, jnp.clip(g_s, 0, S // GROUP - 1),
                       S // GROUP)
    tok_targets = (g_safe[:, None] * GROUP + jnp.arange(GROUP)[None, :]
                   ).reshape(-1)
    vl_flat = vl.reshape(B, V_LOCAL_GROUPS * GROUP, H, D)
    v = v.at[:, tok_targets].set(vl_flat)
    v = v[:, :S]
    r = L % GROUP
    resid_valid = jnp.arange(GROUP) < r
    resid = jnp.where(resid_valid[None, :, None, None],
                      cache.v_resid.astype(jnp.float32), 0.0)
    resid_q = bfp.bfp_fake_quant(resid, GROUP, 8, "trunc", axis=1)
    tok0 = jnp.clip(cg * GROUP, 0, S - GROUP)
    window = jax.lax.dynamic_slice_in_dim(v, tok0, GROUP, axis=1)
    merged = jnp.where(resid_valid[None, :, None, None],
                       resid_q.astype(dtype), window)
    v = jax.lax.dynamic_update_slice_in_dim(v, merged, tok0, axis=1)

    valid = pos < L
    return k, v, valid


def cache_bytes(cache: AsymKVCache) -> int:
    """Physical bytes of the packed cache (for EXPERIMENTS.md §Dry-run)."""
    return sum(x.size * x.dtype.itemsize for x in cache)


def fp16_cache_bytes(batch: int, n_kv: int, head_dim: int,
                     max_seq: int) -> int:
    return batch * n_kv * head_dim * max_seq * 2 * 2  # K and V, fp16


__all__ = ["AsymKVCache", "init_cache", "prefill_cache", "append_token",
           "gather_kv", "fake_quant_kv", "cache_bytes", "fp16_cache_bytes",
           "predicated_write",
           "INIT_TOKENS", "LOCAL_TOKENS", "GROUP", "V_LOCAL_GROUPS"]
