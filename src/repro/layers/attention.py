"""Attention with all-layer BFP activations (the paper's key extension).

Quantization sites (paper Fig. 6a):
  * Q, K: per-token BFP groups along head_dim (the QK^T contraction dim),
  * P (post-softmax scores): groups along the key-token dim (the P.V
    contraction dim),
  * V: groups along the token dim per channel,
  * KV cache: asymmetric 8b/4b policy (repro.core.kvcache).

Three execution paths:
  1. ``attention_forward`` — train / prefill full-sequence attention
     (causal, local-window or bidirectional), optional BFP on fresh
     Q/K/V/P, returns (out, k_cacheable, v) so callers can build caches.
  2. ``attention_eval_quant`` — *decode-faithful* fake-quant evaluation:
     each query reads key t' at the precision it would have in the cache at
     that moment (8-bit if t' < 32 or t' >= t - 64, else 4-bit).  Used by
     the accuracy benchmarks (Table I/II analogues).  Costs 2x scores.
  3. ``attention_decode_packed`` — one-token decode against the packed
     ``AsymKVCache`` (dequantize-and-attend; the Pallas kernel fuses this
     on TPU).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import bfp, kvcache
from repro.core.quant_config import QuantConfig
from repro.core.smoothing import compute_online_offsets
from repro.layers.common import softcap as _softcap

NEG_INF = -2.3819763e38  # < bf16 min


def _group_heads(q, k):
    """GQA einsum without materializing repeated KV.

    q: (B,S,H,hd), k: (B,T,Hkv,hd) -> scores (B, Hkv, rep, S, T) f32.
    Inputs stay in their storage dtype (bf16 on the serve path — BFP8
    mantissas dequantize exactly into bf16); accumulation is f32 via
    preferred_element_type, matching the MXU."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    qg = q.reshape(B, S, Hkv, rep, hd).astype(k.dtype)
    return jnp.einsum("bsgrd,btgd->bgrst", qg, k,
                      preferred_element_type=jnp.float32)


def _apply_scores_v(p, v):
    """p: (B, Hkv, rep, S, T) f32, v: (B, T, Hkv, hd) -> (B, S, H, hd)."""
    B, Hkv, rep, S, T = p.shape
    out = jnp.einsum("bgrst,btgd->bsgrd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, Hkv * rep, out.shape[-1])


def make_mask(q_pos: jax.Array, k_pos: jax.Array, kind: str,
              window: int = 0,
              k_valid: Optional[jax.Array] = None) -> jax.Array:
    """Boolean (.., Sq, Sk) mask; True = attend.

    kind: "causal" | "local" (causal sliding window) | "bidir".
    """
    d = q_pos[..., :, None] - k_pos[..., None, :]
    if kind == "causal":
        m = d >= 0
    elif kind == "local":
        m = (d >= 0) & (d < window)
    elif kind == "bidir":
        m = jnp.ones(d.shape, bool)
    else:
        raise ValueError(f"unknown mask kind {kind!r}")
    if k_valid is not None:
        m = m & k_valid[..., None, :]
    return m


def _masked_softmax(scores, mask, logit_cap: float):
    if logit_cap > 0:
        scores = _softcap(scores, logit_cap)
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    # rows with no valid key (padding) -> zero output
    p = jnp.where(mask.any(axis=-1, keepdims=True), p, 0.0)
    return p


def _quant_p(p, quant: Optional[QuantConfig]):
    if quant is not None and quant.enabled and quant.quant_attention:
        p = bfp.bfp_fake_quant(p, quant.group_size,
                               quant.score_mantissa_bits, quant.rounding,
                               axis=-1, ste=quant.ste)
    return p


def _quant_qk(x, quant: Optional[QuantConfig]):
    if quant is not None and quant.enabled and quant.quant_attention:
        x = bfp.bfp_fake_quant(x, quant.group_size, quant.act_mantissa_bits,
                               quant.rounding, axis=-1, ste=quant.ste)
    return x


def _quant_v_fresh(v, quant: Optional[QuantConfig]):
    if quant is not None and quant.enabled and quant.quant_attention:
        v = bfp.bfp_fake_quant(v, quant.group_size, quant.act_mantissa_bits,
                               quant.rounding, axis=1,  # token axis
                               ste=quant.ste)
    return v


# Above this many keys, attention_forward switches to the chunked
# (flash-style) path: O(chunk^2) temporaries instead of O(S^2).  The dense
# path keeps the exact post-softmax P-BFP semantics used by accuracy
# evals; the flash path (like the Pallas kernel) keeps P in fp32 tiles.
# 2048: train_4k and prefill_32k both take the flash path (§Perf iter 3 —
# the dense path materializes (B,H,Sq,Sk) f32 scores ~6x per layer).
FLASH_THRESHOLD = 2048
FLASH_Q_CHUNK = 1024
FLASH_KV_CHUNK = 2048


def attention_forward(q: jax.Array, k: jax.Array, v: jax.Array,
                      positions: jax.Array, *, mask_kind: str = "causal",
                      window: int = 0, logit_cap: float = 0.0,
                      quant: Optional[QuantConfig] = None,
                      k_valid: Optional[jax.Array] = None,
                      kq_positions: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence attention on fresh (post-RoPE) q/k/v.

    q: (B,S,H,hd); k,v: (B,T,Hkv,hd); positions: (B,S) query positions;
    kq_positions: (B,T) key positions (defaults to ``positions``).
    """
    hd = q.shape[-1]
    kpos = positions if kq_positions is None else kq_positions
    q = _quant_qk(q, quant)
    k = _quant_qk(k, quant)
    v = _quant_v_fresh(v, quant)
    if k.shape[1] > FLASH_THRESHOLD:
        return _flash_forward(q, k, v, positions, kpos,
                              mask_kind=mask_kind, window=window,
                              logit_cap=logit_cap, k_valid=k_valid)
    scores = _group_heads(q, k) / jnp.sqrt(float(hd))
    mask = make_mask(positions, kpos, mask_kind, window, k_valid)
    p = _masked_softmax(scores, mask[:, None, None], logit_cap)
    p = _quant_p(p, quant)
    return _apply_scores_v(p, v)


def _flash_forward(q, k, v, q_pos, k_pos, *, mask_kind: str, window: int,
                   logit_cap: float, k_valid,
                   q_chunk: int = FLASH_Q_CHUNK,
                   kv_chunk: int = FLASH_KV_CHUNK) -> jax.Array:
    """Flash-style attention in pure XLA: scan over query chunks, inner
    scan over KV chunks with online softmax.  Inner body is checkpointed
    so the backward pass recomputes P tiles instead of storing O(S^2)."""
    B, S, H, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    bq = min(q_chunk, S)
    if S % bq:
        bq = S
    bkv = min(kv_chunk, T)
    if T % bkv:
        bkv = T
    nq, nk = S // bq, T // bkv
    scale = 1.0 / jnp.sqrt(float(hd))

    qs = q.reshape(B, nq, bq, Hkv, rep, hd)
    qp = q_pos.reshape(B, nq, bq)
    ks = k.reshape(B, nk, bkv, Hkv, hd)
    vs = v.reshape(B, nk, bkv, Hkv, hd)
    kp = k_pos.reshape(B, nk, bkv)
    kv_val = None if k_valid is None else k_valid.reshape(B, nk, bkv)

    def q_step(_, xq):
        q_c, qp_c = xq  # (B,bq,Hkv,rep,hd), (B,bq)

        @partial(jax.checkpoint, prevent_cse=False)
        def kv_step(carry, xkv):
            acc, m, l = carry
            k_c, v_c, kp_c, valid_c = xkv
            s = jnp.einsum("bqgrd,bkgd->bgrqk", q_c.astype(jnp.float32),
                           k_c.astype(jnp.float32)) * scale
            if logit_cap > 0:
                s = logit_cap * jnp.tanh(s / logit_cap)
            d = qp_c[:, :, None] - kp_c[:, None, :]
            if mask_kind == "causal":
                msk = d >= 0
            elif mask_kind == "local":
                msk = (d >= 0) & (d < window)
            else:
                msk = jnp.ones(d.shape, bool)
            if valid_c is not None:
                msk = msk & valid_c[:, None, :]
            msk = msk[:, None, None]
            s = jnp.where(msk, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(msk, jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bgrqk,bkgd->bgrqd", p, v_c.astype(jnp.float32))
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, Hkv, rep, bq, hd), jnp.float32)
        m0 = jnp.full((B, Hkv, rep, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, rep, bq), jnp.float32)
        xs = (jnp.moveaxis(ks, 1, 0), jnp.moveaxis(vs, 1, 0),
              jnp.moveaxis(kp, 1, 0),
              None if kv_val is None else jnp.moveaxis(kv_val, 1, 0))
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0), xs)
        out = jnp.where(l[..., None] > 0,
                        acc / jnp.maximum(l[..., None], 1e-30), 0.0)
        # (B,Hkv,rep,bq,hd) -> (B,bq,H,hd)
        return None, jnp.moveaxis(out, 3, 1).reshape(B, bq, H, hd)

    _, outs = jax.lax.scan(
        q_step, None,
        (jnp.moveaxis(qs, 1, 0), jnp.moveaxis(qp, 1, 0)))
    # outs: (nq, B, bq, H, hd)
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, H, hd)


def attention_eval_quant(q: jax.Array, k: jax.Array, v: jax.Array,
                         positions: jax.Array, quant: QuantConfig, *,
                         mask_kind: str = "causal", window: int = 0,
                         logit_cap: float = 0.0,
                         k_valid: Optional[jax.Array] = None) -> jax.Array:
    """Decode-faithful asymmetric-KV fake-quant attention (teacher-forced).

    Key/value t' is read at 8-bit when t' < init or t' >= t - local
    (it would still be in the init region / local ring when query t runs),
    else at the demoted 4-bit precision.  V precision follows its 32-token
    group (a group is high iff any resident token is high *at read time*).
    """
    hd = q.shape[-1]
    kv = quant.kv
    S = q.shape[1]
    q = _quant_qk(q, quant)

    def _qk(x, bits):
        if bits >= 16:
            return x
        return bfp.bfp_fake_quant(x, kv.group_size, bits, quant.rounding,
                                  axis=-1, ste=quant.ste)

    def _qv(x, bits):
        if bits >= 16:
            return x
        return bfp.bfp_fake_quant(x, kv.group_size, bits, quant.rounding,
                                  axis=1, ste=quant.ste)

    if not kv.asymmetric:
        k_lo = _qk(k, kv.mantissa_bits)
        v_lo = _qv(v, kv.mantissa_bits)
        scores = _group_heads(q, k_lo) / jnp.sqrt(float(hd))
        mask = make_mask(positions, positions, mask_kind, window, k_valid)
        p = _masked_softmax(scores, mask[:, None, None], logit_cap)
        p = _quant_p(p, quant)
        return _apply_scores_v(p, v_lo)

    k_hi, k_lo = _qk(k, kv.high_mantissa_bits), _qk(k, kv.mantissa_bits)
    v_hi, v_lo = _qv(v, kv.high_mantissa_bits), _qv(v, kv.mantissa_bits)

    s_hi = _group_heads(q, k_hi)
    s_lo = _group_heads(q, k_lo)
    scale = 1.0 / jnp.sqrt(float(hd))

    tq = positions[:, :, None]                      # (B,S,1)
    tk = positions[:, None, :]                      # (B,1,S)
    hi_region = (tk < kv.initial_tokens) | (tk >= tq - kv.local_tokens)
    scores = jnp.where(hi_region[:, None, None], s_hi, s_lo) * scale

    mask = make_mask(positions, positions, mask_kind, window, k_valid)
    p = _masked_softmax(scores, mask[:, None, None], logit_cap)
    p = _quant_p(p, quant)

    # V group precision at read time: group g hi iff any of its tokens in hi
    grp = (jnp.arange(S) // kv.group_size)[None, None, :]
    ghi = hi_region  # token-level; lift to group via segment max over tk
    # group is hi for query t iff any token of the group is hi for t
    ghi_g = jax.ops.segment_max(
        ghi.astype(jnp.int32).swapaxes(0, 2), jnp.arange(S) // kv.group_size,
        num_segments=-(-S // kv.group_size)).swapaxes(0, 2)
    v_hi_tok = ghi_g[..., grp[0, 0]]                # (B,S,S) back to tokens
    p_hi = jnp.where(v_hi_tok[:, None, None].astype(bool), p, 0.0)
    p_lo = p - p_hi
    return _apply_scores_v(p_hi, v_hi) + _apply_scores_v(p_lo, v_lo)


def attention_prefill_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                             causal: bool = True, window: int = 0,
                             logit_cap: float = 0.0,
                             quant: Optional[QuantConfig] = None,
                             interpret: Optional[bool] = None) -> jax.Array:
    """Prefill attention through the grid-fused Pallas kernel.

    q: (B,S,H,hd); k, v: (B,S,Hkv,hd) fresh (post-RoPE) values.  K/V are
    materialized as packed BFP (K per-token groups along hd, V token
    groups — the paper's Fig. 6a sites) and consumed compressed by one
    batched ``pallas_call`` over the (B·Hkv, S/bq, S/bs) grid.  Unlike
    ``attention_forward`` the post-softmax P stays fp32 inside the kernel
    (DESIGN.md §2), so this is the serving path, not the fake-quant
    accuracy path.  Requires S % 32 == 0 (the V token-group layout).
    """
    from repro.kernels import ops as kernel_ops
    bits = (quant.act_mantissa_bits
            if quant is not None and quant.enabled and quant.quant_attention
            else 8)
    q = _quant_qk(q, quant)
    # one-launch grid-fused FP->BFP converter: per-token K groups and
    # token-grouped V share the (B·Hkv, S/bs) grid and are reduced and
    # packed on the VMEM tile (no XLA moveaxis re-layout pass between
    # the dense QKV and the kernel, one launch instead of two quantizes)
    km, ke, vm, ve = kernel_ops.bfp_quantize_kv_pair(
        k.astype(jnp.float32), v.astype(jnp.float32), bits,
        interpret=interpret)
    return kernel_ops.bfp_attention_prefill(
        q.astype(jnp.float32), km, ke, vm, ve, mantissa_bits=bits,
        causal=causal, logit_cap=logit_cap, window=window,
        interpret=interpret)


def _decode_packed_pallas_single(q: jax.Array, cache: kvcache.AsymKVCache,
                                 *, logit_cap: float,
                                 quant: Optional[QuantConfig],
                                 extra_invalid_prefix: Optional[jax.Array],
                                 interpret: Optional[bool]) -> jax.Array:
    """Single-launch kernel decode: one ``pallas_call`` whose grid covers
    all three asymmetric-cache regions — the 4-bit bulk tiles plus a
    final step that dequantizes the 8-bit init block and the recent
    window (local K ring, freshly-demoted K band, V group ring, residual
    group) in-tile and merges the flash triples in-kernel.  Bit-exact
    against :func:`_decode_packed_pallas` at matched bulk tiles, minus
    its two extra launches and XLA dynamic-slice/select epilogue."""
    from repro.kernels import ops as kernel_ops
    B, _, H, hd = q.shape
    q = _quant_qk(q, quant).astype(jnp.float32)
    start = None
    if extra_invalid_prefix is not None:
        start = extra_invalid_prefix.astype(jnp.int32)
    out = kernel_ops.bfp_attention_decode_cache(
        q[:, 0], cache, start=start, logit_cap=logit_cap,
        interpret=interpret)
    return out.reshape(B, 1, H, hd)


def _decode_packed_pallas(q: jax.Array, cache: kvcache.AsymKVCache, *,
                          logit_cap: float,
                          quant: Optional[QuantConfig],
                          extra_invalid_prefix: Optional[jax.Array],
                          interpret: Optional[bool]) -> jax.Array:
    """Legacy two-launch kernel decode (the ``kernels_micro`` benchmark
    baseline): the 4-bit bulk region goes through the grid-fused Pallas
    kernel; the small 8-bit init/local/residual regions are handled by an
    XLA epilogue and merged via the flash triple.

    Region split at length L (cg = L//32):
      * bulk (kernel): tokens [32, 32·(cg-2)) — the common range where
        both K and V are already demoted to 4-bit,
      * epilogue: init tokens [0, 32) plus the recent window
        [32·max(cg-2, 1), L) (< 96 tokens) — K from the local ring and
        the freshly-demoted bulk band, V from the local group ring and
        the residual group (re-converted at its current size).
    """
    from repro.kernels import ops as kernel_ops
    B, _, H, hd = q.shape
    Hkv = cache.k_init_mant.shape[2]
    rep = H // Hkv
    G, INIT, LOCAL = kvcache.GROUP, kvcache.INIT_TOKENS, kvcache.LOCAL_TOKENS
    L = cache.length
    cg = L // G
    q = _quant_qk(q, quant).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(float(hd))

    # ---- bulk region through the fused kernel ----
    vl_bulk = jnp.maximum(G * (cg - 2) - INIT, 0)          # valid bulk slots
    start = None
    if extra_invalid_prefix is not None:
        start = jnp.maximum(extra_invalid_prefix.astype(jnp.int32) - INIT, 0)
    # v_bulk_exp is bulk-relative (slot g-1 holds group g) — exactly the
    # order the kernel indexes, so it is passed straight through
    o_b, m_b, l_b = kernel_ops.bfp_attention_decode_bulk(
        q[:, 0], cache.k_bulk_mant, cache.k_bulk_exp,
        cache.v_bulk_mant, cache.v_bulk_exp, vl_bulk, start=start,
        logit_cap=logit_cap, interpret=interpret)

    # ---- epilogue: init region + recent window ----
    k_init = kvcache._dq_k(cache.k_init_mant, cache.k_init_exp, 8)
    v_init = kvcache._dq_v_group(cache.v_init_mant, cache.v_init_exp, 8)

    W = LOCAL + G                                          # 96-slot window
    R0 = G * jnp.maximum(cg - 2, 1)
    t_win = R0 + jnp.arange(W)                             # absolute tokens
    # K: local ring for the last LOCAL tokens, bulk band for the rest
    use_local = t_win >= jnp.maximum(INIT, L - LOCAL)
    k_loc = kvcache._dq_k(cache.k_local_mant, cache.k_local_exp, 8)
    k_from_local = k_loc[:, (t_win - INIT) % LOCAL]
    s_bulk = cache.k_bulk_mant.shape[1]
    b0 = jnp.clip(R0 - INIT, 0, s_bulk - W)
    kb_m = jax.lax.dynamic_slice_in_dim(cache.k_bulk_mant, b0, W, axis=1)
    kb_e = jax.lax.dynamic_slice_in_dim(cache.k_bulk_exp, b0, W, axis=1)
    k_band = kvcache._dq_k(bfp.unpack_int4(kb_m, axis=-1), kb_e, 4)
    k_from_bulk = k_band[:, jnp.clip(t_win - INIT - b0, 0, W - 1)]
    k_win = jnp.where(use_local[None, :, None, None], k_from_local,
                      k_from_bulk)
    # V: groups a, a+1, a+2 from the local group ring / residual group
    v_loc = kvcache._dq_v_group(cache.v_local_mant, cache.v_local_exp, 8)
    r = L % G
    resid = jnp.where((jnp.arange(G) < r)[None, :, None, None],
                      cache.v_resid.astype(jnp.float32), 0.0)
    resid_q = bfp.bfp_fake_quant(resid, G, 8, "trunc", axis=1)
    a0 = jnp.maximum(cg - 2, 1)
    v_parts = []
    for off in range(W // G):
        gg = a0 + off
        from_ring = jnp.where(gg % kvcache.V_LOCAL_GROUPS == 0,
                              v_loc[:, :G], v_loc[:, G:2 * G])
        v_parts.append(jnp.where(gg == cg, resid_q, from_ring))
    v_win = jnp.concatenate(v_parts, axis=1)               # (B, 96, Hkv, hd)

    k_ep = jnp.concatenate([k_init, k_win], axis=1)        # (B, 32+96, ..)
    v_ep = jnp.concatenate([v_init, v_win], axis=1)
    pos_ep = jnp.concatenate([jnp.arange(INIT), t_win])
    valid_ep = pos_ep[None, :] < L
    if extra_invalid_prefix is not None:
        valid_ep = valid_ep & (pos_ep[None, :]
                               >= extra_invalid_prefix[:, None])

    s_e = _group_heads(q, k_ep) * scale                    # (B,Hkv,rep,1,T)
    if logit_cap > 0:
        s_e = _softcap(s_e, logit_cap)
    s_e = jnp.where(valid_ep[:, None, None, None], s_e, -1e30)
    m_e = jnp.max(s_e, axis=-1)                            # (B,Hkv,rep,1)
    p_e = jnp.where(valid_ep[:, None, None, None],
                    jnp.exp(s_e - m_e[..., None]), 0.0)
    l_e = jnp.sum(p_e, axis=-1)
    o_e = jnp.einsum("bgrst,btgd->bgrsd", p_e, v_ep,
                     preferred_element_type=jnp.float32)[:, :, :, 0]

    # ---- merge the two flash triples ----
    m_e, l_e = m_e[..., 0], l_e[..., 0]                    # (B,Hkv,rep)
    o_b = o_b.reshape(B, Hkv, rep, hd)
    m_b = m_b.reshape(B, Hkv, rep)
    l_b = l_b.reshape(B, Hkv, rep)
    m = jnp.maximum(m_e, m_b)
    a_e = jnp.exp(m_e - m)
    a_b = jnp.exp(m_b - m)
    l = l_e * a_e + l_b * a_b
    o = o_e * a_e[..., None] + o_b * a_b[..., None]
    out = jnp.where(l[..., None] > 0,
                    o / jnp.maximum(l[..., None], 1e-30), 0.0)
    return out.reshape(B, 1, H, hd)


def attention_decode_packed(q: jax.Array, cache: kvcache.AsymKVCache, *,
                            logit_cap: float = 0.0,
                            quant: Optional[QuantConfig] = None,
                            extra_invalid_prefix: Optional[jax.Array] = None,
                            seq_shard: bool = False,
                            dp_axes: tuple = ("data",),
                            use_pallas: bool = False,
                            single_launch: bool = True,
                            interpret: Optional[bool] = None) -> jax.Array:
    """One-token decode: q (B,1,H,hd) against the packed asymmetric cache.

    ``extra_invalid_prefix``: optional (B,) count of left-pad positions to
    mask out (serving engine).  Returns (B,1,H,hd).

    ``use_pallas=True`` routes the whole cache read through one
    single-launch grid-fused Pallas kernel: the 4-bit bulk tiles and the
    small 8-bit init/local/residual regions are dequantized per-region in
    the tile body and the flash triples merge in-kernel — no XLA epilogue
    and no extra launches.  ``single_launch=False`` restores the legacy
    two-launch form (bulk kernel + XLA flash epilogue), kept as the
    ``kernels_micro`` benchmark baseline.  P stays fp32 inside the
    kernels on both forms (DESIGN.md §2), so ``quant.quant_attention``
    P-quantization is not applied there.

    The default XLA path dequantizes the cache to bf16 (mantissas <= 8
    bits are exactly representable; the 2^e scales are exact) — halves
    decode HBM traffic vs f32 (§Perf iteration 3); scores still
    accumulate in f32.
    """
    hd = q.shape[-1]
    if use_pallas and not seq_shard:
        fn = (_decode_packed_pallas_single if single_launch
              else _decode_packed_pallas)
        with jax.named_scope("attend"):
            return fn(
                q, cache, logit_cap=logit_cap, quant=quant,
                extra_invalid_prefix=extra_invalid_prefix,
                interpret=interpret)
    with jax.named_scope("attend"):
        q = _quant_qk(q, quant)
    with jax.named_scope("kv_gather"):
        # gather in f32 and cast once: identical values (the dequants
        # compute in f32 either way; cast commutes with the pure data
        # movement), but ~1.6x faster on XLA CPU, where bf16 elementwise
        # lowers poorly
        k, v, valid = kvcache.gather_kv(cache, dtype=jnp.float32)
        k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    with jax.named_scope("attend"):
        if seq_shard:
            # keep head_dim sharded through the QK contraction: partial
            # score rows all-reduce (~40 MiB) instead of all-gathering the
            # entire dequantized K cache (~1 GiB/layer measured; §Perf
            # iteration 3)
            from jax.sharding import PartitionSpec as P
            wsc = jax.lax.with_sharding_constraint
            k = wsc(k, P(dp_axes, None, None, "model"))
            v = wsc(v, P(dp_axes, None, None, "model"))
            q = wsc(q, P(dp_axes, None, None, "model"))
        scores = _group_heads(q, k) / jnp.sqrt(float(hd))  # (B,Hkv,rep,1,T)
        m = valid[None, :]
        if extra_invalid_prefix is not None:
            pos = jnp.arange(k.shape[1])[None, :]
            m = m & (pos >= extra_invalid_prefix[:, None])
        p = _masked_softmax(scores, m[:, None, None, None], logit_cap)
        p = _quant_p(p, quant)
        return _apply_scores_v(p, v)


# ---------------------------------------------------------------------------
# Ring cache for sliding-window layers (gemma2 local, recurrentgemma)
# ---------------------------------------------------------------------------

class RingKVCache(NamedTuple):
    """8-bit BFP ring cache for local-attention layers.

    K per-token groups along hd; V committed in 32-token groups along the
    token dim (incremental grouping), residual kept raw.  Window must be a
    multiple of 32."""
    k_mant: jax.Array    # (B, W, n_kv, hd) int8
    k_exp: jax.Array     # (B, W, n_kv, hd//32) int8
    k_pos: jax.Array     # (W,) int32 — absolute position per slot (-1 empty)
    v_resid: jax.Array   # (B, 32, n_kv, hd) f32
    v_mant: jax.Array    # (B, W, n_kv, hd) int8
    v_exp: jax.Array     # (B, W//32, n_kv, hd) int8
    length: jax.Array    # () int32


def init_ring_cache(batch: int, n_kv: int, head_dim: int,
                    window: int) -> RingKVCache:
    if window % kvcache.GROUP != 0:
        raise ValueError("window must be a multiple of 32")
    z, i8 = jnp.zeros, jnp.int8
    return RingKVCache(
        k_mant=z((batch, window, n_kv, head_dim), i8),
        k_exp=z((batch, window, n_kv, head_dim // kvcache.GROUP), i8),
        k_pos=jnp.full((window,), -1, jnp.int32),
        v_resid=z((batch, kvcache.GROUP, n_kv, head_dim), jnp.float32),
        v_mant=z((batch, window, n_kv, head_dim), i8),
        v_exp=z((batch, window // kvcache.GROUP, n_kv, head_dim), i8),
        length=jnp.zeros((), jnp.int32))


def ring_prefill(cache: RingKVCache, k: jax.Array,
                 v: jax.Array) -> RingKVCache:
    """Build the ring from a prefill chunk (keeps the last ``window``)."""
    B, S, H, D = k.shape
    W = cache.k_mant.shape[1]
    G = kvcache.GROUP
    if S % G != 0:
        raise ValueError("prefill length must be a multiple of 32")
    toks = jnp.arange(max(0, S - W), S)
    slots = toks % W
    km, ke = kvcache._q_k(k[:, max(0, S - W):], 8)
    k_mant = cache.k_mant.at[:, slots].set(km)
    k_exp = cache.k_exp.at[:, slots].set(ke)
    k_pos = cache.k_pos.at[slots].set(toks)
    vm, ve = kvcache._q_v_group(v[:, max(0, S - W):], 8)
    v_mant = cache.v_mant.at[:, slots].set(vm)
    g_tok = toks.reshape(-1, G)[:, 0] // G
    v_exp = cache.v_exp.at[:, g_tok % (W // G)].set(ve)
    return cache._replace(k_mant=k_mant, k_exp=k_exp, k_pos=k_pos,
                          v_mant=v_mant, v_exp=v_exp,
                          length=jnp.asarray(S, jnp.int32))


def ring_append(cache: RingKVCache, k_new: jax.Array,
                v_new: jax.Array) -> RingKVCache:
    """Append one (B, n_kv, hd) token to the ring.

    V-group commits use ``kvcache.predicated_write`` (slab-level select +
    unconditional dynamic-update-slice) instead of a whole-buffer
    ``jnp.where`` so a donated / scan-carried ring mutates in place.
    """
    t = cache.length
    W = cache.k_mant.shape[1]
    G = kvcache.GROUP
    slot = t % W
    km, ke = kvcache._q_k(k_new[:, None], 8)
    k_mant = jax.lax.dynamic_update_slice_in_dim(cache.k_mant, km, slot, 1)
    k_exp = jax.lax.dynamic_update_slice_in_dim(cache.k_exp, ke, slot, 1)
    k_pos = jax.lax.dynamic_update_slice_in_dim(
        cache.k_pos, t[None], slot, 0)
    r = t % G
    v_resid = jax.lax.dynamic_update_slice_in_dim(
        cache.v_resid, v_new[:, None].astype(cache.v_resid.dtype), r, 1)
    completes = r == G - 1
    gm, ge = kvcache._q_v_group(v_resid, 8)
    gslot = (t // G) % (W // G)
    v_mant = kvcache.predicated_write(cache.v_mant, gm, completes,
                                      gslot * G)
    v_exp = kvcache.predicated_write(cache.v_exp, ge, completes, gslot)
    v_resid = jnp.where(completes, jnp.zeros_like(v_resid), v_resid)
    return cache._replace(k_mant=k_mant, k_exp=k_exp, k_pos=k_pos,
                          v_resid=v_resid, v_mant=v_mant, v_exp=v_exp,
                          length=t + 1)


def ring_decode_attention(q: jax.Array, cache: RingKVCache, *,
                          window: int, logit_cap: float = 0.0,
                          quant: Optional[QuantConfig] = None) -> jax.Array:
    """q: (B,1,H,hd) against the ring + residual V."""
    hd = q.shape[-1]
    G = kvcache.GROUP
    t = cache.length  # query position == number of cached tokens
    q = _quant_qk(q, quant)
    k = kvcache._dq_k(cache.k_mant, cache.k_exp, 8)        # (B,W,H,hd)
    valid = (cache.k_pos >= 0) & (cache.k_pos >= t - window) \
        & (cache.k_pos < t)
    scores = _group_heads(q, k) / jnp.sqrt(float(hd))
    p = _masked_softmax(scores, valid[None, None, None, None, :], logit_cap)
    p = _quant_p(p, quant)
    v = kvcache._dq_v_group(cache.v_mant, cache.v_exp, 8)
    # overlay the residual group (tokens >= (t//G)*G) at its ring slots
    r = t % G
    resid_valid = jnp.arange(G) < r
    resid = jnp.where(resid_valid[None, :, None, None],
                      cache.v_resid.astype(jnp.float32), 0.0)
    resid_q = bfp.bfp_fake_quant(resid, G, 8, "trunc", axis=1)
    gslot = (t // G) % (cache.v_mant.shape[1] // G)
    window_v = jax.lax.dynamic_slice_in_dim(v, gslot * G, G, 1)
    merged = jnp.where(resid_valid[None, :, None, None], resid_q, window_v)
    v = jax.lax.dynamic_update_slice_in_dim(v, merged, gslot * G, 1)
    return _apply_scores_v(p, v)


__all__ = ["attention_forward", "attention_eval_quant",
           "attention_prefill_pallas", "attention_decode_packed",
           "make_mask", "RingKVCache",
           "init_ring_cache", "ring_prefill", "ring_append",
           "ring_decode_attention", "compute_online_offsets"]
