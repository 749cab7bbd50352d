"""Attention paths: flash vs dense, eval-quant semantics, decode parity."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.layers.attention as A
from repro.core.quant_config import (KvQuantConfig, QuantConfig,
                                     SmoothingConfig, harmonia)

RNG = np.random.default_rng(0)


def _qkv(B=2, S=256, H=4, Hkv=2, hd=32):
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    return q, k, v, pos


@pytest.mark.parametrize("mask_kind,window,cap",
                         [("causal", 0, 0.0), ("local", 64, 0.0),
                          ("bidir", 0, 0.0), ("causal", 0, 30.0)])
def test_flash_matches_dense(mask_kind, window, cap):
    q, k, v, pos = _qkv()
    dense = A.attention_forward(q, k, v, pos, mask_kind=mask_kind,
                                window=window, logit_cap=cap)
    flash = A._flash_forward(q, k, v, pos, pos, mask_kind=mask_kind,
                             window=window, logit_cap=cap, k_valid=None,
                             q_chunk=64, kv_chunk=32)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(flash),
                               atol=2e-5)


def test_flash_grad_matches_dense():
    q, k, v, pos = _qkv(S=128)

    def loss(fn, q_):
        return jnp.sum(fn(q_) ** 2)
    gd = jax.grad(lambda q_: loss(
        lambda x: A.attention_forward(x, k, v, pos), q_))(q)
    gf = jax.grad(lambda q_: loss(
        lambda x: A._flash_forward(x, k, v, pos, pos, mask_kind="causal",
                                   window=0, logit_cap=0.0, k_valid=None,
                                   q_chunk=32, kv_chunk=32), q_))(q)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(gf), atol=1e-4)


def test_eval_quant_reduces_to_flat_when_symmetric():
    """asymmetric=False must equal flat KV fake-quant."""
    q, k, v, pos = _qkv(S=128)
    qc = QuantConfig(kv=KvQuantConfig(mantissa_bits=8,
                                      high_mantissa_bits=8,
                                      asymmetric=True),
                     smoothing=SmoothingConfig(offline=False, online=False))
    flat = dataclasses.replace(qc, kv=KvQuantConfig(
        mantissa_bits=8, high_mantissa_bits=8, asymmetric=False))
    a = A.attention_eval_quant(q, k, v, pos, qc)
    b = A.attention_eval_quant(q, k, v, pos, flat)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_eval_quant_asym_beats_naive_4bit():
    """Asymmetric 4-bit attention output should be closer to fp than
    flat 4-bit (the Fig. 8 effect at the attention level)."""
    q, k, v, pos = _qkv(S=256)
    fp = A.attention_forward(q, k, v, pos)
    no_smooth = SmoothingConfig(offline=False, online=False)
    naive = QuantConfig(kv=KvQuantConfig(mantissa_bits=4,
                                         asymmetric=False),
                        smoothing=no_smooth, quant_attention=True)
    asym = QuantConfig(kv=KvQuantConfig(mantissa_bits=4, asymmetric=True),
                       smoothing=no_smooth, quant_attention=True)
    e_naive = float(jnp.abs(A.attention_eval_quant(q, k, v, pos, naive)
                            - fp).mean())
    e_asym = float(jnp.abs(A.attention_eval_quant(q, k, v, pos, asym)
                           - fp).mean())
    assert e_asym < e_naive


def test_decode_packed_matches_eval_quant_early():
    """Within the first 96 tokens everything is 8-bit in both paths."""
    from repro.core import kvcache
    B, S, Hkv, hd = 1, 64, 2, 64
    H = 4
    q1 = jnp.asarray(RNG.normal(size=(B, 1, H, hd)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    c = kvcache.init_cache(B, Hkv, hd, max_seq=256)
    c = kvcache.prefill_cache(c, k, v)
    out = A.attention_decode_packed(q1, c)
    # reference: dense attention against 8-bit fake-quant K/V
    from repro.core import bfp
    kf = bfp.bfp_fake_quant(k, 32, 8, axis=-1)
    vf = bfp.bfp_fake_quant(v, 32, 8, axis=1)
    pos_q = jnp.full((B, 1), S, jnp.int32)
    pos_k = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    ref = A.attention_forward(q1, kf, vf, pos_q, mask_kind="causal",
                              kq_positions=pos_k)
    # decode path runs bf16 (dequantized mantissas are bf16-exact; the
    # unquantized test q loses bits in the cast) — tolerance reflects that
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-2)


# ---------------------------------------------------------------------------
# Pallas-backed serving paths (grid-fused kernels)
# ---------------------------------------------------------------------------

from repro.core import bfp, kvcache


def _decode_ref_f32(q, cache, logit_cap=0.0, prefix=None, quant=None):
    """f32 gather-everything reference for the packed decode: the cache
    dequantized into position order by ``gather_kv``, then q's and P's BFP
    (``quant``), masks and softmax as the decode applies them."""
    hd = q.shape[-1]
    k, v, valid = kvcache.gather_kv(cache, dtype=jnp.float32)
    q = A._quant_qk(q.astype(jnp.float32), quant)
    scores = A._group_heads(q, k) / jnp.sqrt(float(hd))
    m = valid[None, :]
    if prefix is not None:
        pos = jnp.arange(k.shape[1])[None, :]
        m = m & (pos >= prefix[:, None])
    p = A._masked_softmax(scores, m[:, None, None, None], logit_cap)
    p = A._quant_p(p, quant)
    return A._apply_scores_v(p, v)


def _build_cache(B, Hkv, hd, max_seq, S_pre, n_append):
    cache = kvcache.init_cache(B, Hkv, hd, max_seq)
    k = jnp.asarray(RNG.normal(size=(B, S_pre, Hkv, hd)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, S_pre, Hkv, hd)).astype(np.float32))
    cache = kvcache.prefill_cache(cache, k, v)
    for _ in range(n_append):
        kn = jnp.asarray(RNG.normal(size=(B, Hkv, hd)).astype(np.float32))
        vn = jnp.asarray(RNG.normal(size=(B, Hkv, hd)).astype(np.float32))
        cache = kvcache.append_token(cache, kn, vn)
    return cache


# (S_pre, n_append, logit_cap): the cache regions a decode step meets
_DECODE_REGION_CASES = [(128, 0, 0.0),    # bulk exactly at region edge
                        (128, 5, 0.0),    # residual group active
                        (256, 37, 0.0),   # deep bulk + residual
                        (96, 0, 0.0),     # bulk empty, window ragged
                        (64, 3, 0.0),     # local ring only
                        (32, 1, 0.0),     # init + one token
                        (256, 0, 30.0)]   # logit softcap


def _rel_err(out, ref):
    return float(jnp.abs(out - ref).max()) / float(jnp.abs(ref).max())


@pytest.mark.parametrize("S_pre,n_append,cap", _DECODE_REGION_CASES)
def test_decode_packed_pallas_matches_f32_reference(S_pre, n_append, cap):
    B, Hkv, H, hd = 2, 2, 4, 64
    cache = _build_cache(B, Hkv, hd, 512, S_pre, n_append)
    q = jnp.asarray(RNG.normal(size=(B, 1, H, hd)).astype(np.float32))
    out_p = A.attention_decode_packed(q, cache, logit_cap=cap,
                                      use_pallas=True)
    rel = _rel_err(out_p, _decode_ref_f32(q, cache, cap))
    assert rel < 1e-5, rel


def test_decode_packed_pallas_left_pad_prefix():
    B, Hkv, H, hd = 2, 2, 4, 64
    cache = _build_cache(B, Hkv, hd, 512, 192, 70)
    q = jnp.asarray(RNG.normal(size=(B, 1, H, hd)).astype(np.float32))
    prefix = jnp.asarray([0, 40], jnp.int32)
    out_p = A.attention_decode_packed(q, cache, extra_invalid_prefix=prefix,
                                      use_pallas=True)
    rel = _rel_err(out_p, _decode_ref_f32(q, cache, prefix=prefix))
    assert rel < 1e-5, rel


def test_decode_packed_pallas_close_to_xla_path():
    """The XLA path (dots in q's dtype, bf16 when serving) and the f32
    Pallas path agree to bf16 P resolution."""
    B, Hkv, H, hd = 2, 2, 4, 64
    cache = _build_cache(B, Hkv, hd, 512, 256, 10)
    q = jnp.asarray(RNG.normal(size=(B, 1, H, hd)).astype(np.float32))
    out_p = A.attention_decode_packed(q, cache, use_pallas=True)
    out_x = A.attention_decode_packed(q, cache, use_pallas=False)
    assert float(jnp.abs(out_p - out_x).max()) < 0.05


# The default XLA decode (use_pallas=False) contracts the packed cache in
# place.  Its dots take q and P in q's dtype: float32 here, so it differs
# from the float32 gather reference only in the order of float32 sums
# (the mantissas are exact integers, the 2^e steps exact), and the
# Pallas tests' relative 1e-5 applies.  A wrong region, mask or exponent
# is off by ~1e-2.
@pytest.mark.parametrize("Hkv,H", [(2, 2), (2, 6)], ids=["mha", "gqa"])
@pytest.mark.parametrize("S_pre,n_append,cap", _DECODE_REGION_CASES)
def test_decode_packed_xla_matches_f32_reference(S_pre, n_append, cap,
                                                 Hkv, H):
    B, hd = 2, 64
    cache = _build_cache(B, Hkv, hd, 512, S_pre, n_append)
    q = jnp.asarray(RNG.normal(size=(B, 1, H, hd)).astype(np.float32))
    out = jax.jit(lambda q, c: A.attention_decode_packed(
        q, c, logit_cap=cap))(q, cache)
    assert _rel_err(out, _decode_ref_f32(q, cache, cap)) < 1e-5


@pytest.mark.parametrize("Hkv,H", [(2, 2), (2, 4)], ids=["mha", "gqa"])
def test_decode_packed_xla_left_pad_prefix(Hkv, H):
    B, hd = 2, 64
    cache = _build_cache(B, Hkv, hd, 512, 192, 70)
    q = jnp.asarray(RNG.normal(size=(B, 1, H, hd)).astype(np.float32))
    prefix = jnp.asarray([0, 40], jnp.int32)
    out = A.attention_decode_packed(q, cache, extra_invalid_prefix=prefix)
    assert _rel_err(out, _decode_ref_f32(q, cache, prefix=prefix)) < 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_decode_packed_xla_quantized_scores(dtype):
    """With quant_attention, q and P are BFP8 on both sides: P's groups
    of 32 run along position order, as over the gathered cache.  BFP8
    values are exact in bf16, so the served bf16 dots (P cast to q's
    dtype) keep the float32 reference's tolerance too."""
    B, Hkv, H, hd = 2, 2, 4, 64
    quant = harmonia(4)
    assert quant.quant_attention
    cache = _build_cache(B, Hkv, hd, 512, 256, 10)
    q = jnp.asarray(RNG.normal(size=(B, 1, H, hd)).astype(np.float32)
                    ).astype(dtype)
    out = A.attention_decode_packed(q, cache, quant=quant)
    ref = _decode_ref_f32(q, cache, quant=quant)
    assert _rel_err(out, ref) < 1e-5
    # P's BFP8 acts: dropping it moves the output far beyond the tolerance
    assert _rel_err(out, _decode_ref_f32(q, cache)) > 1e-4


def _largest_float_array(hlo_text):
    sizes = [int(np.prod([int(d) for d in m.group(2).split(",")]))
             for m in re.finditer(r"\b(f64|f32|bf16|f16)\[([0-9,]+)\]",
                                  hlo_text)]
    return max(sizes, default=0)


def test_decode_packed_xla_builds_no_float_cache():
    """The optimized decode holds no float array as large as the cache
    dequantized into position order (B·max_seq·Hkv·hd); the gather
    reference does."""
    B, S, Hkv, H, hd = 2, 512, 2, 4, 64
    cache = jax.eval_shape(lambda: kvcache.init_cache(B, Hkv, hd, S))
    q = jax.ShapeDtypeStruct((B, 1, H, hd), jnp.bfloat16)
    prefix = jax.ShapeDtypeStruct((B,), jnp.int32)
    whole = B * S * Hkv * hd

    def largest(fn):
        return _largest_float_array(
            jax.jit(fn).lower(q, cache, prefix).compile().as_text())

    assert largest(lambda q, c, p: A.attention_decode_packed(
        q, c, quant=harmonia(4), extra_invalid_prefix=p)) < whole
    assert largest(lambda q, c, p: _decode_ref_f32(q, c, prefix=p)) >= whole


def test_prefill_pallas_matches_fakequant_forward():
    """The kernel path == attention_forward on pre-fake-quantized K/V
    (packed dequantization is exact), up to flash accumulation order."""
    B, S, H, Hkv, hd = 2, 128, 4, 2, 64
    q, k, v, pos = _qkv(B, S, H, Hkv, hd)
    out_k = A.attention_prefill_pallas(q, k, v)
    k_fq = bfp.bfp_fake_quant(k, 32, 8, "trunc", axis=-1)
    v_fq = bfp.bfp_fake_quant(v, 32, 8, "trunc", axis=1)
    out_r = A.attention_forward(q, k_fq, v_fq, pos)
    rel = (float(jnp.abs(out_k - out_r).max())
           / float(jnp.abs(out_r).max()))
    assert rel < 1e-5, rel


def test_prefill_pallas_gqa_quant_config():
    q, k, v, _ = _qkv(1, 96, 8, 2, 64)
    out = A.attention_prefill_pallas(q, k, v, quant=harmonia(4))
    assert out.shape == q.shape
    assert not bool(jnp.isnan(out).any())
