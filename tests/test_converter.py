"""In-kernel FP->BFP converter + single-launch decode regression tier.

Four pins:
  * the grid-fused batched converter kernels (K per-token groups, V token
    groups, int4 nibble packing in VMEM) are bit-exact against the XLA
    quantize formulations they replace,
  * ``prefill_cache(use_pallas=True)`` — the single-launch region
    converter — builds a bit-identical packed cache,
  * the single-launch decode kernel and the legacy bulk-kernel +
    XLA-epilogue path both match the dense float32 decode oracle of
    ``kernels/ref.py`` within its stated ``DECODE_ATOL``,
  * the decode-step jaxpr contains no exponent re-layout op: the
    bulk-relative ``v_bulk_exp`` layout removed the per-step
    shift-and-pad concat that used to rebuild the whole exponent array.

Plus the region-seam equivalence of ``prefill_cache`` vs repeated
``append_token`` (token-32 init->bulk hand-off, local-ring wrap, last
partial V group).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.layers.attention as A
from repro.core import bfp, kvcache
from repro.kernels import ops
from repro.kernels import ref as kref

RNG = np.random.default_rng(7)


def _kv(B, S, H, hd):
    k = jnp.asarray(RNG.normal(size=(B, S, H, hd)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, S, H, hd)).astype(np.float32))
    return k, v


# ---------------------------------------------------------------------------
# Converter kernels vs the XLA quantize pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [4, 8])
def test_v_converter_kernel_bit_exact(bits):
    v = _kv(2, 160, 3, 64)[1] * 3
    m_x, e_x = ops.quantize_v_token_grouped_batched_xla(v, bits)
    m_k, e_k = ops.quantize_v_token_grouped_batched(v, bits)
    assert bool(jnp.all(m_x == m_k)) and bool(jnp.all(e_x == e_k))


def test_v_converter_kernel_packs_in_kernel():
    v = _kv(1, 128, 2, 64)[1]
    m_x, e_x = ops.quantize_v_token_grouped_batched_xla(v, 4)
    m_k, e_k = ops.quantize_v_token_grouped_batched(v, 4, pack=True)
    assert m_k.shape == (1, 64, 2, 64)  # token pairs packed 2/byte
    assert bool(jnp.all(bfp.pack_int4(m_x, axis=1) == m_k))
    assert bool(jnp.all(e_x == e_k))


def test_k_converter_kernel_bit_exact():
    k = _kv(2, 96, 2, 64)[0] * 2
    m_f, e_f = ops.bfp_quantize(k)          # flat Pallas converter
    m_b, e_b = ops.bfp_quantize_kv_batched(k)
    assert bool(jnp.all(m_f == m_b)) and bool(jnp.all(e_f == e_b))
    m4, e4 = bfp.bfp_quantize(k, 32, 4, axis=-1)
    m4p = bfp.pack_int4(m4.reshape(k.shape), axis=-1)
    m_bp, e_bp = ops.bfp_quantize_kv_batched(k, 4, pack=True)
    assert m_bp.shape == k.shape[:-1] + (k.shape[-1] // 2,)
    assert bool(jnp.all(m4p == m_bp)) and bool(jnp.all(e4 == e_bp))


@pytest.mark.parametrize("S", [32, 64, 96, 128, 256, 480])
def test_prefill_cache_converter_bit_identical(S):
    """The single-launch region converter == the XLA ``prefill_cache``
    on every packed leaf, across all region occupancies."""
    B, H, hd = 2, 2, 64
    k, v = _kv(B, S, H, hd)
    off = jnp.asarray(RNG.normal(size=(B, H, hd)).astype(np.float32)) * .1
    c = kvcache.init_cache(B, H, hd, max_seq=512)
    cx = kvcache.prefill_cache(c, k, v, off)
    cp = kvcache.prefill_cache(c, k, v, off, use_pallas=True)
    for name, a, b in zip(cx._fields, cx, cp):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_prefill_cache_converter_hd128():
    B, H, hd = 1, 2, 128
    k, v = _kv(B, 224, H, hd)
    c = kvcache.init_cache(B, H, hd, max_seq=256)
    cx = kvcache.prefill_cache(c, k, v)
    cp = kvcache.prefill_cache(c, k, v, use_pallas=True)
    for name, a, b in zip(cx._fields, cx, cp):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


# ---------------------------------------------------------------------------
# Single-launch and two-launch decode vs the dense oracle
# ---------------------------------------------------------------------------

def _build_cache(B, Hkv, hd, max_seq, S_pre, n_append):
    cache = kvcache.init_cache(B, Hkv, hd, max_seq)
    k, v = _kv(B, S_pre, Hkv, hd)
    cache = kvcache.prefill_cache(cache, k, v)
    app = jax.jit(kvcache.append_token)
    for _ in range(n_append):
        kn = jnp.asarray(RNG.normal(size=(B, Hkv, hd)).astype(np.float32))
        vn = jnp.asarray(RNG.normal(size=(B, Hkv, hd)).astype(np.float32))
        cache = app(cache, kn, vn)
    return cache


def _assert_decode_matches_ref(B, Hkv, H, hd, cache, cap=0.0, prefix=None):
    q = jnp.asarray(RNG.normal(size=(B, 1, H, hd)).astype(np.float32))
    pfx = None if prefix is None else jnp.asarray(prefix, jnp.int32)
    k, v, valid = kvcache.gather_kv(cache)
    want = np.asarray(kref.ref_attention_decode(
        q[:, 0], k, v, valid, logit_cap=cap, start=pfx))
    for single_launch in (True, False):
        f = jax.jit(lambda q, c, p: A.attention_decode_packed(
            q, c, logit_cap=cap, use_pallas=True,
            single_launch=single_launch, extra_invalid_prefix=p))
        got = np.asarray(f(q, cache, pfx))[:, 0]
        np.testing.assert_allclose(got, want, rtol=0, atol=kref.DECODE_ATOL,
                                   err_msg=f"single_launch={single_launch}")


@pytest.mark.parametrize("S_pre,n_append,cap,prefix",
                         [(128, 0, 0.0, None),   # bulk exactly one group
                          (128, 5, 0.0, None),   # residual active
                          (256, 37, 0.0, None),  # deep bulk + residual
                          (96, 0, 0.0, None),    # bulk empty
                          (64, 3, 0.0, None),    # local ring only
                          (32, 1, 0.0, None),    # init + one token
                          (256, 0, 30.0, None),  # logit softcap
                          (192, 70, 0.0, (0, 40)),   # left-pad prefix
                          (480, 31, 0.0, None)])     # near-capacity
def test_single_launch_decode_bit_exact_vs_merged(S_pre, n_append, cap,
                                                  prefix):
    """GQA (rep=2) shapes: the single-launch kernel and the bulk-kernel +
    XLA epilogue both match the dense oracle under jit (the production
    compilation context) across every region occupancy."""
    B, Hkv, H, hd = 2, 2, 4, 64
    cache = _build_cache(B, Hkv, hd, 512, S_pre, n_append)
    _assert_decode_matches_ref(B, Hkv, H, hd, cache, cap, prefix)


def test_single_launch_decode_rep1_one_ulp():
    """MHA (rep=1): the epilogue contraction is a GEMV."""
    B, Hkv, H, hd = 2, 2, 2, 64
    cache = _build_cache(B, Hkv, hd, 512, 256, 10)
    _assert_decode_matches_ref(B, Hkv, H, hd, cache)


def test_single_launch_decode_hd128_bit_exact():
    B, Hkv, H, hd = 1, 2, 8, 128
    cache = _build_cache(B, Hkv, hd, 256, 192, 17)
    _assert_decode_matches_ref(B, Hkv, H, hd, cache)


# ---------------------------------------------------------------------------
# Jaxpr regression: no exponent re-layout on the decode step
# ---------------------------------------------------------------------------

def _relayout_eqns(jaxpr, shape, acc):
    """Collect concat/pad/transpose/gather eqns producing int8 arrays of
    ``shape`` anywhere outside pallas_call bodies."""
    from jax._src import core as jcore
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue                   # in-kernel ops are the point
        if eqn.primitive.name in ("concatenate", "pad", "transpose",
                                  "gather"):
            for ov in eqn.outvars:
                aval = getattr(ov, "aval", None)
                if (aval is not None and tuple(aval.shape) == shape
                        and aval.dtype == jnp.int8):
                    acc.append(eqn.primitive.name)
        for val in eqn.params.values():
            vs = val if isinstance(val, (tuple, list)) else (val,)
            for x in vs:
                if isinstance(x, jcore.ClosedJaxpr):
                    _relayout_eqns(x.jaxpr, shape, acc)
                elif isinstance(x, jcore.Jaxpr):
                    _relayout_eqns(x, shape, acc)
    return acc


def test_decode_step_jaxpr_free_of_exponent_relayout():
    """The bulk-relative ``v_bulk_exp`` layout killed the per-step
    shift-and-pad concat: no concat/pad/transpose/gather may produce a
    v_bulk_exp-shaped int8 array in the decode-step jaxpr (kernel bodies
    excluded — the kernel *consumes* the exponents, it never re-lays
    them out)."""
    B, Hkv, H, hd = 2, 2, 4, 64
    cache = _build_cache(B, Hkv, hd, 512, 256, 0)
    q = jnp.asarray(RNG.normal(size=(B, 1, H, hd)).astype(np.float32))
    jaxpr = jax.make_jaxpr(
        lambda q, c: A.attention_decode_packed(q, c, use_pallas=True)
    )(q, cache)
    shape = tuple(cache.v_bulk_exp.shape)
    hits = _relayout_eqns(jaxpr.jaxpr, shape, [])
    assert not hits, f"exponent re-layout ops in decode jaxpr: {hits}"


# ---------------------------------------------------------------------------
# Region-seam equivalence: prefill_cache vs repeated append_token
# ---------------------------------------------------------------------------

def _append_from(cache, k, v, lo, hi):
    app = jax.jit(kvcache.append_token)
    for t in range(lo, hi):
        cache = app(cache, k[:, t], v[:, t])
    return cache


def _assert_caches_equal(c1, c2):
    for name, a, b in zip(c1._fields, c1, c2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


@pytest.mark.parametrize("total", [96, 128])
def test_seam_token32_init_to_bulk_handoff(total):
    """Appending across t=96 demotes token 32 (the first init->bulk
    hand-off): the demote-via-8-bit path must equal prefill's direct
    4-bit conversion (truncation composes exactly for power-of-two
    steps and the shared exponent is width-invariant)."""
    B, H, hd = 2, 2, 32
    k, v = _kv(B, total, H, hd)
    c_pre = kvcache.prefill_cache(kvcache.init_cache(B, H, hd, 256), k, v)
    c_app = _append_from(
        kvcache.prefill_cache(kvcache.init_cache(B, H, hd, 256),
                              k[:, :32], v[:, :32]), k, v, 32, total)
    _assert_caches_equal(c_pre, c_app)


def test_seam_local_ring_wrap():
    """Appends far enough that the 64-slot K ring wraps (t >= 160)."""
    B, H, hd = 2, 2, 32
    total = 224
    k, v = _kv(B, total, H, hd)
    c_pre = kvcache.prefill_cache(kvcache.init_cache(B, H, hd, 256), k, v)
    c_app = _append_from(
        kvcache.prefill_cache(kvcache.init_cache(B, H, hd, 256),
                              k[:, :64], v[:, :64]), k, v, 64, total)
    _assert_caches_equal(c_pre, c_app)


def test_seam_partial_last_group():
    """Two append-built caches reaching the same mid-group length from
    different prefill starts agree on every leaf, including the raw
    residual and the last committed (partially packed) V group."""
    B, H, hd = 1, 2, 32
    total = 203                        # r = 203 % 32 = 11
    k, v = _kv(B, total, H, hd)
    c_a = _append_from(
        kvcache.prefill_cache(kvcache.init_cache(B, H, hd, 256),
                              k[:, :64], v[:, :64]), k, v, 64, total)
    c_b = _append_from(
        kvcache.prefill_cache(kvcache.init_cache(B, H, hd, 256),
                              k[:, :96], v[:, :96]), k, v, 96, total)
    _assert_caches_equal(c_a, c_b)
    assert int(c_a.length) == total
    # and the gather agrees with the fake-quant reference at the seam
    kk, vv, valid = kvcache.gather_kv(c_a)
    assert int(valid.sum()) == total
    kr, vr = kvcache.fake_quant_kv(k, v, __import__(
        "repro.core.quant_config", fromlist=["KvQuantConfig"]
    ).KvQuantConfig(), length=total)
    np.testing.assert_allclose(np.asarray(kk[:, :total]), np.asarray(kr),
                               atol=2e-2)


def test_engine_pallas_pipeline_generates():
    """End-to-end: use_pallas_kernels=True now routes prefill-cache
    build + single-launch decode through the kernels inside the fused
    generation loop."""
    from repro.models.config import ModelConfig
    from repro.models.init import init_params
    from repro.quant.int4 import pack_params
    from repro.serving.engine import Engine, EngineConfig
    cfg = ModelConfig(name="t-pallas", family="dense", n_layers=2,
                      d_model=64, n_heads=2, n_kv_heads=1, head_dim=32,
                      d_ff=128, vocab_size=259, param_dtype="float32")
    params = pack_params(init_params(cfg, jax.random.PRNGKey(0)))
    eng = Engine(params, cfg, EngineConfig(max_seq=192, max_new_tokens=6,
                                           use_pallas_kernels=True))
    out = eng.generate(["hello kernel", "second prompt"])
    assert out["tokens"].shape == (2, 6)
    assert np.isfinite(out["tokens"]).all()
