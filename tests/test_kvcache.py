"""Packed asymmetric KV cache vs the position-mask fake-quant reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kvcache import (append_token, fake_quant_kv, gather_kv,
                                init_cache, prefill_cache, cache_bytes,
                                fp16_cache_bytes)
from repro.core.quant_config import KvQuantConfig
from repro.layers.attention import (init_ring_cache, ring_append,
                                    ring_prefill)
from repro.core import kvcache as kvmod


@pytest.fixture(scope="module")
def kv_data():
    rng = np.random.default_rng(0)
    B, S, H, D = 2, 256, 2, 64
    k = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
    return k, v


def test_prefill_matches_fake_quant(kv_data):
    k, v = kv_data
    B, S, H, D = k.shape
    c = init_cache(B, H, D, max_seq=512)
    c = prefill_cache(c, k, v)
    kk, vv, valid = gather_kv(c)
    kr, vr = fake_quant_kv(k, v, KvQuantConfig(), length=S)
    assert int(valid.sum()) == S
    np.testing.assert_allclose(np.asarray(kk[:, :S]), np.asarray(kr),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(vv[:, :S]), np.asarray(vr),
                               atol=1e-5)


def test_append_then_gather_matches_reference(kv_data):
    k, v = kv_data
    B, S, H, D = k.shape
    c = init_cache(B, H, D, max_seq=512)
    c = prefill_cache(c, k[:, :160], v[:, :160])
    app = jax.jit(append_token)
    for t in range(160, 233):  # crosses group boundaries + demotions
        c = app(c, k[:, t], v[:, t])
    kk, vv, valid = gather_kv(c)
    kr, vr = fake_quant_kv(k[:, :233], v[:, :233], KvQuantConfig(),
                           length=233)
    # residual group of V uses incremental conversion — compare exactly
    np.testing.assert_allclose(np.asarray(kk[:, :233]), np.asarray(kr),
                               atol=2e-2)
    np.testing.assert_allclose(np.asarray(vv[:, :233]), np.asarray(vr),
                               atol=2e-2)


def test_storage_reduction(kv_data):
    k, v = kv_data
    B, S, H, D = k.shape
    c = init_cache(B, H, D, max_seq=2048)
    frac = cache_bytes(c) / fp16_cache_bytes(B, H, D, 2048)
    # 4-bit bulk dominates at long context; fp32 resid + offsets overhead
    assert frac < 0.40, f"packed cache fraction {frac:.3f}"


def test_demotion_is_4bit(kv_data):
    """Tokens outside init+local must live in the packed 4-bit region."""
    k, v = kv_data
    B, S, H, D = k.shape
    c = init_cache(B, H, D, max_seq=512)
    c = prefill_cache(c, k, v)  # S=256 > 32+64
    bulk = np.asarray(c.k_bulk_mant[:, :S - 96])
    assert np.any(bulk != 0)
    kk, _, _ = gather_kv(c)
    # a mid-sequence token must show 4-bit-size quantization error
    mid_err = float(jnp.abs(kk[:, 100] - k[:, 100]).max())
    loc_err = float(jnp.abs(kk[:, S - 10] - k[:, S - 10]).max())
    assert mid_err > loc_err


def test_storage_fraction_formula():
    kv = KvQuantConfig()
    f4k = kv.storage_fraction(4096)
    # paper: 3.05x reduction => 32.8% at 4K (mantissa + ~1b overhead)
    assert 0.30 < f4k < 0.34
    flat = KvQuantConfig(asymmetric=False).storage_fraction(4096)
    assert flat == pytest.approx(5.0 / 16.0)  # paper's 68.75% reduction


def test_ring_cache_prefill_vs_append(kv_data):
    k, v = kv_data
    B, S, H, D = k.shape
    W = 128
    c1 = ring_prefill(init_ring_cache(B, H, D, W), k, v)
    c2 = init_ring_cache(B, H, D, W)
    app = jax.jit(ring_append)
    for t in range(S):
        c2 = app(c2, k[:, t], v[:, t])
    np.testing.assert_array_equal(np.asarray(c1.k_mant),
                                  np.asarray(c2.k_mant))
    np.testing.assert_array_equal(np.asarray(c1.k_pos),
                                  np.asarray(c2.k_pos))
    np.testing.assert_array_equal(np.asarray(c1.v_mant),
                                  np.asarray(c2.v_mant))


def test_v_residual_group_roundtrip():
    """Incremental V grouping: committing exactly at a group boundary."""
    rng = np.random.default_rng(1)
    B, H, D = 1, 1, 32
    k = jnp.asarray(rng.normal(size=(B, 160, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, 160, H, D)).astype(np.float32))
    c = init_cache(B, H, D, max_seq=256)
    c = prefill_cache(c, k[:, :128], v[:, :128])
    for t in range(128, 160):  # exactly one more group
        c = append_token(c, k[:, t], v[:, t])
    assert int(c.length) == 160
    _, vv, _ = gather_kv(c)
    vr = jnp.asarray(np.asarray(v[:, 128:160]))
    got = vv[:, 128:160]
    # 8-bit BFP error: step = 2^(E-6) ~ 0.03 for N(0,1) groups
    assert float(jnp.abs(got - vr).max()) < 0.05


def test_legacy_cache_ops_bit_identical():
    """The legacy select/scatter formulations (behind ``legacy=True``)
    and the predicated-write
    / overlay rewrites are pure data-movement variants: bit-identical
    caches and gathers across region boundaries (ring entry, demotion
    start, group commits, partial residual, full cache)."""
    rng = np.random.default_rng(3)
    B, H, D, S = 2, 2, 32, 256
    for prefill_len, extra in [(32, 0), (32, 65), (64, 33), (128, 95),
                               (224, 31), (256, 0)]:
        k = jnp.asarray(rng.normal(size=(B, prefill_len, H, D)
                                   ).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, prefill_len, H, D)
                                   ).astype(np.float32))
        c_new = prefill_cache(init_cache(B, H, D, S), k, v)
        c_old = c_new
        for _ in range(extra):
            kn = jnp.asarray(rng.normal(size=(B, H, D)).astype(np.float32))
            vn = jnp.asarray(rng.normal(size=(B, H, D)).astype(np.float32))
            c_new = append_token(c_new, kn, vn)
            c_old = append_token(c_old, kn, vn, legacy=True)
        for a, b in zip(jax.tree.leaves(c_new), jax.tree.leaves(c_old)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for dt in (jnp.float32, jnp.bfloat16):
            kn_, vn_, valn = gather_kv(c_new, dt)
            ko_, vo_, valo = gather_kv(c_old, dt, legacy=True)
            np.testing.assert_array_equal(np.asarray(kn_), np.asarray(ko_))
            np.testing.assert_array_equal(np.asarray(vn_), np.asarray(vo_))
            np.testing.assert_array_equal(np.asarray(valn),
                                          np.asarray(valo))
