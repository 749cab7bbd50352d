"""Per-kernel interpret-mode sweeps vs the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bfp
from repro.kernels import ops, ref
from repro.kernels.bfp_attention import (bfp_attention_decode_kernel,
                                         bfp_attention_prefill_kernel)
from repro.kernels.bfp_matmul import bfp_matmul_kernel, choose_dataflow
from repro.kernels.bfp_quant import bfp_quantize_kernel
from repro.quant.int4 import quantize_weight

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("shape", [(32, 64), (64, 256), (128, 96)])
@pytest.mark.parametrize("m_bits", [4, 8])
def test_quantize_kernel_bit_exact(shape, m_bits):
    x = jnp.asarray(RNG.normal(size=shape).astype(np.float32)) * 5
    mk, ek = bfp_quantize_kernel(x, mantissa_bits=m_bits, block_m=32,
                                 block_k=64, interpret=True)
    mr, er = ref.ref_bfp_quantize(x, m_bits)
    assert jnp.all(mk == mr) and jnp.all(ek == er)


@pytest.mark.parametrize("mkn", [(32, 128, 32), (64, 256, 96),
                                 (16, 384, 64)])
@pytest.mark.parametrize("dataflow", ["act_stationary",
                                      "weight_stationary"])
def test_matmul_kernel_vs_oracle(mkn, dataflow):
    M, K, N = mkn
    a = jnp.asarray(RNG.normal(size=(M, K)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(K, N)).astype(np.float32)) * 0.05
    am, ae = ref.ref_bfp_quantize(a)
    qw = quantize_weight(w, 128)
    oracle = ref.ref_bfp_matmul(am, ae, qw.packed, qw.scale)
    out = bfp_matmul_kernel(am, ae, qw.packed, qw.scale, dataflow=dataflow,
                            block_m=16, block_n=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-4, atol=1e-4)


def test_matmul_int_path():
    M, K, N = 32, 256, 48
    a = jnp.asarray(RNG.normal(size=(M, K)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(K, N)).astype(np.float32)) * 0.05
    am, ae = ref.ref_bfp_quantize(a)
    qw = quantize_weight(w, 128)
    out = bfp_matmul_kernel(am, ae, qw.packed, qw.scale, int_path=True,
                            block_m=16, block_n=16, interpret=True)
    oracle = ref.ref_bfp_matmul_int(am, ae, qw.packed, qw.scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal,cap,window", [(True, 0.0, 0),
                                               (True, 50.0, 0),
                                               (True, 0.0, 64),
                                               (False, 0.0, 0)])
def test_attention_prefill_kernel(causal, cap, window):
    S, hd = 128, 64
    q = jnp.asarray(RNG.normal(size=(S, hd)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(S, hd)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(S, hd)).astype(np.float32))
    km, ke = ref.ref_bfp_quantize(k)
    vm, ve = ops.quantize_v_token_grouped(v)
    o_k = bfp_attention_prefill_kernel(q, km, ke, vm, ve, causal=causal,
                                       logit_cap=cap, window=window,
                                       block_q=32, block_s=32,
                                       interpret=True)
    o_r = ref.ref_bfp_attention_prefill(q, km, ke, vm, ve, causal=causal,
                                        logit_cap=cap, window=window)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-4)


@pytest.mark.parametrize("valid_len", [1, 100, 256])
def test_attention_decode_kernel(valid_len):
    S, hd, rep = 256, 64, 4
    q = jnp.asarray(RNG.normal(size=(rep, hd)).astype(np.float32))
    kb = jnp.asarray(RNG.normal(size=(S, hd)).astype(np.float32))
    vb = jnp.asarray(RNG.normal(size=(S, hd)).astype(np.float32))
    km4, ke4 = bfp.bfp_quantize(kb, 32, 4, axis=-1)
    km4p = bfp.pack_int4(km4.reshape(S, hd), axis=-1)
    vm4, ve4 = bfp.bfp_quantize(vb, 32, 4, axis=0)
    vm4 = jnp.moveaxis(vm4, (0, 1, 2), (2, 0, 1)).reshape(S, hd)
    vm4p = bfp.pack_int4(vm4, axis=0)
    o_k, m_k, l_k = bfp_attention_decode_kernel(
        q, km4p, ke4, vm4p, ve4.T, valid_len, block_s=64, interpret=True)
    o_r, m_r, l_r = ref.ref_bfp_decode_bulk(q, km4p, ke4, vm4p, ve4.T,
                                            valid_len)
    np.testing.assert_allclose(np.asarray(o_k / l_k),
                               np.asarray(o_r / l_r[:, None]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(m_k[:, 0]), np.asarray(m_r),
                               atol=1e-6)


def test_batched_wrappers_gqa():
    B, S, H, Hkv, hd = 2, 64, 4, 2, 64
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    km, ke = ops.bfp_quantize(k)
    vm = jnp.stack([jnp.stack([ops.quantize_v_token_grouped(v[b, :, h])[0]
                               for h in range(Hkv)], 1) for b in range(B)])
    ve = jnp.stack([jnp.stack([ops.quantize_v_token_grouped(v[b, :, h])[1]
                               for h in range(Hkv)], 1) for b in range(B)])
    o = ops.bfp_attention_prefill(q, km, ke, vm, ve, interpret=True)
    assert o.shape == (B, S, H, hd)
    assert not bool(jnp.isnan(o).any())


def test_dataflow_choice_crossover():
    assert choose_dataflow(16, 4096, 4096) == "act_stationary"
    assert choose_dataflow(8192, 4096, 4096) == "weight_stationary"


def test_dataflow_crossover_as_function_of_m():
    """Regression-pin the weight<->activation-stationary crossover vs M
    (N=K=4096, bm=bn=128).  The EMA model sawtooths at tile boundaries
    (ceil-division re-read terms; DESIGN.md §2): weight-stationary first
    wins just past a full M tile, act-stationary recovers a few rows
    later, and weight-stationary wins permanently once its N*K advantage
    exceeds the sawtooth amplitude."""
    N = K = 4096
    # act-stationary strictly below one M tile
    assert all(choose_dataflow(M, N, K) == "act_stationary"
               for M in (1, 16, 64, 128))
    # first flip exactly at the tile boundary, recovery at M=133
    assert choose_dataflow(129, N, K) == "weight_stationary"
    assert choose_dataflow(133, N, K) == "act_stationary"
    # permanently weight-stationary at large M
    assert all(choose_dataflow(M, N, K) == "weight_stationary"
               for M in (4096, 5000, 8192, 16384))
    # K-split makes both orders re-read both operands -> tie -> ws
    assert choose_dataflow(16, N, K, bk=512) == "weight_stationary"
    assert choose_dataflow(8192, N, K, bk=512) == "weight_stationary"


def test_bfp_linear_end_to_end():
    x = jnp.asarray(RNG.normal(size=(4, 8, 256)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(256, 64)).astype(np.float32)) * 0.05
    qw = quantize_weight(w, 128)
    out = ops.bfp_linear(x, qw.packed, qw.scale, interpret=True)
    from repro.layers.common import weight_dequant
    x_fq = bfp.bfp_fake_quant(x, 32, 8)
    expect = x_fq @ weight_dequant(qw, jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Grid-fused batched kernels vs the legacy per-head vmap towers
# ---------------------------------------------------------------------------

def _pack_attention_inputs(B, S, H, Hkv, hd):
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    km, ke = ops.bfp_quantize(k)
    vm, ve = ops.quantize_v_token_grouped_batched(v)
    return q, km, ke, vm, ve


@pytest.mark.parametrize("shape", [(2, 128, 4, 2),    # GQA rep=2
                                   (1, 64, 8, 2),     # rep=4
                                   (3, 96, 2, 2),     # ragged S, rep=1
                                   (2, 160, 6, 3)])   # ragged S, rep=2
def test_prefill_fused_matches_legacy_bit_exact(shape):
    """Same tile sizes => same flash accumulation order => bit-exact."""
    B, S, H, Hkv = shape
    q, km, ke, vm, ve = _pack_attention_inputs(B, S, H, Hkv, 64)
    o_fused = ops.bfp_attention_prefill(q, km, ke, vm, ve,
                                        block_q=32, block_s=32)
    o_legacy = ops.bfp_attention_prefill(q, km, ke, vm, ve, legacy=True,
                                         block_q=32, block_s=32)
    assert o_fused.shape == (B, S, H, 64)
    np.testing.assert_array_equal(np.asarray(o_fused), np.asarray(o_legacy))


@pytest.mark.parametrize("kw", [dict(causal=True, window=64),
                                dict(causal=False),
                                dict(logit_cap=30.0)])
def test_prefill_fused_matches_legacy_variants(kw):
    q, km, ke, vm, ve = _pack_attention_inputs(2, 128, 4, 2, 64)
    o_fused = ops.bfp_attention_prefill(q, km, ke, vm, ve,
                                        block_q=32, block_s=32, **kw)
    o_legacy = ops.bfp_attention_prefill(q, km, ke, vm, ve, legacy=True,
                                         block_q=32, block_s=32, **kw)
    np.testing.assert_array_equal(np.asarray(o_fused), np.asarray(o_legacy))


def test_prefill_fused_default_blocks_close_to_legacy():
    """Different tile sizes (512 fused vs 128 legacy) change the flash
    accumulation order only: <= 1e-5 relative."""
    q, km, ke, vm, ve = _pack_attention_inputs(2, 256, 4, 4, 64)
    o_fused = ops.bfp_attention_prefill(q, km, ke, vm, ve)
    o_legacy = ops.bfp_attention_prefill(q, km, ke, vm, ve, legacy=True)
    rel = (float(jnp.abs(o_fused - o_legacy).max())
           / float(jnp.abs(o_legacy).max()))
    assert rel < 1e-5


def test_prefill_fused_vs_oracle_per_head():
    B, S, H, Hkv, hd = 2, 96, 4, 2, 64
    q, km, ke, vm, ve = _pack_attention_inputs(B, S, H, Hkv, hd)
    o = ops.bfp_attention_prefill(q, km, ke, vm, ve)
    rep = H // Hkv
    for b in range(B):
        for h in range(H):
            g = h // rep
            o_r = ref.ref_bfp_attention_prefill(
                q[b, :, h], km[b, :, g], ke[b, :, g], vm[b, :, g],
                ve[b, :, g])
            np.testing.assert_allclose(np.asarray(o[b, :, h]),
                                       np.asarray(o_r), atol=1e-4)


def _pack_bulk_inputs(B, S, Hkv, hd):
    kb = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    vb = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    km4, ke4 = bfp.bfp_quantize(kb, 32, 4, axis=-1)
    km4 = bfp.pack_int4(km4.reshape(B, S, Hkv, hd), axis=-1)
    vm4, ve4 = bfp.bfp_quantize(vb, 32, 4, axis=1)
    vm4 = bfp.pack_int4(jnp.moveaxis(vm4.reshape(B, Hkv, hd, S), -1, 1),
                        axis=1)
    ve4 = jnp.moveaxis(ve4, -1, 1)
    return km4, ke4, vm4, ve4


@pytest.mark.parametrize("valid_len", [1, 100, 256])
@pytest.mark.parametrize("BHkvH", [(2, 2, 4), (1, 2, 8)])
def test_decode_fused_matches_legacy_bit_exact(valid_len, BHkvH):
    B, Hkv, H = BHkvH
    S, hd = 256, 64
    q = jnp.asarray(RNG.normal(size=(B, H, hd)).astype(np.float32))
    km4, ke4, vm4, ve4 = _pack_bulk_inputs(B, S, Hkv, hd)
    vl = jnp.asarray(valid_len, jnp.int32)
    t_f = ops.bfp_attention_decode_bulk(q, km4, ke4, vm4, ve4, vl,
                                        block_s=64)
    t_l = ops.bfp_attention_decode_bulk(q, km4, ke4, vm4, ve4, vl,
                                        legacy=True, block_s=64)
    for a, b in zip(t_f, t_l):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_fused_start_masking():
    """Per-row left-pad starts mask exactly like a NEG_INF prefix."""
    B, S, Hkv, H, hd = 2, 256, 2, 4, 64
    q = jnp.asarray(RNG.normal(size=(B, H, hd)).astype(np.float32))
    km4, ke4, vm4, ve4 = _pack_bulk_inputs(B, S, Hkv, hd)
    vl = jnp.asarray(200, jnp.int32)
    start = jnp.asarray([0, 48], jnp.int32)
    o, m, l = ops.bfp_attention_decode_bulk(q, km4, ke4, vm4, ve4, vl,
                                            start=start, block_s=64)
    # reference: dequantize and compute the masked flash triple per row
    for b in range(B):
        k = ref.dequant_act(
            bfp.unpack_int4(km4[b], axis=-1).reshape(S, Hkv * hd),
            ke4[b].reshape(S, Hkv * hd // 32), 4).reshape(S, Hkv, hd)
        vum = bfp.unpack_int4(vm4[b], axis=0)            # (S, Hkv, hd)
        step = bfp.pow2(ve4[b].astype(jnp.float32) - 2.0)
        v = (vum.astype(jnp.float32).reshape(S // 32, 32, Hkv, hd)
             * step[:, None]).reshape(S, Hkv, hd)
        pos = np.arange(S)
        valid = (pos >= int(start[b])) & (pos < int(vl))
        for h in range(H):
            g = h // (H // Hkv)
            s = (np.asarray(q[b, h]) @ np.asarray(k[:, g]).T
                 / np.sqrt(float(hd)))
            s = np.where(valid, s, -np.inf)
            m_r = s.max()
            p = np.where(valid, np.exp(s - m_r), 0.0)
            o_r = p @ np.asarray(v[:, g])
            np.testing.assert_allclose(np.asarray(o[b, h] / l[b, h]),
                                       o_r / p.sum(), atol=1e-5)
            np.testing.assert_allclose(float(m[b, h, 0]), m_r, atol=1e-6)


def test_decode_fused_logit_cap_matches_reference():
    B, S, Hkv, H, hd = 1, 128, 2, 2, 64
    q = jnp.asarray(RNG.normal(size=(B, H, hd)).astype(np.float32))
    km4, ke4, vm4, ve4 = _pack_bulk_inputs(B, S, Hkv, hd)
    vl = jnp.asarray(128, jnp.int32)
    cap = 20.0
    o, m, l = ops.bfp_attention_decode_bulk(q, km4, ke4, vm4, ve4, vl,
                                            logit_cap=cap, block_s=64)
    o_u, m_u, l_u = ops.bfp_attention_decode_bulk(q, km4, ke4, vm4, ve4,
                                                  vl, block_s=64)
    # capped scores differ from uncapped ones
    assert not np.allclose(np.asarray(o / l), np.asarray(o_u / l_u))


# ---------------------------------------------------------------------------
# K-blocked GEMM + ragged padding
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mkn,bk", [((32, 256, 48), 128),
                                    ((64, 512, 96), 256),
                                    ((40, 384, 72), 128)])   # ragged M/N
def test_matmul_kblocked_vs_oracle(mkn, bk):
    M, K, N = mkn
    a = jnp.asarray(RNG.normal(size=(M, K)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(K, N)).astype(np.float32)) * 0.05
    am, ae = ref.ref_bfp_quantize(a)
    qw = quantize_weight(w, 128)
    oracle = ref.ref_bfp_matmul(am, ae, qw.packed, qw.scale)
    out = bfp_matmul_kernel(am, ae, qw.packed, qw.scale, block_m=32,
                            block_n=32, block_k=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-4, atol=1e-4)


def test_matmul_ragged_padding_keeps_tiling():
    """Ragged M/N no longer degrade to whole-operand tiles: result equals
    the oracle with proper bm/bn tiling."""
    M, K, N = 50, 256, 70
    a = jnp.asarray(RNG.normal(size=(M, K)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(K, N)).astype(np.float32)) * 0.05
    am, ae = ref.ref_bfp_quantize(a)
    qw = quantize_weight(w, 128)
    oracle = ref.ref_bfp_matmul(am, ae, qw.packed, qw.scale)
    for dataflow in ("act_stationary", "weight_stationary"):
        out = bfp_matmul_kernel(am, ae, qw.packed, qw.scale, block_m=16,
                                block_n=32, dataflow=dataflow,
                                interpret=True)
        assert out.shape == (M, N)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                                   rtol=1e-4, atol=1e-4)


def test_matmul_kblock_rejects_int_path():
    am = jnp.zeros((16, 256), jnp.int8)
    ae = jnp.zeros((16, 8), jnp.int8)
    wp = jnp.zeros((128, 16), jnp.int8)
    ws = jnp.zeros((2, 16), jnp.float32)
    with pytest.raises(ValueError):
        bfp_matmul_kernel(am, ae, wp, ws, int_path=True, block_k=128,
                          interpret=True)


# ---------------------------------------------------------------------------
# Causal tile skipping
# ---------------------------------------------------------------------------

def test_prefill_tile_counts():
    from repro.kernels.bfp_attention import prefill_tile_counts
    # S=2048, 512-tiles: lower triangle of a 4x4 tile grid
    assert prefill_tile_counts(2048, 512, 512) == (10, 16)
    # non-causal never skips
    assert prefill_tile_counts(2048, 512, 512, causal=False) == (16, 16)
    # sliding window drops below-diagonal tiles too
    live_w, total = prefill_tile_counts(2048, 256, 256, window=256)
    assert total == 64 and live_w < 36  # < plain-causal live count
    # single-tile grids can't skip
    assert prefill_tile_counts(512, 512, 512) == (1, 1)


def test_tile_skip_is_a_real_branch():
    """The causal guard must be a cond whose skip arm runs no dots."""
    from benchmarks.kernels_micro import verify_tile_skip_guard
    assert verify_tile_skip_guard()
