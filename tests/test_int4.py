"""INT4 weight quantization (OmniQuant-lite)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, list_archs
from repro.core import bfp
from repro.core.quant_config import harmonia
from repro.layers.common import QuantizedWeight, qlinear, weight_dequant
from repro.models.config import ModelConfig
from repro.models.init import init_packed_params, init_params
from repro.quant.int4 import (fake_quant_params, fake_quant_weight,
                              pack_params, quantize_weight)


def test_fake_matches_packed():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(256, 32)).astype(np.float32))
    fq = fake_quant_weight(w, 128, search_clip=False)
    qw = quantize_weight(w, 128)
    deq = weight_dequant(qw, jnp.float32)
    np.testing.assert_allclose(np.asarray(fq), np.asarray(deq), atol=1e-6)


def test_clip_search_no_worse():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_t(3, size=(256, 16)).astype(np.float32))
    e_plain = float(jnp.mean((w - fake_quant_weight(w, 128, False)) ** 2))
    e_clip = float(jnp.mean((w - fake_quant_weight(w, 128, True)) ** 2))
    assert e_clip <= e_plain + 1e-9


def test_pack_params_tree():
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=128,
                      n_heads=2, n_kv_heads=1, head_dim=64, d_ff=256,
                      vocab_size=64, param_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    packed = pack_params(params)
    attn = packed["blocks"]["attn"]
    assert isinstance(attn["wq"], QuantizedWeight)
    assert attn["wq"].packed.dtype == jnp.int8
    # stacked layer axis preserved
    assert attn["wq"].packed.shape == (2, 64, 128)
    # norms stay fp
    assert not isinstance(attn["ln1"], QuantizedWeight)
    # embeddings stay fp
    assert not isinstance(packed["embed"], QuantizedWeight)


def test_quant_error_reasonable():
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(512, 64)).astype(np.float32)) * 0.02
    fq = fake_quant_weight(w)
    rel = float(jnp.abs(w - fq).mean() / jnp.abs(w).mean())
    # int4 symmetric g128 on gaussians: step = absmax/7 ~ 0.43 sigma,
    # E|err| ~ step/4 ~ 0.11 sigma vs E|w| = 0.8 sigma
    assert rel < 0.15


@pytest.mark.parametrize("arch", sorted(list_archs()))
def test_init_packed_params_equals_pack_of_init(arch):
    """The layer-by-layer serving initializer draws the same weights from
    the same seed as the whole-tree pair it replaces, bit for bit."""
    cfg = get_arch(arch).smoke
    want = pack_params(init_params(cfg, jax.random.PRNGKey(3)))
    got = init_packed_params(cfg, jax.random.PRNGKey(3))
    w_leaves, w_def = jax.tree_util.tree_flatten_with_path(want)
    g_leaves, g_def = jax.tree_util.tree_flatten_with_path(got)
    assert w_def == g_def
    for (path, a), (_, b) in zip(w_leaves, g_leaves):
        assert a.shape == b.shape and a.dtype == b.dtype, path
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("bias,quantize_input", [(False, True), (True, True),
                                                 (False, False)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("lead", [(3,), (2, 3)])
@pytest.mark.parametrize("in_dim", [128, 512])
def test_packed_qlinear_matches_float32_dequant_product(in_dim, lead, dtype,
                                                        bias, quantize_input):
    """Packed ``qlinear`` (nibbles contracted per group, scales applied to
    the float32 partial sums) equals BFP(x) @ weight_dequant(w) computed in
    float32.  Tolerance: 1e-5 of sum |x||w| + |b| (float32 summation order),
    plus 2^-8 of |y| for bf16 activations (the output's rounding).  In
    bf16 it is also no further from that product than the form that
    dequantizes the weight to bf16 first."""
    rng = np.random.default_rng(in_dim + len(lead))
    out_dim = 64
    qw = quantize_weight(
        jnp.asarray(rng.normal(size=(in_dim, out_dim)), jnp.float32) * 0.05)
    x = jnp.asarray(rng.normal(size=lead + (in_dim,)), dtype)
    b = jnp.asarray(rng.normal(size=(out_dim,)), jnp.float32) * 0.1 \
        if bias else jnp.zeros((out_dim,), jnp.float32)
    quant = harmonia(4)
    got = qlinear(x, qw, quant, bias=b if bias else None,
                  quantize_input=quantize_input)
    assert got.shape == lead + (out_dim,) and got.dtype == dtype

    xq = bfp.bfp_fake_quant(x, quant.group_size, quant.act_mantissa_bits,
                            quant.rounding) if quantize_input else x
    xf = xq.astype(jnp.float32)
    wf = weight_dequant(qw, jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    ref = np.asarray(jnp.einsum("...i,io->...o", xf, wf, precision=hi) + b)
    mag = np.asarray(jnp.einsum("...i,io->...o", jnp.abs(xf), jnp.abs(wf),
                                precision=hi) + jnp.abs(b))
    tol = 1e-5 * mag
    if dtype == jnp.bfloat16:
        tol = tol + 2.0 ** -8 * np.abs(ref)
    err = np.abs(np.asarray(got, np.float32) - ref)
    assert (err <= tol).all(), float((err - tol).max())

    if dtype == jnp.bfloat16:
        old = jnp.einsum("...i,io->...o", xq, weight_dequant(qw, dtype))
        old = old + b.astype(dtype)
        err_old = np.abs(np.asarray(old, np.float32) - ref)
        assert np.sqrt((err ** 2).mean()) <= np.sqrt((err_old ** 2).mean())
