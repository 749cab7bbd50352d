"""INT4 weight quantization (OmniQuant-lite)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, list_archs
from repro.layers.common import QuantizedWeight, weight_dequant
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
