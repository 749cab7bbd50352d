"""Sharding rules: PartitionSpecs for every assigned arch (no devices
needed — specs are pure metadata) + debug-mesh end-to-end jit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, get_arch
from repro.distributed.sharding import (batch_pspec, cache_pspecs,
                                        param_pspecs)
from repro.models.init import abstract_params
from repro.quant.int4 import abstract_pack_params


class FakeMesh:
    """Mesh stand-in: sharding-rule functions only read .shape."""
    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


MESH = FakeMesh(data=16, model=16)
MESH_MP = FakeMesh(pod=2, data=16, model=16)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_param_pspecs_cover_tree(arch):
    cfg = get_arch(arch).config
    ap = abstract_params(cfg)
    specs = param_pspecs(cfg, ap, MESH)
    leaves_p = jax.tree.leaves(ap)
    leaves_s = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves_p) == len(leaves_s)
    for leaf, spec in zip(leaves_p, leaves_s):
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            assert leaf.shape[dim] % MESH.shape[ax] == 0, \
                f"{arch}: {leaf.shape} dim {dim} not divisible by {ax}"


@pytest.mark.parametrize("arch", ["qwen2.5-32b", "llama4-scout-17b-a16e"])
def test_packed_params_inherit_rules(arch):
    cfg = get_arch(arch).config
    ap = abstract_pack_params(abstract_params(cfg))
    specs = param_pspecs(cfg, ap, MESH)
    # expert stacks shard on the expert axis under EP
    if cfg.n_experts:
        s = specs["blocks"]["attn"]["w_gate"]
        gate_spec = s.packed if hasattr(s, "packed") else s
        assert "model" in tuple(gate_spec)


def test_moe_expert_parallel():
    cfg = get_arch("phi3.5-moe-42b-a6.6b").config
    ap = abstract_params(cfg)
    specs = param_pspecs(cfg, ap, MESH)
    g = specs["blocks"]["attn"]["w_gate"]
    # (L, E, d, ff): expert axis sharded
    assert tuple(g) [1] == "model"


def test_batch_pspec():
    sp = batch_pspec(MESH, 256)
    assert "data" in str(sp) and "pod" not in str(sp)
    mp = batch_pspec(MESH_MP, 256)
    assert "data" in str(mp) and "pod" in str(mp)
    assert tuple(batch_pspec(MESH, 1)) == ()


def test_cache_pspecs_shard_batch_and_tail():
    from functools import partial
    from repro.models import lm
    cfg = get_arch("deepseek-7b").smoke
    caches = jax.eval_shape(partial(lm.init_decode_caches, cfg, 128, 128))
    specs = cache_pspecs(caches, MESH, 128)
    k_spec = specs["scan"]["attn"].k_bulk_mant
    assert "data" in str(k_spec) or ("data",) in tuple(k_spec)


def _axes(entry) -> tuple:
    """A PartitionSpec entry as a tuple of mesh-axis names: jax 0.9 stores
    a one-axis tuple such as ``("data",)`` as the bare name ``"data"``."""
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


def test_cache_pspecs_overlay_slab_layout():
    """Field-aware specs on the PR 2 overlay/slab cache layout: batch at
    the scan-stacked axis 2, kv-heads (divisible) on model for every
    packed region incl. the 4-bit k/v bulk, shared counters and ring
    positions replicated."""
    from functools import partial
    from repro.models import lm
    # gemma2 smoke alternates local_attn (ring cache) and attn (packed)
    cfg = get_arch("gemma2-2b").config  # n_kv_heads=4: divisible by 16?
    mesh = FakeMesh(data=4, model=4)
    caches = jax.eval_shape(partial(lm.init_decode_caches, cfg, 16, 8192))
    specs = cache_pspecs(caches, mesh, 16)
    attn = specs["scan"]["attn"]
    for name in ("k_init_mant", "k_bulk_mant", "v_bulk_mant",
                 "v_local_exp"):
        s = tuple(getattr(attn, name))
        assert _axes(s[2]) == ("data",), (name, s)   # batch under the stack
        assert "model" in s, (name, s)           # kv-heads sharded
        assert s[3] is None, (name, s)           # token axis never split
    assert tuple(attn.length) == (None, None)    # shared counter
    ring = specs["scan"]["local_attn"]
    assert all(a is None for a in tuple(ring.k_pos))
    assert tuple(specs["_pos"]) == ()


def test_cache_pspecs_gqa_head_dim_fallback():
    """kv-heads not divisible by model (GQA) -> mantissa slabs fall back
    to head_dim sharding; exponent leaves whose trailing dim is hd//32
    degrade to replication rather than erroring."""
    from functools import partial
    from repro.models import lm
    cfg = get_arch("gemma2-2b").smoke          # n_kv_heads=1, head_dim=32
    mesh = FakeMesh(data=2, model=2)
    caches = jax.eval_shape(partial(lm.init_decode_caches, cfg, 4, 128))
    specs = cache_pspecs(caches, mesh, 4)
    attn = specs["scan"]["attn"]
    assert tuple(attn.k_init_mant)[-1] == "model"      # hd=32 % 2 == 0
    assert "model" not in tuple(attn.k_init_exp)       # hd//32=1: replicate


def test_divisibility_degrades_to_replication():
    """Non-divisible dims must degrade to replication, never error or
    pad: whisper's 51866 vocab against a model axis that divides neither
    vocab nor d_model leaves the embedding fully replicated."""
    cfg = get_arch("whisper-large-v3").config   # vocab 51866, d_model 1280
    ap = abstract_params(cfg)
    mesh = FakeMesh(data=2, model=48)           # 51866 % 48, 1280 % 48 != 0
    specs = param_pspecs(cfg, ap, mesh)
    assert tuple(specs["embed"]) == (), specs["embed"]
    # under the production mesh the vocab still doesn't divide 16 but the
    # d_model axis does -> the documented d-shard fallback, not an error
    specs16 = param_pspecs(cfg, ap, MESH)
    emb = tuple(specs16["embed"])
    assert 51866 % 16 != 0 and "model" in emb and emb[0] is None, emb


@pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >= 4 devices: run under XLA_FLAGS="
           "--xla_force_host_platform_device_count=8 (multidevice tier) "
           "or the dryrun sweep")
def test_debug_mesh_end_to_end():
    """Real 4-device jit on a forced-multi-device subprocess-free path."""
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(2, 2)
    x = jnp.arange(16.0).reshape(4, 4)
    y = jax.jit(lambda a: a * 2,
                in_shardings=jax.NamedSharding(mesh, P("data", "model"))
                )(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x) * 2)


def test_make_debug_mesh_clear_error_when_underprovisioned():
    """make_debug_mesh must fail loudly with the forced-host recipe in
    the message (not a bare device-count assert) so the multi-device
    tier's skip reasons stay actionable."""
    from repro.launch.mesh import make_debug_mesh, mesh_available
    need = len(jax.devices()) + 1
    assert not mesh_available(need, 1)
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        make_debug_mesh(need, 1)
