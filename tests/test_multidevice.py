"""Multi-device tier: mesh-sharded tensor-parallel serving on a real
(2, 2) debug mesh.

This tier needs >= 8 devices and is therefore env-guarded: under the
plain single-device tier-1 run every test here *skips* with a reason
(never error-collects).  Run it locally with

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m pytest -q tests/test_multidevice.py

(the flag must be set before the first jax import — pytest imports jax
during collection, so it has to come from the environment, not from a
fixture).  CI runs it as the dedicated ``multidevice`` job.

What is pinned here:
  * the sharded fused ``generate_loop`` is bit-exact (greedy and seeded
    temperature) against the single-device engine across model families,
    including GQA (kv-heads not divisible by the model axis -> head_dim /
    replication degradation paths),
  * donation under sharding: the compiled sharded continuation scan
    aliases every per-device cache byte in place and allocates no second
    cache copy (the mesh mirror of tests/test_donation.py),
  * the continuous-batching row swap stays sharded (ServeLoop results
    identical to the single-device loop, cache leaves still sharded and
    donated afterwards).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch.mesh import make_debug_mesh, mesh_available
from repro.models.config import ModelConfig
from repro.models.init import init_params
from repro.quant.int4 import pack_params
from repro.serving.engine import Engine, EngineConfig, ServeLoop

pytestmark = pytest.mark.skipif(
    not mesh_available(2, 2),     # every test here builds a 2x2 mesh
    reason="multi-device tier needs >= 4 devices: run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")

DENSE_GQA = ModelConfig(name="md-gqa", family="dense", n_layers=2,
                        d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
                        d_ff=256, vocab_size=259, param_dtype="float32")

MAX_SEQ, M = 160, 8

# dense-gqa: kv-heads divide the model axis (the clean TP layout);
# deepseek-mha: 3 heads/kv-heads — nothing divides, degradation paths;
# gemma2: local+global rings, softcaps, kv=1 (head_dim fallback).
ARCHS = ["dense-gqa", "deepseek-mha", "gemma2-local-gqa"]


def _cfg(name):
    if name == "dense-gqa":
        return DENSE_GQA
    if name == "deepseek-mha":
        return get_arch("deepseek-7b").smoke
    return get_arch("gemma2-2b").smoke


_PARAMS = {}


def _params(name):
    if name not in _PARAMS:
        _PARAMS[name] = pack_params(init_params(_cfg(name),
                                                jax.random.PRNGKey(0)))
    return _PARAMS[name]


def _engine(name, mesh, sampler="greedy"):
    return Engine(_params(name), _cfg(name),
                  EngineConfig(max_seq=MAX_SEQ, max_new_tokens=M,
                               sampler=sampler, temperature=0.8, seed=3,
                               mesh=mesh))


PROMPTS = ["the shared exponent", "block floating point is"]


@pytest.mark.parametrize("name", ARCHS)
@pytest.mark.parametrize("sampler", ["greedy", "temperature"])
def test_sharded_generate_bit_exact(name, sampler):
    """2x2-mesh fused loop == single-device fused loop, token for token
    (greedy and seeded temperature) under the full harmonia BFP recipe,
    incl. a GQA config.  Temperature exactness leans on the engine's
    sampler fence (replicated-RNG subgraph): an unfenced batch-sharded
    categorical draws different threefry bits than a single device and
    flips tokens with top-2 gaps of O(1)."""
    mesh = make_debug_mesh(2, 2)
    ref = _engine(name, None, sampler).generate(PROMPTS)
    out = _engine(name, mesh, sampler).generate(PROMPTS)
    np.testing.assert_array_equal(np.asarray(ref["tokens"]),
                                  np.asarray(out["tokens"]))
    assert ref["texts"] == out["texts"]


def test_cache_and_params_actually_sharded():
    """The mesh path really distributes state: param and cache leaves are
    NamedSharding-placed with addressable shards smaller than the global
    shape (not replication dressed up as sharding)."""
    mesh = make_debug_mesh(2, 2)
    eng = _engine("dense-gqa", mesh)
    toks, _ = eng._prepare(PROMPTS)
    _, caches = eng.prefill(toks)
    wq = eng.params["blocks"]["attn"]["wq"]
    wq_arr = wq.packed if hasattr(wq, "packed") else wq
    assert "model" in str(wq_arr.sharding.spec)
    assert wq_arr.addressable_shards[0].data.size < wq_arr.size
    kb = caches["scan"]["attn"].k_bulk_mant
    assert "model" in str(kb.sharding.spec)
    assert kb.addressable_shards[0].data.size < kb.size
    # shared counters stay replicated
    assert np.prod(caches["_pos"].sharding.shard_shape(
        caches["_pos"].shape)) == caches["_pos"].size


@pytest.mark.parametrize("name", ARCHS)
def test_init_packed_params_born_sharded(name):
    """Under a mesh the layer-by-layer initializer writes every packed
    leaf straight into its ``param_pspecs`` sharding, with the same
    values as the single-device tree."""
    from repro.distributed.sharding import param_pspecs
    from repro.models.init import init_packed_params
    mesh = make_debug_mesh(2, 2)
    cfg = _cfg(name)
    got = init_packed_params(cfg, jax.random.PRNGKey(0), mesh=mesh)
    want = _params(name)
    specs = jax.tree.leaves(param_pspecs(cfg, want, mesh),
                            is_leaf=lambda x: isinstance(
                                x, jax.sharding.PartitionSpec))
    for g, w, spec in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                          specs):
        assert g.sharding.mesh.shape == mesh.shape
        assert g.sharding.spec == spec
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _per_device_bytes(tree) -> int:
    return sum(l.addressable_shards[0].data.size * l.dtype.itemsize
               for l in jax.tree.leaves(tree))


def test_sharded_continuation_donation_no_second_cache_copy():
    """Mesh mirror of tests/test_donation.py: the compiled sharded
    continuation scan aliases the whole per-device cache shard in place,
    and its temp allocation never reaches the *global* cache size — i.e.
    the cache is not gathered to a replicated copy mid-scan."""
    mesh = make_debug_mesh(2, 2)
    eng = _engine("dense-gqa", mesh)
    toks, pp = eng._prepare(PROMPTS)
    _, caches = eng.prefill(toks)
    B = toks.shape[0]
    tok = jnp.zeros((B,), jnp.int32)
    fin = jnp.zeros((B,), bool)
    key = jax.random.PRNGKey(0)
    fn = eng._fused(4, start=False, batch=B)
    ma = fn.lower(eng.params, tok, caches, pp, key,
                  fin).compile().memory_analysis()
    per_dev = _per_device_bytes(caches)
    glob = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(caches))
    assert per_dev < glob                      # sharding is real
    assert ma.alias_size_in_bytes >= per_dev, (
        f"sharded loop aliases {ma.alias_size_in_bytes} < per-device "
        f"cache {per_dev} bytes — donation broke under sharding")
    assert ma.temp_size_in_bytes < glob, (
        f"temps {ma.temp_size_in_bytes} >= global cache {glob} bytes — "
        f"the sharded cache is being gathered to a replicated copy")


def test_sharded_donated_cache_is_consumed():
    mesh = make_debug_mesh(2, 2)
    eng = _engine("dense-gqa", mesh)
    toks, pp = eng._prepare(PROMPTS)
    _, caches = eng.prefill(toks)
    tok = jnp.zeros((toks.shape[0],), jnp.int32)
    _, new_caches = eng.decode(tok, caches, pp)
    jax.block_until_ready(jax.tree.leaves(new_caches))
    with pytest.raises(RuntimeError, match="deleted"):
        _ = jax.tree.leaves(caches["scan"]["attn"])[0] + 0


def test_serveloop_sharded_row_swap_matches_single_device():
    """Continuous batching with the sharded scatter_cache_rows produces
    the same texts as the single-device loop, with real swaps."""
    mesh = make_debug_mesh(2, 2)
    prompts = ["first", "second longer prompt", "third", "fourth"]
    budgets = [4, 90, 12, 12]
    ref_loop = ServeLoop(_engine("dense-gqa", None), batch_size=2,
                         max_steps=32)
    ref = ref_loop.serve(prompts, max_new_tokens=budgets)
    loop = ServeLoop(_engine("dense-gqa", mesh), batch_size=2,
                     max_steps=32)
    res = loop.serve(prompts, max_new_tokens=budgets)
    assert res == ref
    assert loop.stats["swaps"] >= 1, loop.stats
