"""Ahead-of-time compiles of the served path for one TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and refuses what the chip would
refuse (unsupported ops, programs that do not fit its memory).  These
tests compile Llama-3.1-8B's prefill, decode step and one fused
``generate_loop`` chunk at full published width from abstract INT4-packed
parameters, and check that each program's arguments plus temporaries fit
one chip.  They also compile a packed ``qlinear`` at decode widths and
check that it reads near the packed INT4 bytes and builds no float copy of
the weight.  Nothing runs, so they say nothing about speed or results.

The topology is described in a fixture, never at import: only one process
may load the TPU library, and every test worker imports this file.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core.quant_config import harmonia
from repro.layers.common import QuantizedWeight, qlinear
from repro.models import lm
from repro.models.init import abstract_params
from repro.quant.int4 import abstract_pack_params

CFG = get_arch("harmonia-llama3.1-8b").config
B, PROMPT, MAX_SEQ, CHUNK = 8, 512, 2048, 32
HBM_BYTES = 16e9          # one v5e chip (Google Cloud, "TPU v5e")
QUANT = harmonia(4)       # the engine's default recipe


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the cache
    # but cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _on(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.fixture(scope="module")
def shapes(one_chip):
    params = abstract_pack_params(abstract_params(CFG))
    toks = jax.ShapeDtypeStruct((B, PROMPT), jnp.int32)
    logits, caches = jax.eval_shape(
        lambda p, t: lm.prefill(p, CFG, t, max_seq=MAX_SEQ, quant=QUANT),
        params, toks)
    row = jax.ShapeDtypeStruct((B,), jnp.int32)
    return {name: _on(x, one_chip) for name, x in dict(
        params=params, toks=toks, caches=caches, row=row,
        key=jax.ShapeDtypeStruct((2,), jnp.uint32),
        finished=jax.ShapeDtypeStruct((B,), jnp.bool_)).items()}


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, (mem.argument_size_in_bytes,
                              mem.temp_size_in_bytes)
    return mem


def test_prefill_compiles_for_v5e(shapes):
    f = jax.jit(partial(lm.prefill, cfg=CFG, max_seq=MAX_SEQ, quant=QUANT))
    _fits(f.lower(shapes["params"], tokens=shapes["toks"]).compile())


def test_decode_step_compiles_for_v5e(shapes):
    f = jax.jit(lambda p, t, c, pp: lm.decode_step(
        p, CFG, t, c, quant=QUANT, pad_prefix=pp), donate_argnums=2)
    mem = _fits(f.lower(shapes["params"], shapes["row"], shapes["caches"],
                        shapes["row"]).compile())
    # the donated cache is updated in place, not copied
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(shapes["caches"]))
    assert mem.alias_size_in_bytes >= cache_bytes


def test_generate_loop_chunk_compiles_for_v5e(shapes):
    f = jax.jit(lambda p, t, c, pp, k, fin: lm.generate_loop(
        p, CFG, c, num_steps=CHUNK, tok0=t, key=k, finished=fin,
        quant=QUANT, pad_prefix=pp, eos_id=258), donate_argnums=2)
    _fits(f.lower(shapes["params"], shapes["row"], shapes["caches"],
                  shapes["row"], shapes["key"],
                  shapes["finished"]).compile())


@pytest.mark.parametrize("in_dim,out_dim,rows", [
    (6144, 24576, 8),      # StarCoder2-15B MLP up, batch 8
    (24576, 6144, 8),      # StarCoder2-15B MLP down
    (4096, 102400, 4),     # DeepSeek-LLM-7B head, batch 4
])
def test_packed_qlinear_reads_packed_bytes_on_v5e(one_chip, in_dim, out_dim,
                                                  rows):
    """A decode-width packed linear moves a few times its packed nibbles and
    keeps no weight-sized float temporary (a dequantize-then-dot form moves
    about 69 times and keeps 16 times the packed bytes)."""
    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    x = on((rows, in_dim), jnp.bfloat16)
    qw = QuantizedWeight(on((in_dim // 2, out_dim), jnp.int8),
                         on((in_dim // 128, out_dim), jnp.float32))
    compiled = jax.jit(partial(qlinear, quant=QUANT)).lower(x, qw).compile()
    packed = in_dim * out_dim // 2
    assert compiled.cost_analysis()["bytes accessed"] <= 8 * packed
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * packed
