"""OmniQuant-lite INT4 weight quantization (group 128, symmetric).

The paper realizes weights with OmniQuant [54] (INT4, group size 128).
Full OmniQuant learns clipping + equivalent transformations; the lite
version here does the part that matters for a systems reproduction:
per-group symmetric scales with a small grid search over clipping ratios
minimizing reconstruction MSE (the "learnable weight clipping" objective
evaluated on a grid instead of by gradient descent — deterministic,
dependency-free, and within ~0.1 PPL of the learned version at 4 bits for
small models).

APIs:
  * ``quantize_weight``      — (in, out) fp -> QuantizedWeight (packed)
  * ``fake_quant_weight``    — quantize->dequantize (accuracy eval path)
  * ``fake_quant_params``    — map over a model tree (linear weights only)
  * ``pack_params``          — model tree -> packed QuantizedWeight leaves
                               (serving / dry-run path, real 4-bit storage)
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core import bfp
from repro.layers.common import QuantizedWeight

DEFAULT_GROUP = 128
CLIP_GRID = (1.0, 0.95, 0.9, 0.85, 0.8)
INT4_MAX = 7.0

# model-tree keys that are linear weights quantized to INT4.  Embeddings,
# norms, routers, SSM recurrence params and biases stay in fp (as in the
# paper's setup: OmniQuant quantizes the transformer linear layers).
QUANTIZABLE_KEYS = frozenset({
    "wq", "wk", "wv", "wo", "wq_x", "wk_x", "wv_x", "wo_x",
    "w_gate", "w_up", "w_down",
    "w_shared_gate", "w_shared_up", "w_shared_down",
    "w_in", "w_out", "w_in_x", "w_in_gate",
    "lm_head",
})


def _group_scales(w: jax.Array, group: int, clip: float) -> jax.Array:
    """w: (in, out) -> scales (in//group, out)."""
    gin = w.reshape(w.shape[0] // group, group, -1)
    absmax = jnp.max(jnp.abs(gin), axis=1)
    return jnp.maximum(absmax * clip / INT4_MAX, 1e-8)


def _quant_deq(w: jax.Array, group: int, clip: float):
    scales = _group_scales(w, group, clip)
    gin = w.reshape(w.shape[0] // group, group, -1)
    q = jnp.clip(jnp.round(gin / scales[:, None]), -INT4_MAX, INT4_MAX)
    deq = (q * scales[:, None]).reshape(w.shape)
    return q, scales, deq


@partial(jax.jit, static_argnames=("group",))
def _best_clip(w: jax.Array, group: int = DEFAULT_GROUP):
    """Grid-search the clipping ratio per tensor by reconstruction MSE."""
    errs = []
    for c in CLIP_GRID:
        _, _, deq = _quant_deq(w.astype(jnp.float32), group, c)
        errs.append(jnp.mean(jnp.square(w.astype(jnp.float32) - deq)))
    return jnp.argmin(jnp.stack(errs))


def fake_quant_weight(w: jax.Array, group: int = DEFAULT_GROUP,
                      search_clip: bool = True) -> jax.Array:
    """Quantize->dequantize an (in, out) weight (pads ragged in-dims)."""
    orig_in = w.shape[0]
    pad = (-orig_in) % group
    wf = jnp.pad(w.astype(jnp.float32), ((0, pad), (0, 0)))
    if search_clip:
        idx = _best_clip(wf, group)
        deqs = jnp.stack([_quant_deq(wf, group, c)[2] for c in CLIP_GRID])
        deq = deqs[idx]
    else:
        _, _, deq = _quant_deq(wf, group, 1.0)
    return deq[:orig_in].astype(w.dtype)


# Output columns quantize independently, so a weight is packed in blocks
# of columns holding at most this many elements: run op by op (the
# serving initializer), a (d_model, vocab) head then needs a block's f32
# temporaries, not the whole matrix's several times over.
_QUANT_BLOCK_ELEMS = 1 << 26


def quantize_weight(w: jax.Array, group: int = DEFAULT_GROUP,
                    clip: float = 1.0) -> QuantizedWeight:
    """Pack to real INT4 storage (in-dim must be even; group-divisible)."""
    if w.shape[0] % group != 0:
        raise ValueError(f"in_dim {w.shape[0]} not divisible by {group}")
    cols = max(1, _QUANT_BLOCK_ELEMS // w.shape[0])
    packed, scales = [], []
    for j in range(0, w.shape[1], cols):
        blk = w[:, j:j + cols]
        q, sc = _quant_deq(blk.astype(jnp.float32), group, clip)[:2]
        packed.append(bfp.pack_int4(q.reshape(blk.shape).astype(jnp.int8),
                                    axis=0))
        scales.append(sc.astype(jnp.float32))
        del blk, q, sc
    if len(packed) == 1:
        return QuantizedWeight(packed=packed[0], scale=scales[0])
    return QuantizedWeight(packed=jnp.concatenate(packed, axis=1),
                           scale=jnp.concatenate(scales, axis=1))


def _is_quantizable(path: tuple, leaf) -> bool:
    if not hasattr(leaf, "ndim") or leaf.ndim < 2:
        return False
    key = None
    for p in reversed(path):
        name = getattr(p, "key", None) or getattr(p, "name", None)
        if isinstance(name, str):
            key = name
            break
    return key in QUANTIZABLE_KEYS


def fake_quant_params(params: Dict, group: int = DEFAULT_GROUP,
                      search_clip: bool = True) -> Dict:
    """Offline weight fake-quant over a model tree (eval path)."""
    def f(path, leaf):
        if not _is_quantizable(path, leaf):
            return leaf
        if leaf.ndim == 2:
            return fake_quant_weight(leaf, group, search_clip)
        # stacked blocks: (layers..., in, out) — vmap over leading axes
        flat = leaf.reshape((-1,) + leaf.shape[-2:])
        out = jax.vmap(lambda w: fake_quant_weight(w, group, search_clip))(
            flat)
        return out.reshape(leaf.shape)
    return jax.tree_util.tree_map_with_path(f, params)


def pack_params(params: Dict, group: int = DEFAULT_GROUP) -> Dict:
    """Model tree -> packed INT4 leaves (serving / dry-run path).

    Weights whose in-dim is not group-divisible stay fp (rare: none of the
    assigned configs hit this for transformer projections)."""
    def f(path, leaf):
        if not _is_quantizable(path, leaf) or leaf.shape[-2] % group:
            return leaf
        if leaf.ndim == 2:
            return quantize_weight(leaf, group)
        flat = leaf.reshape((-1,) + leaf.shape[-2:])
        qw = jax.vmap(lambda w: quantize_weight(w, group))(flat)
        lead = leaf.shape[:-2]
        return QuantizedWeight(
            packed=qw.packed.reshape(lead + qw.packed.shape[1:]),
            scale=qw.scale.reshape(lead + qw.scale.shape[1:]))
    return jax.tree_util.tree_map_with_path(f, params)


def abstract_pack_params(abstract_tree: Dict,
                         group: int = DEFAULT_GROUP) -> Dict:
    """ShapeDtypeStruct tree version of ``pack_params`` (dry-run)."""
    return jax.eval_shape(lambda t: pack_params(t, group), abstract_tree)


__all__ = ["quantize_weight", "fake_quant_weight", "fake_quant_params",
           "pack_params", "abstract_pack_params", "QUANTIZABLE_KEYS",
           "DEFAULT_GROUP"]
