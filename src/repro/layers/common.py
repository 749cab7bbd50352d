"""Common layers: norms, activations, BFP/INT4-aware linear.

Every linear in the framework funnels through ``qlinear`` so the paper's
technique (BFP-quantized activations feeding INT4 weights — the hardware's
M8W4 mode) is applied uniformly, and so the packed-weight serving path and
the fp training path share one code site.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro.core import bfp
from repro.core.quant_config import QuantConfig


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6,
             zero_centered: bool = False) -> jax.Array:
    """RMSNorm in fp32 (gemma uses (1 + scale) — ``zero_centered``)."""
    with jax.named_scope("norm"):
        dt = x.dtype
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        xf = xf * jax.lax.rsqrt(var + eps)
        w = (1.0 + scale.astype(jnp.float32)) if zero_centered \
            else scale.astype(jnp.float32)
        return (xf * w).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    with jax.named_scope("norm"):
        dt = x.dtype
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        xf = (xf - mu) * jax.lax.rsqrt(var + eps)
        return (xf * scale.astype(jnp.float32)
                + bias.astype(jnp.float32)).astype(dt)


def activation(x: jax.Array, kind: str) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    if kind == "relu":
        return jax.nn.relu(x)
    raise ValueError(f"unknown activation {kind!r}")


def softcap(x: jax.Array, cap: float) -> jax.Array:
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    if cap <= 0:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# ---------------------------------------------------------------------------
# INT4 packed weights
# ---------------------------------------------------------------------------

class QuantizedWeight(NamedTuple):
    """Symmetric INT4 weight, group_size along the contraction (in) dim.

    packed: (in_dim // 2, out_dim) int8 — two 4-bit values per byte along in.
    scale:  (in_dim // group, out_dim) float32.
    """
    packed: jax.Array
    scale: jax.Array

    @property
    def in_dim(self) -> int:
        return self.packed.shape[0] * 2

    @property
    def out_dim(self) -> int:
        return self.packed.shape[-1]


def weight_dequant(qw: QuantizedWeight, dtype=jnp.bfloat16) -> jax.Array:
    """Supports leading stack dims: packed (..., in/2, out), scale
    (..., in/128, out) -> (..., in, out)."""
    mant = bfp.unpack_int4(qw.packed, axis=-2).astype(jnp.float32)
    in_dim = mant.shape[-2]
    out_dim = mant.shape[-1]
    ngroups = qw.scale.shape[-2]
    g = in_dim // ngroups
    lead = mant.shape[:-2]
    mant = mant.reshape(lead + (ngroups, g, out_dim))
    w = mant * qw.scale[..., :, None, :]
    return w.reshape(lead + (in_dim, out_dim)).astype(dtype)


WeightLike = Union[jax.Array, QuantizedWeight]


# ---------------------------------------------------------------------------
# The universal linear
# ---------------------------------------------------------------------------

def qlinear(x: jax.Array, w: WeightLike, quant: Optional[QuantConfig] = None,
            bias: Optional[jax.Array] = None,
            quantize_input: bool = True) -> jax.Array:
    """y = BFP(x) @ W[int4] + b — the hardware's M8W4 path.

    * ``quant`` None or disabled -> plain matmul.
    * activation BFP: group 32 along the contraction dim (per token).
    * ``w`` may be a raw array (training / fp eval; weight fake-quant is
      applied offline by ``repro.quant.int4.fake_quant_params``) or a packed
      ``QuantizedWeight`` (serving; dequantized on the fly — on TPU the
      Pallas ``bfp_matmul`` kernel fuses this; the XLA path here is the
      portable fallback with identical numerics).

    Its work runs under the named scope ``qlinear``, split into
    ``act_quant``, ``weight_dequant`` and ``matmul`` (bias included).
    """
    with jax.named_scope("qlinear"):
        if quant is not None and quant.enabled and quant.quant_linear_acts \
                and quantize_input:
            with jax.named_scope("act_quant"):
                x = bfp.bfp_fake_quant(x, quant.group_size,
                                       quant.act_mantissa_bits,
                                       quant.rounding, axis=-1, ste=quant.ste)
        if isinstance(w, QuantizedWeight):
            with jax.named_scope("weight_dequant"):
                w = weight_dequant(w, x.dtype)
        with jax.named_scope("matmul"):
            y = jnp.einsum("...i,io->...o", x, w)
            if bias is not None:
                y = y + bias.astype(y.dtype)
        return y


def embed_lookup(tokens: jax.Array, table: jax.Array,
                 scale: float = 1.0) -> jax.Array:
    e = jnp.take(table, tokens, axis=0)
    if scale != 1.0:
        e = e * jnp.asarray(scale, e.dtype)
    return e


__all__ = ["rms_norm", "layer_norm", "activation", "softcap",
           "QuantizedWeight", "weight_dequant", "WeightLike", "qlinear",
           "embed_lookup"]
