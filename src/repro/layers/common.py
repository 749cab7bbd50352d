"""Common layers: norms, activations, BFP/INT4-aware linear.

Every linear in the framework funnels through ``qlinear`` so the paper's
technique (BFP-quantized activations feeding INT4 weights — the hardware's
M8W4 mode) is applied uniformly, and so the packed-weight serving path and
the fp training path share one code site.
"""
from __future__ import annotations

import math
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

# Rows (all of x's leading dims) up to which ``qlinear`` multiplies packed
# INT4 weights without dequantizing them.  On a v5e the per-group form is
# 10-25x faster at 4-8 rows and 2-3x faster at 1024; from 2048 rows one dot
# over the dequantized weight wins at some widths (chip readings in PERF.md).
PACKED_DOT_MAX_ROWS = 1024


def qlinear(x: jax.Array, w: WeightLike, quant: Optional[QuantConfig] = None,
            bias: Optional[jax.Array] = None,
            quantize_input: bool = True) -> jax.Array:
    """y = BFP(x) @ W[int4] + b — the hardware's M8W4 path.

    * ``quant`` None or disabled -> plain matmul.
    * activation BFP: group 32 along the contraction dim (per token).
    * ``w`` may be a raw array (training / fp eval; weight fake-quant is
      applied offline by ``repro.quant.int4.fake_quant_params``) or a packed
      ``QuantizedWeight`` (serving).  With at most ``PACKED_DOT_MAX_ROWS``
      rows (decode, short prefills) the packed weight is never dequantized:
      its nibbles are contracted per 128-group with the matching
      activations into float32 partial sums, and the group scales are
      applied to those sums (``_packed_linear``).  No float weight reaches
      memory: the dot reads INT4 nibbles.  The products of BFP8 activations
      and INT4 values are exact in float32, so this rounds less than a dot
      over a bf16 weight.  With more rows the weight is dequantized once
      (``weight_dequant``) and multiplied in one dot, which the MXU runs
      faster than per-group partials.

    Its work runs under the named scope ``qlinear``, split into
    ``act_quant``, ``weight_dequant`` (nibbles to floats) and ``matmul``
    (dot, group scales and bias).
    """
    with jax.named_scope("qlinear"):
        if quant is not None and quant.enabled and quant.quant_linear_acts \
                and quantize_input:
            with jax.named_scope("act_quant"):
                x = bfp.bfp_fake_quant(x, quant.group_size,
                                       quant.act_mantissa_bits,
                                       quant.rounding, axis=-1, ste=quant.ste)
        if isinstance(w, QuantizedWeight):
            if math.prod(x.shape[:-1]) <= PACKED_DOT_MAX_ROWS:
                return _packed_linear(x, w, bias)
            with jax.named_scope("weight_dequant"):
                w = weight_dequant(w, x.dtype)
        with jax.named_scope("matmul"):
            y = jnp.einsum("...i,io->...o", x, w)
            if bias is not None:
                y = y + bias.astype(y.dtype)
        return y


def _packed_linear(x: jax.Array, qw: QuantizedWeight,
                   bias: Optional[jax.Array]) -> jax.Array:
    """x @ weight_dequant(qw) + bias without building the float weight."""
    ngroups, out_dim = qw.scale.shape
    half = qw.in_dim // ngroups // 2
    with jax.named_scope("weight_dequant"):
        # (G, g/2, out, 2) INT4 values, [..., 0] the low nibble (the even
        # index along in), sign-extended by the shifts.  Through int4 the
        # split fuses with the layer scan's weight slice and writes int4
        # nibbles, which the dot reads; a split fused into the dot makes
        # the scan copy each layer's packed weight first, slower on v5e.
        # (A bitcast of the bytes to int4 pairs gives wrong products there.)
        p = qw.packed.reshape(ngroups, half, out_dim)
        lo = (jnp.left_shift(p, 4) >> 4).astype(jnp.int4)
        hi = (p >> 4).astype(jnp.int4)
        w = jnp.stack([lo, hi], axis=-1).astype(x.dtype)
    with jax.named_scope("matmul"):
        xs = x.reshape(x.shape[:-1] + (ngroups, half, 2))
        part = jnp.einsum("...gkn,gkon->...go", xs, w,
                          preferred_element_type=jnp.float32)
        y = jnp.einsum("...go,go->...o", part, qw.scale)
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        return y.astype(x.dtype)


def embed_lookup(tokens: jax.Array, table: jax.Array,
                 scale: float = 1.0) -> jax.Array:
    e = jnp.take(table, tokens, axis=0)
    if scale != 1.0:
        e = e * jnp.asarray(scale, e.dtype)
    return e


__all__ = ["rms_norm", "layer_norm", "activation", "softcap",
           "QuantizedWeight", "weight_dequant", "WeightLike", "qlinear",
           "embed_lookup"]
