"""Block floating point (BFP) numerics — the paper's core data format.

A BFP group is a contiguous run of ``group_size`` elements along the
inner-product (contraction) dimension that shares a single exponent.  Each
element keeps an ``m``-bit two's-complement mantissa.  Conversion from FP
(paper Fig. 3):

  1. partition the vector into groups,
  2. take the largest exponent in the group as the shared exponent ``E``,
  3. right-shift and truncate each mantissa by its exponent difference.

With ``E = floor(log2(max|x|))`` clipped to the FP16 exponent range
[-14, 15] (5-bit shared exponent) and an ``m``-bit signed mantissa, the
quantization step is ``2^(E - m + 2)`` and values dequantize as
``x_hat = M * 2^(E - m + 2)``.  Truncation (round toward zero) is the
paper-faithful mode — it matches a hardware right-shift and can never
overflow the mantissa; round-to-nearest is available as a beyond-paper
option (slightly better accuracy, still cannot overflow after clamping).

Two families of API:

* ``bfp_fake_quant`` / ``BfpTensor``-free path: quantize->dequantize in one
  jitted op, used *inside models* to simulate BFP numerics for accuracy
  experiments (Table I/II, Fig. 4/5/8 analogues).
* packed path (``bfp_quantize`` / ``bfp_dequantize`` / nibble packing):
  materializes int8 mantissas + int8 shared exponents (and 2-per-byte int4
  mantissas), used by the serving KV cache and the Pallas kernels.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# FP16 exponent range for the 5-bit shared exponent.
EXP_MIN = -14
EXP_MAX = 15

DEFAULT_GROUP_SIZE = 32
DEFAULT_MANTISSA_BITS = 8


@dataclasses.dataclass(frozen=True)
class BfpConfig:
    """Configuration of one BFP conversion site."""

    group_size: int = DEFAULT_GROUP_SIZE
    mantissa_bits: int = DEFAULT_MANTISSA_BITS
    rounding: str = "trunc"  # "trunc" (paper-faithful) | "nearest"

    def __post_init__(self):
        if self.group_size < 1:
            raise ValueError(f"group_size must be >= 1, got {self.group_size}")
        if not (1 <= self.mantissa_bits <= 16):
            raise ValueError(
                f"mantissa_bits must be in [1, 16], got {self.mantissa_bits}")
        if self.rounding not in ("trunc", "nearest"):
            raise ValueError(f"unknown rounding mode {self.rounding!r}")

    @property
    def bits_per_element(self) -> float:
        """Storage cost incl. the amortized shared exponent (5 bits)."""
        return self.mantissa_bits + 5.0 / self.group_size


def pow2(e) -> jax.Array:
    """Exact float32 ``2**e`` for integer-valued ``e`` in [-126, 127].

    Built from the exponent bits.  ``jnp.exp2`` is approximate on XLA
    CPU (``exp2(-18.0) != 2**-18``), so a step made with it is not a
    power of two and a quantized value no longer sits on the grid it
    was quantized to."""
    e = jnp.asarray(e).astype(jnp.int32)
    return jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)


def shared_exponent(group_absmax: jax.Array) -> jax.Array:
    """floor(log2(absmax)) clipped to the 5-bit FP16 exponent range, as
    float32.

    The exponent is read from the float32 bit pattern, not computed with
    ``log2``: XLA's ``log2`` is approximate (``log2(8192)`` returns
    12.999999 on CPU), which put exact powers of two one bucket low.  The
    biased-exponent field is exact for every normal float; subnormals
    read as -127 and clip to EXP_MIN like any value below 2^-14.  Zero
    groups get EXP_MIN so their mantissas quantize to exactly zero.  The
    XLA path and the Pallas kernels share this one helper.
    """
    bits = jax.lax.bitcast_convert_type(
        group_absmax.astype(jnp.float32), jnp.int32)
    e = ((bits >> 23) & 0xFF) - 127
    e = jnp.where(group_absmax > 0, e, EXP_MIN)
    return jnp.clip(e, EXP_MIN, EXP_MAX).astype(jnp.float32)


def _group_reshape(x: jax.Array, group_size: int, axis: int):
    """Move ``axis`` last and split it into (n_groups, group_size)."""
    axis = axis % x.ndim
    x = jnp.moveaxis(x, axis, -1)
    n = x.shape[-1]
    if n % group_size != 0:
        pad = group_size - n % group_size
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    grouped = x.reshape(x.shape[:-1] + (x.shape[-1] // group_size, group_size))
    return grouped, n


def _group_unreshape(grouped: jax.Array, orig_len: int, axis: int,
                     ndim: int) -> jax.Array:
    x = grouped.reshape(grouped.shape[:-2] + (-1,))
    x = x[..., :orig_len]
    return jnp.moveaxis(x, -1, axis % ndim)


def _quantize_grouped(grouped: jax.Array, cfg: BfpConfig):
    """Quantize a (..., n_groups, group_size) array.

    Returns (mantissa int32 in [-2^(m-1)+1, 2^(m-1)-1], exponent int8 of
    shape (..., n_groups)).
    """
    m = cfg.mantissa_bits
    absmax = jnp.max(jnp.abs(grouped), axis=-1)
    e = shared_exponent(absmax)  # (..., n_groups) float32
    step = pow2(e - (m - 2))[..., None]
    scaled = grouped.astype(jnp.float32) / step
    if cfg.rounding == "trunc":
        mant = jnp.trunc(scaled)
    else:
        mant = jnp.round(scaled)
    lim = float(2 ** (m - 1) - 1)
    mant = jnp.clip(mant, -lim, lim)
    return mant.astype(jnp.int32), e.astype(jnp.int8)


def _dequantize_grouped(mant: jax.Array, exp: jax.Array,
                        cfg: BfpConfig) -> jax.Array:
    m = cfg.mantissa_bits
    step = pow2(exp.astype(jnp.int32) - (m - 2))[..., None]
    return mant.astype(jnp.float32) * step


@partial(jax.jit, static_argnames=("group_size", "mantissa_bits", "rounding",
                                   "axis", "ste"))
def bfp_fake_quant(x: jax.Array,
                   group_size: int = DEFAULT_GROUP_SIZE,
                   mantissa_bits: int = DEFAULT_MANTISSA_BITS,
                   rounding: str = "trunc",
                   axis: int = -1,
                   ste: bool = False) -> jax.Array:
    """Quantize->dequantize in the input dtype (BFP numerics simulation).

    ``ste=True``: straight-through estimator — forward value is quantized,
    gradient passes through unquantized (used by the offline-smoothing
    calibration, which differentiates Eq. 3 through Convert_BFP)."""
    cfg = BfpConfig(group_size, mantissa_bits, rounding)
    orig_dtype = x.dtype
    grouped, n = _group_reshape(x, group_size, axis)
    mant, exp = _quantize_grouped(grouped, cfg)
    deq = _dequantize_grouped(mant, exp, cfg)
    out = _group_unreshape(deq, n, axis, x.ndim).astype(orig_dtype)
    if ste:
        out = x + jax.lax.stop_gradient(out - x)
    return out


def bfp_quantize(x: jax.Array,
                 group_size: int = DEFAULT_GROUP_SIZE,
                 mantissa_bits: int = DEFAULT_MANTISSA_BITS,
                 rounding: str = "trunc",
                 axis: int = -1) -> Tuple[jax.Array, jax.Array]:
    """Materialize packed BFP: (mantissa int8, shared exponent int8).

    The grouped axis is moved last; mantissas come back with the original
    axis order restored, exponents have shape ``x.shape`` with ``axis``
    replaced by ``ceil(len/axis_group)`` groups *in the moved-last layout*:
    concretely ``exp.shape == mant_grouped.shape[:-1]`` where mantissas are
    laid out (..., n_groups, group_size) before the axis is restored.  For
    simplicity the packed API always returns the *moved-last* layout::

        mant: (..., n_groups, group_size) int8
        exp:  (..., n_groups)             int8

    Callers that need the original layout use ``bfp_dequantize`` which
    restores it.
    """
    if mantissa_bits > 8:
        raise ValueError("packed path supports mantissa_bits <= 8")
    cfg = BfpConfig(group_size, mantissa_bits, rounding)
    grouped, _ = _group_reshape(x, group_size, axis)
    mant, exp = _quantize_grouped(grouped, cfg)
    return mant.astype(jnp.int8), exp


def bfp_dequantize(mant: jax.Array, exp: jax.Array,
                   orig_len: int,
                   group_size: int = DEFAULT_GROUP_SIZE,
                   mantissa_bits: int = DEFAULT_MANTISSA_BITS,
                   axis: int = -1,
                   ndim: Optional[int] = None,
                   dtype=jnp.float32) -> jax.Array:
    """Inverse of ``bfp_quantize`` back to the original layout."""
    cfg = BfpConfig(group_size, mantissa_bits)
    deq = _dequantize_grouped(mant.astype(jnp.int32), exp, cfg)
    ndim = ndim if ndim is not None else deq.ndim - 1
    return _group_unreshape(deq, orig_len, axis, ndim).astype(dtype)


# ---------------------------------------------------------------------------
# int4 nibble packing (two 4-bit mantissas per int8 byte) — KV-cache storage
# ---------------------------------------------------------------------------

def pack_int4(mant: jax.Array, axis: int = -1) -> jax.Array:
    """Pack int4 values (stored as int8 in [-8, 7]) two-per-byte.

    ``axis`` length must be even.  Low nibble = even index, high = odd.
    """
    axis = axis % mant.ndim
    m = jnp.moveaxis(mant, axis, -1)
    if m.shape[-1] % 2 != 0:
        raise ValueError("pack_int4 needs an even axis length")
    lo = m[..., 0::2].astype(jnp.uint8) & 0xF
    hi = m[..., 1::2].astype(jnp.uint8) & 0xF
    packed = (lo | (hi << 4)).astype(jnp.int8)
    return jnp.moveaxis(packed, -1, axis)


def unpack_int4(packed: jax.Array, axis: int = -1) -> jax.Array:
    """Inverse of ``pack_int4`` -> int8 values in [-8, 7]."""
    axis = axis % packed.ndim
    p = jnp.moveaxis(packed, axis, -1).astype(jnp.uint8)
    lo = (p & 0xF).astype(jnp.int8)
    hi = ((p >> 4) & 0xF).astype(jnp.int8)
    # sign-extend 4-bit two's complement
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    out = jnp.stack([lo, hi], axis=-1).reshape(p.shape[:-1] + (-1,))
    return jnp.moveaxis(out, -1, axis)


def int4_pairs(packed: jax.Array) -> jax.Array:
    """The signed values of packed 4-bit pairs as ``int4``, shape
    ``packed.shape + (2,)``: [..., 0] the low nibble (the even index of
    ``pack_int4``), [..., 1] the high one.  For a contraction that reads
    the pair axis in place instead of interleaving it.

    Split by shifts, written as ``int4`` (the packed bytes' size): a
    ``bitcast_convert_type`` of the bytes to int4 pairs gives wrong
    products on a TPU v5e."""
    lo = (jnp.left_shift(packed, 4) >> 4).astype(jnp.int4)
    hi = (packed >> 4).astype(jnp.int4)
    return jnp.stack([lo, hi], axis=-1)


# ---------------------------------------------------------------------------
# Site-specific helpers (paper Fig. 6a grouping directions)
# ---------------------------------------------------------------------------

def quant_per_token(x: jax.Array, mantissa_bits: int = 8,
                    group_size: int = 32, rounding: str = "trunc"):
    """Per-token grouping: groups along the last (hidden/head) dim.

    Used for linear-layer inputs, Q, K and attention-score rows P (whose
    last dim is the key-token dim — the P·V contraction dim)."""
    return bfp_fake_quant(x, group_size, mantissa_bits, rounding, axis=-1)


def quant_v_cache(v: jax.Array, mantissa_bits: int = 8,
                  group_size: int = 32, rounding: str = "trunc",
                  token_axis: int = -2):
    """V grouping: along the *token* dim per channel (paper Fig. 6b).

    The P·V contraction dim is the token dim, so V groups must run along
    it.  During decode the trailing partial group is the 'residual group';
    fake-quant handles it by padding (the padded zeros never raise the
    shared exponent), which matches the incremental re-conversion: the
    residual group is converted at its current size each step."""
    return bfp_fake_quant(v, group_size, mantissa_bits, rounding,
                          axis=token_axis)


def quantization_error(x: jax.Array, cfg: BfpConfig,
                       axis: int = -1) -> jax.Array:
    """Max abs error bound check helper: |x - fq(x)| <= 2^(E-m+2)."""
    fq = bfp_fake_quant(x, cfg.group_size, cfg.mantissa_bits, cfg.rounding,
                        axis)
    return jnp.abs(x - fq)


def kv_cache_reduction(mantissa_bits: int, group_size: int = 32,
                       baseline_bits: int = 16) -> float:
    """Storage reduction vs FP16 (paper: 43.75% at m8, 68.75% at m4)."""
    bits = mantissa_bits + 5.0 / group_size
    # The paper quotes reductions ignoring the amortized exponent
    # (8/16 -> 50%? no: they quote 43.75% for m8 => (16-9)/16 with the
    # 5-bit exponent counted per 5 bits/32... 16 - (8+1) = 43.75% exactly
    # if one counts 1 exponent bit per element (5 bits / group of ~5?).
    # 43.75% = 7/16  => 9 bits/elem;  68.75% = 11/16 => 5 bits/elem.
    # i.e. the paper counts mantissa + 1 bit/elem of exponent overhead
    # (group 32 × 1 bit = 32 bits ≈ 5-bit exp + alignment/metadata).
    paper_bits = mantissa_bits + 1
    del bits
    return 1.0 - paper_bits / float(baseline_bits)


__all__ = [
    "BfpConfig", "bfp_fake_quant", "bfp_quantize", "bfp_dequantize",
    "pack_int4", "unpack_int4", "int4_pairs", "quant_per_token",
    "quant_v_cache",
    "quantization_error", "kv_cache_reduction", "EXP_MIN", "EXP_MAX",
    "DEFAULT_GROUP_SIZE", "DEFAULT_MANTISSA_BITS",
]
