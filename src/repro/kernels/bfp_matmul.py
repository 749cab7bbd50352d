"""BFP-INT GEMM kernel — the Harmonia PE array's M8W4 mode on TPU.

Operands stay compressed in HBM (int8 mantissas + per-group-32 exponents
for activations; INT4 nibbles + per-group-128 fp32 scales for weights) and
are dequantized *in VMEM* immediately before an MXU dot — the TPU-native
realization of the paper's integer PE + shared-exponent scaling (see
DESIGN.md §2).  fp32 accumulation (stronger than the ASIC's shared FP
accumulator).

Tiling-aware dataflow (paper Sec. IV-D / FDGF): the grid order decides
which operand stays resident across the inner sweep:

  * ``weight_stationary``  (paper's column-major output flow): grid
    (N/bn, M/bm) — the (K, bn) weight tile is revisited across the inner
    M sweep, weights are read from HBM exactly once;
  * ``act_stationary``     (row-major output flow): grid (M/bm, N/bn) —
    the (bm, K) activation tile is revisited, activations read once.

Both of those keep the whole contraction dim in VMEM.  When K is too
large for that, ``block_k`` switches to the K-blocked grid
(M/bm, N/bn, K/bk) with an fp32 VMEM accumulator scratch: the output is
still written once, but *neither* operand is stationary anymore — every
(i, j) output tile re-reads its K-strip of both operands.  That re-read
is the K-split term in ``choose_dataflow``'s EMA model.

Ragged M/N are zero-padded up to the tile size and the result sliced
back, so small or odd shapes keep the intended tiling instead of
silently degrading to ``bm = M`` / ``bn = N`` whole-operand tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bfp import pow2

GROUP_A = 32
GROUP_W = 128


def _unpack_w(wp, bk):
    """(bk/2, bn) int8 nibbles -> (bk, bn) int32 in [-8, 7]."""
    wpu = wp.astype(jnp.uint8)
    lo = (wpu & 0xF).astype(jnp.int32)
    hi = ((wpu >> 4) & 0xF).astype(jnp.int32)
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    w = jnp.stack([lo, hi], axis=1)                  # (bk/2, 2, bn)
    return w.reshape(bk, wp.shape[-1])


def _dequant_tiles(a_mant_ref, a_exp_ref, w_packed_ref, w_scale_ref,
                   mantissa_bits):
    """Dequantize the VMEM-resident operand tiles to f32."""
    a_m = a_mant_ref[...].astype(jnp.float32)        # (bm, bk)
    bm, bk = a_m.shape
    step = pow2(a_exp_ref[...].astype(jnp.int32)
                - (mantissa_bits - 2))               # (bm, bk/32)
    a = (a_m.reshape(bm, bk // GROUP_A, GROUP_A)
         * step[..., None]).reshape(bm, bk)

    w_int = _unpack_w(w_packed_ref[...], bk).astype(jnp.float32)
    bn = w_int.shape[-1]
    ws = w_scale_ref[...]                            # (bk/128, bn)
    w = (w_int.reshape(bk // GROUP_W, GROUP_W, bn)
         * ws[:, None, :]).reshape(bk, bn)
    return a, w


def _mm_kernel(a_mant_ref, a_exp_ref, w_packed_ref, w_scale_ref, out_ref, *,
               mantissa_bits: int, out_dtype):
    a, w = _dequant_tiles(a_mant_ref, a_exp_ref, w_packed_ref, w_scale_ref,
                          mantissa_bits)
    out_ref[...] = jnp.dot(a, w, preferred_element_type=jnp.float32
                           ).astype(out_dtype)


def _mm_kblock_kernel(a_mant_ref, a_exp_ref, w_packed_ref, w_scale_ref,
                      out_ref, acc_ref, *, mantissa_bits: int, out_dtype,
                      n_k: int):
    """K-blocked body: grid (M/bm, N/bn, K/bk), K innermost.  Partial
    products accumulate in the fp32 VMEM scratch; the output tile is
    written to HBM exactly once, at the last K step."""
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a, w = _dequant_tiles(a_mant_ref, a_exp_ref, w_packed_ref, w_scale_ref,
                          mantissa_bits)
    acc_ref[...] += jnp.dot(a, w, preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_dtype)


def _mm_int_kernel(a_mant_ref, a_exp_ref, w_packed_ref, w_scale_ref,
                   out_ref, *, mantissa_bits: int, out_dtype):
    """Integer-subdot variant: per-32-group int32 dot products with fp32
    cross-group accumulation — the literal Harmonia PE dataflow.  On MXU
    this underutilizes the K=32 contraction (documented trade-off); kept
    for numerical comparison and as the int8-MXU path."""
    a_m = a_mant_ref[...].astype(jnp.int32)
    bm, K = a_m.shape
    nga = K // GROUP_A
    w_int = _unpack_w(w_packed_ref[...], K).astype(jnp.int32)
    bn = w_int.shape[-1]
    a_g = a_m.reshape(bm, nga, GROUP_A)
    w_g = w_int.reshape(nga, GROUP_A, bn)
    # integer partial products per shared-exponent group
    pp = jax.lax.dot_general(
        a_g.astype(jnp.float32), w_g.astype(jnp.float32),
        (((2,), (1,)), ((1,), (0,))),
        preferred_element_type=jnp.float32)          # (nga, bm, bn)
    a_step = pow2(a_exp_ref[...].astype(jnp.int32)
                  - (mantissa_bits - 2))             # (bm, nga)
    ws = w_scale_ref[...]                            # (K/128, bn)
    ws_g = jnp.repeat(ws, GROUP_W // GROUP_A, axis=0)  # (nga, bn)
    acc = jnp.sum(pp * a_step.T[:, :, None] * ws_g[:, None, :], axis=0)
    out_ref[...] = acc.astype(out_dtype)


def _cdiv(a: int, b: int) -> int:
    return -(-a // max(b, 1))


def choose_dataflow(M: int, N: int, K: int, bm: int = 128,
                    bn: int = 128, bk: int | None = None) -> str:
    """External-memory-access (EMA) model for the grid-order choice.

    In element loads (bytes cancel for the comparison — both operands are
    ~4-bit-per-element compressed)::

        weight_stationary:  W_once + ceil(N/bn)·M·K + M·N
        act_stationary:     A_once + ceil(M/bm)·N·K + M·N

    where ``W_once = N·K`` / ``A_once = M·K`` when the whole contraction
    dim is VMEM-resident (``bk >= K``).  This is the paper's Fig. 15
    column- vs row-major EMA trade (col: K/k·(M·N)+N·K vs
    row: M/m·(N·K)+M·N) adapted to this kernel's dataflow: the paper's
    accelerator spills partial output sums to external memory when K is
    split (its K/k·M·N term), whereas the TPU kernel holds the
    accumulator in VMEM scratch and writes the output once — so the
    K-split cost appears as *operand* re-reads instead.  Concretely, with
    ``bk < K`` (grid (M/bm, N/bn, K/bk)) the stationary operand loses its
    read-once property::

        weight_stationary:  ceil(M/bm)·N·K + ceil(N/bn)·M·K + M·N
        act_stationary:     ceil(N/bn)·M·K + ceil(M/bm)·N·K + M·N

    i.e. both orders converge to the same traffic and the choice becomes
    a tie (resolved toward ``weight_stationary``); K-blocking is selected
    by VMEM capacity, not by this model.  See DESIGN.md §2.
    """
    bm = max(1, min(bm, M))
    bn = max(1, min(bn, N))
    bk = K if bk is None else max(1, min(bk, K))
    k_split = _cdiv(K, bk) > 1
    w_once = _cdiv(M, bm) * N * K if k_split else N * K
    a_once = _cdiv(N, bn) * M * K if k_split else M * K
    ema_weight_stationary = w_once + _cdiv(N, bn) * M * K + M * N
    ema_act_stationary = a_once + _cdiv(M, bm) * N * K + M * N
    return ("weight_stationary"
            if ema_weight_stationary <= ema_act_stationary
            else "act_stationary")


def _pad_dim(x, axis: int, to: int):
    pad = (-x.shape[axis]) % to
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def bfp_matmul_kernel(a_mant, a_exp, w_packed, w_scale, *,
                      mantissa_bits: int = 8, block_m: int = 128,
                      block_n: int = 128, block_k: int | None = None,
                      dataflow: str = "auto",
                      int_path: bool = False, out_dtype=jnp.float32,
                      interpret: bool = False):
    """(M, K)x(K, N) BFP-INT GEMM on packed operands.

    a_mant (M, K) int8; a_exp (M, K/32) int8; w_packed (K/2, N) int8;
    w_scale (K/128, N) f32.

    ``block_k``: optional contraction tile.  When set (and < K), the grid
    becomes (M/bm, N/bn, K/bk) with an fp32 VMEM accumulator so K no
    longer has to fit in VMEM whole; must be a multiple of 128
    (= GROUP_W, the weight-scale group).  The K-split grid order is
    fixed — ``dataflow`` only selects the grid when K is VMEM-resident
    (both orders cost the same EMA once K is split; see
    ``choose_dataflow``).  Ragged M/N are zero-padded to the tile size
    and the result sliced back.
    """
    M, K = a_mant.shape
    N = w_packed.shape[-1]
    if K % GROUP_W:
        raise ValueError(f"K={K} must be a multiple of {GROUP_W}")
    bm = min(block_m, M)
    bn = min(block_n, N)
    bk = K if block_k is None else min(block_k, K)
    if bk % GROUP_W:
        raise ValueError(f"block_k={bk} must be a multiple of {GROUP_W}")
    if K % bk:
        raise ValueError(f"block_k={bk} must divide K={K}")
    n_k = K // bk
    if int_path and n_k > 1:
        raise ValueError("int_path does not support K-blocking "
                         "(per-group integer subdots already tile K=32)")
    if dataflow not in ("auto", "act_stationary", "weight_stationary"):
        raise ValueError(f"unknown dataflow {dataflow!r}")
    if dataflow == "auto" and n_k == 1:
        dataflow = choose_dataflow(M, N, K, bm, bn, bk)

    # pad ragged M/N up to the tile size (zero mantissas/scales contribute
    # exact zeros) instead of degrading to whole-operand tiles
    a_mant = _pad_dim(a_mant, 0, bm)
    a_exp = _pad_dim(a_exp, 0, bm)
    w_packed = _pad_dim(w_packed, 1, bn)
    w_scale = _pad_dim(w_scale, 1, bn)
    Mp = a_mant.shape[0]
    Np = w_packed.shape[-1]

    out_shape = jax.ShapeDtypeStruct((Mp, Np), out_dtype)

    if n_k > 1:
        kernel = functools.partial(_mm_kblock_kernel,
                                   mantissa_bits=mantissa_bits,
                                   out_dtype=out_dtype, n_k=n_k)
        from jax.experimental.pallas import tpu as pltpu
        out = pl.pallas_call(
            kernel,
            grid=(Mp // bm, Np // bn, n_k),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bm, bk // GROUP_A), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
                pl.BlockSpec((bk // GROUP_W, bn), lambda i, j, k: (k, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=interpret,
        )(a_mant, a_exp, w_packed, w_scale)
        return out[:M, :N]

    body = _mm_int_kernel if int_path else _mm_kernel
    kernel = functools.partial(body, mantissa_bits=mantissa_bits,
                               out_dtype=out_dtype)

    if dataflow == "act_stationary":
        # grid (i, j): activation tile index (i, 0) constant across inner j
        grid = (Mp // bm, Np // bn)
        in_specs = [
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, K // GROUP_A), lambda i, j: (i, 0)),
            pl.BlockSpec((K // 2, bn), lambda i, j: (0, j)),
            pl.BlockSpec((K // GROUP_W, bn), lambda i, j: (0, j)),
        ]
        out_specs = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    elif dataflow == "weight_stationary":
        # grid (j, i): weight tile index (0, j) constant across inner i
        grid = (Np // bn, Mp // bm)
        in_specs = [
            pl.BlockSpec((bm, K), lambda j, i: (i, 0)),
            pl.BlockSpec((bm, K // GROUP_A), lambda j, i: (i, 0)),
            pl.BlockSpec((K // 2, bn), lambda j, i: (0, j)),
            pl.BlockSpec((K // GROUP_W, bn), lambda j, i: (0, j)),
        ]
        out_specs = pl.BlockSpec((bm, bn), lambda j, i: (i, j))
    else:
        raise ValueError(f"unknown dataflow {dataflow!r}")

    out = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
    )(a_mant, a_exp, w_packed, w_scale)
    return out[:M, :N]


__all__ = ["bfp_matmul_kernel", "choose_dataflow"]
