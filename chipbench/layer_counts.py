"""Bytes and operations of single layers of a decode step, from a
configuration's shapes (``model`` is the ``model`` mapping of a
configuration file).  Kept apart from ``counts.py``, whose whole-model
counts the accepted metrics read.

* The linears under the program's ``qlinear`` scope: every INT4 weight of
  every block and, where the embedding is not tied, the INT4 head.  A
  tied head multiplies the bfloat16 embedding outside ``qlinear`` and is
  left out, and so are the biases.
* The packed KV cache the attention of one decode step must read: for a
  row that holds ``L`` positions, the regions of ``core/kvcache.py`` that
  hold them, each at its stored width (8-bit init and local K tokens and
  V groups, 4-bit bulk with its exponents, the raw float32 V residual).
"""
from __future__ import annotations

import counts

GROUP = 32            # BFP group and V token group (core/kvcache.py)
INIT_TOKENS = 32
LOCAL_TOKENS = 64
V_LOCAL_GROUPS = 2
RESID_BYTES = 4       # the V residual group is kept in float32


def decode_linear_bytes(m: dict) -> int:
    """Packed INT4 bytes (nibbles and float32 group scales) of the
    weights one decode step multiplies under ``qlinear``."""
    blocks = m["n_layers"] * sum(counts._int4_bytes(i, o)
                                 for i, o in counts._linears(m))
    head = 0 if m["tie_embeddings"] else counts._int4_bytes(
        m["d_model"], m["vocab_size"])
    return blocks + head


def decode_linear_flops(m: dict, rows: int) -> int:
    """Operations of those multiplications for ``rows`` batch rows."""
    per_row = m["n_layers"] * sum(i * o for i, o in counts._linears(m))
    if not m["tie_embeddings"]:
        per_row += m["d_model"] * m["vocab_size"]
    return 2 * rows * per_row


def decode_linear_floor_s(m: dict, rows: int, peaks: dict) -> float:
    """Least time of one decode step's ``qlinear`` work on the chip: the
    larger of its bytes over the HBM rate and its operations over the
    bf16 rate."""
    return max(decode_linear_bytes(m) / peaks["hbm_bytes_per_s"],
               decode_linear_flops(m, rows) / peaks["bf16_flops_per_s"])


def cache_row_bytes(m: dict, positions: int) -> int:
    """Packed cache bytes, all layers, of one row holding ``positions``
    tokens (the token map of ``core/kvcache.py``)."""
    H, D, L = m["n_kv_heads"], m["head_dim"], positions
    k8, k4 = H * (D + D // GROUP), H * (D // 2 + D // GROUP)
    v8, v4 = H * (GROUP * D + D), H * (GROUP * D // 2 + D)
    k_hi = min(L, INIT_TOKENS) + max(0, min(LOCAL_TOKENS, L - INIT_TOKENS))
    k_bulk = max(0, L - INIT_TOKENS - LOCAL_TOKENS)
    cg = L // GROUP                              # complete V groups
    v_hi = min(cg, 1) + min(V_LOCAL_GROUPS, max(cg - 1, 0))
    v_bulk = max(0, cg - 1 - V_LOCAL_GROUPS)
    resid = (L - GROUP * cg) * H * D * RESID_BYTES
    per_layer = k_hi * k8 + k_bulk * k4 + v_hi * v8 + v_bulk * v4 + resid
    return m["n_layers"] * per_layer


def chunk_cache_bytes(m: dict, pos: int, steps: int, rows: int) -> int:
    """Cache bytes the attention of a decode chunk must read: ``steps``
    steps from the shared counter ``pos``; step ``i`` appends the token at
    ``pos + i`` and attends over ``pos + i + 1`` positions in every one of
    the ``rows`` rows."""
    return rows * sum(cache_row_bytes(m, pos + i + 1) for i in range(steps))
