"""Operations and bytes a dense decoder needs, from a configuration's shapes.

``model`` is the ``model`` mapping of a configuration file.  A multiply
and an add count as two operations.  Counted: the linear layers, the
head and causal attention (QK^T and PV over the keys a query may see).
Not counted: norms, rotary embedding, softmax, quantization, padding
rows and the rows of requests that already finished.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
INT4_GROUP = 128


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "about":
        raise KeyError(f"no peak rates for device kind {device_kind!r}")
    return table[device_kind]


def _linears(m: dict) -> list:
    """(in, out) of every linear weight of one block."""
    d, ff = m["d_model"], m["d_ff"]
    qd, kvd = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    mlp = ([(d, ff), (d, ff), (ff, d)] if m["mlp_style"] == "gated"
           else [(d, ff), (ff, d)])
    return [(d, qd), (d, kvd), (d, kvd), (qd, d)] + mlp


def linear_flops_per_token(m: dict) -> int:
    return 2 * m["n_layers"] * sum(i * o for i, o in _linears(m))


def head_flops_per_token(m: dict) -> int:
    return 2 * m["d_model"] * m["vocab_size"]


def attention_flops(m: dict, keys: int) -> int:
    """One query over ``keys`` keys, all layers (QK^T and PV)."""
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * keys


def request_flops(m: dict, prompt: int, served: int) -> int:
    """A request of ``prompt`` tokens that was served ``served`` tokens:
    the prompt's prefill (its last position through the head), then one
    decode step for each served token after the first."""
    lin, head = linear_flops_per_token(m), head_flops_per_token(m)
    # query i of the prompt sees i + 1 keys; decode step t sees prompt + t
    prompt_attn = attention_flops(m, prompt * (prompt + 1) // 2)
    steps = max(served - 1, 0)
    decode_keys = steps * prompt + steps * (steps + 1) // 2
    return (prompt * lin + head + steps * (lin + head)
            + prompt_attn + attention_flops(m, decode_keys))


def _int4_bytes(i: int, o: int) -> int:
    return i * o // 2 + (i // INT4_GROUP) * o * 4      # nibbles + f32 scales


def decode_weight_bytes(m: dict) -> int:
    """Weight bytes one decode step must read: every packed INT4 linear
    (nibbles and float32 group scales), the head (INT4, or the bfloat16
    embedding when tied) and the norm scales.  The embedding rows
    gathered for the batch's tokens are left out."""
    d, V = m["d_model"], m["vocab_size"]
    blocks = m["n_layers"] * sum(_int4_bytes(i, o) for i, o in _linears(m))
    head = 2 * V * d if m["tie_embeddings"] else _int4_bytes(d, V)
    norms_per_layer = 2 if m["norm_type"] == "rms" else 4
    norms = 2 * d * (m["n_layers"] * norms_per_layer + norms_per_layer // 2)
    return blocks + head + norms
