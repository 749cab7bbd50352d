"""Gated MLP and Mixture-of-Experts blocks (BFP-INT on every GEMM)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quant_config import QuantConfig
from repro.layers.common import activation, qlinear


def gated_mlp(x: jax.Array, p: dict, act: str,
              quant: Optional[QuantConfig] = None) -> jax.Array:
    """SwiGLU-style MLP: down( act(gate(x)) * up(x) ); the activation and
    gate run under the named scope ``mlp_act``."""
    g = qlinear(x, p["w_gate"], quant)
    u = qlinear(x, p["w_up"], quant)
    with jax.named_scope("mlp_act"):
        h = activation(g, act) * u
    return qlinear(h, p["w_down"], quant)


def plain_mlp(x: jax.Array, p: dict, act: str,
              quant: Optional[QuantConfig] = None) -> jax.Array:
    """2-layer MLP (Whisper / classic transformer); the activation runs
    under the named scope ``mlp_act``."""
    h = qlinear(x, p["w_up"], quant, bias=p.get("b_up"))
    with jax.named_scope("mlp_act"):
        h = activation(h, act)
    return qlinear(h, p["w_down"], quant, bias=p.get("b_down"))


# ---------------------------------------------------------------------------
# Mixture of Experts (GShard-style capacity dispatch; EP-shardable)
# ---------------------------------------------------------------------------

MOE_GROUP_TOKENS = 512  # dispatch group size (see note below)


def moe_block(x: jax.Array, p: dict, act: str, n_experts: int, top_k: int,
              quant: Optional[QuantConfig] = None,
              capacity_factor: float = 1.25,
              group_tokens: int = MOE_GROUP_TOKENS) -> jax.Array:
    """Top-k routed MoE with *grouped* capacity dispatch (GShard-style).

    x: (B, S, d).  Expert weights are stacked on a leading expert axis so
    the `model` mesh axis can shard them (expert parallelism).

    Tokens are dispatched within fixed-size groups of ``group_tokens``:
    with a global capacity the dispatch one-hot einsums cost
    O(T * E * cap * d) = O(T^2 * k * d / E) — at T = 64k train tokens per
    device that was ~100x the expert GEMM flops (measured; see
    EXPERIMENTS.md §Perf iteration 1).  Grouping bounds capacity per
    group, making dispatch O(T * g * k * d) — a few percent of expert
    compute at g=512 — while keeping everything dense/static for SPMD.

    p: w_router (d, E), w_gate/w_up (E, d, ff), w_down (E, ff, d),
       optional w_shared_{gate,up,down} for a Llama-4-style shared expert.
    """
    B, S, d = x.shape
    T = B * S
    g = min(group_tokens, T)
    if T % g:
        g = T  # fall back for tiny inputs
    G = T // g
    xt = x.reshape(G, g, d)

    logits = qlinear(xt, p["w_router"], None).astype(jnp.float32)  # (G,g,E)
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, top_k)                       # (G,g,k)
    topv = topv / jnp.clip(topv.sum(-1, keepdims=True), 1e-9)

    cap = max(int(capacity_factor * g * top_k / n_experts), 1)
    cap = min(cap, g)

    # position of each (token, k) within its expert's per-group buffer
    onehot = jax.nn.one_hot(topi, n_experts, dtype=jnp.int32)    # G,g,k,E
    flat = onehot.reshape(G, g * top_k, n_experts)
    pos_in_e = jnp.cumsum(flat, axis=1) * flat - 1
    pos = pos_in_e.max(axis=-1).reshape(G, g, top_k)
    keep = (pos < cap) & (pos >= 0)
    gate_w = jnp.where(keep, topv, 0.0)

    # dispatch: (G, g, k, E, cap) one-hot combine tensor
    oh_e = jax.nn.one_hot(topi, n_experts, dtype=x.dtype)
    oh_c = jax.nn.one_hot(jnp.clip(pos, 0, cap - 1), cap, dtype=x.dtype)
    disp = (oh_e[..., :, None] * oh_c[..., None, :]
            * keep[..., None, None].astype(x.dtype))             # G,g,k,E,cap
    disp_te = disp.sum(2)                                        # G,g,E,cap
    xe = jnp.einsum("Gtd,Gtec->Gecd", xt, disp_te)               # G,E,cap,d

    w_gate = _deq(p["w_gate"], xe.dtype)
    w_up = _deq(p["w_up"], xe.dtype)
    w_down = _deq(p["w_down"], xe.dtype)
    gg = jnp.einsum("Gecd,edf->Gecf", _maybe_q(xe, quant), w_gate)
    u = jnp.einsum("Gecd,edf->Gecf", _maybe_q(xe, quant), w_up)
    h = activation(gg, act) * u
    ye = jnp.einsum("Gecf,efd->Gecd", _maybe_q(h, quant), w_down)

    combine = (disp * gate_w[..., None, None].astype(x.dtype)).sum(2)
    y = jnp.einsum("Gecd,Gtec->Gtd", ye, combine)

    if "w_shared_gate" in p:
        y = y + gated_mlp(xt, {"w_gate": p["w_shared_gate"],
                               "w_up": p["w_shared_up"],
                               "w_down": p["w_shared_down"]}, act, quant)
    return y.reshape(B, S, d)


def _deq(w, dtype):
    """Dequantize stacked INT4 expert weights (serving path)."""
    from repro.layers.common import QuantizedWeight, weight_dequant
    if isinstance(w, QuantizedWeight):
        return weight_dequant(w, dtype)
    return w


def _maybe_q(x, quant: Optional[QuantConfig]):
    if quant is not None and quant.enabled and quant.quant_linear_acts:
        from repro.core import bfp
        return bfp.bfp_fake_quant(x, quant.group_size,
                                  quant.act_mantissa_bits, quant.rounding,
                                  axis=-1, ste=quant.ste)
    return x


def moe_aux_loss(x: jax.Array, w_router: jax.Array,
                 n_experts: int) -> jax.Array:
    """Load-balancing auxiliary loss (Switch-style) for MoE training."""
    T = x.shape[0] * x.shape[1]
    logits = x.reshape(T, -1) @ w_router
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top1 = jnp.argmax(gates, axis=-1)
    frac_tokens = jnp.mean(jax.nn.one_hot(top1, n_experts), axis=0)
    frac_probs = jnp.mean(gates, axis=0)
    return n_experts * jnp.sum(frac_tokens * frac_probs)


__all__ = ["gated_mlp", "plain_mlp", "moe_block", "moe_aux_loss"]
