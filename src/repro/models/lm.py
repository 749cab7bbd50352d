"""Unified LM: forward (train/eval), prefill and decode over any
ModelConfig — dense GQA, MoE, Mamba-2 SSD, RG-LRU hybrid, enc-dec, VLM.

Layer stacks run under ``jax.lax.scan`` over pattern repeats (params
stacked per block *kind*), keeping compiled HLO size O(1) in depth.
Remainder blocks (pattern not dividing n_layers, e.g. recurrentgemma's
38 = 12x(r,r,a)+2r) run unrolled after the scan.

All GEMMs go through the Harmonia quantization hooks (BFP activations +
INT4 weights); attention uses the paper's all-layer BFP sites.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import kvcache
from repro.core.quant_config import QuantConfig
from repro.core.smoothing import compute_online_offsets
from repro.layers import attention as attn_lib
from repro.layers import rglru as rglru_lib
from repro.layers import ssd as ssd_lib
from repro.layers.common import (embed_lookup, layer_norm, qlinear, rms_norm,
                                 softcap)
from repro.layers.mlp import gated_mlp, moe_block, plain_mlp
from repro.layers.rope import apply_rope, sinusoidal_embedding
from repro.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class Ctx:
    """Static + traced context threaded through block application."""
    mode: str                      # full | prefill | decode
    positions: Any                 # (B,S) int32 query positions
    bidir: bool = False            # encoder stacks
    eval_kv: bool = False          # decode-faithful asymmetric fake-quant
    enc_out: Any = None            # (B,T,d) encoder output (whisper)
    enc_positions: Any = None
    k_valid: Any = None            # (B,S) padding mask
    max_seq: int = 0               # cache capacity (prefill/decode)
    pad_prefix: Any = None         # (B,) left-pad counts for decode masks
    seq_shard: bool = False        # Megatron-SP-style constraints (dry-run
    dp_axes: tuple = ("data",)     # + production meshes only)
    use_pallas: bool = False       # grid-fused Pallas kernels on the
                                   # prefill/decode global-attn hot paths


def _c(x, ctx: Ctx, *spec):
    """with_sharding_constraint under the active mesh (no-op unless
    ctx.seq_shard — tests/single-device paths never hit it)."""
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _norm(h, p, name, cfg: ModelConfig):
    if cfg.norm_type == "layer":
        return layer_norm(h, p[name], p[name + "_bias"], cfg.norm_eps)
    return rms_norm(h, p[name], cfg.norm_eps, cfg.zero_centered_norm)


def _mlp_part(h, p, cfg: ModelConfig, quant):
    x = _norm(h, p, "ln2", cfg)
    if cfg.n_experts:
        y = moe_block(x, p, cfg.act_fn, cfg.n_experts, cfg.moe_top_k,
                      quant, cfg.capacity_factor)
    elif cfg.mlp_style == "gated":
        y = gated_mlp(x, p, cfg.act_fn, quant)
    else:
        y = plain_mlp(x, p, cfg.act_fn, quant)
    if cfg.post_block_norm:
        y = _norm(y, p, "post_ln2", cfg)
    with jax.named_scope("residual"):
        return h + y


def _qkv(x, p, cfg: ModelConfig, quant, prefix=""):
    B, S, _ = x.shape
    q = qlinear(x, p[prefix + "wq" if prefix else "wq"], quant,
                bias=p.get("bq") if not prefix else None)
    k = qlinear(x, p[prefix + "wk" if prefix else "wk"], quant,
                bias=p.get("bk") if not prefix else None)
    v = qlinear(x, p[prefix + "wv" if prefix else "wv"], quant,
                bias=p.get("bv") if not prefix else None)
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def _cross_attention(h, p, cfg: ModelConfig, quant, ctx: Ctx,
                     enc_kv=None):
    """Whisper cross-attn; enc_kv = precomputed (k,v) during decode."""
    x = _norm(h, p, "ln_x", cfg)
    B, S, _ = x.shape
    q = qlinear(x, p["wq_x"], quant).reshape(B, S, cfg.n_heads, cfg.head_dim)
    if enc_kv is None:
        e = ctx.enc_out
        Te = e.shape[1]
        k = qlinear(e, p["wk_x"], quant).reshape(B, Te, cfg.n_kv_heads,
                                                 cfg.head_dim)
        v = qlinear(e, p["wv_x"], quant).reshape(B, Te, cfg.n_kv_heads,
                                                 cfg.head_dim)
    else:
        k, v = enc_kv
        Te = k.shape[1]
    kpos = jnp.broadcast_to(jnp.arange(Te)[None], (B, Te))
    out = attn_lib.attention_forward(
        q, k, v, positions=jnp.zeros((B, S), jnp.int32), mask_kind="bidir",
        quant=quant, kq_positions=kpos)
    out = qlinear(out.astype(h.dtype).reshape(B, S, cfg.q_dim), p["wo_x"],
                  quant)
    return h + out, (k, v)


def _attention(q, k, v, kind: str, cfg: ModelConfig,
               quant: Optional[QuantConfig], ctx: Ctx, cache):
    """Rotary embedding, the attention itself and the cache build or
    append of one attention block: (attn, new cache)."""
    B, S = q.shape[:2]
    if cfg.pos_embed == "rope":
        q = apply_rope(q, ctx.positions, cfg.rope_theta)
        k = apply_rope(k, ctx.positions, cfg.rope_theta)
    if ctx.seq_shard and ctx.mode in ("full", "prefill"):
        # heads (e.g. qwen's 40) need not divide the model axis: shard the
        # query *sequence* instead and gather K/V — kills the partial-sum
        # (B,H,S,hd) f32 all-reduce in attention bwd (§Perf iteration 2)
        q = _c(q, ctx, ctx.dp_axes, "model", None, None)
        k = _c(k, ctx, ctx.dp_axes, None, None, None)
        v = _c(v, ctx, ctx.dp_axes, None, None, None)
    window = cfg.window_size if kind == "local_attn" else 0
    mask_kind = "bidir" if ctx.bidir else (
        "local" if kind == "local_attn" else "causal")
    online = (quant is not None and quant.enabled and quant.quant_attention
              and quant.smoothing.online)
    new_cache = cache

    if ctx.mode == "full":
        with jax.named_scope("attend"):
            if online:
                w = min(quant.smoothing.online_window, S)
                off = compute_online_offsets(k[:, :w].astype(jnp.float32),
                                             quant.smoothing.online_topk)
                k = k - off[:, None].astype(k.dtype)
            if ctx.eval_kv and quant is not None and quant.enabled \
                    and quant.quant_attention:
                attn = attn_lib.attention_eval_quant(
                    q, k, v, ctx.positions, quant, mask_kind=mask_kind,
                    window=window, logit_cap=cfg.attn_logit_softcap,
                    k_valid=ctx.k_valid)
            else:
                attn = attn_lib.attention_forward(
                    q, k, v, ctx.positions, mask_kind=mask_kind,
                    window=window, logit_cap=cfg.attn_logit_softcap,
                    quant=quant, k_valid=ctx.k_valid)
    elif ctx.mode == "prefill":
        # grid-fused Pallas path: engine-style causal prefill (arange
        # positions, no padding mask, un-sharded) on the global-attn kind
        pallas_ok = (ctx.use_pallas and kind == "attn" and not ctx.bidir
                     and ctx.k_valid is None and not ctx.seq_shard
                     and S % 32 == 0 and cfg.head_dim % 32 == 0)
        with jax.named_scope("attend"):
            if pallas_ok:
                attn = attn_lib.attention_prefill_pallas(
                    q, k, v, causal=True, logit_cap=cfg.attn_logit_softcap,
                    quant=quant)
            else:
                attn = attn_lib.attention_forward(
                    q, k, v, ctx.positions, mask_kind=mask_kind,
                    window=window, logit_cap=cfg.attn_logit_softcap,
                    quant=quant, k_valid=ctx.k_valid)
        with jax.named_scope("kv_convert"):
            if kind == "attn":
                off = None
                if online:
                    w = min(quant.smoothing.online_window, S)
                    off = compute_online_offsets(
                        k[:, :w].astype(jnp.float32),
                        quant.smoothing.online_topk)
                c = kvcache.init_cache(B, cfg.n_kv_heads, cfg.head_dim,
                                       ctx.max_seq)
                # same guard as the attention kernel: the packed cache is
                # built by the single-launch FP->BFP converter kernel
                # (only packed bytes hit HBM) instead of the XLA quantize
                # chains
                new_cache = kvcache.prefill_cache(
                    c, k.astype(jnp.float32), v.astype(jnp.float32), off,
                    use_pallas=pallas_ok)
            else:
                c = attn_lib.init_ring_cache(
                    B, cfg.n_kv_heads, cfg.head_dim,
                    min(cfg.window_size, ctx.max_seq))
                new_cache = attn_lib.ring_prefill(
                    c, k.astype(jnp.float32), v.astype(jnp.float32))
    elif ctx.mode == "decode":
        if kind == "attn":
            with jax.named_scope("kv_append"):
                new_cache = kvcache.append_token(cache, k[:, 0], v[:, 0])
            # attends on the packed cache in place: region reads and the
            # nibble split under kv_gather, dots and softmax under attend
            attn = attn_lib.attention_decode_packed(
                q, new_cache, logit_cap=cfg.attn_logit_softcap, quant=quant,
                extra_invalid_prefix=ctx.pad_prefix,
                seq_shard=ctx.seq_shard, dp_axes=ctx.dp_axes,
                use_pallas=ctx.use_pallas)
        else:
            with jax.named_scope("kv_append"):
                new_cache = attn_lib.ring_append(cache, k[:, 0], v[:, 0])
            with jax.named_scope("attend"):
                attn = attn_lib.ring_decode_attention(
                    q, new_cache, window=cfg.window_size,
                    logit_cap=cfg.attn_logit_softcap, quant=quant)
    else:
        raise ValueError(ctx.mode)
    return attn, new_cache


def _attn_block(h, p, kind: str, cfg: ModelConfig,
                quant: Optional[QuantConfig], ctx: Ctx, cache):
    B, S, _ = h.shape
    x = _norm(h, p, "ln1", cfg)
    q, k, v = _qkv(x, p, cfg, quant)
    with jax.named_scope("attention"):
        attn, new_cache = _attention(q, k, v, kind, cfg, quant, ctx, cache)
        attn = attn.astype(h.dtype).reshape(B, S, cfg.q_dim)
    if ctx.seq_shard and ctx.mode in ("full", "prefill"):
        attn = _c(attn, ctx, ctx.dp_axes, "model", None)
    out = qlinear(attn, p["wo"], quant)
    if cfg.post_block_norm:
        out = _norm(out, p, "post_ln1", cfg)
    with jax.named_scope("residual"):
        h = h + out
    if ctx.seq_shard and ctx.mode in ("full", "prefill"):
        # Megatron-SP residual: S-sharded between blocks -> row-sharded
        # projections reduce-scatter instead of all-reduce; norms shard
        h = _c(h, ctx, ctx.dp_axes, "model", None)
    return h, new_cache


def _wrap_cross(h, p, cfg, quant, ctx: Ctx, cache):
    """Self-attn (+cache) then cross-attn for enc-dec decoders."""
    if not cfg.cross_attention:
        return None
    self_cache = cache["self"] if isinstance(cache, dict) else None
    h, new_self = _attn_block(h, p, "attn", cfg, quant, ctx, self_cache)
    enc_kv = None
    if isinstance(cache, dict) and "enc_k" in cache and ctx.mode == "decode":
        enc_kv = (cache["enc_k"], cache["enc_v"])
    h, (ek, ev) = _cross_attention(h, p, cfg, quant, ctx, enc_kv)
    if not cfg.mixer_only:
        h = _mlp_part(h, p, cfg, quant)
    if ctx.mode in ("prefill", "decode"):
        new_cache = {"self": new_self, "enc_k": ek.astype(jnp.float32),
                     "enc_v": ev.astype(jnp.float32)}
    else:
        new_cache = cache
    return h, new_cache


def apply_block(h, p, kind: str, cfg: ModelConfig,
                quant: Optional[QuantConfig], ctx: Ctx, cache=None):
    if kind in ("attn", "local_attn"):
        if cfg.cross_attention and not ctx.bidir:
            return _wrap_cross(h, p, cfg, quant, ctx, cache)
        h, new_cache = _attn_block(h, p, kind, cfg, quant, ctx, cache)
        if not cfg.mixer_only:
            h = _mlp_part(h, p, cfg, quant)
        return h, new_cache
    if kind == "ssd":
        x = _norm(h, p, "ln1", cfg)
        y, new_state = ssd_lib.ssd_mixer(x, p, cfg, quant, state=cache,
                                         decode=(ctx.mode == "decode"))
        return h + y, new_state
    if kind == "rglru":
        x = _norm(h, p, "ln1", cfg)
        y, new_state = rglru_lib.rglru_mixer(x, p, cfg, quant, state=cache,
                                             decode=(ctx.mode == "decode"))
        h = h + y
        if not cfg.mixer_only:
            h = _mlp_part(h, p, cfg, quant)
        return h, new_state
    raise ValueError(f"unknown block kind {kind!r}")


# ---------------------------------------------------------------------------
# Stack execution: scan over pattern repeats + unrolled remainder
# ---------------------------------------------------------------------------

def _split_stacks(cfg: ModelConfig, blocks: Dict):
    """Per-kind stacked trees -> (scan view (n_rep, c_k, ...), remainder)."""
    n_rep, rem = cfg.pattern_layout()
    c = {}
    for k in cfg.block_pattern:
        c[k] = c.get(k, 0) + 1
    scan_view, rem_view = {}, []
    for kind, ck in c.items():
        tree = blocks[kind]
        scan_view[kind] = jax.tree.map(
            lambda a: a[: n_rep * ck].reshape((n_rep, ck) + a.shape[1:]),
            tree)
    offs = {k: cfg.pattern_layout()[0] * c[k] for k in c}
    for kind in rem:
        i = offs[kind]
        rem_view.append((kind, jax.tree.map(lambda a: a[i], blocks[kind])))
        offs[kind] += 1
    return scan_view, rem_view, n_rep, c


def _run_stack(h, blocks: Dict, cfg: ModelConfig, quant, ctx: Ctx,
               caches=None, remat: bool = False, unroll: bool = False):
    """Returns (h, new_caches) — caches mirror the input structure:
    {"scan": {kind: (n_rep, c_k, ...)}, "rem": [per-block, ...]}."""
    scan_params, rem_params, n_rep, c = _split_stacks(cfg, blocks)

    def step(carry, xs):
        hh = carry
        idx = {k: 0 for k in c}
        new_cs: Dict = {k: [] for k in c}
        for kind in cfg.block_pattern:
            i = idx[kind]
            p_i = jax.tree.map(lambda a: a[i], xs[kind][0])
            c_i = None
            if xs[kind][1] is not None:
                c_i = jax.tree.map(lambda a: a[i], xs[kind][1])
            hh, c_new = apply_block(hh, p_i, kind, cfg, quant, ctx, c_i)
            new_cs[kind].append(c_new)
            idx[kind] += 1
        ys = None
        if ctx.mode in ("prefill", "decode"):
            ys = {k: jax.tree.map(lambda *a: jnp.stack(a), *v)
                  if v[0] is not None else None
                  for k, v in new_cs.items()}
        return hh, ys

    step_fn = jax.checkpoint(step) if remat else step
    xs = {k: (scan_params[k],
              caches["scan"].get(k) if caches is not None else None)
          for k in c}
    # the scan's own work (each layer's weights and cache sliced out of
    # the stacks, the new cache written back) runs under "layers"
    with jax.named_scope("layers"):
        h, ys = jax.lax.scan(step_fn, h, xs,
                             unroll=n_rep if unroll else 1)
        rem_caches = []
        for j, (kind, p_j) in enumerate(rem_params):
            c_j = caches["rem"][j] if caches is not None else None
            h, c_new = apply_block(h, p_j, kind, cfg, quant, ctx, c_j)
            rem_caches.append(c_new)

    new_caches = None
    if ctx.mode in ("prefill", "decode"):
        new_caches = {"scan": ys, "rem": rem_caches}
    return h, new_caches


# ---------------------------------------------------------------------------
# Encoder (whisper) + embedding + heads
# ---------------------------------------------------------------------------

def encoder_forward(params, cfg: ModelConfig, frames: jax.Array,
                    quant=None, unroll: bool = False) -> jax.Array:
    """frames: (B, T, d) precomputed conv-frontend embeddings (stub)."""
    B, T, _ = frames.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    h = frames + sinusoidal_embedding(pos, cfg.d_model).astype(frames.dtype)
    from repro.models.init import _encoder_view
    enc_cfg = _encoder_view(cfg)
    ctx = Ctx(mode="full", positions=pos, bidir=True)
    blocks = {"attn": params["enc_blocks"]}
    one = dataclasses.replace(enc_cfg, block_pattern=("attn",),
                              n_layers=cfg.encoder_layers)
    h, _ = _run_stack(h, blocks, one, quant, ctx, unroll=unroll)
    return _norm(h, params, "enc_final_norm", enc_cfg)


def _embed(params, cfg: ModelConfig, tokens, positions):
    import math
    scale = math.sqrt(cfg.d_model) if cfg.embed_scale else 1.0
    with jax.named_scope("embed"):
        h = embed_lookup(tokens, params["embed"], scale)
        if cfg.pos_embed == "sinusoidal":
            h = h + sinusoidal_embedding(positions,
                                         cfg.d_model).astype(h.dtype)
        return h


def head_logits(params, cfg: ModelConfig, h, quant=None):
    """LM-head projection on already-normalized hidden states."""
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", h, params["embed"])
    else:
        logits = qlinear(h, params["lm_head"], quant)
    if cfg.final_logit_softcap > 0:
        logits = softcap(logits, cfg.final_logit_softcap)
    return logits


def _head(params, cfg: ModelConfig, h, quant=None):
    with jax.named_scope("head"):
        h = _norm(h, params, "final_norm", cfg)
        return head_logits(params, cfg, h, quant)


def _prepend_frontend(h, positions, frontend_embeds):
    fe = frontend_embeds.astype(h.dtype)
    B, n_f, _ = fe.shape
    h = jnp.concatenate([fe, h], axis=1)
    pos = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(n_f)[None], (B, n_f)),
         positions + n_f], axis=1)
    return h, pos, n_f


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def forward(params, cfg: ModelConfig, tokens: jax.Array, *,
            quant: Optional[QuantConfig] = None,
            frontend_embeds: Optional[jax.Array] = None,
            eval_kv: bool = False, positions: Optional[jax.Array] = None,
            k_valid: Optional[jax.Array] = None,
            remat: bool = False, return_hidden: bool = False,
            unroll: bool = False, seq_shard: bool = False,
            dp_axes: tuple = ("data",)) -> jax.Array:
    """Full-sequence logits (B, S, V).  ``eval_kv`` turns on the
    decode-faithful asymmetric KV fake-quant (accuracy benchmarks).
    ``return_hidden``: skip the LM head and return final hidden states
    (B, S, d) — used by the chunked-CE training loss so the full
    (B, S, V) logits never materialize."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = _embed(params, cfg, tokens, positions)

    n_f = 0
    enc_out = None
    if cfg.is_encoder_decoder and frontend_embeds is not None:
        enc_out = encoder_forward(params, cfg, frontend_embeds, quant,
                                  unroll=unroll)
    elif cfg.frontend == "vision_stub" and frontend_embeds is not None:
        h, positions, n_f = _prepend_frontend(h, positions, frontend_embeds)

    ctx = Ctx(mode="full", positions=positions, eval_kv=eval_kv,
              enc_out=enc_out, k_valid=k_valid, seq_shard=seq_shard,
              dp_axes=dp_axes)
    h, _ = _run_stack(h, params["blocks"], cfg, quant, ctx, remat=remat,
                      unroll=unroll)
    if return_hidden:
        h = _norm(h, params, "final_norm", cfg)
        return h[:, n_f:] if n_f else h
    logits = _head(params, cfg, h, quant)
    if n_f:
        logits = logits[:, n_f:]
    return logits


def prefill(params, cfg: ModelConfig, tokens: jax.Array, *,
            max_seq: int, quant: Optional[QuantConfig] = None,
            frontend_embeds: Optional[jax.Array] = None,
            k_valid: Optional[jax.Array] = None, unroll: bool = False,
            seq_shard: bool = False, dp_axes: tuple = ("data",),
            use_pallas: bool = False):
    """Returns (logits_last (B, V), caches)."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = _embed(params, cfg, tokens, positions)

    n_f = 0
    enc_out = None
    if cfg.is_encoder_decoder and frontend_embeds is not None:
        enc_out = encoder_forward(params, cfg, frontend_embeds, quant,
                                  unroll=unroll)
    elif cfg.frontend == "vision_stub" and frontend_embeds is not None:
        h, positions, n_f = _prepend_frontend(h, positions, frontend_embeds)

    ctx = Ctx(mode="prefill", positions=positions, enc_out=enc_out,
              max_seq=max_seq, k_valid=k_valid, seq_shard=seq_shard,
              dp_axes=dp_axes, use_pallas=use_pallas)
    h, caches = _run_stack(h, params["blocks"], cfg, quant, ctx,
                           unroll=unroll)
    caches["_pos"] = jnp.asarray(h.shape[1], jnp.int32)
    logits = _head(params, cfg, h[:, -1:], quant)[:, 0]
    return logits, caches


def decode_step(params, cfg: ModelConfig, token: jax.Array, caches, *,
                quant: Optional[QuantConfig] = None,
                pad_prefix: Optional[jax.Array] = None,
                unroll: bool = False, seq_shard: bool = False,
                dp_axes: tuple = ("data",), use_pallas: bool = False):
    """token: (B,) -> (logits (B, V), new caches)."""
    B = token.shape[0]
    t = caches["_pos"]
    positions = jnp.broadcast_to(t[None, None], (B, 1)).astype(jnp.int32)
    h = _embed(params, cfg, token[:, None], positions)
    ctx = Ctx(mode="decode", positions=positions, pad_prefix=pad_prefix,
              seq_shard=seq_shard, dp_axes=dp_axes, use_pallas=use_pallas)
    h, new_caches = _run_stack(h, params["blocks"], cfg, quant, ctx, caches,
                               unroll=unroll)
    new_caches["_pos"] = t + 1
    logits = _head(params, cfg, h, quant)[:, 0]
    return logits, new_caches


def generate_loop(params, cfg: ModelConfig, caches, *, num_steps: int,
                  logits0: Optional[jax.Array] = None,
                  tok0: Optional[jax.Array] = None,
                  key: Optional[jax.Array] = None,
                  sample_fn=None, eos_id: Optional[int] = None,
                  finished: Optional[jax.Array] = None,
                  quant: Optional[QuantConfig] = None,
                  pad_prefix: Optional[jax.Array] = None,
                  unroll: bool = False, seq_shard: bool = False,
                  dp_axes: tuple = ("data",),
                  use_pallas: bool = False,
                  cache_shardings: Any = None) -> Dict[str, Any]:
    """Fused on-device generation: one ``lax.scan`` whose body embeds the
    carried token, runs a decode step (which appends to the carried
    caches), samples the next token and updates per-row finished masks —
    so a whole ``num_steps``-token generation is a single dispatch instead
    of one dispatch (plus a host-side sample) per token.

    Exactly one of ``logits0`` / ``tok0`` must be given:
      * ``logits0`` (B, V): start-of-generation form.  The first emitted
        token is sampled from these prefill logits with ``key`` itself
        (un-split), then ``num_steps - 1`` decode steps run — the same key
        schedule as the per-step host loop, so outputs are bit-exact
        against it.
      * ``tok0`` (B,): continuation form (the serving loop's
        ``max_steps``-chunked scan).  ``tok0`` is the last token already
        emitted; ``num_steps`` decode steps run, each emitting one token.
        ``finished`` carries the per-row EOS state across chunks.

    ``sample_fn(logits, key) -> (B,) int32`` must be trace-safe (the
    repro.serving.sampler functions all are); it defaults to greedy.
    ``cache_shardings``: optional pytree of ``NamedSharding`` matching
    ``caches`` — applied to the carried caches inside the scan body so
    GSPMD keeps the mesh-sharded cache layout (batch on data, kv-heads
    on model) stable across steps instead of resharding or gathering a
    replicated copy mid-loop.
    ``eos_id``: when set, a row that has emitted EOS keeps stepping (the
    packed cache shares one position counter, so shapes stay static) but
    both its fed-back and emitted tokens are frozen to ``eos_id``; when
    ``None``, no masking is applied (raw per-step-loop equivalence).

    The carried caches are updated via predicated writes (see
    ``kvcache.append_token``), so under ``jax.jit(...,
    donate_argnums=...)`` the scan mutates the packed cache in place —
    no step allocates a second copy.

    Returns ``{"tokens": (B, num_steps) int32, "caches", "finished": (B,)
    bool, "last_tok": (B,) int32, "key"}``.
    """
    if (logits0 is None) == (tok0 is None):
        raise ValueError("pass exactly one of logits0 / tok0")
    if num_steps < 1:
        raise ValueError(f"num_steps must be >= 1, got {num_steps}")
    if sample_fn is None:
        sample_fn = lambda lg, k: jnp.argmax(lg, axis=-1).astype(jnp.int32)
    if key is None:
        key = jax.random.PRNGKey(0)

    def sample(lg, k, fin):
        """The next token of every row, frozen to EOS where finished."""
        with jax.named_scope("sample"):
            nxt = sample_fn(lg, k).astype(jnp.int32)
            if eos_id is not None:
                nxt = jnp.where(fin, jnp.int32(eos_id), nxt)
                fin = fin | (nxt == eos_id)
            return nxt, fin

    if logits0 is not None:
        B = logits0.shape[0]
        if finished is None:
            finished = jnp.zeros((B,), bool)
        tok, finished = sample(logits0, key, finished)
        emit_first = tok[:, None]
        n_scan = num_steps - 1
    else:
        B = tok0.shape[0]
        if finished is None:
            finished = jnp.zeros((B,), bool)
        tok = tok0.astype(jnp.int32)
        emit_first = None
        n_scan = num_steps

    def step(carry, _):
        tk, cs, k, fin = carry
        k, sk = jax.random.split(k)
        lg, cs = decode_step(params, cfg, tk, cs, quant=quant,
                             pad_prefix=pad_prefix, unroll=unroll,
                             seq_shard=seq_shard, dp_axes=dp_axes,
                             use_pallas=use_pallas)
        if cache_shardings is not None:
            cs = jax.tree.map(jax.lax.with_sharding_constraint, cs,
                              cache_shardings)
        nxt, fin = sample(lg, sk, fin)
        return (nxt, cs, k, fin), nxt

    (tok, caches, key, finished), toks = jax.lax.scan(
        step, (tok, caches, key, finished), length=n_scan)
    toks = jnp.moveaxis(toks, 0, 1)                    # (B, n_scan)
    if emit_first is not None:
        toks = jnp.concatenate([emit_first, toks], axis=1)
    return {"tokens": toks, "caches": caches, "finished": finished,
            "last_tok": tok, "key": key}


def init_decode_caches(cfg: ModelConfig, batch: int, max_seq: int,
                       enc_tokens: int = 0):
    """Allocate empty caches in the scan layout (for decode dry-runs and
    engine cold-starts).  ``enc_tokens``: cross-attn KV length."""
    n_rep, rem = cfg.pattern_layout()

    def one(kind):
        if kind == "attn":
            c = kvcache.init_cache(batch, cfg.n_kv_heads, cfg.head_dim,
                                   max_seq)
            if cfg.cross_attention:
                z = jnp.zeros((batch, enc_tokens, cfg.n_kv_heads,
                               cfg.head_dim), jnp.float32)
                return {"self": c, "enc_k": z, "enc_v": z}
            return c
        if kind == "local_attn":
            return attn_lib.init_ring_cache(
                batch, cfg.n_kv_heads, cfg.head_dim,
                min(cfg.window_size, max_seq))
        if kind == "ssd":
            return ssd_lib.init_ssd_state(batch, cfg)
        if kind == "rglru":
            return rglru_lib.init_rglru_state(batch, cfg)
        raise ValueError(kind)

    c_per = {}
    for k in cfg.block_pattern:
        c_per[k] = c_per.get(k, 0) + 1
    scan = {}
    for kind, ck in c_per.items():
        stacked = [jax.tree.map(lambda a: jnp.stack([a] * ck), one(kind))
                   for _ in range(1)]
        base = stacked[0]
        scan[kind] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_rep,) + a.shape), base)
    rem_caches = [one(kind) for kind in rem]
    return {"scan": scan, "rem": rem_caches,
            "_pos": jnp.zeros((), jnp.int32)}


__all__ = ["forward", "prefill", "decode_step", "generate_loop",
           "encoder_forward", "init_decode_caches", "Ctx", "apply_block"]
