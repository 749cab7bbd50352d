"""Parameter initialization for the unified model stack.

Params are plain nested dicts of arrays.  Blocks are stacked per *kind*
with leading axis = count-of-kind so ``jax.lax.scan`` can run the layer
stack (keeps HLO size O(1) in depth — essential for 80-layer dry-runs).

Layout convention: every weight is (in_dim, out_dim).
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.quant.int4 import pack_params


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _dense(key, fan_in, fan_out, dtype):
    scale = 1.0 / jnp.sqrt(float(fan_in))
    return (jax.random.normal(key, (fan_in, fan_out), jnp.float32)
            * scale).astype(dtype)


def _norm_params(cfg: ModelConfig, prefix: str, out: dict, dt):
    if cfg.norm_type == "layer":
        out[prefix] = jnp.ones((cfg.d_model,), dt)
        out[prefix + "_bias"] = jnp.zeros((cfg.d_model,), dt)
    else:
        init = 0.0 if cfg.zero_centered_norm else 1.0
        out[prefix] = jnp.full((cfg.d_model,), init, dt)


def init_attn_block(key, cfg: ModelConfig, cross: bool = False) -> Dict:
    dt = _dtype(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 16)
    p: Dict = {}
    _norm_params(cfg, "ln1", p, dt)
    p["wq"] = _dense(ks[0], d, cfg.q_dim, dt)
    p["wk"] = _dense(ks[1], d, cfg.kv_dim, dt)
    p["wv"] = _dense(ks[2], d, cfg.kv_dim, dt)
    p["wo"] = _dense(ks[3], cfg.q_dim, d, dt)
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dt)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dt)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dt)
    if cfg.post_block_norm:
        _norm_params(cfg, "post_ln1", p, dt)
    if cross:
        _norm_params(cfg, "ln_x", p, dt)
        p["wq_x"] = _dense(ks[4], d, cfg.q_dim, dt)
        p["wk_x"] = _dense(ks[5], d, cfg.kv_dim, dt)
        p["wv_x"] = _dense(ks[6], d, cfg.kv_dim, dt)
        p["wo_x"] = _dense(ks[7], cfg.q_dim, d, dt)
    if not cfg.mixer_only:
        _norm_params(cfg, "ln2", p, dt)
        p.update(init_mlp(ks[8], cfg))
        if cfg.post_block_norm:
            _norm_params(cfg, "post_ln2", p, dt)
    return p


def init_mlp(key, cfg: ModelConfig) -> Dict:
    dt = _dtype(cfg)
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 8)
    p: Dict = {}
    if cfg.n_experts:
        E = cfg.n_experts
        p["w_router"] = _dense(ks[0], d, E, jnp.float32)
        p["w_gate"] = jnp.stack(
            [_dense(k, d, ff, dt) for k in jax.random.split(ks[1], E)])
        p["w_up"] = jnp.stack(
            [_dense(k, d, ff, dt) for k in jax.random.split(ks[2], E)])
        p["w_down"] = jnp.stack(
            [_dense(k, ff, d, dt) for k in jax.random.split(ks[3], E)])
        if cfg.shared_expert:
            p["w_shared_gate"] = _dense(ks[4], d, ff, dt)
            p["w_shared_up"] = _dense(ks[5], d, ff, dt)
            p["w_shared_down"] = _dense(ks[6], ff, d, dt)
    elif cfg.mlp_style == "gated":
        p["w_gate"] = _dense(ks[0], d, ff, dt)
        p["w_up"] = _dense(ks[1], d, ff, dt)
        p["w_down"] = _dense(ks[2], ff, d, dt)
    else:
        p["w_up"] = _dense(ks[0], d, ff, dt)
        p["b_up"] = jnp.zeros((ff,), dt)
        p["w_down"] = _dense(ks[1], ff, d, dt)
        p["b_down"] = jnp.zeros((d,), dt)
    return p


def init_ssd_block(key, cfg: ModelConfig) -> Dict:
    dt = _dtype(cfg)
    d, di = cfg.d_model, cfg.ssm_inner
    H, N, G = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_groups
    ks = jax.random.split(key, 6)
    p: Dict = {}
    _norm_params(cfg, "ln1", p, dt)
    p["w_in"] = _dense(ks[0], d, 2 * di + 2 * G * N + H, dt)
    p["conv_w"] = (jax.random.normal(ks[1], (4, di + 2 * G * N), jnp.float32)
                   * 0.1).astype(dt)
    p["A_log"] = jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32)
    p["D"] = jnp.ones((H,), jnp.float32)
    # dt_bias: inverse-softplus of uniform(1e-3, 0.1)
    u = jnp.linspace(1e-3, 0.1, H)
    p["dt_bias"] = jnp.log(jnp.expm1(u)).astype(jnp.float32)
    p["norm"] = jnp.ones((di,), dt)
    p["w_out"] = _dense(ks[2], di, d, dt)
    return p


def init_rglru_block(key, cfg: ModelConfig) -> Dict:
    dt = _dtype(cfg)
    d, w, nb = cfg.d_model, cfg.lru_width, cfg.lru_blocks
    bs = w // nb
    ks = jax.random.split(key, 10)
    p: Dict = {}
    _norm_params(cfg, "ln1", p, dt)
    p["w_in_x"] = _dense(ks[0], d, w, dt)
    p["w_in_gate"] = _dense(ks[1], d, w, dt)
    p["conv_w"] = (jax.random.normal(ks[2], (4, w), jnp.float32)
                   * 0.1).astype(dt)
    p["w_a"] = (jax.random.normal(ks[3], (nb, bs, bs), jnp.float32)
                / jnp.sqrt(float(bs))).astype(dt)
    p["w_x"] = (jax.random.normal(ks[4], (nb, bs, bs), jnp.float32)
                / jnp.sqrt(float(bs))).astype(dt)
    p["b_a"] = jnp.zeros((w,), jnp.float32)
    p["b_x"] = jnp.zeros((w,), jnp.float32)
    # sigmoid(lam)^8 in ~(0.9, 0.999)
    a_target = jnp.linspace(0.987, 0.9999, w)
    p["lam"] = jnp.log(a_target / (1 - a_target)).astype(jnp.float32)
    p["w_out"] = _dense(ks[5], w, d, dt)
    if not cfg.mixer_only:
        _norm_params(cfg, "ln2", p, dt)
        p.update(init_mlp(ks[6], cfg))
    return p


_KIND_INIT = {
    "attn": init_attn_block,
    "local_attn": init_attn_block,
    "ssd": init_ssd_block,
    "rglru": init_rglru_block,
}


def init_block(key, cfg: ModelConfig, kind: str, cross: bool = False):
    if kind in ("attn", "local_attn"):
        return init_attn_block(key, cfg, cross=cross)
    return _KIND_INIT[kind](key, cfg)


def _stack_blocks(key, cfg: ModelConfig, kind: str, count: int,
                  cross: bool = False):
    keys = jax.random.split(key, count)
    blocks = [init_block(k, cfg, kind, cross) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


def _top_params(cfg: ModelConfig, ks) -> Dict:
    """Every leaf outside the stacked blocks: embeddings, head, norms."""
    dt = _dtype(cfg)
    params: Dict = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dt),
    }
    _norm_params(cfg, "final_norm", params, dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense(ks[1], cfg.d_model, cfg.vocab_size, dt)
    if cfg.is_encoder_decoder:
        _norm_params(_encoder_view(cfg), "enc_final_norm", params, dt)
    return params


def _block_stacks(cfg: ModelConfig, ks):
    """(tree path, block config, kind, count, key, cross) of each stack of
    blocks — the one place that assigns keys to layers, so the whole-tree
    and the layer-by-layer initializers draw the same weights."""
    kinds = sorted(cfg.kind_counts().items())
    stacks = [(("blocks", kind), cfg, kind, count, kk, cfg.cross_attention)
              for (kind, count), kk in zip(
                  kinds, jax.random.split(ks[2], len(kinds)))]
    if cfg.is_encoder_decoder:
        stacks.append((("enc_blocks",), _encoder_view(cfg), "attn",
                       cfg.encoder_layers, ks[3], False))
    return stacks


def _put(tree: Dict, path: tuple, value) -> None:
    for name in path[:-1]:
        tree = tree.setdefault(name, {})
    tree[path[-1]] = value


def init_params(cfg: ModelConfig, key: jax.Array) -> Dict:
    """Full parameter tree (training, tests, accuracy runs).  Use
    jax.eval_shape(init_params, cfg, key) (with cfg static via partial)
    for allocation-free dry-runs.  Serving builds its INT4 weights with
    :func:`init_packed_params`, which never holds this tree."""
    ks = jax.random.split(key, 8)
    params = _top_params(cfg, ks)
    params["blocks"] = {}
    for path, bcfg, kind, count, kk, cross in _block_stacks(cfg, ks):
        _put(params, path, _stack_blocks(kk, bcfg, kind, count, cross))
    return params


def _drop_lead(spec):
    """Per-layer spec of a layer-stacked leaf (the layer axis is never
    sharded)."""
    from jax.sharding import PartitionSpec as P
    if len(spec) and spec[0] is not None:
        raise ValueError(f"layer axis of a stacked leaf is sharded: {spec}")
    return P(*tuple(spec)[1:])


def init_packed_params(cfg: ModelConfig, key: jax.Array,
                       mesh=None) -> Dict:
    """INT4-packed serving weights, drawn and packed one layer at a time.

    Equal, leaf for leaf, to ``pack_params(init_params(cfg, key))``, but
    the full-precision tree never exists: each layer is drawn from its
    key and packed by the same op-by-op code as that pair (so the bits
    agree), then written into its slot of a preallocated stacked buffer
    (donated, so updated in place) before the next layer is drawn.  At
    Llama-3.1-8B width the bf16 tree is ~16 GB and the packed one
    ~5 GB, so this is what lets one 16 GB chip hold the model.

    ``mesh``: the stacked buffers are allocated in their
    :func:`repro.distributed.sharding.param_pspecs` shardings and each
    packed layer is placed into its shards as soon as it is packed; only
    the layer being drawn exists unsharded, on the default device."""
    from jax.sharding import NamedSharding

    def named(spec):
        return None if mesh is None else NamedSharding(mesh, spec)

    ks = jax.random.split(key, 8)
    stacks = _block_stacks(cfg, ks)

    def draw(bcfg, kind, cross, k):
        return pack_params(init_block(k, bcfg, kind, cross))

    abstract = jax.eval_shape(
        lambda ks: pack_params(_top_params(cfg, ks)), ks)
    for path, bcfg, kind, count, kk, cross in stacks:
        layer = jax.eval_shape(partial(draw, bcfg, kind, cross), kk)
        _put(abstract, path, jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((count,) + a.shape, a.dtype),
            layer))
    if mesh is None:
        specs = jax.tree.map(lambda _: None, abstract)
    else:
        from repro.distributed.sharding import param_pspecs
        specs = param_pspecs(cfg, abstract, mesh)
    is_spec = lambda x: x is None or isinstance(x, jax.sharding.PartitionSpec)

    params = {}
    for name, leaf in pack_params(_top_params(cfg, ks)).items():
        params[name] = jax.device_put(leaf, jax.tree.map(
            named, specs[name], is_leaf=is_spec))
    for path, bcfg, kind, count, kk, cross in stacks:
        st_abs, st_spec = abstract, specs
        for name in path:
            st_abs, st_spec = st_abs[name], st_spec[name]
        st_sh = jax.tree.map(named, st_spec, is_leaf=is_spec)
        layer_sh = jax.tree.map(
            lambda s: named(None if s is None else _drop_lead(s)), st_spec,
            is_leaf=is_spec)
        buf = jax.jit(lambda: jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype), st_abs),
            out_shardings=st_sh)()
        write = jax.jit(
            lambda buf, x, i: jax.tree.map(
                lambda b, a: jax.lax.dynamic_update_index_in_dim(
                    b, a, i, 0), buf, x),
            donate_argnums=0, out_shardings=st_sh)
        for i, k in enumerate(jax.random.split(kk, count)):
            layer = jax.device_put(draw(bcfg, kind, cross, k), layer_sh)
            buf = write(buf, layer, i)
        _put(params, path, buf)
    return params


def _encoder_view(cfg: ModelConfig) -> ModelConfig:
    """Encoder blocks: bidirectional, no cross-attn, plain MLP, no MoE."""
    import dataclasses
    return dataclasses.replace(cfg, cross_attention=False, n_experts=0,
                               mixer_only=False)


def abstract_params(cfg: ModelConfig):
    """ShapeDtypeStruct tree without any allocation (dry-run path)."""
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(partial(init_params, cfg), key)


__all__ = ["init_params", "init_packed_params", "abstract_params",
           "init_block", "init_mlp", "_encoder_view"]
