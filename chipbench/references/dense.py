"""Plain float32 reference of a dense decoder served with Harmonia numerics.

It imports nothing of the program and takes nothing the program made.
From the seed it draws the weights itself (the configuration's
initializer: N(0, 1/fan_in) linears and N(0, 0.02^2) embeddings drawn in
float32 and stored in ``param_dtype``, with the key schedule of the
repository's seeded initializer), rounds every linear weight to symmetric
INT4 in groups of 128 along its input, and then runs the model one layer
at a time in float32 under ``default_matmul_precision("highest")``.

The norm scales and shifts and the biases the configuration states come
from :func:`affine_params`, drawn from the seed on a stream of their own;
``run.py`` puts the same draw into the served weights in place of the
initializer's ones and zeros, so that the comparison sees the norm and
bias paths.

The numerics are the ones the configuration states (Harmonia: BFP
groups of 32 with a 5-bit shared exponent and truncated mantissas):

* every linear input is BFP at ``act_bits``, except the tied head's;
* Q and K are BFP at ``act_bits`` along the head dimension, fresh V along
  the token dimension, and the post-softmax scores at ``score_bits``
  along the key dimension;
* the prompt attends its own fresh Q/K/V (prefill);
* each served position attends the cache as it stands when that token is
  decoded: keys less the online offsets of the first 32 prompt keys
  (the top-16 channels per head, half the signed value of largest
  magnitude), at ``kv_high_bits`` for the first ``init_tokens`` and the
  last ``local_tokens`` positions and at ``kv_bits`` in between; values
  in 32-token groups at ``kv_high_bits`` for group 0 and the two newest
  complete groups, at ``kv_bits`` for the others, and the incomplete
  newest group at ``kv_high_bits`` with the exponent of the tokens it
  holds so far.

Departures of the program it leaves out: the program computes in
bfloat16 (this computes in float32), and its prefill attention keeps the
scores in float32 above 2048 keys (this quantizes them, as Harmonia
does).  Both are inside the limit.  Like the program, it has no bias on
the attention output projection, which the published StarCoder2 has
(the configuration lists ``use_bias`` as changed).

``served_logits`` returns, for each request, the reference's logits at
every position that predicted a served token.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

GROUP = 32
EXP_MIN, EXP_MAX = -14, 15
Q_BLOCK = 512                       # prompt query rows per attention block
PAD_TO = 128                        # sequence lengths are rounded up to this


# -- BFP and INT4 ----------------------------------------------------------

def _pow2(e):
    return jax.lax.bitcast_convert_type(
        (e.astype(jnp.int32) + 127) << 23, jnp.float32)


def _exponent(absmax):
    bits = jax.lax.bitcast_convert_type(absmax.astype(jnp.float32),
                                        jnp.int32)
    e = ((bits >> 23) & 0xFF) - 127
    return jnp.clip(jnp.where(absmax > 0, e, EXP_MIN), EXP_MIN, EXP_MAX)


def _bfp_groups(g, bits, absmax):
    """Quantize ``g`` (..., GROUP) with the shared exponent of
    ``absmax`` (..., 1): truncated ``bits``-bit signed mantissas."""
    step = _pow2(_exponent(absmax) - (bits - 2))
    lim = 2.0 ** (bits - 1) - 1
    return jnp.clip(jnp.trunc(g / step), -lim, lim) * step


def bfp(x, bits, axis=-1):
    """Quantize-dequantize ``x`` in groups of 32 along ``axis`` (a length
    that is not a multiple of 32 is padded with zeros)."""
    x = jnp.moveaxis(x, axis, -1)
    n = x.shape[-1]
    pad = -n % GROUP
    g = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    g = g.reshape(g.shape[:-1] + (-1, GROUP))
    q = _bfp_groups(g, bits, jnp.max(jnp.abs(g), -1, keepdims=True))
    return jnp.moveaxis(q.reshape(q.shape[:-2] + (-1,))[..., :n], -1, axis)


def int4(w, group):
    """Symmetric INT4 in groups of ``group`` along the input (axis 0)."""
    wf = w.astype(jnp.float32)
    g = wf.reshape(wf.shape[0] // group, group, -1)
    scale = jnp.maximum(jnp.max(jnp.abs(g), axis=1) / 7.0, 1e-8)[:, None]
    q = jnp.clip(jnp.round(g / scale), -7.0, 7.0)
    return (q * scale).reshape(wf.shape)


# -- weights, drawn from the seed -----------------------------------------

def _dense(key, fan_in, fan_out, dtype):
    scale = 1.0 / jnp.sqrt(float(fan_in))
    return (jax.random.normal(key, (fan_in, fan_out), jnp.float32)
            * scale).astype(dtype)


@partial(jax.jit, static_argnums=(0,))
def _top_weights(mh, key):
    m = dict(mh)
    dt = jnp.dtype(m["param_dtype"])
    d, V = m["d_model"], m["vocab_size"]
    ks = jax.random.split(key, 8)
    w = {"embed": (jax.random.normal(ks[0], (V, d), jnp.float32)
                   * 0.02).astype(dt).astype(jnp.float32)}
    if not m["tie_embeddings"]:
        w["head"] = int4(_dense(ks[1], d, V, dt), m["weight_group"])
    return w


def _layer_keys(m, key):
    ks = jax.random.split(key, 8)
    kk = jax.random.split(ks[2], 1)[0]       # the one kind of block
    return jax.random.split(kk, m["n_layers"])


@partial(jax.jit, static_argnums=(0,))
def _layer_weights(mh, key):
    m = dict(mh)
    dt = jnp.dtype(m["param_dtype"])
    d, ff = m["d_model"], m["d_ff"]
    qd, kvd = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    g = m["weight_group"]
    ks = jax.random.split(key, 16)
    w = {"wq": int4(_dense(ks[0], d, qd, dt), g),
         "wk": int4(_dense(ks[1], d, kvd, dt), g),
         "wv": int4(_dense(ks[2], d, kvd, dt), g),
         "wo": int4(_dense(ks[3], qd, d, dt), g)}
    mk = jax.random.split(ks[8], 8)
    if m["mlp_style"] == "gated":
        w["w_gate"] = int4(_dense(mk[0], d, ff, dt), g)
        w["w_up"] = int4(_dense(mk[1], d, ff, dt), g)
        w["w_down"] = int4(_dense(mk[2], ff, d, dt), g)
    else:
        w["w_up"] = int4(_dense(mk[0], d, ff, dt), g)
        w["w_down"] = int4(_dense(mk[1], ff, d, dt), g)
    return w


AFFINE_STREAM = 0x5AFF1E      # folded into the seed's key for affine_params
SCALE_STD = 0.25              # norm scales: 1 + N(0, SCALE_STD^2)
SHIFT_STD = 0.1               # biases and norm shifts: N(0, SHIFT_STD^2)


def _affine_shapes(m):
    """(name, size, is_scale) of each norm and bias parameter a layer
    holds, by the names the program's tree gives them."""
    d, ff = m["d_model"], m["d_ff"]
    qd, kvd = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    out = []
    for norm in ("ln1", "ln2"):
        out.append((norm, d, True))
        if m["norm_type"] == "layer":
            out.append((norm + "_bias", d, False))
    if m["qkv_bias"]:
        out += [("bq", qd, False), ("bk", kvd, False), ("bv", kvd, False)]
    if m["mlp_style"] == "plain":
        out += [("b_up", ff, False), ("b_down", d, False)]
    return out


@partial(jax.jit, static_argnums=(0,))
def _affine(mh, key):
    m = dict(mh)
    dt = jnp.dtype(m["param_dtype"])
    L, d = m["n_layers"], m["d_model"]
    top = [("final_norm", d, True)]
    if m["norm_type"] == "layer":
        top.append(("final_norm_bias", d, False))
    key = jax.random.fold_in(key, AFFINE_STREAM)
    out = {}
    for j, (part, names, lead) in enumerate(
            (("top", top, ()), ("layers", _affine_shapes(m), (L,)))):
        out[part] = {}
        for i, (name, size, is_scale) in enumerate(names):
            k = jax.random.fold_in(jax.random.fold_in(key, j), i)
            z = jax.random.normal(k, lead + (size,), jnp.float32)
            v = 1.0 + SCALE_STD * z if is_scale else SHIFT_STD * z
            out[part][name] = v.astype(dt)
    return out


def affine_params(model: dict, key) -> dict:
    """The norm scales and shifts and the biases of ``model``, drawn
    from ``key`` and stored in ``param_dtype``: ``{"top": {name: (d,)},
    "layers": {name: (n_layers, size)}}``, named as in the program's tree
    (``final_norm``, ``ln1``, ``ln2``, their ``_bias`` under LayerNorm,
    ``bq``/``bk``/``bv`` with QKV biases, ``b_up``/``b_down`` in a plain
    MLP).  Scales are 1 + N(0, 0.25^2), shifts and biases N(0, 0.1^2)."""
    return _affine(tuple(sorted(model.items())), key)


# -- layers ----------------------------------------------------------------

def _norm(m, x, w, name):
    if m["norm_type"] == "layer":
        x = x - jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    x = x * jax.lax.rsqrt(var + m["norm_eps"]) * w[name]
    return x + w[name + "_bias"] if name + "_bias" in w else x


def _bias(w, name):
    return w[name] if name in w else 0.0


def _linear(m, x, w):
    return bfp(x, m["act_bits"]) @ w


def _act(m, x):
    if m["act_fn"] == "silu":
        return jax.nn.silu(x)
    if m["act_fn"] == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"activation {m['act_fn']!r}")


def _rope(m, x, pos):
    hd = x.shape[-1]
    freqs = 1.0 / (m["rope_theta"] ** (jnp.arange(0, hd, 2, jnp.float32)
                                       / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _online_offsets(k_init, top_k):
    """k_init: (32, Hkv, hd) -> (Hkv, hd)."""
    mag = jnp.max(jnp.abs(k_init), axis=0)
    idx = jnp.argmax(jnp.abs(k_init), axis=0)
    val = jnp.take_along_axis(k_init, idx[None], axis=0)[0]
    thresh = jax.lax.top_k(mag, top_k)[0][..., -1:]
    return jnp.where(mag >= thresh, 0.5 * val, 0.0)


def _softmax_bfp(m, s, mask):
    s = jnp.where(mask, s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
    p = p / jnp.sum(p, -1, keepdims=True)
    return bfp(p, m["score_bits"], axis=-1)


def _prefill_attention(m, q, k, v, P):
    """Rows [0, P) attending keys [0, P): fresh BFP Q/K/V."""
    Hkv, hd = k.shape[1], k.shape[2]
    rep = q.shape[1] // Hkv
    ab = m["act_bits"]
    q8 = bfp(q[:P], ab).reshape(P, Hkv, rep, hd)
    k8 = bfp(k[:P], ab)
    v8 = bfp(v[:P], ab, axis=0)
    scale = 1.0 / np.sqrt(hd)
    outs = []
    for i0 in range(0, P, Q_BLOCK):
        rows = jnp.arange(i0, min(P, i0 + Q_BLOCK))
        s = jnp.einsum("igrd,jgd->grij", q8[rows], k8) * scale
        p = _softmax_bfp(m, s, rows[:, None] >= jnp.arange(P)[None, :])
        outs.append(jnp.einsum("grij,jgd->igrd", p, v8))
    return jnp.concatenate(outs).reshape(P, Hkv * rep, hd)


def _decode_attention(m, q, k, v, P, T):
    """Rows [P, T) attending the packed cache as it stands after the
    row's token is appended (length L = row + 1)."""
    Hkv, hd = k.shape[1], k.shape[2]
    rep = q.shape[1] // Hkv
    it, lt = m["init_tokens"], m["local_tokens"]
    hb, lb = m["kv_high_bits"], m["kv_bits"]
    n = T - P
    rows = jnp.arange(P, T)
    L = rows + 1
    j = jnp.arange(T)
    off = _online_offsets(k[:min(GROUP, P)], m["online_topk"])
    kc = k - off[None]
    q8 = bfp(q[P:], m["act_bits"]).reshape(n, Hkv, rep, hd)
    scale = 1.0 / np.sqrt(hd)
    s_hi = jnp.einsum("igrd,jgd->grij", q8, bfp(kc, hb)) * scale
    s_lo = jnp.einsum("igrd,jgd->grij", q8, bfp(kc, lb)) * scale
    k_hi = (j[None] < it) | (j[None] >= L[:, None] - lt)
    p = _softmax_bfp(m, jnp.where(k_hi, s_hi, s_lo),
                     j[None] <= rows[:, None])
    # values: complete groups from the 8- or 4-bit regions
    gj = (j // GROUP)[None]
    cg = (L // GROUP)[:, None]
    v_hi = (gj < cg) & ((gj == 0) | (gj >= cg - 2))
    v_lo = (gj < cg) & (gj >= 1) & (gj < cg - 2)
    out = (jnp.einsum("grij,jgd->igrd", jnp.where(v_hi, p, 0.0),
                      bfp(v, hb, axis=0))
           + jnp.einsum("grij,jgd->igrd", jnp.where(v_lo, p, 0.0),
                        bfp(v, lb, axis=0)))
    # the incomplete newest group: the tokens [32*cg, L) it holds, at the
    # high precision with the exponent of those tokens alone
    base = (L // GROUP) * GROUP
    r = L - base
    vp = jnp.pad(v, ((0, GROUP), (0, 0), (0, 0)))
    win = jax.vmap(lambda b: jax.lax.dynamic_slice_in_dim(vp, b, GROUP))(
        base)                                              # (n, 32, Hkv, hd)
    held = (jnp.arange(GROUP)[None] < r[:, None])[..., None, None]
    win = jnp.where(held, win, 0.0)
    absmax = jnp.max(jnp.abs(win), axis=1, keepdims=True)
    win_q = _bfp_groups(jnp.moveaxis(win, 1, -1), hb,
                        jnp.moveaxis(absmax, 1, -1))
    win_q = jnp.moveaxis(win_q, -1, 1)
    pp = jnp.pad(p, ((0, 0), (0, 0), (0, 0), (0, GROUP)))
    pw = jax.vmap(lambda pi, b: jax.lax.dynamic_slice_in_dim(pi, b, GROUP,
                                                             axis=-1),
                  in_axes=(2, 0))(pp, base)                # (n, g, r, 32)
    pw = jnp.where(held[:, None, None, :, 0, 0], pw, 0.0)
    out = out + jnp.einsum("igrm,imgd->igrd", pw, win_q)
    return out.reshape(n, Hkv * rep, hd)


@partial(jax.jit, static_argnums=(0, 3))
def _layer(mh, h, w, P):
    """One block over one request's hidden states h: (T, d)."""
    m = dict(mh)
    T = h.shape[0]
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    x = _norm(m, h, w, "ln1")
    pos = jnp.arange(T)
    q = _linear(m, x, w["wq"]) + _bias(w, "bq")
    k = _linear(m, x, w["wk"]) + _bias(w, "bk")
    v = _linear(m, x, w["wv"]) + _bias(w, "bv")
    q = _rope(m, q.reshape(T, H, hd), pos)
    k = _rope(m, k.reshape(T, Hkv, hd), pos)
    v = v.reshape(T, Hkv, hd)
    attn = _prefill_attention(m, q, k, v, P)
    if T > P:
        attn = jnp.concatenate([attn, _decode_attention(m, q, k, v, P, T)])
    h = h + _linear(m, attn.reshape(T, H * hd), w["wo"])
    x = _norm(m, h, w, "ln2")
    if m["mlp_style"] == "gated":
        a = _act(m, _linear(m, x, w["w_gate"])) * _linear(m, x, w["w_up"])
        return h + _linear(m, a, w["w_down"])
    a = _act(m, _linear(m, x, w["w_up"]) + _bias(w, "b_up"))
    return h + _linear(m, a, w["w_down"]) + _bias(w, "b_down")


@partial(jax.jit, static_argnums=(0,))
def _logits(mh, h, top):
    m = dict(mh)
    x = _norm(m, h, top, "final_norm")
    if m["tie_embeddings"]:
        return x @ top["embed"].T
    return _linear(m, x, top["head"])


def served_logits(model: dict, numerics: dict, key, requests):
    """Reference logits for each request.

    ``model``: sizes and kinds (``n_layers``, ``d_model``, ``n_heads``,
    ``n_kv_heads``, ``head_dim``, ``d_ff``, ``vocab_size``,
    ``rope_theta``, ``norm_type``, ``norm_eps``, ``act_fn``,
    ``mlp_style``, ``qkv_bias``, ``tie_embeddings``, ``param_dtype``);
    ``numerics``: ``act_bits``, ``score_bits``, ``kv_bits``,
    ``kv_high_bits``, ``init_tokens``, ``local_tokens``, ``online_topk``,
    ``weight_group``;
    ``key``: the weights' PRNG key; ``requests``: ``(prompt_ids,
    served_ids)`` pairs.

    Returns one ``(len(served_ids), vocab)`` float32 array per request:
    row ``t`` is the reference's logits after the prompt and the first
    ``t`` served tokens."""
    m = dict(model, **numerics)
    mh = tuple(sorted(m.items()))
    # every sequence is right-padded to one length, so that one program
    # serves them all: causal attention never reads a later position
    length = -(-max(len(p) + len(s) - 1 for p, s in requests) // PAD_TO) \
        * PAD_TO
    seqs, prompt_lens = [], []
    for prompt, served in requests:
        seq = list(prompt) + list(served[:-1])
        seqs.append(np.asarray(seq + [0] * (length - len(seq)), np.int32))
        prompt_lens.append(len(prompt))
    with jax.default_matmul_precision("highest"):
        aff = jax.tree.map(lambda a: a.astype(jnp.float32),
                           affine_params(model, key))
        top = dict(_top_weights(mh, key), **aff["top"])
        hs = [top["embed"][jnp.asarray(s)] for s in seqs]
        for i, lk in enumerate(_layer_keys(m, key)):
            w = dict(_layer_weights(mh, lk),
                     **{n: a[i] for n, a in aff["layers"].items()})
            hs = [_layer(mh, h, w, P) for h, P in zip(hs, prompt_lens)]
            del w
        out = [np.asarray(_logits(mh, h[P - 1:], top))[:len(s)]
               for h, P, (_, s) in zip(hs, prompt_lens, requests)]
    return out
