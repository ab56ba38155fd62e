"""Shared model building blocks: norms, rotary, GQA attention (chunked
online computation for 32k prefill), gated MLPs, embeddings.

All params are ``sharding.Param(value, logical_axes)`` leaves; all functions
are pure.  Compute dtype follows cfg.dtype, accumulation/softmax in f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import approx
from repro.kernels import ops
from repro.parallel.sharding import Param, constrain

# ---------------------------------------------------------------------------
# Param helpers
# ---------------------------------------------------------------------------


def _split(key, n):
    return jax.random.split(key, n)


def dense_init(key, d_in, d_out, axes, bias=False, dtype=jnp.float32,
               scale=None):
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": Param(jax.random.normal(key, (d_in, d_out), dtype) * scale,
                    axes)}
    if bias:
        p["b"] = Param(jnp.zeros((d_out,), dtype), (axes[1],))
    return p


def dense(p, x, compute_dtype=None):
    """Apply-time: p is a PLAIN value tree (Params stripped by registry).

    When ``p`` carries a ``w_scale`` sibling (cfg.weight_dtype="int8"),
    ``w`` holds per-output-channel int8 codes and is dequantized HERE —
    at the point of consumption.  The megakernel bodies call this inside
    their Pallas launch, so for the cross-layer decode path the int8 ->
    f32 expansion happens in-kernel on the grid-local (per-layer) weight
    block; the XLA reference and prefill paths run the identical scale
    multiply, keeping all step impls on one scale math."""
    w = p["w"]
    if "w_scale" in p:
        w = w.astype(jnp.float32) * p["w_scale"]
    if compute_dtype is not None:
        w = w.astype(compute_dtype)
        x = x.astype(compute_dtype)
    # f32 accumulation, rounded once to the operand dtype: what XLA does
    # for a bf16 matmul anyway, and what a Pallas TPU body (the
    # megakernels) must ask for explicitly
    y = jnp.matmul(x, w, preferred_element_type=jnp.float32).astype(
        jnp.result_type(x, w))
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(cfg, key=None):
    d = cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": Param(jnp.ones((d,), jnp.float32), ("embed",))}
    if cfg.norm == "ln":
        return {"scale": Param(jnp.ones((d,), jnp.float32), ("embed",)),
                "bias": Param(jnp.zeros((d,), jnp.float32), ("embed",))}
    if cfg.norm == "ln_nonparam":          # olmo: no affine params
        return {}
    raise ValueError(cfg.norm)


def apply_norm(cfg, p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        xf = xf * p["scale"]
    else:
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        xf = (xf - mu) * jax.lax.rsqrt(var + eps)
        if cfg.norm == "ln":
            xf = xf * p["scale"] + p["bias"]
    return xf.astype(x.dtype)


def group_norm(x, scale, n_groups, eps=1e-5):
    """x (..., d); per-group normalization (xLSTM head norm)."""
    shp = x.shape
    xf = x.astype(jnp.float32).reshape(*shp[:-1], n_groups, -1)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    xf = ((xf - mu) * jax.lax.rsqrt(var + eps)).reshape(shp)
    return (xf * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope(x, positions, theta):
    """x (b, l, h, dh); positions (b, l) int32."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = (theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs   # (b, l, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], -1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA + rotary + optional bias), cache-aware
# ---------------------------------------------------------------------------

def attention_init(cfg, key):
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = _split(key, 4)
    return {
        "wq": dense_init(ks[0], d, hq * dh, ("embed", "heads"),
                         bias=cfg.qkv_bias),
        "wk": dense_init(ks[1], d, hkv * dh, ("embed", "kv"),
                         bias=cfg.qkv_bias),
        "wv": dense_init(ks[2], d, hkv * dh, ("embed", "kv"),
                         bias=cfg.qkv_bias),
        "wo": dense_init(ks[3], hq * dh, d, ("heads", "embed")),
    }


def _grouped_scores(q, k, scale):
    """q (b,lq,hkv,rep,dh), k (b,lk,hkv,dh) -> (b,hkv,rep,lq,lk) f32.

    Inputs stay in their storage dtype (bf16): the MXU accumulates in f32
    via preferred_element_type — half the stream bytes and bf16 cotangents
    (EXPERIMENTS.md §Perf Q3)."""
    return jnp.einsum("bqgrd,bkgd->bgrqk", q, k,
                      preferred_element_type=jnp.float32) * scale


def chunked_causal_attention(q, k, v, chunk=512, q_offset=0):
    """Memory-bounded causal attention: scan over query chunks, scores kept
    at (chunk x lk), grouped-head einsums (no kv repetition).  Differentiable
    and GSPMD-friendly; used for prefill_32k.  q (b,lq,hq,dh),
    k/v (b,lk,hkv,dh)."""
    b, lq, hq, dh = q.shape
    lk = k.shape[1]
    hkv = k.shape[2]
    rep = hq // hkv
    scale = dh ** -0.5
    chunk = min(chunk, lq)
    pad = (-lq) % chunk
    nq = (lq + pad) // chunk
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qg = qp.reshape(b, nq, chunk, hkv, rep, dh).swapaxes(0, 1)
    kcols = jnp.arange(lk)

    def one(ci, qc):
        s = _grouped_scores(qc, k, scale)                  # (b,g,r,cq,lk)
        rows = q_offset + ci * chunk + jnp.arange(chunk)
        mask = rows[:, None] >= kcols[None, :]
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype)

    one_ck = jax.checkpoint(one, static_argnums=())

    def body(_, inp):
        ci, qc = inp
        return None, one_ck(ci, qc)

    _, outs = jax.lax.scan(body, None, (jnp.arange(nq), qg))
    o = outs.swapaxes(0, 1).reshape(b, nq * chunk, hq, dh)
    return o[:, :lq]


def decode_attention(q, k_cache, v_cache, pos):
    """Single-token attention over a cache.  q (b,1,hq,dh);
    k/v_cache (b,S,hkv,dh); pos (b,) index of the query token."""
    b, _, hq, dh = q.shape
    hkv = k_cache.shape[2]
    rep = hq // hkv
    scale = dh ** -0.5
    qg = q.reshape(b, 1, hkv, rep, dh)
    s = _grouped_scores(qg, k_cache, scale)            # (b,g,r,1,S)
    cols = jnp.arange(k_cache.shape[1])
    mask = cols[None, :] <= pos[:, None]               # (b,S)
    s = jnp.where(mask[:, None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, 1, hq, dh).astype(q.dtype)


def _kv_quant(t):
    """(b, l, hkv*dh) -> int8 payload + per-(b,l) f32 absmax scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1,
                                keepdims=True), 1e-6) / 127.0
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _kv_dequant(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def attention_apply(cfg, p, x, positions, cache=None, pos=None,
                    return_kv=False):
    """cache: dict(k (b,S,hkv*dh), v (b,S,hkv*dh)) flat-layout (+ k_scale /
    v_scale when cfg.kv_cache_dtype == "int8"); pos (b,).
    return_kv: full-seq path also returns the rotated (k, v) flat tensors
    (prefill cache fill)."""
    b, l, d = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cdt = x.dtype
    q = dense(p["wq"], x, cdt).reshape(b, l, hq, dh)
    k = dense(p["wk"], x, cdt).reshape(b, l, hkv, dh)
    v = dense(p["wv"], x, cdt).reshape(b, l, hkv, dh)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    new_cache = None
    if cache is not None:
        # write the new kv at per-batch position pos, then attend over cache
        S = cache["k"].shape[1]
        onehot = jnp.arange(S)[None, :] == pos[:, None]      # (b,S)
        quantized = cfg.kv_cache_dtype == "int8"
        if quantized:
            kq, ks = _kv_quant(k.reshape(b, l, hkv * dh))   # (b,1,D),(b,1,1)
            vq, vs = _kv_quant(v.reshape(b, l, hkv * dh))
            kcq = jnp.where(onehot[..., None], kq, cache["k"])
            vcq = jnp.where(onehot[..., None], vq, cache["v"])
            kss = jnp.where(onehot[..., None], ks, cache["k_scale"])
            vss = jnp.where(onehot[..., None], vs, cache["v_scale"])
            kc = _kv_dequant(kcq, kss, cdt).reshape(b, S, hkv, dh)
            vc = _kv_dequant(vcq, vss, cdt).reshape(b, S, hkv, dh)
            new_cache = {"k": kcq, "v": vcq,
                         "k_scale": kss, "v_scale": vss}
        else:
            kc = jnp.where(onehot[..., None, None],
                           k.astype(cache["k"].dtype),
                           cache["k"].reshape(b, S, hkv, dh))
            vc = jnp.where(onehot[..., None, None],
                           v.astype(cache["v"].dtype),
                           cache["v"].reshape(b, S, hkv, dh))
            new_cache = {"k": kc.reshape(b, S, hkv * dh),
                         "v": vc.reshape(b, S, hkv * dh)}
        o = decode_attention(q, kc, vc, pos)
    elif cfg.attn_impl == "pallas":
        from repro.kernels import flash_attention as fk
        o = fk.flash_attention(q, k, v, causal=True)
    elif cfg.attn_impl == "ref":
        o = ops.attention(q, k, v, causal=True, impl="xla")
    else:
        o = chunked_causal_attention(q, k, v, chunk=cfg.attn_chunk)
    o = constrain(o, "act_batch", "act_seq", "act_heads", None)
    out = dense(p["wo"], o.reshape(b, l, hq * dh), cdt)
    if return_kv and cache is None:
        new_cache = {"k": k.reshape(b, l, hkv * dh),
                     "v": v.reshape(b, l, hkv * dh)}
    return out, new_cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(cfg, key, d_ff=None, d_in=None):
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    ks = _split(key, 3)
    if cfg.mlp == "swiglu":
        return {"w1": dense_init(ks[0], d, f, ("embed", "ffn")),
                "w3": dense_init(ks[1], d, f, ("embed", "ffn")),
                "w2": dense_init(ks[2], f, d, ("ffn", "embed"))}
    return {"w1": dense_init(ks[0], d, f, ("embed", "ffn")),
            "w2": dense_init(ks[2], f, d, ("ffn", "embed"))}


def mlp_apply(cfg, p, x):
    cdt = x.dtype
    if cfg.mlp == "swiglu":
        h = approx.get_silu(cfg.silu_impl)(dense(p["w1"], x, cdt))
        h = h * dense(p["w3"], x, cdt)
    else:
        h = jax.nn.gelu(dense(p["w1"], x, cdt))
    h = constrain(h, "act_batch", "act_seq", "act_ffn")
    return dense(p["w2"], h, cdt)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_init(cfg, key):
    p = {"tok": Param(
        jax.random.normal(key, (cfg.vocab, cfg.d_model), jnp.float32) * 0.02,
        ("vocab", "embed"))}
    return p


def embed_apply(cfg, p, tokens, dtype):
    # gather, then cast: the same values without converting the whole
    # table (which stays wide: the tied head reads it at f32)
    return p["tok"][tokens].astype(dtype)


def unembed_init(cfg, key):
    if cfg.tie_embeddings:
        return {}
    return {"w": Param(
        jax.random.normal(key, (cfg.d_model, cfg.vocab), jnp.float32)
        * cfg.d_model ** -0.5, ("embed", "vocab"))}


def unembed_apply(cfg, p, embed_p, x):
    if cfg.tie_embeddings:
        w = embed_p["tok"].T
    else:
        w = p["w"]
    ldt = jnp.dtype(cfg.logits_dtype)
    logits = jnp.einsum("bld,dv->blv", x.astype(ldt), w.astype(ldt),
                        preferred_element_type=jnp.float32).astype(ldt)
    return constrain(logits, "act_batch", "act_seq", "act_vocab")
