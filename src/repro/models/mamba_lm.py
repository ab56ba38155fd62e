"""Pure Mamba LM (the paper's evaluation models, Table 1: 130M..2.8B).

Homogeneous stack of Mamba blocks (residual, pre-norm), lax.scan over
stacked layer params, tied embeddings (as in the released Mamba family).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import state_quant
from repro.models import blocks, mamba
from repro.parallel import sharding
from repro.parallel.sharding import Param, constrain


def _layer_init(cfg, key):
    ks = jax.random.split(key, 2)
    return {"norm": blocks.norm_init(cfg, ks[0]),
            "mixer": mamba.mamba_block_init(cfg, ks[1])}


def _layer_apply(cfg, p, x, state=None, step=False):
    xn = blocks.apply_norm(cfg, p["norm"], x)
    if step:
        y, new_state = mamba.mamba_block_step(cfg, p["mixer"], xn, state)
    else:
        y, new_state = mamba.mamba_block_apply(cfg, p["mixer"], xn,
                                               state=state)
    x = x + y
    return constrain(x, "act_batch", "act_seq", "act_embed"), new_state


def init(cfg, key):
    ks = jax.random.split(key, 3)
    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    stacked = jax.vmap(lambda k: _layer_init(cfg, k))(layer_keys)
    stacked = jax.tree.map(
        lambda q: Param(q.value, ("layers",) + q.axes), stacked,
        is_leaf=lambda q: isinstance(q, Param))
    return {"embed": blocks.embed_init(cfg, ks[1]),
            "layers": stacked,
            "norm_f": blocks.norm_init(cfg, ks[2]),
            "unembed": blocks.unembed_init(cfg, ks[2])}


def forward(cfg, p, batch):
    dtype = jnp.dtype(cfg.dtype)
    h = blocks.embed_apply(cfg, p["embed"], batch["tokens"], dtype)
    h = constrain(h, "act_batch", "act_seq", "act_embed")

    def body(x, lp):
        y, _ = _layer_apply(cfg, lp, x)
        return y, None

    if cfg.remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, p["layers"])
    h = blocks.apply_norm(cfg, p["norm_f"], h)
    logits = blocks.unembed_apply(cfg, p.get("unembed", {}), p["embed"], h)
    return logits, {}


def _quantized(cfg):
    return state_quant.is_quantized(cfg.state_dtype)


def init_cache(cfg, batch, max_seq, dtype):
    L = cfg.n_layers
    di, n, k = cfg.d_inner, cfg.d_state, cfg.d_conv
    out = {
        "h": Param(jnp.zeros((L, batch, di, n),
                             state_quant.storage_dtype(cfg.state_dtype)),
                   ("layers", "act_batch", "act_ffn", None)),
        "conv": Param(jnp.zeros((L, batch, k - 1, di), dtype),
                      ("layers", "act_batch", None, "act_ffn")),
        "pos": Param(jnp.zeros((batch,), jnp.int32), ("act_batch",)),
    }
    if _quantized(cfg):
        # per-slot-per-layer-per-channel-group f32 absmax scales live in
        # the cache pytree: gather/scatter/mask (and eviction's
        # fresh-state reset) move payload and scale together
        out["h_scale"] = Param(
            jnp.zeros((L, batch, state_quant.n_groups(di)), jnp.float32),
            ("layers", "act_batch", None))
    return out


def cache_slot_axes(cfg):
    """Batch/slot axis index per cache leaf (layout matches init_cache)."""
    ax = {"h": 1, "conv": 1, "pos": 0}
    if _quantized(cfg):
        ax["h_scale"] = 1
    return ax


def _pack_state(cfg, ns):
    """Per-layer state dict -> the lax.scan-stacked leaf tuple."""
    if _quantized(cfg):
        return (ns["h"], ns["h_scale"], ns["conv"])
    return (ns["h"], ns["conv"])


def _cache_from_stacked(cfg, stacked, pos):
    if _quantized(cfg):
        nh, nscale, nc = stacked
        return {"h": nh, "h_scale": nscale, "conv": nc, "pos": pos}
    nh, nc = stacked
    return {"h": nh, "conv": nc, "pos": pos}


# ---------------------------------------------------------------------------
# Self-speculative draft views: the draft model is the target's first
# ``n`` layers (embed / final norm / unembed shared), so a draft needs no
# second parameter set — just a slice of the stacked layer leaves, and a
# matching slice of the pooled cache that merges back leaf-for-leaf.
# ---------------------------------------------------------------------------

def draft_params(cfg, p, n):
    """First-``n``-layers view of a (plain-value) param tree."""
    return {**p, "layers": jax.tree.map(lambda q: q[:n], p["layers"])}


def draft_cache(cfg, cache, n):
    """First-``n``-layers view of a pooled cache (pos shared)."""
    keys = ["h", "conv"] + (["h_scale"] if _quantized(cfg) else [])
    out = {k: cache[k][:n] for k in keys}
    out["pos"] = cache["pos"]
    return out


def draft_cache_merge(cfg, full, sub, n):
    """Write a draft-updated first-``n``-layers cache back into the full
    cache (the inverse of draft_cache; layers >= n untouched)."""
    keys = ["h", "conv"] + (["h_scale"] if _quantized(cfg) else [])
    out = {k: full[k].at[:n].set(sub[k]) for k in keys}
    out["pos"] = sub["pos"]
    return out


def _megakernel_operands(cfg, p, cache):
    """The megakernel's stacked per-layer inputs (weights + pooled
    state, leading L axis) and its per-layer output structs."""
    stacked_in = {"p": p["layers"], "h": cache["h"], "conv": cache["conv"]}
    if _quantized(cfg):
        stacked_in["h_scale"] = cache["h_scale"]
    b = cache["h"].shape[1]
    di, n, k = cfg.d_inner, cfg.d_state, cfg.d_conv
    storage = state_quant.storage_dtype(cfg.state_dtype)
    out_structs = [jax.ShapeDtypeStruct((b, di, n), storage)]
    if _quantized(cfg):
        out_structs.append(jax.ShapeDtypeStruct(
            (b, state_quant.n_groups(di)), jnp.float32))
    out_structs.append(
        jax.ShapeDtypeStruct((b, k - 1, di), cache["conv"].dtype))
    return stacked_in, out_structs


def stacked_step(cfg, p, cache, batch):
    """Single-token decode as ONE Pallas launch for the whole stack.

    The layer loop that ``decode_step`` runs as a lax.scan of per-layer
    launches becomes the kernel grid: stacked layer params and the
    pooled recurrent cache ride in with a leading L axis, the residual
    stream is carried in a revisited output block, and each grid step
    runs norm -> mamba megastep -> residual for its layer.  Embed and
    the final norm/unembed stay in XLA — exactly one pallas_call per
    decoded token."""
    from repro.kernels import decode_step as dsk
    dtype = jnp.dtype(cfg.dtype)
    x0 = blocks.embed_apply(cfg, p["embed"], batch["tokens"], dtype)
    x0 = constrain(x0, "act_batch", None, "act_embed")
    quant = _quantized(cfg)
    stacked_in, out_structs = _megakernel_operands(cfg, p, cache)

    def body(x, ins):
        state = {"h": ins["h"], "conv": ins["conv"]}
        if quant:
            state["h_scale"] = ins["h_scale"]
        xn = blocks.apply_norm(cfg, ins["p"]["norm"], x)
        y, ns = mamba.mamba_block_megastep(cfg, ins["p"]["mixer"], xn,
                                           state)
        x = constrain(x + y, "act_batch", "act_seq", "act_embed")
        return x, _pack_state(cfg, ns)

    # the pooled state goes in and out as the launch's own operands and
    # results: no staging op of this code's feeds it, so the kernel is
    # the one scope here
    with jax.named_scope(dsk.SCOPE_KERNEL):
        h, stacked = dsk.stacked_layer_launch(
            body, x0, stacked_in, out_structs,
            name="marca_megakernel_mamba")
    h = blocks.apply_norm(cfg, p["norm_f"], h)
    logits = blocks.unembed_apply(cfg, p.get("unembed", {}), p["embed"], h)
    return logits, _cache_from_stacked(cfg, stacked, cache["pos"] + 1)


def decode_path(cfg, p, cache):
    """The decode path ``decode_step`` runs for these operands:
    cfg.step_impl resolved against the megakernel's VMEM need at the
    served widths and pool size (core.selective_scan.resolve_step_impl).
    Under a mesh there is no need to weigh: the megakernel's matmuls
    span the sharded channels and a Mosaic kernel cannot be partitioned
    automatically, while the per-layer kernel runs per channel shard."""
    from repro.core.selective_scan import resolve_step_impl
    from repro.kernels import decode_step as dsk
    need = None
    if sharding.active_mesh() is None:
        need = dsk.stacked_layer_vmem_bytes(
            *_megakernel_operands(cfg, p, cache))
    return resolve_step_impl(cfg.step_impl, megakernel_vmem=need)


def decode_step(cfg, p, cache, batch):
    if decode_path(cfg, p, cache) == "megakernel":
        return stacked_step(cfg, p, cache, batch)
    dtype = jnp.dtype(cfg.dtype)
    h = blocks.embed_apply(cfg, p["embed"], batch["tokens"], dtype)
    h = constrain(h, "act_batch", None, "act_embed")
    quant = _quantized(cfg)

    def body(x, lp_state):
        if quant:
            lp, hs, ss, cs = lp_state
            state = {"h": hs, "h_scale": ss, "conv": cs}
        else:
            lp, hs, cs = lp_state
            state = {"h": hs, "conv": cs}
        y, ns = _layer_apply(cfg, lp, x, state=state, step=True)
        return y, _pack_state(cfg, ns)

    xs = ((p["layers"], cache["h"], cache["h_scale"], cache["conv"])
          if quant else (p["layers"], cache["h"], cache["conv"]))
    h, stacked = jax.lax.scan(body, h, xs)
    h = blocks.apply_norm(cfg, p["norm_f"], h)
    logits = blocks.unembed_apply(cfg, p.get("unembed", {}), p["embed"], h)
    return logits, _cache_from_stacked(cfg, stacked, cache["pos"] + 1)


def verify_window(cfg, p, cache, tokens):
    """Spec-decode verify over a K-token window through the batched
    block front-ends: ONE embed + per-layer ``mamba_block_verify``
    (projections/conv/dt over the whole window, SSM recurrence as the
    K-step micro-scan) instead of K chained ``decode_step`` calls.
    Token-stream equivalence to the chained path rests on XLA's
    row-wise GEMM determinism: a (b, K, d) matmul computes each row
    exactly as the (b, 1, d) one does.

    tokens (b, K) int32.  Returns (logits (b, K, V), caches) in the
    chained verify_scan layout: cache pytree with a leading per-step
    axis (caches[t] = cache after consuming tokens[:, t])."""
    dtype = jnp.dtype(cfg.dtype)
    K = tokens.shape[1]
    x = blocks.embed_apply(cfg, p["embed"], tokens, dtype)
    x = constrain(x, "act_batch", "act_seq", "act_embed")
    quant = _quantized(cfg)

    def body(x, lp_state):
        if quant:
            lp, hs, ss, cs = lp_state
            state = {"h": hs, "h_scale": ss, "conv": cs}
        else:
            lp, hs, cs = lp_state
            state = {"h": hs, "conv": cs}
        xn = blocks.apply_norm(cfg, lp["norm"], x)
        y, states = mamba.mamba_block_verify(cfg, lp["mixer"], xn, state)
        x = constrain(x + y, "act_batch", "act_seq", "act_embed")
        return x, _pack_state(cfg, states)

    xs = ((p["layers"], cache["h"], cache["h_scale"], cache["conv"])
          if quant else (p["layers"], cache["h"], cache["conv"]))
    x, stacked = jax.lax.scan(body, x, xs)
    x = blocks.apply_norm(cfg, p["norm_f"], x)
    logits = blocks.unembed_apply(cfg, p.get("unembed", {}), p["embed"], x)
    # scan stacks L leading and block_verify stacks steps on axis 1 of
    # (b, K, ...): (L, b, K, ...) -> the chained layout (K, L, b, ...)
    stacked = jax.tree.map(lambda t: jnp.moveaxis(t, 2, 0), stacked)
    pos = (cache["pos"][None, :]
           + jnp.arange(1, K + 1, dtype=jnp.int32)[:, None])
    return logits, _cache_from_stacked(cfg, stacked, pos)


def prefill(cfg, p, cache, batch):
    """Full-sequence forward that also returns the decode cache."""
    dtype = jnp.dtype(cfg.dtype)
    h = blocks.embed_apply(cfg, p["embed"], batch["tokens"], dtype)
    h = constrain(h, "act_batch", "act_seq", "act_embed")

    def body(x, lp):
        y, ns = _layer_apply(cfg, lp, x)
        return y, _pack_state(cfg, ns)

    h, stacked = jax.lax.scan(body, h, p["layers"])
    h = blocks.apply_norm(cfg, p["norm_f"], h)
    logits = blocks.unembed_apply(cfg, p.get("unembed", {}), p["embed"], h)
    b = h.shape[0]
    pos = jnp.full((b,), batch["tokens"].shape[1], jnp.int32)
    return logits, _cache_from_stacked(cfg, stacked, pos)
