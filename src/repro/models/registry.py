"""Model registry: family dispatch + step functions + abstract inits.

API surface used by the launcher / trainer / dry-run:

  init_params(cfg, key)        -> Param tree (real arrays)
  abstract_params(cfg)         -> Param tree (ShapeDtypeStructs)  [no alloc]
  forward(cfg, params, batch)  -> (logits, aux)     params/batch plain values
  loss_fn(cfg, params, batch)  -> (loss, metrics)
  train_step / make_train_step -> jit-able step with optimizer
  init_cache / abstract_cache  -> decode cache (Param tree)
  decode_step(cfg, p, cache, batch) -> (logits, new_cache)
  input_specs(cfg, shape)      -> ShapeDtypeStruct batch stand-ins
  count_params(cfg)            -> analytical N (for 6ND roofline)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import jamba, mamba, mamba_lm, transformer, xlstm


_FAMILIES = {
    "transformer": transformer,
    "mamba": mamba_lm,
    "jamba": jamba,
    "xlstm": xlstm,
}


def family(cfg):
    return _FAMILIES[cfg.family]


# ---------------------------------------------------------------------------
# Params / caches
# ---------------------------------------------------------------------------

def init_params(cfg, key):
    from repro.core import weight_quant
    p = family(cfg).init(cfg, key)
    if weight_quant.is_quantized(cfg.weight_dtype):
        p = weight_quant.quantize_tree(p)
    return p


def abstract_params(cfg):
    """Param tree of ShapeDtypeStructs — Param.axes survive eval_shape.
    Routed through the quantizing ``init_params`` so the abstract tree
    (and the shardings derived from it) matches the real one leaf for
    leaf under any cfg.weight_dtype."""
    return jax.eval_shape(
        lambda k: init_params(cfg, k), jax.random.key(0))


def quantize_params(cfg, values):
    """Quantize a PLAIN-VALUE param tree per cfg.weight_dtype (no-op for
    "f32").  Serving entry: the Engine hands f32 weights in and this
    produces the int8+scale tree its jitted steps expect."""
    from repro.core import weight_quant
    if not weight_quant.is_quantized(cfg.weight_dtype):
        return values
    return weight_quant.quantize_tree(values)


def precast_params(cfg, values):
    """The serving copy of a PLAIN-VALUE param tree: the ``w`` of every
    Mamba mixer projection (``mamba.PROJECTIONS``) held at the compute
    dtype (cfg.dtype) where it is stored as a wider float and the
    weights are not quantized.  Each consumer casts it to that dtype
    before its matmul anyway, so the copy gives bitwise the same
    operands and the steps no longer cast it on every call.  The
    embedding (the tied head computes logits at f32), an untied head,
    norms, conv, A, D, dt_bias, int8 codes and scales, and attention,
    MoE and xLSTM leaves stay as they are.  One jitted cast, waited
    for; every other leaf aliases ``values``, which is neither changed
    nor donated.  Returns (tree, bytes cast)."""
    from repro.core import weight_quant
    if weight_quant.is_quantized(cfg.weight_dtype):
        return values, 0
    dt = jnp.dtype(cfg.dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(values)

    def wanted(path, leaf):
        keys = tuple(getattr(k, "key", None) for k in path[-2:])
        return (len(keys) == 2 and keys[1] == "w"
                and keys[0] in mamba.PROJECTIONS
                and jnp.issubdtype(leaf.dtype, jnp.floating)
                and leaf.dtype.itemsize > dt.itemsize)

    at = [i for i, (path, leaf) in enumerate(flat) if wanted(path, leaf)]
    if not at:
        return values, 0
    ins = tuple(flat[i][1] for i in at)
    # an element-wise cast: each output keeps its input's sharding
    outs = jax.block_until_ready(
        jax.jit(lambda ws: tuple(w.astype(dt) for w in ws))(ins))
    leaves = [leaf for _, leaf in flat]
    for i, w in zip(at, outs):
        leaves[i] = w
    return (jax.tree_util.tree_unflatten(treedef, leaves),
            sum(w.nbytes for w in outs))


def init_cache(cfg, batch, max_seq, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    return family(cfg).init_cache(cfg, batch, max_seq, dtype)


def abstract_cache(cfg, batch, max_seq):
    return jax.eval_shape(
        functools.partial(init_cache, cfg, batch, max_seq))


@functools.lru_cache(maxsize=None)
def cache_axes(cfg):
    """Pytree (matching init_cache structure) of per-leaf logical-axis
    tuples — the sharding counterpart of ``cache_slot_axes``.  The
    serving stack constrains its jit outputs with these so a pooled
    cache sharded over a mesh stays sharded across decode/fork/evict
    dispatches.  Structure depends only on cfg (state/kv dtypes add or
    drop scale leaves), never on batch or max_seq."""
    from repro.parallel import sharding
    return sharding.tree_axes(abstract_cache(cfg, 1, 8))


# ---------------------------------------------------------------------------
# Slot-indexable caches (continuous-batching serving engine)
#
# Every family exposes cache_slot_axes(cfg): a pytree congruent with
# init_cache whose leaves are the index of the batch ("slot") axis of the
# corresponding cache leaf.  The three operations below are the whole
# contract the serving engine needs: fixed-shape gather/scatter of
# per-sequence state by slot id, plus masking so inactive slots never
# mutate.  All are jit-safe with traced slot ids.
# ---------------------------------------------------------------------------

def cache_slot_axes(cfg):
    """Pytree (matching init_cache structure) of per-leaf slot-axis ints."""
    return family(cfg).cache_slot_axes(cfg)


def gather_slots(cfg, cache, slot_ids):
    """Extract a sub-cache for ``slot_ids`` (int array (m,)) from a pooled
    cache: each leaf is narrowed to m entries along its slot axis."""
    return jax.tree.map(
        lambda ax, leaf: jnp.take(leaf, slot_ids, axis=ax),
        cache_slot_axes(cfg), cache)


def scatter_slots(cfg, pool_cache, sub_cache, slot_ids):
    """Write a sub-cache (m slot entries) into ``pool_cache`` at
    ``slot_ids``; the pooled shapes are unchanged (pure functional .at)."""
    def put(ax, dst, src):
        idx = (slice(None),) * ax + (slot_ids,)
        return dst.at[idx].set(src.astype(dst.dtype))
    return jax.tree.map(put, cache_slot_axes(cfg), pool_cache, sub_cache)


def mask_slots(cfg, old_cache, new_cache, active):
    """Per-slot select: keep ``new_cache`` where ``active`` (bool (slots,))
    else ``old_cache`` — freezes state (incl. pos) of inactive slots so a
    pooled decode step cannot disturb free or finished slots."""
    def mix(ax, old, new):
        shape = [1] * old.ndim
        shape[ax] = -1
        return jnp.where(active.reshape(shape), new.astype(old.dtype), old)
    return jax.tree.map(mix, cache_slot_axes(cfg), old_cache, new_cache)


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------

def forward(cfg, params, batch):
    return family(cfg).forward(cfg, params, batch)


def decode_step(cfg, params, cache, batch):
    return family(cfg).decode_step(cfg, params, cache, batch)


def stacked_step(cfg, params, cache, batch):
    """Cross-layer megakernel decode: the whole layer stack in one (or,
    for heterogeneous stacks, per homogeneous run) Pallas launch, with
    per-layer weights/state carried on a stacked leading axis.  This is
    what ``decode_step`` dispatches to when cfg.step_impl resolves to
    "megakernel"; exposed for direct use by launch-count tests and
    benchmarks."""
    fam = family(cfg)
    if not hasattr(fam, "stacked_step"):
        raise NotImplementedError(
            f"family {cfg.family!r} has no megakernel decode path")
    return fam.stacked_step(cfg, params, cache, batch)


# ---------------------------------------------------------------------------
# Speculative decode support: K-step verify micro-scan, per-slot step
# selection (rollback), and self-speculative draft views.
# ---------------------------------------------------------------------------

def _freeze_steps(cfg, cache0, stacked, active):
    """Per-slot freeze over a verify cache stack (leading per-step axis):
    inactive slots read ``cache0`` at EVERY step — exactly what the
    chained scan's per-step mask_slots accumulates to, since a frozen
    slot never advances past its initial state."""
    def mix(ax, old, new):
        shape = [1] * new.ndim
        shape[ax + 1] = -1
        return jnp.where(active.reshape(shape), new.astype(old.dtype),
                         old[None])
    return jax.tree.map(mix, cache_slot_axes(cfg), cache0, stacked)


def verify_scan(cfg, params, cache, tokens, active=None):
    """Run K candidate tokens through the model — the spec-decode verify
    pass.  Families with a batched ``verify_window`` (mamba / jamba /
    xlstm) run the whole window through their block_verify front-ends:
    projections and convs batched over K tokens, only the recurrences
    sequential.  Token identity with the chained path holds because a
    (b, K, d) matmul computes each row exactly as the (b, 1, d) one
    (and the recurrence micro-scans chain the same per-token cells at
    the same shapes).  Families without one (transformer) chain
    ``decode_step`` per token.

    tokens (b, K) int32; ``active`` (b,) bool freezes inactive slots
    (as the engine's burst does).  Returns (logits (b, K, V), caches)
    where ``caches`` is the cache pytree with a leading per-step axis:
    caches[t] = cache after consuming tokens[:, t]."""
    window = getattr(family(cfg), "verify_window", None)
    if window is not None:
        logits, caches = window(cfg, params, cache, tokens)
        if active is not None:
            caches = _freeze_steps(cfg, cache, caches, active)
        return logits, caches

    def step(c, tok_t):
        logits, c2 = decode_step(cfg, params, c, {"tokens": tok_t})
        if active is not None:
            c2 = mask_slots(cfg, c, c2, active)
        return c2, (logits[:, -1, :], c2)

    xs = jnp.moveaxis(tokens[..., None], 1, 0)         # (K, b, 1)
    _, (logits, caches) = jax.lax.scan(step, cache, xs)
    return jnp.moveaxis(logits, 0, 1), caches


def select_step(cfg, stacked_cache, step_idx):
    """Per-slot rollback gather: from a ``verify_scan`` cache stack
    (leading per-step axis, length K) pick step ``step_idx[s]`` for
    slot ``s``.  Returns a normal cache pytree — the state each slot
    would have had had it decoded exactly its accepted prefix."""
    def pick(ax, leaf):
        m = jnp.moveaxis(leaf, ax + 1, 0)              # (slots, K, ...)
        sel = jax.vmap(lambda row, i: row[i])(m, step_idx)
        return jnp.moveaxis(sel, 0, ax)
    return jax.tree.map(pick, cache_slot_axes(cfg), stacked_cache)


def supports_draft(cfg) -> bool:
    return hasattr(family(cfg), "draft_params")


def draft_config(cfg, n_layers: int):
    """Model config of the first-``n_layers`` self-speculative draft
    (embed/norm/unembed shared with the target).  Families validate
    their own granularity (jamba: whole groups)."""
    import dataclasses
    if not supports_draft(cfg):
        raise NotImplementedError(
            f"family {cfg.family!r} has no self-speculative draft view")
    if cfg.family == "jamba":
        jamba._n_draft_groups(cfg, n_layers)           # validates
    elif not (0 < n_layers <= cfg.n_layers):
        raise ValueError(
            f"draft layers must be in (0, {cfg.n_layers}]; got {n_layers}")
    return dataclasses.replace(cfg, n_layers=n_layers)


def draft_params(cfg, params, n_layers: int):
    """First-``n_layers`` view of a plain-value param tree."""
    return family(cfg).draft_params(cfg, params, n_layers)


def draft_cache(cfg, cache, n_layers: int):
    return family(cfg).draft_cache(cfg, cache, n_layers)


def draft_cache_merge(cfg, full_cache, sub_cache, n_layers: int):
    return family(cfg).draft_cache_merge(cfg, full_cache, sub_cache,
                                         n_layers)


def prefill(cfg, params, cache, batch):
    """Full-seq forward that fills the decode cache (serving entry)."""
    return family(cfg).prefill(cfg, params, cache, batch)


def loss_fn(cfg, params, batch):
    """Causal LM loss (multi-codebook aware), fp32 softmax, z-reg metrics."""
    logits, aux = forward(cfg, params, batch)
    labels = batch["labels"]
    if cfg.n_codebooks > 1:                    # (b, l, ncb, V) vs (b, l, ncb)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None],
                                 axis=-1)[..., 0]
        nll = (lse - ll).mean()
    else:
        if cfg.frontend == "vision_stub":      # image prefix carries no loss
            logits = logits[:, -labels.shape[1]:]
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None],
                                 axis=-1)[..., 0]
        nll = (lse - ll).mean()
    loss = nll
    metrics = {"nll": nll}
    for k, v in aux.items():
        loss = loss + v
        metrics[k] = v
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins, shardable, no allocation)
# ---------------------------------------------------------------------------

def batch_struct(cfg, batch_size, seq_len, with_labels=True):
    """Concrete-shape dict for one step (tokens/embeds per frontend)."""
    tok = jax.ShapeDtypeStruct((batch_size, seq_len), jnp.int32)
    out = {}
    if cfg.frontend == "audio_stub":
        out["embeds"] = jax.ShapeDtypeStruct(
            (batch_size, seq_len, cfg.d_model), jnp.dtype(cfg.dtype))
        if with_labels:
            out["labels"] = jax.ShapeDtypeStruct(
                (batch_size, seq_len, cfg.n_codebooks), jnp.int32)
    elif cfg.frontend == "vision_stub":
        out["tokens"] = tok
        out["img_embeds"] = jax.ShapeDtypeStruct(
            (batch_size, cfg.img_tokens, cfg.d_model), jnp.dtype(cfg.dtype))
        if with_labels:
            out["labels"] = jax.ShapeDtypeStruct(
                (batch_size, seq_len), jnp.int32)
    else:
        out["tokens"] = tok
        if with_labels:
            out["labels"] = jax.ShapeDtypeStruct(
                (batch_size, seq_len), jnp.int32)
    return out


def batch_axes(cfg, struct):
    """Logical axes tree matching batch_struct (for in_shardings)."""
    ax = {}
    for k, v in struct.items():
        if v.ndim == 2:
            ax[k] = ("act_batch", "act_seq")
        elif k in ("embeds", "img_embeds"):
            ax[k] = ("act_batch", "act_seq", "act_embed")
        else:
            ax[k] = ("act_batch", "act_seq", None)
    return ax


def decode_batch_struct(cfg, batch_size):
    out = {}
    if cfg.frontend == "audio_stub":
        out["embeds"] = jax.ShapeDtypeStruct(
            (batch_size, 1, cfg.d_model), jnp.dtype(cfg.dtype))
    else:
        out["tokens"] = jax.ShapeDtypeStruct((batch_size, 1), jnp.int32)
    return out


def make_batch(cfg, batch_size, seq_len, key=None, with_labels=True):
    """Concrete random batch with the struct above (smoke tests/examples)."""
    key = key if key is not None else jax.random.key(0)
    struct = batch_struct(cfg, batch_size, seq_len, with_labels)
    ks = jax.random.split(key, len(struct))
    out = {}
    for (name, s), k in zip(sorted(struct.items()), ks):
        if jnp.issubdtype(s.dtype, jnp.integer):
            out[name] = jax.random.randint(k, s.shape, 0, cfg.vocab,
                                           dtype=s.dtype)
        else:
            out[name] = jax.random.normal(k, s.shape, s.dtype)
    return out


# ---------------------------------------------------------------------------
# Analytical parameter counts (roofline MODEL_FLOPS = 6 N D)
# ---------------------------------------------------------------------------

def count_params(cfg, active_only: bool = False) -> int:
    d, f, V, L = cfg.d_model, cfg.d_ff, cfg.vocab, cfg.n_layers
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    n = 0

    def attn():
        return d * hq * dh + 2 * d * hkv * dh + hq * dh * d

    def dense_mlp(ff):
        return 3 * d * ff if cfg.mlp == "swiglu" else 2 * d * ff

    def moe_mlp():
        E = cfg.top_k if active_only else cfg.n_experts
        m = E * 3 * d * f + d * cfg.n_experts  # router always full
        if cfg.n_shared_experts:
            m += 3 * d * (cfg.n_shared_experts * f)
        if cfg.dense_residual:
            m += dense_mlp(f)
        return m

    def mamba_blk():
        di, ns, r, k = cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.d_conv
        return (2 * d * di + k * di + di * (r + 2 * ns) + r * di
                + di * ns + di + di * d)

    def mlstm_blk():
        di = 2 * d
        dh2 = di // hq
        return 2 * d * di + cfg.d_conv * di + 2 * hq * dh2 * dh2 + di + d * di

    def slstm_blk():
        dh2 = d // hq
        return 4 * d * d + 4 * hq * dh2 * dh2 + d * d

    if cfg.family == "mamba":
        n += L * mamba_blk()
    elif cfg.family == "xlstm":
        for i in range(L):
            n += slstm_blk() if xlstm._is_slstm(cfg, i) else mlstm_blk()
    elif cfg.family == "jamba":
        for i in range(L):
            is_attn, is_moe = jamba._pos_kind(cfg, i)
            n += attn() if is_attn else mamba_blk()
            n += moe_mlp() if is_moe else dense_mlp(f)
    else:
        per = attn() + (moe_mlp() if cfg.is_moe else dense_mlp(f))
        n += L * per
    n += V * d                      # embed
    if not cfg.tie_embeddings:
        n += d * V * cfg.n_codebooks
    return int(n)
