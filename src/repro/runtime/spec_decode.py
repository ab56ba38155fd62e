"""Speculative decoding over the slot state pool.

Because an SSM's whole decode state is a fixed O(d_inner * d_state)
block per layer, a draft fork is ONE gather+scatter of pool slots and a
rollback is one per-slot select — no tree attention, no ragged KV
bookkeeping.  That is the structural advantage this module exploits
(attention-based spec decode spends most of its complexity budget
exactly there), and the part eMamba/FastMamba leave on the table by
targeting single-stream edge inference.

One speculative pass over the live slots:

  1. FORK    — lease one scratch slot per live slot and fork its pooled
               state into it (``SlotStatePool.fork``: payload + absmax
               scales + sampling params move together).
  2. DRAFT   — run K cheap decode steps on the scratch slots with the
               self-speculative draft model: the target's first
               ``DraftConfig.layers`` layers (embed / final norm /
               unembed shared), optionally with a different step_impl
               ("unfused-cheap").  Live slots are mask-frozen.  The
               draft samples with each slot's OWN SamplingParams and
               per-slot key stream (runtime/sampling.py).
  3. VERIFY  — one jit'd target pass: a (K+1)-step micro-scan chaining
               the SAME per-token ``decode_step`` dispatch the normal
               burst runs (fused kernel per layer per step) over
               [pending token, draft_1..draft_K], keeping every
               intermediate cache.
  4. ACCEPT  — per-slot speculative rejection sampling
               (``accept_tokens_hetero``): greedy slots take the greedy
               shortcut (accept while the draft equals the target
               argmax; the first mismatch emits the target's own token
               — bitwise plain greedy decode), sampled slots use
               min(1, p_t/p_d) with both distributions filtered and
               scaled by the slot's params, so one jit'd verify serves
               a batch mixing greedy and sampled requests with zero
               retracing when params change.
  5. ROLLBACK— per-slot select of the cache after each slot's accepted
               prefix (``registry.select_step``).

Exactness contract: the verify micro-scan evaluates the target at the
same shapes and through the same jitted per-token step as plain decode,
so a greedy slot's spec-decoded stream is token-identical to plain
greedy decode — even inside a mixed greedy+sampled batch (gated per
family / state_dtype / step_impl in tests/test_spec_decode.py).  Each
target pass emits between 1 and K+1 tokens per slot; the
accepted-tokens-per-target-pass counter in ServeStats is the speedup
proxy the benchmarks gate on.  ``DraftConfig.adaptive`` clamps each
slot's window to its realized acceptance (depth arithmetic only —
never the token values, so greedy identity survives adaptivity).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import registry
from repro.parallel import sharding
from repro.runtime import sampling


@dataclasses.dataclass(frozen=True)
class DraftConfig:
    """Self-speculative draft settings (``EngineConfig.draft``).

    k: draft depth — tokens proposed per target pass.  Each pass emits
       between 1 and k+1 tokens, so k bounds the per-pass win.
    layers: draft depth in model layers; 0 = full depth (the draft IS
       the target: every proposal is accepted — useful for gating the
       accounting deterministically, pointless for speed).  Jamba
       requires a multiple of its group period.
    step_impl: override for the draft's per-token step routing (e.g.
       "xla" for an unfused-cheap draft while the target runs fused);
       None inherits the target's — including "megakernel", where the
       draft's burst is its own single stacked launch over the
       first-n-layers slice of the same stacked params.
    adaptive: clamp each slot's speculative window to its realized
       acceptance (ceil(accepted/passes) + 1, floored at 1) after
       ``adapt_warmup`` full-depth passes — a low-acceptance slot stops
       paying for drafts it will reject.  Token streams are unchanged
       (the clamp shortens windows, never alters accept/emit math), so
       greedy identity stays bitwise.
    adapt_warmup: target passes at full depth before the clamp engages.
    """
    k: int = 4
    layers: int = 0
    step_impl: Optional[str] = None
    adaptive: bool = False
    adapt_warmup: int = 2


def default_shallow_layers(cfg) -> int:
    """A ~half-depth draft rounded to the family's draft granularity.

    Jamba drafts whole groups (``attn_every`` layers each), so its
    depth must be a group multiple — a config with a single group (the
    smoke config) degrades to full depth.  Other families draft any
    layer prefix."""
    if cfg.family == "jamba":
        period = cfg.attn_every or 8
        groups = cfg.n_layers // period
        return max(1, groups // 2) * period
    return max(1, cfg.n_layers // 2)


# ---------------------------------------------------------------------------
# Acceptance core (pure; property-tested in tests/test_spec_decode.py)
# ---------------------------------------------------------------------------

def accept_tokens_hetero(draft_toks, target_logits, draft_logits, sp,
                         step, depth_limit):
    """Per-slot-parameter speculative acceptance over one window.

    draft_toks (K, b) int32 — the draft's proposals d_1..d_K.
    target_logits (K+1, b, V) — the target's verify micro-scan logits.
    draft_logits (K, b, V) — the draft's logits at each proposal.
    sp — SlotParams dict with b rows (temperature/top_k/top_p/key_data).
    step (b,) int32 — each slot's stream position at pass start (keys
      the per-slot acceptance randomness, batch-independently).
    depth_limit (b,) int32 — per-slot cap on accepted drafts (adaptive
      depth); pass K to disable.

    Returns (emit (K+1, b), n_acc (b,), pending (b,)) with the same
    meaning as the scalar path: n_acc[s] accepted drafts, emit[:j+1, s]
    the emitted stream, pending[s] = emit[j, s] the token whose state
    update is not yet applied.

    Greedy rows (temperature <= 0) reduce EXACTLY to the greedy
    shortcut — emit is the target argmax stream, so a greedy slot in a
    mixed batch is bitwise the all-greedy engine.  Sampled rows use
    rejection sampling with p_t/p_d computed on each slot's OWN
    filtered+scaled distributions (the same ``sampling.sample_dist``
    the draft proposed from), keeping the emitted marginal exactly the
    target sampling distribution.  Clamping n_acc to depth_limit only
    shortens the accepted prefix — every emitted token is still either
    an accepted draft or the target's own — so adaptivity never
    changes token values.
    """
    K = draft_toks.shape[0]
    tgt = jnp.argmax(target_logits.astype(jnp.float32),
                     axis=-1).astype(jnp.int32)             # (K+1, b)
    ok_greedy = draft_toks == tgt[:K]
    sampled = sp["temperature"] > 0

    def _mixed(_):
        # both distributions filtered/scaled per slot, exactly as the
        # draft sampled its proposals
        logp_t = jax.nn.log_softmax(
            jax.vmap(sampling.sample_dist, in_axes=(0, None))(
                target_logits[:K], sp), axis=-1)            # (K, b, V)
        logp_d = jax.nn.log_softmax(
            jax.vmap(sampling.sample_dist, in_axes=(0, None))(
                draft_logits, sp), axis=-1)
        d = draft_toks[..., None]
        lp_t = jnp.take_along_axis(logp_t, d, axis=-1)[..., 0]  # (K, b)
        lp_d = jnp.take_along_axis(logp_d, d, axis=-1)[..., 0]
        base = sampling.slot_keys(sp["key_data"], step)
        k_u, k_res, k_bonus = (sampling.fold_tag(base, t)
                               for t in (1, 2, 3))
        u = jax.vmap(lambda k: jax.random.uniform(k, (K,), minval=1e-20),
                     out_axes=1)(k_u)                       # (K, b)
        # a draft token filtered out of the slot's target dist has
        # lp_t = -inf -> always rejected; lp_d is finite by construction
        # (the draft sampled it from the same filtered support)
        ok_sampled = jnp.log(u) < (lp_t - lp_d)
        # residual resample at the rejection point: max(p_t - p_d, 0),
        # renormalized; degenerate (p_t == p_d exactly) falls back to p_t
        res = jnp.maximum(jnp.exp(logp_t) - jnp.exp(logp_d), 0.0)
        norm = res.sum(axis=-1, keepdims=True)
        safe = jnp.where(norm > 0, res / jnp.maximum(norm, 1e-30),
                         jnp.exp(logp_t))
        corr = jax.vmap(jax.random.categorical,
                        in_axes=(0, 1), out_axes=1)(
            k_res, jnp.log(safe + 1e-30)).astype(jnp.int32)  # (K, b)
        bonus_dist = sampling.sample_dist(target_logits[K], sp)
        bonus = jax.vmap(jax.random.categorical)(
            k_bonus, bonus_dist).astype(jnp.int32)[None]    # (1, b)
        emit_sampled = jnp.concatenate(
            [jnp.where(ok_sampled, draft_toks, corr), bonus], axis=0)
        return (jnp.where(sampled[None, :], emit_sampled, tgt),
                jnp.where(sampled[None, :], ok_sampled, ok_greedy))

    # the whole rejection-sampling battery sits behind a cond on
    # any(sampled): an all-greedy verify pays only the argmax path at
    # runtime, with ONE compiled program (same rationale as
    # sampling.sample)
    emit, ok = jax.lax.cond(jnp.any(sampled), _mixed,
                            lambda _: (tgt, ok_greedy), None)
    acc = jnp.cumprod(ok.astype(jnp.int32), axis=0)
    n_acc = jnp.minimum(acc.sum(axis=0), depth_limit)       # (b,)
    pending = jnp.take_along_axis(emit, n_acc[None], axis=0)[0]
    return emit, n_acc, pending


def accept_tokens(draft_toks, target_logits, temperature: float,
                  draft_logits=None, key=None):
    """Scalar-parameter acceptance (reference entry; the engine's jit
    uses ``accept_tokens_hetero`` with per-slot params).

    Temperature 0 takes the greedy shortcut: accept while the draft
    matches the target argmax; the rejection/bonus token IS the argmax,
    so emit = argmax.  Temperature > 0 delegates to the vectorized path
    with every row carrying the same temperature (no top-k/top-p) and
    per-row keys folded from ``key`` — standard speculative rejection
    sampling whose emitted marginal is exactly the target distribution
    (property-tested in tests/test_spec_decode.py).
    """
    K, b = draft_toks.shape
    if temperature <= 0:
        tgt = jnp.argmax(target_logits.astype(jnp.float32),
                         axis=-1).astype(jnp.int32)         # (K+1, b)
        ok = (draft_toks == tgt[:K])
        acc = jnp.cumprod(ok.astype(jnp.int32), axis=0)      # (K, b)
        n_acc = acc.sum(axis=0)                              # (b,)
        emit = tgt
        pending = jnp.take_along_axis(emit, n_acc[None], axis=0)[0]
        return emit, n_acc, pending

    if draft_logits is None or key is None:
        raise ValueError("sampled acceptance needs draft_logits and key")
    sp = {"temperature": jnp.full((b,), temperature, jnp.float32),
          "top_k": jnp.zeros((b,), jnp.int32),
          "top_p": jnp.ones((b,), jnp.float32),
          "key_data": jnp.tile(jax.random.key_data(key), (b, 1))}
    return accept_tokens_hetero(
        draft_toks, target_logits, draft_logits, sp,
        step=jnp.arange(b, dtype=jnp.int32),
        depth_limit=jnp.full((b,), K, jnp.int32))


# ---------------------------------------------------------------------------
# Jit'd draft / verify passes (shared per config, as in engine.py).
# Sampling params are traced array arguments — never jit cache keys —
# so one compile serves arbitrary heterogeneous traffic.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _jit_draft_step(cfg, dcfg, n_layers: int, shard=None):
    """One draft decode step over the pool: slice the first-n-layers
    cache view, run the draft model's decode_step, merge the updated
    layers back, freeze everything but the scratch slots, sample with
    each slot's own params.  ``shard`` ((mesh, rules) or None) keys a
    separate tensor-parallel trace whose output cache is constrained to
    the pool's sharding (fork/draft/verify chain reshard-free)."""
    full = n_layers == cfg.n_layers and dcfg == cfg
    cax = registry.cache_axes(cfg) if shard is not None else None

    def draft_step(pd, cache, toks, scratch_mask, sp, step):
        sampling.TRACE_COUNTS["draft_step"] += 1
        with sharding.shard_ctx(shard):
            cd = (cache if full
                  else registry.draft_cache(cfg, cache, n_layers))
            logits, cd2 = registry.decode_step(dcfg, pd, cd,
                                               {"tokens": toks})
            new_cache = (cd2 if full else
                         registry.draft_cache_merge(cfg, cache, cd2,
                                                    n_layers))
            new_cache = registry.mask_slots(cfg, cache, new_cache,
                                            scratch_mask)
            if shard is not None:
                new_cache = sharding.constrain_tree(new_cache, cax)
            tok = sampling.sample(logits[:, -1, :], sp, step)
        return tok[:, None], logits[:, -1, :], new_cache
    return jax.jit(draft_step)


@functools.lru_cache(maxsize=None)
def _jit_verify(cfg, k: int, shard=None):
    """The fused verify pass: (k+1)-step micro-scan over
    [pending, drafts], per-step freeze of inactive slots, per-slot
    acceptance, and the per-slot rollback select — one dispatch, one
    host sync.  Only the window depth k (bounded by DraftConfig.k) and
    the tensor-parallel shard key the compile; sampling params are
    traced arrays."""
    cax = registry.cache_axes(cfg) if shard is not None else None

    def verify(p, cache, x0, draft_toks, draft_logits, active, sp, step,
               depth_limit):
        sampling.TRACE_COUNTS["verify"] += 1
        with sharding.shard_ctx(shard):
            # x0 (total, 1) pending tokens; draft_toks (k, total)
            inputs = jnp.concatenate(
                [x0, jnp.moveaxis(draft_toks, 0, 1)], axis=1)  # (total, k+1)
            logits, caches = registry.verify_scan(cfg, p, cache, inputs,
                                                  active=active)
            tl = jnp.moveaxis(logits, 1, 0)                  # (k+1, b, V)
            emit, n_acc, pending = accept_tokens_hetero(
                draft_toks, tl, draft_logits, sp, step, depth_limit)
            snap = registry.select_step(cfg, caches, n_acc)
            if shard is not None:
                # the rolled-back cache replaces the pool's — pin its
                # sharding so the next burst starts reshard-free
                snap = sharding.constrain_tree(snap, cax)
            # logprob surface for every emitted position (the engine
            # keeps only the accepted prefix) — raw-logit log-softmax,
            # so the emit/accept math above is untouched and token
            # streams stay bitwise identical to the surface-free verify
            lp, tv, ti = jax.vmap(sampling.token_logprobs)(tl, emit)
        return emit, n_acc, pending, snap, lp, tv, ti
    return jax.jit(verify)


class SpecDecoder:
    """Per-engine speculative-decode driver (jit caches shared per
    config across instances, like the engine's step functions)."""

    def __init__(self, cfg, params, draft: DraftConfig, shard=None):
        if draft.k < 1:
            raise ValueError("draft.k must be >= 1")
        n = draft.layers or cfg.n_layers
        dcfg = registry.draft_config(cfg, n)
        if draft.step_impl is not None:
            dcfg = dataclasses.replace(dcfg, step_impl=draft.step_impl)
        self.cfg = cfg
        self.dcfg = dcfg
        self.k = draft.k
        self.n_draft = n
        # tensor-parallel shard key ((mesh, rules) or None) — the engine
        # passes already-sharded params, so slicing the draft view below
        # keeps the layer-stacked leaves on their TP placement
        self._shard = shard
        # slice the draft's param view once (host-side, shares buffers)
        self.draft_params = (params if n == cfg.n_layers
                             else registry.draft_params(cfg, params, n))
        self._draft = _jit_draft_step(cfg, dcfg, n, shard)
        # warm the full-depth verify jit cache entry; shallower windows
        # (end-of-request budget clamps, adaptive depth) compile on
        # demand, bounded by the k distinct depths
        _jit_verify(cfg, draft.k, shard)

    def propose(self, cache, toks, scratch_mask, sp, base_step,
                k_eff: int):
        """Run ``k_eff`` draft steps (<= self.k: the engine clamps the
        window to the shortest remaining token budget and the adaptive
        per-slot depth) on the scratch slots.  ``toks`` (total, 1)
        carries the forked slots' pending tokens at their scratch rows;
        ``sp``/``base_step`` are the pool's per-row params and stream
        positions (scratch rows mirror their live slot's, so draft
        proposal i at a slot whose stream position is n draws with the
        same fold_in(key, n + i) a plain burst would use).  Returns
        (cache, draft_toks (K, total), draft_logits (K, total, V)) —
        all device-side, indexed by POOL row (the caller maps scratch
        rows back to their live slots)."""
        d_toks, d_logits = [], []
        for i in range(k_eff):
            toks, lg, cache = self._draft(self.draft_params, cache, toks,
                                          scratch_mask, sp, base_step + i)
            d_toks.append(toks[:, 0])
            d_logits.append(lg)
        return cache, jnp.stack(d_toks), jnp.stack(d_logits)

    def verify(self, params, cache, x0, draft_toks, draft_logits,
               active, sp, step, depth_limit):
        """One batched target pass + per-slot acceptance + rollback
        select.  Returns (emit (K+1, total), n_acc (total,), pending
        (total,), rolled-back cache, chosen-logprobs (K+1, total),
        top-logprob values (K+1, total, TOP), top-logprob ids).  K is
        taken from draft_toks."""
        fn = _jit_verify(self.cfg, int(draft_toks.shape[0]), self._shard)
        return fn(params, cache, x0, draft_toks, draft_logits,
                  active, sp, step, depth_limit)
