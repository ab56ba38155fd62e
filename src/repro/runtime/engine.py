"""Continuous-batching serving engine over the slot-based state pool.

Request lifecycle (see also runtime/__init__.py):

  submit(prompt, SamplingParams) -> [pending until arrival] -> ready
  queue (priority-ordered) -> prefill-into-slot -> joins the running
  decode batch -> per-slot stop-token / max-token finish (or cancel())
  -> evict (slot reset + freed) -> Request returned with tokens +
  timings.  A ``stream_cb`` receives each request's new tokens at every
  scheduler sync.

Scheduling policy: admit-eagerly, highest priority first (FIFO within a
priority).  Each engine ``step()`` first admits ready requests into
every free slot (one fused exact-length prefill-scatter-sample dispatch
per request), then runs a pooled decode BURST over all ``n_slots``
slots with inactive slots masked.  Sampling is fused into the decode
jit so tokens chain on-device; the host syncs once per burst.  A burst
runs to the next *certain* scheduling event (the shortest remaining
token budget = the next guaranteed eviction), capped by
``sched_quantum`` only when an uncertain event could act sooner (an
active stop token, a streaming callback that must be serviced — it may
cancel — or a free slot with queued work).  Because an SSM slot is
O(d_inner * d_state) regardless of sequence length, admission/eviction
are O(1) scatters and the decode batch shape never changes — no
ragged-batch re-bucketing between steps.

Sampling discipline (runtime/sampling.py): every per-request knob —
temperature, top-k, top-p, seed, stop ids, budget — is DATA.  The pool
carries per-slot parameter arrays that enter the jit'd steps as traced
arguments, so ONE compiled prefill/decode/verify signature serves a
batch mixing greedy and sampled requests and changing any
SamplingParams field never retraces (``sampling.TRACE_COUNTS`` is the
proof hook).  Randomness is per-slot counter-based: token i of request
r is drawn with fold_in(key(seed_r), i), so a sampled stream is
bitwise reproducible regardless of slot placement, batch composition,
or co-resident cancellations.

jit discipline: decode compiles once (fixed pool shape) and is shared
across Engine instances per config; the prefill compiles once per
distinct prompt length (callers that care should quantize prompt
lengths; the benchmark draws from a small set).

Speculative decoding (``EngineConfig.draft``): each scheduler iteration
becomes one fork -> K-draft -> batched-verify -> rollback pass
(runtime/spec_decode.py) instead of a token-by-token burst.  The pool
gains one scratch slot per live slot for draft forks; a greedy slot's
spec decode is token-identical to plain greedy decode — even in a
mixed greedy+sampled batch — and each target pass emits 1..K+1 tokens
per slot.  ``DraftConfig.adaptive`` clamps each slot's window to its
realized acceptance (Request.spec_accepted / spec_passes).

Prefix-state cache (``EngineConfig.prefix_cache``): because an SSM
slot's decode state is a fixed-size block, a prompt prefix is cacheable
as a tiny state *snapshot* — the batch-1 cache pytree (quantized
payload + absmax scales + stream position together, the same invariant
``fork`` keeps) captured at block boundaries into a bounded LRU store
(runtime/prefix_cache.py).  Admission of a prompt sharing a cached
prefix restores the snapshot and prefills only the suffix via a
decode-step micro-scan — the same per-token dispatch the verify scan
chains, so the result is token-identical to the cold full prefill.
Cold admissions snapshot every block boundary they cross, so unaligned
shared prefixes still hit at the deepest common boundary.

Best-of-n (``SamplingParams.n``): one prefill, n forked slots.  The
fork re-derives each branch's key by folding a branch tag into the
source key (``SlotStatePool.fork(branch_tags=...)``) — the fix for the
fork-seed aliasing bug where forked "alternatives" sampled bitwise-
identical streams.  Spec-decode draft forks pass NO tags and keep the
verbatim key copy their exactness contract requires; branch 0 is
bitwise the same request served at n=1.  Branches are ranked by
cumulative logprob (always accumulated, from the raw-logit log-softmax
every step jit now returns) on the parent ``Request``.

Caveat: MoE families route tokens across the batch through shared expert
capacity, so slot composition can perturb logits at tight
capacity_factor.  Pure Mamba / dense attention families are exactly
slot-independent (the engine's correctness tests assert this).

Front-end hooks (PR 10): ``submit(tenant=...)`` threads a tenant label
into per-tenant ServeStats; ``submit(session=True)`` opens an
infinite-stream session (no max_new horizon, slot pinned against
eviction — legal only for families whose decode state does not grow
with max_seq); ``submit_snapshot`` admits a request whose prompt was
prefilled elsewhere (runtime/disagg.py) by restoring the shipped state
block with the pool's one-scatter admit; ``spec_cap`` is the
scheduler's degradation knob (clamps speculative depth under load
without retracing).  A raising ``stream_cb`` no longer propagates into
the scheduler loop: the engine counts it, drops the callback, and
auto-cancels that request — co-resident streams are untouched.

Host spans (runtime/tracing.py): ``step`` records ``engine.step``
around all of it, ``engine.admit`` (request id) around each admission
with ``engine.admit.prefill`` (the prefill dispatch and prefix-cache
inserts) and ``engine.admit.sync`` (the first token's host read) inside,
``engine.burst`` around each decode burst with its ``prepare``,
``dispatch``, ``sync`` and ``deliver`` phases, ``engine.evict`` (request
id) around a slot's eviction, ``engine.spec_pass`` and
``engine.flush_pending``.  A request's ``t_admit`` and ``t_first`` and
the ServeStats prefill and decode times are these spans' stamps.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import heapq
import math
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import registry
from repro.parallel import sharding
from repro.runtime import metrics as metrics_lib
from repro.runtime import sampling, tracing
from repro.runtime.prefix_cache import (PrefixCache, PrefixCacheConfig,
                                        snapshot_to_device)
from repro.runtime.sampling import SamplingParams
from repro.runtime.spec_decode import DraftConfig, SpecDecoder
from repro.runtime.state_pool import SlotStatePool


# Per-config jit'd step functions, shared across Engine instances (cfg is
# a frozen dataclass, hence hashable).  Without this every Engine would
# carry its own jit cache and re-trace/compile prefill and decode that an
# earlier engine — or the warmup pass — already compiled.  Sampling
# parameters are traced ARRAY arguments, never part of the cache key:
# heterogeneous per-request settings share one compile.
#
# ``shard`` ((mesh, rules) or None, both halves hashable) keys the
# tensor-parallel traces separately: the body enters sharding.shard_ctx
# so the models' logical ``constrain`` calls bake the mesh at trace
# time, and every returned pool cache is re-constrained to the pool's
# own placement — output sharding == input sharding, so bursts, forks
# and eviction scatters chain with zero per-step resharding.  With
# shard=None the context is a no-op and the traces are byte-identical
# to the pre-mesh engine.
@functools.lru_cache(maxsize=None)
def _jit_prefill_admit(cfg, shard=None):
    """Fused prefill-into-slot: full-seq prefill of one request, scatter
    of its state into the pool slot, and first-token sampling with the
    request's own params — one dispatch per admission.  Also returns
    the logprob surface (chosen + fixed-width top-k over the raw-logit
    log-softmax; token math untouched) and the last-position logits,
    which best-of-n admission samples each forked branch's first token
    from without re-running the prefill."""
    cax = registry.cache_axes(cfg) if shard is not None else None

    # the program's name on a device trace is jit__fn, as the decode
    # step's is jit__decode_fn: trace readers find them by these names
    def _fn(p, fresh, tokens, pool_cache, slot_id, sp, step):
        sampling.TRACE_COUNTS["prefill_admit"] += 1
        with sharding.shard_ctx(shard):
            logits, sub = registry.prefill(cfg, p, fresh,
                                           {"tokens": tokens})
            new_pool = registry.scatter_slots(cfg, pool_cache, sub,
                                              slot_id)
            if shard is not None:
                new_pool = sharding.constrain_tree(new_pool, cax)
            last = logits[:, -1, :]
            tok = sampling.sample(last, sp, step)
            lp, tv, ti = sampling.token_logprobs(last, tok)
        return tok[:, None], lp, tv, ti, last, new_pool
    return jax.jit(_fn)


@functools.lru_cache(maxsize=None)
def _jit_prefill_prefix(cfg, shard=None):
    """Prefix-only prefill: consume the first ``block`` prompt tokens
    from the init state and return the batch-1 cache — the snapshot a
    cold admission inserts into the prefix cache before chaining the
    remaining tokens through the suffix micro-scan.  No scatter, no
    sampling: the snapshot is position-complete state, nothing else."""
    cax = registry.cache_axes(cfg) if shard is not None else None

    def prefill_prefix(p, fresh, tokens):
        sampling.TRACE_COUNTS["prefill_prefix"] += 1
        with sharding.shard_ctx(shard):
            _, sub = registry.prefill(cfg, p, fresh, {"tokens": tokens})
            if shard is not None:
                # batch-1 snapshot: slot axis replicated, TP-interior
                # leaves stay on "model" — restores scatter shard-local
                sub = sharding.constrain_tree(sub, cax)
        return sub
    return jax.jit(prefill_prefix)


@functools.lru_cache(maxsize=None)
def _jit_suffix_admit(cfg, m: int, shard=None):
    """Cached-prefix admission: restore a prefix snapshot and prefill
    only the ``m``-token suffix as a decode-step micro-scan — the SAME
    per-token dispatch a decode burst (and the spec-decode verify scan)
    runs, so the resulting state and sampled token are what the cold
    full prefill produces.  One fused dispatch: scan, scatter of the
    final state into the slot, first-token sampling.  The per-step
    cache stack rides back so the engine can insert snapshots at every
    block boundary the chain crossed.  Compiles once per distinct
    suffix length (same discipline as the exact-length prefill)."""
    cax = registry.cache_axes(cfg) if shard is not None else None

    def suffix_admit(p, snap, toks, pool_cache, slot_id, sp, step):
        sampling.TRACE_COUNTS["suffix_admit"] += 1
        with sharding.shard_ctx(shard):
            def body(c, tok_t):
                logits, c2 = registry.decode_step(cfg, p, c,
                                                  {"tokens": tok_t})
                return c2, (logits[:, -1, :], c2)

            xs = jnp.moveaxis(toks[:, :, None], 1, 0)    # (1,m) -> (m,1,1)
            final, (lg, caches) = jax.lax.scan(body, snap, xs)
            new_pool = registry.scatter_slots(cfg, pool_cache, final,
                                              slot_id)
            if shard is not None:
                # pin the pool output only; ``caches`` has an extra
                # leading scan axis and stays wherever GSPMD puts it
                new_pool = sharding.constrain_tree(new_pool, cax)
            last = lg[-1]
            tok = sampling.sample(last, sp, step)
            lp, tv, ti = sampling.token_logprobs(last, tok)
        return tok[:, None], lp, tv, ti, last, new_pool, caches
    return jax.jit(suffix_admit)


@functools.lru_cache(maxsize=None)
def _jit_decode_sample(cfg, shard=None):
    """Fused decode + per-slot sample: tokens stay on device so
    consecutive steps chain without a host round-trip (the burst loop
    syncs once per scheduling quantum, keeping XLA dispatch
    pipelined).  The logprob surface (chosen + top-k over the raw-logit
    log-softmax) rides along; the sampled-token math is untouched, so
    streams are bitwise the surface-free engine's."""
    cax = registry.cache_axes(cfg) if shard is not None else None

    def _decode_fn(p, cache, toks, active, sp, step):
        sampling.TRACE_COUNTS["decode_step"] += 1
        with sharding.shard_ctx(shard):
            logits, new_cache = registry.decode_step(cfg, p, cache,
                                                     {"tokens": toks})
            new_cache = registry.mask_slots(cfg, cache, new_cache,
                                            active)
            if shard is not None:
                new_cache = sharding.constrain_tree(new_cache, cax)
            last = logits[:, -1, :]
            tok = sampling.sample(last, sp, step)
            lp, tv, ti = sampling.token_logprobs(last, tok)
        return tok[:, None], lp, tv, ti, new_cache
    # the pool cache is donated: the steps of a burst chain through one
    # pool buffer instead of each enqueued step allocating its own
    return jax.jit(_decode_fn, donate_argnums=1)


def derive_seed(engine_seed: int, req_id: int) -> int:
    """Per-request seed for unseeded requests — module-level because a
    disaggregated pipeline must derive the SAME seed for request i that
    a monolithic engine would, or the token-identity contract breaks at
    the first sampled request."""
    return (engine_seed * 1_000_003 + req_id) & 0x7FFFFFFF


@dataclasses.dataclass
class EngineConfig:
    n_slots: int = 4
    max_seq: int = 256
    # engine seed: derives per-request seeds for requests whose
    # SamplingParams.seed is None (deterministically from the request
    # id, so unseeded streams are still reproducible per trace)
    seed: int = 0
    # default per-request params when submit() gets none (greedy)
    default_params: SamplingParams = dataclasses.field(
        default_factory=SamplingParams)
    # scheduling quantum: max decode steps per burst between host syncs /
    # admission checks.  Larger = fewer syncs (throughput), smaller =
    # faster admission + tighter stop-token eviction + lower streaming /
    # cancellation latency.
    sched_quantum: int = 8
    # override for the model's per-token step routing (cfg.step_impl):
    # "megakernel" = ONE Pallas launch per token for the whole layer
    # stack (layer axis in the kernel grid, stacked weights/state;
    # jamba's attention sublayers stay on their own path), "fused" = one
    # kernel launch per layer per token for the SSM state-update/
    # contraction/gate chain, "xla" = unfused reference ops, None = keep
    # the model config's setting ("auto" resolves from the backend and
    # the megakernel's VMEM need: core.selective_scan.resolve_step_impl).
    step_impl: Optional[str] = None
    # override for the pooled recurrent-state storage dtype
    # (cfg.state_dtype): "f32" | "bf16" | "int8" | "fp8".  int8/fp8
    # multiply slot capacity ~4x (per-slot absmax scales ride along in
    # the cache pytree); None = keep the model config's setting.
    state_dtype: Optional[str] = None
    # override for the attention KV-cache storage dtype
    # (cfg.kv_cache_dtype): "model" | "int8".  Composes with
    # state_dtype: on jamba, state_dtype covers the recurrent blocks
    # and kv_cache_dtype the per-position KV strips (which dominate
    # slot bytes at long max_seq).  None = keep the model config's.
    kv_cache_dtype: Optional[str] = None
    # override for the weight storage dtype (cfg.weight_dtype):
    # "f32" | "int8".  int8 quantizes the handed-in f32 params
    # per output channel (core/weight_quant.py) so decode streams
    # ~4x fewer weight bytes per token, dequantizing inside the
    # fused/megakernel decode kernels; embed/unembed/MoE stay f32.
    # The quantization is DECODE-side: prefill is compute-bound and
    # runs once per request, so it keeps serving from the caller's
    # f32 master weights (``Engine.prefill_params`` aliases them —
    # no copy) while every per-token decode/verify step streams the
    # int8 tree.  Unquantized engines serve prefill and decode from
    # one tree (``Engine.params``, which ``prefill_params`` aliases):
    # the caller's, with the Mamba projections held at the compute
    # dtype when that is narrower (``registry.precast_params``;
    # ``Engine.precast_bytes`` counts them).  Composes with
    # state_dtype/kv_cache_dtype (W8A8 + quantized state/KV) and with
    # ``mesh`` (scale leaves shard with their payloads).  None = keep
    # the model config's setting — the default leaves engines
    # byte-identical to unquantized serving.
    weight_dtype: Optional[str] = None
    # speculative decoding: None = plain decode bursts; a DraftConfig
    # turns every decode step into a fork -> K-draft -> batched-verify
    # -> rollback pass emitting 1..K+1 tokens per slot per target pass.
    # Greedy slots are token-identical to plain greedy decode; sampled
    # slots preserve their target distribution via per-slot rejection
    # sampling.  The pool grows n_slots scratch slots.
    draft: Optional[DraftConfig] = None
    # prompt-prefix state cache: None = every admission prefills its
    # full prompt; a PrefixCacheConfig snapshots per-block prefix state
    # into a bounded LRU store so admissions sharing a cached prefix
    # restore it with one scatter and prefill only the suffix —
    # token-identical to the cold prefill (gated in tests + bench).
    prefix_cache: Optional[PrefixCacheConfig] = None
    # tensor-parallel serving: a jax.sharding.Mesh (typically
    # launch/mesh.make_serving_mesh(tp) — 1-D over "model") shards the
    # stacked weights on their TP axes (ffn/heads/vocab -> "model") and
    # the pool's state/scale/KV leaves on the matching axes; slot
    # (batch) axes stay replicated, so admit/evict/fork scatters are
    # shard-local and every step chains reshard-free.  None (default)
    # = single-device, bitwise unchanged (the jit caches key on the
    # (mesh, rules) pair, so the unsharded traces are untouched).
    mesh: Optional[jax.sharding.Mesh] = None
    # logical-axis -> mesh-axis rules; None = sharding.ShardingRules()
    rules: Optional[sharding.ShardingRules] = None


@dataclasses.dataclass
class Request:
    """One generation request; engine fills tokens + timing fields."""
    req_id: int
    prompt: np.ndarray                    # (Lp,) int32
    params: SamplingParams = dataclasses.field(
        default_factory=SamplingParams)
    seed: int = 0                         # resolved per-request PRNG seed
    max_new: int = 32                     # mirrors params.max_new
    stop_ids: frozenset = frozenset()     # params.stop (+ eos_id)
    eos_id: Optional[int] = None          # convenience mirror
    priority: int = 0                     # higher admits earlier
    stream_cb: Optional[Callable] = None  # (req, new_tokens) per sync
    cancelled: bool = False
    arrival: float = 0.0                  # offset (s) from run() start
    tenant: Optional[str] = None          # per-tenant stats label
    # infinite-stream session: no max_new horizon; the slot is pinned
    # (eviction-free lease) until a stop token/sequence or cancel()
    session: bool = False
    # disaggregated admission: a shipped prefill snapshot (state block +
    # scales + position + first-token surface) restored instead of
    # running the prefill locally — see Engine.submit_snapshot
    snapshot: Optional[object] = dataclasses.field(default=None,
                                                   repr=False)
    tokens: list = dataclasses.field(default_factory=list)
    # timings, on the engine's clock.  t_arrival: the request entered
    # the system (the front end or scheduler that took it, else
    # submit) — TTFT and latency count from here; t_submit: it entered
    # this engine; t_admit: start of its ``engine.admit`` span;
    # t_first: its first token reached the host
    t_arrival: float = 0.0
    t_submit: float = 0.0
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    slot: Optional[int] = None            # pool slot, once admitted
    # per-slot speculative-depth bookkeeping (spec decode only): how
    # many target passes this request's slot took and how many drafted
    # tokens were accepted — accepted/passes is the request's realized
    # speculative depth (and drives DraftConfig.adaptive).
    spec_passes: int = 0
    spec_accepted: int = 0
    # logprob return surface (params.logprobs / params.top_logprobs):
    # per-emitted-token chosen logprob and [(token_id, logprob)] top
    # alternatives, from the raw-logit log-softmax.  cum_logprob is
    # ALWAYS accumulated (it ranks best-of-n branches).
    logprobs: list = dataclasses.field(default_factory=list)
    top_logprobs: list = dataclasses.field(default_factory=list)
    cum_logprob: float = 0.0
    # best-of-n (params.n > 1): the submitted request is the PARENT —
    # it never holds a slot; n child branch requests do.  On finish the
    # parent carries the best branch's tokens/logprobs and ``branches``
    # holds every child ranked by (-cum_logprob, branch).  Children
    # point back via ``parent`` and carry their ``branch`` tag (the
    # same integer folded into their sampling key at fork time).
    branches: Optional[list] = dataclasses.field(default=None, repr=False)
    parent: Optional["Request"] = dataclasses.field(default=None,
                                                    repr=False)
    branch: int = 0
    _open: int = 0                        # unfinished children (parent)

    @property
    def finished(self) -> bool:
        return self.t_done is not None


class Engine:
    def __init__(self, cfg, params, ecfg: EngineConfig,
                 clock: Callable[[], float] = time.perf_counter):
        if cfg.frontend in ("audio_stub", "vision_stub"):
            raise NotImplementedError(
                "serving engine supports token frontends only")
        if ecfg.step_impl is not None:
            # cfg keys the shared jit caches, so fused and unfused engines
            # compile (and benchmark) independently
            cfg = dataclasses.replace(cfg, step_impl=ecfg.step_impl)
        if ecfg.state_dtype is not None:
            # same reasoning: a quantized-state engine and an f32 engine
            # have different cache pytrees and must not share compiles
            cfg = dataclasses.replace(cfg, state_dtype=ecfg.state_dtype)
        if ecfg.kv_cache_dtype is not None:
            cfg = dataclasses.replace(cfg,
                                      kv_cache_dtype=ecfg.kv_cache_dtype)
        prefill_params = params
        if ecfg.weight_dtype is not None:
            from repro.core import weight_quant
            already = weight_quant.is_quantized(cfg.weight_dtype)
            cfg = dataclasses.replace(cfg, weight_dtype=ecfg.weight_dtype)
            if weight_quant.is_quantized(ecfg.weight_dtype) and not already:
                # quantize BEFORE the mesh device_put below so sharded
                # engines place the int8+scale tree (abstract_params
                # reflects the quantized structure for the same cfg).
                # prefill_params keeps aliasing the caller's f32 tree:
                # weight quantization is a decode-bandwidth lever, and
                # the compute-bound prefill stays exact on the master
                # weights (a caller handing in an already-quantized
                # tree has no f32 master, so prefill then dequantizes
                # the codes like the XLA decode reference does)
                params = registry.quantize_params(cfg, params)
        ecfg.default_params.validate()
        # tensor-parallel serving: place the weights once (shape-aware
        # specs — non-divisible dims fall back to replicated) and key
        # every shared jit cache on the (mesh, rules) pair.  Committed
        # sharded params + pool drive jit sharding inference; outputs
        # are constrained back to the pool's placement, so no step ever
        # reshards.  mesh=None leaves params and traces untouched.
        self._shard = None
        if ecfg.mesh is not None:
            # MoE dispatch must stay on the pjit-auto dense path: the
            # expert-parallel shard_map path drops overflow tokens per
            # SHARD-local capacity, so its logits differ from the
            # single-device global-capacity routing — which would break
            # the sharded == single-device token-identity contract
            # (moe.py's EP docstring states the same caveat for tests)
            if getattr(cfg, "moe_impl", None) == "ep":
                raise ValueError(
                    "moe_impl='ep' is unsupported under a serving mesh: "
                    "per-shard capacity drops break token identity")
            if getattr(cfg, "moe_impl", None) == "auto":
                cfg = dataclasses.replace(cfg, moe_impl="dense")
            rules = ecfg.rules or sharding.ShardingRules()
            self._shard = (ecfg.mesh, rules)
            distinct = prefill_params is not params
            params = jax.device_put(
                params, sharding.tree_shardings(
                    registry.abstract_params(cfg), ecfg.mesh, rules))
            if distinct:
                # the f32 prefill master shards under the same rules as
                # an unquantized engine's weights would
                f32_cfg = dataclasses.replace(cfg, weight_dtype="f32")
                prefill_params = jax.device_put(
                    prefill_params, sharding.tree_shardings(
                        registry.abstract_params(f32_cfg), ecfg.mesh,
                        rules))
            else:
                prefill_params = params
        # the projection matrices at the compute dtype, cast once here
        # and not by every step; placed tree in, placed tree out
        with tracing.span("engine.precast", clock=clock):
            precast, self.precast_bytes = registry.precast_params(cfg,
                                                                  params)
        if prefill_params is params:
            prefill_params = precast
        params = precast
        self.cfg = cfg
        self.params = params
        self.prefill_params = prefill_params
        self.ecfg = ecfg
        # one scratch slot per live slot: every live slot can fork a
        # draft in the same speculative pass
        n_scratch = ecfg.n_slots if ecfg.draft is not None else 0
        self.pool = SlotStatePool(cfg, ecfg.n_slots, ecfg.max_seq,
                                  n_scratch=n_scratch, mesh=ecfg.mesh,
                                  rules=ecfg.rules)
        # after device_put: the spec decoder slices its draft param view
        # from the already-sharded tree
        self._spec = (SpecDecoder(cfg, params, ecfg.draft,
                                  shard=self._shard)
                      if ecfg.draft is not None else None)
        # scheduler degradation knob: clamp every slot's speculative
        # window to this depth (None = uncapped).  Pure host-side depth
        # arithmetic — flipping it never retraces, and the clamp flows
        # through _slot_depth so greedy identity survives.
        self.spec_cap: Optional[int] = None
        # infinite-stream sessions are legal only when the decode state
        # is max_seq-independent (mamba/xlstm fixed blocks yes; jamba's
        # per-position KV strips no).  Probe by comparing abstract cache
        # shapes at two horizons — family-agnostic, no allocation.
        a = registry.abstract_cache(cfg, 1, ecfg.max_seq)
        b = registry.abstract_cache(cfg, 1, ecfg.max_seq + 1)
        self._cache_growable = any(
            x.shape != y.shape for x, y in
            zip(jax.tree.leaves(a), jax.tree.leaves(b)))
        self.stats = metrics_lib.ServeStats()
        self._now = clock
        self._prefill = _jit_prefill_admit(cfg, self._shard)
        self._decode = _jit_decode_sample(cfg, self._shard)
        self._prefill_prefix = _jit_prefill_prefix(cfg, self._shard)
        self._prefix = (PrefixCache(ecfg.prefix_cache)
                        if ecfg.prefix_cache is not None else None)
        self._pending: list[Request] = []      # arrival-gated, sorted
        self._ready: list[tuple] = []          # (-priority, seq, Request)
        self._seq = 0                          # FIFO tiebreak in _ready
        self._by_id: dict[int, Request] = {}   # unfinished requests
        self._cancel_dirty = False
        self._slot_req: list[Optional[Request]] = [None] * ecfg.n_slots
        self._next_tok = np.zeros((self.pool.n_total, 1), np.int32)
        self._finished: list[Request] = []
        self._next_id = 0

    # ------------------------------------------------------------------
    # Request intake
    # ------------------------------------------------------------------

    def submit(self, prompt, params: Optional[SamplingParams] = None,
               max_new: Optional[int] = None,
               eos_id: Optional[int] = None,
               arrival: Optional[float] = None,
               priority: int = 0,
               stream_cb: Optional[Callable] = None,
               tenant: Optional[str] = None,
               session: bool = False,
               t_arrival: Optional[float] = None) -> Request:
        """Enqueue a request.

        params: per-request SamplingParams (None = the engine's
          default_params, greedy unless configured).  ``max_new`` /
          ``eos_id`` are conveniences layered onto it: max_new
          overrides params.max_new, eos_id extends params.stop.
        arrival: seconds from run() start; gates admission for trace
          replay (None = ready immediately).
        priority: higher admits earlier among ready requests (FIFO
          within a priority level).
        stream_cb: ``cb(req, new_tokens)`` called at every scheduler
          sync with the >= 1 tokens appended since the last call; the
          final call has ``req.finished`` True.  The callback may call
          ``Engine.cancel`` (including on its own request).  A raising
          callback is isolated: counted in
          ``ServeStats.n_callback_errors``, dropped, and its request
          auto-cancelled — co-resident requests are unaffected.
        tenant: label for per-tenant ServeStats breakdowns (TTFT/TPOT
          percentiles, shed/degraded/SLO-violation counters).
        session: infinite-stream session — no max_new horizon (the
          stream runs until a stop token/sequence or cancel) and the
          slot holds an eviction-free lease (pinned).  Legal only for
          families whose decode state is max_seq-independent: a fixed
          O(d_inner * d_state) block decodes forever in constant
          bytes, which is exactly what per-position KV strips cannot
          do, so jamba-style hybrids are refused up front.
        t_arrival: when the request entered the system, on this
          engine's clock (a front end or scheduler that held it first
          passes its own stamp); None = now.
        """
        t_submit = self._now()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        params = params if params is not None else self.ecfg.default_params
        if max_new is not None:
            params = dataclasses.replace(params, max_new=max_new)
        if eos_id is not None:
            params = dataclasses.replace(
                params, stop=tuple(params.stop) + (eos_id,))
        params.validate()
        if session:
            if self._cache_growable:
                raise ValueError(
                    "infinite-stream sessions need a max_seq-independent "
                    "decode state; this family's cache grows with "
                    "max_seq (per-position KV strips)")
            if params.n > 1:
                raise ValueError("sessions are single-stream (n == 1)")
            if prompt.size > self.ecfg.max_seq:
                raise ValueError(
                    f"session prompt ({prompt.size}) exceeds max_seq "
                    f"({self.ecfg.max_seq})")
        elif prompt.size + params.max_new > self.ecfg.max_seq:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new ({params.max_new}) "
                f"exceeds max_seq ({self.ecfg.max_seq})")
        if params.n > self.ecfg.n_slots:
            raise ValueError(
                f"n ({params.n}) exceeds n_slots ({self.ecfg.n_slots}): "
                f"every branch needs a slot")
        if params.n > 1 and stream_cb is not None:
            raise ValueError("stream_cb is unsupported for n > 1 "
                             "(n branches have no single stream)")
        req_id = self._next_id
        self._next_id += 1
        seed = (params.seed if params.seed is not None
                else self._derive_seed(req_id))
        req = Request(req_id=req_id, prompt=prompt, params=params,
                      seed=seed, max_new=params.max_new,
                      stop_ids=frozenset(params.stop), eos_id=eos_id,
                      priority=priority, stream_cb=stream_cb,
                      arrival=arrival or 0.0, t_submit=t_submit,
                      t_arrival=(t_submit if t_arrival is None
                                 else t_arrival),
                      tenant=tenant, session=session)
        self._by_id[req_id] = req
        if arrival is None:
            self._push_ready(req)
        else:
            # bisect keeps the arrival-sorted invariant in O(n) per
            # insert — re-sorting on every submit was O(n^2 log n)
            # across a heavy trace replay
            bisect.insort(self._pending, req, key=lambda r: r.arrival)
        return req

    def _derive_seed(self, req_id: int) -> int:
        """Deterministic per-request seed for unseeded requests: a
        function of (engine seed, request id) only, so streams stay
        reproducible per trace and distinct across requests."""
        return derive_seed(self.ecfg.seed, req_id)

    def submit_snapshot(self, snap, arrival: Optional[float] = None,
                        priority: int = 0,
                        stream_cb: Optional[Callable] = None,
                        tenant: Optional[str] = None,
                        session: bool = False,
                        t_arrival: Optional[float] = None) -> Request:
        """Enqueue a request whose prompt was already prefilled by a
        disaggregated prefill worker (runtime/disagg.py).

        ``snap`` carries the prompt, resolved SamplingParams + seed,
        the post-prompt state block (batch-1 cache pytree: payload,
        absmax scales, stream position — one tree), and the worker's
        first-token surface (token, logprob, top-k rows).  Admission
        restores the state with the pool's one-scatter admit and
        installs the shipped first token — no local prefill — so the
        resulting stream is bitwise the monolithic engine's by
        construction: the worker ran the SAME compiled prefill program
        with the same seed/params, and scatter(gather(x)) is exact
        data movement at any state_dtype.

        The snapshot must come from a compatible engine: same model
        config and state/kv dtypes (checked structurally against the
        pool's cache leaves).  ``t_arrival`` as in ``submit``.
        """
        t_submit = self._now()
        prompt = np.asarray(snap.prompt, np.int32).reshape(-1)
        params = snap.params
        params.validate()
        if params.n > 1:
            raise ValueError("snapshot admission is single-stream "
                             "(best-of-n forks decode-side state that "
                             "does not exist yet)")
        if session and self._cache_growable:
            raise ValueError(
                "infinite-stream sessions need a max_seq-independent "
                "decode state")
        if not session and prompt.size + params.max_new > self.ecfg.max_seq:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new ({params.max_new}) "
                f"exceeds max_seq ({self.ecfg.max_seq})")
        want = jax.tree.leaves(registry.abstract_cache(
            self.cfg, 1, self.ecfg.max_seq))
        got = jax.tree.leaves(snap.state)
        if len(want) != len(got) or any(
                w.shape != g.shape or w.dtype != g.dtype
                for w, g in zip(want, got)):
            raise ValueError(
                "snapshot state does not match this engine's cache "
                "layout (model config / state_dtype / max_seq mismatch)")
        req_id = self._next_id
        self._next_id += 1
        req = Request(req_id=req_id, prompt=prompt, params=params,
                      seed=snap.seed, max_new=params.max_new,
                      stop_ids=frozenset(params.stop),
                      priority=priority, stream_cb=stream_cb,
                      arrival=arrival or 0.0, t_submit=t_submit,
                      t_arrival=(t_submit if t_arrival is None
                                 else t_arrival),
                      tenant=tenant, session=session, snapshot=snap)
        self._by_id[req_id] = req
        if arrival is None:
            self._push_ready(req)
        else:
            bisect.insort(self._pending, req, key=lambda r: r.arrival)
        return req

    def _push_ready(self, req: Request) -> None:
        heapq.heappush(self._ready, (-req.priority, self._seq, req))
        self._seq += 1

    def cancel(self, req_id: int) -> bool:
        """Cancel a request.  Queued requests are dropped before
        admission; a running request's slot (and, mid-speculation, its
        scratch lease) is reclaimed at the next scheduler sync — any
        tokens already delivered stand, no further tokens are produced.
        Safe to call from a ``stream_cb`` (including the request's
        own).  Returns False for unknown / already-finished ids."""
        req = self._by_id.get(req_id)
        if req is None or req.finished or req.cancelled:
            return False
        req.cancelled = True
        if req.branches is not None:
            # best-of-n cascade: the parent holds no slot, the branches
            # do — flag every live child so the sweep reclaims them all
            for child in req.branches:
                if not child.finished:
                    child.cancelled = True
        self._cancel_dirty = True
        return True

    # ------------------------------------------------------------------
    # Scheduler core
    # ------------------------------------------------------------------

    def _drop_cancelled(self, req: Request) -> None:
        """Retire a request cancelled before admission (no slot held)."""
        req.t_done = self._now()
        self.stats.record_cancelled()
        self._finished.append(req)
        self._by_id.pop(req.req_id, None)

    def _sweep_cancelled(self) -> bool:
        """Reclaim every cancelled request at a sync point: evict
        running ones (slot + params row reset), purge queued ones."""
        if not self._cancel_dirty:
            return False
        self._cancel_dirty = False
        did = False
        for slot, req in enumerate(self._slot_req):
            if req is not None and req.cancelled:
                self._finish(slot)
                did = True
        if any(r.cancelled for r in self._pending):
            keep = []
            for r in self._pending:
                (keep.append(r) if not r.cancelled
                 else self._drop_cancelled(r))
            self._pending = keep
            did = True
        if any(e[2].cancelled for e in self._ready):
            for e in self._ready:
                if e[2].cancelled:
                    self._drop_cancelled(e[2])
            # keep the ORIGINAL (priority, seq) tuples: re-pushing with
            # fresh seqs would reassign FIFO order from raw heap-array
            # order and let later submissions jump earlier ones
            self._ready = [e for e in self._ready if not e[2].cancelled]
            heapq.heapify(self._ready)
            did = True
        return did

    def _deliver(self, req: Request, new_toks: list) -> None:
        """Stream delivery at a scheduler sync; the callback may flag a
        cancellation, which the caller reclaims right after.

        A RAISING callback is the client's failure, not the batch's:
        the exception is caught here (it used to propagate out of the
        scheduler loop and abort every co-resident stream), counted in
        ``ServeStats.n_callback_errors``, the callback dropped so it is
        never called again, and the offending request auto-cancelled —
        its slot is reclaimed at this same sync by the caller's
        existing cancelled-check, and every other stream is bitwise
        untouched (delivery never feeds back into token math)."""
        if req.stream_cb is None or not new_toks:
            return
        try:
            req.stream_cb(req, new_toks)
        except Exception:
            self.stats.n_callback_errors += 1
            req.stream_cb = None
            if not req.finished and not req.cancelled:
                self.cancel(req.req_id)

    def _append_token(self, req: Request, tok: int, lp, tv, ti) -> None:
        """Record one emitted token plus its logprob surface: chosen
        logprob always accumulates into cum_logprob (it ranks best-of-n
        branches); the per-token lists fill only when the request asked
        (params.logprobs / params.top_logprobs)."""
        req.tokens.append(tok)
        req.cum_logprob += float(lp)
        if req.params.logprobs:
            req.logprobs.append(float(lp))
        if req.params.top_logprobs:
            k = req.params.top_logprobs
            req.top_logprobs.append(
                [(int(ti[i]), float(tv[i])) for i in range(k)])

    def _span(self, name: str, req: Optional[int] = None):
        """A host span (runtime/tracing.py) on this engine's clock."""
        return tracing.span(name, req, self._now)

    def _admit_into_slot(self, req: Request, slot: int):
        """Prefill ``req``'s prompt into ``slot`` (params row already
        set, ``req.t_admit`` stamped by the caller's ``engine.admit``
        span), consulting the prefix cache when enabled.  Cache hit:
        restore the deepest cached block-boundary snapshot and chain
        only the suffix through the decode-step micro-scan.  Cold (with
        a usable boundary): prefill the first block once, then chain
        the rest — inserting a snapshot at EVERY boundary the chain
        crosses, so later prompts sharing any block-aligned prefix hit.
        Returns (tok, lp, tv_row, ti_row, last_logits) with the first
        three host-side and ``last_logits`` the device (1, V) logits
        best-of-n samples its remaining branches' first tokens from."""
        prompt = req.prompt
        length = int(prompt.size)
        pc = self._prefix
        hit = None
        p_from = 0
        bound = pc.boundary(length) if pc is not None else 0
        with self._span("engine.admit.prefill"):
            sp_row = self.pool.params.row(slot)
            step0 = jnp.zeros((1,), jnp.int32)
            slot_arr = jnp.asarray([slot])
            snap = None
            if pc is not None and bound > 0:
                hit = pc.lookup(prompt)
                if hit is not None:
                    p_from, snap = hit
                else:
                    # cold: one fixed-block-length prefill seeds the
                    # first snapshot; the suffix scan below computes the
                    # rest
                    p_from = pc.cfg.block
                    snap = self._prefill_prefix(
                        self.prefill_params, self.pool.fresh,
                        jnp.asarray(prompt[None, :p_from]))
                    pc.insert(prompt[:p_from], snap)
            if snap is None:
                tok_dev, lp, tv, ti, last, new_pool = self._prefill(
                    self.prefill_params, self.pool.fresh,
                    jnp.asarray(prompt[None]),
                    self.pool.cache, slot_arr, sp_row, step0)
                self.pool.cache = new_pool
            else:
                m = length - p_from
                fn = _jit_suffix_admit(self.cfg, m, self._shard)
                tok_dev, lp, tv, ti, last, new_pool, chain = fn(
                    self.prefill_params, snap,
                    jnp.asarray(prompt[None, p_from:]),
                    self.pool.cache, slot_arr, sp_row, step0)
                self.pool.cache = new_pool
                # chain index j is the state after prompt[:p_from + j + 1]
                for p in range(p_from + pc.cfg.block, bound + 1,
                               pc.cfg.block):
                    pc.insert(prompt[:p],
                              jax.tree.map(
                                  lambda leaf, j=p - p_from - 1: leaf[j],
                                  chain))
        if pc is not None and bound > 0:
            self.stats.record_prefix(hit is not None,
                                     p_from if hit is not None else 0)
        n_computed = length - (p_from if hit is not None else 0)
        with self._span("engine.admit.sync") as sync:
            tok_h, lp_h, tv_h, ti_h = jax.device_get((tok_dev, lp, tv, ti))
        req.t_first = sync.t1
        # prefill_tokens stays the honest COMPUTE count: restored-from-
        # cache tokens land in prefix_cached_tokens instead, which is
        # what the bench gate's strict-reduction assertion diffs
        self.stats.record_prefill(n_computed, req.t_first - req.t_admit)
        return (int(tok_h[0, 0]), float(lp_h[0]), tv_h[0], ti_h[0], last)

    def _install(self, req: Request, slot: int, tok: int, lp, tv,
                 ti) -> None:
        """Bind an admitted request to its slot and deliver its first
        token (shared tail of plain and best-of-n admission)."""
        self._slot_req[slot] = req
        req.slot = slot
        self._next_tok[slot, 0] = tok
        self._append_token(req, tok, lp, tv, ti)
        if self._hit_stop(req):
            self._finish(slot)
        self._deliver(req, [tok])
        if req.cancelled and not req.finished:
            self._finish(slot)

    def _admit_snapshot_into_slot(self, req: Request, slot: int):
        """Disaggregated admission: one scatter of the shipped state
        block into ``slot`` — the same ``SlotStatePool.admit`` a prefix
        restore uses — then install the worker's first token.  No local
        prefill ran, so prefill_tokens is untouched; the transfer is
        accounted in the snapshot_* counters."""
        snap = req.snapshot
        with self._span("engine.admit.prefill") as restore:
            self.pool.admit(slot, snapshot_to_device(snap.state))
        req.t_first = restore.t1
        self.stats.record_snapshot_admit(n_tokens=int(req.prompt.size),
                                         nbytes=snap.nbytes)
        return snap.tok, snap.lp, np.asarray(snap.tv), np.asarray(snap.ti)

    def _admit(self, req: Request) -> None:
        """One admission, inside the ``engine.admit`` span: slot and
        params row, prefill (or snapshot restore) and the first token's
        host read, then ``_install``."""
        with self._span("engine.admit", req.req_id) as span:
            req.t_admit = span.t0
            slot = self.pool.alloc()
            assert slot is not None
            self.pool.params.set(slot, req.params, req.seed)
            if req.snapshot is not None:
                tok, lp, tv, ti = self._admit_snapshot_into_slot(req, slot)
            else:
                tok, lp, tv, ti, _ = self._admit_into_slot(req, slot)
            if req.session:
                # eviction-free lease: _finish unpins before evicting
                self.pool.pin(slot)
            self._install(req, slot, tok, lp, tv, ti)

    def _branch_request(self, parent: Request, b: int) -> Request:
        """Child request for branch ``b`` of a best-of-n parent.  The
        child's key row is NOT derived from its seed — the fork's
        branch-tag fold is its key derivation — so ``seed`` is carried
        only for bookkeeping."""
        child = Request(
            req_id=self._next_id, prompt=parent.prompt,
            params=dataclasses.replace(parent.params, n=1),
            seed=parent.seed, max_new=parent.max_new,
            stop_ids=parent.stop_ids, eos_id=parent.eos_id,
            priority=parent.priority, t_arrival=parent.t_arrival,
            t_submit=parent.t_submit, t_admit=parent.t_admit,
            branch=b, parent=parent)
        self._next_id += 1
        self._by_id[child.req_id] = child
        return child

    def _admit_group(self, parent: Request) -> None:
        """Best-of-n admission, one ``engine.admit`` span under the
        parent's id: ONE prefill into the first slot, then one fused
        fork into the remaining n-1 slots with branch tags 1..n-1 (each
        branch's key = fold_in(parent key, branch) — the fork-seed
        aliasing fix), then each remaining branch's first token sampled
        from the prefill's last-position logits under its own folded
        key.  Branch 0 keeps the parent's verbatim key, so its stream
        is bitwise the same request served at n=1."""
        with self._span("engine.admit", parent.req_id) as span:
            parent.t_admit = span.t0
            n = parent.params.n
            slots = [self.pool.alloc() for _ in range(n)]
            assert all(s is not None for s in slots)
            children = [self._branch_request(parent, b) for b in range(n)]
            parent.branches = list(children)
            parent._open = n
            self.pool.params.set(slots[0], parent.params, parent.seed)
            tok0, lp0, tv0, ti0, last = self._admit_into_slot(children[0],
                                                              slots[0])
            parent.t_first = children[0].t_first
            # fork BEFORE any stop/cancel handling can evict slot 0:
            # every branch needs its post-prompt state (and its params
            # row, which the tagged copy re-keys)
            self.pool.fork([slots[0]] * (n - 1), slots[1:],
                           branch_tags=list(range(1, n)))
            firsts = [(tok0, lp0, tv0, ti0)]
            for b in range(1, n):
                row = self.pool.params.row(slots[b])
                tb = sampling.sample(last, row, jnp.zeros((1,), jnp.int32))
                lb, tvb, tib = sampling.token_logprobs(last, tb)
                firsts.append((int(np.asarray(tb)[0]),
                               float(np.asarray(lb)[0]),
                               np.asarray(tvb)[0], np.asarray(tib)[0]))
            for b in range(n):
                tok, lp, tv, ti = firsts[b]
                self._install(children[b], slots[b], tok, lp, tv, ti)

    def _hit_stop(self, req: Request) -> bool:
        # a session has no token horizon: only stops / cancel end it
        if not req.session and len(req.tokens) >= req.max_new:
            return True
        if req.stop_ids and req.tokens[-1] in req.stop_ids:
            return True
        # multi-token stop sequences: suffix-window match on the emitted
        # stream (the whole sequence is delivered; burst overshoot past
        # the match is trimmed by the caller's break, like single stops)
        for seq in req.params.stop_seqs:
            seq = tuple(seq)
            if (len(req.tokens) >= len(seq)
                    and tuple(req.tokens[-len(seq):]) == seq):
                return True
        return False

    def _finish(self, slot: int) -> None:
        req = self._slot_req[slot]
        req.t_done = self._now()
        if req.parent is not None:
            # branch of a best-of-n group: stats and the finished list
            # see only the parent (one request submitted, one retired)
            pass
        elif req.cancelled:
            self.stats.record_cancelled()
        else:
            self.stats.record_request(ttft=req.t_first - req.t_arrival,
                                      latency=req.t_done - req.t_arrival,
                                      n_tokens=len(req.tokens),
                                      tenant=req.tenant)
        with self._span("engine.evict", req.req_id):
            if req.session:
                self.pool.unpin(slot)
            self.pool.evict(slot)
        self._slot_req[slot] = None
        self._next_tok[slot, 0] = 0
        if req.parent is None:
            self._finished.append(req)
        self._by_id.pop(req.req_id, None)
        if req.parent is not None:
            self._child_done(req)

    def _child_done(self, child: Request) -> None:
        parent = child.parent
        parent._open -= 1
        if parent._open == 0:
            self._finalize_parent(parent)

    def _finalize_parent(self, parent: Request) -> None:
        """All branches finished: rank them by cumulative logprob
        (ties broken by branch index — deterministic), surface the best
        branch's stream on the parent, retire the parent."""
        kids = sorted(parent.branches,
                      key=lambda c: (-c.cum_logprob, c.branch))
        parent.branches = kids
        best = kids[0]
        parent.tokens = list(best.tokens)
        parent.logprobs = list(best.logprobs)
        parent.top_logprobs = list(best.top_logprobs)
        parent.cum_logprob = best.cum_logprob
        parent.t_done = self._now()
        if parent.cancelled:
            self.stats.record_cancelled()
        else:
            self.stats.record_request(
                ttft=parent.t_first - parent.t_arrival,
                latency=parent.t_done - parent.t_arrival,
                n_tokens=len(parent.tokens), tenant=parent.tenant)
        self._finished.append(parent)
        self._by_id.pop(parent.req_id, None)

    def _base_steps(self, active) -> np.ndarray:
        """Per-slot stream positions at sync start: tokens already
        emitted — the fold_in counter that keys each slot's next
        draws."""
        base = np.zeros((self.pool.n_total,), np.int32)
        for s in active:
            base[s] = len(self._slot_req[s].tokens)
        return base

    @staticmethod
    def _remaining(req: Request) -> int:
        """Token budget left before the CERTAIN eviction — a session
        has none (only stops/cancel end it), so it reports an effectively
        infinite horizon and must never be the burst planner's certain
        event."""
        if req.session:
            return 1 << 30
        return req.max_new - len(req.tokens)

    def _burst_len(self, active) -> int:
        """Decode steps until the next scheduling event.

        The shortest remaining token budget among active slots is the
        next *certain* eviction; nothing can be admitted before then when
        all slots are busy, so in that state the burst runs uncapped to
        the eviction — zero intermediate host syncs, matching a static
        loop's dispatch pipelining with none of its wasted steps.  The
        quantum caps the burst only when an *uncertain* event could act
        sooner: a stop token (single-id or multi-token sequence) may
        evict any step (overshoot is trimmed but wastes the slot until
        the burst ends), a streaming callback must be serviced
        regularly (it may cancel mid-stream), a pending prefix-cache
        snapshot offload is waiting for the next host sync (the
        cache-snapshot deadline), a free slot plus queued/pending
        work means an admission check is worth taking, and an
        infinite-stream session can only ever end on an uncertain
        event (its ``_remaining`` is unbounded — without the quantum
        cap the burst would never return to the host)."""
        remaining = min(self._remaining(self._slot_req[s])
                        for s in active)
        uncertain = any(self._slot_req[s].stop_ids
                        or self._slot_req[s].params.stop_seqs
                        or self._slot_req[s].stream_cb is not None
                        or self._slot_req[s].session
                        for s in active)
        if self._prefix is not None and self._prefix.has_pending():
            uncertain = True
        may_admit = self.pool.n_free > 0 and (self._ready or self._pending)
        if uncertain or may_admit:
            return max(1, min(remaining, self.ecfg.sched_quantum))
        return max(1, remaining)

    def _decode_burst(self) -> None:
        """One ``engine.burst``: prepare the step inputs, enqueue
        ``n_steps`` chained decode steps, one host sync, then deliver
        (token append, stop checks, callbacks, evictions)."""
        with self._span("engine.burst") as span:
            with self._span("engine.burst.prepare"):
                active = self.pool.active_slots()
                n_steps = self._burst_len(active)
                toks = jnp.asarray(self._next_tok)
                act = jnp.asarray(self.pool.active_mask())
                sp = self.pool.params.device()
                base = jnp.asarray(self._base_steps(active))
            with self._span("engine.burst.dispatch"):
                cache = self.pool.cache
                outs, lps, tvs, tis = [], [], [], []
                for t in range(n_steps):
                    toks, lp, tv, ti, cache = self._decode(
                        self.params, cache, toks, act, sp, base + t)
                    outs.append(toks)
                    lps.append(lp)
                    tvs.append(tv)
                    tis.append(ti)
                self.pool.cache = cache
            # one host sync per burst; device_get on the lists avoids
            # compiling an XLA concatenate per distinct burst length
            with self._span("engine.burst.sync"):
                outs_h, lp_h, tv_h, ti_h = jax.device_get(
                    (outs, lps, tvs, tis))
            with self._span("engine.burst.deliver"):
                n_appended = self._deliver_burst(
                    active, n_steps, np.concatenate(outs_h, axis=1), lp_h,
                    tv_h, ti_h)
        self.stats.record_decode(n_active=len(active),
                                 n_slots=self.ecfg.n_slots,
                                 dt=span.t1 - span.t0,
                                 n_steps=n_steps, n_tokens=n_appended)

    def _deliver_burst(self, active, n_steps: int, burst, lp_h, tv_h,
                       ti_h) -> int:
        """Append each active slot's burst tokens up to its first stop,
        stream them, and retire finished or cancelled requests.
        Returns the tokens appended."""
        n_appended = 0
        for slot in active:
            req = self._slot_req[slot]
            new_toks = []
            for t in range(n_steps):
                tok = int(burst[slot, t])
                self._append_token(req, tok, lp_h[t][slot],
                                   tv_h[t][slot], ti_h[t][slot])
                new_toks.append(tok)
                n_appended += 1
                self._next_tok[slot, 0] = tok
                if self._hit_stop(req):
                    self._finish(slot)
                    break                 # trim overshoot past a stop
            self._deliver(req, new_toks)
            if req.cancelled and not req.finished:
                self._finish(slot)
        return n_appended

    # ------------------------------------------------------------------
    # Speculative decoding (EngineConfig.draft)
    # ------------------------------------------------------------------

    def _slot_depth(self, req: Request) -> int:
        """Per-slot speculative window (DraftConfig.adaptive): after
        warmup, clamp to the request's realized acceptance + 1 token of
        optimism — pure depth arithmetic, never touches token values,
        so greedy identity survives."""
        dc = self.ecfg.draft
        # the scheduler's degradation cap composes with (never replaces)
        # the adaptive clamp: under load the window shrinks to spec_cap
        # even for a perfectly-accepting slot
        kmax = (self._spec.k if self.spec_cap is None
                else max(1, min(self._spec.k, self.spec_cap)))
        # warmup floors at 1 pass: the clamp needs at least one realized
        # pass or the division below has nothing to divide by
        if not dc.adaptive or req.spec_passes < max(1, dc.adapt_warmup):
            return kmax
        realized = req.spec_accepted / req.spec_passes
        return int(min(kmax, max(1, math.ceil(realized) + 1)))

    def _spec_pass(self) -> None:
        """One fork -> K-draft -> batched-verify -> rollback pass over
        the live slots, emitting 1..K+1 tokens per slot per target
        pass.  Device work chains across fork/draft/verify; the host
        syncs once per pass for accept/stop bookkeeping (vs once per
        token for plain decode — the sync amortization IS part of the
        spec win).  Scratch leases are released even if a jit raises
        mid-pass (the pool-leak tests cover an abandoned burst)."""
        spec = self._spec
        active = self.pool.active_slots()
        # clamp the draft window to the shortest remaining token budget:
        # a slot about to hit max_new would have its whole window
        # trimmed anyway, so drafting past it is pure wasted dispatch
        # (stop tokens stay an uncertain event and are still trimmed
        # host-side); adaptive per-slot depth shrinks it further when
        # every slot's realized acceptance is low
        remaining = min(self._remaining(self._slot_req[s])
                        for s in active)
        depths = {s: self._slot_depth(self._slot_req[s]) for s in active}
        k_eff = min(max(depths.values()), remaining - 1)
        if k_eff < 1:
            # every active slot needs exactly one more token: plain
            # decode burst (its own burst-length logic handles this)
            self._decode_burst()
            return
        with self._span("engine.spec_pass") as span:
            leases: list[int] = []
            try:
                for _ in active:
                    sc = self.pool.lease_scratch()
                    assert sc is not None        # n_scratch == n_slots
                    leases.append(sc)
                # branch_tags deliberately None: the draft scratch slot must
                # continue the request's EXACT key schedule (fork copies the
                # key verbatim) or spec decode loses its faithfulness
                # contract — only best-of-n forks tag
                self.pool.fork(active, leases)   # state + sampling params
                total = self.pool.n_total
                toks = np.zeros((total, 1), np.int32)
                toks[leases, 0] = self._next_tok[active, 0]
                scratch_mask = np.zeros((total,), bool)
                scratch_mask[leases] = True
                base = self._base_steps(active)
                base[leases] = base[active]      # draft keys mirror live
                limit = np.full((total,), k_eff, np.int32)
                for s in active:
                    limit[s] = min(depths[s], k_eff)
                sp = self.pool.params.device()
                cache, d_toks, d_logits = spec.propose(
                    self.pool.cache, jnp.asarray(toks),
                    jnp.asarray(scratch_mask), sp, jnp.asarray(base), k_eff)
                # proposals were drafted at scratch rows; the verify wants
                # them at their live slots' rows
                perm = np.arange(total)
                perm[active] = leases
                perm = jnp.asarray(perm)
                emit, n_acc, _, snap, v_lp, v_tv, v_ti = spec.verify(
                    self.params, cache, jnp.asarray(self._next_tok),
                    d_toks[:, perm], d_logits[:, perm],
                    jnp.asarray(self.pool.active_mask()), sp,
                    jnp.asarray(base), jnp.asarray(limit))
                # the rollback: every live slot's row of ``snap`` is the
                # state after exactly its accepted prefix
                self.pool.cache = snap
                emit_h, n_acc_h = np.asarray(emit), np.asarray(n_acc)
                lp_h, tv_h, ti_h = (np.asarray(v_lp), np.asarray(v_tv),
                                    np.asarray(v_ti))
            finally:
                for sc in leases:
                    self.pool.release_scratch(sc)
            n_appended = 0
            n_accepted = 0
            for slot in active:
                req = self._slot_req[slot]
                n_emit = int(n_acc_h[slot]) + 1
                n_accepted += n_emit - 1
                req.spec_passes += 1
                req.spec_accepted += n_emit - 1
                new_toks = []
                for t in range(n_emit):
                    tok = int(emit_h[t, slot])
                    self._append_token(req, tok, lp_h[t, slot],
                                       tv_h[t, slot], ti_h[t, slot])
                    new_toks.append(tok)
                    n_appended += 1
                    self._next_tok[slot, 0] = tok
                    if self._hit_stop(req):
                        self._finish(slot)
                        break                 # trim overshoot past stop/budget
                self._deliver(req, new_toks)
                if req.cancelled and not req.finished:
                    self._finish(slot)
        self.stats.record_decode(n_active=len(active),
                                 n_slots=self.ecfg.n_slots,
                                 dt=span.t1 - span.t0,
                                 n_steps=k_eff + 1, n_tokens=n_appended)
        self.stats.record_spec(n_active=len(active),
                               n_drafted=k_eff * len(active),
                               n_accepted=n_accepted,
                               n_emitted=n_appended)

    def step(self) -> bool:
        """One scheduler iteration: reclaim cancellations, admit into
        free slots (highest priority first), then one decode burst (or
        one speculative pass).  Returns False when there was nothing
        to do.  Admission peeks before popping: a best-of-n request
        needs ``n`` free slots at once, and blocks the line until it
        has them (admitting lower-priority work past it would starve
        it forever under load).  All of it is the ``engine.step``
        span."""
        with self._span("engine.step"):
            did = self._sweep_cancelled()
            while self._ready and self.pool.n_free:
                req = self._ready[0][2]
                if req.cancelled:
                    heapq.heappop(self._ready)
                    self._drop_cancelled(req)
                    continue
                if req.params.n > self.pool.n_free:
                    break
                heapq.heappop(self._ready)
                if req.params.n > 1:
                    self._admit_group(req)
                else:
                    self._admit(req)
                did = True
            if self.pool.n_active:
                if self._spec is not None:
                    self._spec_pass()
                else:
                    self._decode_burst()
                did = True
            if self._prefix is not None:
                # the burst just host-synced: drain one deferred
                # host-store snapshot offload (the cache-snapshot
                # deadline) and adopt the cache's storage counters
                if self._prefix.has_pending():
                    with self._span("engine.flush_pending"):
                        self._prefix.flush_pending(limit=1)
                    did = True
                self.stats.sync_prefix(self._prefix.counters())
            return did

    # ------------------------------------------------------------------
    # Drive loop
    # ------------------------------------------------------------------

    def run(self) -> list[Request]:
        """Run until every submitted request is finished or cancelled;
        replays arrival-gated requests against a wall clock starting
        now.  Returns the requests retired during THIS call, in
        completion order (the engine keeps no reference afterwards)."""
        self.stats.start()
        self._finished = []
        t0 = self._now()
        while self._pending or self._ready or self.pool.n_active:
            now = self._now() - t0
            while self._pending and self._pending[0].arrival <= now:
                req = self._pending.pop(0)
                if req.cancelled:
                    self._drop_cancelled(req)
                    continue
                # TTFT/latency are measured from the (simulated) arrival,
                # not from when the trace was queued before run()
                req.t_submit = req.t_arrival = self._now()
                self._push_ready(req)
            if not self.step() and self._pending:
                wait = self._pending[0].arrival - (self._now() - t0)
                if wait > 0:
                    time.sleep(min(wait, 0.05))
        if self._prefix is not None:
            # idle: no burst deadline competes with the offloads
            with self._span("engine.flush_pending"):
                self._prefix.flush_pending(limit=None)
            self.stats.sync_prefix(self._prefix.counters())
        self.stats.stop()
        return self._finished
