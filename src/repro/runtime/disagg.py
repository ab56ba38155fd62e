"""Prefill/decode disaggregation over the snapshot-admission path.

Prefill and decode want different machines: prefill is one big
compute-bound matmul over the whole prompt, decode is thousands of tiny
bandwidth-bound steps.  Disaggregated serving runs them in different
pools and ships the post-prompt state across.  For transformer serving
that means moving an O(L * max_seq * d) KV cache; for the SSM families
here the entire per-sequence state is a fixed O(d_inner * d_state)
block (plus conv tail / absmax scales / stream position) — the same
tiny pytree the prefix cache already snapshots — so the handoff is one
host round-trip of a few hundred KB regardless of prompt length.

Exactness contract (bitwise, by construction, per family x state_dtype):

  1. The prefill worker is a 1-slot Engine over the same model config —
     it runs the SAME compiled ``_jit_prefill_admit`` /
     ``_jit_suffix_admit`` programs a monolithic engine runs at
     admission, with the same resolved seed and params (seeds derive
     from the submission index via ``engine.derive_seed``, matching the
     monolithic engine's numbering).
  2. The shipped payload is ``snapshot_to_host(pool.read([slot]))`` and
     decode-side admission is ``pool.admit(slot, snapshot_to_device(.))``
     — gather, copy, scatter: exact data movement at any state_dtype
     (quantized payloads and their scales travel in one pytree).
  3. The first token (and its logprob surface) was already sampled by
     the worker's fused prefill under the request's own key at step 0;
     it ships with the snapshot and is installed verbatim.  Decode
     steps >= 1 then run under per-slot counter-based keys
     (fold_in(key(seed), token_index)) — batch-composition-independent
     by the engine's existing PRNG discipline.

So a disaggregated stream is token-identical to the monolithic engine's
stream for the same submission order — not "close", identical — which
``tests/test_disagg.py`` asserts across families and state dtypes.

The transfer queue between the pools is BOUNDED (``queue_depth``):
prefill production stalls rather than buffering unbounded state blocks,
which is the backpressure a real two-pool deployment needs (the queue
stands in for the interconnect; counters expose depth/bytes so the
bench gate can pin them).
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Optional

import numpy as np

from repro.runtime.engine import Engine, EngineConfig, Request, derive_seed
from repro.runtime.prefix_cache import snapshot_to_host, tree_bytes
from repro.runtime.sampling import SamplingParams


@dataclasses.dataclass
class Snapshot:
    """One prefilled request, ready to decode anywhere: prompt +
    resolved sampling identity (params, seed) + the post-prompt state
    block + the worker-sampled first-token surface."""
    prompt: np.ndarray
    params: SamplingParams
    seed: int
    state: object                 # host-resident batch-1 cache pytree
    tok: int                      # first token (sampled at step 0)
    lp: float                     # its chosen logprob
    tv: np.ndarray                # top-k logprob values row
    ti: np.ndarray                # top-k token id row
    nbytes: int                   # state payload bytes (the wire cost)


class PrefillWorker:
    """A 1-slot prefill pool: admits into its single slot with the
    shared compiled prefill programs, gathers the state back out, and
    never decodes.  Reuses the engine's prefix-cache path, so a worker
    serving prompts with shared prefixes snapshots/restores exactly
    like a monolithic engine would."""

    def __init__(self, cfg, params, ecfg: EngineConfig):
        wcfg = dataclasses.replace(ecfg, n_slots=1, draft=None)
        self.engine = Engine(cfg, params, wcfg)
        self.n_prefilled = 0

    def prefill(self, prompt, params: SamplingParams, seed: int) -> Snapshot:
        """Run one prompt through the fused prefill-admit path and
        export the slot as a host snapshot.  The slot is evicted
        immediately — the worker holds no residency."""
        eng = self.engine
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        slot = eng.pool.alloc()
        assert slot is not None          # 1-slot pool, always drained
        eng.pool.params.set(slot, params, seed)
        req = Request(req_id=self.n_prefilled, prompt=prompt,
                      params=params, seed=seed, max_new=params.max_new,
                      stop_ids=frozenset(params.stop))
        try:
            with eng._span("engine.admit", req.req_id) as span:
                req.t_admit = span.t0
                tok, lp, tv, ti, _ = eng._admit_into_slot(req, slot)
            state = snapshot_to_host(eng.pool.read([slot]))
        finally:
            eng.pool.evict(slot)
        if eng._prefix is not None:
            eng._prefix.flush_pending(limit=None)
            eng.stats.sync_prefix(eng._prefix.counters())
        self.n_prefilled += 1
        return Snapshot(prompt=prompt, params=params, seed=seed,
                        state=state, tok=tok, lp=lp, tv=tv, ti=ti,
                        nbytes=tree_bytes(state))


@dataclasses.dataclass
class DisaggConfig:
    """queue_depth: max prefilled snapshots in flight between the
    pools — prefill production stalls at the bound (backpressure)."""
    queue_depth: int = 8

    def validate(self) -> None:
        if self.queue_depth < 1:
            raise ValueError("queue_depth must be >= 1")


@dataclasses.dataclass(eq=False)
class _Item:
    """A submission moving through the pipeline.  Identity semantics
    (eq=False): tickets are handles, and dataclass field comparison
    would ambiguously compare prompt arrays in ``deque.__contains__``.
    """
    prompt: np.ndarray
    params: SamplingParams
    seed: int
    kw: dict                      # decode-side submit_snapshot kwargs
    snap: Optional[Snapshot] = None
    req: Optional[Request] = None


class DisaggPipeline:
    """Prefill pool -> bounded transfer queue -> decode pool.

    Drop-in for an Engine at the submit/run level: ``submit`` mirrors
    ``Engine.submit`` (minus best-of-n, which forks decode-side state
    that does not exist at prefill time), ``run`` drives both pools to
    completion.  ``step`` interleaves deterministically: fill the
    transfer queue up to its bound, drain into free decode slots, one
    decode scheduler step."""

    def __init__(self, cfg, params, ecfg: EngineConfig,
                 dcfg: Optional[DisaggConfig] = None):
        self.dcfg = dcfg or DisaggConfig()
        self.dcfg.validate()
        self.worker = PrefillWorker(cfg, params, ecfg)
        self.decode = Engine(cfg, params, ecfg)
        self._pending: "collections.deque[_Item]" = collections.deque()
        self._queue: "collections.deque[_Item]" = collections.deque()
        self._next_id = 0
        # wire accounting (the bench gate pins these)
        self.transfers = 0
        self.transfer_bytes = 0
        self.max_queue_depth = 0

    def submit(self, prompt, params: Optional[SamplingParams] = None,
               max_new: Optional[int] = None,
               eos_id: Optional[int] = None,
               stream_cb=None, tenant: Optional[str] = None,
               session: bool = False, priority: int = 0) -> _Item:
        """Mirror of ``Engine.submit`` — including its seed numbering:
        submission i gets ``derive_seed(ecfg.seed, i)`` when unseeded,
        so the pipeline's streams are bitwise a monolithic engine's for
        the same submission order."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        params = (params if params is not None
                  else self.decode.ecfg.default_params)
        if max_new is not None:
            params = dataclasses.replace(params, max_new=max_new)
        if eos_id is not None:
            params = dataclasses.replace(
                params, stop=tuple(params.stop) + (eos_id,))
        params.validate()
        if params.n > 1:
            raise ValueError("disaggregated serving is single-stream "
                             "(best-of-n forks decode-side state)")
        if not session and (prompt.size + params.max_new
                            > self.decode.ecfg.max_seq):
            raise ValueError(
                f"prompt ({prompt.size}) + max_new ({params.max_new}) "
                f"exceeds max_seq ({self.decode.ecfg.max_seq})")
        seed = (params.seed if params.seed is not None
                else derive_seed(self.decode.ecfg.seed, self._next_id))
        self._next_id += 1
        item = _Item(prompt=prompt, params=params, seed=seed,
                     kw=dict(stream_cb=stream_cb, tenant=tenant,
                             session=session, priority=priority))
        self._pending.append(item)
        return item

    def cancel(self, item: _Item) -> bool:
        """Cancel wherever the request currently lives: un-prefilled
        and in-flight snapshots are dropped from the pipeline; admitted
        requests cancel through the decode engine."""
        if item in self._pending:
            self._pending.remove(item)
            return True
        if item in self._queue:
            self._queue.remove(item)
            return True
        if item.req is not None:
            return self.decode.cancel(item.req.req_id)
        return False

    # -- drive --------------------------------------------------------------

    def step(self) -> bool:
        did = False
        # produce: prefill into the transfer queue up to its bound
        while self._pending and len(self._queue) < self.dcfg.queue_depth:
            item = self._pending.popleft()
            item.snap = self.worker.prefill(item.prompt, item.params,
                                            item.seed)
            self._queue.append(item)
            self.transfers += 1
            self.transfer_bytes += item.snap.nbytes
            self.max_queue_depth = max(self.max_queue_depth,
                                       len(self._queue))
            did = True
        # drain: one-scatter admission into free decode slots
        while (self._queue and self.decode.pool.n_free
               > len(self.decode._ready)):
            item = self._queue.popleft()
            item.req = self.decode.submit_snapshot(item.snap, **item.kw)
            did = True
        return self.decode.step() or did

    def busy(self) -> bool:
        return bool(self._pending or self._queue or self.decode._ready
                    or self.decode.pool.n_active)

    def run(self) -> list:
        """Drive both pools until every request retires (sessions must
        be cancelled by the caller, as with ``Engine.run``).  Returns
        the decode engine's finished requests in completion order."""
        self.decode.stats.start()
        self.decode._finished = []
        while self.busy():
            self.step()
        self.decode.stats.stop()
        return self.decode._finished

    def counters(self) -> dict:
        return {
            "transfers": self.transfers,
            "transfer_bytes": self.transfer_bytes,
            "max_queue_depth": self.max_queue_depth,
            "prefilled": self.worker.n_prefilled,
        }
