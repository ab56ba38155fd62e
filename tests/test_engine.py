"""Continuous-batching engine tests: slot pool state hygiene, decode
parity over many steps (the invariant slot admission relies on), scan
resumability across chunk boundaries, and engine-vs-sequential
equivalence under slot churn."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import ops
from repro.models import registry
from repro.parallel import sharding
from repro.runtime.engine import Engine, EngineConfig
from repro.runtime.state_pool import SlotStatePool

jax.config.update("jax_platform_name", "cpu")


def _setup(name):
    cfg = configs.smoke_variant(configs.get_config(name))
    cfg = dataclasses.replace(cfg, vocab=64, dtype="float32",
                              capacity_factor=float(max(cfg.n_experts, 1)))
    params = sharding.tree_values(
        registry.init_params(cfg, jax.random.key(0)))
    return cfg, params


def _tree_equal(a, b):
    flat_a, flat_b = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(flat_a) == len(flat_b)
    return all(bool(jnp.array_equal(x, y.astype(x.dtype)))
               for x, y in zip(flat_a, flat_b))


DECODE_ARCHS = ["mamba-130m", "granite-20b", "qwen2-7b", "jamba-v0.1-52b",
                "xlstm-350m", "qwen2-moe-a2.7b"]
POOL_ARCHS = ["mamba-130m", "granite-20b", "jamba-v0.1-52b", "xlstm-350m"]


# ---------------------------------------------------------------------------
# Slot state pool
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", POOL_ARCHS)
def test_pool_admit_read_roundtrip_bitexact(name):
    """Scatter of prefilled state into a slot, then gather, is the
    identity — per-slot state survives pooling bit-exactly."""
    cfg, params = _setup(name)
    pool = SlotStatePool(cfg, n_slots=3, max_seq=32)
    fresh = sharding.tree_values(registry.init_cache(cfg, 1, 32))
    toks = jax.random.randint(jax.random.key(1), (1, 7), 0, cfg.vocab,
                              dtype=jnp.int32)
    _, sub = registry.prefill(cfg, params, fresh, {"tokens": toks})
    slot = pool.alloc()
    pool.admit(slot, sub)
    assert _tree_equal(sub, pool.read([slot]))


def test_pool_alloc_evict_accounting():
    cfg, _ = _setup("mamba-130m")
    pool = SlotStatePool(cfg, n_slots=2, max_seq=16)
    a, b = pool.alloc(), pool.alloc()
    assert {a, b} == {0, 1} and pool.alloc() is None
    assert pool.n_active == 2 and pool.n_free == 0
    pool.evict(a)
    assert pool.n_free == 1 and pool.active_slots() == [b]
    assert pool.alloc() == a           # lowest-first reuse


@pytest.mark.parametrize("name", ["mamba-130m", "granite-20b"])
def test_evicted_slot_never_leaks_into_new_request(name):
    """Admit A, decode it a few steps, evict, admit B into the same slot:
    the slot's state must equal a fresh prefill of B bit-exactly, and the
    other slot must be untouched throughout."""
    cfg, params = _setup(name)
    pool = SlotStatePool(cfg, n_slots=2, max_seq=32)
    fresh = lambda: sharding.tree_values(registry.init_cache(cfg, 1, 32))
    key = jax.random.key(2)
    pa, pb, pc = (jax.random.randint(jax.random.fold_in(key, i), (1, 5 + i),
                                     0, cfg.vocab, dtype=jnp.int32)
                  for i in range(3))
    # bystander request C in slot 1
    sc_slot = 1
    _, sub_c = registry.prefill(cfg, params, fresh(), {"tokens": pc})
    s0 = pool.alloc()
    s1 = pool.alloc()
    assert (s0, s1) == (0, 1)
    pool.admit(sc_slot, sub_c)
    # A lives in slot 0, decodes 3 steps (slot 1 masked/frozen)
    _, sub_a = registry.prefill(cfg, params, fresh(), {"tokens": pa})
    pool.admit(0, sub_a)
    tok = jnp.zeros((2, 1), jnp.int32)
    for _ in range(3):
        _, new_cache = registry.decode_step(cfg, params, pool.cache,
                                            {"tokens": tok})
        pool.commit(new_cache, active=np.array([True, False]))
    pool.evict(0)
    # slot 0 is back to the init state — nothing of A remains
    assert _tree_equal(pool.read([0]), fresh())
    # B admitted into the recycled slot equals a standalone prefill of B
    _, sub_b = registry.prefill(cfg, params, fresh(), {"tokens": pb})
    slot = pool.alloc()
    assert slot == 0
    pool.admit(slot, sub_b)
    assert _tree_equal(pool.read([0]), sub_b)
    # bystander C was frozen through all of it
    assert _tree_equal(pool.read([sc_slot]), sub_c)


# ---------------------------------------------------------------------------
# Decode parity: prefill + N decode steps == full forward (the invariant
# slot admission relies on)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", DECODE_ARCHS)
def test_prefill_plus_n_decode_steps_matches_forward(name):
    cfg, params = _setup(name)
    b, lp, n_steps = 2, 4, 6
    L = lp + n_steps
    toks = jax.random.randint(jax.random.key(3), (b, L), 0, cfg.vocab,
                              dtype=jnp.int32)
    full, _ = registry.forward(cfg, params, {"tokens": toks})
    cache = sharding.tree_values(registry.init_cache(cfg, b, max_seq=16))
    logits, cache = registry.prefill(cfg, params, cache,
                                     {"tokens": toks[:, :lp]})
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full[:, :lp]),
                               rtol=2e-2, atol=2e-2)
    for t in range(n_steps):
        logits, cache = registry.decode_step(
            cfg, params, cache, {"tokens": toks[:, lp + t:lp + t + 1]})
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full[:, lp + t]),
            rtol=2e-2, atol=2e-2,
            err_msg=f"decode step {t} diverged from forward")


# ---------------------------------------------------------------------------
# Scan resumability: split + carry h equals one-shot, across chunk
# padding boundaries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["seq", "assoc", "chunked", "chunked_seq"])
@pytest.mark.parametrize("L1", [1, 7, 16, 17, 31, 39])
def test_selective_scan_resumes_across_split(impl, L1):
    """scan([0:L1]) carrying h into scan([L1:L]) == scan([0:L]) even when
    L1 straddles the chunk (block_l) padding boundary (chunk=16)."""
    rng = np.random.default_rng(11)
    b, L, d, n = 2, 40, 8, 4
    x = jnp.asarray(rng.normal(size=(b, L, d)).astype(np.float32))
    dt = jax.nn.softplus(jnp.asarray(
        rng.normal(size=(b, L, d)).astype(np.float32)))
    A = -jnp.exp(jnp.asarray(
        rng.normal(size=(d, n)).astype(np.float32)) * 0.5)
    B = jnp.asarray(rng.normal(size=(b, L, n)).astype(np.float32))
    C = jnp.asarray(rng.normal(size=(b, L, n)).astype(np.float32))
    D = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    z = jnp.asarray(rng.normal(size=(b, L, d)).astype(np.float32))

    kw = dict(D=D, z=z, impl=impl, chunk=16)
    y_full, h_full = ops.selective_scan(x, dt, A, B, C, **kw)
    y1, h1 = ops.selective_scan(x[:, :L1], dt[:, :L1], A, B[:, :L1],
                                C[:, :L1], D=D, z=z[:, :L1],
                                impl=impl, chunk=16)
    y2, h2 = ops.selective_scan(x[:, L1:], dt[:, L1:], A, B[:, L1:],
                                C[:, L1:], D=D, z=z[:, L1:], h0=h1,
                                impl=impl, chunk=16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], axis=1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Engine end-to-end: continuous batching must equal per-request greedy
# decode under admission/eviction churn
# ---------------------------------------------------------------------------

def _reference_greedy(cfg, params, prompt, max_new, eos_id=None):
    """Single-request greedy generation straight off registry functions."""
    cache = sharding.tree_values(registry.init_cache(cfg, 1, max_seq=64))
    logits, cache = registry.prefill(cfg, params, cache,
                                     {"tokens": jnp.asarray(prompt[None])})
    tok = int(jnp.argmax(logits[0, -1].astype(jnp.float32)))
    out = [tok]
    while len(out) < max_new and (eos_id is None or tok != eos_id):
        logits, cache = registry.decode_step(
            cfg, params, cache, {"tokens": jnp.asarray([[tok]], jnp.int32)})
        tok = int(jnp.argmax(logits[0, -1].astype(jnp.float32)))
        out.append(tok)
    return out


@pytest.mark.parametrize("name", ["mamba-130m", "granite-20b"])
def test_engine_matches_sequential_reference(name):
    """5 variable-length requests through 2 slots (forcing queueing,
    eviction, and slot reuse) produce exactly the tokens each request
    would get decoded alone."""
    cfg, params = _setup(name)
    rng = np.random.default_rng(5)
    lens = [3, 5, 9, 4, 7]
    max_news = [6, 3, 8, 5, 4]
    prompts = [rng.integers(0, cfg.vocab, size=(l,)).astype(np.int32)
               for l in lens]
    eng = Engine(cfg, params, EngineConfig(n_slots=2, max_seq=64))
    reqs = [eng.submit(p, max_new=m) for p, m in zip(prompts, max_news)]
    done = eng.run()
    assert len(done) == len(reqs)
    for p, m, r in zip(prompts, max_news, reqs):
        assert r.finished and len(r.tokens) == m
        assert r.tokens == _reference_greedy(cfg, params, p, m), \
            f"req {r.req_id} diverged under continuous batching"


def test_engine_eos_evicts_and_backfills():
    """A request whose EOS fires early frees its slot; the queued request
    is admitted and still decodes exactly."""
    cfg, params = _setup("mamba-130m")
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab, size=(l,)).astype(np.int32)
               for l in (4, 6, 5)]
    # learn req0's natural 3rd token, then make it the EOS
    ref0 = _reference_greedy(cfg, params, prompts[0], 10)
    eos = ref0[2]
    eng = Engine(cfg, params, EngineConfig(n_slots=1, max_seq=64))
    r0 = eng.submit(prompts[0], max_new=10, eos_id=eos)
    r1 = eng.submit(prompts[1], max_new=4)
    r2 = eng.submit(prompts[2], max_new=3)
    eng.run()
    assert r0.tokens == ref0[:3] and r0.tokens[-1] == eos
    assert r1.tokens == _reference_greedy(cfg, params, prompts[1], 4)
    assert r2.tokens == _reference_greedy(cfg, params, prompts[2], 3)
    assert eng.stats.n_requests == 3


def test_engine_stats_counters():
    cfg, params = _setup("mamba-130m")
    rng = np.random.default_rng(13)
    eng = Engine(cfg, params, EngineConfig(n_slots=2, max_seq=64))
    reqs = [eng.submit(rng.integers(0, cfg.vocab, size=(4,)).astype(np.int32),
                       max_new=m) for m in (5, 3, 4)]
    eng.run()
    s = eng.stats
    assert s.n_requests == 3
    assert s.prefill_calls == 3 and s.prefill_tokens == 12
    assert s.useful_tokens == sum(len(r.tokens) for r in reqs) == 12
    smry = s.summary()
    assert smry["tokens_per_s"] > 0
    assert 0 < smry["occupancy"] <= 1
    assert all(t >= 0 for t in (smry["ttft_mean_s"], smry["latency_p95_s"]))


def test_engine_rejects_oversized_request():
    cfg, params = _setup("mamba-130m")
    eng = Engine(cfg, params, EngineConfig(n_slots=1, max_seq=16))
    with pytest.raises(ValueError):
        eng.submit(np.arange(10, dtype=np.int32), max_new=10)


# ---------------------------------------------------------------------------
# Scratch slots (speculative-decode forks): leases never collide with
# live slots, and no lease survives a burst — even an aborted one
# ---------------------------------------------------------------------------

def test_scratch_lease_never_collides_with_live_slots():
    """Interleave admission/eviction with lease/release arbitrarily:
    live ids and leased ids must stay disjoint (the id ranges are
    disjoint by construction — this pins that invariant), and both
    accountings must stay exact."""
    cfg, _ = _setup("mamba-130m")
    pool = SlotStatePool(cfg, n_slots=3, max_seq=16, n_scratch=3)
    rng = np.random.default_rng(21)
    live, leased = [], []
    for _ in range(200):
        op = rng.integers(0, 4)
        if op == 0:
            slot = pool.alloc()
            if slot is not None:
                live.append(slot)
        elif op == 1 and live:
            pool.evict(live.pop(rng.integers(len(live))))
        elif op == 2:
            sc = pool.lease_scratch()
            if sc is not None:
                leased.append(sc)
        elif op == 3 and leased:
            pool.release_scratch(leased.pop(rng.integers(len(leased))))
        assert not (set(live) & set(leased))
        assert all(s < pool.n_slots for s in live)
        assert all(pool.n_slots <= s < pool.n_total for s in leased)
        assert pool.n_active == len(live)
        assert pool.n_scratch_free == pool.n_scratch - len(leased)
    # scratch ids never appear in the live active mask
    mask = pool.active_mask()
    assert not mask[pool.n_slots:].any()


def test_scratch_release_rejects_bad_ids():
    cfg, _ = _setup("mamba-130m")
    pool = SlotStatePool(cfg, n_slots=2, max_seq=16, n_scratch=1)
    with pytest.raises(ValueError):
        pool.release_scratch(0)            # live id, not scratch
    with pytest.raises(ValueError):
        pool.release_scratch(2)            # scratch id, but not leased


def test_no_scratch_lease_leaks_after_spec_run():
    """Every speculative pass leases scratch slots; after run() returns
    the pool must be fully drained: all live slots free, all scratch
    leases returned."""
    from repro.runtime.spec_decode import DraftConfig
    cfg, params = _setup("mamba-130m")
    rng = np.random.default_rng(23)
    eng = Engine(cfg, params,
                 EngineConfig(n_slots=2, max_seq=64,
                              draft=DraftConfig(k=2, layers=2)))
    for m in (5, 3, 4):
        eng.submit(rng.integers(0, cfg.vocab, size=(4,)).astype(np.int32),
                   max_new=m)
    eng.run()
    assert eng.pool.n_active == 0 and eng.pool.n_free == eng.pool.n_slots
    assert eng.pool.n_scratch_free == eng.pool.n_scratch


# ---------------------------------------------------------------------------
# Cancellation: aborting a request mid-burst / mid-spec-pass reclaims
# its slot (and scratch leases) and leaves every survivor's stream
# bitwise unchanged — per-slot keys make sampling independent of
# co-resident evictions.
# ---------------------------------------------------------------------------

def _cancel_fixture(cfg):
    from repro.runtime.sampling import SamplingParams
    rng = np.random.default_rng(41)
    pa, pb, pc = (rng.integers(0, cfg.vocab, size=(l,)).astype(np.int32)
                  for l in (4, 6, 5))
    # the survivor is SAMPLED: bitwise survival is only guaranteed
    # because randomness is per-slot counter-based, never shared
    sp = SamplingParams(temperature=0.9, seed=7, max_new=12)
    return pa, pb, pc, sp


def test_cancel_mid_burst_reclaims_slot_and_preserves_survivors():
    cfg, params = _setup("mamba-130m")
    pa, pb, pc, sp = _cancel_fixture(cfg)
    # reference: the same trace with the victim never submitted
    ref = Engine(cfg, params, EngineConfig(n_slots=2, max_seq=64,
                                           sched_quantum=2))
    a0 = ref.submit(pa, params=sp)
    c0 = ref.submit(pc, max_new=6)
    ref.run()

    eng = Engine(cfg, params, EngineConfig(n_slots=2, max_seq=64,
                                           sched_quantum=2))

    def cb(req, toks):
        if len(req.tokens) >= 3:
            assert eng.cancel(req.req_id)

    a = eng.submit(pa, params=sp)
    b = eng.submit(pb, max_new=12, stream_cb=cb)
    c = eng.submit(pc, max_new=6)          # backfills the freed slot
    eng.run()
    assert b.cancelled and b.finished
    assert 3 <= len(b.tokens) < 12          # stopped well short of budget
    assert a.tokens == a0.tokens, \
        "sampled survivor perturbed by a co-resident cancellation"
    assert c.tokens == c0.tokens
    # no pool leak: every slot free, params rows reset
    assert eng.pool.n_active == 0 and eng.pool.n_free == eng.pool.n_slots
    assert not eng.pool.params.temperature.any()
    assert eng.stats.n_cancelled == 1
    assert eng.stats.summary()["cancelled"] == 1
    assert eng.stats.n_requests == 2        # cancelled req not counted


def test_cancel_mid_spec_pass_reclaims_scratch_and_preserves_survivors():
    from repro.runtime.spec_decode import DraftConfig
    cfg, params = _setup("mamba-130m")
    pa, pb, pc, sp = _cancel_fixture(cfg)
    draft = DraftConfig(k=3, layers=2)
    ref = Engine(cfg, params, EngineConfig(n_slots=2, max_seq=64,
                                           draft=draft))
    a0 = ref.submit(pa, params=sp)
    c0 = ref.submit(pc, max_new=6)
    ref.run()

    eng = Engine(cfg, params, EngineConfig(n_slots=2, max_seq=64,
                                           draft=draft))

    def cb(req, toks):
        if len(req.tokens) >= 3:
            eng.cancel(req.req_id)

    a = eng.submit(pa, params=sp)
    b = eng.submit(pb, max_new=12, stream_cb=cb)
    c = eng.submit(pc, max_new=6)
    eng.run()
    assert b.cancelled and len(b.tokens) < 12
    assert a.tokens == a0.tokens and c.tokens == c0.tokens
    assert eng.pool.n_active == 0 and eng.pool.n_free == eng.pool.n_slots
    assert eng.pool.n_scratch_free == eng.pool.n_scratch, \
        "cancellation leaked a scratch lease"
    assert eng.stats.n_cancelled == 1


def test_cancel_queued_request_never_admitted():
    cfg, params = _setup("mamba-130m")
    pa, pb, _, _ = _cancel_fixture(cfg)
    eng = Engine(cfg, params, EngineConfig(n_slots=1, max_seq=64))
    r1 = eng.submit(pa, max_new=4)
    r2 = eng.submit(pb, max_new=4)
    assert eng.cancel(r2.req_id)
    assert not eng.cancel(r2.req_id)        # idempotent: already flagged
    eng.run()
    assert r2.cancelled and r2.finished and r2.tokens == []
    assert r1.tokens and not r1.cancelled
    assert eng.stats.n_cancelled == 1 and eng.stats.n_requests == 1
    assert not eng.cancel(12345)            # unknown id


def test_cancel_sweep_preserves_fifo_order_of_survivors():
    """The cancel sweep rebuilds the ready heap from the ORIGINAL
    (priority, seq) tuples: queued survivors keep their FIFO order
    even though raw heap-array order is scrambled after a pop."""
    cfg, params = _setup("mamba-130m")
    rng = np.random.default_rng(43)
    prompts = [rng.integers(0, cfg.vocab, size=(4,)).astype(np.int32)
               for _ in range(4)]
    eng = Engine(cfg, params, EngineConfig(n_slots=1, max_seq=64))

    def cb(req, toks):
        if len(req.tokens) >= 2:
            eng.cancel(rb.req_id)      # cancel a QUEUED request

    ra = eng.submit(prompts[0], max_new=4, stream_cb=cb)
    rb = eng.submit(prompts[1], max_new=4)
    rc = eng.submit(prompts[2], max_new=4)
    rd = eng.submit(prompts[3], max_new=4)
    done = eng.run()
    assert rb.cancelled and rb.tokens == []
    # submission order among survivors must hold: a, then c, then d
    completed = [r.req_id for r in done if not r.cancelled]
    assert completed == [ra.req_id, rc.req_id, rd.req_id], completed


def test_adaptive_draft_warmup_zero_does_not_crash():
    """adapt_warmup=0 floors at one pass (the clamp needs a realized
    pass before it can divide by the pass count)."""
    from repro.runtime.spec_decode import DraftConfig
    cfg, params = _setup("mamba-130m")
    eng = Engine(cfg, params,
                 EngineConfig(n_slots=1, max_seq=64,
                              draft=DraftConfig(k=3, layers=2,
                                                adaptive=True,
                                                adapt_warmup=0)))
    r = eng.submit(np.arange(4, dtype=np.int32), max_new=8)
    eng.run()
    assert len(r.tokens) == 8


def test_cancel_pending_arrival_gated_request():
    cfg, params = _setup("mamba-130m")
    pa, pb, _, _ = _cancel_fixture(cfg)
    eng = Engine(cfg, params, EngineConfig(n_slots=1, max_seq=64))
    r1 = eng.submit(pa, max_new=3)
    r2 = eng.submit(pb, max_new=3, arrival=0.01)
    eng.cancel(r2.req_id)
    eng.run()
    assert r2.cancelled and r2.tokens == [] and r1.finished


def test_abandoned_lease_released_when_burst_aborts(monkeypatch):
    """A speculative pass that dies mid-burst (here: the verify jit
    raises) must still return its scratch leases — an abandoned lease
    would silently halve speculation capacity forever."""
    from repro.runtime.spec_decode import DraftConfig
    cfg, params = _setup("mamba-130m")
    eng = Engine(cfg, params,
                 EngineConfig(n_slots=2, max_seq=64,
                              draft=DraftConfig(k=2, layers=2)))
    eng.submit(np.arange(4, dtype=np.int32), max_new=4)

    def boom(*a, **k):
        raise RuntimeError("verify died mid-burst")

    monkeypatch.setattr(eng._spec, "verify", boom)
    with pytest.raises(RuntimeError):
        eng.run()
    assert eng.pool.n_scratch_free == eng.pool.n_scratch, \
        "aborted burst leaked a scratch lease"


def test_raising_stream_cb_isolated_and_auto_cancelled():
    """A client callback that raises must not take down the scheduler
    loop: the error is counted, the offender's stream is auto-cancelled
    at that sync, and co-resident streams — including a SAMPLED one —
    are bitwise untouched."""
    cfg, params = _setup("mamba-130m")
    pa, pb, pc, sp = _cancel_fixture(cfg)
    # reference: the same trace with the offender never submitted
    ref = Engine(cfg, params, EngineConfig(n_slots=2, max_seq=64,
                                           sched_quantum=2))
    a0 = ref.submit(pa, params=sp)
    c0 = ref.submit(pc, max_new=6)
    ref.run()

    eng = Engine(cfg, params, EngineConfig(n_slots=2, max_seq=64,
                                           sched_quantum=2))
    a_deliveries = []

    def good_cb(req, toks):
        a_deliveries.append(list(toks))

    def bad_cb(req, toks):
        raise RuntimeError("client connection went away")

    a = eng.submit(pa, params=sp, stream_cb=good_cb)
    b = eng.submit(pb, max_new=12, stream_cb=bad_cb)
    c = eng.submit(pc, max_new=6)          # backfills the freed slot
    eng.run()                              # must NOT raise
    assert eng.stats.n_callback_errors == 1
    assert b.stream_cb is None             # offender's cb dropped
    assert b.cancelled and b.finished
    assert len(b.tokens) < 12              # stopped short of its budget
    assert a.tokens == a0.tokens, \
        "sampled survivor perturbed by a co-resident callback failure"
    assert c.tokens == c0.tokens
    # the healthy callback saw a's complete stream, before and after
    # the offender was dropped
    assert [t for batch in a_deliveries for t in batch] == a.tokens
    assert eng.pool.n_active == 0 and eng.pool.n_free == eng.pool.n_slots
    assert eng.stats.n_cancelled == 1


@pytest.mark.parametrize("name", ["mamba-130m", "jamba-v0.1-52b",
                                  "xlstm-350m"])
def test_decode_burst_reuses_the_pool_buffer(name):
    """A decode burst donates the pool cache to its first step and
    chains the rest through the steps' outputs: the cache a burst
    started from is released, the engine's new pool is live, and the
    tokens are those of the same engine with an undonating step."""
    cfg, params = _setup(name)
    prompts = [np.arange(1, 6, dtype=np.int32),
               np.arange(7, 15, dtype=np.int32)]

    def serve(donate):
        eng = Engine(cfg, params, EngineConfig(n_slots=2, max_seq=40,
                                               sched_quantum=4))
        if not donate:
            eng._decode = jax.jit(eng._decode.__wrapped__)
        # a stop id caps each burst at the quantum
        reqs = [eng.submit(p, max_new=12, eos_id=cfg.vocab - 1)
                for p in prompts]
        eng.step()                       # both admissions, one burst
        before = eng.pool.cache
        eng.step()                       # the next burst starts there
        assert all(leaf.is_deleted() for leaf in
                   jax.tree.leaves(before)) == donate
        assert not any(leaf.is_deleted()
                       for leaf in jax.tree.leaves(eng.pool.cache))
        eng.run()
        return [r.tokens for r in reqs]

    assert serve(True) == serve(False)
