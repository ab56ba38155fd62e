"""Host spans of the serving engine (runtime/tracing.py) and the names
the decode programs carry: the recorder's nesting, ring and query; the
span tree of one ``Engine.step``; request arrival stamped where the
request enters the system; stable jit names; the decode kernels'
named scopes in the lowered program."""
import asyncio
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import decode_step as dsk
from repro.models import registry
from repro.parallel import sharding
from repro.runtime import engine as engine_lib
from repro.runtime import spec_decode, state_pool, tracing
from repro.runtime.engine import Engine, EngineConfig
from repro.runtime.frontend import AsyncFrontend
from repro.runtime.scheduler import SchedConfig, SLOClass, SLOScheduler

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(autouse=True)
def _fresh_record():
    tracing.reset()
    tracing.enable()
    yield
    tracing.reset()
    tracing.enable()


class _Clock:
    """A clock that reads ``t`` and moves only when told to."""

    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def _tree(records):
    """Span records -> nested (name, req, children) tuples from the
    roots, children in start order."""
    ids = {r.id for r in records}
    kids = {}
    for r in sorted(records, key=lambda r: (r.t0, r.id)):
        kids.setdefault(r.parent if r.parent in ids else None, []).append(r)

    def build(r):
        return (r.name, r.req, tuple(build(c) for c in kids.get(r.id, ())))
    return tuple(build(r) for r in kids.get(None, ()))


# --- the recorder -------------------------------------------------------

def test_nesting_keeps_parent_ids_and_request_ids():
    clk = _Clock()
    with tracing.span("engine.step", clock=clk) as outer:
        clk.t = 1.0
        with tracing.span("engine.admit", 7, clock=clk) as inner:
            clk.t = 2.0
        with tracing.span("engine.burst", clock=clk) as burst:
            clk.t = 3.0
    recs, dropped = tracing.query(0.0, 10.0)
    assert not dropped
    by = {r.name: r for r in recs}
    assert by["engine.step"].parent is None
    assert by["engine.admit"].parent == by["engine.step"].id
    assert by["engine.burst"].parent == by["engine.step"].id
    assert by["engine.admit"].req == 7 and by["engine.step"].req is None
    assert (outer.t0, outer.t1) == (0.0, 3.0)
    assert (inner.t0, inner.t1) == (1.0, 2.0)
    assert (by["engine.burst"].t0, by["engine.burst"].t1) == \
        (burst.t0, burst.t1) == (2.0, 3.0)
    # the inner spans ended first
    assert [r.name for r in recs] == ["engine.admit", "engine.burst",
                                      "engine.step"]


def test_ring_overflow_is_counted_and_flags_its_interval():
    tracing.reset(capacity=4)
    clk = _Clock()
    for i in range(6):
        clk.t = float(i)
        with tracing.span("engine.step", clock=clk):
            clk.t = i + 0.5
    assert tracing.dropped() == 2
    recs, dropped = tracing.query(0.0, 10.0)
    assert dropped and [r.t0 for r in recs] == [2.0, 3.0, 4.0, 5.0]
    # spans 0 and 1 ended at 0.5 and 1.5: an interval after them lost
    # nothing
    recs, dropped = tracing.query(2.0, 10.0)
    assert not dropped and len(recs) == 4


def test_disable_records_nothing_but_still_stamps():
    clk = _Clock(5.0)
    tracing.disable()
    with tracing.span("engine.step", clock=clk) as s:
        clk.t = 6.0
    assert (s.t0, s.t1) == (5.0, 6.0)
    assert tracing.query(float("-inf"), float("inf")) == ([], False)
    tracing.enable()
    with tracing.span("engine.step", clock=clk):
        pass
    assert len(tracing.query(0.0, 10.0)[0]) == 1


def test_query_returns_whole_spans_of_the_named_kinds():
    clk = _Clock()
    for name, t0, t1 in [("engine.step", 0.0, 2.0),     # starts before
                         ("engine.step", 2.0, 4.0),     # inside
                         ("engine.burst", 4.0, 5.0),    # inside
                         ("engine.step", 5.0, 8.0),     # ends at t1
                         ("engine.step", 7.0, 9.0)]:    # ends after
        clk.t = t0
        with tracing.span(name, clock=clk):
            clk.t = t1
    recs, _ = tracing.query(1.0, 8.0)
    assert [(r.name, r.t0) for r in recs] == [("engine.step", 2.0),
                                              ("engine.burst", 4.0)]
    recs, _ = tracing.query(1.0, 8.0, ("engine.step",))
    assert [r.t0 for r in recs] == [2.0]


# --- the engine's spans --------------------------------------------------

def _setup():
    cfg = configs.smoke_variant(configs.get_config("mamba-130m"))
    cfg = dataclasses.replace(cfg, vocab=64, dtype="float32")
    params = sharding.tree_values(
        registry.init_params(cfg, jax.random.key(0)))
    return cfg, params


def test_one_step_yields_the_span_tree():
    """One ``Engine.step`` that admits a request and decodes it to its
    budget: the admission, the burst with its four phases, and the
    eviction under the burst's delivery — no other span.  The request's
    stamps and the ServeStats durations are the spans' own."""
    cfg, params = _setup()
    eng = Engine(cfg, params, EngineConfig(n_slots=2, max_seq=32))
    req = eng.submit(np.arange(1, 7), max_new=4)
    tracing.reset()
    assert eng.step()
    assert req.finished and len(req.tokens) == 4 and req.slot == 0
    recs, dropped = tracing.query(float("-inf"), float("inf"))
    assert not dropped
    rid = req.req_id
    assert _tree(recs) == ((
        "engine.step", None, (
            ("engine.admit", rid, (
                ("engine.admit.prefill", None, ()),
                ("engine.admit.sync", None, ()))),
            ("engine.burst", None, (
                ("engine.burst.prepare", None, ()),
                ("engine.burst.dispatch", None, ()),
                ("engine.burst.sync", None, ()),
                ("engine.burst.deliver", None, (
                    ("engine.evict", rid, ()),)))))),)
    by = {r.name: r for r in recs}
    assert req.t_admit == by["engine.admit"].t0
    assert req.t_first == by["engine.admit.sync"].t1
    assert eng.stats.prefill_time == req.t_first - req.t_admit
    burst = by["engine.burst"]
    assert eng.stats.decode_time == burst.t1 - burst.t0
    # an idle step is a bare engine.step
    tracing.reset()
    assert not eng.step()
    assert _tree(tracing.query(float("-inf"), float("inf"))[0]) == \
        (("engine.step", None, ()),)


def test_disabled_recorder_serves_the_same_tokens():
    cfg, params = _setup()
    prompts = [np.arange(1, 6), np.arange(3, 12)]

    def serve():
        eng = Engine(cfg, params, EngineConfig(n_slots=2, max_seq=32))
        reqs = [eng.submit(p, max_new=5) for p in prompts]
        eng.run()
        return [r.tokens for r in reqs], eng.stats

    on, _ = serve()
    tracing.reset()
    tracing.disable()
    off, stats = serve()
    assert off == on
    assert tracing.query(float("-inf"), float("inf")) == ([], False)
    assert stats.prefill_time > 0 and stats.decode_time > 0


# --- arrival -------------------------------------------------------------

def test_scheduler_queue_wait_counts_against_the_ttft_slo():
    """A ticket held in the scheduler's queue arrives when it is
    submitted there: its wait counts in its TTFT and makes it violate a
    5 s budget, though it is admitted the moment the engine takes it."""
    cfg, params = _setup()
    clk = _Clock()
    eng = Engine(cfg, params, EngineConfig(n_slots=1, max_seq=32),
                 clock=clk)
    sched = SLOScheduler(eng, SchedConfig(
        weights={"t": 1.0},
        classes=(SLOClass(ttft_budget=10_000, ttft_slo_s=5.0),)))
    first = sched.submit(np.arange(1, 5), tenant="t", max_new=4)
    held = sched.submit(np.arange(2, 6), tenant="t", max_new=4)
    sched.step()                       # `first` takes the one slot
    assert first.req.finished and held.req is None
    clk.t = 10.0
    sched.run()
    r = held.req
    assert r.t_arrival == 0.0 and r.t_submit == 10.0
    assert r.t_first - r.t_submit == 0.0
    assert eng.stats.summary()["slo_ttft_violations"] == 1
    assert sorted(eng.stats._ttft) == [0.0, 10.0]


class _Ticks:
    """A clock that moves one second at every reading."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def test_engine_and_frontend_stamp_arrival():
    """``Engine.submit`` stamps arrival itself unless handed one; the
    async front end stamps it before the request reaches the engine."""
    cfg, params = _setup()
    clk = _Clock(3.0)
    eng = Engine(cfg, params, EngineConfig(n_slots=1, max_seq=32),
                 clock=clk)
    assert eng.submit(np.arange(1, 5), max_new=2).t_arrival == 3.0
    assert eng.submit(np.arange(1, 5), max_new=2,
                      t_arrival=1.5).t_arrival == 1.5
    eng.run()
    eng._now = _Ticks()

    async def main():
        async with AsyncFrontend(eng) as fe:
            h = await fe.submit(np.arange(1, 5), max_new=2)
            toks = [t async for t in h.tokens()]
        return h, toks

    h, toks = asyncio.run(asyncio.wait_for(main(), 120.0))
    assert len(toks) == 2
    assert h.req.t_arrival < h.req.t_submit
    assert eng.stats._ttft[-1] == h.req.t_first - h.req.t_arrival


# --- program names --------------------------------------------------------

def test_jit_names_are_distinct():
    """Each serving jit has its own name; ``_fn`` is the prefill admit
    alone and ``_decode_fn`` the decode step (the benchmark's readers
    find them by these names)."""
    cfg, _ = _setup()
    names = [engine_lib._jit_prefill_admit(cfg).__name__,
             engine_lib._jit_prefill_prefix(cfg).__name__,
             engine_lib._jit_suffix_admit(cfg, 3).__name__,
             engine_lib._jit_decode_sample(cfg).__name__,
             state_pool._jit_gather(cfg).__name__,
             state_pool._jit_scatter(cfg).__name__,
             state_pool._jit_mask(cfg).__name__,
             state_pool._jit_fork(cfg).__name__,
             spec_decode._jit_draft_step(cfg, cfg, 1).__name__,
             spec_decode._jit_verify(cfg, 2).__name__]
    assert len(set(names)) == len(names)
    assert names[0] == "_fn" and names[3] == "_decode_fn"
    assert names[4:8] == ["pool_gather", "pool_scatter", "pool_mask",
                          "pool_fork"]


@pytest.mark.parametrize("impl,scopes", [
    ("fused", (dsk.SCOPE_KERNEL, dsk.SCOPE_STATE_IN, dsk.SCOPE_STATE_OUT)),
    ("megakernel", (dsk.SCOPE_KERNEL,)),
])
def test_decode_kernels_carry_named_scopes(impl, scopes):
    """The lowered decode step's op locations carry the kernel's scope,
    and on the per-layer path the state staging's scopes."""
    cfg, _ = _setup()
    cfg = dataclasses.replace(cfg, step_impl=impl)
    p = sharding.tree_values(registry.abstract_params(cfg))
    cache = sharding.tree_values(registry.abstract_cache(cfg, 2, 16))
    toks = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    text = jax.jit(lambda p, c, t: registry.decode_step(
        cfg, p, c, {"tokens": t})).lower(p, cache, toks).as_text(
            debug_info=True)
    found = set(re.findall(r'loc\("(?:[^"]*/)?(decode_[a-z_]+)/', text))
    assert found == set(scopes)
