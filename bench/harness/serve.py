"""Set-up, the measured window and the drain, around the program's Engine.

The window drives ``Engine.submit`` and ``Engine.step`` from this loop
(never ``Engine.run``): a request is submitted when it is due, and every
latency is timed from its due time.  Output tokens are counted when
``Engine.step`` returns, so a stall inside a step counts against the
window.  Host spans (``jax.profiler.TraceAnnotation``) mark each step,
each submit and each wait for the next arrival, so a traced run can say
what the host did in each idle gap of the device.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Optional

import jax
import numpy as np

from harness import traffic as traffic_lib

#: seconds past the window's close that a request due in the window may
#: take to finish before it counts as failed
DRAIN_LIMIT_S = 60.0

SPAN_STEP = "bench.step"
SPAN_SUBMIT = "bench.submit"
SPAN_WAIT = "bench.wait"
SPAN_TRACED = "bench.traced"


@dataclasses.dataclass
class Sent:
    spec: traffic_lib.Req
    req: object            # the program's Request
    due: float             # absolute, on ``clock``
    submitted: float       # absolute, on ``clock``
    seen: int = 0          # tokens already counted
    slot: Optional[int] = None   # pool slot it decoded in, where seen


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    sent: list
    tokens: int            # output tokens delivered inside the window
    steps: int             # Engine.step calls inside the window
    open_loop: bool
    t_drained: Optional[float] = None
    t_mark: Optional[float] = None   # when the mark's callback ran
    tokens_mark: int = 0             # output tokens delivered before it

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0

    def due_in_window(self) -> list:
        """Requests the window is answerable for: all sent requests of an
        open loop; of a backlog, those admitted before the close."""
        if self.open_loop:
            return self.sent
        return [s for s in self.sent
                if s.req.t_admit is not None and s.req.t_admit <= self.t_end]


def sampling_params(spec: traffic_lib.Req):
    from repro.runtime.sampling import SamplingParams
    return SamplingParams(temperature=spec.temperature, top_p=spec.top_p,
                          seed=spec.seed, stop=spec.stop_ids,
                          max_new=spec.max_new)


def _submit(engine, spec, due, clock, sent):
    with jax.profiler.TraceAnnotation(SPAN_SUBMIT):
        req = engine.submit(spec.prompt, sampling_params(spec))
    sent.append(Sent(spec=spec, req=req, due=due, submitted=clock()))


def _slots(engine) -> dict:
    """Pool slot of each resident request (read from the engine's slot
    table; used only to spread the check's sample over the pool)."""
    return {id(r): i for i, r in enumerate(engine._slot_req)
            if r is not None}


def _count(live: list, slots: dict) -> tuple[int, list]:
    """Tokens delivered since the last count, and the still-open sends."""
    new, still = 0, []
    for s in live:
        if s.slot is None:
            s.slot = slots.get(id(s.req))
        n = len(s.req.tokens)
        new += n - s.seen
        s.seen = n
        if not s.req.finished:
            still.append(s)
    return new, still


def _step(engine) -> bool:
    with jax.profiler.TraceAnnotation(SPAN_STEP):
        return engine.step()


def window(engine, source, seconds: float, n_queued: int = 0,
           clock: Callable[[], float] = time.perf_counter,
           t0: Optional[float] = None,
           mark: Optional[tuple] = None) -> Window:
    """Drive ``engine`` for ``seconds`` from ``t0`` (default: now).

    ``source`` is a list of requests with due times (open loop) or a
    ``traffic.Backlog``, which is kept at ``n_queued`` requests waiting
    for a slot.  ``mark`` = (earliest, latest, callback), in seconds
    after t0: the callback runs once, between steps, at the first turn
    of the loop past ``earliest`` that submitted a request (so the step
    that follows admits it), or past ``latest`` where none came; the
    rest of the window is inside the host span ``bench.traced``."""
    t0 = clock() if t0 is None else t0
    t_close = t0 + seconds
    spans = contextlib.ExitStack()
    sent: list[Sent] = []
    live: list[Sent] = []
    open_loop = not isinstance(source, traffic_lib.Backlog)
    pending = list(source) if open_loop else []
    i, tokens, steps = 0, 0, 0
    t_mark, tokens_mark = None, 0
    while True:
        now = clock()
        if now >= t_close:
            break
        n_sent = len(sent)
        if open_loop:
            while i < len(pending) and t0 + pending[i].due <= now:
                _submit(engine, pending[i], t0 + pending[i].due, clock,
                        sent)
                live.append(sent[-1])
                i += 1
        else:
            waiting = sum(s.req.t_admit is None for s in live)
            for _ in range(n_queued - waiting):
                _submit(engine, source.take(), now, clock, sent)
                live.append(sent[-1])
        if mark is not None and (
                (now >= t0 + mark[0] and len(sent) > n_sent)
                or now >= t0 + mark[1]):
            t_mark, tokens_mark = clock(), tokens
            mark[2]()
            spans.enter_context(jax.profiler.TraceAnnotation(SPAN_TRACED))
            mark = None
        if _step(engine):
            steps += 1
            new, live = _count(live, _slots(engine))
            tokens += new
        elif open_loop:
            nxt = (t0 + pending[i].due) if i < len(pending) else t_close
            if mark is not None:
                nxt = min(nxt, t0 + mark[1])
            wait = min(nxt, t_close) - clock()
            if wait > 0:
                with jax.profiler.TraceAnnotation(SPAN_WAIT):
                    time.sleep(wait)
    spans.close()
    return Window(t0=t0, t_end=clock(), sent=sent, tokens=tokens,
                  steps=steps, open_loop=open_loop, t_mark=t_mark,
                  tokens_mark=tokens_mark)


def drain(engine, win: Window, clock=time.perf_counter,
          limit_s: float = DRAIN_LIMIT_S) -> None:
    """After the close of an open loop: send nothing more and step until
    every request due in the window has finished, or for ``limit_s``.  A
    backlog is not drained: the requests in flight at the close are cut,
    and only those finished count."""
    if not win.open_loop:
        return
    deadline = clock() + limit_s
    while (any(not s.req.finished for s in win.sent)
           and clock() < deadline):
        _step(engine)
    win.t_drained = clock()


def warm(engine, mix: dict, vocab: int, seed: int) -> int:
    """One short request per prompt length on the mix's ladder (greedy
    and sampled, with the mix's stop ids), served to the end: every
    prefill shape and the decode step compile here.  Returns how many
    requests were served."""
    rng = traffic_lib.rng_for(seed, 9)
    stop = tuple(mix.get("stop_ids", ()))
    specs = []
    for j, length in enumerate(traffic_lib.ladder(mix)):
        g = j % 2 == 0
        specs.append(traffic_lib.Req(
            index=-1 - j, due=0.0,
            prompt=rng.integers(1, vocab, size=(length,), dtype=np.int32),
            max_new=3, temperature=0.0 if g else float(mix["temperature"]),
            top_p=1.0 if g else float(mix["top_p"]), seed=j,
            stop_ids=stop))
    sent: list[Sent] = []
    for spec in specs:
        _submit(engine, spec, 0.0, time.perf_counter, sent)
    while any(not s.req.finished for s in sent):
        engine.step()
    return len(sent)


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100), linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def latencies(win: Window) -> dict:
    """TTFT and TPOT of every request due in the window, in seconds.  In
    an open loop a request that never finished counts with the time to
    the drain's end as its latency (a lower bound) and as failed; in a
    backlog the requests in flight at the close are cut, and counted as
    such."""
    end = win.t_drained if win.t_drained is not None else win.t_end
    ttft, tpot, failed, cut = [], [], 0, 0
    for s in win.due_in_window():
        r = s.req
        if not r.finished and not win.open_loop:
            cut += 1
            continue
        if not r.finished:
            failed += 1
            ttft.append(end - s.due if r.t_first is None
                        else r.t_first - s.due)
            continue
        ttft.append(r.t_first - s.due)
        if len(r.tokens) > 1:
            tpot.append((r.t_done - r.t_first) / (len(r.tokens) - 1))
    return {"ttft": ttft, "tpot": tpot, "failed": failed, "cut": cut}


def budget_faults(win: Window, vocab: int) -> int:
    """Finished requests that ended neither on their budget nor on a
    stop id, or that hold a token outside the vocabulary: each is a
    wrong answer."""
    bad = 0
    for s in win.due_in_window():
        r = s.req
        if not r.finished:
            continue
        stopped = bool(r.tokens) and r.tokens[-1] in r.stop_ids
        if len(r.tokens) != r.max_new and not stopped:
            bad += 1
        if any(t < 0 or t >= vocab for t in r.tokens):
            bad += 1
    return bad
