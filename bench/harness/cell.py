"""One cell: its configuration, mix and parameters, and one run of it."""
from __future__ import annotations

import dataclasses
import gc
import shutil
import tempfile
import time
from typing import Optional

import numpy as np

from harness import check, serve, spec, traffic, work
from harness import weights as weights_lib

clock = time.perf_counter


class RunError(RuntimeError):
    """The run cannot report a result."""


@dataclasses.dataclass
class Cell:
    name: str
    conf: dict             # bench/configs/<config>.json
    mix: dict              # bench/traffic/<traffic>.json
    e2e: list              # BENCHMARK.json end_to_end entries it reports
    per_layer: list        # BENCHMARK.json per_layer entries it reports

    @classmethod
    def load(cls, bm: dict, wl: dict) -> "Cell":
        return cls(name=wl["name"], conf=spec.config(bm, wl["config"]),
                   mix=spec.traffic(wl["traffic"]),
                   e2e=spec.metrics_for(bm, wl["name"], False),
                   per_layer=spec.metrics_for(bm, wl["name"], True))

    @property
    def dm(self) -> weights_lib.Dims:
        return weights_lib.dims(self.conf)

    @property
    def serve_conf(self) -> dict:
        return self.conf["serve"]

    @property
    def rate(self) -> Optional[float]:
        return self.mix.get("rate_per_s")

    @property
    def max_seq(self) -> int:
        return max(self.mix["prompt_ladder"]) + self.mix["max_new"]["max"]

    @property
    def n_queued(self) -> int:
        return int(self.mix.get("queued_per_slot", 0)
                   * self.serve_conf["n_slots"])

    def program(self, seed: int):
        """The program's ModelConfig and EngineConfig for this cell, with
        the widths checked against the configuration file."""
        from repro import configs
        from repro.runtime.engine import EngineConfig
        sc = self.serve_conf
        base = configs.get_config(sc["arch"])
        if sc.get("smoke"):
            base = configs.smoke_variant(base)
        dm = self.dm
        # depth is the one size a configuration may cut
        base = dataclasses.replace(base, n_layers=dm.n_layer)
        have = (base.d_model, base.n_layers, base.vocab, base.d_state,
                base.d_conv, base.expand, base.dt_rank)
        want = (dm.d_model, dm.n_layer, dm.vocab, dm.d_state, dm.d_conv,
                dm.expand, dm.dt_rank)
        if have != want:
            raise RunError(f"program config {base.name} has sizes {have}, "
                           f"the configuration file {want}")
        cfg = dataclasses.replace(base, dtype=sc["dtype"],
                                  step_impl=sc["step_impl"],
                                  state_dtype=sc["state_dtype"],
                                  weight_dtype=sc["weight_dtype"])
        ecfg = EngineConfig(n_slots=sc["n_slots"], max_seq=self.max_seq,
                            seed=int(seed) & 0x7FFFFFFF,
                            sched_quantum=sc["sched_quantum"])
        return cfg, ecfg


def check_layout(cfg, weights) -> None:
    """The benchmark's weight tree has the program's layout: the same
    leaves at the same paths, shapes and dtypes."""
    import jax
    from repro.models import registry
    from repro.parallel import sharding
    want = jax.tree_util.tree_flatten_with_path(
        sharding.tree_values(registry.abstract_params(cfg)))[0]
    have = jax.tree_util.tree_flatten_with_path(weights)[0]
    def fmt(xs):
        return sorted((jax.tree_util.keystr(k), tuple(v.shape),
                       str(v.dtype)) for k, v in xs)
    if fmt(want) != fmt(have):
        raise RunError(f"weight layout differs from the program's: "
                       f"{set(fmt(want)) ^ set(fmt(have))}")


class CompileGuard:
    """Counts jit traces (``sampling.TRACE_COUNTS``) and JAX compile
    events while armed."""

    _events = [0]
    _armed = [False]
    _registered = [False]

    def __init__(self):
        import jax
        if not CompileGuard._registered[0]:
            def on_event(name, secs, **kw):
                if CompileGuard._armed[0] and name.startswith(
                        "/jax/core/compile/"):
                    CompileGuard._events[0] += 1
            jax.monitoring.register_event_duration_secs_listener(on_event)
            CompileGuard._registered[0] = True

    def __enter__(self):
        from repro.runtime import sampling
        self._traces0 = sum(sampling.TRACE_COUNTS.values())
        self._events0 = CompileGuard._events[0]
        CompileGuard._armed[0] = True
        return self

    def __exit__(self, *exc):
        from repro.runtime import sampling
        CompileGuard._armed[0] = False
        self.traces = sum(sampling.TRACE_COUNTS.values()) - self._traces0
        self.compiles = CompileGuard._events[0] - self._events0
        return False


STATS = ("decode_steps", "active_steps", "prefill_calls", "prefill_tokens",
         "useful_tokens")


def stats_snapshot(engine) -> dict:
    return {k: getattr(engine.stats, k) for k in STATS}


@dataclasses.dataclass
class Untraced:
    """The window's part before the profiler started, [t0, t1): the
    host-side readers take their numbers here, where the profiler's
    start and its cost do not reach."""
    t0: float
    t1: float
    tokens: int            # output tokens delivered in it
    stats: dict            # ServeStats counters over it

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Ctx:
    """What a per-layer metric's reader may read."""
    cell: Cell
    win: serve.Window
    untraced: Untraced
    trace: object          # trace.Reduced, or None
    trace_stats: dict      # ServeStats counters over the traced part
    peak: dict             # bench/data/peaks.json entry of the device
    decode_path: str       # "fused" | "megakernel" | "xla"

    @property
    def dm(self):
        return self.cell.dm


def decode_path(engine) -> str:
    from repro.models import mamba_lm
    return mamba_lm.decode_path(engine.cfg, engine.params, engine.pool.cache)


def log(msg: str) -> None:
    import sys
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def setup(c: Cell, seed: int):
    """Weights, engine and warm-up: everything before the window."""
    from repro.runtime.engine import Engine
    t = clock()
    cfg, ecfg = c.program(seed)
    w = weights_lib.make(c.dm, seed)
    check_layout(cfg, w)
    log(f"set-up: weights {clock() - t:.2f} s")
    t = clock()
    engine = Engine(cfg, w, ecfg)
    log(f"set-up: engine {clock() - t:.2f} s")
    t = clock()
    n = serve.warm(engine, c.mix, c.dm.vocab_real, seed)
    log(f"set-up: warm-up ({n} requests) {clock() - t:.2f} s")
    return w, engine


#: ``--keep-trace`` copies a raw trace up to this size
KEEP_BYTES = 8 << 20


class Tracer:
    """The profiler over about the last ``trace_seconds`` of the window
    (a mix's parameter: the profiler keeps some five million op events,
    and a prefill scan emits hundreds of thousands a second), reduced on
    stop."""

    def __init__(self, engine):
        self.engine = engine
        self.stats0 = None

    def start(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans, not every call
        opts.enable_hlo_proto = False
        t = clock()
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        log(f"trace: start {clock() - t:.2f} s")
        self.stats0 = stats_snapshot(self.engine)

    def stop(self, keep: Optional[str] = None):
        import os

        import jax

        from harness import trace
        t = clock()
        jax.profiler.stop_trace()
        log(f"trace: stop {clock() - t:.2f} s")
        try:
            path = trace.find_xplane(self.dir)
            log(f"trace: {os.path.getsize(path)} bytes")
            if keep and os.path.getsize(path) <= KEEP_BYTES:
                os.makedirs(keep, exist_ok=True)
                shutil.copy(path, keep)
            t = clock()
            planes = trace.load(path)
            log(f"trace: load {clock() - t:.2f} s")
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        lo, hi = trace.span_bounds(planes, serve.SPAN_TRACED)
        if keep:
            os.makedirs(keep, exist_ok=True)
            with open(os.path.join(keep, "planes.txt"), "w") as f:
                f.write(trace.describe(planes))
            trace.save(trace.extract(planes, hi - 20e6, hi),
                       os.path.join(keep, "last_20ms.json.gz"))
        t = clock()
        red = trace.reduce_planes(planes, lo, hi)
        log(f"trace: reduce {clock() - t:.2f} s")
        return red


def _open_window(engine, c: Cell, src, seconds: float, tracer=None):
    """The window; with a tracer, the profiler starts with the first
    request sent ``trace_seconds`` before the close or later (so the
    traced part holds an admission), and at half that before the close
    at the latest."""
    import jax
    mark = None
    if tracer is not None:
        lead = c.mix.get("trace_seconds", 2.0)
        mark = (max(0.0, seconds - lead), max(0.0, seconds - lead / 2),
                tracer.start)
    with jax.profiler.TraceAnnotation("bench.window"):
        return serve.window(engine, src, seconds, n_queued=c.n_queued,
                            clock=clock, mark=mark)


def end_to_end(c: Cell, win, lat, setup_s: float) -> dict:
    vals = {"output_tok_s": win.tokens / win.seconds,
            "setup_s": setup_s}
    if lat["ttft"]:
        vals["ttft_p95_ms"] = 1e3 * serve.percentile(lat["ttft"], 95)
    if lat["tpot"]:
        vals["tpot_p95_ms"] = 1e3 * serve.percentile(lat["tpot"], 95)
    out = {}
    for m in c.e2e:
        if m["name"] not in vals:
            raise RunError(f"end-to-end metric {m['name']} has no value")
        out[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
    return out


def per_layer(ctx: Ctx) -> dict:
    out = {}
    for m in ctx.cell.per_layer:
        v = spec.metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def correctness(c: Cell, w, win, seed: int) -> dict:
    """The numbers compared, each with its limit (the configuration's
    ``checks``)."""
    items = check.sample(win, seed)
    limits = c.conf["checks"]
    out = {"budget_faults": {"value": serve.budget_faults(win, c.dm.vocab),
                             "limit": 0}}
    if not items:
        out["greedy_sampled"] = {"value": 0, "limit": 1}
        return out
    ref = spec.reference(c.conf["reference"])
    got = check.served_gaps(ref, c.dm, w, items, c.max_seq,
                            c.mix["max_new"]["max"])
    out["gap_mean"] = {
        "value": got["gap_mean"], "limit": limits["gap_mean"],
        "info": f"{got['positions']} positions, {got['agree']} on the "
                f"reference's best, widest gap {got['gap_max']!r}"}
    return out


def passed(checks: dict) -> bool:
    ok = True
    for k, v in checks.items():
        if k == "greedy_sampled":
            ok &= v["value"] >= v["limit"]
        else:
            ok &= v["value"] <= v["limit"]
    return bool(ok)


def run(c: Cell, seed: int, seconds: float, traced: bool, t_start: float,
        dev, keep_trace: Optional[str] = None):
    """One run: set-up, window, drain, metrics, the check.  Returns the
    result line (a dict) and the checks."""
    import jax
    w, engine = setup(c, seed)
    path = decode_path(engine)
    src = traffic.source(c.mix, seed, seconds, c.dm.vocab_real)
    tracer = Tracer(engine) if traced else None
    s0 = stats_snapshot(engine)
    guard = CompileGuard()
    t0 = clock()
    setup_s = t0 - t_start
    with guard:
        win = _open_window(engine, c, src, seconds, tracer)
    s1 = stats_snapshot(engine)
    if guard.traces or guard.compiles:
        raise RunError(f"{guard.traces} jit traces and {guard.compiles} "
                       f"compile events inside the window")
    if tracer is not None and win.t_mark is None:
        raise RunError("the window closed before the profiler started")
    # the profiler stops at the close: writing the trace delays the
    # drain, not the window
    red = tracer.stop(keep_trace) if tracer else None
    serve.drain(engine, win, clock=clock)
    mem = dev.memory_stats() or {}
    lat = serve.latencies(win)
    due = win.due_in_window()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    result = {"correct": False, "attempted": len(due),
              "failed": lat["failed"], "metrics": {}, "device": device}
    if traced:
        from harness import trace
        untraced = Untraced(
            t0=win.t0, t1=win.t_mark, tokens=win.tokens_mark,
            stats={k: tracer.stats0[k] - s0[k] for k in STATS})
        ctx = Ctx(cell=c, win=win, untraced=untraced, trace=red,
                  trace_stats={k: s1[k] - tracer.stats0[k] for k in STATS},
                  peak=work.peak(dev.device_kind), decode_path=path)
        result["metrics"] = per_layer(ctx)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = trace.breakdown(red)
    else:
        result["metrics"] = end_to_end(c, win, lat, setup_s)
    result["decode_path"] = path
    result["generator_late_p95_ms"] = 1e3 * serve.percentile(
        [s.submitted - s.due for s in win.sent] or [0.0], 95)
    del engine
    gc.collect()
    t = clock()
    checks = correctness(c, w, win, seed)
    log(f"check: {clock() - t:.2f} s")
    result["correct"] = passed(checks) and lat["failed"] == 0
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in checks.items()}
    return result, checks


def sweep(c: Cell, seed: int, seconds: float, rates, t_start: float):
    """The knee sweep: one set-up, then for each rate a window of
    ``seconds`` and its drain.  Yields one line per rate."""
    w, engine = setup(c, seed)
    yield {"setup_s": clock() - t_start, "decode_path": decode_path(engine)}
    for k, rate in enumerate(rates):
        src = traffic.source(c.mix, seed + k, seconds, c.dm.vocab_real,
                             rate)
        win = serve.window(engine, src, seconds, clock=clock)
        waiting = sum(s.req.t_admit is None for s in win.sent)
        serve.drain(engine, win, clock=clock)
        lat = serve.latencies(win)
        qw = [s.req.t_admit - s.due for s in win.sent
              if s.req.t_admit is not None]
        thirds = np.array_split(np.asarray(qw), 3) if qw else []
        yield {"rate_per_s": rate, "sent": len(win.sent),
               "waiting_at_close": waiting,
               "drain_s": win.t_drained - win.t_end,
               "output_tok_s": win.tokens / win.seconds,
               "ttft_p50_ms": 1e3 * serve.percentile(lat["ttft"], 50),
               "ttft_p95_ms": 1e3 * serve.percentile(lat["ttft"], 95),
               "tpot_p95_ms": (1e3 * serve.percentile(lat["tpot"], 95)
                               if lat["tpot"] else None),
               "queue_wait_ms_by_third": [
                   1e3 * float(np.median(t)) if len(t) else None
                   for t in thirds],
               "failed": lat["failed"]}
