"""The readers of the engine's host spans (``host_serial_share``,
``step_p90_ms``, ``admit_behind_p90_ms``) on span sets built by hand in
the program's recorder, with a made-up clock: the values, and None where
the recorder dropped spans of the part, holds none there, or is not in
the program at all; and a traced smoke-size run that reads all four."""
import pathlib
import sys
import types

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import cell, spec  # noqa: E402

from repro import runtime  # noqa: E402
from repro.runtime import tracing  # noqa: E402

METRICS = ["host_serial_share.batch", "host_serial_share.chat",
           "step_p90_ms.chat", "admit_behind_p90_ms.chat"]

#: the untraced part of the window, on the made-up clock
T0, T1 = 10.0, 100.0


@pytest.fixture(autouse=True)
def _fresh_record():
    tracing.reset()
    tracing.enable()
    yield
    tracing.reset()


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _ctx():
    return types.SimpleNamespace(untraced=cell.Untraced(
        t0=T0, t1=T1, tokens=0, stats={}))


def _span(clk, node):
    """Record ``(name, t0, t1, children)`` and its children, nested."""
    name, t0, t1, kids = node
    clk.t = t0
    with tracing.span(name, clock=clk):
        for k in kids:
            _span(clk, k)
        clk.t = t1


def _step(t0, admits=(), burst=None):
    """An ``engine.step`` from ``t0``: admissions of (prefill, sync)
    seconds after 0.01 s of bookkeeping each, then a burst of
    (prepare, dispatch, sync, deliver) seconds; 0.02 s of bookkeeping
    closes the step."""
    kids, t = [], t0
    for pre, syn in admits:
        a0 = t = t + 0.01
        kids.append(("engine.admit", a0, a0 + pre + syn, [
            ("engine.admit.prefill", a0, a0 + pre, []),
            ("engine.admit.sync", a0 + pre, a0 + pre + syn, [])]))
        t = a0 + pre + syn
    if burst is not None:
        b0, phases = t, []
        for name, dt in zip(("prepare", "dispatch", "sync", "deliver"),
                            burst):
            phases.append((f"engine.burst.{name}", t, t + dt, []))
            t += dt
        kids.append(("engine.burst", b0, t, phases))
    return ("engine.step", t0, t + 0.02, kids)


def _read(name):
    return spec.metric_reader(name)(_ctx())


def test_host_serial_share_counts_bookkeeping_inside_whole_steps():
    clk = _Clock()
    steps = [
        # straddles the part's start: none of it counts, its dispatch
        # inside the part neither
        _step(9.9, burst=(0.01, 0.2, 0.3, 0.01)),
        _step(20.0, admits=[(0.5, 0.1)], burst=(0.01, 0.2, 0.3, 0.04)),
        _step(30.0, burst=(0.02, 0.1, 0.2, 0.03)),
        # straddles the part's end
        _step(99.9, burst=(0.01, 0.2, 0.3, 0.01)),
    ]
    for s in steps:
        _span(clk, s)
    # step 2: 0.01 + 0.01 + 0.04 + 0.02 outside the device spans;
    # step 3: 0.02 + 0.03 + 0.02
    serial = 0.08 + 0.07
    assert _read("host_serial_share.batch") == pytest.approx(
        100.0 * serial / (T1 - T0))
    assert _read("host_serial_share.chat") == \
        _read("host_serial_share.batch")


def test_step_p90_reads_whole_steps_only():
    clk = _Clock()
    durations = [0.05 * (i + 1) for i in range(20)]
    t = 20.0
    for d in durations:
        _span(clk, ("engine.step", t, t + d, []))
        t += 1.0
    _span(clk, ("engine.step", 99.0, 101.0, []))     # ends after the part
    assert _read("step_p90_ms.chat") == pytest.approx(
        1e3 * np.percentile(durations, 90))


def test_admit_behind_p90_is_the_wait_behind_earlier_admissions():
    clk = _Clock()
    behind = []
    t = 20.0
    for n in range(1, 6):
        s = _step(t, admits=[(0.1, 0.05)] * n, burst=(0.01, 0.1, 0.1, 0.01))
        _span(clk, s)
        # the k-th admission starts after k - 1 earlier ones and k times
        # 0.01 s of bookkeeping
        behind += [k * 0.01 + (k - 1) * 0.15 for k in range(1, n + 1)]
        t += 2.0
    assert _read("admit_behind_p90_ms.chat") == pytest.approx(
        1e3 * np.percentile(behind, 90))


@pytest.mark.parametrize("name", METRICS)
def test_none_where_the_recorder_dropped_spans_of_the_part(name):
    tracing.reset(capacity=8)
    clk = _Clock()
    for i in range(6):
        _span(clk, _step(20.0 + i, admits=[(0.1, 0.05)],
                         burst=(0.01, 0.1, 0.1, 0.01)))
    assert tracing.dropped() > 0
    assert _read(name) is None


@pytest.mark.parametrize("name", METRICS)
def test_none_where_the_part_holds_no_span(name):
    clk = _Clock()
    _span(clk, _step(5.0, admits=[(0.1, 0.05)], burst=(0.01, 0.1, 0.1, 0.01)))
    _span(clk, _step(150.0, admits=[(0.1, 0.05)],
                     burst=(0.01, 0.1, 0.1, 0.01)))
    assert _read(name) is None


@pytest.mark.parametrize("name", METRICS)
def test_none_where_the_program_has_no_recorder(name, monkeypatch):
    """A program that predates ``repro.runtime.tracing`` reads as no
    value, not an error."""
    clk = _Clock()
    _span(clk, _step(20.0, admits=[(0.1, 0.05)], burst=(0.01, 0.1, 0.1, 0.01)))
    monkeypatch.delattr(runtime, "tracing")
    monkeypatch.setitem(sys.modules, "repro.runtime.tracing", None)
    assert _read(name) is None


def test_traced_smoke_run_reports_the_span_metrics():
    """A traced run of a smoke-size cell on the CPU, through the
    harness's own functions, reads all four from the program's spans."""
    from cpu_smoke import run, smoke_cell
    res, _ = run(smoke_cell("backlog", per_layer=METRICS), traced=True,
                 seconds=1.5)
    assert res["correct"] is True, res
    assert set(res["metrics"]) == set(METRICS)
    assert all(m["value"] > 0 for m in res["metrics"].values())
