"""From a profiler trace (``.xplane.pb``) to what per-layer metrics read.

The traced run records the measured window under ``jax.profiler``.  Its
device planes (``/device:TPU:<n>``) hold a line of XLA module events, one
per program execution, and a line of op events inside them; the host
plane holds this harness's spans (``bench.step``, ``bench.submit``,
``bench.wait``) and the program's own host events on the Python thread.
``reduce`` keeps what the metrics need:

- the busy time: the union of op intervals inside the window, averaged
  over the chips used;
- time and count per program execution (``jit__decode_fn(<id>)``: the id
  tells programs of one jit name apart) and per op within its program,
  by the op's own name in its HLO text (``marca_decode_step.3 = ...
  custom-call(...)``);
- the idle gaps of the device inside the window, each labelled by the
  harness span open at its middle (and the innermost host event under
  it), summed by label.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import glob
import gzip
import json
import os
import re
from typing import Optional

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    chips: int
    modules: dict          # program execution name -> [count, seconds]
    gaps: dict             # label -> seconds
    module_ops: dict       # (program, op label) -> [count, seconds]
    module_kinds: dict     # program execution name -> set of op kinds

    def module(self, pattern: str, with_op: Optional[str] = None):
        """Executions and device seconds of the programs whose name (as
        ``jit__decode_fn(<id>)``) matches ``pattern``; with ``with_op``,
        only programs that ran an op of that kind."""
        n = s = 0
        for name, (c, t) in self.modules.items():
            if re.search(pattern, name) and (
                    with_op is None
                    or with_op in self.module_kinds.get(name, ())):
                n += c
                s += t
        return n, s


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


_OP = re.compile(r"^%?([^ =]+) = (.*?) ([a-z][a-z0-9_-]*)\(")


@functools.lru_cache(maxsize=None)
def parse_op(text: str) -> tuple[str, str, str]:
    """An op event's HLO text ``%name = type kind(operands), ...`` ->
    (name, kind, result type); an event that is not HLO text keeps its
    text as its name."""
    m = _OP.match(text)
    if not m:
        return text, "", ""
    return m.group(1), m.group(3), m.group(2)


def program_name(name: str) -> str:
    """``jit__decode_fn(123)`` -> ``jit__decode_fn``."""
    return re.sub(r"\(\d+\)$", "", name)


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce_planes(planes, t_lo: float, t_hi: float,
                  min_gap_s: float = 1e-4) -> Reduced:
    """``planes``: [(plane name, [(line name, [(name, start_ns, dur_ns)])])]
    with every timestamp on one clock; the window is [t_lo, t_hi] in ns."""
    dev = [p for p in planes if re.match(r"^/device:TPU:\d+$", p[0])]
    modules = collections.defaultdict(lambda: [0, 0.0])
    kinds = collections.defaultdict(set)
    module_ops = collections.defaultdict(lambda: [0, 0.0])
    busy_total = 0.0
    unions = []
    for _, lines in dev:
        lines = dict(lines)
        spans = []
        mod_ev = sorted(lines.get(MODULES_LINE, []), key=lambda e: e[1])
        for name, st, du in mod_ev:
            s, e = _clip(st, st + du, t_lo, t_hi)
            if e <= s:
                continue
            m = modules[name]
            m[0] += 1
            m[1] += (e - s) * 1e-9
        mi = 0
        for text, st, du in sorted(lines.get(OPS_LINE, []),
                                   key=lambda e: e[1]):
            s, e = _clip(st, st + du, t_lo, t_hi)
            if e <= s:
                continue
            spans.append((s, e))
            name, kind, rtype = parse_op(text)
            while mi < len(mod_ev) and mod_ev[mi][1] + mod_ev[mi][2] < st:
                mi += 1
            mod = (mod_ev[mi][0]
                   if mi < len(mod_ev) and mod_ev[mi][1] <= st else "?")
            kinds[mod].add(kind)
            label = f"{program_name(mod)}/{name} {kind} {rtype[:48]}"
            mo = module_ops[label]
            mo[0] += 1
            mo[1] += (e - s) * 1e-9
        u = _union(spans)
        busy_total += sum(e - s for s, e in u) * 1e-9
        unions.append(u)
    n = max(1, len(dev))
    gaps = collections.defaultdict(float)
    if unions:
        host = _host_spans(planes)
        u = unions[0]
        edges = [t_lo] + [x for iv in u for x in iv] + [t_hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if (b - a) * 1e-9 >= min_gap_s:
                gaps[_label(host, (a + b) / 2)] += (b - a) * 1e-9
    return Reduced(window_s=(t_hi - t_lo) * 1e-9, busy_s=busy_total / n,
                   chips=len(dev), modules=dict(modules),
                   gaps=dict(gaps), module_ops=dict(module_ops),
                   module_kinds={k: frozenset(v) for k, v in kinds.items()})


def _host_spans(planes) -> list:
    """Events of the host thread that holds the harness's spans."""
    out = []
    for pname, lines in planes:
        if not pname.startswith("/host:"):
            continue
        for lname, events in lines:
            if any(name.startswith("bench.") for name, _, _ in events):
                out.extend((st, st + du, name) for name, st, du in events)
    return out


def _label(host, t) -> str:
    """The harness span open at ``t`` and the innermost host event under
    it on the Python thread."""
    around = [(s, e, n) for s, e, n in host if s <= t <= e]
    if not around:
        return "no host span"
    around.sort(key=lambda x: (x[0], -x[1]))
    outer = [n for _, _, n in around if n.startswith("bench.")]
    inner = around[-1][2]
    # the innermost harness span; the window span only where no other is
    # open (the harness's own loop between spans)
    head = ([n for n in outer if n != "bench.window"] or outer
            or ["other"])[-1]
    return head if inner == head else f"{head} > {inner}"


def span_bounds(planes, name: str) -> tuple[float, float]:
    """Start and end (ns, on the trace's clock) of the host span ``name``."""
    for s, e, n in _host_spans(planes):
        if n == name:
            return s, e
    raise KeyError(f"no host span {name!r} in the trace")


def load(path: str):
    """The planes of an ``.xplane.pb`` as plain tuples."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    planes = []
    for p in pd.planes:
        lines = []
        for ln in p.lines:
            lines.append((ln.name, [(e.name, e.start_ns, e.duration_ns)
                                    for e in ln.events]))
        planes.append((p.name, lines))
    return planes


def extract(planes, lo: float, hi: float) -> list:
    """The device lines and the harness's host line, cut to the events
    that overlap [lo, hi]: a small sample of a trace to keep."""
    out = []
    for pname, lines in planes:
        keep = []
        for lname, events in lines:
            if pname.startswith("/device:TPU:") or any(
                    n.startswith("bench.") for n, _, _ in events):
                cut = [(n, st, du) for n, st, du in events
                       if st < hi and st + du > lo]
                if cut:
                    keep.append((lname, cut))
        if keep:
            out.append((pname, keep))
    return out


def save(planes, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(planes, f)


def load_saved(path: str) -> list:
    with gzip.open(path, "rt") as f:
        return [(p, [(ln, [tuple(e) for e in evs]) for ln, evs in lines])
                for p, lines in json.load(f)]


def describe(planes, k: int = 12) -> str:
    """Planes, lines, event counts and the commonest names: for a reader
    of a trace this code has not seen."""
    out = []
    for pname, lines in planes:
        out.append(f"PLANE {pname}")
        for lname, events in lines:
            names = collections.Counter(n for n, _, _ in events)
            out.append(f"  LINE {lname!r} {len(events)} events: "
                       f"{names.most_common(k)}")
            for n, st, du in events[:3]:
                out.append(f"    {n} start {st} dur {du}")
    return "\n".join(out) + "\n"


def breakdown(red: Reduced, k: int = 10) -> dict:
    """The device ops that took most time, as ``program/op kind type``,
    and the idle time by what the host was doing, longest first."""
    ops = sorted(((label, v[1]) for label, v in red.module_ops.items()),
                 key=lambda x: -x[1])[:k]
    gaps = sorted(red.gaps.items(), key=lambda x: -x[1])[:k]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
