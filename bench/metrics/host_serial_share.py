"""Share of the untraced part of the window, in %, in which the host did
the engine's own bookkeeping while the device held none of its work:
time inside ``engine.step`` spans less the time inside the device-bound
spans under them (``engine.burst.dispatch``, ``engine.burst.sync``,
``engine.admit.prefill``, ``engine.admit.sync``), over the part's
length.  Read from the program's span record (``repro.runtime.tracing``);
None where the program has none, where the record holds no step in the
part, or where it dropped spans of the part.  Layer: the engine's
scheduler."""

DEVICE = frozenset({"engine.burst.dispatch", "engine.burst.sync",
                    "engine.admit.prefill", "engine.admit.sync"})


def _step_of(rec, by_id):
    """The ``engine.step`` span that ``rec`` lies in, or None."""
    while rec is not None and rec.name != "engine.step":
        rec = by_id.get(rec.parent)
    return rec


def read(ctx):
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    u = ctx.untraced
    spans, dropped = tracing.query(u.t0, u.t1)
    steps = [s for s in spans if s.name == "engine.step"]
    if dropped or not steps or u.seconds <= 0:
        return None
    by_id = {s.id: s for s in spans}
    inside = sum(s.t1 - s.t0 for s in steps)
    device = sum(s.t1 - s.t0 for s in spans
                 if s.name in DEVICE and _step_of(s, by_id) is not None)
    return 100.0 * (inside - device) / u.seconds
