"""90th percentile of the durations of the ``engine.step`` spans that lie
wholly in the untraced part of the window, in ms: how long a request
that falls due may wait for the step in flight.  Read from the program's
span record (``repro.runtime.tracing``); None where the program has
none, where the record holds no step in the part, or where it dropped
spans of the part.  Layer: the engine's scheduler."""
from harness import serve


def read(ctx):
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    u = ctx.untraced
    steps, dropped = tracing.query(u.t0, u.t1, ("engine.step",))
    if dropped or not steps:
        return None
    return 1e3 * serve.percentile([s.t1 - s.t0 for s in steps], 90)
