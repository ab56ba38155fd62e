"""90th percentile over admissions of the time from the start of the
``engine.step`` span to the start of its ``engine.admit`` span, in ms,
over the steps that lie wholly in the untraced part of the window: the
wait of an admission behind the same step's earlier admissions.  Read
from the program's span record (``repro.runtime.tracing``); None where
the program has none, where the record holds no admission in the part,
or where it dropped spans of the part.  Layer: the engine's
scheduler."""
from harness import serve


def read(ctx):
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    u = ctx.untraced
    spans, dropped = tracing.query(u.t0, u.t1,
                                   ("engine.step", "engine.admit"))
    steps = {s.id: s for s in spans if s.name == "engine.step"}
    behind = [s.t0 - steps[s.parent].t0 for s in spans
              if s.name == "engine.admit" and s.parent in steps]
    if dropped or not behind:
        return None
    return 1e3 * serve.percentile(behind, 90)
