"""95th percentile of the wait from a request's due time to the start of
its admission (``Request.t_admit``), in ms, over the requests due in the
untraced part of the window at least ``MARGIN_S`` before the profiler
started: their admissions come before it (the wait at the knee is well
under a second), so neither the profiler's start nor its cost is in
them.  Layer: the engine's scheduler."""
from harness import serve

MARGIN_S = 5.0


def read(ctx):
    u = ctx.untraced
    waits = [s.req.t_admit - s.due for s in ctx.win.sent
             if s.due < u.t1 - MARGIN_S and s.req.t_admit is not None]
    return 1e3 * serve.percentile(waits, 95) if waits else None
