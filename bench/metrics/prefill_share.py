"""Share of the untraced part of the window, in %, spent inside
admissions: the sum over admissions of ``t_first - t_admit`` (prefill,
first-token sampling and its host sync), clipped to that part.  The
profiler starts between steps, so no admission straddles its start.
Layer: the engine's scheduler."""


def read(ctx):
    u = ctx.untraced
    inside = 0.0
    for s in ctx.win.sent:
        r = s.req
        if r.t_admit is None or r.t_first is None:
            continue
        inside += max(0.0, min(r.t_first, u.t1) - max(r.t_admit, u.t0))
    return 100.0 * inside / u.seconds if inside > 0 else None
