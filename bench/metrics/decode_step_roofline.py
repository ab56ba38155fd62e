"""The decode program's share of its roofline, in %: the least time the
chip needs for one pooled decode step of the active slots
(``work.decode_step``), times the steps traced, over the device time of
the decode program (``_decode_fn``).  Layer: the model step."""
from harness import work

PROGRAM = r"^jit__decode_fn\("


def read(ctx):
    st = ctx.trace_stats
    if ctx.trace is None or not st["decode_steps"]:
        return None
    n, secs = ctx.trace.module(PROGRAM)
    if n == 0 or secs <= 0:
        return None
    active = st["active_steps"] / st["decode_steps"]
    sc = ctx.cell.serve_conf
    need = work.decode_step(ctx.dm, active, sc["state_dtype"]).seconds(
        ctx.peak)
    return 100.0 * need * n / secs
