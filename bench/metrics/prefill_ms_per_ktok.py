"""Device time of the prefill programs per 1,000 prompt tokens admitted
while traced, in ms.  Layer: the model step (prefill through
``_jit_prefill_admit``).  The prefill admit shares its jit name ``_fn``
with the pool's scatter, gather and mask programs; it is the one among
them that runs a ``while`` (the scan over layers)."""

PROGRAM = r"^jit__fn\("


def read(ctx):
    tokens = ctx.trace_stats["prefill_tokens"]
    if ctx.trace is None or tokens == 0:
        return None
    n, secs = ctx.trace.module(PROGRAM, with_op="while")
    if n == 0 or secs <= 0:
        return None
    return 1e3 * secs / (tokens / 1e3)
