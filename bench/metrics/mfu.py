"""The whole step's share of the chip's bf16 peak, in %: 2 x matrix
parameters x (prompt + output tokens) served in the untraced part of the
window, plus the output head per output token, per second of that part,
over the peak.  Layer: device (whole step)."""
from harness import work


def read(ctx):
    u = ctx.untraced
    prompt = u.stats["prefill_tokens"]
    out = u.tokens
    if prompt + out == 0 or u.seconds <= 0:
        return None
    flops = work.model_flops(ctx.dm, prompt, out)
    return 100.0 * flops / u.seconds / ctx.peak["bf16_flops_per_s"]
