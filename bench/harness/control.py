"""The control: the program with its int8 path switched on, teacher-forced.

The control is what ``correct`` must refuse: the same prompts and served
tokens, replayed through the program's own prefill and decode step at
the cell's pool size with weights (and, where asked, the state) stored
as int8, the precision a later change would be tempted to take.  At
each served position it reads the token the control puts first, and the
reference's gap for that token.  Only the calibration runs it
(``bench/calibrate.py``); a benchmark run does not.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from harness import check


def replay_tokens(engine, items, slots) -> np.ndarray:
    """The first-choice token (B, G_max) of ``engine``'s program at every
    served position of ``items``, teacher-forced with their served
    tokens; item b sits in pool slot ``slots[b]``."""
    from repro.models import registry
    cfg = engine.cfg
    g_max = max(len(it.tokens) for it in items)
    pool = engine.pool.cache
    fresh = engine.pool.fresh
    prefill = jax.jit(lambda p, c, t: registry.prefill(
        cfg, p, c, {"tokens": t}))
    scatter = jax.jit(lambda pool, sub, i: registry.scatter_slots(
        cfg, pool, sub, i))
    step = jax.jit(lambda p, c, t: registry.decode_step(
        cfg, p, c, {"tokens": t}))
    out = np.zeros((len(items), g_max), np.int32)
    for b, it in enumerate(items):
        logits, sub = prefill(engine.prefill_params, fresh,
                              jnp.asarray(it.prompt[None]))
        out[b, 0] = int(jnp.argmax(logits[0, -1]))
        pool = scatter(pool, sub, jnp.asarray([slots[b]]))
    rows = jnp.asarray(slots)
    firsts = []
    for t in range(g_max - 1):
        feed = np.ones((engine.ecfg.n_slots, 1), np.int32)
        for b, it in enumerate(items):
            if t < len(it.tokens):
                feed[slots[b], 0] = it.tokens[t]
        logits, pool = step(engine.params, pool, jnp.asarray(feed))
        firsts.append(check.argmax_tokens(logits[rows, 0]))
    if firsts:
        out[:, 1:] = np.asarray(jnp.stack(firsts, axis=1))
    return out


def control_engine(cfg, weights, ecfg, weight_dtype: str,
                   state_dtype: str):
    from repro.runtime.engine import Engine
    return Engine(cfg, weights, dataclasses.replace(
        ecfg, weight_dtype=weight_dtype, state_dtype=state_dtype))


def gaps_of(ref_logits, served_mask, tokens) -> dict:
    g = np.asarray(check.gaps(ref_logits, jnp.asarray(tokens), served_mask))
    m = np.asarray(served_mask)
    return {"gap_max": float(g[m].max()), "gap_mean": float(g[m].mean()),
            "positions": int(m.sum()), "agree": int(((g == 0) & m).sum())}
