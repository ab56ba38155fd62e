"""How ``correct`` is decided: the served tokens against the plain reference.

After the window has closed, the program's state has been freed and the
device's peak memory has been read, a sample of the greedy requests the
window finished, drawn from the seed and always holding the longest, is
run once through the configuration's plain reference (``bench/reference``)
over each prompt with its served tokens.  At every served position the
number compared is the gap by which the served token's reference logit
lies below the reference's best logit there; a greedy token that the
program chose correctly, up to its own rounding, lies within a small gap
of the best, and most lie on it.  ``gap_mean``, the mean gap over every
served position of the sample, is held to the configuration's
``checks.gap_mean`` limit (``PERF.md`` gives the readings it was set
from: the program's, and the int8 control's).  The widest gap is
reported beside it; it swings from seed to seed, and the control does
not read three times the program's widest gap, so it sets no limit.
Besides, every finished request must end on its budget or on a stop
id, and every token must be a vocabulary id (``budget_faults``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from harness import traffic as traffic_lib

#: requests in the sample (a fixed count keeps the reference's shapes,
#: and so its compiled programs, the same from run to run)
SAMPLE = 4


@dataclasses.dataclass
class Item:
    prompt: np.ndarray     # (P,) int32
    tokens: np.ndarray     # (G,) int32: the served tokens


def sample(win, seed: int, n: int = SAMPLE) -> list[Item]:
    """``n`` greedy requests the window finished: the one with the most
    served tokens, and the rest drawn from the seed, spread over the
    pool: each next one from a slot not yet in the sample where there is
    one, the highest such slot first, so a fault confined to part of
    the pool shows."""
    done = [s for s in win.due_in_window()
            if s.req.finished and s.spec.temperature == 0.0
            and len(s.req.tokens) > 0]
    if not done:
        return []
    done.sort(key=lambda s: s.spec.index)
    longest = max(range(len(done)), key=lambda i: len(done[i].req.tokens))
    rng = traffic_lib.rng_for(seed, 5)
    rest = list(rng.permutation([i for i in range(len(done))
                                 if i != longest]))
    pick, used = [longest], {done[longest].slot}
    while rest and len(pick) < n:
        fresh = [i for i in rest if done[i].slot not in used]
        i = (max(fresh, key=lambda i: (done[i].slot is not None,
                                       done[i].slot or 0))
             if fresh else rest[0])
        rest.remove(i)
        pick.append(i)
        used.add(done[i].slot)
    return [Item(prompt=np.asarray(done[i].spec.prompt, np.int32),
                 tokens=np.asarray(done[i].req.tokens, np.int32))
            for i in pick]


def pad_batch(items: list[Item], length: int, g_max: int,
              rows: int = SAMPLE):
    """Rows of prompt + served tokens (the last served token is never
    consumed), padded at the end to ``length``; the position whose
    logits chose each row's first served token; the served tokens,
    padded to ``g_max``, and their mask.  Fixed sizes keep the
    reference's programs the same from run to run."""
    toks = np.ones((rows, length), np.int32)
    starts = np.zeros((rows,), np.int32)
    served = np.zeros((rows, g_max), np.int32)
    mask = np.zeros((rows, g_max), bool)
    for b, it in enumerate(items):
        seq = np.concatenate([it.prompt, it.tokens[:-1]])
        toks[b, :seq.size] = seq
        starts[b] = it.prompt.size - 1
        served[b, :it.tokens.size] = it.tokens
        mask[b, :it.tokens.size] = True
    return toks, starts, served, mask


@jax.jit
def gaps(ref_logits, tokens, mask):
    """Reference best minus the reference logit of ``tokens``, per
    position; 0 where ``mask`` is False."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tokens[..., None], axis=-1)[..., 0]
    return jnp.where(mask, best - got, 0.0)


@jax.jit
def argmax_tokens(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def reference_logits(ref, dm, weights, items: list[Item], length: int,
                     g_max: int):
    """The reference's logits at every served position of ``items``,
    with the batch laid out by ``pad_batch``: prompts and tokens of up
    to ``length`` positions, up to ``g_max`` served tokens a request."""
    toks, starts, served, mask = pad_batch(items, length, g_max)
    logits = ref.logits_along(dm, weights, jnp.asarray(toks),
                              jnp.asarray(starts), n_out=served.shape[1])
    return logits, jnp.asarray(served), jnp.asarray(mask)


def served_gaps(ref, dm, weights, items: list[Item], length: int,
                g_max: int) -> dict:
    """The numbers compared for the served tokens of ``items``."""
    logits, served, mask = reference_logits(ref, dm, weights, items, length,
                                            g_max)
    g = np.asarray(gaps(logits, served, mask))
    m = np.asarray(mask)
    return {"gap_max": float(g[m].max()),
            "gap_mean": float(g[m].mean()),
            "positions": int(m.sum()),
            "agree": int(((g == 0.0) & m).sum())}
