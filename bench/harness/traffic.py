"""Seeded traffic: a mix's parameters and a seed in, requests out.

A mix file (``bench/traffic/<name>.json``) names its generator by
``kind``; the generator reads the rest of the file, an open loop's rate
(``rate_per_s``) too.  Every seed gets the same multiset of prompt
lengths, output budgets, greedy shares and arrival counts per episode,
so runs with different seeds do the same work.  Without a
``schedule_seed`` the run's seed puts them in its own order; a mix with
a ``schedule_seed`` draws the whole schedule (episodes, due times, and
the length, budget and greedy flag of each request) from that seed, the
same for every run, and the run's seed draws only the contents: token
ids and sampling seeds.  A tail latency then reads the system, not
where the seed put the long prompts.

Prompt lengths come from the mix's ladder only, so set-up can compile
every prefill shape the window will use.  Prompt token ids are real
vocabulary ids, never the stop id 0.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Req:
    """One request as the harness sends it."""
    index: int
    due: float              # seconds after the window opens
    prompt: np.ndarray      # (length,) int32
    max_new: int
    temperature: float      # 0: greedy
    top_p: float
    seed: int
    stop_ids: tuple


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy generator per (seed, purpose); any whole
    number is a seed, however large."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def rngs(mix: dict, seed: int, stream: int):
    """(schedule generator, contents generator): one generator from the
    run's seed for both, or the schedule's from the mix's
    ``schedule_seed``."""
    run = rng_for(seed, stream)
    if "schedule_seed" not in mix:
        return run, run
    return rng_for(mix["schedule_seed"], stream), run


def _counts(shares, n: int) -> list[int]:
    """``n`` split by ``shares`` with the largest remainders rounded up:
    the same counts for every seed."""
    raw = [s * n for s in shares]
    out = [math.floor(r) for r in raw]
    order = sorted(range(len(raw)), key=lambda i: out[i] - raw[i])
    for i in order[:n - sum(out)]:
        out[i] += 1
    return out


def _quantiles(dist: dict, n: int) -> list[int]:
    """``n`` output budgets at the quantiles (i + 1/2) / n of ``dist``."""
    qs = [(i + 0.5) / n for i in range(n)]
    kind, lo, hi = dist["dist"], dist["min"], dist["max"]
    if kind == "lognormal":
        z = statistics.NormalDist()
        vals = [dist["median"] * math.exp(dist["sigma"] * z.inv_cdf(q))
                for q in qs]
    elif kind == "uniform":
        vals = [lo + q * (hi - lo) for q in qs]
    elif kind == "loguniform":
        vals = [math.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
                for q in qs]
    else:
        raise ValueError(f"unknown max_new dist {kind!r}")
    return [int(min(hi, max(lo, round(v)))) for v in vals]


def ladder(mix: dict) -> list[int]:
    return list(mix["prompt_ladder"])


def _sizes(mix: dict, rng, n: int):
    """Prompt lengths, budgets and greedy flags for ``n`` requests:
    fixed multisets, each permuted by ``rng``."""
    counts = _counts(mix["prompt_shares"], n)
    lengths = np.repeat(np.asarray(mix["prompt_ladder"]), counts)
    budgets = np.asarray(_quantiles(mix["max_new"], n))
    greedy = np.arange(n) < round(mix["greedy_share"] * n)
    return (rng.permutation(lengths), rng.permutation(budgets),
            rng.permutation(greedy))


def _requests(mix: dict, sched, rng, dues, vocab: int,
              first: int) -> list[Req]:
    """Requests due at ``dues``: sizes from ``sched``, contents from
    ``rng``."""
    lengths, budgets, greedy = _sizes(mix, sched, len(dues))
    stop = tuple(mix.get("stop_ids", ()))
    out = []
    for i, due in enumerate(dues):
        prompt = rng.integers(1, vocab, size=(int(lengths[i]),),
                              dtype=np.int32)
        g = bool(greedy[i])
        out.append(Req(
            index=first + i, due=float(due), prompt=prompt,
            max_new=int(budgets[i]),
            temperature=0.0 if g else float(mix["temperature"]),
            top_p=1.0 if g else float(mix["top_p"]),
            seed=int(rng.integers(0, 2**31 - 1)), stop_ids=stop))
    return out


def _episodes(mix: dict, rng, seconds: float):
    """Calm and burst episodes covering ``seconds``: the durations are
    the quantiles of an exponential with the mix's mean burst length,
    scaled so bursts cover ``burst_share`` of the time; the seed orders
    them.  Returns [(start, duration, rate factor)]."""
    share = mix["burst_share"]
    n = max(1, round(share * seconds / mix["burst_mean_s"]))
    base = [-math.log(1 - (i + 0.5) / n) for i in range(n)]

    def scaled(total):
        s = sum(base)
        return list(rng.permutation([b * total / s for b in base]))

    bursts = scaled(share * seconds)
    calms = scaled((1 - share) * seconds)
    calm_first = bool(rng.integers(2))
    out, t = [], 0.0
    for b, c in zip(bursts, calms):
        pair = ([(c, mix["calm_factor"]), (b, mix["burst_factor"])]
                if calm_first else
                [(b, mix["burst_factor"]), (c, mix["calm_factor"])])
        for dur, factor in pair:
            out.append((t, dur, factor))
            t += dur
    return out


def bursty_open_loop(mix: dict, seed: int, seconds: float, rate: float,
                     vocab: int) -> list[Req]:
    """Open loop: Poisson arrivals at ``calm_factor * rate`` in calm
    episodes and ``burst_factor * rate`` in bursts, the mean rate
    ``rate`` (the factors and the burst share must average to 1).
    ``round(rate * seconds)`` requests in all, each episode's count
    fixed, their times uniform within it (a Poisson process given its
    count)."""
    mean = (mix["calm_factor"] * (1 - mix["burst_share"])
            + mix["burst_factor"] * mix["burst_share"])
    if abs(mean - 1.0) > 1e-9:
        raise ValueError(f"calm and burst factors average to {mean}, not 1")
    sched, rng = rngs(mix, seed, 1)
    eps = _episodes(mix, sched, seconds)
    n = round(rate * seconds)
    counts = _counts([d * f / seconds for _, d, f in eps], n)
    dues = []
    for (start, dur, _), k in zip(eps, counts):
        dues.extend(sorted(sched.uniform(start, start + dur, size=k)))
    return _requests(mix, sched, rng, dues, vocab, 0)


class Backlog:
    """A saturated queue: requests on demand, in blocks of ``block``
    whose sizes are fixed multisets permuted by the seed."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.vocab = mix, vocab
        self.sched, self.rng = rngs(mix, seed, 2)
        self.block = int(mix.get("block", 256))
        self._buf: list[Req] = []
        self._next = 0

    def take(self) -> Req:
        if not self._buf:
            self._buf = _requests(self.mix, self.sched, self.rng,
                                  [0.0] * self.block, self.vocab,
                                  self._next)
            self._next += self.block
        return self._buf.pop(0)


def source(mix: dict, seed: int, seconds: float, vocab: int,
           rate: Optional[float] = None):
    """The mix's generator by ``kind``: a list of requests with due
    times (open loop, at ``rate`` or the mix's ``rate_per_s``) or a
    ``Backlog``."""
    kind = mix["kind"]
    if kind == "bursty_open_loop":
        rate = mix.get("rate_per_s") if rate is None else rate
        if rate is None:
            raise ValueError("an open-loop mix needs a rate")
        return bursty_open_loop(mix, seed, seconds, rate, vocab)
    if kind == "backlog":
        return Backlog(mix, seed, vocab)
    raise ValueError(f"unknown traffic kind {kind!r}")
