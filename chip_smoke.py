#!/usr/bin/env python3
"""Chip smoke test: serve mamba-1.4b at its published widths on one TPU.

    python chip_smoke.py             # one chip: serve + XLA f32 reference
    python chip_smoke.py --chips 4   # 4-way tensor-parallel vs one device

One process drives every device it uses.  Weights are random, drawn
from ``--seed``; nothing is downloaded.  The one-chip run builds the
model with ``registry.init_params``, serves 8 seeded requests (prompt
lengths from {128, 512}, half greedy, half sampled at temperature 0.8 /
top-p 0.95, 32 new tokens each) through ``runtime.engine.Engine`` with
an 8-slot pool, checks every request ends on its budget or a stop
token, and replays two greedy requests through the served decode path
and through the XLA reference step (``step_impl="xla"``, f32,
``jax.default_matmul_precision("highest")``) to compare their logits.

``--chips 4`` runs only the sharded comparison: the same requests
through an Engine on ``make_serving_mesh(4)`` and on one device.

The script exits non-zero, printing no result line, when JAX finds no
TPU or any check fails.  On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Compile and run seconds are printed as set-up information only.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs  # noqa: E402
from repro.models import mamba_lm, registry  # noqa: E402
from repro.parallel import sharding  # noqa: E402
from repro.runtime.engine import Engine, EngineConfig  # noqa: E402
from repro.runtime.sampling import SamplingParams  # noqa: E402

ARCH = "mamba-1.4b"
N_SLOTS = 8
N_REQUESTS = 8
PROMPT_LENS = (128, 512)
MAX_NEW = 32
#: decode steps whose logits are compared against the reference
N_DECODE = 8
#: max |served - reference| logit allowed, as a fraction of the
#: reference's largest |logit| over the compared positions: the served
#: path computes in bf16 (cfg.dtype) with default-precision matmuls,
#: the reference in f32 at "highest" (see CHANGES.md, PR 12)
LOGIT_RTOL = 0.1


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def make_requests(vocab: int, seed: int, n: int = N_REQUESTS,
                  lens=PROMPT_LENS, max_new: int = MAX_NEW):
    """``n`` seeded (prompt, SamplingParams) pairs: prompt lengths from
    ``lens`` in equal shares, shuffled; even requests greedy, odd ones
    sampled at temperature 0.8 / top-p 0.95 with their own seed."""
    rng = np.random.default_rng(seed)
    lengths = rng.permutation(np.resize(np.asarray(lens), n))
    out = []
    for i, length in enumerate(lengths):
        prompt = rng.integers(0, vocab, size=(int(length),), dtype=np.int32)
        if i % 2 == 0:
            sp = SamplingParams(max_new=max_new)
        else:
            sp = SamplingParams(temperature=0.8, top_p=0.95,
                                seed=seed * 1000 + i, max_new=max_new)
        out.append((prompt, sp))
    return out


def serve(engine: Engine, requests):
    """Submit every request, run the engine to completion."""
    reqs = [engine.submit(prompt, sp) for prompt, sp in requests]
    engine.run()
    return reqs


def check_budgets(reqs) -> None:
    """Every request finished with its token budget or on a stop token."""
    for r in reqs:
        stopped = bool(r.tokens) and r.tokens[-1] in r.stop_ids
        if not r.finished or (len(r.tokens) != r.max_new and not stopped):
            raise AssertionError(
                f"request {r.req_id}: finished={r.finished}, "
                f"{len(r.tokens)} of {r.max_new} tokens")


def replay_logits(cfg, params, prefill_params, reqs, n_slots: int,
                  max_seq: int, n_decode: int, shard=None,
                  want_hlo: bool = False):
    """Logits of ``reqs`` along their own token streams, through
    ``registry.prefill`` and ``registry.decode_step`` at the pool shape
    (``n_slots`` rows; request i in slot i, teacher-forced with its
    tokens).  ``shard`` is the engine's (mesh, rules) or None.  Returns
    ([(prefill (L, V), decode (n_decode, V)), ...], HLO text of the
    compiled decode step if ``want_hlo`` else None)."""
    fresh_p = registry.init_cache(cfg, 1, max_seq)
    pool_p = registry.init_cache(cfg, n_slots, max_seq)
    fresh = sharding.tree_values(fresh_p)
    pool = sharding.tree_values(pool_p)
    if shard is not None:
        fresh = jax.device_put(fresh, sharding.tree_shardings(fresh_p,
                                                              *shard))
        pool = jax.device_put(pool, sharding.tree_shardings(pool_p, *shard))

    def prefill_fn(p, c, tokens):
        with sharding.shard_ctx(shard):
            return registry.prefill(cfg, p, c, {"tokens": tokens})

    def step_fn(p, c, tokens):
        with sharding.shard_ctx(shard):
            return registry.decode_step(cfg, p, c, {"tokens": tokens})

    prefill = jax.jit(prefill_fn)
    scatter = jax.jit(functools.partial(registry.scatter_slots, cfg))
    pre = []
    for slot, r in enumerate(reqs):
        logits, sub = prefill(prefill_params, fresh,
                              jnp.asarray(r.prompt[None]))
        pre.append(logits[0])
        pool = scatter(pool, sub, jnp.asarray([slot]))
    # one fresh host array per step: a device array made from a numpy
    # buffer may alias it, so refilling one buffer would race the
    # asynchronously dispatched steps
    toks = np.zeros((n_decode, n_slots, 1), np.int32)
    for slot, r in enumerate(reqs):
        toks[:, slot, 0] = r.tokens[:n_decode]
    step, hlo = jax.jit(step_fn), None
    if want_hlo:
        step = step.lower(params, pool, jnp.asarray(toks[0])).compile()
        hlo = step.as_text()
    dec = []
    for t in range(n_decode):
        logits, pool = step(params, pool, jnp.asarray(toks[t]))
        dec.append(logits[:len(reqs), 0])
    dec = jnp.stack(dec, axis=1)                     # (reqs, n_decode, V)
    return [(pre[i], dec[i]) for i in range(len(reqs))], hlo


def compare_logits(a, b, reqs):
    """Max |a - b| over every compared position, the largest |b|, and
    how many of the requests' emitted tokens are b's argmax."""
    diff = scale = 0.0
    agree = total = 0
    for (pa, da), (pb, db), r in zip(a, b, reqs):
        diff = max(diff, float(jnp.max(jnp.abs(pa - pb))),
                   float(jnp.max(jnp.abs(da - db))))
        scale = max(scale, float(jnp.max(jnp.abs(pb))),
                    float(jnp.max(jnp.abs(db))))
        want = [int(jnp.argmax(pb[-1]))] + [int(t) for t in
                                            jnp.argmax(db, axis=-1)]
        got = r.tokens[:len(want)]
        agree += sum(int(x == y) for x, y in zip(want, got))
        total += len(want)
    return diff, scale, agree, total


def check_reference(engine: Engine, params, greedy,
                    n_decode: int = N_DECODE):
    """Replay ``greedy`` requests through the served decode path and the
    XLA f32 reference on ``params``, the f32 weights the engine was
    built from (the engine's own tree may hold its projections at the
    compute dtype).  Returns a dict of the comparison."""
    n_slots, max_seq = engine.ecfg.n_slots, engine.ecfg.max_seq
    served, hlo = replay_logits(engine.cfg, engine.params,
                                engine.prefill_params, greedy, n_slots,
                                max_seq, n_decode, want_hlo=True)
    ref_cfg = dataclasses.replace(engine.cfg, step_impl="xla",
                                  dtype="float32", state_dtype="f32",
                                  weight_dtype="f32")
    with jax.default_matmul_precision("highest"):
        ref, _ = replay_logits(ref_cfg, params, params, greedy, n_slots,
                               max_seq, n_decode)
    diff, scale, agree, total = compare_logits(served, ref, greedy)
    return {"max_logit_diff": diff, "ref_logit_scale": scale,
            "agree": agree, "total": total,
            "tpu_custom_call": "tpu_custom_call" in hlo}


def greedy_pair(reqs):
    """One greedy request of each prompt length (the first two greedy
    requests if there is only one length)."""
    greedy = [r for r in reqs if r.params.temperature == 0.0]
    by_len = {}
    for r in greedy:
        by_len.setdefault(r.prompt.size, r)
    pair = list(by_len.values())[:2]
    return pair if len(pair) == 2 else greedy[:2]


def init_params(cfg, seed: int):
    params = jax.jit(lambda k: sharding.tree_values(
        registry.init_params(cfg, k)))(jax.random.key(seed))
    return jax.block_until_ready(params)


def one_chip(args) -> None:
    cfg = configs.get_config(ARCH)
    t0 = time.perf_counter()
    params = init_params(cfg, args.seed)
    log(f"built {ARCH}: {cfg.n_params() / 1e9:.3f} B params, "
        f"{time.perf_counter() - t0:.1f} s (set-up)")
    requests = make_requests(cfg.vocab, args.seed)
    ecfg = EngineConfig(n_slots=N_SLOTS,
                        max_seq=max(PROMPT_LENS) + MAX_NEW, seed=args.seed)
    engine = Engine(cfg, params, ecfg)
    path = mamba_lm.decode_path(engine.cfg, engine.params, engine.pool.cache)
    log(f"decode path: {path}")
    t0 = time.perf_counter()
    warm = {p.size: (p, dataclasses.replace(sp, max_new=2))
            for p, sp in requests}
    serve(engine, list(warm.values()))
    log(f"compile seconds (one short request per prompt length, set-up): "
        f"{time.perf_counter() - t0:.1f}")
    t0 = time.perf_counter()
    reqs = serve(engine, requests)
    run_s = time.perf_counter() - t0
    check_budgets(reqs)
    log(f"run seconds (8 requests, set-up information, not a device "
        f"metric): {run_s:.2f}; {sum(len(r.tokens) for r in reqs)} tokens")
    res = check_reference(engine, params, greedy_pair(reqs))
    if path == "xla":
        log("decode path is the XLA step: no Pallas kernel to check")
    else:
        log(f"tpu_custom_call in the served decode step: "
            f"{res['tpu_custom_call']}")
        if not res["tpu_custom_call"]:
            raise AssertionError(f"{path} decode step has no Pallas kernel")
    log(f"max |logit - reference|: {res['max_logit_diff']!r} "
        f"(reference max |logit| {res['ref_logit_scale']!r}, "
        f"tolerance {LOGIT_RTOL} of it)")
    log(f"greedy agreement with the reference: {res['agree']}/"
        f"{res['total']}")
    if res["max_logit_diff"] > LOGIT_RTOL * res["ref_logit_scale"]:
        raise AssertionError("served logits outside the tolerance")
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    log(f"peak_bytes_in_use: {peak}")


def four_chips(args) -> None:
    from repro.launch.mesh import make_serving_mesh
    if len(jax.devices()) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, have "
                           f"{len(jax.devices())}")
    cfg = configs.get_config(ARCH)
    params = init_params(cfg, args.seed)
    requests = make_requests(cfg.vocab, args.seed)
    ecfg = EngineConfig(n_slots=N_SLOTS,
                        max_seq=max(PROMPT_LENS) + MAX_NEW, seed=args.seed)
    res = compare_sharded(cfg, params, requests, ecfg, make_serving_mesh(4))
    log(f"sharded vs one device: {res['same_streams']}/{len(requests)} "
        f"identical streams, greedy token agreement "
        f"{res['greedy_agree']}/{res['greedy_total']}, max |logit diff| "
        f"{res['max_logit_diff']!r} (one-device max |logit| "
        f"{res['logit_scale']!r}, tolerance {LOGIT_RTOL} of it)")
    if res["max_logit_diff"] > LOGIT_RTOL * res["logit_scale"]:
        raise AssertionError("sharded logits outside the tolerance")


def compare_sharded(cfg, params, requests, ecfg, mesh):
    """Serve ``requests`` on one device and on ``mesh``; compare the
    streams and the replayed logits of the greedy pair."""
    t0 = time.perf_counter()
    single = Engine(cfg, params, ecfg)
    ref = serve(single, requests)
    sharded = Engine(cfg, params, dataclasses.replace(ecfg, mesh=mesh))
    got = serve(sharded, requests)
    log(f"served on both ({time.perf_counter() - t0:.1f} s, set-up)")
    check_budgets(ref)
    check_budgets(got)
    greedy = [(a, b) for a, b in zip(ref, got)
              if a.params.temperature == 0.0]
    pair = greedy_pair(ref)
    n_slots, max_seq = ecfg.n_slots, ecfg.max_seq
    one, _ = replay_logits(single.cfg, single.params, single.prefill_params,
                           pair, n_slots, max_seq, N_DECODE)
    tp, _ = replay_logits(sharded.cfg, sharded.params,
                          sharded.prefill_params, pair, n_slots, max_seq,
                          N_DECODE, shard=(mesh, sharding.ShardingRules()))
    diff, scale, _, _ = compare_logits(tp, one, pair)
    return {
        "same_streams": sum(a.tokens == b.tokens for a, b in zip(ref, got)),
        "greedy_agree": sum(x == y for a, b in greedy
                            for x, y in zip(a.tokens, b.tokens)),
        "greedy_total": sum(len(a.tokens) for a, _ in greedy),
        "max_logit_diff": diff,
        "logit_scale": scale,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX's first device is {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.launch import compile_cache
    log(f"device: {dev.device_kind} x{len(jax.devices())}; compile cache "
        f"{compile_cache.enable()}")
    (four_chips if args.chips == 4 else one_chip)(args)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
