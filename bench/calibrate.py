#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's and the control's.

    python bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 10 \
        [--control-seeds 1,2,3] [--controls int8w,int8ws]

One process, one chip.  For each seed: the cell's set-up, a window of
``--seconds`` at the cell's own load and its drain, then the check's
sample of greedy requests and the reference's gap for each served token
(the program's reading, as a benchmark run reads it).  For each control
seed, each control replays the same prompts and served tokens through
the program with its int8 path switched on (``int8w``: int8 weights, the
control the limits are set against; ``int8ws``: int8 weights and int8
state) and reads the reference's gap for the token it puts first; the
served path replayed the same way (``program_replayed``) is the
witness that the replay itself reads as the window did.  One JSON line
per seed and reading; ``PERF.md`` gives the readings each limit was set
from.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

CONTROLS = {"int8w": ("int8", "f32"), "int8ws": ("int8", "int8")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default="int8w")
    args = ap.parse_args(argv)
    import run as run_mod
    from harness import cell as cell_lib
    from harness import check, control, serve, spec, traffic
    from harness.cell import RunError
    bm = spec.load_benchmark()
    wl = spec.workload(bm, args.workload)
    try:
        run_mod.device_or_refuse(wl["chips"])
    except (RunError, KeyError) as e:
        print(f"[calibrate] refused: {e}", file=sys.stderr)
        return 2
    run_mod.enable_cache()
    c = cell_lib.Cell.load(bm, wl)
    ref = spec.reference(c.conf["reference"])
    ctl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        w, engine = cell_lib.setup(c, seed)
        src = traffic.source(c.mix, seed, args.seconds,
                             c.dm.vocab_real)
        win = serve.window(engine, src, args.seconds, n_queued=c.n_queued)
        serve.drain(engine, win)
        items = check.sample(win, seed)
        ecfg, cfg = engine.ecfg, engine.cfg
        faults = serve.budget_faults(win, c.dm.vocab)
        del engine
        gc.collect()
        if not items:
            print(json.dumps({"workload": c.name, "seed": seed,
                              "reading": "program", "error":
                              "no greedy request finished"}), flush=True)
            continue
        logits, served, mask = check.reference_logits(
            ref, c.dm, w, items, c.max_seq, c.mix["max_new"]["max"])
        got = control.gaps_of(logits, mask, served)
        print(json.dumps({"workload": c.name, "seed": seed,
                          "reading": "program", **got,
                          "budget_faults": faults,
                          "tokens": [len(it.tokens) for it in items],
                          "seconds": time.perf_counter() - t0}), flush=True)
        if seed not in ctl_seeds:
            continue
        slots = [1 + (b * (ecfg.n_slots - 1)) // len(items)
                 for b in range(len(items))]
        for name in [x for x in args.controls.split(",") if x]:
            wd, sd = CONTROLS[name]
            ce = control.control_engine(cfg, w, ecfg, wd, sd)
            toks = control.replay_tokens(ce, items, slots)
            del ce
            gc.collect()
            padded = served.at[:, :].set(0)
            padded = padded.at[:toks.shape[0], :toks.shape[1]].set(toks)
            got = control.gaps_of(logits, mask, padded)
            print(json.dumps({"workload": c.name, "seed": seed,
                              "reading": name, **got}), flush=True)
        base = control.control_engine(cfg, w, ecfg, cfg.weight_dtype,
                                      cfg.state_dtype)
        toks = control.replay_tokens(base, items, slots)
        del base
        gc.collect()
        padded = served.at[:, :].set(0)
        padded = padded.at[:toks.shape[0], :toks.shape[1]].set(toks)
        got = control.gaps_of(logits, mask, padded)
        print(json.dumps({"workload": c.name, "seed": seed,
                          "reading": "program_replayed", **got}), flush=True)
        del logits
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
