#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python bench/run.py --workload W --seed N --seconds S --trace 0|1
    python bench/run.py --workload W --seed N --seconds S --sweep 4,6,8

The cell (``BENCHMARK.json``: ``workloads``) names a configuration
(``bench/configs/``) and a traffic mix (``bench/traffic/``).  The run refuses any
device but a TPU whose ``device_kind`` is in ``bench/data/peaks.json``,
and fewer chips than the cell asks for.  Set-up makes the weights from
the seed on the device, builds the program's ``Engine`` and warms every
prompt length on the mix's ladder and the decode step; ``setup_s`` runs
from the start of this script to the window's first due request.  The
window lasts ``--seconds``; a jit trace or compile inside it ends the run
with no result.  Then the reference decides ``correct``
(``harness.check``), and the last line of standard output is the
result: the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics, read from a profiler trace of the window, with ``--trace 1``.

``--sweep`` runs the window at each listed rate of an open-loop cell
after one set-up, and prints one line per rate (the knee sweep); it
reports no metrics.  ``--keep-trace DIR`` also copies the raw trace of
a ``--trace 1`` run into DIR.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))

#: the persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rates (requests/s) to sweep")
    ap.add_argument("--keep-trace", default=None)
    return ap.parse_args(argv)


def device_or_refuse(chips: int):
    """The first device, when it is a TPU of a known kind and there are
    at least ``chips`` of them; else RunError."""
    import jax

    from harness import work
    from harness.cell import RunError
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise RunError(f"no TPU: JAX's first device is {d.platform!r}")
    work.peak(d.device_kind)
    if len(devs) < chips:
        raise RunError(f"the cell asks for {chips} chips, JAX has "
                       f"{len(devs)}")
    return d


def enable_cache() -> None:
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # no eviction: eviction reads an access-time file beside every entry,
    # and a cache directory restored without them refuses every write
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    args = parse(argv)
    from harness import cell as cell_lib
    from harness import spec
    from harness.cell import RunError
    bm = spec.load_benchmark()
    wl = spec.workload(bm, args.workload)
    try:
        dev = device_or_refuse(wl["chips"])
    except (RunError, KeyError) as e:
        log(f"refused: {e}")
        return 2
    enable_cache()
    log(f"set-up: imports and device {time.perf_counter() - T_START:.2f} s")
    c = cell_lib.Cell.load(bm, wl)
    if args.sweep:
        rates = [float(x) for x in args.sweep.split(",")]
        for line in cell_lib.sweep(c, args.seed, args.seconds, rates,
                                   T_START):
            print(json.dumps(line), flush=True)
        return 0
    try:
        result, checks = cell_lib.run(c, args.seed, args.seconds,
                                      bool(args.trace), T_START, dev,
                                      keep_trace=args.keep_trace)
    except RunError as e:
        log(f"no result: {e}")
        return 3
    for name, v in checks.items():
        info = f"; {v['info']}" if "info" in v else ""
        print(f"[bench] check {name}: {v['value']!r} (limit "
              f"{v['limit']!r}){info}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
