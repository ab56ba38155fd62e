"""Support for the benchmark's CPU tests: a cell at smoke size, run
through the harness's own functions (the command itself refuses a
CPU)."""
import json
import pathlib
import sys
import time


BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

from harness import cell as cell_lib  # noqa: E402
from harness import spec  # noqa: E402

CONF = {
    "d_model": 64, "n_layer": 4, "vocab_size": 250,
    "padded_vocab_size": 256,
    "ssm_cfg_defaults": {"d_state": 16, "d_conv": 4, "expand": 2,
                         "dt_rank": 8},
    "reference": "mamba",
    "serve": {"arch": "mamba-130m", "smoke": True, "dtype": "float32",
              "weight_dtype": "f32", "state_dtype": "f32",
              "step_impl": "auto", "n_slots": 4, "sched_quantum": 8},
    # f32 on both sides: the served tokens are the reference's best up
    # to f32 rounding (gap 0); the limit is far above that and far below
    # what a wrong token reads at this size
    "checks": {"gap_mean": 1e-5},
}
MIX = {"kind": "bursty_open_loop", "calm_factor": 0.5, "burst_factor": 2.5,
       "burst_share": 0.25, "burst_mean_s": 0.5, "prompt_ladder": [8, 16],
       "prompt_shares": [0.5, 0.5], "queued_per_slot": 2,
       "max_new": {"dist": "lognormal", "median": 6, "sigma": 0.8,
                   "min": 3, "max": 16},
       "greedy_share": 0.5, "temperature": 0.8, "top_p": 0.95,
       "stop_ids": [0], "rate_per_s": 8.0, "trace_seconds": 0.4}
SEED = 2**31 + 17


class CpuAsChip:
    """Stands in for the device handle the command would pass."""
    platform = "cpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {}


def smoke_cell(kind="bursty_open_loop", per_layer=()):
    bm = spec.load_benchmark()
    e2e = [m for m in bm["end_to_end"]]
    if kind == "backlog":
        e2e = [m for m in e2e if "workloads" not in m]
    return cell_lib.Cell(
        name="smoke", conf=json.loads(json.dumps(CONF)),
        mix=dict(MIX, kind=kind), e2e=e2e,
        per_layer=[m for m in bm["per_layer"] if m["name"] in per_layer])


def run(cell, traced=False, seconds=1.0, seed=SEED):
    return cell_lib.run(cell, seed, seconds, traced, time.perf_counter(),
                        CpuAsChip())


def clear_jits():
    """Clear the engine's shared jit caches (around a test that patches
    what they trace)."""
    from repro.runtime import engine
    engine._jit_decode_sample.cache_clear()
    engine._jit_prefill_admit.cache_clear()


