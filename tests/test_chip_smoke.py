"""chip_smoke.py's phases at smoke size on the CPU.

The script refuses to run without a TPU; its serving, budget and
comparison functions run here on a smoke_variant of mamba-1.4b, so its
control flow stays covered without a chip.
"""
import dataclasses
import importlib.util
import json
import os

import pytest

from repro import configs
from repro.runtime.engine import Engine, EngineConfig
from _multidevice import run8

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

LENS = (8, 16)
MAX_NEW = cs.N_DECODE + 2


def _smoke_cfg(**over):
    return dataclasses.replace(
        configs.smoke_variant(configs.get_config(cs.ARCH)), **over)


@pytest.mark.parametrize("dtype,step_impl", [("bfloat16", "fused"),
                                             ("float32", "megakernel")])
def test_serving_and_reference_phases(dtype, step_impl):
    """Serve seeded mixed greedy/sampled requests, check budgets, and
    compare the greedy pair's served logits with the XLA f32
    reference — the one-chip run's phases at smoke size."""
    cfg = _smoke_cfg(dtype=dtype)
    params = cs.init_params(cfg, 0)
    requests = cs.make_requests(cfg.vocab, 0, n=4, lens=LENS,
                                max_new=MAX_NEW)
    assert [sp.temperature for _, sp in requests] == [0.0, 0.8, 0.0, 0.8]
    engine = Engine(cfg, params, EngineConfig(
        n_slots=4, max_seq=max(LENS) + MAX_NEW, step_impl=step_impl))
    reqs = cs.serve(engine, requests)
    cs.check_budgets(reqs)
    pair = cs.greedy_pair(reqs)
    assert sorted(r.prompt.size for r in pair) == list(LENS)
    res = cs.check_reference(engine, params, pair)
    assert res["max_logit_diff"] <= cs.LOGIT_RTOL * res["ref_logit_scale"]
    assert res["agree"] == res["total"] == 2 * (cs.N_DECODE + 1)
    if dtype == "float32":
        assert res["max_logit_diff"] < 1e-5


def test_check_budgets_rejects_a_short_stream():
    cfg = _smoke_cfg()
    engine = Engine(cfg, cs.init_params(cfg, 0),
                    EngineConfig(n_slots=2, max_seq=32))
    (req,) = cs.serve(engine, cs.make_requests(cfg.vocab, 0, n=1,
                                               lens=(4,), max_new=3))
    cs.check_budgets([req])
    req.tokens.pop()
    with pytest.raises(AssertionError, match="2 of 3 tokens"):
        cs.check_budgets([req])


def test_refuses_without_a_tpu(capsys):
    assert cs.main([]) == 1
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


def test_sharded_comparison_phase():
    """The --chips 4 comparison on four virtual CPU devices."""
    out = run8(f"""
        import json, sys
        sys.path.insert(0, {ROOT!r})
        import chip_smoke as cs
        from repro import configs
        from repro.launch.mesh import make_serving_mesh
        from repro.runtime.engine import EngineConfig
        cfg = configs.smoke_variant(configs.get_config(cs.ARCH))
        reqs = cs.make_requests(cfg.vocab, 0, n=4, lens={LENS!r},
                                max_new={MAX_NEW})
        res = cs.compare_sharded(
            cfg, cs.init_params(cfg, 0), reqs,
            EngineConfig(n_slots=4, max_seq={max(LENS) + MAX_NEW}),
            make_serving_mesh(4))
        print(json.dumps(res))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["same_streams"] == 4
    assert res["greedy_agree"] == res["greedy_total"] == 2 * MAX_NEW
    assert res["max_logit_diff"] < 1e-4
