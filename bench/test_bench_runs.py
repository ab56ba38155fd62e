"""The serve-and-measure loop at smoke size on the CPU, through the
harness's own functions (the command itself refuses a CPU): results,
per-layer readers, the zero-compile guard; and ``correct`` coming out
false when the timed path is broken underneath or the int8 control
takes its place."""
import json
import pathlib
import sys

import jax.numpy as jnp
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from cpu_smoke import (BENCH, CONF, SEED, clear_jits, run,  # noqa: E402
                       smoke_cell)
from harness import cell as cell_lib  # noqa: E402
from harness import check, control, serve, spec, traffic  # noqa: E402


@pytest.fixture
def fresh_jits():
    clear_jits()
    yield
    clear_jits()


def test_open_loop_run_is_correct_and_reports_its_metrics():
    res, checks = run(smoke_cell())
    assert res["correct"] is True, res
    assert res["attempted"] >= 4 and res["failed"] == 0
    assert set(res["metrics"]) == {"output_tok_s", "ttft_p95_ms",
                                   "tpot_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["gap_mean"]["value"] <= CONF["checks"]["gap_mean"]
    assert res["checks"]["budget_faults"]["value"] == 0


def test_backlog_traced_run_reads_its_per_layer_metrics():
    names = ("prefill_share.batch", "mfu.batch", "idle_share.batch")
    res, _ = run(smoke_cell("backlog", per_layer=names), traced=True)
    assert res["correct"] is True, res
    # no TPU plane in a CPU trace: the device's readers find nothing
    assert set(res["metrics"]) == {"prefill_share.batch", "mfu.batch"}
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_compile_inside_the_window_is_an_error(monkeypatch, fresh_jits):
    """Warm-up covers the ladder; a prompt length it skipped compiles in
    the window, and the run reports nothing."""
    monkeypatch.setattr(traffic, "ladder", lambda mix: [8])
    with pytest.raises(cell_lib.RunError, match="inside the window"):
        run(smoke_cell(), seed=5)


def test_warm_window_traces_nothing():
    from repro.runtime import sampling
    c = smoke_cell()
    _, engine = cell_lib.setup(c, 3)
    before = dict(sampling.TRACE_COUNTS)
    src = traffic.source(c.mix, 3, 1.0, c.dm.vocab_real)
    with cell_lib.CompileGuard() as guard:
        win = serve.window(engine, src, 1.0)
    assert guard.traces == 0 and guard.compiles == 0
    assert dict(sampling.TRACE_COUNTS) == before
    assert win.tokens > 0 and win.steps > 0


def test_the_command_refuses_a_cpu(capsys):
    import run as run_mod
    rc = run_mod.main(["--workload", "mamba-130m.gen-batch", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""


def _state_unchanged(monkeypatch):
    from repro.models import registry
    real = registry.decode_step

    def step(cfg, p, cache, batch):
        logits, _ = real(cfg, p, cache, batch)
        return logits, cache
    monkeypatch.setattr(registry, "decode_step", step)


def _half_batch(monkeypatch):
    from repro.models import registry
    real = registry.decode_step

    def step(cfg, p, cache, batch):
        logits, new = real(cfg, p, cache, batch)
        n = batch["tokens"].shape[0]
        keep = jnp.arange(n) < n // 2
        return logits, registry.mask_slots(cfg, cache, new, keep)
    monkeypatch.setattr(registry, "decode_step", step)


def _token_altered(monkeypatch):
    from repro.runtime import sampling
    real = sampling.sample

    def sample(logits, sp, step):
        tok = real(logits, sp, step)
        return jnp.where(step == 2, (tok + 1) % logits.shape[-1], tok)
    monkeypatch.setattr(sampling, "sample", sample)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch, fresh_jits):
    """One chip, so no exchange between chips to leave out."""
    fault(monkeypatch)
    res, checks = run(smoke_cell("backlog"))
    assert res["correct"] is False, checks
    assert checks["gap_mean"]["value"] > checks["gap_mean"]["limit"]


def test_the_int8_control_is_not_correct():
    """The control (the program with int8 weights and state) replayed over
    the served tokens reads a gap over the limit, where the served path
    replayed reads none.  At mamba-130m widths with 4 layers, served in
    f32 on the CPU: the served tokens are the reference's best exactly,
    and int8 moves a few of them (mean gaps 1e-4 to 2e-3 on three
    seeds)."""
    c = smoke_cell("backlog")
    c.conf = json.loads((BENCH / "configs" / "mamba-130m.json").read_text())
    c.conf["n_layer"] = 4
    c.conf["serve"].update(n_slots=4, dtype="float32")
    c.mix["prompt_ladder"] = [16, 32]
    limit = 5e-5
    w, engine = cell_lib.setup(c, SEED)
    src = traffic.source(c.mix, SEED, 1.0, c.dm.vocab_real)
    win = serve.window(engine, src, 1.0, n_queued=c.n_queued)
    items = check.sample(win, SEED)
    ref = spec.reference("mamba")
    logits, served, mask = check.reference_logits(
        ref, c.dm, w, items, c.max_seq, c.mix["max_new"]["max"])
    assert control.gaps_of(logits, mask, served)["gap_mean"] <= limit
    slots = [3, 2, 1, 0][:len(items)]

    def replayed(weight_dtype, state_dtype):
        eng = control.control_engine(engine.cfg, w, engine.ecfg,
                                     weight_dtype, state_dtype)
        toks = control.replay_tokens(eng, items, slots)
        padded = jnp.zeros_like(served).at[:toks.shape[0],
                                           :toks.shape[1]].set(toks)
        return control.gaps_of(logits, mask, padded)["gap_mean"]

    assert replayed("int8", "int8") > limit
    assert replayed("f32", "f32") <= limit


def test_reference_matches_the_program_in_f32():
    """The plain reference and the program's prefill agree to f32
    rounding at mamba-130m widths (3 layers) on the same weights."""
    import dataclasses

    import jax
    import numpy as np
    from repro import configs
    from repro.models import registry
    from repro.parallel import sharding

    from harness import weights
    conf = json.loads((BENCH / "configs" / "mamba-130m.json").read_text())
    conf["n_layer"] = 3
    dm = weights.dims(conf)
    cfg = dataclasses.replace(configs.get_config("mamba-130m"), n_layers=3,
                              dtype="float32", step_impl="xla")
    w = weights.make(dm, 5)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        1, dm.vocab_real, size=(2, 40)).astype(np.int32))
    cache = sharding.tree_values(registry.init_cache(cfg, 2, 64))
    with jax.default_matmul_precision("highest"):
        prog, _ = jax.jit(lambda p, t: registry.prefill(
            cfg, p, cache, {"tokens": t}))(w, toks)
    ref = spec.reference("mamba").logits_along(
        dm, w, toks, jnp.zeros((2,), jnp.int32), n_out=40)
    assert float(jnp.max(jnp.abs(ref))) > 1.0
    assert float(jnp.max(jnp.abs(prog - ref))) < 1e-4
