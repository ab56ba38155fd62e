"""The benchmark's pieces without a model run: the loader (every piece
found by name, BENCHMARK.json in its contract's shape), the traffic
generators, the ops and bytes yardstick against hand counts, and the
trace reduction (a made-up trace with known answers, and a sample of a
trace recorded on the chip)."""
import collections
import json
import math
import pathlib
import re
import sys

import jax
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import spec, trace, traffic, weights, work  # noqa: E402


# --- loader ----------------------------------------

BM = spec.load_benchmark()
METRIC_KEYS = {"name", "unit", "better", "source"}
E2E_KEYS = METRIC_KEYS | {"bound"}
PL_KEYS = METRIC_KEYS | {"layer", "moves", "workloads"}


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert BM["command"] == ["python3", "bench/run.py"]
    assert isinstance(BM["run_seconds"], int) and 1 <= BM["run_seconds"] <= 51


def _names():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BM[key]:
            yield key, entry["name"]
    for w in BM["workloads"]:
        yield "config", w["config"]
        yield "traffic", w["traffic"]
    for c in BM["configs"]:
        for r in c["reduced"]:
            yield "reduced", r


@pytest.mark.parametrize("kind,name", list(_names()))
def test_names_use_allowed_characters(kind, name):
    assert spec.NAME_RE.match(name), (kind, name)


@pytest.mark.parametrize("m", BM["end_to_end"] + BM["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(m):
    assert spec.UNIT_RE.match(m["unit"]), m["unit"]
    assert m["better"] in ("lower", "higher")
    if m in BM["end_to_end"]:
        assert set(m) - {"workloads"} == E2E_KEYS
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert set(m) == PL_KEYS
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in {e["name"] for e in BM["end_to_end"]}
        assert m["layer"] and "\n" not in m["layer"]
        assert spec.reader_path(m["name"]).exists()
        assert callable(spec.metric_reader(m["name"]))
    cells = {w["name"] for w in BM["workloads"]}
    assert set(m.get("workloads", cells)) <= cells


def test_unique_names_and_pairs():
    for key in ("configs", "workloads"):
        names = [e["name"] for e in BM[key]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_cell_reports_enough():
    names = {m["name"] for m in BM["end_to_end"]}
    assert "setup_s" in names
    for w in BM["workloads"]:
        e2e = [m["name"] for m in spec.metrics_for(BM, w["name"], False)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec.metrics_for(BM, w["name"], True)
        # a per-layer metric moves an end-to-end metric the cell reports
        for m in spec.metrics_for(BM, w["name"], True):
            assert m["moves"] in e2e, (w["name"], m["name"])


@pytest.mark.parametrize("w", BM["workloads"], ids=lambda w: w["name"])
def test_cell_pieces_load_by_name(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    conf = spec.config(BM, w["config"])
    mix = spec.traffic(w["traffic"])
    assert mix["kind"] in ("bursty_open_loop", "backlog")
    assert spec.reference(conf["reference"]).logits_along
    if mix["kind"] == "bursty_open_loop":
        assert mix["rate_per_s"] > 0


@pytest.mark.parametrize("c", BM["configs"], ids=lambda c: c["name"])
def test_config_entry(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert c["file"].startswith("bench/configs/")
    conf = json.loads((spec.ROOT / c["file"]).read_text())
    for key in ("source", "reference", "serve", "checks", "assumed"):
        assert key in conf
    assert conf["source"] == c["source"]
    assert any(w["config"] == c["name"] for w in BM["workloads"])


def test_every_file_is_named_by_the_benchmark():
    """Files of the data directories belong to an entry, so a new cell,
    mix or metric is a new file and a new entry."""
    configs = {pathlib.Path(c["file"]).name for c in BM["configs"]}
    assert {p.name for p in (BENCH / "configs").glob("*.json")} == configs
    mixes = {w["traffic"] + ".json" for w in BM["workloads"]}
    assert {p.name for p in (BENCH / "traffic").glob("*.json")} == mixes
    readers = {spec.reader_path(m["name"]).name for m in BM["per_layer"]}
    assert {p.name for p in (BENCH / "metrics").glob("*.py")} == readers


def test_a_new_metric_is_found_by_name(tmp_path, monkeypatch):
    """A metric named ``x.serve`` is read by ``metrics/x.serve.py`` when
    that file exists, else by ``metrics/x.py``."""
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "x.py").write_text("def read(ctx): return 1.0\n")
    (tmp_path / "metrics" / "x.serve.py").write_text(
        "def read(ctx): return 2.0\n")
    monkeypatch.setattr(spec, "BENCH", tmp_path)
    assert spec.metric_reader("x.batch")(None) == 1.0
    assert spec.metric_reader("x.serve")(None) == 2.0
    with pytest.raises(KeyError):
        spec.metric_reader("y.batch")


def test_benchmark_json_is_small():
    assert (spec.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


# --- traffic ---------------------------------------

VOCAB = 50277
SEEDS = [0, 7, 2**31 + 12345, 2**33 + 1]


def _key(reqs):
    return [(r.due, r.prompt.tobytes(), r.max_new, r.temperature, r.top_p,
             r.seed) for r in reqs]


def _chat(seed, seconds=60.0, rate=6.0, mix=None):
    mix = spec.traffic("chat") if mix is None else mix
    return traffic.source(mix, seed, seconds, VOCAB, rate)


@pytest.mark.parametrize("seed", SEEDS)
def test_open_loop_deterministic_and_on_ladder(seed):
    mix = spec.traffic("chat")
    a, b = _chat(seed), _chat(seed)
    assert _key(a) == _key(b)
    assert _key(a) != _key(_chat(seed + 1))
    assert all(r.prompt.size in mix["prompt_ladder"] for r in a)
    assert all(0 < t < VOCAB for r in a for t in r.prompt[:64])
    assert all(mix["max_new"]["min"] <= r.max_new <= mix["max_new"]["max"]
               for r in a)
    dues = [r.due for r in a]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 60.0


def test_every_seed_sends_the_same_work():
    """Same multisets of lengths, budgets and greedy flags, and the same
    arrivals per episode: in another order per seed, or, with the mix's
    ``schedule_seed``, in one schedule for every seed, only the token
    ids and sampling seeds drawn from the run's seed."""
    def work(reqs):
        return (sorted(r.prompt.size for r in reqs),
                sorted(r.max_new for r in reqs),
                sorted(r.temperature for r in reqs))

    def schedule(reqs):
        return [(r.due, r.prompt.size, r.max_new, r.temperature)
                for r in reqs]
    mix = spec.traffic("chat")
    assert "schedule_seed" in mix
    runs = [_chat(s) for s in SEEDS]
    assert all(schedule(r) == schedule(runs[0]) for r in runs)
    assert len({r[0].prompt.tobytes() for r in runs}) == len(SEEDS)
    assert len({r[0].seed for r in runs}) == len(SEEDS)
    free = {k: v for k, v in mix.items() if k != "schedule_seed"}
    runs = [_chat(s, mix=free) for s in SEEDS]
    assert all(work(r) == work(runs[0]) for r in runs)
    assert any([r.prompt.size for r in runs[0]]
               != [r.prompt.size for r in other] for other in runs[1:])


def test_mean_rate_shares_and_bursts():
    mix = spec.traffic("chat")
    seconds, rate = 60.0, 6.0
    reqs = _chat(3, seconds, rate)
    assert len(reqs) == round(rate * seconds)
    counts = collections.Counter(r.prompt.size for r in reqs)
    for length, share in zip(mix["prompt_ladder"], mix["prompt_shares"]):
        assert abs(counts[length] / len(reqs) - share) < 0.01
    assert sum(r.temperature == 0 for r in reqs) == len(reqs) // 2
    # episodes: bursts cover burst_share of the time at burst_factor x rate
    eps = traffic._episodes(mix, traffic.rngs(mix, 3, 1)[0], seconds)
    burst = [(s, d) for s, d, f in eps if f == mix["burst_factor"]]
    assert abs(sum(d for _, d in burst) - 0.25 * seconds) < 1e-6
    assert abs(np.mean([d for _, d in burst]) - mix["burst_mean_s"]) < 0.5
    in_burst = sum(any(s <= r.due < s + d for s, d in burst) for r in reqs)
    assert abs(in_burst / len(reqs) - 0.25 * 2.5) < 0.02


def test_budgets_follow_the_distribution():
    mix = spec.traffic("chat")
    budgets = np.array([r.max_new for r in _chat(1, 300.0, 6.0)])
    assert abs(np.median(budgets) - mix["max_new"]["median"]) <= 2
    assert 150 <= budgets.mean() <= 190


BACKLOGS = sorted({w["traffic"] for w in spec.load_benchmark()["workloads"]
                   if spec.traffic(w["traffic"])["kind"] == "backlog"})


@pytest.mark.parametrize("name", BACKLOGS)
def test_backlog_blocks(name):
    mix = spec.traffic(name)
    a = traffic.source(mix, 5, 30.0, VOCAB)
    b = traffic.source(mix, 5, 30.0, VOCAB)
    first = [a.take() for _ in range(300)]
    assert _key(first) == _key([b.take() for _ in range(300)])
    block = first[:mix.get("block", 256)]
    counts = collections.Counter(r.prompt.size for r in block)
    assert set(counts) == set(mix["prompt_ladder"])
    assert max(counts.values()) - min(counts.values()) <= 1
    lo, hi = mix["max_new"]["min"], mix["max_new"]["max"]
    assert all(lo <= r.max_new <= hi for r in first)
    n_greedy = sum(r.temperature == 0 for r in block)
    assert n_greedy == round(mix["greedy_share"] * len(block))


def test_unknown_kind_and_bad_factors():
    with pytest.raises(ValueError):
        traffic.source({"kind": "nope"}, 0, 1.0, VOCAB, 1.0)
    mix = dict(spec.traffic("chat"), burst_factor=3.0)
    with pytest.raises(ValueError):
        traffic.source(mix, 0, 10.0, VOCAB, 1.0)
    no_rate = {k: v for k, v in spec.traffic("chat").items()
               if k != "rate_per_s"}
    with pytest.raises(ValueError):
        traffic.source(no_rate, 0, 10.0, VOCAB)
    # the mix's own rate where the caller gives none
    assert len(traffic.source(spec.traffic("chat"), 0, 10.0, VOCAB)) == \
        round(spec.traffic("chat")["rate_per_s"] * 10.0)


# --- work ------------------------------------------

def _dims(name):
    return weights.dims(json.loads(
        (BENCH / "configs" / f"{name}.json").read_text()))


PEAK = work.peak("TPU v5 lite")


def test_sizes_1_4b():
    dm = _dims("mamba-1.4b")
    assert (dm.d_model, dm.d_inner, dm.d_state, dm.dt_rank, dm.n_layer,
            dm.vocab) == (2048, 4096, 16, 128, 48, 50304)
    # in_proj 2048x8192, x_proj 4096x(128+32), dt_proj 128x4096,
    # out_proj 4096x2048
    assert work.matmul_params(dm) == (2048 * 8192 + 4096 * 160 + 128 * 4096
                                      + 4096 * 2048) == 26_345_472
    # conv taps + bias, dt bias, A, D, norm scale
    assert work.vector_params(dm) == (4096 * 4 + 4096 + 4096 + 4096 * 16
                                      + 4096 + 2048)


@pytest.mark.parametrize("name,params", [("mamba-1.4b", 1_372_227_584),
                                         ("mamba-130m", 129_153_792)])
def test_parameter_count_matches_the_weight_tree(name, params):
    dm = _dims(name)
    tree = jax.eval_shape(weights._init_fn(dm), jax.random.key(0))
    have = sum(math.prod(x.shape) for x in jax.tree.leaves(tree))
    assert have == params == (dm.n_layer * (work.matmul_params(dm)
                                            + work.vector_params(dm))
                              + dm.vocab * dm.d_model + dm.d_model)


def test_decode_step_1_4b_64_slots_by_hand():
    dm = _dims("mamba-1.4b")
    got = work.decode_step(dm, 64)
    weights_b = 48 * (26_345_472 * 2 + 96_256 * 4)          # bf16 + f32
    state_b = 2 * 64 * 48 * (4096 * 16 * 4 + 3 * 4096 * 2)  # h f32, conv bf16
    head_b = 50304 * 2048 * 4 + 64 * 50304 * 4               # f32 head, logits
    assert got.bytes == weights_b + state_b + head_b + 64 * 2048 * 4
    assert got.bytes == 4_734_746_624
    assert got.bound(PEAK) == "memory"
    assert got.seconds(PEAK) == pytest.approx(4_734_746_624 / 819e9)
    flops_tok = 48 * (2 * 26_345_472 + 2 * 4 * 4096 + 7 * 4096 * 16
                      + 10 * 4096 + 4 * 2048) + 2 * 2048 * 50304
    assert got.flops == 64 * flops_tok


def test_decode_step_130m_8_slots_by_hand():
    dm = _dims("mamba-130m")
    mm = 768 * 3072 + 1536 * 80 + 48 * 1536 + 1536 * 768
    vec = 1536 * 4 + 1536 + 1536 + 1536 * 16 + 1536 + 768
    got = work.decode_step(dm, 8)
    state_b = 2 * 8 * 24 * (1536 * 16 * 4 + 3 * 1536 * 2)
    head_b = 50304 * 768 * 4 + 8 * 50304 * 4
    assert got.bytes == (24 * (mm * 2 + vec * 4) + state_b + head_b
                         + 8 * 768 * 4)
    assert got.bound(PEAK) == "memory"


def test_state_bytes_1_4b_by_hand():
    dm = _dims("mamba-1.4b")
    # h (f32) and the conv tail (bf16), read and written, per slot
    assert work.state_bytes(dm, 64, "f32", "bf16") == \
        2 * 64 * 48 * (4096 * 16 * 4 + 3 * 4096 * 2)
    assert work.state_bytes(dm, 1, "int8", "bf16") == \
        2 * 48 * (4096 * 16 + 3 * 4096 * 2)


def test_prefill_and_model_flops():
    dm = _dims("mamba-1.4b")
    p = work.prefill(dm, 1024)
    assert p.bound(PEAK) == "compute"
    per_tok = 48 * (2 * 26_345_472 + 2 * 4 * 4096 + 7 * 4096 * 16
                    + 10 * 4096 + 4 * 2048)
    assert p.flops == 1024 * per_tok + 2 * 2048 * 50304
    assert work.model_flops(dm, 1000, 10) == (2 * 48 * 26_345_472 * 1010
                                              + 2 * 2048 * 50304 * 10)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peak("TPU v9 imaginary")
    assert PEAK["bf16_flops_per_s"] == 197e12
    assert PEAK["hbm_bytes_per_s"] == 819e9


# --- trace -----------------------------------------

MS = 1_000_000  # ns
RECORDED = {"trace_14b_chat.json.gz": "marca_decode_step",
            "trace_130m_gen_batch.json.gz": "marca_megakernel_mamba"}


def _op(name, kind="fusion"):
    return f"%{name} = f32[8,128]{{1,0}} {kind}(f32[8,128]{{1,0}} %p.1)"


def _planes():
    """Window [0, 100 ms]; two programs; one gap under bench.step and one
    under bench.step > PjitFunction."""
    ops = [(_op("fusion.1"), 0, 10 * MS),
           (_op("marca_decode_step.2", "custom-call"), 5 * MS, 10 * MS),
           (_op("while.3", "while"), 30 * MS, 20 * MS),
           (_op("fusion.4"), 90 * MS, 20 * MS)]
    mods = [("jit__decode_fn(7)", 0, 15 * MS),
            ("jit__fn(9)", 30 * MS, 20 * MS),
            ("jit__fn(11)", 90 * MS, 20 * MS)]
    host = [("bench.window", 0, 100 * MS), ("bench.step", 0, 29 * MS),
            ("bench.wait", 29 * MS, 1 * MS),
            ("bench.step", 30 * MS, 70 * MS),
            ("PjitFunction(_decode_fn)", 60 * MS, 10 * MS)]
    return [("/host:CPU", [("python3", host)]),
            ("/device:TPU:0", [(trace.MODULES_LINE, mods),
                               (trace.OPS_LINE, ops)])]


def _op_time(red, pattern):
    """Launches and seconds of the ops whose own name matches, over all
    programs (``module_ops`` is keyed ``program/op kind type``)."""
    n = s = 0
    for label, (c, t) in red.module_ops.items():
        if re.search(pattern, label.split("/", 1)[1].split(" ")[0]):
            n += c
            s += t
    return n, s


def test_busy_idle_and_times():
    planes = _planes()
    lo, hi = trace.span_bounds(planes, "bench.window")
    red = trace.reduce_planes(planes, lo, hi)
    # ops cover [0,15] + [30,50] + [90,100] inside the window: 45 ms
    assert red.window_s == pytest.approx(0.1)
    assert red.busy_s == pytest.approx(0.045)
    assert red.chips == 1
    assert red.module(r"^jit__decode_fn\(") == (1, pytest.approx(0.015))
    assert red.module(r"^jit__fn\(") == (2, pytest.approx(0.03))
    # the prefill is the _fn program that runs a while
    assert red.module(r"^jit__fn\(", with_op="while") == \
        (1, pytest.approx(0.02))
    assert _op_time(red, r"^marca_decode_step(\.\d+)?$") == \
        (1, pytest.approx(0.01))
    # gaps: [15,30] (midpoint 22.5, under bench.step), [50,90] (midpoint
    # 70: bench.step and the PjitFunction event ending at 70)
    assert sum(red.gaps.values()) == pytest.approx(0.055)
    assert red.gaps["bench.step"] == pytest.approx(0.015)
    assert red.gaps["bench.step > PjitFunction(_decode_fn)"] == \
        pytest.approx(0.04)
    out = trace.breakdown(red)
    assert out["device_ops"][0][0].startswith("jit__fn/while.3 while")
    assert out["idle_gaps"][0][0] == "bench.step > PjitFunction(_decode_fn)"


def test_parse_op():
    text = ("%marca_megakernel_mamba.1 = (bf16[8,1,768]{2,1,0:T(2,128)}, "
            "f32[24,8,1536,16]{3,2,1,0:T(8,128)}) custom-call(bf16[8,1,768]"
            "{2,1,0:T(2,128)} %copy.21), custom_call_target=\"tpu\"")
    name, kind, _ = trace.parse_op(text)
    assert (name, kind) == ("marca_megakernel_mamba.1", "custom-call")
    assert trace.parse_op("not hlo")[0] == "not hlo"


def test_chips_average_and_missing_span():
    planes = _planes()
    dev = planes[1]
    planes.append(("/device:TPU:1", [(trace.OPS_LINE,
                                      [(_op("fusion.9"), 0, 100 * MS)])]))
    red = trace.reduce_planes(planes, 0, 100 * MS)
    assert red.chips == 2
    assert red.busy_s == pytest.approx((0.045 + 0.1) / 2)
    with pytest.raises(KeyError):
        trace.span_bounds([dev], "bench.window")


@pytest.mark.parametrize("name,kernel", sorted(RECORDED.items()))
def test_recorded_trace(name, kernel):
    """Busy union and kernel time against a count on a 1 us grid."""
    planes = trace.load_saved(str(BENCH / "data" / name))
    lo, hi = trace.span_bounds(planes, "bench.traced")
    lo = max(lo, hi - 20 * MS)
    red = trace.reduce_planes(planes, lo, hi)
    ops = dict(dict(planes)["/device:TPU:0"])[trace.OPS_LINE]
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    k_ns = 0
    for text, st, du in ops:
        s, e = max(st, lo), min(st + du, hi)
        if e > s:
            grid[int((s - lo) // 1000):int(np.ceil((e - lo) / 1000))] = True
            if trace.parse_op(text)[0].startswith(kernel):
                k_ns += e - s
    assert red.busy_s == pytest.approx(grid.sum() * 1e-6, abs=2e-5)
    assert 0 < red.busy_s < red.window_s
    n, secs = _op_time(red, rf"^{kernel}(\.\d+)?$")
    assert n > 0 and secs == pytest.approx(k_ns * 1e-9)
    assert red.module(r"^jit__decode_fn\(")[0] >= 1
    # idle gaps of 0.1 ms or more, all of them labelled
    assert 0 <= sum(red.gaps.values()) <= red.window_s - red.busy_s + 1e-9


# --- the traced run's two parts --------------------------------------

class _Req:
    def __init__(self, t_admit=None, t_first=None):
        self.t_admit, self.t_first = t_admit, t_first
        self.tokens, self.finished = [], False


class _Engine:
    """Admits nothing and steps nothing: the window only submits."""

    def __init__(self):
        self._slot_req = []

    def submit(self, prompt, sp):
        return _Req()

    def step(self):
        return False


def _window_with_mark(monkeypatch, dues, earliest, latest):
    from harness import serve
    monkeypatch.setattr(serve, "sampling_params", lambda spec: None)
    src = [traffic.Req(index=i, due=d, prompt=np.ones(4, np.int32),
                       max_new=2, temperature=0.0, top_p=1.0, seed=0,
                       stop_ids=()) for i, d in enumerate(dues)]
    fired = []
    win = serve.window(_Engine(), src, 0.4,
                       mark=(earliest, latest, lambda: fired.append(1)))
    return win, fired


def test_the_profiler_starts_with_a_request(monkeypatch):
    """The mark waits past ``earliest`` for a turn that submits, so the
    traced part opens with an admission; with none it comes at
    ``latest``."""
    win, fired = _window_with_mark(monkeypatch, [0.05, 0.25], 0.1, 0.35)
    assert fired == [1]
    assert 0.25 <= win.t_mark - win.t0 < 0.3
    win, fired = _window_with_mark(monkeypatch, [0.05], 0.1, 0.2)
    assert fired == [1]
    assert 0.2 <= win.t_mark - win.t0 < 0.25


def test_host_readers_read_the_untraced_part():
    """Queue wait, admission share and MFU come from the part before the
    profiler started: a request due near the mark or after it, an
    admission after it and tokens after it do not count."""
    from harness import cell, serve
    conf = json.loads((BENCH / "configs" / "mamba-130m.json").read_text())

    def sent(due, t_admit, t_first):
        return serve.Sent(spec=None, req=_Req(t_admit, t_first), due=due,
                          submitted=due)
    reqs = ([sent(float(d), d + 0.1, d + 0.2) for d in range(20)]
            + [sent(21.0, 27.0, 27.5),   # due 4 s before the mark
               sent(26.0, 28.0, 28.5)])  # admitted after the mark
    win = serve.Window(t0=0.0, t_end=30.0, sent=reqs, tokens=9000, steps=1,
                       open_loop=True, t_mark=25.0, tokens_mark=1000)
    u = cell.Untraced(t0=0.0, t1=25.0, tokens=1000,
                      stats={"prefill_tokens": 500})
    c = cell.Cell(name="x", conf=conf, mix={}, e2e=[], per_layer=[])
    ctx = cell.Ctx(cell=c, win=win, untraced=u, trace=None, trace_stats={},
                   peak=PEAK, decode_path="megakernel")
    # due before 25 - 5 s: the twenty waits of 0.1 s
    assert spec.metric_reader("queue_wait_p95_ms.chat")(ctx) == \
        pytest.approx(100.0)
    # 20 admissions of 0.1 s, and the one from 27 s not at all
    assert spec.metric_reader("prefill_share.batch")(ctx) == \
        pytest.approx(100.0 * 2.0 / 25.0)
    flops = work.model_flops(c.dm, 500, 1000)
    assert spec.metric_reader("mfu.batch")(ctx) == \
        pytest.approx(100.0 * flops / 25.0 / PEAK["bf16_flops_per_s"])
