"""The engine holds the Mamba projection matrices at the compute dtype.

Every consumer of ``in_proj``/``x_proj``/``dt_proj``/``out_proj`` casts
the ``w`` to cfg.dtype before its matmul, so a bf16 copy made once when
the engine is built (``registry.precast_params``) must serve bitwise the
logits, states and tokens the caller's f32 tree serves, on every decode
path, while the steps lose their per-call whole-matrix casts.  The
caller's tree is neither changed nor donated, and engines that compute
at f32 or store int8 weights hold no copy.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _multidevice import run8
from repro import configs
from repro.models import mamba, registry
from repro.parallel import sharding
from repro.runtime.engine import Engine, EngineConfig
from repro.runtime.sampling import SamplingParams

PATHS = ("fused", "megakernel")
FAMILIES = ("mamba-130m", "jamba-v0.1-52b")


def _setup(arch="mamba-130m", **over):
    cfg = configs.smoke_variant(configs.get_config(arch))
    cfg = dataclasses.replace(cfg, **{"vocab": 256, "dtype": "bfloat16",
                                      **over})
    params = sharding.tree_values(
        registry.init_params(cfg, jax.random.key(0)))
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_seq", 40)
    return Engine(cfg, params, EngineConfig(**kw))


def _leaves(tree):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _is_projection(key):
    return any(f"['{n}']['w']" in key for n in mamba.PROJECTIONS)


def _bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    return all(x.dtype == y.dtype and np.array_equal(np.asarray(x),
                                                     np.asarray(y))
               for x, y in zip(la, lb))


@pytest.mark.parametrize("arch", FAMILIES)
@pytest.mark.parametrize("path", PATHS)
def test_logits_bitwise_from_precast_tree(arch, path):
    """Prefill and a teacher-forced run of pooled decode steps give the
    same logits and states bit for bit from the engine's tree as from
    the caller's f32 tree."""
    cfg, params = _setup(arch, step_impl=path)
    eng = _engine(cfg, params)
    assert eng.precast_bytes > 0
    assert eng.prefill_params is eng.params

    def prefill(p, c, t):
        return registry.prefill(cfg, p, c, {"tokens": t})

    def step(p, c, t):
        return registry.decode_step(cfg, p, c, {"tokens": t})

    prefill, step = jax.jit(prefill), jax.jit(step)
    prompt = jnp.asarray(np.arange(3, 12, dtype=np.int32)[None])
    assert _bitwise(prefill(eng.prefill_params, eng.pool.fresh, prompt),
                    prefill(params, eng.pool.fresh, prompt))
    ours = theirs = eng.pool.cache
    rng = np.random.default_rng(1)
    for _ in range(4):
        toks = jnp.asarray(rng.integers(0, cfg.vocab,
                                        (eng.pool.n_total, 1)), jnp.int32)
        lo, ours = step(eng.params, ours, toks)
        lt, theirs = step(params, theirs, toks)
        assert _bitwise((lo, ours), (lt, theirs))


@pytest.mark.parametrize("path", PATHS)
def test_token_streams_identical(path):
    """Greedy and sampled streams through the engine match an engine
    that serves the caller's f32 tree, token for token."""
    cfg, params = _setup(step_impl=path)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, size=int(n)).astype(np.int32)
               for n in (5, 7, 9, 6)]
    sps = [SamplingParams(max_new=8),
           SamplingParams(max_new=8, temperature=0.8, top_p=0.95, seed=7)]

    def serve(eng):
        reqs = [eng.submit(p, sps[i % 2]) for i, p in enumerate(prompts)]
        eng.run()
        return [r.tokens for r in reqs]

    ours = _engine(cfg, params)
    theirs = _engine(cfg, params)
    theirs.params = theirs.prefill_params = params
    assert serve(ours) == serve(theirs)


def test_which_leaves_are_precast():
    """The four projection matrices are held at bf16 and counted in
    ``precast_bytes``; every other leaf is the caller's own array."""
    cfg, params = _setup()
    eng = _engine(cfg, params)
    mine, theirs = _leaves(eng.params), _leaves(params)
    assert mine.keys() == theirs.keys()
    cast = [k for k in mine if _is_projection(k)]
    assert len(cast) == len(mamba.PROJECTIONS)
    for k, v in mine.items():
        if k in cast:
            assert v.dtype == jnp.bfloat16, k
            assert theirs[k].dtype == jnp.float32, k
        else:
            assert v is theirs[k], k
            assert v.dtype == jnp.float32, k
    L, d, di = cfg.n_layers, cfg.d_model, cfg.d_inner
    r, n = cfg.dt_rank, cfg.d_state
    per_layer = d * 2 * di + di * (r + 2 * n) + r * di + di * d
    assert eng.precast_bytes == L * per_layer * 2
    assert eng.precast_bytes == sum(mine[k].nbytes for k in cast)


@pytest.mark.parametrize("arch,want", [("mamba-1.4b", 2_529_165_312),
                                       ("mamba-130m", 179_306_496)])
def test_precast_bytes_at_published_widths(arch, want):
    """The bytes a bf16 engine holds at the compute dtype at the
    benchmark's published widths: 2 B for each matrix parameter."""
    cfg = configs.get_config(arch)
    f32 = sharding.tree_values(registry.abstract_params(cfg))
    cast = jax.eval_shape(lambda v: registry.precast_params(cfg, v)[0], f32)
    held = [leaf for leaf in jax.tree.leaves(cast)
            if leaf.dtype == jnp.bfloat16]
    assert len(held) == len(mamba.PROJECTIONS)
    assert sum(leaf.size * leaf.dtype.itemsize for leaf in held) == want


def test_caller_tree_untouched():
    """Building and serving changes no leaf of the caller's tree: same
    dtypes, same values, nothing donated."""
    cfg, params = _setup()
    before = jax.tree.map(np.array, params)
    eng = _engine(cfg, params)
    eng.submit(np.arange(1, 7, dtype=np.int32), max_new=4)
    eng.run()
    for (k, b), a in zip(_leaves(before).items(), jax.tree.leaves(params)):
        assert not a.is_deleted(), k
        assert a.dtype == b.dtype, k
        assert np.array_equal(np.asarray(a), b), k


def test_f32_compute_and_int8_weights_hold_no_copy():
    """Where the rule does not apply nothing is cast: an f32-compute
    engine serves the caller's tree itself, an int8-weight engine its
    quantized tree for decode and the caller's for prefill."""
    cfg, params = _setup(dtype="float32")
    eng = _engine(cfg, params)
    assert eng.precast_bytes == 0
    assert eng.params is params and eng.prefill_params is params
    cfg, params = _setup()
    eng = _engine(cfg, params, weight_dtype="int8")
    assert eng.precast_bytes == 0
    assert eng.prefill_params is params
    assert any(leaf.dtype == jnp.int8
               for leaf in jax.tree.leaves(eng.params))
    assert not any(leaf.dtype == jnp.bfloat16
                   for leaf in jax.tree.leaves(eng.params))


def _f32_converts(text, shapes):
    """Converts in an HLO or StableHLO text whose operand is an f32
    array of one of ``shapes``."""
    found = set()
    for m in re.finditer(r"convert\(f32\[([\d,]*)\]", text):
        found.add(tuple(int(x) for x in m.group(1).split(",") if x))
    for m in re.finditer(
            r"stablehlo\.convert\s+\S+\s*:\s*\(tensor<([\dx]*)xf32>\)", text):
        found.add(tuple(int(x) for x in m.group(1).split("x") if x))
    return found & set(shapes)


@pytest.mark.parametrize("path", PATHS)
def test_serving_programs_cast_no_weights(path):
    """The decode step (``_decode_fn``) and the prefill admit, lowered
    and compiled with the engine's trees, convert no f32 array of a
    projection's shape (per layer or stacked) or of the embedding
    table's; with the caller's f32 tree they do, so the check sees the
    casts it guards against."""
    cfg, params = _setup(step_impl=path)
    eng = _engine(cfg, params)
    shapes = {tuple(params["embed"]["tok"].shape)}
    for name in mamba.PROJECTIONS:
        w = params["layers"]["mixer"][name]["w"]
        shapes |= {tuple(w.shape), tuple(w.shape[1:])}
    toks = jnp.asarray(eng._next_tok)
    act = jnp.asarray(eng.pool.active_mask())
    sp = eng.pool.params.device()
    steps = jnp.zeros((eng.pool.n_total,), jnp.int32)
    prompt = jnp.asarray(np.arange(1, 9, dtype=np.int32)[None])

    def programs(p):
        dec = eng._decode.lower(p, eng.pool.cache, toks, act, sp, steps)
        pre = eng._prefill.lower(p, eng.pool.fresh, prompt, eng.pool.cache,
                                 jnp.asarray([0]), eng.pool.params.row(0),
                                 jnp.zeros((1,), jnp.int32))
        return [text for low in (dec, pre)
                for text in (low.as_text(), low.compile().as_text())]

    for text in programs(eng.params):
        assert not _f32_converts(text, shapes)
    assert any(_f32_converts(text, shapes) for text in programs(params))


def test_precast_keeps_mesh_shardings():
    """Under a tensor-parallel mesh the bf16 leaves are placed as the
    f32 ones are, from ``abstract_params``."""
    run8("""
    import dataclasses
    import jax
    from repro import configs
    from repro.launch import mesh as mesh_lib
    from repro.models import registry
    from repro.parallel import sharding
    from repro.runtime.engine import Engine, EngineConfig

    cfg = configs.smoke_variant(configs.get_config('mamba-130m'))
    cfg = dataclasses.replace(cfg, vocab=256, dtype='bfloat16')
    params = sharding.tree_values(
        registry.init_params(cfg, jax.random.key(0)))
    mesh = mesh_lib.make_serving_mesh(2)
    eng = Engine(cfg, params, EngineConfig(n_slots=2, max_seq=40,
                                           mesh=mesh))
    want = sharding.tree_shardings(registry.abstract_params(cfg), mesh,
                                   sharding.ShardingRules())
    n_cast = n_split = 0
    for got, w in zip(jax.tree.leaves(eng.params), jax.tree.leaves(want)):
        assert got.sharding.is_equivalent_to(w, got.ndim), (got.sharding, w)
        n_cast += got.dtype == jax.numpy.bfloat16
        n_split += got.dtype == jax.numpy.bfloat16 and any(
            s is not None for s in got.sharding.spec)
    assert n_cast == 4 and n_split > 0, (n_cast, n_split)
    eng.submit([1, 2, 3, 4], max_new=4)
    assert len(eng.run()[0].tokens) == 4
    """)
