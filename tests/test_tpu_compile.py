"""Compiles for a TPU v5e that is described, not attached.

The decode kernels and the decode step "auto" resolves to are compiled
by the TPU compiler at published widths (mamba-1.4b, and mamba-130m
where "auto" picks the megakernel) with an 8-slot pool.  Interpret-mode
tests cannot see what this catches: blocks the (8, 128) tiling refuses,
ops Mosaic cannot lower, kernels that overrun VMEM.  Nothing runs, so
nothing here says anything about results or times.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and under pytest-xdist every
worker imports this file.  The persistent compilation cache is off
around these compiles (an entry compiled for a described chip cannot be
read back without one).  Code that asks which backend it runs on is
steered to its TPU branch by patching ``kernels.backend.on_tpu`` and
making the described chip the default device.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import state_quant
from repro.kernels import backend
from repro.kernels import decode_step as dsk
from repro.models import mamba, mamba_lm, registry
from repro.parallel import sharding

SLOTS = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def chip(topo, monkeypatch):
    """One described chip, with the code's backend checks steered to it.
    Shapes are built off it; lowering runs under ``on(chip)``."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    return topo.devices[0]


def on(chip):
    """The described chip as the default device: what the kernels'
    VMEM budget (``pltpu.get_tpu_info``) reads."""
    return jax.default_device(chip)


def _sds(tree, where):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=where),
        tree)


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("state_dtype,weight_dtype", [
    ("f32", "f32"), ("int8", "f32"), ("f32", "int8")])
def test_fused_decode_kernel_compiles(chip, state_dtype, weight_dtype):
    cfg = configs.get_config("mamba-1.4b")
    d, n = cfg.d_inner, cfg.d_state
    where = SingleDeviceSharding(chip)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)

    a_dtype = jnp.int8 if weight_dtype == "int8" else jnp.float32
    a_scale = s((d,)) if weight_dtype == "int8" else None
    rows = (s((SLOTS, d)), s((SLOTS, d)), s((d, n), a_dtype),
            s((SLOTS, n)), s((SLOTS, n)), s((d,)), s((SLOTS, d)))
    if state_dtype == "int8":
        def step(h, scale, x, dt, A, B, C, D, z, a_s):
            return dsk.selective_state_step_q(
                h, scale, x, dt, A, B, C, D=D, z_t=z, a_scale=a_s)
        args = (s((SLOTS, d, n), jnp.int8),
                s((SLOTS, state_quant.n_groups(d)))) + rows + (a_scale,)
    else:
        def step(h, x, dt, A, B, C, D, z, a_s):
            return dsk.selective_state_step(h, x, dt, A, B, C, D=D, z_t=z,
                                            a_scale=a_s)
        args = (s((SLOTS, d, n)),) + rows + (a_scale,)
    with on(chip):
        assert "tpu_custom_call" in _hlo(step, *args)


@pytest.mark.parametrize("arch,state_dtype,weight_dtype,path", [
    ("mamba-1.4b", "f32", "f32", "fused"),
    ("mamba-1.4b", "int8", "int8", "fused"),
    ("mamba-130m", "f32", "f32", "megakernel"),
    ("mamba-130m", "int8", "f32", "megakernel"),
    ("mamba-130m", "f32", "int8", "megakernel"),
])
def test_auto_decode_step_compiles(chip, arch, state_dtype, weight_dtype,
                                   path):
    """The whole decode step "auto" serves at these widths compiles,
    and its Pallas kernel is in the program."""
    cfg = dataclasses.replace(configs.get_config(arch),
                              state_dtype=state_dtype,
                              weight_dtype=weight_dtype)
    where = SingleDeviceSharding(chip)
    p = _sds(sharding.tree_values(registry.abstract_params(cfg)), where)
    cache = _sds(sharding.tree_values(
        registry.abstract_cache(cfg, SLOTS, 256)), where)
    toks = jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32, sharding=where)
    with on(chip):
        assert mamba_lm.decode_path(cfg, p, cache) == path
        hlo = _hlo(lambda p, c, t: registry.decode_step(cfg, p, c,
                                                        {"tokens": t}),
                   p, cache, toks)
    assert "tpu_custom_call" in hlo


def _ops(hlo, op, dtype):
    """Result shapes of the ``op`` instructions of ``dtype`` in a
    compiled HLO text."""
    pat = rf"= {dtype}\[([\d,]*)\]\S* {op}\("
    return {tuple(int(x) for x in m.group(1).split(",") if x)
            for m in re.finditer(pat, hlo)}


@pytest.mark.parametrize("arch,slots,path", [
    ("mamba-1.4b", 64, "fused"),
    ("mamba-130m", 8, "megakernel"),
])
def test_precast_decode_step_compiles(chip, arch, slots, path):
    """The decode step over the engine's precast tree (bf16 projection
    stacks, ``registry.precast_params``) compiles at published widths;
    "auto" still picks the same path with the VMEM need read from the
    bf16 leaves; the program converts no weight stack or embedding
    table, and copies no weight stack that the f32 tree's step did not
    copy already."""
    cfg = configs.get_config(arch)
    where = SingleDeviceSharding(chip)
    f32 = sharding.tree_values(registry.abstract_params(cfg))
    cast = jax.eval_shape(lambda v: registry.precast_params(cfg, v)[0], f32)
    mixer = cast["layers"]["mixer"]
    weights = {tuple(cast["embed"]["tok"].shape)}
    for name in mamba.PROJECTIONS:
        assert mixer[name]["w"].dtype == jnp.bfloat16
        weights.add(tuple(mixer[name]["w"].shape))
        weights.add(tuple(mixer[name]["w"].shape[1:]))
    cache = _sds(sharding.tree_values(
        registry.abstract_cache(cfg, slots, 256)), where)
    toks = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=where)

    def step(p, c, t):
        return registry.decode_step(cfg, p, c, {"tokens": t})

    hlo = {}
    with on(chip):
        for name, tree in (("f32", f32), ("bf16", cast)):
            p = _sds(tree, where)
            assert mamba_lm.decode_path(cfg, p, cache) == path
            hlo[name] = _hlo(step, p, cache, toks)
    assert "tpu_custom_call" in hlo["bf16"]
    if path == "fused":
        # the control: the f32 tree's step casts its stacks in XLA (the
        # megakernel casts its f32 blocks in-kernel, out of HLO's sight)
        assert _ops(hlo["f32"], "convert", "bf16") & weights
    assert not _ops(hlo["bf16"], "convert", "bf16") & weights
    copied = {name: set.union(*(_ops(hlo[name], op, dt) & weights
                                for op in ("copy", "copy-done")
                                for dt in ("f32", "bf16")))
              for name in hlo}
    assert copied["bf16"] <= copied["f32"]
