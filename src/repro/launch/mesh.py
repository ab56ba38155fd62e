"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis maps to
DCN, so the sharding rules keep parameters off it (DESIGN.md §4).

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  Every mesh has Auto axes: the models
place arrays through logical sharding constraints and let GSPMD
propagate the rest, which ``jax.make_mesh``'s default Explicit axes
would instead turn into type errors (e.g. on the embedding gather).
"""
from __future__ import annotations

import math

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)}; "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(launch/dryrun.py does this automatically)")
    return _auto_mesh(shape, axes, devices[:need])


def _auto_mesh(shape, axes, devices):
    return jax.make_mesh(
        shape, axes, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over however many devices exist (tests)."""
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(f"need {need} devices, have {len(devices)}")
    return _auto_mesh(shape, axes, devices[:need])


def make_serving_mesh(tp: int = 2, axis: str = "model"):
    """1-D tensor-parallel mesh for the serving engine
    (``EngineConfig.mesh``): ``tp`` devices on the "model" axis, so the
    default ShardingRules put stacked weights (ffn/heads/vocab) and the
    pool's TP-interior cache leaves on it, while slot (batch) axes stay
    replicated — admission/eviction scatters touch every shard locally.
    """
    if tp < 1:
        raise ValueError(f"tp must be >= 1; got {tp}")
    devices = jax.devices()
    if len(devices) < tp:
        raise RuntimeError(
            f"serving mesh ({axis}={tp}) needs {tp} devices, have "
            f"{len(devices)}; run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp} "
            "(CPU) or on a host with enough accelerators")
    return _auto_mesh((tp,), (axis,), devices[:tp])
