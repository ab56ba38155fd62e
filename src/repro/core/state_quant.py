"""Quantized storage for pooled decode state (cfg.state_dtype).

MARCA's buffer-management insight — shrink the recurrent working set so
more of it lives close to the PEs — applied to the serving tier: the
slot pool holds one f32 ``(layers, d_inner, d_state)`` SSM state per
in-flight sequence, and slot count is bounded by device memory.
FastMamba/eMamba show these states tolerate low-precision storage with
per-tensor scales, so storing them int8 (or fp8) with f32 absmax scales
multiplies slot capacity ~4x while decode math stays f32: dequantize on
read, step in f32, requantize on write — the f32 state exists only
inside the step, never in HBM.

Scale layout
------------
Scales are symmetric-linear absmax (dequant is ``q * scale``), f32, kept
as ordinary cache-pytree leaves *next to* the quantized payload so every
slot operation (gather/scatter/mask, eviction's fresh-state reset) moves
payload and scale together — a freed slot can never leak a stale scale.

Granularity: per slot, per layer, per channel group of ``D_BLOCK``
channels (all ``d_state`` entries of a group share one scale).  For the
SSM ``h`` this matches the decode kernel's channel blocking, so the
fused step requantizes each grid cell locally with no cross-block
reduction; for xLSTM's matrix memory ``C`` the group is one head's
(dh, dh) block.

Scale dynamics
--------------
The per-step scale update is a decayed running absmax:

    amax_run' = max(amax(h_new), EMA_DECAY * amax_run)

Growth is tracked immediately (requantization never clips: the write
scale is >= the step's true absmax), shrinkage is tracked with a decay
so a transient near-zero state does not collapse the scale and destroy
resolution for the next step.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: storage dtypes accepted by cfg.state_dtype
STATE_DTYPES = ("f32", "bf16", "int8", "fp8")

#: channel-group size for SSM h scales; matches the fused decode
#: kernel's block_d so requantization is local to one grid cell
D_BLOCK = 512

#: decayed-running-absmax rate (see module docstring)
EMA_DECAY = 0.99

#: absmax floor — a slot whose state is exactly zero (fresh slot, first
#: step) still gets a positive, tiny scale so requant never divides by 0
EPS_AMAX = 1e-30


def is_quantized(state_dtype: str) -> bool:
    """True for the scale-carrying dtypes (int8/fp8); bf16 is a plain
    storage cast and f32 is the unquantized baseline."""
    if state_dtype not in STATE_DTYPES:
        raise KeyError(
            f"unknown state_dtype {state_dtype!r}; one of {STATE_DTYPES}")
    return state_dtype in ("int8", "fp8")


def storage_dtype(state_dtype: str):
    """jnp dtype the state payload is stored as."""
    if state_dtype not in STATE_DTYPES:
        raise KeyError(
            f"unknown state_dtype {state_dtype!r}; one of {STATE_DTYPES}")
    return {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8,
            "fp8": jnp.float8_e4m3fn}[state_dtype]


def qmax(state_dtype: str) -> float:
    """Largest representable code magnitude the absmax is mapped to."""
    return {"int8": 127.0, "fp8": 448.0}[state_dtype]


def n_groups(d: int) -> int:
    """Number of channel-scale groups for a d-channel state tensor."""
    return max(1, math.ceil(d / D_BLOCK))


def encode(x, state_dtype: str):
    """f32 values already divided by scale -> storage codes."""
    if state_dtype == "int8":
        return jnp.clip(jnp.round(x), -127.0, 127.0).astype(jnp.int8)
    return x.astype(jnp.float8_e4m3fn)


def update_scale(amax, prev_scale, state_dtype: str):
    """Decayed-running-absmax scale update (shared by the XLA path and
    the fused kernel so the two quantize identically up to float
    reassociation — payloads match to within one code).

    ``amax`` is this step's true absmax per group; ``prev_scale`` (or
    None) the scale the group was last stored with."""
    qm = qmax(state_dtype)
    if prev_scale is not None:
        amax = jnp.maximum(amax, EMA_DECAY * (prev_scale * qm))
    return jnp.maximum(amax, EPS_AMAX) / qm


# ---------------------------------------------------------------------------
# SSM h: (..., d, n) payload, (..., g) scales (g = n_groups(d))
# ---------------------------------------------------------------------------

def _group_parts(x, axis: int, size: int = D_BLOCK):
    """Static ``size``-wide slices of ``x`` along ``axis``: one per
    scale group (channels, or size 1 on the group axis of scales)."""
    d = x.shape[axis]
    return [jax.lax.slice_in_dim(x, i, min(i + size, d), axis=axis)
            for i in range(0, d, size)]


def quantize_h(h, state_dtype: str, prev_scale=None):
    """Quantize an SSM state (..., d, n) -> (payload, scale (..., g)).

    ``prev_scale`` feeds the decayed-running-absmax update; None means
    cold start (prefill of a fresh slot) and uses the step's absmax.
    Groups are static channel slices, never a reshape of the channel
    axis, which is what lets the megakernel body lower this on TPU."""
    parts = _group_parts(h.astype(jnp.float32), -2)
    amax = jnp.concatenate(
        [jnp.max(jnp.max(jnp.abs(x), axis=-1), axis=-1, keepdims=True)
         for x in parts], axis=-1)                           # (..., g)
    scale = update_scale(amax, prev_scale, state_dtype)
    codes = jnp.concatenate(
        [encode(x / s[..., None], state_dtype)
         for x, s in zip(parts, _group_parts(scale, -1, 1))], axis=-2)
    return codes, scale


def dequantize_h(q, scale):
    """Inverse of quantize_h (up to rounding): (..., d, n) f32."""
    return jnp.concatenate(
        [x.astype(jnp.float32) * s[..., None]
         for x, s in zip(_group_parts(q, -2), _group_parts(scale, -1, 1))],
        axis=-2)


# ---------------------------------------------------------------------------
# Matrix memory (xLSTM C): (..., dh, dh) payload, (..., dh) scales — one
# scale per matrix row.  Rows of C are written by different keys
# (C' = f (*) C + i (*) k (x) v), so row magnitudes span decades and a
# single per-matrix scale floors the quiet rows to zero; per-row scales
# keep the relative error uniform at ~dh f32 words per dh*dh payload.
# ---------------------------------------------------------------------------

def quantize_mat(x, state_dtype: str, prev_scale=None):
    """Quantize (..., r, c) -> (payload, scale (..., r))."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = update_scale(amax, prev_scale, state_dtype)
    return encode(xf / scale[..., None], state_dtype), scale


def dequantize_mat(q, scale):
    return q.astype(jnp.float32) * scale[..., None]
