"""Pallas TPU kernel: causal depthwise conv1d (Mamba short conv, k=4).

MARCA executes this with the CONV instruction on the same PE arrays.  On TPU
it is another element-wise-class op (depthwise = no channel reduction), so it
belongs on the VPU.  The (k-1)-sample history is carried across sequence
blocks in a VMEM scratch — the same inter-operation buffer-residency idea as
the scan kernel's hidden state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import backend


def _conv_kernel(x_ref, w_ref, b_ref, xprev_ref, y_ref, tail_ref, hist,
                 *, bl: int, k: int, has_bias: bool):
    l_idx = pl.program_id(2)

    @pl.when(l_idx == 0)
    def _init():
        hist[...] = xprev_ref[0].astype(jnp.float32)   # (k-1, BD)

    x = x_ref[0].astype(jnp.float32)                   # (BL, BD)
    w = w_ref[...].astype(jnp.float32)                 # (k, BD)
    xp = jnp.concatenate([hist[...], x], axis=0)       # (BL+k-1, BD)
    y = jnp.zeros_like(x)
    for i in range(k):
        y = y + xp[i:i + bl, :] * w[i][None, :]
    if has_bias:
        y = y + b_ref[0].astype(jnp.float32)[None, :]
    y_ref[0] = y.astype(y_ref.dtype)
    hist[...] = xp[bl:, :]
    tail_ref[0] = xp[bl:, :].astype(tail_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "block_l",
                                             "interpret"))
def _conv_padded(x, w, b, x_prev, block_d: int, block_l: int,
                 interpret: bool | None):
    bsz, L, d = x.shape
    k = w.shape[0]
    has_bias = b is not None
    grid = (bsz, d // block_d, L // block_l)
    in_specs = [
        pl.BlockSpec((1, block_l, block_d), lambda bb, dd, ll: (bb, ll, dd)),
        pl.BlockSpec((k, block_d), lambda bb, dd, ll: (0, dd)),
    ]
    args = [x, w]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, block_d), lambda bb, dd, ll: (0, dd)))
        args.append(b.reshape(1, -1))
    else:
        in_specs.append(pl.BlockSpec((1, 1), lambda bb, dd, ll: (0, 0)))
        args.append(jnp.zeros((1, 1), jnp.float32))
    in_specs.append(
        pl.BlockSpec((1, k - 1, block_d), lambda bb, dd, ll: (bb, 0, dd)))
    args.append(x_prev)

    out_shapes = (
        jax.ShapeDtypeStruct((bsz, L, d), x.dtype),
        jax.ShapeDtypeStruct((bsz, k - 1, d), x.dtype),
    )
    out_specs = (
        pl.BlockSpec((1, block_l, block_d), lambda bb, dd, ll: (bb, ll, dd)),
        pl.BlockSpec((1, k - 1, block_d), lambda bb, dd, ll: (bb, 0, dd)),
    )
    return pl.pallas_call(
        functools.partial(_conv_kernel, bl=block_l, k=k, has_bias=has_bias),
        out_shape=out_shapes,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((k - 1, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=backend.resolve_interpret(interpret),
        name="marca_causal_conv1d",
    )(*args)


def causal_conv1d(x, w, b=None, x_prev=None, block_d: int = 256,
                  block_l: int = 256, interpret: bool | None = None):
    """x (b, L, d); w (k, d); b (d,)|None; x_prev (b, k-1, d)|None.

    Returns (y (b, L, d), new_state (b, k-1, d)) matching
    kernels.ref.causal_conv1d.
    """
    bsz, L, d = x.shape
    k = w.shape[0]
    block_d = min(block_d, d)
    block_l = min(block_l, L)
    pad_l = (-L) % block_l
    pad_d = (-d) % block_d
    xp = jnp.pad(x, ((0, 0), (0, pad_l), (0, pad_d)))
    wp = jnp.pad(w, ((0, 0), (0, pad_d)))
    bp = None if b is None else jnp.pad(b, (0, pad_d))
    if x_prev is None:
        x_prev = jnp.zeros((bsz, k - 1, d), x.dtype)
    xprev_p = jnp.pad(x_prev, ((0, 0), (0, 0), (0, pad_d)))
    y, tail = _conv_padded(xp, wp, bp, xprev_p, block_d=block_d,
                           block_l=block_l, interpret=interpret)
    y = y[:, :L, :d]
    # new state = last k-1 *true* inputs (padding-safe reconstruction)
    full = jnp.concatenate([x_prev, x], axis=1)
    new_state = full[:, full.shape[1] - (k - 1):, :]
    return y, new_state
