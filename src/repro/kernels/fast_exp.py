"""Pallas TPU kernel: fast biased exponential (MARCA EXP-RCU mode).

The paper's EXP-RCU reconfigures the PE array so each PE does one FP
multiply, one FP add, then routes through the "exponential shift unit"
(Fig. 6).  On TPU the same decomposition maps onto the VPU: the multiply-add
is a vector FMA and the shift unit is an f32->i32 convert + bitcast, all
8x128-lane element-wise ops.  No transcendental unit is involved.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

from repro.core import approx

_LANES = backend.LANES
_DEFAULT_COLS = backend.DEFAULT_COLS
_DEFAULT_ROWS = backend.DEFAULT_ROWS


def _fast_exp_kernel(x_ref, o_ref, *, b_shift: float, c: float):
    # the bit-trick formula lives ONLY in core.approx; the kernel body is
    # just the block load/store around it
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = approx.fast_exp(x, b_shift, c).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("b_shift", "c", "block_rows",
                                             "cols", "interpret"))
def fast_exp_2d(x, b_shift=approx.OUR_EXP_B_SHIFT, c=approx.OUR_EXP_C,
                block_rows=_DEFAULT_ROWS, cols=_DEFAULT_COLS,
                interpret=None):
    """Element-wise biased exp over a 2D array (rows, cols)."""
    rows = x.shape[0]
    grid = (pl.cdiv(rows, block_rows),)
    return pl.pallas_call(
        functools.partial(_fast_exp_kernel, b_shift=b_shift, c=c),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, cols), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((block_rows, cols), lambda r: (r, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=backend.resolve_interpret(interpret),
        name="marca_fast_exp",
    )(x)


def fast_exp(x, b_shift=approx.OUR_EXP_B_SHIFT, c=approx.OUR_EXP_C,
             interpret=None):
    """Shape-polymorphic wrapper: flatten -> pad -> tile -> kernel -> unpad."""
    n = x.size
    cols = _DEFAULT_COLS if n >= _DEFAULT_COLS else _LANES
    rows = -(-n // cols)
    pad = rows * cols - n
    flat = jnp.pad(x.reshape(-1), (0, pad))
    block_rows = min(_DEFAULT_ROWS, rows)
    y = fast_exp_2d(flat.reshape(rows, cols), b_shift=float(b_shift),
                    c=float(c), block_rows=block_rows, cols=cols,
                    interpret=interpret)
    return y.reshape(-1)[:n].reshape(x.shape)
