"""Pallas TPU kernel: piecewise SiLU (MARCA SiLU-RCU mode).

The SiLU-RCU adds a range detector + constant unit to each PE and evaluates
a per-segment polynomial (paper eq. 3).  On the TPU VPU the range detector
is a chain of vector compares feeding selects, and the polynomial is two
FMAs -- everything stays on the 8x128 element-wise path, no divider and no
transcendental unit (the point of the paper's decomposition).
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


def _silu_kernel(x_ref, o_ref, *, variant: str):
    x = x_ref[...].astype(jnp.float32)
    if variant == "paper":
        y = approx.piecewise_silu_paper(x)
    else:
        y = approx.piecewise_silu(x)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("variant", "block_rows", "cols",
                                             "interpret"))
def piecewise_silu_2d(x, variant="ours", block_rows=_DEFAULT_ROWS,
                      cols=_DEFAULT_COLS, interpret=None):
    rows = x.shape[0]
    grid = (pl.cdiv(rows, block_rows),)
    return pl.pallas_call(
        functools.partial(_silu_kernel, variant=variant),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, cols), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((block_rows, cols), lambda r: (r, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=backend.resolve_interpret(interpret),
        name="marca_piecewise_silu",
    )(x)


def piecewise_silu(x, variant="ours", interpret=None):
    """Shape-polymorphic wrapper (flatten -> pad -> tile)."""
    n = x.size
    cols = _DEFAULT_COLS if n >= _DEFAULT_COLS else _LANES
    rows = -(-n // cols)
    pad = rows * cols - n
    flat = jnp.pad(x.reshape(-1), (0, pad))
    block_rows = min(_DEFAULT_ROWS, rows)
    y = piecewise_silu_2d(flat.reshape(rows, cols), variant=variant,
                          block_rows=block_rows, cols=cols,
                          interpret=interpret)
    return y.reshape(-1)[:n].reshape(x.shape)
