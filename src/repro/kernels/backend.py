"""Where the Pallas kernels run, and the TPU tiling they share.

``resolve_interpret`` is the one place a kernel wrapper's ``interpret``
argument is decided: ``None`` compiles the kernel when the default
backend is a TPU and runs it under the Pallas interpreter everywhere
else, so no kernel is interpreted on the chip it was written for and
every CPU test still runs the kernel bodies.

The tiling constants are the single source for the element-wise kernel
wrappers (fast_exp, piecewise_silu) and the decode-step blocks: a vreg
is SUBLANES x LANES 32-bit words, so 2D blocks are LANES-wide and a
slot (batch) block is SUBLANES rows or the whole array.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

#: VPU lane width — min last-dim tile for element-wise kernels
LANES = 128

#: VPU sublane count — min second-minor tile for 32-bit blocks
SUBLANES = 8

#: default 2D tile the flatten->pad->tile wrappers reshape to
DEFAULT_COLS = 1024
DEFAULT_ROWS = 256


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> interpret everywhere but on a TPU; a bool is kept."""
    return (not on_tpu()) if interpret is None else interpret


def vmem_budget_bytes() -> int:
    """VMEM a kernel may claim on the default device (TPU only): three
    quarters of one TensorCore's VMEM, the rest left to the compiler's
    own scratch."""
    return pltpu.get_tpu_info().vmem_capacity_bytes * 3 // 4


def block_bytes(shape, dtype) -> int:
    """VMEM bytes of one block: the minor dim padded to LANES and the
    second-minor to the dtype's sublane tile (SUBLANES rows of 32-bit
    words, so 16 rows of bf16 and 32 of int8)."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, rows, cols = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    sub = SUBLANES * max(1, 4 // itemsize)
    return (math.prod(lead) * -(-rows // sub) * sub
            * -(-cols // LANES) * LANES * itemsize)
