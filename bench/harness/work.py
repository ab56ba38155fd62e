"""Operations and bytes that a step of a configuration requires.

This is the roofline's yardstick, computed from the configuration's
sizes and not from the implementation, so a later change to the program
cannot move it.  Each weight is counted once per step, at the dtype the
step computes with (bf16 for the matrix weights, f32 for the tied
output head, whose logits are f32, and for the small per-channel
vectors); the recurrent state is read and written once, at its
configured dtype; the logits are written once.  A program that reads
more (f32 weights cast every step, a pool copied whole) reads more than
this, so its share stays under 100%.

``peak(kind)`` reads ``bench/data/peaks.json``, keyed by the device's
``device_kind``; an unknown device is an error.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parents[1] / "data" / "peaks.json"

BYTES = {"f32": 4, "float32": 4, "bf16": 2, "bfloat16": 2, "int8": 1,
         "fp8": 1}


def peak(kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in the peak table "
                       f"{sorted(table)}")
    return table[kind]


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, o):
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def __mul__(self, k):
        return Work(self.flops * k, self.bytes * k)

    def seconds(self, pk: dict) -> float:
        """The least time the chip could take: the larger bound."""
        return max(self.flops / pk["bf16_flops_per_s"],
                   self.bytes / pk["hbm_bytes_per_s"])

    def bound(self, pk: dict) -> str:
        return ("compute" if self.flops / pk["bf16_flops_per_s"]
                >= self.bytes / pk["hbm_bytes_per_s"] else "memory")


def matmul_params(dm) -> int:
    """Matrix weights of one layer: in_proj, x_proj, dt_proj, out_proj."""
    d, di, n, r = dm.d_model, dm.d_inner, dm.d_state, dm.dt_rank
    return d * 2 * di + di * (r + 2 * n) + r * di + di * d


def vector_params(dm) -> int:
    """Per-channel weights of one layer: conv (taps and bias), dt bias,
    A, D, the norm scale."""
    d, di, n, k = dm.d_model, dm.d_inner, dm.d_state, dm.d_conv
    return di * k + di + di + di * n + di + d


def _layer_token_flops(dm) -> float:
    """One token through one layer: the matrix products, the conv, and
    the scan's update and contraction (about 7 operations per state
    element), the gate and the norm."""
    di, n, k = dm.d_inner, dm.d_state, dm.d_conv
    return (2 * matmul_params(dm) + 2 * k * di + 7 * di * n + 10 * di
            + 4 * dm.d_model)


def head(dm, rows: int) -> Work:
    """Final norm and tied output head for ``rows`` positions: f32
    embedding read once, f32 logits written."""
    return Work(flops=rows * 2 * dm.d_model * dm.vocab,
                bytes=dm.vocab * dm.d_model * 4 + rows * dm.vocab * 4)


def state_bytes(dm, slots: float, state_dtype: str, conv_dtype: str) -> float:
    """One read and one write of ``slots`` slots' state over all layers."""
    di, n, k = dm.d_inner, dm.d_state, dm.d_conv
    per = di * n * BYTES[state_dtype] + (k - 1) * di * BYTES[conv_dtype]
    return 2 * slots * dm.n_layer * per


def decode_step(dm, slots: float, state_dtype: str = "f32",
                compute_dtype: str = "bf16") -> Work:
    """One pooled decode step over ``slots`` sequences (one token each)."""
    L = dm.n_layer
    weights = L * (matmul_params(dm) * BYTES[compute_dtype]
                   + vector_params(dm) * 4)
    embed_rows = slots * dm.d_model * 4
    return (Work(flops=slots * L * _layer_token_flops(dm),
                 bytes=weights + embed_rows
                 + state_bytes(dm, slots, state_dtype, compute_dtype))
            + head(dm, slots))


def prefill(dm, length: int, state_dtype: str = "f32",
            compute_dtype: str = "bf16") -> Work:
    """Prefill of one prompt of ``length`` tokens: every layer over every
    position, the state written once, the logits of the last position
    only (the one the first token is sampled from)."""
    L = dm.n_layer
    weights = L * (matmul_params(dm) * BYTES[compute_dtype]
                   + vector_params(dm) * 4)
    return (Work(flops=length * L * _layer_token_flops(dm),
                 bytes=weights + length * dm.d_model * 4
                 + state_bytes(dm, 1, state_dtype, compute_dtype) / 2)
            + head(dm, 1))


def model_flops(dm, prompt_tokens: int, output_tokens: int) -> float:
    """2 x matrix parameters per prompt and per output token, and the
    output head once per output token: the work users are served."""
    mm = 2 * dm.n_layer * matmul_params(dm)
    return (mm * (prompt_tokens + output_tokens)
            + 2 * dm.d_model * dm.vocab * output_tokens)
