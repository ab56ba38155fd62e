"""Pallas TPU kernel: causal GQA flash attention (online softmax).

Not a MARCA contribution (Mamba has no attention) but a hot spot for the
assigned *attention* architectures at prefill_32k: materializing 32k x 32k
scores is impossible, so scores are computed block-wise with the running
(max, sum) rescaling trick, accumulator resident in VMEM — the same
"intermediates never leave the buffer" discipline as the scan kernel.

Layout: q/k/v as (b, h, l, dh); grid (b, hq, lq/BQ, lk/BK) with the KV axis
innermost ("arbitrary") so m/l/acc scratch persists across KV blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import backend

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, bq: int, bk: int, scale: float, causal: bool,
                  q_offset: int):
    kv_idx = pl.program_id(3)
    q_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * np.float32(scale)   # (BQ, dh)
    k = k_ref[0, 0].astype(jnp.float32)                        # (BK, dh)
    v = v_ref[0, 0].astype(jnp.float32)                        # (BK, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (BQ, BK)
    if causal:
        rows = q_offset + q_idx * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        cols = kv_idx * bk + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 1)
        s = jnp.where(rows >= cols, s, _NEG_INF)

    m_prev = m_scr[...]                                        # (BQ, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                                     # (BQ, BK)
    corr = jnp.exp(m_prev - m_new)                             # (BQ, 1)
    l_new = corr * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_new = corr * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(kv_idx == pl.num_programs(3) - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_q", "block_k", "causal", "scale", "q_offset", "interpret"))
def _flash_bhld(q, k, v, block_q: int, block_k: int, causal: bool,
                scale: float, q_offset: int, interpret: bool | None):
    """q (b, hq, lq, dh); k/v (b, hkv, lk, dh); lq % bq == lk % bk == 0."""
    b, hq, lq, dh = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    lk = k.shape[2]
    grid = (b, hq, lq // block_q, lk // block_k)
    return pl.pallas_call(
        functools.partial(_flash_kernel, bq=block_q, bk=block_k, scale=scale,
                          causal=causal, q_offset=q_offset),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda bb, hh, qq, kk: (bb, hh, qq, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda bb, hh, qq, kk, _rep=rep:
                         (bb, hh // _rep, kk, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda bb, hh, qq, kk, _rep=rep:
                         (bb, hh // _rep, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda bb, hh, qq, kk: (bb, hh, qq, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=backend.resolve_interpret(interpret),
        name="flash_attention",
    )(q, k, v)


def flash_attention(q, k, v, causal: bool = True, scale=None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """q (b, lq, hq, dh); k/v (b, lk, hkv, dh) — matches kernels.ref.attention.

    Handles lq < lk (q is the suffix of the sequence, decode-chunk style).
    """
    b, lq, hq, dh = q.shape
    lk = k.shape[1]
    if scale is None:
        scale = dh ** -0.5
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    pad_q = (-lq) % block_q
    pad_k = (-lk) % block_k
    qt = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    kt = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vt = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    if pad_k:
        # mask padded keys with causal-style column bound: rows >= cols fails
        # automatically only in causal mode; for non-causal, bias via value 0
        # and score -inf is needed — implemented by causal=True requirement.
        assert causal, "non-causal with padded kv not supported"
    o = _flash_bhld(qt, kt, vt, block_q=block_q, block_k=block_k,
                    causal=causal, scale=float(scale),
                    q_offset=lk - lq, interpret=interpret)
    return o.transpose(0, 2, 1, 3)[:, :lq]
