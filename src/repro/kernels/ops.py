"""Public jit'd wrappers over the Pallas kernels with impl dispatch.

Models call these; ``impl`` selects between the Pallas kernel ("pallas",
compiled on TPU and interpreted elsewhere — kernels.backend decides)
and the pure-jnp oracle ("xla").  The oracle is also what autodiff differentiates through for
training paths (the Pallas forward is inference/serving + perf analysis;
see DESIGN.md §2).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import approx
from repro.kernels import ref
from repro.kernels import conv1d as _conv1d_k
from repro.kernels import fast_exp as _fast_exp_k
from repro.kernels import flash_attention as _flash_k
from repro.kernels import piecewise_silu as _silu_k
from repro.kernels import selective_scan as _scan_k


def exp(x, impl: str = "exact", backend: str = "xla"):
    """impl in {exact, ours, fast}; backend in {xla, pallas}."""
    if impl == "exact":
        return jnp.exp(x)
    if backend == "pallas":
        if impl == "ours":
            return _fast_exp_k.fast_exp(x)
        return _fast_exp_k.fast_exp(x, b_shift=approx.FAST_EXP_B_SHIFT, c=0.0)
    return approx.get_exp(impl)(x)


def silu(x, impl: str = "exact", backend: str = "xla"):
    """impl in {exact, ours, paper}; backend in {xla, pallas}."""
    if impl == "exact":
        import jax
        return jax.nn.silu(x)
    if backend == "pallas":
        return _silu_k.piecewise_silu(x, variant=impl)
    return approx.get_silu(impl)(x)


def selective_scan(x, dt, A, B, C, D=None, z=None, h0=None,
                   impl: str = "chunked", chunk: int = 64,
                   exp_impl: str = "exact", silu_impl: str = "exact"):
    """impl in {seq, assoc, chunked, chunked_seq, pallas, pallas_vjp}."""
    if impl == "pallas":
        return _scan_k.selective_scan(x, dt, A, B, C, D=D, z=z, h0=h0,
                                      exp_impl=exp_impl, silu_impl=silu_impl)
    if impl == "pallas_vjp":
        # trainable kernel path: custom VJP covers the recurrence core;
        # D-skip and z-gate stay in autodiff-able jnp
        assert h0 is None, "pallas_vjp path starts from h0=0 (training)"
        y, h_last = _scan_k.selective_scan_trainable(x, dt, A, B, C, chunk)
        if D is not None:
            y = y + D.astype(jnp.float32)[None, None, :] \
                * x.astype(jnp.float32)
        if z is not None:
            y = y * approx.get_silu(silu_impl)(z.astype(jnp.float32))
        return y.astype(x.dtype), h_last
    from repro.core import selective_scan as css
    if impl in ("chunked", "chunked_seq"):
        return css.selective_scan_chunked(
            x, dt, A, B, C, D=D, z=z, h0=h0, chunk=chunk,
            exp_impl=exp_impl, silu_impl=silu_impl,
            inner="seq" if impl == "chunked_seq" else "assoc")
    if impl == "assoc":
        return css.selective_scan_assoc(x, dt, A, B, C, D=D, z=z, h0=h0,
                                        exp_impl=exp_impl,
                                        silu_impl=silu_impl)
    return ref.selective_scan(x, dt, A, B, C, D=D, z=z, h0=h0,
                              exp_impl=exp_impl, silu_impl=silu_impl)


def selective_state_step(h, x_t, dt_t, A, B_t, C_t, D=None, z_t=None,
                         impl: str = "xla",
                         exp_impl: str = "exact", silu_impl: str = "exact",
                         a_scale=None):
    """Single-token decode step; impl in {xla, fused/pallas}.

    The fused impl is one Pallas launch for the whole state-update /
    contraction / gate chain (interpret-mode on CPU); xla is the ref.py
    oracle with identical semantics.  ``a_scale`` (d,) marks A as int8
    weight codes (cfg.weight_dtype="int8") dequantized at the point of
    consumption — in-kernel for the fused impl."""
    from repro.core import selective_scan as css
    return css.decode_step(h, x_t, dt_t, A, B_t, C_t, D=D, z_t=z_t,
                           impl=impl, exp_impl=exp_impl,
                           silu_impl=silu_impl, a_scale=a_scale)


def selective_state_step_q(hq, h_scale, x_t, dt_t, A, B_t, C_t, D=None,
                           z_t=None, state_dtype: str = "int8",
                           impl: str = "xla", exp_impl: str = "exact",
                           silu_impl: str = "exact", a_scale=None):
    """Quantized-state single-token decode step; impl in {xla, fused}.

    Same chain as selective_state_step but the state payload stays in
    its int8/fp8 storage dtype across the HBM round-trip: dequant on
    read, requant on write with a decayed-running-absmax scale (inside
    the kernel for the fused impl)."""
    from repro.core import selective_scan as css
    return css.decode_step_q(hq, h_scale, x_t, dt_t, A, B_t, C_t, D=D,
                             z_t=z_t, state_dtype=state_dtype, impl=impl,
                             exp_impl=exp_impl, silu_impl=silu_impl,
                             a_scale=a_scale)


def causal_conv1d(x, w, b=None, x_prev=None, impl: str = "xla"):
    if impl == "pallas":
        return _conv1d_k.causal_conv1d(x, w, b=b, x_prev=x_prev)
    return ref.causal_conv1d(x, w, b=b, x_prev=x_prev)


def attention(q, k, v, causal: bool = True, impl: str = "xla"):
    if impl == "pallas":
        return _flash_k.flash_attention(q, k, v, causal=causal)
    return ref.attention(q, k, v, causal=causal)
