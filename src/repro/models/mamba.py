"""Mamba block (Gu & Dao 2023) — the architecture MARCA accelerates.

Computational flow per block (paper Fig. 3): LN -> in_proj -> [x | z] ->
causal depthwise conv -> SiLU -> x_proj -> (dt, B, C) -> softplus(dt_proj) ->
selective scan (the element-wise chain MARCA fuses) -> gate by SiLU(z) ->
out_proj -> residual.

The MARCA knobs: cfg.scan_impl selects seq/assoc/chunked/pallas,
cfg.exp_impl/silu_impl select exact vs the paper's approximations, and
cfg.conv_impl selects the Pallas conv kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import approx, state_quant, weight_quant
from repro.kernels import ops
from repro.models import blocks
from repro.parallel.sharding import Param, constrain

#: the mixer's matrices: every consumer hands them to ``blocks.dense`` at
#: the compute dtype (cfg.dtype), so a copy held at that dtype feeds
#: bitwise the same matmul operands (``registry.precast_params``)
PROJECTIONS = ("in_proj", "x_proj", "dt_proj", "out_proj")


def _a_and_scale(p):
    """The SSM A matrix as the step math consumes it: (A, a_scale).

    f32 weights (no "A_q" leaf) recompute A = -exp(A_log) and carry no
    scale; int8 weights (cfg.weight_dtype="int8") hand back the stored
    codes plus their per-d_inner-channel scales, leaving the dequant to
    the point of consumption — in-kernel for fused/megakernel steps."""
    if "A_q" in p:
        return p["A_q"], p["A_scale"]
    return -jnp.exp(p["A_log"]), None


def read_state_h(cfg, state):
    """Decode the stored recurrent state to the f32 the scan/step math
    uses.  f32/bf16 is a cast; int8/fp8 dequantizes with the state's
    group scales (state["h_scale"])."""
    if state_quant.is_quantized(cfg.state_dtype):
        return state_quant.dequantize_h(state["h"], state["h_scale"])
    return state["h"].astype(jnp.float32)


def write_state_h(cfg, h, prev_state=None):
    """Encode a f32 state for storage: the {"h": ...} (+"h_scale") leaves
    of the new state dict.  ``prev_state`` supplies the previous scales
    for the decayed-running-absmax update; None = cold start (prefill)."""
    if state_quant.is_quantized(cfg.state_dtype):
        prev = None if prev_state is None else prev_state["h_scale"]
        q, scale = state_quant.quantize_h(h, cfg.state_dtype,
                                          prev_scale=prev)
        return {"h": q, "h_scale": scale}
    return {"h": h.astype(state_quant.storage_dtype(cfg.state_dtype))}


def _silu(cfg, x):
    """SiLU of the conv output, computed in f32 and rounded once to
    x's dtype — the same bits on every path, and the form a Pallas TPU
    body (the megakernel) can lower for bf16 activations."""
    return approx.get_silu(cfg.silu_impl)(x.astype(jnp.float32)).astype(
        x.dtype)


def mamba_block_init(cfg, key):
    d, di, n, k, r = (cfg.d_model, cfg.d_inner, cfg.d_state, cfg.d_conv,
                      cfg.dt_rank)
    ks = jax.random.split(key, 6)
    # S4D-real initialization for A; dt bias init for softplus range
    a_init = jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[None, :],
                      (di, 1))
    dt_init = jnp.exp(
        jax.random.uniform(ks[0], (di,)) * (jnp.log(0.1) - jnp.log(0.001))
        + jnp.log(0.001))
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))  # inverse softplus
    return {
        "in_proj": blocks.dense_init(ks[1], d, 2 * di, ("embed", "ffn")),
        "conv_w": Param(
            jax.random.normal(ks[2], (k, di), jnp.float32) * (1.0 / k),
            ("conv", "ffn")),
        "conv_b": Param(jnp.zeros((di,), jnp.float32), ("ffn",)),
        "x_proj": blocks.dense_init(ks[3], di, r + 2 * n, ("ffn", None)),
        "dt_proj": blocks.dense_init(ks[4], r, di, (None, "ffn"),
                                     scale=r ** -0.5),
        "dt_bias": Param(dt_bias, ("ffn",)),
        "A_log": Param(jnp.log(a_init), ("ffn", "state")),
        "D": Param(jnp.ones((di,), jnp.float32), ("ffn",)),
        "out_proj": blocks.dense_init(ks[5], di, d, ("ffn", "embed")),
    }


def _project(cfg, p, x):
    """Shared pre-scan computation: returns x_conv_in, z."""
    cdt = x.dtype
    xz = blocks.dense(p["in_proj"], x, cdt)
    xz = constrain(xz, "act_batch", "act_seq", "act_ffn")
    return jnp.split(xz, 2, axis=-1)


def _ssm_inputs(cfg, p, x_a):
    """x_a (b, l, di) -> dt (b,l,di), B (b,l,n), C (b,l,n)."""
    n, r = cfg.d_state, cfg.dt_rank
    cdt = x_a.dtype
    dbc = blocks.dense(p["x_proj"], x_a, cdt)
    dt_low, B, C = jnp.split(dbc, [r, r + n], axis=-1)
    dt = blocks.dense(p["dt_proj"], dt_low, cdt)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"]).astype(cdt)
    return dt, B, C


def mamba_block_apply(cfg, p, x, state=None):
    """Full-sequence path.  state (decode continuation) is a dict with
    'h' (b, di, n) f32 and 'conv' (b, k-1, di); returns (y, new_state)."""
    x_in, z = _project(cfg, p, x)
    conv_state = None if state is None else state["conv"]
    x_c, new_conv = ops.causal_conv1d(
        x_in, p["conv_w"], p["conv_b"], x_prev=conv_state,
        impl=cfg.conv_impl)
    x_a = _silu(cfg, x_c)
    dt, B, C = _ssm_inputs(cfg, p, x_a)
    A, a_scale = _a_and_scale(p)
    if a_scale is not None:
        # prefill is compute-bound; dequant up front with the same
        # multiply the decode kernels run in their dequant phase
        A = weight_quant.dequantize_rows(A, a_scale)
    h0 = None if state is None else read_state_h(cfg, state)
    y, h_last = ops.selective_scan(
        x_a, dt, A, B, C, D=p["D"], z=z, h0=h0,
        impl=cfg.scan_impl, chunk=cfg.scan_chunk,
        exp_impl=cfg.exp_impl, silu_impl=cfg.silu_impl)
    y = constrain(y, "act_batch", "act_seq", "act_ffn")
    out = blocks.dense(p["out_proj"], y, x.dtype)
    new_state = write_state_h(cfg, h_last, prev_state=state)
    new_state["conv"] = new_conv
    return out, new_state


def mamba_block_step(cfg, p, x_t, state):
    """Single-token decode.  x_t (b, 1, d); state dict as above.

    The conv-state update (shift window, depthwise filter at the last tap)
    is the L=1 case of the streaming causal conv, so it shares the
    ops.causal_conv1d dispatch with prefill — decode uses the same
    cfg.conv_impl kernel.

    The SSM step itself routes through ops.selective_state_step: with
    cfg.step_impl resolving to "fused" the state update, output
    contraction, D-skip, and SiLU gate are one Pallas launch over the
    pooled batch instead of the per-op XLA chain."""
    from repro.core.selective_scan import resolve_cell_impl
    x_in, z = _project(cfg, p, x_t)             # (b,1,di)
    x_c, new_conv = ops.causal_conv1d(
        x_in, p["conv_w"], p["conv_b"], x_prev=state["conv"],
        impl=cfg.conv_impl)
    x_a = _silu(cfg, x_c)
    dt, B, C = _ssm_inputs(cfg, p, x_a)
    A, a_scale = _a_and_scale(p)
    impl = resolve_cell_impl(cfg.step_impl)
    if state_quant.is_quantized(cfg.state_dtype):
        # storage-dtype round-trip stays inside the step: dequant on
        # read, requant on write (in-kernel for the fused impl) — the
        # pooled h never crosses HBM at f32
        y, hq, scale = ops.selective_state_step_q(
            state["h"], state["h_scale"], x_a[:, 0], dt[:, 0], A,
            B[:, 0], C[:, 0], D=p["D"], z_t=z[:, 0],
            state_dtype=cfg.state_dtype, impl=impl,
            exp_impl=cfg.exp_impl, silu_impl=cfg.silu_impl,
            a_scale=a_scale)
        out = blocks.dense(p["out_proj"], y[:, None, :], x_t.dtype)
        return out, {"h": hq, "h_scale": scale, "conv": new_conv}
    y, h = ops.selective_state_step(
        read_state_h(cfg, state), x_a[:, 0], dt[:, 0], A, B[:, 0],
        C[:, 0], D=p["D"], z_t=z[:, 0], impl=impl,
        exp_impl=cfg.exp_impl, silu_impl=cfg.silu_impl, a_scale=a_scale)
    out = blocks.dense(p["out_proj"], y[:, None, :], x_t.dtype)
    return out, {**write_state_h(cfg, h), "conv": new_conv}


def mamba_block_megastep(cfg, p, x_t, state):
    """``mamba_block_step`` restated for INSIDE a megakernel body.

    Same signature and bitwise-identical values, but no nested
    pallas_call: the SSM step is the s6 cell skeleton applied inline
    (the per-layer kernel's ``_chain`` is the same cell at (N, BD)
    block shapes; element-wise phases + the exactly-associative N-sum
    make blocking/batching irrelevant to the produced bits), and the
    conv tail always uses the reference math (a Pallas kernel cannot
    nest another launch).  With cfg.conv_impl="xla" — the default —
    that is the identical computation; under conv_impl="pallas" the
    megakernel silently uses the ref conv instead (documented caveat).
    """
    from repro.kernels import decode_step as dsk
    from repro.kernels import ref as kref
    x_in, z = _project(cfg, p, x_t)             # (b,1,di)
    x_c, new_conv = kref.causal_conv1d(
        x_in, p["conv_w"], p["conv_b"], x_prev=state["conv"])
    x_a = _silu(cfg, x_c)
    dt, B, C = _ssm_inputs(cfg, p, x_a)
    A, a_scale = _a_and_scale(p)
    wq = a_scale is not None
    cell = dsk.s6_cell(cfg.exp_impl, cfg.silu_impl, True, True, wq)
    at = A.astype(jnp.float32).T                         # (n, di)
    ins = {
        "x": x_a[:, 0].astype(jnp.float32),
        "dt": dt[:, 0].astype(jnp.float32),
        "at": at,
        "b": B[:, 0].astype(jnp.float32),
        "c": C[:, 0].astype(jnp.float32),
        "d": p["D"].astype(jnp.float32),
        "z": z[:, 0].astype(jnp.float32),
    }
    if wq:
        # at holds int8 codes (transposed, cast f32); the cell's dequant
        # phase multiplies the per-channel scales back in — inside the
        # megakernel launch, on this layer's grid-local weight slice
        ins["at_scale"] = a_scale.astype(jnp.float32)
    h = read_state_h(cfg, state).swapaxes(1, 2)          # (b, n, di)
    y, h_new = cell(h, ins)
    y = y.astype(x_a.dtype)
    h_new = h_new.swapaxes(1, 2)                         # (b, di, n)
    out = blocks.dense(p["out_proj"], y[:, None, :], x_t.dtype)
    new_state = write_state_h(cfg, h_new, prev_state=state)
    new_state["conv"] = new_conv
    return out, new_state


def _conv_tail_states(conv_state, x_in):
    """Per-step conv tails over a K-token window.

    conv_state (b, k-1, di) entering tail; x_in (b, K, di) the window's
    raw conv inputs.  Returns (b, K, k-1, di): entry t is exactly the
    ``new_state`` ops.causal_conv1d would return after consuming tokens
    0..t — so rolling back to step t restores the same conv tail a
    per-token decode would have."""
    k1 = conv_state.shape[1]
    K = x_in.shape[1]
    full = jnp.concatenate([conv_state, x_in.astype(conv_state.dtype)],
                           axis=1)
    idx = jnp.arange(K)[:, None] + jnp.arange(k1)[None, :] + 1
    return full[:, idx]


def mamba_block_verify(cfg, p, x, state):
    """K-token verify pass (speculative decode): semantically K chained
    ``mamba_block_step`` calls, but the block front-end (projections,
    conv, dt/B/C) runs over the whole K-token window at once and only
    the SSM recurrence is sequential — a K-step micro-scan
    (core.selective_scan.decode_scan) that reuses the fused decode-step
    kernel per step and returns every intermediate state.

    x (b, K, d_model); state as in mamba_block_step.  Returns
    (out (b, K, d_model), states) where ``states`` leaves are stacked
    per step on axis 1: states[t] is the block state after consuming
    token t (spec-decode rollback selects one index).
    """
    from repro.core.selective_scan import (decode_scan, decode_scan_q,
                                           resolve_cell_impl)
    x_in, z = _project(cfg, p, x)                # (b,K,di)
    x_c, _ = ops.causal_conv1d(
        x_in, p["conv_w"], p["conv_b"], x_prev=state["conv"],
        impl=cfg.conv_impl)
    conv_all = _conv_tail_states(state["conv"], x_in)
    x_a = _silu(cfg, x_c)
    dt, B, C = _ssm_inputs(cfg, p, x_a)
    A, a_scale = _a_and_scale(p)
    impl = resolve_cell_impl(cfg.step_impl)
    if state_quant.is_quantized(cfg.state_dtype):
        y, hq_all, scale_all = decode_scan_q(
            state["h"], state["h_scale"], x_a, dt, A, B, C,
            D=p["D"], z_seq=z, state_dtype=cfg.state_dtype, impl=impl,
            exp_impl=cfg.exp_impl, silu_impl=cfg.silu_impl,
            a_scale=a_scale)
        out = blocks.dense(p["out_proj"], y, x.dtype)
        return out, {"h": hq_all, "h_scale": scale_all, "conv": conv_all}
    y, h_all = decode_scan(
        read_state_h(cfg, state), x_a, dt, A, B, C, D=p["D"], z_seq=z,
        impl=impl, exp_impl=cfg.exp_impl, silu_impl=cfg.silu_impl,
        a_scale=a_scale)
    out = blocks.dense(p["out_proj"], y, x.dtype)
    storage = state_quant.storage_dtype(cfg.state_dtype)
    return out, {"h": h_all.astype(storage), "conv": conv_all}


def mamba_state_init(cfg, batch, dtype):
    di, n, k = cfg.d_inner, cfg.d_state, cfg.d_conv
    out = {
        "h": Param(jnp.zeros((batch, di, n),
                             state_quant.storage_dtype(cfg.state_dtype)),
                   ("act_batch", "act_ffn", None)),
        "conv": Param(jnp.zeros((batch, k - 1, di), dtype),
                      ("act_batch", None, "act_ffn")),
    }
    if state_quant.is_quantized(cfg.state_dtype):
        # zero scales decode the zero init state exactly; the first
        # write (prefill quantize or step requant) sets real scales
        out["h_scale"] = Param(
            jnp.zeros((batch, state_quant.n_groups(di)), jnp.float32),
            ("act_batch", None))
    return out
