"""Pallas TPU kernel: fused single-token SSM decode step.

This is the serving-engine counterpart of kernels/selective_scan.py:
where the scan kernel fuses the recurrence over the *time* axis for
prefill/training, this kernel fuses the entire per-token chain the
engine's decode burst executes per layer:

    h' = exp(dt * A) (*) h + (dt * x) (*) B        state update (EW FMA)
    y  = sum_n C_n * h'_n + D * x                  output contraction
    out = y * silu(z)                              gate

MARCA's point (Fig. 1 / §4) is that this chain is element-wise with a
single tiny N=d_state reduction, so dispatching it as a dozen separate
XLA ops per layer per token pays kernel-launch + HBM round-trip for
every arrow in the chain.  Here the whole chain — including the fast
biased exp and the piecewise SiLU when approx mode is on — is one
kernel over the slot-pooled state: state in, token out, one launch.

Layout mirrors the scan kernel: channels D on lanes (128-aligned),
state N on sublanes; h is carried as (slots, N, D).  Grid is
(slot-blocks, D-blocks), both parallel — a decode step has no
sequential axis, which is exactly why it fuses so cleanly.

``interpret=None`` (the default) compiles on TPU and runs the same
kernel body under the Pallas interpreter elsewhere (kernels.backend),
so every CPU test exercises the fused path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import approx, state_quant
from repro.kernels import backend

# ``jax.named_scope`` names around each decode kernel launch and the
# recurrent-state staging that feeds it and writes it back, so ops in
# a compiled program (and on a device trace) say which of the three
# they belong to.
SCOPE_KERNEL = "decode_kernel"
SCOPE_STATE_IN = "decode_state_in"
SCOPE_STATE_OUT = "decode_state_out"


# ---------------------------------------------------------------------------
# Cell skeleton — MARCA's reconfigurable PE, expressed as code.
#
# Every recurrent decode cell this repo serves is the same three-phase
# shape (the paper's Fig. 1 regime):
#
#   state_update  — an element-wise FMA on the carried state
#                   (S6: exp(dt*A) (*) h + (dt*x) (*) B;
#                    mLSTM: f (*) C + i (*) k (x) v;  sLSTM: f (*) c + i*z)
#   contract      — a tiny reduction (or identity) producing the output
#                   (S6: sum_n C_n h_n;  mLSTM: q-query + normalizer;
#                    sLSTM: scalar memory, no reduction)
#   gate          — an element-wise epilogue
#                   (S6: D-skip + SiLU(z);  sLSTM: sigmoid output gate)
#
# The decomposed nonlinearities (fast biased exp, piecewise SiLU) plug
# into the phases via core.approx, so "reconfiguring" a PE is picking a
# phase function, exactly the paper's RCU modes.  Phase functions use
# ``...`` broadcasting so ONE implementation serves both the per-layer
# kernel's unbatched (N, BD) grid cell and the megakernel's batched
# (b, N, D) block — the two paths cannot drift.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CellSkeleton:
    """A recurrent decode cell as three pluggable phases.

    ``state_update(state, ins) -> state_new``;
    ``contract(state_new, ins) -> y``;
    ``gate(y, state_new, ins) -> y`` (None = identity).  ``state`` is an
    array or tuple of arrays; ``ins`` a dict of per-token inputs.

    ``dequant(ins) -> ins`` (None = identity) is a fourth, *leading*
    phase: when weights are stored quantized (cfg.weight_dtype="int8")
    the per-channel scale multiply expanding int8 codes into the f32
    operands the other phases consume runs here — inside the kernel, on
    the grid cell's own weight block — so weight bytes cross HBM at
    int8.  In MARCA terms, one more reconfigured PE mode ahead of the
    FMA."""
    name: str
    state_update: Callable
    contract: Callable
    gate: Optional[Callable] = None
    dequant: Optional[Callable] = None

    def __call__(self, state, ins):
        if self.dequant is not None:
            ins = self.dequant(ins)
        state_new = self.state_update(state, ins)
        y = self.contract(state_new, ins)
        if self.gate is not None:
            y = self.gate(y, state_new, ins)
        return y, state_new


@functools.lru_cache(maxsize=None)
def s6_cell(exp_impl: str, silu_impl: str, has_d: bool,
            has_z: bool, wq: bool = False) -> CellSkeleton:
    """The mamba/jamba selective-SSM cell.  State (..., N, D) f32; ins:
    x/dt (..., D), at (N, D) [A transposed], b/c (..., N), d (D,)|None,
    z (..., D)|None — all f32.

    ``wq=True``: ``at`` holds int8 codes cast to f32 and ``ins`` carries
    ``at_scale`` (D,) — the per-d_inner-channel absmax scales from
    core.weight_quant — which the dequant phase multiplies back in.  The
    broadcasting serves the per-layer kernel's (N, BD) block and the
    megakernel's (n, d_inner) slice with the same line, and the multiply
    is element-for-element the one ``weight_quant.dequantize_rows`` runs
    on the XLA path, so all step impls see bit-identical A."""
    exp = approx.get_exp(exp_impl)
    silu = approx.get_silu(silu_impl)

    def dequant(ins):
        out = dict(ins)
        out["at"] = ins["at"] * ins["at_scale"][..., None, :]
        return out

    def state_update(h, ins):
        da = exp(ins["dt"][..., None, :] * ins["at"])     # EW + "shift"
        dbx = ((ins["dt"] * ins["x"])[..., None, :]
               * ins["b"][..., :, None])                  # EW outer prod
        return da * h + dbx                               # EW FMA

    def contract(h_new, ins):
        # tiny N-reduction: y_d = sum_n C_n h_nd
        return jnp.sum(h_new * ins["c"][..., :, None], axis=-2)

    def gate(y, _state, ins):
        if has_d:
            y = y + ins["d"] * ins["x"]
        if has_z:
            y = y * silu(ins["z"])
        return y

    return CellSkeleton("s6", state_update, contract,
                        gate if (has_d or has_z) else None,
                        dequant if wq else None)


@functools.lru_cache(maxsize=None)
def mlstm_cell(dh: int) -> CellSkeleton:
    """The xLSTM matrix-memory cell.  State (C (..., dh, dh),
    n (..., dh), m (...,)); ins: q/k/v (..., dh), i/f (...,) — all f32.
    The gate stabilizers pin exact exp/log-sigmoid (approximating the
    max-subtracted exponents breaks the stabilization contract); the
    MARCA approximations enter through the block front-end instead."""
    def state_update(state, ins):
        C, n, m = state
        logf = jax.nn.log_sigmoid(ins["f"])
        m_new = jnp.maximum(logf + m, ins["i"])
        i_p = jnp.exp(ins["i"] - m_new)
        f_p = jnp.exp(logf + m - m_new)
        kv = ins["k"][..., :, None] * ins["v"][..., None, :]
        C = f_p[..., None, None] * C + i_p[..., None, None] * kv
        n = f_p[..., None] * n + i_p[..., None] * ins["k"]
        return (C, n, m_new)

    def contract(state, ins):
        C, n, _ = state
        qn = ins["q"] * (dh ** -0.5)
        num = jnp.einsum("...de,...d->...e", C, qn)
        den = jnp.abs(jnp.einsum("...d,...d->...", n, qn))
        return num / jnp.maximum(den, 1.0)[..., None]

    return CellSkeleton("mlstm", state_update, contract, None)


@functools.lru_cache(maxsize=None)
def slstm_cell() -> CellSkeleton:
    """The xLSTM scalar-memory cell.  State (c, n, m) each (..., nh, dh);
    ins: g (..., 4, nh, dh) combined pre-activations [z, i, f, o]."""
    def state_update(state, ins):
        c, n, m = state
        g = ins["g"]
        z_t = jnp.tanh(g[..., 0, :, :])
        i_t = g[..., 1, :, :]
        f_t = g[..., 2, :, :]
        logf = jax.nn.log_sigmoid(f_t)
        m_new = jnp.maximum(logf + m, i_t)
        i_p = jnp.exp(i_t - m_new)
        f_p = jnp.exp(logf + m - m_new)
        c_new = f_p * c + i_p * z_t
        n_new = f_p * n + i_p
        return (c_new, n_new, m_new)

    def contract(state, _ins):
        # scalar memory: no reduction, the cell output IS the state
        return state[0]

    def gate(y, state, ins):
        _, n_new, _ = state
        o_t = jax.nn.sigmoid(ins["g"][..., 3, :, :])
        return o_t * y / jnp.maximum(n_new, 1.0)

    return CellSkeleton("slstm", state_update, contract, gate)


def _chain(h, x_ref, dt_ref, at_ref, at_scale_ref, b_ref, c_ref, d_ref,
           z_ref, *, exp_impl: str, silu_impl: str, has_d: bool,
           has_z: bool, wq: bool):
    """The fused per-token chain on one (slot-block, D-block) grid cell:
    block loads + f32 casts around the S6 cell skeleton.
    h (BB, N, BD) f32 already dequantized; with ``wq`` the At block holds
    int8 codes and at_scale_ref the (1, BD) per-channel scales the
    cell's dequant phase expands them with.
    Returns (y (BB, BD), h_new (BB, N, BD))."""
    cell = s6_cell(exp_impl, silu_impl, has_d, has_z, wq)
    ins = {
        "x": x_ref[...].astype(jnp.float32),           # (BB, BD)
        "dt": dt_ref[...].astype(jnp.float32),         # (BB, BD)
        "at": at_ref[...].astype(jnp.float32),         # (N, BD)
        "b": b_ref[...].astype(jnp.float32),           # (BB, N)
        "c": c_ref[...].astype(jnp.float32),           # (BB, N)
        "d": d_ref[0, :].astype(jnp.float32) if has_d else None,
        "z": z_ref[...].astype(jnp.float32) if has_z else None,
    }
    if wq:
        ins["at_scale"] = at_scale_ref[0, :].astype(jnp.float32)  # (BD,)
    return cell(h, ins)


def _step_kernel(*refs, exp_impl: str, silu_impl: str, has_d: bool,
                 has_z: bool, wq: bool, state_dtype: str):
    """One grid cell of the decode step.  With a quantized
    ``state_dtype`` the int8/fp8 payload is dequantized on read and
    requantized on write *inside* the kernel, so the f32 state lives
    only in VMEM/registers — never in HBM.  Each grid cell owns one
    channel group's scale per slot (scale blocking == channel
    blocking), so the running-absmax update needs no cross-block
    reduction."""
    quant = state_quant.is_quantized(state_dtype)
    if quant:
        h_ref, scale_ref, *in_refs, y_ref, hout_ref, scale_out_ref = refs
    else:
        h_ref, *in_refs, y_ref, hout_ref = refs
    h = h_ref[...].astype(jnp.float32)             # (BB, N, BD)
    if quant:
        s_in = scale_ref[0]                        # (BB, 1, 1)
        h = h * s_in                               # dequant on read
    y, h_new = _chain(h, *in_refs, exp_impl=exp_impl, silu_impl=silu_impl,
                      has_d=has_d, has_z=has_z, wq=wq)
    y_ref[...] = y.astype(y_ref.dtype)
    if not quant:
        hout_ref[...] = h_new.astype(hout_ref.dtype)
        return
    amax = jnp.max(jnp.max(jnp.abs(h_new), axis=-1, keepdims=True),
                   axis=-2, keepdims=True)         # (BB, 1, 1)
    s_out = state_quant.update_scale(amax, s_in, state_dtype)
    hout_ref[...] = state_quant.encode(h_new / s_out, state_dtype)
    scale_out_ref[0] = s_out


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_d", "exp_impl", "silu_impl",
                     "state_dtype", "interpret"))
def _step_padded(h, h_scale, x_t, dt_t, at, at_scale, b_t, c_t, d_skip,
                 z_t, *, block_b: int, block_d: int, exp_impl: str,
                 silu_impl: str, state_dtype: str, interpret: bool):
    """One launch over pre-padded inputs: slots % block_b == 0 and
    D % block_d == 0.  h (slots, N, D) in the storage dtype; h_scale
    (D // block_d, slots, 1, 1) f32 for a quantized ``state_dtype``,
    else None.  ``at_scale`` (1, D) rides the d_skip-style per-channel
    blocking; None means f32 weights (placeholder block, dequant phase
    compiled out).

    Every block is legal for the TPU's (8, 128) tiling at any slot
    count: a slot block is 8 rows or the whole pool, channel blocks are
    multiples of 128, and N-wide or unit dims are whole array dims."""
    bsz, n, d_in = h.shape
    quant = h_scale is not None
    grid = (bsz // block_b, d_in // block_d)
    state = pl.BlockSpec((block_b, n, block_d), lambda bb, dd: (bb, 0, dd))
    scale = pl.BlockSpec((1, block_b, 1, 1), lambda bb, dd: (dd, bb, 0, 0))
    row = pl.BlockSpec((block_b, block_d), lambda bb, dd: (bb, dd))
    vec = pl.BlockSpec((block_b, n), lambda bb, dd: (bb, 0))
    chan = pl.BlockSpec((1, block_d), lambda bb, dd: (0, dd))
    unused = pl.BlockSpec((1, 1), lambda bb, dd: (0, 0))

    def _opt(arg, spec):
        if arg is None:
            return jnp.zeros((1, 1), jnp.float32), unused
        return arg, spec

    pairs = ([(h, state)] + ([(h_scale, scale)] if quant else [])
             + [(x_t, row), (dt_t, row),
                (at, pl.BlockSpec((n, block_d), lambda bb, dd: (0, dd))),
                _opt(at_scale, chan), (b_t, vec), (c_t, vec),
                _opt(d_skip, chan), _opt(z_t, row)])
    args, in_specs = zip(*pairs)

    out_shapes = [jax.ShapeDtypeStruct((bsz, d_in), x_t.dtype),
                  jax.ShapeDtypeStruct(h.shape, h.dtype)]
    out_specs = [row, state]
    if quant:
        out_shapes.append(jax.ShapeDtypeStruct(h_scale.shape, jnp.float32))
        out_specs.append(scale)

    kernel = functools.partial(
        _step_kernel, exp_impl=exp_impl, silu_impl=silu_impl,
        has_d=d_skip is not None, has_z=z_t is not None,
        wq=at_scale is not None, state_dtype=state_dtype)

    return pl.pallas_call(
        kernel,
        out_shape=tuple(out_shapes),
        grid=grid,
        in_specs=list(in_specs),
        out_specs=tuple(out_specs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="marca_decode_step_q" if quant else "marca_decode_step",
    )(*args)


def _launch_step(h, h_scale, x_t, dt_t, A, B_t, C_t, D, z_t, a_scale, *,
                 block_d: int, exp_impl: str, silu_impl: str,
                 state_dtype: str, interpret: bool | None):
    """Pad slots and channels to the kernel blocks, launch, unpad.

    h (b, d, n) storage payload; h_scale (b, g) f32 or None.  A pool of
    at most SUBLANES slots is one slot block; a larger pool is padded
    to a multiple of SUBLANES and blocked by it."""
    bsz, d_in, n = h.shape
    block_b = bsz if bsz <= backend.SUBLANES else backend.SUBLANES
    pad_b = (-bsz) % block_b
    pad_d = (-d_in) % block_d

    def _pad_row(t):
        if t is None:
            return None
        return jnp.pad(t, ((0, pad_b), (0, pad_d)))

    def _pad_chan(t):
        if t is None:
            return None
        return jnp.pad(t.astype(jnp.float32), (0, pad_d)).reshape(1, -1)

    with jax.named_scope(SCOPE_STATE_IN):
        hp = jnp.pad(h.swapaxes(1, 2), ((0, pad_b), (0, 0), (0, pad_d)))
        sp = (None if h_scale is None else
              jnp.pad(h_scale, ((0, pad_b), (0, 0))).T[:, :, None, None])
    at = jnp.pad(A, ((0, pad_d), (0, 0))).T                 # (n, Dp)
    if a_scale is None:
        at = at.astype(jnp.float32)
    bp = jnp.pad(B_t, ((0, pad_b), (0, 0)))
    cp = jnp.pad(C_t, ((0, pad_b), (0, 0)))

    with jax.named_scope(SCOPE_KERNEL):
        out = _step_padded(
            hp, sp, _pad_row(x_t), _pad_row(dt_t), at, _pad_chan(a_scale),
            bp, cp, _pad_chan(D), _pad_row(z_t), block_b=block_b,
            block_d=block_d, exp_impl=exp_impl, silu_impl=silu_impl,
            state_dtype=state_dtype,
            interpret=backend.resolve_interpret(interpret))
    with jax.named_scope(SCOPE_STATE_OUT):
        y = out[0][:bsz, :d_in]
        h_new = out[1][:bsz, :, :d_in].swapaxes(1, 2)
        if h_scale is None:
            return y, h_new
        return y, h_new, out[2][:, :bsz, 0, 0].T


# ---------------------------------------------------------------------------
# Cross-layer megakernel launcher
# ---------------------------------------------------------------------------

def stacked_layer_launch(body, x0, stacked, out_structs, *,
                         interpret: bool | None = None,
                         name: str = "marca_megakernel"):
    """Run ``body`` once per layer inside a SINGLE Pallas launch.

    The layer axis becomes the kernel grid ((L,), semantics "arbitrary" —
    it is sequential: layer l reads the residual stream layer l-1 wrote).
    The residual stream is a *revisited output block*: its BlockSpec index
    map is constant, so Pallas keeps the same block resident across grid
    steps and the kernel carries ``x`` through it — seeded from ``x0``
    at l == 0.  Per-layer operands (weights + recurrent state) arrive as
    pytrees with a stacked leading L axis; each grid step sees its own
    (1, ...) slice with the leading axis dropped.

    The issue sketches a (L, slots, d-block) grid; slots and d stay folded
    into the block here because the in-body projections couple the full
    channel dimension (and bitwise identity with the per-layer path needs
    the matmuls at identical shapes).  On real TPU the intra-layer split
    is the obvious follow-on once weights are resident per-core.

    body(x, ins) -> (x_new, outs):  ``x`` (b, 1, d_model) residual stream;
    ``ins`` one layer's slice of ``stacked``; ``outs`` a flat list/tuple of
    arrays matching ``out_structs`` (ShapeDtypeStructs of the PER-LAYER
    shapes — the launch returns them stacked to (L, ...)).

    Returns (x_final, tuple(stacked_outs)).
    """
    interpret = backend.resolve_interpret(interpret)
    leaves, treedef = jax.tree.flatten(stacked)
    n_layers = leaves[0].shape[0]
    for lf in leaves:
        assert lf.shape[0] == n_layers, (lf.shape, n_layers)
    out_structs = tuple(out_structs)

    x_nz = (0,) * x0.ndim

    def _const_map(l):
        return x_nz

    # A block's last two dims must tile (8, 128) or span the array, so
    # a per-layer leaf of rank < 2 (a norm scale, a bias) gets unit dims
    # after the layer axis: (L, X) -> (L, 1, X) with block (1, 1, X).
    lifted = [(1,) * max(0, 2 - (lf.ndim - 1)) for lf in leaves]
    leaves = [lf.reshape((n_layers,) + u + lf.shape[1:])
              for lf, u in zip(leaves, lifted)]
    in_specs = [pl.BlockSpec(x0.shape, _const_map)]
    for lf in leaves:
        rest = lf.shape[1:]
        in_specs.append(pl.BlockSpec(
            (1,) + rest,
            lambda l, _nz=(0,) * len(rest): (l,) + _nz))

    out_shapes = [jax.ShapeDtypeStruct(x0.shape, x0.dtype)]
    out_specs = [pl.BlockSpec(x0.shape, _const_map)]
    for s in out_structs:
        out_shapes.append(
            jax.ShapeDtypeStruct((n_layers,) + s.shape, s.dtype))
        out_specs.append(pl.BlockSpec(
            (1,) + s.shape,
            lambda l, _nz=(0,) * len(s.shape): (l,) + _nz))

    n_in = len(leaves)

    def kernel(x0_ref, *refs):
        in_refs = refs[:n_in]
        x_ref = refs[n_in]
        out_refs = refs[n_in + 1:]
        l = pl.program_id(0)

        @pl.when(l == 0)
        def _seed():
            x_ref[...] = x0_ref[...]

        x = x_ref[...]
        ins = treedef.unflatten([r[(0,) * (1 + len(u))]
                                 for r, u in zip(in_refs, lifted)])
        x_new, outs = body(x, ins)
        x_ref[...] = x_new.astype(x_ref.dtype)
        for o_ref, o in zip(out_refs, outs):
            o_ref[0] = o.astype(o_ref.dtype)

    res = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shapes),
        grid=(n_layers,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(None if interpret
                              else backend.vmem_budget_bytes())),
        interpret=interpret,
        name=name,
    )(x0, *leaves)
    return res[0], tuple(res[1:])


def stacked_layer_vmem_bytes(stacked, out_structs) -> int:
    """VMEM one grid step of ``stacked_layer_launch`` needs, from its
    operands: every per-layer input and output block double-buffered,
    plus an f32 working copy of each input block — the body dequantizes
    or casts weights and state to f32 (or from f32 to the compute
    dtype) before it uses them.  ``stacked`` may hold arrays, tracers
    or ShapeDtypeStructs with the leading layer axis; ``out_structs``
    the per-layer output shapes, as the launcher takes them."""
    ins = [(lf.shape[1:], lf.dtype) for lf in jax.tree.leaves(stacked)]
    outs = [(s.shape, s.dtype) for s in out_structs]
    return (2 * sum(backend.block_bytes(*b) for b in ins + outs)
            + sum(backend.block_bytes(shape, jnp.float32)
                  for shape, _ in ins))


def selective_state_step_q(hq, h_scale, x_t, dt_t, A, B_t, C_t, D=None,
                           z_t=None, state_dtype: str = "int8",
                           exp_impl: str = "exact",
                           silu_impl: str = "exact",
                           a_scale=None,
                           interpret: bool | None = None):
    """Fused quantized-state decode step.  Same semantics as
    kernels.ref.selective_state_step_q.

    hq (b, d, n) int8/fp8 payload; h_scale (b, g) f32 with one scale per
    ``state_quant.D_BLOCK`` channel group; other args as in
    selective_state_step.  Returns (y (b, d), hq_new, scale_new (b, g)).

    The channel blocking is pinned to the scale grouping (block_d =
    min(D_BLOCK, d)), so dequant/requant stay local to one grid cell.
    The payload block's N-wide sublane dim is the whole array dim, which
    the (32, 128) int8 tiling pads rather than rejects.  fp8 payloads
    (float8_e4m3fn) compile for v5e too, though v5e has no fp8
    arithmetic: the kernel only converts them to and from f32."""
    g = state_quant.n_groups(hq.shape[1])
    assert h_scale.shape == (hq.shape[0], g), (h_scale.shape, g)
    return _launch_step(
        hq, h_scale, x_t, dt_t, A, B_t, C_t, D, z_t, a_scale,
        block_d=min(state_quant.D_BLOCK, hq.shape[1]), exp_impl=exp_impl,
        silu_impl=silu_impl, state_dtype=state_dtype, interpret=interpret)


def selective_state_step(h, x_t, dt_t, A, B_t, C_t, D=None, z_t=None,
                         block_d: int = 512,
                         exp_impl: str = "exact", silu_impl: str = "exact",
                         a_scale=None,
                         interpret: bool | None = None):
    """Fused decode step.  Same semantics as kernels.ref.selective_state_step.

    h (b, d, n) f32 pooled state; x_t/dt_t (b, d); A (d, n); B_t/C_t (b, n);
    D (d,)|None; z_t (b, d)|None.
    With ``a_scale`` (d,) set, A holds int8 codes (cfg.weight_dtype) and
    the kernel's dequant phase expands them per channel in VMEM — the A
    matrix streams from HBM at one byte per entry.
    Returns (y (b, d) in x_t.dtype, h_new (b, d, n) f32).

    ``interpret=None`` resolves per backend (kernels.backend): compiled
    on TPU, the Pallas interpreter elsewhere — so the serving hot path
    is never accidentally interpreted on the hardware the kernel
    targets.
    """
    return _launch_step(
        h.astype(jnp.float32), None, x_t, dt_t, A, B_t, C_t, D, z_t,
        a_scale, block_d=min(block_d, h.shape[1]), exp_impl=exp_impl,
        silu_impl=silu_impl, state_dtype="f32", interpret=interpret)
