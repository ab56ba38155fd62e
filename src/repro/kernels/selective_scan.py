"""Pallas TPU kernel: fused selective-SSM scan (MARCA's core, TPU-native).

MARCA's three insights, re-derived for the TPU memory hierarchy:

  * C1 (reduction-alternative PE array): the SSM recurrence is a chain of
    element-wise ops with *no* reduction over a contraction dim (the only
    reduction is the tiny N=d_state sum for y_t).  Running it through
    MXU-shaped HLOs wastes the systolic array exactly like the paper's
    "1/16 normalized speed" on Tensor Cores.  This kernel keeps the whole
    chain on the VPU (8x128 element-wise datapath = the reduction-disabled
    PE array) while matmuls elsewhere in the block stay on the MXU.

  * C2 (reusable nonlinear unit): exp inside the recurrence is the fast
    biased exponential (bitcast shift) and the output gate uses the
    piecewise SiLU — both plain element-wise sequences, selectable per call
    (``exp_impl`` / ``silu_impl``; "exact" uses the VPU transcendental).

  * C3 (inter-operation buffer management): the hidden state h and the
    intermediates dA/dBx never leave VMEM between time steps.  One HBM pass
    over x/dt/B/C/z in, one pass of y out.  The XLA associative-scan
    baseline writes/reads O(B·L·D·N) intermediates — this kernel's traffic
    is O(B·L·D), an N-fold (16x) reduction, mirroring the paper's -49%
    DRAM traffic inter-op result.

Layout: channels D on lanes (128-aligned), state N on sublanes.  Grid is
(batch, D-blocks, L-chunks) with the time axis marked "arbitrary" so the
VMEM scratch h (N, BD) persists across L-chunks for a given (b, d) block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import backend

from repro.core import approx


def _scan_kernel(x_ref, dt_ref, at_ref, b_ref, c_ref, d_ref, z_ref, h0_ref,
                 y_ref, hlast_ref, h_scr, *, bl: int, l_true: int,
                 exp_impl: str, silu_impl: str, has_z: bool, has_d: bool):
    l_idx = pl.program_id(2)
    exp = approx.get_exp(exp_impl)
    silu = approx.get_silu(silu_impl)

    @pl.when(l_idx == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    at = at_ref[...].astype(jnp.float32)            # (N, BD)
    if has_d:
        d_skip = d_ref[0, :].astype(jnp.float32)    # (BD,)

    def body(t, h):
        x_t = x_ref[0, t, :].astype(jnp.float32)    # (BD,)
        dt_t = dt_ref[0, t, :].astype(jnp.float32)  # (BD,)
        b_t = b_ref[0, t, :].astype(jnp.float32)    # (N,)
        c_t = c_ref[0, t, :].astype(jnp.float32)    # (N,)
        da = exp(dt_t[None, :] * at)                # (N, BD)  EW + "shift"
        dbx = (dt_t * x_t)[None, :] * b_t[:, None]  # (N, BD)  EW outer prod
        # Padded tail must be a no-op on h even under approximate exp
        # (fast_exp(0) != 1 exactly, which would decay h through padding).
        valid = (l_idx * bl + t) < l_true
        da = jnp.where(valid, da, 1.0)
        dbx = jnp.where(valid, dbx, 0.0)
        h = da * h + dbx                            # (N, BD)  EW FMA
        y_t = jnp.sum(h * c_t[:, None], axis=0)     # (BD,) tiny N-reduction
        if has_d:
            y_t = y_t + d_skip * x_t
        if has_z:
            z_t = z_ref[0, t, :].astype(jnp.float32)
            y_t = y_t * silu(z_t)
        y_ref[0, t, :] = y_t.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bl, body, h_scr[...])
    h_scr[...] = h
    hlast_ref[0] = h.astype(hlast_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_d", "block_l", "l_true", "exp_impl", "silu_impl",
                     "interpret"))
def _selective_scan_padded(x, dt, at, b, c, d_skip, z, h0,
                           block_d: int, block_l: int, l_true: int,
                           exp_impl: str, silu_impl: str,
                           interpret: bool | None):
    """All inputs pre-padded: L % block_l == 0, D % block_d == 0."""
    bsz, L, d_in = x.shape
    n = at.shape[0]
    has_z = z is not None
    has_d = d_skip is not None
    grid = (bsz, d_in // block_d, L // block_l)

    def _ld(_):
        return pl.BlockSpec((1, block_l, block_d), lambda bb, dd, ll: (bb, ll, dd))

    in_specs = [
        _ld("x"), _ld("dt"),
        pl.BlockSpec((n, block_d), lambda bb, dd, ll: (0, dd)),      # At
        pl.BlockSpec((1, block_l, n), lambda bb, dd, ll: (bb, ll, 0)),  # B
        pl.BlockSpec((1, block_l, n), lambda bb, dd, ll: (bb, ll, 0)),  # C
    ]
    args = [x, dt, at, b, c]
    if has_d:
        in_specs.append(pl.BlockSpec((1, block_d), lambda bb, dd, ll: (0, dd)))
        args.append(d_skip)
    else:
        in_specs.append(pl.BlockSpec((1, 1), lambda bb, dd, ll: (0, 0)))
        args.append(jnp.zeros((1, 1), jnp.float32))
    if has_z:
        in_specs.append(_ld("z"))
        args.append(z)
    else:
        in_specs.append(pl.BlockSpec((1, 1), lambda bb, dd, ll: (0, 0)))
        args.append(jnp.zeros((1, 1), jnp.float32))
    in_specs.append(
        pl.BlockSpec((1, n, block_d), lambda bb, dd, ll: (bb, 0, dd)))  # h0
    args.append(h0)

    out_shapes = (
        jax.ShapeDtypeStruct((bsz, L, d_in), x.dtype),
        jax.ShapeDtypeStruct((bsz, n, d_in), jnp.float32),
    )
    out_specs = (
        pl.BlockSpec((1, block_l, block_d), lambda bb, dd, ll: (bb, ll, dd)),
        pl.BlockSpec((1, n, block_d), lambda bb, dd, ll: (bb, 0, dd)),
    )

    kernel = functools.partial(
        _scan_kernel, bl=block_l, l_true=l_true, exp_impl=exp_impl,
        silu_impl=silu_impl, has_z=has_z, has_d=has_d)

    y, h_last = pl.pallas_call(
        kernel,
        out_shape=out_shapes,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((n, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=backend.resolve_interpret(interpret),
        name="marca_selective_scan",
    )(*args)
    return y, h_last


def selective_scan(x, dt, A, B, C, D=None, z=None, h0=None,
                   block_d: int = 256, block_l: int = 128,
                   exp_impl: str = "exact", silu_impl: str = "exact",
                   interpret: bool | None = None):
    """Fused selective scan.  Same semantics as kernels.ref.selective_scan.

    x, dt: (b, L, d); A: (d, n); B, C: (b, L, n); D: (d,)|None;
    z: (b, L, d)|None; h0: (b, d, n)|None.
    Returns (y (b, L, d), h_last (b, d, n) f32).
    """
    bsz, L, d_in = x.shape
    n = A.shape[1]
    block_d = min(block_d, d_in)
    block_l = min(block_l, L)
    pad_l = (-L) % block_l
    pad_d = (-d_in) % block_d

    def _pad3(t):
        if t is None:
            return None
        return jnp.pad(t, ((0, 0), (0, pad_l), (0, pad_d)))

    xp = _pad3(x)
    dtp = _pad3(dt)
    zp = _pad3(z)
    bp = jnp.pad(B, ((0, 0), (0, pad_l), (0, 0)))
    cp = jnp.pad(C, ((0, 0), (0, pad_l), (0, 0)))
    at = jnp.pad(A, ((0, pad_d), (0, 0))).T            # (n, Dp)
    dp = (None if D is None
          else jnp.pad(D, (0, pad_d)).reshape(1, -1))  # (1, Dp)
    h0p = (jnp.zeros((bsz, n, d_in + pad_d), jnp.float32) if h0 is None
           else jnp.pad(h0.astype(jnp.float32).swapaxes(1, 2),
                        ((0, 0), (0, 0), (0, pad_d))))

    y, h_last = _selective_scan_padded(
        xp, dtp, at, bp, cp, dp, zp, h0p,
        block_d=block_d, block_l=block_l, l_true=L,
        exp_impl=exp_impl, silu_impl=silu_impl, interpret=interpret)
    y = y[:, :L, :d_in]
    h_last = h_last[:, :, :d_in].swapaxes(1, 2)        # (b, d, n)
    return y, h_last


# ---------------------------------------------------------------------------
# Trainable wrapper: Pallas forward + chunk-recompute backward (custom VJP).
#
# XLA autodiff of any scan implementation stacks O(B*L*D*N) residuals to HBM
# (EXPERIMENTS.md §Perf Cell M: the 6.6 TB/chip wall).  This wrapper saves
# only the *inputs* plus chunk-boundary states, and the backward pass
# recomputes h within each chunk while running the reverse recurrence:
#
#   ghat_t = C_t (x) ybar_t + dA_{t+1} * ghat_{t+1}
#   dtbar  += sum_n ghat*(h_{t-1}*dA*A + x*B);  Abar += sum_l ghat*h_{t-1}*dA*dt
#   xbar   += sum_n ghat*dt*B;  Bbar += sum_d ghat*dt*x;  Cbar = sum_d h*ybar
#
# Traffic: forward streams + one recompute — the MARCA inter-op-BM story
# applied to training.  D-skip and z-gate are handled OUTSIDE (plain jnp,
# autodiff-able), so the custom VJP covers exactly the recurrence core.
# ---------------------------------------------------------------------------


def _fwd_boundaries(x, dt, A, B, C, chunk):
    """Forward over chunks, returning (y, h_last, h_bounds) where
    h_bounds[i] is the state ENTERING chunk i."""
    from repro.core import selective_scan as css
    bsz, L, d = x.shape
    n = A.shape[1]
    nc = -(-L // chunk)
    pad = nc * chunk - L

    def _pad(t):
        return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))

    xs = tuple(_pad(t.astype(jnp.float32)).reshape(
        bsz, nc, chunk, *t.shape[2:]).swapaxes(0, 1)
        for t in (x, dt, B, C))
    Af = A.astype(jnp.float32)

    def step(h, inp):
        xc, dtc, Bc, Cc = inp
        y, h_new = css._scan_inner_seq(xc, dtc, Bc, Cc, Af, h, jnp.exp)
        return h_new, (y, h)

    h0 = jnp.zeros((bsz, d, n), jnp.float32)
    h_last, (ys, h_bounds) = jax.lax.scan(step, h0, xs)
    y = ys.swapaxes(0, 1).reshape(bsz, nc * chunk, d)[:, :L]
    return y, h_last, h_bounds          # h_bounds (nc, b, d, n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def selective_scan_trainable(x, dt, A, B, C, chunk: int = 128,
                             interpret: bool | None = None):
    """Recurrence core with kernel forward + memory-lean backward.
    x/dt (b,L,d); A (d,n); B/C (b,L,n) -> (y (b,L,d) f32, h_last f32)."""
    y, h_last = selective_scan(x, dt, A, B, C, interpret=interpret)
    return y.astype(jnp.float32), h_last


def _sst_fwd(x, dt, A, B, C, chunk, interpret):
    y, h_last = selective_scan(x, dt, A, B, C, interpret=interpret)
    return ((y.astype(jnp.float32), h_last), (x, dt, A, B, C))


def _sst_bwd(chunk, interpret, res, cts):
    from repro.core.selective_scan import _affine_combine as css_affine
    x, dt, A, B, C = res
    ybar, hbar_last = cts
    bsz, L, d = x.shape
    n = A.shape[1]
    nc = -(-L // chunk)
    pad = nc * chunk - L
    # recompute chunk-boundary states (one extra forward, streams only)
    _, _, h_bounds = _fwd_boundaries(x, dt, A, B, C, chunk)

    def _pad(t):
        return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))

    def _chunks(t):
        return _pad(t.astype(jnp.float32)).reshape(
            bsz, nc, chunk, *t.shape[2:]).swapaxes(0, 1)

    xs, dts, Bs, Cs, ybars = map(_chunks, (x, dt, B, C, ybar))
    Af = A.astype(jnp.float32)

    def chunk_bwd(ghat, inp):
        """Reverse over one chunk.  ghat (b,d,n) = dL/dh at chunk end."""
        xc, dtc, Bc, Cc, ybc, h_in = inp
        # rematerialize h_t within the chunk (chunk-sized, not L-sized)
        dA = jnp.exp(dtc[..., None] * Af)                  # (b,ck,d,n)
        dBx = (dtc * xc)[..., None] * Bc[:, :, None, :]
        Acum, Bcum = jax.lax.associative_scan(
            css_affine, (dA, dBx), axis=1)
        h_all = Acum * h_in[:, None] + Bcum                # h_t per step
        h_prev = jnp.concatenate([h_in[:, None], h_all[:, :-1]], axis=1)

        def step(g, t):
            # t runs reversed within the chunk
            ghat_t = Cc[:, t][:, None, :] * ybc[:, t][..., None] + g
            dA_t = dA[:, t]
            gh_prev = ghat_t * dA_t                        # to t-1
            ddA = ghat_t * h_prev[:, t]                    # bar(dA_t)
            ddt = jnp.sum(ddA * dA_t * Af[None], -1) \
                + jnp.sum(ghat_t * Bc[:, t][:, None, :], -1) * xc[:, t]
            dAbar = jnp.sum(ddA * dA_t * dtc[:, t][..., None], 0)
            dx = jnp.sum(ghat_t * Bc[:, t][:, None, :], -1) * dtc[:, t]
            dB = jnp.sum(ghat_t * (dtc[:, t] * xc[:, t])[..., None], 1)
            dC = jnp.sum(h_all[:, t] * ybc[:, t][..., None], 1)
            return gh_prev, (ddt, dAbar, dx, dB, dC)

        ghat_in, outs = jax.lax.scan(step, ghat,
                                     jnp.arange(chunk - 1, -1, -1))
        ddt_r, dAbar_c, dx_r, dB_r, dC_r = outs           # (ck, ...) reversed
        rev = jnp.arange(chunk - 1, -1, -1)
        return ghat_in, (ddt_r[rev].swapaxes(0, 1),
                         dAbar_c.sum(0),
                         dx_r[rev].swapaxes(0, 1),
                         dB_r[rev].swapaxes(0, 1),
                         dC_r[rev].swapaxes(0, 1))

    ghat_L = hbar_last.astype(jnp.float32)
    rev_idx = jnp.arange(nc - 1, -1, -1)
    ghat0, outs = jax.lax.scan(
        chunk_bwd, ghat_L,
        tuple(t[rev_idx] for t in (xs, dts, Bs, Cs, ybars, h_bounds)))
    ddt_c, dA_c, dx_c, dB_c, dC_c = outs                  # (nc, ...) reversed

    def _join(t):
        return t[rev_idx].swapaxes(0, 1).reshape(
            bsz, nc * chunk, *t.shape[3:])[:, :L]

    dxo = _join(dx_c).astype(x.dtype)
    ddto = _join(ddt_c).astype(dt.dtype)
    dBo = _join(dB_c).astype(B.dtype)
    dCo = _join(dC_c).astype(C.dtype)
    dAo = dA_c.sum(0).astype(A.dtype)
    return (dxo, ddto, dAo, dBo, dCo)


selective_scan_trainable.defvjp(_sst_fwd, _sst_bwd)
