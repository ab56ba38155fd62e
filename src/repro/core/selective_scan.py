"""Selective-SSM scan algorithms (the compute core MARCA accelerates).

Three implementations with identical semantics (tests assert equivalence):

  * ``selective_scan_seq``     — lax.scan over time; the semantic reference.
  * ``selective_scan_assoc``   — jax.lax.associative_scan over the (a, b)
    affine monoid; O(log L) depth but materializes (B, L, D, N) — the
    "unfused XLA" baseline whose HBM traffic MARCA's fusion removes.
  * ``selective_scan_chunked`` — lax.scan over chunks of length `chunk`,
    associative scan inside a chunk, state carried across chunks.  This is
    the framework-level realization of MARCA's *inter-operation buffer
    management*: the recurrent state (and the chunk's dA/dBx intermediates)
    stay in registers/VMEM instead of round-tripping HBM per operation.
    With ``remat=True`` the inner chunk is wrapped in jax.checkpoint so
    training saves only chunk-boundary states (the paper's "cache h in the
    buffer" applied to the backward pass).

The Pallas kernel (repro.kernels.selective_scan) implements the fully fused
single-pass version for TPU and is validated against ``selective_scan_seq``.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.core import approx, state_quant
from repro.kernels import backend
from repro.kernels import ref as kref

selective_scan_seq = kref.selective_scan
selective_state_step = kref.selective_state_step


def _affine_combine(left, right):
    """Monoid for h_t = a_t h_{t-1} + b_t (left = older, right = newer)."""
    a1, b1 = left
    a2, b2 = right
    return a2 * a1, a2 * b1 + b2


def _scan_inner(xf, dtf, Bf, Cf, Af, h_in, exp):
    """Associative scan over one chunk.  xf/dtf (b,ck,d); Bf/Cf (b,ck,n)."""
    dA = exp(dtf[..., None] * Af)                       # (b,ck,d,n)
    dBx = (dtf * xf)[..., None] * Bf[:, :, None, :]     # (b,ck,d,n)
    Acum, Bcum = jax.lax.associative_scan(
        _affine_combine, (dA, dBx), axis=1)
    h_all = Acum * h_in[:, None] + Bcum                 # (b,ck,d,n)
    y = jnp.einsum("bldn,bln->bld", h_all, Cf)
    return y, h_all[:, -1]


def _scan_inner_seq(xf, dtf, Bf, Cf, Af, h_in, exp):
    """Sequential scan over one chunk: per-step (b,d,n) intermediates fuse
    into the loop body — no (b,ck,d,n) materialization.  With the chunk
    wrapped in jax.checkpoint this is the MARCA dataflow at XLA level:
    state resident, inputs streamed (in their storage dtype — cast to f32
    per step so the streamed tensors stay bf16), residuals only at chunk
    boundaries."""
    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp
        dA = exp(dt_t[..., None] * Af)
        h = dA * h + (dt_t * x_t)[..., None] * B_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, C_t)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (xf, dtf, Bf, Cf))
    h_last, ys = jax.lax.scan(step, h_in, xs)
    return jnp.moveaxis(ys, 0, 1), h_last


def selective_scan_assoc(x, dt, A, B, C, D=None, z=None, h0=None,
                         exp_impl: str = "exact", silu_impl: str = "exact"):
    """Single associative scan over the full length (XLA baseline)."""
    return selective_scan_chunked(x, dt, A, B, C, D=D, z=z, h0=h0,
                                  chunk=x.shape[1], remat=False,
                                  exp_impl=exp_impl, silu_impl=silu_impl)


def selective_scan_chunked(x, dt, A, B, C, D=None, z=None, h0=None,
                           chunk: int = 64, remat: bool = True,
                           exp_impl: str = "exact",
                           silu_impl: str = "exact",
                           inner: str = "assoc"):
    """Chunked scan: state carried across chunks (inter-op buffer mgmt).

    Same signature/semantics as kernels.ref.selective_scan.
    """
    exp = approx.get_exp(exp_impl)
    silu = approx.get_silu(silu_impl)
    bsz, L, d = x.shape
    n = A.shape[1]
    chunk = min(chunk, L)
    pad = (-L) % chunk
    nc = (L + pad) // chunk

    def _pad(t):
        return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))

    xf = _pad(x.astype(jnp.float32))
    dtf = _pad(dt.astype(jnp.float32))
    Bf = _pad(B.astype(jnp.float32))
    Cf = _pad(C.astype(jnp.float32))
    Af = A.astype(jnp.float32)
    h_init = (jnp.zeros((bsz, d, n), jnp.float32) if h0 is None
              else h0.astype(jnp.float32))

    def _resh(t):
        return t.reshape(bsz, nc, chunk, *t.shape[2:]).swapaxes(0, 1)

    inner_fn = _scan_inner if inner == "assoc" else _scan_inner_seq
    if remat:
        inner_fn = jax.checkpoint(inner_fn, static_argnums=(6,))

    def chunk_step(h, inp):
        xc, dtc, Bc, Cc = inp
        y, h_new = inner_fn(xc, dtc, Bc, Cc, Af, h, exp)
        return h_new, y

    h_last, ys = jax.lax.scan(
        chunk_step, h_init, (_resh(xf), _resh(dtf), _resh(Bf), _resh(Cf)))
    y = ys.swapaxes(0, 1).reshape(bsz, nc * chunk, d)[:, :L]
    if D is not None:
        y = y + D.astype(jnp.float32)[None, None, :] * x.astype(jnp.float32)
    if z is not None:
        y = y * silu(z.astype(jnp.float32))
    return y.astype(x.dtype), h_last


IMPLS = {
    "seq": selective_scan_seq,
    "assoc": selective_scan_assoc,
    "chunked": selective_scan_chunked,
}


def get_scan(name: str):
    if name in IMPLS:
        return IMPLS[name]
    if name == "pallas":    # resolved lazily to avoid import cycle
        from repro.kernels import selective_scan as ssk
        return ssk.selective_scan
    raise KeyError(f"unknown scan impl {name!r}")


# ---------------------------------------------------------------------------
# Single-token decode step (the serving engine's per-layer hot path)
# ---------------------------------------------------------------------------

def resolve_step_impl(name: str, needs_pallas: bool = True,
                      megakernel_vmem: int | None = None) -> str:
    """Resolve cfg.step_impl to a concrete impl.

    "auto" on a TPU picks the cross-layer megakernel when one grid
    step's VMEM need — ``megakernel_vmem``, which the caller computes
    from the operands it would launch with (weights and pooled state of
    one layer at the served widths and slot count,
    kernels.decode_step.stacked_layer_vmem_bytes) — fits the chip's
    kernel VMEM budget, and the per-layer fused kernel otherwise (or
    when the caller gives no need: a family without a megakernel
    estimate).  Off TPU it keeps the split that served before: fused
    where it is pure XLA (``needs_pallas=False``, e.g. xLSTM's chained
    paths), the XLA reference elsewhere.  Both inputs are observed, not
    configured.  The ``REPRO_STEP_IMPL`` env var overrides "auto" only
    — explicit config always wins — so CI can sweep the whole suite
    over an impl without touching configs.  Callers can force any impl
    with "megakernel" / "fused" / "xla" (parity tests and TPU-less
    benchmarking do)."""
    if name == "auto":
        name = os.environ.get("REPRO_STEP_IMPL", "auto")
    if name == "auto":
        if backend.on_tpu():
            fits = (megakernel_vmem is not None
                    and megakernel_vmem <= backend.vmem_budget_bytes())
            return "megakernel" if fits else "fused"
        if not needs_pallas:
            return "fused"
        return "xla"
    if name == "megakernel":
        return "megakernel"
    if name in ("fused", "pallas"):
        return "fused"
    if name == "xla":
        return "xla"
    raise KeyError(f"unknown step impl {name!r}")


def resolve_cell_impl(name: str, needs_pallas: bool = True) -> str:
    """Resolve cfg.step_impl for a PER-LAYER call site.

    The megakernel is a whole-stack launch; block-level entry points
    (single-layer steps, verify windows, drafts running a layer slice
    chained) can't use it directly — under a megakernel config they run
    the per-layer fused cell, which computes bit-identical values (the
    megakernel body is the same cell skeleton at the same shapes)."""
    r = resolve_step_impl(name, needs_pallas)
    return "fused" if r == "megakernel" else r


def _per_channel_shard(fn, args, axes, out_axes, group: int = 1):
    """Call ``fn(*args)`` — a fused Pallas step — once per channel shard
    of the active mesh.

    A Mosaic kernel cannot be partitioned automatically, and the decode
    step is independent across d_inner channels, so under a mesh each
    device runs the kernel on the channels the "act_ffn" rule gives it
    (where the pooled state already lives).  ``axes[i]`` is args[i]'s
    channel axis (None: replicated; a None arg passes through);
    ``out_axes`` the outputs' (axis, ndim).  A shard must hold a whole
    number of ``group`` channels (quantized state keeps one scale per
    D_BLOCK channels); otherwise every device runs the whole step.
    Without a mesh this is ``fn(*args)``."""
    from jax.sharding import PartitionSpec as P
    from repro.parallel import sharding
    mesh = sharding.active_mesh()
    if mesh is None:
        return fn(*args)
    name = sharding.logical_to_spec(("act_ffn",))[0]
    d = args[0].shape[1]
    if name is not None and d % (mesh.shape[name] * group):
        name = None

    def spec(ax, ndim):
        return P(*(name if i == ax else None for i in range(ndim)))

    live = [i for i, a in enumerate(args) if a is not None]

    def body(*xs):
        full = list(args)
        for i, x in zip(live, xs):
            full[i] = x
        return fn(*full)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(spec(axes[i], args[i].ndim) for i in live),
        out_specs=tuple(spec(ax, nd) for ax, nd in out_axes),
        check_vma=False)(*(args[i] for i in live))


def decode_step(h, x_t, dt_t, A, B_t, C_t, D=None, z_t=None,
                impl: str = "xla",
                exp_impl: str = "exact", silu_impl: str = "exact",
                a_scale=None):
    """One fused-or-reference SSM decode step over the (pooled) batch.

    h (b, d, n) f32; x_t/dt_t (b, d); A (d, n); B_t/C_t (b, n).
    Returns (y (b, d), h_new (b, d, n) f32).  ``impl="fused"`` runs the
    single-launch Pallas kernel (interpret-mode on CPU); "xla" the
    pure-jnp reference with identical semantics.

    ``a_scale`` (d,) marks A as int8 weight codes (cfg.weight_dtype):
    the fused kernel dequantizes in its dequant phase; the XLA path runs
    the identical ``weight_quant.dequantize_rows`` multiply up front, so
    both impls consume bit-identical A values."""
    if a_scale is not None and impl == "xla":
        from repro.core import weight_quant
        A = weight_quant.dequantize_rows(A, a_scale)
        a_scale = None
    if impl in ("fused", "pallas"):
        from repro.kernels import decode_step as dsk   # lazy: import cycle

        def fused(h, x_t, dt_t, A, B_t, C_t, D, z_t, a_scale):
            return dsk.selective_state_step(
                h, x_t, dt_t, A, B_t, C_t, D=D, z_t=z_t,
                exp_impl=exp_impl, silu_impl=silu_impl, a_scale=a_scale)
        return _per_channel_shard(
            fused, (h, x_t, dt_t, A, B_t, C_t, D, z_t, a_scale),
            (1, 1, 1, 0, None, None, 0, 1, 0), ((1, 2), (1, 3)))
    if impl != "xla":
        # "auto" must go through resolve_step_impl first; a typo or raw
        # cfg string silently falling back to the unfused path would eat
        # the fused kernel's win with no error anywhere
        raise KeyError(f"unknown step impl {impl!r}")
    return kref.selective_state_step(
        h, x_t, dt_t, A, B_t, C_t, D=D, z_t=z_t,
        exp_impl=exp_impl, silu_impl=silu_impl)


def decode_step_q(hq, h_scale, x_t, dt_t, A, B_t, C_t, D=None, z_t=None,
                  state_dtype: str = "int8", impl: str = "xla",
                  exp_impl: str = "exact", silu_impl: str = "exact",
                  a_scale=None):
    """Quantized-state decode step (cfg.state_dtype in {int8, fp8}).

    hq (b, d, n) storage payload, h_scale (b, g) f32 group scales (see
    core.state_quant); returns (y (b, d), hq_new, scale_new).  "fused"
    dequantizes/requantizes inside the single Pallas launch; "xla" is
    the dequant -> ref step -> requant oracle with identical scale math
    (the two match to within one quantization code — XLA may contract
    da*h + dbx into an FMA, which can flip a value sitting exactly on a
    rounding boundary)."""
    if a_scale is not None and impl == "xla":
        from repro.core import weight_quant
        A = weight_quant.dequantize_rows(A, a_scale)
        a_scale = None
    if impl in ("fused", "pallas"):
        from repro.kernels import decode_step as dsk   # lazy: import cycle

        def fused(hq, h_scale, x_t, dt_t, A, B_t, C_t, D, z_t, a_scale):
            return dsk.selective_state_step_q(
                hq, h_scale, x_t, dt_t, A, B_t, C_t, D=D, z_t=z_t,
                state_dtype=state_dtype, exp_impl=exp_impl,
                silu_impl=silu_impl, a_scale=a_scale)
        return _per_channel_shard(
            fused, (hq, h_scale, x_t, dt_t, A, B_t, C_t, D, z_t, a_scale),
            (1, 1, 1, 1, 0, None, None, 0, 1, 0),
            ((1, 2), (1, 3), (1, 2)), group=state_quant.D_BLOCK)
    if impl != "xla":
        raise KeyError(f"unknown step impl {impl!r}")
    return kref.selective_state_step_q(
        hq, h_scale, x_t, dt_t, A, B_t, C_t, D=D, z_t=z_t,
        state_dtype=state_dtype, exp_impl=exp_impl, silu_impl=silu_impl)


# ---------------------------------------------------------------------------
# K-step verify micro-scan (speculative decode)
#
# Verifying K drafted tokens means running the target's per-token step K
# times from a known state and keeping EVERY intermediate state: the
# accepted prefix length is only known after the pass, and rollback
# needs the state after exactly that many steps.  Each micro-scan step
# is the SAME decode_step dispatch the serving burst uses (one fused
# Pallas launch per step under impl="fused"), so verify-pass numerics
# are the per-token decode numerics — the property the token-identical
# spec-decode gate rests on.
# ---------------------------------------------------------------------------

def decode_scan(h, x_seq, dt_seq, A, B_seq, C_seq, D=None, z_seq=None,
                impl: str = "xla",
                exp_impl: str = "exact", silu_impl: str = "exact",
                a_scale=None):
    """Chain ``decode_step`` over a K-token window.

    h (b, d, n) f32 start state; x_seq/dt_seq (b, K, d); B_seq/C_seq
    (b, K, n); z_seq (b, K, d)|None.  Returns (y_seq (b, K, d),
    h_all (b, K, d, n)) — h_all[:, t] is the state after consuming
    token t (rollback picks an index into it)."""
    has_z = z_seq is not None

    def step(h_c, inp):
        x_t, dt_t, B_t, C_t = inp[:4]
        z_t = inp[4] if has_z else None
        y, h_new = decode_step(h_c, x_t, dt_t, A, B_t, C_t, D=D, z_t=z_t,
                               impl=impl, exp_impl=exp_impl,
                               silu_impl=silu_impl, a_scale=a_scale)
        return h_new, (y, h_new)

    seqs = (x_seq, dt_seq, B_seq, C_seq) + ((z_seq,) if has_z else ())
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in seqs)
    _, (ys, hs) = jax.lax.scan(step, h, xs)
    return jnp.moveaxis(ys, 0, 1), jnp.moveaxis(hs, 0, 1)


def decode_scan_q(hq, h_scale, x_seq, dt_seq, A, B_seq, C_seq, D=None,
                  z_seq=None, state_dtype: str = "int8", impl: str = "xla",
                  exp_impl: str = "exact", silu_impl: str = "exact",
                  a_scale=None):
    """Quantized-state K-step micro-scan: chains ``decode_step_q`` so the
    storage round-trip (dequant on read, decayed-absmax requant on
    write) happens per step exactly as in serving — the per-step
    payloads AND scales come back stacked, because rolling back to step
    t must restore both together.

    Returns (y_seq (b, K, d), hq_all (b, K, d, n), scale_all (b, K, g)).
    """
    has_z = z_seq is not None

    def step(carry, inp):
        hq_c, s_c = carry
        x_t, dt_t, B_t, C_t = inp[:4]
        z_t = inp[4] if has_z else None
        y, hq_new, s_new = decode_step_q(
            hq_c, s_c, x_t, dt_t, A, B_t, C_t, D=D, z_t=z_t,
            state_dtype=state_dtype, impl=impl, exp_impl=exp_impl,
            silu_impl=silu_impl, a_scale=a_scale)
        return (hq_new, s_new), (y, hq_new, s_new)

    seqs = (x_seq, dt_seq, B_seq, C_seq) + ((z_seq,) if has_z else ())
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in seqs)
    _, (ys, hqs, ss) = jax.lax.scan(step, (hq, h_scale), xs)
    return (jnp.moveaxis(ys, 0, 1), jnp.moveaxis(hqs, 0, 1),
            jnp.moveaxis(ss, 0, 1))
