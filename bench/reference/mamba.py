"""Plain reference of a Mamba language model (Gu & Dao, arXiv:2312.00752).

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``, following ``mamba_ssm``'s ``Mamba`` block and
``MambaLMHeadModel``: pre-norm residual blocks (RMSNorm, eps 1e-5),
in_proj -> causal depthwise conv with bias -> SiLU -> x_proj ->
softplus(dt_proj + bias) -> selective scan with ``A = -exp(A_log)`` and
the ``D`` skip -> gate by SiLU(z) -> out_proj; a final RMSNorm and the
tied embedding as the output head.  The residual stream stays in f32.
The scan is the sequential recurrence over positions, one layer at a
time, so a long batch fits the device.  It imports nothing of the
program under test: the weights it reads are the benchmark's own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
EPS = 1e-5


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _mm(x, w):
    return jnp.matmul(x, w, precision=HI)


@functools.lru_cache(maxsize=None)
def _layer_fn(d_state: int, d_conv: int, dt_rank: int):
    n, k, r = d_state, d_conv, dt_rank

    def layer(x, layers, i):
        lw = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
            layers)
        m = lw["mixer"]
        T = x.shape[1]
        xz = _mm(_rmsnorm(x, lw["norm"]["scale"]), m["in_proj"]["w"])
        xi, z = jnp.split(xz, 2, axis=-1)
        xp = jnp.pad(xi, ((0, 0), (k - 1, 0), (0, 0)))
        xc = m["conv_b"] + sum(xp[:, i:i + T] * m["conv_w"][i]
                               for i in range(k))
        xa = jax.nn.silu(xc)
        dbc = _mm(xa, m["x_proj"]["w"])
        dt_low, bm, cm = jnp.split(dbc, [r, r + n], axis=-1)
        dt = jax.nn.softplus(_mm(dt_low, m["dt_proj"]["w"]) + m["dt_bias"])
        a = -jnp.exp(m["A_log"])                        # (di, n)

        def step(h, inp):
            x_t, dt_t, b_t, c_t = inp
            h = (jnp.exp(dt_t[..., None] * a) * h
                 + (dt_t * x_t)[..., None] * b_t[:, None, :])
            return h, jnp.sum(h * c_t[:, None, :], axis=-1)

        h0 = jnp.zeros((x.shape[0],) + a.shape, jnp.float32)
        seq = tuple(jnp.moveaxis(t, 1, 0) for t in (xa, dt, bm, cm))
        _, ys = jax.lax.scan(step, h0, seq, unroll=8)
        y = (jnp.moveaxis(ys, 0, 1) + xa * m["D"]) * jax.nn.silu(z)
        return x + _mm(y, m["out_proj"]["w"])
    return jax.jit(layer)


@jax.jit
def _embed(tok, tokens):
    return tok[tokens]


@functools.partial(jax.jit, static_argnames=("n_out",))
def _head(x, norm_f, tok, starts, n_out: int):
    idx = jnp.minimum(starts[:, None] + jnp.arange(n_out)[None, :],
                      x.shape[1] - 1)
    xs = jnp.take_along_axis(x, idx[..., None], axis=1)
    return jnp.einsum("bjd,vd->bjv", _rmsnorm(xs, norm_f), tok,
                      precision=HI)


def logits_along(dims, w, tokens, starts, n_out: int):
    """Logits (B, n_out, V) at positions ``starts[b] + j``, j < n_out,
    of the batch ``tokens`` (B, T) int32.  ``dims`` gives d_state,
    d_conv and dt_rank; ``w`` is the weight tree."""
    layer = _layer_fn(dims.d_state, dims.d_conv, dims.dt_rank)
    x = _embed(w["embed"]["tok"], tokens)
    for i in range(dims.n_layer):
        x = layer(x, w["layers"], jnp.int32(i))
    return _head(x, w["norm_f"]["scale"], w["embed"]["tok"], starts,
                 n_out=n_out)
