"""A configuration's sizes, and its weights made from the seed.

The weights are the benchmark's, not the program's: the plain reference
reads the same arrays, so it takes nothing the program made.  They are
drawn in one jitted call on the device, in f32 (the configurations
serve f32 weights).  The SSM parameters follow the published Mamba
initialisation (``mamba_ssm/modules/mamba_simple.py``): the conv
PyTorch's default uniform, ``dt_proj`` uniform in +-dt_rank^-1/2, the
dt bias the inverse softplus of a log-uniform dt in [0.001, 0.1],
S4D-real ``A_log``, ``D`` = 1, unit norm scales, and the tied embedding
normal with std 0.02.  The three projections are normal with variance
1/fan_in and ``out_proj`` is not rescaled by 1/sqrt(n_layer) as the
published training init does: with that rescale an untrained stack
copies its last input token (the tied head maps the residual stream's
own embedding back to it, by a margin of about 4 logits), so greedy
tokens would not depend on the layers, the state or the decode path,
and the check could not see a fault in them.  With these projections
every layer moves the logits and the top tokens lie within tenths of a
logit of each other, as in a trained model.  The tree has the layout the
program's ``Engine`` takes; ``serve.check_layout`` compares it with the
program's abstract parameters before a run.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Dims:
    d_model: int
    n_layer: int
    vocab: int          # rows of the embedding as served (padded)
    vocab_real: int     # ids a prompt may hold
    d_state: int
    d_conv: int
    expand: int
    dt_rank: int

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model


def dims(conf: dict) -> Dims:
    """Sizes from a configuration file (``bench/configs/<name>.json``)."""
    ssm = conf["ssm_cfg_defaults"]
    d = conf["d_model"]
    r = ssm["dt_rank"]
    return Dims(d_model=d, n_layer=conf["n_layer"],
                vocab=conf["padded_vocab_size"],
                vocab_real=conf["vocab_size"], d_state=ssm["d_state"],
                d_conv=ssm["d_conv"], expand=ssm["expand"],
                dt_rank=math.ceil(d / 16) if r == "auto" else int(r))


def key_of(seed: int) -> jax.Array:
    """A weights key from any whole-number seed."""
    word = np.random.SeedSequence([int(seed), 3]).generate_state(1)[0]
    return jax.random.key(int(word) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _init_fn(dm: Dims):
    d, L, V = dm.d_model, dm.n_layer, dm.vocab
    di, n, k, r = dm.d_inner, dm.d_state, dm.d_conv, dm.dt_rank

    def uniform(key, shape, bound):
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)

    def normal(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5

    def init(key):
        ks = jax.random.split(key, 9)
        dt = jnp.exp(jax.random.uniform(ks[6], (L, di))
                     * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        dt = jnp.maximum(dt, 1e-4)
        a = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
        mixer = {
            "in_proj": {"w": normal(ks[0], (L, d, 2 * di), d)},
            "conv_w": uniform(ks[1], (L, k, di), k ** -0.5),
            "conv_b": uniform(ks[2], (L, di), k ** -0.5),
            "x_proj": {"w": normal(ks[3], (L, di, r + 2 * n), di)},
            "dt_proj": {"w": uniform(ks[4], (L, r, di), r ** -0.5)},
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.broadcast_to(a, (L, di, n)),
            "D": jnp.ones((L, di), jnp.float32),
            "out_proj": {"w": normal(ks[5], (L, di, d), di)},
        }
        return {
            "embed": {"tok": jax.random.normal(ks[7], (V, d), jnp.float32)
                      * 0.02},
            "layers": {"norm": {"scale": jnp.ones((L, d), jnp.float32)},
                       "mixer": mixer},
            "norm_f": {"scale": jnp.ones((d,), jnp.float32)},
            "unembed": {},
        }
    return jax.jit(init)


def make(dm: Dims, seed: int):
    """The configuration's weights for ``seed``, on the default device."""
    return jax.block_until_ready(_init_fn(dm)(key_of(seed)))
