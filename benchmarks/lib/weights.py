"""GPT-2 weights from a seed, made on the device in one jitted call.

The benchmark owns the weights: the program gets them as its initial model,
the plain reference gets the same ones made again from the same seed, and
neither takes anything the other has made.  Layout: a flat dict whose block
leaves are stacked over layers (leading axis ``n_layer``), matrices as
``(in, out)``.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from .counts import widths


def fold_seed(seed: int) -> int:
    """Any whole number (the driver's exceed 2**31) to a 31-bit seed that
    numpy, jax and the program's own ``seed + k`` arithmetic all take."""
    return int(seed) % (2 ** 31 - 1024)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _make(key, d, layers, inner, vocab, positions, dtype):
    ks = iter(jax.random.split(key, 24))
    std = 0.02
    proj_std = std / (2.0 * layers) ** 0.5  # GPT-2's residual-projection init

    def normal(shape, s=std):
        return (s * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dtype)

    def gain(shape):
        return (1.0 + std * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dtype)

    L = layers
    return {
        "wte": normal((vocab, d)), "wpe": normal((positions, d)),
        "ln1_g": gain((L, d)), "ln1_b": normal((L, d)),
        "wq": normal((L, d, d)), "bq": normal((L, d)),
        "wk": normal((L, d, d)), "bk": normal((L, d)),
        "wv": normal((L, d, d)), "bv": normal((L, d)),
        "wo": normal((L, d, d), proj_std), "bo": normal((L, d)),
        "ln2_g": gain((L, d)), "ln2_b": normal((L, d)),
        "w1": normal((L, d, inner)), "b1": normal((L, inner)),
        "w2": normal((L, inner, d), proj_std), "b2": normal((L, d)),
        "lnf_g": gain((d,)), "lnf_b": normal((d,)),
        "head_w": normal((d, vocab)), "head_b": normal((vocab,)),
    }


def make_weights(cfg: Dict, seed: int, dtype: str = "float32") -> Dict:
    """The stacked weights of ``cfg`` from ``seed``: normal(0, 0.02) matrices,
    biases and tables (GPT-2's init, with small random biases and gains near
    one so that no term of the forward pass is multiplied by exactly 0 or
    1), on the default device, in ``dtype``."""
    w = widths(cfg)
    return _make(jax.random.PRNGKey(fold_seed(seed)), w["d"], w["layers"],
                 w["inner"], w["vocab"], w["positions"], jnp.dtype(dtype))
