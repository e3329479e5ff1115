"""Granite-4.0-H weights from a seed, made on the device layer by layer.

The benchmark owns the weights (as ``weights_nemotronh.py`` does Nemotron's):
the program gets them as its model, the plain reference gets the same ones
made again from the same seed.  One jitted call a layer draws in float32 and
casts to the stated parameter type before it returns, so at most one layer's
largest tensor (the MLP's up projection, 134 MB in float32) and the table
(822 MB) ever exist in float32.

Layout: ``{"embed", "layers": [per-layer dict], "final_norm"}``; NO head:
the head is ``embed`` (``tie_word_embeddings``).  Matrices as ``(in, out)``.
A layer's dict has ``kind`` ``"mamba"`` or ``"attn"`` (a Python string beside
the arrays), the mixer's arrays under ``norm`` (its RMSNorm) and the MLP's
(``mlp_in`` gate then up, ``mlp_out``) under ``norm2``.

Drawn so that the mechanisms matter.  A Mamba-2 mixer as
``weights_nemotronh.py`` spreads it: ``dt_bias`` puts the steps around a
log-uniform (0.001, 0.1), ``a_log`` the rates over (1, 16), so a head's
decay spreads over (0.2, 0.999); the taps of ``B`` and ``C`` are wide (std
0.5), so what the state carries is as large as the skip ``D x``; the taps of
``x`` small and its bias centred (no common vector in every token's
residual, which a TIED head would turn into one favourite token); ``D`` and
the norm scales near one, not one.  The four constants are the config's:
with the table drawn at std 0.01 the embedding enters the stream at 0.12,
each branch leaves its projection (std 0.05) at order one and joins at 0.22
of that, so after forty blocks the stream is a few units wide and a token's
own embedding, which the tied head meets again, lifts its own logit by about
one spread of the logits: it does not decide the next token.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Iterator

import jax
import jax.numpy as jnp

from .counts_granite import dims
from .weights import fold_seed

#: at the published hidden size; :func:`_scale` keeps a smaller model's
#: activations as large (a ``tiny`` rehearsal exercises the same arithmetic)
STD = 0.02
OUT_STD = 0.05
EMBED_STD = 0.01
PUBLISHED_HIDDEN = 2048


def _scale(hidden: int) -> float:
    """What the three spreads are multiplied by at another hidden size: a
    projection's output is as wide as at 2,048 (exactly 1 there)."""
    return (PUBLISHED_HIDDEN / hidden) ** 0.5


def _draws(key, dtype):
    ks = iter(jax.random.split(key, 24))

    def normal(shape, s=STD):
        return (s * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dtype)

    def gain(shape):
        return (1.0 + STD * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)
    return normal, gain, uniform


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _layer(key, kind: str, d: tuple, dtype):
    (hidden, heads, kv_heads, head_dim, m_heads, inner, conv_dim, conv,
     mlp_dim) = d
    normal, gain, uniform = _draws(key, dtype)
    std, out_std = STD * _scale(hidden), OUT_STD * _scale(hidden)
    w: Dict[str, Any] = {"norm": gain((hidden,)), "norm2": gain((hidden,)),
                         "mlp_in": normal((hidden, 2 * mlp_dim), std),
                         "mlp_out": normal((mlp_dim, hidden), out_std)}
    if kind == "attn":
        w.update(wq=normal((hidden, heads * head_dim), std),
                 wk=normal((hidden, kv_heads * head_dim), std),
                 wv=normal((hidden, kv_heads * head_dim), std),
                 wo=normal((heads * head_dim, hidden), out_std))
    else:
        dt = jnp.exp(uniform((m_heads,), -6.908, -2.303))   # (0.001, 0.1)
        w.update(w_in=normal((hidden, inner + conv_dim + m_heads), std),
                 conv_w=jnp.concatenate(
                     [normal((conv, inner), 0.1),
                      normal((conv, conv_dim - inner), 0.5)], axis=1),
                 # x's bias small and centred where SiLU's mean over the
                 # taps' spread (0.2) is zero: m / 2 + 0.2^2 / 4 = 0
                 conv_b=jnp.concatenate(
                     [normal((inner,), 0.005) - jnp.asarray(0.02, dtype),
                      normal((conv_dim - inner,), 0.05)]),
                 # softplus(dt_bias) = dt
                 dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                 a_log=uniform((m_heads,), 0.0, 2.773).astype(dtype),
                 d_skip=gain((m_heads,)), gnorm=gain((inner,)),
                 w_out=normal((inner, hidden), out_std))
    return w


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _ends(key, hidden: int, vocab: int, dtype):
    normal, gain, _ = _draws(key, dtype)
    return {"embed": normal((vocab, hidden), EMBED_STD * _scale(hidden)),
            "final_norm": gain((hidden,))}


def weight_parts(cfg: Dict, seed: int,
                 dtype: str = "bfloat16") -> Iterator[Dict]:
    """The weights one part at a time, each made when it is asked for: first
    ``{"embed", "final_norm"}``, then a layer's dict after a layer's dict.
    The draws do not depend on who asks, or when."""
    d = dims(cfg)
    dt = jnp.dtype(dtype)
    key = jax.random.PRNGKey(fold_seed(seed))
    shape = (d["hidden"], d["heads"], d["kv_heads"], d["head_dim"],
             d["m_heads"], d["inner"], d["conv_dim"], d["conv"],
             d["mlp_dim"])
    yield _ends(jax.random.fold_in(key, 0), d["hidden"], d["vocab"], dt)
    for i, kind in enumerate(d["kinds"]):
        yield dict(_layer(jax.random.fold_in(key, i + 1), kind, shape, dt),
                   kind=kind)


def make_weights(cfg: Dict, seed: int, dtype: str = "bfloat16") -> Dict:
    """The weights of ``cfg`` from ``seed`` on the default device, in
    ``dtype`` (the configuration's ``precision.params``)."""
    parts = weight_parts(cfg, seed, dtype)
    w = next(parts)
    w["layers"] = list(parts)
    return w
