"""Nemotron-3-Nano weights from a seed, made on the device layer by layer.

The benchmark owns the weights (as ``weights_solar.py`` does Solar's): the
program gets them as its model, the plain reference gets the same ones made
again from the same seed.  One jitted call a layer draws in float32 and casts
to the stated parameter type before it returns, so at most one layer's largest
tensor (the 64 held experts' up projections, 1.3 GB in float32) ever exists in
float32.

Layout: ``{"embed", "layers": [per-layer dict], "final_norm", "head"}``,
matrices as ``(in, out)``, experts stacked over the HELD experts.  A layer's
dict has ``kind`` ``"mamba"``, ``"attn"`` or ``"experts"`` (a Python string
beside the arrays) and ONE part's arrays under ``norm`` (the part's RMSNorm).

Drawn so that the mechanisms matter: ``dt_bias`` puts the steps ``dt =
softplus(. + dt_bias)`` around a log-uniform (0.001, 0.1) and ``a_log`` the
rates ``exp(a_log)`` over (1, 16), as Mamba-2's own initialisation does, so a
head's decay ``exp(-exp(a_log) dt)`` spreads over (0.2, 0.999); the taps of
``B`` and ``C`` are wide (std 0.5), so ``C_t . B_s`` is of order ten and what
the state carries from earlier tokens is as large as the skip ``D x``; ``D``
and the norm scales are near one, not one.

Drawn so that the router is BALANCED, as a trained one is (PERF.md section 6,
PR 32: a seed's step time is its router's lottery unless the draw loads every
share alike).  SiLU after the convolution gives ``x`` a positive mean in every
channel; carried through ``D x``, the gate and ``W_out`` that is ONE common
vector in every token's residual, which the next router turns into a
per-expert offset drawn with the seed.  So the taps of ``x`` are small (std
0.1: the SiLU is used where it is nearly odd, and the common vector holds about
a hundredth of the energy of the next router's input; ``tests/`` measures it
at the published widths), the router's columns come in PAIRS ``w, -w`` inside
each share of ``held`` experts, and so does the selection bias
``e_score_correction_bias`` (``b, -b``; std 0.02, nonzero: a few hundredths on
scores whose top six lie a few hundredths apart, so selecting with and without
it differs on a measurable share of tokens, which ``tests/`` counts).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Iterator

import jax
import jax.numpy as jnp

from .counts_nemotronh import dims
from .weights import fold_seed

STD = 0.02
BIAS_STD = 0.02


def _draws(key, dtype):
    ks = iter(jax.random.split(key, 24))

    def normal(shape, s=STD, to=dtype):
        return (s * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(to)

    def gain(shape):
        return (1.0 + STD * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)
    return normal, gain, uniform


def _twinned(draw, lead: tuple, experts: int, held: int):
    """(..., experts) whose every share of ``held`` columns is ``[w | -w]``."""
    twins = draw(lead + (experts // held, held // 2))
    return jnp.concatenate([twins, -twins], axis=-1).reshape(
        lead + (experts,))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _layer(key, kind: str, d: tuple, dtype):
    (hidden, heads, kv_heads, head_dim, m_heads, inner, conv_dim, conv,
     experts, held, expert_dim, shared_dim, layers) = d
    normal, gain, uniform = _draws(key, dtype)
    out_std = STD / float(layers) ** 0.5    # rescale_prenorm_residual
    w: Dict[str, Any] = {"norm": gain((hidden,))}
    if kind == "attn":
        w.update(wq=normal((hidden, heads * head_dim)),
                 wk=normal((hidden, kv_heads * head_dim)),
                 wv=normal((hidden, kv_heads * head_dim)),
                 wo=normal((heads * head_dim, hidden), out_std))
    elif kind == "mamba":
        dt = jnp.exp(uniform((m_heads,), -6.908, -2.303))   # (0.001, 0.1)
        w.update(w_in=normal((hidden, inner + conv_dim + m_heads)),
                 # small taps on x: no common mode, a balanced router
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
    else:
        w.update(
            # each share of ``held`` experts: columns w and their twins -w
            router=_twinned(normal, (hidden,), experts, held),
            # the selection bias, float32 as the router: b and twins -b
            router_bias=_twinned(
                lambda shape: normal(shape, BIAS_STD, jnp.float32), (),
                experts, held),
            w_in=normal((held, hidden, expert_dim)),
            w_out=normal((held, expert_dim, hidden), out_std),
            shared_in=normal((hidden, shared_dim)),
            shared_out=normal((shared_dim, hidden), out_std))
    return w


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _ends(key, hidden: int, vocab: int, dtype):
    normal, gain, _ = _draws(key, dtype)
    return {"embed": normal((vocab, hidden)), "final_norm": gain((hidden,)),
            "head": normal((hidden, vocab))}


def weight_parts(cfg: Dict, seed: int,
                 dtype: str = "bfloat16") -> Iterator[Dict]:
    """The weights one part at a time, each made when it is asked for: first
    ``{"embed", "final_norm", "head"}``, then a layer's dict after a layer's
    dict.  A caller that lays each part out before it asks for the next
    (``program_nemotronh.build_engine``) never holds two forms of the whole
    model; the draws do not depend on who asks, or when."""
    d = dims(cfg)
    dt = jnp.dtype(dtype)
    key = jax.random.PRNGKey(fold_seed(seed))
    shape = (d["hidden"], d["heads"], d["kv_heads"], d["head_dim"],
             d["m_heads"], d["inner"], d["conv_dim"], d["conv"],
             d["experts"], d["held"], d["expert_dim"], d["shared_dim"],
             d["layers"])
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
