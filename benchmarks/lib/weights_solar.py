"""Solar-Open2 weights from a seed, made on the device layer by layer.

The benchmark owns the weights (as ``weights.py`` does GPT-2's): the program
gets them as its model, the plain reference gets the same ones made again from
the same seed.  One jitted call a layer draws in float32 and casts to the
stated parameter type before it returns, so at most one layer's largest tensor
(the 40 held experts' gate and up, 1.7 GB) ever exists in float32: never the
13 GB the whole model would take.

Layout: ``{"embed", "layers": [per-layer dict], "final_norm", "head"}``,
matrices as ``(in, out)``, experts stacked over the HELD experts, gate and up
side by side (``[gate | up]`` on the last axis).  A layer's dict has ``kind``
``"gqa"`` or ``"kda"`` (a Python string beside the arrays).

Drawn so that the mechanisms matter: ``a_log`` and ``dt_bias`` spread the
per-channel decays ``alpha = exp(-exp(a_log) softplus(. + dt_bias))`` over
(0.005, 0.99), the beta projection is wide enough that ``beta = 2 sigmoid(.)``
spreads over most of (0, 2) (so the negative-eigenvalue branch, beta > 1, is
taken about half the time), and norm scales are near one, not one.

Drawn so that the router is BALANCED, as a trained one is: the convolution
taps of q and k are small (std 0.1, v's 0.5), so that q and k reach their SiLU
where it is nearly odd and come out with nearly no mean.  With taps of 0.5 on
all three SiLU gives every channel of q, k and v a positive mean of half its
spread: ``q . k`` is positive on average, every output of a KDA layer carries
one common vector (17 to 20 % of the energy of the next router's input,
whatever the token), the router turns that vector into a per-expert bias
drawn with the seed, and which experts run hot — and how many of them this
chip holds — is a lottery of the seed: 18.9 to 22.5 of the 40 held experts
touched a layer-step at the same load, and a decode step up to 8 % longer
(PERF.md section 6, PR 32).  A trained router does not do
that (its load-balance loss is there to prevent it); with the common vector
gone (1 % of the energy) every layer's routing is as even as the first
layer's, which reads token embeddings.  What is left, a popularity that
differs by a quarter from expert to expert, still moves this chip's share of
the assignments by 4 % a layer from seed to seed (40 experts are few), so the
router's columns come in PAIRS ``w, -w`` inside each share of ``held`` experts:
whatever bias a seed's common vector gives an expert, its twin gets the
opposite, and a share's load is an eighth to first order — what a deployment
gets by placing its experts by load.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .counts_solar import dims
from .weights import fold_seed

STD = 0.02


def _draws(key, dtype):
    ks = iter(jax.random.split(key, 40))

    def normal(shape, s=STD):
        return (s * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dtype)

    def gain(shape):
        return (1.0 + STD * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi
                                  ).astype(dtype)
    return normal, gain, uniform


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _layer(key, kind: str, d: tuple, dtype):
    (hidden, heads, kv_heads, head_dim, lin_heads, lin_dim, conv, rank,
     experts, held, expert_dim, shared_dim, layers) = d
    normal, gain, uniform = _draws(key, dtype)
    out_std = STD / (2.0 * layers) ** 0.5   # residual projections
    twins = normal((hidden, experts // held, held // 2))
    w: Dict[str, Any] = {
        "norm1": gain((hidden,)), "norm2": gain((hidden,)),
        # each share of ``held`` experts: columns w and their twins -w
        "router": jnp.concatenate([twins, -twins], axis=2
                                  ).reshape(hidden, experts),
        "w_in": normal((held, hidden, 2 * expert_dim)),
        "w_out": normal((held, expert_dim, hidden), out_std),
        "shared_in": normal((hidden, 2 * shared_dim)),
        "shared_out": normal((shared_dim, hidden), out_std),
    }
    if kind == "gqa":
        inner = heads * head_dim
        w.update(wq=normal((hidden, inner)),
                 wk=normal((hidden, kv_heads * head_dim)),
                 wv=normal((hidden, kv_heads * head_dim)),
                 wg=normal((hidden, inner)),
                 wo=normal((inner, hidden), out_std))
    else:
        inner = lin_heads * lin_dim
        w.update(wq=normal((hidden, inner)), wk=normal((hidden, inner)),
                 wv=normal((hidden, inner)),
                 wo=normal((inner, hidden), out_std),
                 # small taps on q and k: no common mode, a balanced router
                 conv_q=normal((conv, inner), 0.1),
                 conv_k=normal((conv, inner), 0.1),
                 conv_v=normal((conv, inner), 0.5),
                 wb=normal((hidden, lin_heads), 2 * STD),
                 wf_down=normal((hidden, rank)),
                 wf_up=normal((rank, inner)),
                 wg_down=normal((hidden, rank)),
                 wg_up=normal((rank, inner)),
                 # exp(a_log) in (1/4, 4); softplus(dt_bias) in (0.05, 1.3)
                 a_log=uniform((lin_heads,), -1.386, 1.386),
                 dt_bias=uniform((inner,), -3.0, 1.0),
                 o_norm=gain((lin_dim,)))
    return w


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _ends(key, hidden: int, vocab: int, dtype):
    normal, gain, _ = _draws(key, dtype)
    return {"embed": normal((vocab, hidden)), "final_norm": gain((hidden,)),
            "head": normal((hidden, vocab))}


def make_weights(cfg: Dict, seed: int, dtype: str = "bfloat16") -> Dict:
    """The weights of ``cfg`` from ``seed`` on the default device, in
    ``dtype`` (the configuration's ``precision.params``)."""
    d = dims(cfg)
    dt = jnp.dtype(dtype)
    key = jax.random.PRNGKey(fold_seed(seed))
    shape = (d["hidden"], d["heads"], d["kv_heads"], d["head_dim"],
             d["lin_heads"], d["lin_dim"], d["conv"], d["rank"],
             d["experts"], d["held"], d["expert_dim"], d["shared_dim"],
             d["layers"])
    w = _ends(jax.random.fold_in(key, 0), d["hidden"], d["vocab"], dt)
    w["layers"] = []
    for i, kind in enumerate(d["kinds"]):
        layer = _layer(jax.random.fold_in(key, i + 1), kind, shape, dt)
        w["layers"].append(dict(layer, kind=kind))
    return w
