"""The plain reference of the Granite-4.0-H configuration (``model_type``
``granitemoehybrid``): its forward pass in straightforward ``jax.numpy``,
float32, every matmul under ``precision=HIGHEST``.  Imports nothing of the
program and takes nothing the program has made.

Written from the catalog row's config and the Mamba-2 paper
(arXiv:2405.21060); what the config does not say is listed under ``assumed``
in ``configs/granite-4.0-h-micro.json``.  ``x`` is the residual stream::

    x_0    = embedding_multiplier * E[token]
    h      = x + residual_multiplier * mixer_l(RMSNorm(x))
    x'     = h + residual_multiplier * MLP(RMSNorm(h))
    logits = RMSNorm(x_L) E^T / logits_scaling        (the SAME table E)

No bias but the convolution's; RMSNorm with a learned scale everywhere.

- ``mamba``, Mamba-2 (``reference_nemotronh.mamba_mixer``, imported):
  ``[z | xBC | dt] = u W_in``; ``xBC <- SiLU(conv4(xBC) +
  bias)`` (causal, depthwise, zero history); ``x`` (H heads of P), ``B``, ``C``
  (G groups of N; head ``h`` reads group ``h // (H / G)``); ``dt =
  softplus(dt + dt_bias)``, ``a = exp(-exp(A_log) dt)``, and the recurrence
  TOKEN BY TOKEN (a ``lax.scan``; the program runs the chunked form)::

      S <- a_t S + dt_t x_t B_t^T;   y_t = S C_t + D x_t

  then ``y * SiLU(z)``, an RMSNorm whose mean square is taken inside each of
  the G groups of channels (G = 1: over all of them), a learned scale, and
  ``W_out``.
- ``attention``: softmax attention over all earlier positions, grouped
  queries, scores times ``attention_multiplier`` (NOT ``Dh^-1/2``), NO
  positional term.
- MLP: ``[g | u] = v W_in`` (gate first), ``(SiLU(g) * u) W_out``.

One layer's weights are cast up at a time; ONE jitted function a layer kind
and padded length, called layer by layer from Python, so compiling does not
grow with depth.  One hook, for the control of ``correct``: ``mm`` (the
matmul: :func:`reference.int8_matmul` rounds both operands to int8).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from .reference import f32_matmul, int8_matmul  # noqa: F401 (re-exported)
# the Mamba-2 mixer is the other state-space configuration's reference, to
# the letter (its ``d`` names are ``counts_granite.dims``'s): one copy
from .reference_nemotronh import mamba_mixer, rms_norm

F32 = jnp.float32


def attention_mixer(u, p, d: Dict, mm: Callable):
    """(S, D) normed input -> (S, D)."""
    s = u.shape[0]
    h, hkv, dh = d["heads"], d["kv_heads"], d["head_dim"]
    q = mm(u, p["wq"]).reshape(s, h, dh)
    k = mm(u, p["wk"]).reshape(s, hkv, dh)
    v = mm(u, p["wv"]).reshape(s, hkv, dh)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    kt, vh = k.transpose(1, 2, 0), v.transpose(1, 0, 2)
    # query rows at a time: (H, block, S) scores
    block = 256 if s % 256 == 0 else s

    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        scores = mm(qb.transpose(1, 0, 2), kt) * d["attn_mult"]
        seen = (jnp.arange(s)[None, :]
                <= (i * block + jnp.arange(block))[:, None])
        scores = jnp.where(seen, scores, -jnp.inf)
        return mm(jax.nn.softmax(scores, axis=-1), vh).transpose(1, 0, 2)

    ctx = jax.lax.map(rows, jnp.arange(s // block)).reshape(s, h * dh)
    return mm(ctx, p["wo"])


def gated_mlp(u, w_in, w_out, mm: Callable):
    gu = mm(u, w_in)
    f = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], w_out)


MIXERS = {"attn": attention_mixer, "mamba": mamba_mixer}


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(x, p, kind: str, d_items: tuple, mm: Callable):
    d = dict(d_items)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    r = d["resid_mult"]
    h = x + r * MIXERS[kind](rms_norm(x, p["norm"], d["eps"]), p, d, mm)
    return h + r * gated_mlp(rms_norm(h, p["norm2"], d["eps"]), p["mlp_in"],
                             p["mlp_out"], mm)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _scores(x, norm, table, candidates, eps: float, divisor: float,
            mm: Callable):
    """The logits of one row (through the TIED table), reduced on the device
    to what the comparison reads: the best logit and its token at every
    position, and the logit of each candidate token there (``candidates``:
    (k, S))."""
    logits = mm(rms_norm(x, norm.astype(F32), eps),
                table.astype(F32).T) / divisor
    picked = jnp.take_along_axis(logits, candidates.T, axis=-1).T
    return logits.max(axis=-1), logits.argmax(axis=-1), picked


def _static(d: Dict) -> tuple:
    return tuple(sorted((k, v) for k, v in d.items()
                        if isinstance(v, (int, float))))


def hidden(w: Dict, tokens, d: Dict, mm: Callable = f32_matmul):
    """(S,) tokens -> (S, D) residual stream after the last layer (before
    the final norm), layer by layer, each cast up on its own."""
    x = d["embed_mult"] * w["embed"][tokens].astype(F32)
    for layer in w["layers"]:
        arrays = {k: v for k, v in layer.items() if k != "kind"}
        x = _layer(x, arrays, layer["kind"], _static(d), mm)
    return x


def logits_fn(w: Dict, tokens, d: Dict, mm: Callable = f32_matmul):
    x = hidden(w, tokens, d, mm)
    return mm(rms_norm(x, w["final_norm"].astype(F32), d["eps"]),
              w["embed"].astype(F32).T) / d["logits_div"]


def served_position_scores(w: Dict, prompt: np.ndarray, served: np.ndarray,
                           candidates, d: Dict, pad_to: int,
                           mm: Callable = f32_matmul):
    """Teacher-force one finished request (``reference.
    served_position_scores``'s contract): the forward once over the prompt
    with its served tokens; at every served position, how far each candidate
    token's logit lies below the best (``gaps``, a row a candidate sequence,
    >= 0) and the token the forward itself puts first.  The row is
    right-padded to ``pad_to`` (causal attention, a causal convolution and a
    causal recurrence: padding changes nothing before it)."""
    p, n = len(prompt), len(served)
    row = np.zeros((pad_to,), np.int32)
    row[:p] = prompt
    row[p:p + n - 1] = served[:-1]
    cand = np.zeros((len(candidates), pad_to), np.int32)
    for i, c in enumerate(candidates):
        cand[i, p - 1:p - 1 + n] = c
    x = hidden(w, jnp.asarray(row), d, mm)
    best, first, picked = _scores(x, w["final_norm"], w["embed"],
                                  jnp.asarray(cand), d["eps"],
                                  d["logits_div"], mm)
    at = slice(p - 1, p - 1 + n)
    gaps = np.asarray(best, np.float32)[at] - np.asarray(picked,
                                                         np.float32)[:, at]
    return gaps, np.asarray(first)[at]


def pad_length(n: int, step: int = 512) -> int:
    """Rows share programs by length: the next multiple of ``step`` (at most
    ten lengths to the configuration's 5,120 positions)."""
    return -(-n // step) * step
