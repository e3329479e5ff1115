"""The plain reference of the Solar-Open2 configuration: its forward pass in
straightforward ``jax.numpy``, float32, every matmul under
``precision=HIGHEST``.  Imports nothing of the program and takes nothing the
program has made.

Written from the catalog row's config and the paper it names (Kimi Linear,
arXiv:2510.26692); what the config does not say is listed under ``assumed`` in
``configs/solar-open2-250b.json``.  ``x`` is the residual stream; every layer
is ``h = x + Mixer(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; a final RMSNorm
and an untied head close the stack.  No bias anywhere.

- GQA layers: softmax attention over all earlier positions with scale
  ``Dh^-1/2`` and NO positional term, the heads' outputs times
  ``sigmoid(W_g u)`` elementwise before ``W_o``.
- KDA layers: ``q, k, v = SiLU(conv4(W u))`` (causal, depthwise, zero
  history), ``q`` and ``k`` L2-normalised per head (``x / sqrt(sum x^2 +
  1e-6)``; ``q`` also scaled by ``Dh^-1/2``), a per-channel log-decay ``g =
  -exp(A_log) softplus(W_f_up W_f_down u + dt_bias)``, ``beta = 2
  sigmoid(W_b u)``, and the delta rule TOKEN BY TOKEN (a ``lax.scan``; the
  program runs the chunked form)::

      S <- Diag(exp g_t) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T;
      o_t = S^T q_t

  then per-head RMSNorm of ``o_t``, times ``sigmoid(W_g_up W_g_down u)``, then
  ``W_o``.
- Experts: router logits over ALL experts in float32, softmax, the top
  ``k``, their weights renormalised to 1; each expert
  ``W_down(SiLU(W_gate u) * W_up u)``; the shared expert added for every
  token.  Only the HELD experts' terms are computed — a dense loop over them,
  each over every token, weighted by the router (0 where not chosen): the
  chip's share of the layer, as the program computes it.

One layer's weights are cast up at a time, so the bf16 weights of the whole
model and one layer in float32 fit beside each other.  One hook, for the
control of ``correct``: ``mm`` (the matmul: :func:`reference.int8_matmul`
rounds both operands to int8).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from .reference import f32_matmul, int8_matmul  # noqa: F401 (re-exported)

F32 = jnp.float32


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def gqa_mixer(u, p, d: Dict, mm: Callable):
    """(S, D) normed input -> (S, D)."""
    s = u.shape[0]
    h, hkv, dh = d["heads"], d["kv_heads"], d["head_dim"]
    q = mm(u, p["wq"]).reshape(s, h, dh)
    k = mm(u, p["wk"]).reshape(s, hkv, dh)
    v = mm(u, p["wv"]).reshape(s, hkv, dh)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    kt, vh = k.transpose(1, 2, 0), v.transpose(1, 0, 2)
    block = min(s, 256)         # query rows at a time: (H, block, S) scores

    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        scores = mm(qb.transpose(1, 0, 2), kt) * dh ** -0.5
        seen = (jnp.arange(s)[None, :]
                <= (i * block + jnp.arange(block))[:, None])
        scores = jnp.where(seen, scores, -jnp.inf)
        return mm(jax.nn.softmax(scores, axis=-1), vh).transpose(1, 0, 2)

    ctx = jax.lax.map(rows, jnp.arange(s // block)).reshape(s, h * dh)
    return mm(ctx * jax.nn.sigmoid(mm(u, p["wg"])), p["wo"])


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_mixer(u, p, d: Dict, mm: Callable):
    """(S, D) normed input -> (S, D), the recurrence token by token."""
    s = u.shape[0]
    h, dh, c = d["lin_heads"], d["lin_dim"], d["conv"]

    def conv(x, taps):          # causal depthwise over the last c positions
        pad = jnp.concatenate([jnp.zeros((c - 1, x.shape[1]), F32), x])
        return jax.nn.silu(sum(pad[i:i + s] * taps[i] for i in range(c)))

    q = conv(mm(u, p["wq"]), p["conv_q"]).reshape(s, h, dh)
    k = conv(mm(u, p["wk"]), p["conv_k"]).reshape(s, h, dh)
    v = conv(mm(u, p["wv"]), p["conv_v"]).reshape(s, h, dh)
    q = _l2(q) * dh ** -0.5
    k = _l2(k)
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
        mm(mm(u, p["wf_down"]), p["wf_up"]) + p["dt_bias"]).reshape(s, h, dh)
    beta = 2.0 * jax.nn.sigmoid(mm(u, p["wb"]))                    # (S, H)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[:, :, None]
        u_t = v_t - jnp.sum(state * k_t[:, :, None], axis=1)
        state = state + (b_t[:, None] * k_t)[:, :, None] * u_t[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((h, dh, dh), F32),
                        (q, k, v, g, beta))
    o = rms_norm(o, p["o_norm"], d["eps"]).reshape(s, h * dh)
    gate = jax.nn.sigmoid(mm(mm(u, p["wg_down"]), p["wg_up"]))
    return mm(o * gate, p["wo"])


def gated_mlp(u, w_in, w_out, mm: Callable):
    hidden = mm(u, w_in)
    f = hidden.shape[-1] // 2
    return mm(jax.nn.silu(hidden[:, :f]) * hidden[:, f:], w_out)


def experts(u, p, d: Dict, mm: Callable, first: int = 0):
    """(S, D) normed input -> (S, D): the terms of the experts ``first ..
    first + held - 1`` (those of ``p["w_in"]``), and the shared expert."""
    logits = jnp.matmul(u, p["router"], precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, d["top_k"])
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # (S, held): the router's weight of each held expert, 0 where not chosen
    held = p["w_in"].shape[0]
    ids = first + jnp.arange(held)
    weight = jnp.sum(jnp.where(top_e[:, :, None] == ids[None, None, :],
                               top_w[:, :, None], 0.0), axis=1)

    def one(y, x):
        w_in, w_out, w_tok = x
        return y + w_tok[:, None] * gated_mlp(u, w_in, w_out, mm), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (p["w_in"], p["w_out"], weight.T))
    return y + gated_mlp(u, p["shared_in"], p["shared_out"], mm)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(x, p, kind: str, d_items: tuple, mm: Callable):
    d = dict(d_items)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    u = rms_norm(x, p["norm1"], d["eps"])
    x = x + (gqa_mixer(u, p, d, mm) if kind == "gqa"
             else kda_mixer(u, p, d, mm))
    return x + experts(rms_norm(x, p["norm2"], d["eps"]), p, d, mm)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _scores(x, norm, head, candidates, eps: float, mm: Callable):
    """The logits of one row, reduced on the device to what the comparison
    reads: the best logit and its token at every position, and the logit of
    each candidate token there (``candidates``: (k, S))."""
    logits = mm(rms_norm(x, norm.astype(F32), eps), head.astype(F32))
    picked = jnp.take_along_axis(logits, candidates.T, axis=-1).T
    return logits.max(axis=-1), logits.argmax(axis=-1), picked


def _static(d: Dict) -> tuple:
    return tuple(sorted((k, v) for k, v in d.items()
                        if isinstance(v, (int, float))))


def hidden(w: Dict, tokens, d: Dict, mm: Callable = f32_matmul):
    """(S,) tokens -> (S, D) residual stream after the last layer (before
    the final norm), layer by layer, each cast up on its own."""
    x = w["embed"][tokens].astype(F32)
    for layer in w["layers"]:
        arrays = {k: v for k, v in layer.items() if k != "kind"}
        x = _layer(x, arrays, layer["kind"], _static(d), mm)
    return x


def logits_fn(w: Dict, tokens, d: Dict, mm: Callable = f32_matmul):
    x = hidden(w, tokens, d, mm)
    return mm(rms_norm(x, w["final_norm"].astype(F32), d["eps"]),
              w["head"].astype(F32))


def served_position_scores(w: Dict, prompt: np.ndarray, served: np.ndarray,
                           candidates, d: Dict, pad_to: int,
                           mm: Callable = f32_matmul):
    """Teacher-force one finished request (``reference.
    served_position_scores``'s contract): the forward once over the prompt
    with its served tokens; at every served position, how far each candidate
    token's logit lies below the best (``gaps``, a row a candidate sequence,
    >= 0) and the token the forward itself puts first.  The row is
    right-padded to ``pad_to`` (causal attention and a causal recurrence:
    padding changes nothing before it)."""
    p, n = len(prompt), len(served)
    row = np.zeros((pad_to,), np.int32)
    row[:p] = prompt
    row[p:p + n - 1] = served[:-1]
    cand = np.zeros((len(candidates), pad_to), np.int32)
    for i, c in enumerate(candidates):
        cand[i, p - 1:p - 1 + n] = c
    x = hidden(w, jnp.asarray(row), d, mm)
    best, first, picked = _scores(x, w["final_norm"], w["head"],
                                  jnp.asarray(cand), d["eps"], mm)
    at = slice(p - 1, p - 1 + n)
    gaps = np.asarray(best, np.float32)[at] - np.asarray(picked,
                                                         np.float32)[:, at]
    return gaps, np.asarray(first)[at]


def pad_length(n: int, floor: int = 256) -> int:
    """Rows share programs by length: the next power of two from ``floor``."""
    m = floor
    while m < n:
        m *= 2
    return m
