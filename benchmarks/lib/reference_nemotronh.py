"""The plain reference of the Nemotron-3-Nano configuration (``model_type``
``nemotron_h``): its forward pass in straightforward ``jax.numpy``, float32,
every matmul under ``precision=HIGHEST``.  Imports nothing of the program and
takes nothing the program has made.

Written from the catalog row's config, the Mamba-2 paper (arXiv:2405.21060)
and what is known of the published ``modeling_nemotron_h.py``; what the config
does not say is listed under ``assumed`` in
``configs/nemotron3-nano-30b-a3b.json``.  ``x`` is the residual stream; layer
``l`` is ONE part, ``x <- x + part_l(RMSNorm(x))``, chosen by
``hybrid_override_pattern[l]``; a final RMSNorm and an untied head close the
stack.  No bias but the convolution's.

- ``M``, Mamba-2: ``[z | xBC | dt] = u W_in``; ``xBC <- SiLU(conv4(xBC) +
  bias)`` (causal, depthwise, zero history); ``x`` (H heads of P), ``B``, ``C``
  (G groups of N; head ``h`` reads group ``h // (H / G)``); ``dt =
  softplus(dt + dt_bias)``, ``a = exp(-exp(A_log) dt)``, and the recurrence
  TOKEN BY TOKEN (a ``lax.scan``; the program runs the chunked form)::

      S <- a_t S + dt_t x_t B_t^T;   y_t = S C_t + D x_t

  then ``y * SiLU(z)``, an RMSNorm whose mean square is taken inside each of
  the G groups of channels, a learned scale, and ``W_out``.
- ``*``, attention: softmax attention over all earlier positions, grouped
  queries, scale ``Dh^-1/2``, NO positional term.
- ``E``, experts: router logits over ALL experts in float32, ``s =
  sigmoid(.)``, the ``k`` experts with the largest ``s + bias``, their
  weights the UNBIASED ``s`` renormalised to 1 times ``routed_scaling_factor``;
  each expert ``relu(u W_up)^2 W_down``; the shared expert, of the same form,
  added for every token.  Only the HELD experts' terms are computed: a dense
  loop over them, each over every token, weighted by the router (0 where not
  chosen): the chip's share of the layer, as the program computes it.

One layer's weights are cast up at a time.  One hook, for the control of
``correct``: ``mm`` (the matmul: :func:`reference.int8_matmul` rounds both
operands to int8).
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
        scores = mm(qb.transpose(1, 0, 2), kt) * dh ** -0.5
        seen = (jnp.arange(s)[None, :]
                <= (i * block + jnp.arange(block))[:, None])
        scores = jnp.where(seen, scores, -jnp.inf)
        return mm(jax.nn.softmax(scores, axis=-1), vh).transpose(1, 0, 2)

    ctx = jax.lax.map(rows, jnp.arange(s // block)).reshape(s, h * dh)
    return mm(ctx, p["wo"])


def mamba_mixer(u, p, d: Dict, mm: Callable):
    """(S, D) normed input -> (S, D), the recurrence token by token."""
    s = u.shape[0]
    h, hp, g, n, c = (d["m_heads"], d["m_dim"], d["groups"], d["state"],
                      d["conv"])
    inner, conv_dim = d["inner"], d["conv_dim"]
    zxd = mm(u, p["w_in"])
    z, xbc, dt = (zxd[:, :inner], zxd[:, inner:inner + conv_dim],
                  zxd[:, inner + conv_dim:])
    # causal depthwise convolution over the last c positions, with bias
    pad = jnp.concatenate([jnp.zeros((c - 1, conv_dim), F32), xbc])
    xbc = jax.nn.silu(sum(pad[i:i + s] * p["conv_w"][i] for i in range(c))
                      + p["conv_b"])
    x = xbc[:, :inner].reshape(s, h, hp)
    b = jnp.repeat(xbc[:, inner:inner + g * n].reshape(s, g, n), h // g, 1)
    c_ = jnp.repeat(xbc[:, inner + g * n:].reshape(s, g, n), h // g, 1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                        # (S, H)
    a = jnp.exp(-jnp.exp(p["a_log"]) * dt)

    def token(state, t):
        x_t, b_t, c_t, dt_t, a_t = t
        state = (a_t[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((h, hp, n), F32), (x, b, c_, dt, a))
    y = (y + p["d_skip"][:, None] * x).reshape(s, inner) * jax.nn.silu(z)
    # gate first, then the norm group by group (``assumed``)
    y = rms_norm(y.reshape(s, g, inner // g), 1.0, d["eps"]).reshape(s, inner)
    return mm(y * p["gnorm"], p["w_out"])


def relu2_mlp(u, w_in, w_out, mm: Callable):
    return mm(jnp.square(jax.nn.relu(mm(u, w_in))), w_out)


def router(u, p, d: Dict):
    """``(chosen (S, k), weights (S, k))`` over ALL experts: who is chosen
    reads the bias, the weights do not."""
    logits = jnp.matmul(u, p["router"], precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, top_e = jax.lax.top_k(scores + p["router_bias"], d["top_k"])
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    return top_e, d["scale"] * top_w / jnp.sum(top_w, axis=-1, keepdims=True)


def experts(u, p, d: Dict, mm: Callable, first: int = 0,
            shared: bool = True):
    """(S, D) normed input -> (S, D): the terms of the experts ``first ..
    first + held - 1`` (those of ``p["w_in"]``), and the shared expert
    (``shared`` False leaves it out: the share test counts it once)."""
    top_e, top_w = router(u, p, d)
    # (S, held): the router's weight of each held expert, 0 where not chosen
    held = p["w_in"].shape[0]
    ids = first + jnp.arange(held)
    weight = jnp.sum(jnp.where(top_e[:, :, None] == ids[None, None, :],
                               top_w[:, :, None], 0.0), axis=1)

    def one(y, x):
        w_in, w_out, w_tok = x
        return y + w_tok[:, None] * relu2_mlp(u, w_in, w_out, mm), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (p["w_in"], p["w_out"], weight.T))
    if shared:
        y = y + relu2_mlp(u, p["shared_in"], p["shared_out"], mm)
    return y


PARTS = {"attn": attention_mixer, "mamba": mamba_mixer, "experts": experts}


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(x, p, kind: str, d_items: tuple, mm: Callable):
    d = dict(d_items)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    return x + PARTS[kind](rms_norm(x, p["norm"], d["eps"]), p, d, mm)


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
    best, first, picked = _scores(x, w["final_norm"], w["head"],
                                  jnp.asarray(cand), d["eps"], mm)
    at = slice(p - 1, p - 1 + n)
    gaps = np.asarray(best, np.float32)[at] - np.asarray(picked,
                                                         np.float32)[:, at]
    return gaps, np.asarray(first)[at]


def pad_length(n: int, step: int = 1024) -> int:
    """Rows share programs by length: the next multiple of ``step`` (at most
    nine lengths to the configuration's 9,216 positions)."""
    return -(-n // step) * step
