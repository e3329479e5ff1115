"""The plain reference: GPT-2's forward pass, its loss and gradient and
Adam's rule, in straightforward ``jax.numpy``, float32, every matmul under
``precision=HIGHEST`` (on a TPU a float32 matmul is otherwise a bf16 one).

Written from the published description (Radford et al. 2019; the
``openai-community/gpt2`` config: pre-LayerNorm blocks, learned positions,
``gelu_new``, biases everywhere, eps 1e-5) with the repo's one departure, an
untied head with a bias.  No kernels, no cache, no batching tricks; it imports
nothing of the program and takes nothing the program has made.

``mm`` is the one hook: the matmul.  The lower-precision control of
``correct`` passes :func:`int8_matmul` here and changes nothing else.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK_KEYS = ("ln1_g", "ln1_b", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
              "bo", "ln2_g", "ln2_b", "w1", "b1", "w2", "b2")


def f32_matmul(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _fake_int8(x, axis):
    """Symmetric int8 along ``axis`` (per row of the contraction), with a
    straight-through gradient: the value is the quantised one, the
    derivative that of the identity."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def int8_matmul(a, b):
    """The control: both operands rounded to int8 (activations per row,
    weights per output column: the usual W8A8 recipe), accumulated exactly."""
    return jnp.matmul(_fake_int8(a, -1), _fake_int8(b, -2),
                      precision=HIGHEST)


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, n_head: int, eps: float, mm: Callable):
    """One pre-LN block on (B, S, D)."""
    b, s, d = x.shape
    dh = d // n_head
    h = layer_norm(x, p["ln1_g"], p["ln1_b"], eps)

    def heads(w, bias):
        return (mm(h, w) + bias).reshape(b, s, n_head, dh).transpose(
            0, 2, 1, 3)

    q, k, v = (heads(p["wq"], p["bq"]), heads(p["wk"], p["bk"]),
               heads(p["wv"], p["bv"]))
    scores = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = mm(probs, v).transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + mm(ctx, p["wo"]) + p["bo"]
    h = layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
    h = gelu_new(mm(h, p["w1"]) + p["b1"])
    return x + mm(h, p["w2"]) + p["b2"]


def hidden(w: Dict, tokens, n_head: int, eps: float = 1e-5,
           mm: Callable = f32_matmul):
    """(B, S) tokens -> (B, S, D) final hidden states (after ln_f).  The
    blocks run under ``lax.scan`` over the stacked weights, each rebuilt in
    the backward pass (``jax.checkpoint``) so a full-size batch fits."""
    s = tokens.shape[1]
    x = w["wte"][tokens] + w["wpe"][:s]
    stacked = {k: w[k] for k in BLOCK_KEYS}

    @jax.checkpoint
    def body(x, p):
        return block(x, p, n_head, eps, mm), None

    x, _ = jax.lax.scan(body, x, stacked)
    return layer_norm(x, w["lnf_g"], w["lnf_b"], eps)


def logits_fn(w: Dict, tokens, n_head: int, eps: float = 1e-5,
              mm: Callable = f32_matmul):
    return mm(hidden(w, tokens, n_head, eps, mm), w["head_w"]) + w["head_b"]


def loss_fn(w: Dict, tokens, labels, n_head: int, eps: float = 1e-5,
            mm: Callable = f32_matmul):
    """Mean next-token cross-entropy over every position of every row."""
    logp = jax.nn.log_softmax(logits_fn(w, tokens, n_head, eps, mm), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _loss_and_grad_block(w, tokens, labels, n_head, eps, mm):
    return jax.value_and_grad(loss_fn)(w, tokens, labels, n_head, eps, mm)


def loss_and_grad(w: Dict, tokens: np.ndarray, labels: np.ndarray,
                  n_head: int, eps: float = 1e-5, mm: Callable = f32_matmul,
                  rows_per_block: int = 2) -> Tuple[float, Dict]:
    """Loss and gradient of one batch, in blocks of rows so that the
    full-size batch fits beside nothing else: every row has as many
    positions, so the batch mean is the mean of the blocks' means."""
    n = len(tokens)
    if n % rows_per_block:
        rows_per_block = 1
    blocks = n // rows_per_block
    loss, grad = 0.0, None
    for i in range(blocks):
        sl = slice(i * rows_per_block, (i + 1) * rows_per_block)
        l, g = _loss_and_grad_block(w, jnp.asarray(tokens[sl]),
                                    jnp.asarray(labels[sl]), n_head, eps, mm)
        loss = loss + l / blocks
        grad = (jax.tree_util.tree_map(lambda a: a / blocks, g)
                if grad is None else
                jax.tree_util.tree_map(lambda a, b: a + b / blocks, grad, g))
    return loss, grad


@jax.jit
def _adam_update(w, m, v, g, t, lr, b1, b2, eps):
    m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree_util.tree_map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_,
                               v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    w = jax.tree_util.tree_map(
        lambda w_, m_, v_: w_ - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + eps),
        w, m, v)
    return w, m, v


def adag_rounds(w: Dict, schedule, n_head: int, lr: float, b1: float = 0.9,
                b2: float = 0.999, adam_eps: float = 1e-7, eps: float = 1e-5,
                mm: Callable = f32_matmul):
    """Follow ADAG (Hermans 2017, as dist-keras runs it in lockstep) from the
    center ``w``.  ``schedule[round][worker]`` is that worker's list of
    ``(tokens, labels)`` batches in the round.  Every round each worker pulls
    the center, takes its Adam steps (Kingma & Ba 2015, bias-corrected,
    epsilon outside the root; moments and step count are the worker's own
    and persist across rounds), and commits its change; the center moves by
    the mean of the workers' changes.  With one worker this is plain Adam.

    Returns the loss of every step as ``losses[round][worker][step]``, every
    worker's first gradient, and the center after the last round."""
    workers = len(schedule[0])
    zeros = jax.tree_util.tree_map(jnp.zeros_like, w)
    moments = [(zeros, zeros, 0) for _ in range(workers)]
    first_grads = [None] * workers
    losses = []
    center = w
    for round_ in schedule:
        deltas, round_losses = [], []
        for k, batches in enumerate(round_):
            p, (m, v, t) = center, moments[k]
            mine = []
            for x, y in batches:
                loss, g = loss_and_grad(p, x, y, n_head, eps, mm)
                mine.append(float(loss))
                if first_grads[k] is None:
                    first_grads[k] = g
                t += 1
                p, m, v = _adam_update(p, m, v, g, jnp.float32(t), lr, b1,
                                       b2, adam_eps)
            moments[k] = (m, v, t)
            round_losses.append(mine)
            deltas.append(jax.tree_util.tree_map(jnp.subtract, p, center))
        mean = jax.tree_util.tree_map(lambda *d: sum(d) / workers, *deltas)
        center = jax.tree_util.tree_map(jnp.add, center, mean)
        losses.append(round_losses)
    return losses, first_grads, center


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _row_scores(w, tokens, candidates, n_head, eps, mm):
    """One row's logits, reduced on the device to what the comparison reads:
    the best logit and its token at every position, and the logit of each
    candidate token there (``candidates``: (k, S))."""
    logits = logits_fn(w, tokens, n_head, eps, mm)[0]
    picked = jnp.take_along_axis(logits, candidates.T, axis=-1).T
    return logits.max(axis=-1), logits.argmax(axis=-1), picked


def served_position_scores(w: Dict, prompt: np.ndarray, served: np.ndarray,
                           candidates, n_head: int, pad_to: int,
                           eps: float = 1e-5, mm: Callable = f32_matmul):
    """Teacher-force one finished request: run the forward once over the
    prompt with its served tokens and return, at every served position, how
    far each candidate token's logit lies below the best (``gaps``, one row
    a candidate sequence, >= 0) and the token the forward itself puts first.
    The row is right-padded to ``pad_to`` (causal: padding changes nothing
    before it) so that every request shares one program."""
    p, n = len(prompt), len(served)
    row = np.zeros((1, pad_to), np.int32)
    row[0, :p] = prompt
    row[0, p:p + n - 1] = served[:-1]
    cand = np.zeros((len(candidates), pad_to), np.int32)
    for k, c in enumerate(candidates):
        cand[k, p - 1:p - 1 + n] = c
    best, first, picked = _row_scores(w, jnp.asarray(row), jnp.asarray(cand),
                                      n_head, eps, mm)
    at = slice(p - 1, p - 1 + n)
    gaps = np.asarray(best, np.float32)[at] - np.asarray(picked,
                                                         np.float32)[:, at]
    return gaps, np.asarray(first)[at]
