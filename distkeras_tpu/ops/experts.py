"""A sparse expert layer's compute outside ``shard_map``: dropless routing
over ALL experts, and a grouped matmul over the experts held here.

``parallel/moe.py`` routes with a capacity and drops what exceeds it, inside
an all-to-all; decoding cannot drop a token, and one chip of an
expert-parallel deployment computes only its own experts' terms.  Here every
(token, expert) assignment is kept: the assignments to held experts are
sorted by expert, and ONE grouped matmul over the held experts computes them
(``group_sizes`` rows a group, no capacity, any skew).  Assignments to experts
held elsewhere are left out — nothing stands in for the other chips.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

#: rows of a grouped-matmul tile on the TPU: the sorted assignments are padded
#: to a whole number of them
_TILE_M = 128


def route(logits, top_k: int, kind: str = "softmax", bias=None,
          scale: float = 1.0):
    """The ``top_k`` experts of every token and their weights, renormalised
    to sum to 1, in float32.  ``logits``: (T, E).  ``kind`` ``"softmax"``:
    softmax over all experts, the ``top_k`` largest.  ``"sigmoid_bias"``:
    the scores are ``sigmoid(logits)``; the ``top_k`` are those with the
    largest score PLUS ``bias`` (E,), which moves who is chosen and nothing
    else; the weights are the chosen experts' unbiased scores, renormalised,
    times ``scale``.  Returns ``(experts (T, K) int32, weights (T, K)
    float32)``."""
    if kind == "softmax":
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        w, e = jax.lax.top_k(probs, int(top_k))
        return e.astype(jnp.int32), w / jnp.sum(w, axis=-1, keepdims=True)
    if kind != "sigmoid_bias":
        raise ValueError(f"route: unknown router kind {kind!r}")
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, e = jax.lax.top_k(scores + bias.astype(jnp.float32), int(top_k))
    w = jnp.take_along_axis(scores, e, axis=-1)
    return (e.astype(jnp.int32),
            scale * w / jnp.sum(w, axis=-1, keepdims=True))


def dispatch(experts, weights, held: Tuple[int, int], token_live=None):
    """Sort the assignments to held experts by expert.  ``experts``,
    ``weights``: (T, K); ``held`` = (first, count) of the experts held here;
    ``token_live``: (T,) bool or None, tokens whose assignments count (a dead
    slot's junk token is routed nowhere).  Returns ``(token (M,), weight
    (M,), group_sizes (count,), total)`` with ``M = T * K``: the sorted
    assignments' tokens and weights — rows at and past ``total`` belong to no
    held expert and carry weight 0 — and how many rows each held expert
    got."""
    first, count = int(held[0]), int(held[1])
    t, k = experts.shape
    local = experts - first
    mine = (local >= 0) & (local < count)
    if token_live is not None:
        mine = mine & token_live[:, None]
    key = jnp.where(mine, local, count).reshape(-1)            # (M,)
    order = jnp.argsort(key, stable=True)
    token = (jnp.arange(t * k, dtype=jnp.int32) // k)[order]
    weight = jnp.where(mine, weights, 0.0).reshape(-1)[order]
    group_sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    return token, weight, group_sizes, jnp.sum(group_sizes)


def _tiling(k: int, n: int):
    """(tm, tk, tn) of the megablox kernel: weight tiles of a few MB, so a
    step's DMA outweighs its fixed cost and two of them fit scoped VMEM.
    A width that none of the usual tiles divides takes the largest multiple
    of 128 that does (2,688 = 3 x 896) or, where none does, itself as one
    whole tile (1,856 = 14.5 x 128; the kernel masks an irregular last tile,
    but a tile of 128 at such a width is 300 grid steps an expert of 2 us
    each: PERF.md section 6, PR 34)."""
    def fit(x):
        for t in (1280, 1024, 512, 256):
            if x % t == 0:
                return t
        whole = next((t for t in range(1152, 128, -128) if x % t == 0), None)
        return whole or (x if 128 < x <= 2048 else 128)
    return _TILE_M, fit(k), fit(n)


def grouped_matmul(x, w, group_sizes, kernel=None, transpose_rhs=False):
    """``x[rows of group g] @ w[g]`` for every group, float32 out.  ``x``:
    (M, K) sorted by group; ``w``: (G, K, N), or with ``transpose_rhs`` the
    same matrices stored (G, N, K) — the form ``SparseMoE`` holds an
    up-projection in for serving where the chip would keep (G, K, N) with
    ``K`` minor-most and relay it before every call (PERF.md section 6,
    PR 35); the tiles are chosen from (K, N) either way.  ``group_sizes``:
    (G,) int32 summing to at most M (rows past the sum come back 0).  On a
    TPU this is the Pallas grouped matmul of
    ``jax.experimental.pallas.ops.tpu.megablox`` — it visits (group,
    row-tile) pairs that hold rows and no others, so an expert no token
    chose costs no weight read; elsewhere ``lax.ragged_dot`` (``kernel``:
    None asks the backend)."""
    m, k = x.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    if kernel:
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        pad = -m % _TILE_M      # whole row tiles; the added rows are past
        if pad:                 # the groups' sum and are never visited
            x = jnp.pad(x, ((0, pad), (0, 0)))
        out = gmm(x, w, group_sizes, jnp.float32, _tiling(k, n),
                  transpose_rhs=transpose_rhs)[:m]
    else:
        out = jax.lax.ragged_dot(
            x, jnp.swapaxes(w, 1, 2) if transpose_rhs else w, group_sizes,
            preferred_element_type=jnp.float32)
    live = jnp.arange(m) < jnp.sum(group_sizes)
    return jnp.where(live[:, None], out, 0.0)
