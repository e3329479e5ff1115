"""Fused softmax-cross-entropy in Pallas (TPU) — the LM-head hot op.

The last op of every LM train step is ``-log_softmax(logits)[label]`` over a
(tokens, vocab) logits matrix.  XLA's lowering materializes the full f32
log-probability matrix in HBM (at vocab 50k and 8k tokens that is a 1.6 GB
round-trip per step — comparable to the whole rest of the backward).  This
kernel computes the per-token loss in ONE streaming pass with the
online-softmax recurrence, so HBM traffic is read-logits-once plus an O(T)
write, and nothing (T, V)-shaped is ever written:

 - forward, grid (T/block_t, V/block_v): the inner grid dimension streams
   vocab blocks through VMEM; f32 scratch carries the running max / sum-exp
   / picked-label-logit across inner iterations (TPU grids run sequentially,
   innermost fastest); the last block writes per-row ``loss = lse - picked``
   and the ``lse`` residual, both broadcast over a 128-lane trailing dim
   (the TPU-tileable layout for per-row stats, as in flash_attention);
 - backward, grid (T/block_t, V/block_v): pure streaming map — each block
   recomputes ``p = exp(logits - lse)`` from the saved O(T) residual and
   writes ``ct · (p - onehot(label))``; no scratch carry, no (T, V)
   intermediate beyond the unavoidable gradient output itself (written in
   the logits dtype, not f32);
 - ragged edges are handled in-kernel: vocab/token positions past the true
   extent are masked to -inf / zero contribution, so any (T, V) shape works
   without host-side padding copies.

Every trainer reaches it through ``core.losses`` (the sparse
cross-entropies from logits, by ``fused_ce_applies``' rule on shape, dtype
and backend); ``ParallelTransformerLM(fused_ce=True)`` calls it directly.

On non-TPU backends the kernel runs in Pallas interpret mode (tests); the
XLA path (``log_softmax`` + ``take_along_axis``, ``core.losses``' other side)
stays the correctness oracle — value/grad parity asserted in
tests/test_fused_ce.py.  No reference counterpart (the reference's losses
are whole-array Keras ops; SURVEY.md §2.1 row 21) — this exists because a
TPU-first LM stack is HBM-bound exactly here.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._vma import _vma_of, out_struct

NEG_INF = float("-inf")
_LANES = 128

# the largest block (``_tiles``) and what the kernels may ask of VMEM for it:
# 256 x 2,048 of f32 is 2 MiB, so the backward's double-buffered blocks
# (logits in, ``dlogits`` out) and its f32 temporaries (``s``, ``p``, the
# column ids, the product) come to 16 MiB, over the compiler's default
_MAX_T, _MAX_V = 256, 2048
VMEM_LIMIT = 32 << 20


def _tiles(t: int, v: int) -> Tuple[int, int]:
    """(block_t, block_v) for a call on ``(t, v)`` logits: up to ``_MAX_T``
    rows by the widest vocab block of whole lane tiles up to ``_MAX_V``
    (the stream's direction: a grid step costs a third of a microsecond
    whatever it moves).  The head and loss of ``train-adag-gpt2s`` read
    19.6 ms at 256 x 2,048 against 20.9 at 256 x 512 (PERF.md section 6,
    PR 33).  A short or narrow operand is one block along that axis;
    ragged edges are masked in the kernels, so nothing has to divide."""
    return (min(t, _MAX_T),
            v if v <= _LANES else min(_MAX_V, v // _LANES * _LANES))


def _col_ids(v0, bt, bv):
    return v0 + jax.lax.broadcasted_iota(jnp.int32, (bt, bv), 1)


def _fwd_kernel(logits_ref, labels_ref, loss_ref, lse_ref,
                m_scr, l_scr, pick_scr, *,
                block_t: int, block_v: int, num_v: int, v_total: int):
    vj = pl.program_id(1)
    bt, bv = block_t, block_v

    @pl.when(vj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        pick_scr[...] = jnp.zeros_like(pick_scr)

    s = logits_ref[...].astype(jnp.float32)                 # (bt, bv)
    cols = _col_ids(vj * bv, bt, bv)
    s = jnp.where(cols < v_total, s, NEG_INF)               # ragged vocab edge

    lab = labels_ref[...]                                   # (bt, 1) int32
    hit = (cols == lab)                                     # one-hot block
    # the label column appears in exactly one vocab block, so += is a select
    pick_scr[...] = pick_scr[...] + jnp.broadcast_to(
        jnp.sum(jnp.where(hit, s, 0.0), axis=-1, keepdims=True),
        pick_scr.shape)

    m = m_scr[:, 0:1]
    l = l_scr[:, 0:1]
    new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    safe = jnp.where(new_m == NEG_INF, 0.0, new_m)
    p = jnp.exp(s - safe)                                   # -inf cols -> 0
    l = l * jnp.exp(m - safe) + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = jnp.broadcast_to(new_m, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l, l_scr.shape)

    @pl.when(vj == num_v - 1)
    def _finalize():
        m = m_scr[:, 0:1]
        l = l_scr[:, 0:1]
        safe_m = jnp.where(m == NEG_INF, 0.0, m)
        lse = safe_m + jnp.log(jnp.where(l == 0.0, 1.0, l))
        loss_ref[...] = jnp.broadcast_to(lse - pick_scr[:, 0:1],
                                         loss_ref.shape)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _bwd_kernel(logits_ref, labels_ref, lse_ref, ct_ref, dlogits_ref, *,
                block_t: int, block_v: int, v_total: int):
    vj = pl.program_id(1)
    bt, bv = block_t, block_v
    s = logits_ref[...].astype(jnp.float32)
    cols = _col_ids(vj * bv, bt, bv)
    lse = lse_ref[:, 0:1]
    p = jnp.where(cols < v_total, jnp.exp(s - lse), 0.0)
    hit = (cols == labels_ref[...]).astype(jnp.float32)
    ct = ct_ref[:, 0:1]
    # ragged token rows need no masking here: writes to out-of-range rows
    # of an edge block are dropped by pallas, and every op is row-local
    dlogits_ref[...] = (ct * (p - hit)).astype(dlogits_ref.dtype)


def _resolve_interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _specs(bt, bv):
    return dict(
        logits=pl.BlockSpec((bt, bv), lambda ti, vj: (ti, vj)),
        rows=pl.BlockSpec((bt, 1), lambda ti, vj: (ti, 0)),
        lanes=pl.BlockSpec((bt, _LANES), lambda ti, vj: (ti, 0)),
    )


def _params(vocab_axis: str):
    """Token blocks are independent; the forward carries its running
    statistics along the vocabulary."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", vocab_axis),
        vmem_limit_bytes=VMEM_LIMIT)


def _fwd_call(logits, labels, block_t, block_v, interpret):
    t, v = logits.shape
    bt = min(block_t, t)
    bv = min(block_v, v)
    grid = (pl.cdiv(t, bt), pl.cdiv(v, bv))
    sp = _specs(bt, bv)
    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_t=bt, block_v=bv,
                          num_v=grid[1], v_total=v),
        out_shape=(out_struct((t, _LANES), jnp.float32, logits),
                   out_struct((t, _LANES), jnp.float32, logits)),
        grid=grid,
        in_specs=[sp["logits"], sp["rows"]],
        out_specs=(sp["lanes"], sp["lanes"]),
        scratch_shapes=[pltpu.VMEM((bt, _LANES), jnp.float32),
                        pltpu.VMEM((bt, _LANES), jnp.float32),
                        pltpu.VMEM((bt, _LANES), jnp.float32)],
        compiler_params=_params("arbitrary"),
        interpret=interpret,
        name="fused_ce_fwd",
    )(logits, labels.reshape(t, 1).astype(jnp.int32))
    return loss[:, 0], lse[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused_ce(logits, labels, block_t: int, block_v: int, interpret: bool):
    loss, _ = _fwd_call(logits, labels, block_t, block_v, interpret)
    return loss


def fused_softmax_cross_entropy(logits, labels,
                                block_t: Optional[int] = None,
                                block_v: Optional[int] = None,
                                interpret: Optional[bool] = None):
    """Per-token ``-log_softmax(logits)[label]`` without materializing the
    (T, V) log-probability matrix.

    logits: (T, V) any float dtype; labels: (T,) integer class ids.
    Returns (T,) f32 losses — sum/mean (and psum, under shard_map) are the
    caller's.  Differentiable wrt ``logits`` (grad streams block-wise from
    an O(T) logsumexp residual, written in the logits dtype).  The blocks
    are chosen from the shape (``_tiles``) unless given.

    Under shard_map on a non-TPU backend the call falls back to the XLA
    math: interpret-mode kernels inline into the traced program, where the
    scratch-carried online recurrence cannot satisfy shard_map's
    varying-axes checks (compiled TPU kernels trace in a fresh context and
    are unaffected — same dispatch rule as ``ops.attention``).
    """
    interpret = _resolve_interpret(interpret)
    if interpret and _vma_of(logits):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(
            logp, labels.astype(jnp.int32)[:, None], axis=-1)[:, 0]
    bt, bv = _tiles(*logits.shape)
    return _fused_ce(logits, labels, block_t or bt, block_v or bv, interpret)


def _ce_fwd(logits, labels, block_t, block_v, interpret):
    loss, lse = _fwd_call(logits, labels, block_t, block_v, interpret)
    return loss, (logits, labels, lse)


def _ce_bwd(block_t, block_v, interpret, res, g):
    logits, labels, lse = res
    t, v = logits.shape
    bt = min(block_t, t)
    bv = min(block_v, v)
    sp = _specs(bt, bv)
    # per-row cotangent and lse ride the lane-broadcast layout
    ct = jnp.broadcast_to(g.astype(jnp.float32)[:, None], (t, _LANES))
    lse_b = jnp.broadcast_to(lse[:, None], (t, _LANES))
    dlogits = pl.pallas_call(
        functools.partial(_bwd_kernel, block_t=bt, block_v=bv, v_total=v),
        out_shape=out_struct((t, v), logits.dtype, logits),
        grid=(pl.cdiv(t, bt), pl.cdiv(v, bv)),
        in_specs=[sp["logits"], sp["rows"], sp["lanes"], sp["lanes"]],
        out_specs=sp["logits"],
        compiler_params=_params("parallel"),
        interpret=interpret,
        name="fused_ce_bwd",
    )(logits, labels.reshape(t, 1).astype(jnp.int32), lse_b, ct)
    return dlogits, None


_fused_ce.defvjp(_ce_fwd, _ce_bwd)
