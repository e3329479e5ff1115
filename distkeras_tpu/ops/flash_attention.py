"""Fused flash-attention forward AND backward kernels in Pallas (TPU).

The hot op of the long-context path.  XLA's unfused attention materializes
the (S×S) score matrix in HBM; these kernels stream k/v blocks through VMEM
with the online-softmax recurrence, so HBM traffic stays O(S·D) per head and
VMEM residency stays O(block²) — the standard flash schedule, shaped for the
MXU:

 - every kernel runs on a 3-D grid (batch·heads, outer block, inner block):
   the *inner* grid dimension streams the contraction blocks, with f32 VMEM
   scratch accumulators carried across inner iterations and the output block
   written on the last one (TPU grids execute sequentially, innermost
   fastest, and an output block whose index map ignores the inner dim stays
   resident in VMEM) — so no kernel ever holds a whole (S, D) operand in
   VMEM, which is what bounds sequence length;
 - forward, grid (B·H, S/block_q, S/block_k): online-softmax over k/v
   blocks; alongside the output it writes the per-row logsumexp — the O(S)
   statistics the backward needs;
 - backward is the classic two-pass recompute schedule over the saved
   (q, k, v, o, lse) — no (S×S) intermediate is ever materialized:
     * dq kernel, grid (B·H, S/block_q, S/block_k): recompute
       p = exp(q·kᵀ·scale − lse), accumulate dq += (p ∘ (dO·vᵀ − Δ))·k·scale
       with Δ = rowsum(dO ∘ O) computed in-VMEM from the resident blocks;
     * dk/dv kernel, grid (B·H, S/block_k, S/block_q): accumulate
       dv += pᵀ·dO and dk += (p ∘ (dO·vᵀ − Δ))ᵀ·q·scale;
   causal inner blocks that are fully masked skip their compute via
   ``pl.when`` (the standard ~2x causal saving);
 - scores/accumulators are f32 tiles — MXU matmuls with f32 accumulation,
   2-D shapes throughout (TPU vector layout); per-row statistics are stored
   broadcast over a 128-lane trailing dim (the TPU-tileable layout for
   per-row stats, same trick as jax's reference TPU flash kernel).

On non-TPU backends the kernels run in Pallas interpret mode (tests); the
``ops.attention.attention`` dispatcher only routes here on TPU.  The XLA
reference (``ops.attention.dot_product_attention``) stays the correctness
oracle — gradient parity is asserted in tests/test_flash_attention.py
(interpret mode); tests/test_chip_compile.py compiles the kernels for a
described v5e, and ``chip_smoke.py`` checks them against the oracle on
the chip.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._vma import out_struct
from .attention import validate_window

NEG_INF = float("-inf")
_LANES = 128  # TPU lane width: per-row stats are stored broadcast over it


def _causal_mask(s, q0, k0, bq, bk, window=None):
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    hide = k_pos > q_pos
    if window is not None:  # sliding window: q sees (q_pos-window, q_pos]
        hide = hide | (k_pos <= q_pos - window)
    return jnp.where(hide, NEG_INF, s)


def _live_kq(qi, kj, bq, bk, causal, window):
    """Is k-block kj within reach of q-block qi?  Causal skips the future;
    a sliding window additionally skips blocks entirely behind the window —
    that drops compute to O(S·W) per head instead of the full causal
    triangle."""
    live = (kj * bk < (qi + 1) * bq) if causal else True
    if window is not None:
        live = live & ((kj + 1) * bk + window > qi * bq + 1)
    return live


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  num_k: int, window: Optional[int] = None):
    # outputs/scratch: [lse_ref,] m_scr, l_scr, acc_scr — the lse output only
    # exists on the training path (save_residuals); inference pays nothing
    lse_ref = rest[0] if len(rest) == 4 else None
    m_scr, l_scr, acc_scr = rest[-3:]
    qi, kj = pl.program_id(1), pl.program_id(2)
    bq, bk = block_q, block_k

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: blocks entirely in the future of this q block contribute
    # nothing — skip their compute (the standard flash causal saving);
    # a window also skips blocks entirely behind it
    live = _live_kq(qi, kj, bq, bk, causal, window)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, qi * bq, kj * bk, bq, bk, window)
        m = m_scr[:, 0:1]
        l = l_scr[:, 0:1]
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        safe = jnp.where(new_m == NEG_INF, 0.0, new_m)
        p = jnp.exp(s - safe)                             # (bq, bk)
        corr = jnp.exp(m - safe)                          # (bq, 1)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = jnp.broadcast_to(new_m, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l, l_scr.shape)

    @pl.when(kj == num_k - 1)
    def _finalize():
        m = m_scr[:, 0:1]
        l = l_scr[:, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            # logsumexp of the scaled scores per row: p = exp(s - lse) in
            # the backward.  Fully-masked rows keep a finite lse (their p
            # is 0 wherever s = -inf).
            safe_m = jnp.where(m == NEG_INF, 0.0, m)
            lse_ref[0] = jnp.broadcast_to(safe_m + jnp.log(l),
                                          lse_ref.shape[1:])


def _flash_forward(q, k, v, scale: float, causal: bool, block_q: int,
                   block_k: int, interpret: bool,
                   save_residuals: bool = True,
                   window: Optional[int] = None):
    b, s, h, d = q.shape
    bq = min(block_q, s)
    bk = min(block_k, s)
    if s % bq or s % bk:
        raise ValueError(f"seq_len {s} not divisible by blocks ({bq},{bk})")
    # (B, S, H, D) → (B·H, S, D): one grid row per (batch, head)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    qf, kf, vf = fold(q), fold(k), fold(v)

    # out_struct: under shard_map (tp/ulysses paths on TPU) pallas outputs
    # must declare the mesh axes they vary over — they vary as q does
    out_shape = [out_struct(qf.shape, q.dtype, qf)]
    out_specs = [pl.BlockSpec((1, bq, d), lambda bh, qi, kj: (bh, qi, 0))]
    if save_residuals:  # inference skips the O(128·S) lse write entirely
        out_shape.append(
            out_struct((b * h, s, _LANES), jnp.float32, qf))
        out_specs.append(
            pl.BlockSpec((1, bq, _LANES), lambda bh, qi, kj: (bh, qi, 0)))

    res = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, num_k=s // bk,
                          window=window),
        out_shape=tuple(out_shape),
        grid=(b * h, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, kj: (bh, kj, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, kj: (bh, kj, 0)),
        ],
        out_specs=tuple(out_specs),
        scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)
    out = res[0]
    lse = res[1] if save_residuals else None
    unfold = lambda t: t.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return unfold(out), lse


def _dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dq_scr,
               *, scale: float, causal: bool, block_q: int, block_k: int,
               num_k: int, window: Optional[int] = None):
    qi, kj = pl.program_id(1), pl.program_id(2)
    bq, bk = block_q, block_k

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = _live_kq(qi, kj, bq, bk, causal, window)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)                  # (bq, d)
        do = do_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, 0:1]                          # (bq, 1)
        # Δ = rowsum(dO ∘ O), computed in-VMEM from the resident blocks
        delta = jnp.sum(do * o, axis=-1, keepdims=True)   # (bq, 1)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi * bq, kj * bk, bq, bk, window)
        p = jnp.exp(s - lse)                              # (bq, bk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                     # (bq, bk)
        dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == num_k - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale: float, causal: bool, block_q: int,
                block_k: int, num_q: int, window: Optional[int] = None):
    ki, qi = pl.program_id(1), pl.program_id(2)
    bq, bk = block_q, block_k

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # causal: q blocks entirely before this k block see none of it; a
    # window also skips q blocks entirely past this k block's reach
    live = _live_kq(qi, ki, bq, bk, causal, window)

    @pl.when(live)
    def _step():
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)                  # (bq, d)
        do = do_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, 0:1]
        delta = jnp.sum(do * o, axis=-1, keepdims=True)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi * bq, ki * bk, bq, bk, window)
        p = jnp.exp(s - lse)                              # (bq, bk)
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # pᵀ·dO (bk, d)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                     # (bq, bk)
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # dsᵀ·q (bk, d)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, scale: float, causal: bool,
                    block_q: int, block_k: int, interpret: bool,
                    window: Optional[int] = None):
    b, s, h, d = q.shape
    bq = min(block_q, s)
    bk = min(block_k, s)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    qf, kf, vf, of, gf = fold(q), fold(k), fold(v), fold(out), fold(g)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, num_k=s // bk,
                          window=window),
        out_shape=out_struct(qf.shape, q.dtype, qf),
        grid=(b * h, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, kj: (bh, kj, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, kj: (bh, kj, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh, qi, kj: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, kj: (bh, qi, 0)),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )(qf, kf, vf, of, gf, lse)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, num_q=s // bq,
                          window=window),
        out_shape=(out_struct(kf.shape, k.dtype, kf),
                   out_struct(vf.shape, v.dtype, vf)),
        grid=(b * h, s // bk, s // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh, ki, qi: (bh, qi, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0))),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        name="flash_dkv",
    )(qf, kf, vf, of, gf, lse)

    unfold = lambda t: t.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return unfold(dq), unfold(dk), unfold(dv)


def _resolve(q, scale, interpret):
    """nondiff_argnums hand each custom_vjp entry point the raw argument
    values, so defaults resolve in one place for primal/fwd/bwd alike."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return scale, interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None,
                    window: Optional[int] = None):
    """Flash attention on (B, S, H, Dh) tensors; same contract as
    ``ops.attention.dot_product_attention``, including sliding-window
    (``window``, requires causal) — out-of-window k blocks are skipped
    entirely, so windowed compute is O(S·W) per head."""
    window = validate_window(window, causal)
    scale, interpret = _resolve(q, scale, interpret)
    out, _ = _flash_forward(q, k, v, scale, causal, block_q, block_k,
                            interpret, save_residuals=False, window=window)
    return out


def _fwd(q, k, v, causal, scale, block_q, block_k, interpret, window):
    window = validate_window(window, causal)
    scale, interpret = _resolve(q, scale, interpret)
    out, lse = _flash_forward(q, k, v, scale, causal, block_q, block_k,
                              interpret, window=window)
    return out, (q, k, v, out, lse)


def _bwd(causal, scale, block_q, block_k, interpret, window, res, g):
    q, k, v, out, lse = res
    scale, interpret = _resolve(q, scale, interpret)
    return _flash_backward(q, k, v, out, lse, g, scale, causal,
                           block_q, block_k, interpret, window=window)


flash_attention.defvjp(_fwd, _bwd)
