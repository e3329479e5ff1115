"""Fused flash-attention forward AND backward kernels in Pallas (TPU).

The hot op of the long-context path.  XLA's unfused attention materializes
the (S×S) score matrix in HBM; these kernels stream k/v through VMEM with
the online-softmax recurrence, so HBM traffic stays O(S·D) per head and
VMEM residency stays O(tile²) — the standard flash schedule, shaped for the
MXU:

 - matmul operands go to the MXU in the dtype they arrive in (bf16 in
   training: one MXU pass; f32 inputs keep f32 matmuls) and every product
   accumulates in f32.  Scores, softmax statistics, ``p``, ``dp``, ``ds``
   and all accumulators are f32; ``p`` and ``ds`` are cast to the operand
   dtype only where they enter the second matmul — the recipe of the XLA
   oracle (``ops.attention.dot_product_attention``), line for line;
 - every kernel runs on a 3-D grid (batch·heads, outer tile, inner tile).
   The outer tile (``rows``) is the matmuls' streaming dimension; the inner
   tile (``span``) is what one grid step holds of the other operand, and a
   loop inside the step walks it in chunks, so a grid step carries
   ``rows x span`` of work against its fixed cost.  The loop's bounds are
   the causal / sliding-window frontier: chunks out of reach are never
   visited, inner tiles out of reach are never fetched (their block index
   is clamped onto the last live one), and the mask is built only for the
   chunks the diagonal or the window's edge crosses.  f32 VMEM scratch
   accumulators carry across the inner grid dimension and the output block
   is written on its last step, so no kernel holds a whole (S, D) operand
   unless S fits one tile (the one-kernel backward's dq accumulator is the
   exception, taken only where it fits) — which is what bounds sequence
   length;
 - tiles are chosen from what the call shows (``_tiles``: S, head_dim,
   itemsize, causal, window) inside a VMEM budget; an explicit
   ``block_q``/``block_k`` is honoured as given (one chunk per grid step
   when it is small);
 - forward, grid (B·H, S/rows, S/span): online softmax over k/v chunks;
   alongside the output it writes the per-row logsumexp, LANE-DENSE: one
   (1, S) row per batch·head, positions along the lanes — the O(S)
   statistics the backward needs (inside the kernels per-row statistics
   live broadcast over a 128-lane trailing dim, the TPU-tileable layout;
   that copy never leaves VMEM);
 - backward recomputes p from the saved (q, k, v, lse) and
   Δ = rowsum(dO ∘ O), computed once per call by XLA — no (S×S)
   intermediate is ever materialized.  It runs in TRANSPOSED space, grid
   (B·H, S/rows, S/span) with k/v as the outer tile: scores are
   (keys, queries), so lse and Δ are used as the lane-dense rows they are
   stored as and dv += pᵀ·dO, dk += dsᵀ·q need no transposed operand.
   Two forms, chosen by ``_backward_plan`` from the call's shapes alone:
     * ONE kernel (``flash_bwd``): per visible chunk one recompute of
       p = exp(k·qᵀ·scale − lse) and ds = p ∘ (v·dOᵀ − Δ) feeds dv, dk AND
       dq[chunk] += dsᵀ·k — five matmuls, one ``exp``.  dq accumulates in
       an f32 VMEM scratch that holds the whole (S, Dh) of the batch·head
       across its k/v tiles and is written out on the head's last step;
     * the two-pass pair, where that accumulator does not fit: a dq kernel
       (``flash_dq``, q as the outer tile, dq += (p ∘ (dO·vᵀ − Δ))·k) and
       the dk/dv kernel (``flash_dkv``) each recompute p — seven matmuls,
       two ``exp``, nothing that grows with S in VMEM.
   The rule: the one-kernel form is taken when, WITH the (rows, span) the
   call gets anyway (``_tiles``' or the explicit blocks), ``_vmem_bytes``
   plus the accumulator and the double-buffered dq block —
   S × max(Dh, 128) × (4 + 2·itemsize) bytes, a 64-wide row pads to the
   128 lanes — stays inside ``VMEM_BUDGET`` (24 MiB).  At the largest
   tiles that is 1 KiB a position in bf16 over 6.9 MiB (Dh = 64) or
   8.1 MiB (Dh = 128): every S up to 8,192 takes one kernel, 16,384 only
   at Dh = 64 (or under a window short enough to cap the rows), 32,768
   and longer never; f32 (1.5 KiB a position) up to 8,192.  No argument,
   environment variable or model takes part;
   the softmax scale multiplies the f32 scores and, once, the finished dq
   and dk accumulators.

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
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._vma import out_struct
from .attention import validate_window

# masked scores: finite, so that a chunk that hides a whole row gives
# exp(0) weights the next live chunk's correction wipes, never inf - inf
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
_LANES = 128  # TPU lane width: in VMEM, per-row stats are broadcast over it
_NT = (((1,), (1,)), ((), ()))  # a · bᵀ
_NN = (((1,), (0,)), ((), ()))  # a · b
_TN = (((0,), (0,)), ((), ()))  # aᵀ · b

# tile choice (``_tiles``): the budget is what the kernels may ask of VMEM
# for their double-buffered blocks, scratch and score-tile temporaries
VMEM_BUDGET = 24 << 20
_MAX_ROWS, _MAX_SPAN, _MAX_CHUNK = 512, 1024, 512


def _divisors(s: int, cap: int):
    """Tile sizes the kernels can walk ``s`` in, largest first: multiples
    of the 128-lane block that divide it, or the whole of a short one."""
    if s <= _LANES:
        return [s]
    return [t for t in range(min(cap, s) // _LANES * _LANES, 0, -_LANES)
            if s % t == 0]


def _chunk(span: int) -> int:
    """What a grid step walks its inner tile in: the largest lane-block
    divisor up to ``_MAX_CHUNK``, or the whole of a tile that has none (an
    explicit block that is no multiple of 128)."""
    return (_divisors(span, _MAX_CHUNK) or [span])[0]


def _vmem_bytes(rows: int, span: int, chunk: int, d: int,
                itemsize: int, dq_rows: int = 0) -> int:
    """What one kernel asks of VMEM (the dk/dv kernel, the largest):
    double-buffered blocks of the outer tile (k, v in; dk, dv out) and the
    inner one (q, dO, two statistics rows), the f32 accumulators and
    statistics scratch, and the (rows, chunk) f32 score-tile temporaries
    (s, p, dp, ds and the operand-dtype copies of p and ds).  ``dq_rows``:
    the one-kernel backward also holds that many rows (the whole S) of dq,
    as its f32 accumulator and its double-buffered output block, each row
    padded to the 128 lanes."""
    blocks = 2 * (4 * rows + 2 * span) * d * itemsize + 2 * 2 * 8 * span * 4
    scratch = rows * (2 * d + 2 * _LANES) * 4
    temps = rows * chunk * (4 * 4 + 2 * itemsize)
    dq = dq_rows * max(d, _LANES) * (4 + 2 * itemsize)
    return blocks + scratch + temps + dq


def _tiles(s: int, d: int, itemsize: int, causal: bool,
           window: Optional[int]) -> Tuple[int, int]:
    """(rows, span) for a call on sequences of ``s`` with heads of ``d``:
    the largest tiles that divide ``s`` and fit the VMEM budget.  ``rows``
    is the outer (streaming) tile, ``span`` the inner tile a grid step
    walks in chunks.  Under a mask the work a tile wastes past the frontier
    grows with ``rows`` (every row of a tile visits the chunks any of them
    reaches), so rows stay at or under half the reach; without one there
    is nothing to waste and they may fill the budget."""
    if s % _LANES if s > _LANES else s % 16:
        # ops.attention._pallas_eligible's rule: whole lane blocks, or one
        # short block of whole (bf16) sublane tiles
        raise ValueError(f"seq_len {s} is not tileable: neither a multiple "
                         f"of {_LANES} nor a single block of whole 16-row "
                         "sublane tiles; pass block_q/block_k or use the "
                         "XLA path")
    reach = s if not causal else min(s, window or s)
    row_cap = _MAX_ROWS if not causal else max(_LANES, min(_MAX_ROWS,
                                                           reach // 2))
    for rows in _divisors(s, row_cap):
        for span in _divisors(s, _MAX_SPAN):
            if _vmem_bytes(rows, span, _chunk(span), d,
                           itemsize) <= VMEM_BUDGET:
                return rows, span
    return _divisors(s, _LANES)[-1], _divisors(s, _LANES)[-1]


ONE_KERNEL, TWO_PASS = ("flash_bwd",), ("flash_dq", "flash_dkv")


def _backward_plan(s: int, d: int, itemsize: int, causal: bool,
                   window: Optional[int],
                   tiles: Optional[Tuple[int, int]] = None):
    """The kernels a call's backward runs, by name: ``ONE_KERNEL`` where
    the whole-S dq accumulator fits the VMEM budget beside the (rows, span)
    the dk/dv schedule has anyway (``tiles``: explicit blocks; ``_tiles``'
    otherwise — they are never shrunk to make room), else ``TWO_PASS``."""
    rows, span = tiles or _tiles(s, d, itemsize, causal, window)
    fits = _vmem_bytes(rows, span, _chunk(span), d, itemsize,
                       dq_rows=s) <= VMEM_BUDGET
    return ONE_KERNEL if fits else TWO_PASS


def _rep(x, n: int):
    """(rows, 128) lane-replicated statistics → (rows, n)."""
    return jnp.tile(x, (1, pl.cdiv(n, _LANES)))[:, :n] if n > _LANES \
        else x[:, :n]


def _visible(q0, k0, shape, q_axis: int, window: Optional[int]):
    """Causal (and sliding-window) visibility of a score tile whose
    ``q_axis`` holds queries q0.. and whose other axis holds keys k0..."""
    d = (q0 - k0) + (jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
                     - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))
    vis = d >= 0
    if window is not None:  # q sees (q_pos - window, q_pos]
        vis = vis & (d < window)
    return vis


def _clip_div(x, by: int, n: int, ceil: bool = False):
    """floor (or ceil) of x / by, clipped into [0, n]; x may be negative."""
    x = jnp.maximum(x, 0)
    return jnp.minimum((x + (by - 1 if ceil else 0)) // by, n)


def _k_chunks(q0, rows: int, k0, chunk: int, n: int, causal: bool,
              window: Optional[int]):
    """Which of the ``n`` chunks of ``chunk`` keys from ``k0`` the queries
    q0..q0+rows-1 reach: (lo, mid_lo, mid_hi, hi) — chunks in [lo, hi) are
    live, those in [mid_lo, mid_hi) need no mask."""
    if not causal:
        return 0, 0, n, n
    hi = _clip_div(q0 + rows - k0, chunk, n, ceil=True)
    full_hi = _clip_div(q0 - k0 + 1, chunk, n)
    if window is None:
        return 0, 0, jnp.minimum(full_hi, hi), hi
    lo = _clip_div(q0 - window + 1 - k0, chunk, n)
    full_lo = _clip_div(q0 + rows - window - k0, chunk, n, ceil=True)
    mid_lo = jnp.clip(full_lo, lo, hi)
    return lo, mid_lo, jnp.clip(full_hi, mid_lo, hi), hi


def _q_chunks(k0, rows: int, q0, chunk: int, n: int, causal: bool,
              window: Optional[int]):
    """The same for the dk/dv kernel: which chunks of ``chunk`` queries
    from ``q0`` reach the keys k0..k0+rows-1."""
    if not causal:
        return 0, 0, n, n
    lo = _clip_div(k0 - q0, chunk, n)
    full_lo = _clip_div(k0 + rows - 1 - q0, chunk, n, ceil=True)
    if window is None:
        return lo, jnp.maximum(full_lo, lo), n, n
    hi = _clip_div(k0 + rows + window - 1 - q0, chunk, n, ceil=True)
    full_hi = _clip_div(k0 + window - q0, chunk, n)
    mid_lo = jnp.clip(full_lo, lo, hi)
    return lo, mid_lo, jnp.clip(full_hi, mid_lo, hi), hi


def _offset(c, chunk: int, span: int):
    """Where chunk ``c`` starts in its tile (a tile of one chunk: 0, which
    a short unaligned sequence needs to be static)."""
    return 0 if chunk == span else pl.multiple_of(c * chunk, chunk)


def _walk(step, lo, mid_lo, mid_hi, hi):
    """Run ``step(chunk_index, masked=...)`` over the live chunks: masked
    where the frontier crosses, unmasked between."""
    for a, b, masked in ((lo, mid_lo, True), (mid_lo, mid_hi, False),
                         (mid_hi, hi, True)):
        if isinstance(a, int) and isinstance(b, int) and a >= b:
            continue  # statically empty (no mask, or no window)
        jax.lax.fori_loop(
            a, b, lambda c, _, masked=masked: step(c, masked=masked), None)


def _inner_index(rows: int, span: int, n: int, causal: bool,
                 window: Optional[int], inner_is_k: bool):
    """Index map of an inner-tile operand: grid step ``j`` of outer tile
    ``i`` is clamped onto the (first, last) inner tile any of the outer
    tile's rows reaches, so a step outside them re-uses the block already
    in VMEM (no fetch) — and walks no chunk."""
    def index(bh, i, j):
        if not causal:
            return bh, j, 0
        if inner_is_k:
            last = ((i + 1) * rows - 1) // span
            first = 0 if window is None else \
                jnp.maximum(i * rows - window + 1, 0) // span
        else:
            first = (i * rows) // span
            last = n - 1 if window is None else \
                jnp.minimum((i * rows + rows + window - 2) // span, n - 1)
        return bh, jnp.clip(j, first, last), 0
    return index


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale: float,
                causal: bool, window: Optional[int], rows: int, span: int,
                chunk: int):
    # outputs/scratch: [lse_ref,] m_scr, l_scr, acc_scr — the lse output only
    # exists on the training path (save_residuals); inference pays nothing
    lse_ref = rest[0] if len(rest) == 4 else None
    m_scr, l_scr, acc_scr = rest[-3:]
    qi, kj = pl.program_id(1), pl.program_id(2)
    q0, k0 = qi * rows, kj * span
    d = acc_scr.shape[-1]

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(c, masked):
        at = _offset(c, chunk, span)
        k, v = k_ref[0, pl.ds(at, chunk), :], v_ref[0, pl.ds(at, chunk), :]
        s = jax.lax.dot_general(q_ref[0], k, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_visible(q0, k0 + at, s.shape, 0, window),
                          s, MASK_VALUE)
        m_prev = m_scr[...]                                # (rows, 128)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _rep(m_next, chunk))               # (rows, chunk)
        alpha = jnp.exp(m_prev - m_next)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_next
        acc_scr[...] = _rep(alpha, d) * acc_scr[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)

    _walk(step, *_k_chunks(q0, rows, k0, chunk, span // chunk, causal,
                           window))

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] * _rep(1.0 / l, d)).astype(o_ref.dtype)
        if lse_ref is not None:
            # logsumexp of the scaled scores per row, as a lane-dense row:
            # p = exp(s - lse) in the backward
            lse_ref[0] = (m_scr[...] + jnp.log(l)).T[0:1, :]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, lse_scr, delta_scr, *, scale: float, causal: bool,
               window: Optional[int], rows: int, span: int, chunk: int):
    qi, kj = pl.program_id(1), pl.program_id(2)
    q0, k0 = qi * rows, kj * span

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        # the stored rows, once per q tile, as lane-replicated columns
        lse_scr[...] = jnp.broadcast_to(lse_ref[0], (_LANES, rows)).T
        delta_scr[...] = jnp.broadcast_to(delta_ref[0], (_LANES, rows)).T

    def step(c, masked):
        at = _offset(c, chunk, span)
        k, v = k_ref[0, pl.ds(at, chunk), :], v_ref[0, pl.ds(at, chunk), :]
        s = jax.lax.dot_general(q_ref[0], k, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_visible(q0, k0 + at, s.shape, 0, window),
                          s, MASK_VALUE)
        p = jnp.exp(s - _rep(lse_scr[...], chunk))         # (rows, chunk)
        dp = jax.lax.dot_general(do_ref[0], v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _rep(delta_scr[...], chunk))
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    _walk(step, *_k_chunks(q0, rows, k0, chunk, span // chunk, causal,
                           window))

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                scale: float, causal: bool, window: Optional[int], rows: int,
                span: int, chunk: int):
    # outputs/scratch: [dq_ref,] dk_ref, dv_ref, dk_scr, dv_scr[, dq_scr] —
    # with dq this is the one-kernel backward: dq_ref is the whole (S, Dh)
    # of the batch·head, dq_scr its f32 accumulator across the k/v tiles
    if len(rest) == 6:
        dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, dq_scr = rest
    else:
        (dk_ref, dv_ref, dk_scr, dv_scr), dq_ref, dq_scr = rest, None, None
    # transposed space: a score tile is (keys, queries), so the per-query
    # statistics are the (1, chunk) lane-dense rows they are stored as
    ki, qj = pl.program_id(1), pl.program_id(2)
    k0, q0 = ki * rows, qj * span
    last_q = qj == pl.num_programs(2) - 1

    @pl.when(qj == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if dq_ref is not None:
        @pl.when((ki == 0) & (qj == 0))
        def _init_dq():
            dq_scr[...] = jnp.zeros_like(dq_scr)

    def step(c, masked):
        qs = pl.ds(_offset(c, chunk, span), chunk)
        q, do = q_ref[0, qs, :], do_ref[0, qs, :]
        st = jax.lax.dot_general(k_ref[0], q, _NT,
                                 preferred_element_type=jnp.float32) * scale
        if masked:
            st = jnp.where(_visible(q0 + qs.start, k0, st.shape, 1, window),
                           st, MASK_VALUE)
        pt = jnp.exp(st - lse_ref[0, :, qs])               # (rows, chunk)
        dv_scr[...] += jax.lax.dot_general(
            pt.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[0], do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta_ref[0, :, qs])).astype(q.dtype)
        dk_scr[...] += jax.lax.dot_general(
            dst, q, _NN, preferred_element_type=jnp.float32)
        if dq_ref is not None:
            at = pl.multiple_of(q0 + qs.start, chunk)
            dq_scr[pl.ds(at, chunk), :] += jax.lax.dot_general(
                dst, k_ref[0], _TN, preferred_element_type=jnp.float32)

    _walk(step, *_q_chunks(k0, rows, q0, chunk, span // chunk, causal,
                           window))

    @pl.when(last_q)
    def _finalize():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    if dq_ref is not None:
        @pl.when(last_q & (ki == pl.num_programs(1) - 1))
        def _finalize_dq():
            dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _fold(t):
    """(B, S, H, D) → (B·H, S, D): one grid row per (batch, head)."""
    b, s, h, d = t.shape
    return t.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold(t, b: int):
    bh, s, d = t.shape
    return t.reshape(b, bh // b, s, d).transpose(0, 2, 1, 3)


def _call(kernel, name, tiles, q, *, scale, causal, window, interpret,
          outer="parallel", **pallas):
    """The part of a ``pallas_call`` the kernels share: the grid, the
    static parameters, the compiler's VMEM allowance.  ``outer``: the outer
    tile's dimension is ``"arbitrary"`` where an accumulator crosses it
    (the one-kernel backward's dq)."""
    rows, span, chunk = tiles
    bh, s, _ = q.shape
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, causal=causal, window=window,
                          rows=rows, span=span, chunk=chunk),
        grid=(bh, s // rows, s // span),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", outer, "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET + (8 << 20)),
        interpret=interpret, name=name, **pallas)


def _specs(tiles, s: int, d: int, causal, window, inner_is_k: bool):
    """BlockSpecs of an outer-tile operand, an inner-tile operand, and the
    statistics rows that go with queries of either."""
    rows, span, _ = tiles
    inner = _inner_index(rows, span, s // span, causal, window, inner_is_k)
    outer_spec = pl.BlockSpec((1, rows, d), lambda bh, i, j: (bh, i, 0))
    inner_spec = pl.BlockSpec((1, span, d), inner)
    outer_row = pl.BlockSpec((1, 1, rows), lambda bh, i, j: (bh, 0, i))
    inner_row = pl.BlockSpec(
        (1, 1, span), lambda bh, i, j: (bh, 0, inner(bh, i, j)[1]))
    return outer_spec, inner_spec, outer_row, inner_row


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "window", "tiles", "interpret", "save_residuals"))
def _flash_forward(q, k, v, *, scale: float, causal: bool,
                   window: Optional[int], tiles, interpret: bool,
                   save_residuals: bool = True):
    """Jitted, like ``paged_decode_attention``: a model's layers share ONE
    trace of each kernel and one lowering to Mosaic inside the step
    program that calls them."""
    b, s, h, d = q.shape
    rows = tiles[0]
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    q_spec, k_spec, q_row, _ = _specs(tiles, s, d, causal, window, True)

    # out_struct: under shard_map (tp/ulysses paths on TPU) pallas outputs
    # must declare the mesh axes they vary over — they vary as q does
    out_shape = [out_struct(qf.shape, q.dtype, qf)]
    out_specs = [q_spec]
    if save_residuals:  # inference skips the lse write entirely
        out_shape.append(out_struct((b * h, 1, s), jnp.float32, qf))
        out_specs.append(q_row)
    res = _call(_fwd_kernel, "flash_fwd", tiles, qf, scale=scale,
                causal=causal, window=window, interpret=interpret,
                out_shape=tuple(out_shape),
                in_specs=[q_spec, k_spec, k_spec],
                out_specs=tuple(out_specs),
                scratch_shapes=[pltpu.VMEM((rows, _LANES), jnp.float32),
                                pltpu.VMEM((rows, _LANES), jnp.float32),
                                pltpu.VMEM((rows, d), jnp.float32)],
                )(qf, kf, vf)
    return _unfold(res[0], b), (res[1] if save_residuals else None)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "window", "tiles", "kv_tiles", "plan", "interpret"))
def _flash_backward(q, k, v, out, lse, g, *, scale: float, causal: bool,
                    window: Optional[int], tiles, kv_tiles, plan,
                    interpret: bool):
    """``plan``: ``_backward_plan``'s answer for the call — ONE_KERNEL on
    ``kv_tiles``, or TWO_PASS (the dq kernel on ``tiles``, dk/dv on
    ``kv_tiles``)."""
    b, s, h, d = q.shape
    qf, kf, vf, gf = _fold(q), _fold(k), _fold(v), _fold(g)
    # Δ = rowsum(dO ∘ O), once per call, stored as lse is
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)
    delta = delta.transpose(0, 2, 1).reshape(b * h, 1, s)
    shared = dict(scale=scale, causal=causal, window=window,
                  interpret=interpret)

    # under shard_map the outputs vary as their primals do: dq as q, dk/dv
    # as k/v
    dq_shape = out_struct(qf.shape, q.dtype, qf)
    one = plan == ONE_KERNEL
    if not one:
        rows = tiles[0]
        q_spec, k_spec, q_row, _ = _specs(tiles, s, d, causal, window, True)
        dq = _call(_dq_kernel, "flash_dq", tiles, qf, out_shape=dq_shape,
                   in_specs=[q_spec, k_spec, k_spec, q_spec, q_row, q_row],
                   out_specs=q_spec,
                   scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32),
                                   pltpu.VMEM((rows, _LANES), jnp.float32),
                                   pltpu.VMEM((rows, _LANES), jnp.float32)],
                   **shared)(qf, kf, vf, gf, lse, delta)

    # the dk/dv schedule; the one kernel adds dq's whole-S output block
    # (first) and accumulator (last) to it; either plan names it last
    rows = kv_tiles[0]
    k_spec, q_spec, _, q_row = _specs(kv_tiles, s, d, causal, window, False)
    whole = pl.BlockSpec((1, s, d), lambda bh, i, j: (bh, 0, 0))
    res = _call(_dkv_kernel, plan[-1], kv_tiles, qf,
                outer="arbitrary" if one else "parallel",
                out_shape=(dq_shape,) * one + (
                    out_struct(kf.shape, k.dtype, kf),
                    out_struct(vf.shape, v.dtype, vf)),
                in_specs=[q_spec, k_spec, k_spec, q_spec, q_row, q_row],
                out_specs=(whole,) * one + (k_spec, k_spec),
                scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)] * 2
                + [pltpu.VMEM((s, d), jnp.float32)] * one,
                **shared)(qf, kf, vf, gf, lse, delta)
    dq, dk, dv = res if one else (dq, *res)
    return _unfold(dq, b), _unfold(dk, b), _unfold(dv, b)


def _resolve(q, causal, scale, block_q, block_k, interpret, window):
    """nondiff_argnums hand each custom_vjp entry point the raw argument
    values, so defaults resolve in one place for primal/fwd/bwd alike:
    the static parameters of the jitted kernels' wrappers, with the tiles
    of the q-outer kernels and of the k/v-outer schedule (dk/dv, or the
    one-kernel backward) as (rows, span, chunk)."""
    window = validate_window(window, causal)
    s, d = q.shape[1], q.shape[3]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows, span = (_tiles(s, d, q.dtype.itemsize, causal, window)
                  if block_q is None or block_k is None else (None, None))
    bq = rows if block_q is None else min(block_q, s)
    bk = span if block_k is None else min(block_k, s)
    if s % bq or s % bk:
        raise ValueError(f"seq_len {s} not divisible by blocks ({bq},{bk})")
    # chosen tiles are (rows, span) for every kernel; explicit blocks tile
    # q and k as they say, whichever of the two a kernel has outermost
    kv = (bq, bk) if block_q is None and block_k is None else (bk, bq)
    return dict(scale=float(scale), causal=causal, window=window,
                interpret=interpret), \
        (bq, bk, _chunk(bk)), (kv[0], kv[1], _chunk(kv[1]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None):
    """Flash attention on (B, S, H, Dh) tensors; same contract as
    ``ops.attention.dot_product_attention``, including sliding-window
    (``window``, requires causal) — out-of-window k chunks are skipped
    entirely, so windowed compute is O(S·W) per head.  ``block_q`` /
    ``block_k``: ``None`` (the default) lets ``_tiles`` choose from the
    shapes; a number is honoured as given."""
    static, tiles, _ = _resolve(q, causal, scale, block_q, block_k,
                                interpret, window)
    out, _ = _flash_forward(q, k, v, tiles=tiles, save_residuals=False,
                            **static)
    return out


def _fwd(q, k, v, causal, scale, block_q, block_k, interpret, window):
    static, tiles, _ = _resolve(q, causal, scale, block_q, block_k,
                                interpret, window)
    out, lse = _flash_forward(q, k, v, tiles=tiles, **static)
    return out, (q, k, v, out, lse)


def _bwd(causal, scale, block_q, block_k, interpret, window, res, g):
    q, k, v, out, lse = res
    static, tiles, kv_tiles = _resolve(q, causal, scale, block_q, block_k,
                                       interpret, window)
    plan = _backward_plan(q.shape[1], q.shape[3], q.dtype.itemsize, causal,
                          static["window"], kv_tiles[:2])
    return _flash_backward(q, k, v, out, lse, g, tiles=tiles,
                           kv_tiles=kv_tiles, plan=plan, **static)


flash_attention.defvjp(_fwd, _bwd)
