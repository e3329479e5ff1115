"""Paged decode attention in Pallas (TPU): one query token per row, K and V
read IN PLACE from the paged arena through the block tables.

The serving engine's decode step attends one new token per slot over that
slot's cached positions.  The XLA path (``ops.attention.paged_gather`` +
``dot_product_attention``) first materialises a ``(slots, view_len, ...)``
copy of K and of V for EVERY slot — live or not, whatever its length — and
runs dense attention over it.  This kernel never builds that view: per row
it walks ``ceil(length / page_size)`` pages of the arena by DMA, several
pages to a compute block, double-buffered, with the online-softmax
recurrence in f32 (the flash schedule of ``ops/flash_attention.py``, one
query row per head).  A row of length 0 reads nothing and returns zeros.
Cost grows with the LIVE tokens of the batch, not with ``slots x view_len``.

Layout.  The arena stores a position's K (or V) for all kv heads as ONE
lane-dense row of ``F = Hkv * Dh`` features (``core.decode.
init_paged_arena``), so a page is a contiguous ``(page_size, F)`` slab and
``arena.reshape(pages, page_size, F)`` is free.  Heads are NOT separated by
slicing 64-lane columns out of that row; instead the query is laid out
block-diagonally — row h of ``q_bd`` holds head h's query in the columns of
its kv head and zeros elsewhere — so that

    scores (H, T) = q_bd (H, F) . K (T, F)^T        one MXU matmul, all heads
    acc    (H, F) += p (H, T) . V (T, F)            one MXU matmul, all heads

and head h's output is the ``Dh`` columns of ``acc[h]`` under its own kv
head (the other columns are products with other heads' values and are
masked off).  The matmuls are weight-load bound at H query rows either way,
so the off-diagonal work costs nothing, K and V are fed to the MXU as they
are stored (bf16 in, f32 accumulate — no f32 copy of anything pool-sized),
and grouped-query attention (Hkv < H) is the same two matmuls.

Work list.  Rows are ragged, so the kernel first enumerates the (row, block)
pairs that hold live positions — a scalar loop over the prefetched lengths
into SMEM, a microsecond — and then is ONE loop over that list: start the
DMAs of item i + 1, wait for item i, compute.  The prefetch so runs across
row boundaries, and a dead row costs one scalar comparison.

Numerics match the XLA oracle's recipe: scores and softmax in f32 from
bf16 operands, probabilities cast to the value dtype for the second matmul,
f32 accumulation, output in ``q.dtype``.  Positions at or past a row's
length never reach the output, whatever they hold (NaN included): masked
scores are replaced, masked value rows are zeroed before the matmul.

On non-TPU backends the kernel runs in Pallas interpret mode (tier-1 tests);
``tests/test_chip_compile.py`` compiles it for a described v5e at the
serving cell's shapes and ``chip_smoke.py`` (phase ``kernels``) checks it
against the gather path on the chip.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
_LANES = 128          # per-row softmax stats are stored broadcast over it
_BLOCK_TOKENS = 256   # positions per compute block (pages_per_block pages)
#: whole-array VMEM residents (q_bd and the output) plus the page buffers
#: must fit the kernel's scoped VMEM; larger batches take the XLA path
_VMEM_BUDGET = 48 << 20


def _sublanes(dtype) -> int:
    """Rows of one packed VMEM tile: 8 for 32-bit, 16 for bf16, 32 for 8."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _pages_per_block(page_size: int, view_len: int) -> int:
    """Pages DMA'd and attended together: ``_BLOCK_TOKENS`` positions, or
    the whole view where it is shorter."""
    return max(1, min(_BLOCK_TOKENS, int(view_len)) // int(page_size))


def _pad_heads(h: int, dtype) -> int:
    """Query rows of the block-diagonal q: whole packed sublane tiles."""
    return -(-h // _sublanes(dtype)) * _sublanes(dtype)


def _vmem_bytes(b: int, hp: int, f: int, ppb: int, page_size: int,
                q_dtype, kv_dtype) -> int:
    qo = 2 * b * hp * f * jnp.dtype(q_dtype).itemsize
    bufs = 2 * 2 * ppb * page_size * f * jnp.dtype(kv_dtype).itemsize
    return qo + bufs + hp * f * 4 + 2 * hp * _LANES * 4


def kernel_tiles(q_shape, q_dtype, arena_shape, kv_dtype, page_size: int,
                 view_len: int) -> bool:
    """Can the compiled kernel take these shapes?  (B, H, Dh) queries over
    a ``(slots, Hkv * Dh)`` arena: feature rows a whole number of 128-lane
    tiles, pages a whole number of packed sublane tiles (so the page view
    of the arena is a free reshape and a page DMA lands tile-aligned), and
    the VMEM residents inside the budget."""
    if len(arena_shape) != 2 or len(q_shape) != 3:
        return False
    b, h, dh = q_shape
    f = arena_shape[1]
    vmem = _vmem_bytes(b, _pad_heads(h, q_dtype), f,
                       _pages_per_block(page_size, view_len), page_size,
                       q_dtype, kv_dtype)
    return (f % dh == 0 and h % (f // dh) == 0 and f % _LANES == 0
            and page_size % _sublanes(kv_dtype) == 0
            and arena_shape[0] % page_size == 0 and vmem <= _VMEM_BUDGET)


def _paged_decode_kernel(len_ref, tbl_ref,                      # prefetch
                         q_ref, k_hbm, v_hbm,                   # inputs
                         o_ref,                                 # output
                         item_ref, kbuf, vbuf, sems, m_scr, l_scr, acc_scr,
                         *, scale: float, page_size: int, ppb: int,
                         max_blocks: int, table_len: int, group: int,
                         head_dim: int):
    hp, f = acc_scr.shape
    t = ppb * page_size
    o_ref[...] = jnp.zeros_like(o_ref)       # rows with no item stay zero

    # the work list: (row, block) pairs holding live positions, row-major,
    # as flat ids row * max_blocks + block
    def list_row(row, n):
        def list_block(blk, n):
            item_ref[n] = row * max_blocks + blk
            return n + 1
        return jax.lax.fori_loop(0, -(-len_ref[row] // t), list_block, n)

    n_items = jax.lax.fori_loop(0, len_ref.shape[0], list_row, 0)

    def locate(item):
        it = item_ref[item]
        row = it // max_blocks
        blk = it - row * max_blocks
        return row, blk, len_ref[row]

    def page_copies(item, slot, go):
        """Start (or wait for) the DMAs of one item's live pages."""
        row, blk, length = locate(item)
        live_pages = -(-length // page_size) - blk * ppb
        base = row * table_len + blk * ppb
        for j in range(ppb):
            @pl.when(j < live_pages)
            def _():
                page = tbl_ref[base + j]
                for hbm, buf, s in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                    go(pltpu.make_async_copy(hbm.at[page], buf.at[slot, j],
                                             sems.at[s, slot]))

    @pl.when(n_items > 0)
    def _():
        page_copies(0, 0, lambda c: c.start())

    # head h may keep the columns of its own kv head, h // group
    h_idx = jax.lax.broadcasted_iota(jnp.int32, (hp, f), 0)
    c_idx = jax.lax.broadcasted_iota(jnp.int32, (hp, f), 1)
    own = (h_idx // group) == (c_idx // head_dim)

    def body(item, carry):
        slot = item % 2
        row, blk, length = locate(item)

        @pl.when(item + 1 < n_items)
        def _():
            page_copies(item + 1, 1 - slot, lambda c: c.start())

        page_copies(item, slot, lambda c: c.wait())

        @pl.when(blk == 0)
        def _():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        k = kbuf[slot].reshape(t, f)
        v = vbuf[slot].reshape(t, f)
        first = blk * t
        # value rows past the length (a partial page's tail, buffer pages
        # this item never loaded) must not meet a zero probability as NaN
        v_live = (first + jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0)
                  < length)
        v = jnp.where(v_live, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(q_ref[row], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s_live = (first + jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
                  < length)
        s = jnp.where(s_live, s, NEG_INF)                      # (hp, t)
        m = m_scr[:, 0:1]
        # every listed block holds a live position, so new_m is finite
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - new_m)
        corr = jnp.exp(m - new_m)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        l = l_scr[:, 0:1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = jnp.broadcast_to(new_m, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l, l_scr.shape)

        @pl.when((blk + 1) * t >= length)
        def _():
            out = jnp.where(own, acc_scr[...] / l, 0.0)
            o_ref[row] = out.astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, n_items, body, 0)


@functools.partial(jax.jit,
                   static_argnames=("page_size", "scale", "interpret"))
def paged_decode_attention(q, k_arena, v_arena, block_tables, lengths,
                           page_size: int, *, scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    """Single-token attention over a paged KV arena.

    ``q``: (B, H, Dh), the new token's queries.  ``k_arena``/``v_arena``:
    ``(slots, Hkv * Dh)`` with ``slots = pages * page_size`` — a position's
    kv heads side by side in one row (``init_paged_arena``), the row's new
    K/V already written.  ``block_tables``: (B, T) int32, row r's logical
    page i lives at physical page ``block_tables[r, i]``.  ``lengths``:
    (B,) int32, how many leading positions of each row to attend (0: none,
    the row comes back zero); entries of the table past a row's length are
    never read.  Returns (B, H, Dh) in ``q.dtype``.  H must be a multiple
    of Hkv (grouped-query attention shares a kv head among H / Hkv query
    heads).

    Jitted, so that a model's layers — the same shapes layer after layer —
    share ONE trace of the kernel and one lowering to Mosaic inside the
    step program that calls them (24 separate ``pallas_call``s cost the
    serving cell 15 s of set-up on every start; PERF.md section 6,
    PR 25)."""
    b, h, dh = q.shape
    slots, f = k_arena.shape
    hkv, page_size = f // dh, int(page_size)
    if f % dh or h % hkv:
        raise ValueError(f"arena rows of {f} features do not hold kv heads "
                         f"of {dh} that divide {h} query heads")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = (1.0 / math.sqrt(dh)) if scale is None else float(scale)
    table_len = block_tables.shape[1]
    view_len = table_len * page_size
    ppb = _pages_per_block(page_size, view_len)
    t = ppb * page_size
    max_blocks = -(-view_len // t)
    lengths = jnp.minimum(lengths.astype(jnp.int32), view_len)

    # block-diagonal queries, heads padded to whole sublane tiles
    hp = _pad_heads(h, q.dtype)
    group = h // hkv
    own = (jnp.arange(hp)[:, None] // group) == (jnp.arange(f)[None] // dh)
    q_pad = jnp.pad(q, ((0, 0), (0, hp - h), (0, 0)))
    q_bd = jnp.where(own[None], jnp.tile(q_pad, (1, 1, hkv)),
                     jnp.zeros((), q.dtype))                   # (B, hp, F)

    pages = slots // page_size
    vmem = _vmem_bytes(b, hp, f, ppb, page_size, q.dtype, k_arena.dtype)
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale,
                          page_size=page_size, ppb=ppb,
                          max_blocks=max_blocks, table_len=table_len,
                          group=group, head_dim=dh),
        out_shape=jax.ShapeDtypeStruct((b, hp, f), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.SMEM((b * max_blocks,), jnp.int32),
                pltpu.VMEM((2, ppb, page_size, f), k_arena.dtype),
                pltpu.VMEM((2, ppb, page_size, f), v_arena.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hp, _LANES), jnp.float32),
                pltpu.VMEM((hp, _LANES), jnp.float32),
                pltpu.VMEM((hp, f), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + (16 << 20)),
        interpret=interpret,
        name="paged_decode",
    )(lengths, block_tables.reshape(-1).astype(jnp.int32), q_bd,
      k_arena.reshape(pages, page_size, f),
      v_arena.reshape(pages, page_size, f))
    # head h's output sits under its own kv head; the rest was zeroed
    return out.reshape(b, hp, hkv, dh).sum(axis=2)[:, :h]
