"""Autoregressive decoding with a KV cache for Sequential causal LMs.

No reference counterpart (SURVEY.md §2.3: the reference has no sequence
models at all) — this completes the long-context layer's inference story.
Training materializes attention over the full sequence; decoding re-runs
one token at a time against cached k/v, so each step is O(S) instead of
O(S²), and with grouped-query attention (``MultiHeadAttention
num_kv_heads``) the cache shrinks by ``num_heads / num_kv_heads``.

Design: rather than adding an incremental-apply method to every layer, one
walker here understands the sequence-model layer kinds (``Embedding``,
``PositionalEmbedding``, ``TransformerBlock``, ``LayerNormalization``,
``Dense``) and reuses their own helpers (``_project``,
``LayerNormalization.apply``) plus ``ops.attention.dot_product_attention``
(via its ``q_offset``/``kv_length`` hooks), so decode numerics ARE the
full-forward numerics — there is no forked attention implementation.

The walker is length-generic: ``generate`` prefills the whole prompt in
ONE batched forward (MXU-shaped (B, P, D) matmuls, all P cache slots
written in parallel), then scans single-token steps for the continuation.
``decode_step`` is the jittable single-token form.
"""

from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp

tmap = jax.tree_util.tree_map

from .layers import (Dense, Embedding, HybridBlock, LayerNormalization,
                     MultiHeadAttention, PositionalEmbedding, RMSNorm,
                     TiedHead, TransformerBlock, _project, params_of,
                     scope_names)

_STATELESS = (LayerNormalization, RMSNorm, Dense, TiedHead)
#: the layers that keep per-request state.  What they keep is their mixer's
#: ``state_kind`` — ``kv``: keys and values of every position (a dense slab,
#: or rows of ``Hkv * Dh`` features in a paged arena); ``recurrent``: a
#: fixed-size pytree a row, whatever the context; ``none``: the block has no
#: mixer and keeps nothing — and the cached step dispatches on it
#: (``_CACHED_MIX``), not on the block's class
_BLOCKS = (TransformerBlock, HybridBlock)


def _check_supported(model) -> None:
    for layer in model.layers:
        if not isinstance(layer, (Embedding, PositionalEmbedding)
                          + _BLOCKS + _STATELESS):
            raise ValueError(
                f"decode: unsupported layer kind {layer.kind!r} — cached "
                "decoding walks Embedding / PositionalEmbedding / "
                "TransformerBlock / HybridBlock (a GatedAttention, "
                "MultiHeadAttention, KimiDeltaAttention or Mamba2Mixer "
                "mixer, a SparseMoE or GatedMLP, or both) / "
                "LayerNormalization / RMSNorm / Dense / TiedHead sequences "
                "(transformer_lm and hybrid_lm)")
        if isinstance(layer, _BLOCKS) and not layer.causal:
            raise ValueError(
                "decode: TransformerBlock(causal=False) — autoregressive "
                "decoding is only meaningful for causal models, and the "
                "cached step would silently diverge from the full forward")
        if isinstance(layer, _BLOCKS) and \
                layer.state_kind not in _CACHED_MIX:
            raise ValueError(f"decode: no cached step for state kind "
                             f"{layer.state_kind!r} ({layer.kind})")


def has_recurrent_state(model) -> bool:
    """Does some layer keep a fixed-size recurrent state instead of keys and
    values?  Then a request's past is NOT all in its KV blocks: prefix
    sharing, preemption and block transfer, which assume it is, do not
    apply (``ServingEngine`` decides from this, never from an option)."""
    return any(isinstance(layer, _BLOCKS)
               and layer.state_kind == "recurrent"
               for layer in model.layers)


def _context_limit(model) -> Optional[int]:
    for layer in model.layers:
        if isinstance(layer, PositionalEmbedding):
            return layer.max_len
    return None


def _vocab_size(model) -> Optional[int]:
    for layer in model.layers:
        if isinstance(layer, Embedding):
            return layer.input_dim
    return None


def _validate_rolling(model) -> None:
    """Every block must carry a window for a ring cache to be sound:
    without one, old positions stay visible and must stay cached."""
    for layer in model.layers:
        if isinstance(layer, _BLOCKS) and (
                layer.state_kind != "kv"
                or layer.mixer().attention_window is None):
            raise ValueError(
                "rolling=True needs attention_window on every "
                "TransformerBlock: without a window, old positions stay "
                "visible and must stay cached (and a recurrent layer keeps "
                "no positions to roll over)")


def init_cache(model, batch: int, max_len: int,
               rolling: bool = False, kv_dtype: Optional[str] = None,
               ring_slack: int = 0) -> List[Any]:
    """One cache slot per layer: ``{"k", "v"}`` of shape
    (batch, max_len, num_kv_heads, key_dim) for TransformerBlocks, None
    elsewhere.  Cache dtype = the model's compute dtype (bf16 on TPU).

    ``rolling=True`` (sliding-window models only): each block's cache is a
    ring buffer of its ``attention_window`` slots instead of ``max_len`` —
    slot ``p % W`` holds position ``p``, old entries are overwritten as
    generation advances, and memory stays O(W) however long the
    continuation runs (the point of windowed attention at decode time).
    ``ring_slack`` widens each ring by that many EXTRA slots (modulus
    W + slack): entries survive ``slack`` positions past the window, which
    is what makes multi-token per-row steps (the serving engine's
    speculative verify, L = spec_len + 1) exact on rolling pools — a
    query at the oldest position in the write window still finds its
    full attention window un-overwritten.

    ``kv_dtype="int8"``: entries are stored as int8 codes plus a
    per-(row, slot, head) f32 scale (``{"k", "v", "ks", "vs"}``),
    quantized at write time and dequantized inside the attention read
    (``core.quant.quantize_kv``) — roughly half the slot bytes of a bf16
    pool, 4× down from f32.  Written through the per-row (serving)
    decode paths only; offline scalar-position walkers keep their
    full-precision caches."""
    _check_supported(model)
    if rolling:
        _validate_rolling(model)
    if kv_dtype not in (None, "int8"):
        raise ValueError(f"kv_dtype must be None or 'int8', got "
                         f"{kv_dtype!r}")
    limit = _context_limit(model)
    if limit is not None and max_len > limit:
        raise ValueError(
            f"cache max_len {max_len} exceeds the model's positional-"
            f"embedding range {limit} — positions past it have no trained "
            "embedding (the full forward rejects such sequences too)")
    dtype = model._cdtype
    caches: List[Any] = []
    for layer in model.layers:
        kind = layer.state_kind if isinstance(layer, _BLOCKS) else "none"
        if kind == "recurrent":
            caches.append(layer.mixer().init_state(batch, dtype))
        elif kind == "kv":
            mha = layer.mixer()
            slots = max_len
            if rolling:
                slots = min(mha.attention_window + int(ring_slack), max_len)
            shape = (batch, slots, mha._kv_heads(), mha.key_dim)
            if kv_dtype == "int8":
                caches.append({"k": jnp.zeros(shape, jnp.int8),
                               "v": jnp.zeros(shape, jnp.int8),
                               "ks": jnp.zeros(shape[:3], jnp.float32),
                               "vs": jnp.zeros(shape[:3], jnp.float32)})
            else:
                caches.append({"k": jnp.zeros(shape, dtype),
                               "v": jnp.zeros(shape, dtype)})
        else:
            caches.append(None)
    return caches


class PagedView:
    """Static+traced description of a paged-KV access, threaded through
    the decode walker (``_forward(paged=...)``): ``tables`` (B, T) int32
    per-row block tables (traced), ``page`` tokens per block and ``view``
    the logical sequence length (both STATIC — construct this object
    INSIDE the jitted program, closing over the ints).  ``floor``/``ceil``
    (B,) bound each row's write range: logical positions below ``floor``
    (a shared — refcounted — prefix another request owns) or at/above
    ``ceil`` (right-pad junk past the real prompt) are routed into the
    arena's null block instead of written.  ``qcap`` (B,) clamps pad
    QUERY positions onto the last real position (see
    ``ops.attention.dot_product_attention(q_positions=)``).  ``ring``
    lays logical positions out modulo ``view`` (the paged form of the
    rolling ring — same slot-holds-``p % view`` contract, addressed
    through the block table)."""

    __slots__ = ("tables", "page", "view", "floor", "ceil", "qcap", "ring")

    def __init__(self, tables, page: int, view: int, floor=None, ceil=None,
                 qcap=None, ring: bool = False):
        self.tables = tables
        self.page = int(page)
        self.view = int(view)
        self.floor = floor
        self.ceil = ceil
        self.qcap = qcap
        self.ring = bool(ring)


def init_paged_arena(model, num_blocks: int, block_size: int,
                     kv_dtype: Optional[str] = None,
                     num_slots: Optional[int] = None) -> List[Any]:
    """The paged slot pool's backing store, layer by layer by state kind.  A
    ``recurrent`` layer gets its mixer's fixed-size state for ``num_slots``
    slots (``mixer.init_state``, e.g. ``{"S": f32[slots, H, Dk, Dv], "conv":
    [slots, c - 1, 3 H Dk]}``: no blocks, nothing to page); a block of state
    kind ``none`` gets nothing.  A ``kv`` layer gets a FLAT
    arena of ``num_blocks + 1`` fixed-size blocks laid out contiguously —
    ``{"k", "v"}`` of shape ((num_blocks + 1) * block_size, num_kv_heads *
    key_dim): a position's kv heads side by side in ONE row (plus
    ``{"ks", "vs"}`` per-(slot, head) scales for ``kv_dtype="int8"``,
    quantized codes paged identically to the full-precision entries).
    Rows, not a (slots, heads, key_dim) cube, because of where the device
    keeps them: a TPU lays a bf16[slots, 16, 64] array out with the SLOT
    axis minor-most (``{0,2,1:T(8,128)(2,1)}``: key_dim 64 would fill half
    a 128-lane tile, so the large axis takes the lanes), which no
    per-position write and no per-block read can use — every paged program
    then copies the whole pool into row-major order and back (PERF.md
    section 6, PR 25).  A row of ``heads * key_dim`` features is
    lane-dense, stays row-major at rest, is written in place, and makes a
    block one contiguous slab that ``ops.paged_attention`` reads by DMA.
    Physical block b owns arena slots
    [b * block_size, (b + 1) * block_size); logical position p of a
    request whose block table maps logical block ``p // block_size`` to b
    lives at slot ``b * block_size + p % block_size``.  The EXTRA
    trailing block (id ``num_blocks``) is the NULL block: free slots'
    junk decode writes, right-pad prefill writes, and warmup all route
    there, so no real request's blocks are ever touched by another row's
    program.  Unlike ``init_cache`` there is no per-slot ``max_len``
    axis — capacity is ``num_blocks × block_size`` TOKENS, allocated on
    demand per request instead of ``num_slots × max_len`` up front."""
    _check_supported(model)
    if kv_dtype not in (None, "int8"):
        raise ValueError(f"kv_dtype must be None or 'int8', got "
                         f"{kv_dtype!r}")
    if int(num_blocks) < 1:
        raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
    if int(block_size) < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    arena_len = (int(num_blocks) + 1) * int(block_size)
    dtype = model._cdtype
    caches: List[Any] = []
    for layer in model.layers:
        kind = layer.state_kind if isinstance(layer, _BLOCKS) else "none"
        if kind == "recurrent":
            if num_slots is None:
                raise ValueError("a recurrent layer's state is per slot: "
                                 "init_paged_arena needs num_slots")
            if kv_dtype is not None:
                raise ValueError(
                    f"kv_dtype={kv_dtype!r} quantises keys and values; a "
                    "recurrent state is float32 and has no such form")
            caches.append(layer.mixer().init_state(int(num_slots), dtype))
        elif kind == "kv":
            mha = layer.mixer()
            shape = (arena_len, mha._kv_heads() * mha.key_dim)
            if kv_dtype == "int8":
                scales = (arena_len, mha._kv_heads())
                caches.append({"k": jnp.zeros(shape, jnp.int8),
                               "v": jnp.zeros(shape, jnp.int8),
                               "ks": jnp.zeros(scales, jnp.float32),
                               "vs": jnp.zeros(scales, jnp.float32)})
            else:
                caches.append({"k": jnp.zeros(shape, dtype),
                               "v": jnp.zeros(shape, dtype)})
        else:
            caches.append(None)
    return caches


def _kv_quantized(cache) -> bool:
    """True for an int8 KV cache dict (codes + per-entry scales)."""
    return isinstance(cache, dict) and "ks" in cache


def _kv_write(cache, idx, k_t, v_t):
    """Scatter a (B, L, Hkv, Dh) k/v write into ``cache`` at ``idx`` (a
    tuple of broadcastable row/slot index arrays); int8 caches quantize on
    write, storing codes and per-entry scales side by side.  Entries take
    the cache's own trailing shape: (Hkv, Dh) in a dense slab, one row of
    Hkv * Dh features in a paged arena.  Out-of-bounds indices drop (jit
    scatter semantics) — the serving engine's speculative verify leans on
    that at the end-of-request boundary."""
    def put(name, x):
        # idx addresses the leading axes; what is left is one entry
        entry = cache[name].shape[len(idx):]
        return cache[name].at[idx].set(x.reshape(x.shape[:2] + entry))

    if _kv_quantized(cache):
        from .quant import quantize_kv
        kq, ks = quantize_kv(k_t)
        vq, vs = quantize_kv(v_t)
        return {"k": put("k", kq), "v": put("v", vq),
                "ks": put("ks", ks), "vs": put("vs", vs)}
    return {"k": put("k", k_t), "v": put("v", v_t)}


def _kv_read(cache, dtype):
    """The attention-side view of a cache: dense (codes × scales for int8
    caches — fused into the consuming matmuls under jit)."""
    if _kv_quantized(cache):
        from .quant import dequantize_kv
        return (dequantize_kv(cache["k"], cache["ks"], dtype),
                dequantize_kv(cache["v"], cache["vs"], dtype))
    return cache["k"], cache["v"]


def gather_blocks(caches, rows):
    """Pull the arena slots named by ``rows`` (a flat (n,) int32 vector of
    PHYSICAL slot indices — block table rows expanded by ``block_size``)
    out of a flat paged arena (``init_paged_arena``): per TransformerBlock
    a dict of ``(n, Hkv * Dh)`` payloads (int8 arenas also gather their
    ``(n, Hkv)`` scales).  The prefill half of a disaggregated transfer —
    read-only, so gathering a radix-shared prefix block is safe.  Shape is
    static in ``rows.shape``: callers pad ``rows`` with null-block slots
    to a fixed length to keep one trace."""
    return [None if c is None else
            {k: jnp.take(v, rows, axis=0) for k, v in c.items()}
            for c in caches]


def scatter_blocks(caches, rows, payload):
    """The decode half: write ``payload`` (the ``gather_blocks`` layout)
    into this arena's slots ``rows`` — the receiver's OWN physical slots
    for the shipped logical blocks.  Junk rows in a fixed-shape transfer
    are padded to the null block on the caller's side, where the write is
    harmless by the arena contract."""
    return [c if c is None else
            {k: v.at[rows].set(payload[i][k]) for k, v in c.items()}
            for i, c in enumerate(caches)]


def gather_slot_state(caches, rows, tok, pos, keys, slot):
    """The suspend half of a QoS preemption swap-out: one jitted dispatch
    returning the arena slots named by ``rows`` (``gather_blocks``
    layout) TOGETHER with the preempted slot's device-resident decode
    frontier — its current un-written token (``tok[slot]``), position
    (``pos[slot]``, entries written so far), and RNG key row.  The
    frontier must come off the device in the same dispatch as the blocks:
    the pair (KV prefix, frontier) is what makes a later re-install
    bit-identical, and reading the device copy (not a host mirror) makes
    the snapshot authoritative by construction."""
    payload = gather_blocks(caches, rows)
    return payload, tok[slot], pos[slot], keys[slot]


def _per_row(pos) -> bool:
    """True when ``pos`` is a (B,) per-row position vector (the serving
    engine's slot pool) rather than the scalar all-rows-share-one-position
    form.  Scalar ``pos`` keeps the exact original code path."""
    return getattr(pos, "ndim", 0) == 1


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def paged_kernel_applies(mha: MultiHeadAttention, cache, paged: "PagedView",
                         q) -> bool:
    """Does this paged attention read go to the Pallas decode kernel
    (``ops.paged_attention``) instead of gather + dense attention?  Decided
    from what the call itself shows, never by an option: ONE query token a
    row (``q``: (B, 1, H, Dh), an array or its shape-and-dtype), a
    full-view table (no ring: a ring's slots are not in position order),
    no write or query bounds (those are prefill's), full-precision entries
    (an int8 arena dequantizes on the gather path), no sliding window on
    the layer, a TPU underneath, and shapes the kernel tiles.  The serving
    engine asks the same question when it builds its decode program
    (``paged_step_on_kernel``), so its counter says what the program
    does."""
    from ..ops.paged_attention import kernel_tiles
    b, length, h, dh = q.shape
    return (length == 1 and not paged.ring
            and paged.floor is None and paged.ceil is None
            and paged.qcap is None and not _kv_quantized(cache)
            and mha.attention_window is None and _on_tpu()
            and kernel_tiles((b, h, dh), q.dtype, cache["k"].shape,
                             cache["k"].dtype, paged.page, paged.view))


def paged_step_on_kernel(model, caches, batch: int, page: int, view: int,
                         ring: bool = False) -> bool:
    """True when EVERY attention layer (the layers of state kind ``kv``; a
    recurrent layer reads no keys) of a single-token paged step over
    ``caches`` reads through the decode kernel — what the serving engine's
    decode program is built on (``paged_kernel_applies`` layer by layer,
    with the shapes that step will trace)."""
    probe = PagedView(None, page, view, ring=ring)
    blocks = [(layer.mixer(), c) for layer, c in zip(model.layers, caches)
              if isinstance(layer, _BLOCKS) and layer.state_kind == "kv"]
    return bool(blocks) and all(
        paged_kernel_applies(m, c, probe, jax.ShapeDtypeStruct(
            (batch, 1, m.num_heads, m.key_dim), model._cdtype))
        for m, c in blocks)


def _live_lengths(paged: "PagedView", pos, arena_slots: int):
    """Positions each row of a single-token paged step attends, its new
    token included: ``pos + 1`` for a row that holds a request, 0 for one
    that does not.  Which is which is read off the table, not off ``pos``:
    a retired slot's row is all null block while its position stays stale
    (``ServingEngine._build_deact_fn``), and so is a slot still being
    prefilled, so a row is live exactly when the block its new token was
    written into is a real one."""
    null_block = arena_slots // paged.page - 1
    blk = jnp.minimum(pos // paged.page, paged.tables.shape[1] - 1)
    here = jnp.take_along_axis(paged.tables, blk[:, None], axis=1)[:, 0]
    return jnp.where(here != null_block, jnp.minimum(pos + 1, paged.view), 0)


def _mha_forward(mha: MultiHeadAttention, params, h, cache, pos, cdtype,
                 rolling: bool = False, paged: Optional[PagedView] = None):
    """Cached attention over (B, L, D) queries starting at position
    ``pos``; writes k/v for those L positions into the cache and attends
    through ``ops.attention.dot_product_attention`` (same numerics as the
    training forward).  ``pos`` may be a (B,) vector: each row writes its
    k/v at — and attends from — its own position, and per-row positions
    compose with L > 1 (the serving engine's speculative verify: L =
    spec_len + 1 entries written at each row's own offsets, all L queries
    scored in this one forward).  Rolling caches additionally need a ring
    of >= window + L - 1 slots for L > 1 (``init_cache(ring_slack=...)``)
    so the oldest query's attention window survives the newest write.

    Right-padded batches (the serving engine's bucketed prefill pads a
    mixed-length prompt batch to one bucket length) need no extra
    masking here: pad tokens sit at positions >= every real query, so the
    causal mask already keeps their keys out of every real row's softmax,
    and their (finite) junk cache entries stay behind each row's decode
    ``kv_length`` frontier until real writes overwrite them.  (An explicit
    per-row kv_length mask would be WRONG for windowed models: a pad
    query whose window has slid past the real prompt would mask every
    key, and the resulting empty-softmax NaN row poisons real outputs
    through the next layer's ``0 * NaN`` value products.)

    ``paged`` (a :class:`PagedView`): the cache is a FLAT block arena
    (``init_paged_arena``) addressed through per-row block tables instead
    of a (B, S, ...) slab.  Writes scatter at gather-computed physical
    slots (``floor``/``ceil`` route shared-prefix and right-pad positions
    into the null block).  Reads take one of two ways, chosen by what the
    call shows (``paged_kernel_applies``): the single-token decode step on
    a TPU reads K and V in place through the block tables, each row as far
    as its own length, in one Pallas kernel
    (``ops.paged_attention.paged_decode_attention``: f32 softmax and
    accumulation over the stored entries, the flash recurrence); everything
    else (prefill units, the speculative verify's L > 1, ring views, int8
    arenas, windowed layers, the CPU) gathers each row's logical view back
    out (``ops.attention.paged_gather``) and attends with the SAME per-row
    masks as the dense path.  Either way the paged step is a storage
    relayout, not a numerics change.  Requires per-row ``pos``."""
    from ..ops.attention import dot_product_attention, paged_gather
    b, length = h.shape[0], h.shape[1]
    dh = mha.key_dim
    per_row = _per_row(pos)
    q_clamped = None
    if paged is not None:
        if not per_row:
            raise ValueError("paged KV access needs per-row (B,) positions")
        q_idx = pos[:, None] + jnp.arange(length)[None, :]       # (B, L)
        q_clamped = (q_idx if paged.qcap is None
                     else jnp.minimum(q_idx, paged.qcap[:, None]))

    def proj(name, heads):
        bias = params.get("b" + name[1]) if mha.use_bias else None
        y = _project(h, params[name], bias, cdtype)
        return y.astype(cdtype).reshape(b, length, heads, dh)

    q = proj("wq", mha.num_heads)
    k_t = proj("wk", mha._kv_heads())
    v_t = proj("wv", mha._kv_heads())
    if mha.rope:
        # rotate by the suffix's ABSOLUTE positions; cached k stay rotated
        # by their own positions (RoPE scores depend only on distance)
        from ..ops.rope import apply_rope
        if q_clamped is not None:
            positions = q_clamped
        else:
            positions = (pos[:, None] + jnp.arange(length)[None, :]
                         if per_row else pos + jnp.arange(length))
        q = apply_rope(q, positions, mha.rope_theta, mha.rope_scale)
        k_t = apply_rope(k_t, positions, mha.rope_theta, mha.rope_scale)
    new_cache = None

    def attend(k, v, **where):
        with jax.named_scope("attn_core"):
            return dot_product_attention(q, k, v, causal=True,
                                         scale=mha.score_scale,
                                         window=mha.attention_window,
                                         **where)

    if paged is not None:
        # -- paged arena: block-table-indexed scatter write, gathered read
        bs, view = paged.page, paged.view
        idx = pos[:, None] + jnp.arange(length)[None, :]         # (B, L)
        if paged.ring:
            w = view
            if length > 1 and w < mha.attention_window + length - 1:
                raise ValueError(
                    f"multi-token per-row steps on a paged ring need a "
                    f"view of >= window + L - 1 = "
                    f"{mha.attention_window + length - 1} slots, got {w} "
                    f"— the oldest query's window would be overwritten "
                    f"by the newest write")
            lidx = idx % w
        else:
            lidx = idx
        blk = jnp.minimum(lidx // bs, paged.tables.shape[1] - 1)
        phys = (jnp.take_along_axis(paged.tables, blk, axis=1) * bs
                + lidx % bs)
        null_phys = cache["k"].shape[0] - 1  # inside the null block
        if paged.floor is not None:
            phys = jnp.where(idx >= jnp.reshape(paged.floor, (-1, 1)),
                             phys, null_phys)
        if paged.ceil is not None:
            phys = jnp.where(idx < jnp.reshape(paged.ceil, (-1, 1)),
                             phys, null_phys)
        with jax.named_scope("kv_write"):
            new_cache = _kv_write(cache, (phys,), k_t, v_t)
        if paged_kernel_applies(mha, new_cache, paged, q):
            # the single-token step: K and V are read where they lie, each
            # row as far as its own length, by one kernel over the tables
            from ..ops.paged_attention import paged_decode_attention
            with jax.named_scope("kv_gather"):
                lengths = _live_lengths(paged, pos, new_cache["k"].shape[0])
            with jax.named_scope("attn_core"):
                out = paged_decode_attention(
                    q[:, 0], new_cache["k"], new_cache["v"], paged.tables,
                    lengths, bs, scale=mha.score_scale)[:, None]
        else:
            def view_of(name):  # each row's (view, ...) entries
                return paged_gather(new_cache[name], paged.tables, bs, view)

            def heads(rows):    # a row of Hkv * Dh features, unfolded
                return rows.reshape(b, view, mha._kv_heads(), dh)

            with jax.named_scope("kv_gather"):
                k, v = heads(view_of("k")), heads(view_of("v"))
                if _kv_quantized(new_cache):
                    from .quant import dequantize_kv
                    k = dequantize_kv(k, view_of("ks"), cdtype)
                    v = dequantize_kv(v, view_of("vs"), cdtype)
            kv_positions = kv_length = None
            if paged.ring:
                # same frontier layout as the dense ring: view slot j holds
                # the newest position <= each row's write frontier
                # congruent to j mod view (negative = never written)
                front = pos[:, None] + (length - 1)
                j = jnp.arange(view)
                kv_positions = front - jnp.mod(front - j[None, :], view)
            else:
                kv_length = pos + length
            out = attend(k, v, q_positions=q_clamped, kv_length=kv_length,
                         kv_positions=kv_positions)
    elif per_row:
        # L >= 1: every row writes its L entries at its own offsets (the
        # serving engine's decode step at L == 1, its speculative verify
        # at L == spec_len + 1) and the per-row masks score all L queries
        # in this one forward
        rows = jnp.arange(b)
        idx = pos[:, None] + jnp.arange(length)[None, :]          # (B, L)
        if rolling:
            w = cache["k"].shape[1]
            if length > 1 and w < mha.attention_window + length - 1:
                raise ValueError(
                    f"multi-token per-row steps on a rolling cache need a "
                    f"ring of >= window + L - 1 = "
                    f"{mha.attention_window + length - 1} slots, got {w} "
                    f"(init_cache(ring_slack=...)) — the oldest query's "
                    f"window would be overwritten by the newest write")
            with jax.named_scope("kv_write"):
                new_cache = _kv_write(cache, (rows[:, None], idx % w), k_t,
                                      v_t)
            # slot j holds the newest position <= each row's write
            # frontier congruent to j mod w (negative = never written);
            # queries older than the frontier hide the just-written
            # future entries through the causal kv_positions comparison
            front = pos[:, None] + (length - 1)
            j = jnp.arange(w)
            kv_positions = front - jnp.mod(front - j[None, :], w)
            with jax.named_scope("kv_gather"):
                k, v = _kv_read(new_cache, cdtype)
            out = attend(k, v, q_offset=pos, kv_positions=kv_positions)
        else:
            with jax.named_scope("kv_write"):
                new_cache = _kv_write(cache, (rows[:, None], idx), k_t, v_t)
            with jax.named_scope("kv_gather"):
                k, v = _kv_read(new_cache, cdtype)
            out = attend(k, v, q_offset=pos, kv_length=pos + length)
    elif rolling:
        # ring buffer of the block's window: slot p % W holds position p.
        # Single-token writes only — generate() prefills with a full cache
        # and converts (a batched ring write would wrap around the buffer).
        if length != 1:
            raise ValueError("rolling cache steps are single-token "
                             "(prefill uses a full cache, then converts)")
        w = cache["k"].shape[1]
        slot = pos % w
        with jax.named_scope("kv_write"):
            k = jax.lax.dynamic_update_slice(cache["k"], k_t,
                                             (0, slot, 0, 0))
            v = jax.lax.dynamic_update_slice(cache["v"], v_t,
                                             (0, slot, 0, 0))
        # slot j currently holds position pos - ((pos - j) mod W); slots
        # not yet written come out negative and mask themselves
        j = jnp.arange(w)
        kv_positions = pos - jnp.mod(pos - j, w)
        out = attend(k, v, q_offset=pos, kv_positions=kv_positions)
    else:
        with jax.named_scope("kv_write"):
            k = jax.lax.dynamic_update_slice(cache["k"], k_t,
                                             (0, pos, 0, 0))
            v = jax.lax.dynamic_update_slice(cache["v"], v_t,
                                             (0, pos, 0, 0))
        out = attend(k, v, q_offset=pos, kv_length=pos + length)
    out = out.reshape(b, length, mha.num_heads * dh)
    out = mha.gate(params, h, out, cdtype)
    bias_o = params.get("bo") if mha.use_bias else None
    y = _project(out, params["wo"], bias_o, cdtype)
    return y, (new_cache if new_cache is not None else {"k": k, "v": v})


class RowView:
    """Which per-slot state each row of a program's batch owns, for the
    layers whose state is per slot and not paged (state kind
    ``recurrent``).  ``slots`` (B,) int32: row r continues slot
    ``slots[r]`` of the state arrays — from ZERO where the row starts at
    position 0, which is how a slot is cleared on admission — and writes it
    back (an index past the last slot drops the write: ``warmup`` and the
    unused rows of a bucket program); None: row r IS slot r, advanced in
    place (the decode step, and every offline walker).  ``live`` (B,) bool:
    the rows that hold a request; a dead row's state stays as it is."""

    __slots__ = ("slots", "live")

    def __init__(self, slots=None, live=None):
        self.slots = slots
        self.live = live


def _token_mask(length: int, pos, paged: Optional[PagedView],
                rows: Optional[RowView]):
    """(B, L) bool, the positions of a step that count: not the right-pad
    past a row's ``ceil``, not a dead row's.  None: all of them."""
    mask = None
    if paged is not None and paged.ceil is not None:
        idx = pos[:, None] + jnp.arange(length)[None, :]
        mask = idx < jnp.reshape(paged.ceil, (-1, 1))
    if rows is not None and rows.live is not None:
        live = jnp.broadcast_to(rows.live[:, None],
                                (rows.live.shape[0], length))
        mask = live if mask is None else mask & live
    return mask


def _recurrent_forward(mixer, params, h, cache, pos, cdtype, rolling,
                       paged, rows, token_mask):
    """Cached step of a ``recurrent`` mixer over (B, L, D): each row
    continues its slot's state (``RowView``) through ``mixer.mix`` and
    leaves the state after its last live token.  A prefill unit of any
    length carries the state on; the single-token step of the serving pool
    goes through the mixer's fused kernel (``kda_decode``, ``ssd_decode``)
    on a TPU."""
    slots = None if rows is None else rows.slots
    state = cache
    if slots is not None:
        n = jax.tree_util.tree_leaves(cache)[0].shape[0]
        fresh = jnp.reshape(pos, (-1,)) == 0
        state = tmap(
            lambda a: jnp.where(
                fresh.reshape((-1,) + (1,) * (a.ndim - 1)),
                jnp.zeros((), a.dtype), a[jnp.clip(slots, 0, n - 1)]),
            cache)
    fused = (h.shape[1] == 1 and slots is None and _on_tpu()
             and mixer.kernel_tiles(state))
    y, new = mixer.mix(params, h, state, compute_dtype=cdtype,
                       token_mask=token_mask, fused_step=fused)
    if slots is not None:
        new = tmap(lambda big, row: big.at[slots].set(row, mode="drop"),
                   cache, new)
    return y, new


def _kv_forward(mixer, params, h, cache, pos, cdtype, rolling, paged, rows,
                token_mask):
    return _mha_forward(mixer, params, h, cache, pos, cdtype, rolling, paged)


#: the cached step of a block's mixer, by the kind of state it keeps; a
#: block without a mixer (``none``) has no cached step, no cache entry, and
#: is never asked for one
_CACHED_MIX = {"kv": _kv_forward, "recurrent": _recurrent_forward,
               "none": None}


def _block_forward(block, params, x, cache, pos, cdtype,
                   rolling: bool = False,
                   paged: Optional[PagedView] = None,
                   rows: Optional[RowView] = None, token_mask=None):
    """``block.run`` (the block's own norms, residuals and feed-forward
    part: the lines its full-sequence ``apply`` runs) around the CACHED step
    of its mixer.  Returns ``(y, new cache, counters or None)``."""
    box = {}

    def mix(mixer, mixer_params, h):
        y, box["cache"] = _CACHED_MIX[mixer.state_kind](
            mixer, mixer_params, h, cache, pos, cdtype, rolling, paged,
            rows, token_mask)
        return y

    x, counters = block.run(params, x, mix, compute_dtype=cdtype,
                            token_mask=token_mask)
    return x, box.get("cache", cache), counters


def _forward(model, params, caches, toks, pos, rolling: bool = False,
             paged: Optional[PagedView] = None,
             rows: Optional[RowView] = None, aux: Optional[list] = None):
    """Walk the layer stack over (B, L) tokens starting at position
    ``pos``; returns ((B, L, V) f32 logits, new caches).  L == 1 is a
    decode step, L == P is the batched prompt prefill.  ``pos`` may be a
    (B,) per-row position vector: every row advances at its own position —
    the serving engine's mixed-length slot batch (L == 1), or its batched
    speculative verify (L == spec_len + 1, each row scoring its own L
    continuation positions in one forward).  L > 1
    batches may be right-padded to a shared length (the serving engine's
    bucketed prefill) — see ``_mha_forward`` for why the causal mask
    alone keeps pad tokens out of every real position's numerics.  ``rows``
    (a :class:`RowView`) places the batch's rows on the slots of per-slot
    state; ``aux``, a list, receives each block's counters (a ``SparseMoE``
    feed-forward part's) in layer order."""
    cdtype = model._cdtype
    x = None
    new_caches: List[Any] = []
    token_mask = None
    if any(isinstance(layer, _BLOCKS) and layer.wants_token_mask
           for layer in model.layers):
        token_mask = _token_mask(toks.shape[1], pos, paged, rows)
    for i, (layer, p, cache, scope) in enumerate(zip(
            model.layers, params, caches, scope_names(model.layers))):
        with jax.named_scope(scope):
            if isinstance(layer, Embedding):
                x = layer.apply(p, toks, compute_dtype=cdtype)
            elif isinstance(layer, PositionalEmbedding):
                if _per_row(pos) and toks.shape[1] == 1:
                    pe = jnp.asarray(p["embedding"])[pos]          # (B, D)
                    x = x + pe.astype(x.dtype)[:, None]
                elif _per_row(pos):
                    # per-row multi-token (the speculative verify): row r's
                    # token i sits at absolute position pos[r] + i.  OOB rows
                    # (a request at its very end) clamp — their logits are
                    # junk the engine never commits
                    idx = pos[:, None] + jnp.arange(toks.shape[1])[None, :]
                    pe = jnp.asarray(p["embedding"])[idx]          # (B, L, D)
                    x = x + pe.astype(x.dtype)
                else:
                    pe = jax.lax.dynamic_slice_in_dim(
                        jnp.asarray(p["embedding"]), pos, toks.shape[1])
                    x = x + pe.astype(x.dtype)[None]
            elif isinstance(layer, _BLOCKS):
                x, cache, counters = _block_forward(
                    layer, p, x, cache, pos, cdtype, rolling, paged, rows,
                    token_mask)
                if aux is not None and counters is not None:
                    aux.append(counters)
            else:  # norms / Dense / TiedHead: position-independent
                x = layer.apply(params_of(model.layers, params, i), x,
                                compute_dtype=cdtype, train=False)
        new_caches.append(cache)
    return x.astype(jnp.float32), new_caches


def decode_step(model, params, caches, tok, pos, rolling: bool = False,
                paged: Optional[PagedView] = None,
                rows: Optional[RowView] = None, aux: Optional[list] = None):
    """Advance one position.  tok: (B,) int32 current tokens; pos: scalar
    int32 position (0-based), or a (B,) int32 vector advancing every row
    at its OWN position (the serving engine's slot batch — each row writes
    its k/v at, and attends from, its own position).  ``paged``: the
    caches are a flat block arena addressed through per-row block tables
    (the serving engine's paged slot pool) — same numerics, block-granular
    storage.  Returns (logits (B, V) f32, new caches).  Jittable — wrap
    in ``jax.jit`` (or let ``generate`` do it) for real use;
    ``jit_decode_step`` packages exactly that."""
    logits, caches = _forward(model, params, caches, tok[:, None], pos,
                              rolling, paged, rows, aux)
    return logits[:, 0], caches


def jit_decode_step(model, rolling: bool = False):
    """The jitted single-token entry point for serving loops that own their
    own sampling/stopping logic (``generate`` builds its scan from the same
    ``decode_step``, so numerics are identical).

    Returns ``step(params, caches, tok, pos) -> (logits (B, V) f32,
    new caches)`` compiled once per (batch, cache-length) shape::

        caches = init_cache(model, batch, max_len)
        step = jit_decode_step(model)
        for pos in range(p_len, max_len):
            logits, caches = step(params, caches, tok, pos)
            tok = my_sampler(logits)

    ``model`` and ``rolling`` are closed over (they shape the program);
    ``pos`` is a traced argument, so advancing it does NOT recompile.
    """
    _check_supported(model)
    if rolling:
        _validate_rolling(model)

    @jax.jit
    def step(params, caches, tok, pos):
        return decode_step(model, params, caches,
                           jnp.asarray(tok, jnp.int32), pos, rolling)

    return step


def _validate_sampling(temperature: float, rng,
                       top_k: Optional[int], top_p: Optional[float]):
    """The one sampling-surface rule set, shared by ``generate`` and
    ``speculative_generate``."""
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature > 0 sampling needs rng")
    if top_k is not None or top_p is not None:
        if temperature <= 0.0:
            raise ValueError(
                "top_k/top_p shape the SAMPLING distribution — pass "
                "temperature > 0 (greedy argmax ignores them)")
        if top_k is not None and top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")


def _validate_stopping(eos_id: Optional[int], pad_id: Optional[int],
                       vocab: Optional[int]):
    """The one eos_id/pad_id rule set, shared by ``generate`` and
    ``beam_search``.  Out-of-range ids would be silently clamped by the
    ``.at[].set`` scatter and the embedding gather — refuse instead."""
    if pad_id is not None and eos_id is None:
        raise ValueError("pad_id only means something with eos_id")
    if eos_id is not None and vocab is not None \
            and not 0 <= eos_id < vocab:
        raise ValueError(f"eos_id {eos_id} outside the model's vocabulary "
                         f"[0, {vocab}) — stopping could never trigger")
    if pad_id is not None and vocab is not None \
            and not 0 <= pad_id < vocab:
        raise ValueError(f"pad_id {pad_id} outside the model's vocabulary "
                         f"[0, {vocab})")


def _filter_logits(logits, top_k: Optional[int], top_p: Optional[float]):
    """Restrict a (B, V) logit row to the top-k tokens and/or the smallest
    nucleus whose probability mass reaches top_p (the top token always
    survives); filtered entries go to -inf.  k-then-p order, the standard
    composition."""
    if top_k is not None:
        k = min(int(top_k), logits.shape[-1])  # k past vocab = keep all
        kth = jax.lax.top_k(logits, k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens whose preceding cumulative mass is < top_p (the top
        # token's is 0, so at least one survives); the cut logit is the
        # smallest kept one
        kept = jnp.sum((cum - probs) < top_p, axis=-1, keepdims=True)
        cut = jnp.take_along_axis(sorted_desc, kept - 1, axis=-1)
        logits = jnp.where(logits < cut, -jnp.inf, logits)
    return logits


@jax.named_scope("sample")
def sample_logits(logits, pos, temperature: float = 0.0,
                  rng: Optional[jax.Array] = None,
                  top_k: Optional[int] = None,
                  top_p: Optional[float] = None) -> jnp.ndarray:
    """The ONE per-step sampling rule: (B, V) f32 logits at absolute
    position ``pos`` → (B,) int32 next tokens.  temperature 0 = greedy
    argmax; > 0 = softmax sampling after ``_filter_logits`` warping, with
    the step key derived as ``fold_in(rng, pos)`` so a position's draw is
    a pure function of (rng, pos).  ``generate`` samples through exactly
    this function, and the serving engine reuses it for per-request
    prefill sampling — the two paths cannot drift."""
    if temperature > 0.0:
        step_rng = jax.random.fold_in(rng, pos)
        logits = _filter_logits(logits / temperature, top_k, top_p)
        nxt = jax.random.categorical(step_rng, logits)
    else:
        nxt = jnp.argmax(logits, axis=-1)
    return nxt.astype(jnp.int32)


def filter_logits_batched(logits, top_k, top_p):
    """Per-row ``_filter_logits`` with TRACED per-row parameters: ``top_k``
    (B,) int32 (0 = disabled), ``top_p`` (B,) f32 (0 = disabled).  Row r
    with ``top_k[r] == K > 0`` and ``top_p[r] == P > 0`` computes exactly
    what ``_filter_logits(row, K, P)`` computes (the k-th value comes from
    a descending sort instead of ``lax.top_k`` — the same exact selection —
    and the k-then-p composition order is preserved), so one jitted program
    serves a slot batch with heterogeneous sampling configs.

    The work follows the rows: a call none of whose rows filters returns
    its logits as they came (what every row's two ``where``s would leave),
    decided on the device by a ``lax.cond`` — no sort runs.  A call that
    does filter sorts ONCE: the nucleus is taken over the k-filtered
    logits in descending order, and that array is the first sort's result
    with the entries below the k-th value sent to ``-inf`` (they are the
    tail already, the order of the rest is untouched, ties at the k-th
    value stay on both sides)."""
    v = logits.shape[-1]
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    k_on, p_on = (top_k > 0)[:, None], (top_p > 0)[:, None]

    def filtered():
        sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
        k = jnp.clip(top_k, 1, v)
        kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
        k_logits = jnp.where(k_on & (logits < kth), -jnp.inf, logits)
        # p filter runs on the k-filtered logits (k-then-p, as
        # _filter_logits): their descending sort, derived
        sorted_desc = jnp.where(k_on & (sorted_desc < kth), -jnp.inf,
                                sorted_desc)
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        kept = jnp.sum((cum - probs) < top_p[:, None], axis=-1,
                       keepdims=True)
        cut = jnp.take_along_axis(sorted_desc, jnp.maximum(kept, 1) - 1,
                                  axis=-1)
        return jnp.where(p_on & (k_logits < cut), -jnp.inf, k_logits)

    return jax.lax.cond(jnp.any(k_on | p_on), filtered, lambda: logits)


@jax.named_scope("sample")
def sample_logits_batched(logits, positions, temperature, rngs,
                          top_k, top_p) -> jnp.ndarray:
    """Per-row ``sample_logits``: every row carries its own sampling config.

    ``positions`` (B,) int32 absolute positions; ``temperature`` (B,) f32
    (<= 0 = greedy argmax for that row); ``rngs`` (B, 2) uint32 per-row base
    keys (each folded by its row's position, exactly as ``sample_logits``
    folds the shared key); ``top_k``/``top_p`` as in
    ``filter_logits_batched``.  Row-for-row this reproduces
    ``sample_logits`` on that row's scalar params — vmapped ``fold_in`` +
    ``categorical`` draw the same counter-based random bits as the
    unbatched calls, which is what makes the serving engine's output
    bit-identical to offline ``generate``.

    The call does what its rows ask for and no more, in ONE program (the
    parameters are traced, so the choice is a ``lax.cond`` on the device):
    no row samples → the ``argmax`` alone; some row samples → the divide
    and the draw, and the filter's sort only if a SAMPLING row filters (a
    greedy row's ``top_k``/``top_p`` shape nothing).  A caller whose batch
    has rows it will discard hands them ``temperature`` 0."""
    temp = jnp.asarray(temperature, jnp.float32)
    samples = temp > 0.0
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw():
        safe = jnp.where(samples, temp, 1.0)
        warped = filter_logits_batched(
            logits / safe[:, None], jnp.where(samples, top_k, 0),
            jnp.where(samples, top_p, 0.0))
        keys = jax.vmap(jax.random.fold_in)(rngs, positions)
        sampled = jax.vmap(jax.random.categorical)(keys, warped)
        return jnp.where(samples, sampled.astype(jnp.int32), greedy)

    return jax.lax.cond(jnp.any(samples), draw, lambda: greedy)


def _to_ring(full_cache, p_len: int, window: int):
    """Convert a full prefill cache (positions 0..p_len-1 at slots
    0..p_len-1) into a W-slot ring where slot ``p % W`` holds position
    ``p``, keeping the last ``window`` positions."""
    if p_len >= window:
        # entries for positions p0..p_len-1 (p0 = p_len - W), in order;
        # rolling by p0 % W puts position p at slot p % W
        p0 = p_len - window
        last = jax.lax.dynamic_slice_in_dim(full_cache, p0, window, axis=1)
        return jnp.roll(last, p0 % window, axis=1)
    # shorter prompt: positions 0..p_len-1 already sit at their slots;
    # grow/trim to W slots (unwritten tail masks itself via kv_positions)
    pad = window - full_cache.shape[1]
    if pad > 0:
        zeros = jnp.zeros(full_cache.shape[:1] + (pad,)
                          + full_cache.shape[2:], full_cache.dtype)
        return jnp.concatenate([full_cache, zeros], axis=1)
    return full_cache[:, :window]


def ring_from_prefill(full_cache, p_lens, window: int):
    """Traced, per-row ``_to_ring``: (B, S, H, D) full prefill cache rows →
    (B, W, H, D) rings where slot ``p % W`` holds position ``p``, keeping
    each row's last ``window`` prompt positions.  ``p_lens`` is a (B,)
    TRACED vector of true prompt lengths (the serving engine's bucketed
    prefill converts a whole mixed-length batch in one jitted program);
    slots a short row never wrote come out zero, exactly like
    ``_to_ring``'s zero tail (they self-mask through ``kv_positions`` at
    decode time).  Row-for-row this gathers the same entries ``_to_ring``
    copies — it is a pure relayout, bit-identical by construction."""
    w = int(window)
    j = jnp.arange(w)
    p = jnp.reshape(jnp.asarray(p_lens, jnp.int32), (-1, 1))      # (B, 1)
    # ring slot j holds the newest prompt position congruent to j mod W;
    # rows shorter than W leave their tail slots negative (= never written)
    q = (p - 1) - jnp.mod(p - 1 - j[None, :], w)                  # (B, W)
    src = jnp.clip(q, 0, full_cache.shape[1] - 1)
    rows = jnp.take_along_axis(full_cache, src[:, :, None, None], axis=1)
    return jnp.where((q >= 0)[:, :, None, None], rows,
                     jnp.zeros((), full_cache.dtype))


def generate(model, params, prompt, num_steps: int,
             temperature: float = 0.0, rng: Optional[jax.Array] = None,
             max_len: Optional[int] = None,
             rolling: bool = False,
             top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             eos_id: Optional[int] = None,
             pad_id: Optional[int] = None) -> jnp.ndarray:
    """Continue ``prompt`` (B, P) int tokens by ``num_steps`` tokens.

    temperature 0 = greedy argmax; > 0 = softmax sampling (needs ``rng``).
    ``top_k`` / ``top_p`` (sampling only) restrict each step's distribution
    to the k highest-logit tokens and/or the smallest nucleus reaching
    probability mass ``top_p`` before drawing — combinable (k first, then
    p, the standard composition).
    ``eos_id``: once a sequence emits it, every later slot in that row is
    ``pad_id`` (default: ``eos_id`` itself) — per-row stopping for batched
    serving; the output stays the static (B, P + num_steps) shape.
    Returns (B, P + num_steps) tokens.  Prefill is one batched forward;
    the continuation is one compiled ``lax.scan`` of single-token steps.

    ``rolling=True`` (sliding-window models): after the prefill, each
    block's cache collapses to a ring of its ``attention_window`` slots,
    so generation memory is O(W) regardless of ``num_steps`` — identical
    tokens to ``rolling=False`` (windowed attention never looks past W).
    """
    _check_supported(model)
    prompt = jnp.asarray(prompt, jnp.int32)
    b, p_len = prompt.shape
    if num_steps < 0:
        raise ValueError(f"num_steps must be >= 0, got {num_steps}")
    total = p_len + int(num_steps)
    if max_len is None:
        max_len = total
    if max_len < total:
        raise ValueError(f"max_len {max_len} < prompt+steps {total}")
    limit = _context_limit(model)
    if limit is not None and total > limit:
        raise ValueError(
            f"prompt ({p_len}) + num_steps ({num_steps}) = {total} exceeds "
            f"the model's positional-embedding range {limit}")
    _validate_sampling(temperature, rng, top_k, top_p)
    _validate_stopping(eos_id, pad_id, _vocab_size(model))
    if rolling:
        # the prefill below still uses a full P-slot cache (one batched
        # forward), which then collapses to rings — peak memory O(P + W),
        # steady-state O(W)
        _validate_rolling(model)
    if num_steps == 0:
        # after validation, so invalid argument combinations fail the same
        # way regardless of step count
        return prompt
    caches = init_cache(model, b, p_len if rolling else max_len)

    def sample(logits, pos):
        return sample_logits(logits, pos, temperature, rng, top_k, top_p)

    # prefill: all P prompt positions in one batched forward
    logits, caches = _forward(model, params, caches, prompt, 0)
    first = sample(logits[:, -1], p_len - 1)

    if rolling:
        ringed = []
        for layer, cache in zip(model.layers, caches):
            if cache is None:
                ringed.append(None)
                continue
            w = layer._mha().attention_window
            ringed.append({name: _to_ring(cache[name], p_len, w)
                           for name in ("k", "v")})
        caches = ringed

    pad = jnp.int32(pad_id if pad_id is not None else (eos_id or 0))

    def body(carry, i):
        caches, tok, done = carry
        pos = p_len + i
        logits, caches = decode_step(model, params, caches, tok, pos,
                                     rolling)
        nxt = sample(logits, pos)
        if eos_id is not None:
            # rows whose CURRENT token is eos (or that finished earlier)
            # emit padding from the next slot on
            done = done | (tok == eos_id)
            nxt = jnp.where(done, pad, nxt)
        return (caches, nxt, done), tok

    done0 = jnp.zeros((b,), bool)
    (caches, last, _), toks = jax.lax.scan(
        body, (caches, first, done0), jnp.arange(int(num_steps) - 1))
    gen = jnp.concatenate(
        [jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1) \
        if num_steps > 1 else first[:, None]
    return jnp.concatenate([prompt, gen], axis=1)


def speculative_generate(model, params, draft_model, draft_params, prompt,
                         num_steps: int, draft_len: int = 4,
                         max_len: Optional[int] = None,
                         temperature: float = 0.0,
                         rng: Optional[jax.Array] = None,
                         top_k: Optional[int] = None,
                         top_p: Optional[float] = None,
                         eos_id: Optional[int] = None,
                         pad_id: Optional[int] = None,
                         return_stats: bool = False):
    """Decoding accelerated by a cheaper draft model — distribution-exact.

    ``temperature == 0`` (default): greedy-exact — every committed token is
    the TARGET's own argmax, whatever the draft proposes.  (The argmax
    comes from the batched verify forward; it can differ from single-token
    ``generate`` only where two logits tie to within the fusion-order
    rounding between an L-token and a 1-token program — measure-zero for
    trained models, asserted bit-identical across this suite's CI models
    and drafts.)

    ``temperature > 0`` (needs ``rng``): SPECULATIVE SAMPLING (Leviathan
    et al. 2022 / Chen et al. 2023 rejection rule).  Both distributions
    are first warped identically (temperature, then ``top_k``/``top_p``
    as in ``generate``); each drafted token x ~ q is accepted with
    probability min(1, p(x)/q(x)), and the first rejection draws from the
    residual norm(max(p − q, 0)).  The committed-token distribution is
    EXACTLY the warped target distribution — the draft changes wall-clock
    only, never statistics (asserted against closed-form marginals in
    tests/test_speculative.py).

    Each round the draft proposes ``draft_len`` tokens one at a time; the
    target then scores ALL of them in ONE batched forward (the MXU-shaped
    win: k positions per target call instead of 1) and commits the
    accepted prefix plus one bonus/correction token.  A good draft commits
    ``draft_len + 1`` tokens per target call; a useless draft still
    commits 1.

    No cache rollback is needed on rejection: rejected positions hold
    stale k/v, but every attention in this walker masks slots ``>=
    kv_length``, and the next round overwrites them before they can be
    unmasked.  Batched prompts commit the MINIMUM accepted length across
    rows (greedy: every committed token is the target's own argmax for
    every row; sampling: truncating a row's accepted run early never
    conditions on later randomness — exactness holds row-wise either way).

    Both models must share the vocabulary.  ``eos_id``/``pad_id`` behave
    exactly as in ``generate``: once a row emits eos, its later slots are
    ``pad_id`` (default: the eos itself), the output keeps its static
    shape — and a batch whose EVERY row has finished stops issuing
    draft/verify calls entirely (the speculative serving win compounds).
    ``return_stats=True`` additionally returns ``{"target_calls",
    "drafted", "accepted"}`` — ``target_calls`` counts the decode-phase
    verify forwards (the prompt prefill is one more target forward on
    top).
    """
    _check_supported(model)
    _check_supported(draft_model)
    prompt = jnp.asarray(prompt, jnp.int32)
    b, p_len = prompt.shape
    if num_steps < 1:
        raise ValueError(f"speculative_generate needs num_steps >= 1, got "
                         f"{num_steps}")
    if draft_len < 1:
        raise ValueError(f"draft_len must be >= 1, got {draft_len}")
    _validate_sampling(temperature, rng, top_k, top_p)
    tv, dv = _vocab_size(model), _vocab_size(draft_model)
    _validate_stopping(eos_id, pad_id, tv)
    if tv is not None and dv is not None and tv != dv:
        raise ValueError(f"target and draft vocabularies differ: {tv} vs "
                         f"{dv} — argmax agreement would be meaningless")
    total = p_len + int(num_steps)
    if max_len is None:
        max_len = total
    if max_len < total:
        raise ValueError(f"max_len {max_len} < prompt+steps {total}")
    for name, m in (("target", model), ("draft", draft_model)):
        limit = _context_limit(m)
        if limit is not None and total > limit:
            raise ValueError(
                f"prompt + num_steps = {total} exceeds the {name} model's "
                f"positional-embedding range {limit}")

    # allocate draft_len slots of slack so every round can draft and
    # verify at the SAME (B, draft_len + 1) shape — without it the tail
    # rounds shrink k and each distinct width pays a fresh XLA compile.
    # Slack slots only ever hold discarded writes (kv_length-masked);
    # learned-positional models cap the slack at their trained range and
    # may shrink on the final rounds.
    def alloc_for(m):
        limit = _context_limit(m)
        want = max_len + int(draft_len)
        return want if limit is None else min(want, limit)

    t_caches = init_cache(model, b, alloc_for(model))
    d_caches = init_cache(draft_model, b, alloc_for(draft_model))
    alloc = min(alloc_for(model), alloc_for(draft_model))
    logits, t_caches = _forward(model, params, t_caches, prompt, 0)
    _, d_caches = _forward(draft_model, draft_params, d_caches, prompt, 0)

    sampled = temperature > 0.0

    def warp(l):
        # identical warp for target and draft — the rejection rule is
        # exact for whatever pair of distributions it compares, so
        # warping both reproduces plain warped-target sampling
        return _filter_logits(l / temperature, top_k, top_p) if sampled \
            else l

    _draw = [0]  # host-side draw counter -> a fresh fold per random draw

    def _key():
        _draw[0] += 1
        return jax.random.fold_in(rng, _draw[0])

    if sampled:
        cur = jax.random.categorical(
            _key(), warp(logits[:, -1])).astype(jnp.int32)
    else:
        cur = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)  # (B,)

    # model closes over (it shapes the program); params stay a traced arg.
    # caches are donated: every call rebinds t_caches to the output and the
    # input buffer is dead — rejection never rolls back (rejected positions
    # are simply overwritten by the next round), so no alias survives
    verify = jax.jit(lambda p, caches, toks, pos: _forward(
        model, p, caches, toks, pos), donate_argnums=(1,))
    d_step = jit_decode_step(draft_model)

    # eos stopping, same semantics as generate: a row that emitted eos
    # gets pad in every later slot.  Applied per COMMITTED token in commit
    # order, so it composes with both the greedy and the sampled rule
    # (padding is a row-wise post-map; exactness is untouched).
    pad_tok = jnp.int32(pad_id if pad_id is not None else (eos_id or 0))
    done = jnp.zeros((b,), bool)
    out = []

    def commit(tok):
        nonlocal done
        if eos_id is not None:
            tok = jnp.where(done, pad_tok, tok)
            done = done | (tok == eos_id)
        out.append(tok)

    commit(cur)
    cur = out[-1]
    pos = p_len - 1  # cur continues from here; its cache slot is pos + 1
    stats = {"target_calls": 0, "drafted": 0, "accepted": 0}
    while len(out) < num_steps:
        if eos_id is not None and bool(jnp.all(done)):
            # every row finished: no more draft/verify calls — fill the
            # remaining slots with one shared pad row and stop
            pad_row = jnp.full((b,), pad_tok, jnp.int32)
            out.extend([pad_row] * (num_steps - len(out)))
            break
        # fixed k = draft_len whenever the allocation allows (one compiled
        # verify shape); the commit clamp below keeps outputs exact even
        # when more is drafted than remains to emit
        k = max(min(int(draft_len), alloc - (pos + 1) - 1), 0)
        # draft k tokens from cur (argmax, or a sample from warped q)
        d_toks, q_logits = [], []
        tok = cur
        for i in range(k):
            dl, d_caches = d_step(draft_params, d_caches, tok, pos + 1 + i)
            wl = warp(dl)
            tok = (jax.random.categorical(_key(), wl) if sampled
                   else jnp.argmax(dl, axis=-1)).astype(jnp.int32)
            d_toks.append(tok)
            q_logits.append(wl)
        # one target forward over [cur, d_1 .. d_k] (L = k + 1): logits[i]
        # scores the token FOLLOWING fed[i], so a fully-accepted round
        # still has a bonus logit at index k
        fed = jnp.stack([cur] + d_toks, axis=1)               # (B, k + 1)
        logits, t_caches = verify(params, t_caches, fed, pos + 1)
        stats["target_calls"] += 1
        stats["drafted"] += k
        if k == 0:
            nxt = (jax.random.categorical(_key(), warp(logits[:, 0]))
                   if sampled else jnp.argmax(logits[:, 0], axis=-1))
            commit(nxt.astype(jnp.int32))
            cur = out[-1]
            pos += 1
            continue
        drafted = jnp.stack(d_toks, axis=1)                   # (B, k)
        if sampled:
            # rejection rule: accept x ~ q with prob min(1, p(x)/q(x));
            # the first rejection redraws from norm(max(p - q, 0))
            p = jax.nn.softmax(warp(logits[:, :k]), axis=-1)  # (B, k, V)
            q = jax.nn.softmax(jnp.stack(q_logits, axis=1), axis=-1)
            px = jnp.take_along_axis(
                p, drafted[..., None], axis=-1)[..., 0]       # (B, k)
            qx = jnp.take_along_axis(q, drafted[..., None], axis=-1)[..., 0]
            u = jax.random.uniform(_key(), (b, k))
            accept = u * jnp.maximum(qx, 1e-30) < px          # u < p/q
            prefix = jnp.cumprod(accept.astype(jnp.int32), axis=1)
            n_row = jnp.sum(prefix, axis=1)                   # (B,)
            a = int(jnp.min(n_row))
            a = min(a, num_steps - len(out) - 1)
            for i in range(a):
                commit(drafted[:, i])         # accepted by every row
            if a == k:
                # fully accepted: bonus token straight from warped p
                tok_a = jax.random.categorical(
                    _key(), warp(logits[:, k])).astype(jnp.int32)
            else:
                res = jnp.maximum(p[:, a] - q[:, a], 0.0)
                rsum = jnp.sum(res, axis=-1, keepdims=True)
                # res == 0 iff p <= q everywhere, i.e. p == q: fall back
                res = jnp.where(rsum > 0.0, res / jnp.maximum(rsum, 1e-38),
                                p[:, a])
                rej = jax.random.categorical(
                    _key(), jnp.log(jnp.maximum(res, 1e-38)))
                # rows that accepted position a keep their drafted token
                # (truncation never conditions on later randomness)
                tok_a = jnp.where(n_row > a, drafted[:, a],
                                  rej).astype(jnp.int32)
            commit(tok_a)
        else:
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            match = drafted == greedy[:, :k]                  # (B, k)
            # per-row accepted prefix length; commit the batch minimum
            prefix = jnp.cumprod(match.astype(jnp.int32), axis=1)
            a = int(jnp.min(jnp.sum(prefix, axis=1)))
            a = min(a, num_steps - len(out) - 1)
            for i in range(a):
                commit(greedy[:, i])          # == accepted draft tokens
            commit(greedy[:, a])              # bonus / correction token
        stats["accepted"] += a
        cur = out[-1]
        pos += a + 1
        if a == k and len(out) < num_steps:
            # fully-accepted round: d_k was committed (position pos, the
            # new continuation point) but never FED to the draft, so its
            # draft-cache slot would stay a zero hole inside every later
            # step's attended range, quietly eroding draft quality.  One
            # catch-up step writes it (logits discarded).
            _, d_caches = d_step(draft_params, d_caches, drafted[:, -1],
                                 pos)

    gen = jnp.stack(out[:num_steps], axis=1)
    result = jnp.concatenate([prompt, gen], axis=1)
    return (result, stats) if return_stats else result


def beam_search(model, params, prompt, num_steps: int, num_beams: int = 4,
                length_penalty: float = 0.0,
                eos_id: Optional[int] = None,
                pad_id: Optional[int] = None):
    """Deterministic beam decoding: keep the ``num_beams`` highest
    log-probability continuations of each prompt row.

    prompt: (B, P) int tokens → ``(tokens (B, num_beams, P + num_steps),
    scores (B, num_beams))``, beams sorted best-first.  Scores are summed
    token log-probabilities; ``length_penalty`` alpha > 0 divides by
    ``generated_length ** alpha`` before the final ranking (alpha = 0:
    pure sum, favors short sequences when ``eos_id`` is set).

    ``eos_id``: a beam that emits it is FINISHED — its score freezes, its
    later slots fill with ``pad_id`` (default: the eos itself), and it
    keeps competing against live beams at the frozen score.  The KV caches
    ride at batch B·num_beams and are re-gathered to each step's surviving
    parents, so memory is ``num_beams``× a greedy ``generate``.

    Beam 0 with ``num_beams=1`` is exactly greedy ``generate`` (asserted
    in tests); rolling-window caches are not supported here (beam
    reordering and ring slots don't compose yet — use ``generate``).
    """
    _check_supported(model)
    prompt = jnp.asarray(prompt, jnp.int32)
    b, p_len = prompt.shape
    k = int(num_beams)
    if k < 1:
        raise ValueError(f"num_beams must be >= 1, got {num_beams}")
    if num_steps < 1:
        raise ValueError(f"beam_search needs num_steps >= 1, got "
                         f"{num_steps}")
    if length_penalty < 0:
        raise ValueError(f"length_penalty must be >= 0, got "
                         f"{length_penalty}")
    total = p_len + int(num_steps)
    limit = _context_limit(model)
    if limit is not None and total > limit:
        raise ValueError(
            f"prompt ({p_len}) + num_steps ({num_steps}) = {total} exceeds "
            f"the model's positional-embedding range {limit}")
    vocab = _vocab_size(model)
    _validate_stopping(eos_id, pad_id, vocab)
    pad = jnp.int32(pad_id if pad_id is not None else (eos_id or 0))

    # prefill once at batch B, then tile every cache to B·k rows laid out
    # row-major (batch, beam) — beam j of row i lives at i·k + j
    caches = init_cache(model, b, total)
    logits, caches = _forward(model, params, caches, prompt, 0)
    logp0 = jax.nn.log_softmax(logits[:, -1], axis=-1)        # (B, V)
    v = logp0.shape[-1]
    scores, first = jax.lax.top_k(logp0, k)                   # (B, k)
    first = first.astype(jnp.int32)
    caches = tmap(lambda c: jnp.repeat(c, k, axis=0), caches)
    done = (first == eos_id) if eos_id is not None \
        else jnp.zeros((b, k), bool)

    # candidate row for a finished beam: only the pad column, at +0 — the
    # beam's score freezes but it stays in the running
    frozen = jnp.full((v,), -jnp.inf).at[pad].set(0.0)

    def body(carry, i):
        caches, scores, tok, done = carry
        pos = p_len + i
        logits, caches = decode_step(model, params, caches,
                                     tok.reshape(b * k), pos)
        logp = jax.nn.log_softmax(logits, axis=-1).reshape(b, k, v)
        logp = jnp.where(done[..., None], frozen, logp)
        cand = (scores[..., None] + logp).reshape(b, k * v)
        scores, idx = jax.lax.top_k(cand, k)                  # (B, k)
        parent = idx // v
        nxt = (idx % v).astype(jnp.int32)
        flat_parent = (jnp.arange(b)[:, None] * k + parent).reshape(-1)
        caches = tmap(lambda c: jnp.take(c, flat_parent, axis=0), caches)
        done = jnp.take_along_axis(done, parent, axis=1)
        if eos_id is not None:
            nxt = jnp.where(done, pad, nxt)
            done = done | (nxt == eos_id)
        return (caches, scores, nxt, done), (nxt, parent)

    (caches, scores, last, done), (toks, parents) = jax.lax.scan(
        body, (caches, scores, first, done),
        jnp.arange(int(num_steps) - 1))

    # reconstruct each surviving beam's token path by walking the parent
    # pointers backward from the final beam order
    steps = int(num_steps)
    tokens = jnp.zeros((b, k, steps), jnp.int32)
    beam = jnp.broadcast_to(jnp.arange(k), (b, k))            # final slots
    for i in range(steps - 1, 0, -1):
        tokens = tokens.at[:, :, i].set(
            jnp.take_along_axis(toks[i - 1], beam, axis=1))
        beam = jnp.take_along_axis(parents[i - 1], beam, axis=1)
    tokens = tokens.at[:, :, 0].set(
        jnp.take_along_axis(first, beam, axis=1))

    if length_penalty > 0:
        if eos_id is not None:
            hit = tokens == eos_id
            first_eos = jnp.argmax(hit, axis=-1)
            lengths = jnp.where(hit.any(axis=-1), first_eos + 1, steps)
        else:
            lengths = jnp.full((b, k), steps)
        ranked = scores / (lengths.astype(jnp.float32) ** length_penalty)
    else:
        ranked = scores
    order = jnp.argsort(-ranked, axis=-1)
    tokens = jnp.take_along_axis(tokens, order[..., None], axis=1)
    ranked = jnp.take_along_axis(ranked, order, axis=1)
    out = jnp.concatenate(
        [jnp.broadcast_to(prompt[:, None], (b, k, p_len)), tokens], axis=2)
    return out, ranked
