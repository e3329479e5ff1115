"""Attention ops — XLA reference implementation + dispatch.

No counterpart exists in the reference (its models are MLPs/small ConvNets;
SURVEY.md §2.3 "sequence parallelism: absent") — this is part of the
framework's long-context layer.  Layout is **BSHD** ``(batch, seq, heads,
head_dim)`` throughout: S in the second dimension keeps the (S, Dh) matmuls
MXU-shaped and makes the sequence axis shardable for ring attention
(``parallel/ring.py``).

``impl``: ``"xla"`` — plain jnp, XLA fuses the softmax chain; ``"pallas"`` —
the fused flash kernel in ``ops/flash_attention.py`` (TPU); ``None`` — pick
pallas on TPU when shapes qualify, else xla.  The paged single-token decode
step has a kernel of its own, ``ops/paged_attention.py``.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = float("-inf")


def validate_window(window: Optional[int], causal: bool) -> Optional[int]:
    """The single sliding-window rule, shared by every attention entry
    point (XLA, flash, ring, layers): requires causal, must be >= 1."""
    if window is None:
        return None
    if not causal:
        raise ValueError("window (sliding-window attention) requires "
                         "causal=True")
    if int(window) < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    return int(window)


def dot_product_attention(q, k, v, *, causal: bool = False,
                          scale: Optional[float] = None,
                          q_offset=None, kv_length=None,
                          window: Optional[int] = None,
                          kv_positions=None, segment_ids=None,
                          q_positions=None):
    """Softmax(q·kᵀ)·v with f32 softmax arithmetic.

    q: (B, Sq, H, Dh); k, v: (B, Sk, Hkv, Dh) → (B, Sq, H, Dh), in q.dtype.
    Hkv may divide H (grouped-query / multi-query attention): each group of
    H/Hkv query heads shares one k/v head, shrinking the KV projection and —
    at decode time — the KV cache by the same factor.  Hkv == H is classic
    MHA; the grouped einsum below reduces to it at G == 1.

    ``window`` (requires ``causal``): sliding-window attention — query at
    position p sees keys in (p - window, p], i.e. itself and the previous
    ``window - 1`` tokens.  Information still propagates ``window`` tokens
    per layer, so reach grows with depth.  Here (the XLA path) the window
    is mask-only — scores are computed then hidden; the flash kernel
    (``flash_attention(window=...)``, used automatically on TPU) skips
    out-of-window blocks outright for true O(S·W) compute.

    KV-cache decoding hooks (``core/decode.py`` — keeps decode on this
    exact numerics path): ``q_offset`` places query i at absolute position
    ``q_offset + i`` for the causal mask (queries continuing a cached
    prefix); ``kv_length`` masks key slots >= it out of the softmax
    (zero-filled tail of a preallocated cache); ``kv_positions`` gives
    each key slot an EXPLICIT absolute position (rolling/ring-buffer
    caches, where slot order ≠ position order — negative = empty slot),
    overriding the identity slot→position layout that ``causal``/
    ``kv_length`` otherwise assume.  All accept tracers.

    Each hook also accepts a PER-ROW form — ``q_offset``/``kv_length`` of
    shape (B,), ``kv_positions`` of shape (B, Sk) — so one batched decode
    step can advance every row at its own position (the serving engine's
    slot pool, where slots hold requests of different lengths).  The
    per-row forms compose with Sq > 1: the serving engine's speculative
    verify scores L = spec_len + 1 continuation tokens per row in one
    forward, each row's causal mask anchored at its own ``q_offset`` and
    its ``kv_length`` frontier at ``q_offset + L`` (ring caches pass a
    ``kv_positions`` built from each row's write FRONTIER, which also
    hides the round's just-written future entries from its earlier
    queries).  The scalar form takes the exact code path it always did.  Together the two hooks
    carry the serving engine's BUCKETED PREFILL masking: at prefill time a
    batch of prompts right-padded to one bucket length needs only the
    causal mask — pad keys sit at positions >= every real query, so no
    real row's softmax ever sees them — and at decode time the per-row
    ``kv_length`` frontier keeps the padded cache tail masked until real
    writes overwrite it.  (Masking pad QUERIES' keys explicitly would be
    wrong under ``window``: a pad position past the real prompt can end up
    with an all-masked — empty — softmax row, and the resulting NaN
    output poisons real rows through the next layer's 0·NaN value
    products.  The causal mask always leaves a query its own key.)

    ``q_positions`` (B, Sq) int: EXPLICIT per-query absolute positions,
    overriding the ``q_offset + arange(Sq)`` layout (and forcing the
    per-row mask path).  The paged-KV suffix prefill uses it to clamp its
    right-pad queries onto the last real prompt position — a pad query
    past the view (or past a sliding window's reach over the view) would
    otherwise mask EVERY key and poison real rows with its empty-softmax
    NaN; clamped, it attends like the final real token and its junk
    output is simply discarded.  Real queries pass their true positions,
    so this is mask-identical to ``q_offset`` for them.

    ``segment_ids`` (B, S) int: sequence-packing isolation — query and key
    attend only within equal segment ids (on top of causal/window), so
    several documents packed into one row never see each other.  Id 0 is
    the padding convention (``data/packing.py``); padded slots still see
    themselves under ``causal``, so no softmax row is ever empty.  With
    RoPE (relative positions) each packed document attends exactly as it
    would unpacked.  Self-attention only (Sq == Sk).
    """
    *_, d = q.shape
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    b, sq, h, _ = q.shape
    hkv = k.shape[2]
    if h % hkv:
        raise ValueError(f"num_heads {h} not divisible by kv heads {hkv}")
    window = validate_window(window, causal)
    if kv_positions is not None and not causal:
        raise ValueError("kv_positions (rolling-cache slot positions) "
                         "requires causal=True — its empty-slot masking "
                         "lives in the causal mask")
    if segment_ids is not None and k.shape[1] != sq:
        raise ValueError("segment_ids (sequence packing) is a "
                         "self-attention feature: Sq must equal Sk, got "
                         f"{sq} vs {k.shape[1]}")
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    k_pos = (jnp.arange(k.shape[1]) if kv_positions is None
             else jnp.asarray(kv_positions))
    per_row = (q_positions is not None
               or k_pos.ndim == 2
               or getattr(q_offset, "ndim", 0) >= 1
               or getattr(kv_length, "ndim", 0) >= 1)
    if causal:
        if per_row:
            # batched masks: row r is a request at its own position
            if q_positions is not None:
                q_pos = jnp.asarray(q_positions)
            else:
                q_off = jnp.asarray(0 if q_offset is None else q_offset)
                q_pos = jnp.arange(sq)[None, :] + jnp.reshape(q_off,
                                                              (-1, 1))
            kp = k_pos if k_pos.ndim == 2 else k_pos[None, :]  # (B|1, Sk)
            mask = kp[:, None, :] > q_pos[:, :, None]          # (B, Sq, Sk)
            if window is not None:
                mask = mask | (kp[:, None, :] <= q_pos[:, :, None] - window)
            if kv_positions is not None:
                mask = mask | (kp[:, None, :] < 0)  # negative = empty slot
            scores = jnp.where(mask[:, None, None], NEG_INF, scores)
        else:
            q_pos = jnp.arange(sq) + (0 if q_offset is None else q_offset)
            mask = k_pos[None, :] > q_pos[:, None]  # (Sq, Sk): True = hide
            if window is not None:
                mask = mask | (k_pos[None, :] <= q_pos[:, None] - window)
            if kv_positions is not None:
                mask = mask | (k_pos[None, :] < 0)  # negative = empty slot
            scores = jnp.where(mask[None, None, None], NEG_INF, scores)
    if kv_length is not None:
        if per_row:
            kl = jnp.reshape(jnp.asarray(kv_length), (-1, 1))  # (B, 1)
            kp = k_pos if k_pos.ndim == 2 else k_pos[None, :]
            scores = jnp.where((kp < kl)[:, None, None, None, :],
                               scores, NEG_INF)
        else:
            scores = jnp.where((k_pos < kv_length)[None, None, None, None],
                               scores, NEG_INF)
    if segment_ids is not None:
        seg = jnp.asarray(segment_ids)
        cross = seg[:, :, None] != seg[:, None, :]        # (B, Sq, Sk)
        scores = jnp.where(cross[:, None, None], NEG_INF, scores)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, h, d)


# ---------------------------------------------------------------------------
# paged-KV (block-table) forms — the serving engine's paged slot pool
# ---------------------------------------------------------------------------

def paged_gather(arena, block_tables, page_size: int, view_len: int):
    """Gather a per-row logical K/V view out of a flat paged arena.

    ``arena``: (A, ...) — a flat pool of fixed-size blocks laid out
    contiguously along axis 0 (``A = (num_blocks + 1) * page_size``; the
    trailing block is the NULL block junk writes are routed into).
    ``block_tables``: (B, T) int32 — row r's logical block i lives at
    physical block ``block_tables[r, i]``; entries equal to the null
    block id drop reads into junk (masked by the caller's frontier).
    Returns the (B, view_len, ...) logical view: entry (r, p) is the
    arena slot holding row r's logical position p.  This is the
    gather-by-block-table read of the paged PREFILL programs, the
    speculative verify, ring and int8 pools and every CPU engine; the
    single-token decode step on a TPU no longer builds this view and reads
    the arena in place (``ops.paged_attention``; ``core.decode.
    paged_kernel_applies`` says which).  The values are bit-identical to a
    dense (B, view_len, ...) cache holding the same writes, so attention
    over the view reproduces the dense path's numerics exactly; the
    serving arena's rows are ``Hkv * Dh`` features wide and the caller
    unfolds the heads.
    """
    idx = jnp.arange(int(view_len))
    blk = jnp.minimum(idx // int(page_size), block_tables.shape[1] - 1)
    phys = (jnp.take(block_tables, blk, axis=1) * int(page_size)
            + (idx % int(page_size))[None, :])            # (B, view_len)
    return arena[phys]


def paged_attention(q, k_arena, v_arena, block_tables, page_size: int,
                    view_len: int, *, q_positions=None, q_offset=None,
                    kv_length=None, window: Optional[int] = None,
                    kv_positions=None, scale: Optional[float] = None):
    """``dot_product_attention`` over block-table-gathered K/V: each row's
    keys/values are gathered from the flat ``k_arena``/``v_arena`` through
    its block table, then attended with the usual per-row causal masks
    (``q_positions``/``q_offset`` anchor the queries, ``kv_length`` masks
    the unwritten logical tail, ``kv_positions`` carries ring layouts).
    Arena rows hold a position's kv heads side by side (``Hkv * Dh``
    features, ``core.decode.init_paged_arena``) and are unfolded by ``q``'s
    head_dim.  Quantized arenas dequantize BEFORE this entry point (the
    caller gathers codes + scales and fuses the dequant — see
    ``core/decode.py``)."""
    def view(arena):
        rows = paged_gather(arena, block_tables, page_size, view_len)
        return rows.reshape(rows.shape[:2] + (-1, q.shape[-1]))

    k, v = view(k_arena), view(v_arena)
    return dot_product_attention(q, k, v, causal=True, scale=scale,
                                 q_positions=q_positions, q_offset=q_offset,
                                 kv_length=kv_length, window=window,
                                 kv_positions=kv_positions)


def attention(q, k, v, *, causal: bool = False, scale: Optional[float] = None,
              impl: Optional[str] = None, window: Optional[int] = None,
              segment_ids=None):
    """Dispatching entry point used by the MultiHeadAttention layer."""
    # validate before the window>=S normalization below, so the error
    # doesn't depend on the window size
    window = validate_window(window, causal)
    if window is not None and window >= k.shape[1]:
        window = None  # covers every key: mathematically plain causal
    if segment_ids is not None and impl == "pallas":
        # packing isolation is mask-level — the flash kernel has no
        # segment support, so packed batches take the XLA path
        raise ValueError("segment_ids (sequence packing) is not "
                         "supported by the pallas flash kernel — use "
                         "impl='xla' (or leave impl unset)")
    if impl is None:
        impl = ("pallas" if segment_ids is None and _pallas_eligible(q, k)
                else "xla")
    if impl == "xla":
        return dot_product_attention(q, k, v, causal=causal, scale=scale,
                                     window=window, segment_ids=segment_ids)
    if impl == "pallas":
        from .flash_attention import flash_attention
        if k.shape[2] != q.shape[2]:
            # GQA/MQA: the kernel is written for equal head counts; repeat
            # k/v up to H.  The flash win (no S×S materialization) is
            # head-count independent, and the repeat is HBM-cheap next to
            # the scores it avoids; the GQA KV-cache/projection savings
            # live in the layer, not the kernel.
            if q.shape[2] % k.shape[2]:
                raise ValueError(f"num_heads {q.shape[2]} not divisible "
                                 f"by kv heads {k.shape[2]}")
            g = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               window=window)
    raise ValueError(f"unknown attention impl {impl!r}")


def _pallas_eligible(q, k) -> bool:
    """Fused kernel wants TPU, self-attention lengths (the kernel folds k/v
    with q's sequence length — cross-attention falls back to XLA), and a
    block-tileable sequence: a multiple of the 128-lane block, or a single
    block whose rows satisfy the strictest (bf16: 16) sublane tile.
    head_dim is unconstrained — the kernel's blocks span the whole (d) dim,
    which TPU tiling always allows (d=64 exercised on the chip by
    ``chip_smoke.py``, phase ``kernels``)."""
    if jax.default_backend() != "tpu":
        return False
    if q.shape[1] != k.shape[1]:
        return False
    s = q.shape[1]
    return s % 128 == 0 or (s <= 128 and s % 16 == 0)
