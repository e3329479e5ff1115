"""The paged decode kernel (``ops/paged_attention.py``) against the path it
stands in for: ``paged_gather`` + ``dot_product_attention`` over the same
arena.  Interpret mode on the CPU; ``tests/test_chip_compile.py`` compiles
the kernel for a described v5e and ``chip_smoke.py`` runs it on the chip.
Then the dispatch: which paged reads ``_mha_forward`` hands to the kernel,
and what the serving engine reports about its decode program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.core import decode
from distkeras_tpu.core.model import FittedModel
from distkeras_tpu.models import transformer_lm
from distkeras_tpu.ops.attention import paged_attention
from distkeras_tpu.ops.paged_attention import (kernel_tiles,
                                               paged_decode_attention)
from distkeras_tpu.serving import ServingEngine


def oracle(q, k_arena, v_arena, tables, lengths, page, view):
    """One query token a row through the gather path (``paged_gather`` +
    ``dot_product_attention``), as the decode step runs it: the query sits
    at the row's last position."""
    pos = jnp.maximum(lengths - 1, 0)
    return paged_attention(q[:, None], k_arena, v_arena, tables, page, view,
                           q_positions=pos[:, None], kv_length=lengths)[:, 0]


def pool(page, h, hkv, dh, view, lengths, dtype, shared=0, junk=None,
         seed=0):
    """Arenas, tables and queries for rows of ``lengths`` positions, blocks
    dealt out in a shuffled order.  ``shared``: rows 0 and 1 hold the SAME
    physical blocks for their first ``shared`` pages (a radix prefix hit).
    ``junk``: what to write into the null block and past each row's length
    inside its last page (the oracle gets zeros there instead: it multiplies
    a zero probability with whatever a masked position holds)."""
    rng = np.random.default_rng(seed)
    b, cols = len(lengths), -(-view // page) + 1
    blocks = b * (cols - 1) + 1
    f = hkv * dh
    arenas = [rng.standard_normal(((blocks + 1) * page, f)).astype(np.float32)
              for _ in range(2)]
    tables = np.full((b, cols), blocks, np.int32)       # null = `blocks`
    free = list(rng.permutation(blocks))
    for r, n in enumerate(lengths):
        for i in range(-(-n // page)):
            tables[r, i] = (tables[0, i] if r == 1 and i < shared
                            else free.pop())
    clean = [a.copy() for a in arenas]
    for a, c in zip(arenas, clean):
        spots = [slice(blocks * page, None)]
        spots += [slice(tables[r, (n - 1) // page] * page + n % page,
                        (tables[r, (n - 1) // page] + 1) * page)
                  for r, n in enumerate(lengths) if n % page]
        for s in spots:
            c[s] = 0.0
            if junk is not None:
                a[s] = junk
    q = rng.standard_normal((b, h, dh)).astype(np.float32)
    cast = lambda x: jnp.asarray(x, dtype)
    return (cast(q), [cast(a) for a in arenas], [cast(c) for c in clean],
            jnp.asarray(tables), jnp.asarray(lengths, jnp.int32))


CASES = {
    # page, H, Hkv, Dh, view, lengths
    "ragged_1_page_page+1_max": (16, 4, 4, 64, 512, [1, 16, 17, 512]),
    "two_blocks_and_an_edge": (16, 4, 4, 64, 1024, [256, 257, 1024, 255]),
    "dead_row_among_live": (16, 4, 4, 64, 128, [40, 0, 128, 0, 3]),
    "all_rows_dead": (16, 4, 4, 64, 64, [0, 0]),
    "gqa_hkv_2_of_8": (16, 8, 2, 64, 128, [100, 16, 1]),
    "mqa_one_kv_head_dh128": (16, 4, 1, 128, 64, [64, 9]),
    "twelve_heads_pad_to_a_tile": (16, 12, 12, 64, 64, [33, 64]),
    "page_32": (32, 4, 4, 64, 256, [1, 32, 33, 256, 0]),
    "page_8_f32_tile": (8, 2, 2, 64, 64, [8, 9, 64]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_the_gather_path(case, dtype):
    page, h, hkv, dh, view, lengths = CASES[case]
    q, arenas, _, tables, n = pool(page, h, hkv, dh, view, lengths, dtype)
    got = paged_decode_attention(q, *arenas, tables, n, page, interpret=True)
    want = oracle(q, *arenas, tables, n, page, view)
    want = jnp.where((n > 0)[:, None, None], want, 0)   # a dead row: zeros
    assert got.shape == q.shape and got.dtype == q.dtype
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_rows_sharing_a_prefix_block_read_the_same_pages():
    page, lengths = 16, [70, 50, 20]
    q, arenas, _, tables, n = pool(page, 4, 4, 64, 128, lengths,
                                   jnp.float32, shared=3)
    assert (tables[0, :3] == tables[1, :3]).all()
    got = paged_decode_attention(q, *arenas, tables, n, page, interpret=True)
    want = oracle(q, *arenas, tables, n, page, 128)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("junk", [np.nan, np.inf, 1e30],
                         ids=["nan", "inf", "huge"])
def test_junk_past_a_rows_length_never_reaches_the_output(junk):
    """The null block and the tail of each row's last page hold junk (the
    engine parks idle rows' writes in the first and leaves stale entries in
    the second); the kernel's output is that of an arena with zeros there."""
    page, lengths = 16, [1, 17, 40, 0, 64, 63]
    q, dirty, clean, tables, n = pool(page, 4, 2, 64, 64, lengths,
                                      jnp.float32, junk=junk)
    got = paged_decode_attention(q, *dirty, tables, n, page, interpret=True)
    want = oracle(q, *clean, tables, n, page, 64)
    want = jnp.where((n > 0)[:, None, None], want, 0)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_table_entries_past_a_rows_length_are_never_read():
    """Out-of-range ids past the live pages would fault a DMA on the chip
    and index out of bounds here."""
    page, lengths = 16, [20, 1]
    q, arenas, _, tables, n = pool(page, 4, 4, 64, 64, lengths, jnp.float32)
    want = paged_decode_attention(q, *arenas, tables, n, page,
                                  interpret=True)
    wild = np.asarray(tables).copy()
    wild[0, 2:] = 10 ** 6
    wild[1, 1:] = -7
    got = paged_decode_attention(q, *arenas, jnp.asarray(wild), n, page,
                                 interpret=True)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("why,q_shape,arena,dtype,page,ok", [
    ("the cell", (64, 16, 64), (65552, 1024), jnp.bfloat16, 16, True),
    ("gpt2-small, 12 heads", (8, 12, 64), (1040, 768), jnp.bfloat16, 16,
     True),
    ("f32 pages of 8", (4, 2, 64), (136, 128), jnp.float32, 8, True),
    ("bf16 pages of 8: half a packed tile", (4, 2, 64), (136, 128),
     jnp.bfloat16, 8, False),
    ("rows of 32 features: a quarter of a lane tile", (4, 2, 16), (136, 32),
     jnp.float32, 8, False),
    ("a cube, not rows", (4, 2, 64), (136, 2, 64), jnp.float32, 8, False),
    ("4096 slots: q and the output outgrow VMEM", (4096, 16, 64),
     (65552, 1024), jnp.bfloat16, 16, False),
])
def test_which_shapes_the_compiled_kernel_takes(why, q_shape, arena, dtype,
                                                page, ok):
    assert kernel_tiles(q_shape, dtype, arena, dtype, page, 1024) is ok


# -- the dispatch ---------------------------------------------------------------

def lm(window=None, seed=0):
    model = transformer_lm(vocab_size=64, seq_len=64, d_model=128,
                           num_heads=2, num_layers=2, mlp_dim=128,
                           compute_dtype="float32", attention_window=window)
    return model, model.init(jax.random.PRNGKey(seed))


def applies(model, length=1, *, ring=False, kv_dtype=None, bounds=False,
            page=8):
    arena = decode.init_paged_arena(model, 8, page, kv_dtype=kv_dtype)
    block = next(i for i, c in enumerate(arena) if c is not None)
    mha = model.layers[block]._mha()
    lim = jnp.zeros((4,), jnp.int32) if bounds else None
    view = decode.PagedView(None, page, 64, floor=lim, ceil=lim, qcap=lim,
                            ring=ring)
    return decode.paged_kernel_applies(
        mha, arena[block], view, jax.ShapeDtypeStruct(
            (4, length, mha.num_heads, mha.key_dim), jnp.float32))


def test_the_kernel_takes_the_single_token_step_on_a_tpu(monkeypatch):
    model, _ = lm()
    assert not applies(model)                     # the CPU: gather
    monkeypatch.setattr(decode, "_on_tpu", lambda: True)
    assert applies(model)
    assert decode.paged_step_on_kernel(model, decode.init_paged_arena(
        model, 8, 8), 4, 8, 64)


@pytest.mark.parametrize("why,kw", [
    ("speculative verify: L > 1", dict(length=3)),
    ("a ring view", dict(ring=True)),
    ("an int8 arena", dict(kv_dtype="int8")),
    ("prefill's write and query bounds", dict(bounds=True)),
    ("pages the kernel cannot tile", dict(page=4)),
])
def test_everything_else_keeps_the_gather_path(monkeypatch, why, kw):
    monkeypatch.setattr(decode, "_on_tpu", lambda: True)
    assert not applies(lm()[0], **kw)


def test_a_windowed_layer_keeps_the_gather_path(monkeypatch):
    monkeypatch.setattr(decode, "_on_tpu", lambda: True)
    assert not applies(lm(window=16)[0])


PROMPTS = [(np.arange(1, 6) % 64, 7), (np.arange(3, 43) % 64, 5),
           (np.arange(7, 27) % 64, 9), (np.arange(2, 4) % 64, 4)]


def served(**kw):
    eng = ServingEngine(FittedModel(*lm()), num_slots=3, max_len=64,
                        paged=True, block_size=8, prefill_chunk=16, **kw)
    eng.start()
    try:
        handles = [eng.submit(p, n) for p, n in PROMPTS]
        assert all(h.wait(300) for h in handles)
    finally:
        eng.stop()
    return eng, [list(h.tokens) for h in handles]


def test_the_engine_counts_its_kernel_steps_and_serves_the_same_tokens(
        monkeypatch):
    """With the TPU test answered yes the decode program is built on the
    kernel (interpreted here): every decode step counts, retired and
    prefilling slots ride along as rows of length 0, and the greedy tokens
    are those of the gather path."""
    plain, want = served()
    assert plain._decode_attn == "gather"
    assert plain.stats["paged_kernel_steps"] == 0
    monkeypatch.setattr(decode, "_on_tpu", lambda: True)
    eng, got = served()
    assert eng._decode_attn == "kernel"
    assert eng.stats["decode_steps"] > 0
    assert eng.stats["paged_kernel_steps"] == eng.stats["decode_steps"]
    assert got == want


@pytest.mark.parametrize("why,kw", [
    ("int8 arena", dict(kv_dtype="int8")),
    ("speculative round", dict(spec_draft=FittedModel(*lm(seed=1)),
                               spec_len=2)),
])
def test_engines_off_the_kernel_path_say_so(monkeypatch, why, kw):
    monkeypatch.setattr(decode, "_on_tpu", lambda: True)
    eng, _ = served(**kw)
    assert eng._decode_attn == "gather"
    assert eng.stats["decode_steps"] > 0
    assert eng.stats["paged_kernel_steps"] == 0
