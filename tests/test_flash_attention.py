"""Pallas flash-attention kernel vs the XLA reference (interpret mode on CPU;
the same kernel compiles for TPU — SURVEY.md §2.2 TPU-native kernel note)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.ops import attention as attention_ops
from distkeras_tpu.ops import flash_attention as flash_ops
from distkeras_tpu.ops.attention import dot_product_attention
from distkeras_tpu.ops.flash_attention import flash_attention


def rand_qkv(seed, b=2, s=64, h=2, d=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d)) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = rand_qkv(0)
    out = flash_attention(q, k, v, causal, None, 16, 16, True)
    want = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_flash_single_block():
    q, k, v = rand_qkv(1, s=16)
    out = flash_attention(q, k, v, True, None, 128, 128, True)
    want = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_flash_gradients():
    q, k, v = rand_qkv(2, b=1, s=32, h=1, d=8)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, True, None, 16, 16, True).sum()

    def loss_ref(q, k, v):
        return dot_product_attention(q, k, v, causal=True).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("window", [1, 5, 16, 40])
def test_flash_sliding_window_matches_reference(window):
    """Windowed flash (multi-block: out-of-window k tiles are neither
    fetched nor visited) == windowed XLA reference, forward and all three
    grads."""
    q, k, v = rand_qkv(7, b=1, s=64, h=2, d=8)
    out = flash_attention(q, k, v, True, None, 16, 16, True, window)
    want = dot_product_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)

    def f_flash(q, k, v):
        return flash_attention(q, k, v, True, None, 16, 16, True,
                               window).sum()

    def f_ref(q, k, v):
        return dot_product_attention(q, k, v, causal=True,
                                     window=window).sum()

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_window_requires_causal():
    q, k, v = rand_qkv(8, s=16)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, False, None, 16, 16, True, 4)


def test_indivisible_seq_raises():
    q, k, v = rand_qkv(3, s=48)
    with pytest.raises(ValueError, match="not divisible"):
        flash_attention(q, k, v, False, None, 32, 32, True)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_backward_gradient_parity(causal):
    """The fused Pallas dq/dk/dv kernels match the dense-attention VJP on a
    multi-block problem (several q AND k blocks, both mask modes) with a
    non-uniform cotangent."""
    q, k, v = rand_qkv(4, b=2, s=64, h=2, d=16)
    ct = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal, None, 16, 16, True)
                * ct).sum()

    def loss_ref(q, k, v):
        return (dot_product_attention(q, k, v, causal=causal) * ct).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   err_msg=f"d{name}")


def _walk_avals(jaxpr):
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield var.aval
        for sub in jax.core.jaxprs_in_params(eqn.params) \
                if hasattr(jax.core, "jaxprs_in_params") else []:
            yield from _walk_avals(sub)
        for p in eqn.params.values():
            if hasattr(p, "jaxpr"):
                yield from _walk_avals(p.jaxpr)
            if isinstance(p, (list, tuple)):
                for item in p:
                    if hasattr(item, "jaxpr"):
                        yield from _walk_avals(item.jaxpr)


def test_backward_materializes_no_sxs():
    """Evidence for the flash memory claim: the whole value-and-grad
    computation contains no (S, S)-shaped intermediate — only block-sized
    tiles (the dense reference VJP does materialize S x S)."""
    s, blk = 256, 64
    q, k, v = rand_qkv(5, b=1, s=s, h=1, d=16)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, blk, blk, True).sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        q, k, v)

    def has_sxs(closed):
        return any(
            len(a.shape) >= 2 and a.shape[-1] == s and a.shape[-2] == s
            for a in _walk_avals(closed.jaxpr))

    assert not has_sxs(jaxpr), "flash backward materialized an S x S array"

    # sanity: the same detector fires on the dense reference
    def loss_ref(q, k, v):
        return dot_product_attention(q, k, v, causal=True).sum()

    ref = jax.make_jaxpr(jax.value_and_grad(loss_ref, argnums=(0, 1, 2)))(
        q, k, v)
    assert has_sxs(ref), "detector lost its teeth"


def test_flash_bf16_gradients_close():
    """bf16 inputs (the TPU training dtype): fused backward stays within
    bf16 tolerance of the f32 dense reference."""
    q, k, v = (t.astype(jnp.bfloat16) for t in rand_qkv(6, b=1, s=64, h=2,
                                                        d=16))

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, True, None, 32, 32, True)\
            .astype(jnp.float32).sum()

    def loss_ref(q, k, v):
        return dot_product_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=True).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(
        *(t.astype(jnp.float32) for t in (q, k, v)))
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                   np.asarray(b), atol=0.06)


# -- tiles chosen from the shape (block_q = block_k = None) -------------------

MODES = {"causal": (True, None), "full": (False, None),
         "window512": (True, 512)}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s", [128, 384, 640, 1024, 2048])
def test_chosen_tiles_match_reference(s, d, mode, dtype):
    """With the tiles ``_tiles`` picks, the kernels equal the XLA oracle ON
    THE SAME INPUTS, outputs and all three gradients: bf16 within the
    tolerance of ``test_flash_bf16_gradients_close`` (both sides multiply
    bf16 and accumulate f32), f32 at the f32 parity tests' tolerances
    (f32 inputs keep f32 matmuls).  Covers one tile (128), tiles that are
    no power of two (384, 640), the training cell's length, several tiles
    with clamped fetches (2048), and a window shorter and longer than S."""
    causal, window = MODES[mode]
    q, k, v = (t.astype(dtype) for t in rand_qkv(s + d, b=1, s=s, h=2, d=d))
    ct = jax.random.normal(jax.random.PRNGKey(s), q.shape).astype(dtype)

    def run(attn):
        out, vjp = jax.vjp(attn, q, k, v)
        return (out,) + vjp(ct)

    got = run(lambda q, k, v: flash_attention(q, k, v, causal, None, None,
                                              None, True, window))
    want = run(lambda q, k, v: dot_product_attention(
        q, k, v, causal=causal, window=window))
    tols = (0.06,) * 4 if dtype == "bfloat16" else (1e-5, 1e-4, 1e-4, 1e-4)
    for name, a, b, tol in zip(("out", "dq", "dk", "dv"), got, want, tols):
        assert a.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                   np.asarray(b, dtype=np.float32),
                                   atol=tol, err_msg=name)


LENGTHS = sorted({2 ** e for e in range(7, 18)}
                 | {128 * m for m in (3, 5, 6, 7, 9, 10, 12, 24, 96, 1000)})


@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("d", [64, 128, 256])
def test_tile_function(d, itemsize):
    """``_tiles`` alone: from S = 128 to 131,072 its tiles divide S, are
    whole lane blocks, stay inside the VMEM budget (with the chunk the
    kernels will walk them in), and keep masked calls' rows at or under
    half the reach of a query."""
    for s in LENGTHS:
        for causal, window in ((False, None), (True, None), (True, 512),
                               (True, 100), (True, 4096)):
            rows, span = flash_ops._tiles(s, d, itemsize, causal, window)
            chunk = flash_ops._chunk(span)
            assert s % rows == 0 and s % span == 0 and span % chunk == 0
            assert rows % 128 == 0 and chunk % 128 == 0
            assert flash_ops._vmem_bytes(rows, span, chunk, d, itemsize) \
                <= flash_ops.VMEM_BUDGET
            if causal:
                assert rows <= max(128, min(s, window or s) // 2)


def test_tile_function_refuses_what_the_dispatcher_refuses(monkeypatch):
    """No tile for a length ``_pallas_eligible`` turns away (neither whole
    lane blocks nor one short block of whole bf16 sublane tiles); one for
    every length it takes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for s in list(range(8, 400, 8)) + [500, 1000, 1024, 1100, 4096 + 64]:
        x = jax.ShapeDtypeStruct((1, s, 2, 64), jnp.bfloat16)
        if attention_ops._pallas_eligible(x, x):
            rows, span = flash_ops._tiles(s, 64, 2, True, None)
            assert s % rows == 0 and s % span == 0
        else:
            with pytest.raises(ValueError, match="not tileable"):
                flash_ops._tiles(s, 64, 2, True, None)


# -- the backward's two forms (``_backward_plan``) ----------------------------

ENVELOPE = {  # (B, S, H, Dh), kv heads, causal, window, dtype, (bq, bk)
    "causal-16x16": ((2, 64, 2, 16), None, True, None, "float32", (16, 16)),
    "full-16x16": ((2, 64, 2, 16), None, False, None, "float32", (16, 16)),
    "window5-16x16": ((1, 64, 2, 8), None, True, 5, "float32", (16, 16)),
    "window40-16x32": ((1, 64, 2, 8), None, True, 40, "float32", (16, 32)),
    "causal-32x16-bf16": ((1, 64, 2, 16), None, True, None, "bfloat16",
                          (32, 16)),
    "one-block-s16": ((1, 16, 2, 16), None, True, None, "float32",
                      (128, 128)),
    "one-block-s112": ((1, 112, 2, 64), None, True, None, "bfloat16", None),
    "gqa-s128": ((1, 128, 4, 64), 2, True, None, "bfloat16", None),
    "s384-d64-bf16": ((1, 384, 2, 64), None, True, None, "bfloat16", None),
    "s384-window256": ((1, 384, 1, 64), None, True, 256, "bfloat16", None),
    "s640-d128-f32": ((1, 640, 1, 128), None, True, None, "float32", None),
    "s640-full-d64": ((1, 640, 1, 64), None, False, None, "bfloat16", None),
    "s640-128x128": ((1, 640, 1, 64), None, True, None, "bfloat16",
                     (128, 128)),
    "cell-s1024-d64": ((1, 1024, 1, 64), None, True, None, "bfloat16", None),
}


@pytest.mark.parametrize("case", list(ENVELOPE))
def test_one_kernel_backward_matches_oracle_and_two_pass(case, monkeypatch):
    """The one-kernel backward (what ``_backward_plan`` gives every shape
    here) against the XLA oracle at the parity tests' tolerances AND against
    the two-pass pair on the same residuals: dk and dv to the bit (the same
    code computes them), dq within f32 summation order (a rounding step of
    the operand dtype where an f32 sum lands on either side of one)."""
    shape, kv_heads, causal, window, dtype, blocks = ENVELOPE[case]
    b, s, h, d = shape
    q, k, v = (t.astype(dtype) for t in rand_qkv(s + d, b=b, s=s, h=h, d=d))
    if kv_heads:
        k, v = k[:, :, :kv_heads], v[:, :, :kv_heads]
    ct = jax.random.normal(jax.random.PRNGKey(s), q.shape).astype(dtype)
    bq, bk = blocks or (None, None)
    plans = []
    chosen = flash_ops._backward_plan

    def flash(q, k, v):
        if kv_heads:  # the dispatcher repeats K/V up to H; XLA sums back
            return attention_ops.attention(q, k, v, causal=causal,
                                           window=window, impl="pallas")
        return flash_attention(q, k, v, causal, None, bq, bk, True, window)

    def grads(attn, plan=None):
        def planned(*a):
            plans.append(chosen(*a))
            return plan or plans[-1]
        monkeypatch.setattr(flash_ops, "_backward_plan", planned)
        return jax.vjp(attn, q, k, v)[1](ct)

    one = grads(flash)
    two = grads(flash, flash_ops.TWO_PASS)
    want = grads(lambda q, k, v: attention_ops.attention(
        q, k, v, causal=causal, window=window, impl="xla"))
    assert plans == [flash_ops.ONE_KERNEL] * 2
    bf16 = dtype == "bfloat16"
    for name, a, b2, w in zip(("dq", "dk", "dv"), one, two, want):
        assert a.dtype == b2.dtype == q.dtype and a.shape == w.shape
        a, b2, w = (np.asarray(t, dtype=np.float32) for t in (a, b2, w))
        np.testing.assert_allclose(a, w, atol=0.06 if bf16 else 2e-4,
                                   err_msg=f"{name} vs the oracle")
        if name == "dq":
            np.testing.assert_allclose(
                a, b2, rtol=2 ** -7 if bf16 else 1e-5,
                atol=1e-3 if bf16 else 1e-5, err_msg="dq vs two-pass")
        else:
            np.testing.assert_array_equal(a, b2, err_msg=f"{name} vs "
                                          "two-pass")


PLANS = {  # (S, Dh, itemsize, causal, window) -> one kernel?
    "cell": ((1024, 64, 2, True, None), True),
    "s128k-d128": ((131072, 128, 2, True, None), False),
    "s8192-d128-bf16": ((8192, 128, 2, True, None), True),
    "s8192-d128-f32": ((8192, 128, 4, True, None), True),
    "s8192-d128-full": ((8192, 128, 2, False, None), True),
    # either side of the threshold at the largest tiles, Dh = 128 in bf16:
    # 8.1 MiB of tiles + 1 KiB a position against 24 MiB
    "s15360-d128": ((15360, 128, 2, True, None), True),
    "s16384-d128": ((16384, 128, 2, True, None), False),
    # a window of 512 caps the rows at 256: smaller tiles, so it fits
    "s16384-d128-window512": ((16384, 128, 2, True, 512), True),
    "s32768-d128-window512": ((32768, 128, 2, True, 512), False),
    # Dh = 64 pads to the same 1 KiB a position beside smaller tiles
    "s16384-d64": ((16384, 64, 2, True, None), True),
    "s24576-d64": ((24576, 64, 2, True, None), False),
    "s16384-d64-f32": ((16384, 64, 4, True, None), False),
}


@pytest.mark.parametrize("case", list(PLANS))
def test_backward_plan(case):
    """``_backward_plan`` alone: the one kernel exactly where the whole-S dq
    accumulator and its double-buffered block fit the budget beside the
    tiles ``_tiles`` picks (never smaller ones), the pair elsewhere."""
    (s, d, itemsize, causal, window), one = PLANS[case]
    plan = flash_ops._backward_plan(s, d, itemsize, causal, window)
    assert plan == (flash_ops.ONE_KERNEL if one else flash_ops.TWO_PASS)
    rows, span = flash_ops._tiles(s, d, itemsize, causal, window)
    need = (flash_ops._vmem_bytes(rows, span, flash_ops._chunk(span), d,
                                  itemsize)
            + s * max(d, 128) * (4 + 2 * itemsize))
    assert one == (need <= flash_ops.VMEM_BUDGET)


def test_backward_plan_over_the_lengths():
    """Every tileable length: up to 8,192 one kernel at Dh <= 128 in either
    dtype, from 32,768 the pair; explicit blocks are taken as given (small
    ones leave room a long sequence's accumulator still has to fit)."""
    for s in LENGTHS:
        for d in (64, 128):
            for itemsize in (2, 4):
                for causal, window in ((False, None), (True, None),
                                       (True, 512)):
                    plan = flash_ops._backward_plan(s, d, itemsize, causal,
                                                    window)
                    if s <= 8192:
                        assert plan == flash_ops.ONE_KERNEL, (s, d, itemsize)
                    if s >= 32768:
                        assert plan == flash_ops.TWO_PASS, (s, d, itemsize)
    small = (128, 128)
    assert flash_ops._backward_plan(16384, 128, 2, True, None,
                                    small) == flash_ops.ONE_KERNEL
    assert flash_ops._backward_plan(32768, 128, 2, True, None,
                                    small) == flash_ops.TWO_PASS
    assert flash_ops.ONE_KERNEL + flash_ops.TWO_PASS == (
        "flash_bwd", "flash_dq", "flash_dkv")
