"""The main path's Pallas kernels compile for the chip, without the chip.

libtpu's compiler is installed in the CPU sandbox and compiles for a
DESCRIBED ``v5e:2x2`` topology (``jax.experimental.topologies``): nothing
runs, but Mosaic refuses here exactly what it would refuse on the device —
misaligned slices, too much VMEM, a kernel that cannot be partitioned —
which interpret mode (every other kernel test in this suite) cannot see.
Shapes are the real widths ``chip_smoke.py`` and the benchmark's cells run.

Everything that touches the topology lives in the module-scoped fixtures
below (never at import time: under pytest-xdist every worker imports this
file, but only the one that RUNS it may load libtpu), and the kernels are
compiled in this process with ``interpret=False`` passed by the test — the
``interpret=None`` rule asks ``jax.default_backend()``, which is the CPU
here.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from distkeras_tpu.ops import flash_attention as flash_ops
from distkeras_tpu.ops.flash_attention import flash_attention
from distkeras_tpu.ops.fused_ce import fused_softmax_cross_entropy
from distkeras_tpu.ops.paged_attention import paged_decode_attention

KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable can be written to the persistent
    # cache but never read back; keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *structs):
    return jax.jit(fn).lower(*structs).compile().as_text()


def _copies_of(text, *shapes):
    """The program's ``copy`` instructions whose result has one of
    ``shapes``: a whole array relaid before something reads it."""
    names = ["[" + ",".join(map(str, shp)) + "]" for shp in shapes]
    return [line for line in text.splitlines() if " copy(" in line
            and any(n in line.split(" copy(")[0] for n in names)]


# -- flash attention ---------------------------------------------------------

FLASH_SHAPES = [  # (B, S, H, Dh), dtype
    ((8, 1024, 12, 64), jnp.bfloat16),   # train-adag-gpt2s's own
    ((2, 2048, 16, 128), jnp.bfloat16),
    ((2, 2048, 16, 64), jnp.bfloat16),
    ((1, 8192, 16, 128), jnp.bfloat16),
    ((2, 1024, 8, 64), jnp.float32),
    # the one shape whose whole-S dq accumulator does not fit beside its
    # chosen tiles: the two-pass backward pair stays compiled for a v5e
    ((1, 16384, 2, 128), jnp.bfloat16),
]


def _flash(window, blocks, q, k, v):
    return flash_attention(q, k, v, True, None, blocks, blocks, False,
                           window)


def _flash_grads(blocks, q, k, v):
    return jax.grad(
        lambda *a: _flash(None, blocks, *a).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("blocks", [None, 128], ids=["chosen", "128x128"])
@pytest.mark.parametrize("mode", ["fwd", "bwd", "window512"])
@pytest.mark.parametrize("shape,dtype", FLASH_SHAPES,
                         ids=lambda x: "x".join(map(str, x))
                         if isinstance(x, tuple) else np.dtype(x).name)
def test_flash_attention_compiles_for_v5e(one_chip, shape, dtype, mode,
                                          blocks):
    """With the tiles ``_tiles`` chooses from the shape (``None``) and with
    an explicit 128 x 128: the kernels are in the program under the names
    the benchmark's readers and ``chip_smoke.py`` look for; the backward's
    are the ones ``_backward_plan`` gives for the shape."""
    qkv = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)] * 3
    fn = {"fwd": functools.partial(_flash, None, blocks),
          "bwd": functools.partial(_flash_grads, blocks),
          "window512": functools.partial(_flash, 512, blocks)}[mode]
    text = _compiled_text(fn, *qkv)
    # forward is one kernel; backward is the recomputed fwd + the plan's
    names = ("flash_fwd",)
    if mode == "bwd":
        _, s, _, d = shape
        plan = flash_ops._backward_plan(
            s, d, np.dtype(dtype).itemsize, True, None,
            blocks and (blocks, blocks))
        long = s == 16384
        assert plan == (flash_ops.TWO_PASS if long and blocks is None
                        else flash_ops.ONE_KERNEL)
        names += plan
    assert text.count(KERNEL) == len(names)
    for name in names:
        assert name in text


# -- fused cross-entropy -----------------------------------------------------

CE_SHAPES = [  # (T, V), dtype
    ((8192, 50304), jnp.bfloat16),
    ((8192, 32000), jnp.bfloat16),
    ((1000, 50257), jnp.bfloat16),   # ragged rows AND ragged vocab
    ((4096, 50257), jnp.float32),
]


def _ce(logits, labels):
    return fused_softmax_cross_entropy(logits, labels, interpret=False)


def _ce_grad(logits, labels):
    return jax.grad(lambda lg: _ce(lg, labels).sum())(logits)


@pytest.mark.parametrize("mode", ["fwd", "bwd"])
@pytest.mark.parametrize("shape,dtype", CE_SHAPES,
                         ids=lambda x: "x".join(map(str, x))
                         if isinstance(x, tuple) else np.dtype(x).name)
def test_fused_ce_compiles_for_v5e(one_chip, shape, dtype, mode):
    logits = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    labels = jax.ShapeDtypeStruct(shape[:1], jnp.int32, sharding=one_chip)
    text = _compiled_text(_ce if mode == "fwd" else _ce_grad, logits,
                          labels)
    assert KERNEL in text


def test_trainers_step_at_the_cells_widths_holds_the_ce_kernels(
        topo, monkeypatch):
    """``train-adag-gpt2s``'s epoch program (ADAG on the SPMD engine, one
    worker, 8 x 1,024 tokens, vocabulary 50,257; ONE block for the
    compile's sake, 10 s) for the described v5e: the loss is the two
    kernels; nothing logits-shaped is scattered into, sliced, or copied
    between layouts (the head's product is born ``(8192, 50257)``
    row-major: ``Dense.apply`` flattens its rows); no loop but the two
    scans.  The parent's program had a ``while`` of 8,192 scalar updates
    into a zero-filled ``f32[8,1024,50257]`` here (PERF.md section 6,
    PR 33)."""
    import re
    from distkeras_tpu.core import optimizers as opt_lib
    from distkeras_tpu.models import transformer_lm
    from distkeras_tpu.parallel.spmd import (DistState, SPMDEngine,
                                             WORKER_AXIS)
    # the dispatch rules ask the backend (the CPU here); the program is
    # compiled for the chip, so they are given the chip's answer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    batch, seq, vocab, window, rounds = 8, 1024, 50257, 12, 2
    mesh = Mesh(np.array(topo.devices[:1]), (WORKER_AXIS,))
    model = transformer_lm(vocab_size=vocab, seq_len=seq, d_model=768,
                           num_heads=12, num_layers=1, mlp_dim=3072,
                           compute_dtype="bfloat16")
    eng = SPMDEngine(model, "sparse_categorical_crossentropy_from_logits",
                     "adam", mesh, "adag", communication_window=window,
                     learning_rate=1e-3)
    params = jax.eval_shape(lambda k: model.init(k, (seq,)),
                            jax.random.PRNGKey(0))
    eng.tx = opt_lib.build_tx(eng.optimizer, params)
    rep = NamedSharding(mesh, P())
    per_worker = NamedSharding(mesh, P(WORKER_AXIS))
    columns = NamedSharding(mesh, P(None, None, WORKER_AXIS))
    stacked = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((1,) + a.shape, a.dtype,
                                       sharding=per_worker), tree)
    state = DistState(
        jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=rep), params),
        stacked(params), stacked(jax.eval_shape(eng.tx.init, params)),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
    tokens = jax.ShapeDtypeStruct((rounds, window, 1, batch, seq),
                                  jnp.int32, sharding=columns)
    mask = jax.ShapeDtypeStruct((rounds, window, 1, batch), jnp.float32,
                                sharding=columns)
    keys = jax.ShapeDtypeStruct((1, 2), jnp.uint32, sharding=per_worker)
    text = eng._build_epoch_fn().lower(state, tokens, tokens, mask,
                                       keys).compile().as_text()

    calls = lambda name: len(re.findall(rf"%{name}[.\d]* = ", text))
    assert calls("fused_ce_fwd") == 1 and calls("fused_ce_bwd") == 1
    assert calls("flash_fwd") == calls("flash_bwd") == 1  # once a layer
    assert calls("flash_dq") == calls("flash_dkv") == 0
    assert len(re.findall(r" while\(", text)) == 2  # rounds, window
    logits = re.compile(rf"f32\[({batch},{seq}|{batch * seq}),{vocab}\]")
    moved = [line.split(" = ")[0].strip() for line in text.splitlines()
             if re.search(r"\b(copy|scatter|dynamic-update-slice|"
                          r"dynamic-slice)\(", line)
             and logits.search(line.split("(")[0])]
    assert moved == [], (
        f"{moved}: the logits are moved on their way to or from the loss's "
        "kernels.  The invariant: Dense.apply flattens its rows BEFORE the "
        "product and adds the bias BEFORE reshaping back, so that the head's "
        "result is born f32[8192,50257] row-major; with the bias after the "
        "reshape XLA folds the reshape into the product, which then comes "
        "out sequence-minor, and a 1.65-GB relayout copy stands before "
        "fused_ce_fwd (core/layers.py; PERF.md section 6, PR 33)")
    assert not re.search(r"scatter\(.*op_name=\"[^\"]*loss", text)


# -- paged decode attention --------------------------------------------------

PAGED_SHAPES = [  # slots B, heads H, kv heads, Dh, blocks, page; dtype
    ((64, 16, 16, 64, 4096, 16), jnp.bfloat16),  # serve-chat-gpt2m's pool
    ((8, 12, 12, 64, 512, 16), jnp.bfloat16),    # gpt2-small: 12 heads pad
    ((64, 32, 8, 128, 1024, 32), jnp.bfloat16),  # grouped queries, Dh 128
    ((16, 16, 16, 64, 1024, 8), jnp.float32),
]


@pytest.mark.parametrize("shape,dtype", PAGED_SHAPES,
                         ids=lambda x: "x".join(map(str, x))
                         if isinstance(x, tuple) else np.dtype(x).name)
def test_paged_decode_compiles_for_v5e(one_chip, shape, dtype):
    """The decode step's attention part as ``_mha_forward`` runs it: the
    new token's K and V scattered into the donated arenas, the kernel
    reading them through the tables.  One kernel, and no copy of an arena:
    rows of Hkv * Dh features stay row-major at rest, the page view is a
    bitcast and the scatter writes in place."""
    b, h, hkv, dh, blocks, page = shape
    slots, f, cols = (blocks + 1) * page, hkv * dh, 1024 // page + 1

    def step(ka, va, q, kt, vt, tables, pos):
        at = jnp.take_along_axis(tables, (pos // page)[:, None],
                                 axis=1)[:, 0] * page + pos % page
        ka, va = ka.at[at].set(kt), va.at[at].set(vt)
        out = paged_decode_attention(q, ka, va, tables, pos + 1, page,
                                     interpret=False)
        return out, ka, va

    S = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)
    text = jax.jit(step, donate_argnums=(0, 1)).lower(
        S((slots, f), dtype), S((slots, f), dtype), S((b, h, dh), dtype),
        S((b, f), dtype), S((b, f), dtype), S((b, cols), jnp.int32),
        S((b,), jnp.int32)).compile().as_text()
    assert text.count(KERNEL) == 1
    arena = f"[{slots},{f}]"
    assert not [line for line in text.splitlines()
                if " copy(" in line and arena in line.split(" copy(")[0]]


# -- the hybrid serving cell's kernels at its published shapes ---------------

def test_paged_decode_compiles_for_v5e_at_the_gqa_shape(one_chip):
    """serve-reason-solar2's attention layer: 64 query heads over 8 KV heads
    of 128, 128 slots, a 262,144-token pool, views of 4,096."""
    b, h, hkv, dh, blocks, page, view = 128, 64, 8, 128, 16384, 16, 4096
    slots, f, cols = (blocks + 1) * page, hkv * dh, view // page + 1

    def step(ka, va, q, tables, lengths):
        return paged_decode_attention(q, ka, va, tables, lengths, page,
                                      interpret=False)

    S = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)
    bf = jnp.bfloat16
    text = jax.jit(step).lower(
        S((slots, f), bf), S((slots, f), bf), S((b, h, dh), bf),
        S((b, cols), jnp.int32), S((b,), jnp.int32)).compile().as_text()
    assert text.count(KERNEL) == 1


def test_kda_decode_compiles_for_v5e(one_chip):
    """The fused decode step of a KDA layer over the cell's 128 slots of
    64 x 128 x 128 float32 state: one kernel, the donated state updated in
    place (no copy of it anywhere in the program)."""
    from distkeras_tpu.ops.kda import kda_decode, kernel_tiles
    b, h, d = 128, 64, 128
    assert kernel_tiles((b, h, d, d), jnp.float32)

    def step(q, k, v, g, beta, state, live):
        return kda_decode(q, k, v, g, beta, state, live, interpret=False)

    S = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)
    f32 = jnp.float32
    vec = S((b, h, d), f32)
    text = jax.jit(step, donate_argnums=(5,)).lower(
        vec, vec, vec, vec, S((b, h), f32), S((b, h, d, d), f32),
        S((b,), jnp.bool_)).compile().as_text()
    assert text.count(KERNEL) == 1
    assert not _copies_of(text, (b, h, d, d))


@pytest.mark.parametrize("m,k,n,e,stored", [
    (1024, 4096, 2560, 40, "kn"), (1024, 1280, 4096, 40, "kn"),
    (2048, 4096, 2560, 40, "kn"), (2048, 1280, 4096, 40, "kn"),
    # serve-context-nemotron3n: 64 held experts of 2,688 x 1,856, widths no
    # usual tile divides; a decode step's 256 x 6 and a unit's 1,024 x 6
    (1536, 2688, 1856, 64, "kn"), (1536, 1856, 2688, 64, "kn"),
    (6144, 2688, 1856, 64, "kn"), (6144, 1856, 2688, 64, "kn"),
    # its up-projections as the engine holds them: (E, N, K)
    (1536, 2688, 1856, 64, "nk"), (6144, 2688, 1856, 64, "nk")],
    ids=lambda x: str(x))
def test_grouped_matmul_compiles_for_v5e(one_chip, m, k, n, e, stored):
    """The experts' grouped matmuls (in, out) over the held experts at the
    published widths of both expert configurations: a decode step's
    assignments and a prefill unit's.  Tiles stay whole multiples of 128 or
    one whole width (``_tiling``): never 128 at a width of thousands.  One
    kernel, and the whole weight is copied into another layout first exactly
    where ``SparseMoE.serves_transposed`` says the model's own form would
    be: never in the form the engine holds."""
    from distkeras_tpu.core.layers import SparseMoE
    from distkeras_tpu.ops.experts import _tiling, grouped_matmul
    S = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)
    bf = jnp.bfloat16
    assert min(_tiling(k, n)[1:]) >= 896
    text = jax.jit(functools.partial(
        grouped_matmul, kernel=True, transpose_rhs=stored == "nk")).lower(
        S((m, k), bf), S((e, k, n) if stored == "kn" else (e, n, k), bf),
        S((e,), jnp.int32)).compile().as_text()
    assert text.count(KERNEL) == 1
    relaid = bool(_copies_of(text, (e, k, n), (e, n, k)))
    assert relaid == (stored == "kn" and SparseMoE.serves_transposed(
        (e, k, n)))


# -- the state-space serving cell's programs at its published shapes ---------

def test_ssd_decode_compiles_for_v5e(one_chip):
    """The fused decode step of a Mamba-2 layer over the cell's 256 slots of
    64 x 64 x 128 float32 state: one kernel, the donated state updated in
    place (no copy of it anywhere in the program)."""
    from distkeras_tpu.ops.ssd import kernel_tiles, ssd_decode
    b, h, p, n, g = 256, 64, 64, 128, 8
    assert kernel_tiles((b, h, p, n), jnp.float32)

    def step(x, dt, a, bm, cm, state, live):
        return ssd_decode(x, dt, a, bm, cm, state, live, interpret=False)

    S = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)
    f32 = jnp.float32
    text = jax.jit(step, donate_argnums=(5,)).lower(
        S((b, h, p), f32), S((b, h), f32), S((h,), f32), S((b, g, n), f32),
        S((b, g, n), f32), S((b, h, p, n), f32),
        S((b,), jnp.bool_)).compile().as_text()
    assert text.count(KERNEL) == 1
    assert not _copies_of(text, (b, h, p, n))


@pytest.fixture(scope="module")
def nemotronh(one_chip):
    """``serve-context-nemotron3n``'s model, its parameters AS THE ENGINE
    HOLDS THEM (every layer's ``store_for_serving``) and its paged pool as
    shapes on the described chip (nothing is made)."""
    from benchmarks.lib import manifest as mf, program_nemotronh
    from distkeras_tpu.core import decode as dec
    cfg = mf.load_json(os.path.join(mf.BENCH_DIR, "configs",
                                    "nemotron3-nano-30b-a3b.json"))
    eng = cfg["deployment"]["engine"]
    model = program_nemotronh.build_model(cfg)
    on_chip = lambda a, dt=None: jax.ShapeDtypeStruct(
        a.shape, dt or a.dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: on_chip(a, jnp.bfloat16), jax.eval_shape(
            lambda k: [layer.store_for_serving(p)[0] for layer, p in zip(
                model.layers, model.init(k, (8,)))], jax.random.PRNGKey(0)))
    assert [p["ffn"]["w_in_t"].shape for p in params
            if "ffn" in p] == [(64, 1856, 2688)] * 4
    pool = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: dec.init_paged_arena(model, eng["kv_blocks"],
                                     eng["block_size"],
                                     num_slots=eng["num_slots"])))
    return model, params, pool, eng


@pytest.mark.parametrize("program", ["decode", "stage_1024"])
def test_the_state_space_cells_programs_compile_for_v5e(
        one_chip, nemotronh, monkeypatch, program):
    """The decode step over 256 slots (4 ``ssd_decode``, 1 ``paged_decode``
    at 16 query heads a KV head, 8 grouped matmuls) and a 1,024-token
    prefill unit at a 9,216-position view (the chunked scan in XLA; 6
    grouped matmuls: a stage unit's logits are dead, so the last layer's
    experts are routed and counted but not computed), as ``ServingEngine``
    builds them, at the published
    widths with 64 held experts: they compile, arguments and temporaries
    fit a chip's 16 GB with room, and NO expert weight is copied into
    another layout on its way to the grouped matmul (638 MB a layer in every
    run before PR 35, which was the decode program's 0.70 GB of
    temporaries)."""
    from distkeras_tpu.core import decode as dec
    # the dispatch rules ask the backend (the CPU here); the program is
    # compiled for the chip, so they are given the chip's answer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, params, pool, eng = nemotronh
    page, view, slots = eng["block_size"], eng["max_len"], eng["num_slots"]
    S = lambda shp, dt=jnp.int32: jax.ShapeDtypeStruct(shp, dt,
                                                       sharding=one_chip)
    tables = view // page

    def decode(params, pool, bt, tok, pos, active):
        aux = []
        logits, pool = dec.decode_step(
            model, params, pool, tok, pos,
            paged=dec.PagedView(bt, page, view),
            rows=dec.RowView(live=active), aux=aux)
        return jnp.argmax(logits, -1), pool, sum(aux)

    def stage(params, pool, toks, offset, p_len, row_bt, slot):
        pv = dec.PagedView(row_bt, page, view, floor=offset, ceil=p_len,
                           qcap=p_len - 1)
        aux = []
        _, pool = dec._forward(
            model, params, pool, toks, offset, paged=pv,
            rows=dec.RowView(slots=jnp.reshape(slot, (1,))), aux=aux)
        return pool, sum(aux)

    if program == "decode":
        compiled = jax.jit(decode, donate_argnums=(1,)).lower(
            params, pool, S((slots, tables)), S((slots,)), S((slots,)),
            S((slots,), jnp.bool_)).compile()
        kernels = 13
    else:
        compiled = jax.jit(stage, donate_argnums=(1,)).lower(
            params, pool, S((1, 1024)), S((1,)), S((1,)), S((1, tables)),
            S(())).compile()
        kernels = 6
    text = compiled.as_text()
    assert text.count(KERNEL) == kernels
    for scope in ("ssm/ssm_core", "moe/moe_experts", "attn/attn_core"):
        assert scope in text
    assert ("ssd_decode" if program == "decode" else "ssd_chunk") in text
    assert not _copies_of(text, (64, 2688, 1856), (64, 1856, 2688))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12e9
    if program == "decode":
        assert mem.temp_size_in_bytes < 0.2e9


# -- the tied head of the bursty chat cell reads the table where it lies -------

@pytest.mark.parametrize("program", ["decode", "final_512"])
def test_the_tied_head_copies_no_table_for_v5e(one_chip, monkeypatch,
                                               program):
    """``serve-chat-granite4hm``'s decode step over its 64 slots and a final
    512-token prefill unit (the one prefill program that needs logits), as
    ``ServingEngine`` builds them, at the published widths and the WHOLE
    vocabulary, the first six layers of forty (five Mamba-2, one attention:
    what the table meets does not grow with depth, the compile does): the
    embedding looks rows up in the ``(100352, 2048)`` table and the head
    contracts the same array over its 2,048, and NO copy of it, transposed or
    not, is in the program (411 MB a step if there were); ONE table among
    the arguments; the kernels are there (``ssd_decode`` at one group,
    ``paged_decode`` at 4 query heads a KV head and a score scale of
    1/64)."""
    from benchmarks.lib import manifest as mf, program_granite
    from distkeras_tpu.core import decode as dec
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = mf.load_json(os.path.join(mf.BENCH_DIR, "configs",
                                    "granite-4.0-h-micro.json"))
    eng = cfg["deployment"]["engine"]
    model = program_granite.build_model(dict(cfg, num_hidden_layers=6))
    on_chip = lambda a, dt=None: jax.ShapeDtypeStruct(
        a.shape, dt or a.dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: on_chip(a, jnp.bfloat16),
        jax.eval_shape(lambda k: model.init(k, (8,)), jax.random.PRNGKey(0)))
    v, d = 100352, 2048
    assert [a.shape for a in jax.tree_util.tree_leaves(params)
            if a.shape in ((v, d), (d, v))] == [(v, d)]
    pool = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: dec.init_paged_arena(model, eng["kv_blocks"],
                                     eng["block_size"],
                                     num_slots=eng["num_slots"])))
    page, view, slots = eng["block_size"], eng["max_len"], eng["num_slots"]
    S = lambda shp, dt=jnp.int32: jax.ShapeDtypeStruct(shp, dt,
                                                       sharding=one_chip)
    tables = view // page

    def decode(params, pool, bt, tok, pos, active):
        logits, pool = dec.decode_step(
            model, params, pool, tok, pos,
            paged=dec.PagedView(bt, page, view),
            rows=dec.RowView(live=active))
        return jnp.argmax(logits, -1), pool

    def final(params, pool, toks, offset, p_len, row_bt, slot, last):
        pv = dec.PagedView(row_bt, page, view, floor=offset, ceil=p_len,
                           qcap=p_len - 1)
        logits, pool = dec._forward(
            model, params, pool, toks, offset, paged=pv,
            rows=dec.RowView(slots=jnp.reshape(slot, (1,))))
        return jnp.argmax(logits[0, last]), pool

    if program == "decode":
        compiled = jax.jit(decode, donate_argnums=(1,)).lower(
            params, pool, S((slots, tables)), S((slots,)), S((slots,)),
            S((slots,), jnp.bool_)).compile()
        kernels = 6                        # 5 ssd_decode, 1 paged_decode
    else:
        compiled = jax.jit(final, donate_argnums=(1,)).lower(
            params, pool, S((1, 512)), S((1,)), S((1,)), S((1, tables)),
            S(()), S(())).compile()
        kernels = 0                        # the chunked scan is XLA's
    text = compiled.as_text()
    assert text.count(KERNEL) == kernels
    for scope in ("ssm/ssm_core", "mlp/mlp_in", "mlp/mlp_out",
                  "attn/attn_core", "lm_head"):
        assert scope in text, scope
    assert not _copies_of(text, (v, d), (d, v))
    # nor a transposed table by another road
    assert not [line for line in text.splitlines()
                if " transpose(" in line
                and f"[{d},{v}]" in line.split(" transpose(")[0]]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.0e9


# -- kernels inside shard_map on the 2x2 mesh --------------------------------

@pytest.fixture(scope="module")
def mesh2x2(topo):
    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))


def test_flash_inside_shard_map_compiles_for_2x2(mesh2x2):
    """Batch over 'data', heads over 'model': the kernel's outputs must
    declare their varying mesh axes (ops/_vma.out_struct) or the compiled
    — not interpreted — pallas_call is refused by shard_map's checker."""
    spec = P("data", None, "model", None)
    sh = NamedSharding(mesh2x2, spec)
    qkv = [jax.ShapeDtypeStruct((4, 1024, 12, 64), jnp.bfloat16,
                                sharding=sh)] * 3
    fn = jax.shard_map(functools.partial(_flash_grads, None), mesh=mesh2x2,
                       in_specs=(spec,) * 3, out_specs=(spec,) * 3)
    text = _compiled_text(fn, *qkv)
    assert text.count(KERNEL) == 2 and "flash_bwd" in text


def test_fused_ce_inside_shard_map_compiles_for_2x2(mesh2x2):
    """Tokens over both axes; loss psum'd like ParallelTransformerLM."""
    sh = lambda *s: NamedSharding(mesh2x2, P(*s))
    axes = ("data", "model")

    def local(logits, labels):
        loss, g = jax.value_and_grad(
            lambda lg: _ce(lg, labels).sum())(logits)
        return jax.lax.psum(loss, axes), g

    fn = jax.shard_map(local, mesh=mesh2x2,
                       in_specs=(P(axes, None), P(axes)),
                       out_specs=(P(), P(axes, None)))
    text = _compiled_text(
        fn,
        jax.ShapeDtypeStruct((8192, 50257), jnp.float32,
                             sharding=sh(axes, None)),
        jax.ShapeDtypeStruct((8192,), jnp.int32, sharding=sh(axes)))
    assert text.count(KERNEL) >= 2 and "all-reduce" in text
