"""Hybrid blocks (gated NoPE GQA beside Kimi Delta Attention, sparse experts
held in part) against the benchmark's plain reference
(``benchmarks/lib/reference_solar.py``: float32, the recurrence token by
token, dense per-expert loops; it imports nothing of the program), at the
configuration's ``tiny`` widths with seeded weights, on the CPU.

Tolerances.  Both sides compute in float32 here (``precision.compute`` is set
to float32 for these tests), so what separates them is the ORDER of float32
sums: the chunked solve against the token-by-token recurrence, a grouped
matmul against a dense loop, a paged gather against a full softmax.  Logits
are O(1); 2e-4 absolute is some hundred ulps of room and a thousandth of what
leaving out a term (a decay, the shared expert, one expert's share) moves.
"""

import copy
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import manifest as mf
from benchmarks.lib import program_solar, reference_solar as ref
from benchmarks.lib.counts_solar import dims
from benchmarks.lib.weights_solar import make_weights
from distkeras_tpu import metrics
from distkeras_tpu.core import decode as dec
from distkeras_tpu.core.layers import (GatedAttention, HybridBlock,
                                       KimiDeltaAttention, SparseMoE)
from distkeras_tpu.core.model import FittedModel
from distkeras_tpu.ops import experts as xops
from distkeras_tpu.ops import kda
from distkeras_tpu.serving import ServingEngine

TOL = 2e-4


def tiny_cfg():
    cfg = mf.resolve_sizes(mf.load_json(os.path.join(
        mf.BENCH_DIR, "configs", "solar-open2-250b.json")), True)
    cfg = copy.deepcopy(cfg)
    cfg["precision"]["compute"] = "float32"
    return cfg


@pytest.fixture(scope="module")
def built():
    cfg = tiny_cfg()
    w = make_weights(cfg, 7, "float32")
    return (cfg, dims(cfg), w, program_solar.build_model(cfg),
            program_solar.to_program_layout(w))


def engine_of(built, **kw):
    _, _, _, model, params = built
    opts = dict(num_slots=2, max_len=128, paged=True, block_size=16,
                kv_blocks=40, prefill_chunk=16)
    opts.update(kw)
    return ServingEngine(FittedModel(model, params), **opts)


def prompts(seed, lengths, vocab=512):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


# -- the recurrence: three forms of one arithmetic ------------------------------

def _kda_inputs(b, length, h=4, d=16, seed=0):
    rng = np.random.default_rng(seed)

    def n(*s):
        return jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v = n(b, length, h, d), n(b, length, h, d), n(b, length, h, d)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.exp(n(b, length, h, d))          # decays from 0.99 to 1e-6
    beta = 2 * jax.nn.sigmoid(n(b, length, h) + 1.0)   # most above 1
    return q, k, v, g, beta, n(b, h, d, d) * 0.1


def _recurrence(q, k, v, g, beta, state):
    outs = []
    for t in range(q.shape[1]):
        o, state = kda.kda_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                beta[:, t], state)
        outs.append(o)
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize("length,chunk", [(1, 64), (63, 64), (65, 64),
                                          (150, 64), (150, 16), (37, 200)])
def test_chunked_kda_is_the_recurrence(length, chunk):
    x = _kda_inputs(2, length)
    assert float(jnp.mean(x[4] > 1.0)) > 0.5       # beta above 1: negative
    o_ref, s_ref = _recurrence(*x)                  # eigenvalues are taken
    o, s = kda.kda_chunk(*x, chunk=chunk)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(s, s_ref, atol=2e-5)


def test_a_masked_position_leaves_the_state_alone():
    q, k, v, g, beta, s0 = _kda_inputs(1, 20)
    keep = jnp.arange(20) < 13
    g = jnp.where(keep[None, :, None, None], g, 0.0)
    beta = jnp.where(keep[None, :, None], beta, 0.0)
    _, s = kda.kda_chunk(q, k, v, g, beta, s0, chunk=8)
    _, s13 = kda.kda_chunk(q[:, :13], k[:, :13], v[:, :13], g[:, :13],
                           beta[:, :13], s0, chunk=8)
    np.testing.assert_allclose(s, s13, atol=1e-6)


@pytest.mark.parametrize("live", [(True, False, True), (False,) * 3,
                                  (True,) * 3], ids=["some", "none", "all"])
def test_the_decode_kernel_is_the_step_and_skips_dead_rows(live):
    q, k, v, g, beta, s0 = _kda_inputs(3, 1, h=8, d=128, seed=1)
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    o_ref, s_ref = kda.kda_step(*args, s0)
    live = jnp.asarray(live)
    o, s = kda.kda_decode(*args, s0 + 0, live, interpret=True)
    rows = live[:, None, None]
    np.testing.assert_allclose(o, jnp.where(rows, o_ref, 0.0), atol=1e-6)
    np.testing.assert_allclose(
        s, jnp.where(rows[..., None], s_ref, s0), atol=1e-6)


# -- layers against the reference ---------------------------------------------

def test_the_full_forward_is_the_reference(built):
    cfg, d, w, model, params = built
    toks = prompts(1, [41])[0]
    got = model.apply(params, jnp.asarray(toks)[None])[0]
    np.testing.assert_allclose(got, ref.logits_fn(w, jnp.asarray(toks), d),
                               atol=TOL)


@pytest.mark.parametrize("units", [(50,), (16, 16, 18), (7, 33, 10),
                                   (1, 1, 48)], ids=str)
def test_prefill_in_units_then_decode_is_the_full_forward(built, units):
    """Logits at EVERY position: prompt units of several sizes carry both
    kinds of state on, then single-token steps read and advance them."""
    cfg, d, w, model, params = built
    toks = jnp.asarray(prompts(2, [62])[0])
    want = ref.logits_fn(w, toks, d)
    caches = dec.init_cache(model, 1, 64)
    got, at = [], 0
    for n in units:
        lg, caches = dec._forward(model, params, caches,
                                  toks[None, at:at + n], at)
        got.append(lg[0])
        at += n
    for t in range(at, 62):
        lg, caches = dec.decode_step(model, params, caches, toks[None, t], t)
        got.append(lg)
    np.testing.assert_allclose(jnp.concatenate(got), want, atol=TOL)


def test_the_gated_nope_gqa_layer_through_the_paged_pool(built):
    """One GQA layer alone: a paged prefill of the prompt's first part, a
    second unit, then steps through the block tables == the reference's
    mixer over the whole row."""
    cfg, d, w, model, _ = built
    layer = {k: v for k, v in w["layers"][0].items() if k != "kind"}
    mha = GatedAttention(d["heads"], d["head_dim"], d["kv_heads"])
    p = {k: layer[k] for k in ("wq", "wk", "wv", "wg", "wo")}
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(1, 40, d["hidden"])), jnp.float32)
    want = ref.gqa_mixer(u[0], p, d, ref.f32_matmul)
    page, view = 8, 64
    arena = {n: jnp.zeros((10 * page, d["kv_heads"] * d["head_dim"]),
                          jnp.float32) for n in ("k", "v")}
    tables = jnp.asarray([[3, 1, 4, 7, 2, 0, 5, 6, 9]], jnp.int32)
    got, at = [], 0
    for n in (17, 15, 1, 1, 1, 1, 1, 1, 1, 1):
        pv = dec.PagedView(tables, page, view)
        y, arena = dec._mha_forward(mha, p, u[:, at:at + n], arena,
                                    jnp.asarray([at]), jnp.float32,
                                    paged=pv)
        got.append(y[0])
        at += n
    np.testing.assert_allclose(jnp.concatenate(got), want, atol=TOL)


def test_no_token_is_dropped_when_all_pick_one_expert(built):
    """A router that sends every token to the same experts (the worst skew):
    each assignment is computed, none over any capacity."""
    cfg, d, w, _, _ = built
    layer = w["layers"][1]
    router = jnp.zeros_like(layer["router"]).at[:, :d["top_k"]].set(1.0)
    p = dict(router=router, w_in=layer["w_in"], w_out=layer["w_out"],
             shared_in=layer["shared_in"], shared_out=layer["shared_out"])
    moe = SparseMoE(d["experts"], d["top_k"], d["expert_dim"],
                    held=(0, d["held"]), shared_dim=d["shared_dim"])
    rng = np.random.default_rng(4)
    u = jnp.abs(jnp.asarray(rng.normal(size=(64, d["hidden"])), jnp.float32))
    y, counters = moe.mix(p, u, compute_dtype=jnp.float32)
    np.testing.assert_allclose(y, ref.experts(u, p, d, ref.f32_matmul),
                               atol=TOL)
    # all 64 tokens on each of the first top_k experts, which are held
    assert counters.tolist() == [64 * d["top_k"], d["top_k"], 64]


def test_the_shares_add_up_to_the_uncut_layer(built):
    """THE SHARE TEST.  The layer's result as each of the deployment's
    shares computes it (its own experts' terms and the shared expert), the
    shared expert counted once, adds up to the reference given ALL the
    experts."""
    cfg, d, _, _, _ = built
    e, held = d["experts"], d["held"]
    hidden, f = d["hidden"], d["expert_dim"]
    rng = np.random.default_rng(5)

    def n(*s):
        return jnp.asarray(0.1 * rng.normal(size=s), jnp.float32)
    full = dict(router=n(hidden, e) * 10, w_in=n(e, hidden, 2 * f),
                w_out=n(e, f, hidden), shared_in=n(hidden, 2 * f),
                shared_out=n(f, hidden))
    u = n(33, hidden) * 10
    want = ref.experts(u, full, dict(d, held=e), ref.f32_matmul)
    shared = ref.gated_mlp(u, full["shared_in"], full["shared_out"],
                           ref.f32_matmul)
    total = shared
    for first in range(0, e, held):
        moe = SparseMoE(e, d["top_k"], f, held=(first, held), shared_dim=f)
        part = dict(full, w_in=full["w_in"][first:first + held],
                    w_out=full["w_out"][first:first + held])
        y, _ = moe.mix(part, u, compute_dtype=jnp.float32)
        # and the reference's own share is the same part
        np.testing.assert_allclose(
            y, ref.experts(u, part, d, ref.f32_matmul, first=first),
            atol=TOL)
        total = total + (y - shared)
    np.testing.assert_allclose(total, want, atol=TOL)
    assert float(jnp.abs(want - shared).max()) > 100 * TOL


def test_dispatch_sorts_held_assignments_and_counts_them():
    e = jnp.asarray([[0, 5], [4, 5], [7, 3], [5, 6]], jnp.int32)
    wts = jnp.asarray([[.5, .5], [.4, .6], [.9, .1], [.3, .7]], jnp.float32)
    token, weight, sizes, total = xops.dispatch(
        e, wts, (4, 3), jnp.asarray([True, True, True, False]))
    assert sizes.tolist() == [1, 2, 0] and int(total) == 3
    assert token[:3].tolist() == [1, 0, 1]
    np.testing.assert_allclose(weight[:3], [.4, .5, .6])
    assert float(jnp.abs(weight[3:]).max()) == 0.0


# -- an up-projection in the layout the grouped matmul reads -------------------

@pytest.mark.parametrize("shape,transposed", [
    ((64, 2688, 1856), True),     # Nemotron-3-Nano: 1,856 is 14.5 lane tiles
    ((40, 4096, 2560), False),    # Solar-Open2: row-major as it is
    ((64, 1856, 2688), False),    # the shape of a w_out, which the chip
    ((4, 64, 32), False),         # keeps row-major; the tiny widths of both
    ((4, 64, 64), False),         # configurations
    ((4, 128, 32), True), ((4, 128, 96), True), ((4, 128, 192), True),
    ((4, 128, 256), False), ((2, 256, 128), False)], ids=str)
def test_the_shape_says_whether_an_up_projection_is_served_transposed(
        shape, transposed):
    assert SparseMoE.serves_transposed(shape) is transposed
    e, d, f = shape
    if e > 4:                     # shapes alone: nothing that size is made
        return
    moe = SparseMoE(e, 2, f, expert_form="relu2")
    params, _ = moe.init(jax.random.PRNGKey(0), (3, d))
    assert params["w_in"].shape == shape        # the contract init keeps
    held, n = moe.store_for_serving(params)
    assert n == int(transposed)
    assert ("w_in_t" in held, "w_in" in held) == (transposed, not transposed)
    if not transposed:
        assert held is params
    # the block hands it through, and a layer without experts has nothing
    block = HybridBlock(ffn=moe)
    stored, n = block.store_for_serving({"norm2": {}, "ffn": params})
    assert n == int(transposed) and set(stored["ffn"]) == set(held)
    assert HybridBlock(mixer=GatedAttention(2, 8)).store_for_serving(
        {"mixer": {}}) == ({"mixer": {}}, 0)


@pytest.mark.parametrize("case", ["all_held", "a_strict_share", "token_mask"])
@pytest.mark.parametrize("form", ["gated_silu", "relu2"])
def test_mix_on_stored_parameters_is_mix_on_the_models_own(form, case):
    """The same assignments through ``(E, cols F, D)`` read transposed as
    through ``(E, D, cols F)``: equal sums in another order of storage, and
    storing twice is storing once."""
    experts, d, f = 8, 128, 48
    held = (2, 4) if case == "a_strict_share" else (0, experts)
    moe = SparseMoE(experts, 3, f, held=held, shared_dim=32,
                    expert_form=form)
    params, _ = moe.init(jax.random.PRNGKey(1), (5, d))
    cols = 2 if form == "gated_silu" else 1
    assert params["w_in"].shape == (held[1], d, cols * f)
    stored, n = moe.store_for_serving(params)
    assert n == 1 and "w_in" not in stored
    assert stored["w_in_t"].shape == (held[1], cols * f, d)
    again, n2 = moe.store_for_serving(stored)
    assert n2 == 1 and again is stored
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(2, 21, d)), jnp.float32)
    mask = (jnp.asarray(rng.random((2, 21)) < 0.6)
            if case == "token_mask" else None)
    want, c0 = moe.mix(params, u, compute_dtype=jnp.float32, token_mask=mask)
    got, c1 = moe.mix(stored, u, compute_dtype=jnp.float32, token_mask=mask)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert c0.tolist() == c1.tolist()
    assert 0 < int(c0[0]) < 2 * 21 * 3 or case == "all_held"
    # the layer's own terms are in it: without the experts it reads other
    flat = dict(stored, w_in_t=jnp.zeros_like(stored["w_in_t"]))
    y0, _ = moe.mix(flat, u, compute_dtype=jnp.float32, token_mask=mask)
    assert float(jnp.abs(y0 - want).max()) > 1e-3


def test_the_grouped_matmul_reads_either_form():
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 16, 24)), jnp.float32)
    sizes = jnp.asarray([7, 0, 20], jnp.int32)
    want = xops.grouped_matmul(x, w, sizes)
    got = xops.grouped_matmul(x, jnp.swapaxes(w, 1, 2), sizes,
                              transpose_rhs=True)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(want[:7], x[:7] @ w[0], atol=1e-5)
    assert float(jnp.abs(got[27:]).max()) == 0.0


def test_solars_engine_holds_its_up_projections_as_they_are(built):
    """Tiny or published, Solar's widths are row-major on the chip as they
    are: no leaf of the engine's is another array than the one given."""
    _, _, _, model, params = built
    eng = engine_of(built)
    assert eng.stats["moe_up_projections_transposed"] == 0
    assert jax.tree_util.tree_structure(eng.params) == \
        jax.tree_util.tree_structure(list(params))
    published = program_solar.build_model(mf.load_json(os.path.join(
        mf.BENCH_DIR, "configs", "solar-open2-250b.json")))
    shapes = jax.eval_shape(lambda k: published.init(k, (8,)),
                            jax.random.PRNGKey(0))
    stored = [layer.store_for_serving(p)
              for layer, p in zip(published.layers, shapes)]
    assert sum(n for _, n in stored) == 0
    assert {p["ffn"]["w_in"].shape for p, _ in stored
            if "ffn" in p} == {(40, 4096, 2560)}


# -- the engine: two kinds of state in one manager ---------------------------

def served_gaps(built, prompt, tokens):
    cfg, d, w, _, _ = built
    toks = np.asarray(tokens, np.int32)
    gaps, _ = ref.served_position_scores(
        w, prompt, toks, [toks], d, ref.pad_length(len(prompt) + len(toks),
                                                   16))
    return gaps[0]


def test_the_engine_serves_what_the_reference_computes(built):
    """Bucketed and chunked prefill, paged decode, five requests through two
    slots in turn: at every served position the served token's REFERENCE
    logit lies within TOL of the reference's best (``serve_hybrid``'s
    check; a token that is not the reference's first is a near-tie)."""
    eng = engine_of(built)
    eng.warmup()
    ps = prompts(6, [5, 40, 17, 33, 9])
    hs = [eng.submit(p, 14) for p in ps]
    eng.run_until_idle()
    assert max(eng.stats["slot_requests"]) >= 2
    assert eng.stats["prefill_chunks"] > 0 and eng.stats["prefill_batches"] > 0
    for p, h in zip(ps, hs):
        assert h.finish == "length" and len(h.tokens) == 14
        assert float(served_gaps(built, p, h.tokens).max()) <= TOL
    st = eng.stats
    assert st["recurrent_slots_cleared"] == 5 and st["prefix_hit_tokens"] == 0
    assert st["moe_layer_steps"] == 4 * st["decode_steps"]
    assert 0 < st["moe_experts_touched"] <= st["moe_assignments_held"]
    assert st["moe_load_max"] <= st["moe_assignments_held"]
    assert st["d2h_transfers"] == st["decode_steps"] + st["prefills"]


@pytest.mark.parametrize("first", [9, 40], ids=["bucket", "chunked"])
def test_a_reused_slot_starts_from_zero_state(built, first):
    a, b = prompts(8, [first, 21])
    alone = engine_of(built, num_slots=1)
    want = alone.submit(b, 10)
    alone.run_until_idle()
    eng = engine_of(built, num_slots=1)
    ha, hb = eng.submit(a, 10), eng.submit(b, 10)
    eng.run_until_idle()
    assert eng.stats["slot_requests"] == [2]
    assert list(hb.tokens) == list(want.tokens)


def test_identical_prompts_share_nothing_and_answer_alike(built):
    p = prompts(9, [48])[0]
    eng = engine_of(built)
    h1, h2 = eng.submit(p, 8), eng.submit(p.copy(), 8)
    eng.run_until_idle()
    h3 = eng.submit(p.copy(), 8)          # after both are cached in a trie
    eng.run_until_idle()                  # that shares: none here
    assert list(h1.tokens) == list(h2.tokens) == list(h3.tokens)
    assert eng.stats["prefix_hits"] == 0
    assert eng.stats["prefix_hit_tokens"] == 0
    assert eng.stats["prefill_tokens"] == 3 * 48


def test_the_engine_matches_offline_generate(built):
    _, _, _, model, params = built
    p = prompts(10, [37])[0]
    eng = engine_of(built)
    h = eng.submit(p, 12)
    eng.run_until_idle()
    want = np.asarray(FittedModel(model, params).generate(p[None], 12))
    assert list(h.tokens) == want[0, 37:].tolist()


@pytest.mark.parametrize("kw,word", [
    (dict(role="prefill"), "block transfer"),
    (dict(rolling=True), "roll"),
    (dict(spec_draft="self"), "snapshot"),
    (dict(kv_dtype="int8"), "float32"),
    (dict(paged=False), "paged=True"),
    (dict(quantize="int8"), "quantiser"),
], ids=lambda x: next(iter(x)) if isinstance(x, dict) else x)
def test_what_a_recurrent_model_cannot_have_is_refused_by_name(built, kw,
                                                                word):
    _, _, _, model, params = built
    if kw.get("spec_draft") == "self":
        kw = dict(spec_draft=(model, params))
    with pytest.raises(ValueError, match=word):
        engine_of(built, **kw)
    assert not engine_of(built)._can_preempt


def test_check_supported_names_the_kinds_it_accepts():
    from distkeras_tpu.core.layers import Conv2D
    from distkeras_tpu.core.model import Sequential
    with pytest.raises(ValueError, match="HybridBlock.*KimiDeltaAttention"):
        dec.init_cache(Sequential([Conv2D(4)], input_shape=(8, 8, 1)), 1, 8)


@pytest.mark.parametrize("key,value,word", [
    ("use_rope", True, "rotary"),
    ("use_gqa_gate", False, "gated attention"),
    ("first_k_dense_replace", 1, "dense feed-forward"),
])
def test_the_builder_refuses_what_it_does_not_build(built, key, value, word):
    from distkeras_tpu.models import hybrid_lm
    with pytest.raises(ValueError, match=word):
        hybrid_lm(dict(built[0], **{key: value}))


def test_a_block_says_what_the_cached_step_and_the_engine_ask(built):
    """``_forward`` and ``ServingEngine`` ask the block, never its class:
    what state it keeps, whether it routes tokens (counters, a token mask)
    and whether the int8 weight quantiser knows its names."""
    from distkeras_tpu.core.layers import TransformerBlock
    gqa, kda_block = built[3].layers[1], built[3].layers[2]
    assert (gqa.state_kind, kda_block.state_kind) == ("kv", "recurrent")
    for block in (gqa, kda_block):
        assert block.routes_tokens and block.wants_token_mask
        assert not block.int8_weights
    plain = TransformerBlock(2, 8, 32)
    assert plain.state_kind == "kv" and plain.int8_weights
    assert not plain.routes_tokens and not plain.wants_token_mask
    assert engine_of(built)._moe_layers == 4


def test_the_decode_step_goes_to_both_kernels_on_a_tpu(built, monkeypatch):
    """Steered as ``tests/test_paged_attention.py`` steers the paged kernel:
    with a TPU underneath the recurrent layers' single-token step is the
    fused kernel where its state tiles (here it does not: 16 x 16 heads),
    and ``paged_kernel_applies`` asks the attention layers only."""
    _, _, _, model, _ = built
    caches = dec.init_paged_arena(model, 8, 16, num_slots=2)
    kinds = [c and sorted(c) for c in caches]
    assert kinds == [None, ["k", "v"], ["S", "conv"], ["S", "conv"],
                     ["S", "conv"], None, None]
    monkeypatch.setattr(dec, "_on_tpu", lambda: True)
    wide = HybridBlock(KimiDeltaAttention(8, 128), SparseMoE(8, 2, 16))
    assert kda.kernel_tiles(wide.mixer().init_state(4, jnp.float32)[
        "S"].shape, jnp.float32)
    assert not kda.kernel_tiles(caches[2]["S"].shape, jnp.float32)


def test_the_block_round_trips_through_its_config(built):
    _, _, _, model, params = built
    from distkeras_tpu.core.model import Sequential
    again = Sequential.from_json(model.to_json())
    toks = jnp.asarray(prompts(11, [12])[0])[None]
    assert [l.kind for l in again.layers] == [l.kind for l in model.layers]
    np.testing.assert_array_equal(again.apply(params, toks),
                                  model.apply(params, toks))


# -- tracing: scopes, counters, the dispatch span's field ----------------------

HYBRID_SCOPES = ("block_0", "block_3", "attn", "attn_core", "attn_gate",
                 "kv_write", "kda", "kda_conv", "kda_core", "kda_gate_out",
                 "moe", "moe_route", "moe_dispatch", "moe_experts",
                 "moe_shared", "moe_combine", "final_norm", "lm_head",
                 "sample")


@pytest.fixture(scope="module")
def decode_program_text(built):
    eng = engine_of(built)
    args = (eng.params,) + eng._state_args()
    return eng._decode_fn.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("scope", HYBRID_SCOPES)
def test_the_decode_program_names_the_scope(decode_program_text, scope):
    assert f"/{scope}" in decode_program_text or \
        f"{scope}/" in decode_program_text


def test_the_new_kernel_names_are_the_package_s():
    # tests/test_tracing.py lowers every name of KERNEL_NAMES for a TPU
    assert {"kda_decode"} <= set(metrics.KERNEL_NAMES)


def test_a_traced_run_has_the_state_field_and_the_counters(built, tmp_path):
    from tests.test_tracing import read_spans
    eng = engine_of(built)
    eng.warmup()
    with metrics.trace(str(tmp_path)):
        hs = [eng.submit(p, 6) for p in prompts(12, [5, 40])]
        eng.run_until_idle()
    assert all(h.finish == "length" for h in hs)
    sent = [s for s in read_spans(str(tmp_path))
            if s.name == "serve.decode_dispatch"]
    assert sent and {s.fields["state"] for s in sent} == {"kv+recurrent"}
    assert eng.stats["moe_layer_steps"] > 0
    assert glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))
