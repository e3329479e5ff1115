"""A stack whose every block is a mixer (a Mamba-2 state-space mixer at ONE
group, or NoPE grouped-query attention whose scores carry a constant) THEN a
dense gated-SiLU MLP, with a constant on the embedding, on each residual
branch and under the logits, and a head that IS the embedding table: against
the benchmark's plain reference (``benchmarks/lib/reference_granite.py``:
float32, the recurrence token by token; it imports nothing of the program),
at the configuration's ``tiny`` widths with seeded weights, on the CPU.

Tolerances.  Both sides compute in float32 here, so what separates them is
the ORDER of float32 sums (the chunked scan against the token-by-token
recurrence, a paged gather against a full softmax).  The logits are of order
0.1 (a table drawn at 0.057 under a divisor of 8); TOL = 2e-5 absolute is some
hundred ulps of room.  Every constant set to its neutral value, and the tie
undone, moves the logits by 0.01 to 1: each is required to move them by more
than 100 x TOL, so the comparison that passes could not pass without it.
"""

import copy
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import manifest as mf
from benchmarks.lib import program_granite, reference_granite as ref
from benchmarks.lib.counts_granite import dims, total_params
from benchmarks.lib.weights_granite import make_weights
from distkeras_tpu import metrics
from distkeras_tpu.core import decode as dec
from distkeras_tpu.core import layers as L
from distkeras_tpu.core.layers import (Dense, GatedMLP, HybridBlock,
                                       Mamba2Mixer, MultiHeadAttention,
                                       TiedHead)
from distkeras_tpu.core.model import (FittedModel, Sequential,
                                      deserialize_model, serialize_model)
from distkeras_tpu.models import hybrid_lm
from distkeras_tpu.ops import ssd
from distkeras_tpu.serving import ServingEngine

TOL = 2e-5
CONFIG = os.path.join(mf.BENCH_DIR, "configs", "granite-4.0-h-micro.json")


def tiny_cfg(**over):
    cfg = copy.deepcopy(mf.resolve_sizes(mf.load_json(CONFIG), True))
    cfg["precision"]["compute"] = "float32"
    cfg.update(over)
    return cfg


def params_of(cfg, seed=7):
    cfg = dict(cfg, precision=dict(cfg["precision"], params="float32"))
    return program_granite.program_params(cfg, seed)


@pytest.fixture(scope="module")
def built():
    cfg = tiny_cfg()
    return (cfg, dims(cfg), make_weights(cfg, 7, "float32"),
            program_granite.build_model(cfg), params_of(cfg))


def engine_of(built, **kw):
    _, _, _, model, params = built
    opts = dict(num_slots=2, max_len=128, paged=True, block_size=16,
                kv_blocks=40, prefill_chunk=16)
    opts.update(kw)
    return ServingEngine(FittedModel(model, params), **opts)


def prompts(seed, lengths, vocab=512):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


# -- (a) the whole model ------------------------------------------------------

def test_the_full_forward_is_the_reference(built):
    cfg, d, w, model, params = built
    toks = prompts(1, [41])[0]
    got = model.apply(params, jnp.asarray(toks)[None])[0]
    want = ref.logits_fn(w, jnp.asarray(toks), d)
    assert 0.05 < float(jnp.std(want)) < 1.0
    np.testing.assert_allclose(got, want, atol=TOL)


# -- (b) units, then steps, through the cache and the engine ------------------

@pytest.mark.parametrize("units", [(50,), (16, 16, 18), (7, 33, 10)], ids=str)
def test_prefill_in_units_then_decode_is_the_full_forward(built, units):
    """Logits at EVERY position: prompt units of several sizes carry the
    state-space state and the keys on, then single-token steps read and
    advance them, through the tied head."""
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


def test_paged_rows_at_different_positions_are_the_full_forward(built):
    """The serving pool's own form: a paged arena, per-slot recurrent state,
    two rows prefilled in two and three units through their block tables,
    then decode steps with the rows at DIFFERENT positions: the logits of
    every step against the reference's full forward of each row."""
    cfg, d, w, model, params = built
    page, view = 16, 64
    rows = [jnp.asarray(p) for p in prompts(3, [44, 57])]
    want = [ref.logits_fn(w, r, d) for r in rows]
    pool = dec.init_paged_arena(model, 8, page, num_slots=2)
    tables = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    starts = (30, 41)                      # prompt lengths; the rest decoded
    for slot, (row, p_len) in enumerate(zip(rows, starts)):
        at = 0
        for n in ((16, 14) if slot == 0 else (16, 16, 9)):
            pv = dec.PagedView(tables[slot:slot + 1], page, view,
                               floor=jnp.asarray([at]),
                               ceil=jnp.asarray([p_len]),
                               qcap=jnp.asarray([p_len - 1]))
            lg, pool = dec._forward(
                model, params, pool, row[None, at:at + n],
                jnp.asarray([at]), paged=pv,
                rows=dec.RowView(slots=jnp.asarray([slot])))
            np.testing.assert_allclose(lg[0], want[slot][at:at + n],
                                       atol=TOL)
            at += n
    pos = jnp.asarray(starts)
    for step in range(14):
        tok = jnp.stack([r[p] for r, p in zip(rows, np.asarray(pos))])
        lg, pool = dec.decode_step(
            model, params, pool, tok, pos,
            paged=dec.PagedView(tables, page, view),
            rows=dec.RowView(live=jnp.ones((2,), bool)))
        for slot in range(2):
            np.testing.assert_allclose(lg[slot], want[slot][int(pos[slot])],
                                       atol=TOL)
        pos = pos + 1


def served_gaps(built, prompt, tokens):
    cfg, d, w, _, _ = built
    toks = np.asarray(tokens, np.int32)
    gaps, _ = ref.served_position_scores(
        w, prompt, toks, [toks], d, ref.pad_length(len(prompt) + len(toks),
                                                   16))
    return gaps[0]


def test_the_engine_serves_what_the_reference_computes(built):
    """Bucketed and chunked prefill (up to three units a prompt), paged
    decode, five requests through two slots in turn: at every served
    position the served token's REFERENCE logit lies within TOL of the
    reference's best; the state's bytes are counted a live row a step."""
    cfg, d, _, _, _ = built
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
        # a tied head meets the token's own embedding again: it does not
        # decide the next token at these weights
        assert len(set(h.tokens)) > 7
    st = eng.stats
    assert st["recurrent_slots_cleared"] == 5 and st["moe_layer_steps"] == 0
    row = sum(d["kinds"].count("mamba") * n for n in (
        d["m_heads"] * d["m_dim"] * d["state"] * 4,
        (d["conv"] - 1) * d["conv_dim"] * 4))
    assert eng._recurrent_row_bytes == row
    assert st["recurrent_state_bytes_moved"] == (
        2 * row * st["active_slot_steps"])


def test_a_model_without_recurrent_layers_moves_no_state():
    from distkeras_tpu.models import transformer_lm
    model = transformer_lm(vocab_size=64, seq_len=32, d_model=32,
                           num_heads=2, num_layers=1, mlp_dim=64,
                           compute_dtype="float32")
    eng = ServingEngine(FittedModel(model, model.init(
        jax.random.PRNGKey(0))), num_slots=2, max_len=32)
    eng.submit(prompts(0, [5], 64)[0], 4)
    eng.run_until_idle()
    assert eng._recurrent_row_bytes == 0
    assert eng.stats["recurrent_state_bytes_moved"] == 0


# -- (c) each constant, and the tie, alone ------------------------------------

NEUTRAL = {"embedding_multiplier": 1, "residual_multiplier": 1.0,
           "attention_multiplier": 0.25,       # head_dim ** -0.5 at 16
           "logits_scaling": 1}


@pytest.mark.parametrize("key", sorted(NEUTRAL))
def test_each_constant_is_held_by_the_comparison(built, key):
    """The program with ONE constant at its neutral value (the attention
    multiplier at ``head_dim ** -0.5``, what the layer does unasked) against
    the reference with all four: the comparison of (a) FAILS, full forward
    and cached steps alike."""
    cfg, d, w, _, params = built
    assert d["head_dim"] ** -0.5 == NEUTRAL["attention_multiplier"]
    model = program_granite.build_model(tiny_cfg(**{key: NEUTRAL[key]}))
    toks = jnp.asarray(prompts(1, [41])[0])
    want = ref.logits_fn(w, toks, d)
    got = model.apply(params, toks[None])[0]
    assert float(jnp.abs(got - want).max()) > 100 * TOL
    caches = dec.init_cache(model, 1, 48)
    lg, caches = dec._forward(model, params, caches, toks[None, :30], 0)
    assert float(jnp.abs(lg[0] - want[:30]).max()) > 100 * TOL
    lg, _ = dec.decode_step(model, params, caches, toks[None, 30], 30)
    assert float(jnp.abs(lg[0] - want[30]).max()) > 100 * TOL


def test_the_tie_is_held_by_the_comparison(built):
    """An untied head with a kernel of its own (drawn as the table is) in the
    tied one's place: the comparison FAILS; given the table's transpose it
    passes again, which is all the tie is."""
    cfg, d, w, tied, params = built
    model = program_granite.build_model(
        tiny_cfg(tie_word_embeddings=False, logits_scaling=1))
    assert isinstance(model.layers[-1], Dense)
    assert isinstance(tied.layers[-1], TiedHead)
    toks = jnp.asarray(prompts(1, [41])[0])
    want = ref.logits_fn(w, toks, d)
    other = make_weights(cfg, 8, "float32")["embed"]
    own = params[:-1] + [{"kernel": other.T}]
    got = model.apply(own, toks[None])[0] / d["logits_div"]
    assert float(jnp.abs(got - want).max()) > 100 * TOL
    same = params[:-1] + [{"kernel": w["embed"].T}]
    np.testing.assert_allclose(
        model.apply(same, toks[None])[0] / d["logits_div"], want, atol=TOL)
    with pytest.raises(ValueError, match="logits_scaling"):
        program_granite.build_model(tiny_cfg(tie_word_embeddings=False))


# -- one table ----------------------------------------------------------------

def test_the_engine_and_the_weights_hold_one_table(built):
    cfg, d, w, model, params = built
    eng = engine_of(built)
    tables = [leaf for leaf in jax.tree_util.tree_leaves(eng.params)
              if leaf.shape in ((d["vocab"], d["hidden"]),
                                (d["hidden"], d["vocab"]))]
    assert len(tables) == 1 and eng.params[-1] == {}
    fitted = FittedModel(model, params)
    flat = fitted.get_weights()
    assert sum(a.shape == (d["vocab"], d["hidden"]) for a in flat) == 1
    assert sum(a.size for a in flat) == total_params(cfg)
    # set_weights round-trip: every array doubled moves the table ONCE, and
    # the head follows it (x E^T / 8 with the new E)
    fitted.set_weights([2 * a for a in flat])
    np.testing.assert_array_equal(fitted.params[0]["embedding"],
                                  2 * np.asarray(params[0]["embedding"]))
    back = FittedModel(model, params)
    back.set_weights(flat)
    toks = jnp.asarray(prompts(4, [12])[0])[None]
    np.testing.assert_array_equal(model.apply(back.params, toks),
                                  model.apply(params, toks))
    # a checkpoint's blob keeps the spec (tied_to, divisor) and one table
    m2, p2 = deserialize_model(serialize_model(model, params))
    assert m2.layers[-1].tied_to == 0 and m2.layers[-1].divisor == 8.0
    assert p2[-1] == {}
    np.testing.assert_array_equal(m2.apply(p2, toks),
                                  model.apply(params, toks))


def test_a_tie_that_points_at_no_embedding_is_refused(built):
    """``TiedHead.tied_to`` is an index into the stack: walked as a slice
    (or built by hand around another layer) it would read another layer's
    parameters; both walkers ask ``params_of``, which refuses by name."""
    cfg, d, w, model, params = built
    toks = jnp.asarray(prompts(4, [12])[0])[None]
    part = Sequential(model.layers[1:], compute_dtype=model.compute_dtype)
    with pytest.raises(ValueError, match="TiedHead.*not an Embedding"):
        part.apply(params[1:], jnp.zeros((1, 12, d["hidden"]), jnp.float32))
    head = model.layers[-1]
    far = Sequential(model.layers[:-1] + [TiedHead(head.units, tied_to=99)],
                     compute_dtype=model.compute_dtype)
    with pytest.raises(ValueError, match="tied to layer 99"):
        far.apply(params, toks)
    assert L.params_of(model.layers, params, len(params) - 1) is params[0]
    assert L.params_of(model.layers, params, 1) is params[1]


# -- the recurrence at this configuration's shape -----------------------------

def test_chunked_ssd_at_chunk_256_and_one_group_is_the_recurrence():
    """``mamba_chunk_size`` 256 and ``mamba_n_groups`` 1 (ONE ``B``, ``C``
    for all heads): 600 tokens in chunks of 256 against the step, token by
    token; relative to the largest output, as ``tests/test_nemotronh.py``
    holds the other shapes."""
    rng = np.random.default_rng(0)
    b, length, h, p, g, n = 1, 600, 8, 16, 1, 128

    def nrm(*s):
        return jnp.asarray(rng.normal(size=s), jnp.float32)
    x, bm, cm, s0 = (nrm(b, length, h, p), nrm(b, length, g, n),
                     nrm(b, length, g, n), nrm(b, h, p, n))
    dt = jax.nn.softplus(nrm(b, length, h))
    a = -jnp.exp(jnp.asarray(rng.uniform(-1, 2.5, h), jnp.float32))
    state, outs = s0, []
    for t in range(length):
        y, state = ssd.ssd_step(x[:, t], dt[:, t], a, bm[:, t], cm[:, t],
                                state)
        outs.append(y)
    want = jnp.stack(outs, 1)
    got, s = ssd.ssd_chunk(x, dt, a, bm, cm, s0, chunk=256)
    for g_, w_ in ((got, want), (s, state)):
        np.testing.assert_allclose(g_, w_,
                                   atol=1e-4 * float(jnp.abs(w_).max()))
    assert ssd.kernel_tiles((64, 64, 64, 128), jnp.float32)   # the cell's


# -- tracing ------------------------------------------------------------------

def test_the_programs_name_the_mlp_and_the_tied_heads_scopes(built):
    _, _, _, model, params = built
    caches = dec.init_cache(model, 1, 32)
    toks = jnp.zeros((1, 20), jnp.int32)
    unit = jax.jit(lambda c, t: dec._forward(model, params, c, t, 0)).lower(
        caches, toks).as_text(debug_info=True)
    step = jax.jit(lambda c, t: dec.decode_step(
        model, params, c, t, 20)).lower(caches, toks[:, 0]).as_text(
            debug_info=True)
    full = jax.jit(lambda t: model.apply(params, t)).lower(toks).as_text(
        debug_info=True)
    for text in (unit, step, full):
        for scope in ("ssm/ssm_core", "attn/attn_core", "mlp/mlp_in",
                      "mlp/mlp_out", "lm_head", "final_norm", "embed"):
            assert scope in text, scope
        assert "moe" not in text
    eng = engine_of(built)
    assert eng._state_kinds == "kv+recurrent"


# -- the builder --------------------------------------------------------------

def test_hybrid_lm_builds_the_catalog_rows_config_unchanged():
    """Every key of the published config as it reads (none dropped or
    renamed): 40 blocks, 36 Mamba-2 mixers at ONE group and chunks of 256
    beside 4 attention layers at 5, 15, 25, 35 whose scores are multiplied
    by 1/64, a gated MLP of 8,192 in each, 0.22 on every branch, 12 on the
    embedding, a tied head that divides by 8; 3,191 M parameters, counted
    from shapes alone (nothing is made)."""
    cfg = mf.load_json(CONFIG)
    model = hybrid_lm(cfg)
    blocks = model.layers[1:-2]
    assert len(blocks) == 40 and all(isinstance(b, HybridBlock)
                                     for b in blocks)
    attn = [i for i, b in enumerate(blocks)
            if isinstance(b.mixer(), MultiHeadAttention)]
    assert attn == [5, 15, 25, 35]
    a, m = blocks[5].mixer(), blocks[0].mixer()
    assert (a.num_heads, a.num_kv_heads, a.key_dim, a.score_scale, a.rope,
            a.use_bias) == (32, 8, 64, 1 / 64, False, False)
    assert isinstance(m, Mamba2Mixer) and (
        m.num_heads, m.head_dim, m.state_size, m.num_groups, m.conv_size,
        m.chunk_size) == (64, 64, 128, 1, 4, 256)
    for b in blocks:
        mlp = b.ffn()
        assert isinstance(mlp, GatedMLP) and mlp.mlp_dim == 8192
        assert not b.routes_tokens and b.residual_multiplier == 0.22
        assert b.wants_token_mask == (b.state_kind == "recurrent")
    assert model.layers[0].output_scale == 12.0
    head = model.layers[-1]
    assert isinstance(head, TiedHead) and (head.tied_to, head.divisor,
                                           head.units) == (0, 8.0, 100352)
    shapes = jax.eval_shape(lambda k: model.init(k, (8,)),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert n == total_params(cfg) and round(n / 1e6) == 3191
    # the spec survives JSON (class-level defaults for the older specs)
    again = type(model).from_json(model.to_json())
    assert again.layers[-1].divisor == 8.0
    assert again.layers[6].mixer().score_scale == 1 / 64


@pytest.mark.parametrize("change,word", [
    (dict(num_local_experts=8), "num_local_experts"),
    (dict(position_embedding_type="rope"), "position_embedding_type"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias"),
    (dict(mamba_conv_bias=False), "mamba_conv_bias"),
    (dict(normalization_function="layernorm"), "normalization_function"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(num_hidden_layers=9), "layer_types names 8"),
    (dict(layer_types=["mamba", "moe"], num_hidden_layers=2), "'moe'"),
    (dict(mamba_expand=3), "mamba_expand"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_the_typed_builder_does_not_build_it_refuses_by_name(change,
                                                                  word):
    with pytest.raises(ValueError, match=word):
        hybrid_lm(tiny_cfg(**change))


# -- (d) the other two hybrid models are the programs they were ---------------

#: sha256 (first 16 hex digits) of the float32 logits of the two accepted
#: hybrid configurations' ``tiny`` models at seeded weights (seed 7), made
#: at the PARENT commit of the PR that added this file (c88e416) by the
#: recipe of ``_logits`` below: (full forward, 16-token unit then 8 cached
#: steps) by compute type
PARENT = {
    ("nemotronh", "float32"): ("dfc6fc235a8fe30e", "731f60fe0f6f78db"),
    ("nemotronh", "bfloat16"): ("a6e95f6a7681d7ce", "83171dccc913c438"),
    ("solar", "float32"): ("b4f582fdba727109", "74ff7e9f9840763e"),
    ("solar", "bfloat16"): ("dc3e49e0fc4ba2c0", "5d367ccadfb16d74"),
}
FILES = {"nemotronh": "nemotron3-nano-30b-a3b.json",
         "solar": "solar-open2-250b.json"}


def _logits(name, compute):
    import importlib
    prog = importlib.import_module(f"benchmarks.lib.program_{name}")
    wts = importlib.import_module(f"benchmarks.lib.weights_{name}")
    cfg = copy.deepcopy(mf.resolve_sizes(mf.load_json(os.path.join(
        mf.BENCH_DIR, "configs", FILES[name])), True))
    cfg["precision"]["compute"] = compute
    model = prog.build_model(cfg)
    params = prog.to_program_layout(wts.make_weights(cfg, 7, "float32"))
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, 512, (2, 24)).astype(np.int32))
    full = np.asarray(model.apply(params, toks))
    caches = dec.init_cache(model, 2, 32)
    lg, caches = dec._forward(model, params, caches, toks[:, :16], 0)
    steps = [np.asarray(lg)]
    for t in range(16, 24):
        lg, caches = dec.decode_step(model, params, caches, toks[:, t], t)
        steps.append(np.asarray(lg)[:, None])
    return full, np.concatenate(steps, 1)


@pytest.mark.parametrize("name,compute", sorted(PARENT))
def test_the_accepted_hybrid_models_give_the_parents_logits_bit_for_bit(
        name, compute):
    """Every default of this PR (no score scale, an embedding and residual
    constant of 1, an untied head) IS the parent's code path: same bits."""
    got = tuple(hashlib.sha256(a.tobytes()).hexdigest()[:16]
                for a in _logits(name, compute))
    assert got == PARENT[(name, compute)]
