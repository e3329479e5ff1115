"""A stack whose layers are ONE part each (Mamba-2 state-space mixers,
attention at 16 query heads a KV head, sparse experts with a sigmoid router,
a selection bias and ungated relu^2 experts) against the benchmark's plain
reference (``benchmarks/lib/reference_nemotronh.py``: float32, the recurrence
token by token, dense per-expert loops; it imports nothing of the program),
at the configuration's ``tiny`` widths with seeded weights, on the CPU.

Tolerances.  Both sides compute in float32 here (``precision.compute`` is set
to float32 for these tests), so what separates them is the ORDER of float32
sums: the chunked scan against the token-by-token recurrence, a grouped
matmul against a dense loop, a paged gather against a full softmax.  Logits
are O(1); 2e-4 absolute is some hundred ulps of room and a thousandth of what
leaving out a term (a decay, the skip, the shared expert, one expert's share,
the selection bias) moves.  A part alone, whose outputs at these widths are
of order 0.01 to 0.1, and the recurrence alone, whose outputs on the tests'
strong inputs are of order a hundred, are held RELATIVE to their largest
output (``close``): 1e-4 of it.  The chunked form reads every decay as ``exp``
of a difference of cumulative log-decays, which reach a few hundred inside a
chunk on these inputs (the model's own stay under 60): float32 rounds such a
sum to 3e-5, and so the decay.
"""

import copy
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import manifest as mf
from benchmarks.lib import program_nemotronh, reference_nemotronh as ref
from benchmarks.lib.counts_nemotronh import dims
from benchmarks.lib.weights_nemotronh import make_weights
from distkeras_tpu import metrics
from distkeras_tpu.core import decode as dec
from distkeras_tpu.core.layers import (HybridBlock, Mamba2Mixer,
                                       MultiHeadAttention, SparseMoE)
from distkeras_tpu.core.model import FittedModel, serialize_model
from distkeras_tpu.models import hybrid_lm
from distkeras_tpu.ops import experts as xops
from distkeras_tpu.ops import ssd
from distkeras_tpu.serving import ServingEngine

TOL = 2e-4


def close(got, want, rel=1e-4):
    np.testing.assert_allclose(got, want,
                               atol=rel * float(jnp.abs(want).max()))


CONFIG = os.path.join(mf.BENCH_DIR, "configs", "nemotron3-nano-30b-a3b.json")


def tiny_cfg():
    cfg = copy.deepcopy(mf.resolve_sizes(mf.load_json(CONFIG), True))
    cfg["precision"]["compute"] = "float32"
    return cfg


@pytest.fixture(scope="module")
def built():
    cfg = tiny_cfg()
    w = make_weights(cfg, 7, "float32")
    return (cfg, dims(cfg), w, program_nemotronh.build_model(cfg),
            program_nemotronh.to_program_layout(w))


def engine_of(built, **kw):
    _, _, _, model, params = built
    opts = dict(num_slots=2, max_len=128, paged=True, block_size=16,
                kv_blocks=40, prefill_chunk=16)
    opts.update(kw)
    return ServingEngine(FittedModel(model, params), **opts)


def prompts(seed, lengths, vocab=512):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


def layer_of(built, kind):
    _, d, w, _, _ = built
    layer = next(x for x in w["layers"] if x["kind"] == kind)
    return {k: v for k, v in layer.items() if k != "kind"}


# -- the recurrence: three forms of one arithmetic ------------------------------

def _ssd_inputs(b, length, h=8, p=8, g=2, n=128, seed=0):
    rng = np.random.default_rng(seed)

    def nrm(*s):
        return jnp.asarray(rng.normal(size=s), jnp.float32)
    dt = jax.nn.softplus(nrm(b, length, h))
    a = -jnp.exp(jnp.asarray(rng.uniform(-1, 2.5, h), jnp.float32))
    return (nrm(b, length, h, p), dt, a, nrm(b, length, g, n),
            nrm(b, length, g, n), nrm(b, h, p, n))


def _recurrence(x, dt, a, b, c, state):
    outs = []
    for t in range(x.shape[1]):
        y, state = ssd.ssd_step(x[:, t], dt[:, t], a, b[:, t], c[:, t],
                                state)
        outs.append(y)
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize("length,chunk", [(1, 128), (127, 128), (129, 128),
                                          (300, 128), (150, 16), (37, 200)])
def test_chunked_ssd_is_the_recurrence(length, chunk):
    x = _ssd_inputs(2, length)
    if length >= 127:   # strong decays among them: they must not overflow
        assert float(jnp.exp(x[1] * x[2]).min()) < 1e-6
    y_ref, s_ref = _recurrence(*x)
    y, s = ssd.ssd_chunk(*x, chunk=chunk)
    close(y, y_ref)
    close(s, s_ref)


@pytest.mark.parametrize("cut", [1, 100, 137, 256])
def test_the_state_is_carried_from_unit_to_unit(cut):
    x, dt, a, b, c, s0 = _ssd_inputs(1, 300, seed=1)
    y_ref, s_ref = _recurrence(x, dt, a, b, c, s0)
    y1, s1 = ssd.ssd_chunk(x[:, :cut], dt[:, :cut], a, b[:, :cut],
                           c[:, :cut], s0)
    y2, s2 = ssd.ssd_chunk(x[:, cut:], dt[:, cut:], a, b[:, cut:],
                           c[:, cut:], s1)
    close(jnp.concatenate([y1, y2], 1), y_ref)
    close(s2, s_ref)


def test_a_masked_position_leaves_the_ssd_state_alone():
    x, dt, a, b, c, s0 = _ssd_inputs(1, 20)
    dt = jnp.where((jnp.arange(20) < 13)[None, :, None], dt, 0.0)
    _, s = ssd.ssd_chunk(x, dt, a, b, c, s0, chunk=8)
    _, s13 = ssd.ssd_chunk(x[:, :13], dt[:, :13], a, b[:, :13], c[:, :13],
                           s0, chunk=8)
    np.testing.assert_allclose(s, s13, atol=1e-6)


@pytest.mark.parametrize("live", [(True, False, True), (False,) * 3,
                                  (True,) * 3], ids=["some", "none", "all"])
def test_the_ssd_decode_kernel_is_the_step_and_skips_dead_rows(live):
    x, dt, a, b, c, s0 = _ssd_inputs(3, 1, h=16, p=64, g=2, seed=2)
    args = (x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0])
    assert ssd.kernel_tiles(s0.shape, s0.dtype)
    y_ref, s_ref = ssd.ssd_step(*args, s0)
    live = jnp.asarray(live)
    y, s = ssd.ssd_decode(*args, s0 + 0, live, interpret=True)
    rows = live[:, None, None]
    np.testing.assert_allclose(y, jnp.where(rows, y_ref, 0.0), atol=1e-5)
    np.testing.assert_allclose(
        s, jnp.where(rows[..., None], s_ref, s0), atol=1e-6)


# -- the parts against the reference --------------------------------------------

def test_the_mamba2_mixer_is_the_reference(built):
    """Whole, and in two units that carry ``S`` and the convolution's
    history, then token by token."""
    cfg, d, _, _, _ = built
    p = layer_of(built, "mamba")
    mixer = Mamba2Mixer(d["m_heads"], d["m_dim"], d["state"],
                        num_groups=d["groups"], conv_size=d["conv"],
                        chunk_size=16, norm_eps=d["eps"])
    mine = dict({k: p[k] for k in ("w_in", "conv_w", "conv_b", "dt_bias",
                                   "a_log", "d_skip", "w_out")},
                norm=p["gnorm"])
    rng = np.random.default_rng(3)
    # 6 x: the projections' outputs are then of order one, as at the
    # published width, and the state carries as much as the skip
    u = 6 * jnp.asarray(rng.normal(size=(1, 45, d["hidden"])), jnp.float32)
    want = ref.mamba_mixer(u[0], p, d, ref.f32_matmul)
    got = mixer.apply(mine, u, compute_dtype=jnp.float32)[0]
    close(got, want)
    state, outs, at = mixer.init_state(1, jnp.float32), [], 0
    for n in (20, 17, 1, 1, 1, 1, 1, 1, 1, 1):
        y, state = mixer.mix(mine, u[:, at:at + n], state,
                             compute_dtype=jnp.float32)
        outs.append(y[0])
        at += n
    close(jnp.concatenate(outs), want)
    # the state matters: without what earlier tokens left, a tenth is off
    cold, _ = mixer.mix(mine, u[:, 30:], mixer.init_state(1, jnp.float32),
                        compute_dtype=jnp.float32)
    assert float(jnp.abs(cold[0, 5:] - want[35:]).max()) > \
        0.05 * float(jnp.abs(want).max())


def test_attention_at_16_query_heads_a_kv_head_through_the_paged_pool(built):
    """The attention part at the published RATIO (32 query heads over 2 KV
    heads): a paged prefill in two units, then steps through the block
    tables == the reference's mixer over the whole row."""
    cfg, d, _, _, _ = built
    d = dict(d, heads=32, kv_heads=2, head_dim=8)
    rng = np.random.default_rng(4)

    def nrm(*s):
        return jnp.asarray(0.1 * rng.normal(size=s), jnp.float32)
    p = dict(wq=nrm(d["hidden"], 256), wk=nrm(d["hidden"], 16),
             wv=nrm(d["hidden"], 16), wo=nrm(256, d["hidden"]))
    mha = MultiHeadAttention(32, 8, causal=True, use_bias=False,
                             num_kv_heads=2)
    u = nrm(1, 40, d["hidden"]) * 10
    want = ref.attention_mixer(u[0], p, d, ref.f32_matmul)
    page, view = 8, 64
    arena = {n: jnp.zeros((10 * page, 16), jnp.float32) for n in ("k", "v")}
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


def moe_of(d, held=None, shared=True):
    return SparseMoE(d["experts"], d["top_k"], d["expert_dim"],
                     held=held or (0, d["held"]),
                     shared_dim=d["shared_dim"] if shared else 0,
                     router="sigmoid_bias", router_scale=d["scale"],
                     expert_form="relu2")


def test_sigmoid_routed_relu2_experts_are_the_reference(built):
    cfg, d, _, _, _ = built
    p = layer_of(built, "experts")
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.normal(size=(64, d["hidden"])), jnp.float32)
    y, counters = moe_of(d).mix(p, u, compute_dtype=jnp.float32)
    want = ref.experts(u, p, d, ref.f32_matmul)
    close(y, want)
    # half the experts are held: about half of 64 x top_k assignments
    assert 0.3 < int(counters[0]) / (64 * d["top_k"]) < 0.7
    # the selection bias is in it: without it other experts are chosen
    flat = dict(p, router_bias=jnp.zeros_like(p["router_bias"]))
    y0, _ = moe_of(d).mix(flat, u, compute_dtype=jnp.float32)
    assert float(jnp.abs(y0 - want).max()) > 0.05 * float(jnp.abs(want).max())


def test_the_bias_moves_who_is_chosen_and_not_the_weights():
    """Scores 0.9, 0.8, 0.7, 0.6: a bias of +0.25 on the last puts it among
    the top two IN PLACE of 0.8, and its weight is its unbiased score's."""
    logits = jnp.log(jnp.asarray([[0.9, 0.8, 0.7, 0.6]]) /
                     (1 - jnp.asarray([[0.9, 0.8, 0.7, 0.6]])))
    zero = jnp.zeros((4,), jnp.float32)
    e0, w0 = xops.route(logits, 2, kind="sigmoid_bias", bias=zero, scale=2.5)
    assert sorted(e0[0].tolist()) == [0, 1]
    np.testing.assert_allclose(sorted(w0[0].tolist()),
                               [2.5 * 0.8 / 1.7, 2.5 * 0.9 / 1.7], rtol=1e-6)
    e1, w1 = xops.route(logits, 2, kind="sigmoid_bias",
                        bias=zero.at[3].set(0.25), scale=2.5)
    assert sorted(e1[0].tolist()) == [0, 3]
    np.testing.assert_allclose(sorted(w1[0].tolist()),
                               [2.5 * 0.6 / 1.5, 2.5 * 0.9 / 1.5], rtol=1e-6)


def test_routes_default_is_bit_for_bit_the_softmax_router():
    rng = np.random.default_rng(6)
    logits = jnp.asarray(rng.normal(size=(50, 16)), jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, e = jax.lax.top_k(probs, 4)
    got_e, got_w = xops.route(logits, 4)
    assert (got_e == e.astype(jnp.int32)).all()
    assert (got_w == w / jnp.sum(w, axis=-1, keepdims=True)).all()
    # and a layer built as before carries no new field and no new weight
    old = SparseMoE(8, 2, 16, shared_dim=16)
    assert set(old.get_config()) == {"num_experts", "top_k", "expert_dim",
                                     "held", "shared_dim", "kind"}
    params, _ = old.init(jax.random.PRNGKey(0), (4, 8))
    assert set(params) == {"router", "w_in", "w_out", "shared_in",
                           "shared_out"}
    assert params["w_in"].shape == (8, 8, 32)
    with pytest.raises(ValueError, match="router_scale"):
        SparseMoE(8, 2, 16, router_scale=2.5)
    with pytest.raises(ValueError, match="router must be"):
        SparseMoE(8, 2, 16, router="noisy")
    with pytest.raises(ValueError, match="expert_form"):
        SparseMoE(8, 2, 16, expert_form="gelu")


def test_the_shares_add_up_to_the_uncut_layer(built):
    """THE SHARE TEST.  The layer's result as each of the deployment's two
    shares computes it (its own experts' terms and the shared expert), the
    shared expert counted once, adds up to the reference given ALL the
    experts."""
    cfg, d, _, _, _ = built
    e, held = d["experts"], d["held"]
    hidden, f = d["hidden"], d["expert_dim"]
    rng = np.random.default_rng(7)

    def nrm(*s):
        return jnp.asarray(0.1 * rng.normal(size=s), jnp.float32)
    full = dict(router=nrm(hidden, e) * 10, router_bias=nrm(e),
                w_in=nrm(e, hidden, f), w_out=nrm(e, f, hidden),
                shared_in=nrm(hidden, d["shared_dim"]),
                shared_out=nrm(d["shared_dim"], hidden))
    u = nrm(33, hidden) * 10
    want = ref.experts(u, full, d, ref.f32_matmul)
    shared = ref.relu2_mlp(u, full["shared_in"], full["shared_out"],
                           ref.f32_matmul)
    total = shared
    for first in range(0, e, held):
        part = dict(full, w_in=full["w_in"][first:first + held],
                    w_out=full["w_out"][first:first + held])
        y, _ = moe_of(d, held=(first, held)).mix(part, u,
                                                 compute_dtype=jnp.float32)
        # and the reference's own share is the same part
        np.testing.assert_allclose(
            y, ref.experts(u, part, d, ref.f32_matmul, first=first),
            atol=TOL)
        total = total + (y - shared)
    np.testing.assert_allclose(total, want, atol=TOL)
    assert float(jnp.abs(want - shared).max()) > 100 * TOL


# -- the whole model ------------------------------------------------------------

def test_the_full_forward_is_the_reference(built):
    cfg, d, w, model, params = built
    toks = prompts(1, [41])[0]
    got = model.apply(params, jnp.asarray(toks)[None])[0]
    np.testing.assert_allclose(got, ref.logits_fn(w, jnp.asarray(toks), d),
                               atol=TOL)


@pytest.mark.parametrize("units", [(50,), (16, 16, 18), (7, 33, 10),
                                   (1, 1, 48)], ids=str)
def test_prefill_in_units_then_decode_is_the_full_forward(built, units):
    """Logits at EVERY position: prompt units of several sizes carry the
    state-space state and the keys on, past blocks that keep nothing, then
    single-token steps read and advance them."""
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


def test_three_kinds_of_state_in_one_stack(built):
    _, d, _, model, _ = built
    blocks = [l for l in model.layers if isinstance(l, HybridBlock)]
    kinds = [b.state_kind for b in blocks]
    assert kinds == [{"mamba": "recurrent", "attn": "kv",
                      "experts": "none"}[k] for k in d["kinds"]]
    caches = dec.init_cache(model, 2, 32)[1:-2]
    arena = dec.init_paged_arena(model, 4, 16, num_slots=3)[1:-2]
    for kind, cache, pool in zip(kinds, caches, arena):
        if kind == "none":
            assert cache is None and pool is None
        elif kind == "kv":
            assert set(cache) == {"k", "v"} and pool["k"].shape[0] == 5 * 16
        else:
            assert cache["S"].shape == (2, d["m_heads"], d["m_dim"],
                                        d["state"])
            assert pool["S"].shape[0] == 3 and pool["S"].dtype == jnp.float32
            assert pool["conv"].shape == (3, d["conv"] - 1, d["conv_dim"])
    none = blocks[kinds.index("none")]
    assert none.mixer() is None and none.routes_tokens
    assert none.wants_token_mask            # no state, still a routing mask
    with pytest.raises(ValueError, match="needs a mixer"):
        HybridBlock()


# -- the engine -----------------------------------------------------------------

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
    logit lies within TOL of the reference's best."""
    _, d, _, _, _ = built
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
    n_moe = d["kinds"].count("experts")
    assert st["recurrent_slots_cleared"] == 5 and st["prefix_hit_tokens"] == 0
    assert st["moe_layer_steps"] == n_moe * st["decode_steps"]
    assert 0 < st["moe_experts_touched"] <= st["moe_assignments_held"]
    assert st["d2h_transfers"] == st["decode_steps"] + st["prefills"]
    # the experts' counters over prefill units, under their own keys
    units = st["prefill_chunks"] + st["prefill_batches"]
    assert st["moe_prefill_layer_units"] == n_moe * units
    tokens = sum(len(p) for p in ps)
    share = st["moe_prefill_assignments_held"] / (
        tokens * n_moe * d["top_k"])
    assert 0.3 < share < 0.7                # half the experts are held
    assert 0 < st["moe_prefill_experts_touched"] <= d["held"] * n_moe * units


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
    h3 = eng.submit(p.copy(), 8)
    eng.run_until_idle()
    assert list(h1.tokens) == list(h2.tokens) == list(h3.tokens)
    assert eng.stats["prefix_hits"] == 0
    assert eng.stats["prefill_tokens"] == 3 * 48


# -- up-projections in the layout the grouped matmul reads ----------------------

@pytest.fixture(scope="module")
def built_wide():
    """The tiny model at a hidden size of 128 lanes: its up-projections
    ``(4, 128, 32)`` are of the kind the engine holds transposed, as the
    published ``(64, 2688, 1856)`` are (``SparseMoE.serves_transposed``)."""
    cfg = tiny_cfg()
    cfg["hidden_size"] = 128
    w = make_weights(cfg, 11, "float32")
    return (cfg, dims(cfg), w, program_nemotronh.build_model(cfg),
            program_nemotronh.to_program_layout(w))


def _other_weights(built_wide):
    """``built_wide`` with another draw of the weights."""
    cfg = built_wide[0]
    return built_wide[:4] + (program_nemotronh.to_program_layout(
        make_weights(cfg, 12, "float32")),)


def _built(built_wide):
    return engine_of(built_wide)


def _respawned(built_wide):
    return engine_of(built_wide).respawn_clone()


def _assigned(built_wide):
    eng = engine_of(_other_weights(built_wide))
    eng.params = built_wide[4]      # the model's own layout, as a tool has it
    return eng


def _reloaded(built_wide):
    """Built on other weights, then one pull of ``built_wide``'s from a live
    parameter server (the flat wire list is in the model's own layout), and
    a clone of that engine after a pull of its own: the skeleton a pull is
    mapped onto carries over."""
    from distkeras_tpu.parameter_servers import (DeltaParameterServer,
                                                 SocketParameterServer)
    _, _, _, model, params = built_wide
    ps = SocketParameterServer(DeltaParameterServer(
        serialize_model(model, params)))
    ps.start()
    try:
        eng = engine_of(_other_weights(built_wide))
        eng.attach_ps("127.0.0.1", ps.port, every=10 ** 6)
        clone = eng.respawn_clone()
        for e in (eng, clone):
            e._pull_weights()
            assert e.stats["weight_reloads"] == 1
            e._reload_sock.close()
        np.testing.assert_array_equal(eng.params[2]["ffn"]["w_in_t"],
                                      clone.params[2]["ffn"]["w_in_t"])
        return clone
    finally:
        ps.stop()


@pytest.mark.parametrize("door", [_built, _respawned, _assigned, _reloaded],
                         ids=lambda f: f.__name__.strip("_"))
def test_every_door_to_the_engines_parameters_stores_them_for_serving(
        built_wide, door):
    """Construction, ``respawn_clone``, plain assignment and the
    parameter-server reload: the engine holds every expert layer's
    up-projection transposed and no other form of it, says so in ``stats``,
    and serves the full forward's tokens on the model's own parameters."""
    _, d, _, model, params = built_wide
    eng = door(built_wide)
    n_moe = d["kinds"].count("experts")
    assert eng.stats["moe_up_projections_transposed"] == n_moe > 0
    for layer, p, q in zip(model.layers, params, eng.params):
        if isinstance(layer, HybridBlock) and layer.routes_tokens:
            assert "w_in" not in q["ffn"]
            assert q["ffn"]["w_in_t"].shape == (d["held"], d["expert_dim"],
                                                d["hidden"])
            np.testing.assert_array_equal(
                q["ffn"]["w_in_t"], jnp.swapaxes(p["ffn"]["w_in"], 1, 2))
    ps = prompts(21, [37, 9])
    hs = [eng.submit(p, 10) for p in ps]
    eng.run_until_idle()
    fitted = FittedModel(model, params)
    for p, h in zip(ps, hs):
        want = np.asarray(fitted.generate(p[None], 10))
        assert list(h.tokens) == want[0, len(p):].tolist()
        assert float(served_gaps(built_wide, p, h.tokens).max()) <= TOL
    # what the caller handed in is what it was
    assert params[2]["ffn"]["w_in"].shape == (d["held"], d["hidden"],
                                              d["expert_dim"])


def test_the_tiny_widths_are_served_as_they_are(built):
    """A hidden size of 64 is not a whole lane tile: nothing is transposed,
    and the engine holds the very leaves it was given."""
    eng = engine_of(built)
    assert eng.stats["moe_up_projections_transposed"] == 0
    assert eng.params[2]["ffn"]["w_in"].shape == built[4][2]["ffn"][
        "w_in"].shape


@pytest.mark.parametrize("kw,word", [
    (dict(role="prefill"), "block transfer"),
    (dict(rolling=True), "roll"),
    (dict(spec_draft="self"), "snapshot"),
    (dict(kv_dtype="int8"), "float32"),
    (dict(paged=False), "paged=True"),
    (dict(quantize="int8"), "quantiser"),
], ids=lambda x: next(iter(x)) if isinstance(x, dict) else x)
def test_the_six_refusals_hold_for_this_model(built, kw, word):
    _, _, _, model, params = built
    if kw.get("spec_draft") == "self":
        kw = dict(spec_draft=(model, params))
    with pytest.raises(ValueError, match=word):
        engine_of(built, **kw)


def test_the_decode_span_names_three_kinds_of_state(built, tmp_path):
    eng = engine_of(built)
    with metrics.trace(str(tmp_path)):
        eng.submit(prompts(11, [20])[0], 3)
        eng.run_until_idle()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    states = {dict(e.stats).get("state")
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name == "serve.decode_dispatch"}
    assert states == {"kv+recurrent+none"}


def test_the_programs_name_the_state_space_scopes(built):
    """``ssm`` and its parts beside ``attn`` and ``moe*`` in the decode
    step's and a prefill unit's HLO, and the kernel's name in the table."""
    _, _, _, model, params = built
    caches = dec.init_cache(model, 1, 32)
    toks = jnp.zeros((1, 20), jnp.int32)
    unit = jax.jit(lambda c, t: dec._forward(model, params, c, t, 0)).lower(
        caches, toks).as_text(debug_info=True)
    step = jax.jit(lambda c, t: dec.decode_step(
        model, params, c, t, 20)).lower(caches, toks[:, 0]).as_text(
            debug_info=True)
    for text in (unit, step):
        for scope in ("ssm/ssm_proj", "ssm/ssm_conv", "ssm/ssm_core",
                      "ssm/ssm_out", "attn/attn_core", "moe/moe_experts",
                      "moe/moe_route", "moe/moe_shared"):
            assert scope in text, scope
    assert "ssd_chunk" in unit
    assert "ssd_decode" in metrics.KERNEL_NAMES


# -- the builder ----------------------------------------------------------------

def test_hybrid_lm_builds_the_published_pattern_and_the_cut():
    cfg = mf.load_json(CONFIG)
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == 52 and cfg["num_hidden_layers"] == 9
    small = dict(cfg, **{k: v for k, v in cfg["tiny"].items()
                         if not isinstance(v, dict)})

    def kinds(model):
        return "".join({"recurrent": "M", "kv": "*", "none": "E"}[b.state_kind]
                       for b in model.layers if isinstance(b, HybridBlock))
    whole = hybrid_lm(dict(small, num_hidden_layers=52, n_routed_experts=8))
    assert kinds(whole) == pattern
    assert (kinds(whole).count("M"), kinds(whole).count("E"),
            kinds(whole).count("*")) == (23, 23, 6)
    cut = program_nemotronh.build_model(mf.resolve_sizes(cfg, True))
    assert kinds(cut) == "MEMEM*EME" == pattern[:9]
    # the published widths build as they read (specs only: nothing is made)
    real = program_nemotronh.build_model(cfg)
    mixer = real.layers[1].mixer()
    assert (mixer.num_heads, mixer.head_dim, mixer.state_size,
            mixer.num_groups, mixer._sizes()) == (64, 64, 128, 8,
                                                  (4096, 6144))
    moe = real.layers[2].ffn()
    assert (moe.num_experts, moe.held, moe.top_k, moe.expert_dim,
            moe.shared_dim, moe.router, moe.router_scale, moe.expert_form
            ) == (128, (0, 64), 6, 1856, 3712, "sigmoid_bias", 2.5, "relu2")
    attn = real.layers[6].mixer()
    assert (attn.num_heads, attn.num_kv_heads, attn.key_dim, attn.rope,
            attn.use_bias, attn.causal) == (32, 2, 128, False, False, True)


@pytest.mark.parametrize("change,word", [
    (dict(hybrid_override_pattern="ME-M*EMEM"), "dense MLP"),
    (dict(hybrid_override_pattern="MEM"), "names 3 layers"),
    (dict(mlp_hidden_act="silu"), "relu2"),
    (dict(mamba_hidden_act="gelu"), "SiLU"),
    (dict(use_bias=True), "use_bias"),
    (dict(use_conv_bias=False), "use_conv_bias"),
    (dict(n_group=2), "expert groups"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(logits_scaling=8), "untied"),
], ids=lambda x: next(iter(x)) if isinstance(x, dict) else x)
def test_what_the_pattern_builder_does_not_build_it_refuses_by_name(change,
                                                                    word):
    cfg = dict(mf.resolve_sizes(mf.load_json(CONFIG), True), **change)
    with pytest.raises(ValueError, match=word):
        hybrid_lm(cfg)


def test_a_tied_pattern_config_is_built_with_one_table():
    """``tie_word_embeddings`` was refused until the head could read the
    embedding's table (``TiedHead``): the same pattern, tied, now holds no
    head of its own, and its cached step is its full forward."""
    from distkeras_tpu.core.layers import TiedHead
    cfg = dict(mf.resolve_sizes(mf.load_json(CONFIG), True),
               tie_word_embeddings=True)
    model = hybrid_lm(cfg, compute_dtype="float32")
    params = model.init(jax.random.PRNGKey(0), (8,))
    assert isinstance(model.layers[-1], TiedHead) and params[-1] == {}
    toks = jnp.asarray(prompts(3, [20])[0])[None]
    want = model.apply(params, toks)
    caches = dec.init_cache(model, 1, 32)
    got, caches = dec._forward(model, params, caches, toks[:, :12], 0)
    np.testing.assert_allclose(got, want[:, :12], atol=TOL)
    step, _ = dec.decode_step(model, params, caches, toks[:, 12], 12)
    np.testing.assert_allclose(step, want[:, 12], atol=TOL)
