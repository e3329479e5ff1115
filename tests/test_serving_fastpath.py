"""The serving engine's compute path (distkeras_tpu/serving.py).

Three layers: compiled bucketed batch prefill, chunked long-prompt prefill
interleaved with decode, and device-resident decode state with one-step
lookahead.  The contract pinned here:

 - bucketed AND chunked prefill emit tokens BIT-IDENTICAL to offline
   ``generate`` (per request), across greedy + sampled × rolling +
   full-cache × mixed prompt lengths sharing one bucketed batch — the
   engine is an execution strategy, never a numerics change;
 - the engine runs no forward outside a jitted program;
 - a decode-only iteration performs ZERO host→device uploads and exactly
   ONE device→host readback (the sampled token row) — asserted with a
   transfer-counting double wrapped around the jitted step;
 - a long-prompt admission stalls the running batch by at most one
   ``prefill_chunk`` chunk per iteration (deterministic counter
   assertion — the Sarathi-style stall-free property);
 - ``warmup()`` precompiles every bucket/chunk/decode program, so live
   traffic after a supervisor respawn re-traces NOTHING;
 - hot weight reload fires only when ``decode_steps`` actually advances
   (a reap-only iteration parked on a reload multiple must not re-pull).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distkeras_tpu.core import decode
from distkeras_tpu.core.model import FittedModel
from distkeras_tpu.models import transformer_lm
from distkeras_tpu.serving import ServingEngine, _pow2_buckets

VOCAB = 17
PROMPT = np.array([3, 4, 5, 6], np.int32)


def _fitted(seed=0, **kw):
    model = transformer_lm(vocab_size=VOCAB, seq_len=32, d_model=16,
                           num_heads=2, num_layers=2, mlp_dim=32,
                           compute_dtype="float32", **kw)
    params = model.init(jax.random.PRNGKey(seed), (32,))
    return FittedModel(model, params)


@pytest.fixture(scope="module")
def fitted():
    return _fitted()


@pytest.fixture(scope="module")
def windowed():
    return _fitted(seed=1, attention_window=6)


@pytest.fixture(scope="module")
def trained():
    """The x+1 LM of tests/test_quant.py: its greedy margins dwarf int8
    rounding, so two LOSSY programs can be held to token equality (the
    untrained ``fitted`` has top-2 logit gaps of ~0.002 on a range of 4,
    which any change of rounding order flips)."""
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.trainers import SingleTrainer
    model = transformer_lm(vocab_size=VOCAB, seq_len=32, d_model=32,
                           num_heads=4, num_layers=2, mlp_dim=64,
                           compute_dtype="float32")
    toks = np.random.default_rng(2).integers(
        0, VOCAB, (256, 32)).astype(np.int32)
    t = SingleTrainer(model, batch_size=32, num_epoch=25,
                      loss="sparse_categorical_crossentropy_from_logits",
                      worker_optimizer="adam", learning_rate=3e-3)
    return t.train(Dataset({"features": toks,
                            "label": (toks + 1) % VOCAB}))


def _want(fitted, h, **kw):
    return np.asarray(fitted.generate(
        h.prompt[None], h.num_steps, max_len=kw.pop("max_len"),
        temperature=h.temperature,
        rng=h.key if h.temperature > 0 else None,
        top_k=h.top_k, top_p=h.top_p, **kw))[0]


# ---------------------------------------------------------------------------
# bit-identity: bucketed / chunked / rolling vs offline generate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    {},                                                       # greedy
    {"temperature": 0.7, "seed": 11},                         # plain sample
    {"temperature": 0.7, "top_k": 5, "top_p": 0.9, "seed": 11},
])
def test_bucketed_lone_request_matches_generate(fitted, kw):
    eng = ServingEngine(fitted, num_slots=3, max_len=24)
    h = eng.submit(PROMPT, 8, **kw)
    eng.run_until_idle()
    np.testing.assert_array_equal(h.result(), _want(fitted, h, max_len=24))


def test_mixed_prompt_lengths_share_one_bucketed_batch(fitted):
    """Four requests of four different lengths admitted in the same
    iteration land in ONE batched bucket prefill (their lengths all round
    up to the same bucket), and every output still matches generate."""
    eng = ServingEngine(fitted, num_slots=4, max_len=24,
                        prefills_per_step=4)
    hs = [eng.submit(np.arange(1, 1 + p, dtype=np.int32) % VOCAB, 6,
                     temperature=0.5, seed=40 + p)
          for p in (2, 3, 5, 7)]
    eng.run_until_idle()
    assert eng.stats["prefill_batches"] == 1
    assert eng.stats["prefill_batch_size_mean"] == 4.0
    for h in hs:
        np.testing.assert_array_equal(h.result(),
                                      _want(fitted, h, max_len=24))


def test_chunked_prefill_bit_identical_and_counted(fitted):
    """A prompt past ``prefill_chunk`` splits into ceil(P/chunk) chunks
    (the final one bucket-rounded) and still reproduces generate exactly,
    greedy and sampled, while a short concurrent request rides along."""
    long_p = (np.arange(1, 14, dtype=np.int32) * 3) % VOCAB  # 13 tokens
    for kw in ({}, {"temperature": 0.6, "seed": 5}):
        eng = ServingEngine(fitted, num_slots=2, max_len=32,
                            prefill_chunk=4)
        h = eng.submit(long_p, 8, **kw)
        h2 = eng.submit(PROMPT, 4)
        eng.run_until_idle()
        assert eng.stats["prefill_chunks"] == 4  # 4+4+4 + final 1
        np.testing.assert_array_equal(h.result(),
                                      _want(fitted, h, max_len=32))
        np.testing.assert_array_equal(h2.result(),
                                      _want(fitted, h2, max_len=32))


def test_rolling_bucketed_and_chunked_bit_identical(windowed):
    """Rolling engines: the bucket program ring-converts per-row traced
    lengths; the chunked path stages a full cache and collapses it on the
    final chunk — both must match offline rolling generate."""
    eng = ServingEngine(windowed, num_slots=2, max_len=24, rolling=True)
    h1 = eng.submit(np.arange(1, 8, dtype=np.int32) % VOCAB, 10,
                    temperature=0.6, seed=9)
    h2 = eng.submit(np.array([1, 2], np.int32), 6)
    eng.run_until_idle()
    for h in (h1, h2):
        np.testing.assert_array_equal(
            h.result(), _want(windowed, h, max_len=24, rolling=True))
    assert eng.caches[2]["k"].shape[1] == 6  # the pool really is a ring

    eng = ServingEngine(windowed, num_slots=2, max_len=28, rolling=True,
                        prefill_chunk=4)
    lp = (np.arange(1, 14, dtype=np.int32) * 5) % VOCAB
    h = eng.submit(lp, 8, temperature=0.8, seed=3)
    eng.run_until_idle()
    assert eng.stats["prefill_chunks"] == 4
    np.testing.assert_array_equal(
        h.result(), _want(windowed, h, max_len=28, rolling=True))


def test_ring_from_prefill_matches_to_ring():
    """The traced per-row ring conversion is a relayout: bit-equal to the
    host-side _to_ring for every p_len/window relation, including a
    mixed-length batch in one call."""
    rng = np.random.default_rng(0)
    c = jnp.asarray(rng.standard_normal((3, 12, 2, 3)), jnp.float32)
    for lens, w in (([9, 3, 4], 4), ([1, 12, 6], 6)):
        got = np.asarray(decode.ring_from_prefill(c, jnp.array(lens), w))
        for r, p in enumerate(lens):
            want = np.asarray(decode._to_ring(c[r:r + 1, :p], p, w))
            np.testing.assert_array_equal(got[r:r + 1], want)


def test_eos_retirement_on_fast_path(fitted):
    greedy = np.asarray(fitted.generate(PROMPT[None], 8, max_len=24))[0]
    eos = int(greedy[len(PROMPT) + 2])
    eng = ServingEngine(fitted, num_slots=2, max_len=24)
    h = eng.submit(PROMPT, 8, eos_id=eos, pad_id=1)
    eng.run_until_idle()
    want = np.asarray(fitted.generate(PROMPT[None], 8, eos_id=eos,
                                      pad_id=1, max_len=24))[0]
    np.testing.assert_array_equal(h.result(), want)
    assert h.finish == "eos"


# ---------------------------------------------------------------------------
# hot-path discipline: no forward outside jit, one transfer each way
# ---------------------------------------------------------------------------

def test_no_eager_forward_in_bucketed_hot_path(fitted, monkeypatch):
    """The engine runs no forward outside a jitted program: every call of
    ``core.decode._forward`` it makes, on the bucket, the chunked and the
    decode path, sees TRACED tokens."""
    real, calls = decode._forward, []

    def traced_only(model, params, caches, tokens, *a, **k):
        assert isinstance(tokens, jax.core.Tracer), \
            "_forward ran op by op on the engine's hot path"
        calls.append(1)
        return real(model, params, caches, tokens, *a, **k)

    monkeypatch.setattr(decode, "_forward", traced_only)
    eng = ServingEngine(fitted, num_slots=2, max_len=24, prefill_chunk=4)
    h = eng.submit(PROMPT, 4)
    hl = eng.submit((np.arange(1, 12, dtype=np.int32) * 7) % VOCAB, 4)
    eng.run_until_idle()  # the batch, the chunked and the decode programs
    assert h.done and hl.done and calls
    # the wrapper does tell the two apart: offline generate prefills op
    # by op and trips it
    with pytest.raises(AssertionError, match="op by op"):
        fitted.generate(PROMPT[None], 2, max_len=24)


def test_decode_iteration_transfer_discipline(fitted):
    """Steady-state decode: zero host→device uploads, exactly one
    device→host readback per iteration, and every jitted-step argument is
    already a device array (the test double wraps the step)."""
    eng = ServingEngine(fitted, num_slots=2, max_len=24).warmup()
    h = eng.submit(PROMPT, 14)
    eng.step()  # admission iteration (uploads happen here, counted apart)
    orig = eng._decode_fn

    def checked(*args):
        leaves = jax.tree_util.tree_leaves(args)
        assert all(isinstance(a, jax.Array) for a in leaves), \
            "decode step received a host array (implicit h2d transfer)"
        return orig(*args)

    eng._decode_fn = checked
    h0, d0 = eng.stats["h2d_transfers"], eng.stats["d2h_transfers"]
    for _ in range(6):
        eng.step()
    assert eng.stats["h2d_transfers"] - h0 == 0
    assert eng.stats["d2h_transfers"] - d0 == 6
    eng.run_until_idle()
    np.testing.assert_array_equal(h.result(),
                                  _want(fitted, h, max_len=24))


def test_lookahead_flushes_at_idle(fitted):
    """One-step lookahead leaves the pipeline drained when work runs out:
    every token is delivered, nothing pends, and the engine reports idle."""
    eng = ServingEngine(fitted, num_slots=2, max_len=24)
    h = eng.submit(PROMPT, 5)
    eng.run_until_idle()
    assert h.done and len(h.tokens) == 5
    assert not eng._pending and not eng._prefilling
    assert not eng.step()  # truly idle


# ---------------------------------------------------------------------------
# stall-free chunked admission (deterministic counters, tier-1)
# ---------------------------------------------------------------------------

def test_long_prompt_admission_does_not_stall_decode(fitted):
    """While a 12-token prompt chunk-prefills at prefill_chunk=4, the
    running request keeps decoding EVERY iteration: the admission costs
    the running batch at most one chunk of prefill per step, never the
    whole prompt (the counter twin of the wall-clock TTFT bench)."""
    eng = ServingEngine(fitted, num_slots=2, max_len=32, prefill_chunk=4)
    a = eng.submit(PROMPT, 20)
    while not a.tokens:
        eng.step()
    steps0 = eng.stats["decode_steps"]
    a0 = len(a.tokens)
    b = eng.submit((np.arange(1, 13, dtype=np.int32) * 3) % VOCAB, 4)
    iters = 0
    while not b.tokens and iters < 20:
        eng.step()
        iters += 1
    assert eng.stats["prefill_chunks"] == 3        # 4 + 4 + final 4
    # every chunk iteration also ran a decode step for the running batch
    decoded = eng.stats["decode_steps"] - steps0
    assert decoded >= 3 and decoded == iters
    assert len(a.tokens) - a0 >= 3
    # and B's first token arrived within chunks + pipeline slack
    assert iters <= 5
    eng.run_until_idle()
    np.testing.assert_array_equal(a.result(), _want(fitted, a, max_len=32))
    np.testing.assert_array_equal(b.result(), _want(fitted, b, max_len=32))


# ---------------------------------------------------------------------------
# warmup precompilation + reload gate
# ---------------------------------------------------------------------------

def test_warmup_precompiles_every_program(fitted, monkeypatch):
    """After warmup(), traffic through every bucket AND the chunked path
    triggers zero new jit traces (counted via decode._forward, which every
    program traces through) — a supervisor respawn must not pay per-bucket
    compiles under live traffic."""
    calls = []
    orig = decode._forward

    def counting(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(decode, "_forward", counting)
    eng = ServingEngine(fitted, num_slots=2, max_len=24, prefill_chunk=4,
                        prefills_per_step=2).warmup()
    traced = len(calls)
    assert traced > 0
    h1 = eng.submit(np.array([2, 3, 4], np.int32), 3)       # bucket batch
    h2 = eng.submit((np.arange(1, 12, dtype=np.int32)) % VOCAB, 3)  # chunks
    eng.run_until_idle()
    assert h1.done and h2.done
    assert len(calls) == traced, "live traffic re-traced a program"


def test_warmup_refuses_mid_prefill_engine(fitted):
    eng = ServingEngine(fitted, num_slots=1, max_len=32, prefill_chunk=4)
    eng.submit((np.arange(1, 13, dtype=np.int32)) % VOCAB, 4)
    eng.step()
    assert eng._prefilling
    with pytest.raises(RuntimeError, match="active"):
        eng.warmup()


def test_pow2_bucket_ladder():
    assert _pow2_buckets(32) == [8, 16, 32]
    assert _pow2_buckets(100) == [8, 16, 32, 64, 100]
    assert _pow2_buckets(8) == [8]
    assert _pow2_buckets(5) == [5]


def test_prefill_knob_validation(fitted):
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingEngine(fitted, num_slots=1, max_len=24, prefill_chunk=0)


def test_respawn_clone_carries_prefill_knobs(fitted):
    eng = ServingEngine(fitted, num_slots=2, max_len=24, prefill_chunk=16)
    clone = eng.respawn_clone()
    assert clone.prefill_chunk == 16


def test_reload_gate_requires_decode_progress(fitted):
    """The hot-reload satellite: _pull_weights fires only when
    decode_steps ADVANCES onto a reload multiple — a reap-only iteration
    parked on a multiple must not re-pull every pass."""
    eng = ServingEngine(fitted, num_slots=1, max_len=24)
    pulls = []
    eng._pull_weights = lambda: pulls.append(1)
    eng._reload_every = 1
    eng.submit(PROMPT, 3)
    eng.run_until_idle()
    base = len(pulls)
    assert base >= 1  # decode progress pulled as expected
    # park the counter on a multiple, then run a reap-only iteration
    h2 = eng.submit(PROMPT, 3)
    eng.cancel(h2)
    assert eng.step()  # reap does work, decode_steps does not advance
    assert len(pulls) == base


# ---------------------------------------------------------------------------
# speculative decoding on the fast path (PR 11): greedy token-identity,
# heterogeneous per-row accept lengths, stats vocabulary, warmup coverage
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def draft():
    return _fitted(seed=99)  # independent random draft: near-floor accepts


@pytest.mark.parametrize("draft_kind", ["self", "random"])
@pytest.mark.parametrize("spec_len", [1, 3])
def test_spec_greedy_token_identity_vs_generate(fitted, draft, draft_kind,
                                                spec_len):
    """The tentpole contract: greedy speculation is TOKEN-IDENTICAL to
    the non-speculative engine whatever the draft proposes — a self-draft
    (high accept: rows ride the fast lane) and an independent random
    draft (near-floor accept: every round falls back to the correction
    token) both reproduce offline greedy ``generate`` bit for bit, with
    MIXED prompt lengths (so mixed accept lengths) sharing one batch."""
    d = fitted if draft_kind == "self" else draft
    subs = [(np.arange(1, 1 + p, dtype=np.int32) % VOCAB, 5 + p % 3)
            for p in (2, 4, 7)]
    eng = ServingEngine(fitted, num_slots=3, max_len=24, spec_draft=d,
                        spec_len=spec_len, prefills_per_step=3)
    got = [eng.submit(pr, n) for pr, n in subs]
    eng.run_until_idle()
    for g in got:
        np.testing.assert_array_equal(g.result(),
                                      _want(fitted, g, max_len=24))
    assert eng.stats["verify_calls"] >= 1
    assert eng.stats["drafted"] >= spec_len
    assert 0 <= eng.stats["accepted"] <= eng.stats["drafted"]


def test_spec_rolling_token_identity(windowed):
    """Rolling pools under speculation: the ring carries spec_len slack
    slots so the L-token verify never overwrites the oldest query's
    window — greedy output still matches rolling ``generate``."""
    subs = [(np.arange(1, 8, dtype=np.int32) % VOCAB, 10),
            (np.array([1, 2], np.int32), 6)]
    eng = ServingEngine(windowed, num_slots=2, max_len=24, rolling=True,
                        spec_draft=windowed, spec_len=3,
                        prefills_per_step=2)
    # the pool ring really is window + spec_len slots
    assert eng.caches[2]["k"].shape[1] == 6 + 3
    got = [eng.submit(pr, n) for pr, n in subs]
    eng.run_until_idle()
    for g in got:
        np.testing.assert_array_equal(
            g.result(), _want(windowed, g, max_len=24, rolling=True))


def test_spec_sampled_deterministic_and_greedy_rows_exact(fitted):
    """A mixed greedy + sampled batch under speculation: sampled rows are
    deterministic per seed (run twice, identical) and the GREEDY rows in
    the same batch stay bit-identical to offline ``generate`` — per-row
    independence of the accept/commit machinery."""
    subs = [((PROMPT, 8), {}),
            ((np.array([1, 2], np.int32), 6),
             {"temperature": 0.7, "top_k": 5, "seed": 3}),
            ((np.arange(1, 8, dtype=np.int32), 5), {})]

    def run():
        eng = ServingEngine(fitted, num_slots=3, max_len=24,
                            spec_draft=fitted, spec_len=4,
                            prefills_per_step=3)
        hs = [eng.submit(*a, **k) for a, k in subs]
        eng.run_until_idle()
        return hs

    hs1, hs2 = run(), run()
    for a, b in zip(hs1, hs2):
        np.testing.assert_array_equal(a.result(), b.result())
    for h in (hs1[0], hs1[2]):
        np.testing.assert_array_equal(h.result(),
                                      _want(fitted, h, max_len=24))


def test_spec_chunked_prefill_and_eos(fitted):
    """Long prompts chunk-prefill into BOTH pools (target + draft
    staging), and eos retirement mid-round matches generate's stopping
    semantics token for token."""
    lp = (np.arange(1, 14, dtype=np.int32) * 3) % VOCAB
    eng = ServingEngine(fitted, num_slots=2, max_len=32, spec_draft=fitted,
                        spec_len=3, prefill_chunk=4)
    h = eng.submit(lp, 8)
    eng.run_until_idle()
    assert eng.stats["prefill_chunks"] == 4
    np.testing.assert_array_equal(h.result(), _want(fitted, h, max_len=32))

    greedy = np.asarray(fitted.generate(PROMPT[None], 8, max_len=24))[0]
    eos = int(greedy[len(PROMPT) + 2])
    eng = ServingEngine(fitted, num_slots=2, max_len=24, spec_draft=fitted,
                        spec_len=4)
    h = eng.submit(PROMPT, 8, eos_id=eos, pad_id=1)
    eng.run_until_idle()
    want = np.asarray(fitted.generate(PROMPT[None], 8, eos_id=eos,
                                      pad_id=1, max_len=24))[0]
    np.testing.assert_array_equal(h.result(), want)
    assert h.finish == "eos"


def test_spec_stats_mirror_offline_vocabulary(fitted):
    """The engine reports speculation through speculative_generate's own
    stats keys: drafted/accepted (+ verify_calls, mirrored verbatim by
    target_calls) — one vocabulary across offline and serving."""
    eng = ServingEngine(fitted, num_slots=2, max_len=24, spec_draft=fitted,
                        spec_len=3)
    h = eng.submit(PROMPT, 10)
    eng.run_until_idle()
    s = eng.stats
    assert h.done and s["verify_calls"] >= 1
    assert s["target_calls"] == s["verify_calls"]
    assert s["drafted"] == 3 * s["verify_calls"]
    assert 0 <= s["accepted"] <= s["drafted"]
    # offline stats carry the same keys (the satellite's shared contract)
    _, off = fitted.speculative_generate(fitted, PROMPT[None], 6,
                                         draft_len=3, return_stats=True)
    assert set(off) == {"target_calls", "drafted", "accepted"}
    assert set(off) < set(s)


def test_spec_warmup_precompiles_draft_and_verify(fitted, monkeypatch):
    """warmup() on a speculative engine compiles the spec round (draft
    steps + verify + back-fill), every bucket's dual-pool prefill, and
    the chunk programs — live traffic re-traces NOTHING (the respawn-
    under-traffic guarantee, extended to the new programs)."""
    calls = []
    orig = decode._forward

    def counting(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(decode, "_forward", counting)
    eng = ServingEngine(fitted, num_slots=2, max_len=24, spec_draft=fitted,
                        spec_len=2, prefill_chunk=4,
                        prefills_per_step=2).warmup()
    traced = len(calls)
    assert traced > 0
    h1 = eng.submit(np.array([2, 3, 4], np.int32), 6)        # bucket batch
    h2 = eng.submit((np.arange(1, 12, dtype=np.int32)) % VOCAB, 6)  # chunks
    eng.run_until_idle()
    assert h1.done and h2.done
    assert len(calls) == traced, "live speculative traffic re-traced"


def test_spec_and_quant_validation(fitted, draft):
    with pytest.raises(ValueError, match="spec_len"):
        ServingEngine(fitted, num_slots=1, max_len=24, spec_draft=fitted,
                      spec_len=0)
    with pytest.raises(ValueError, match="quantize"):
        ServingEngine(fitted, num_slots=1, max_len=24, quantize="fp4")
    with pytest.raises(ValueError, match="kv_dtype"):
        ServingEngine(fitted, num_slots=1, max_len=24, kv_dtype="int4")
    small = _fitted(seed=5)
    small.model.layers[0].input_dim = VOCAB + 1  # forge a vocab mismatch
    with pytest.raises(ValueError, match="vocabularies differ"):
        ServingEngine(fitted, num_slots=1, max_len=24, spec_draft=small)


# ---------------------------------------------------------------------------
# quantization on the fast path: int8/bf16 weights, int8 KV pool
# ---------------------------------------------------------------------------

def test_weight_quant_int8_matches_offline_quantized_generate(fitted):
    """quantize="int8" routes construction through quantize_params: the
    engine's output equals offline generate on the SAME quantized params
    (lossy vs fp32, exact vs the quantized reference)."""
    q = fitted.quantize()
    want = np.asarray(q.generate(PROMPT[None], 8, max_len=24))[0]
    eng = ServingEngine(fitted, num_slots=2, max_len=24, quantize="int8")
    h = eng.submit(PROMPT, 8)
    eng.run_until_idle()
    np.testing.assert_array_equal(h.result(), want)


def test_kv_int8_pool_halves_slot_bytes(fitted):
    """The capacity math: an int8 KV pool sustains >= 1.5x the slots of
    the full-precision pool at fixed bytes (byte-accounted, not assumed),
    and requests still complete sanely through the quantized read/write
    path — including under speculation (both pools quantized)."""
    fp = ServingEngine(fitted, num_slots=4, max_len=24)
    q8 = ServingEngine(fitted, num_slots=4, max_len=24, kv_dtype="int8")
    per_slot_q8 = q8.kv_pool_bytes // q8.num_slots
    assert fp.kv_pool_bytes // per_slot_q8 >= int(1.5 * fp.num_slots)
    h = q8.submit(PROMPT, 8)
    q8.run_until_idle()
    row = h.result()
    assert row.shape == (len(PROMPT) + 8,)
    assert (0 <= row).all() and (row < VOCAB).all()
    spec = ServingEngine(fitted, num_slots=2, max_len=24, kv_dtype="int8",
                         spec_draft=fitted, spec_len=3, quantize="int8")
    h2 = spec.submit(PROMPT, 8)
    spec.run_until_idle()
    assert h2.result().shape == (len(PROMPT) + 8,)
    assert spec.stats["verify_calls"] >= 1


def test_respawn_clone_carries_spec_and_quant_state(fitted, draft):
    """The supervisor contract: a respawned clone carries the draft model,
    spec_len, and both quantization knobs — and still warms up and
    serves (greedy spec identity preserved across the respawn)."""
    eng = ServingEngine(fitted, num_slots=2, max_len=24, spec_draft=draft,
                        spec_len=2, quantize="bf16", kv_dtype="int8")
    clone = eng.respawn_clone().warmup()
    assert clone.spec_len == 2 and clone.quantize == "bf16"
    assert clone.kv_dtype == "int8"
    assert clone._draft_model is draft.model
    h = clone.submit(PROMPT, 4)
    clone.run_until_idle()
    assert h.result().shape == (len(PROMPT) + 4,)

    # without quantization, the clone's greedy spec output is bit-equal
    eng2 = ServingEngine(fitted, num_slots=2, max_len=24, spec_draft=fitted)
    clone2 = eng2.respawn_clone()
    h2 = clone2.submit(PROMPT, 8)
    clone2.run_until_idle()
    np.testing.assert_array_equal(h2.result(),
                                  _want(fitted, h2, max_len=24))


def test_defaults_unchanged_no_spec_counters_move(fitted):
    """spec_draft=None / quantize=None / kv_dtype=None: the PR 9 engine,
    bit for bit — pools keep their dtypes and the speculation counters
    never move."""
    eng = ServingEngine(fitted, num_slots=2, max_len=24)
    assert "ks" not in eng.caches[2] and eng.d_caches is None
    h = eng.submit(PROMPT, 8)
    eng.run_until_idle()
    np.testing.assert_array_equal(h.result(), _want(fitted, h, max_len=24))
    assert eng.stats["drafted"] == 0 and eng.stats["verify_calls"] == 0


# ---------------------------------------------------------------------------
# paged KV pool + radix prefix sharing (PR 12)
# ---------------------------------------------------------------------------

def _assert_no_block_leaks(eng):
    """Every retirement path must return the pool to baseline: no block
    held by a live request, and free + cached + private == arena."""
    assert eng.kv_blocks_in_use == 0
    assert eng._pool.check_conservation()


@pytest.mark.paged
@pytest.mark.parametrize("kw", [
    {},                                                       # greedy
    {"temperature": 0.7, "seed": 11},                         # plain sample
    {"temperature": 0.7, "top_k": 5, "top_p": 0.9, "seed": 11},
])
def test_paged_lone_request_matches_dense_and_generate(fitted, kw):
    """The paged pool is a storage relayout, not a numerics change: a lone
    request through block-table decode/prefill emits tokens identical to
    the dense engine and to offline generate."""
    eng = ServingEngine(fitted, num_slots=3, max_len=24, paged=True,
                        block_size=4)
    h = eng.submit(PROMPT, 8, **kw)
    eng.run_until_idle()
    np.testing.assert_array_equal(h.result(), _want(fitted, h, max_len=24))
    _assert_no_block_leaks(eng)


@pytest.mark.paged
def test_paged_rolling_lone_request_matches_generate(windowed):
    """Rolling paged pools: the ring lives in blocks behind the table
    (fixed per-slot allocation, no sharing) — tokens identical to rolling
    generate, bucketed AND chunked admission."""
    eng = ServingEngine(windowed, num_slots=2, max_len=24, rolling=True,
                        paged=True, block_size=4)
    h = eng.submit(PROMPT, 10)
    eng.run_until_idle()
    want = np.asarray(windowed.generate(h.prompt[None], 10, max_len=24,
                                        rolling=True))[0]
    np.testing.assert_array_equal(h.result(), want)
    _assert_no_block_leaks(eng)
    eng = ServingEngine(windowed, num_slots=2, max_len=28, rolling=True,
                        paged=True, block_size=4, prefill_chunk=4)
    long_p = (np.arange(1, 14, dtype=np.int32) * 5) % VOCAB
    h = eng.submit(long_p, 6, temperature=0.5, seed=7)
    eng.run_until_idle()
    want = np.asarray(windowed.generate(
        h.prompt[None], 6, max_len=28, rolling=True,
        temperature=0.5, rng=h.key))[0]
    np.testing.assert_array_equal(h.result(), want)
    _assert_no_block_leaks(eng)


@pytest.mark.paged
def test_paged_spec_greedy_identity_and_sampled_determinism(fitted):
    """Speculation on the paged pool: greedy committed chains stay the
    target argmax chain (== generate), and sampled rows reproduce the
    dense speculative engine's draws exactly (same key-fold schedule —
    the block tables change storage, not randomness)."""
    eng = ServingEngine(fitted, num_slots=3, max_len=24, paged=True,
                        block_size=4, spec_draft=fitted, spec_len=3)
    g = eng.submit(PROMPT, 8)
    s = eng.submit(np.array([5, 6, 7], np.int32), 8, temperature=0.7,
                   seed=5)
    eng.run_until_idle()
    np.testing.assert_array_equal(g.result(), _want(fitted, g, max_len=24))
    dense = ServingEngine(fitted, num_slots=3, max_len=24,
                          spec_draft=fitted, spec_len=3)
    s2 = dense.submit(np.array([5, 6, 7], np.int32), 8, temperature=0.7,
                      seed=5)
    dense.run_until_idle()
    np.testing.assert_array_equal(s.result(), s2.result())
    assert eng.stats["drafted"] > 0
    _assert_no_block_leaks(eng)


@pytest.mark.paged
def test_paged_prefix_sharing_reuses_blocks_exactly(fitted):
    """The tentpole contract: a second admission sharing a full-block
    prefix walks the trie, SHARES the matched blocks (allocation shrinks
    by exactly the reuse — byte-accounted, not just faster), prefills
    only its suffix, and still emits generate-identical tokens."""
    eng = ServingEngine(fitted, num_slots=2, max_len=28, paged=True,
                        block_size=4)
    prefix = (np.arange(12) % VOCAB).astype(np.int32)      # 3 full blocks
    h1 = eng.submit(np.concatenate([prefix, [1, 2]]).astype(np.int32), 6)
    eng.run_until_idle()
    alloc1 = eng.stats["blocks_allocated"]
    pf1 = eng.stats["prefill_tokens"]
    h2 = eng.submit(np.concatenate([prefix, [5, 6]]).astype(np.int32), 6,
                    temperature=0.5, seed=3)
    eng.run_until_idle()
    np.testing.assert_array_equal(h1.result(), _want(fitted, h1,
                                                     max_len=28))
    np.testing.assert_array_equal(h2.result(), _want(fitted, h2,
                                                     max_len=28))
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["prefix_hit_tokens"] == 12
    assert eng.stats["blocks_reused"] == 3
    # h2 allocated 3 fewer fresh blocks than a cold admission would
    assert (eng.stats["blocks_allocated"] - alloc1
            == alloc1 - eng.stats["blocks_reused"])
    # and prefilled only its 2-token suffix
    assert eng.stats["prefill_tokens"] - pf1 == 2
    _assert_no_block_leaks(eng)


@pytest.mark.paged
def test_paged_cow_copies_partial_boundary_block(fitted):
    """A prompt matching a cached chain PARTIALLY into a block gets a
    copy-on-write duplicate: the original stays shared/cached, the new
    request writes its divergent suffix into its own copy — outputs
    exact on both sides."""
    eng = ServingEngine(fitted, num_slots=2, max_len=28, paged=True,
                        block_size=4)
    p1 = (np.arange(10) % VOCAB).astype(np.int32)  # 2 full + 2 boundary
    h1 = eng.submit(np.concatenate([p1, [1, 2]]).astype(np.int32), 4)
    eng.run_until_idle()
    h2 = eng.submit(np.concatenate([p1, [9, 9]]).astype(np.int32), 4)
    eng.run_until_idle()
    np.testing.assert_array_equal(h2.result(), _want(fitted, h2,
                                                     max_len=28))
    assert eng.stats["cow_copies"] == 1
    assert eng.stats["prefix_hit_tokens"] == 10   # 8 shared + 2 copied
    _assert_no_block_leaks(eng)


@pytest.mark.paged
def test_paged_chunked_prefill_and_prefix_hit_skips_chunks(fitted):
    """Paged chunked prefill writes straight into the request's blocks
    (no staging — they are private until the final chunk installs the
    table), stays generate-identical, and a later admission hitting the
    long prompt's prefix skips the chunked path entirely (suffix fits a
    bucket)."""
    eng = ServingEngine(fitted, num_slots=2, max_len=32, paged=True,
                        block_size=4, prefill_chunk=4)
    long_p = (np.arange(1, 14, dtype=np.int32) * 3) % VOCAB  # 13 tokens
    h = eng.submit(long_p, 8)
    h2 = eng.submit(PROMPT, 4)
    eng.run_until_idle()
    assert eng.stats["prefill_chunks"] == 4
    np.testing.assert_array_equal(h.result(), _want(fitted, h, max_len=32))
    np.testing.assert_array_equal(h2.result(), _want(fitted, h2,
                                                     max_len=32))
    chunks0 = eng.stats["prefill_chunks"]
    h3 = eng.submit(np.concatenate([long_p[:12], [9, 9]]).astype(np.int32),
                    6)
    eng.run_until_idle()
    np.testing.assert_array_equal(h3.result(), _want(fitted, h3,
                                                     max_len=32))
    assert eng.stats["prefill_chunks"] == chunks0  # hit → bucket path
    assert eng.stats["prefix_hits"] >= 1
    _assert_no_block_leaks(eng)


@pytest.mark.paged
def test_paged_capacity_pressure_evicts_and_backpressures(fitted):
    """A deliberately tiny arena: admissions queue when live requests
    hold every block, cached refcount-0 chains are LRU-evicted to make
    room, every request still completes exactly, and the pool returns to
    baseline."""
    eng = ServingEngine(fitted, num_slots=4, max_len=24, paged=True,
                        block_size=4, kv_blocks=8).warmup()
    hs = [eng.submit((np.arange(i + 1, i + 5) % VOCAB).astype(np.int32),
                     6, seed=i) for i in range(6)]
    eng.run_until_idle()
    for h in hs:
        np.testing.assert_array_equal(h.result(), _want(fitted, h,
                                                        max_len=24))
    assert eng.stats["blocks_evicted"] > 0
    _assert_no_block_leaks(eng)


@pytest.mark.paged
def test_paged_transfer_discipline_zero_h2d_one_d2h(fitted):
    """PR 9's decode transfer contract survives paging: block tables are
    device-resident (installed by the prefill program, nulled by the
    retire program), so a decode-only iteration still uploads nothing
    and reads back exactly the sampled token row."""
    eng = ServingEngine(fitted, num_slots=2, max_len=24, paged=True,
                        block_size=4).warmup()
    h = eng.submit(PROMPT, 14)
    eng.step()
    orig = eng._decode_fn

    def checked(*args):
        leaves = jax.tree_util.tree_leaves(args)
        assert all(isinstance(a, jax.Array) for a in leaves), \
            "paged decode step received a host array (implicit h2d)"
        return orig(*args)

    eng._decode_fn = checked
    h0, d0 = eng.stats["h2d_transfers"], eng.stats["d2h_transfers"]
    for _ in range(6):
        eng.step()
    assert eng.stats["h2d_transfers"] - h0 == 0
    assert eng.stats["d2h_transfers"] - d0 == 6
    eng.run_until_idle()
    np.testing.assert_array_equal(h.result(), _want(fitted, h, max_len=24))


@pytest.mark.paged
def test_paged_warmup_precompiles_every_program(fitted, monkeypatch):
    """warmup() on a paged engine compiles the block-table decode, every
    bucket's paged prefill, the in-arena chunk programs, and the COW
    copy — live traffic (prefix hits and COW included) re-traces
    nothing."""
    calls = []
    orig = decode._forward

    def counting(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(decode, "_forward", counting)
    eng = ServingEngine(fitted, num_slots=2, max_len=24, paged=True,
                        block_size=4, prefill_chunk=4,
                        prefills_per_step=2).warmup()
    traced = len(calls)
    assert traced > 0
    h1 = eng.submit(np.array([2, 3, 4], np.int32), 3)       # bucket batch
    h2 = eng.submit((np.arange(1, 12, dtype=np.int32)) % VOCAB, 3)  # chunks
    eng.run_until_idle()
    h3 = eng.submit((np.arange(1, 11, dtype=np.int32)) % VOCAB, 3)  # COW hit
    eng.run_until_idle()
    assert h1.done and h2.done and h3.done
    assert eng.stats["prefix_hits"] >= 1
    assert len(calls) == traced, "paged live traffic re-traced a program"


@pytest.mark.paged
def test_paged_respawn_clone_fresh_trie_same_arena(fitted):
    """respawn_clone() carries the paged knobs and arena SHAPE but builds
    a FRESH trie + allocator: cached chains index the dead pool's arena
    contents, which the clone does not share."""
    eng = ServingEngine(fitted, num_slots=2, max_len=24, paged=True,
                        block_size=4, kv_blocks=10)
    h = eng.submit(PROMPT, 4)
    eng.run_until_idle()
    assert h.done and eng._pool.cached_blocks() > 0
    clone = eng.respawn_clone()
    assert clone.paged and clone.block_size == 4 and clone.kv_blocks == 10
    assert clone._pool is not eng._pool
    assert clone._pool.cached_blocks() == 0
    assert clone.stats["prefix_hits"] == 0
    assert len(clone._pool.free) == 10
    h2 = clone.submit(PROMPT, 4)
    clone.run_until_idle()
    np.testing.assert_array_equal(h2.result(), h.result())


@pytest.mark.paged
def test_paged_knob_validation(fitted):
    with pytest.raises(ValueError, match="block_size"):
        ServingEngine(fitted, num_slots=1, max_len=24, paged=True,
                      block_size=0)
    with pytest.raises(ValueError, match="kv_blocks"):
        ServingEngine(fitted, num_slots=1, max_len=24, paged=True,
                      block_size=4, kv_blocks=2)   # can't hold one request


@pytest.mark.paged
def test_paged_default_off_is_dense(fitted):
    """paged=False (the default) builds the exact dense engine: no pool,
    no trie, per-slot cache rows, and zeroed paged stats."""
    eng = ServingEngine(fitted, num_slots=2, max_len=24)
    assert not eng.paged and eng._pool is None and eng.kv_blocks is None
    assert eng.kv_blocks_in_use is None
    assert eng.caches[2]["k"].shape[0] == 2     # (num_slots, max_len, ...)
    h = eng.submit(PROMPT, 6)
    eng.run_until_idle()
    np.testing.assert_array_equal(h.result(), _want(fitted, h, max_len=24))
    assert eng.stats["blocks_allocated"] == 0
    assert eng.stats["prefix_hits"] == 0


@pytest.mark.paged
def test_paged_pool_byte_accounting(fitted, trained):
    """kv_pool_bytes counts the arena (blocks + the null block), shrinks
    with kv_blocks, and the int8 arena pages codes + scales identically
    (fewer bytes than the f32 arena at the same block count)."""
    from distkeras_tpu.core import quant as quant_mod
    big = ServingEngine(fitted, num_slots=2, max_len=24, paged=True,
                        block_size=4)
    small = ServingEngine(fitted, num_slots=2, max_len=24, paged=True,
                          block_size=4, kv_blocks=6)
    assert small.kv_pool_bytes < big.kv_pool_bytes
    assert small.stats["kv_pool_bytes"] == small.kv_pool_bytes
    q8 = ServingEngine(fitted, num_slots=2, max_len=24, paged=True,
                       block_size=4, kv_dtype="int8")
    assert q8.kv_pool_bytes < big.kv_pool_bytes
    blk = quant_mod.kv_block_bytes(big.caches, big.block_size)
    assert blk * (big.kv_blocks + 1) == big.kv_pool_bytes
    # and the int8 paged engine still decodes exactly like the dense
    # int8 engine.  Both are lossy and they are NOT the same program: the
    # dense prefill attends its full-precision rows and quantizes on
    # commit (_commit_rows), the paged prefill attends the quantized
    # arena (shared prefix blocks exist in no other form).  So equality
    # is held on the trained model, where both must also keep the rule
    q8 = ServingEngine(trained, num_slots=2, max_len=24, paged=True,
                       block_size=4, kv_dtype="int8")
    h = q8.submit(PROMPT, 6)
    q8.run_until_idle()
    dense8 = ServingEngine(trained, num_slots=2, max_len=24,
                           kv_dtype="int8")
    h2 = dense8.submit(PROMPT, 6)
    dense8.run_until_idle()
    np.testing.assert_array_equal(h.result(), h2.result())
    np.testing.assert_array_equal(
        h.result()[len(PROMPT):], (PROMPT[-1] + 1 + np.arange(6)) % VOCAB)
    _assert_no_block_leaks(q8)


@pytest.mark.paged
def test_paged_same_iteration_batch_admissions_exact(fitted):
    """prefills_per_step > 1: same-pass admissions sharing a prefix do
    NOT cross-match (the epoch guard — a same-pass matcher could land in
    a bucket group dispatched before the writer's), but every output is
    still exact and later admissions DO hit the published chains."""
    eng = ServingEngine(fitted, num_slots=4, max_len=28, paged=True,
                        block_size=4, prefills_per_step=4)
    prefix = (np.arange(8) % VOCAB).astype(np.int32)
    hs = [eng.submit(np.concatenate([prefix, [i]]).astype(np.int32), 5,
                     seed=i) for i in range(4)]
    eng.run_until_idle()
    assert eng.stats["prefix_hits"] == 0          # same pass: no matches
    for h in hs:
        np.testing.assert_array_equal(h.result(), _want(fitted, h,
                                                        max_len=28))
    h5 = eng.submit(np.concatenate([prefix, [9]]).astype(np.int32), 5)
    eng.run_until_idle()
    np.testing.assert_array_equal(h5.result(), _want(fitted, h5,
                                                     max_len=28))
    assert eng.stats["prefix_hits"] == 1          # later pass: hit
    _assert_no_block_leaks(eng)
