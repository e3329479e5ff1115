"""Continuous-batching serving engine (distkeras_tpu/serving.py).

The invariants pinned here are the engine's whole contract:

 - a lone request through the engine emits tokens BIT-IDENTICAL to offline
   ``generate`` under the same seed/params (greedy, sampled top-k/top-p,
   eos stopping, rolling-window caches) — the slot pool is an execution
   strategy, never a numerics change;
 - the slot lifecycle: admission → prefill → decode → eos/length
   retirement → slot reuse, including a mixed-length batch where a short
   request retires and a queued one back-fills its slot MID-RUN (the
   continuous-batching property itself);
 - bounded-queue backpressure (``QueueFull``), in process and over the
   wire;
 - the per-row ``decode_step``/sampling substrate matches the scalar path
   row for row.
"""

import re
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distkeras_tpu.core import decode
from distkeras_tpu.core.model import FittedModel, serialize_model
from distkeras_tpu.models import transformer_lm
from distkeras_tpu.serving import (QueueFull, ServingClient, ServingEngine,
                                   ServingServer)

VOCAB = 17


def _fitted(seed=0, **kw):
    model = transformer_lm(vocab_size=VOCAB, seq_len=32, d_model=16,
                           num_heads=2, num_layers=2, mlp_dim=32,
                           compute_dtype="float32", **kw)
    params = model.init(jax.random.PRNGKey(seed), (32,))
    return FittedModel(model, params)


@pytest.fixture(scope="module")
def fitted():
    return _fitted()


PROMPT = np.array([3, 4, 5, 6], np.int32)


# ---------------------------------------------------------------------------
# bit-identity with offline generate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    {},                                                       # greedy
    {"temperature": 0.7, "seed": 11},                         # plain sample
    {"temperature": 0.7, "top_k": 5, "top_p": 0.9, "seed": 11},
])
def test_lone_request_bit_identical_to_generate(fitted, kw):
    eng = ServingEngine(fitted, num_slots=3, max_len=24)
    h = eng.submit(PROMPT, 8, **kw)
    eng.run_until_idle()
    gkw = dict(kw)
    seed = gkw.pop("seed", None)
    if seed is not None:
        gkw["rng"] = jax.random.PRNGKey(seed)
    want = np.asarray(fitted.generate(PROMPT[None], 8, max_len=24, **gkw))[0]
    np.testing.assert_array_equal(h.result(), want)


def test_eos_stopping_matches_generate(fitted):
    greedy = np.asarray(fitted.generate(PROMPT[None], 8, max_len=24))[0]
    eos = int(greedy[len(PROMPT) + 2])  # a token greedy WILL emit
    eng = ServingEngine(fitted, num_slots=2, max_len=24)
    h = eng.submit(PROMPT, 8, eos_id=eos, pad_id=1)
    eng.run_until_idle()
    want = np.asarray(fitted.generate(PROMPT[None], 8, eos_id=eos, pad_id=1,
                                      max_len=24))[0]
    np.testing.assert_array_equal(h.result(), want)
    assert h.finish == "eos"
    assert len(h.tokens) < 8  # retired early; result() pads to num_steps


def test_rolling_slots_bit_identical(fitted):
    windowed = _fitted(seed=1, attention_window=6)
    eng = ServingEngine(windowed, num_slots=2, max_len=24, rolling=True)
    long_p = np.arange(1, 8, dtype=np.int32) % VOCAB
    h1 = eng.submit(long_p, 10, temperature=0.6, seed=9)
    h2 = eng.submit(np.array([1, 2], np.int32), 6)
    eng.run_until_idle()
    w1 = np.asarray(windowed.generate(long_p[None], 10, temperature=0.6,
                                      rng=jax.random.PRNGKey(9),
                                      rolling=True, max_len=24))[0]
    w2 = np.asarray(windowed.generate(np.array([[1, 2]], np.int32), 6,
                                      rolling=True, max_len=24))[0]
    np.testing.assert_array_equal(h1.result(), w1)
    np.testing.assert_array_equal(h2.result(), w2)
    # the pool really is a ring: W slots per block, not max_len
    assert eng.caches[2]["k"].shape[1] == 6


# ---------------------------------------------------------------------------
# slot lifecycle: admission → prefill → decode → retirement → reuse
# ---------------------------------------------------------------------------

def test_mixed_length_batch_backfills_mid_run(fitted):
    """2 slots, 3 requests: the short one retires first and the queued
    third back-fills its slot while the long one is still decoding."""
    eng = ServingEngine(fitted, num_slots=2, max_len=24)
    long_h = eng.submit(np.array([1, 2, 3], np.int32), 14)
    short_h = eng.submit(np.array([4, 5], np.int32), 3)
    queued_h = eng.submit(np.array([6, 7, 8, 9], np.int32), 5)
    assert eng.queue_depth == 3 and not eng._active.any()
    eng.run_until_idle()
    # zero requests lost; outputs still match offline generate
    for h in (long_h, short_h, queued_h):
        assert h.finish == "length"
        want = np.asarray(fitted.generate(h.prompt[None], h.num_steps,
                                          max_len=24))[0]
        np.testing.assert_array_equal(h.result(), want)
    # the third request reused the short one's slot, MID-run of the long one
    assert queued_h.slot == short_h.slot
    assert queued_h.started_at < long_h.finished_at
    # every slot served at least one request; the short slot served two
    assert all(n >= 1 for n in eng.stats["slot_requests"])
    assert eng.stats["slot_requests"][short_h.slot] == 2
    assert eng.stats["requests_completed"] == 3
    assert eng.slot_occupancy > 0.5


def test_many_requests_zero_lost_every_slot_reused(fitted):
    eng = ServingEngine(fitted, num_slots=2, max_len=24)
    rng = np.random.default_rng(0)
    handles = []
    for i in range(7):
        p_len = int(rng.integers(1, 6))
        steps = int(rng.integers(1, 8))
        prompt = rng.integers(0, VOCAB, p_len).astype(np.int32)
        handles.append(eng.submit(prompt, steps, temperature=0.5,
                                  seed=100 + i))
    eng.run_until_idle()
    assert eng.stats["requests_completed"] == 7  # zero lost
    assert all(n >= 2 for n in eng.stats["slot_requests"])  # all reused
    for h in handles:
        want = np.asarray(fitted.generate(h.prompt[None], h.num_steps,
                                          temperature=0.5, rng=h.key,
                                          max_len=24))[0]
        np.testing.assert_array_equal(h.result(), want)


def test_retired_slot_state_is_cleared(fitted):
    eng = ServingEngine(fitted, num_slots=1, max_len=24)
    h = eng.submit(PROMPT, 3, temperature=0.9, top_k=3, seed=5)
    eng.run_until_idle()
    assert h.done and eng._handles[0] is None
    assert not eng._active.any()
    assert not bool(eng._dev_act[0])
    assert eng._free == [0]
    # a greedy follow-up through the same slot is unpolluted by the
    # previous occupant's sampling params
    h2 = eng.submit(PROMPT, 4)
    eng.run_until_idle()
    want = np.asarray(fitted.generate(PROMPT[None], 4, max_len=24))[0]
    np.testing.assert_array_equal(h2.result(), want)


def test_num_steps_zero_completes_without_slot(fitted):
    eng = ServingEngine(fitted, num_slots=1, max_len=24)
    h = eng.submit(PROMPT, 0)
    assert h.done and h.finish == "empty"
    np.testing.assert_array_equal(h.result(), PROMPT)
    assert eng.queue_depth == 0


# ---------------------------------------------------------------------------
# admission queue + backpressure
# ---------------------------------------------------------------------------

def test_queue_backpressure_sheds(fitted):
    eng = ServingEngine(fitted, num_slots=1, max_len=24, queue_capacity=2)
    eng.submit(PROMPT, 4)
    eng.submit(PROMPT, 4)
    with pytest.raises(QueueFull):
        eng.submit(PROMPT, 4, block=False)
    with pytest.raises(QueueFull):
        eng.submit(PROMPT, 4, timeout=0.05)  # blocking, bounded wait
    assert eng.stats["requests_rejected"] == 2
    eng.run_until_idle()
    assert eng.stats["requests_completed"] == 2


def test_blocking_submit_unblocks_when_queue_drains(fitted):
    eng = ServingEngine(fitted, num_slots=1, max_len=24, queue_capacity=1)
    eng.submit(PROMPT, 2)
    results = []

    def producer():
        results.append(eng.submit(PROMPT, 2, timeout=10.0))

    t = threading.Thread(target=producer)
    t.start()
    eng.run_until_idle()   # drains the queue, freeing capacity
    t.join(timeout=10.0)
    assert not t.is_alive() and len(results) == 1
    eng.run_until_idle()
    assert results[0].done


def test_submit_validation(fitted):
    eng = ServingEngine(fitted, num_slots=1, max_len=16)
    with pytest.raises(ValueError, match="exceeds the engine's max_len"):
        eng.submit(np.arange(10, dtype=np.int32) % VOCAB, 10)
    with pytest.raises(ValueError, match="1-D"):
        eng.submit(PROMPT[None], 4)
    with pytest.raises(ValueError, match="top_k"):
        eng.submit(PROMPT, 4, temperature=0.5, top_k=0)
    with pytest.raises(ValueError, match="vocabulary"):
        eng.submit(PROMPT, 4, eos_id=VOCAB + 3)
    with pytest.raises(ValueError, match="max_len"):
        ServingEngine(fitted, num_slots=1, max_len=64)  # > positional range


# ---------------------------------------------------------------------------
# background thread + wire server
# ---------------------------------------------------------------------------

def test_background_thread_drives_requests(fitted):
    with ServingEngine(fitted, num_slots=2, max_len=24) as eng:
        h = eng.submit(PROMPT, 6)
        assert h.wait(timeout=30.0)
    want = np.asarray(fitted.generate(PROMPT[None], 6, max_len=24))[0]
    np.testing.assert_array_equal(h.result(), want)


def test_wire_server_roundtrip_and_streaming(fitted, server_core):
    with ServingServer(ServingEngine(fitted, num_slots=2, max_len=24)) as srv:
        with ServingClient(*srv.addr) as c:
            rid = c.submit(PROMPT, 6, temperature=0.7, top_k=5, seed=11)
            chunks, final = [], None
            for tokens, done in c.stream(rid):
                chunks.append(tokens)
                if done is not None:
                    final = done
            want = np.asarray(fitted.generate(
                PROMPT[None], 6, temperature=0.7, top_k=5,
                rng=jax.random.PRNGKey(11), max_len=24))[0]
            np.testing.assert_array_equal(final["row"], want)
            # the streamed chunks concatenate to the emitted tokens
            np.testing.assert_array_equal(np.concatenate(chunks),
                                          want[len(PROMPT):])
            assert final["finish"] == "length"
            # one-call form on the same connection
            np.testing.assert_array_equal(c.generate(PROMPT, 6),
                np.asarray(fitted.generate(PROMPT[None], 6, max_len=24))[0])


def test_wire_server_backpressure_reply(fitted, server_core):
    eng = ServingEngine(fitted, num_slots=1, max_len=24, queue_capacity=1)
    with ServingServer(eng) as srv:
        with ServingClient(*srv.addr) as c:
            # saturate: the engine thread may drain some, so push until shed
            with pytest.raises(QueueFull):
                for _ in range(200):
                    c.submit(PROMPT, 12)
    assert eng.stats["requests_rejected"] >= 1


def test_wire_server_bad_request_reply(fitted, server_core):
    with ServingServer(ServingEngine(fitted, num_slots=1, max_len=16)) as srv:
        with ServingClient(*srv.addr) as c:
            with pytest.raises(ValueError, match="max_len"):
                c.submit(np.arange(12, dtype=np.int32) % VOCAB, 12)
            with pytest.raises(ValueError, match="unknown id"):
                list(c.stream(999))


# ---------------------------------------------------------------------------
# hot weight reload (stretch: training and serving share one deployment)
# ---------------------------------------------------------------------------

def test_hot_reload_pulls_fresh_center(fitted):
    from distkeras_tpu.parameter_servers import (DeltaParameterServer,
                                                 SocketParameterServer)
    blob = serialize_model(fitted.model, fitted.params)
    ps = SocketParameterServer(DeltaParameterServer(blob))
    ps.start()
    try:
        eng = ServingEngine(_fitted(), num_slots=2, max_len=24)
        eng.attach_ps("127.0.0.1", ps.port, every=1)
        before = [w.copy() for w in eng.model.get_weights(eng.params)]
        ps.ps.handle_commit(
            {"delta": [np.ones_like(w) for w in blob["weights"]]})
        eng.submit(PROMPT, 4)
        eng.run_until_idle()
        assert eng.stats["weight_reloads"] >= 1
        after = eng.model.get_weights(eng.params)
        assert any((np.asarray(a) != b).any()
                   for a, b in zip(after, before))
        eng.stop()
    finally:
        ps.stop()


# ---------------------------------------------------------------------------
# engine-backed ModelPredictor route
# ---------------------------------------------------------------------------

def test_model_predictor_engine_route(fitted):
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.predictors import ModelPredictor

    prompts = np.stack([PROMPT, PROMPT[::-1].copy(), (PROMPT + 1) % VOCAB])
    ds = Dataset({"features": prompts})
    eng = ServingEngine(fitted, num_slots=2, max_len=24)
    pred = ModelPredictor(fitted, engine=eng, num_steps=5,
                          generate_kwargs={"temperature": 0.6, "seed": 3})
    out = pred.predict(ds)["prediction"]
    assert out.shape == (3, len(PROMPT) + 5)
    for row, prompt in zip(out, prompts):  # per-request generate parity
        want = np.asarray(fitted.generate(
            prompt[None], 5, temperature=0.6,
            rng=jax.random.PRNGKey(3), max_len=24))[0]
        np.testing.assert_array_equal(row, want)
    assert eng._thread is None  # predictor stopped the thread it started


def test_model_predictor_default_path_unchanged(fitted):
    """No engine constructed → the original sharded-numpy forward, same
    values as Sequential.predict (the defaults-bit-identical gate)."""
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.predictors import ModelPredictor

    ds = Dataset({"features": np.stack([PROMPT, (PROMPT + 2) % VOCAB])})
    out = ModelPredictor(fitted, mesh=None).predict(ds)["prediction"]
    want = fitted.model.predict(fitted.params,
                                np.asarray(ds["features"]))
    np.testing.assert_array_equal(out, want)


def test_model_predictor_engine_needs_num_steps(fitted):
    from distkeras_tpu.predictors import ModelPredictor
    eng = ServingEngine(fitted, num_slots=1, max_len=24)
    with pytest.raises(ValueError, match="num_steps"):
        ModelPredictor(fitted, engine=eng)


# ---------------------------------------------------------------------------
# per-row decode substrate (the satellite fix in core/decode.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("positional", ["learned", "rope"])
def test_per_row_positions_match_scalar_decode(positional):
    fm = _fitted(seed=2, positional=positional)
    model, params = fm.model, fm.params
    prompt = np.array([[3, 4, 5, 6], [7, 8, 9, 1]], np.int32)
    want = np.asarray(fm.generate(prompt, 6, max_len=16))
    caches = decode.init_cache(model, 2, 16)
    logits, caches = decode._forward(model, params, caches,
                                     jnp.asarray(prompt), 0)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    got = [tok]
    pos = jnp.array([4, 4], jnp.int32)   # per-row vector, equal values
    step = jax.jit(lambda p, c, t, q: decode.decode_step(model, p, c, t, q))
    for i in range(5):
        lg, caches = step(params, caches, tok, pos + i)
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        got.append(tok)
    np.testing.assert_array_equal(
        np.stack([np.asarray(t) for t in got], 1), want[:, 4:])


def test_per_row_multi_token_forward_matches_chain():
    """Per-row positions with L > 1 (PR 11's speculative verify): one
    batched forward over L tokens at each row's own offset produces the
    same logits as L single-token per-row steps — the substrate the
    engine's draft-then-verify round stands on."""
    fm = _fitted(seed=2)
    prompt = jnp.asarray([[3, 4, 5], [9, 2, 7]], jnp.int32)
    caches = decode.init_cache(fm.model, 2, 16)
    logits, caches = decode._forward(fm.model, fm.params, caches, prompt, 0)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    pos = jnp.array([3, 3], jnp.int32)
    chain, toks, cc = [], [tok], caches
    for i in range(3):
        lg, cc = decode.decode_step(fm.model, fm.params, cc, toks[-1],
                                    pos + i)
        chain.append(lg)
        toks.append(jnp.argmax(lg, -1).astype(jnp.int32))
    fed = jnp.stack(toks[:3], axis=1)                          # (2, 3)
    multi, _ = decode._forward(fm.model, fm.params, caches, fed, pos)
    for i in range(3):
        np.testing.assert_allclose(np.asarray(multi[:, i]),
                                   np.asarray(chain[i]),
                                   rtol=2e-5, atol=2e-5)


def test_batched_sampler_matches_scalar_rows():
    """sample_logits_batched row-for-row == sample_logits with that row's
    scalar params (the engine's bit-identity substrate)."""
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.standard_normal((4, VOCAB)), jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(4)])
    positions = jnp.array([3, 9, 1, 7])
    temp = jnp.array([0.0, 0.5, 0.7, 1.3], jnp.float32)
    topk = jnp.array([0, 4, 4, 0], jnp.int32)
    topp = jnp.array([0.0, 0.0, 0.9, 0.6], jnp.float32)
    got = np.asarray(jax.jit(decode.sample_logits_batched)(
        logits, positions, temp, keys, topk, topp))
    for r in range(4):
        want = decode.sample_logits(
            logits[r:r + 1], int(positions[r]), float(temp[r]),
            jax.random.PRNGKey(r),
            int(topk[r]) or None,
            float(topp[r]) or None)
        assert got[r] == int(np.asarray(want)[0]), f"row {r}"


def _two_sort_filter(logits, top_k, top_p):
    """``filter_logits_batched`` as it stood before it sorted once: the
    k-th value from one full sort, the nucleus from a second one over the
    k-filtered logits, whatever the rows ask for.  The plain reference."""
    v = logits.shape[-1]
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    k = jnp.clip(top_k, 1, v)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    logits = jnp.where((top_k > 0)[:, None] & (logits < kth),
                       -jnp.inf, logits)
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    kept = jnp.sum((cum - probs) < top_p[:, None], axis=-1, keepdims=True)
    cut = jnp.take_along_axis(sorted_desc, jnp.maximum(kept, 1) - 1, axis=-1)
    return jnp.where((top_p > 0)[:, None] & (logits < cut),
                     -jnp.inf, logits)


# (temperature, top_k, top_p) a row; 0 = off, as the engine's slot arrays
_GREEDY, _PLAIN = (0.0, 0, 0.0), (0.8, 0, 0.0)
_K, _P, _KP = (0.7, 4, 0.0), (1.3, 0, 0.6), (0.5, 3, 0.9)
SAMPLER_CASES = {
    "all_greedy": [_GREEDY] * 4,
    "all_sampling_no_filter": [_PLAIN, (0.5, 0, 0.0), (1.3, 0, 0.0), _PLAIN],
    "top_k_only": [_K, (0.7, 1, 0.0), (1.0, 9, 0.0), _K],
    "top_p_only": [_P, (0.7, 0, 0.1), (1.0, 0, 1.0), _P],
    "top_k_and_top_p": [_KP, (0.7, 5, 0.5), (1.0, 2, 1.0), _KP],
    "mixed_batch": [_GREEDY, _PLAIN, _K, _P, _KP, _GREEDY],
    # logits in half steps: exact ties at the k-th value and at the
    # nucleus' cut; top_k at and past the vocabulary keeps every token
    "ties_and_k_past_vocab": [(1.0, 3, 0.0), (1.0, 5, 0.7), (1.0, VOCAB, 0.9),
                              (1.0, 4 * VOCAB, 0.0), (1.0, 4 * VOCAB, 0.5),
                              (1.0, 6, 1.0)],
}


@pytest.mark.parametrize("case", list(SAMPLER_CASES))
def test_batched_sampler_does_what_its_rows_ask(case):
    """Whatever mix of rows a call carries (so whichever branch of the
    sampler's two ``lax.cond``s it takes), ``filter_logits_batched`` equals
    the two-sort formula bit for bit and ``sample_logits_batched`` equals
    the scalar ``sample_logits`` row for row."""
    rows = SAMPLER_CASES[case]
    b = len(rows)
    rng = np.random.default_rng(7)
    raw = rng.standard_normal((b, VOCAB)) * 2
    if case == "ties_and_k_past_vocab":
        raw = np.round(raw * 2) / 2
    logits = jnp.asarray(raw, jnp.float32)
    temp = jnp.asarray([r[0] for r in rows], jnp.float32)
    topk = jnp.asarray([r[1] for r in rows], jnp.int32)
    topp = jnp.asarray([r[2] for r in rows], jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(i + 20) for i in range(b)])
    positions = jnp.arange(b) * 3 + 1

    scaled = logits / jnp.where(temp > 0, temp, 1.0)[:, None]
    np.testing.assert_array_equal(
        np.asarray(jax.jit(decode.filter_logits_batched)(scaled, topk, topp)),
        np.asarray(jax.jit(_two_sort_filter)(scaled, topk, topp)))

    got = np.asarray(jax.jit(decode.sample_logits_batched)(
        logits, positions, temp, keys, topk, topp))
    assert got.dtype == np.int32
    for r, (t, k, p) in enumerate(rows):
        want = decode.sample_logits(logits[r:r + 1], int(positions[r]), t,
                                    jax.random.PRNGKey(r + 20), k or None,
                                    p or None)
        assert got[r] == int(np.asarray(want)[0]), f"row {r}"


def _computations(hlo):
    """A compiled module's text by computation: ``{name: lines}``, and for
    every ``conditional`` the computation it sits in and those it branches
    into, ``[(holder, branches)]``."""
    bodies, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
        if m:
            name = m.group(1)
            bodies[name] = []
        elif name is not None:
            bodies[name].append(line)
    conds = []
    for name, body in bodies.items():
        for line in body:
            if re.search(r"\sconditional\(", line):
                group, = re.findall(r"branch_computations=\{([^}]*)\}", line)
                conds.append((name, {n.strip().lstrip("%")
                                     for n in group.split(",")}))
    return bodies, conds


def test_compiled_sampler_sorts_once_inside_a_conditional():
    """The compiled program holds ONE sort (the second is derived from the
    first), in a branch of a conditional (a call whose rows do not filter
    never runs it) that itself sits in a branch of the other (a greedy call
    runs neither the divide nor the draw): the gates survive the compiler,
    none is flattened into a select."""
    b = 4
    args = (jnp.zeros((b, VOCAB), jnp.float32), jnp.zeros((b,), jnp.int32),
            jnp.zeros((b,), jnp.float32), jnp.zeros((b, 2), jnp.uint32),
            jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.float32))
    hlo = jax.jit(decode.sample_logits_batched).lower(
        *args).compile().as_text()
    bodies, conds = _computations(hlo)
    is_sort = re.compile(r"=\s.*\ssort\(")
    sorts = [n for n, body in bodies.items() for line in body
             if is_sort.search(line)]
    assert len(sorts) == 1, sorts
    assert len(conds) == 2, conds
    (inner_at, inner), = [c for c in conds if sorts[0] in c[1]]
    (outer_at, outer), = [c for c in conds if inner_at in c[1]]
    assert outer_at.startswith("main") and outer != inner
    # the yardstick of that reading: the two-sort formula, ungated
    plain = jax.jit(_two_sort_filter).lower(
        args[0], args[4], args[5]).compile().as_text()
    assert len(is_sort.findall(plain)) == 2 and "conditional(" not in plain


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_a_retired_sampling_slot_costs_later_greedy_steps_nothing(
        fitted, monkeypatch, paged):
    """A ``top_p`` request retires while greedy requests go on in other
    slots.  Its slot keeps its temperature and ``top_p`` on the device
    (retirement clears the active flag alone), yet the decode step hands
    the sampler live rows only: every later step is a greedy one to the
    sampler (all-zero temperatures: no draw, no sort), to the host's
    counters and to the ``serve.decode_dispatch`` span, and every request's
    tokens are ``generate``'s."""
    temps, spans = [], []
    real = decode.sample_logits_batched

    def spy(logits, positions, temperature, rngs, top_k, top_p):
        jax.debug.callback(lambda t: temps.append(np.asarray(t)),
                           temperature)
        return real(logits, positions, temperature, rngs, top_k, top_p)

    monkeypatch.setattr(decode, "sample_logits_batched", spy)
    kw = dict(paged=True, block_size=4) if paged else {}
    eng = ServingEngine(fitted, num_slots=3, max_len=24, **kw)
    stamp = eng.account.phase      # the loop's phases open their spans here

    def record(name, **fields):
        spans.append(("serve." + name, fields))
        return stamp(name, **fields)

    monkeypatch.setattr(eng.account, "phase", record)
    long_a = eng.submit(PROMPT, 14)
    nucleus = eng.submit(PROMPT[::-1].copy(), 3, temperature=0.7, top_p=0.9,
                         seed=11)
    long_b = eng.submit(PROMPT + 1, 14)
    while nucleus.finish is None:
        assert eng.step()
    jax.effects_barrier()
    assert not long_a.done and not long_b.done
    stats = dict(eng.stats)
    assert stats["sampler_filter_steps"] >= 2
    assert stats["sampler_draw_steps"] == stats["sampler_filter_steps"]
    assert any(f["sample"] == "filter" for n, f in spans
               if n == "serve.decode_dispatch")
    del temps[:], spans[:]

    eng.run_until_idle()
    jax.effects_barrier()
    # the retired slot is stale on the device, not cleared
    stale, = np.flatnonzero(np.asarray(eng._dev_topp) > 0)
    assert np.asarray(eng._dev_temp)[stale] == np.float32(0.7)
    assert not np.asarray(eng._dev_act)[stale]
    steps = eng.stats["decode_steps"] - stats["decode_steps"]
    assert steps >= 8
    assert eng.stats["sampler_filter_steps"] == stats["sampler_filter_steps"]
    assert eng.stats["sampler_draw_steps"] == stats["sampler_draw_steps"]
    sent = [f for n, f in spans if n == "serve.decode_dispatch"]
    assert len(sent) == steps
    assert {f["sample"] for f in sent} == {"greedy"}
    seen = [t for t in temps if t.shape == (3,)]
    assert len(seen) == steps and not np.any(seen)

    for h, prompt in ((long_a, PROMPT), (long_b, PROMPT + 1)):
        np.testing.assert_array_equal(h.result(), np.asarray(
            fitted.generate(prompt[None], 14, max_len=24))[0])
    np.testing.assert_array_equal(nucleus.result(), np.asarray(
        fitted.generate(PROMPT[::-1][None], 3, max_len=24, temperature=0.7,
                        top_p=0.9, rng=jax.random.PRNGKey(11)))[0])


def test_speculative_round_tokens_unchanged_by_the_gated_filter(
        fitted, monkeypatch):
    """The speculative round warps through ``filter_logits_batched`` too
    (k draft steps, the verify, the bonus).  Greedy, filtered and
    unfiltered sampling rows side by side, then a second wave beside the
    first one's retired slots: every token equals what the round emits
    with the ungated two-sort formula in the filter's place."""
    def served():
        eng = ServingEngine(fitted, num_slots=4, max_len=28,
                            spec_draft=_fitted(seed=5), spec_len=3,
                            prefills_per_step=4)
        hs = [eng.submit(PROMPT, 12),
              eng.submit(PROMPT[::-1].copy(), 3, temperature=0.7, top_p=0.8,
                         seed=3),
              eng.submit(PROMPT + 1, 14, temperature=0.9, top_k=4, top_p=0.9,
                         seed=4),
              eng.submit(PROMPT + 2, 13, temperature=1.1, seed=5)]
        eng.run_until_idle()
        hs += [eng.submit(PROMPT + 3, 9),
               eng.submit(PROMPT + 4, 9, temperature=0.8, seed=6)]
        eng.run_until_idle()
        return [list(h.tokens) for h in hs], dict(eng.stats)

    got, stats = served()
    assert 0 < stats["sampler_filter_steps"] < stats["sampler_draw_steps"] \
        < stats["decode_steps"]
    monkeypatch.setattr(decode, "filter_logits_batched", _two_sort_filter)
    assert served()[0] == got


def test_generate_unchanged_by_sampling_factor():
    """The factored sample_logits left generate's defaults bit-identical:
    two invocations and a pre/post-refactor spot value agree."""
    fm = _fitted(seed=4)
    a = np.asarray(fm.generate(PROMPT[None], 8, temperature=0.7, top_k=4,
                               top_p=0.9, rng=jax.random.PRNGKey(0)))
    b = np.asarray(fm.generate(PROMPT[None], 8, temperature=0.7, top_k=4,
                               top_p=0.9, rng=jax.random.PRNGKey(0)))
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# paged-KV substrate (PR 12): block-table decode is a storage relayout
# ---------------------------------------------------------------------------

@pytest.mark.paged
def test_paged_decode_step_bit_identical_to_dense():
    """Raw substrate parity: the same prefill + decode chain through a
    dense (B, S) cache and through a flat block arena + block tables
    produces BIT-identical logits at every step (the paged serving
    engine's exactness rests on this)."""
    fm = _fitted(seed=6)
    model, params = fm.model, fm.params
    B, max_len, bs = 2, 16, 4
    nblocks = B * (max_len // bs)
    dense = decode.init_cache(model, B, max_len)
    arena = decode.init_paged_arena(model, nblocks, bs)
    bt = np.full((B, max_len // bs + 1), nblocks, np.int32)
    for r in range(B):
        bt[r, :max_len // bs] = np.arange(max_len // bs) + r * (
            max_len // bs)
    bt = jnp.asarray(bt)
    prompt = jnp.asarray(np.stack([PROMPT, PROMPT[::-1].copy()]))
    zero = jnp.zeros((B,), jnp.int32)
    ld, dense = decode._forward(model, params, dense, prompt, 0)
    pv = decode.PagedView(bt, bs, max_len, floor=zero,
                          ceil=jnp.full((B,), 4, jnp.int32),
                          qcap=jnp.full((B,), 3, jnp.int32))
    lp, arena = decode._forward(model, params, arena, prompt, zero,
                                paged=pv)
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))
    tok = jnp.argmax(ld[:, -1], axis=-1).astype(jnp.int32)
    pos = jnp.full((B,), 4, jnp.int32)
    pvd = decode.PagedView(bt, bs, max_len)
    for _ in range(6):
        ld, dense = decode.decode_step(model, params, dense, tok, pos)
        lp, arena = decode.decode_step(model, params, arena, tok, pos,
                                       paged=pvd)
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))
        tok = jnp.argmax(ld, axis=-1).astype(jnp.int32)
        pos = pos + 1


@pytest.mark.paged
def test_paged_write_floor_protects_shared_blocks():
    """The copy-on-write safety rail: writes below a row's ``floor`` land
    in the NULL block, so a sharer can run the full forward over a prompt
    whose prefix blocks belong to someone else without perturbing them."""
    fm = _fitted(seed=7)
    model, params = fm.model, fm.params
    bs = 4
    arena = decode.init_paged_arena(model, 4, bs)
    bt = jnp.asarray([[0, 1, 4]], np.int32)
    prompt = jnp.asarray(PROMPT[None])
    pv = decode.PagedView(bt, bs, 8, floor=jnp.full((1,), 4, jnp.int32),
                          ceil=jnp.full((1,), 8, jnp.int32))
    li = [i for i, c in enumerate(arena) if c is not None][0]
    before = np.asarray(arena[li]["k"][:bs])       # block 0 (the "shared")
    # the suffix forward starts AT the floor, exactly like a prefix-hit
    # admission: queries at positions 4..7, floor 4
    _, arena2 = decode._forward(model, params, arena, prompt,
                                jnp.full((1,), 4, jnp.int32), paged=pv)
    np.testing.assert_array_equal(np.asarray(arena2[li]["k"][:bs]), before)
    # while positions >= floor DID write their block (block id 1)
    assert np.abs(np.asarray(arena2[li]["k"][bs:2 * bs])).sum() > 0


@pytest.mark.paged
def test_paged_gather_layout():
    """ops.attention.paged_gather: entry (r, p) of the view is arena slot
    ``table[r, p // bs] * bs + p % bs``, null entries read the null
    block, and the table's trailing null column absorbs out-of-range
    logical blocks (the spec-lookahead clip)."""
    from distkeras_tpu.ops.attention import paged_gather
    bs, nblocks = 2, 3
    arena = jnp.arange((nblocks + 1) * bs, dtype=jnp.float32)
    bt = jnp.asarray([[2, 0, 3], [1, 3, 3]], np.int32)
    view = np.asarray(paged_gather(arena, bt, bs, 6))
    np.testing.assert_array_equal(view[0], [4, 5, 0, 1, 6, 7])
    np.testing.assert_array_equal(view[1], [2, 3, 6, 7, 6, 7])
