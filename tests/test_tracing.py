"""The program's own spans and scopes (``distkeras_tpu/metrics.py`` lists
them): under a ``jax.profiler`` session a tiny engine run and a two-epoch
ADAG job yield every span with its fields, nested on one thread and in a
request's order; outputs are bit-equal with a session open and closed; with
no session nothing is written; and the compiled programs carry every scope
and kernel name without a change to their arithmetic.

Two profiler sessions in all (a start/stop costs seconds), both in
module-scoped fixtures of this one file.
"""

import collections
import contextlib
import glob
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import ADAG, Dataset, metrics
from distkeras_tpu.core.model import FittedModel
from distkeras_tpu.models import mnist_mlp, transformer_lm
from distkeras_tpu.router import ServingRouter
from distkeras_tpu.serving import DisaggPair, ServingEngine, TenantPolicy

Span = collections.namedtuple("Span", "start end name thread fields")

PROMPTS = [(np.arange(1, 6) % 64, 5),        # one bucket program
           (np.arange(3, 43) % 64, 4),       # chunks, then a final unit
           (np.arange(7, 27) % 64, 6)]


def read_spans(log_dir):
    """Every ``serve.*`` / ``train.*`` event of the session's trace."""
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans, thread = [], 0
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "train.")):
                    spans.append(Span(e.start_ns, e.start_ns + e.duration_ns,
                                      e.name, thread, dict(e.stats)))
            thread += 1
    return sorted(spans, key=lambda s: (s.start, -s.end))


def lm():
    model = transformer_lm(vocab_size=64, seq_len=64, d_model=32,
                           num_heads=2, num_layers=2, mlp_dim=64,
                           compute_dtype="float32")
    return model, model.init(jax.random.PRNGKey(0))


def engine():
    eng = ServingEngine(FittedModel(*lm()), num_slots=2, max_len=64,
                        paged=True, block_size=8, prefill_chunk=16)
    eng.register_tenant(TenantPolicy("vip", tier="interactive"))
    return eng


def wait_until(cond, what, limit_s=5.0):
    end = time.monotonic() + limit_s
    while not cond():
        assert time.monotonic() < end, f"{what}: not within {limit_s} s"
        time.sleep(0.002)


def serve(eng):
    """The three prompts through the engine's own thread; tokens served.
    The loop is seen to go idle before ``stop()``: with nothing queued or
    running at most one more ``step()`` has work (the lookahead's flush),
    ``step()`` counts its idle calls too, and between two idle calls of
    ``_loop`` lies one ``serve.idle_wait`` (its wait times out in 0.05 s)."""
    eng.start()
    try:
        handles = [eng.submit(p, n, tenant="vip" if i == 2 else None)
                   for i, (p, n) in enumerate(PROMPTS)]
        assert all(h.wait(120) for h in handles)
        wait_until(lambda: not eng.queue_depth and not eng.active_requests,
                   "engine drained")
        seen = eng._iterations
        wait_until(lambda: eng._iterations >= seen + 2, "two more iterations")
    finally:
        eng.stop()
    return [list(h.tokens) for h in handles]


@pytest.fixture(scope="module")
def serve_trace(tmp_path_factory):
    log_dir = str(tmp_path_factory.mktemp("serve_trace"))
    eng = engine()
    # the reload hook without a parameter server: the span is what is tested
    eng._reload_every, eng._pull_weights = 1, lambda: None
    with metrics.trace(log_dir):
        eng.warmup()
        tokens = serve(eng)
    return read_spans(log_dir), tokens


def dataset():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 784)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 64)]
    return Dataset({"features": x, "label": y})


def train(validation_data=None, **kw):
    trainer = ADAG(mnist_mlp("float32"), num_workers=2, batch_size=8,
                   num_epoch=2, communication_window=2,
                   worker_optimizer="adam", learning_rate=1e-3, **kw)
    trainer.train(dataset(), validation_data=validation_data)
    return list(trainer.history)


@pytest.fixture(scope="module")
def train_trace(tmp_path_factory):
    log_dir = str(tmp_path_factory.mktemp("train_trace"))
    with metrics.trace(log_dir):
        losses = (train(checkpoint_dir=os.path.join(log_dir, "ckpt"))
                  + train(validation_data=dataset()))
    return read_spans(log_dir), losses


SERVE_SPANS = {
    "serve.submit": ("rid", "prompt", "steps"),
    "serve.iteration": ("it", "active"),
    "serve.reap": (),
    "serve.qos": (),
    "serve.schedule": (),
    "serve.hold": ("reason", "queued"),
    "serve.admit": ("rid",),
    "serve.prefill_unit": ("rid", "tokens", "kind", "width", "hit"),
    "serve.decode_dispatch": ("active", "step", "attn", "sample", "state"),
    "serve.fetch": ("step",),
    "serve.emit": ("kind", "rows", "step"),
    "serve.retire": ("rid", "reason"),
    "serve.publish": (),
    "serve.reload": (),
    "serve.idle_wait": (),
    "serve.warmup": ("program",),
}


@pytest.mark.parametrize("name", sorted(SERVE_SPANS))
def test_engine_run_yields_the_span_with_its_fields(serve_trace, name):
    found = [s for s in serve_trace[0] if s.name == name]
    assert found, f"no {name} span in the trace"
    for s in found:
        assert tuple(s.fields) == SERVE_SPANS[name]
        assert all(isinstance(v, (int, str)) for v in s.fields.values())


PARENTS = {
    "serve.reap": "serve.iteration", "serve.qos": "serve.iteration",
    "serve.schedule": "serve.iteration", "serve.admit": "serve.schedule",
    "serve.hold": "serve.schedule",
    "serve.prefill_unit": "serve.schedule",
    "serve.decode_dispatch": "serve.iteration",
    "serve.fetch": "serve.iteration", "serve.emit": "serve.iteration",
    "serve.retire": "serve.iteration", "serve.reload": "serve.iteration",
}


@pytest.mark.parametrize("child", sorted(PARENTS))
def test_children_lie_inside_their_parent_on_one_thread(serve_trace, child):
    parents = [s for s in serve_trace[0] if s.name == PARENTS[child]]
    for c in (s for s in serve_trace[0] if s.name == child):
        assert any(p.thread == c.thread and p.start <= c.start
                   and c.end <= p.end for p in parents), c


def test_spans_of_one_thread_nest_and_never_cross(serve_trace):
    by_thread = collections.defaultdict(list)
    for s in serve_trace[0]:
        by_thread[s.thread].append(s)
    for spans in by_thread.values():
        open_ends = []
        for s in spans:
            while open_ends and open_ends[-1] <= s.start:
                open_ends.pop()
            assert not open_ends or s.end <= open_ends[-1], s
            open_ends.append(s.end)


@pytest.mark.parametrize("rid", [1, 2, 3])
def test_a_request_is_submitted_admitted_prefilled_retired(serve_trace, rid):
    mine = [s for s in serve_trace[0] if s.fields.get("rid") == rid]
    names = [s.name for s in mine]
    assert names[0] == "serve.submit" and names[1] == "serve.admit"
    assert names[-1] == "serve.retire"
    assert set(names[2:-1]) == {"serve.prefill_unit"}
    assert mine[-1].fields["reason"] == "length"
    prompt, steps = PROMPTS[rid - 1]
    assert mine[0].fields["prompt"] == len(prompt)
    assert mine[0].fields["steps"] == steps
    assert sum(s.fields["tokens"] for s in mine[2:-1]) == len(prompt)
    kinds = [s.fields["kind"] for s in mine[2:-1]]
    assert kinds == (["bucket"] if len(prompt) <= 16
                     else ["chunk"] * (len(kinds) - 1) + ["final"])
    # the submit ends on the caller's thread before the engine admits
    assert mine[0].end <= mine[1].start and mine[0].thread != mine[1].thread


def test_step_joins_a_dispatch_to_the_fetch_that_drains_it(serve_trace):
    spans = serve_trace[0]
    sent = {s.fields["step"]: s for s in spans
            if s.name == "serve.decode_dispatch"}
    assert sorted(sent) == list(range(1, len(sent) + 1))
    drained = [s for s in spans if s.name == "serve.fetch"]
    for step, d in sent.items():
        # the last step is the lookahead's junk: stop() leaves it in flight
        assert step == len(sent) or any(
            f.fields["step"] == step and f.start >= d.end for f in drained)
    assert all(s.fields["active"] > 0 for s in sent.values())
    # the CPU's decode program gathers; a TPU's reads through the kernel
    assert {s.fields["attn"] for s in sent.values()} == {"gather"}
    # the three requests are greedy: no step asks the sampler for a draw
    assert {s.fields["sample"] for s in sent.values()} == {"greedy"}
    # a step's tokens are emitted once, as a decode entry of its live rows
    emitted = {s.fields["step"]: s for s in spans
               if s.name == "serve.emit" and s.fields["kind"] == "decode"}
    assert all(emitted[step].fields["rows"] == sent[step].fields["active"]
               for step in emitted)


TRAIN_CHILDREN = ["train.shuffle", "train.shape", "train.dispatch",
                  "train.fetch", "train.log", "train.checkpoint",
                  "train.validate"]


@pytest.mark.parametrize("last", [False, True])
def test_an_epoch_holds_its_phases_in_order(train_trace, last):
    spans = train_trace[0]
    epochs = [s for s in spans if s.name == "train.epoch"]
    # two trainers: one with a checkpoint directory, one with validation
    # ten classes: the loss's rule says XLA on any backend
    assert [e.fields for e in epochs] == [{"epoch": 0, "ce": "xla"},
                                          {"epoch": 1, "ce": "xla"}] * 2
    e = epochs[-1 if last else 1]
    inside = [s.name for s in spans if s.thread == e.thread
              and s is not e and e.start <= s.start and s.end <= e.end]
    want = [n for n in TRAIN_CHILDREN
            if n != ("train.checkpoint" if last else "train.validate")]
    assert inside == want
    dispatch, = (s for s in spans if s.name == "train.dispatch"
                 and e.start <= s.start and s.end <= e.end)
    assert dispatch.fields == {"rounds": 2}


@pytest.mark.parametrize("model,tpu,want", [
    ("lm", True, "kernel"), ("lm", False, "xla"), ("mlp", True, "xla")])
def test_the_epoch_span_says_where_the_cross_entropy_runs(monkeypatch, model,
                                                          tpu, want):
    """``ce`` on ``train.epoch`` is the loss's own predicate on what the
    model hands it: ``kernel`` for an LM's (4, 128, 1024) logits on a TPU,
    ``xla`` off it and for ten classes.  No session: the trainer's ``span``
    is replaced by a recorder (the field's way through a real session is
    the ``train_trace`` fixture's, above)."""
    from distkeras_tpu import trainers
    from distkeras_tpu.core import losses
    seen = []

    @contextlib.contextmanager
    def recorder(name, **fields):
        seen.append((name, fields))
        yield

    monkeypatch.setattr(trainers, "span", recorder)
    monkeypatch.setattr(losses, "_on_tpu", lambda: tpu)
    rng = np.random.default_rng(0)
    if model == "lm":
        x = rng.integers(0, 1024, (8, 128)).astype(np.int32)
        net = transformer_lm(vocab_size=1024, seq_len=128, d_model=32,
                             num_heads=2, num_layers=1, mlp_dim=64,
                             compute_dtype="float32")
        data = Dataset({"features": x, "label": (x + 1) % 1024})
    else:
        net = mnist_mlp("float32")
        data = Dataset({
            "features": rng.normal(size=(8, 784)).astype(np.float32),
            "label": rng.integers(0, 10, 8).astype(np.int32)})
    ADAG(net, num_workers=1, batch_size=4, num_epoch=1,
         communication_window=2, worker_optimizer="adam",
         loss="sparse_categorical_crossentropy" + (
             "_from_logits" if model == "lm" else "")).train(data)
    epochs = [f for n, f in seen if n == "train.epoch"]
    assert epochs == [{"epoch": 0, "ce": want}]


def test_served_tokens_are_the_same_with_and_without_a_session(serve_trace):
    assert serve(engine()) == serve_trace[1]


def test_losses_are_the_same_with_and_without_a_session(train_trace):
    # the traced fixture trained twice over the same data; each run's
    # history is that of a run with no session open
    assert train() * 2 == train_trace[1]


def test_no_session_no_trace_file(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    with metrics.trace(str(tmp_path / "off"), enabled=False):
        serve(engine())
        with metrics.span("serve.iteration", it=0):
            pass
    assert not os.listdir(tmp_path)


# -- the engine's own account (metrics.EngineAccount) ---------------------------

def inline(eng, requests):
    """``requests`` (prompt, steps) through ``eng`` on this thread: the
    same iterations every time."""
    handles = [eng.submit(p, n) for p, n in requests]
    eng.run_until_idle()
    assert all(h.finish == "length" for h in handles)
    return eng.account.snapshot()


def counts(snap):
    """What of a snapshot is the same from run to run of one workload."""
    return dict(
        iterations=snap["iterations"], phases=sorted(snap["phase_s"]),
        units=snap["prefill_unit"]["n"],
        classes={c: v["n"] for c, v in snap["classes"].items()},
        gaps={c: v["n"] for c, v in snap["gap_ms"].items()},
        held={r: v["n"] for r, v in snap["held"].items()},
        slowest=len(snap["slowest"]))


@pytest.fixture(scope="module")
def alone():
    """The long prompt alone, then the short ones, on this thread, no
    session open."""
    return inline(engine(), [PROMPTS[1]]), inline(engine(), PROMPTS)


def test_the_phases_sum_to_the_loops_wall_time():
    eng = engine()
    serve(eng)
    snap = eng.account.snapshot()
    assert set(snap["phase_s"]) >= {"reap", "schedule", "decode_dispatch",
                                    "fetch", "emit", "publish", "idle_wait"}
    assert snap["iterations"] == eng._iterations
    assert 0.9 * snap["loop_s"] <= sum(snap["phase_s"].values()) \
        <= snap["loop_s"]
    # the iterations' own seconds are the loop's less its waits between them
    inside = sum(v["s"] for v in snap["classes"].values())
    assert inside + snap["phase_s"]["idle_wait"] <= snap["loop_s"]
    assert inside >= sum(v for k, v in snap["phase_s"].items()
                         if k != "idle_wait")
    # a unit's host side is part of the schedule pass
    assert 0 < snap["prefill_unit"]["s"] <= snap["phase_s"]["schedule"]


@pytest.mark.parametrize("cls", ["decode", "decode+prefill", "prefill",
                                 "none"])
def test_every_class_of_iteration_is_counted(alone, cls):
    snap = alone[0]
    # 40 tokens in chunks of 16: two units with no row live, the final one
    # starts the row and its first step; 3 more steps (the last the
    # lookahead's junk); then nothing is dispatched: one call drains the
    # junk step, one finds nothing to do
    want = {"prefill": 2, "decode+prefill": 1, "decode": 3, "none": 2}
    assert snap["classes"][cls]["n"] == want[cls]
    assert (snap["classes"][cls]["s"] > 0) == (want[cls] > 0)
    assert snap["iterations"] == sum(want.values())
    assert snap["step_carries_prefill_pct"] == pytest.approx(25.0)
    assert snap["prefill_unit"]["n"] == 3


def test_the_token_gap_is_counted_a_row_and_a_class(alone):
    gaps = alone[1]["gap_ms"]
    steps = alone[1]["classes"]
    # every decode step but the first after an empty pool closes a gap for
    # each row it held; a step whose iteration dispatched a unit is its own
    # class
    assert gaps["all"]["n"] == gaps["step"]["n"] + gaps["step+unit"]["n"] > 0
    assert gaps["step+unit"]["n"] > 0
    assert gaps["all"]["n"] < 2 * (steps["decode"]["n"]
                                   + steps["decode+prefill"]["n"])
    for c in gaps.values():
        assert 0 < c["p50"] <= c["p95"]


@pytest.mark.parametrize("ms", [0.1, 0.3, 2.5, 33.0, 127.0, 5000.0])
def test_gap_percentiles_lie_within_a_bucket_of_the_gap(ms):
    acct = metrics.EngineAccount()
    for step in range(1, 41):
        acct.decode_step(step)
        acct.step_emitted(step, 3, step * ms / 1e3)
    got = acct.snapshot()["gap_ms"]
    assert got["step"]["n"] == got["all"]["n"] == 39 * 3
    assert got["step+unit"] == {"n": 0, "p50": None, "p95": None}
    for q in ("p50", "p95"):
        if ms < 0.25:
            assert 0 <= got["all"][q] <= 0.25
        elif ms > 4096:
            assert got["all"][q] == 4096
        else:
            assert ms / 2 ** 0.125 <= got["all"][q] <= ms * 2 ** 0.125


def test_a_slow_phase_heads_the_slowest_iterations():
    eng = engine()
    # every program compiled (warmup() leaves out the one a retirement
    # runs), then an account of the second request alone
    inline(eng, [PROMPTS[0]])
    eng.account = metrics.EngineAccount()
    eng.start()
    try:
        h = eng.submit(*PROMPTS[0])
        h.set_listener(lambda: len(h.tokens) == 3 and time.sleep(0.03))
        assert h.wait(120)
    finally:
        eng.stop()
    snap = eng.account.snapshot()
    top = snap["slowest"][0]
    # the listener runs in the token loop, asleep: off the CPU, in `emit`
    assert top["phase"] == "emit" and top["phase_ms"] >= 30
    assert top["wall_ms"] >= top["phase_ms"] and top["cpu_ms"] < 15
    assert top["class"] == "decode" and top["active"] == 1
    assert top["it"] > 1 and snap["slowest"][1]["wall_ms"] < 30
    assert len(snap["slowest"]) <= 8
    assert [e["wall_ms"] for e in snap["slowest"]] == sorted(
        (e["wall_ms"] for e in snap["slowest"]), reverse=True)


HELD = {
    # one slot: the second and third wait for it
    "no_slot": (dict(num_slots=1), 3),
    # free slots, one unit an iteration: the third waits for the second's
    "budget": (dict(num_slots=4, prefills_per_step=1), 3),
    # a pool of one slot's blocks: 36 positions take 5 of its 8
    "no_blocks": (dict(num_slots=2, kv_blocks=8, prefills_per_step=2), 2),
}


@pytest.mark.parametrize("reason", sorted(HELD))
def test_held_says_why_the_queues_head_waited(reason):
    kw, n = HELD[reason]
    eng = ServingEngine(FittedModel(*lm()), max_len=64, paged=True,
                        block_size=8, prefill_chunk=16, **kw)
    snap = inline(eng, [((np.arange(10) + 11 * i) % 64, 26)
                        for i in range(n)])
    assert set(snap["held"]) == {reason}
    held = snap["held"][reason]
    assert 0 < held["n"] < snap["iterations"]
    assert 0 < held["s"] < snap["loop_s"]
    if reason == "budget":
        assert held["n"] == 2       # one admission an iteration: 2, then 1


@pytest.fixture(scope="module")
def alone_traced(tmp_path_factory):
    with metrics.trace(str(tmp_path_factory.mktemp("account_trace"))):
        return inline(engine(), PROMPTS)


def test_the_account_is_the_same_with_a_session_open_and_closed(
        alone, alone_traced):
    assert counts(alone_traced) == counts(alone[1])
    # two slots, one unit an iteration, three requests
    assert set(counts(alone[1])["held"]) == {"budget", "no_slot"}


def test_snapshot_is_plain_json(alone):
    for snap in alone:
        assert json.loads(json.dumps(snap)) == snap
    assert set(alone[1]) == {
        "loop_s", "iterations", "phase_s", "prefill_unit", "classes",
        "step_carries_prefill_pct", "gap_ms", "slowest", "held"}
    assert set(alone[1]["slowest"][0]) == {
        "it", "at", "wall_ms", "cpu_ms", "class", "phase", "phase_ms",
        "active"}
    # an engine that never ran
    assert json.dumps(metrics.EngineAccount().snapshot())


@pytest.mark.parametrize("front", ["pair", "router"])
def test_merged_stats_are_as_before_and_hold_no_account(front):
    """``DisaggPair.stats`` / ``ServingRouter.stats`` sum numbers and
    concatenate lists of ``engine.stats``: the account is an attribute of
    each engine, never a key of ``stats``."""
    def eng(role):
        return ServingEngine(FittedModel(*lm()), num_slots=2, max_len=64,
                             paged=True, block_size=8, role=role)
    if front == "pair":
        whole = DisaggPair([eng("prefill")], decode=eng("decode"),
                           poll_s=0.005)
    else:
        whole = ServingRouter([eng("unified"), eng("unified")])
    with whole:
        assert whole.submit(*PROMPTS[0]).wait(120)
    merged = whole.stats
    for e in whole.engines:
        assert "account" not in e.stats
        assert set(e.stats) <= set(merged)
        for k, v in e.stats.items():
            if isinstance(v, list):
                assert isinstance(merged[k], list), k
            elif isinstance(v, (int, float)) and not isinstance(v, bool):
                assert isinstance(merged[k], (int, float)), k
        assert e.account.snapshot()["iterations"] > 0
    assert merged["requests_completed"] == 1
    assert merged["decode_steps"] == sum(
        e.stats["decode_steps"] for e in whole.engines)
    assert len(merged["slot_requests"]) == 4


# -- scopes and kernel names in the compiled programs ---------------------------

def train_step_lowered():
    import optax
    from distkeras_tpu.core.train import make_masked_step
    model, params = lm()
    tx = optax.adam(1e-3)
    step = make_masked_step(
        model, "sparse_categorical_crossentropy_from_logits", tx)
    x = jnp.zeros((2, 64), jnp.int32)
    return jax.jit(step).lower(params, tx.init(params), x, x,
                               jnp.ones((2,)), jax.random.PRNGKey(0))


def adag_round_lowered():
    trainer = ADAG(lm()[0], num_workers=2, batch_size=2, num_epoch=1,
                   communication_window=2, worker_optimizer="adam",
                   loss="sparse_categorical_crossentropy_from_logits")
    trainer._input_shape = (64,)
    eng = trainer.service((64,))
    state = eng.init_state(jax.random.PRNGKey(0), (64,))
    x = np.zeros((2, 2, 2, 64), np.int32)
    return eng._build_round_step().lower(
        state, x, x, np.ones((2, 2, 2), np.float32), eng.worker_rngs(0))


def decode_step_lowered():
    eng = engine()
    return eng._decode_fn.lower(eng.params, *eng._state_args())


MODEL = ["embed", "block_0", "block_1", "attn", "attn_core", "mlp",
         "final_norm", "lm_head"]
LOWERED = {
    "train_step": (train_step_lowered, MODEL + ["loss", "optimizer"]),
    "adag_round": (adag_round_lowered,
                   MODEL + ["loss", "optimizer", "commit"]),
    "decode_step": (decode_step_lowered,
                    MODEL + ["kv_write", "kv_gather", "sample"]),
}


@pytest.mark.parametrize("program", sorted(LOWERED))
def test_the_lowered_program_names_every_scope(program):
    lower, scopes = LOWERED[program]
    text = lower().as_text(debug_info=True)
    # a transformed scope is wrapped: transpose(jvp(lm_head))
    named = {part for line in text.splitlines() if "loc(" in line
             for part in re.findall(r"[\w.\-]+", line)}
    assert not [s for s in scopes if s not in named]
    assert "attn/attn_core" in text


@pytest.mark.parametrize("program", sorted(LOWERED))
def test_scopes_leave_the_arithmetic_as_it_was(program, monkeypatch):
    lower, _ = LOWERED[program]
    scoped = lower().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert lower().as_text() == scoped


@pytest.fixture(scope="module")
def kernels_lowered():
    """Flash attention and the fused CE, forward and backward, and the
    recurrent layers' fused decode step, lowered for a TPU once."""
    from distkeras_tpu.ops.flash_attention import flash_attention
    from distkeras_tpu.ops.fused_ce import fused_softmax_cross_entropy
    from distkeras_tpu.ops.kda import kda_decode
    from distkeras_tpu.ops.ssd import ssd_decode

    def both(q, long, logits, labels):
        # ``q``'s backward is the one kernel; ``long``'s dq accumulator
        # does not fit VMEM, so its backward is the two-pass pair
        attn = sum(flash_attention(t, t, t, causal=True, interpret=False)
                   .astype(jnp.float32).sum() for t in (q, long))
        ce = fused_softmax_cross_entropy(logits, labels, interpret=False)
        return attn + ce.sum()

    def step(vec, state):
        return kda_decode(vec, vec, vec, vec, vec[..., 0], state,
                          jnp.ones((1,), bool), interpret=False)

    def ssd_step(vec, state):
        return ssd_decode(vec, vec[..., 0], vec[0, :, 0], vec[:, :1],
                          vec[:, :1], state, jnp.ones((1,), bool),
                          interpret=False)

    q = jax.ShapeDtypeStruct((2, 128, 2, 64), jnp.bfloat16)
    long = jax.ShapeDtypeStruct((1, 16384, 1, 128), jnp.bfloat16)
    logits = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    labels = jax.ShapeDtypeStruct((256,), jnp.int32)
    vec = jax.ShapeDtypeStruct((1, 8, 128), jnp.float32)
    state = jax.ShapeDtypeStruct((1, 8, 128, 128), jnp.float32)
    return (jax.jit(jax.grad(both, argnums=(0, 1, 2))).trace(
        q, long, logits, labels).lower(lowering_platforms=("tpu",)).as_text()
        + jax.jit(step).trace(vec, state).lower(
            lowering_platforms=("tpu",)).as_text()
        + jax.jit(ssd_step).trace(vec, state).lower(
            lowering_platforms=("tpu",)).as_text())


# paged_decode has the test below: its program is the engine's decode step
@pytest.mark.parametrize("name", [k for k in metrics.KERNEL_NAMES
                                  if k != "paged_decode"])
def test_the_kernels_carry_their_names(kernels_lowered, name):
    assert f'kernel_name = "{name}"' in kernels_lowered


def test_the_train_step_holds_one_backward_kernel_a_layer(monkeypatch):
    """The training cell's attention, (8, 1024, 12, 64) in bf16, lowered
    for a TPU: the whole-S dq accumulator fits, so a layer's backward is
    the ONE kernel and neither of the two-pass pair; the layers share one
    lowering of each kernel (the wrappers are jitted) and each calls it
    from under its own ``attn_core``."""
    import optax
    from distkeras_tpu.core.train import make_masked_step
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = transformer_lm(vocab_size=256, seq_len=1024, d_model=768,
                           num_heads=12, num_layers=2, mlp_dim=256,
                           compute_dtype="bfloat16")
    params = jax.eval_shape(lambda k: model.init(k, (1024,)),
                            jax.random.PRNGKey(0))
    tx = optax.adam(1e-3)
    step = make_masked_step(
        model, "sparse_categorical_crossentropy_from_logits", tx)
    x = jax.ShapeDtypeStruct((8, 1024), jnp.int32)
    text = jax.jit(step).trace(
        params, jax.eval_shape(tx.init, params), x, x,
        jax.ShapeDtypeStruct((8,), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    count = lambda name: text.count(f'kernel_name = "{name}"')
    assert count("flash_fwd") == count("flash_bwd") == 1
    assert count("flash_dq") == count("flash_dkv") == 0
    for block in ("block_0", "block_1"):
        assert (f"transpose(jvp({block}))/attn/attn_core/"
                "jit(_flash_backward)") in text


def test_chip_smoke_requires_kernels_the_package_names():
    """``chip_smoke.py`` runs on the chip alone; tier 1 sees its names."""
    import chip_smoke  # conftest.py puts the checkout's root on sys.path
    wanted = {k for names in chip_smoke.REQUIRED_KERNELS.values()
              for k in names}
    assert wanted and wanted <= set(metrics.KERNEL_NAMES)


def test_the_paged_decode_step_names_its_kernel_under_attn_core(monkeypatch):
    """What a TPU engine's decode program lowers to: the kernel by its
    name, under ``attn_core``; ``kv_gather`` still a scope of the program
    (the row lengths), so the scope readers find it and read it near 0."""
    from distkeras_tpu.core import decode
    from distkeras_tpu.ops import paged_attention
    monkeypatch.setattr(decode, "_on_tpu", lambda: True)
    compiled = paged_attention.paged_decode_attention
    monkeypatch.setattr(
        paged_attention, "paged_decode_attention",
        lambda *a, **kw: compiled(*a, **kw, interpret=False))
    model = transformer_lm(vocab_size=64, seq_len=64, d_model=128,
                           num_heads=2, num_layers=2, mlp_dim=64,
                           compute_dtype="float32")
    eng = ServingEngine(FittedModel(model, model.init(jax.random.PRNGKey(0))),
                        num_slots=2, max_len=64, paged=True, block_size=8)
    assert eng._decode_attn == "kernel"
    text = eng._decode_fn.trace(eng.params, *eng._state_args()).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    # the layers share ONE lowering of the kernel (the wrapper is jitted);
    # each calls it from under its own attn_core
    assert "paged_decode" in metrics.KERNEL_NAMES
    assert text.count('kernel_name = "paged_decode"') == 1
    for block in ("block_0", "block_1"):
        assert f"{block}/attn/attn_core/jit(paged_decode_attention)" in text
    assert "attn/kv_gather" in text and "attn/kv_write" in text
