"""The yardstick of the state-space serving cell
(``serve-context-nemotron3n``): its configuration against the catalog's
numbers, its counts on hand-computed shapes, the reference against itself, the
weights' draw at the published widths, each ``.nemotronh`` reader on a made-up
trace, the manifest's pairing, the controls of ``correct`` at test size, and a
rehearsal of the command."""

import json
import os

import numpy as np
import pytest

from benchmarks.lib import counts_nemotronh as C
from benchmarks.lib import manifest as mf, spans as S, trace as T

from helpers import context
from test_run import start

CELL = "serve-context-nemotron3n"
CONFIG = "nemotron3-nano-30b-a3b"
PEAKS = dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)
READERS = ["mfu.nemotronh", "decode_hbm_roofline.nemotronh",
           "ssm_device_pct.nemotronh", "moe_device_pct.nemotronh",
           "ssd_decode_roofline.nemotronh", "moe_experts_roofline.nemotronh",
           "prefill_unit_ms.nemotronh", "expert_load_max_over_mean.nemotronh"]


@pytest.fixture(scope="module")
def cfg():
    return mf.Manifest().config(CONFIG)


# -- the configuration ----------------------------------------------------------

def test_every_catalog_number_is_under_its_key_or_listed_as_reduced(cfg):
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    row = next(json.loads(l) for l in open(catalog)
               if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in l)
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert all(cfg["published"][k] == row["config"][k] for k in differs)
    entry = next(c for c in mf.Manifest().data["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]


def test_the_cut_keeps_to_the_floors(cfg):
    d = C.dims(cfg)
    # layers 0..8 of the published pattern, which the file keeps whole
    assert len(cfg["hybrid_override_pattern"]) == 52
    assert cfg["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert d["kinds"] == ["mamba", "experts", "mamba", "experts", "mamba",
                          "attn", "experts", "mamba", "experts"]
    assert d["held"] >= 8 and d["held"] * 2 == d["experts"] == 128
    assert d["vocab"] * 2 == cfg["published"]["vocab_size"]
    assert cfg["deployment"]["layer_shared_by_chips"] == 2
    assert (d["inner"], d["conv_dim"]) == (4096, 6144)     # no width changed
    eng = cfg["deployment"]["engine"]
    assert eng["max_len"] == cfg["context"]["max_len"] == 9216
    for key in ("mamba_d_inner", "mamba_gate_norm", "attention_nope",
                "router", "experts"):
        assert key in cfg["assumed"]


# -- counts, by hand -----------------------------------------------------------

def test_parameters_are_the_issue_s_arithmetic(cfg):
    assert C.expert_params(cfg) == 2 * 2688 * 1856 == 9_977_856
    assert C.mixer_params(cfg, "attn") == (2 * 2688 * 4096
                                           + 2 * 2688 * 256) == 23_396_352
    assert C.mixer_params(cfg, "mamba") == (2688 * (4096 + 6144 + 64)
                                            + 4096 * 2688) == 38_707_200
    # with its norm, convolution, bias, dt_bias, A_log, D and gated norm
    assert C.mixer_params(cfg, "mamba") + C.small_params(cfg, "mamba") == \
        38_707_200 + 2688 + 5 * 6144 + 3 * 64 + 4096 == 38_744_896
    per_expert_layer = 2688 * 128 + 2 * 2688 * 3712
    assert C.dense_params_per_layer(cfg, "experts") == per_expert_layer \
        == 20_299_776
    assert C.dense_params(cfg) == (4 * 38_707_200 + 23_396_352
                                   + 4 * per_expert_layer + 2688 * 65536)
    # 3,166 M parameters = 6.33 GB in bf16
    assert round(C.total_params(cfg) / 1e6) == 3166
    assert round(2 * C.total_params(cfg) / 1e7) == 633


def test_state_and_flops(cfg):
    s = 64 * 64 * 128 * 4
    conv = 3 * 6144 * 2
    assert (s, conv) == (2_097_152, 36_864)
    assert C.ssm_state_bytes(cfg) == s
    assert C.recurrent_state_bytes(cfg) == 4 * (s + conv)   # 8.54 MB a slot
    assert round(256 * C.recurrent_state_bytes(cfg) / 1e7) == 219
    assert C.kv_bytes_per_token(cfg) == 2 * 2 * 128 * 2 == 1024
    assert C.held_share(cfg) == 3.0                  # 6 x 64 / 128
    no_ctx = C.flops_per_token(cfg, 0.0, False)
    assert no_ctx == 2.0 * (C.dense_params(cfg) - 2688 * 65536
                            + 4 * 3 * C.expert_params(cfg)) \
        + 4 * 5.0 * 64 * 64 * 128
    assert C.flops_per_token(cfg, 100.0, True) == (
        no_ctx + 4.0 * 32 * 128 * 100 + 2.0 * 2688 * 65536)


def test_least_bytes_and_seconds(cfg):
    b = C.decode_least_bytes(cfg, 2, 10, 7, 1000)
    assert b == (2 * C.dense_params(cfg) * 2 + 7 * 9_977_856 * 2
                 + 10 * 2 * C.recurrent_state_bytes(cfg) + 1000 * 1024)
    # three touched experts are 60 MB: bytes bound a handful of rows
    t = C.experts_least_seconds(cfg, 5, 3, 197e12, 819e9)
    assert t == pytest.approx(3 * 9_977_856 * 2 / 819e9)
    # a prefill unit's 48 rows an expert are still bound by the weights ...
    t = C.experts_least_seconds(cfg, 3072, 64, 197e12, 819e9)
    assert t == pytest.approx(64 * 9_977_856 * 2 / 819e9)
    # ... and 10,000 rows on one expert by FLOPs
    t = C.experts_least_seconds(cfg, 10_000, 1, 197e12, 819e9)
    assert t == pytest.approx(2.0 * 10_000 * 9_977_856 / 197e12)
    assert C.ssd_decode_least_seconds(cfg, 4, 819e9) == pytest.approx(
        4 * 4 * 2 * 64 * 64 * 128 * 4 / 819e9)


# -- the reference against itself; the weights' draw ------------------------------

@pytest.fixture(scope="module")
def tiny():
    import copy
    from benchmarks.lib.weights_nemotronh import make_weights
    cfg = copy.deepcopy(mf.resolve_sizes(mf.Manifest().config(CONFIG), True))
    return cfg, C.dims(cfg), make_weights(cfg, 11, "float32")


def test_the_reference_whole_is_the_reference_padded(tiny):
    """Causal all the way: a row padded to the next program length reads at
    its own positions what it reads alone, and a prefix of it likewise."""
    import jax.numpy as jnp
    from benchmarks.lib import reference_nemotronh as ref
    _, d, w = tiny
    toks = np.random.default_rng(0).integers(0, d["vocab"], 90)
    whole = ref.logits_fn(w, jnp.asarray(toks), d)
    padded = ref.logits_fn(
        w, jnp.asarray(np.pad(toks, (0, ref.pad_length(90, 64) - 90))), d)
    np.testing.assert_allclose(padded[:90], whole, atol=1e-5)
    part = ref.logits_fn(w, jnp.asarray(toks[:37]), d)
    np.testing.assert_allclose(part, whole[:37], atol=1e-5)
    assert ref.pad_length(9216) == 9216 and ref.pad_length(1025) == 2048
    assert len({ref.pad_length(n) for n in range(1, 9217)}) == 9


def test_the_reference_imports_nothing_of_the_program():
    import benchmarks.lib.reference_nemotronh as ref
    text = open(ref.__file__).read()
    assert "distkeras_tpu" not in text and "import_program" not in text
    assert "HIGHEST" in text


def test_the_selection_bias_changes_who_is_chosen_on_many_tokens(tiny):
    """``e_score_correction_bias`` is drawn nonzero: with and without it the
    six chosen differ on a measurable share of tokens, at the published
    router's width (one router, 2,688 x 128, on the CPU)."""
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import reference_nemotronh as ref
    from benchmarks.lib import weights_nemotronh as W
    cfg = mf.Manifest().config(CONFIG)
    d = C.dims(cfg)
    key = jax.random.PRNGKey(5)
    twins = W._twinned(lambda s: 0.02 * jax.random.normal(key, s),
                       (d["hidden"],), 128, 64)
    bias = W._twinned(lambda s: W.BIAS_STD * jax.random.normal(
        jax.random.fold_in(key, 1), s), (), 128, 64)
    assert float(jnp.abs(bias).min()) > 0
    # pairs w, -w inside each share of 64
    np.testing.assert_array_equal(twins[:, :32], -twins[:, 32:64])
    np.testing.assert_array_equal(bias[64:96], -bias[96:])
    u = jax.random.normal(jax.random.fold_in(key, 2), (2048, d["hidden"]))
    p = dict(router=twins, router_bias=bias)
    with_b, _ = ref.router(u, p, d)
    without, _ = ref.router(u, dict(p, router_bias=0 * bias), d)
    moved = np.mean([set(a) != set(b) for a, b in
                     zip(np.asarray(with_b), np.asarray(without))])
    assert 0.2 < moved < 0.95
    # and each share gets half the assignments, to a few per cent
    share = float(jnp.mean(with_b < 64))
    assert abs(share - 0.5) < 0.03


def test_no_common_vector_reaches_the_next_router():
    """PR 32's lesson at the published widths, through the reference: after a
    Mamba-2 layer of the seeded draw, the mean over tokens of the next
    layer's normed input holds about a hundredth of its energy (a draw with
    wide taps and a free bias on ``x`` reads 3 % and more), and what the
    state carries is as large as the skip: a layer started cold eight tokens
    ago is a quarter off."""
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import reference_nemotronh as ref
    from benchmarks.lib import weights_nemotronh as W
    cfg = mf.Manifest().config(CONFIG)
    d = C.dims(cfg)
    shape = (d["hidden"], d["heads"], d["kv_heads"], d["head_dim"],
             d["m_heads"], d["inner"], d["conv_dim"], d["conv"],
             d["experts"], d["held"], d["expert_dim"], d["shared_dim"],
             d["layers"])
    key = jax.random.PRNGKey(3)
    p = W._layer(key, "mamba", shape, jnp.dtype("float32"))
    x = 0.02 * jax.random.normal(jax.random.fold_in(key, 9),
                                 (192, d["hidden"]))
    u = ref.rms_norm(x, p["norm"], d["eps"])
    y = ref.mamba_mixer(u, p, d, ref.f32_matmul)
    nxt = ref.rms_norm(x + y, 1.0, d["eps"])
    common = float(jnp.sum(jnp.mean(nxt, 0) ** 2)
                   / jnp.mean(jnp.sum(nxt ** 2, -1)))
    assert common < 0.02
    cold = ref.mamba_mixer(u[128:], p, d, ref.f32_matmul)
    off = float(jnp.sqrt(jnp.mean((cold[8] - y[136]) ** 2)
                         / jnp.mean(y[136] ** 2)))
    assert off > 0.1


# -- the readers on a made-up trace -------------------------------------------

WINDOW = (1_000, 10_000_000)
CALL = 'custom_call_target="tpu_custom_call"'
MOE = ("%custom-call.4 = f32[768,1856] custom-call(bf16[768,2688] %p.1), "
       + CALL, "jit(pstep)/block_1/moe/moe_experts/pallas_call:", 400_000)
ROUTE = ("%fusion.2 = f32[128,128] fusion(f32[128,2688] %p.2)",
         "jit(pstep)/block_1/moe/moe_route/dot_general:", 50_000)
SSD = ("%custom-call.9 = f32[256,64,64,128] custom-call(f32[256,64,64] "
       "%p.3), " + CALL, "jit(pstep)/block_0/ssm/ssm_core/pallas_call:",
       300_000)
SSD_XLA = ("%fusion.5 = f32[256,64,64] fusion(f32[256,64,64] %p.4)",
           "jit(pstep)/block_0/ssm/ssm_core/exp:", 20_000)
HEAD = ("%fusion.7 = f32[256,65536] fusion(bf16[256,2688] %p.5)",
        "jit(pstep)/lm_head/dot_general:", 230_000)
STEP = [MOE, ROUTE, SSD, SSD_XLA, HEAD]                    # 1,000,000 ns
UNIT = [("%custom-call.4 = f32[3072,1856] custom-call(bf16[3072,2688] %p.1),"
         " " + CALL, "jit(stage)/block_1/moe/moe_experts/pallas_call:",
         1_200_000),
        ("%fusion.9 = f32[1,1024,64,64] fusion(f32[1,1024,64,64] %p.4)",
         "jit(stage)/block_0/ssm/ssm_core/ssd_chunk/while:", 800_000)]


def made_up(monkeypatch, steps=2, units=1, pieces=STEP):
    ops, modules, at = [], [], 2_000
    for program, body in ([("jit_pstep(1)", pieces)] * steps
                          + [("jit_stage(2)", UNIT)] * units):
        start_ = at
        for name, op_name, ns in body:
            ops.append(S.Op(at, at + ns, name, op_name))
            at += ns
        modules.append((start_, at, program))
        at += 10_000
    plane = T.DevicePlane("/device:TPU:0",
                          [(o.start, o.end, o.name) for o in ops], modules)
    monkeypatch.setattr(S, "of_run", lambda t: S.Spans([], ops))
    return T.Trace([plane], {T.WINDOW_SPAN: [WINDOW]})


def records(steps=2, units=1, **over):
    traced = dict(decode_steps=steps, active_slot_steps=60 * steps,
                  prefill_tokens=1024 * units, prefill_chunks=units,
                  prefill_batches=0, moe_assignments_held=720 * steps,
                  moe_experts_touched=240 * steps, moe_load_max=30 * steps,
                  moe_layer_steps=4 * steps,
                  moe_prefill_assignments_held=12_288 * units,
                  moe_prefill_experts_touched=256 * units,
                  moe_prefill_layer_units=4 * units, seconds=0.01)
    out = dict(kind="serve", decode_programs=["jit_pstep"],
               serve_programs=["jit_pstep", "jit_run", "jit_stage"],
               traced_context_positions=40_000, traced_counters=traced,
               window_counters=dict(traced))
    out.update(over)
    return out


def read(name, rec, trace, cfg):
    traffic = mf.Manifest().traffic("context-open-poisson")
    return mf.load_layer_metric(name).read(
        rec, trace, dict(cfg=cfg, peaks=PEAKS, chips=1, traffic=traffic))


def test_scope_shares(monkeypatch, cfg):
    trace = made_up(monkeypatch)          # 2 x 1,000 us + one unit of 2,000
    assert read("moe_device_pct.nemotronh", records(), trace, cfg) == \
        pytest.approx(100 * (2 * 450 + 1200) / 4000)
    assert read("ssm_device_pct.nemotronh", records(), trace, cfg) == \
        pytest.approx(100 * (2 * 320 + 800) / 4000)


def test_rooflines_divide_the_least_time_by_the_measured(monkeypatch, cfg):
    trace = made_up(monkeypatch)
    rec = records()
    # decode steps (bytes-bound) and the prefill unit (each family's own
    # bound), over the moe_experts time of both families of programs
    least = (C.experts_least_seconds(cfg, 1440, 480, 197e12, 819e9)
             + C.experts_least_seconds(cfg, 12_288, 256, 197e12, 819e9))
    assert read("moe_experts_roofline.nemotronh", rec, trace, cfg) == \
        pytest.approx(100 * least / (800e-6 + 1200e-6))
    # the Pallas kernel under ssm_core alone, not the XLA ops beside it
    least = C.ssd_decode_least_seconds(cfg, 120, 819e9)
    assert read("ssd_decode_roofline.nemotronh", rec, trace, cfg) == \
        pytest.approx(100 * least / 600e-6)
    least = C.decode_least_bytes(cfg, 2, 120, 480, 40_000) / 819e9
    assert read("decode_hbm_roofline.nemotronh", rec, trace, cfg) == \
        pytest.approx(100 * least / 2e-3)
    assert read("prefill_unit_ms.nemotronh", rec, trace, cfg) == \
        pytest.approx(2.0)


def test_counters_are_scaled_to_the_runs_the_trace_holds(monkeypatch, cfg):
    trace = made_up(monkeypatch, steps=2, units=1)
    twice = records(steps=4, units=2)   # the host counted twice the trace's
    twice["traced_context_positions"] = 80_000
    for name in ("moe_experts_roofline.nemotronh",
                 "ssd_decode_roofline.nemotronh",
                 "decode_hbm_roofline.nemotronh"):
        assert read(name, twice, trace, cfg) == pytest.approx(
            read(name, records(), trace, cfg))


def test_mfu_counts_prompt_and_decoded_tokens(monkeypatch, cfg):
    trace = made_up(monkeypatch)
    flops = (1024 * C.flops_per_token(cfg, 0.0, False)
             + 120 * C.flops_per_token(cfg, 0.0, True)
             + 4.0 * 32 * 128 * 40_000)
    assert read("mfu.nemotronh", records(), trace, cfg) == pytest.approx(
        100 * flops / 0.01 / 197e12)


def test_load_skew_from_the_counters(cfg):
    # fullest 30 rows against a mean of 720 / 64 = 11.25
    assert read("expert_load_max_over_mean.nemotronh", records(), None, cfg) \
        == pytest.approx(30 * 64 / 720)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_counters_reads_nothing(monkeypatch, cfg,
                                                      name):
    """The parent of the PR that added them: no counters, no scopes, and
    under the accepted driver's records no prefill programs either."""
    bare = dict(kind="serve", decode_programs=["jit_pstep"],
                traced_context_positions=10, traced_counters=None,
                window_counters=None)
    trace = made_up(monkeypatch, units=0, pieces=[(
        "%fusion.1 = f32[8] fusion(f32[8] %p)", "jit(pstep)/add:", 100)])
    assert read(name, bare, trace, cfg) is None
    assert read(name, dict(kind="train"), None, cfg) is None
    # the counters of decode steps alone (a program before the prefill keys)
    old = records()
    for k in [k for k in old["traced_counters"] if "moe_prefill" in k]:
        del old["traced_counters"][k]
    if name == "moe_experts_roofline.nemotronh":
        assert read(name, old, made_up(monkeypatch), cfg) is None


def test_the_manifest_pairs_the_cell_with_its_metrics():
    man = mf.Manifest()
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "context-open-poisson", 1)
    listed = {m["name"] for m in man.end_to_end(CELL)}
    assert {"itl_p95_ms", "serve_tokens_per_s", "setup_s"} <= listed
    assert listed <= {"itl_p95_ms", "serve_tokens_per_s", "setup_s",
                      "ttft_p95_ms"}
    mine = {m["name"]: m for m in man.per_layer(CELL)}
    assert {n for n in mine if n.endswith(".nemotronh")} == set(READERS)
    for n in READERS:
        reader = mf.load_layer_metric(n)
        assert (mine[n]["layer"], mine[n]["unit"], mine[n]["better"],
                mine[n]["source"], mine[n]["moves"]) == (
            reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE,
            reader.MOVES)
        assert mine[n]["workloads"] == [CELL]
    assert not {n for n in mine if n.endswith(".hybrid")}
    # the accepted readers that read its records as they are
    assert {"batch_occupancy_pct.serve", "decode_step_ms.serve",
            "device_idle_pct.serve", "iteration_p95_ms.serve",
            "host_busy_ms.serve"} <= set(mine)
    assert not {"sample_device_pct.serve", "paged_kernel_step_pct.serve",
                "decode_hbm_roofline.serve"} & set(mine)
    assert all(m["moves"] in listed for m in mine.values())
    theirs = {m["name"] for m in man.per_layer("serve-reason-solar2")}
    assert not {n for n in theirs if n.endswith(".nemotronh")}


def test_the_traffic_file_is_the_issue_s_table():
    t = mf.Manifest().traffic("context-open-poisson")
    assert t["kind"] == "serve_nemotronh" and t["shape_seed"] == 0
    assert t["arrival"]["process"] == "poisson"
    assert t["arrival"]["rate"] * 2 == int(t["arrival"]["rate"] * 2)
    assert t["prompt_len"] == dict(dist="lognormal", median=2048, sigma=0.8,
                                   min=256, max=8192)
    assert t["output_len"] == dict(dist="lognormal", median=256, sigma=0.7,
                                   min=32, max=1024)
    assert (t["prefix_groups"], t["lead_in_s"], t["drain_timeout_s"]) == \
        (0, 10, 120)
    assert t["trace"] == dict(start_s=12, span_s=6)
    assert t["correct"]["sample"] >= 48 and t["correct"]["pad_to"] == 1024
    assert t["prompt_len"]["max"] + t["output_len"]["max"] <= \
        mf.Manifest().config(CONFIG)["deployment"]["engine"]["max_len"]
    assert set(t["prefill_programs"]) < set(t["serve_programs"])


# -- correct at test size: the program inside, the control outside -------------

@pytest.fixture(scope="module")
def served_window():
    ctx = context(CELL, seconds=3.0)
    drv = mf.load_driver("serve_nemotronh")
    engine = drv.build_engine(ctx)
    from benchmarks.lib.traffic import generate
    reqs = generate(ctx.traffic, ctx.seed, ctx.seconds,
                    int(ctx.cfg["vocab_size"]))
    handles = [engine.submit(r.prompt, r.output_len) for r in reqs]
    engine.run_until_idle()
    below = drv.precision_below_stated(engine, ctx.cfg)
    served = [(r.prompt, np.asarray(h.tokens, np.int32))
              for r, h in zip(reqs, handles)]
    return ctx, drv, served, below


def test_the_program_is_inside_its_limit(served_window):
    ctx, drv, served, below = served_window
    compared = drv.check(ctx, served, below)
    assert below == 0 and all(c.ok for c in compared)
    assert {c.name for c in compared} == {"precision_below_stated",
                                          "served_token_gap"}


def test_the_int8_control_in_the_program_s_place_is_outside(served_window):
    ctx, drv, served, below = served_window
    compared = {c.name: c for c in drv.check(ctx, served, below,
                                             in_place="int8")}
    assert not compared["served_token_gap"].ok


def test_the_driver_is_the_hybrid_window_with_another_engine():
    """One window: this driver loads ``serve_hybrid`` as a module of its own
    and gives it another engine, reference and counters; the accepted
    driver, loaded again, is as it was."""
    drv = mf.load_driver("serve_nemotronh")
    theirs = mf.load_driver("serve_hybrid")
    assert drv._sh.build_engine is drv.build_engine
    assert drv._sh.score is drv.score
    assert theirs.build_engine is not drv.build_engine
    assert "moe_prefill_layer_units" in drv._sh._COUNTERS
    assert "moe_prefill_layer_units" not in theirs._COUNTERS
    for name in ("serve_window", "score", "build_engine", "Tracked",
                 "offer_open", "wait_all", "check", "run"):
        assert callable(getattr(drv, name))     # what the tools ask of it


def test_a_narrower_recurrent_state_is_counted(served_window):
    ctx, drv, _, _ = served_window
    import jax.numpy as jnp
    engine = drv.build_engine(ctx)
    assert drv.precision_below_stated(engine, ctx.cfg) == 0
    assert sum(c is None for c in engine.caches[1:-2]) == 4    # E blocks
    engine.caches = [dict(c, S=c["S"].astype(jnp.bfloat16))
                     if isinstance(c, dict) and "S" in c else c
                     for c in engine.caches]
    # each of the four: against ``recurrent_state`` and, in a rehearsal
    # (float32 throughout), as a leaf of the caches against ``kv_cache``
    assert drv.precision_below_stated(engine, ctx.cfg) == 2 * 4


# -- the engine is handed the form it holds ---------------------------------------

def test_the_engine_is_built_without_the_originals_beside_their_transposes():
    """``program_nemotronh.build_engine`` lays each layer out as it is drawn
    and hands the engine ``store_for_serving``'s form: the same engine as one
    built from a ``FittedModel`` of the model's own layout (PR 35's way), and
    no expert layer's ``w_in`` alive beside its ``w_in_t`` when it returns.
    At a hidden size the rule engages for (128 lanes; the rehearsal's 64
    does not)."""
    import gc
    import jax
    from benchmarks.lib import program_nemotronh as P
    from distkeras_tpu.core.model import FittedModel
    from distkeras_tpu.serving import ServingEngine
    cfg = dict(mf.resolve_sizes(mf.Manifest().config(CONFIG), True),
               hidden_size=128)
    d = C.dims(cfg)
    original = (d["held"], d["hidden"], d["expert_dim"])
    experts = d["kinds"].count("experts")

    def originals_alive():
        gc.collect()
        return sum(a.shape == original for a in jax.live_arrays())

    before = originals_alive()
    engine = P.build_engine(cfg, 21)
    assert originals_alive() == before
    assert engine.stats["moe_up_projections_transposed"] == experts == 4
    blocks = [p["ffn"] for p in engine.params if "ffn" in p]
    assert all("w_in" not in b and b["w_in_t"].shape == (
        d["held"], d["expert_dim"], d["hidden"]) for b in blocks)
    old = ServingEngine(FittedModel(P.build_model(cfg),
                                    P.program_params(cfg, 21)),
                        **cfg["deployment"]["engine"])
    assert old.stats["moe_up_projections_transposed"] == experts
    for mine, theirs in zip(jax.tree_util.tree_leaves(engine.params),
                            jax.tree_util.tree_leaves(old.params)):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, d["vocab"], n).astype(np.int32)
               for n in (9, 40, 70)]           # a bucket, and chunked units
    served = []
    for eng in (engine, old):
        handles = [eng.submit(p, 12) for p in prompts]
        eng.run_until_idle()
        served.append([list(h.tokens) for h in handles])
    assert served[0] == served[1] and all(len(t) == 12 for t in served[0])


def test_the_share_of_values_over_so_many_medians():
    from benchmarks.lib.stats import share_over_pct
    gaps = [7.0] * 17 + [29.0, 30.0, 31.0]          # 15 % carry a unit
    assert share_over_pct(gaps, 2.0) == pytest.approx(15.0)
    assert share_over_pct([7.0, 8.0, 14.0], 2.0) == 0.0   # 14 is not OVER 16
    # where most gaps carry a unit the median is such a gap: near nothing
    assert share_over_pct([30.0] * 12 + [7.0] * 8, 2.0) == 0.0
    assert share_over_pct([], 2.0) == 0.0


def test_the_sweep_prints_the_share_of_gaps_that_carry_a_prefill_unit():
    """``tools/sweep_serve.py`` in rehearsal: a rung's line holds
    ``gaps_over_2x_p50_pct`` (the class of token gap that carries a prefill
    unit, from the stamps) and the engine's own count of it, both shares."""
    import subprocess
    import sys
    tool = os.path.join(os.path.dirname(mf.__file__), os.pardir, "tools",
                        "sweep_serve.py")
    p = subprocess.run(
        [sys.executable, tool, "--workload", CELL, "--rates", "8",
         "--seconds", "2", "--rehearse"], capture_output=True, text=True,
        timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    rung = next(json.loads(l) for l in p.stdout.splitlines()
                if l.startswith('{"rate"'))
    assert rung["rate"] == 8.0 and rung["ok"] == rung["requests"] > 0
    for key in ("gaps_over_2x_p50_pct", "gaps_with_prefill_unit_pct"):
        assert 0.0 <= rung[key] <= 100.0
    assert "0.6 x the knee" in open(tool).read()
    assert "0.8 x the knee" not in open(tool).read()


# -- the command ---------------------------------------------------------------

@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_of_the_cell(trace):
    p = start("--workload", CELL, "--seed", str(2 ** 31 + 29),
              "--seconds", "3", "--trace", trace, "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    man = mf.Manifest()
    declared = {m["name"]: m for m in (
        man.end_to_end(CELL) if trace == "0" else man.per_layer(CELL))}
    assert out["metrics"] and set(out["metrics"]) <= set(declared)
    if trace == "0":
        assert set(out["metrics"]) == set(declared)
    else:
        assert "expert_load_max_over_mean.nemotronh" in out["metrics"]
        assert all(declared[n]["source"] != "device_trace"
                   for n in out["metrics"])
    hybrid = next(json.loads(l) for l in lines
                  if '"driver": "serve_hybrid"' in l)
    assert hybrid["prefix_hit_tokens"] == 0
    assert hybrid["recurrent_slots_cleared"] == hybrid["requests"]
    mine = next(json.loads(l) for l in lines
                if '"driver": "serve_nemotronh"' in l)
    assert mine["window_requests"] == out["attempted"]
    assert mine["prefill_units"] > 0 and mine["prefill_tokens"] > 0
    assert mine["gaps_with_prefill_unit_pct"] > 0
    assert 0.0 <= mine["gaps_over_2x_p50_pct"] <= 100.0
    stalls = next(json.loads(l) for l in lines
                  if '"stalls": "serve_nemotronh"' in l)
    assert 0 <= stalls["engine_quiet_max_ms"] < 3000
    assert stalls["gen_lag_max_ms"] is not None


def test_the_parent_under_these_files_fails_at_once(tmp_path):
    """A program without the layers (the parent of the PR that added them)
    stops in ``program_nemotronh.import_layers``, by name, before anything
    is built."""
    from benchmarks.lib import program_nemotronh
    import distkeras_tpu.core.layers as layers
    real = layers.Mamba2Mixer
    del layers.Mamba2Mixer
    try:
        with pytest.raises(ImportError, match="Mamba2Mixer"):
            program_nemotronh.import_layers()
    finally:
        layers.Mamba2Mixer = real
