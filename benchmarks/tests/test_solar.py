"""The yardstick of the hybrid serving cell (``serve-reason-solar2``): its
configuration against the catalog's numbers, its counts on hand-computed
shapes, each ``.hybrid`` reader on a made-up trace, the manifest's pairing,
the controls of ``correct`` at test size, and a rehearsal of the command."""

import json
import os

import numpy as np
import pytest

from benchmarks.lib import counts_solar as C
from benchmarks.lib import manifest as mf, spans as S, trace as T

from helpers import context
from test_run import start

CELL = "serve-reason-solar2"
PEAKS = dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)


@pytest.fixture(scope="module")
def cfg():
    return mf.Manifest().config("solar-open2-250b")


# -- the configuration ----------------------------------------------------------

def test_every_catalog_number_is_under_its_key_or_listed_as_reduced(cfg):
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    row = next(json.loads(l) for l in open(catalog)
               if '"Solar-Open2-250B"' in l)
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert all(cfg["published"][k] == row["config"][k] for k in differs)
    entry = next(c for c in mf.Manifest().data["configs"]
                 if c["name"] == "solar-open2-250b")
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]


def test_the_cut_keeps_to_the_floors(cfg):
    d = C.dims(cfg)
    assert d["kinds"] == ["gqa", "kda", "kda", "kda"]      # a whole period
    assert d["held"] >= 8 and d["held"] * 8 == d["experts"]
    assert d["vocab"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["deployment"]["layer_shared_by_chips"] == 8


# -- counts, by hand -----------------------------------------------------------

def test_parameters_are_the_issue_s_arithmetic(cfg):
    assert C.expert_params(cfg) == 3 * 4096 * 1280 == 15_728_640
    assert C.mixer_params(cfg, "gqa") == (3 * 4096 * 8192
                                          + 2 * 4096 * 1024) == 109_051_904
    low_rank = 4096 * 128 + 128 * 8192
    assert C.mixer_params(cfg, "kda") == (4 * 4096 * 8192 + 2 * low_rank
                                          + 4096 * 64) == 137_625_600
    per_layer_rest = 4096 * 320 + 15_728_640
    assert C.dense_params(cfg) == (109_051_904 + 3 * 137_625_600
                                   + 4 * per_layer_rest + 4096 * 24576)
    # 3,308 M parameters = 6.6 GB in bf16
    assert round(C.total_params(cfg) / 1e6) == 3308


def test_state_and_flops(cfg):
    s = 64 * 128 * 128 * 4
    conv = 3 * 3 * 8192 * 2
    assert C.recurrent_state_bytes(cfg) == 3 * (s + conv)
    assert C.kv_bytes_per_token(cfg) == 2 * 8 * 128 * 2        # one layer
    assert C.held_share(cfg) == 1.0                  # 8 x 40 / 320
    no_ctx = C.flops_per_token(cfg, 0.0, False)
    assert no_ctx == 2.0 * (C.dense_params(cfg) - 4096 * 24576
                            + 4 * C.expert_params(cfg)) \
        + 3 * 7.0 * 64 * 128 * 128
    assert C.flops_per_token(cfg, 100.0, True) == (
        no_ctx + 4.0 * 64 * 128 * 100 + 2.0 * 4096 * 24576)


def test_least_bytes_and_seconds(cfg):
    b = C.decode_least_bytes(cfg, 2, 10, 7, 1000)
    assert b == (2 * C.dense_params(cfg) * 2 + 7 * 15_728_640 * 2
                 + 10 * 2 * C.recurrent_state_bytes(cfg) + 1000 * 4096)
    # three touched experts are 94 MB: bytes bound a handful of rows
    t = C.experts_least_seconds(cfg, 5, 3, 197e12, 819e9)
    assert t == pytest.approx(3 * 15_728_640 * 2 / 819e9)
    # 10,000 rows on one expert are bound by FLOPs
    t = C.experts_least_seconds(cfg, 10_000, 1, 197e12, 819e9)
    assert t == pytest.approx(2.0 * 10_000 * 15_728_640 / 197e12)
    assert C.kda_decode_least_seconds(cfg, 4, 819e9) == pytest.approx(
        4 * 3 * 2 * 64 * 128 * 128 * 4 / 819e9)


# -- the readers on a made-up trace -------------------------------------------

WINDOW = (1_000, 10_000_000)
MOE = ("%custom-call.4 = f32[1024,2560] custom-call(bf16[1024,4096] %p.1), "
       'custom_call_target="tpu_custom_call"',
       "jit(pstep)/block_1/moe/moe_experts/pallas_call:", 400_000)
ROUTE = ("%fusion.2 = f32[128,320] fusion(f32[128,4096] %p.2)",
         "jit(pstep)/block_1/moe/moe_route/dot_general:", 50_000)
KDA = ("%custom-call.9 = f32[128,64,128,128] custom-call(f32[128,64,128] "
       '%p.3), custom_call_target="tpu_custom_call"',
       "jit(pstep)/block_1/kda/kda_core/pallas_call:", 300_000)
KDA_XLA = ("%fusion.5 = f32[128,64,128] fusion(f32[128,64,128] %p.4)",
           "jit(pstep)/block_1/kda/kda_core/exp:", 20_000)
HEAD = ("%fusion.7 = f32[128,24576] fusion(bf16[128,4096] %p.5)",
        "jit(pstep)/lm_head/dot_general:", 230_000)
STEP = [MOE, ROUTE, KDA, KDA_XLA, HEAD]                    # 1,000,000 ns


def made_up(monkeypatch, steps=2, pieces=STEP):
    ops, runs, at = [], [], 2_000
    for _ in range(steps):
        start_ = at
        for name, op_name, ns in pieces:
            ops.append(S.Op(at, at + ns, name, op_name))
            at += ns
        runs.append((start_, at))
        at += 10_000
    plane = T.DevicePlane("/device:TPU:0",
                          [(o.start, o.end, o.name) for o in ops],
                          [(s, e, "jit_pstep(1)") for s, e in runs])
    monkeypatch.setattr(S, "of_run", lambda t: S.Spans([], ops))
    return T.Trace([plane], {T.WINDOW_SPAN: [WINDOW]})


def records(steps=2, **over):
    traced = dict(decode_steps=steps, active_slot_steps=60 * steps,
                  prefill_tokens=512, moe_assignments_held=480 * steps,
                  moe_experts_touched=120 * steps, moe_load_max=30 * steps,
                  moe_layer_steps=4 * steps, seconds=0.01)
    out = dict(kind="serve", decode_programs=["jit_pstep"],
               serve_programs=["jit_pstep", "jit_run"],
               traced_context_positions=40_000, traced_counters=traced,
               window_counters=dict(traced))
    out.update(over)
    return out


def read(name, rec, trace, cfg):
    return mf.load_layer_metric(name).read(
        rec, trace, dict(cfg=cfg, peaks=PEAKS, chips=1))


def test_scope_shares(monkeypatch, cfg):
    trace = made_up(monkeypatch)
    assert read("moe_device_pct.hybrid", records(), trace, cfg) == \
        pytest.approx(45.0)
    assert read("kda_device_pct.hybrid", records(), trace, cfg) == \
        pytest.approx(32.0)


def test_rooflines_divide_the_least_time_by_the_measured(monkeypatch, cfg):
    trace = made_up(monkeypatch)
    rec = records()
    least = C.experts_least_seconds(cfg, 960, 240, 197e12, 819e9)
    assert read("moe_experts_roofline.hybrid", rec, trace, cfg) == \
        pytest.approx(100 * least / 800e-6)
    # the Pallas kernel under kda_core alone, not the XLA ops beside it
    least = C.kda_decode_least_seconds(cfg, 120, 819e9)
    assert read("kda_decode_roofline.hybrid", rec, trace, cfg) == \
        pytest.approx(100 * least / 600e-6)
    least = C.decode_least_bytes(cfg, 2, 120, 240, 40_000) / 819e9
    assert read("decode_hbm_roofline.hybrid", rec, trace, cfg) == \
        pytest.approx(100 * least / 2e-3)


def test_counters_are_scaled_to_the_runs_the_trace_holds(monkeypatch, cfg):
    trace = made_up(monkeypatch, steps=2)
    twice = records(steps=4)            # the host counted 4, the trace has 2
    twice["traced_context_positions"] = 80_000
    for name in ("moe_experts_roofline.hybrid", "kda_decode_roofline.hybrid",
                 "decode_hbm_roofline.hybrid"):
        assert read(name, twice, trace, cfg) == pytest.approx(
            read(name, records(), trace, cfg))


def test_mfu_counts_prompt_and_decoded_tokens(monkeypatch, cfg):
    trace = made_up(monkeypatch)
    flops = (512 * C.flops_per_token(cfg, 0.0, False)
             + 120 * C.flops_per_token(cfg, 0.0, True)
             + 4.0 * 64 * 128 * 40_000)
    assert read("mfu.hybrid", records(), trace, cfg) == pytest.approx(
        100 * flops / 0.01 / 197e12)


def test_load_skew_from_the_counters(cfg):
    # fullest 30 rows against a mean of 480 / 40 = 12
    assert read("expert_load_max_over_mean.hybrid", records(), None, cfg) \
        == pytest.approx(2.5)


@pytest.mark.parametrize("name", [
    "mfu.hybrid", "decode_hbm_roofline.hybrid", "moe_device_pct.hybrid",
    "kda_device_pct.hybrid", "moe_experts_roofline.hybrid",
    "kda_decode_roofline.hybrid", "expert_load_max_over_mean.hybrid"])
def test_a_program_without_the_counters_reads_nothing(monkeypatch, cfg,
                                                      name):
    """The parent of the PR that added them: no counters, no scopes."""
    bare = dict(kind="serve", decode_programs=["jit_pstep"],
                traced_context_positions=10, traced_counters=None,
                window_counters=None)
    trace = made_up(monkeypatch, pieces=[(
        "%fusion.1 = f32[8] fusion(f32[8] %p)", "jit(pstep)/add:", 100)])
    assert read(name, bare, trace, cfg) is None
    assert read(name, dict(kind="train"), None, cfg) is None


def test_the_manifest_pairs_the_cell_with_its_metrics():
    man = mf.Manifest()
    # the cell is judged on its token gaps and the tokens it completes; its
    # time to first token spreads too widely over seeds to be listed (PERF.md)
    assert {m["name"] for m in man.end_to_end(CELL)} == {
        "itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    mine = {m["name"]: m for m in man.per_layer(CELL)}
    hybrid = {n for n in mine if n.endswith(".hybrid")}
    assert hybrid == {
        "mfu.hybrid", "decode_hbm_roofline.hybrid", "moe_device_pct.hybrid",
        "kda_device_pct.hybrid", "moe_experts_roofline.hybrid",
        "kda_decode_roofline.hybrid", "expert_load_max_over_mean.hybrid"}
    assert all(mine[n]["moves"] == "itl_p95_ms" for n in hybrid)
    assert "decode_hbm_roofline.serve" not in mine     # GPT-2's count
    # the accepted readers that read its records as they are
    assert {"batch_occupancy_pct.serve", "decode_step_ms.serve",
            "device_idle_pct.serve", "iteration_p95_ms.serve",
            "host_busy_ms.serve"} <= set(mine)
    reported = {m["name"] for m in man.end_to_end(CELL)}
    assert all(m["moves"] in reported for m in mine.values())
    theirs = {m["name"] for m in man.per_layer("serve-chat-gpt2m")}
    assert "decode_hbm_roofline.serve" in theirs
    assert not {n for n in theirs if n.endswith(".hybrid")}


def test_the_traffic_file_is_the_issue_s_table():
    t = mf.Manifest().traffic("reason-open-poisson")
    assert t["kind"] == "serve_hybrid" and t["shape_seed"] == 0
    assert t["arrival"]["process"] == "poisson"
    assert t["prompt_len"] == dict(dist="lognormal", median=256, sigma=0.9,
                                   min=32, max=2048)
    assert t["output_len"] == dict(dist="lognormal", median=256, sigma=0.7,
                                   min=32, max=1024)
    assert (t["prefix_groups"], t["lead_in_s"], t["drain_timeout_s"]) == \
        (0, 8, 120)
    assert t["trace"] == dict(start_s=12, span_s=6)
    assert round(t["arrival"]["rate"] * 40) >= 300      # requests a window
    assert t["correct"]["sample"] == 64


# -- correct at test size: the program inside, the control outside -------------

@pytest.fixture(scope="module")
def served_window():
    ctx = context(CELL, seconds=3.0)
    drv = mf.load_driver("serve_hybrid")
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


def test_the_controls_are_those_that_read_outside():
    """A recurrent state rounded to bfloat16 reads inside every limit made
    from tokens, so it is no control: the files say that it is held by type
    alone, and name one control."""
    man = mf.Manifest()
    cfg = man.config("solar-open2-250b")
    assert set(cfg["controls"]) == {"int8_reference"}
    assert set(man.traffic("reason-open-poisson")["controls"]) == {
        "int8_reference"}
    assert "precision_below_stated" in cfg["not_held_numerically"]
    assert cfg["precision"]["recurrent_state"] == "float32"


def test_a_narrower_recurrent_state_is_counted(served_window):
    ctx, drv, _, _ = served_window
    import jax.numpy as jnp
    engine = drv.build_engine(ctx)
    assert drv.precision_below_stated(engine, ctx.cfg) == 0
    engine.caches = [dict(c, S=c["S"].astype(jnp.bfloat16))
                     if isinstance(c, dict) and "S" in c else c
                     for c in engine.caches]
    assert drv.precision_below_stated(engine, ctx.cfg) == 3


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
        assert "expert_load_max_over_mean.hybrid" in out["metrics"]
        assert all(declared[n]["source"] != "device_trace"
                   for n in out["metrics"])
    driver = next(json.loads(l) for l in lines if '"driver"' in l)
    assert driver["prefix_hit_tokens"] == 0
    assert driver["recurrent_slots_cleared"] == driver["requests"]
