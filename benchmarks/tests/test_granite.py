"""The yardstick of the bursty chat cell (``serve-chat-granite4hm``): its
configuration against the catalog's numbers, its counts against hand
arithmetic, the reference against itself, the weights' draw, each
``.granite`` reader on a made-up trace, the manifest's pairing, the controls
of ``correct`` at test size, the driver as a third copy of one window, and a
rehearsal of the command."""

import json
import os

import numpy as np
import pytest

from benchmarks.lib import counts_granite as C
from benchmarks.lib import manifest as mf, spans as S, trace as T

from helpers import context
from test_run import start

CELL = "serve-chat-granite4hm"
CONFIG = "granite-4.0-h-micro"
TRAFFIC = "chat-burst-gamma"
PEAKS = dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)
READERS = ["mfu.granite", "decode_hbm_roofline.granite",
           "ssd_decode_roofline.granite", "ssm_device_pct.granite",
           "mlp_device_pct.granite", "prefill_unit_ms.granite"]


@pytest.fixture(scope="module")
def cfg():
    return mf.Manifest().config(CONFIG)


# -- the configuration ----------------------------------------------------------

def test_every_catalog_number_is_under_its_key_and_nothing_is_reduced(cfg):
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    row = next(json.loads(l) for l in open(catalog)
               if '"granite-4.0-h-micro"' in l)
    assert {k for k, v in row["config"].items() if cfg.get(k) != v} == set()
    assert cfg["reduced"] == []
    entry = next(c for c in mf.Manifest().data["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert entry["reduced"] == []


def test_the_whole_model_as_published(cfg):
    d = C.dims(cfg)
    assert d["layers"] == 40 and len(cfg["layer_types"]) == 40
    assert [i for i, k in enumerate(d["kinds"]) if k == "attn"] == \
        [5, 15, 25, 35]
    assert (d["inner"], d["conv_dim"], d["head_dim"], d["groups"]) == \
        (4096, 4352, 64, 1)
    assert (d["embed_mult"], d["resid_mult"], d["attn_mult"],
            d["logits_div"]) == (12.0, 0.22, 1 / 64, 8.0)
    assert cfg["tie_word_embeddings"] is True
    eng = cfg["deployment"]["engine"]
    assert eng == dict(num_slots=64, max_len=5120, paged=True, block_size=16,
                       kv_blocks=8192, prefill_chunk=512, queue_capacity=1024)
    assert eng["max_len"] == cfg["context"]["max_len"]
    assert cfg["deployment"]["layer_shared_by_chips"] == 1
    for key in ("head_dim", "mamba_in_proj_order", "mlp_order",
                "mamba_gate_norm", "mamba_dt", "attention_nope",
                "multipliers"):
        assert key in cfg["assumed"]


# -- counts, by hand -----------------------------------------------------------

def test_parameters_are_the_issue_s_arithmetic(cfg):
    mixer = 2048 * 8512 + 4096 * 2048
    small = 4 * 4352 + 4352 + 3 * 64 + 4096
    assert C.mixer_params(cfg, "mamba") == mixer == 25_821_184
    assert round((mixer + small) / 1e4) == 2585              # 25.85 M
    assert C.mlp_params(cfg) == 2048 * 16384 + 8192 * 2048 == 50_331_648
    assert C.small_params(cfg, "mamba") == small + 4096
    assert C.mixer_params(cfg, "attn") == (2 * 2048 * 2048
                                           + 2 * 2048 * 512) == 10_485_760
    mamba = mixer + small + 50_331_648 + 4096
    attn = 10_485_760 + 50_331_648 + 4096
    assert (round(mamba / 1e4), round(attn / 1e4)) == (7618, 6082)
    table = 100352 * 2048
    assert round(table / 1e5) == 2055
    assert C.total_params(cfg) == 36 * mamba + 4 * attn + table + 2048
    assert round(C.total_params(cfg) / 1e6) == 3191
    assert round(2 * C.total_params(cfg) / 1e7) == 638       # 6.38 GB
    # the table is multiplied through ONCE (the head), never twice
    assert C.matmul_params(cfg) == 36 * (mixer + 50_331_648) + 4 * (
        10_485_760 + 50_331_648) + table


def test_state_keys_and_flops(cfg):
    s = 64 * 64 * 128 * 4
    conv = 3 * 4352 * 2
    assert C.ssm_state_bytes(cfg) == s == 2_097_152
    assert C.recurrent_state_bytes(cfg) == 36 * (s + conv) == 76_437_504
    assert round(64 * C.recurrent_state_bytes(cfg) / 1e7) == 489   # 4.89 GB
    assert C.kv_bytes_per_token(cfg) == 4 * 2 * 512 * 2 == 8192    # 8 KB
    assert 8192 * 16 * 8192 == 1_073_741_824                       # the pool
    no_ctx = C.flops_per_token(cfg, 0.0, False)
    assert no_ctx == 2.0 * (C.matmul_params(cfg) - 100352 * 2048) \
        + 36 * 5.0 * 64 * 64 * 128
    assert C.flops_per_token(cfg, 100.0, True) == (
        no_ctx + 4 * 4.0 * 32 * 64 * 100 + 2.0 * 2048 * 100352)
    # a unit of 512 tokens: 3.1 TFLOP before its attention
    assert round(512 * no_ctx / 1e11) == 31


def test_least_bytes_and_seconds(cfg):
    moved = 10 * 2 * C.recurrent_state_bytes(cfg)
    assert C.decode_least_bytes(cfg, 2, moved, 1000) == (
        2 * C.matmul_params(cfg) * 2 + moved + 1000 * 8192)
    assert C.ssd_decode_least_seconds(cfg, 4, 819e9) == pytest.approx(
        4 * 36 * 2 * 64 * 64 * 128 * 4 / 819e9)


# -- the reference against itself; the weights' draw ------------------------------

@pytest.fixture(scope="module")
def tiny():
    import copy
    from benchmarks.lib.weights_granite import make_weights
    cfg = copy.deepcopy(mf.resolve_sizes(mf.Manifest().config(CONFIG), True))
    return cfg, C.dims(cfg), make_weights(cfg, 11, "float32")


def test_the_reference_whole_is_the_reference_padded(tiny):
    import jax.numpy as jnp
    from benchmarks.lib import reference_granite as ref
    cfg, d, w = tiny
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 512, 21).astype(np.int32)
    served = rng.integers(0, 512, 9).astype(np.int32)
    row = np.concatenate([prompt, served[:-1]])
    logits = np.asarray(ref.logits_fn(w, jnp.asarray(row), d))[20:]
    gaps, first = ref.served_position_scores(w, prompt, served, [served], d,
                                             ref.pad_length(30, 16))
    np.testing.assert_array_equal(first, logits.argmax(-1))
    np.testing.assert_allclose(
        gaps[0], logits.max(-1) - logits[np.arange(9), served], atol=1e-6)


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(mf.BENCH_DIR, "lib", "reference_granite.py")
    imports = [line for line in open(path).read().splitlines()
               if line.startswith(("import ", "from "))]
    assert imports and not [line for line in imports
                            if "distkeras" in line or "program" in line]


def test_weights_are_the_seeds_and_the_head_is_the_table(tiny):
    from benchmarks.lib import program_granite
    from benchmarks.lib.weights_granite import make_weights, _scale
    cfg, d, w = tiny
    again = make_weights(cfg, 11, "float32")
    other = make_weights(cfg, 12, "float32")
    assert "head" not in w and set(w) == {"embed", "final_norm", "layers"}
    np.testing.assert_array_equal(w["embed"], again["embed"])
    np.testing.assert_array_equal(w["layers"][3]["w_in"],
                                  again["layers"][3]["w_in"])
    assert not np.array_equal(w["embed"], other["embed"])
    assert [l["kind"] for l in w["layers"]] == d["kinds"]
    assert _scale(2048) == 1.0 and _scale(64) == pytest.approx(32 ** 0.5)
    # the program's list: one table, an EMPTY entry for the tied head
    params = program_granite.program_params(
        dict(cfg, precision=dict(cfg["precision"], params="float32")), 11)
    assert params[-1] == {} and set(params[0]) == {"embedding"}
    np.testing.assert_array_equal(params[0]["embedding"], w["embed"])
    assert set(params[1]) == {"norm1", "mixer", "norm2", "ffn"}
    # a 2**31-and-over seed is taken
    make_weights(cfg, 2 ** 31 + 29, "float32")


def test_the_state_matters_and_no_common_vector_decides_the_token(tiny):
    """What the draw is for: at 40 of these blocks the stream is wide beside
    a token's own embedding (the tied head meets it again), a mixer's output
    depends on tokens long past (the state is not decoration), and no common
    vector fills the stream."""
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import reference_granite as ref
    from benchmarks.lib import weights_granite as W
    cfg, d, _ = tiny
    deep = dict(cfg, num_hidden_layers=40,
                layer_types=mf.Manifest().config(CONFIG)["layer_types"],
                embedding_multiplier=12)
    dd = C.dims(deep)
    w = W.make_weights(deep, 5, "float32")
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 512, 160))
    x = ref.hidden(w, toks, dd)
    rms = float(jnp.sqrt(jnp.mean(x * x)))
    x0 = 12 * float(jnp.sqrt(jnp.mean(w["embed"] ** 2)))
    assert rms > 5 * x0
    common = float(jnp.sum(jnp.mean(x, 0) ** 2)
                   / jnp.mean(jnp.sum(x * x, -1)))
    assert common < 0.05
    first = np.asarray(ref.logits_fn(w, toks, dd).argmax(-1))
    assert np.mean(first == np.asarray(toks)) < 0.1
    assert len(set(first.tolist())) > 100
    p = {k: v for k, v in w["layers"][0].items() if k != "kind"}
    u = ref.rms_norm(x, p["norm"], dd["eps"])
    y = ref.mamba_mixer(u, p, dd, ref.f32_matmul)
    cold = ref.mamba_mixer(u[128:], p, dd, ref.f32_matmul)
    off = float(jnp.sqrt(jnp.mean((cold[8] - y[136]) ** 2)
                         / jnp.mean(y[136] ** 2)))
    assert off > 0.1


# -- the readers on a made-up trace -------------------------------------------

WINDOW = (1_000, 10_000_000)
CALL = 'custom_call_target="tpu_custom_call"'
SSD = ("%custom-call.9 = f32[64,64,64,128] custom-call(f32[64,64,64] "
       "%p.3), " + CALL, "jit(pstep)/block_0/ssm/ssm_core/pallas_call:",
       300_000)
SSD_XLA = ("%fusion.5 = f32[64,64,64] fusion(f32[64,64,64] %p.4)",
           "jit(pstep)/block_0/ssm/ssm_core/exp:", 20_000)
MLP_IN = ("%fusion.2 = f32[64,16384] fusion(bf16[64,2048] %p.2)",
          "jit(pstep)/block_0/mlp/mlp_in/dot_general:", 300_000)
MLP_OUT = ("%fusion.3 = f32[64,2048] fusion(bf16[64,8192] %p.6)",
           "jit(pstep)/block_0/mlp/mlp_out/dot_general:", 150_000)
HEAD = ("%fusion.7 = f32[64,100352] fusion(bf16[64,2048] %p.5)",
        "jit(pstep)/lm_head/dot_general:", 230_000)
STEP = [SSD, SSD_XLA, MLP_IN, MLP_OUT, HEAD]               # 1,000,000 ns
UNIT = [("%fusion.8 = f32[512,16384] fusion(bf16[512,2048] %p.1)",
         "jit(stage)/block_1/mlp/mlp_in/dot_general:", 1_200_000),
        ("%fusion.9 = f32[1,512,64,64] fusion(f32[1,512,64,64] %p.4)",
         "jit(stage)/block_0/ssm/ssm_core/ssd_chunk/while:", 800_000)]


def made_up(monkeypatch, steps=2, units=1):
    ops, modules, at = [], [], 2_000
    for program, body in ([("jit_pstep(1)", STEP)] * steps
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


def records(cfg, steps=2, units=1, rows=20, **over):
    traced = dict(decode_steps=steps, active_slot_steps=rows * steps,
                  prefill_tokens=512 * units, prefill_chunks=units,
                  prefill_batches=0, recurrent_state_bytes_moved=(
                      rows * steps * 2 * C.recurrent_state_bytes(cfg)),
                  seconds=0.01)
    out = dict(kind="serve", decode_programs=["jit_pstep"],
               serve_programs=["jit_pstep", "jit_run", "jit_stage"],
               traced_context_positions=40_000, traced_counters=traced,
               window_counters=dict(traced))
    out.update(over)
    return out


def read(name, rec, trace, cfg):
    return mf.load_layer_metric(name).read(
        rec, trace, dict(cfg=cfg, peaks=PEAKS, chips=1,
                         traffic=mf.Manifest().traffic(TRAFFIC)))


def test_scope_shares(monkeypatch, cfg):
    trace = made_up(monkeypatch)          # 2 x 1,000 us + one unit of 2,000
    assert read("ssm_device_pct.granite", records(cfg), trace, cfg) == \
        pytest.approx(100 * (2 * 320 + 800) / 4000)
    assert read("mlp_device_pct.granite", records(cfg), trace, cfg) == \
        pytest.approx(100 * (2 * 450 + 1200) / 4000)


def test_rooflines_divide_the_least_time_by_the_measured(monkeypatch, cfg):
    trace = made_up(monkeypatch)
    rec = records(cfg)
    # the kernel alone (300 us a step), not the XLA ops beside it
    assert read("ssd_decode_roofline.granite", rec, trace, cfg) == \
        pytest.approx(100 * C.ssd_decode_least_seconds(cfg, 40, 819e9)
                      / 600e-6)
    moved = 40 * 2 * C.recurrent_state_bytes(cfg)
    assert read("decode_hbm_roofline.granite", rec, trace, cfg) == \
        pytest.approx(100 * C.decode_least_bytes(cfg, 2, moved, 40_000)
                      / 819e9 / 2000e-6)
    assert read("prefill_unit_ms.granite", rec, trace, cfg) == \
        pytest.approx(2.0)


def test_counters_are_scaled_to_the_runs_the_trace_holds(monkeypatch, cfg):
    """Three steps counted on the host, two whole in the trace: two thirds
    of the live rows, of the state's bytes and of the contexts."""
    trace = made_up(monkeypatch, steps=2)
    rec = records(cfg, steps=3)
    moved = 40 * 2 * C.recurrent_state_bytes(cfg)
    assert read("decode_hbm_roofline.granite", rec, trace, cfg) == \
        pytest.approx(100 * C.decode_least_bytes(
            cfg, 2, moved, 40_000 * 2 / 3) / 819e9 / 2000e-6)
    assert read("ssd_decode_roofline.granite", rec, trace, cfg) == \
        pytest.approx(100 * C.ssd_decode_least_seconds(cfg, 40, 819e9)
                      / 600e-6)


def test_mfu_counts_prompt_and_decoded_tokens(monkeypatch, cfg):
    trace = made_up(monkeypatch)
    flops = (512 * C.flops_per_token(cfg, 0.0, False)
             + 40 * C.flops_per_token(cfg, 0.0, True)
             + 4.0 * 32 * 64 * 4 * 40_000)
    assert read("mfu.granite", records(cfg), trace, cfg) == pytest.approx(
        100 * flops / 0.01 / 197e12)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_counter_or_the_scopes_reads_nothing(
        monkeypatch, cfg, name):
    """The parent of the PR that added the cell: no
    ``recurrent_state_bytes_moved`` (the driver's ``stats.get`` gives 0), no
    ``mlp`` scope in a ``HybridBlock``, or no trace at all: nothing is
    returned and nothing raises."""
    assert read(name, records(cfg), None, cfg) is None
    assert read(name, dict(kind="train"), made_up(monkeypatch), cfg) is None
    old = records(cfg)
    old["traced_counters"]["recurrent_state_bytes_moved"] = 0
    got = read(name, old, made_up(monkeypatch), cfg)
    if name == "decode_hbm_roofline.granite":
        assert got is None
    monkeypatch.setattr(S, "of_run", lambda t: S.Spans([], [
        S.Op(2_000, 900_000, HEAD[0], "jit(pstep)/dot_general:")]))
    if name in ("ssm_device_pct.granite", "mlp_device_pct.granite"):
        trace = T.Trace([T.DevicePlane(
            "/device:TPU:0", [(2_000, 900_000, HEAD[0])],
            [(2_000, 900_000, "jit_pstep(1)")])], {T.WINDOW_SPAN: [WINDOW]})
        assert read(name, records(cfg), trace, cfg) is None


# -- the manifest and the traffic file -----------------------------------------------

def test_the_manifest_pairs_the_cell_with_its_metrics():
    """Membership and subsets only, the cell and configuration found by
    name: a later PR appends cells, configurations and list entries, and
    none of that may fail here."""
    man = mf.Manifest()
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert man.config(CONFIG)["reduced"] == []
    listed = {m["name"] for m in man.end_to_end(CELL)}
    assert {"itl_p95_ms", "serve_tokens_per_s", "setup_s"} <= listed
    mine = {m["name"]: m for m in man.per_layer(CELL)}
    assert set(READERS) <= set(mine)
    for n in READERS:
        reader = mf.load_layer_metric(n)
        assert (mine[n]["layer"], mine[n]["unit"], mine[n]["better"],
                mine[n]["source"], mine[n]["moves"]) == (
            reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE,
            reader.MOVES)
        assert CELL in mine[n]["workloads"]
    # the other hybrid cells' readers reach their own program layout
    assert not {n for n in mine if n.endswith((".hybrid", ".nemotronh"))}
    # the accepted readers that read its records as they are.  Three more
    # (``decode_launch_ms.serve``, ``step_carries_prefill_pct.serve``,
    # ``queue_hold_pct.serve``) can read them too and wait on a
    # ``benchmark`` PR: PERF.md section 7 says which edit.  Nothing is
    # asserted of them here, so that the append needs no edit to this file.
    assert {"batch_occupancy_pct.serve", "decode_step_ms.serve",
            "device_idle_pct.serve", "iteration_p95_ms.serve",
            "host_busy_ms.serve"} <= set(mine)
    assert all(m["moves"] in listed for m in mine.values())


def test_the_traffic_file_is_the_issue_s_table():
    t = mf.Manifest().traffic(TRAFFIC)
    assert t["kind"] == "serve_granite" and t["shape_seed"] == 0
    assert t["arrival"]["process"] == "gamma" and t["arrival"]["cv"] == 2.0
    assert t["arrival"]["rate"] * 2 == int(t["arrival"]["rate"] * 2)
    assert t["prompt_len"] == dict(dist="lognormal", median=192, sigma=1.0,
                                   min=16, max=4096)
    assert t["output_len"] == dict(dist="lognormal", median=128, sigma=0.7,
                                   min=8, max=1024)
    assert (t["prefix_groups"], t["lead_in_s"], t["drain_timeout_s"]) == \
        (0, 10, 120)
    assert t["correct"]["sample"] == 48 and t["correct"]["pad_to"] == 512
    assert t["correct"]["limits"] == dict(served_mean_gap_vs_int8=0.1,
                                          served_token_gap=0.06)
    assert t["trace"]["span_s"] <= 3      # 6 s took the traced run over 360 s
    assert t["prompt_len"]["max"] + t["output_len"]["max"] <= \
        mf.Manifest().config(CONFIG)["deployment"]["engine"]["max_len"]
    assert set(t["prefill_programs"]) < set(t["serve_programs"])
    # the generator that is there draws the bursts: gaps with CV 2
    from benchmarks.lib.traffic import generate
    reqs = generate(dict(t, arrival=dict(t["arrival"], rate=50.0)), 1, 40.0,
                    1000)
    due = np.array([r.due_s for r in reqs if r.phase == "window"])
    gaps = np.diff(due)
    assert 1.6 < gaps.std() / gaps.mean() < 2.4
    same = generate(dict(t, arrival=dict(t["arrival"], rate=50.0)), 2, 40.0,
                    1000)
    assert [r.due_s for r in same] == [r.due_s for r in reqs]


# -- correct at test size: the program inside, the control outside -------------

@pytest.fixture(scope="module")
def served_window():
    ctx = context(CELL, seconds=3.0)
    drv = mf.load_driver("serve_granite")
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


def test_a_narrower_recurrent_state_is_counted(served_window):
    ctx, drv, _, _ = served_window
    import jax.numpy as jnp
    engine = drv.build_engine(ctx)
    assert drv.precision_below_stated(engine, ctx.cfg) == 0
    n = C.count(ctx.cfg, "mamba")
    engine.caches = [dict(c, S=c["S"].astype(jnp.bfloat16))
                     if isinstance(c, dict) and "S" in c else c
                     for c in engine.caches]
    assert drv.precision_below_stated(engine, ctx.cfg) == 2 * n


def test_the_driver_is_a_third_copy_of_one_window():
    """This driver loads ``serve_hybrid`` as a module of its own and gives
    it another engine, reference and counter; the two accepted drivers,
    loaded again, are as they were."""
    drv = mf.load_driver("serve_granite")
    hybrid = mf.load_driver("serve_hybrid")
    nemo = mf.load_driver("serve_nemotronh")
    assert drv._sh.build_engine is drv.build_engine
    assert drv._sh.score is drv.score
    assert hybrid.build_engine is not drv.build_engine
    assert nemo._sh is not drv._sh and nemo._sh is not hybrid
    assert "recurrent_state_bytes_moved" in drv._sh._COUNTERS
    assert "recurrent_state_bytes_moved" not in hybrid._COUNTERS
    assert "recurrent_state_bytes_moved" not in nemo._sh._COUNTERS
    assert "moe_prefill_layer_units" not in drv._sh._COUNTERS
    assert nemo._sh.build_engine is nemo.build_engine
    for name in ("serve_window", "score", "build_engine", "Tracked",
                 "offer_open", "wait_all", "check", "run"):
        assert callable(getattr(drv, name))     # what the tools ask of it


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
        assert all(declared[n]["source"] != "device_trace"
                   for n in out["metrics"])
    hybrid = next(json.loads(l) for l in lines
                  if '"driver": "serve_hybrid"' in l)
    assert hybrid["prefix_hit_tokens"] == 0
    assert hybrid["recurrent_slots_cleared"] == hybrid["requests"]
    mine = next(json.loads(l) for l in lines
                if '"driver": "serve_granite"' in l)
    assert mine["window_requests"] == out["attempted"]
    assert mine["prefill_units"] > 0 and mine["prefill_tokens"] > 0
    assert mine["recurrent_state_mb_per_step"] > 0
    if trace == "1":
        span = next(json.loads(l) for l in lines if '"traced_rows_live"' in l)
        assert 0 < span["traced_rows_live"] <= 8
    served = next(json.loads(l) for l in lines if '"served_tokens"' in l)
    assert served["served_repeat_share"] < 0.5
    stalls = next(json.loads(l) for l in lines
                  if '"stalls": "serve_granite"' in l)
    assert stalls["gen_lag_max_ms"] is not None


def test_the_parent_under_these_files_fails_at_once():
    """A program without the layers (the parent of the PR that added them)
    stops in ``program_granite.import_layers``, by name, before anything is
    built."""
    from benchmarks.lib import program_granite
    import distkeras_tpu.core.layers as layers
    real = layers.TiedHead
    del layers.TiedHead
    try:
        with pytest.raises(ImportError, match="TiedHead"):
            program_granite.import_layers()
    finally:
        layers.TiedHead = real
