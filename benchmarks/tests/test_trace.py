"""The reduction from an ``.xplane.pb`` to numbers, on two small slices cut
out of a real trace of the training cell on a TPU v5 lite (PR 23's probe):

- ``train_epoch_boundary``: the last 25 ms of one ``jit_epoch`` run, the host
  gap, and the first 17.6 ms of the next; the benchmark's ``bench_window``
  annotation (added to the slice) covers the middle 30 ms;
- ``train_mid_step``: 36.5 ms in the middle of a step, from the last layer's
  forward flash kernel through the loss into the first backward kernels.
"""

import os

import pytest

from benchmarks.lib import counts, manifest as mf, trace as T

from helpers import FIXTURES


@pytest.fixture(scope="module")
def boundary():
    return T.load(os.path.join(FIXTURES,
                               "train_epoch_boundary.xplane.pb.gz"))


@pytest.fixture(scope="module")
def mid_step():
    return T.load(os.path.join(FIXTURES, "train_mid_step.xplane.pb.gz"))


def brute_force_busy(plane, window):
    """Independent of ``trace.union``: sweep over sorted leaf intervals."""
    lo, hi = window
    leaves = sorted((max(s, lo), min(e, hi)) for s, e, n in plane.ops
                    if T.opcode(n) not in ("while", "conditional", "call")
                    and e > lo and s < hi)
    covered, cursor = 0, lo
    for s, e in leaves:
        if e > cursor:
            covered += e - max(s, cursor)
            cursor = e
    return covered


def test_planes_lines_and_window(boundary):
    assert [d.name for d in boundary.devices] == ["/device:TPU:0"]
    d = boundary.devices[0]
    assert len(d.ops) == 6663 and len(d.modules) == 2
    assert {T.module_name(n) for _, _, n in d.modules} == {"jit_epoch"}
    lo, hi = boundary.window
    assert hi - lo == 30_000_000          # the annotation, not the events


def test_busy_is_the_union_of_leaf_ops_inside_the_window(boundary):
    busy, window = T.busy_and_window_s(boundary)
    d = boundary.devices[0]
    assert window == pytest.approx(0.030)
    assert busy == pytest.approx(brute_force_busy(d, boundary.window) / 1e9)
    assert busy == pytest.approx(0.022538346)
    # the gap between the two epoch programs is host time: 7.4 of 30 ms
    assert 100 * (1 - busy / window) == pytest.approx(24.87, abs=0.01)


def test_containers_are_not_counted_as_work(mid_step):
    d = mid_step.devices[0]
    containers = [n for _, _, n in d.ops if not T.is_leaf(n)]
    assert sorted(T.short_name(n) for n in containers) == [
        "while.6798", "while.6799"]
    busy, window = T.busy_and_window_s(mid_step)
    # the two while events span the whole slice; the leaves leave 1 us idle
    assert busy < window and busy == pytest.approx(0.036511044)


def test_idle_gaps_are_labelled_by_the_programs_around_them(boundary):
    gaps = dict(T.idle_gaps(boundary))
    assert gaps["host between jit_epoch and jit_epoch"] == \
        pytest.approx(0.007441903)
    assert gaps["within jit_epoch"] < 1e-4
    busy, window = T.busy_and_window_s(boundary)
    assert sum(gaps.values()) == pytest.approx(window - busy)


def test_whole_program_runs_only(boundary, mid_step):
    d = boundary.devices[0]
    assert T.module_runs(d, boundary.window, ["jit_epoch"]) == []
    whole = (min(s for s, _, _ in d.modules), max(e for _, e, _ in d.modules))
    assert len(T.module_runs(d, whole, ["jit_epoch"])) == 2
    assert T.module_runs(d, whole, ["jit_other"]) == []
    m = mid_step.devices[0]
    runs = T.module_runs(m, mid_step.window, ["jit_epoch"])
    assert len(runs) == 1
    assert T.ops_inside(m, runs) == pytest.approx(
        sum(e - s for s, e, n in m.ops if T.is_leaf(n)) / 1e9)


def test_pallas_kernels_are_found_by_target_and_shape(mid_step):
    d = mid_step.devices[0]
    kernels = [(s, e, n) for s, e, n in d.ops if T.is_pallas(n)]
    assert len(kernels) == 6
    assert [T.short_name(n).split(".")[0] for _, _, n in kernels] == \
        ["jvp__"] + ["transpose_jvp___"] * 5
    assert all(T.opcode(n) == "custom-call" for _, _, n in kernels)
    # batch 8 x 12 heads, 1024 positions, head size 64: forward takes q, k,
    # v; backward q, k, v, o, do and the log-sum-exp
    assert [len(T.operand_shapes(n)) for _, _, n in kernels] == [3] + [6] * 5
    assert all(T.operand_shapes(n)[0] == ("bf16", (96, 1024, 64))
               for _, _, n in kernels)
    seconds, n = T.op_seconds(d, mid_step.window, T.is_pallas)
    assert n == 6 and seconds == pytest.approx(0.012506515)
    top = T.top_device_ops(mid_step, 3)
    assert top[0][0] == "pallas:transpose_jvp___"     # one label a kernel
    assert top[0][1] == pytest.approx(0.010294487)


def test_opcode_reads_past_tuple_shapes_and_layouts():
    text = ("%while.7 = (s32[]{:T(128)}, f32[8,1024]{1,0:T(8,128)S(1)}) "
            "while((s32[]{:T(128)}, f32[8,1024]{1,0:T(8,128)}) %tuple.3), "
            "condition=%c, body=%b")
    assert T.opcode(text) == "while" and not T.is_leaf(text)
    assert T.short_name(text) == "while.7"
    fusion = "%fusion.3609 = f32[50257]{0:T(1024)} fusion(f32[8,4]{1,0} %p)"
    assert T.opcode(fusion) == "fusion" and T.is_leaf(fusion)
    assert T.operand_shapes(fusion) == [("f32", (8, 4))]
    assert T.opcode("SomeRuntimeSpan") == "SomeRuntimeSpan"
    assert T.module_name("jit_pstep(123)") == "jit_pstep"


def test_flash_roofline_share_from_the_real_kernels(mid_step):
    """The forward kernel of the slice took 2.212 ms; the least a causal
    forward needs at these shapes is 12.9 GFLOP / 197 TFLOP/s = 0.065 ms:
    3 % of its roofline, far under 100."""
    d = mid_step.devices[0]
    fwd = [(e - s) / 1e9 for s, e, n in d.ops if T.is_pallas(n)
           and len(T.operand_shapes(n)) == 3]
    call = counts.flash_attention_call(8, 12, 1024, 64)
    least, bound = counts.roofline_seconds(call["fwd_flops"],
                                           call["fwd_bytes"], 197e12, 819e9)
    assert bound == "flops"
    assert 100 * least / fwd[0] == pytest.approx(2.96, abs=0.01)


def test_layer_metric_readers_on_the_recorded_trace(mid_step):
    cfg = mf.Manifest().config("gpt2-small")
    env = dict(cfg=cfg, chips=1, traffic={},
               peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9))
    # pretend the slice is one whole epoch of one step (it is cut out of one)
    records = dict(kind="train", epoch_events=[], tokens_per_epoch=8192,
                   seq_len=1024, batch=8, steps_per_epoch=1,
                   epoch_programs=["jit_epoch"])
    idle = mf.load_layer_metric("device_idle_pct.train").read(
        records, mid_step, env)
    assert 0 <= idle < 0.01
    mfu = mf.load_layer_metric("mfu.train").read(records, mid_step, env)
    span = (mid_step.window[1] - mid_step.window[0]) / 1e9
    assert mfu == pytest.approx(
        100 * 8192 * counts.train_flops_per_token(cfg, 1024) / span / 197e12)
    flash = mf.load_layer_metric("flash_attn_roofline.train").read(
        records, mid_step, env)
    # 12 layer-steps' least time over the 12.5 ms of kernels in the slice
    assert flash == pytest.approx(100 * 12 * 0.19617e-3 / 0.012506515,
                                  rel=1e-3)
    assert mf.load_layer_metric("epoch_wall_ms.train").read(
        records, mid_step, env) is None       # no epoch events: left out


# -- a slice of the serving cell's trace: one whole paged decode step ----------

@pytest.fixture(scope="module")
def decode_step():
    return T.load(os.path.join(FIXTURES, "serve_decode_step.xplane.pb.gz"))


def test_decode_program_runs_and_their_device_time(decode_step):
    d = decode_step.devices[0]
    names = [T.module_name(n) for _, _, n in d.modules]
    assert names[:2] == ["jit_stage", "jit_pstep"] and names[-1] == "jit_run"
    runs = T.module_runs(d, decode_step.window, ["jit_pstep", "jit_step"])
    assert len(runs) == 1                   # the clipped neighbours are not
    assert (runs[0][1] - runs[0][0]) / 1e9 == pytest.approx(0.187423033)
    assert T.ops_inside(d, runs) == pytest.approx(0.187418119)
    busy, window = T.busy_and_window_s(decode_step)
    assert window == pytest.approx(0.192423033)
    assert busy == pytest.approx(brute_force_busy(d, decode_step.window)
                                 / 1e9)


def test_serving_layer_metric_readers_on_the_recorded_step(decode_step):
    cfg = mf.Manifest().config("gpt2-medium")
    env = dict(cfg=cfg, chips=1, traffic={},
               peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9))
    records = dict(kind="serve", queue_wait_s=[], gen_lag_s=[], num_slots=64,
                   decode_steps=0, active_slot_steps=0,
                   decode_programs=["jit_pstep", "jit_step"],
                   traced_context_positions=20 * 300)
    step = mf.load_layer_metric("decode_step_ms.serve").read(
        records, decode_step, env)
    assert step == pytest.approx(187.418119)
    share = mf.load_layer_metric("decode_hbm_roofline.serve").read(
        records, decode_step, env)
    # 20 rows at 300 positions: 0.71 GB of weights + 0.59 GB of keys and
    # values at 819 GB/s = 1.59 ms of the step's 187 ms
    least = (counts.weight_bytes(cfg) + 6000 * 98_304) / 819e9
    assert share == pytest.approx(100 * least / 0.187418119)
    assert 0.5 < share < 1.5
    idle = mf.load_layer_metric("device_idle_pct.serve").read(
        records, decode_step, env)
    assert 0 <= idle < 0.1
    for name in ("queue_wait_p95_ms.serve", "gen_lag_p95_ms.serve",
                 "batch_occupancy_pct.serve"):
        assert mf.load_layer_metric(name).read(records, decode_step,
                                               env) is None
