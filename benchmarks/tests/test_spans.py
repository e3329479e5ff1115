"""The program's spans and scopes read back (``lib/spans.py``) and the
readers built on them, against recorded traces:

- ``serve_tiny_cpu_spans`` / ``train_tiny_cpu_spans``: a tiny engine run
  (three requests, no warm-up, so its programs compile inside the spans; one
  compile before the engine starts, under no span) and a five-epoch ADAG job,
  recorded on the CPU inside the benchmark's own ``Profiler``, cut to the
  program's spans, the compiles and the window annotation;
- ``train_step_scopes`` / ``serve_decode_scopes``: 30 ms of a train step
  (the last block's backward, the LM head, the loss) and 25 ms of a decode
  step (three layers) cut out of real TPU v5 lite traces of the two one-chip
  cells WITH the stats of their events' metadata (PR 24's first chip call),
  the program's spans beside them.
"""

import os

import pytest

from benchmarks.lib import manifest as mf, spans as S, trace as T

from helpers import FIXTURES


def fixture(name):
    return os.path.join(FIXTURES, name + ".xplane.pb.gz")


@pytest.fixture(scope="module")
def serve():
    return S.read(fixture("serve_tiny_cpu_spans"))


@pytest.fixture(scope="module")
def train():
    return S.read(fixture("train_tiny_cpu_spans"))


def device_trace(spans, gaps):
    """A one-chip ``Trace`` over the spans' window whose chip is busy (one
    leaf operation after another) everywhere but in ``gaps``."""
    lo, hi = spans.window
    ops, cursor = [], lo
    for s, e in sorted(gaps) + [(hi, hi)]:
        if s > cursor:
            ops.append((cursor, s, "%fusion.1 = f32[8] fusion(f32[8] %p)"))
        cursor = e
    plane = T.DevicePlane("/device:TPU:0", ops,
                          [(lo, hi, "jit_pstep(1)")])
    return T.Trace([plane], {T.WINDOW_SPAN: [spans.window]})


def reader(name):
    return mf.load_layer_metric(name)


# -- the loader -----------------------------------------------------------------

def test_spans_come_back_with_their_fields(serve):
    its = serve.named("serve.iteration")
    assert [s.fields["it"] for s in its] == list(range(1, len(its) + 1))
    assert all(set(s.fields) == {"it", "active"} for s in its)
    unit = serve.named("serve.prefill_unit")[0]
    assert unit.fields == dict(rid=1, tokens=5, kind="bucket", width=8, hit=0)
    assert [s.fields["rid"] for s in serve.named("serve.submit")] == [1, 2, 3]
    # submit is on the caller's thread, the loop on the engine's
    assert serve.main_threads() == [its[0].thread]
    assert serve.named("serve.submit")[0].thread != its[0].thread
    lo, hi = serve.window
    assert serve.named("serve.iteration", (lo, hi)) == its
    assert serve.named("serve.iteration", (its[1].start, hi)) == its[1:]


def test_children_and_self_time(serve):
    busy = next(s for s in serve.named("serve.iteration")
                if serve.children(s, "serve.decode_dispatch"))
    inside = serve.children(busy)
    assert all(busy.start <= c.start and c.end <= busy.end
               and c.thread == busy.thread for c in inside)
    assert {"serve.reap", "serve.schedule", "serve.decode_dispatch",
            "serve.publish"} <= {c.name for c in inside}
    # an admission is inside the schedule pass, and counted once below
    sched = serve.children(busy, "serve.schedule")[0]
    nested = serve.children(sched)
    covered = T.union((c.start, c.end) for c in inside)
    assert serve.self_ns(busy) == (busy.end - busy.start) - sum(
        e - s for s, e in covered)
    assert 0 < serve.self_ns(busy) < busy.end - busy.start
    assert serve.self_ns(sched) == (sched.end - sched.start) - sum(
        e - s for s, e in T.union((c.start, c.end) for c in nested))


def test_innermost_segments_are_disjoint_and_name_the_deepest_span(serve):
    segs = serve.innermost(serve.main_threads())
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))
    unit = serve.named("serve.prefill_unit")[0]
    mid = (unit.start + unit.end) // 2
    name = next(n for s, e, n in segs if s <= mid < e)
    # the unit's program compiled inside it; the compile is no program span
    assert name == "serve.prefill_unit"
    fetch = serve.named("serve.fetch")[-1]
    assert ("serve.fetch" ==
            next(n for s, e, n in segs if s <= fetch.start < e))


# -- idle gaps and compiles -------------------------------------------------------

def test_idle_gaps_go_to_the_span_the_compile_or_nobody(serve):
    lo, hi = serve.window
    unit = serve.named("serve.prefill_unit")[1]
    fetch = max(serve.named("serve.fetch"), key=lambda s: s.ms)
    first = serve.named(S.COMPILE_SPAN)[0]      # before the engine started
    assert not [s for s in serve.named("serve.iteration")
                if s.start <= first.start]
    emit = next(s for s in serve.named("serve.emit") if s.start >= fetch.end)
    assert emit.start - fetch.end < 100_000     # the token loop follows
    gaps = [(first.start + 1000, first.start + 3_001_000),      # 3 ms
            (unit.start + 1000, unit.start + 2_001_000),        # 2 ms
            (fetch.start, emit.start + 1000),   # crosses three phases
            (hi - 4_000_000, hi)]       # after stop(): no span, no compile
    idle = serve.idle_by_phase(device_trace(serve, gaps))
    between = (emit.start - fetch.end) / 1e9    # the iteration's own time
    assert idle == pytest.approx({
        S.COMPILE_SPAN: 0.003, "serve.prefill_unit": 0.002,
        "serve.fetch": fetch.ms / 1e3, "serve.iteration": between,
        "serve.emit": 1e-6, S.UNATTRIBUTED: 0.004})


def test_a_busy_chip_has_no_idle_phase(serve):
    assert serve.idle_by_phase(device_trace(serve, [])) == {}


def test_compiles_are_counted_with_the_span_they_fell_in(serve, train):
    lo, hi = serve.window
    found = serve.compiles((lo, hi))
    assert len(found) == len(serve.named(S.COMPILE_SPAN)) == 7
    where = [w for _, w in found]
    assert where.count("serve.prefill_unit") == 3
    assert where.count("serve.decode_dispatch") == 1
    assert where.count(S.UNATTRIBUTED) == 2     # the caller's thread
    # a window that opens after the last compile holds none
    assert serve.compiles((found[-1][0].end, hi)) == []
    # the trainer: everything before the first epoch, then its two programs
    where = [w for _, w in train.compiles(train.window)]
    assert where.count("train.dispatch") == 2
    assert set(where) == {"train.dispatch", S.UNATTRIBUTED}


def test_report_prints_the_two_earlier_lines(serve, capsys):
    import json
    unit = serve.named("serve.prefill_unit")[1]
    lo, hi = serve.window
    trace = device_trace(serve, [(unit.start + 1000, unit.start + 2_001_000),
                                 (hi - 1_000_000, hi)])
    S.report(serve, trace)
    first, second = (json.loads(x) for x in
                     capsys.readouterr().out.strip().splitlines())
    assert first == {"idle_by_phase": [["serve.prefill_unit", 0.002]],
                     "unattributed": 0.001}
    assert second["compiles_in_trace"] == 7
    assert ["serve.prefill_unit", 3] in second["in"]


# -- of_run: the newest trace, held to the Trace a reader was given ---------------

@pytest.fixture
def as_newest(tmp_path, monkeypatch):
    """Puts a fixture where a run's trace would be."""
    import gzip

    def put(name):
        d = tmp_path / "trace-cell" / "plugins" / "profile" / "t"
        d.mkdir(parents=True, exist_ok=True)
        with gzip.open(fixture(name), "rb") as f:
            (d / "vm.xplane.pb").write_bytes(f.read())
        monkeypatch.setattr(S, "newest_trace", lambda: str(d / "vm.xplane.pb"))
        S._OF_RUN.clear()
    yield put
    S._OF_RUN.clear()


def test_of_run_holds_the_file_to_the_window(serve, as_newest, capsys):
    as_newest("serve_tiny_cpu_spans")
    trace = device_trace(serve, [])
    got = S.of_run(trace)
    assert len(got.all) == len(serve.all)
    assert "compiles_in_trace" in capsys.readouterr().out
    assert S.of_run(trace) is got               # once a process
    assert capsys.readouterr().out == ""
    lo, hi = serve.window
    other = T.Trace(trace.devices, {T.WINDOW_SPAN: [(lo + 1, hi)]})
    assert S.of_run(other) is None              # another run's trace
    assert S.of_run(None) is None


def test_a_program_without_spans_reads_as_nothing(as_newest):
    # PR 23's slice: the parent's trace, window annotation and no span
    as_newest("train_epoch_boundary")
    trace = T.load(fixture("train_epoch_boundary"))
    assert S.of_run(trace) is None
    for name in ("host_gap_ms.train", "attn_device_pct.train",
                 "lm_head_loss_device_pct.train"):
        assert reader(name).read(
            dict(kind="train", epoch_programs=["jit_epoch"]), trace,
            {}) is None
    for name in ("iteration_p95_ms.serve", "prefill_stretch_ms.serve",
                 "host_busy_ms.serve", "gather_convert_device_pct.serve"):
        assert reader(name).read(
            dict(kind="serve", decode_programs=["jit_pstep"]), trace,
            {}) is None


# -- the readers of host spans ----------------------------------------------------

def test_serving_readers(serve, as_newest):
    as_newest("serve_tiny_cpu_spans")
    trace = device_trace(serve, [])
    records = dict(kind="serve")
    its = [s for s in serve.named("serve.iteration", serve.window)
           if s.fields["active"] > 0]
    from benchmarks.lib.stats import median, percentile
    # from one decode step's token loop to the next, once a row it held;
    # a prefill's first token (same step as the decode before it) is no step
    emits = serve.named("serve.emit")
    assert [e.fields["kind"] for e in emits].count("prefill") == 3
    steps = [e for e in emits if e.fields["kind"] == "decode"]
    assert [e.fields["step"] for e in steps] == list(range(1, 13))
    gaps = [(b.start - a.start) / 1e6 for a, b in zip(steps, steps[1:])
            for _ in range(b.fields["rows"])]
    assert len(gaps) == sum(e.fields["rows"] for e in steps[1:]) > 11
    assert reader("iteration_p95_ms.serve").read(records, trace, {}) == \
        pytest.approx(percentile(gaps, 95))
    with_unit = [s.ms for s in its if serve.children(s, "serve.prefill_unit")]
    without = [s.ms for s in its
               if not serve.children(s, "serve.prefill_unit")]
    assert with_unit and without
    assert reader("prefill_stretch_ms.serve").read(records, trace, {}) == \
        pytest.approx(median(with_unit) - median(without))
    # the host's own time: an iteration less what it waited in fetches
    worked = [s for s in serve.named("serve.iteration")
              if serve.children(s, "serve.decode_dispatch")
              or serve.children(s, "serve.prefill_unit")]
    own = [s.ms - sum(f.ms for f in serve.children(s, "serve.fetch"))
           for s in worked]
    got = reader("host_busy_ms.serve").read(records, trace, {})
    assert got == pytest.approx(median(own))
    assert 0 < got < median([s.ms for s in worked])
    for name in ("iteration_p95_ms.serve", "host_busy_ms.serve"):
        assert reader(name).read(dict(kind="train"), trace, {}) is None


def test_host_gap_is_fetch_end_to_next_dispatch(train, as_newest):
    as_newest("train_tiny_cpu_spans")
    trace = device_trace(train, [])
    fetched = train.named("train.fetch")
    sent = train.named("train.dispatch")
    gaps = [(d.start - f.end) / 1e6 for f, d in zip(fetched, sent[1:])]
    assert len(gaps) == 4 and all(g > 0 for g in gaps)
    from benchmarks.lib.stats import median
    got = reader("host_gap_ms.train").read(dict(kind="train"), trace, {})
    assert got == pytest.approx(median(gaps))
    # log, shuffle and shape lie in the gap; the epoch's own span does not end
    e0, e1 = train.named("train.epoch")[:2]
    assert fetched[0].end < e0.end <= e1.start < sent[1].start


# -- device time by scope, on real TPU slices -------------------------------------

@pytest.fixture(scope="module")
def train_step():
    return (S.read(fixture("train_step_scopes")),
            T.load(fixture("train_step_scopes")))


@pytest.fixture(scope="module")
def decode_step():
    return (S.read(fixture("serve_decode_scopes")),
            T.load(fixture("serve_decode_scopes")))


def test_op_names_come_from_the_event_metadata(train_step, decode_step):
    spans, trace = train_step
    assert len(spans.ops) == len(trace.devices[0].ops) == 372
    named = [o for o in spans.ops if o.op_name]
    assert len(named) > 0.9 * len(spans.ops)
    # the kernel's name is jax's name stack plus the kernel's own
    dq = next(o for o in spans.ops if T.is_pallas(o.name)
              and "flash_dq" in o.name)
    assert "transpose(jvp(block_11))/attn/attn_core" in dq.op_name
    head = next(o for o in named if "lm_head" in o.op_name)
    assert head.op_name.startswith("jit(epoch)/while/body/")
    # what ProfileData itself hands out for an op holds no name at all
    import gzip
    import jax
    with gzip.open(fixture("train_step_scopes"), "rb") as f:
        data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    line = next(l for p in data.planes if p.name == "/device:TPU:0"
                for l in p.lines if l.name == "XLA Ops")
    assert {k for e in line.events for k, _ in e.stats} == {
        "device_offset_ps", "device_duration_ps", "Time Scale Multiplier"}
    # the decode step: the compiler's own converts carry no name of their
    # own, and take that of the gather whose rows they read
    spans, _ = decode_step
    own = S.op_names(fixture("serve_decode_scopes"), "/device:TPU:0")
    converts = [o for o in spans.ops if T.opcode(o.name) == "convert"]
    assert converts and not any(o.name in own for o in converts)
    for c in converts:
        read_from = next(o for o in spans.ops if T.short_name(o.name)
                         == c.name.rsplit("%", 1)[1].rstrip(")"))
        assert c.op_name == own[read_from.name]
    assert sum("/attn/kv_gather/gather" in o.op_name for o in converts) == 3
    assert all(o.op_name == own[o.name] for o in spans.ops if o.name in own)


def test_a_nameless_operation_takes_the_name_of_what_it_reads():
    gather = "%fusion.3 = bf16[8,4] fusion(bf16[9,4] %p.1, s32[8] %p.2)"
    conv = "%convert.7 = f32[8,4] convert(bf16[8,4] %fusion.3)"
    copy = "%copy.2 = f32[8,4]{0,1} copy(f32[8,4]{1,0} %convert.7)"
    lone = "%iota.1 = s32[8] iota(), iota_dimension=0"
    loop = "%add.5 = s32[] add(s32[] %add.5, s32[] %c.1)"     # reads itself
    mlp = "%fusion.9 = f32[8,4] fusion(f32[8,4] %copy.2)"
    named = {gather: "jit(pstep)/block_0/attn/kv_gather/gather:",
             mlp: "jit(pstep)/block_0/mlp/dot_general:"}
    got = S.inherit(named, [copy, conv, gather, lone, loop, mlp])
    assert got[conv] == got[copy] == named[gather]      # through a chain
    assert got[mlp] == named[mlp] and got[gather] == named[gather]
    assert lone not in got and loop not in got


def test_scope_seconds_match_a_sum_by_hand(train_step):
    spans, trace = train_step
    runs = T.module_runs(trace.devices[0], trace.window, ["jit_epoch"])
    assert len(runs) == 1
    leaves = [o for o in spans.ops if T.is_leaf(o.name)]
    total = sum(o.end - o.start for o in leaves) / 1e9

    def by_hand(*scopes):
        # a scope is a whole path element, bare or wrapped: jvp(loss)
        import re
        return sum(o.end - o.start for o in leaves
                   if any(re.search(rf"(^|[/(]){s}($|[/):])", o.op_name)
                          for s in scopes)) / 1e9
    for scopes in (("attn",), ("lm_head", "loss"), ("mlp",), ("attn_core",)):
        under, whole = spans.scope_seconds(runs, scopes)
        assert whole == pytest.approx(total)
        assert under == pytest.approx(by_hand(*scopes)) and under > 0
    # attn_core lies inside attn; lm_head and loss are disjoint
    assert (spans.scope_seconds(runs, ["attn_core"])[0]
            < spans.scope_seconds(runs, ["attn"])[0])
    assert spans.scope_seconds(runs, ["lm_head", "loss"])[0] == pytest.approx(
        spans.scope_seconds(runs, ["lm_head"])[0]
        + spans.scope_seconds(runs, ["loss"])[0])
    assert spans.scope_seconds(runs, ["kv_gather"])[0] == 0.0
    assert spans.scope_seconds([], ["attn"]) is None
    # a trace whose operations name nothing gives nothing, not zero; nor
    # does an executable compiled without the program's scopes, whose
    # operations still carry jax's own name stack
    bare = S.Spans([], [o._replace(op_name="") for o in spans.ops])
    assert bare.scope_seconds(runs, ["attn"]) is None
    import re
    unscoped = S.Spans([], [o._replace(op_name=re.sub(
        r"(transpose\(jvp\()?block_\d+\)*/|/attn\b|/attn_core\b", "",
        o.op_name)) for o in spans.ops])
    assert any(o.op_name.startswith("jit(epoch)/") for o in unscoped.ops)
    assert unscoped.scope_seconds(runs, ["attn"]) is None


@pytest.mark.parametrize("name,fix,records,share", [
    ("attn_device_pct.train", "train_step_scopes",
     dict(kind="train", epoch_programs=["jit_epoch"]), 30.468),
    ("lm_head_loss_device_pct.train", "train_step_scopes",
     dict(kind="train", epoch_programs=["jit_epoch"]), 63.528),
    ("gather_convert_device_pct.serve", "serve_decode_scopes",
     dict(kind="serve", decode_programs=["jit_pstep", "jit_step"]), 48.956),
])
def test_scope_readers_on_the_tpu_slices(as_newest, name, fix, records,
                                         share):
    as_newest(fix)
    trace = T.load(fixture(fix))
    assert reader(name).read(records, trace, {}) == pytest.approx(
        share, abs=1e-3)
    other = dict(records, kind="serve" if records["kind"] == "train"
                 else "train")
    assert reader(name).read(other, trace, {}) is None
