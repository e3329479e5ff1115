"""``sample_device_pct.serve``: the share of the decode programs' leaf-op
time in the traced window that lies under the program's scope ``sample``,
whichever branch of the sampler's conditionals ran; nothing from a trace
that names no scope, has no run of the decode program, or is a training
cell's."""

import pytest

from benchmarks.lib import manifest as mf, spans as S, trace as T

from test_spans import as_newest, fixture  # noqa: F401

WINDOW = (1_000, 100_000)
PROGRAMS = ["jit_pstep", "jit_step"]

# (instruction text, op_name, ns) of one decode step, in order
BLOCK = ("%fusion.7 = bf16[64,1024] fusion(bf16[64,1024] %p.1)",
         "jit(pstep)/block_0/mlp/dot_general:", 600)
ARGMAX = ("%reduce.3 = s32[64] reduce(f32[64,50257] %p.2, s32[] %c.0)",
          "jit(pstep)/sample/argmax:", 100)
COND = ("%conditional.1 = (s32[64]) conditional(s32[] %p.3, (s32[64]) %t.1)",
        "jit(pstep)/sample/cond:", 0)
SORT = ("%sort.5 = (f32[64,50257], s32[64,50257]) sort(f32[64,50257] %p.4)",
        "jit(pstep)/sample/cond/branch_1_fun/cond/branch_1_fun/jit(sort)/"
        "sort:", 3_000)
DRAW = ("%fusion.9 = s32[64] fusion(f32[64,50257] %p.5, u32[64,2] %p.6)",
        "jit(pstep)/sample/cond/branch_1_fun/vmap(categorical)/argmax:", 300)
# a word that merely contains the scope's name is not under it
LOOKALIKE = ("%fusion.11 = f32[64] fusion(f32[64] %p.7)",
             "jit(pstep)/block_0/resample_rows/add:", 200)


def step(start, pieces):
    """One run of the decode program from ``start``: its operations one
    after another; a conditional spans what follows it."""
    ops, at = [], start
    for i, (name, op_name, ns) in enumerate(pieces):
        inside = sum(p[2] for p in pieces[i + 1:]) if name is COND[0] else 0
        ops.append(S.Op(at, at + ns + inside, name, op_name))
        at += ns
    return (start, at), ops


def read(monkeypatch, steps, programs=("jit_pstep(1)",), kind="serve"):
    runs = [r for r, _ in steps]
    ops = [o for _, mine in steps for o in mine]
    plane = T.DevicePlane(
        "/device:TPU:0", [(o.start, o.end, o.name) for o in ops],
        [(s, e, programs[i % len(programs)])
         for i, (s, e) in enumerate(runs)])
    trace = T.Trace([plane], {T.WINDOW_SPAN: [WINDOW]})
    monkeypatch.setattr(S, "of_run", lambda t: S.Spans([], ops))
    return mf.load_layer_metric("sample_device_pct.serve").read(
        dict(kind=kind, decode_programs=PROGRAMS), trace, {})


GREEDY = [BLOCK, ARGMAX, LOOKALIKE]                      # 100 of 900
DRAWN = [BLOCK, ARGMAX, COND, DRAW, LOOKALIKE]           # 400 of 1,200
FILTERED = [BLOCK, ARGMAX, COND, SORT, DRAW, LOOKALIKE]  # 3,400 of 4,200


@pytest.mark.parametrize("pieces,want", [
    ([GREEDY] * 3, 100.0 * 100 / 900),
    ([DRAWN] * 2, 100.0 * 400 / 1_200),
    ([FILTERED] * 2, 100.0 * 3_400 / 4_200),
    ([GREEDY, FILTERED, GREEDY], 100.0 * 3_600 / 6_000),
], ids=["greedy_steps_hold_the_argmax_alone", "a_draw_without_a_sort",
        "the_sort_inside_both_conditionals", "mixed"])
def test_share_of_the_step_under_the_sampler(monkeypatch, pieces, want):
    steps = [step(2_000 + 10_000 * i, p) for i, p in enumerate(pieces)]
    assert read(monkeypatch, steps) == pytest.approx(want)


def test_only_decode_programs_inside_the_window_count(monkeypatch):
    steps = [step(100, FILTERED),           # starts before the window
             step(2_000, GREEDY),
             step(20_000, FILTERED),        # a prefill program's own sampler
             step(99_000, FILTERED)]        # ends after it
    got = read(monkeypatch, steps,
               programs=("jit_pstep(1)", "jit_pstep(1)", "jit_bucket(2)",
                         "jit_pstep(1)"))
    assert got == pytest.approx(100.0 * 100 / 900)


@pytest.mark.parametrize("why,pieces,programs,kind", [
    ("no run of the decode program", [GREEDY], ("jit_bucket(2)",), "serve"),
    ("an executable compiled without the scopes",
     [[(n, "jit(pstep)/add:", ns) for n, _, ns in GREEDY]],
     ("jit_pstep(1)",), "serve"),
    ("a training cell", [GREEDY], ("jit_pstep(1)",), "train"),
])
def test_nothing_to_read_gives_nothing(monkeypatch, why, pieces, programs,
                                       kind):
    steps = [step(2_000 + 10_000 * i, p) for i, p in enumerate(pieces)]
    assert read(monkeypatch, steps, programs, kind) is None


def test_the_recorded_slice_of_a_decode_step_reads_zero(as_newest):
    # PR 24's slice of the old decode step holds its attention, no sampler
    as_newest("serve_decode_scopes")
    trace = T.load(fixture("serve_decode_scopes"))
    assert mf.load_layer_metric("sample_device_pct.serve").read(
        dict(kind="serve", decode_programs=PROGRAMS), trace, {}) == 0.0


def test_the_manifest_lists_it_for_the_serving_cell():
    entry, = (m for m in mf.Manifest().data["per_layer"]
              if m["name"] == "sample_device_pct.serve")
    assert entry == dict(
        name="sample_device_pct.serve", unit="%", better="lower",
        source="device_trace", layer="model step", moves="itl_p95_ms",
        workloads=["serve-chat-gpt2m"])
