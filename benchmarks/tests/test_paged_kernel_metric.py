"""``paged_kernel_step_pct.serve``: the share of decode dispatches whose span
says ``attn=kernel``, over the traced window; nothing from a program that
does not say (the recorded fixture is such a one: PR 24's spans)."""

import pytest

from benchmarks.lib import manifest as mf, spans as S, trace as T

from test_spans import as_newest, device_trace, fixture  # noqa: F401

WINDOW = (1_000, 9_000)


def dispatch(start, attn=None, step=1):
    fields = dict(active=3, step=step)
    if attn is not None:
        fields["attn"] = attn
    return S.Span(start, start + 100, "serve.decode_dispatch", 0, fields)


def read(monkeypatch, spans, kind="serve"):
    trace = T.Trace([], {T.WINDOW_SPAN: [WINDOW]})
    monkeypatch.setattr(S, "of_run",
                        lambda t: None if spans is None else S.Spans(spans))
    return mf.load_layer_metric("paged_kernel_step_pct.serve").read(
        dict(kind=kind), trace, {})


@pytest.mark.parametrize("attns,want", [
    (["kernel"] * 5, 100.0),
    (["gather"] * 4, 0.0),
    (["kernel", "gather", "kernel", "kernel"], 75.0),
], ids=["every_step_on_the_kernel", "a_silent_fall_back", "mixed"])
def test_share_of_dispatches_on_the_kernel(monkeypatch, attns, want):
    spans = [dispatch(2_000 + 500 * i, a, i) for i, a in enumerate(attns)]
    assert read(monkeypatch, spans) == pytest.approx(want)


def test_only_the_traced_window_counts(monkeypatch):
    spans = [dispatch(100, "gather"), dispatch(2_000, "kernel"),
             dispatch(8_950, "gather"), dispatch(9_500, "gather")]
    assert read(monkeypatch, spans) == 100.0


@pytest.mark.parametrize("why,spans,kind", [
    ("a program whose spans do not say", [dispatch(2_000), dispatch(3_000)],
     "serve"),
    ("no dispatch in the window", [dispatch(100, "kernel")], "serve"),
    ("a program without spans", None, "serve"),
    ("a training cell", [dispatch(2_000, "kernel")], "train"),
])
def test_nothing_to_read_gives_nothing(monkeypatch, why, spans, kind):
    assert read(monkeypatch, spans, kind) is None


def test_the_recorded_parent_trace_reads_nothing(as_newest):
    as_newest("serve_tiny_cpu_spans")
    serve = S.read(fixture("serve_tiny_cpu_spans"))
    assert serve.named("serve.decode_dispatch", serve.window)
    assert mf.load_layer_metric("paged_kernel_step_pct.serve").read(
        dict(kind="serve"), device_trace(serve, []), {}) is None


def test_the_manifest_lists_it_for_the_serving_cell():
    entry, = (m for m in mf.Manifest().data["per_layer"]
              if m["name"] == "paged_kernel_step_pct.serve")
    assert entry == dict(
        name="paged_kernel_step_pct.serve", unit="%", better="higher",
        source="program_span", layer="kernels", moves="itl_p95_ms",
        workloads=["serve-chat-gpt2m"])
