"""The four readers of the serving engine's spans that came with its account
(``decode_launch_ms.serve``, ``prefill_launch_ms.serve``,
``step_carries_prefill_pct.serve``, ``queue_hold_pct.serve``), against
``serve_tiny_cpu_account``: a tiny engine (two slots, one prefill unit an
iteration, chunks of 16), warmed up, serving six requests on the CPU inside
the benchmark's own ``Profiler``, recorded from the program that opens
``serve.hold`` and cut to the program's spans and the window annotation
(``tools/dump_trace.py --slice ... --host-names serve.,bench_window``).  By
hand: 28 iterations in the window, 19 of them dispatch a decode step, 9 of
those (``it`` 7-10, 12-14, 25, 26) a prefill unit too; 9 iterations hold the
queue's head, 6 for a slot (``it`` 8-13) and 3 for the unit budget (23-25,
while the fifth request's chunks run with both slots free)."""

import json

import pytest

from benchmarks.lib import manifest as mf, spans as S
from benchmarks.lib.stats import median

from test_spans import as_newest, device_trace, fixture  # noqa: F401

NEW = ("decode_launch_ms.serve", "prefill_launch_ms.serve",
       "step_carries_prefill_pct.serve", "queue_hold_pct.serve")
RECORDS = dict(kind="serve")


@pytest.fixture(scope="module")
def account():
    return S.read(fixture("serve_tiny_cpu_account"))


def read(name, spans, records=RECORDS):
    return mf.load_layer_metric(name).read(records, device_trace(spans, []),
                                           {})


def test_the_fixture_holds_what_the_docstring_counts(account):
    its = account.named("serve.iteration", account.window)
    assert [s.fields["it"] for s in its] == list(range(6, 34))
    with_step = [s for s in its
                 if account.children(s, "serve.decode_dispatch")]
    assert len(with_step) == 19
    assert [s.fields["it"] for s in with_step
            if account.children(s, "serve.prefill_unit")] == [
                7, 8, 9, 10, 12, 13, 14, 25, 26]
    holds = account.named("serve.hold")
    assert [(h.fields["reason"], h.fields["queued"]) for h in holds] == [
        ("no_slot", 2)] * 4 + [("no_slot", 1)] * 2 + [("budget", 1)] * 3
    # a hold lies inside the schedule pass of its iteration
    for h in holds:
        sched, = [s for s in account.named("serve.schedule")
                  if s.start <= h.start and h.end <= s.end]
        assert sched.thread == h.thread


def test_launch_medians_are_the_spans_own(account, as_newest):
    as_newest("serve_tiny_cpu_account")
    sent = account.named("serve.decode_dispatch", account.window)
    units = account.named("serve.prefill_unit", account.window)
    assert len(sent) == 19 and len(units) == 11
    assert read(NEW[0], account) == pytest.approx(
        median([s.ms for s in sent]))
    assert read(NEW[1], account) == pytest.approx(
        median([s.ms for s in units]))
    # a launch is a part of the host's own time in an iteration
    assert 0 < read(NEW[0], account) < read("host_busy_ms.serve", account)
    assert 0 < read(NEW[1], account)


def test_share_of_steps_that_carry_a_unit(account, as_newest):
    as_newest("serve_tiny_cpu_account")
    assert read(NEW[2], account) == pytest.approx(100.0 * 9 / 19)


def test_share_of_iterations_that_hold_the_queue(account, as_newest, capsys):
    as_newest("serve_tiny_cpu_account")
    assert read(NEW[3], account) == pytest.approx(100.0 * 9 / 28)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line == {"queue_hold_by_reason": {"budget": 3, "no_slot": 6},
                    "iterations": 28}


@pytest.mark.parametrize("name", NEW)
def test_a_trace_without_spans_reads_nothing(as_newest, name):
    # PR 23's slice of the parent's trace: a window annotation and no span
    from benchmarks.lib import trace as T
    as_newest("train_epoch_boundary")
    trace = T.load(fixture("train_epoch_boundary"))
    assert mf.load_layer_metric(name).read(RECORDS, trace, {}) is None


@pytest.mark.parametrize("name", NEW)
def test_a_training_cell_reads_nothing(account, as_newest, name):
    as_newest("serve_tiny_cpu_account")
    assert read(name, account, dict(kind="train")) is None


def test_a_program_that_opens_no_hold_reads_nothing_not_zero(as_newest):
    # PR 24's recording: the loop's spans, before serve.hold existed
    as_newest("serve_tiny_cpu_spans")
    old = S.read(fixture("serve_tiny_cpu_spans"))
    assert read(NEW[3], old) is None
    # what it does have is read as it is
    assert read(NEW[0], old) > 0 and read(NEW[1], old) > 0
    assert 0 < read(NEW[2], old) <= 100


@pytest.mark.parametrize("name,cells", [
    (NEW[0], ["serve-chat-gpt2m", "serve-reason-solar2",
              "serve-context-nemotron3n"]),
    (NEW[1], ["serve-chat-gpt2m"]),
    (NEW[2], ["serve-chat-gpt2m", "serve-reason-solar2",
              "serve-context-nemotron3n"]),
    (NEW[3], ["serve-reason-solar2", "serve-context-nemotron3n"]),
])
def test_the_manifest_lists_each_for_the_cells_it_can_read(name, cells):
    man = mf.Manifest()
    entry, = (m for m in man.data["per_layer"] if m["name"] == name)
    assert entry["workloads"] == cells and entry["layer"] == "serving engine"
    e2e = {c: {m["name"] for m in man.end_to_end(c)} for c in cells}
    assert all(entry["moves"] in e2e[c] for c in cells)
