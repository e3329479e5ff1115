"""Structured metrics + profiler tracing.

The reference's observability is wall-clock only —
``Trainer.record_training_start/stop`` plus loss-history lists collected from
workers, and scattered ``print`` statements (SURVEY.md §5).  Here metrics are
structured events (JSONL) with throughput derived per epoch, and the
program's own phases are spans and scopes of ONE tracing system,
``jax.profiler``'s: no second recorder, clock, flag or environment variable.

**Host spans** (``span(name, **fields)``, a ``jax.profiler.TraceAnnotation``)
are live while a profiler session is open — ``trace(log_dir)`` here, or any
``jax.profiler.start_trace`` — and cost under a microsecond otherwise.  They
land on the ``/host:CPU`` plane of the session's ``.xplane.pb``, on the
device trace's own timeline, each with its fields as the event's stats.
Fields are ints or short strings known when the span OPENS; a result known
only at exit rides on the next span of the same request.  The names below
are the contract that ``docs/serving.md`` and the benchmark's readers
(``benchmarks/lib/spans.py``) point at.

Serving (``serving.py``; the engine's thread unless said).  The loop's own
phases (``serve.iteration`` and, inside it or between two of them,
``serve.reap``, ``qos``, ``schedule``, ``decode_dispatch``, ``fetch``,
``emit``, ``reload``, ``publish``, ``idle_wait``) are opened by ONE helper,
``EngineAccount.phase(name, **fields)`` (``engine.account.phase("reap")``),
which opens the span below AND adds the phase's ``time.perf_counter()``
seconds to the engine's own account, whether a session is open or not: span
and account share their boundaries by construction.  The other spans
(``submit``, ``admit``, ``retire``, ``warmup``, and a ``publish`` outside the
loop's own) are plain ``span(...)``s; ``prefill_unit`` is opened by
``EngineAccount.unit(**fields)``, which also counts it and its seconds.

===================== ======================================================
``serve.submit``      ``submit``/``submit_prefilled`` on the CALLER's
                      thread, from the id's taking to the queue push (key
                      creation included): ``rid``, ``prompt`` (tokens),
                      ``steps``
``serve.iteration``   one ``step()``: ``it`` (iteration count), ``active``
                      (running slots at entry)
``serve.reap``        ``_reap``: cancelled / expired requests retired
``serve.qos``         ``_balance_qos``, only when it runs
``serve.schedule``    ``_schedule_prefills``
``serve.hold``        the instant ``_schedule_prefills`` stops admitting
                      with a request still queued: ``reason``
                      (``no_slot``: every slot taken; ``no_blocks``: the
                      head's block chain does not fit the pool even after
                      eviction; ``budget``: the iteration's
                      ``prefills_per_step`` units are spent), ``queued``
                      (the queue's depth, read without the lock); at most
                      one an iteration; the account's ``held`` counts the
                      same instants
``serve.admit``       one admission (block plan, radix match, slot take):
                      ``rid``
``serve.prefill_unit`` one dispatched prefill work unit: ``rid`` (first of
                      a batch), ``tokens``, ``kind``
                      (``bucket``/``chunk``/``final``), ``width``, ``hit``
                      (prefix-hit tokens the admission found; on the first
                      unit of a request, else 0)
``serve.decode_dispatch`` ``_decode_once``: ``active``, ``step``
                      (``stats["decode_steps"]`` after this dispatch),
                      ``attn`` (``kernel``: the dispatched program reads K
                      and V in place through ``paged_decode``; ``gather``:
                      it gathers each slot's view — settled when the
                      program is built; ``stats["paged_kernel_steps"]``
                      counts the ``kernel`` dispatches), ``sample`` (what
                      the step's live rows ask of the sampler:
                      ``greedy`` = none samples, the argmax alone;
                      ``draw`` = some sample, none filters; ``filter`` = a
                      sampling row has ``top_k``/``top_p``, the sort runs;
                      ``stats["sampler_draw_steps"]`` counts ``draw`` and
                      ``filter``, ``stats["sampler_filter_steps"]``
                      ``filter``), ``state`` (the kinds of per-request
                      state the dispatched step advances: ``kv``, or
                      ``kv+recurrent`` for a model with recurrent layers,
                      whose per-slot state the step updates in place)
``serve.fetch``       the device→host read of one in-flight step — the time
                      the host WAITS for the device: ``step`` of the entry
                      drained (joins it to its ``serve.decode_dispatch``;
                      a prefill entry carries the step count at dispatch)
``serve.emit``        the token loop after the fetch (push, listeners,
                      retirements): ``kind`` of the step drained
                      (``decode``/``spec``/``prefill``), ``rows`` (slots it
                      held), ``step``
``serve.retire``      ``_retire``: ``rid``, ``reason``
``serve.publish``     ``_publish_load``
``serve.reload``      ``_pull_weights``
``serve.idle_wait``   ``_loop`` waiting for work after an idle ``step()``
``serve.warmup``      ``warmup()``, one span per program compiled:
                      ``program``
===================== ======================================================

``rid`` is ``RequestHandle.id``: the spans of one request share it
(submit → admit → prefill_unit... → retire).

**The engine's account** (``ServingEngine.account``, an ``EngineAccount``;
always on, no flag; written by the engine's thread alone, without a lock, in
fixed memory; ``snapshot()`` gives a plain JSON-able dict from any thread).
It is the spans' twin over the WHOLE life of the engine, where a profiler
session holds a few seconds:

===================== ======================================================
``loop_s``            first iteration's start to the latest stamp's end
``iterations``        ``step()`` calls
``phase_s``           seconds by phase (keys as they occur: ``reap`` ...
                      ``idle_wait``), each the sum of its spans'
                      durations; they sum to ``loop_s`` less the loop's own
                      few lines.  A phase opened inside another (the QoS
                      pass flushing the pipeline before a swap-out) is the
                      outer phase's time: every second is counted once
``prefill_unit``      ``{n, s}``: prefill units dispatched and their host
                      seconds (arrays built, uploads, the launch), part of
                      ``phase_s["schedule"]``
``classes``           iterations by what they dispatched: ``decode``,
                      ``decode+prefill``, ``prefill``, ``none``, each
                      ``{n, s}``
``step_carries_prefill_pct``  of the iterations that dispatched a decode
                      step, the per cent that dispatched a prefill unit
                      too: which class of token gap a percentile reads
                      (10 or more: step + unit; 2.5 or less: step)
``gap_ms``            the token gap from inside: from one decode (or
                      ``spec``) step's ``serve.emit`` to the next step's,
                      once for every row the later step held, in a
                      histogram of fixed edges (a factor 2**(1/8) apart,
                      0.25 ms to 4 s) a class: ``step``, or ``step+unit``
                      if a prefill unit was dispatched between the two
                      steps' dispatches; ``{n, p50, p95}`` a class and
                      over ``all``, interpolated inside a bucket (within
                      9 %).  A step after an iteration that dispatched no
                      step (the pool ran empty) starts anew
``slowest``           the 8 longest iterations, longest first: ``it``,
                      ``at`` (``time.perf_counter()`` at its start, the
                      clock of ``RequestHandle``'s stamps), ``wall_ms``,
                      ``cpu_ms`` (``time.thread_time()`` of the engine's
                      thread since the iteration before ended: its wait
                      between two costs none), ``class``, ``phase`` (the
                      one that took most of it), ``phase_ms``, ``active``.
                      ``wall_ms`` far above ``cpu_ms`` with ``phase`` not
                      ``fetch``/``idle_wait``: the thread was off the CPU
                      (the machine stood still, or another thread held
                      the interpreter); ``fetch``: the device or the
                      runtime; else, ``cpu_ms`` near ``wall_ms``: host
                      code, and ``phase`` says which
``held``              why the queue's head was left waiting, by
                      ``serve.hold``'s ``reason``: ``{n, s}``, iterations
                      and the seconds of those iterations
===================== ======================================================

Counters of a model of hybrid blocks (``ServingEngine.stats``; the decode
step's program hands them back behind its tokens, so they cost no device
read of their own): ``moe_assignments_held`` (live rows' assignments to the
experts held here), ``moe_experts_touched`` (held experts that got one),
``moe_load_max`` (the fullest held expert's rows), each summed over expert
layers and decode steps, ``moe_layer_steps`` the (layer, step) pairs summed
over; ``recurrent_slots_cleared``, admissions whose slot's recurrent state
was started from zero; ``recurrent_state_bytes_moved``, live rows times a
row's recurrent state (all recurrent layers), read and written, summed over
decode steps.  The benchmark's ``.hybrid``, ``.nemotronh`` and ``.granite``
readers read them.

Training (``DistributedTrainer.train``, epoch and per-round paths):
``train.epoch`` (``epoch``; ``ce`` = ``kernel``/``xla``: whether the step's
sparse cross-entropy runs in the ``fused_ce_*`` kernels, by
``core.losses.fused_ce_applies`` on what the model hands the loss) with
children ``train.shuffle``,
``train.shape`` (``shape_epoch_data``), ``train.dispatch`` (host→device
transfer and launch of ``run_epoch``/``run_round``: ``rounds``),
``train.fetch`` (the host waits for the device's losses), ``train.log``,
``train.checkpoint``, ``train.validate``.

**Device scopes** (``jax.named_scope``; HLO metadata only, free at run
time) name the compiled programs' phases in every profile and HLO dump:
``embed``, ``block_<i>`` ⊃ ``attn`` ⊃ ``attn_core``, ``mlp``,
``final_norm``, ``lm_head`` (model forward, training and decoding alike);
in a ``HybridBlock``, ``attn`` ⊃ ``attn_core``, ``attn_gate`` (the output
gate) or ``kda`` ⊃ ``kda_conv`` (projections, convolution, normalisation),
``kda_gates``, ``kda_core`` (the recurrence: ``kda_chunk`` in a prefill
unit, the ``kda_decode`` kernel in the decode step), ``kda_gate_out``, or
``ssm`` ⊃ ``ssm_proj`` (the input projection), ``ssm_conv``, ``ssm_core``
(the state-space recurrence: ``ssd_chunk`` in a prefill unit, the
``ssd_decode`` kernel in the decode step), ``ssm_out`` (skip, gate, grouped
norm, output projection); and, in a block that has a feed-forward part,
``moe`` ⊃ ``moe_route``, ``moe_dispatch``, ``moe_experts`` (the grouped
matmuls), ``moe_combine``, ``moe_shared`` (sparse experts), or ``mlp`` ⊃
``mlp_in`` (gate and up, SiLU), ``mlp_out`` (a dense ``GatedMLP``);
``lm_head`` holds the head's matmul whether the head has a kernel of its
own (``Dense``) or reads the embedding table (``TiedHead``);
``loss``, ``optimizer``, ``commit`` (train step and the SPMD round);
``kv_write``, ``kv_gather``, ``sample`` (decode step; on the kernel path
``kv_gather`` holds only the row lengths' preparation).  Pallas kernels
carry the names in ``KERNEL_NAMES``: ``flash_fwd`` and the backward's
``flash_bwd`` (one kernel for dq, dk and dv, wherever its whole-S dq
accumulator fits VMEM: the name in a trace says which form engaged) or
``flash_dq``/``flash_dkv`` (the two-pass pair of the sequences where it does
not), ``fused_ce_fwd``/``fused_ce_bwd``, ``paged_decode``
(under ``attn_core`` of the paged single-token step), ``kda_decode``
(under ``kda_core`` of the same step) and ``ssd_decode`` (under
``ssm_core``).  The experts' grouped matmul is
jax's own Pallas kernel (``jax.experimental.pallas.ops.tpu.megablox``),
which carries no name of this package: it is found by its scope,
``moe_experts``.
"""

from __future__ import annotations

import contextlib
import json
import math
import threading
import time
from typing import IO, Any, Dict, List, Optional

import jax

#: ``name=`` of every ``pallas_call`` in ``ops/``: what a trace, an HLO dump,
#: ``chip_smoke.py``'s ``require_kernels`` and ``tests/test_tracing.py`` look
#: the kernels up by.
KERNEL_NAMES = ("flash_fwd", "flash_bwd", "flash_dq", "flash_dkv",
                "fused_ce_fwd", "fused_ce_bwd", "paged_decode", "kda_decode",
                "ssd_decode")


class MetricsLogger:
    """Append-only JSONL event log + in-memory history.

    Events carry a monotonic wall-clock ``t`` and arbitrary scalar fields:
    ``log(step=3, loss=0.7, examples_per_sec=1e6)``.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self.events: List[Dict[str, Any]] = []
        self._fh: Optional[IO[str]] = open(path, "a") if path else None

    def log(self, **fields) -> Dict[str, Any]:
        # absolute wall time: stays monotonic when a resumed run appends to
        # the same JSONL file
        event = {"t": round(time.time(), 6)}
        event.update({k: (float(v) if hasattr(v, "item") else v)
                      for k, v in fields.items()})
        self.events.append(event)
        if self._fh:
            self._fh.write(json.dumps(event) + "\n")
            self._fh.flush()
        return event

    def scalar_series(self, field: str) -> List[float]:
        return [e[field] for e in self.events if field in e]

    def close(self):
        if self._fh:
            self._fh.close()
            self._fh = None


class EpochMetrics:
    """Derives per-epoch throughput for the trainers: examples/sec and
    examples/sec/chip from (rows, seconds, num_chips)."""

    def __init__(self, logger: Optional[MetricsLogger] = None,
                 num_chips: int = 1):
        self.logger = logger or MetricsLogger()
        self.num_chips = max(int(num_chips), 1)

    def epoch(self, epoch: int, examples: int, seconds: float,
              mean_loss: float) -> Dict[str, Any]:
        eps = examples / seconds if seconds > 0 else float("inf")
        return self.logger.log(
            kind="epoch", epoch=epoch, examples=examples,
            seconds=round(seconds, 6), loss=mean_loss,
            examples_per_sec=round(eps, 2),
            examples_per_sec_per_chip=round(eps / self.num_chips, 2))


@contextlib.contextmanager
def trace(log_dir: str, enabled: bool = True):
    """Capture a ``jax.profiler`` trace (device operations and the
    program's host spans, one timeline) for the enclosed block; view with
    TensorBoard / Perfetto, or read the ``.xplane.pb`` with
    ``jax.profiler.ProfileData``.  The tracer levels are the benchmark's
    (``--trace 1``), so an operator's trace and the benchmark's hold the
    same events: runtime and annotation spans, no Python call stacks.
    No-ops cleanly when disabled."""
    if not enabled:
        yield
        return
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def span(name: str, **fields):
    """A named host span with ``fields`` (ints / short strings, fixed at
    entry) as its stats — a ``jax.profiler.TraceAnnotation``, so it is
    recorded only while a profiler session is open and shares the device
    trace's timeline.  The module docstring lists the program's spans."""
    return jax.profiler.TraceAnnotation(name, **fields)


# -- the serving engine's own account ------------------------------------------
# token gaps are counted in buckets a factor 2**(1/8) apart: bucket 0 holds
# what is under _GAP_LO_MS, bucket i (1.._GAP_BUCKETS) the gaps from
# _GAP_LO_MS * 2**((i - 1) / 8) up, the last bucket what is over 4 s
_GAP_LO_MS = 0.25
_GAP_PER_OCTAVE = 8
_GAP_BUCKETS = 14 * _GAP_PER_OCTAVE          # 0.25 ms * 2**14 = 4.096 s
_SLOWEST = 8
_CLASSES = ("decode", "decode+prefill", "prefill", "none")
_now = time.perf_counter


def _note(name: str, fields: Dict[str, Any]):
    """The span ``name``, or nothing while no profiler session is open
    (where a ``TraceAnnotation`` records nothing and still costs its
    making): the account's stamps run in every iteration of every run."""
    if jax.profiler.TraceAnnotation.is_enabled():
        return jax.profiler.TraceAnnotation(name, **fields)
    return None


def _gap_edge(i: int) -> float:
    """Lower edge, in ms, of gap bucket ``i``."""
    return 0.0 if i <= 0 else _GAP_LO_MS * 2.0 ** (
        (min(i, _GAP_BUCKETS + 1) - 1) / _GAP_PER_OCTAVE)


def _gap_percentiles(hist: List[int]) -> Dict[str, Any]:
    """``{n, p50, p95}`` of a gap histogram, linear inside a bucket."""
    n = sum(hist)
    out: Dict[str, Any] = {"n": n, "p50": None, "p95": None}
    for key, q in (("p50", 0.50), ("p95", 0.95)):
        rank, seen = q * n, 0
        for i, c in enumerate(hist):
            if c and seen + c >= rank:
                lo, hi = _gap_edge(i), _gap_edge(i + 1)
                out[key] = lo + (hi - lo) * (rank - seen) / c
                break
            seen += c
    return out


class _Stamp:
    """One open phase of the engine's loop: the span (``None`` while no
    profiler session is open) and the clock."""
    __slots__ = ("acct", "name", "note", "t0")

    def __init__(self, acct: "EngineAccount", name: str, note):
        self.acct, self.name, self.note = acct, name, note

    def __enter__(self):
        if self.note is not None:
            self.note.__enter__()
        self.acct._depth += 1
        self.t0 = _now()
        return self

    def __exit__(self, *exc):
        t1 = _now()
        acct = self.acct
        acct._depth -= 1
        if not acct._depth:      # inside another phase: the outer one's time
            acct.phase_s[self.name] = (acct.phase_s.get(self.name, 0.0)
                                       + t1 - self.t0)
            acct._last = t1
        if self.note is not None:
            self.note.__exit__(*exc)
        return False


class _Unit(_Stamp):
    """``serve.prefill_unit``: counted with its seconds, inside
    ``schedule``'s."""
    __slots__ = ()

    def __enter__(self):
        if self.note is not None:
            self.note.__enter__()
        self.t0 = _now()
        return self

    def __exit__(self, *exc):
        acct = self.acct
        acct.prefill_unit[0] += 1
        acct.prefill_unit[1] += _now() - self.t0
        acct._units_since_step += 1
        if self.note is not None:
            self.note.__exit__(*exc)
        return False


class _Iteration(_Stamp):
    """``serve.iteration``: one ``step()``."""
    __slots__ = ()

    def __enter__(self):
        if self.note is not None:
            self.note.__enter__()
        self.t0 = _now()
        self.acct._begin(self.t0)
        return self

    def __exit__(self, *exc):
        self.acct._end(self.t0, _now())
        if self.note is not None:
            self.note.__exit__(*exc)
        return False


class EngineAccount:
    """The serving engine's own account of every iteration, tracing on or
    off (the module docstring lists ``snapshot()``'s keys).  One stamp feeds
    the span and the account: ``iteration``, ``phase`` and ``unit`` open the
    ``serve.*`` span of that name with its fields and add the elapsed
    ``time.perf_counter()`` seconds here.  The engine's thread is the only
    writer and takes no lock; memory is fixed (two histograms, eight kept
    iterations, a few dicts whose keys are the phases, classes and
    reasons)."""

    def __init__(self):
        self.iterations = 0
        self.phase_s: Dict[str, float] = {}
        self.prefill_unit: List[float] = [0, 0.0]            # n, seconds
        self.classes = {c: [0, 0.0] for c in _CLASSES}
        self.gaps = {c: [0] * (_GAP_BUCKETS + 2)
                     for c in ("step", "step+unit")}
        self.held: Dict[str, List[float]] = {}
        self.slowest: List[Dict[str, Any]] = []
        self._first: Optional[float] = None    # the first iteration's start
        self._last = 0.0                       # the latest stamp's end
        self._depth = 0                        # phases open
        self._thread = 0                       # the thread that iterates
        self._cpu = 0.0                        # its CPU time, last iteration's end
        # the open iteration
        self._it = self._active = 0
        self._at_start: Dict[str, float] = {}
        self._units_at_start = 0
        self._decoded = False
        self._held: Optional[str] = None
        # the token gap: the last step emitted, and which dispatched steps
        # had a prefill unit dispatched since the step before (the
        # lookahead keeps one step in flight: eight places are plenty)
        self._emit_step = -1
        self._emit_at = 0.0
        self._units_since_step = 0
        self._carries = [False] * 8

    # -- stamps ----------------------------------------------------------------
    def iteration(self, it: int, active: int) -> _Iteration:
        self._it, self._active = it, active
        return _Iteration(self, "iteration", _note(
            "serve.iteration", {"it": it, "active": active}))

    def phase(self, name: str, **fields) -> _Stamp:
        """``with account.phase("fetch", step=step):`` is the span
        ``serve.fetch`` and ``phase_s["fetch"]``'s seconds."""
        return _Stamp(self, name, _note("serve." + name, fields))

    def unit(self, **fields) -> _Unit:
        return _Unit(self, "prefill_unit",
                     _note("serve.prefill_unit", fields))

    def hold(self, reason: str, queued: int) -> None:
        """The scheduler stopped admitting with ``queued`` requests
        waiting: the span ``serve.hold``, and this iteration under
        ``held[reason]``."""
        self._held = reason
        note = _note("serve.hold", {"reason": reason, "queued": queued})
        if note is not None:
            with note:
                pass

    def decode_step(self, step: int) -> None:
        """Decode step ``step`` is being dispatched."""
        self._decoded = True
        self._carries[step & 7] = self._units_since_step > 0
        self._units_since_step = 0

    def step_emitted(self, step: int, rows: int, at: float) -> None:
        """Step ``step``'s token loop opened at ``at`` with ``rows`` rows:
        if the step before was the last one emitted, each row waited
        ``at`` less that step's instant for this token."""
        if step == self._emit_step + 1:
            ms = (at - self._emit_at) * 1e3
            i = (0 if ms < _GAP_LO_MS else min(1 + int(
                _GAP_PER_OCTAVE * math.log2(ms / _GAP_LO_MS)),
                _GAP_BUCKETS + 1))
            self.gaps["step+unit" if self._carries[step & 7]
                      else "step"][i] += rows
        self._emit_step, self._emit_at = step, at

    # -- an iteration's ends -----------------------------------------------------
    def _begin(self, t0: float) -> None:
        if self._first is None:
            self._first = t0
        ident = threading.get_ident()
        if ident != self._thread:            # the loop's (new) thread
            self._thread = ident
            self._cpu = time.thread_time()
        self.iterations += 1
        self._at_start = self.phase_s.copy()
        self._units_at_start = self.prefill_unit[0]
        self._decoded = False
        self._held = None

    def _end(self, t0: float, t1: float) -> None:
        wall = t1 - t0
        self._last = t1
        cpu0, self._cpu = self._cpu, time.thread_time()
        unit = self.prefill_unit[0] > self._units_at_start
        if self._decoded:
            cls = "decode+prefill" if unit else "decode"
        else:
            cls = "prefill" if unit else "none"
            self._emit_step = -1     # the pool ran empty: no gap across it
        entry = self.classes[cls]
        entry[0] += 1
        entry[1] += wall
        if self._held is not None:
            entry = self.held.setdefault(self._held, [0, 0.0])
            entry[0] += 1
            entry[1] += wall
        kept = self.slowest
        if len(kept) < _SLOWEST or wall * 1e3 > kept[-1]["wall_ms"]:
            before = self._at_start
            spent = {k: v - before.get(k, 0.0)
                     for k, v in self.phase_s.items()}
            top = max(spent, key=spent.get, default=None)
            kept.append({
                "it": self._it, "at": t0, "wall_ms": wall * 1e3,
                "cpu_ms": (self._cpu - cpu0) * 1e3, "class": cls,
                "phase": top, "phase_ms": spent.get(top, 0.0) * 1e3,
                "active": self._active})
            kept.sort(key=lambda e: -e["wall_ms"])
            del kept[_SLOWEST:]

    # -- the reader's side --------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """The account as a plain dict (``json.dumps`` takes it), from any
        thread; taken while the engine runs it may trail it by a stamp."""
        classes = {c: {"n": n, "s": s}
                   for c, (n, s) in self.classes.items()}
        steps = classes["decode"]["n"] + classes["decode+prefill"]["n"]
        gaps = {c: list(h) for c, h in self.gaps.items()}
        both = [a + b for a, b in zip(*gaps.values())]
        return {
            "loop_s": (0.0 if self._first is None
                       else self._last - self._first),
            "iterations": self.iterations,
            "phase_s": dict(self.phase_s),
            "prefill_unit": {"n": self.prefill_unit[0],
                             "s": self.prefill_unit[1]},
            "classes": classes,
            "step_carries_prefill_pct": (
                100.0 * classes["decode+prefill"]["n"] / steps
                if steps else None),
            "gap_ms": {**{c: _gap_percentiles(h) for c, h in gaps.items()},
                       "all": _gap_percentiles(both)},
            "slowest": [dict(e) for e in list(self.slowest)],
            "held": {r: {"n": n, "s": s}
                     for r, (n, s) in list(self.held.items())},
        }
