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

Serving (``serving.py``; the engine's thread unless said):

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

Counters of a model of hybrid blocks (``ServingEngine.stats``; the decode
step's program hands them back behind its tokens, so they cost no device
read of their own): ``moe_assignments_held`` (live rows' assignments to the
experts held here), ``moe_experts_touched`` (held experts that got one),
``moe_load_max`` (the fullest held expert's rows), each summed over expert
layers and decode steps, ``moe_layer_steps`` the (layer, step) pairs summed
over; ``recurrent_slots_cleared``, admissions whose slot's recurrent state
was started from zero.  The benchmark's ``.hybrid`` readers read them.

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
matmuls), ``moe_combine``, ``moe_shared``;
``loss``, ``optimizer``, ``commit`` (train step and the SPMD round);
``kv_write``, ``kv_gather``, ``sample`` (decode step; on the kernel path
``kv_gather`` holds only the row lengths' preparation).  Pallas kernels
carry the names in ``KERNEL_NAMES``: ``flash_fwd``/``flash_dq``/
``flash_dkv``, ``fused_ce_fwd``/``fused_ce_bwd``, ``paged_decode``
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
import time
from typing import IO, Any, Dict, List, Optional

import jax

#: ``name=`` of every ``pallas_call`` in ``ops/``: what a trace, an HLO dump,
#: ``chip_smoke.py``'s ``require_kernels`` and ``tests/test_tracing.py`` look
#: the kernels up by.
KERNEL_NAMES = ("flash_fwd", "flash_dq", "flash_dkv",
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
