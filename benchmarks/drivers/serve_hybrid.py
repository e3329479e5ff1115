"""Driver of kind ``serve_hybrid``: ``ServingEngine`` serving a model of
hybrid blocks (attention layers beside recurrent layers, sparse experts)
built through ``lib/program_solar.py``, driven by the same load generator and
read by the same expressions as ``serve_engine``: ``Tracked``, ``offer_open``,
``wait_all``, ``pick_sample`` and ``precision_below_stated`` are that
driver's, imported.  Time to first token counts from the instant a request was
DUE.

What differs: how the engine is built; what ``records`` holds (the experts'
and the recurrent state's counters over the traced span, for the ``.hybrid``
readers; nothing that only GPT-2's counts can read); and ``correct``, which
runs ``lib/reference_solar.py`` and also holds the recurrent state to the
TYPE the configuration states.  (By type alone: rounding the state to
bfloat16 moves the served tokens less than bfloat16 matmuls do, so no number
made from tokens holds the state's precision; PERF.md section 2.)

``run`` is :func:`serve_window` (set-up, lead-in, window, drain: everything
that is measured) and then :func:`check`; ``tools/read_limits_solar.py``
calls the two apart, for many seeds in one process.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

import numpy as np

from benchmarks.lib import harness, manifest as mf, program_solar
from benchmarks.lib.stats import median, percentile
from benchmarks.lib.traffic import generate

_se = mf.load_driver("serve_engine")
Tracked, offer_open, wait_all = _se.Tracked, _se.offer_open, _se.wait_all
pick_sample = _se.pick_sample

#: engine.stats keys snapshotted at the window's and the traced span's edges
_COUNTERS = ("decode_steps", "active_slot_steps", "tokens_generated",
             "prefill_tokens", "prefix_hit_tokens", "prefill_chunks",
             "prefill_batches", "moe_assignments_held",
             "moe_experts_touched", "moe_load_max", "moe_layer_steps",
             "recurrent_slots_cleared")


def build_engine(ctx: harness.RunContext):
    return program_solar.build_engine(ctx.cfg, ctx.seed)


def precision_below_stated(engine, cfg: Dict[str, Any]) -> int:
    """``serve_engine``'s count (parameters against ``compute``, every leaf
    of ``engine.caches`` against ``kv_cache``) plus the recurrent layers'
    state ``S`` against ``recurrent_state``."""
    import jax.numpy as jnp
    want = jnp.dtype(cfg["precision"]["recurrent_state"]).itemsize
    narrow = sum(1 for c in engine.caches
                 if isinstance(c, dict) and "S" in c
                 and jnp.dtype(c["S"].dtype).itemsize < want)
    return _se.precision_below_stated(engine, cfg) + narrow


class Window(NamedTuple):
    """What :func:`serve_window` measured, and what ``correct`` needs of it
    once the engine is gone."""
    end_to_end: Dict[str, float]
    records: Dict[str, Any]
    attempted: int
    failed: int
    memory_peak_bytes: int
    trace_path: Optional[str]
    served: list                  # (prompt, served tokens) of the sample
    below_stated: int


def serve_window(ctx: harness.RunContext) -> Window:
    traffic, cfg = ctx.traffic, ctx.cfg
    engine = build_engine(ctx)
    engine.warmup()
    engine.start()
    requests = generate(traffic, ctx.seed, ctx.seconds,
                        int(cfg["vocab_size"]))
    lead_in = float(traffic.get("lead_in_s", 0.0))

    profiler = harness.Profiler(ctx.out_dir, ctx.cell["name"]) \
        if ctx.trace else None
    t0 = time.perf_counter() + 0.05            # the schedule's zero
    t_open = t0 + lead_in                      # the window
    t_close = t_open + ctx.seconds
    at = {}
    tracer = None
    span0: Dict[str, int] = {}
    span1: Dict[str, int] = {}

    def counters(into):
        into.update({k: engine.stats.get(k, 0) for k in _COUNTERS})

    if profiler:
        # on a thread of its own, as in serve_engine: the profiler's start
        # and stop take tenths of a second and must not hold up arrivals
        start = t_open + min(float(traffic["trace"]["start_s"]),
                             max(ctx.seconds - 1.0, 0.0))
        stop = min(start + float(traffic["trace"]["span_s"]), t_close)

        def trace_span():
            time.sleep(max(start - time.perf_counter(), 0.0))
            profiler.start()
            counters(span0)
            time.sleep(max(stop - time.perf_counter(), 0.0))
            counters(span1)
            profiler.stop()
        tracer = threading.Thread(target=trace_span, name="bench-tracer",
                                  daemon=True)
        tracer.start()
    stats0: Dict[str, int] = {}
    at[t_open] = lambda: counters(stats0)
    try:
        items = [Tracked(r, t0 + r.due_s) for r in requests]
        offer_open(engine, items, t_close, at)
        stats1: Dict[str, int] = {}
        counters(stats1)
        setup_s = t_open - ctx.t_start
        window = [it for it in items if t_open <= it.due < t_close]
        wait_all(window, float(traffic["drain_timeout_s"]))
        drained = time.perf_counter()
        peak = harness.memory_peak_bytes()
        shed = dict(engine.stats)
    finally:
        if tracer is not None:
            tracer.join(timeout=120)
        engine.stop()
    failed = [it for it in window if not it.ok]
    horizon = float(traffic["drain_timeout_s"]) + ctx.seconds

    # -- the end-to-end metrics: serve_engine's expressions
    def first_token_s(it: Tracked) -> float:
        return (it.stamps[0] - it.due) if it.ok else horizon
    ttft = [first_token_s(it) for it in window]
    gaps = [b - a for it in window if it.ok
            for a, b in zip(it.stamps, it.stamps[1:])]
    in_window = sum(1 for it in items for t in it.stamps
                    if t_open <= t < t_close)
    e2e = {"ttft_p95_ms": 1000.0 * percentile(ttft, 95),
           "itl_p95_ms": 1000.0 * percentile(gaps, 95),
           "serve_tokens_per_s": in_window / ctx.seconds,
           "setup_s": setup_s}
    lag = [it.submitted - it.due for it in window
           if it.submitted is not None]
    queue_wait = [it.handle.started_at - it.handle.submitted_at
                  for it in window
                  if it.ok and it.handle.started_at is not None]
    first = failed[0] if failed else None
    steps = stats1["decode_steps"] - stats0["decode_steps"]
    layer_steps = stats1["moe_layer_steps"] - stats0["moe_layer_steps"]
    ctx.log(end_to_end=e2e)
    ctx.log(driver="serve_hybrid", requests=len(items), window=len(window),
            failed=len(failed),
            first_failure=first and (first.error or (
                first.handle.finish if first.handle else "not submitted")),
            ttft_p50_ms=1000 * median(ttft),
            ttft_p95_ms=1000 * percentile(ttft, 95), ttft_samples=len(ttft),
            itl_p50_ms=1000 * median(gaps), itl_samples=len(gaps),
            tokens_in_window=in_window, drain_s=drained - t_close,
            gen_lag_p95_ms=1000 * percentile(lag, 95),
            queue_wait_p95_ms=1000 * percentile(queue_wait, 95),
            backlog_at_close=sum(1 for it in items if it.handle is not None
                                 and it.handle.finished_at is not None
                                 and it.handle.finished_at > t_close),
            rejected=shed["requests_rejected"],
            expired=shed["requests_expired"],
            engine_failed=shed["requests_failed"],
            decode_steps=steps,
            occupancy_pct=100.0 * (stats1["active_slot_steps"]
                                   - stats0["active_slot_steps"])
            / max(steps * engine.num_slots, 1),
            prefill_units=(stats1["prefill_chunks"] + stats1["prefill_batches"]
                           - stats0["prefill_chunks"]
                           - stats0["prefill_batches"]),
            experts_touched_per_layer_step=(
                (stats1["moe_experts_touched"] - stats0["moe_experts_touched"])
                / max(layer_steps, 1)),
            prefix_hit_tokens=shed["prefix_hit_tokens"],
            recurrent_slots_cleared=shed.get("recurrent_slots_cleared"))

    # -- what the per-layer readers read
    span = (profiler.started_at, profiler.stopped_at) if profiler else None
    contexts = None
    traced = None
    if span:
        # context positions attended by every token decoded inside the span
        # (token i >= 1 of a request attends its prompt and the i before it)
        contexts = sum(len(it.req.prompt) + i
                       for it in items for i, t in enumerate(it.stamps)
                       if i >= 1 and span[0] <= t < span[1])
        traced = {k: span1[k] - span0[k] for k in span0}
        traced["seconds"] = span[1] - span[0]
    records = dict(
        kind="serve", queue_wait_s=queue_wait, gen_lag_s=lag,
        num_slots=int(engine.num_slots), decode_steps=steps,
        active_slot_steps=(stats1["active_slot_steps"]
                           - stats0["active_slot_steps"]),
        decode_programs=list(traffic["decode_programs"]),
        serve_programs=list(traffic["serve_programs"]),
        traced_context_positions=contexts,
        window_counters={k: stats1[k] - stats0[k] for k in stats0},
        traced_counters=traced)

    # -- correct: after the window, the engine's state freed first
    sample = pick_sample(window, int(traffic["correct"]["sample"]), ctx.seed)
    served = [(it.req.prompt, np.asarray(it.handle.tokens, np.int32))
              for it in sample]
    below = precision_below_stated(engine, cfg)
    del engine
    gc.collect()
    return Window(end_to_end=e2e, records=records, attempted=len(window),
                  failed=len(failed), memory_peak_bytes=peak,
                  trace_path=profiler.dir if profiler else None,
                  served=served, below_stated=below)


def run(ctx: harness.RunContext) -> harness.RunResult:
    w = serve_window(ctx)
    return harness.RunResult(
        compared=check(ctx, w.served, w.below_stated), attempted=w.attempted,
        failed=w.failed, end_to_end=w.end_to_end, records=w.records,
        memory_peak_bytes=w.memory_peak_bytes, trace_path=w.trace_path)


# -- correct --------------------------------------------------------------------

def _numbers(per_request) -> Dict[str, float]:
    allg = np.concatenate(per_request)
    return {"served_token_gap": float(allg.max()),
            "served_token_mean_gap": float(allg.mean()),
            "served_token_flip_share": float((allg > 0).mean())}


def score(ctx: harness.RunContext, served) -> Dict[str, Dict[str, float]]:
    """The reference over each sampled request, once a pass: with int8
    matmuls (its own first tokens: the yardstick and the control), and as it
    is, scoring the served tokens and the control's at every served
    position.  Returns the numbers of ``"program"`` and ``"int8"`` tokens."""
    from benchmarks.lib import reference_solar as ref
    from benchmarks.lib.counts_solar import dims
    from benchmarks.lib.weights_solar import make_weights
    cfg = ctx.cfg
    w = make_weights(cfg, ctx.seed, cfg["precision"]["params"])
    d = dims(cfg)
    names = ["program", "int8"]
    gaps: Dict[str, list] = {n: [] for n in names}
    for prompt, toks in served:
        pad = ref.pad_length(len(prompt) + len(toks))
        cands = [toks, ref.served_position_scores(
            w, prompt, toks, [], d, pad, mm=ref.int8_matmul)[1]]
        both, _ = ref.served_position_scores(w, prompt, toks, cands, d, pad)
        for n, g in zip(names, both):
            gaps[n].append(g)
    out = {n: _numbers(g) for n, g in gaps.items()}
    yard = out["int8"]["served_token_mean_gap"]
    for nums in out.values():
        mean = nums["served_token_mean_gap"]
        nums["served_mean_gap_vs_int8"] = (
            mean / yard if yard > 0 else 0.0 if mean == 0 else float("inf"))
    return out


def check(ctx: harness.RunContext, served, below_stated: int,
          in_place: Optional[str] = None) -> List[harness.Compared]:
    """``serve_engine.check``'s comparison against ``reference_solar``: the
    plain reference run once over each sampled prompt with its served tokens
    (prefill in buckets and chunks, the recurrent state carried from unit to
    unit, then decoding through both kinds of state, held to the full
    forward with the recurrence token by token); over all served positions,
    how far the served token's logit lies below the reference's best
    (``served_token_gap``, the widest) and the mean gap over the mean gap of
    the tokens the reference with int8 matmuls puts first
    (``served_mean_gap_vs_int8``).  ``below_stated`` against 0, exactly.

    ``in_place="int8"`` (the tests, never a run) puts the control's own
    first tokens where the program's were: those of the reference with int8
    matmuls."""
    limits = ctx.traffic["correct"]["limits"]
    t0 = time.perf_counter()
    stated = harness.Compared("precision_below_stated", float(below_stated),
                              0.0, exact=True)
    if not served:
        return [stated] + [harness.Compared(k, float("inf"), float(v))
                           for k, v in limits.items() if v is not None]
    all_ = score(ctx, served)
    got = all_[in_place or "program"]
    ctx.log(check="served_tokens", requests=len(served),
            tokens=int(sum(len(t) for _, t in served)),
            longest=max(len(p) + len(t) for p, t in served),
            reference_s=time.perf_counter() - t0, in_place=in_place,
            **got, reference_int8=all_["int8"])
    return [stated] + [harness.Compared(k, got[k], float(v))
                       for k, v in limits.items() if v is not None]
