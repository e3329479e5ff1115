"""Driver of kind ``serve_granite``: ``ServingEngine`` serving a model whose
every block is a mixer (a Mamba-2 state-space mixer or attention) THEN a
dense gated MLP, under a tied head, built through ``lib/program_granite.py``.

The window is ``serve_hybrid``'s own (set-up, lead-in, window, drain, the
records the readers read): that driver is loaded a second time, as a module of
this driver's own, and given another ``build_engine``, another ``score`` (the
reference of ``correct`` is ``lib/reference_granite.py``) and the engine's
counter of the recurrent state's bytes moved by the decode steps.  Nothing
of the window is written again here.  ``tools/read_limits_solar.py`` and
``tools/sweep_serve.py`` take this kind as they take that one
(``serve_window``, ``score``, ``build_engine``, ``Tracked``, ``offer_open``,
``wait_all``).

What is added: two lines of the run's log that the cell's tables in PERF.md
hold: how many requests the window held with the share of its token gaps that
carry a prefill unit (from the stamps, and from the engine's counters: which
class of gap ``itl_p95_ms`` reads), and ``serve_engine``'s ``stalls`` line
(whose clock stood still in a run that reads far off).  ``ttft_p95_ms`` is on
the window's own ``end_to_end`` line whether or not the manifest lists it.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.lib import harness, manifest as mf, program_granite
from benchmarks.lib.stats import share_over_pct

_sh = mf.load_driver("serve_hybrid")        # this driver's own copy
Tracked, offer_open, wait_all = _sh.Tracked, _sh.offer_open, _sh.wait_all
pick_sample, Window = _sh.pick_sample, _sh.Window


def build_engine(ctx: harness.RunContext):
    return program_granite.build_engine(ctx.cfg, ctx.seed)


def score(ctx: harness.RunContext, served) -> Dict[str, Dict[str, float]]:
    """``serve_hybrid.score`` over ``reference_granite``: each sampled
    request once with int8 matmuls (its own first tokens: the yardstick and
    the control) and once as it is, scoring the served tokens and the
    control's at every served position.  Rows are padded to multiples of
    ``correct.pad_to`` so that the reference compiles few programs."""
    from benchmarks.lib import reference_granite as ref
    from benchmarks.lib.counts_granite import dims
    from benchmarks.lib.weights_granite import make_weights
    cfg = ctx.cfg
    w = make_weights(cfg, ctx.seed, cfg["precision"]["params"])
    d = dims(cfg)
    step = int(ctx.traffic["correct"]["pad_to"])
    names = ["program", "int8"]
    gaps: Dict[str, list] = {n: [] for n in names}
    for prompt, toks in served:
        pad = ref.pad_length(len(prompt) + len(toks), step)
        cands = [toks, ref.served_position_scores(
            w, prompt, toks, [], d, pad, mm=ref.int8_matmul)[1]]
        both, _ = ref.served_position_scores(w, prompt, toks, cands, d, pad)
        for n, g in zip(names, both):
            gaps[n].append(g)
    out = {n: _sh._numbers(g) for n, g in gaps.items()}
    yard = out["int8"]["served_token_mean_gap"]
    for nums in out.values():
        mean = nums["served_token_mean_gap"]
        nums["served_mean_gap_vs_int8"] = (
            mean / yard if yard > 0 else 0.0 if mean == 0 else float("inf"))
    return out


_sh.build_engine = build_engine
_sh.score = score
_sh._COUNTERS = _sh._COUNTERS + ("recurrent_state_bytes_moved",)
check, precision_below_stated = _sh.check, _sh.precision_below_stated


def serve_window(ctx: harness.RunContext) -> Window:
    # the window keeps its stamps to itself; what it offers the engine is
    # seen here on the way through, for the two lines below that need them
    seen: Dict = {}

    def offer(engine, items, t_close, at):
        seen.update(items=items, t_close=t_close)
        return offer_open(engine, items, t_close, at)
    _sh.offer_open = offer
    try:
        w = _sh.serve_window(ctx)
    finally:
        _sh.offer_open = offer_open
    items, t_close = seen["items"], seen["t_close"]
    t_open = t_close - ctx.seconds
    window = [it for it in items if t_open <= it.due < t_close]
    # serve_engine's line: where a run reads far off, whose clock stood
    # still: the generator's worst lag and when, and the longest span of the
    # window in which the engine emitted no token
    late = max((it for it in window if it.submitted is not None),
               key=lambda it: it.submitted - it.due, default=None)
    beats = sorted({t for it in items for t in it.stamps
                    if t_open <= t < t_close})
    quiet = max(zip(beats[1:], beats), key=lambda ab: ab[0] - ab[1],
                default=None)
    ctx.log(stalls="serve_granite",
            gen_lag_max_ms=late and 1000 * (late.submitted - late.due),
            gen_lag_max_at_s=late and late.due - t_open,
            engine_quiet_max_ms=quiet and 1000 * (quiet[0] - quiet[1]),
            engine_quiet_max_at_s=quiet and quiet[1] - t_open)
    gaps = [b - a for it in window if it.ok
            for a, b in zip(it.stamps, it.stamps[1:])]
    c = w.records["window_counters"]
    units = c["prefill_chunks"] + c["prefill_batches"]
    ctx.log(driver="serve_granite", window_requests=w.attempted,
            prefill_units=units, decode_steps=w.records["decode_steps"],
            # which class of token gap itl_p95_ms reads (PERF.md section 4):
            # the share of the window's token gaps longer than twice their
            # median, the gaps that carry a prefill unit beside the step ...
            gaps_over_2x_p50_pct=share_over_pct(gaps, 2.0),
            # ... and the engine's count of it, by iteration and not by
            # row: an iteration spends at most one prefill unit
            # (prefills_per_step 1) before its decode step
            gaps_with_prefill_unit_pct=100.0 * units
            / max(w.records["decode_steps"], 1),
            prefill_tokens=c["prefill_tokens"],
            # the window's mean of rows live a decode step: what the end-to-
            # end metrics are taken at (the traced span's own is below)
            window_rows_live=(c["active_slot_steps"]
                              / max(w.records["decode_steps"], 1)),
            # a live row's state, read and written, a decode step (MB)
            recurrent_state_mb_per_step=(
                c["recurrent_state_bytes_moved"] / 1e6
                / max(w.records["decode_steps"], 1)))
    traced = w.records.get("traced_counters")
    if traced and traced.get("decode_steps"):
        # what the traced span held, beside which the ``.granite`` readers'
        # numbers are read: the span lies inside the schedule's largest
        # burst, so they are burst-span readings (rows live against the
        # window's mean on the line above; the share of its decode steps
        # that carry a prefill unit against ``gaps_with_prefill_unit_pct``)
        ctx.log(traced_span_s=traced["seconds"],
                traced_decode_steps=traced["decode_steps"],
                traced_rows_live=(traced["active_slot_steps"]
                                  / traced["decode_steps"]),
                traced_prefill_units=(traced["prefill_chunks"]
                                      + traced["prefill_batches"]),
                traced_steps_with_prefill_unit_pct=(
                    100.0 * (traced["prefill_chunks"]
                             + traced["prefill_batches"])
                    / traced["decode_steps"]),
                traced_prefill_tokens=traced["prefill_tokens"])
    # a tied head meets each token's own embedding again: were that to
    # decide the next token, the served tokens would repeat themselves and
    # ``correct`` would compare nothing.  Of the sample's served tokens, the
    # share equal to the token before, and the distinct ones
    toks = [t for _, t in w.served if len(t) > 1]
    if toks:
        import numpy as np
        allt = np.concatenate(toks)
        ctx.log(served_tokens=int(allt.size),
                served_repeat_share=float(np.mean(np.concatenate(
                    [t[1:] == t[:-1] for t in toks]))),
                served_distinct_share=float(np.unique(allt).size / allt.size))
    return w


def run(ctx: harness.RunContext) -> harness.RunResult:
    w = serve_window(ctx)
    return harness.RunResult(
        compared=check(ctx, w.served, w.below_stated), attempted=w.attempted,
        failed=w.failed, end_to_end=w.end_to_end, records=w.records,
        memory_peak_bytes=w.memory_peak_bytes, trace_path=w.trace_path)
