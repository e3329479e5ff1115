"""Driver of kind ``serve_engine``: ``ServingEngine`` driven in process, on its
own scheduler thread, by the benchmark's load generator.

Set-up: weights on the device from the seed, the engine the configuration's
``deployment.engine`` describes, ``warmup()``, ``start()``, the schedule's
lead-in.  Window: ``--seconds`` of arrivals.  After it arrivals stop and every
request that was due inside the window is waited to its end.

Clocks: the engine stamps handles with ``time.perf_counter``; so does this
file.  A per-token stamp is taken from this side through
``RequestHandle.set_listener`` (fired on the engine's thread after every
token).  Time to first token counts from the instant a request was DUE, not
from ``submit``; how late the generator ran is reported beside it.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks.lib import harness, program, reference
from benchmarks.lib.stats import median, percentile
from benchmarks.lib.traffic import Request, generate
from benchmarks.lib.weights import make_weights


class Tracked:
    """One request as the generator saw it."""

    __slots__ = ("req", "due", "submitted", "handle", "stamps", "error")

    def __init__(self, req: Request, due: float):
        self.req, self.due = req, due
        self.submitted: Optional[float] = None
        self.handle = None
        self.stamps: List[float] = []       # perf_counter of every token
        self.error: Optional[str] = None

    def listen(self) -> None:
        h, stamps = self.handle, self.stamps

        def fire():     # engine thread, after every token and on retirement
            n = len(h.tokens)
            if n > len(stamps):
                stamps.extend([time.perf_counter()] * (n - len(stamps)))
        h.set_listener(fire)

    @property
    def ok(self) -> bool:
        h = self.handle
        return (h is not None and h.error is None
                and h.finish in ("length", "eos")
                and len(h.tokens) == self.req.output_len)


def build_engine(ctx: harness.RunContext):
    program.import_program()
    from distkeras_tpu.core.model import FittedModel
    from distkeras_tpu.serving import ServingEngine
    kw = dict(ctx.cfg["deployment"]["engine"])
    fitted = FittedModel(program.build_model(ctx.cfg),
                         program.program_params(ctx.cfg, ctx.seed))
    return ServingEngine(fitted, **kw)


def precision_below_stated(engine, cfg: Dict[str, Any]) -> int:
    """How many leaves of the engine's parameters (``engine.params``) and of
    its KV pool (``engine.caches``) are held in a narrower type than the
    configuration's ``precision`` block states.  A weight enters every matmul
    in the compute type, so a parameter counts when it is narrower than
    ``compute`` (wider is the same arithmetic); a leaf of the pool when it is
    narrower than ``kv_cache``.  Integer codes (int8, int4) count; 32-bit
    integers are indices, not values.  Served greedy tokens cannot tell the
    engine's int8 paths from its bf16 one (PERF.md section 2), so what the
    deployment states is held here, exactly."""
    import jax
    import jax.numpy as jnp
    stated = cfg["precision"]

    def narrow(x, at_least: str) -> bool:
        dt = jnp.dtype(x.dtype)
        if jnp.issubdtype(dt, jnp.floating):
            return dt.itemsize < jnp.dtype(at_least).itemsize
        return dt.itemsize < 4

    leaves = jax.tree_util.tree_leaves
    return (sum(narrow(x, stated["compute"]) for x in leaves(engine.params))
            + sum(narrow(x, stated["kv_cache"])
                  for x in leaves(engine.caches)))


def _submit(engine, item: Tracked) -> None:
    from distkeras_tpu.serving import QueueFull
    item.submitted = time.perf_counter()
    try:
        item.handle = engine.submit(item.req.prompt, item.req.output_len,
                                    block=False)
    except QueueFull as e:
        item.error = f"QueueFull: {e}"
        return
    item.listen()


def _sleep_until(t: float, at: Dict[float, Any], marks: List[float]) -> None:
    """Sleep to the instant ``t``, running on the way the callbacks of ``at``
    whose instants (``marks``, sorted) have passed."""
    while True:
        now = time.perf_counter()
        while marks and marks[0] <= now:
            at[marks.pop(0)]()
        if now >= t:
            return
        time.sleep(min((min(t, marks[0]) if marks else t) - now, 0.05))


def offer_open(engine, items: List[Tracked], t_end: float,
               at: Dict[float, Any]) -> None:
    """Submit every request at its due time (never early), then wait for
    ``t_end``; ``at`` maps instants to callbacks run as they pass."""
    marks = sorted(at)
    for item in items:
        _sleep_until(item.due, at, marks)
        _submit(engine, item)
    _sleep_until(t_end, at, marks)


def offer_closed(engine, requests: List[Request], clients: int,
                 t_end: float, at: Dict[float, Any]) -> List[Tracked]:
    """``clients`` callers, each sending its next request when its last one
    has finished, until ``t_end``; the requests are taken in order and used
    again from the start if they run out."""
    marks, items = sorted(at), []

    def send():
        item = Tracked(requests[len(items) % len(requests)],
                       time.perf_counter())
        _submit(engine, item)
        items.append(item)
        return item

    live = [send() for _ in range(clients)]
    while time.perf_counter() < t_end:
        _sleep_until(time.perf_counter() + 0.002, at, marks)
        for i, item in enumerate(live):
            if item.handle is None or item.handle.done:
                live[i] = send()
    return items


def wait_all(items: List[Tracked], timeout_s: float) -> None:
    """Wait for every submitted request to retire, ``timeout_s`` in all."""
    deadline = time.perf_counter() + timeout_s
    for it in items:
        if it.handle is not None:
            it.handle.wait(max(deadline - time.perf_counter(), 0.0))


def run(ctx: harness.RunContext) -> harness.RunResult:
    traffic, cfg = ctx.traffic, ctx.cfg
    engine = build_engine(ctx)
    engine.warmup()
    engine.start()
    requests = generate(traffic, ctx.seed, ctx.seconds,
                        int(cfg["vocab_size"]))
    lead_in = float(traffic.get("lead_in_s", 0.0))
    closed = traffic["arrival"]["process"] == "closed"

    profiler = harness.Profiler(ctx.out_dir, ctx.cell["name"]) \
        if ctx.trace else None
    t0 = time.perf_counter() + 0.05            # the schedule's zero
    t_open = t0 + lead_in                      # the window
    t_close = t_open + ctx.seconds
    at = {}
    tracer = None
    if profiler:
        # on a thread of its own: starting and stopping the profiler takes
        # tenths of a second to seconds, and must not hold up arrivals
        start = t_open + min(float(traffic["trace"]["start_s"]),
                             max(ctx.seconds - 1.0, 0.0))
        stop = min(start + float(traffic["trace"]["span_s"]), t_close)

        def trace_span():
            time.sleep(max(start - time.perf_counter(), 0.0))
            profiler.start()
            time.sleep(max(stop - time.perf_counter(), 0.0))
            profiler.stop()
        tracer = threading.Thread(target=trace_span, name="bench-tracer",
                                  daemon=True)
        tracer.start()
    stats0 = {}

    def snap():
        stats0.update({k: engine.stats[k] for k in (
            "decode_steps", "active_slot_steps", "tokens_generated",
            "prefill_tokens", "prefix_hit_tokens")})
    at[t_open] = snap
    try:
        if closed:
            items = offer_closed(engine, requests,
                                 int(traffic["arrival"]["clients"]),
                                 t_close, at)
        else:
            items = [Tracked(r, t0 + r.due_s) for r in requests]
            offer_open(engine, items, t_close, at)
        stats1 = {k: engine.stats[k] for k in stats0}
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

    # -- the end-to-end metrics: all requests due in the window, every token
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
    # where a run reads far off, these say whose clock stood still: the
    # generator's worst lag and when, and the longest span of the window in
    # which the engine emitted no token (an iteration is 0.19-0.25 s)
    late = max((it for it in window if it.submitted is not None),
               key=lambda it: it.submitted - it.due, default=None)
    beats = sorted({t for it in items for t in it.stamps
                    if t_open <= t < t_close})
    quiet = max(zip(beats[1:], beats), key=lambda ab: ab[0] - ab[1],
                default=None)
    ctx.log(stalls="serve_engine",
            gen_lag_max_ms=late and 1000 * (late.submitted - late.due),
            gen_lag_max_at_s=late and late.due - t_open,
            engine_quiet_max_ms=quiet and 1000 * (quiet[0] - quiet[1]),
            engine_quiet_max_at_s=quiet and quiet[1] - t_open)
    ctx.log(end_to_end=e2e)
    ctx.log(driver="serve_engine", requests=len(items), window=len(window),
            failed=len(failed),
            first_failure=first and (first.error or (
                first.handle.finish if first.handle else "not submitted")),
            ttft_p50_ms=1000 * median(ttft), ttft_samples=len(ttft),
            itl_p50_ms=1000 * median(gaps), itl_samples=len(gaps),
            tokens_in_window=in_window, drain_s=drained - t_close,
            gen_lag_p95_ms=1000 * percentile(lag, 95),
            backlog_at_close=sum(1 for it in items if it.handle is not None
                                 and it.handle.finished_at is not None
                                 and it.handle.finished_at > t_close),
            rejected=shed["requests_rejected"],
            expired=shed["requests_expired"],
            engine_failed=shed["requests_failed"])

    # -- what the per-layer readers read
    span = (profiler.started_at, profiler.stopped_at) if profiler else None
    contexts = None
    if span:
        # context positions attended by every token decoded inside the span:
        # token i of a request (i >= 1; the first comes out of prefill)
        # attends its prompt and the i tokens before it
        contexts = sum(len(it.req.prompt) + i
                       for it in items for i, t in enumerate(it.stamps)
                       if i >= 1 and span[0] <= t < span[1])
    records = dict(
        kind="serve", queue_wait_s=queue_wait, gen_lag_s=lag,
        num_slots=int(engine.num_slots),
        decode_steps=stats1["decode_steps"] - stats0["decode_steps"],
        active_slot_steps=(stats1["active_slot_steps"]
                           - stats0["active_slot_steps"]),
        decode_programs=list(traffic["decode_programs"]),
        traced_context_positions=contexts)

    # -- correct: after the window, the engine's state freed first
    sample = pick_sample(window, int(traffic["correct"]["sample"]), ctx.seed)
    served = [(it.req.prompt, np.asarray(it.handle.tokens, np.int32))
              for it in sample]
    below = precision_below_stated(engine, cfg)
    del engine
    gc.collect()
    compared = check(ctx, served, below)
    return harness.RunResult(
        compared=compared, attempted=len(window), failed=len(failed),
        end_to_end=e2e, records=records, memory_peak_bytes=peak,
        trace_path=profiler.dir if profiler else None)


# -- correct --------------------------------------------------------------------

def pick_sample(window: List[Tracked], n: int, seed: int) -> List[Tracked]:
    """``n`` finished requests of the window drawn from the seed, the longest
    (prompt and answer together) always among them."""
    done = [it for it in window if it.ok]
    if not done:
        return []
    longest = max(done, key=lambda it: len(it.req.prompt) + it.req.output_len)
    rest = [it for it in done if it is not longest]
    rng = np.random.default_rng(int(seed) + 1)
    take = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in take]


def check(ctx: harness.RunContext, served, below_stated: int,
          low_in_place: bool = False) -> List[harness.Compared]:
    """Run the plain reference once over each sampled prompt with its served
    tokens and read, over all served positions, how far the served token's
    logit lies below the reference's best: the widest gap, the mean gap and
    the share of positions where the served token is not the reference's
    first.  Seeded weights put near-ties at every position, so how often
    rounding flips a token depends on the seed's weights as much as on the
    precision; the reference run again with int8 matmuls over the same rows
    gives the yardstick: ``served_mean_gap_vs_int8`` is the served tokens'
    mean gap over the mean gap of the tokens that int8 computation puts
    first.  The numbers that the traffic file gives a limit are compared, and
    ``below_stated`` (see ``precision_below_stated``) against 0, exactly.
    Greedy requests only (the mix is all greedy).  Holds prefill, chunked
    prefill and decoding through the paged cache to the full forward, at the
    sizes that were served.  ``low_in_place`` (the tools and the tests, never
    a run) puts the int8 reference's own tokens in the program's place."""
    cfg = ctx.cfg
    limits = ctx.traffic["correct"]["limits"]
    t0 = time.perf_counter()
    stated = harness.Compared("precision_below_stated", float(below_stated),
                              0.0, exact=True)
    if not served:
        return [stated] + [harness.Compared(k, float("inf"), float(v))
                           for k, v in limits.items() if v is not None]
    w = make_weights(cfg, ctx.seed, "float32")
    args = (int(cfg["n_head"]), int(cfg["n_positions"]),
            float(cfg["layer_norm_epsilon"]))
    gaps, low_gaps = [], []
    for prompt, toks in served:
        # the token the reference in int8 puts first at each position of the
        # same row, then both token sequences scored by the reference proper
        _, first = reference.served_position_scores(
            w, prompt, toks, [], *args, mm=reference.int8_matmul)
        both, _ = reference.served_position_scores(w, prompt, toks,
                                                   [toks, first], *args)
        gaps.append(both[0])
        low_gaps.append(both[1])

    def numbers(per_request):
        allg = np.concatenate(per_request)
        return {"served_token_gap": float(allg.max()),
                "served_token_mean_gap": float(allg.mean()),
                "served_token_flip_share": float((allg > 0).mean())}
    low = numbers(low_gaps)
    got = numbers(low_gaps if low_in_place else gaps)
    yard = low["served_token_mean_gap"]
    got["served_mean_gap_vs_int8"] = (
        got["served_token_mean_gap"] / yard if yard > 0
        else 0.0 if got["served_token_mean_gap"] == 0 else float("inf"))
    ctx.log(check="served_tokens", requests=len(served),
            tokens=int(sum(len(g) for g in gaps)),
            longest=max(len(p) + len(t) for p, t in served),
            reference_s=time.perf_counter() - t0, low_in_place=low_in_place,
            **got, reference_int8=low)
    return [stated] + [harness.Compared(k, got[k], float(v))
                       for k, v in limits.items() if v is not None]
