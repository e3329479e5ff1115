"""Find the knee of a serving cell once: ONE process, one set-up, a ladder of
arrival rates of ``--seconds`` each, the same mix at each.

    python benchmarks/tools/sweep_serve.py --workload serve-chat-gpt2m \\
        --rates 4,6,8,10,12,14,18 --seconds 15 --seed 1

The rule in use (PERF.md section 4; PRs 32, 34, 37): the knee is the highest
rung at which at most 2 requests stand queued when arrivals stop
(``queued_at_close``), and the cell's fixed rate is 0.6 x the knee, rounded
down to a multiple of 0.5 and written into its traffic file by hand with this
table in PERF.md.  ``gaps_over_2x_p50_pct`` is the share of a rung's token gaps
longer than twice its median gap: the class of gap that carries a prefill unit
beside the decode step, from the stamps alone.  ``itl_p95_ms`` reads that class
where the share is 10 % or above and a decode-only gap where it is 2.5 % or
below; between the two the percentile sits on the edge and the rate is not
admissible.  Not part of any measurement."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    from benchmarks.lib import harness, manifest as mf
    from benchmarks.lib.stats import median, percentile, share_over_pct
    from benchmarks.lib.traffic import generate
    _, ctx, dev = harness.open_run(args.workload, args.seed, args.seconds,
                                   rehearse=args.rehearse)
    cfg, traffic = ctx.cfg, ctx.traffic
    drv = mf.load_driver(traffic["kind"])
    engine = drv.build_engine(ctx)
    t0 = time.perf_counter()
    engine.warmup()
    engine.start()
    print(json.dumps(dict(device=dev, warmup_s=time.perf_counter() - t0,
                          memory_peak_bytes=harness.memory_peak_bytes())),
          flush=True)
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix = mf.deep_merge(traffic, {"arrival": {"rate": rate},
                                          "lead_in_s": 0})
            reqs = generate(mix, args.seed + i, args.seconds,
                            int(cfg["vocab_size"]))
            before = dict(engine.stats)
            start = time.perf_counter() + 0.05
            items = [drv.Tracked(r, start + r.due_s) for r in reqs]
            t_close = start + args.seconds
            drv.offer_open(engine, items, t_close, {})
            unfinished = sum(1 for it in items if it.handle is None
                             or not it.handle.done)
            queued = engine.queue_depth
            drv.wait_all(items, float(traffic["drain_timeout_s"]))
            drained = time.perf_counter()
            ok = [it for it in items if it.ok]
            ttft = [it.stamps[0] - it.due for it in ok]
            gaps = [b - a for it in ok
                    for a, b in zip(it.stamps, it.stamps[1:])]
            toks = sum(1 for it in items for t in it.stamps
                       if start <= t < t_close)
            after = engine.stats
            steps = after["decode_steps"] - before["decode_steps"]
            units = sum(after.get(k, 0) - before.get(k, 0)
                        for k in ("prefill_chunks", "prefill_batches"))
            print(json.dumps(dict(
                rate=rate, requests=len(items), ok=len(ok),
                shed=after["requests_rejected"] - before["requests_rejected"],
                unfinished_at_close=unfinished, queued_at_close=queued,
                drain_s=drained - t_close,
                tokens_per_s=toks / args.seconds,
                ttft_p50_ms=1000 * median(ttft),
                ttft_p95_ms=1000 * percentile(ttft, 95),
                itl_p50_ms=1000 * median(gaps),
                itl_p95_ms=1000 * percentile(gaps, 95),
                gaps_over_2x_p50_pct=share_over_pct(gaps, 2.0),
                # the engine's own count of the same class, by iteration and
                # not by row (the driver's log line has it under this name):
                # it still tells where the median gap itself carries a unit
                # and the share above reads near nothing
                gaps_with_prefill_unit_pct=100.0 * units / max(steps, 1),
                decode_steps_per_s=steps / (drained - start),
                occupancy_pct=100.0 * (after["active_slot_steps"]
                                       - before["active_slot_steps"])
                / max(steps * engine.num_slots, 1),
                gen_lag_p95_ms=1000 * percentile(
                    [it.submitted - it.due for it in items], 95))),
                flush=True)
    finally:
        engine.stop()
    print(json.dumps(dict(memory_peak_bytes=harness.memory_peak_bytes())))
    return 0


if __name__ == "__main__":
    sys.exit(main())
