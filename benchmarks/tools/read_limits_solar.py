"""Read a ``serve_hybrid`` cell on many seeds in ONE process: the window's
end-to-end numbers (what the cell's spreads are read from) and the numbers
that ``correct`` compares, with the int8 control beside them (what the cell's
limits are set from).  ``read_limits.py`` keeps one engine and gives it each
seed's weights; here the engine (6.6 GB of weights, 2.7 GB of state) and the
reference's own weights with one layer cast up do not fit the chip together,
so each seed goes through the driver's own ``serve_window`` (its engine built,
warmed, served for the lead-in and the window, drained, freed) and then, with
``--sample`` above 0, the driver's ``score`` over that many finished requests:
the reference as it is and with int8 matmuls, the control's first tokens
scored in the program's place.  ``setup_s`` is the seed's own build and
warm-up (programs come from the process's cache after the first seed).  One
line a seed.  Not part of any measurement.

    python benchmarks/tools/read_limits_solar.py --workload \\
        serve-reason-solar2 --seeds 101,102,... [--seconds 40] [--sample 64]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--sample", type=int, default=None,
                    help="requests the reference scores (0: none; default: "
                         "the cell's own)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    seeds = [int(x) for x in args.seeds.split(",") if x]
    from benchmarks.lib import harness, manifest as mf
    _, ctx0, _ = harness.open_run(args.workload, seeds[0], args.seconds,
                                  rehearse=args.rehearse)
    drv = mf.load_driver(ctx0.traffic["kind"])
    for seed in seeds:
        ctx = dataclasses.replace(ctx0, seed=seed,
                                  t_start=time.perf_counter())
        w = drv.serve_window(ctx)
        line = dict(seed=seed, attempted=w.attempted, failed=w.failed,
                    precision_below_stated=w.below_stated,
                    memory_peak_bytes=w.memory_peak_bytes, **w.end_to_end)
        served = w.served if args.sample is None else w.served[:args.sample]
        if served:
            t1 = time.perf_counter()
            line.update(sample=len(served),
                        tokens=int(sum(len(t) for _, t in served)),
                        **drv.score(ctx, served))
            line["reference_s"] = time.perf_counter() - t1
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
