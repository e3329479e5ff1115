#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the machine it is started on and prints,
as the last line of its standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``.  Earlier lines hold medians, counts, each number ``correct``
compared beside its limit, and the paths of trace files.

Without a TPU holding as many chips as the cell asks for it exits 3 and prints
no result, unless ``--rehearse``: the cell end to end on the CPU at the
configuration file's ``tiny`` widths, counts only, the device named ``cpu`` and
no device metric.  The lower-precision controls of ``correct`` are read by
``tools/read_limits.py`` and held by ``tests/``, never by this command.
"""

from __future__ import annotations

import time

T_START, WALL_START = time.perf_counter(), time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from benchmarks.lib import harness, manifest as mf
    man, ctx, dev = harness.open_run(
        args.workload, args.seed, args.seconds, bool(args.trace),
        args.rehearse, T_START, WALL_START)
    cell, cfg, traffic = ctx.cell, ctx.cfg, ctx.traffic
    driver = mf.load_driver(traffic["kind"])
    result = driver.run(ctx)
    for c in result.compared:
        ctx.log(**c.line())

    device = dict(dev, memory_peak_bytes=result.memory_peak_bytes)
    out = dict(correct=result.correct, attempted=result.attempted,
               failed=result.failed)
    metrics = {}
    if not args.trace:
        for m in man.end_to_end(cell["name"]):
            if m["name"] in result.end_to_end:
                metrics[m["name"]] = dict(
                    value=result.end_to_end[m["name"]], unit=m["unit"])
    else:
        from benchmarks.lib import peaks, trace as trace_lib
        trace = None
        if result.trace_path and dev["platform"] == "tpu":
            trace = trace_lib.load(result.trace_path)
            busy, window = trace_lib.busy_and_window_s(trace)
            device.update(busy_s=busy, window_s=window)
            out["breakdown"] = dict(
                device_ops=trace_lib.top_device_ops(trace),
                idle_gaps=trace_lib.idle_gaps(trace))
            ctx.log(trace=trace_lib.find_xplane(result.trace_path))
        env = dict(cfg=cfg, traffic=traffic, chips=cell["chips"], device=dev,
                   peaks=(peaks.peaks_for(dev["kind"])
                          if dev["platform"] == "tpu" else None))
        for m in man.per_layer(cell["name"]):
            reader = mf.load_layer_metric(m["name"])
            value = reader.read(result.records, trace, env)
            if value is None or not math.isfinite(value):
                continue        # nothing to read: left out of the line
            metrics[m["name"]] = dict(value=value, unit=m["unit"])
    out["metrics"] = metrics
    out["device"] = device
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
