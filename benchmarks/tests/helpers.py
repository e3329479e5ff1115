"""Build a run's context for a cell or for a fixture cell that exists only in
the tests (its traffic file under ``tests/fixtures``): what ``run.py`` does,
without the look for a chip."""

import json
import os
import time

from benchmarks.lib import harness, manifest as mf

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def context(cell, traffic=None, seed=7, seconds=2.0, trace=False,
            tmp="/tmp"):
    man = mf.Manifest()
    if isinstance(cell, str):
        cell = man.cell(cell)
    cfg = mf.resolve_sizes(man.config(cell["config"]), True)
    if traffic is None:
        traffic = man.traffic(cell["traffic"])
    elif isinstance(traffic, str):
        with open(os.path.join(FIXTURES, traffic)) as f:
            traffic = json.load(f)
    traffic = mf.deep_merge(traffic, traffic.get("tiny", {}))
    return harness.RunContext(
        cell=cell, cfg=cfg, traffic=traffic, seed=seed, seconds=seconds,
        trace=trace, rehearse=True,
        t_start=time.perf_counter(), wall_start=time.time(), out_dir=str(tmp))


def run(ctx):
    return mf.load_driver(ctx.traffic["kind"]).run(ctx)
