"""Read the numbers that ``correct`` compares, on many seeds, in ONE process:
what a cell's limits are set from (steps 4 and 5 of "How correct is decided").
Set-up is long, so the program is built once and given each seed's weights and
inputs in turn; the control (the lower precision the cell's files name) is read
the same way.  Prints one line a seed.  Not part of any measurement.

    python benchmarks/tools/read_limits.py --workload train-adag-gpt2s \\
        --seeds 101,102,...  --control-seeds 201,202,203 --control int8
    python benchmarks/tools/read_limits.py --workload serve-chat-gpt2m \\
        --seeds ... --control-seeds ... --control int8_weights --seconds 12

A control is named in the cell's files: one with an ``engine`` block (the
configuration's ``controls``) is a lower-precision path of the program itself,
switched on by laying that block over the deployment; one with a ``matmul``
(the job or traffic file's ``controls``) puts the reference computed with that
matmul in the program's place.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def ints(text):
    return [int(x) for x in text.split(",") if x]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, default=[])
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--control", default=None)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    from benchmarks.lib import harness, manifest as mf
    _, ctx0, _ = harness.open_run(
        args.workload, (args.seeds + args.control_seeds + [0])[0],
        args.seconds, rehearse=args.rehearse)
    traffic = ctx0.traffic
    drv = mf.load_driver(traffic["kind"])
    reader = {"train_adag": read_train, "serve_engine": read_serve}[
        traffic["kind"]]
    reader(args, ctx0, drv)
    return 0


def show(seed, control, compared):
    print(json.dumps(dict(seed=seed, control=control,
                          **{c.name: c.value for c in compared})),
          flush=True)


def read_train(args, ctx0, drv):
    """One trainer, trained once (so that its engine and compiled epoch
    program exist); then, a seed at a time, its initial weights and the data
    are replaced and the driver's own check is run."""
    import jax
    import numpy as np
    from benchmarks.lib import program
    from benchmarks.lib.weights import fold_seed
    from distkeras_tpu import Dataset
    job = ctx0.traffic
    batch, seq = int(job["trainer"]["batch_size"]), int(job["seq_len"])
    rows = int(job["steps_per_epoch"]) * batch * ctx0.chips

    def data(seed):
        return drv.corpus(fold_seed(seed), rows, seq, int(job["token_range"]))

    trainer = drv.build_trainer(ctx0, int(job["setup_epochs"]))
    toks, labels = data(ctx0.seed)
    trainer.train(Dataset({"features": toks, "label": labels}),
                  shuffle=bool(job["shuffle"]))
    trainer._state = None
    for seed in args.seeds:
        ctx = dataclasses.replace(ctx0, seed=seed)
        params = program.program_params(ctx.cfg, seed)
        trainer._initial_weights = [
            np.asarray(w) for w in jax.tree_util.tree_leaves(params)]
        del params
        trainer.seed = fold_seed(seed)
        toks, labels = data(seed)
        show(seed, None, drv.check(ctx, trainer, toks, labels))
    kind = job.get("controls", {}).get(args.control or "", {}).get("matmul")
    for seed in args.control_seeds:
        ctx = dataclasses.replace(ctx0, seed=seed)
        toks, labels = data(seed)
        show(seed, args.control,
             drv.check(ctx, None, toks, labels, in_place=kind))


def read_serve(args, ctx0, drv):
    """One engine for the sound seeds and one for the control's; each seed's
    weights are put in the engine's place between windows (the programs take
    them as an argument), then a short window at the cell's own load is
    served and the driver's own check is run over its sample."""
    import jax
    import numpy as np
    from benchmarks.lib import program
    from benchmarks.lib.manifest import deep_merge
    from benchmarks.lib.traffic import generate
    traffic, cfg = ctx0.traffic, ctx0.cfg
    path = cfg.get("controls", {}).get(args.control or "", {}).get("engine")
    low = traffic.get("controls", {}).get(args.control or "", {}).get(
        "matmul") == "int8"
    for control, seeds in ((None, args.seeds),
                           (args.control, args.control_seeds)):
        if not seeds and not (control and path):
            continue
        laid = cfg
        if control and path:    # the program's own lower-precision path
            laid = deep_merge(cfg, {"deployment": {"engine": path}})
        engine = drv.build_engine(dataclasses.replace(ctx0, cfg=laid))
        below = drv.precision_below_stated(engine, cfg)
        print(json.dumps(dict(control=control, engine=laid["deployment"][
            "engine"], precision_below_stated=below)), flush=True)
        if not seeds:           # the types held, and nothing served
            del engine
            continue
        engine.warmup()
        engine.start()
        try:
            for seed in seeds:
                ctx = dataclasses.replace(ctx0, seed=seed)
                fresh = program.program_params(cfg, seed)
                if control and path and path.get("quantize"):
                    # the engine keeps quantised weights
                    from distkeras_tpu.serving import _quantize_weights
                    fresh = jax.device_put(
                        _quantize_weights(fresh, path["quantize"]))
                engine.params = fresh
                reqs = generate(deep_merge(traffic, {"lead_in_s": 0}), seed,
                                args.seconds, int(cfg["vocab_size"]))
                start = time.perf_counter() + 0.05
                items = [drv.Tracked(r, start + r.due_s) for r in reqs]
                drv.offer_open(engine, items, start + args.seconds, {})
                drv.wait_all(items, float(traffic["drain_timeout_s"]))
                sample = drv.pick_sample(items, int(
                    traffic["correct"]["sample"]), seed)
                served = [(it.req.prompt,
                           np.asarray(it.handle.tokens, np.int32))
                          for it in sample]
                compared = drv.check(ctx, served, below,
                                     low_in_place=bool(control) and low)
                show(seed, control, compared)
                print(json.dumps(dict(
                    seed=seed, control=control, requests=len(items),
                    failed=sum(1 for it in items if not it.ok),
                    correct=all(c.ok for c in compared))), flush=True)
        finally:
            engine.stop()
        del engine
        gc.collect()


if __name__ == "__main__":
    sys.exit(main())
