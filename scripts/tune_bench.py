"""Sweep the north-star bench's knobs on the real chip and rank configs.

Runs ``bench.py`` in a subprocess per (batch, window) point — same
measurement path the driver uses — and prints one JSON line per point
plus a final ``best`` line.  This parent never imports jax: a chip
belongs to one process at a time, and each child needs it in turn.  Use
when hardware characteristics change (new chip generation, new host) to
re-pick the defaults; the
flagship *algorithm* (ADAG window-delta commits) is fixed, only
execution-shape knobs are swept.

Run:  python scripts/tune_bench.py [--batches 64,128,256,512]
                                   [--windows 6,12,24] [--rows 60000]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_point(batch: int, window: int, rows: int, timeout: float):
    env = dict(os.environ,
               DISTKERAS_BENCH_BATCH=str(batch),
               DISTKERAS_BENCH_WINDOW=str(window),
               DISTKERAS_BENCH_ROWS=str(rows))
    try:
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "bench.py")],
            capture_output=True, text=True, timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        return {"batch": batch, "window": window, "error": "timeout"}
    line = None
    for cand in reversed((out.stdout or "").strip().splitlines()):
        try:
            parsed = json.loads(cand)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict):  # a stray numeric line is not a result
            line = parsed
            break
    if line is None:
        tail = (out.stderr or "").strip().splitlines()
        tail = tail[-1] if tail else ""
        return {"batch": batch, "window": window,
                "error": f"no JSON (rc={out.returncode} {tail})"}
    line.update(batch=batch, window=window)
    return line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="64,128,256,512")
    ap.add_argument("--windows", default="6,12,24")
    ap.add_argument("--rows", type=int, default=60000)
    ap.add_argument("--timeout", type=float, default=600.0)
    args = ap.parse_args()

    results = []
    for batch in (int(b) for b in args.batches.split(",")):
        for window in (int(w) for w in args.windows.split(",")):
            r = run_point(batch, window, args.rows, args.timeout)
            print(json.dumps(r), flush=True)
            results.append(r)

    ok = [r for r in results if "error" not in r and "value" in r]
    if ok:
        best = max(ok, key=lambda r: r["value"])
        print(json.dumps({"best": {k: best[k] for k in
                                   ("batch", "window", "value", "mfu",
                                    "platform", "device_kind")
                                   if k in best}}))
    else:
        print(json.dumps({"best": None, "note": "no successful points"}))


if __name__ == "__main__":
    main()
