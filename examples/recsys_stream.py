"""Streaming recommender: online learning on a drifting synthetic
click-stream over a large Embedding table (docs/host_ps.md, "Streaming +
row-sparse embeddings").

The canonical production parameter-server workload: an unbounded stream of
(user-item) click events feeds a large embedding table where each batch
touches only a few rows.  Training runs ONLINE under DOWNPOUR/ADAG with
elastic workers — the stream is re-leased a sliding horizon at a time
through the exactly-once lease ledger — and embedding deltas commit as
EXACT row-sparse blocks (``row_sparse=True``), so commit bytes scale with
the rows a window touched, not the table size.

Mid-stream the world DRIFTS: a fraction of the items re-draw their
preference vectors.  The per-horizon accuracy curve printed at the end is
the "accuracy tracks drift" observable — it dips at the drift point and
recovers online, no restart, no re-fit.

Run:  python examples/recsys_stream.py [--vocab 50000] [--workers 2]
      [--chaos-kill N]   # kill worker 0 at its N-th commit (zero loss)
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # run without installing

import numpy as np

from distkeras_tpu import ADAG, DOWNPOUR, Sequential
from distkeras_tpu.core.layers import Dense, Embedding, Flatten
from distkeras_tpu.streaming import StreamSource


def make_stream(vocab, classes, chunks, rows, drift_at, drift_frac, seed):
    """A drifting click-stream: item → preferred class, redrawn for a
    ``drift_frac`` fraction of items at chunk ``drift_at``.  Yields the
    mapping in force alongside nothing — the trainer only sees (x, y)."""
    rng = np.random.default_rng(seed)
    mapping = rng.integers(0, classes, vocab)
    drifted = mapping.copy()
    flip = rng.permutation(vocab)[: int(drift_frac * vocab)]
    drifted[flip] = (drifted[flip] + rng.integers(1, classes, len(flip))) \
        % classes
    # zipf-flavoured popularity: a few hot items dominate, the long tail
    # trickles — the access pattern that makes row sparsity pay
    pop = 1.0 / np.arange(1, vocab + 1) ** 0.8
    pop /= pop.sum()

    def gen():
        for i in range(chunks):
            m = drifted if i >= drift_at else mapping
            items = rng.choice(vocab, size=rows, p=pop).astype(
                np.int32).reshape(-1, 1)
            yield items, np.eye(classes, dtype=np.float32)[m[items[:, 0]]]

    return gen(), mapping, drifted


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=50000)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--classes", type=int, default=4)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--window", type=int, default=4)
    ap.add_argument("--horizon-windows", type=int, default=None,
                    help="windows re-leased per horizon (default 8/worker)")
    ap.add_argument("--chunks", type=int, default=96,
                    help="stream length in 256-row chunks")
    ap.add_argument("--drift-at", type=int, default=48,
                    help="chunk index where item preferences drift")
    ap.add_argument("--drift-frac", type=float, default=0.5)
    ap.add_argument("--algorithm", default="downpour",
                    choices=["downpour", "adag"])
    ap.add_argument("--ps-shards", type=int, default=1)
    ap.add_argument("--lr", type=float, default=0.5)
    ap.add_argument("--dense", action="store_true",
                    help="disable row-sparse embedding commits (byte "
                         "comparison baseline)")
    ap.add_argument("--chaos-kill", type=int, default=None, metavar="N",
                    help="inject worker 0 exiting at its N-th commit — the "
                         "horizon still completes exactly once (zero loss)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    gen, mapping, drifted = make_stream(
        args.vocab, args.classes, args.chunks, 256, args.drift_at,
        args.drift_frac, args.seed)

    model = Sequential([Embedding(args.vocab, args.dim), Flatten(),
                        Dense(64, activation="relu"),
                        Dense(args.classes, activation="softmax")],
                       input_shape=(1,), compute_dtype="float32")

    cls = {"downpour": DOWNPOUR, "adag": ADAG}[args.algorithm]
    trainer = cls(
        model, num_workers=args.workers, batch_size=args.batch_size,
        num_epoch=1, communication_window=args.window,
        learning_rate=args.lr, execution="host_ps", stream=True,
        horizon_windows=args.horizon_windows, ps_shards=args.ps_shards,
        row_sparse=not args.dense, seed=args.seed,
        fault_injection=({0: ("exit", args.chaos_kill)}
                         if args.chaos_kill else None))

    # evaluate on POPULARITY-WEIGHTED traffic (what the system actually
    # serves) — the zipf tail's never-seen items are unlearnable by
    # construction and would just flatten the curve
    eval_rng = np.random.default_rng(args.seed + 99)
    pop = 1.0 / np.arange(1, args.vocab + 1) ** 0.8
    pop /= pop.sum()
    eval_items = eval_rng.choice(args.vocab, size=4096, p=pop).astype(
        np.int32).reshape(-1, 1)
    drift_row = args.drift_at * 256
    horizon_rows = ((args.horizon_windows or 8 * args.workers)
                    * args.window * args.batch_size)
    curve = []

    def on_horizon(h, fitted):
        live = (drifted if (h + 1) * horizon_rows > drift_row
                else mapping)
        pred = fitted.predict(eval_items, batch_size=4096).argmax(-1)
        acc = float((pred == live[eval_items[:, 0]]).mean())
        curve.append(acc)
        print(f"  horizon {h:3d}: accuracy vs live mapping = {acc:.3f}")

    trainer.on_horizon = on_horizon
    print(f"[recsys_stream] vocab={args.vocab} dim={args.dim} "
          f"workers={args.workers} row_sparse={not args.dense} "
          f"drift at row {drift_row}")
    fitted = trainer.train(StreamSource(generator=gen))

    ss = trainer.stream_stats
    print(f"\n[recsys_stream] {ss['horizons']} horizons, {ss['rows']} rows, "
          f"{ss['examples_per_sec']} examples/sec")
    if trainer.elastic_stats.get("respawns"):
        print(f"[recsys_stream] worker respawns: "
              f"{trainer.elastic_stats['respawns']} "
              f"(failed: {trainer.failed_workers}) — every horizon still "
              "completed exactly once")
    final = float((fitted.predict(eval_items, batch_size=4096).argmax(-1)
                   == drifted[eval_items[:, 0]]).mean())
    print(f"[recsys_stream] final accuracy vs drifted mapping: {final:.3f}")
    print("[recsys_stream] accuracy-tracks-drift curve:",
          " ".join(f"{a:.2f}" for a in curve))


if __name__ == "__main__":
    main()
