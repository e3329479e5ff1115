"""Online recommender deployment: train-while-serve under chaos
(docs/DEPLOY.md, "Online deployment").

The full online-ML process graph from ROADMAP item 5 as ONE running
system: a drifting click-stream trains a tiny next-item transformer
under DOWNPOUR on the elastic host-PS engine, the live parameter server
hot-reloads a :class:`ServingEngine` between decode steps
(``attach_ps``), served recommendations are scored against the live
world and fed BACK into the stream, and every seam is chaos-killed
mid-run:

 - a **worker** exits mid-horizon (``fault_injection``) — the lease
   ledger re-leases its rows exactly once, zero lost examples;
 - the **serving engine** is declared dead — the
   :class:`EngineSupervisor` swaps in a warmed clone through the
   deployment's atomic ``engine`` setter and :meth:`serve` resubmits
   the probe, zero lost requests;
 - **blue/green** swaps (three of them) warm generation *g+1* on the
   freshest center while *g* keeps serving, then cut over atomically —
   every response carries exactly one serve-generation tag.

The model is a recommender-as-1-step-LM: prompt ``[item]``, one greedy
decode step = the recommended next item.  Mid-stream half the items
re-draw their preference; the per-horizon SERVED accuracy curve (probes
answered by the live engine, not the trainer) dips at the drift and
recovers online — accuracy tracks drift on the served path, through
every kill and swap.

Run:  python examples/online_recsys.py [--chunks 8] [--drift-at 4]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # run without installing

import numpy as np

from distkeras_tpu import DOWNPOUR, OnlineDeployment
from distkeras_tpu.models.zoo import transformer_lm
from distkeras_tpu.serving import ServingEngine
from distkeras_tpu.streaming import StreamSource


def make_stream(vocab, seq_len, chunks, rows, drift_at, seed):
    """A drifting next-item stream: token → preferred next token,
    redrawn for half the vocabulary at chunk ``drift_at``."""
    rng = np.random.default_rng(seed)
    mapping = rng.permutation(vocab).astype(np.int32)
    drifted = mapping.copy()
    flip = rng.permutation(vocab)[: vocab // 2]
    drifted[flip] = np.roll(mapping[flip], 1)

    def gen():
        for i in range(chunks):
            m = drifted if i >= drift_at else mapping
            x = rng.integers(0, vocab, (rows, seq_len)).astype(np.int32)
            yield x, m[x]

    return gen(), mapping, drifted


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=8)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--window", type=int, default=2)
    ap.add_argument("--horizon-windows", type=int, default=4)
    ap.add_argument("--chunks", type=int, default=8,
                    help="stream length in --rows chunks")
    ap.add_argument("--rows", type=int, default=128)
    ap.add_argument("--drift-at", type=int, default=4,
                    help="chunk index where item preferences drift")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--kill-worker-at", type=int, default=2, metavar="N",
                    help="worker 1 exits at its N+1-th commit (0 disables)")
    ap.add_argument("--kill-engine-at", type=int, default=2, metavar="H",
                    help="declare the engine dead after horizon H "
                         "(-1 disables)")
    ap.add_argument("--swap-horizons", type=int, nargs="*",
                    default=[3, 5, 7],
                    help="horizons after which to blue/green swap")
    ap.add_argument("--feed-horizons", type=int, default=10,
                    help="feed served traffic back for this many horizons")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    V, L = args.vocab, args.seq_len
    gen, mapping, drifted = make_stream(V, L, args.chunks, args.rows,
                                        args.drift_at, args.seed)

    def make_model():
        return transformer_lm(vocab_size=V, seq_len=L + 2, d_model=32,
                              num_heads=4, num_layers=1, mlp_dim=64,
                              compute_dtype="float32")

    trainer = DOWNPOUR(
        make_model(), num_workers=args.workers,
        batch_size=args.batch_size, num_epoch=1,
        communication_window=args.window, execution="host_ps",
        loss="sparse_categorical_crossentropy_from_logits",
        worker_optimizer="adam", learning_rate=args.lr, stream=True,
        horizon_windows=args.horizon_windows, seed=args.seed,
        max_horizons=args.feed_horizons + 6,  # backstop: feedback ends first
        fault_injection=({1: ("exit", args.kill_worker_at)}
                         if args.kill_worker_at else None))

    # the engine starts from an INDEPENDENT init — horizon-0 accuracy is
    # chance until the first hot reload pulls the live center
    import jax
    serve_model = make_model()
    params = serve_model.init(jax.random.PRNGKey(args.seed + 1), (L + 2,))
    engine = ServingEngine((serve_model, params), num_slots=4,
                           max_len=4)

    dep = OnlineDeployment(trainer, StreamSource(generator=gen), engine,
                           reload_every=1, supervise=True)

    drift_row = args.drift_at * args.rows
    horizon_rows = (args.horizon_windows * args.window * args.batch_size
                    * args.workers)
    probe = np.arange(V, dtype=np.int32).reshape(-1, 1)
    curve, gen_tags = [], []

    def on_horizon(h, fitted):
        live = (drifted if (h + 1) * horizon_rows > drift_row
                else mapping)
        if h == args.kill_engine_at:
            print(f"  horizon {h:2d}: CHAOS — engine declared dead; "
                  "supervisor swapping a warmed clone in")
            dep.kill_engine()
        if h - 1 in args.swap_horizons:
            rec = dep.blue_green_swap()
            print(f"  horizon {h:2d}: blue/green swap -> generation "
                  f"{rec['generation']} (pulled={rec['pulled']}, "
                  f"drained_clean={rec['old_drained_clean']})")
        rows, gens = dep.serve(list(probe), num_steps=1,
                               retry_wait_s=15.0)
        gen_tags.extend(gens)
        pred = np.array([r[1] for r in rows])
        acc = float(np.mean(pred == live[probe[:, 0]]))
        curve.append(acc)
        print(f"  horizon {h:2d}: served accuracy vs live mapping = "
              f"{acc:.3f}  (serve generation {gens[0]})")
        if h < args.feed_horizons:
            fx = np.repeat(probe, L, axis=1)  # served traffic, labeled by
            dep.feed(fx, live[fx])            # the observed (live) world

    trainer.on_horizon = on_horizon
    print(f"[online_recsys] vocab={V} workers={args.workers} "
          f"drift at row {drift_row}; chaos: worker exit"
          f"{' on' if args.kill_worker_at else ' off'}, engine kill at "
          f"horizon {args.kill_engine_at}, blue/green at "
          f"{args.swap_horizons}")
    dep.start()
    dep.join(timeout=600)
    dep.stop()

    s = dep.stats()
    ss = s["stream_stats"]
    print(f"\n[online_recsys] {ss['horizons']} horizons, {ss['rows']} rows "
          f"({s['rows_fed_back']} fed back from serving), "
          f"{ss['examples_per_sec']} examples/sec")
    print(f"[online_recsys] freshness p50={s['freshness_p50_s']:.3f}s "
          f"p99={s['freshness_p99_s']:.3f}s over {s['freshness_rows']} "
          f"rows; {s['engine_reloads']} hot reloads, center generation "
          f"{s['engine_center_generation']}")
    print(f"[online_recsys] serve generation {s['generation']} after "
          f"{len(s['swaps'])} swaps "
          f"({sum(1 for r in s['swaps'] if r.get('blue_green'))} "
          f"blue/green); engine recoveries: "
          f"{[r['reason'] for r in s.get('engine_recoveries', [])]}")
    print(f"[online_recsys] worker respawns: "
          f"{s['elastic_stats'].get('respawns', 0)} — every horizon "
          "still completed exactly once")
    print("[online_recsys] served accuracy-tracks-drift curve:",
          " ".join(f"{a:.2f}" for a in curve))

    # -- the acceptance assertions (docs/DEPLOY.md failure matrix) --------
    assert ss["rows"] == args.chunks * args.rows + s["rows_fed_back"], \
        "lost examples: not every base+feedback row trained"
    assert all(g is not None for g in gen_tags), \
        "a served response lost its generation attribution"
    assert [r["generation"] for r in s["swaps"]] == \
        list(range(1, len(s["swaps"]) + 1)), "swap generations not atomic"
    assert sum(1 for r in s["swaps"] if r.get("blue_green")) >= 3
    if args.kill_engine_at >= 0:
        assert any(r["restarted"] for r in s.get("engine_recoveries", [])), \
            "engine kill was not recovered by the supervisor"
    if args.kill_worker_at:
        assert s["elastic_stats"].get("respawns", 0) >= 1
    assert s["freshness_p50_s"] is not None
    assert s["engine_reloads"] > 0
    assert curve[-1] >= 0.75, f"served accuracy did not track drift: {curve}"
    print("[online_recsys] OK — all acceptance assertions hold")


if __name__ == "__main__":
    main()
