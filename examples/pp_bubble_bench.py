"""Pipeline-parallel bubble measurement: throughput vs microbatch count.

GPipe's fill/drain bubble wastes ``(n-1)/(M+n-1)`` of each stage's ticks
(``PipelineTransformerLM.bubble_fraction``); raising the microbatch count M
amortizes it at the cost of smaller per-tick matmuls.  This script measures
steady-state step time across M and prints the measured efficiency next to
the analytic bound, so the trade is a number rather than a slogan.

Run (8-way simulated mesh: dp=2 × pp=4):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/pp_bubble_bench.py
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # run without installing


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from distkeras_tpu.parallel.pp_transformer import PipelineTransformerLM

    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--pp", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--batch", type=int, default=32,
                    help="global batch (constant across the sweep)")
    ap.add_argument("--microbatches", default="1,2,4,8")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--schedule", choices=["gpipe", "1f1b"],
                    default="gpipe",
                    help="gpipe = autodiff backward after all forwards; "
                         "1f1b = hand-scheduled one-forward-one-backward "
                         "(O(stages) activation buffer)")
    args = ap.parse_args()

    n = args.dp * args.pp
    devs = jax.devices()
    if len(devs) < n:
        raise SystemExit(
            f"need {n} devices (dp*pp), have {len(devs)}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            "JAX_PLATFORMS=cpu")
    mesh = Mesh(np.array(devs[:n]).reshape(args.dp, args.pp),
                ("data", "stage"))
    cdt = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16

    rng = np.random.default_rng(0)
    toks = rng.integers(0, args.vocab,
                        (args.batch, args.seq_len)).astype(np.int32)
    labels = (toks + 1) % args.vocab

    print(f"mesh dp={args.dp} pp={args.pp}  batch={args.batch}  "
          f"layers={args.layers}  d={args.d_model}  S={args.seq_len}")
    rows = []
    for m in (int(v) for v in args.microbatches.split(",")):
        lm = PipelineTransformerLM(
            vocab_size=args.vocab, seq_len=args.seq_len,
            d_model=args.d_model, num_heads=2, num_layers=args.layers,
            mlp_dim=4 * args.d_model, mesh=mesh, num_microbatches=m,
            compute_dtype=cdt, schedule=args.schedule)
        params = lm.init(jax.random.PRNGKey(0))
        opt_state, step = lm.compile_train_step(optax.adam(1e-3), params)
        toks_d = jax.device_put(toks, lm.batch_sharding())
        labels_d = jax.device_put(labels, lm.batch_sharding())
        params, opt_state, loss = step(params, opt_state, toks_d,
                                       labels_d)  # compile + warm
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            params, opt_state, loss = step(params, opt_state, toks_d,
                                           labels_d)
        jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / args.steps
        tput = args.batch * args.seq_len / dt
        rows.append((m, dt, tput, lm.bubble_fraction()))
        print(f"M={m:2d}  step {dt * 1e3:8.1f} ms  {tput:12,.0f} tokens/s  "
              f"analytic bubble {lm.bubble_fraction():.0%}")

    base = rows[0]
    print("\nspeedup vs M=1 (bubble-only ideal = (1-bubble_M)/(1-bubble_1),"
          " assuming per-tick compute scales perfectly with 1/M):")
    for m, dt, tput, bub in rows[1:]:
        ideal = (1 - bub) / (1 - base[3])
        print(f"M={m:2d}  measured {base[1] / dt:4.2f}x   "
              f"bubble-only ideal {ideal:4.2f}x "
              f"(per-tick matmuls shrink {m}x vs M=1, so small shapes "
              "can offset the bubble win)")


if __name__ == "__main__":
    main()
