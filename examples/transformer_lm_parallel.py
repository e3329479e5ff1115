"""Long-context transformer LM over a (data, seq, model) mesh.

The framework's beyond-the-reference flagship: a causal LM train step that
composes data parallelism, ring-attention sequence parallelism, Megatron
tensor parallelism, and one expert-parallel MoE layer inside a single
jitted shard_map program (``parallel/transformer.py``).

Run (8-way simulated mesh: dp=2 × sp=2 × tp=2):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/transformer_lm_parallel.py
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

    from distkeras_tpu.parallel.transformer import ParallelTransformerLM

    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--sp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--schedule", choices=["constant", "warmup_cosine"],
                    default="constant",
                    help="LR schedule (warmup 10%% of --steps, cosine to 0)")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient accumulation: average this many "
                         "mini-step gradients per optimizer update")
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO-1: shard optimizer moments over the data "
                         "axis (same update math, mu/nu HBM / dp)")
    ap.add_argument("--fsdp", action="store_true",
                    help="ZeRO-3/FSDP: params AND moments sharded over "
                         "the data axis at rest (supersedes --zero)")
    ap.add_argument("--fused-ce", action="store_true",
                    help="fused Pallas cross-entropy (TPU; XLA fallback "
                         "under the CPU mesh)")
    ap.add_argument("--sp-impl", choices=["ring", "ulysses"],
                    default="ring", help="sequence-parallel schedule")
    args = ap.parse_args()

    n = args.dp * args.sp * args.tp
    devs = jax.devices()
    if len(devs) < n:
        raise SystemExit(
            f"need {n} devices (dp*sp*tp), have {len(devs)}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            "JAX_PLATFORMS=cpu")
    mesh = Mesh(np.array(devs[:n]).reshape(args.dp, args.sp, args.tp),
                ("data", "seq", "model"))

    # ulysses reshards heads over the seq axis too, so give it tp*sp head
    # granularity (ring has no head-count requirement)
    heads = max(args.tp * (args.sp if args.sp_impl == "ulysses" else 1), 2)
    lm = ParallelTransformerLM(
        vocab_size=args.vocab, seq_len=args.seq_len, d_model=args.d_model,
        num_heads=heads, num_layers=args.layers,
        mlp_dim=4 * args.d_model, mesh=mesh,
        moe_layers=(args.layers - 1,), num_experts=args.tp,
        sp_impl=args.sp_impl, fused_ce=args.fused_ce,
        compute_dtype=jnp.float32 if jax.default_backend() == "cpu"
        else jnp.bfloat16)
    params = lm.init(jax.random.PRNGKey(0))
    # compile_train_step takes any optax transformation, so schedules and
    # accumulation compose with the parallel program unchanged (the same
    # get_schedule spelling the Trainer kwargs surface accepts)
    from distkeras_tpu.core.optimizers import get_schedule
    lr = get_schedule(None if args.schedule == "constant" else args.schedule,
                      args.lr, total_steps=max(args.steps // args.accum, 1))
    tx = optax.adam(lr)
    if args.accum > 1:
        tx = optax.MultiSteps(tx, args.accum).gradient_transformation()
    opt_state, step = lm.compile_train_step(tx, params, zero=args.zero,
                                            fsdp=args.fsdp)

    # task: predict the next token of a shifted stream
    rng = np.random.default_rng(0)
    batch = args.dp * args.tp * 2
    toks = rng.integers(0, args.vocab, (batch, args.seq_len)).astype(np.int32)
    labels = (toks + 1) % args.vocab
    sh = lm.batch_sharding()
    toks_d, labels_d = jax.device_put(toks, sh), jax.device_put(labels, sh)

    t0 = time.time()
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, toks_d, labels_d)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}")
    dt = time.time() - t0
    tokens = args.steps * batch * args.seq_len
    print(f"mesh dp={args.dp} sp={args.sp} tp={args.tp}  "
          f"{tokens / dt:,.0f} tokens/sec (incl. compile)")


if __name__ == "__main__":
    main()
