"""Extended benchmark suite — one JSON line per benchmark.

``bench.py`` at the repo root stays the driver's single-line north-star
(ADAG MNIST ConvNet examples/sec/chip); this suite covers the rest of the
framework surface for regression tracking:

  - single-chip SingleTrainer throughput (MNIST MLP)
  - transformer LM train-step throughput (tokens/sec)
  - attention: XLA reference vs Pallas flash kernel (ms/call)
  - wire codec: native vs Python (MB/s)

Run:  python scripts/bench_suite.py [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(
        globals().get("__file__", "scripts/x"))), ".."))


def emit(metric, value, unit, **extra):
    line = {"metric": metric, "value": round(float(value), 2), "unit": unit}
    line.update(extra)
    print(json.dumps(line), flush=True)


def bench_single_trainer(rows):
    """Steady-state single-chip epoch throughput: one compiled epoch runner
    (the engine inside SingleTrainer), warm it, then time repeat epochs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distkeras_tpu.core.train import (batch_epoch_data, init_state,
                                          make_epoch_runner)
    from distkeras_tpu.data.datasets import load_mnist
    from distkeras_tpu.models.zoo import mnist_mlp

    batch = 128
    train, _ = load_mnist(n_train=rows)
    x = np.asarray(train["features"], np.float32) / 255.0
    y = np.eye(10, dtype=np.float32)[np.asarray(train["label"])]
    xb, yb, mb, nb = batch_epoch_data(x, y, batch)
    xb, yb, mb = jnp.asarray(xb), jnp.asarray(yb), jnp.asarray(mb)

    model = mnist_mlp()
    state, tx = init_state(model, jax.random.PRNGKey(0), (784,), "adam",
                           1e-3)
    runner = make_epoch_runner(model, "categorical_crossentropy", tx)
    rng = jax.random.PRNGKey(1)
    state, losses = runner(state, xb, yb, mb, rng)  # compile
    jax.block_until_ready(losses)
    reps = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 2.0 and reps < 50:
        state, losses = runner(state, xb, yb, mb, rng)
        jax.block_until_ready(losses)
        reps += 1
    dt = time.perf_counter() - t0
    emit("single_trainer_mnist_mlp", reps * nb * batch / dt, "examples/sec")


def bench_transformer_step(steps):
    import jax
    import numpy as np
    import optax
    import jax.numpy as jnp
    from distkeras_tpu.models.zoo import transformer_lm
    from distkeras_tpu.core.train import init_state, make_train_step

    vocab, seq, batch = 256, 128, 8
    model = transformer_lm(vocab_size=vocab, seq_len=seq, d_model=128,
                           num_heads=4, num_layers=2, mlp_dim=512)
    state, tx = init_state(model, jax.random.PRNGKey(0), (seq,), "adam",
                           1e-3)
    step = jax.jit(make_train_step(
        model, "sparse_categorical_crossentropy_from_logits", tx))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, vocab, (batch, seq)), jnp.int32)
    y = jnp.asarray((np.asarray(x) + 1) % vocab, jnp.int32)
    key = jax.random.PRNGKey(1)
    state, _ = step(state, (x, y), key)  # compile
    jax.block_until_ready(state.params)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, loss = step(state, (x, y), key)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    emit("transformer_lm_train", steps * batch * seq / dt, "tokens/sec")


def bench_attention(iters):
    import jax
    import jax.numpy as jnp
    from distkeras_tpu.ops.attention import dot_product_attention
    from distkeras_tpu.ops.flash_attention import flash_attention

    b, s, h, d = 4, 1024, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
               for kk in ks)

    xla = jax.jit(lambda q, k, v: dot_product_attention(q, k, v, causal=True))
    out = xla(q, k, v)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = xla(q, k, v)
    jax.block_until_ready(out)
    emit("attention_xla_causal_1k", (time.perf_counter() - t0) / iters * 1e3,
         "ms/call")

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        fl = jax.jit(lambda q, k, v: flash_attention(q, k, v, True))
        out = fl(q, k, v)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fl(q, k, v)
        jax.block_until_ready(out)
        emit("attention_flash_causal_1k",
             (time.perf_counter() - t0) / iters * 1e3, "ms/call")


def bench_codec(reps):
    import numpy as np
    from distkeras_tpu import networking

    msg = {"delta": [np.random.default_rng(0).standard_normal(
        (500, 500)).astype(np.float32) for _ in range(4)], "clock": 1}
    blob = networking.encode_message(msg)
    mb = len(blob) / 1e6

    impls = [("python", None)]
    if networking._native is not None:
        impls.insert(0, ("native", networking._native))
    saved = networking._native
    for label, impl in impls:
        networking._native = impl
        t0 = time.perf_counter()
        for _ in range(reps):
            blob = networking.encode_message(msg)
        t1 = time.perf_counter()
        for _ in range(reps):
            networking.decode_message(blob)
        t2 = time.perf_counter()
        emit(f"wire_codec_{label}_encode", mb * reps / (t1 - t0), "MB/s")
        emit(f"wire_codec_{label}_decode", mb * reps / (t2 - t1), "MB/s")
    networking._native = saved


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    q = args.quick

    bench_codec(50 if q else 200)
    bench_single_trainer(8192 if q else 30000)
    bench_transformer_step(5 if q else 30)
    bench_attention(3 if q else 20)


if __name__ == "__main__":
    main()
