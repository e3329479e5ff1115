"""Measure the reference-proxy CPU baseline for the north-star benchmark.

The dist-keras reference publishes no throughput numbers (BASELINE.md), so
the ≥8× north-star multiple is measured against a proxy of its hot loop
(reference: ``distkeras/workers.py :: SequentialWorker.train`` — per-minibatch
``train_on_batch`` with Python dispatch on a 2016-era CPU Spark executor):
one CPU process, float32, a jitted single train step invoked per batch from
Python.  This is *generous* to the reference — no pickle serialization, no
socket PS round-trips, no Spark overhead, and XLA-compiled kernels instead of
2016 TF — so beating 8× against it is strictly harder than against the real
thing.

Writes ``BASELINE_MEASURED.json`` at the repo root; ``bench.py`` reads it.
Run on the target CPU host:  python scripts/measure_cpu_baseline.py
"""

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

from distkeras_tpu.core.train import init_state, make_train_step
from distkeras_tpu.data.datasets import load_mnist
from distkeras_tpu.models.zoo import mnist_convnet

BATCH = 128


def main():
    model = mnist_convnet(compute_dtype="float32")  # 2016 CPUs: no bf16
    train, _ = load_mnist(n_train=20_000)
    x = np.asarray(train["features"], np.float32) / 255.0
    y = np.eye(10, dtype=np.float32)[np.asarray(train["label"])]

    state, tx = init_state(model, jax.random.PRNGKey(0), (784,), "adam")
    step = jax.jit(make_train_step(model, "categorical_crossentropy", tx))
    rng = jax.random.PRNGKey(1)

    nb = len(x) // BATCH
    xb = x[:nb * BATCH].reshape(nb, BATCH, 784)
    yb = y[:nb * BATCH].reshape(nb, BATCH, 10)

    # warmup / compile
    state, _ = step(state, (xb[0], yb[0]), rng)
    jax.block_until_ready(state.params)

    t0 = time.perf_counter()
    steps = 0
    while time.perf_counter() - t0 < 20.0:
        for i in range(nb):
            rng, sub = jax.random.split(rng)
            state, _ = step(state, (xb[i], yb[i]), sub)
            steps += 1
            if steps % 20 == 0 and time.perf_counter() - t0 > 20.0:
                break
    jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0
    eps = steps * BATCH / dt

    out = {
        "metric": "examples_per_sec_cpu_proxy_mnist_convnet",
        "value": round(eps, 1),
        "unit": "examples/sec (1 CPU process)",
        "batch_size": BATCH,
        "steps_timed": steps,
        "seconds": round(dt, 2),
        "description": (
            "Reference-proxy baseline: per-minibatch Python-dispatched "
            "jitted train step, float32, one CPU process (emulates "
            "distkeras SequentialWorker train_on_batch hot loop, "
            "generously — no Spark/pickle/socket overhead)."),
    }
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BASELINE_MEASURED.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
