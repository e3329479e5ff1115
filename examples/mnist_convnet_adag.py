"""MNIST ConvNet with ADAG — the flagship / north-star config.

Mirrors the reference's distributed MNIST ConvNet run (reference:
``examples/mnist.ipynb`` + ``trainers.py :: ADAG``; SURVEY.md §3.1,
``BASELINE.json`` north-star).  On TPU the ADAG window-delta exchange executes
as an all-reduce mean over the ICI mesh instead of socket commits to a driver
parameter server.

Run:  python examples/mnist_convnet_adag.py [--workers 8] [--epochs 1]
(On a machine without 8 devices:
 XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu ...)
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # run without installing

import jax

from distkeras_tpu import (ADAG, MinMaxTransformer, OneHotTransformer,
                           ModelPredictor, LabelIndexTransformer,
                           AccuracyEvaluator)
from distkeras_tpu.data.datasets import load_mnist
from distkeras_tpu.models.zoo import mnist_convnet


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=16384)
    ap.add_argument("--test-rows", type=int, default=2048)
    ap.add_argument("--workers", type=int, default=None,
                    help="default: all visible devices")
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--window", type=int, default=12)
    args = ap.parse_args()

    train, test = load_mnist(n_train=args.rows, n_test=args.test_rows)
    for t in (MinMaxTransformer(o_min=0.0, o_max=255.0),
              OneHotTransformer(10)):
        train, test = t.transform(train), t.transform(test)

    workers = args.workers or len(jax.devices())
    trainer = ADAG(mnist_convnet(), num_workers=workers,
                   batch_size=args.batch_size, num_epoch=args.epochs,
                   communication_window=args.window,
                   label_col="label_encoded", worker_optimizer="adam",
                   learning_rate=1e-3)
    fitted = trainer.train(train, shuffle=True)
    secs = trainer.get_training_time()
    examples = sum(e["examples"] for e in trainer.metrics)
    print(f"workers: {workers}  time: {secs:.2f}s  "
          f"throughput: {examples / secs:,.0f} examples/s "
          f"({examples / secs / workers:,.0f} /s/chip)")

    predicted = ModelPredictor(fitted).predict(test)
    predicted = LabelIndexTransformer().transform(predicted)
    print(f"test accuracy: {AccuracyEvaluator().evaluate(predicted):.4f}")


if __name__ == "__main__":
    main()
