"""MNIST MLP with SingleTrainer — the baseline config.

Mirrors the reference's single-worker MNIST path (reference:
``examples/mnist.ipynb`` MLP variant + ``trainers.py :: SingleTrainer``;
SURVEY.md §3.2): load MNIST, MinMax-scale features, one-hot labels, train one
model on one chip, evaluate accuracy.

Run:  python examples/mnist_mlp_single.py [--rows 8192] [--epochs 2]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # run without installing

from distkeras_tpu import (SingleTrainer, MinMaxTransformer, OneHotTransformer,
                           ModelPredictor, LabelIndexTransformer,
                           AccuracyEvaluator)
from distkeras_tpu.data.datasets import load_mnist
from distkeras_tpu.models.zoo import mnist_mlp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--test-rows", type=int, default=2048)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=64)
    args = ap.parse_args()

    train, test = load_mnist(n_train=args.rows, n_test=args.test_rows)
    pipeline = [MinMaxTransformer(o_min=0.0, o_max=255.0),
                OneHotTransformer(10)]
    for t in pipeline:
        train, test = t.transform(train), t.transform(test)

    trainer = SingleTrainer(mnist_mlp(), batch_size=args.batch_size,
                            num_epoch=args.epochs, label_col="label_encoded",
                            worker_optimizer="adam", learning_rate=1e-3)
    fitted = trainer.train(train, shuffle=True)
    print(f"training time: {trainer.get_training_time():.2f}s  "
          f"final loss: {trainer.get_history()[-1]:.4f}")

    predicted = ModelPredictor(fitted).predict(test)
    predicted = LabelIndexTransformer().transform(predicted)
    acc = AccuracyEvaluator().evaluate(predicted)
    print(f"test accuracy: {acc:.4f}")


if __name__ == "__main__":
    main()
