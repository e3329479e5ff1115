"""ATLAS Higgs end-to-end workflow — full pipeline + trainer comparison.

Script form of the reference's ``examples/workflow.ipynb`` (SURVEY.md §3.5):
read the tabular dataset, run the transformer pipeline, train the same model
with several distributed optimization algorithms (AEASGD, EAMSGD, ADAG,
DOWNPOUR, plus the SingleTrainer baseline), and report accuracy + wall-clock
for each — the reference notebook's algorithm-comparison table.

Run:  python examples/higgs_workflow.py [--workers 8] [--rows 65536]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # run without installing

import jax

from distkeras_tpu import (SingleTrainer, ADAG, DOWNPOUR, AEASGD, EAMSGD,
                           StandardScaleTransformer, OneHotTransformer,
                           ModelPredictor, LabelIndexTransformer,
                           AccuracyEvaluator, AUCEvaluator)
from distkeras_tpu.data.datasets import load_atlas_higgs
from distkeras_tpu.models.zoo import higgs_mlp


def evaluate(fitted, test):
    predicted = ModelPredictor(fitted).predict(test)
    # AUC from the class-probability column (the standard Higgs metric),
    # accuracy from the argmax index
    auc = AUCEvaluator().evaluate(predicted)
    predicted = LabelIndexTransformer().transform(predicted)
    return AccuracyEvaluator().evaluate(predicted), auc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=65536)
    ap.add_argument("--test-rows", type=int, default=8192)
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--batch-size", type=int, default=64)
    args = ap.parse_args()

    train, test = load_atlas_higgs(n_train=args.rows, n_test=args.test_rows)
    for t in (StandardScaleTransformer(), OneHotTransformer(2)):
        train, test = t.transform(train), t.transform(test)

    workers = args.workers or len(jax.devices())
    common = dict(batch_size=args.batch_size, num_epoch=args.epochs,
                  label_col="label_encoded", worker_optimizer="adam",
                  learning_rate=1e-3)
    dist = dict(common, num_workers=workers)

    trainers = [
        ("SingleTrainer", SingleTrainer(higgs_mlp(), **common)),
        ("ADAG", ADAG(higgs_mlp(), communication_window=12, **dist)),
        ("DOWNPOUR", DOWNPOUR(higgs_mlp(), communication_window=5, **dist)),
        ("AEASGD", AEASGD(higgs_mlp(), rho=5.0, communication_window=32,
                          **{k: v for k, v in dist.items()
                             if k != "learning_rate"})),
        ("EAMSGD", EAMSGD(higgs_mlp(), rho=5.0, momentum=0.9,
                          communication_window=32,
                          **{k: v for k, v in dist.items()
                             if k not in ("learning_rate",
                                          "worker_optimizer")})),
    ]

    print(f"{'algorithm':<14} {'accuracy':>9} {'auc':>7} {'time (s)':>9}")
    for name, trainer in trainers:
        fitted = trainer.train(train, shuffle=True)
        acc, auc = evaluate(fitted, test)
        print(f"{name:<14} {acc:>9.4f} {auc:>7.4f} "
              f"{trainer.get_training_time():>9.2f}")


if __name__ == "__main__":
    main()
