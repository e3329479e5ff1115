"""Convert raw MNIST/Fashion-MNIST IDX files to the npz layout the data
loaders consume — so a populated ``DISTKERAS_TPU_DATA`` upgrades every
real-data hook (``data/datasets.py :: load_mnist``, the accuracy-parity
gate) with ZERO code changes.

This sandbox has no egress, so the script only documents + performs the
local half: download the four files elsewhere (classic Yann LeCun MNIST
distribution or a mirror), drop them in a directory, run::

    python scripts/ingest_mnist_idx.py /path/with/idx/files \
        --out "$DISTKERAS_TPU_DATA"   # default: ~/.distkeras_tpu/data

Accepts gzipped (``.gz``) or raw files with either classic or
``-idx3-ubyte``-suffixed names.  Writes ``mnist.npz`` with the keys
``x_train (60000, 28, 28) uint8``, ``y_train (60000,) uint8``,
``x_test``, ``y_test`` — the exact shapes ``load_mnist`` reshapes to
flat 784-dim rows (reference parity: its examples fed raw-pixel CSVs
through MinMaxTransformer).
"""

from __future__ import annotations

import argparse
import gzip
import os
import struct

import numpy as np

# canonical basenames -> npz keys (images/labels pairs per split)
_FILES = {
    "train-images-idx3-ubyte": "x_train",
    "train-labels-idx1-ubyte": "y_train",
    "t10k-images-idx3-ubyte": "x_test",
    "t10k-labels-idx1-ubyte": "y_test",
}
_MAGIC_IMAGES, _MAGIC_LABELS = 2051, 2049


def _open(path: str):
    return gzip.open(path, "rb") if path.endswith(".gz") else \
        open(path, "rb")


def read_idx(path: str) -> np.ndarray:
    """Parse one IDX file (images: (N, 28, 28) uint8; labels: (N,))."""
    with _open(path) as f:
        magic, n = struct.unpack(">II", f.read(8))
        if magic == _MAGIC_IMAGES:
            rows, cols = struct.unpack(">II", f.read(8))
            data = np.frombuffer(f.read(n * rows * cols), np.uint8)
            return data.reshape(n, rows, cols)
        if magic == _MAGIC_LABELS:
            return np.frombuffer(f.read(n), np.uint8)
        raise ValueError(f"{path}: magic {magic} is neither IDX images "
                         f"({_MAGIC_IMAGES}) nor labels ({_MAGIC_LABELS})")


def find_file(src: str, base: str) -> str:
    """Locate ``base`` under ``src`` tolerating .gz and '.' vs '-idx'
    name variants (mirrors disagree)."""
    cands = [base, base + ".gz",
             base.replace("-idx", ".idx"),
             base.replace("-idx", ".idx") + ".gz"]
    for c in cands:
        p = os.path.join(src, c)
        if os.path.exists(p):
            return p
    raise FileNotFoundError(
        f"none of {cands} under {src!r} — download the four MNIST IDX "
        "files there first (no network in this sandbox; fetch elsewhere)")


def main():
    ap = argparse.ArgumentParser(
        description="MNIST IDX -> mnist.npz for DISTKERAS_TPU_DATA")
    ap.add_argument("src", help="directory holding the four IDX files")
    ap.add_argument("--out", default=os.environ.get(
        "DISTKERAS_TPU_DATA",
        os.path.expanduser("~/.distkeras_tpu/data")))
    ap.add_argument("--name", default="mnist",
                    help="npz basename (fashion-MNIST IDX files: "
                         "--name fashion_mnist)")
    args = ap.parse_args()

    arrays = {key: read_idx(find_file(args.src, base))
              for base, key in _FILES.items()}
    for split in ("train", "test"):
        nx, ny = len(arrays[f"x_{split}"]), len(arrays[f"y_{split}"])
        if nx != ny:
            raise SystemExit(f"{split}: {nx} images but {ny} labels")
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, args.name + ".npz")
    np.savez_compressed(path, **arrays)
    print(f"wrote {path}: " + ", ".join(
        f"{k} {v.shape} {v.dtype}" for k, v in arrays.items()))
    print("loaders will now prefer it: set DISTKERAS_TPU_DATA="
          f"{args.out!r} (or keep the default ~/.distkeras_tpu/data)")


if __name__ == "__main__":
    main()
