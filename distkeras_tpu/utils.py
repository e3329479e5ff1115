"""Utility helpers (API parity with reference ``distkeras/utils.py``).

The reference's utils are Keras/Spark glue: model (de)serialization, one-hot
vectors, DataFrame row construction, shuffling, uniform weight init.  The
same-named functions here operate on the native Sequential/Dataset types.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence

import jax
import numpy as np

from .core.model import (Sequential, FittedModel, serialize_model,
                         deserialize_model)
from .data.dataset import Dataset


# -- persistent compile cache -------------------------------------------------

def use_compile_cache() -> str:
    """Point jax's persistent compilation cache somewhere that survives the
    process, and return where.  Entry points that compile for the chip
    (``chip_smoke.py``) call this before their first jit; nothing under
    ``tests/`` does.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself, so no cache
    option is touched here.  Unset: ``<checkout>/.jax_cache`` — a FIXED
    path, because the directory is part of the cache key and one that
    moves (a temporary name, a pid, a time) never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# -- model (de)serialization (reference: serialize_keras_model) --------------

def serialize_keras_model(model) -> dict:
    """Serialize a FittedModel — or an actual ``keras.Model`` via the adapter
    (reference: ``utils.py :: serialize_keras_model`` pickles json+weights)."""
    if isinstance(model, FittedModel):
        return model.serialize()
    from .core.keras_adapter import convert_keras_model, keras_weights
    native = convert_keras_model(model)
    params = native.init(jax.random.PRNGKey(0), native.input_shape)
    params = native.set_weights(params, keras_weights(model))
    return serialize_model(native, params)


def deserialize_keras_model(blob: dict) -> FittedModel:
    model, params = deserialize_model(blob)
    return FittedModel(model, params)


# -- vector/row helpers -------------------------------------------------------

def to_dense_vector(value: float, n_dim: int) -> np.ndarray:
    """One-hot vector with ``value`` as the hot index (reference:
    ``utils.py :: to_dense_vector`` backing OneHotTransformer)."""
    out = np.zeros((n_dim,), np.float32)
    out[int(value)] = 1.0
    return out


def new_dataframe_row(row: dict, name: str, value) -> dict:
    """Append a column to a row dict (reference: ``utils.new_dataframe_row``
    rebuilds a Spark Row with an extra field)."""
    out = dict(row)
    out[name] = value
    return out


def shuffle(dataset: Dataset, seed: Optional[int] = None) -> Dataset:
    """Global shuffle (reference: ``utils.shuffle(df)``)."""
    return dataset.shuffle(seed)


def precache(dataset: Dataset) -> Dataset:
    """Parity stub for ``df.cache()`` — our datasets are already host-resident
    numpy; returns the dataset unchanged."""
    return dataset


def uniform_weights(fitted: FittedModel, constraints: Sequence[float] = (-0.5, 0.5),
                    seed: int = 0) -> FittedModel:
    """Re-init all weights uniformly in [lo, hi] (reference:
    ``utils.uniform_weights``)."""
    lo, hi = constraints
    rng = np.random.default_rng(seed)
    new = [rng.uniform(lo, hi, size=w.shape).astype(w.dtype)
           for w in fitted.get_weights()]
    return FittedModel(fitted.model,
                       fitted.model.set_weights(fitted.params, new))


def history_average(history: Sequence[float]) -> float:
    return float(np.mean(np.asarray(history))) if len(history) else 0.0


def history_executors_average(histories) -> float:
    """Average final loss across worker histories (reference keeps per-worker
    loss lists; ours are already merged per-round means)."""
    return history_average([h[-1] if isinstance(h, (list, np.ndarray)) else h
                            for h in histories])
