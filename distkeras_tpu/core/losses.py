"""Loss functions (Keras-name parity).

The reference passes Keras loss *names* into trainers (reference:
``distkeras/trainers.py :: Trainer.__init__(..., loss)`` compiled in
``workers.py :: SequentialWorker.prepare_model``).  We accept the same string
names and resolve them to pure jnp functions.  All losses reduce to a scalar
mean over the batch and compute in float32 regardless of model compute dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_EPS = 1e-7


def categorical_crossentropy(y_true, y_pred, from_logits: bool = False):
    y_true = y_true.astype(jnp.float32)
    y_pred = y_pred.astype(jnp.float32)
    if from_logits:
        logp = jax.nn.log_softmax(y_pred, axis=-1)
    else:
        logp = jnp.log(jnp.clip(y_pred, _EPS, 1.0))
    return -jnp.mean(jnp.sum(y_true * logp, axis=-1))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ``fused_ce_applies``: the kernel's lane block
_CE_MIN_VOCAB = 128


def fused_ce_applies(shape, dtype) -> bool:
    """Does the sparse cross-entropy over logits of this ``shape``
    (``(..., vocab)``) and ``dtype`` go to the Pallas kernels
    (``ops.fused_ce``: ``fused_ce_fwd`` / ``fused_ce_bwd``) instead of XLA's
    ``log_softmax`` + ``take_along_axis``?  Decided from what the call
    itself shows, never by an option: a TPU underneath (off it the kernel
    would run in the Pallas interpreter); f32 or bf16 logits; a vocabulary
    of at least one 128-lane block (narrower, a block is mostly padding:
    MNIST's 10 classes and the Higgs job's 2 stay with XLA, bit for bit).
    No size of ``tokens x vocab``: XLA writes the log-probabilities to HBM
    and reads them back for the gradient, which costs by the matrix, so
    the kernels win from some 2**26 logits on (a ``transformer_lm`` step,
    one v5e: 4.11 against 4.26 ms at 8 x 256 x 32,000, 15.58 against 20.81
    at 8 x 512 x 50,257), and under that the two steps lie within 0.08 ms
    of each other either way (1.39 against 1.41 ms at 2**22 logits, 1.41
    against 1.33 at 2**24), too little to carry a second constant
    (PERF.md section 6, PR 33, second session).  ``trainers.py`` asks the
    same question for the ``ce`` field of its ``train.epoch`` span, so the
    span says what the program does."""
    *lead, vocab = shape
    return (_on_tpu() and bool(lead)
            and jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16)
            and vocab >= _CE_MIN_VOCAB)


def _sparse_nll(y_true, y_pred, from_logits: bool):
    """Per-token ``-log p[label]`` in f32, shaped as the labels, from the
    one place both sparse cross-entropies get it.  Labels below 0 (the
    packing convention) pick column 0; the caller masks them, so their
    cotangent, and with it their row of the logits' gradient, is zero."""
    idx = jnp.maximum(y_true.astype(jnp.int32), 0)
    if from_logits and fused_ce_applies(y_pred.shape, y_pred.dtype):
        from ..ops.fused_ce import fused_softmax_cross_entropy
        vocab = y_pred.shape[-1]
        return fused_softmax_cross_entropy(
            y_pred.reshape(-1, vocab), idx.reshape(-1)).reshape(idx.shape)
    y_pred = y_pred.astype(jnp.float32)
    if from_logits:
        logp = jax.nn.log_softmax(y_pred, axis=-1)
    else:
        logp = jnp.log(jnp.clip(y_pred, _EPS, 1.0))
    return -jnp.take_along_axis(logp, idx[..., None], axis=-1)[..., 0]


def _masked_mean(nll, valid, axis=None):
    count = jnp.maximum(jnp.sum(valid, axis=axis), 1)
    return jnp.sum(jnp.where(valid, nll, 0.0), axis=axis) / count


def sparse_categorical_crossentropy(y_true, y_pred, from_logits: bool = False):
    return jnp.mean(_sparse_nll(y_true, y_pred, from_logits))


def masked_sparse_categorical_crossentropy(y_true, y_pred,
                                           from_logits: bool = False):
    """Sparse CE that skips label < 0 (the sequence-packing convention:
    ``data/packing.py :: packed_lm_labels`` marks cross-document and
    padding positions -1).  Mean over the VALID positions only."""
    return _masked_mean(_sparse_nll(y_true, y_pred, from_logits),
                        y_true.astype(jnp.int32) >= 0)


def _sparse_rows(y_true, y_pred, from_logits: bool, masked: bool):
    """The two sparse cross-entropies a batch row at a time: ``(batch,)``
    losses, each what the mean-reducing form gives on that row alone, from
    ONE call of the per-token form on the whole batch (``per_example``)."""
    nll = _sparse_nll(y_true, y_pred, from_logits)
    axis = tuple(range(1, nll.ndim))
    if masked:
        return _masked_mean(nll, y_true.astype(jnp.int32) >= 0, axis)
    return jnp.mean(nll, axis=axis)


def binary_crossentropy(y_true, y_pred, from_logits: bool = False):
    y_true = y_true.astype(jnp.float32)
    y_pred = y_pred.astype(jnp.float32)
    if from_logits:
        # numerically stable sigmoid BCE
        return jnp.mean(jnp.maximum(y_pred, 0) - y_pred * y_true +
                        jnp.log1p(jnp.exp(-jnp.abs(y_pred))))
    p = jnp.clip(y_pred, _EPS, 1.0 - _EPS)
    return -jnp.mean(y_true * jnp.log(p) + (1.0 - y_true) * jnp.log(1.0 - p))


def mean_squared_error(y_true, y_pred):
    d = y_true.astype(jnp.float32) - y_pred.astype(jnp.float32)
    return jnp.mean(jnp.square(d))


def mean_absolute_error(y_true, y_pred):
    return jnp.mean(jnp.abs(
        y_true.astype(jnp.float32) - y_pred.astype(jnp.float32)))


def mean_absolute_percentage_error(y_true, y_pred):
    y_true = y_true.astype(jnp.float32)
    diff = jnp.abs((y_true - y_pred.astype(jnp.float32))
                   / jnp.clip(jnp.abs(y_true), _EPS, None))
    return 100.0 * jnp.mean(diff)


def mean_squared_logarithmic_error(y_true, y_pred):
    fl = jnp.log1p(jnp.clip(y_pred.astype(jnp.float32), _EPS, None))
    sl = jnp.log1p(jnp.clip(y_true.astype(jnp.float32), _EPS, None))
    return jnp.mean(jnp.square(fl - sl))


def kullback_leibler_divergence(y_true, y_pred):
    y_true = jnp.clip(y_true.astype(jnp.float32), _EPS, 1.0)
    y_pred = jnp.clip(y_pred.astype(jnp.float32), _EPS, 1.0)
    return jnp.mean(jnp.sum(y_true * jnp.log(y_true / y_pred), axis=-1))


def hinge(y_true, y_pred):
    """Hinge loss with {0,1} labels auto-converted to {-1,1}.

    DELIBERATE MODERNIZATION vs Keras-1: upstream Keras-1 performed no label
    conversion (that arrived in Keras 2), so a reference workflow feeding
    0/1 labels under this name effectively trained on a different objective
    (the 0-label rows contribute a constant margin).  We adopt the Keras-2+
    conversion because 0/1 one-hot labels are what this framework's own
    pipeline produces; documented here and in docs/API.md.
    """
    y_true = y_true.astype(jnp.float32)
    y_true = jnp.where(y_true == 0.0, -1.0, y_true)
    return jnp.mean(jnp.maximum(
        1.0 - y_true * y_pred.astype(jnp.float32), 0.0))


def squared_hinge(y_true, y_pred):
    # same deliberate {0,1}->{-1,1} modernization as ``hinge`` above
    y_true = y_true.astype(jnp.float32)
    y_true = jnp.where(y_true == 0.0, -1.0, y_true)
    return jnp.mean(jnp.square(jnp.maximum(
        1.0 - y_true * y_pred.astype(jnp.float32), 0.0)))


def poisson(y_true, y_pred):
    y_pred = jnp.clip(y_pred.astype(jnp.float32), _EPS, None)
    return jnp.mean(y_pred - y_true.astype(jnp.float32) * jnp.log(y_pred))


def cosine_proximity(y_true, y_pred):
    """Keras-1 cosine proximity, reduction included.

    Keras-1 computed ``-mean(l2_normalize(y_true) * l2_normalize(y_pred))``
    — the mean runs over ALL elements, not per-row, so a perfectly aligned
    pair scores ``-1/feature_dim`` (NOT -1).  We reproduce that exactly so
    migrated configs using this loss name keep the same values and gradient
    scale as the reference (a per-row mean would be feature_dim x larger).
    Minimizing still drives vectors together.
    """
    yt = y_true.astype(jnp.float32)
    yp = y_pred.astype(jnp.float32)
    yt = yt / jnp.clip(jnp.linalg.norm(yt, axis=-1, keepdims=True), _EPS)
    yp = yp / jnp.clip(jnp.linalg.norm(yp, axis=-1, keepdims=True), _EPS)
    return -jnp.mean(yt * yp)


def _from_logits(fn):
    def wrapped(y_true, y_pred):
        return fn(y_true, y_pred, from_logits=True)
    return wrapped


_LOSSES = {
    "categorical_crossentropy": categorical_crossentropy,
    "sparse_categorical_crossentropy": sparse_categorical_crossentropy,
    "binary_crossentropy": binary_crossentropy,
    "categorical_crossentropy_from_logits":
        _from_logits(categorical_crossentropy),
    "sparse_categorical_crossentropy_from_logits":
        _from_logits(sparse_categorical_crossentropy),
    "sparse_categorical_crossentropy_masked":
        masked_sparse_categorical_crossentropy,
    "sparse_categorical_crossentropy_masked_from_logits":
        _from_logits(masked_sparse_categorical_crossentropy),
    "binary_crossentropy_from_logits": _from_logits(binary_crossentropy),
    "mean_squared_error": mean_squared_error,
    "mse": mean_squared_error,
    "mean_absolute_error": mean_absolute_error,
    "mae": mean_absolute_error,
    "mean_absolute_percentage_error": mean_absolute_percentage_error,
    "mape": mean_absolute_percentage_error,
    "mean_squared_logarithmic_error": mean_squared_logarithmic_error,
    "msle": mean_squared_logarithmic_error,
    "kullback_leibler_divergence": kullback_leibler_divergence,
    "kld": kullback_leibler_divergence,
    "hinge": hinge,
    "squared_hinge": squared_hinge,
    "poisson": poisson,
    "cosine_proximity": cosine_proximity,
    "cosine": cosine_proximity,
}


def get_loss(name):
    """Resolve a Keras-style loss name (or pass through a callable)."""
    if callable(name):
        return name
    try:
        return _LOSSES[name]
    except KeyError:
        raise ValueError(
            f"Unknown loss {name!r}; known: {sorted(_LOSSES)}") from None


#: the losses with a per-row form of their own, by the function object a
#: name resolves to (engines hand ``per_example`` the resolved callable)
_PER_ROW = {
    _LOSSES[name + suffix]: functools.partial(
        _sparse_rows, from_logits=bool(suffix), masked=masked)
    for name, masked in (("sparse_categorical_crossentropy", False),
                         ("sparse_categorical_crossentropy_masked", True))
    for suffix in ("", "_from_logits")
}


def ce_path(loss_fn, pred) -> str:
    """``kernel`` or ``xla``: where a train step whose model hands
    ``loss_fn`` predictions like ``pred`` (anything with ``shape`` and
    ``dtype``, the batch included) computes its cross-entropy — the
    ``ce`` field of the trainer's ``train.epoch`` span, from the predicate
    the loss itself asks."""
    rows = _PER_ROW.get(loss_fn)
    fused = (rows is not None and rows.keywords["from_logits"]
             and fused_ce_applies(pred.shape, pred.dtype))
    return "kernel" if fused else "xla"


def per_example(loss_fn):
    """Lift a mean-reducing loss to per-example form, a (batch,) vector of
    losses, for the padding/masking path (``shape_epoch_data`` pads the
    tail round; padded rows get weight 0).  The sparse cross-entropies
    have a per-row form of their own (``_sparse_rows``: one call on the
    whole batch, which is what lets the fused kernel see ``(tokens,
    vocab)``); anything else, custom callables too, is vmapped over
    singleton batches, so no loss needs a rewrite."""
    if loss_fn in _PER_ROW:
        return _PER_ROW[loss_fn]

    def fn(y_true, y_pred):
        return jax.vmap(lambda yt, yp: loss_fn(yt[None], yp[None]))(
            y_true, y_pred)
    return fn
