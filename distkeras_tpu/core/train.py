"""Pure train-step construction.

The reference's per-batch hot loop is Keras ``train_on_batch`` inside
``distkeras/workers.py :: SequentialWorker.train`` (SURVEY.md §3.2).  Here the
equivalent is a pure function ``(params, opt_state, batch, rng) -> (params,
opt_state, loss)`` built once per (model, loss, optimizer) triple and jitted,
plus a ``lax.scan`` runner that executes a whole epoch of minibatches inside a
single XLA program — no per-batch Python dispatch, which is where the 8×+
throughput over the reference comes from.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .losses import get_loss, per_example
from .model import Sequential
from . import optimizers as opt_lib


class TrainState(NamedTuple):
    """Carried training state — a flat NamedTuple so it scans/shards cleanly."""
    params: Any
    opt_state: Any
    step: jnp.ndarray  # int32 scalar


def make_loss_fn(model: Sequential, loss) -> Callable:
    """(params, x, y, rng) -> (loss, stats_aux) — stats_aux is the
    ``{layer_index: new_stats}`` dict of EMA-updated BatchNorm running stats
    (empty for stat-free models)."""
    loss_fn = get_loss(loss)

    def compute(params, x, y, rng):
        stats: dict = {}
        pred = model.apply(params, x, train=True, rng=rng, stats_out=stats)
        with jax.named_scope("loss"):
            return loss_fn(y, pred), stats

    return compute


def make_masked_loss_fn(model: Sequential, loss) -> Callable:
    """(params, x, y, w, rng[, seg]) -> (masked-mean loss, stats_aux).

    ``w`` is a per-example weight vector (1 real, 0 padding): the loss is
    Σ wᵢ·lossᵢ / max(Σ w, 1), so padded examples contribute exactly zero to
    value and gradient (``shape_epoch_data`` pads the tail round by wrapping
    real rows, keeping BatchNorm batch statistics sane).  ``seg`` (optional
    trailing arg): per-row segment ids for sequence packing, threaded into
    the forward (``data/packing.py``)."""
    per_ex = per_example(get_loss(loss))

    def compute(params, x, y, w, rng, seg=None):
        stats: dict = {}
        kw = {"segment_ids": seg} if seg is not None else {}
        pred = model.apply(params, x, train=True, rng=rng, stats_out=stats,
                           **kw)
        with jax.named_scope("loss"):
            losses = per_ex(y, pred)
            w = w.astype(jnp.float32)
            return (jnp.sum(losses * w) / jnp.maximum(jnp.sum(w), 1.0),
                    stats)

    return compute


def make_masked_step(model: Sequential, loss,
                     tx: optax.GradientTransformation) -> Callable:
    """The one masked minibatch step shared by all three engines
    (``make_epoch_runner``, the SPMD window scan, the host-PS worker window).

    (params, opt_state, x, y, w, rng[, seg]) -> (params, opt_state, loss,
    wsum) — ``seg`` as in ``make_masked_loss_fn``.

    A fully-padded batch (wsum == 0) is a TRUE no-op: the masked loss gives
    zero gradient, but e.g. Adam still moves parameters on a zero gradient
    (decayed momentum over sqrt(v)), so the whole update — params, optimizer
    state, BatchNorm stats merge — is gated out with ``where`` in that case.
    """
    compute = make_masked_loss_fn(model, loss)

    def step(params, opt_state, x, y, w, rng, seg=None):
        (l, stats), grads = jax.value_and_grad(compute, has_aux=True)(
            params, x, y, w, rng, seg)
        with jax.named_scope("optimizer"):
            updates, new_opt = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
        new_params = Sequential.merge_stats(new_params, stats)
        wsum = jnp.sum(w.astype(jnp.float32))
        keep = wsum > 0.0
        pick = lambda new, old: jax.tree_util.tree_map(
            lambda a, b: jnp.where(keep, a, b), new, old)
        with jax.named_scope("optimizer"):  # the gate is part of the apply
            return (pick(new_params, params), pick(new_opt, opt_state), l,
                    wsum)

    return step


def make_train_step(model: Sequential, loss, tx: optax.GradientTransformation,
                    ) -> Callable:
    """Single-device SGD step: grad + optax update. Pure; jit at call site."""
    compute = make_loss_fn(model, loss)

    def step(state: TrainState, batch, rng) -> Tuple[TrainState, jnp.ndarray]:
        x, y = batch
        (loss_val, stats), grads = jax.value_and_grad(compute, has_aux=True)(
            state.params, x, y, rng)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
        params = Sequential.merge_stats(params, stats)
        return TrainState(params, opt_state, state.step + 1), loss_val

    return step


def make_epoch_runner(model: Sequential, loss, tx,
                      packed: bool = False) -> Callable:
    """Scan stacked batch arrays through train steps inside one XLA program.

    ``xb``/``yb``/``mb`` have shape (num_batches, batch, ...); ``mb`` is the
    per-example real/padding mask (``batch_epoch_data``) so the tail batch
    is padded+masked instead of dropped.  Returns (state, per-batch losses);
    each loss is the exact mean over that batch's real examples.

    ``packed=True`` (sequence packing, ``data/packing.py``): the epoch
    additionally scans a stacked ``sb`` segment-ids array —
    ``epoch(state, xb, yb, sb, mb, rng)`` — threaded into the shared
    masked step's forward; use a ``*_masked`` loss so cross-document
    label -1 positions drop out.
    """
    step = make_masked_step(model, loss, tx)

    def epoch(state: TrainState, xb, yb, *rest):
        (sb, mb, rng) = rest if packed else (None,) + rest

        def body(carry, inp):
            st, key = carry
            x, y, seg, w = inp if packed else inp[:2] + (None,) + inp[2:]
            key, sub = jax.random.split(key)
            params, opt_state, l, _ = step(st.params, st.opt_state, x, y, w,
                                           sub, seg)
            st = TrainState(params, opt_state, st.step + 1)
            return (st, key), l

        xs = (xb, yb, sb, mb) if packed else (xb, yb, mb)
        (state, _), losses = jax.lax.scan(body, (state, rng), xs)
        return state, losses

    return jax.jit(epoch)


def batch_epoch_arrays(batch_size: int, *arrays):
    """Stack flat epoch arrays into (num_batches, batch, ...) + mask,
    wrap-padding the tail batch instead of dropping it.  All arrays share
    one row order; returns ``(*stacked, mask, num_batches)``."""
    n_rows = len(arrays[0])
    if n_rows == 0:
        raise ValueError("empty dataset")
    if any(len(a) != n_rows for a in arrays):
        raise ValueError("epoch arrays must share their row count")
    nb = -(-n_rows // batch_size)  # ceil: pad up, never drop
    rows = nb * batch_size
    idx = np.arange(rows) % n_rows
    mask = (np.arange(rows) < n_rows).astype(np.float32)
    shape = (nb, batch_size)
    stacked = tuple(np.asarray(a)[idx].reshape(shape + np.asarray(a).shape[1:])
                    for a in arrays)
    return stacked + (mask.reshape(shape), nb)


def batch_epoch_data(x: np.ndarray, y: np.ndarray, batch_size: int):
    """Stack a flat epoch into (num_batches, batch, ...) + mask, wrap-padding
    the tail batch instead of dropping it (single-device analogue of
    ``parallel.spmd.shape_epoch_data``)."""
    xb, yb, mask, nb = batch_epoch_arrays(batch_size, x, y)
    return xb, yb, mask, nb


def make_packed_epoch_runner(model: Sequential, loss, tx) -> Callable:
    """``make_epoch_runner(packed=True)`` — one scan body for both
    paths; see there."""
    return make_epoch_runner(model, loss, tx, packed=True)


def init_state(model: Sequential, rng, input_shape, optimizer,
               learning_rate=None, lr_schedule=None, total_steps=None,
               gradient_accumulation: int = 1,
               gradient_clip_norm=None
               ) -> Tuple[TrainState, optax.GradientTransformation]:
    """Initialize params + optimizer state for a model."""
    params = model.init(rng, input_shape)
    tx, opt_state = opt_lib.build(optimizer, params, learning_rate,
                                  lr_schedule, total_steps,
                                  gradient_accumulation,
                                  gradient_clip_norm)
    return TrainState(params, opt_state, jnp.zeros((), jnp.int32)), tx
