"""SPMD execution engine for the distributed trainers.

Reference architecture being replaced (SURVEY.md §2.4, §3.1): N Spark workers
train locally and exchange full weight deltas with a driver parameter server
over TCP/pickle every ``communication_window`` minibatches.  Here the same
algorithm semantics execute as a bulk-synchronous SPMD program over a
``Mesh(('workers',))``:

 - "pull center"      → read the replicated center params (no transfer at all)
 - "commit delta"     → ``lax.psum`` of window deltas over the ICI ring
 - "PS apply rule"    → the pure functions in ``rules.py`` applied in-graph
 - per-worker state   → pytrees with a leading 'workers' axis, sharded
                        ``P('workers')`` so each chip owns exactly its worker

One *round* = ``communication_window`` local minibatch steps (an in-graph
``lax.scan``) + one collective exchange.  A whole epoch of rounds is itself a
``lax.scan``, so an epoch is a single XLA program: zero Python dispatch, zero
host↔device traffic between rounds (vs. the reference's per-window pickle of
the full weight vector through the driver).

Async-semantics note: XLA is bulk-synchronous, so true hogwild interleaving is
not representable on the ICI path.  Each algorithm keeps its *update rule*
exactly (ADAG normalization, elastic term, staleness scaling) while commits
within a round are emulated as a deterministic serialized order (DynSGD's
staleness = position in a per-round rotation).  The semantically-exact
thread-async execution lives in ``distkeras_tpu.parameter_servers`` (host/DCN
path); both engines share ``rules.py``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import _compat

from ..core.model import Sequential
from ..core.losses import get_loss
from ..core import optimizers as opt_lib
from . import rules
from .mesh import WORKER_AXIS, replicated, worker_sharded

tmap = jax.tree_util.tree_map

class DistState(NamedTuple):
    """Distributed training state.

    center:    replicated params pytree (the PS "center" model)
    local:     per-worker params, leaves stacked on a leading 'workers' axis
    opt_state: per-worker optimizer state, same stacking
    round_idx: int32 scalar — the PS clock (reference:
               ``ParameterServer.next_update`` counter)
    """
    center: Any
    local: Any
    opt_state: Any
    round_idx: jnp.ndarray


class SPMDEngine:
    """Builds and runs the jitted per-epoch program for one algorithm."""

    def __init__(self, model: Sequential, loss, worker_optimizer,
                 mesh: Mesh, algorithm: str,
                 communication_window: int = 5,
                 learning_rate: Optional[float] = None,
                 alpha: Optional[float] = None,
                 lr_schedule=None, schedule_steps: Optional[int] = None,
                 gradient_accumulation: int = 1,
                 gradient_clip_norm=None,
                 packed: bool = False):
        self.model = model
        self.loss_fn = get_loss(loss)
        self.mesh = mesh
        self.algorithm = algorithm
        self.window = int(communication_window)
        self.num_workers = int(mesh.devices.size)
        self.alpha = alpha
        self.optimizer = opt_lib.get_optimizer(worker_optimizer, learning_rate)
        self.lr_schedule = lr_schedule
        self.schedule_steps = schedule_steps
        self.gradient_accumulation = int(gradient_accumulation)
        self.gradient_clip_norm = gradient_clip_norm
        # packed=True: the epoch/round programs additionally scan a
        # segment-ids array (sequence packing, data/packing.py) threaded
        # into the masked step's forward so attention keeps per-document
        # isolation — the distributed twin of SingleTrainer(segment_col=…)
        self.packed = bool(packed)
        self.tx = None  # built in init_state (needs params for masking)
        self._epoch_fn = None
        self._round_step = None

    # -- state --------------------------------------------------------------
    def init_state(self, rng, input_shape, initial_params=None) -> DistState:
        params = self.model.init(rng, input_shape)
        if initial_params is not None:
            params = initial_params
        self.tx = opt_lib.build_tx(
            self.optimizer, params, lr_schedule=self.lr_schedule,
            total_steps=self.schedule_steps,
            gradient_accumulation=self.gradient_accumulation,
            gradient_clip_norm=self.gradient_clip_norm)
        n = self.num_workers
        # every worker starts from the same center (reference: initial pull)
        local = tmap(lambda x: jnp.broadcast_to(x, (n,) + x.shape), params)
        opt_state = jax.vmap(self.tx.init)(local)
        center = jax.device_put(params, replicated(self.mesh))
        local = tmap(lambda x: jax.device_put(x, worker_sharded(self.mesh)),
                     local)
        opt_state = tmap(
            lambda x: jax.device_put(x, worker_sharded(self.mesh)), opt_state)
        return DistState(center, local, opt_state,
                         jnp.zeros((), jnp.int32))

    def put_state(self, state: DistState) -> DistState:
        """Re-apply mesh shardings to a host-side state pytree (checkpoint
        restore path — the leaves arrive as numpy arrays)."""
        ws = worker_sharded(self.mesh)
        center = jax.device_put(state.center, replicated(self.mesh))
        local = tmap(lambda x: jax.device_put(x, ws), state.local)
        opt_state = tmap(lambda x: jax.device_put(x, ws), state.opt_state)
        # round_idx may arrive as a live single-device jax scalar (orbax
        # sharded restore): pull it to host so the fresh array doesn't pin
        # a stale placement into the jitted epoch's device set
        return DistState(center, local, opt_state,
                         jnp.asarray(jax.device_get(state.round_idx),
                                     jnp.int32))

    # -- the per-round SPMD body ---------------------------------------------
    def _local_window(self, params, opt_state, xw, yw, mw, rng, sw=None):
        """Run ``window`` minibatch steps on one worker's shard (in-graph).

        ``mw``: (window, batch) per-example weights — 1 for real rows, 0 for
        the wrap-padding ``shape_epoch_data`` adds to fill the tail round.
        ``sw`` (packed engines): (window, batch, S) segment ids threaded
        into the forward.  Returns the example-weighted loss sum and the
        weight sum so the caller can form an exact mean over *real*
        examples only.
        """
        from ..core.train import make_masked_step
        step = make_masked_step(self.model, self.loss_fn, self.tx)
        packed = sw is not None

        def body(carry, inp):
            p, s, key = carry
            x, y, seg, w = inp if packed else inp[:2] + (None,) + inp[2:]
            key, sub = jax.random.split(key)
            p, s, l, wsum = step(p, s, x, y, w, sub, seg)
            return (p, s, key), (l, wsum)

        xs = (xw, yw, sw, mw) if packed else (xw, yw, mw)
        (params, opt_state, _), (losses, wsums) = jax.lax.scan(
            body, (params, opt_state, rng), xs)
        return params, opt_state, jnp.sum(losses * wsums), jnp.sum(wsums)

    def _sync_stats(self, new_p, center):
        """psum-mean each worker's EMA'd BatchNorm stats and write the mean
        into both the worker params and the center, so (a) eval on the center
        model uses real running stats and (b) the stats leaves contribute
        exactly zero to every delta/elastic exchange below (worker == center
        ⇒ tree_sub is 0 there, and each commit rule adds 0)."""
        n = self.num_workers
        out_p, out_c = [], []
        for p, c in zip(new_p, center):
            if isinstance(p, dict) and "stats" in p:
                mean = tmap(lambda v: jax.lax.psum(v, WORKER_AXIS) / n,
                            p["stats"])
                # worker-side copy must stay device-varying for the
                # P(WORKER_AXIS) out_spec; the center copy stays unvarying
                p = {**p, "stats": tmap(
                    lambda v: _compat.pcast(v, WORKER_AXIS, to="varying"),
                    mean)}
                c = {**c, "stats": mean}
            out_p.append(p)
            out_c.append(c)
        return out_p, out_c

    def _make_round_fn(self) -> Callable:
        n = self.num_workers
        algo = self.algorithm
        alpha = self.alpha

        packed = self.packed

        def round_fn(center, local, opt_state, round_idx, xw, yw, *rest):
            # Block shapes inside shard_map: local/opt_state leaves and the
            # rng carry a leading worker axis of size 1; the batch data is
            # (window, workers=1, batch, ...) — squeeze the *worker* axis in
            # each (xw[:, 0], NOT xw[0]: that would squeeze the window axis
            # and silently train on only the first batch of every window).
            (sw, mw, rngs) = rest if packed else (None,) + rest
            squeeze = lambda t: tmap(lambda v: v[0], t)
            local_p = squeeze(local)
            opt_s = squeeze(opt_state)
            x = xw[:, 0]
            y = yw[:, 0]
            m = mw[:, 0]
            s = sw[:, 0] if packed else None
            rng = rngs[0]

            if algo in ("adag", "downpour", "dynsgd"):
                # "pull": start from the replicated center; mark it
                # device-varying so the per-worker scan carry typechecks.
                start = tmap(
                    lambda v: _compat.pcast(v, WORKER_AXIS, to="varying"),
                    center)
            else:  # EASGD family + 'local' keep persistent local params
                start = local_p
            new_p, new_s, loss_sum, wsum = self._local_window(
                start, opt_s, x, y, m, rng, s)
            if algo != "local" and self.model.has_stats():
                # 'local' = independent training: per-worker stats persist
                new_p, center = self._sync_stats(new_p, center)

            # the window's exchange: the collective and the center update
            psum = lambda t: tmap(
                lambda d: jax.lax.psum(d, WORKER_AXIS), t)
            with jax.named_scope("commit"):
                if algo == "adag":
                    delta = rules.tree_sub(new_p, center)
                    summed = psum(delta)
                    center = rules.adag_commit(center, summed, n)
                elif algo == "downpour":
                    delta = rules.tree_sub(new_p, center)
                    summed = psum(delta)
                    center = rules.delta_commit(center, summed)
                elif algo == "dynsgd":
                    # Serialized-commit emulation: within a round, worker w's
                    # commit lands after ``order`` earlier commits, where the
                    # order rotates every round — its delta is scaled by
                    # 1/(staleness+1) exactly as DynSGDParameterServer does.
                    w = jax.lax.axis_index(WORKER_AXIS)
                    order = jnp.mod(w + round_idx, n).astype(jnp.float32)
                    delta = rules.tree_sub(new_p, center)
                    scaled = rules.dynsgd_commit(
                        tmap(jnp.zeros_like, center), delta, order)
                    summed = psum(scaled)
                    center = rules.tree_add(center, summed)
                elif algo == "local":
                    # Independent per-worker training (AveragingTrainer /
                    # EnsembleTrainer): no exchange; center untouched.
                    pass
                elif algo in ("aeasgd", "eamsgd"):
                    e = rules.elastic_difference(new_p, center, alpha)
                    new_p = rules.easgd_worker_update(new_p, e)
                    summed = psum(e)
                    center = rules.easgd_center_update(center, summed)
                else:
                    raise ValueError(f"unknown algorithm {algo!r}")

            # exact mean over real (unpadded) examples across all workers
            mean_loss = (jax.lax.psum(loss_sum, WORKER_AXIS)
                         / jnp.maximum(jax.lax.psum(wsum, WORKER_AXIS), 1.0))
            unsqueeze = lambda t: tmap(lambda v: v[None], t)
            return (center, unsqueeze(new_p), unsqueeze(new_s), mean_loss)

        return round_fn

    # -- epoch program -------------------------------------------------------
    def _shmapped_round(self) -> Callable:
        """The single shard_map'd round program — the one contract both the
        scanned epoch and the streaming path execute."""
        data_spec = (P(None, WORKER_AXIS),) * (4 if self.packed else 3)
        return _compat.shard_map(
            self._make_round_fn(),
            mesh=self.mesh,
            in_specs=(P(), P(WORKER_AXIS), P(WORKER_AXIS), P())
            + data_spec + (P(WORKER_AXIS),),
            out_specs=(P(), P(WORKER_AXIS), P(WORKER_AXIS), P()),
        )

    @staticmethod
    def _run_round(shmapped, state: DistState, data, rngs):
        """One round: fold the per-worker keys with the round clock, execute,
        re-wrap the state (shared by epoch scan and streaming).  ``data`` =
        (x, y, m) or (x, y, seg, m) on the packed engine."""
        keys = jax.vmap(
            lambda k: jax.random.fold_in(k, state.round_idx))(rngs)
        center, local, opt_state, loss = shmapped(
            state.center, state.local, state.opt_state, state.round_idx,
            *data, keys)
        return (DistState(center, local, opt_state, state.round_idx + 1),
                loss)

    def _build_epoch_fn(self) -> Callable:
        shmapped = self._shmapped_round()

        def epoch(state: DistState, xb, yb, *rest):
            # xb, yb, [sb,] mb: (rounds, window, workers, batch, ...) on
            # axis 2; rngs last
            *data_rest, rngs = rest

            def body(st, inp):
                st, loss = self._run_round(shmapped, st, inp, rngs)
                return st, loss

            return jax.lax.scan(body, state, (xb, yb) + tuple(data_rest))

        return jax.jit(epoch, donate_argnums=(0,))

    def run_epoch(self, state: DistState, xb, yb, mb, rngs, sb=None
                  ) -> Tuple[DistState, np.ndarray]:
        """xb/yb/mb: np arrays shaped (rounds, window, workers, batch, ...);
        ``mb`` is the per-example real/padding mask from
        ``shape_epoch_data``; ``sb`` (packed engines) the segment ids."""
        self._check_packed(sb)
        if self._epoch_fn is None:
            self._epoch_fn = self._build_epoch_fn()
        sh = NamedSharding(self.mesh, P(None, None, WORKER_AXIS))
        arrays = (xb, yb) + ((sb,) if self.packed else ()) + (mb,)
        arrays = tuple(jax.device_put(a, sh) for a in arrays)
        state, losses = self._epoch_fn(state, *arrays, rngs)
        return state, losses

    def run_round(self, state: DistState, x, y, m, rngs, s=None
                  ) -> Tuple[DistState, jnp.ndarray]:
        """One jitted round from host arrays shaped (window, workers, batch,
        ...) — the round-granular checkpointing path.  Same math as the
        epoch scan (both execute the one shard_map'd round program), at the
        cost of one jit call + device_put per round."""
        self._check_packed(s)
        if self._round_step is None:
            self._round_step = self._build_round_step()
        sh = NamedSharding(self.mesh, P(None, WORKER_AXIS))
        data = (x, y) + ((s,) if self.packed else ()) + (m,)
        return self._round_step(state,
                                *(jax.device_put(a, sh) for a in data),
                                rngs)

    def _check_packed(self, seg):
        if self.packed and seg is None:
            raise ValueError("packed engine needs segment ids")
        if seg is not None and not self.packed:
            raise ValueError("segment ids passed to an unpacked engine — "
                             "construct SPMDEngine(packed=True)")

    # -- streaming epoch (datasets larger than HBM) ---------------------------
    def _build_round_step(self) -> Callable:
        shmapped = self._shmapped_round()

        def step(state: DistState, *args):
            *data, rngs = args
            return self._run_round(shmapped, state, tuple(data), rngs)

        return jax.jit(step, donate_argnums=(0,))

    def run_epoch_streaming(self, state: DistState, round_iter, rngs
                            ) -> Tuple[DistState, np.ndarray]:
        """Run an epoch from a generator of per-round host array tuples —
        (x, y, mask) triples, or (x, y, seg, mask) quadruples on a packed
        engine — shaped (window, workers, batch, ...) (see
        ``data.pipeline.round_stream``; pass ``seg=`` there iff the engine
        is packed), double-buffered onto the mesh.  Same math as
        ``run_epoch`` — one jit call per round instead of one per epoch —
        for datasets that cannot live in HBM whole.
        """
        from ..data.pipeline import prefetch_to_device
        if self._round_step is None:
            self._round_step = self._build_round_step()
        sh = NamedSharding(self.mesh, P(None, WORKER_AXIS))
        # packed engines stream (x, y, seg, mask) quadruples
        # (round_stream(seg=…)); unpacked stream the classic triples.
        # Arity is checked on the RAW iterator, before prefetch's zip could
        # truncate a too-long item (prefetch_to_device also refuses
        # length mismatches as a second line of defense).
        arity = 4 if self.packed else 3

        def checked(it):
            for item in it:
                if len(item) != arity:
                    raise ValueError(
                        f"streamed round has {len(item)} arrays, the "
                        f"{'packed' if self.packed else 'unpacked'} "
                        f"engine expects {arity} — pass seg=… to "
                        "round_stream iff the engine is packed")
                yield item

        losses = []
        for item in prefetch_to_device(checked(round_iter), (sh,) * arity):
            state, loss = self._round_step(state, *item, rngs)
            losses.append(loss)
        # one device→host transfer for the whole epoch, f32 like run_epoch
        return state, np.asarray(jax.device_get(jnp.stack(losses)),
                                 dtype=np.float32)

    def worker_rngs(self, seed: int):
        keys = jax.random.split(jax.random.PRNGKey(seed), self.num_workers)
        return jax.device_put(keys, worker_sharded(self.mesh))


def shape_epoch_data(columns_x: np.ndarray, columns_y: np.ndarray,
                     num_workers: int, window: int, batch_size: int,
                     columns_seg: Optional[np.ndarray] = None):
    """Reshape flat (rows, ...) arrays into (rounds, window, workers, batch,
    ...) plus a per-example mask, padding the tail to a whole round.

    The worker axis is placed *inside* the scan axes so the arrays can be
    device_put with a single ``P(None, None, 'workers')`` sharding and scanned
    over rounds/window without any transposition inside the program.

    SPMD static shapes require an integer number of rounds; instead of
    truncating the tail (which at an 8-worker MNIST config silently dropped
    up to ~18% of each epoch — Spark's repartition drops nothing), the tail
    round is filled by *wrapping* real rows, and the returned mask is 1.0
    for real rows, 0.0 for padding.  Padded examples contribute zero to loss
    and gradients (``make_masked_loss_fn``) while keeping BatchNorm batch
    statistics over real data values.  The layout itself (round-robin deal
    of rows to workers so padding never concentrates on one worker) lives in
    ``data.pipeline.round_block``, shared with the streaming path.

    Returns ``(xb, yb, mask, rounds)``, or ``(xb, yb, sb, mask, rounds)``
    when ``columns_seg`` (sequence-packing segment ids, same row order) is
    given; every real row appears exactly once.
    """
    from ..data.pipeline import num_rounds, round_block
    n, w, b = num_workers, window, batch_size
    rounds = num_rounds(len(columns_x), n, w, b)
    sel = np.empty((rounds, w, n, b), np.int64)
    mask = np.empty((rounds, w, n, b), np.float32)
    for r in range(rounds):
        sel[r], mask[r] = round_block(len(columns_x), n, w, b, r)
    if columns_seg is not None:
        return (columns_x[sel], columns_y[sel], columns_seg[sel], mask,
                rounds)
    return columns_x[sel], columns_y[sel], mask, rounds
