"""Worker closures for the host-parameter-server execution path.

Reference being replaced: ``distkeras/workers.py`` (SURVEY.md §2.1 rows 12–13)
— per-partition training closures shipped to Spark executors, each connecting
back to the driver's socket PS, pulling the center model, training local
minibatches, and committing weight deltas every ``communication_window``
steps.

Here a worker is a thread (same-host simulation, like the reference's Spark
``local[*]`` mode) or a per-host process on a pod, and the minibatch hot loop
is **one jitted ``lax.scan`` per communication window** instead of a Python
loop of ``train_on_batch`` calls — host↔device traffic happens once per
window, exactly when the algorithm needs the weights on the host anyway for
the commit.  The update-rule math mirrors the SPMD engine's pure functions in
``parallel/rules.py`` (equivalence is asserted by tests/test_host_ps.py);
only the execution differs (true asynchronous hogwild commits against a live
PS, vs. deterministic bulk-synchronous rounds).

With ``comm_overlap`` the transport is additionally *pipelined*: each window
becomes one combined ``'u'`` (commit+pull) round trip whose reply is
received while the next window's jitted compute runs, so the DCN latency
hides behind the device (see ``PSWorker._train_epoch_overlapped`` and
docs/host_ps.md for the per-algorithm staleness contract).
"""

from __future__ import annotations

import socket
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .core import optimizers as opt_lib
from .core.model import Sequential, deserialize_model
from .core.train import batch_epoch_data, make_masked_step
from . import networking
from .ps_sharding import ShardedPSClient
from .resilience import (DEFAULT_CONNECT_POLICY, DEFAULT_RECOVERY_POLICY,
                         RETRYABLE_CONNECT, Partitioned, RetryPolicy, dial)


#: injectable worker fault kinds (fault_injection): 'raise' = thread raises
#: (the legacy int form), 'exit' = the worker vanishes mid-frame (torn
#: commit + RST, then SystemExit — the wire signature of a worker host
#: dying), 'hang' = the worker wedges (stops renewing its lease) while its
#: PS connection stays open, until released at teardown.
FAULT_KINDS = ("raise", "exit", "hang")


def parse_fault_injection(spec: Optional[dict]) -> Dict[int, Tuple[str, int]]:
    """Normalize a ``fault_injection`` spec to ``{worker_id: (kind, budget)}``.

    Accepts the legacy ``{id: n}`` form (= ``('raise', n)``) and the
    PR 5 ``{id: (kind, n)}`` form; keys may be strings (JSON round-trip on
    the process engine) and tuples may arrive as lists for the same reason.
    """
    out: Dict[int, Tuple[str, int]] = {}
    for k, v in (spec or {}).items():
        if isinstance(v, (list, tuple)):
            if len(v) != 2:
                raise ValueError(
                    f"fault_injection value for worker {k} must be "
                    f"(kind, budget), got {v!r}")
            kind, budget = str(v[0]), int(v[1])
        else:
            kind, budget = "raise", int(v)
        if kind not in FAULT_KINDS:
            raise ValueError(
                f"fault_injection kind must be one of {FAULT_KINDS}, "
                f"got {kind!r} for worker {k}")
        out[int(k)] = (kind, budget)
    return out


def topk_select(eff: np.ndarray, k: int, code: Optional[str] = None):
    """Host-side top-k-by-magnitude selection with error feedback.

    ``eff`` is the effective flat f32 delta (this window's delta plus the
    carried residual).  Selects the ``k`` largest-magnitude coordinates
    (Aji & Heafield 2017; Lin et al., Deep Gradient Compression), optionally
    codes the values (``"bfloat16"`` cast or ``"int8"`` with one affine
    scale per commit), and returns::

        (indices int32 sorted, wire_values, applied_f32, scale, residual)

    where ``eff == densify(indices, applied_f32) + residual`` exactly — the
    unsent mass AND any value-coding error telescope into the next window
    instead of accumulating in the center (the EF-SGD recipe).  The device
    twin lives in ``PSWorker._build_topk_window_fn``.
    """
    eff = np.ascontiguousarray(eff, np.float32)
    n = eff.size
    k = max(1, min(int(k), n))
    if k >= n:
        idx = np.arange(n, dtype=np.int32)
    else:
        part = np.argpartition(np.abs(eff), n - k)[n - k:]
        idx = np.sort(part).astype(np.int32)
    vals = eff[idx]
    scale = None
    if code == "int8":
        scale = float(np.max(np.abs(vals)) / 127.0) or 1.0
        wire = np.clip(np.rint(vals / scale), -127, 127).astype(np.int8)
        applied = wire.astype(np.float32) * np.float32(scale)
    elif code == "bfloat16":
        import ml_dtypes
        wire = vals.astype(ml_dtypes.bfloat16)
        applied = wire.astype(np.float32)
    else:
        wire = vals.astype(np.float32)
        applied = wire
    residual = eff.copy()
    residual[idx] = vals - applied
    return idx, wire, applied, scale, residual


class Worker:
    """Base worker (reference: ``workers.py :: Worker``): holds the serialized
    model + training config and builds the jitted local window runner."""

    def __init__(self, model_blob: dict, worker_optimizer, loss,
                 features_col: str = "features", label_col: str = "label",
                 batch_size: int = 32, num_epoch: int = 1,
                 learning_rate: Optional[float] = None, seed: int = 0,
                 lr_schedule=None, schedule_steps: Optional[int] = None,
                 gradient_accumulation: int = 1,
                 gradient_clip_norm=None):
        self.model_blob = model_blob
        self.worker_optimizer = worker_optimizer
        self.loss = loss
        self.features_col = features_col
        self.label_col = label_col
        self.batch_size = int(batch_size)
        self.num_epoch = int(num_epoch)
        self.learning_rate = learning_rate
        self.lr_schedule = lr_schedule
        self.schedule_steps = schedule_steps
        self.gradient_accumulation = int(gradient_accumulation)
        self.gradient_clip_norm = gradient_clip_norm
        self.seed = seed
        self.history: List[float] = []
        # lazily-built jit state (shared across threads is fine: jax caches
        # compiled executables per shape under its own locks)
        self._model: Optional[Sequential] = None
        self._params0 = None
        self._tx = None
        self._window_fn = None

    # -- model/optimizer plumbing -------------------------------------------
    def _ensure_model(self):
        if self._model is None:
            self._model, self._params0 = deserialize_model(self.model_blob)
            self._tx, _ = opt_lib.build(self.worker_optimizer, self._params0,
                                        self.learning_rate,
                                        self.lr_schedule,
                                        self.schedule_steps,
                                        self.gradient_accumulation,
                                        self.gradient_clip_norm)
        return self._model

    def _make_window_body(self):
        """The unjitted window program: (params, opt_state, xw, yw, mw, rng)
        -> (params, opt_state, loss).  Shared by the plain jitted window fn
        and the top-k variant that appends device-side delta selection."""
        model = self._ensure_model()
        step = make_masked_step(model, self.loss, self._tx)

        def window(params, opt_state, xw, yw, mw, rng):
            def body(carry, inp):
                p, s, key = carry
                x, y, w = inp
                key, sub = jax.random.split(key)
                p, s, l, wsum = step(p, s, x, y, w, sub)
                return (p, s, key), (l, wsum)

            (params, opt_state, _), (losses, wsums) = jax.lax.scan(
                body, (params, opt_state, rng), (xw, yw, mw))
            return (params, opt_state,
                    jnp.sum(losses * wsums) / jnp.maximum(jnp.sum(wsums), 1.0))

        return window

    def _build_window_fn(self):
        """jitted (params, opt_state, xw, yw, mw, rng) -> (params, opt_state,
        loss) scanning a (window, batch, ...) stack of minibatches.  ``mw``
        is the per-example real/padding mask from ``_shard_to_windows``; the
        returned loss is the exact mean over real examples."""
        if self._window_fn is not None:
            return self._window_fn
        window = self._make_window_body()

        # donate params/opt_state: the window updates them in place instead
        # of holding input and output copies live at once — same contract as
        # the SPMD engine's epoch/round programs (parallel/spmd.py donates
        # its carry), halving peak device memory per worker thread.  Callers
        # never reuse the passed-in state (they rebind to the outputs); the
        # shared ``_params0`` template and driver-held wave states are
        # defensively copied before entering the loop.
        self._window_fn = jax.jit(window, donate_argnums=(0, 1))
        return self._window_fn

    def _weights_to_params(self, weights: List[np.ndarray]):
        model = self._ensure_model()
        return model.set_weights(self._params0, weights)

    def _params_to_weights(self, params) -> List[np.ndarray]:
        # ONE bulk device→host transfer for the whole pytree (jax batches
        # the per-leaf copies inside a single device_get) instead of a
        # Python loop of per-tensor np.asarray round trips — the fetch every
        # wire mode pays once per window.  Leaf order matches
        # ``model.get_weights`` (both walk ``tree_leaves``).
        self._ensure_model()
        return jax.device_get(jax.tree_util.tree_leaves(params))

    def _shard_to_windows(self, shard: Dict[str, np.ndarray], window: int,
                          epoch_seed: int
                          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Shape one epoch of this worker's shard into
        (num_windows, window, batch, ...) stacks, shuffled per epoch.

        The tail is wrap-padded to a whole window and masked (same zero-drop
        contract as the SPMD path's ``shape_epoch_data``): returns
        ``(xw, yw, mw)`` where ``mw`` is 1.0 for real rows, 0.0 for padding.
        """
        x = np.asarray(shard[self.features_col])
        y = np.asarray(shard[self.label_col])
        perm = np.random.default_rng(epoch_seed).permutation(len(x))
        return self._stack_windows(x[perm], y[perm], window)

    def _stack_windows(self, x: np.ndarray, y: np.ndarray,
                       window: Optional[int] = None
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Shape already-ordered rows into (num_windows, window, batch, ...)
        stacks with the shared wrap-pad + mask contract (no shuffle — the
        elastic lease path shuffles globally at the driver)."""
        window = self.window if window is None else int(window)
        # one window = one "batch" of the shared padder, then split it
        xw, yw, mw, nwin = batch_epoch_data(x, y, window * self.batch_size)
        shape = (nwin, window, self.batch_size)
        return (xw.reshape(shape + x.shape[1:]),
                yw.reshape(shape + y.shape[1:]),
                mw.reshape(shape))


class SequentialWorker(Worker):
    """Plain local training, no PS (reference: ``workers.py ::
    SequentialWorker`` — what SingleTrainer ships to its one partition)."""

    def train(self, index: int, shard: Dict[str, np.ndarray]) -> dict:
        model = self._ensure_model()
        window_fn = self._build_window_fn()
        # the window fn donates params/opt_state; _params0 is the shared
        # template (share_compiled_state) and must survive — train on a copy
        params = jax.tree_util.tree_map(jnp.array, self._params0)
        opt_state = self._tx.init(params)
        rng = jax.random.PRNGKey(self.seed + index)
        for epoch in range(self.num_epoch):
            # window==1: every batch is its own scan step
            xw, yw, mw = self._shard_to_windows(shard, 1, self.seed + epoch)
            for i in range(len(xw)):
                rng, sub = jax.random.split(rng)
                params, opt_state, loss = window_fn(
                    params, opt_state, jnp.asarray(xw[i]), jnp.asarray(yw[i]),
                    jnp.asarray(mw[i]), sub)
                self.history.append(float(loss))
        return {"weights": self._params_to_weights(params),
                "history": self.history}


class PSWorker(Worker):
    """Base for PS-connected workers (reference: the ``*Worker`` family).

    Protocol (reference parity, §2.4): 1-byte opcodes on a persistent TCP
    connection — ``'p'`` pull → PS replies {weights, clock}; ``'c'`` commit →
    worker sends {delta, worker_id, clock}; ``'q'`` quit.
    """

    ALGORITHM = "downpour"

    def __init__(self, model_blob, worker_optimizer, loss, ps_host: str,
                 ps_port: int, communication_window: int = 5,
                 wire_dtype: Optional[str] = None,
                 wire_topk: float = 0.01,
                 wire_topk_dtype: Optional[str] = None,
                 comm_overlap: bool = False,
                 fault_injection: Optional[dict] = None,
                 shard_plan=None, shard_addrs=None,
                 recovery: bool = False,
                 retry_policy: Optional[RetryPolicy] = None,
                 row_sparse_tables=None,
                 partition_windows: int = 0, **kw):
        super().__init__(model_blob, worker_optimizer, loss, **kw)
        self.ps_host = ps_host
        self.ps_port = ps_port
        # PS sharding (ps_sharding.py): when the driver partitioned the
        # center over N shard servers, the worker talks to all of them
        # through one ShardedPSClient (scatter commits / gather pulls) —
        # built fresh per connect(); None keeps the single-socket path
        # below untouched
        self.shard_plan = shard_plan
        self.shard_addrs = shard_addrs
        self._shard_client: Optional[ShardedPSClient] = None
        self.window = int(communication_window)
        # comm_overlap: pipeline the transport — one combined 'u'
        # (commit+pull) round trip per window, received while the NEXT
        # window's jitted compute runs, so the DCN latency hides behind the
        # device (see _train_epoch_overlapped for the staleness contract)
        self.comm_overlap = bool(comm_overlap)
        #: messages initiated toward the PS (each 'p'/'c'/'u' counts 1) —
        #: the transport-cost observable the tests read
        self.transport_ops = 0
        # fault injection (SURVEY §5: the reference had none): worker id ->
        # (kind, budget) — the worker faults at its budget+1-th commit with
        # 'raise' (legacy int form), 'exit' (dies mid-frame) or 'hang'
        # (wedges until _hang_released).  Keys arrive as strings and tuples
        # as lists after a JSON round-trip (process engine).
        self.fault_injection = parse_fault_injection(fault_injection)
        #: set at teardown to unblock a worker wedged on an injected 'hang'
        self._hang_released = threading.Event()
        self._commits = 0
        # e.g. "bfloat16": halve commit bytes; "int8": quarter them with
        # per-tensor affine quantization + error feedback (see commit()).
        # "topk": ship only the wire_topk·n largest-magnitude coordinates of
        # the flat delta as a sparse (indices, values) commit with error
        # feedback — O(k) bytes and O(k) PS apply instead of O(n); values
        # optionally bf16/int8-coded on top (wire_topk_dtype).  Resolved
        # eagerly so a bad name fails at construction, not mid-training in
        # a worker thread.
        self._topk_density: Optional[float] = None
        if wire_dtype == "topk":
            density = float(wire_topk)
            if not 0.0 < density <= 1.0:
                raise ValueError(
                    f"wire_topk must be a density in (0, 1], got {density}")
            if wire_topk_dtype not in (None, "bfloat16", "int8"):
                raise ValueError(
                    "wire_topk_dtype must be None, 'bfloat16' or 'int8', "
                    f"got {wire_topk_dtype!r}")
            self._topk_density = density
            wire_dtype = None
        self.wire_topk_dtype = wire_topk_dtype
        self._quantize = wire_dtype == "int8"
        self.wire_dtype = (networking._dtype_of(wire_dtype)
                           if wire_dtype is not None and not self._quantize
                           else None)
        # row-sparse embedding commits (row_sparse= on the async trainers —
        # streaming.py resolves the knob to weight-list indices): each
        # listed table's window delta ships as an EXACT
        # networking.RowSparseDelta (touched rows only — support detected
        # on device from the delta itself, so it is exact for any
        # optimizer), alongside dense deltas for the rest of the model in
        # the SAME 1-RTT 'u' window.  Delta family only (the elastic
        # force is dense by construction), incompatible with the lossy
        # wire codings (exact is the point) and with comm_overlap (the
        # row-sparse step is itself one blocking 'u' round trip).
        self.row_sparse_tables: Tuple[int, ...] = ()
        self._rs_shapes: Dict[int, tuple] = {}
        self._rs_window_fn = None
        if row_sparse_tables:
            tables = sorted({int(t) for t in row_sparse_tables})
            if not self._ROW_SPARSE_OK:
                raise ValueError(
                    "row_sparse_tables applies to the delta family "
                    "(DOWNPOUR/ADAG/DynSGD); the elastic family's force "
                    f"term is dense by construction ({type(self).__name__})")
            if (self._topk_density is not None or self._quantize
                    or self.wire_dtype is not None):
                raise ValueError(
                    "row_sparse_tables is the exact sparse profile and does "
                    "not compose with lossy wire_dtype codings "
                    "(bfloat16/int8/topk) — use wire_dtype=None")
            if self.comm_overlap:
                raise ValueError(
                    "row_sparse_tables uses the serial 1-RTT 'u' window "
                    "loop; comm_overlap must be off")
            shapes = [tuple(np.shape(w)) for w in self.model_blob["weights"]]
            for t in tables:
                if not 0 <= t < len(shapes):
                    raise ValueError(
                        f"row_sparse_tables names weight {t}; model has "
                        f"{len(shapes)} weights")
                if len(shapes[t]) < 2:
                    raise ValueError(
                        f"row_sparse_tables weight {t} is {shapes[t]} — row "
                        "sparsity needs a (rows, dim...) table")
            self.row_sparse_tables = tuple(tables)
            self._rs_shapes = {t: shapes[t] for t in tables}
        self._residual: Optional[List[np.ndarray]] = None
        # top-k error-feedback state: exactly one of the two residuals is
        # live per worker — the DEVICE flat residual (delta family: selection
        # runs jitted on device, only k values + indices are fetched) or the
        # HOST flat residual (elastic family / direct commit() calls).
        self._residual_dev = None
        self._residual_flat: Optional[np.ndarray] = None
        self._topk_window_fn = None
        self._wire_k: Optional[int] = None
        self._wire_total: Optional[int] = None
        self._wire_shapes: Optional[List[tuple]] = None
        #: (indices, applied f32 values) of the last in-flight 'u' commit —
        #: re-credited into the residual if a respawned PS gen-rejects it
        self._inflight = None
        self._sock: Optional[socket.socket] = None
        self._pool: Optional[networking.BufferPool] = None
        self._send_pool: Optional[networking.BufferPool] = None
        self._last_clock = 0
        # reconnect-resume (resilience.py): with recovery on, a mid-run
        # transport fault re-dials the PS under retry_policy and re-syncs
        # instead of killing the worker — PSShardDown/ConnectionError only
        # after the recovery deadline.  The generation learned from every
        # reply stamps commits, so a restarted PS can reject the in-flight
        # windows its restart rolled back.
        self.recovery = bool(recovery)
        self.retry_policy = retry_policy
        self._gen: Optional[int] = None
        # duplicate-reply baseline: last reply clock on the CURRENT
        # connection (reset on every dial) — a restarted PS's clock
        # legitimately restarts below the monotonic _last_clock view, but
        # within one connection genuine replies never run backwards
        self._conn_clock: Optional[int] = None
        self.resumes = 0
        self.stale_replies = 0
        self.clock_regressions = 0
        #: sparse commits whose gen-rejection re-credited the EF residual
        self.recredits = 0
        # partition tolerance (partition_windows > 0 — resilience.py):
        # instead of blocking in reconnect-resume the moment the PS link
        # dies, the worker keeps computing for up to partition_windows
        # windows, SUMMING each window's as-applied dense delta into a
        # pending buffer, and serving pulls from the last good center.  One
        # cheap heal probe per window ('h' round trip on a fresh dial);
        # on heal the buffer flushes as ONE commit stamped with the
        # generation seen at partition onset — a PS respawned during the
        # partition gen-rejects it (the existing handshake), so the
        # buffered mass is bounded loss, never corruption.  Budget
        # exhausted → blocking resume (when recovery) and finally a typed
        # resilience.Partitioned, distinct from PSShardDown: the PATH is
        # gone, the endpoint is probably fine.  Serial single-socket
        # transport only: the sharded client's reconnect-resume already
        # covers its path (blocking), and the overlap/row-sparse loops
        # have in-flight state a buffer cannot represent.
        self.partition_windows = int(partition_windows or 0)
        if self.partition_windows < 0:
            raise ValueError("partition_windows must be >= 0")
        if self.partition_windows:
            if self.shard_addrs is not None:
                raise ValueError(
                    "partition_windows applies to the single-socket PS "
                    "link; the sharded client heals by reconnect-resume "
                    "(recovery=True) instead")
            if self.comm_overlap:
                raise ValueError(
                    "partition_windows uses the serial per-window "
                    "transport; comm_overlap must be off")
            if self.row_sparse_tables:
                raise ValueError(
                    "partition_windows buffers dense as-applied deltas; "
                    "row_sparse_tables commits cannot be buffered")
        self._pending: Optional[List[np.ndarray]] = None
        self._pending_windows = 0
        self._pending_gen: Optional[int] = None
        self._cached_center: Optional[List[np.ndarray]] = None
        #: partition episodes entered / pending buffers reconciled on heal
        self.partitions = 0
        self.reconciliations = 0

    # -- wire ---------------------------------------------------------------
    def _connect_policy(self, attempts: Optional[int] = None,
                        backoff: Optional[float] = None,
                        policy: Optional[RetryPolicy] = None) -> RetryPolicy:
        if policy is None:
            policy = self.retry_policy or DEFAULT_CONNECT_POLICY
        kw = {}
        if attempts is not None:
            kw["attempts"] = max(int(attempts), 1)
        if backoff is not None:
            kw["backoff"] = float(backoff)
        return policy.replace(**kw) if kw else policy

    def connect(self, attempts: Optional[int] = None,
                backoff: Optional[float] = None,
                policy: Optional[RetryPolicy] = None):
        """Dial the PS with bounded *jittered* retry-with-backoff
        (resilience.RetryPolicy): a worker that starts before the PS accept
        loop is up — or reconnects across a PS restart — retries with
        exponential backoff (~9 s worst case at the defaults) instead of
        dying on the first handshake fault, and the jitter keeps N workers
        from re-dialing a restarted PS in lockstep.  Retried faults:
        ``ConnectionRefusedError`` (nothing listening yet), plus
        ``ConnectionResetError`` and ``socket.timeout`` — a PS mid-start()
        can accept the TCP handshake and then reset or stall before its
        handler thread exists.  Every fresh connection gets a fresh
        receive-buffer pool: center pulls decode into reusable preallocated
        memory.

        With ``shard_addrs`` set the worker instead dials every PS shard
        through a ``ShardedPSClient`` (same retry policy per shard; one
        socket + one buffer pool per shard)."""
        if self.shard_addrs is not None:
            self._shard_client = ShardedPSClient(
                self.shard_plan, self.shard_addrs,
                recovery=self.recovery, policy=self.retry_policy)
            self._shard_client.connect(attempts=attempts, backoff=backoff,
                                       policy=policy)
            return
        pol = self._connect_policy(attempts, backoff, policy)
        try:
            self._sock = dial(self.ps_host, self.ps_port, pol)
        except RETRYABLE_CONNECT as e:
            raise ConnectionError(
                f"PS at {self.ps_host}:{self.ps_port} refused "
                f"{pol.describe()} connection attempts") from e
        self._pool = networking.BufferPool()
        self._send_pool = networking.BufferPool()
        self._conn_clock = None

    def _with_resume(self, fn, fault: BaseException):
        """Mid-run reconnect-resume (single-socket path): repeatedly
        (re-dial + ``fn()``) under the recovery policy.  Dial and first use
        retry as ONE unit — a dial can succeed against a dead listener's
        kernel backlog and only fail on first use.  ``ConnectionError``
        escapes only once the policy (deadline/attempts) is exhausted."""
        pol = self.retry_policy or DEFAULT_RECOVERY_POLICY
        t0 = time.monotonic()
        last = fault
        for d in pol.delays():
            try:
                if self._sock is not None:
                    try:
                        self._sock.close()
                    except OSError:
                        pass
                    self._sock = None
                self._sock = networking.connect(self.ps_host, self.ps_port)
                self._pool = networking.BufferPool()
                self._send_pool = networking.BufferPool()
                self._conn_clock = None
                out = fn()
                self.resumes += 1
                return out
            except (ConnectionError, OSError, ValueError,
                    socket.timeout) as e:
                last = e
                if (pol.deadline is not None
                        and time.monotonic() - t0 + d > pol.deadline):
                    break
                time.sleep(d)
        raise ConnectionError(
            f"PS at {self.ps_host}:{self.ps_port} unrecovered after "
            f"{pol.describe()} reconnect attempts") from last

    def _sync_reply(self, msg):
        """Fold a reply's (gen, clock) into this worker's view: generation
        follows the server; the clock stays monotonic (a restored — older —
        PS clock must not roll the staleness baseline backwards)."""
        g = msg.get("gen")
        if g is not None:
            self._gen = int(g)
        c = int(msg["clock"])
        self._conn_clock = c
        if c < self._last_clock:
            self.clock_regressions += 1
        self._last_clock = max(self._last_clock, c)

    def disconnect(self):
        if self._shard_client is not None:
            self._shard_client.disconnect()
            return
        if self._sock is not None:
            try:
                networking.send_opcode(self._sock, b"q")
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    def pull(self) -> List[np.ndarray]:
        """'p': fetch center weights + PS clock (reference: Worker.pull).

        The reply decodes through the connection's buffer pool: the returned
        weights are zero-copy VIEWS into reusable memory, valid until the
        next receive on this connection — callers move them to device (or
        consume them arithmetically) before their next transport call.

        Sharded: one 'p' per shard (every request in flight before any reply
        is read), replies gathered into the full weight list.
        """
        if self._shard_client is not None:
            weights = self._shard_client.pull()
            self._last_clock = self._shard_client.max_clock
            self.transport_ops += self._shard_client.num_shards
            return weights
        def do_pull():
            networking.send_opcode(self._sock, b"p")
            return networking.recv_data(self._sock, pool=self._pool)

        try:
            msg = do_pull()
        except (ConnectionError, OSError, ValueError) as e:
            if self.partition_windows and self._cached_center is not None:
                # partitioned: serve the last good center (copies — the
                # cache must survive the next real receive); the window
                # trains one partition staler, the same class of staleness
                # the async rules already absorb
                return [w.copy() for w in self._cached_center]
            if not self.recovery:
                raise
            msg = self._with_resume(do_pull, e)
        self._sync_reply(msg)
        self.transport_ops += 1
        if self.partition_windows:
            # pool-backed views are only valid until the next receive;
            # the partition cache needs owned copies
            self._cached_center = [np.array(w, copy=True)
                                   for w in msg["weights"]]
        return msg["weights"]

    # -- sparse top-k compression (wire_dtype="topk") ------------------------
    #: delta-family workers select the top-k ON DEVICE (delta = after − base
    #: inside the jitted window program); the elastic family computes its
    #: force term on the host and selects there
    _DEVICE_TOPK = False
    #: error feedback fits ACCUMULATIVE commits (window deltas: unsent mass
    #: stays valid to add later).  The elastic family's force e = α·(x − x̃)
    #: is recomputed from current state every window — its unsent components
    #: are still present in the next force, so a residual would double-count
    #: them; the elastic workers sparsify WITHOUT a residual instead (the
    #: spring stays stretched until its components are selected).
    _TOPK_EF = True
    #: row-sparse embedding commits need the window delta itself to be the
    #: committed quantity (delta family); the elastic force is dense
    _ROW_SPARSE_OK = False

    # -- row-sparse embedding commits (row_sparse_tables) --------------------
    def _build_rowsparse_window_fn(self):
        """Row-sparse variant of the window fn: runs the same window scan,
        then computes each listed table's full window delta and its
        touched-row mask ON DEVICE (``any(delta != 0)`` per row).  Support
        detection from the delta itself makes the profile EXACT for any
        optimizer — untouched rows are exactly zero by inspection, not by
        assumption about the update rule — and only the mask (num_rows
        bools per table) plus the touched rows' O(k·dim) delta block ever
        reach the host; the full table is never fetched.

        jitted (params, opt_state, xw, yw, mw, rng) -> (params, opt_state,
        loss, [table deltas], [row masks]); donates params/opt_state as
        the plain window fn.
        """
        if self._rs_window_fn is not None:
            return self._rs_window_fn
        tables = self.row_sparse_tables
        window = self._make_window_body()

        def rs_window(params, opt_state, xw, yw, mw, rng):
            leaves = jax.tree_util.tree_leaves(params)
            bases = [leaves[t] for t in tables]
            params, opt_state, loss = window(params, opt_state, xw, yw, mw,
                                             rng)
            new_leaves = jax.tree_util.tree_leaves(params)
            deltas = [new_leaves[t].astype(jnp.float32)
                      - b.astype(jnp.float32)
                      for t, b in zip(tables, bases)]
            masks = [jnp.any(d != 0.0, axis=tuple(range(1, d.ndim)))
                     for d in deltas]
            return params, opt_state, loss, deltas, masks

        self._rs_window_fn = jax.jit(rs_window, donate_argnums=(0, 1))
        return self._rs_window_fn

    def _fetch_dense_weights(self, params) -> List[Optional[np.ndarray]]:
        """ONE bulk device→host fetch of every NON-table leaf: a list in
        weight order with None at table positions — the big embedding
        tables never ride the per-window fetch."""
        skip = set(self.row_sparse_tables)
        leaves = jax.tree_util.tree_leaves(params)
        fetched = iter(jax.device_get(
            [l for i, l in enumerate(leaves) if i not in skip]))
        return [None if i in skip else next(fetched)
                for i in range(len(leaves))]

    def _rowsparse_window_step(self, params, opt_state, xw, yw, mw, rng,
                               index: int):
        """One serial window under row-sparse commits: dense non-table
        deltas + exact row-sparse table deltas, committed in ONE combined
        'u' round trip whose reply (the fresh center) re-bases the next
        window — the serial loop's commit + re-pull, atomically."""
        fn = self._build_rowsparse_window_fn()
        skip = set(self.row_sparse_tables)
        before = self._fetch_dense_weights(params)
        params, opt_state, loss, rs_deltas, rs_masks = fn(
            params, opt_state, jnp.asarray(xw), jnp.asarray(yw),
            jnp.asarray(mw), rng)
        # one bulk fetch for the dense after-weights AND the per-table row
        # masks; the touched rows' values follow as one O(k·dim) gather
        # per table
        leaves = jax.tree_util.tree_leaves(params)
        dense_after, masks = jax.device_get(
            ([l for i, l in enumerate(leaves) if i not in skip], rs_masks))
        after = iter(dense_after)
        delta: List[Any] = []
        ti = 0
        for i in range(len(leaves)):
            if i in skip:
                rows = np.flatnonzero(masks[ti]).astype(np.int32)
                if rows.size:
                    vals = np.asarray(
                        jax.device_get(rs_deltas[ti][jnp.asarray(rows)]),
                        np.float32)
                else:
                    vals = np.zeros((0,) + self._rs_shapes[i][1:],
                                    np.float32)
                delta.append(networking.RowSparseDelta(
                    rows, vals, self._rs_shapes[i][0]))
                ti += 1
            else:
                delta.append(np.asarray(next(after), np.float32)
                             - before[i])
        _applied, center = self.update(delta, index)
        return self._weights_to_params(center), opt_state, loss

    def _ensure_topk(self) -> int:
        """Resolve k and the flat layout (density · total elements, at
        least 1); indices ride as int32 on the wire.  The layout comes from
        the model blob's weight list — the wire order every pull/commit
        already uses — so no model deserialization is needed."""
        if self._wire_k is None:
            self._wire_shapes = [tuple(np.shape(w))
                                 for w in self.model_blob["weights"]]
            total = sum(int(np.prod(s, dtype=np.int64))
                        for s in self._wire_shapes)
            if total >= 2 ** 31:
                raise ValueError(
                    "wire_dtype='topk' indexes the flat weight vector with "
                    f"int32; {total} elements overflow it")
            self._wire_total = total
            self._wire_k = max(1, min(total, int(np.ceil(
                self._topk_density * total))))
        return self._wire_k

    def _build_topk_window_fn(self):
        """The top-k variant of the window fn: runs the same scan, then a
        device-side ``jax.lax.top_k``-by-magnitude pass over the flat delta
        (after − base + residual), so only k values + k int32 indices ever
        leave the device — the full delta is never fetched to host.  Value
        coding (bf16 cast / int8 quantization) also runs on device, and the
        residual keeps both the unsent mass and the coding error (EF-SGD).

        jitted (params, opt_state, residual, xw, yw, mw, rng) ->
        (params, opt_state, loss, codes, indices, scale, residual');
        donates params/opt_state (as the plain window fn) and the residual.
        """
        if self._topk_window_fn is not None:
            return self._topk_window_fn
        k = self._ensure_topk()
        code = self.wire_topk_dtype
        window = self._make_window_body()

        def flatten(params):
            return jnp.concatenate(
                [l.reshape(-1).astype(jnp.float32)
                 for l in jax.tree_util.tree_leaves(params)])

        def topk_window(params, opt_state, residual, xw, yw, mw, rng):
            base = flatten(params)
            params, opt_state, loss = window(params, opt_state, xw, yw, mw,
                                             rng)
            eff = flatten(params) - base + residual
            _, ai = jax.lax.top_k(jnp.abs(eff), k)
            ai = jnp.sort(ai)  # ascending: bisection + scatter friendly
            vals = eff[ai]
            scale = jnp.float32(1.0)
            if code == "int8":
                scale = jnp.max(jnp.abs(vals)) / 127.0
                scale = jnp.where(scale <= 0, jnp.float32(1.0), scale)
                codes = jnp.clip(jnp.round(vals / scale),
                                 -127, 127).astype(jnp.int8)
                applied = codes.astype(jnp.float32) * scale
            elif code == "bfloat16":
                codes = vals.astype(jnp.bfloat16)
                applied = codes.astype(jnp.float32)
            else:
                codes = vals
                applied = vals
            residual = eff.at[ai].add(-applied)
            return (params, opt_state, loss, codes,
                    ai.astype(jnp.int32), scale, residual)

        self._topk_window_fn = jax.jit(topk_window, donate_argnums=(0, 1, 2))
        return self._topk_window_fn

    def _run_topk_window(self, params, opt_state, xw, yw, mw, rng):
        """Dispatch one top-k window on the device.  Returns the device
        handles — callers fetch ``codes``/``idx``/``scale`` (k elements,
        not n) when they need them on the host, which lets the overlapped
        loop receive the previous reply first."""
        fn = self._build_topk_window_fn()
        if self._residual_dev is None:
            self._residual_dev = jnp.zeros((self._wire_total,), jnp.float32)
        (params, opt_state, loss, codes, idx, scale,
         self._residual_dev) = fn(params, opt_state, self._residual_dev,
                                  jnp.asarray(xw), jnp.asarray(yw),
                                  jnp.asarray(mw), rng)
        return params, opt_state, loss, codes, idx, scale

    def _fetch_sparse(self, codes, idx, scale) -> networking.SparseDelta:
        """Materialize a device selection as the wire node: ONE device_get
        of (k values, k indices, scale)."""
        codes_np, idx_np, scale_np = jax.device_get((codes, idx, scale))
        return networking.SparseDelta(
            idx_np, codes_np, self._wire_total,
            float(scale_np) if self.wire_topk_dtype == "int8" else None)

    def _densify(self, idx, vals) -> List[np.ndarray]:
        """Sparse (idx, f32 values) → weight-shaped dense list (the
        as-applied delta ``commit`` returns, keeping elastic coupling and
        the overlap rebase exact)."""
        flat = np.zeros((self._wire_total,), np.float32)
        flat[np.asarray(idx, np.int64)] = vals
        out, off = [], 0
        for s in self._wire_shapes:
            n = int(np.prod(s, dtype=np.int64))
            out.append(flat[off:off + n].reshape(s))
            off += n
        return out

    def _recredit(self, idx: np.ndarray, vals: np.ndarray):
        """Return dropped as-applied sparse mass to the error-feedback
        residual: a respawned PS gen-rejected the commit, so the mass never
        reached the center and must ship again — without this, EF would
        believe it applied and the mass would be lost for good."""
        if not self._TOPK_EF:
            return  # elastic family: the recomputed spring force re-applies
        if self._residual_dev is not None:
            self._residual_dev = self._residual_dev.at[
                jnp.asarray(np.asarray(idx, np.int32))].add(
                jnp.asarray(np.asarray(vals, np.float32)))
        else:
            if self._residual_flat is None:
                self._residual_flat = np.zeros((self._wire_total,),
                                               np.float32)
            np.add.at(self._residual_flat, np.asarray(idx, np.int64),
                      np.asarray(vals, np.float32))
        self.recredits += 1

    def _prepare_topk_commit(self, delta, worker_id: int):
        """Top-k wire form of a commit: either a device-selected
        ``SparseDelta`` (delta family) or a host-side ``topk_select`` over
        the dense delta + flat residual (elastic family, direct callers)."""
        k = self._ensure_topk()
        if isinstance(delta, networking.SparseDelta):
            sp = delta
            idx = np.asarray(sp.indices)
            applied_vals = sp.f32_values()
        else:
            flat = np.concatenate(
                [np.asarray(d, np.float32).reshape(-1) for d in delta])
            if flat.size != self._wire_total:
                raise ValueError(
                    f"delta carries {flat.size} elements, model has "
                    f"{self._wire_total}")
            if self._TOPK_EF:
                if self._residual_flat is None:
                    self._residual_flat = np.zeros((self._wire_total,),
                                                   np.float32)
                eff = flat + self._residual_flat
                idx, wire, applied_vals, scale, self._residual_flat = \
                    topk_select(eff, k, self.wire_topk_dtype)
            else:
                idx, wire, applied_vals, scale, _ = topk_select(
                    flat, k, self.wire_topk_dtype)
            sp = networking.SparseDelta(idx, wire, self._wire_total, scale)
        msg = {"delta": sp, "worker_id": worker_id,
               "clock": self._last_clock}
        if self._gen is not None:
            msg["gen"] = self._gen
        self._inflight = (np.array(idx, np.int64, copy=True),
                          np.array(applied_vals, np.float32, copy=True))
        return msg, self._densify(idx, applied_vals)

    def _inject_fault(self, worker_id: int, kind: str):
        """Realize one injected fault at this commit (see ``FAULT_KINDS``).

        'hang' wedges the worker with its PS connection(s) left open — the
        signature of a stuck host/device: no EOF for the server, no renewal
        for the lease ledger — until ``_hang_released`` is set at teardown
        (then the thread unwinds with a RuntimeError so it never completes
        work it abandoned).  'raise' hard-closes first so the unwind path's
        disconnect() is a no-op (no graceful b'q'): the PS sees a plain
        EOF.  'exit' additionally dies MID-FRAME — opcode plus half a
        commit frame, then an RST — the wire signature of a worker host
        falling over mid-send (the PS must drop that connection cleanly
        without a codec error; tests/test_elastic_workers.py), and raises
        SystemExit instead of RuntimeError.
        """
        if kind == "hang":
            self._hang_released.wait()
            raise RuntimeError(
                f"injected fault: worker {worker_id} hang released at "
                f"commit {self._commits}")
        if kind == "exit" and self._sock is not None:
            # die mid-frame: the torn half-commit exercises the PS
            # handler's half-frame disconnect path through the real engine
            try:
                frame = networking.encode_message(
                    {"delta": [np.zeros((4,), np.float32)],
                     "worker_id": worker_id, "clock": self._last_clock})
                self._sock.sendall(b"c" + frame[:max(9, len(frame) // 2)])
            except OSError:
                pass
            networking._hard_close(self._sock)
            self._sock = None
        if self._shard_client is not None:
            self._shard_client.abort()
        try:
            self._sock.close()
        except (OSError, AttributeError):
            pass
        self._sock = None
        if kind == "exit":
            raise SystemExit(
                f"injected fault: worker {worker_id} exits at commit "
                f"{self._commits}")
        raise RuntimeError(
            f"injected fault: worker {worker_id} dies at commit "
            f"{self._commits}")

    def _prepare_commit(self, delta: List[np.ndarray], worker_id: int):
        """Fault-injection gate + wire compression shared by 'c' and 'u'.
        Returns ``(msg, applied)``: the wire message and the delta the PS
        will actually apply after decompression (see ``commit``)."""
        self._commits += 1
        fault = self.fault_injection.get(worker_id)
        if fault is not None and self._commits > fault[1]:
            self._inject_fault(worker_id, fault[0])
        if self._topk_density is not None:
            return self._prepare_topk_commit(delta, worker_id)
        if self._quantize:
            if self._residual is None:
                self._residual = [np.zeros_like(d, dtype=np.float32)
                                  for d in delta]
            eff = [d.astype(np.float32) + r
                   for d, r in zip(delta, self._residual)]
            scales = [float(np.max(np.abs(e)) / 127.0) or 1.0 for e in eff]
            codes = [np.clip(np.rint(e / s), -127, 127).astype(np.int8)
                     for e, s in zip(eff, scales)]
            applied = [c.astype(np.float32) * s
                       for c, s in zip(codes, scales)]
            self._residual = [e - a for e, a in zip(eff, applied)]
            msg = {"delta": codes, "scales": scales,
                   "worker_id": worker_id, "clock": self._last_clock}
            if self._gen is not None:
                msg["gen"] = self._gen
            return (msg, applied)
        if self.wire_dtype is not None:
            delta = [d.astype(self.wire_dtype) for d in delta]
        msg = {"delta": delta, "worker_id": worker_id,
               "clock": self._last_clock}
        if self._gen is not None:
            # generation handshake: a PS respawned since our last reply
            # rejects this commit instead of applying it to the restored
            # center (the rolled-back windows are the bounded loss)
            msg["gen"] = self._gen
        # row-sparse entries ARE their as-applied form (the profile is
        # exact); dense entries normalize to f32
        return (msg, [d if isinstance(d, networking.RowSparseDelta)
                      else np.asarray(d, dtype=np.float32) for d in delta])

    def commit(self, delta: List[np.ndarray], worker_id: int):
        """'c': push a weight-shaped delta (reference: Worker.commit).

        Returns the delta the PS will actually APPLY (after any wire
        compression) so callers whose local state must stay coupled to the
        center — the elastic family subtracts what it committed — can use
        the as-applied value instead of the pre-compression one.

        ``wire_dtype="bfloat16"``: the delta is rounded to bf16 on the wire
        (half the DCN bytes; the PS upcasts before applying).

        ``wire_dtype="int8"``: per-tensor affine quantization — each tensor
        ships as int8 codes + one f32 scale (max|d|/127), a 4x byte cut —
        with ERROR FEEDBACK: the quantization error of every window is
        carried into the next window's delta, so compression noise
        telescopes instead of accumulating in the center (the 1-bit-SGD /
        EF-SGD recipe).  Lossy compression the reference's pickle transport
        had no counterpart for.

        ``wire_dtype="topk"``: sparse top-k selection — only the
        ``wire_topk``-density largest-magnitude coordinates of the flat
        delta ship (``networking.SparseDelta``: int32 indices + values,
        optionally bf16/int8-coded via ``wire_topk_dtype``), an O(k)
        commit on the wire AND at the PS apply.  Error feedback carries
        the unsent mass (delta family; the elastic force is stateful and
        selects without a residual).  ``delta`` may also be an
        already-selected ``SparseDelta`` (the device-side path).
        """
        msg, applied = self._prepare_commit(delta, worker_id)
        if self._shard_client is not None:
            self._shard_client.send_commit(msg)
            self.transport_ops += self._shard_client.num_shards
            return applied
        if self._pending_windows:
            # already partitioned: one cheap heal probe per window, then
            # either reconcile or keep buffering (until the budget runs out)
            if self._heal_probe():
                try:
                    self._flush_pending(worker_id)
                except (ConnectionError, OSError):
                    pass  # re-partitioned mid-flush: state still buffered
            if self._pending_windows:
                self._buffer_pending(applied, worker_id)
                return applied
        try:
            self._send_request(b"c", msg)
        except (ConnectionError, OSError):
            if not self.partition_windows:
                raise
            self.partitions += 1
            self._buffer_pending(applied, worker_id)
            return applied
        self.transport_ops += 1
        return applied

    def _send_request(self, op: bytes, msg) -> None:
        """Opcode + frame on the single socket, with reconnect-resume: a
        send-side fault re-dials and re-issues the same message (still
        stamped with the old generation — a restarted PS drops it and the
        next reply re-syncs us; bounded loss either way).  With
        ``partition_windows`` set the fault raises through instead — the
        caller buffers into the pending-commit path rather than blocking
        here."""

        def send():
            networking.send_opcode(self._sock, op)
            if self._send_pool is None:
                networking.send_data(self._sock, msg)
            else:
                # encode-side scratch pool: the commit re-serializes into a
                # reusable buffer (same wire bytes, no fresh output blob)
                networking.send_data(self._sock, msg, pool=self._send_pool)

        try:
            send()
        except (ConnectionError, OSError) as e:
            if self.partition_windows or not self.recovery:
                raise
            self._with_resume(send, e)

    # -- partition tolerance (partition_windows > 0) -------------------------
    def _heal_probe(self, timeout: float = 0.25) -> bool:
        """One cheap liveness round trip on a FRESH dial: 'h' answered
        within ``timeout`` means the path healed — the probe socket is
        adopted as the live connection (its reply re-syncs gen + clock).
        False means still partitioned; nothing changes."""
        sock = None
        try:
            sock = networking.connect(self.ps_host, self.ps_port)
            sock.settimeout(timeout)
            networking.send_opcode(sock, b"h")
            msg = networking.recv_data(sock)
            if not isinstance(msg, dict) or "clock" not in msg:
                raise ValueError("malformed heartbeat reply")
            sock.settimeout(None)
        except (ConnectionError, OSError, ValueError, socket.timeout):
            if sock is not None:
                try:
                    sock.close()
                except OSError:
                    pass
            return False
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
        self._sock = sock
        self._pool = networking.BufferPool()
        self._send_pool = networking.BufferPool()
        self._conn_clock = None
        self._sync_reply(msg)
        return True

    def _buffer_pending(self, applied: List[np.ndarray], worker_id: int):
        """Sum one window's as-applied dense delta into the pending buffer;
        escalate once the budget is spent.  ``applied`` is dense and
        weight-shaped for every wire family (top-k densifies), so one
        buffer shape serves them all."""
        if self._pending is None:
            # stamp the flush with the generation seen BEFORE the
            # partition: a PS respawned while we were dark must gen-reject
            # this mass (it was computed against the pre-respawn center)
            self._pending_gen = self._gen
            self._pending = [np.array(a, dtype=np.float32, copy=True)
                             for a in applied]
        else:
            for p, a in zip(self._pending, applied):
                p += np.asarray(a, dtype=np.float32)
        self._pending_windows += 1
        if self._pending_windows <= self.partition_windows:
            return
        # budget exhausted: block in reconnect-resume (when recovery is
        # on) and surface a typed Partitioned once that fails too
        if self.recovery:
            try:
                self._with_resume(
                    lambda: self._flush_pending(worker_id),
                    ConnectionError("partition budget exhausted"))
                return
            except ConnectionError as e:
                raise Partitioned(
                    (self.ps_host, self.ps_port),
                    detail="recovery deadline exhausted after the "
                           "pending-commit budget",
                    pending_windows=self._pending_windows) from e
        raise Partitioned((self.ps_host, self.ps_port),
                          pending_windows=self._pending_windows)

    def _flush_pending(self, worker_id: int):
        """Reconcile: ship the summed pending mass as ONE dense commit on
        the healed link, stamped with the partition-onset generation.
        Raises on transport fault — the buffer survives for the next probe."""
        if self._pending is None:
            return
        msg = {"delta": self._pending, "worker_id": worker_id,
               "clock": self._last_clock}
        if self._pending_gen is not None:
            msg["gen"] = self._pending_gen
        networking.send_opcode(self._sock, b"c")
        networking.send_data(self._sock, msg)
        self.transport_ops += 1
        self.reconciliations += 1
        self._pending = None
        self._pending_windows = 0
        self._pending_gen = None

    def update_begin(self, delta: List[np.ndarray], worker_id: int):
        """'u' part 1: ship the delta (same fault-injection + compression
        contract as ``commit``; returns the as-applied delta).  The PS's
        combined reply — the center *after this commit* + clock, snapshotted
        atomically — is collected by ``update_finish``; overlapped callers
        run device compute between the two halves so the round trip costs
        no device idle time.  Sharded: one 'u' per shard, every shard's
        reply left in flight — the per-shard pipelines advance in
        lockstep with the window loop."""
        msg, applied = self._prepare_commit(delta, worker_id)
        if self._shard_client is not None:
            self._shard_client.send_update(msg)
            self.transport_ops += self._shard_client.num_shards
            return applied
        self._send_request(b"u", msg)
        self.transport_ops += 1
        return applied

    def update_finish(self) -> List[np.ndarray]:
        """'u' part 2: receive the center+clock reply for the
        ``update_begin`` in flight (pool-decoded views, as ``pull``;
        sharded: drain every shard's reply and gather).

        Reconnect-resume: if the reply dies with the connection, its window
        may or may not have applied (bounded loss) — re-dial and re-sync
        with a plain pull, whose reply stands in for the lost one.  With
        recovery on, duplicated 'u' replies (chaos proxies replay them) are
        discarded: a genuine combined reply always advances the clock,
        because our own commit bumped it."""
        if self._shard_client is not None:
            weights = self._shard_client.recv_update()
            self._last_clock = max(self._last_clock,
                                   self._shard_client.max_clock)
            # residual re-sync across a shard restart: shards that
            # gen-rejected the in-flight sparse commit dropped their split
            # of it — re-credit exactly those coordinates (owner-shard
            # lookup by flat-index bisection) so error feedback ships the
            # mass again instead of losing it
            if self._inflight is not None and any(
                    self._shard_client.last_stale):
                idx, vals = self._inflight
                owner = self.shard_plan.shard_of_flat(idx)
                mask = np.asarray(self._shard_client.last_stale,
                                  bool)[owner]
                if mask.any():
                    self._recredit(idx[mask], vals[mask])
            self._inflight = None
            return weights
        resumed = False
        try:
            msg = networking.recv_data(self._sock, pool=self._pool)
        except (ConnectionError, OSError, ValueError) as e:
            if not self.recovery:
                raise

            # the in-flight 'u' reply died with the connection — re-sync
            # with a plain pull on the fresh connection
            def resync():
                networking.send_opcode(self._sock, b"p")
                return networking.recv_data(self._sock, pool=self._pool)

            msg = self._with_resume(resync, e)
            self.transport_ops += 1
            resumed = True
        if self.recovery and not resumed:
            # duplicate-reply discard against the PER-CONNECTION clock
            # baseline ("stale"-marked gen rejections are exempt — they
            # legitimately leave the clock unchanged)
            while (not msg.get("stale")
                   and self._conn_clock is not None
                   and int(msg["clock"]) <= self._conn_clock):
                self.stale_replies += 1
                msg = networking.recv_data(self._sock, pool=self._pool)
        self._sync_reply(msg)
        # residual re-sync across a PS restart: a 'stale'-marked reply means
        # the restarted server gen-rejected (dropped) the in-flight sparse
        # commit — re-credit its as-applied mass into the error-feedback
        # residual so it ships again.  A resumed pull re-sync stays silent:
        # that commit's fate is unknown (the bounded-loss class).
        if (not resumed and msg.get("stale")
                and self._inflight is not None):
            self._recredit(*self._inflight)
        self._inflight = None
        return msg["weights"]

    def update(self, delta: List[np.ndarray], worker_id: int):
        """Blocking combined commit+pull: ONE round trip where the serial
        'c'+'p' pair pays a send plus a full round trip.  Returns
        ``(applied_delta, center_weights)``."""
        applied = self.update_begin(delta, worker_id)
        return applied, self.update_finish()

    # -- the training loop ---------------------------------------------------
    def train(self, index: int, shard: Dict[str, np.ndarray],
              initial_state=None, epoch_range=None) -> dict:
        """Run the PS-connected minibatch loop.

        ``initial_state``: optional ``(params, opt_state)`` to continue from
        (checkpoint resume / epoch-wave execution); default is the reference
        behavior — pull the center and start a fresh optimizer.
        ``epoch_range``: optional ``(start, stop)`` slice of the epoch loop
        so the driver can checkpoint between epoch waves.  Per-epoch RNG is
        derived by folding the epoch index, so a resumed run sees the same
        dropout/shuffle randomness as an uninterrupted one.
        """
        window_fn = self._build_window_fn()
        self.connect()
        try:
            if initial_state is None:
                center = self.pull()
                params = self._weights_to_params(center)
                opt_state = self._tx.init(params)
            else:
                params, opt_state = initial_state
                # the window fn DONATES its params/opt_state arguments; the
                # driver keeps this state object across waves (fault
                # tolerance falls back to it if this worker dies) — train
                # on a device copy so the original stays materializable
                params = jax.tree_util.tree_map(jnp.array, params)
                opt_state = jax.tree_util.tree_map(jnp.array, opt_state)
                # sync the PS clock (DynSGD staleness baseline); the weights
                # double as the overlap loop's initial center snapshot
                center = self.pull()
            start, stop = (epoch_range if epoch_range is not None
                           else (0, self.num_epoch))
            for epoch in range(start, stop):
                xw, yw, mw = self._shard_to_windows(
                    shard, self.window, self.seed + 1000 * epoch + index)
                rng = jax.random.fold_in(
                    jax.random.PRNGKey(self.seed + 100 + index), epoch)
                if self.comm_overlap:
                    params, opt_state, center = self._train_epoch_overlapped(
                        window_fn, params, opt_state, xw, yw, mw, rng,
                        index, center)
                else:
                    for i in range(len(xw)):
                        rng, sub = jax.random.split(rng)
                        params, opt_state, loss = self._window_step(
                            window_fn, params, opt_state, xw[i], yw[i],
                            mw[i], sub, index)
                        self.history.append(float(loss))
        finally:
            self.disconnect()
        return {"history": self.history, "state": (params, opt_state)}

    def _window_step(self, window_fn, params, opt_state, xw, yw, mw, rng,
                     index: int):
        raise NotImplementedError

    # -- elastic lease loop ---------------------------------------------------
    def compile_windows(self, x_sample: np.ndarray,
                        y_sample: np.ndarray) -> float:
        """Compile the window program off the training clock; returns the
        measured wall-clock seconds of the (compile + one window) call.

        Elastic runs measure lease deadlines from the moment a lease is
        acquired; without this, the first window of the run pays the jit
        trace+compile *inside* a live deadline and a healthy worker can
        read as wedged.  The returned time seeds the ledger's
        pre-first-renewal window estimate (``LeaseLedger.default_window_s``)
        — deliberately an OVERestimate (it includes the compile), so cold
        deadlines err generous and the per-worker EWMA tightens them from
        the first real renewal on.  Donation-safe: runs on throwaway
        copies.  Shared across workers via ``share_compiled_state`` (the
        executable caches on the shared function object)."""
        self._ensure_model()
        # np → jnp.asarray, exactly as the real window loop converts its
        # stacks (same dtype demotion, same compiled signature)
        xw = jnp.asarray(np.zeros(
            (self.window, self.batch_size) + x_sample.shape[1:],
            x_sample.dtype))
        yw = jnp.asarray(np.zeros(
            (self.window, self.batch_size) + y_sample.shape[1:],
            y_sample.dtype))
        mw = jnp.asarray(np.zeros((self.window, self.batch_size),
                                  np.float32))
        params = jax.tree_util.tree_map(jnp.array, self._params0)
        opt_state = self._tx.init(params)
        rng = jax.random.PRNGKey(0)
        t0 = time.monotonic()
        if self._topk_density is not None and self._DEVICE_TOPK:
            self._ensure_topk()
            fn = self._build_topk_window_fn()
            residual = jnp.zeros((self._wire_total,), jnp.float32)
            out = fn(params, opt_state, residual, xw, yw, mw, rng)
        elif self.row_sparse_tables:
            out = self._build_rowsparse_window_fn()(params, opt_state, xw,
                                                    yw, mw, rng)
        else:
            out = self._build_window_fn()(params, opt_state, xw, yw, mw, rng)
        jax.block_until_ready(out)
        return time.monotonic() - t0

    def train_leases(self, worker_id: int, ledger, data_fn,
                     initial_state=None) -> dict:
        """The elastic worker loop (``elastic=True`` — resilience.py):
        acquire a lease from the ``LeaseLedger``, train its windows with the
        per-algorithm serial ``_window_step`` (commit + pull per window),
        renew the lease once per committed window (the heartbeat rides the
        commit cadence — no extra transport), complete it, repeat until the
        ledger's epoch runs dry.

        A ``renew`` returning False means the lease was revoked (this
        worker was presumed dead or wedged and a survivor stole the lease):
        the rest of the lease is abandoned — the stealer's completion is
        the one the exactly-once ledger records, and the windows already
        committed here are ordinary extra async commits, the same class as
        any hogwild interleaving.

        A respawned replacement starts with ``initial_state=None``: a fresh
        ``pull()`` of the live center — resuming within the same
        bounded-staleness class the async update rules already tolerate.
        ``data_fn(lease)`` maps a lease to its (x, y) rows of the epoch's
        globally-shuffled arrays.
        """
        window_fn = self._build_window_fn()
        self.connect()
        try:
            center = self.pull()
            if initial_state is None:
                params = self._weights_to_params(center)
                opt_state = self._tx.init(params)
            else:
                params, opt_state = initial_state
                # the window fn donates params/opt_state; the driver keeps
                # this state across epochs — train on a device copy
                params = jax.tree_util.tree_map(jnp.array, params)
                opt_state = jax.tree_util.tree_map(jnp.array, opt_state)
            base_rng = jax.random.PRNGKey(self.seed + 100 + worker_id)
            while True:
                lease = ledger.acquire(worker_id)
                if lease is None:
                    break
                x, y = data_fn(lease)
                xw, yw, mw = self._stack_windows(np.asarray(x),
                                                 np.asarray(y))
                # per-lease RNG: deterministic in (epoch, lease), so a
                # stolen lease retrains under the stealer's own stream
                rng = jax.random.fold_in(
                    jax.random.fold_in(base_rng, lease.epoch),
                    lease.lease_id)
                revoked = False
                for i in range(len(xw)):
                    rng, sub = jax.random.split(rng)
                    params, opt_state, loss = self._window_step(
                        window_fn, params, opt_state, xw[i], yw[i], mw[i],
                        sub, worker_id)
                    self.history.append(float(loss))
                    # renewal piggybacks on the commit this window just
                    # made; False = revoked -> abandon the rest
                    if not ledger.renew(lease.lease_id, worker_id):
                        revoked = True
                        break
                if not revoked:
                    ledger.complete(lease.lease_id, worker_id)
        finally:
            self.disconnect()
        return {"history": self.history, "state": (params, opt_state)}

    # -- overlapped (pipelined) window loop -----------------------------------
    def _train_epoch_overlapped(self, window_fn, params, opt_state, xw, yw,
                                mw, rng, index: int, center):
        """Double-buffered window loop: ONE combined 'u' round trip per
        window, received while the NEXT window's jitted compute runs.

        Per window the loop (1) async-dispatches the jitted window program
        (JAX queues the host→device transfers and the XLA computation and
        returns immediately), (2) blocks on the *previous* window's 'u'
        reply — the DCN round trip rides the wire while the device works,
        (3) materializes this window's weights, ships the delta with
        ``update_begin``, and rebases the next window's input via the
        per-algorithm ``_overlap_next`` hook.

        Staleness contract: each window trains against a center that is one
        window stale — exactly the tolerance the DOWNPOUR family is built
        on (Dean et al., NIPS 2012: workers tolerate stale centers), and
        DynSGD's clock field keeps pricing that staleness into the PS-side
        scale.  The elastic family couples through the as-applied delta
        (``applied``), so x and x̃ still move by the same elastic term.
        """
        # wire_dtype="topk" on the delta family: selection runs ON DEVICE
        # inside the jitted window program — only k values + indices are
        # fetched per window, never the full delta (the elastic family
        # computes its force term on host and selects there instead)
        device_topk = self._topk_density is not None and self._DEVICE_TOPK
        base = self._params_to_weights(params)
        pending = False
        for i in range(len(xw)):
            rng, sub = jax.random.split(rng)
            # async dispatch: the window program starts on the device now
            if device_topk:
                params, opt_state, loss, codes, idxs, scale = \
                    self._run_topk_window(params, opt_state, xw[i], yw[i],
                                          mw[i], sub)
            else:
                params, opt_state, loss = window_fn(
                    params, opt_state, jnp.asarray(xw[i]),
                    jnp.asarray(yw[i]), jnp.asarray(mw[i]), sub)
            if pending:
                # the previous window's reply arrives while this window
                # computes — the transport hides behind the device
                center = self.update_finish()
                pending = False
            if device_topk:
                after = None  # the delta-family hooks never touch it
                delta = self._fetch_sparse(codes, idxs, scale)  # blocks; O(k)
            else:
                after = self._params_to_weights(params)  # blocks; O(n)
                delta = self._overlap_delta(base, after, center)
            applied = self.update_begin(delta, index)
            pending = True
            base = self._overlap_next(base, after, applied, center)
            params = self._weights_to_params(base)
            self.history.append(float(loss))
        if pending:
            # drain the last reply so the epoch (and any checkpoint wave
            # joined after it) observes a center that includes every commit
            center = self.update_finish()
            params = self._weights_to_params(self._overlap_drain(base, center))
        return params, opt_state, center

    # DOWNPOUR-family overlap hooks (ADAG/DynSGD inherit; the elastic
    # family overrides below)
    def _overlap_delta(self, base, after, center):
        """Delta to ship for a window whose input weights were ``base`` and
        output weights ``after``; ``center`` is the last-received center."""
        return [a - b for a, b in zip(after, base)]

    def _overlap_next(self, base, after, applied, center):
        """Weights the next window trains from: the one-window-stale center
        plus this window's as-applied delta (the run-ahead analogue of the
        serial loop's post-commit re-pull)."""
        return [np.asarray(c, np.float32) + a
                for c, a in zip(center, applied)]

    def _overlap_drain(self, base, center):
        """Weights to finish the epoch on once the last reply landed (the
        serial loop ends every window on a fresh pull)."""
        return center


class DOWNPOURWorker(PSWorker):
    """DistBelief async SGD (reference: ``workers.py :: DOWNPOURWorker``):
    commit the raw accumulated window delta, then re-pull the center."""
    ALGORITHM = "downpour"
    _DEVICE_TOPK = True  # delta = after − base: selectable inside the jit
    _ROW_SPARSE_OK = True  # the committed quantity IS the window delta

    def _window_step(self, window_fn, params, opt_state, xw, yw, mw, rng,
                     index):
        if self.row_sparse_tables:
            # row-sparse embedding commit: one combined 'u' round trip,
            # table deltas shipped as exact touched-row blocks
            return self._rowsparse_window_step(params, opt_state, xw, yw,
                                               mw, rng, index)
        if self._topk_density is not None:
            # device-side selection: the full delta never reaches the host
            params, opt_state, loss, codes, idxs, scale = \
                self._run_topk_window(params, opt_state, xw, yw, mw, rng)
            self.commit(self._fetch_sparse(codes, idxs, scale), index)
            params = self._weights_to_params(self.pull())
            return params, opt_state, loss
        before = self._params_to_weights(params)
        params, opt_state, loss = window_fn(
            params, opt_state, jnp.asarray(xw), jnp.asarray(yw),
            jnp.asarray(mw), rng)
        after = self._params_to_weights(params)
        delta = [a - b for a, b in zip(after, before)]
        self.commit(delta, index)
        params = self._weights_to_params(self.pull())
        return params, opt_state, loss


class ADAGWorker(DOWNPOURWorker):
    """ADAG (reference: ``workers.py :: ADAGWorker``): same commit shape as
    DOWNPOUR; the normalization lives on the PS side
    (``ADAGParameterServer`` divides by the concurrent-commit count), matching
    ``rules.adag_commit``."""
    ALGORITHM = "adag"


class DynSGDWorker(DOWNPOURWorker):
    """DynSGD (reference: ``workers.py :: DynSGDWorker``): identical loop; the
    commit's ``clock`` field (last-seen PS update count, set by ``pull``) is
    what ``DynSGDParameterServer`` uses to compute staleness."""
    ALGORITHM = "dynsgd"


class AEASGDWorker(PSWorker):
    """Elastic averaging (reference: ``workers.py :: AEASGDWorker``): keeps a
    *persistent* local model; every window computes the elastic force
    e = α·(x − x̃) against a freshly pulled center, subtracts it locally, and
    commits it (PS does x̃ += e). α = rho · learning_rate."""
    ALGORITHM = "aeasgd"
    _TOPK_EF = False  # the spring force is stateful, not accumulative

    def __init__(self, *args, rho: float = 5.0, **kw):
        super().__init__(*args, **kw)
        self.rho = float(rho)
        lr = self.learning_rate if self.learning_rate is not None else 0.1
        self.alpha = self.rho * lr

    def _window_step(self, window_fn, params, opt_state, xw, yw, mw, rng,
                     index):
        params, opt_state, loss = window_fn(
            params, opt_state, jnp.asarray(xw), jnp.asarray(yw),
            jnp.asarray(mw), rng)
        center = self.pull()
        local = self._params_to_weights(params)
        elastic = [self.alpha * (l - c) for l, c in zip(local, center)]
        # subtract what the PS will actually APPLY (post-wire-compression):
        # x and x-tilde must move by the same e or the elastic coupling
        # drifts under lossy wire dtypes
        applied = self.commit(elastic, index)
        local = [l - e for l, e in zip(local, applied)]
        return self._weights_to_params(local), opt_state, loss

    # overlap hooks: the elastic force is computed against the last-received
    # center (one window stale under comm_overlap — EASGD's coupling is
    # explicitly tolerant of the communication period); x keeps moving by
    # exactly the as-applied e, so x and x̃ stay coupled under lossy wire
    # dtypes, same as the serial path
    def _overlap_delta(self, base, after, center):
        return [self.alpha * (a - c) for a, c in zip(after, center)]

    def _overlap_next(self, base, after, applied, center):
        return [a - e for a, e in zip(after, applied)]

    def _overlap_drain(self, base, center):
        return base  # the elastic worker keeps its persistent local model


class EAMSGDWorker(AEASGDWorker):
    """EAMSGD (reference: ``workers.py :: EAMSGDWorker``): AEASGD whose local
    optimizer carries Nesterov momentum — the momentum state lives in the
    worker optimizer passed in by the ``EAMSGD`` trainer, so the exchange
    logic is identical."""
    ALGORITHM = "eamsgd"


def share_compiled_state(workers: List["Worker"]) -> None:
    """Make all workers reuse one model/optimizer/jitted-window-fn.

    jax.jit caches per function object, so N identical-but-distinct window
    closures would compile N times; jitted callables are thread-safe and the
    shared pieces (model spec, params template, optax tx) are read-only in
    the training loop.
    """
    if not workers:
        return
    head = workers[0]
    head._ensure_model()
    head._build_window_fn()
    share_topk = (getattr(head, "_topk_density", None) is not None
                  and getattr(head, "_DEVICE_TOPK", False))
    if share_topk:
        head._build_topk_window_fn()  # compile the top-k variant once too
    share_rs = bool(getattr(head, "row_sparse_tables", ()))
    if share_rs:
        head._build_rowsparse_window_fn()  # and the row-sparse variant
    for w in workers[1:]:
        w._model = head._model
        w._params0 = head._params0
        w._tx = head._tx
        w._window_fn = head._window_fn
        if share_topk:
            w._topk_window_fn = head._topk_window_fn
            w._wire_k = head._wire_k
            w._wire_total = head._wire_total
            w._wire_shapes = head._wire_shapes
        if share_rs:
            w._rs_window_fn = head._rs_window_fn


WORKER_CLASSES = {
    "downpour": DOWNPOURWorker,
    "adag": ADAGWorker,
    "dynsgd": DynSGDWorker,
    "aeasgd": AEASGDWorker,
    "eamsgd": EAMSGDWorker,
}
