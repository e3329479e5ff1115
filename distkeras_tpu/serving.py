"""Continuous-batching online serving engine (ROADMAP item 1).

The reference's serving story ends at ``predictors.ModelPredictor`` —
offline batch inference over a dataset.  This module composes the offline
decode pieces (``core/decode.py``: KV-cache ``decode_step``, the factored
sampling surface, eos stopping) into a LIVE inference server with
iteration-level (Orca-style) scheduling:

 - **Slot pool** — one batched KV cache (``init_cache(model, num_slots,
   max_len)``); each batch row is a *slot* holding one in-flight request at
   its own position.  The whole pool advances through ONE jitted per-row
   ``decode_step`` (per-slot positions + active mask), so requests of
   different lengths share one compiled decode batch.
 - **Admission queue with backpressure** — ``submit`` enqueues up to
   ``queue_capacity`` requests; beyond that it blocks (or raises
   ``QueueFull`` with ``block=False`` — the wire server turns that into a
   backpressure reply instead of buffering unboundedly).
 - **Prefill/decode interleave** — each engine iteration admits up to
   ``prefills_per_step`` queued requests into free slots, then runs one
   decode step for every running request.  New work never stalls the
   running batch for more than a bounded number of prefill work units.
 - **Compiled bucketed prefill** — admitted prompts are right-padded to a
   small power-of-two length-bucket ladder and prefilled TOGETHER, one
   jitted batched forward per bucket (jit cache keyed on the bucket
   length; per-row ``kv_length`` masking keeps pad tokens out of every
   softmax).
 - **Chunked prefill** — a prompt longer than ``prefill_chunk`` splits
   into chunks advanced one per scheduler iteration, interleaved with
   decode steps (Sarathi-style stall-free prefill): a 1024-token prompt
   no longer freezes every running request for its full length.  The slot
   sits in a *prefilling* state until its final chunk samples the first
   token.
 - **Device-resident decode state** — current tokens, positions, active
   mask, and per-slot sampling params live on device and are advanced
   INSIDE the jitted decode step; only the sampled token row is read back
   each iteration, and step t+1 is dispatched before the host finishes
   emitting step t's tokens (one-step lookahead, the serving twin of the
   host-PS ``comm_overlap`` idiom).
 - **Retirement + slot reuse** — a request leaves its slot the moment it
   emits ``eos_id`` or its ``num_steps``-th token; the slot is immediately
   reusable by the next queued request *mid-run* (continuous batching —
   the point of the whole engine).
 - **Batched per-slot speculative decoding** (``spec_draft=``, off by
   default) — a draft model rides the same slot layout in its OWN KV
   pool; each scheduler iteration drafts ``spec_len`` tokens per active
   row, verifies them all in ONE batched target forward, and commits
   heterogeneous per-row accept lengths (rows advance 1..spec_len+1
   positions per round) — all inside one jitted program, so a round
   costs one dispatch and one d2h like a plain step.  Greedy speculation
   is token-identical to non-speculative greedy.
 - **Quantization** (``quantize=``, ``kv_dtype=``, off by default) —
   int8/bf16 weight-only quantization applied at construction and on
   every hot-reload pull, and an int8 KV slot pool (codes + per-entry
   scales, dequantized inside the attention read) at roughly half the
   bf16 slot bytes — the ``num_slots``-doubling lever at fixed HBM.
 - **Hot weight reload** (stretch, off by default) — ``attach_ps`` points
   the engine at a live parameter server; between decode steps it pulls a
   fresh center over the existing ``'p'`` opcode, so training and serving
   can share one deployment.
 - **Failure semantics** (the serving twin of the host-PS robustness
   stack — see docs/serving.md's failure matrix): per-request
   **deadlines** (``submit(deadline_s=)`` / an engine-wide default) retire
   expired requests mid-run with reason ``"deadline"`` — queued ones are
   shed before ever taking a slot; **cancellation** (``engine.cancel``,
   the wire ``SERVING_OP_CANCEL`` opcode, and server-side
   client-disconnect detection) reclaims a KV slot within one scheduler
   iteration with reason ``"cancel"``; **graceful drain**
   (``engine.drain``) stops admission (``submit`` raises
   :class:`Draining`), finishes in-flight work, then stops; and a
   **crashed or wedged decode loop** fails every in-flight handle with a
   typed :class:`EngineDead` instead of hanging ``result()`` forever
   (``resilience.EngineSupervisor`` watches the loop's heartbeat and can
   restart the engine from the model weights with a fresh slot pool).

Determinism contract: a lone request through the engine emits tokens
BIT-IDENTICAL to offline ``generate`` under the same seed/params
(tests/test_serving.py) — prefill runs the same ``_forward`` inside its
jitted programs, decode sampling runs the factored
``sample_logits_batched`` whose per-row math reproduces ``generate``'s
``sample_logits`` row for row.

The wire layer (``ServingServer``/``ServingClient``) speaks the same frame
codec + ``BufferPool`` transport as the PS stack, with two opcodes of its
own: ``'q'`` (enqueue request → ack/backpressure) and ``'r'`` (stream
reply chunks until done).  The serving protocol owns its port and its
opcode namespace — the PS protocol's ``'q'`` (quit) lives elsewhere.
"""

from __future__ import annotations

import collections
import itertools
import logging
import select
import selectors
import socket
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import networking
from .core import decode as _dec
from .core import quant as _quant
from .core.decode import (_check_supported, _context_limit,
                          _validate_rolling, _validate_sampling,
                          _validate_stopping, _vocab_size, init_cache)
from .core.model import FittedModel, Sequential
from .metrics import EngineAccount, span

logger = logging.getLogger("distkeras_tpu.serving")

from .resilience import RetryPolicy as _RetryPolicy  # noqa: E402 (no cycle:
# resilience imports networking only — and serving needs the policy type at
# module scope for the reload default below)

#: re-dial budget for ``attach_ps`` hot-reload pulls.  Deliberately TIGHT:
#: the pull runs on the decode thread between steps, so the policy's worst
#: case (attempts x backoff, deadline-capped) is the longest serving stall
#: a dead PS can cause — long enough to ride out a ``ShardSupervisor``
#: same-address respawn, short enough that serving p99 survives a PS that
#: is simply gone.  Override per-engine via ``attach_ps(retry_policy=...)``.
DEFAULT_RELOAD_POLICY = _RetryPolicy(attempts=4, backoff=0.02,
                                     max_backoff=0.1, jitter=0.0,
                                     deadline=0.5)

tmap = jax.tree_util.tree_map


class QueueFull(RuntimeError):
    """Admission backpressure: the engine's bounded queue is at capacity
    (``submit(block=False)`` / a blocking submit that timed out).  The wire
    server maps this to an ``{"ok": False, "error": "queue full"}`` reply —
    the client sheds or retries; the server never buffers unboundedly."""


class Draining(RuntimeError):
    """Admission refused because the engine is draining (``engine.drain``):
    in-flight requests finish, new ones go elsewhere.  The wire server maps
    this to a typed ``{"ok": False, "kind": "draining"}`` reply."""


class EngineDead(RuntimeError):
    """The serving engine's decode loop crashed, wedged, or was torn down
    with work in flight.  Raised from ``RequestHandle.result()`` for every
    request the dead engine was carrying (no silent hangs), and from
    ``submit`` on a dead engine.  The wire server maps it to a typed
    ``{"kind": "engine_dead"}`` frame; ``ServingClient.generate`` with a
    ``retry_policy`` treats it as retriable (requests are deterministic in
    their seed, so a resubmit is idempotent)."""


class QuotaExceeded(QueueFull):
    """Admission refused by the submitting tenant's token-bucket quota
    (``TenantPolicy.rate``).  Subclasses :class:`QueueFull` so every
    existing shed path (router spill, open-loop load shedding, wire
    backpressure) treats a quota refusal as sheddable — but the wire
    server replies with its own ``{"kind": "quota"}`` so clients can
    distinguish policy refusal from transient queue pressure.  Raised
    immediately even from a blocking ``submit``: waiting out a refill
    inside the engine would hold admission slots hostage to one tenant's
    burst."""


class TenantPolicy:
    """One tenant's QoS contract: ``weight`` is its weighted-fair share
    of admissions, ``rate``/``burst`` a token-bucket quota in requests/s
    (``rate=None`` = unlimited), ``tier`` the SLO band (``"interactive"``
    tenants are admitted ahead of ``"batch"`` tenants and may preempt
    them; ``"batch"`` tenants are preemptible), and ``deadline_s`` an
    optional tier-default per-request deadline applied when ``submit``
    passes none (explicit ``deadline_s`` still wins).  Bucket state is
    mutated under the engine's queue lock — one policy object belongs to
    one engine (``clone()`` for a fresh-bucket copy)."""

    __slots__ = ("name", "weight", "rate", "burst", "tier", "deadline_s",
                 "_tokens", "_stamp")

    def __init__(self, name: str, weight: float = 1.0,
                 rate: Optional[float] = None,
                 burst: Optional[float] = None, tier: str = "batch",
                 deadline_s: Optional[float] = None):
        if not name:
            raise ValueError("tenant name must be non-empty")
        if not (weight > 0):
            raise ValueError(f"weight must be > 0, got {weight}")
        if rate is not None and not (rate > 0):
            raise ValueError(f"rate must be None or > 0, got {rate}")
        if tier not in ("interactive", "batch"):
            raise ValueError(f"tier must be 'interactive' or 'batch', "
                             f"got {tier!r}")
        if deadline_s is not None and not (deadline_s > 0):
            raise ValueError(f"deadline_s must be None or > 0, "
                             f"got {deadline_s}")
        self.name = str(name)
        self.weight = float(weight)
        self.rate = None if rate is None else float(rate)
        if burst is None:
            burst = None if rate is None else max(1.0, float(rate))
        elif not (burst >= 1.0):
            raise ValueError(f"burst must be >= 1, got {burst}")
        self.burst = None if burst is None else float(burst)
        self.tier = tier
        self.deadline_s = deadline_s
        self._tokens = 0.0 if self.burst is None else self.burst
        self._stamp: Optional[float] = None

    def _take(self, now: float) -> bool:
        """Spend one bucket token (refilling first); False = over quota.
        Caller holds the engine's queue lock."""
        if self.rate is None:
            return True
        if self._stamp is not None:
            self._tokens = min(self.burst,
                               self._tokens + (now - self._stamp)
                               * self.rate)
        self._stamp = now
        if self._tokens >= 1.0:
            self._tokens -= 1.0
            return True
        return False

    def clone(self) -> "TenantPolicy":
        """A copy with a full, unshared token bucket (the
        ``respawn_clone`` seam — the replacement engine must not inherit
        the dead engine's bucket debt)."""
        return TenantPolicy(self.name, weight=self.weight, rate=self.rate,
                            burst=self.burst, tier=self.tier,
                            deadline_s=self.deadline_s)


class RequestHandle:
    """One submitted request's lifecycle + streaming surface.

    Produced tokens arrive incrementally (``next_chunk``) as the engine
    emits them; ``result()`` blocks until retirement and returns the full
    ``generate``-shaped row: prompt + emitted tokens, padded with
    ``pad_id`` (default ``eos_id``, else 0) out to ``num_steps`` — exactly
    the static-shape row offline ``generate`` would return.

    ``finish`` is the retire reason: ``"eos"`` / ``"length"`` / ``"empty"``
    for normal completion, ``"deadline"`` (per-request deadline expired —
    the partial row is still returned, padded), ``"cancel"`` (explicit
    cancel or client disconnect), ``"drain"`` (drain timeout), ``"error"``
    (the engine died — ``result()`` raises the stored :class:`EngineDead`),
    ``"prefilled"`` (a ``role="prefill"`` engine finished its half: the
    first token is pushed and ``kvblocks`` holds the request's extracted
    KV blocks for the decode engine — disaggregated serving's hand-off).
    ``deadline`` is an absolute ``time.perf_counter()`` instant or None.
    """

    __slots__ = ("id", "prompt", "num_steps", "temperature", "top_k",
                 "top_p", "eos_id", "pad_id", "key", "tokens", "finish",
                 "slot", "submitted_at", "started_at", "first_token_at",
                 "finished_at", "deadline", "error", "cancelled_at",
                 "kvblocks", "tenant", "priority", "_cond", "_chunk_read",
                 "_listener")

    def __init__(self, rid: int, prompt: np.ndarray, num_steps: int,
                 temperature: float, top_k: Optional[int],
                 top_p: Optional[float], eos_id: Optional[int],
                 pad_id: Optional[int], key,
                 deadline_s: Optional[float] = None,
                 tenant: str = "default", priority: int = 0):
        self.id = rid
        self.prompt = prompt
        self.num_steps = int(num_steps)
        self.temperature = float(temperature)
        self.top_k = top_k
        self.top_p = top_p
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.key = key
        self.tokens: List[int] = []     # emitted (pre-padding) tokens
        self.finish: Optional[str] = None   # see class docstring
        self.slot: Optional[int] = None
        self.submitted_at = time.perf_counter()
        self.started_at: Optional[float] = None
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.deadline = (None if deadline_s is None
                         else self.submitted_at + float(deadline_s))
        self.error: Optional[BaseException] = None
        self.cancelled_at: Optional[float] = None
        #: networking.KVBlocks on a "prefilled" handle (prefill role's
        #: extraction output) or on a decode-role ingest before admission
        self.kvblocks = None
        self.tenant = str(tenant)
        self.priority = int(priority)
        self._cond = threading.Condition()
        self._chunk_read = 0            # tokens already handed out as chunks
        #: event-transport hook: a no-arg callable invoked (OUTSIDE
        #: ``_cond``) whenever tokens arrive or the handle retires — how
        #: the selector cores get poked without a polling thread per
        #: stream.  Set via ``set_listener``; polling consumers ignore it.
        self._listener: Optional[Callable[[], None]] = None

    @property
    def done(self) -> bool:
        return self.finish is not None

    @property
    def pad(self) -> int:
        return int(self.pad_id if self.pad_id is not None
                   else (self.eos_id or 0))

    # -- engine side ---------------------------------------------------------
    def _push(self, token: int) -> None:
        with self._cond:
            if self.finish is not None:  # a wedged loop emitting past its
                return                   # declared death: drop, don't grow
            if self.first_token_at is None:
                self.first_token_at = time.perf_counter()
            self.tokens.append(int(token))
            self._cond.notify_all()
            fire = self._listener
        if fire is not None:  # invoked OUTSIDE _cond: the listener hops
            fire()            # threads (call_soon) and must not nest locks

    def _finish(self, reason: str) -> bool:
        """Returns whether THIS call made the handle terminal — the engine
        only counts a request once, so a completion racing a concurrent
        failure (or vice versa) must not increment both counters."""
        with self._cond:
            if self.finish is not None:  # first terminal state wins (a
                return False             # wedge diagnosis is never undone)
            self.finish = reason
            self.finished_at = time.perf_counter()
            self._cond.notify_all()
            fire = self._listener
        if fire is not None:
            fire()
        return True

    def _fail(self, exc: BaseException, reason: str = "error") -> bool:
        """Terminal failure: ``result()`` raises ``exc`` instead of
        returning a row.  Idempotent like ``_finish``; same return
        contract."""
        with self._cond:
            if self.finish is not None:
                return False
            self.error = exc
            self.finish = reason
            self.finished_at = time.perf_counter()
            self._cond.notify_all()
            fire = self._listener
        if fire is not None:
            fire()
        return True

    def set_listener(self, fn: Optional[Callable[[], None]]) -> None:
        """Install (or clear, with None) the progress listener — fired
        after every token push and on retirement, outside the handle's
        lock.  One listener at a time; the event transports each attach
        their loop-poke here while they own the stream."""
        with self._cond:
            self._listener = fn
        if fn is not None and (self.done or len(self.tokens)):
            fn()  # catch up on progress that predates the listener

    def _expired(self, now: float) -> bool:
        return self.deadline is not None and now > self.deadline

    # -- consumer side -------------------------------------------------------
    def next_chunk(self, timeout: Optional[float] = None
                   ) -> Tuple[np.ndarray, bool]:
        """Block until new tokens exist (or the request finished); return
        ``(new_tokens, done)``.  After ``done`` the chunk may be empty —
        the stream's final frame."""
        with self._cond:
            self._cond.wait_for(
                lambda: self.done or len(self.tokens) > self._chunk_read,
                timeout=timeout)
            chunk = np.asarray(self.tokens[self._chunk_read:], np.int32)
            self._chunk_read = len(self.tokens)
            return chunk, self.done

    def wait(self, timeout: Optional[float] = None) -> bool:
        with self._cond:
            return self._cond.wait_for(lambda: self.done, timeout=timeout)

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """The full ``generate``-shaped row (prompt + tokens, padded to
        ``num_steps``) — blocks until the request retires.  A request the
        engine failed (crash / wedge / drain timeout) raises its stored
        typed error (:class:`EngineDead`) instead of hanging or returning
        a fabricated row."""
        if not self.wait(timeout):
            raise TimeoutError(f"request {self.id} not done")
        if self.error is not None:
            raise self.error
        gen = list(self.tokens) + [self.pad] * (self.num_steps
                                                - len(self.tokens))
        return np.concatenate([self.prompt,
                               np.asarray(gen, np.int32)])

    @property
    def latency_s(self) -> Optional[float]:
        return (None if self.finished_at is None
                else self.finished_at - self.submitted_at)

    @property
    def ttft_s(self) -> Optional[float]:
        """Time to first token — submit instant → first emitted token
        (queueing AND prefill included), the latency a streaming client
        actually feels.  None until the first token exists."""
        return (None if self.first_token_at is None
                else self.first_token_at - self.submitted_at)


def _quantize_weights(params, mode: str):
    """The engine's one weight-quantization path (construction AND every
    ``attach_ps`` hot-reload pull go through it): ``"int8"`` —
    ``quantize_params`` weight-only post-training quantization (matmul
    kernels become (codes, scale) leaves that dequantize inside the
    unmodified forward); ``"bf16"`` — every float leaf cast to bfloat16
    (half the f32 weight traffic, no code change).  Idempotent."""
    if mode == "int8":
        return _quant.quantize_params(params)
    # bf16: cast float leaves; QuantizedTensor leaves (already int8) and
    # integer leaves pass through untouched
    def cast(x):
        if isinstance(x, _quant.QuantizedTensor):
            return x
        if hasattr(x, "dtype") and jnp.issubdtype(
                jnp.asarray(x).dtype, jnp.floating):
            return jnp.asarray(x).astype(jnp.bfloat16)
        return x
    return tmap(cast, params,
                is_leaf=lambda x: isinstance(x, _quant.QuantizedTensor))


def _commit_rows(big, row, slots, width: int, rolling: bool, p_lens):
    """Scatter freshly-prefilled full-precision cache rows into the slot
    pool: ring-converted per row for rolling pools, quantize-on-commit for
    int8 pools (same per-entry scales the decode-time writes produce).
    ``slots`` rows carrying index ``num_slots`` drop every write."""
    if big is None:
        return None
    if rolling:
        w = big["k"].shape[1]
        row = {n: _dec.ring_from_prefill(row[n], p_lens, w)
               for n in ("k", "v")}

        def put(dst, src):
            return dst.at[slots].set(src, mode="drop")
    else:
        def put(dst, src):
            return dst.at[slots, :width].set(src, mode="drop")
    if "ks" in big:
        kq, ks = _quant.quantize_kv(row["k"])
        vq, vs = _quant.quantize_kv(row["v"])
        return {"k": put(big["k"], kq), "v": put(big["v"], vq),
                "ks": put(big["ks"], ks), "vs": put(big["vs"], vs)}
    return {n: put(big[n], row[n]) for n in ("k", "v")}


def _commit_full_row(big, row, slot, rolling: bool, p_row):
    """The chunked-prefill final commit: one staged full-length row
    atomically replaces pool row ``slot`` (ring-collapsed for rolling
    pools, quantize-on-commit for int8 pools)."""
    if big is None:
        return None
    if rolling:
        w = big["k"].shape[1]
        row = {n: _dec.ring_from_prefill(row[n], p_row, w)
               for n in ("k", "v")}
    if "ks" in big:
        kq, ks = _quant.quantize_kv(row["k"])
        vq, vs = _quant.quantize_kv(row["v"])
        return {"k": big["k"].at[slot].set(kq[0], mode="drop"),
                "v": big["v"].at[slot].set(vq[0], mode="drop"),
                "ks": big["ks"].at[slot].set(ks[0], mode="drop"),
                "vs": big["vs"].at[slot].set(vs[0], mode="drop")}
    return {n: big[n].at[slot].set(row[n][0], mode="drop")
            for n in ("k", "v")}


def _pow2_buckets(cap: int) -> List[int]:
    """The prefill length-bucket ladder: powers of two from 8 up, capped
    (and terminated) at ``cap`` — a SMALL set, so each bucket's jitted
    batched-prefill program compiles once and is reused for every prompt
    that rounds up to it."""
    cap = int(cap)
    out: List[int] = []
    n = 8
    while n < cap:
        out.append(n)
        n *= 2
    out.append(cap)
    return out


def _moe_unit_counters(aux):
    """What a prefill unit hands back of its expert layers (``aux``: each
    layer's ``SparseMoE`` counters): assignments to held experts, held
    experts touched, the fullest expert's rows, all summed over the layers,
    and how many layers that was.  int32[4]."""
    return jnp.concatenate([sum(aux),
                            jnp.asarray([len(aux)], jnp.int32)])


def _sampler_work(entries) -> str:
    """What the live rows of a decode step ask of the sampler, from the
    handles the host holds: ``"greedy"`` (none samples: the argmax alone),
    ``"draw"`` (some sample, none of them filters) or ``"filter"`` (a
    sampling row carries ``top_k``/``top_p``: the sort runs).  The device
    decides the same from the per-slot arrays
    (``decode.sample_logits_batched``); this is the host's account of it."""
    work = "greedy"
    for _, h in entries:
        if h.temperature > 0.0:
            if h.top_k is not None or h.top_p is not None:
                return "filter"
            work = "draw"
    return work


class _PrefillJob:
    """Scheduler-side state of one chunked prefill in flight: the slot is
    claimed (``engine._handles``) but not yet decoding; ``written`` prompt
    tokens are staged so far.  ``staging`` is a full-length one-row cache
    the chunks accumulate into — private to the job, so the decode
    batch's junk writes into free pool rows can't race it — which the
    final chunk commits to the slot's pool row in one atomic program
    (ring-collapsed for rolling engines).

    Paged engines (non-rolling) chunk IN-ARENA instead: the job's blocks
    are private by construction (every other row's writes go through its
    OWN block table, and the prefilling slot's device table stays null
    until the final chunk installs it — junk decode passes drop into the
    null block), so the dense path's staging race cannot exist and the
    chunks write straight into the request's allocated blocks (``bt`` /
    ``dbt`` hold the row's uploaded block tables)."""

    __slots__ = ("handle", "staging", "d_staging", "written", "bt", "dbt",
                 "hit", "moe")

    def __init__(self, handle: RequestHandle, staging=None, d_staging=None,
                 bt=None, dbt=None):
        self.handle = handle
        self.staging = staging
        self.d_staging = d_staging  # the draft model's twin (speculation)
        self.bt = bt                # paged: (1, T) device block-table row
        self.dbt = dbt
        self.written = 0
        self.hit = 0    # prefix-hit tokens, for the first unit's span
        self.moe = None  # the experts' counters of the units so far (device)


class _SuspendedReq:
    """One preempted request swapped out to host memory: the live KV
    blocks (``layers`` — per-layer dicts of host arrays, ``n_blocks`` ×
    ``block_size`` rows each, the same layout ``networking.KVBlocks``
    ships) plus the decode frontier (``pos`` device positions written,
    ``tok`` the current un-written token).  The handle itself stays
    non-terminal — tokens already emitted remain on it, and the RNG key
    (``handle.key``) folds per absolute position, so re-installing
    (tok, pos, key) over the restored blocks resumes a bit-identical
    stream.  Holds NO slot and NO arena blocks."""

    __slots__ = ("handle", "layers", "n_blocks", "pos", "tok",
                 "suspended_at")

    def __init__(self, handle: RequestHandle, layers, n_blocks: int,
                 pos: int, tok: int):
        self.handle = handle
        self.layers = layers
        self.n_blocks = int(n_blocks)
        self.pos = int(pos)
        self.tok = int(tok)
        self.suspended_at = time.perf_counter()


# ---------------------------------------------------------------------------
# paged KV pool: host-side block allocator + radix prefix index
# ---------------------------------------------------------------------------

class _RadixNode:
    """One FULL block of prompt tokens in the prefix trie: ``key`` is its
    ``block_size``-token chunk (the edge label from ``parent``), ``block``
    the physical arena block holding those tokens' K/V (all layers, both
    pools — target and draft arenas share one block-id namespace).
    ``ref`` counts live requests currently sharing the block; at ref 0
    the node stays CACHED (its K/V remain valid in the arena) until the
    allocator evicts it — LRU over ``last_used``, leaves first, so a
    chain is reclaimed suffix-inward.  ``epoch`` stamps the scheduler
    pass that inserted it: a node is matchable only from LATER passes,
    which is what keeps a same-pass matcher from reading blocks whose
    prefill program (possibly a different bucket group) has not been
    dispatched yet."""

    __slots__ = ("parent", "key", "block", "ref", "last_used", "children",
                 "epoch")

    def __init__(self, parent, key: Tuple[int, ...], block: int,
                 epoch: int = -1):
        self.parent = parent
        self.key = key
        self.block = block
        self.ref = 0
        self.last_used = 0
        self.children: Dict[Tuple[int, ...], "_RadixNode"] = {}
        self.epoch = epoch


class _BlockPlan:
    """One admitted request's block bookkeeping: ``blocks`` is the full
    logical chain (matched + fresh, in logical-block order), ``nodes``
    the trie nodes it holds a reference on, ``private`` the block ids it
    owns outright (COW copy, partial prompt boundary, decode region),
    ``matched`` the prefix tokens served from the trie, and ``cow`` the
    ``(src, dst)`` block pair of the copy-on-write boundary copy (or
    None)."""

    __slots__ = ("nodes", "private", "blocks", "matched", "cow")

    def __init__(self, nodes, private, blocks, matched, cow):
        self.nodes = nodes
        self.private = private
        self.blocks = blocks
        self.matched = matched
        self.cow = cow


class _PagedKVPool:
    """Host-side allocator + radix prefix index over a flat block arena
    (``core.decode.init_paged_arena``).  All scheduler-thread-only.

    Allocation is block-granular and on demand: a request takes
    ``ceil((p_len + num_steps) / block_size)`` blocks instead of a
    ``max_len`` row, so capacity is bounded by TOKENS IN FLIGHT rather
    than ``num_slots × max_len``.  With ``share=True`` (non-rolling
    pools) admissions first walk the trie: full blocks whose token chunk
    matches the prompt are SHARED (refcounted — never written again:
    every sharer's write floor sits above them), a partially-matched
    boundary block is COPIED (copy-on-write: the admission owns the
    copy and continues writing into it), and only the unmatched suffix
    is prefilled.  Matching is capped at ``p_len - 1`` so at least one
    prompt token is always prefilled — the logits that sample the first
    token must be computed.  Retirement decrements refs; refcount-0
    chains stay cached until LRU eviction (leaves first) reclaims their
    blocks for new admissions.  Stats are written straight into the
    engine's ``stats`` dict."""

    def __init__(self, num_blocks: int, block_size: int, share: bool,
                 stats: Dict[str, Any]):
        self.num_blocks = int(num_blocks)
        self.bs = int(block_size)
        self.share = bool(share)
        self.stats = stats
        self.free: List[int] = list(range(self.num_blocks - 1, -1, -1))
        self.root = _RadixNode(None, (), -1)
        self.private_out = 0
        self._clock = 0
        self.epoch = 0
        #: incremental mirror of ``cached_blocks()`` — kept so the
        #: engine's lock-free load snapshot reads an int instead of
        #: walking the trie (O(nodes)) on every publish
        self.trie_nodes = 0

    # -- clocks ------------------------------------------------------------
    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def next_epoch(self) -> None:
        """One scheduler pass = one epoch: nodes inserted this pass are
        not matchable until the next (their prefill program may belong
        to a bucket group dispatched AFTER the matcher's)."""
        self.epoch += 1

    # -- introspection -----------------------------------------------------
    def _nodes(self) -> List[_RadixNode]:
        out, stack = [], list(self.root.children.values())
        while stack:
            n = stack.pop()
            out.append(n)
            stack.extend(n.children.values())
        return out

    def cached_blocks(self) -> int:
        """Trie-held blocks (shared + refcount-0 cached)."""
        return len(self._nodes())

    def in_use(self) -> int:
        """Blocks held by LIVE requests: privately-owned ones plus trie
        nodes with a non-zero refcount.  0 when the engine is idle — the
        zero-leak assertion every retirement path must restore."""
        return self.private_out + sum(1 for n in self._nodes() if n.ref > 0)

    def check_conservation(self) -> bool:
        """free + cached == num_blocks − private_out, always."""
        return (len(self.free) + self.cached_blocks() + self.private_out
                == self.num_blocks)

    # -- match / evict / allocate ------------------------------------------
    def _match(self, toks: List[int], cap: int):
        """Walk the trie: full-block matches (chain), then the best
        PARTIAL child match at the divergence point (the COW boundary).
        ``cap`` bounds matchable tokens (< p_len, see class docstring).
        Nodes inserted this epoch are invisible."""
        nodes: List[_RadixNode] = []
        parent = self.root
        d = 0
        while d + self.bs <= cap:
            child = parent.children.get(tuple(toks[d:d + self.bs]))
            if child is None or child.epoch >= self.epoch:
                break
            nodes.append(child)
            parent = child
            d += self.bs
        pnode, plen = None, 0
        lim = min(cap - d, self.bs)
        if lim > 0:
            for key, child in parent.children.items():
                if child.epoch >= self.epoch:
                    continue
                j = 0
                while j < lim and key[j] == toks[d + j]:
                    j += 1
                if j > plen:
                    pnode, plen = child, j
        return nodes, pnode, plen

    def _evictable(self, pinned) -> List[_RadixNode]:
        out, stack = [], list(self.root.children.values())
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children.values())
            elif n.ref == 0 and n not in pinned:
                out.append(n)
        return out

    def _reserve(self, need: int, pinned=()) -> bool:
        """Ensure ``need`` free blocks, evicting LRU refcount-0 leaf
        chains (suffix-inward); False when live requests hold too much —
        the admission stays queued until retirements free blocks."""
        pinned = set(pinned)
        while len(self.free) < need:
            cands = self._evictable(pinned)
            if not cands:
                return False
            victim = min(cands, key=lambda n: n.last_used)
            del victim.parent.children[victim.key]
            self.free.append(victim.block)
            self.trie_nodes -= 1
            self.stats["blocks_evicted"] += 1
        return True

    def admit(self, tokens, n_blocks: int) -> Optional[_BlockPlan]:
        """Reserve a request's block chain.  ``tokens`` (the prompt) is
        None for share-off (rolling) pools — a plain allocation.  Trie
        INSERTION of the request's own full prompt blocks is deferred to
        :meth:`publish` (after their contents' program is dispatched).
        Returns None when blocks are unavailable (admission backs off)."""
        if not self.share or tokens is None:
            if not self._reserve(n_blocks):
                return None
            fresh = [self.free.pop() for _ in range(n_blocks)]
            self.stats["blocks_allocated"] += n_blocks
            self.private_out += n_blocks
            return _BlockPlan([], fresh, list(fresh), 0, None)
        toks = [int(t) for t in tokens]
        cap = len(toks) - 1
        nodes, pnode, plen = self._match(toks, cap)
        matched = len(nodes) * self.bs + plen
        need = n_blocks - len(nodes)
        pinned = list(nodes) + ([pnode] if pnode is not None else [])
        if not self._reserve(need, pinned):
            return None
        fresh = [self.free.pop() for _ in range(need)]
        self.stats["blocks_allocated"] += need
        self.private_out += need
        chain = [n.block for n in nodes] + fresh
        now = self._tick()
        for n in nodes:
            n.ref += 1
            n.last_used = now
        self.stats["blocks_reused"] += len(nodes)
        if matched:
            self.stats["prefix_hits"] += 1
            self.stats["prefix_hit_tokens"] += matched
        cow = None
        if pnode is not None:
            cow = (pnode.block, chain[len(nodes)])
            pnode.last_used = now
            self.stats["cow_copies"] += 1
        return _BlockPlan(list(nodes), fresh, chain, matched, cow)

    def publish(self, plan: _BlockPlan, tokens) -> None:
        """Insert the request's own FULL prompt blocks into the trie
        (ref 1 — held live until release) so later admissions can share
        them.  Called once the program writing their contents has been
        dispatched: immediately for bucket prefills, at the final chunk
        for chunked ones (earlier would let a matcher's program overtake
        an undispatched chunk).  Stops at the first key collision —
        a concurrent chain insertion keeps the existing nodes and this
        plan's duplicates stay private."""
        if not self.share or tokens is None:
            return
        toks = [int(t) for t in tokens]
        parent = plan.nodes[-1] if plan.nodes else self.root
        now = self._tick()
        i = len(plan.nodes)
        while (i + 1) * self.bs <= len(toks) and i < len(plan.blocks):
            key = tuple(toks[i * self.bs:(i + 1) * self.bs])
            if key in parent.children:
                break
            node = _RadixNode(parent, key, plan.blocks[i], self.epoch)
            node.ref = 1
            node.last_used = now
            parent.children[key] = node
            plan.nodes.append(node)
            plan.private.remove(plan.blocks[i])
            self.private_out -= 1
            self.trie_nodes += 1
            parent = node
            i += 1

    def release(self, plan: _BlockPlan) -> None:
        """Retirement: drop the plan's refs (refcount-0 chains stay
        cached for future prefix hits) and free its private blocks."""
        now = self._tick()
        for n in plan.nodes:
            n.ref -= 1
            n.last_used = now
        self.free.extend(plan.private)
        self.private_out -= len(plan.private)
        plan.nodes, plan.private = [], []


class ServingEngine:
    """Iteration-level continuous-batching engine over a slot-pooled KV
    cache.

    ``model``: a ``FittedModel`` (or ``(Sequential, params)`` pair) from the
    decode-supported family (``transformer_lm``).  ``num_slots`` is the
    decode batch — the number of simultaneously running requests;
    ``max_len`` bounds prompt+continuation per request (defaults to the
    model's positional range).  ``rolling=True`` (sliding-window models
    only) makes each slot an O(W) ring instead of ``max_len`` slots.

    Every forward runs inside a jitted program: batched bucket prefill,
    chunked long-prompt prefill, and device-resident decode state with
    one-step lookahead.  ``prefill_chunk`` bounds how many prompt tokens
    one scheduler iteration may prefill for a single request: longer
    prompts split into chunks interleaved with decode steps, so
    admissions never stall the running batch for more than one chunk per
    iteration.

    Speculation + quantization (all default OFF — defaults are
    bit-identical to the pre-speculation engine):

     - ``spec_draft``: a cheaper draft model
       (``FittedModel`` or ``(Sequential, params)``, same vocabulary)
       turns every decode iteration into a speculative ROUND — ``spec_len``
       per-slot draft steps against the draft's own slot-pooled KV cache,
       one batched target verify forward, heterogeneous per-row accept
       lengths (each row advances 1..spec_len+1 positions).  Greedy
       requests stay token-identical to non-speculative greedy (the
       committed chain is the target's own argmax chain); sampled
       requests follow the Leviathan/Chen rejection rule —
       distribution-exact, deterministic per seed, but a different (and
       documented) key-fold schedule than the non-speculative sampler.
     - ``quantize``: ``"int8"`` (weight-only post-training quantization
       through ``core.quant.quantize_params``) or ``"bf16"`` — applied at
       construction and re-applied to every ``attach_ps`` hot-reload
       pull.  Lossy.
     - ``kv_dtype="int8"``: the slot pools (target and
       draft) store int8 codes + per-entry scales — roughly half the
       bf16 slot bytes, so ``num_slots`` can ~double at fixed pool HBM
       (``kv_pool_bytes`` is the byte-accounted observable).  Lossy.
     - ``paged=True``: the slot pool becomes a PAGED KV
       pool — a flat arena of ``kv_blocks`` fixed-size blocks
       (``block_size`` tokens each, int8 codes + scales paged identically
       when ``kv_dtype="int8"``) with per-request block tables, so a
       request allocates ``ceil((p_len + num_steps) / block_size)``
       blocks instead of a ``max_len`` row and capacity is bounded by
       tokens in flight.  On top of the arena a host-side RADIX PREFIX
       INDEX maps full prompt blocks to refcounted chains: an admission
       walks the trie, SHARES matched full blocks (copy-on-write at a
       partially-matched boundary block), and prefills only the
       unmatched suffix — TTFT for a shared-prefix admission drops from
       O(prompt) to O(suffix).  Refcount-0 chains stay cached until LRU
       eviction.  Speculative engines page the draft pool over the SAME
       block chain (one trie serves both).  Exact: a lone request's
       output is token-identical to the dense engine and to offline
       ``generate``; the default ``paged=False`` keeps the dense pool
       byte-for-byte.

    Threading: ``submit`` is thread-safe (any number of producers);
    the scheduler itself — ``step`` / ``run_until_idle`` / the ``start``
    background thread — must be driven from ONE thread at a time.
    """

    def __init__(self, model: Union[FittedModel, Tuple[Sequential, Any]],
                 num_slots: int = 4, max_len: Optional[int] = None,
                 queue_capacity: int = 64, prefills_per_step: int = 1,
                 rolling: bool = False,
                 default_deadline_s: Optional[float] = None,
                 prefill_chunk: int = 128,
                 spec_draft: Optional[Union[FittedModel,
                                            Tuple[Sequential, Any]]] = None,
                 spec_len: int = 4,
                 quantize: Optional[str] = None,
                 kv_dtype: Optional[str] = None,
                 paged: bool = False, block_size: int = 16,
                 kv_blocks: Optional[int] = None,
                 role: str = "unified",
                 tenants: Optional[List[TenantPolicy]] = None):
        if isinstance(model, FittedModel):
            self.model, params = model.model, model.params
        else:
            self.model, params = model
        _check_supported(self.model)
        if rolling:
            _validate_rolling(self.model)
        # -- disaggregation role (default "unified": the engine is exactly
        #    its pre-disaggregation self).  "prefill": admissions run the
        #    ordinary paged prefill programs but STOP before the token
        #    loop — the request retires "prefilled" with its KV blocks
        #    extracted onto the handle.  "decode": admission comes from
        #    submit_prefilled (a shipped block set scattered into this
        #    engine's own arena blocks); plain submit is rejected.
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(f"role must be 'unified', 'prefill' or "
                             f"'decode', got {role!r}")
        if role != "unified":
            if not paged:
                raise ValueError(
                    f"role={role!r} needs the paged block arena "
                    "(paged=True): block transfer is defined over "
                    "fixed-size arena blocks")
            if rolling:
                raise ValueError(
                    f"role={role!r} does not compose with rolling pools — "
                    "ring-laid blocks are not positionally addressable on "
                    "the receiving side")
            if spec_draft is not None:
                raise ValueError(
                    f"role={role!r} does not compose with spec_draft: the "
                    "draft arena is engine-private and never shipped")
        self.role = role
        # -- what the model itself shows: a layer with a fixed-size recurrent
        #    state (state kind "recurrent") keeps a request's past OUTSIDE
        #    its KV blocks, so everything that moves, shares or rolls a
        #    request by its blocks alone is refused here, by name
        self._recurrent = _dec.has_recurrent_state(self.model)
        blocks = [layer for layer in self.model.layers
                  if isinstance(layer, _dec._BLOCKS)]
        self._moe_layers = sum(1 for b in blocks if b.routes_tokens)
        #: the kinds of per-request state the model's blocks keep, for the
        #: decode dispatch span: "kv", "kv+recurrent", "kv+recurrent+none"
        self._state_kinds = "+".join(
            k for k in ("kv", "recurrent", "none")
            if any(b.state_kind == k for b in blocks)) or "kv"
        # the arena-direct prefill programs hand the experts' counters back
        # behind the first token (the one array the host fetches anyway)
        self._moe_prefill = (self._moe_layers > 0 and paged and not rolling
                             and spec_draft is None)
        #: what a chunked request's counters start from (never donated)
        self._moe_zero = (jnp.zeros((4,), jnp.int32) if self._moe_prefill
                          else None)
        if self._recurrent:
            if role != "unified":
                raise ValueError(
                    f"role={role!r} on a model with recurrent layers: block "
                    "transfer ships a request's KV blocks, and its recurrent "
                    "state is not in them — shipping the per-slot state "
                    "beside the blocks is not written")
            if rolling:
                raise ValueError(
                    "rolling=True on a model with recurrent layers: a "
                    "recurrent layer keeps no positions to roll over, and "
                    "its attention layers carry no window")
            if spec_draft is not None:
                raise ValueError(
                    "spec_draft on a model with recurrent layers: a rejected "
                    "draft token has already advanced the recurrent state, "
                    "and rolling it back needs a state snapshot per round, "
                    "which is not written")
            if kv_dtype == "int8":
                raise ValueError(
                    "kv_dtype='int8' on a model with recurrent layers: the "
                    "recurrent state is float32 and has no quantised form; "
                    "an engine half of whose state is int8 is not written")
            if not paged:
                raise ValueError(
                    "a model with recurrent layers needs paged=True: the "
                    "dense pool's staging and row-commit programs copy keys "
                    "and values only")
        if quantize == "int8" and not all(b.int8_weights for b in blocks):
            raise ValueError(
                "quantize='int8' on a model with a block the weight "
                "quantiser does not know: it finds matmul weights by "
                "TransformerBlock's names, and would leave the other "
                "blocks as they are without saying so")
        # -- speculation + quantization knobs (all default OFF: the engine
        #    is bit-identical to its pre-speculation self until asked)
        if quantize not in (None, "int8", "bf16"):
            raise ValueError(f"quantize must be None, 'int8' or 'bf16', "
                             f"got {quantize!r}")
        if kv_dtype not in (None, "int8"):
            raise ValueError(f"kv_dtype must be None or 'int8', got "
                             f"{kv_dtype!r}")
        if int(spec_len) < 1:
            raise ValueError(f"spec_len must be >= 1, got {spec_len}")
        self.spec_len = int(spec_len)
        self.quantize = quantize
        self.kv_dtype = kv_dtype
        if spec_draft is None:
            self._draft_model, self._draft_params = None, None
        else:
            if isinstance(spec_draft, FittedModel):
                self._draft_model = spec_draft.model
                self._draft_params = spec_draft.params
            else:
                self._draft_model, self._draft_params = spec_draft
            _check_supported(self._draft_model)
            tv, dv = _vocab_size(self.model), _vocab_size(self._draft_model)
            if tv is not None and dv is not None and tv != dv:
                raise ValueError(
                    f"target and draft vocabularies differ: {tv} vs {dv} — "
                    f"draft proposals would be meaningless")
        # quantize weights ONCE at construction; attach_ps re-quantizes
        # every pulled center through the same path.  The skeleton (scalar
        # zeros of the dtypes the parameters came in) is what set_weights
        # maps a pulled flat weight list onto before the engine takes it
        # for its own (re-quantization, the ``params`` setter)
        self._fp_skel = tmap(
            lambda x: np.zeros((), x.dtype if hasattr(x, "dtype")
                               else np.asarray(x).dtype), params)
        if quantize is not None:
            params = _quantize_weights(params, quantize)
            if self._draft_params is not None:
                self._draft_params = _quantize_weights(self._draft_params,
                                                       quantize)
        self.num_slots = int(num_slots)
        if self.num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        limit = _context_limit(self.model)
        if max_len is None:
            if limit is None:
                raise ValueError("max_len is required for models without a "
                                 "positional-embedding range")
            max_len = limit
        if limit is not None and max_len > limit:
            raise ValueError(f"max_len {max_len} exceeds the model's "
                             f"positional-embedding range {limit}")
        self.max_len = int(max_len)
        self.rolling = bool(rolling)
        self.queue_capacity = int(queue_capacity)
        self.prefills_per_step = max(int(prefills_per_step), 1)
        if int(prefill_chunk) < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got "
                             f"{prefill_chunk}")
        self.prefill_chunk = int(prefill_chunk)
        if default_deadline_s is not None and default_deadline_s <= 0:
            raise ValueError(f"default_deadline_s must be > 0, got "
                             f"{default_deadline_s}")
        self.default_deadline_s = default_deadline_s
        self._vocab = _vocab_size(self.model)

        # -- slot pool: ONE batched cache, one host-side row of state per
        #    slot.  With speculation on a rolling pool the ring gets
        #    spec_len slots of slack so the L = spec_len + 1 verify write
        #    never overwrites the oldest query's attention window; with
        #    kv_dtype="int8" entries are stored as codes + per-entry
        #    scales at roughly half the bf16 slot bytes.  The draft model
        #    gets its OWN pool over the same slot indices (full-length:
        #    draft caches are small next to the target's)
        ring_slack = (self.spec_len if (rolling and spec_draft is not None)
                      else 0)
        # -- paged KV pool (paged=True): the slot pool becomes a flat
        #    arena of block_size-token blocks + per-request block tables;
        #    blocks are allocated on demand (capacity = tokens in flight,
        #    not num_slots × max_len) and — non-rolling — shared across
        #    requests through the radix prefix index.  Default kv_blocks
        #    matches the dense pool's capacity exactly, so paged=True
        #    alone changes layout, not limits.
        self.paged = bool(paged)
        if int(block_size) < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.block_size = int(block_size)
        self._pool = None
        self._plans: Dict[int, _BlockPlan] = {}
        if self.paged:
            bs = self.block_size
            if self.rolling:
                windows = {layer._mha().attention_window
                           for layer in self.model.layers
                           if hasattr(layer, "_mha")}
                if len(windows) != 1:
                    raise ValueError(
                        "paged rolling pools need one uniform "
                        "attention_window across every TransformerBlock "
                        f"(the block table is per-request, shared by all "
                        f"layers); got windows {sorted(windows)}")
                self._t_view = min(windows.pop() + ring_slack, self.max_len)
            else:
                self._t_view = self.max_len
            self._blocks_per_slot = -(-self._t_view // bs)
            if self._draft_model is not None:
                self._blocks_per_slot = max(self._blocks_per_slot,
                                            -(-self.max_len // bs))
            if kv_blocks is None:
                kv_blocks = self.num_slots * self._blocks_per_slot
            self.kv_blocks = int(kv_blocks)
            if self.kv_blocks < self._blocks_per_slot:
                raise ValueError(
                    f"kv_blocks {self.kv_blocks} cannot hold even one "
                    f"max-length request ({self._blocks_per_slot} blocks "
                    f"of {bs} tokens)")
            self.caches = _dec.init_paged_arena(self.model, self.kv_blocks,
                                                bs, kv_dtype=kv_dtype,
                                                num_slots=self.num_slots)
            if self._draft_model is not None:
                self.d_caches = _dec.init_paged_arena(
                    self._draft_model, self.kv_blocks, bs,
                    kv_dtype=kv_dtype)
            else:
                self.d_caches = None
        else:
            self.kv_blocks = None
            self.caches = init_cache(self.model, self.num_slots,
                                     self.max_len, rolling=self.rolling,
                                     kv_dtype=kv_dtype,
                                     ring_slack=ring_slack)
            if self._draft_model is not None:
                self.d_caches = init_cache(self._draft_model,
                                           self.num_slots, self.max_len,
                                           kv_dtype=kv_dtype)
            else:
                self.d_caches = None
        #: bytes of per-slot recurrent state ONE live row owns, over all
        #: recurrent layers (0: the model keeps none): what a decode step
        #: reads and writes for it
        self._recurrent_row_bytes = sum(
            leaf.nbytes // self.num_slots
            for layer, c in zip(self.model.layers, self.caches)
            if isinstance(layer, _dec._BLOCKS)
            and layer.state_kind == "recurrent"
            for leaf in jax.tree_util.tree_leaves(c))
        self._handles: List[Optional[RequestHandle]] = [None] * self.num_slots
        self._free: List[int] = list(range(self.num_slots - 1, -1, -1))
        self._positions = np.zeros((self.num_slots,), np.int32)
        self._active = np.zeros((self.num_slots,), bool)

        # -- admission queues (the ONLY cross-thread state besides
        #    handles): one FIFO list per tenant, picked by stride-based
        #    weighted-fair scheduling (interactive-tier tenants first).
        #    With no policies registered everything lands in the single
        #    "default" queue and every pick is plain FIFO — bit-identical
        #    to the pre-QoS deque.  _qdepth is the global depth (the
        #    backpressure bound stays engine-wide); _q_int counts queued
        #    interactive-tier requests (the preemption-pressure signal).
        self._queues: Dict[str, List[RequestHandle]] = {}
        self._qdepth = 0
        self._q_int = 0
        self._wf_pass: Dict[str, float] = {}
        self._tenants: Dict[str, TenantPolicy] = {}
        for pol in (tenants or []):
            if not isinstance(pol, TenantPolicy):
                raise ValueError(f"tenants must be TenantPolicy instances, "
                                 f"got {type(pol).__name__}")
            self._tenants[pol.name] = pol
        self._qlock = threading.Lock()
        self._not_full = threading.Condition(self._qlock)
        self._have_work = threading.Condition(self._qlock)
        self._ids = itertools.count(1)   # request ids

        # -- preemption state (QoS swap-out): suspended requests live here
        #    holding NO slot and NO arena blocks — just a host-memory copy
        #    of their live KV blocks + decode frontier.  Scheduler-thread
        #    confined except for the read in _declare_dead (same snapshot
        #    discipline as _handles there).  _preempt_ids carries explicit
        #    preempt() marks to the scheduler; _int_blocked is set when an
        #    interactive admission failed on BLOCK exhaustion (free slot,
        #    empty arena) so starvation-triggered preemption also fires on
        #    pool pressure, not just slot pressure.
        self._suspended: Dict[int, _SuspendedReq] = collections.OrderedDict()
        self._preempt_ids: set = set()
        self._int_blocked = False
        self._can_preempt = (self.paged and not self.rolling
                             and self._draft_model is None
                             and self.role == "unified"
                             and not self._recurrent)
        self._swap_gather_fn = None
        self._swap_ingest_fn = None

        # -- compiled prefill programs + device-resident decode state
        #    (compiled once per engine and shape key: shapes are fixed)
        self._chunk_width = min(self.prefill_chunk, self.max_len)
        self._buckets = _pow2_buckets(self._chunk_width)
        self._pending: "collections.deque" = collections.deque()
        self._iterations = 0  # step() calls so far (serve.iteration's `it`)
        #: the loop's own account of every iteration (metrics.EngineAccount:
        #: seconds by phase, iterations by class, the token gap, the slowest
        #: iterations, why the queue's head waited); its stamps open the
        #: loop's spans, so it is on whether a profiler session is or not.
        #: NOT a key of ``stats``: the pair's and the router's merges sum
        #: numbers and concatenate lists
        self.account = EngineAccount()
        self._prefilling: Dict[int, _PrefillJob] = {}
        #: what the decode program's attention reads K and V through:
        #: "kernel" (ops.paged_attention, in place) or "gather" — settled
        #: where the program is built, reported on serve.decode_dispatch
        self._decode_attn = "gather"
        self._dev_tok = jnp.zeros((self.num_slots,), jnp.int32)
        self._dev_pos = jnp.zeros((self.num_slots,), jnp.int32)
        self._dev_act = jnp.zeros((self.num_slots,), bool)
        self._dev_temp = jnp.zeros((self.num_slots,), jnp.float32)
        self._dev_topk = jnp.zeros((self.num_slots,), jnp.int32)
        self._dev_topp = jnp.zeros((self.num_slots,), jnp.float32)
        self._dev_keys = jnp.zeros((self.num_slots, 2), jnp.uint32)
        if self.paged:
            # device-resident block tables (one row per slot, null-
            # filled — null = kv_blocks, the arena's junk block) plus
            # the host-side allocator/prefix-trie.  A retired slot's
            # table row is re-nulled so its idle decode passes junk
            # into the null block, never a reallocated block.
            bs = self.block_size
            self._t_tbl = -(-self._t_view // bs) + 1
            self._dev_bt = jnp.full((self.num_slots, self._t_tbl),
                                    self.kv_blocks, jnp.int32)
            if self._draft_model is not None:
                self._d_tbl = -(-self.max_len // bs) + 1
                self._dev_dbt = jnp.full(
                    (self.num_slots, self._d_tbl), self.kv_blocks,
                    jnp.int32)
            else:
                self._dev_dbt = None
            self._copy_fn = self._build_copy_fn()
            if self.role == "prefill":
                # read-only arena gather (the extraction half of a
                # disaggregated transfer) — fixed (blocks_per_slot ×
                # block_size) row vector, so one trace serves every
                # prompt length (junk rows gather the null block and
                # are sliced off on host)
                self._gather_fn = jax.jit(_dec.gather_blocks)
            if self.role == "decode":
                self._ingest_fn = self._build_ingest_fn()
        self._decode_fn = self._build_device_step_fn()
        self._deact_fn = self._build_deact_fn()
        self._bucket_fns: Dict[int, Any] = {}
        self._stage_fns: Dict[int, Any] = {}
        self._final_fns: Dict[int, Any] = {}
        if self._draft_model is not None:
            self._draft_params = jax.device_put(self._draft_params)
            self._spec_fn = self._build_spec_fn()

        # -- hot weight reload (stretch; off unless attach_ps is called)
        self._ps_addr: Optional[Tuple[str, int]] = None
        self._reload_every = 0
        self._reload_sock: Optional[socket.socket] = None
        self._reload_pool = networking.BufferPool()
        self._reload_policy = None          # resilience.RetryPolicy or None
        #: sharded attachment (attach_ps shard_plan/shard_addrs): pulls
        #: gather the center across every shard through a ShardedPSClient
        #: instead of one socket's 'p'
        self._ps_shard_plan = None
        self._ps_shard_addrs: Optional[List[Tuple[str, int]]] = None
        self._reload_client = None          # ps_sharding.ShardedPSClient
        #: optional (t_monotonic, center_clock) callback fired after every
        #: SUCCESSFUL pull — the freshness seam deployment_online.py hooks
        #: (called on the decode thread; must be cheap and non-raising)
        self._reload_listener = None

        # -- scheduler thread + stats + failure state
        self._thread: Optional[threading.Thread] = None
        self._running = False
        self._draining = False
        self._dead: Optional[BaseException] = None  # the EngineDead cause
        #: decode-loop heartbeat (monotonic): stamped once per scheduler
        #: iteration, idle iterations included — a stale beat means the
        #: loop is wedged inside a decode step (EngineSupervisor watches it)
        self.last_beat = time.monotonic()
        self.stats: Dict[str, Any] = {
            "requests_submitted": 0, "requests_completed": 0,
            "requests_rejected": 0, "tokens_generated": 0,
            "prefills": 0, "decode_steps": 0, "active_slot_steps": 0,
            # decode steps whose program reads K and V in place through
            # the paged decode kernel (every step or none of an engine's)
            "paged_kernel_steps": 0,
            # decode steps whose live rows opened the sampler's gates
            # (_sampler_work): some row samples (the divide and the draw
            # ran) / some sampling row filters (the sort ran too); a step
            # in neither took the argmax alone
            "sampler_draw_steps": 0, "sampler_filter_steps": 0,
            "queue_peak": 0, "slot_requests": [0] * self.num_slots,
            "weight_reloads": 0,
            # hot-reload hardening observables (docs/serving.md): reloads
            # mirrors weight_reloads (successful pulls — both kept so
            # pre-existing consumers and the online-deployment stats agree),
            # reload_failures counts pulls abandoned after the retry
            # policy's re-dial budget, center_generation is the PS center's
            # update clock stamped on the last successful pull (None until
            # one lands) — the commit→pull→decode generation chain
            # deployment_online.py tracks freshness through
            "reloads": 0, "reload_failures": 0, "center_generation": None,
            # failure-semantics observables (this PR's contract surface):
            # cancelled/expired count retirements by reason; failed counts
            # handles the engine abandoned with EngineDead; reclaim_ms is
            # one sample per mid-run cancel/deadline slot reclamation
            # (cancel/expiry instant → slot free)
            "requests_cancelled": 0, "requests_expired": 0,
            "requests_failed": 0, "slot_reclaim_ms": [],
            # prefill fast-path observables: chunk-program invocations,
            # batched-prefill width (mean admitted requests per bucket
            # program call), prompt tokens prefilled, and the decode
            # loop's transfer discipline (decode-only iterations perform
            # zero h2d and exactly one d2h — the sampled token row)
            "prefill_chunks": 0, "prefill_batches": 0,
            "prefill_batched_requests": 0, "prefill_batch_size_mean": None,
            "prefill_tokens": 0,
            "h2d_transfers": 0, "d2h_transfers": 0,
            # speculative-decoding observables, the same vocabulary as
            # speculative_generate's per-run stats dict: ``drafted`` /
            # ``accepted`` count draft proposals and accepted prefix
            # tokens, ``verify_calls`` the batched target verify forwards
            # (``target_calls`` mirrors it verbatim so offline and serving
            # speculation report through one key set)
            "drafted": 0, "accepted": 0,
            "verify_calls": 0, "target_calls": 0,
            # paged-pool observables: blocks_allocated counts fresh
            # allocations, blocks_reused trie-shared blocks, prefix_hits/
            # prefix_hit_tokens admissions (and their token counts) served
            # from the radix index, cow_copies boundary copy-on-writes,
            # blocks_evicted LRU reclaims of refcount-0 cached chains.
            # kv_pool_bytes is the on-device pool footprint gauge (arena
            # bytes when paged, the dense slot pool's otherwise) — the
            # byte-accounting that proves block reuse next to PR 11's
            # kv_cache_bytes math
            "blocks_allocated": 0, "blocks_reused": 0, "blocks_evicted": 0,
            "prefix_hits": 0, "prefix_hit_tokens": 0, "cow_copies": 0,
            "kv_pool_bytes": _quant.kv_cache_bytes(self.caches),
            # disaggregation transfer accounting (charged against the
            # PR 9 transfer-discipline counters — gather fetches and
            # scatter uploads land in d2h/h2d_transfers too):
            # kv_blocks_shipped/_bytes count blocks a prefill-role engine
            # extracted, kv_blocks_ingested/_bytes blocks a decode-role
            # engine admitted from a shipped set; transfer_ms is one
            # sample per extraction/ingest (device dispatch + host copy)
            "kv_blocks_shipped": 0, "kv_block_bytes_shipped": 0,
            "kv_blocks_ingested": 0, "kv_block_bytes_ingested": 0,
            "transfer_ms": [],
            # multi-tenant QoS observables: preemptions/resumes count
            # swap-out/swap-in events; the block/byte counters account the
            # swapped KV payloads (their d2h/h2d dispatches also land in
            # the PR 9 transfer-discipline counters); preempt_swap_ms /
            # preempt_resume_ms are one sample per suspend / resume
            # (device gather/ingest + host copy); quota_refused counts
            # token-bucket admission refusals (NOT requests_rejected —
            # a policy refusal must not dilute shed_rate); "tenants" maps
            # tenant name -> its own submitted/completed/shed/
            # quota_refused/preemptions/resumes counters
            "preemptions": 0, "resumes": 0,
            "kv_blocks_swapped_out": 0, "kv_block_bytes_swapped_out": 0,
            "kv_blocks_resumed": 0, "kv_block_bytes_resumed": 0,
            "preempt_swap_ms": [], "preempt_resume_ms": [],
            "quota_refused": 0, "tenants": {},
            # hybrid models.  The expert layers' load, read off the decode
            # step's own token fetch (no second device read), summed over
            # layers and decode steps: assignments of live rows to experts
            # held here, held experts that got at least one, the fullest
            # expert's rows; moe_layer_steps counts the (layer, step) pairs
            # summed over.  recurrent_slots_cleared: admissions whose slot's
            # recurrent state was started from zero
            "moe_assignments_held": 0, "moe_experts_touched": 0,
            "moe_load_max": 0, "moe_layer_steps": 0,
            "recurrent_slots_cleared": 0,
            # live rows x a row's recurrent state, read and written, summed
            # over decode steps: the least bytes the state costs the steps
            "recurrent_state_bytes_moved": 0,
            # the same two counts over PREFILL units (buckets, chunks and
            # final chunks of the paged pool), added when the unit's first
            # token is drained; moe_prefill_layer_units counts the (layer,
            # unit) pairs summed over
            "moe_prefill_assignments_held": 0,
            "moe_prefill_experts_touched": 0, "moe_prefill_layer_units": 0,
            # expert up-projections held transposed, in the layout the
            # grouped matmul reads (set by the ``params`` setter)
            "moe_up_projections_transposed": 0,
        }
        self.params = params
        if self.paged:
            # a recurrent layer's state is not in the blocks: a matched
            # prefix would skip tokens that state has to see, so the radix
            # index matches and inserts nothing (every prompt is prefilled
            # whole; prefix_hit_tokens stays 0)
            self._pool = _PagedKVPool(
                self.kv_blocks, self.block_size,
                share=not self.rolling and not self._recurrent,
                stats=self.stats)

        # -- lock-free load snapshot (the routing surface).  A plain dict
        #    republished by REFERENCE assignment from submit/step/drain/
        #    death sites — always OUTSIDE the admission-lock blocks, so
        #    readers (`load()`, a ServingRouter's dispatch loop, the wire
        #    's' probe) never touch `_qlock` or the scheduler's hot path.
        #    Values may lag one scheduler iteration; routing only needs a
        #    recent signal, not a linearizable one.
        self._load_snapshot: Dict[str, Any] = {
            "queue_depth": 0,
            "slots_free": self.num_slots,
            "slots_total": self.num_slots,
            "active": 0,
            "trie_blocks": 0,
            "queue_capacity": self.queue_capacity,
            "max_len": self.max_len,
            "draining": False,
            "dead": False,
            "prefix_hit_tokens": 0,
            "prefill_tokens": 0,
            "tokens_generated": 0,
            "requests_completed": 0,
            "requests_failed": 0,
            "queued_interactive": 0,
        }

    # ------------------------------------------------------------------ jit
    # ------------------------------------------- compiled prefill programs
    #
    # The engine's whole compute surface is a handful of jitted
    # programs, cached per shape key so live traffic never re-traces:
    #
    #  - ``_bucket_fn(L)`` — ONE batched forward prefills up to
    #    ``prefills_per_step`` admitted prompts right-padded to bucket
    #    length L, samples each row's first token, scatters the cache rows
    #    into the pool (ring-converted per row for rolling engines) and
    #    the per-slot decode state in the same program.  Unused batch rows
    #    carry slot index ``num_slots``: every one of their writes drops
    #    (``mode="drop"``), which is also what makes ``warmup()``'s
    #    precompilation side-effect free.
    #  - ``_stage_fn(C)`` / ``_final_fn(C)`` — chunked prefill: chunks
    #    accumulate into a full-length one-row STAGING cache (``q_offset``
    #    = the chunk offset, exactly the scalar decode-walker path); the
    #    final chunk samples the first token and commits the whole row to
    #    the pool in one program (ring-collapsed via ``ring_from_prefill``
    #    for rolling engines, a full-row overwrite otherwise).  Staging is
    #    NOT optional: the per-row decode step writes junk k/v into every
    #    pool row at its stale position — free and prefilling slots
    #    included — which an atomic full-row commit overwrites but an
    #    in-place chunk accumulation would race (a junk write at a stale
    #    position below the chunk frontier corrupts already-written
    #    prompt positions).
    #
    # Every traced call goes through ``_dec`` (the decode MODULE) so a
    # trace is observable/countable.

    def _bucket_fn(self, width: int):
        fn = self._bucket_fns.get(width)
        if fn is None:
            fn = self._bucket_fns[width] = self._build_bucket_fn(width)
        return fn

    def _stage_fn(self, width: int):
        fn = self._stage_fns.get(width)
        if fn is None:
            fn = self._stage_fns[width] = self._build_stage_fn(width)
        return fn

    def _final_fn(self, width: int):
        fn = self._final_fns.get(width)
        if fn is None:
            fn = self._final_fns[width] = self._build_final_fn(width)
        return fn

    def _build_device_step_fn(self):
        """The decode step: state advances ON DEVICE (donated
        caches, new positions), so a steady-state iteration uploads nothing
        and reads back only the sampled token row.  Paged engines take the
        device block tables as an extra (read-only) argument and write/
        read through them — per-row block-indexed cache writes inside the
        same jitted step, and the read either in place by the paged decode
        kernel or by gather, as ``_dec.paged_kernel_applies`` finds (the
        speculative round is its own program, built on the gather path)."""
        model, rolling = self.model, self.rolling

        if self.paged:
            page, view = self.block_size, self._t_view
            if self._draft_model is None and _dec.paged_step_on_kernel(
                    model, self.caches, self.num_slots, page, view,
                    ring=rolling):
                self._decode_attn = "kernel"

            moe = self._moe_layers > 0

            def pstep(params, caches, bt, tok, positions, active, temp,
                      topk, topp, keys):
                pv = _dec.PagedView(bt, page, view, ring=rolling)
                aux = [] if moe else None
                logits, caches = _dec.decode_step(
                    model, params, caches, tok, positions, paged=pv,
                    rows=_dec.RowView(live=active), aux=aux)
                # a retired slot keeps its temp/topk/topp (_build_deact_fn
                # clears act alone) and its row's token is discarded below:
                # it reaches the sampler as a greedy row, so a stale
                # sampling slot costs a greedy batch no draw and no sort
                nxt = _dec.sample_logits_batched(
                    logits, positions, jnp.where(active, temp, 0.0), keys,
                    topk, topp)
                out = jnp.where(active, nxt, tok)
                positions = jnp.where(active, positions + 1, positions)
                if moe:
                    # the experts' counters ride behind the tokens in the
                    # one array the host fetches
                    return out, caches, positions, jnp.concatenate(
                        [out, sum(aux)])
                return out, caches, positions

            return jax.jit(pstep, donate_argnums=(1, 4))

        def step(params, caches, tok, positions, active, temp, topk, topp,
                 keys):
            logits, caches = _dec.decode_step(model, params, caches, tok,
                                              positions, rolling)
            nxt = _dec.sample_logits_batched(
                logits, positions, jnp.where(active, temp, 0.0), keys, topk,
                topp)
            out = jnp.where(active, nxt, tok)
            positions = jnp.where(active, positions + 1, positions)
            return out, caches, positions

        return jax.jit(step, donate_argnums=(1, 3))

    def _build_deact_fn(self):
        """Slot retirement on device: clear the active flag and — paged —
        re-null the slot's block-table row(s), so the retired row's idle
        decode junk drops into the null block instead of blocks the
        allocator may already have handed to a new request."""
        if not self.paged:
            return jax.jit(lambda act, slot: act.at[slot].set(False))
        null = jnp.int32(self.kv_blocks)
        if self._draft_model is None:
            return jax.jit(lambda act, bt, slot: (
                act.at[slot].set(False), bt.at[slot].set(null)))
        return jax.jit(lambda act, bt, dbt, slot: (
            act.at[slot].set(False), bt.at[slot].set(null),
            dbt.at[slot].set(null)))

    def _build_copy_fn(self):
        """The copy-on-write program: duplicate one physical block (all
        layers, target AND draft arenas) so an admission that matched a
        cached block PARTIALLY can keep writing its own suffix into the
        copy while the original stays shared."""
        bs = self.block_size

        def copy_one(caches, src, dst):
            def cp(leaf):
                row = jax.lax.dynamic_slice_in_dim(leaf, src * bs, bs, 0)
                return jax.lax.dynamic_update_slice_in_dim(leaf, row,
                                                           dst * bs, 0)
            # blocks are keys and values: a recurrent layer's per-slot
            # state has none to copy
            return [c if c is None or "k" not in c
                    else {k: cp(v) for k, v in c.items()} for c in caches]

        if self._draft_model is None:
            return jax.jit(copy_one, donate_argnums=(0,))

        def copy_both(caches, dcaches, src, dst):
            return copy_one(caches, src, dst), copy_one(dcaches, src, dst)

        return jax.jit(copy_both, donate_argnums=(0, 1))

    def _build_ingest_fn(self):
        """Decode-role admission program, ONE jitted dispatch per shipped
        request: scatter the transferred block payload into this engine's
        own arena slots (``rows`` — junk rows padded to the null block, so
        the shape is fixed at ``blocks_per_slot × block_size``) and
        install the slot's device row (block table, current token at the
        shipped position, sampling params, RNG key) exactly as a bucket
        prefill program would have.  ``mode="drop"`` on every install
        lets warmup target slot ``num_slots``."""
        def ingest(caches, bt, tok, pos, act, temp, topk, topp, keys,
                   rows, payload, slot, row_bt, r_tok, r_pos, r_temp,
                   r_topk, r_topp, r_keys):
            caches = _dec.scatter_blocks(caches, rows, payload)
            bt = bt.at[slot].set(row_bt, mode="drop")
            tok = tok.at[slot].set(r_tok, mode="drop")
            pos = pos.at[slot].set(r_pos, mode="drop")
            act = act.at[slot].set(True, mode="drop")
            temp = temp.at[slot].set(r_temp, mode="drop")
            topk = topk.at[slot].set(r_topk, mode="drop")
            topp = topp.at[slot].set(r_topp, mode="drop")
            keys = keys.at[slot].set(r_keys, mode="drop")
            return caches, bt, tok, pos, act, temp, topk, topp, keys

        # tok (argnum 2) is NOT donated: with one-step lookahead the live
        # ``_dev_tok`` IS the previous decode step's still-pending output
        # array — donating it would delete the buffer ``_drain_pending``
        # has yet to fetch (the same reason no decode/prefill program
        # donates its token state)
        return jax.jit(ingest, donate_argnums=(0, 1, 3, 4, 5, 6, 7, 8))

    def _build_spec_fn(self):
        """The speculative decode round — ONE jitted program replacing the
        plain device step when ``spec_draft`` is set: k = ``spec_len``
        per-row draft steps (the draft's own slot pool, same slot
        indices), ONE batched L = k + 1 target verify forward, per-row
        accept/commit — greedy rows take the longest drafted prefix
        matching the target's own argmax plus the correction/bonus token
        (so their committed chain IS the target argmax chain, token-
        identical to non-speculative greedy); sampled rows run the
        Leviathan/Chen rejection rule against identically-warped
        distributions with keys folded per (position, purpose), so the
        committed distribution is exactly the warped target's — then a
        draft back-fill step for the full-accept cache hole and the
        device state advance.  Accept lengths are heterogeneous: row r
        advances ``n_r`` in 1..k+1 positions per round.  The output packs
        row r's committed tokens (first ``n_r`` of k+1 columns valid)
        plus ``n_r`` in the last column — ONE drained array per round,
        preserving the one-d2h-per-iteration discipline.

        Rejected-position cache entries are never rolled back: the next
        round's writes start at each row's new frontier and overwrite
        them in-program before any query can attend that far (the same
        no-rollback argument as ``speculative_generate``; on rolling
        pools the ring's ``spec_len`` slack slots keep the oldest query's
        window intact under the L-token write)."""
        model, rolling = self.model, self.rolling
        draft = self._draft_model
        k = self.spec_len
        paged = self.paged
        page = self.block_size
        t_view = self._t_view if paged else None
        d_view = self.max_len

        def fold(keys, idx, tag):
            # per-(row, absolute position, purpose) keys: tag 1 = draft
            # proposal, 2 = accept uniform, 3 = residual/bonus draw.  A
            # position's draws are pure functions of (request key, index),
            # so re-drafting an index after a rejection reuses bits that
            # never influenced any committed token — exactness holds
            ks = jax.vmap(jax.random.fold_in)(keys, idx)
            return jax.vmap(jax.random.fold_in)(ks, jnp.full_like(idx, tag))

        def round_(params, dparams, caches, dcaches, tok, pos, act, temp,
                   topk, topp, keys, bt=None, dbt=None):
            b = tok.shape[0]
            sampled = temp > 0.0
            safe_t = jnp.where(sampled, temp, 1.0)
            # paged pools: the round's every cache access goes through the
            # slot block tables (read-only here — allocation is host-side)
            pv_t = (_dec.PagedView(bt, page, t_view, ring=rolling)
                    if bt is not None else None)
            pv_d = (_dec.PagedView(dbt, page, d_view)
                    if dbt is not None else None)

            # filtered logits reach a committed token only through rows
            # that sample and are live (every other row commits the argmax
            # chain, or nothing): the rest filter nothing, so a round whose
            # live rows are greedy or unfiltered runs no sort
            # (filter_logits_batched's own gate; a retired slot keeps its
            # topk/topp).  The draws themselves stay as they are
            filt = sampled & act
            topk = jnp.where(filt, topk, 0)
            topp = jnp.where(filt, topp, 0.0)

            def warp(l):
                return _dec.filter_logits_batched(l / safe_t[:, None],
                                                  topk, topp)

            # -- draft phase: k per-row single-token steps, own pool
            d_toks, q_logits = [], []
            t = tok
            for i in range(k):
                dl, dcaches = _dec.decode_step(draft, dparams, dcaches, t,
                                               pos + i, paged=pv_d)
                wl = warp(dl)
                prop = jax.vmap(jax.random.categorical)(
                    fold(keys, pos + i + 1, 1), wl).astype(jnp.int32)
                t = jnp.where(sampled, prop,
                              jnp.argmax(dl, axis=-1).astype(jnp.int32))
                d_toks.append(t)
                q_logits.append(wl)
            drafted = jnp.stack(d_toks, axis=1)                   # (B, k)

            # -- verify: one batched target forward over [cur, d_1..d_k];
            # logits[:, i] scores the token following fed position i, so a
            # fully-accepted row still has a bonus distribution at index k
            fed = jnp.concatenate([tok[:, None], drafted], axis=1)
            logits, caches = _dec._forward(model, params, caches, fed, pos,
                                           rolling and pv_t is None,
                                           paged=pv_t)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

            # greedy accept: longest drafted prefix matching the target's
            # argmax; the committed chain is the argmax chain itself
            match = drafted == greedy[:, :k]
            a_g = jnp.sum(jnp.cumprod(match.astype(jnp.int32), 1), 1)

            # sampled accept: accept x ~ q with prob min(1, p(x)/q(x));
            # first rejection redraws from norm(max(p - q, 0)), a full
            # accept draws the bonus from warped p — all per row
            pk = jnp.reshape(_dec.filter_logits_batched(
                jnp.reshape(logits[:, :k] / safe_t[:, None, None],
                            (b * k, -1)),
                jnp.repeat(topk, k), jnp.repeat(topp, k)), (b, k, -1))
            p_probs = jax.nn.softmax(pk, axis=-1)
            q_probs = jax.nn.softmax(jnp.stack(q_logits, 1), axis=-1)
            px = jnp.take_along_axis(p_probs, drafted[..., None],
                                     axis=-1)[..., 0]
            qx = jnp.take_along_axis(q_probs, drafted[..., None],
                                     axis=-1)[..., 0]
            u = jnp.stack(
                [jax.vmap(lambda kk: jax.random.uniform(kk, ()))(
                    fold(keys, pos + i + 1, 2)) for i in range(k)], axis=1)
            accept = u * jnp.maximum(qx, 1e-30) < px
            a_s = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), 1), 1)
            ai = jnp.clip(a_s, 0, k - 1)
            p_a = jnp.take_along_axis(p_probs, ai[:, None, None], 1)[:, 0]
            q_a = jnp.take_along_axis(q_probs, ai[:, None, None], 1)[:, 0]
            res = jnp.maximum(p_a - q_a, 0.0)
            rsum = jnp.sum(res, axis=-1, keepdims=True)
            # res == 0 iff p <= q everywhere, i.e. p == q: fall back to p
            res = jnp.where(rsum > 0.0, res / jnp.maximum(rsum, 1e-38),
                            p_a)
            bonus = jax.nn.softmax(warp(logits[:, k]), axis=-1)
            dist = jnp.where((a_s == k)[:, None], bonus, res)
            corr = jax.vmap(jax.random.categorical)(
                fold(keys, pos + a_s + 1, 3),
                jnp.log(jnp.maximum(dist, 1e-38))).astype(jnp.int32)
            committed_s = jnp.concatenate(
                [drafted, jnp.zeros((b, 1), jnp.int32)], axis=1)
            committed_s = committed_s.at[jnp.arange(b), a_s].set(corr)

            # -- per-row heterogeneous commit + device state advance
            a = jnp.where(sampled, a_s, a_g)
            committed = jnp.where(sampled[:, None], committed_s, greedy)
            n = jnp.where(act, a + 1, 0)
            last = jnp.take_along_axis(committed, a[:, None], axis=1)[:, 0]
            new_tok = jnp.where(act, last, tok)
            new_pos = jnp.where(act, pos + n, pos)

            # draft back-fill: d_k at pos + k — the full-accept rows' cache
            # hole (the committed bonus's predecessor, never fed to the
            # draft); for every other row pos + k is at or past its new
            # frontier, where the junk is masked until overwritten
            _, dcaches = _dec.decode_step(draft, dparams, dcaches,
                                          d_toks[-1], pos + k, paged=pv_d)

            out = jnp.concatenate([committed, n[:, None]], axis=1)
            return out, caches, dcaches, new_tok, new_pos

        if paged:
            def round_paged(params, dparams, caches, dcaches, bt, dbt,
                            tok, pos, act, temp, topk, topp, keys):
                return round_(params, dparams, caches, dcaches, tok, pos,
                              act, temp, topk, topp, keys, bt=bt, dbt=dbt)

            return jax.jit(round_paged, donate_argnums=(2, 3, 6, 7))

        return jax.jit(round_, donate_argnums=(2, 3, 4, 5))

    def _build_bucket_fn(self, width: int):
        if self.paged:
            return self._build_paged_bucket_fn(width)
        model, rolling = self.model, self.rolling
        draft = self._draft_model

        def prefill(params, dparams, pool, dpool, tok, pos, act, temp,
                    topk, topp, keys, prompts, p_lens, slots, r_temp,
                    r_topk, r_topp, r_keys):
            rows = init_cache(model, prompts.shape[0], width)
            # right-padded batch: the causal mask alone keeps pad keys out
            # of every real row (see _mha_forward), and the pad slots each
            # row's prefill writes stay behind its decode kv_length
            # frontier until overwritten
            logits, rows = _dec._forward(model, params, rows, prompts, 0)
            idx = jnp.clip(p_lens - 1, 0, width - 1)
            last = jnp.take_along_axis(logits, idx[:, None, None],
                                       axis=1)[:, 0]
            first = _dec.sample_logits_batched(last, p_lens - 1, r_temp,
                                               r_keys, r_topk, r_topp)
            out = [first,
                   [_commit_rows(big, row, slots, width, rolling, p_lens)
                    for big, row in zip(pool, rows)]]
            if draft is not None:
                # the draft shares the slot layout: prefill its pool from
                # the same prompts (logits unused — the draft's LM head
                # dead-code-eliminates out of this program)
                drows = init_cache(draft, prompts.shape[0], width)
                _, drows = _dec._forward(draft, dparams, drows, prompts, 0)
                out.append(
                    [_commit_rows(big, row, slots, width, False, p_lens)
                     for big, row in zip(dpool, drows)])
            out += [tok.at[slots].set(first, mode="drop"),
                    pos.at[slots].set(p_lens, mode="drop"),
                    act.at[slots].set(True, mode="drop"),
                    temp.at[slots].set(r_temp, mode="drop"),
                    topk.at[slots].set(r_topk, mode="drop"),
                    topp.at[slots].set(r_topp, mode="drop"),
                    keys.at[slots].set(r_keys, mode="drop")]
            return tuple(out)

        if draft is not None:
            return jax.jit(prefill, donate_argnums=(2, 3))

        def run(params, pool, *rest):
            return prefill(params, None, pool, None, *rest)

        return jax.jit(run, donate_argnums=(1,))

    def _build_paged_bucket_fn(self, width: int):
        """The paged bucket program.  Non-rolling: the batch prefills its
        UNMATCHED SUFFIXES directly into the arena through per-row block
        tables — each row's queries start at its matched length, attend
        the shared prefix blocks through the block-table gather (rows
        admitted in the same program read each other's just-written
        prefix: the layer's scatter covers every row before its gather),
        and write with a ``floor`` at the matched frontier so shared
        blocks are never touched.  Rolling: the dense prefill +
        ``ring_from_prefill`` relayout commits through the block table
        instead of into pool rows (no sharing on rings — ring layout is
        position-dependent).  Either way the program also installs the
        slot rows of the DEVICE block tables, so decode needs no
        per-iteration upload."""
        model, rolling = self.model, self.rolling
        draft = self._draft_model
        page, t_view, d_view = self.block_size, self._t_view, self.max_len

        moe_prefill = self._moe_prefill

        def prefill(params, dparams, pool, dpool, bt, dbt, tok, pos, act,
                    temp, topk, topp, keys, prompts, match, p_lens, slots,
                    row_bt, row_dbt, r_temp, r_topk, r_topp, r_keys):
            aux = [] if moe_prefill else None
            if not rolling:
                pv = _dec.PagedView(row_bt, page, t_view, floor=match,
                                    ceil=p_lens, qcap=p_lens - 1)
                logits, pool = _dec._forward(
                    model, params, pool, prompts, match, paged=pv,
                    rows=_dec.RowView(slots=slots), aux=aux)
                idx = jnp.clip(p_lens - match - 1, 0, width - 1)
                last = jnp.take_along_axis(logits, idx[:, None, None],
                                           axis=1)[:, 0]
            else:
                rows = init_cache(model, prompts.shape[0], width)
                logits, rows = _dec._forward(model, params, rows, prompts,
                                             0)
                idx = jnp.clip(p_lens - 1, 0, width - 1)
                last = jnp.take_along_axis(logits, idx[:, None, None],
                                           axis=1)[:, 0]
                j = jnp.arange(t_view)
                blk = jnp.minimum(j // page, row_bt.shape[1] - 1)
                phys = (jnp.take(row_bt, blk, axis=1) * page
                        + (j % page)[None, :])
                new_pool = []
                for big, row in zip(pool, rows):
                    if big is None:
                        new_pool.append(None)
                        continue
                    rk = _dec.ring_from_prefill(row["k"], p_lens, t_view)
                    rv = _dec.ring_from_prefill(row["v"], p_lens, t_view)
                    new_pool.append(_dec._kv_write(big, (phys,), rk, rv))
                pool = new_pool
            first = _dec.sample_logits_batched(last, p_lens - 1, r_temp,
                                               r_keys, r_topk, r_topp)
            out = [first if not aux else
                   jnp.concatenate([first, _moe_unit_counters(aux)]), pool]
            if draft is not None:
                # the draft pool is always full-view (non-rolling): its
                # prefill runs arena-direct per-row whatever the target's
                # layout — match is 0 for rolling targets (no sharing)
                pv_d = _dec.PagedView(row_dbt, page, d_view, floor=match,
                                      ceil=p_lens, qcap=p_lens - 1)
                _, dpool = _dec._forward(draft, dparams, dpool, prompts,
                                         match, paged=pv_d)
                out.append(dpool)
            out.append(bt.at[slots].set(row_bt, mode="drop"))
            if draft is not None:
                out.append(dbt.at[slots].set(row_dbt, mode="drop"))
            out += [tok.at[slots].set(first, mode="drop"),
                    pos.at[slots].set(p_lens, mode="drop"),
                    act.at[slots].set(True, mode="drop"),
                    temp.at[slots].set(r_temp, mode="drop"),
                    topk.at[slots].set(r_topk, mode="drop"),
                    topp.at[slots].set(r_topp, mode="drop"),
                    keys.at[slots].set(r_keys, mode="drop")]
            return tuple(out)

        if draft is not None:
            return jax.jit(prefill, donate_argnums=(2, 3, 4, 5))

        def run(params, pool, bt, tok, pos, act, temp, topk, topp, keys,
                prompts, match, p_lens, slots, row_bt,
                r_temp, r_topk, r_topp, r_keys):
            return prefill(params, None, pool, None, bt, None, tok, pos,
                           act, temp, topk, topp, keys, prompts, match,
                           p_lens, slots, row_bt, None, r_temp, r_topk,
                           r_topp, r_keys)

        return jax.jit(run, donate_argnums=(1, 2))

    def _build_stage_fn(self, width: int):
        if self.paged and not self.rolling:
            return self._build_paged_stage_fn(width)
        model, draft = self.model, self._draft_model

        def stage(params, staging, toks, offset):
            # mid chunk: cache writes only — the logits (and the whole
            # LM-head matmul) dead-code-eliminate
            _, staging = _dec._forward(model, params, staging, toks, offset)
            return staging

        if draft is None:
            return jax.jit(stage, donate_argnums=(1,))

        def stage_spec(params, dparams, staging, d_staging, toks, offset):
            _, staging = _dec._forward(model, params, staging, toks, offset)
            _, d_staging = _dec._forward(draft, dparams, d_staging, toks,
                                         offset)
            return staging, d_staging

        return jax.jit(stage_spec, donate_argnums=(2, 3))

    def _build_paged_stage_fn(self, width: int):
        """Paged (non-rolling) chunked prefill: chunks write STRAIGHT into
        the request's allocated blocks (no staging cache — the blocks are
        private by construction, and the slot's device table stays null
        until the final chunk, so nothing else can write them).  The
        chunk's queries attend every earlier position — shared prefix
        included — through the block-table gather."""
        model, draft = self.model, self._draft_model
        page, t_view, d_view = self.block_size, self._t_view, self.max_len

        def stage(params, pool, toks, offset, p_len, row_bt, slot,
                  moe=None):
            pv = _dec.PagedView(row_bt, page, t_view, floor=offset,
                                ceil=p_len, qcap=p_len - 1)
            # per-slot (recurrent) state is carried from unit to unit in
            # the slot itself: the decode step leaves a prefilling slot's
            # state alone (its row is not live).  ``moe``: the experts'
            # counters of the request's units so far (None: the model has
            # no experts), carried on to the final chunk, which hands them
            # to the host
            aux = None if moe is None else []
            _, pool = _dec._forward(
                model, params, pool, toks, offset, paged=pv,
                rows=_dec.RowView(slots=jnp.reshape(slot, (1,))), aux=aux)
            return pool, (None if moe is None
                          else moe + _moe_unit_counters(aux))

        if draft is None:
            return jax.jit(stage, donate_argnums=(1,))

        def stage_spec(params, dparams, pool, dpool, toks, offset, p_len,
                       row_bt, row_dbt):
            pv = _dec.PagedView(row_bt, page, t_view, floor=offset,
                                ceil=p_len, qcap=p_len - 1)
            _, pool = _dec._forward(model, params, pool, toks, offset,
                                    paged=pv)
            pv_d = _dec.PagedView(row_dbt, page, d_view, floor=offset,
                                  ceil=p_len, qcap=p_len - 1)
            _, dpool = _dec._forward(draft, dparams, dpool, toks, offset,
                                     paged=pv_d)
            return pool, dpool

        return jax.jit(stage_spec, donate_argnums=(2, 3))

    def _build_paged_final_fn(self, width: int):
        """Paged (non-rolling) final chunk: last suffix tokens into the
        arena + first-token sample + device state install (block-table
        row included) — the paged twin of the dense final commit, minus
        the staging copy it no longer needs."""
        model, draft = self.model, self._draft_model
        page, t_view, d_view = self.block_size, self._t_view, self.max_len

        def final(params, dparams, pool, dpool, bt, dbt, tok, pos, act,
                  temp, topk, topp, keys, toks, slot, offset, p_len,
                  last_idx, row_bt, row_dbt, r_temp, r_topk, r_topp,
                  r_key, moe=None):
            pv = _dec.PagedView(row_bt, page, t_view, floor=offset,
                                ceil=p_len, qcap=p_len - 1)
            aux = None if moe is None else []
            logits, pool = _dec._forward(
                model, params, pool, toks, offset, paged=pv,
                rows=_dec.RowView(slots=jnp.reshape(slot, (1,))), aux=aux)
            first = _dec.sample_logits_batched(
                logits[0, last_idx][None], p_len - 1, r_temp, r_key,
                r_topk, r_topp)
            out = [first if moe is None else jnp.concatenate(
                [first, moe + _moe_unit_counters(aux)]), pool]
            if draft is not None:
                pv_d = _dec.PagedView(row_dbt, page, d_view, floor=offset,
                                      ceil=p_len, qcap=p_len - 1)
                _, dpool = _dec._forward(draft, dparams, dpool, toks,
                                         offset, paged=pv_d)
                out.append(dpool)
            out.append(bt.at[slot].set(row_bt[0], mode="drop"))
            if draft is not None:
                out.append(dbt.at[slot].set(row_dbt[0], mode="drop"))
            out += [tok.at[slot].set(first[0], mode="drop"),
                    pos.at[slot].set(p_len[0], mode="drop"),
                    act.at[slot].set(True, mode="drop"),
                    temp.at[slot].set(r_temp[0], mode="drop"),
                    topk.at[slot].set(r_topk[0], mode="drop"),
                    topp.at[slot].set(r_topp[0], mode="drop"),
                    keys.at[slot].set(r_key[0], mode="drop")]
            return tuple(out)

        if draft is not None:
            return jax.jit(final, donate_argnums=(2, 3, 4, 5))

        def run(params, pool, bt, tok, pos, act, temp, topk, topp, keys,
                toks, slot, offset, p_len, last_idx, row_bt,
                r_temp, r_topk, r_topp, r_key, moe=None):
            return final(params, None, pool, None, bt, None, tok, pos,
                         act, temp, topk, topp, keys, toks, slot, offset,
                         p_len, last_idx, row_bt, None, r_temp, r_topk,
                         r_topp, r_key, moe)

        return jax.jit(run, donate_argnums=(1, 2))

    def _build_paged_ring_final_fn(self, width: int):
        """Paged ROLLING final chunk: the dense staging cache (rolling
        chunks still stage — a ring commit needs the whole prompt tail at
        once) ring-collapses through ``ring_from_prefill`` and scatters
        into the slot's blocks via its block table; the draft twin (full
        view, non-rolling) commits its staged positions below ``p_len``
        and routes the rest into the null block."""
        model, draft = self.model, self._draft_model
        page, t_view, d_view = self.block_size, self._t_view, self.max_len

        def final(params, dparams, pool, dpool, bt, dbt, tok, pos, act,
                  temp, topk, topp, keys, staging, d_staging, toks, slot,
                  offset, last_idx, p_len, row_bt, row_dbt, r_temp,
                  r_topk, r_topp, r_key):
            logits, staging = _dec._forward(model, params, staging, toks,
                                            offset)
            first = _dec.sample_logits_batched(
                logits[0, last_idx][None], jnp.asarray(p_len - 1)[None],
                r_temp, r_key, r_topk, r_topp)
            p_row = jnp.asarray(p_len)[None]
            j = jnp.arange(t_view)
            blk = jnp.minimum(j // page, row_bt.shape[1] - 1)
            phys = (jnp.take(row_bt, blk, axis=1) * page
                    + (j % page)[None, :])
            new_pool = []
            for big, row in zip(pool, staging):
                if big is None:
                    new_pool.append(None)
                    continue
                rk = _dec.ring_from_prefill(row["k"], p_row, t_view)
                rv = _dec.ring_from_prefill(row["v"], p_row, t_view)
                new_pool.append(_dec._kv_write(big, (phys,), rk, rv))
            out = [first, new_pool]
            if draft is not None:
                _, d_staging = _dec._forward(draft, dparams, d_staging,
                                             toks, offset)
                null_phys = dpool[[i for i, c in enumerate(dpool)
                                   if c is not None][0]]["k"].shape[0] - 1
                jd = jnp.arange(d_view)
                blkd = jnp.minimum(jd // page, row_dbt.shape[1] - 1)
                physd = (jnp.take(row_dbt, blkd, axis=1) * page
                         + (jd % page)[None, :])
                physd = jnp.where(jd[None, :] < p_row[:, None], physd,
                                  null_phys)
                new_dpool = []
                for big, row in zip(dpool, d_staging):
                    if big is None:
                        new_dpool.append(None)
                        continue
                    new_dpool.append(_dec._kv_write(big, (physd,),
                                                    row["k"], row["v"]))
                out.append(new_dpool)
            out.append(bt.at[slot].set(row_bt[0], mode="drop"))
            if draft is not None:
                out.append(dbt.at[slot].set(row_dbt[0], mode="drop"))
            out += [tok.at[slot].set(first[0], mode="drop"),
                    pos.at[slot].set(p_len, mode="drop"),
                    act.at[slot].set(True, mode="drop"),
                    temp.at[slot].set(r_temp[0], mode="drop"),
                    topk.at[slot].set(r_topk[0], mode="drop"),
                    topp.at[slot].set(r_topp[0], mode="drop"),
                    keys.at[slot].set(r_key[0], mode="drop")]
            return tuple(out)

        if draft is not None:
            return jax.jit(final, donate_argnums=(2, 3, 4, 5))

        def run(params, pool, bt, tok, pos, act, temp, topk, topp, keys,
                staging, toks, slot, offset, last_idx, p_len, row_bt,
                r_temp, r_topk, r_topp, r_key):
            return final(params, None, pool, None, bt, None, tok, pos,
                         act, temp, topk, topp, keys, staging, None, toks,
                         slot, offset, last_idx, p_len, row_bt, None,
                         r_temp, r_topk, r_topp, r_key)

        return jax.jit(run, donate_argnums=(1, 2))

    def _build_final_fn(self, width: int):
        if self.paged and not self.rolling:
            return self._build_paged_final_fn(width)
        if self.paged:
            return self._build_paged_ring_final_fn(width)
        model, rolling = self.model, self.rolling
        draft = self._draft_model

        def final(params, dparams, pool, dpool, tok, pos, act, temp, topk,
                  topp, keys, staging, d_staging, toks, slot, offset,
                  last_idx, p_len, r_temp, r_topk, r_topp, r_key):
            logits, staging = _dec._forward(model, params, staging, toks,
                                            offset)
            first = _dec.sample_logits_batched(
                logits[0, last_idx][None], jnp.asarray(p_len - 1)[None],
                r_temp, r_key, r_topk, r_topp)
            p_row = jnp.asarray(p_len)[None]
            # full-row commit: atomically replaces whatever junk the free
            # slot's decode passes wrote while chunks staged
            out = [first,
                   [_commit_full_row(big, row, slot, rolling, p_row)
                    for big, row in zip(pool, staging)]]
            if draft is not None:
                _, d_staging = _dec._forward(draft, dparams, d_staging,
                                             toks, offset)
                out.append([_commit_full_row(big, row, slot, False, p_row)
                            for big, row in zip(dpool, d_staging)])
            out += [tok.at[slot].set(first[0], mode="drop"),
                    pos.at[slot].set(p_len, mode="drop"),
                    act.at[slot].set(True, mode="drop"),
                    temp.at[slot].set(r_temp[0], mode="drop"),
                    topk.at[slot].set(r_topk[0], mode="drop"),
                    topp.at[slot].set(r_topp[0], mode="drop"),
                    keys.at[slot].set(r_key[0], mode="drop")]
            return tuple(out)

        # staging is NOT donated: the ring relayout is a gather whose
        # output shape differs from the staging buffer, so XLA could not
        # reuse it anyway (it dies with the program instead)
        if draft is not None:
            return jax.jit(final, donate_argnums=(2, 3))

        def run(params, pool, tok, pos, act, temp, topk, topp, keys,
                staging, toks, slot, offset, last_idx, p_len,
                r_temp, r_topk, r_topp, r_key):
            return final(params, None, pool, None, tok, pos, act, temp,
                         topk, topp, keys, staging, None, toks, slot,
                         offset, last_idx, p_len, r_temp, r_topk, r_topp,
                         r_key)

        return jax.jit(run, donate_argnums=(1,))

    # ----------------------------------------------------- device traffic
    def _put(self, x):
        """Host→device upload (admission inputs only).  Counted so the
        transfer discipline is assertable: a decode-only iteration
        performs ZERO uploads."""
        self.stats["h2d_transfers"] += 1
        return jnp.asarray(x)

    def _fetch(self, arr) -> np.ndarray:
        """Device→host readback — the ONE transfer per drained step (the
        sampled token row, or a prefill batch's first tokens)."""
        self.stats["d2h_transfers"] += 1
        return np.asarray(arr)

    def _state_args(self):
        if self.paged:
            if self._draft_model is None:
                return (self.caches, self._dev_bt, self._dev_tok,
                        self._dev_pos, self._dev_act, self._dev_temp,
                        self._dev_topk, self._dev_topp, self._dev_keys)
            return (self.caches, self.d_caches, self._dev_bt,
                    self._dev_dbt, self._dev_tok, self._dev_pos,
                    self._dev_act, self._dev_temp, self._dev_topk,
                    self._dev_topp, self._dev_keys)
        if self._draft_model is None:
            return (self.caches, self._dev_tok, self._dev_pos,
                    self._dev_act, self._dev_temp, self._dev_topk,
                    self._dev_topp, self._dev_keys)
        return (self.caches, self.d_caches, self._dev_tok, self._dev_pos,
                self._dev_act, self._dev_temp, self._dev_topk,
                self._dev_topp, self._dev_keys)

    def _prog_args(self):
        """Leading arguments of every prefill program: params (+ draft
        params under speculation) then the device-resident state."""
        if self._draft_model is None:
            return (self.params,) + self._state_args()
        return (self.params, self._draft_params) + self._state_args()

    def _apply_state(self, res):
        """Unpack a prefill program's ``(first, pool[, draft pool],
        [block tables,] *state)`` result, installing the new device
        arrays; returns ``first``."""
        if self.paged:
            if self._draft_model is None:
                (first, self.caches, self._dev_bt, self._dev_tok,
                 self._dev_pos, self._dev_act, self._dev_temp,
                 self._dev_topk, self._dev_topp, self._dev_keys) = res
            else:
                (first, self.caches, self.d_caches, self._dev_bt,
                 self._dev_dbt, self._dev_tok, self._dev_pos,
                 self._dev_act, self._dev_temp, self._dev_topk,
                 self._dev_topp, self._dev_keys) = res
            return first
        if self._draft_model is None:
            (first, self.caches, self._dev_tok, self._dev_pos,
             self._dev_act, self._dev_temp, self._dev_topk,
             self._dev_topp, self._dev_keys) = res
        else:
            (first, self.caches, self.d_caches, self._dev_tok,
             self._dev_pos, self._dev_act, self._dev_temp, self._dev_topk,
             self._dev_topp, self._dev_keys) = res
        return first

    def _sampling_row(self, h: RequestHandle):
        """One request's sampling params as (1,)-shaped device rows for
        the chunk/final programs."""
        return (self._put(np.asarray([h.temperature], np.float32)),
                self._put(np.asarray(
                    [0 if h.top_k is None else int(h.top_k)], np.int32)),
                self._put(np.asarray(
                    [0.0 if h.top_p is None else float(h.top_p)],
                    np.float32)),
                self._put(np.asarray(h.key, np.uint32)[None]))

    # ------------------------------------------------- tenant QoS plumbing
    def register_tenant(self, policy: TenantPolicy) -> None:
        """Install (or replace) one tenant's :class:`TenantPolicy`.
        Thread-safe; takes effect for the next admission.  Requests naming
        no tenant (or an unregistered one) get batch-tier, weight-1,
        unlimited-quota treatment."""
        if not isinstance(policy, TenantPolicy):
            raise ValueError(f"expected a TenantPolicy, got "
                             f"{type(policy).__name__}")
        with self._qlock:
            self._tenants[policy.name] = policy

    def _tenant_stats(self, tenant: str) -> Dict[str, int]:
        """The per-tenant counter dict, created lazily.  Caller holds
        ``_qlock`` (the counters are summed cross-thread by drain/stats
        consumers under the same lock discipline as the globals)."""
        ts = self.stats["tenants"].get(tenant)
        if ts is None:
            ts = {"submitted": 0, "completed": 0, "shed": 0,
                  "quota_refused": 0, "preemptions": 0, "resumes": 0}
            self.stats["tenants"][tenant] = ts
        return ts

    def _tier_of(self, tenant: str) -> str:  # dklint: holds _qlock
        pol = self._tenants.get(tenant)
        return "batch" if pol is None else pol.tier

    def _q_push(self, h: RequestHandle, front: bool = False) -> None:  # dklint: holds _qlock
        """Enqueue under ``_qlock``.  A tenant's first-ever push seeds its
        stride pass at the current minimum among backlogged tenants, so a
        newcomer (or a long-idle returner) can't bank idle time and then
        monopolize admissions."""
        q = self._queues.get(h.tenant)
        if q is None:
            q = self._queues[h.tenant] = []
        if not q:  # (re)joining the backlog: no banked credit
            floor = min((self._wf_pass.get(n, 0.0)
                         for n, qq in self._queues.items() if qq),
                        default=0.0)
            self._wf_pass[h.tenant] = max(
                self._wf_pass.get(h.tenant, 0.0), floor)
        if front:
            q.insert(0, h)
        else:
            q.append(h)
        self._qdepth += 1
        if self._tier_of(h.tenant) == "interactive":
            self._q_int += 1

    def _q_pop_locked(self) -> Optional[RequestHandle]:  # dklint: holds _qlock
        """Weighted-fair pick under ``_qlock``: interactive-tier tenants
        strictly before batch-tier; within a tier, the backlogged tenant
        with the smallest stride pass (pass += 1/weight per pick); within
        a tenant, highest ``priority`` first, FIFO among equals.  With a
        single tenant of uniform priority this degenerates to the plain
        FIFO the pre-QoS engine ran."""
        best_name, best_key = None, None
        for name, q in self._queues.items():
            if not q:
                continue
            lvl = 0 if self._tier_of(name) == "interactive" else 1
            key = (lvl, self._wf_pass.get(name, 0.0), name)
            if best_key is None or key < best_key:
                best_name, best_key = name, key
        if best_name is None:
            return None
        q = self._queues[best_name]
        idx = max(range(len(q)), key=lambda i: (q[i].priority, -i))
        h = q.pop(idx)
        self._qdepth -= 1
        if self._tier_of(best_name) == "interactive":
            self._q_int -= 1
        pol = self._tenants.get(best_name)
        weight = 1.0 if pol is None else pol.weight
        self._wf_pass[best_name] = (self._wf_pass.get(best_name, 0.0)
                                    + 1.0 / weight)
        return h

    def _q_snapshot_locked(self) -> List[RequestHandle]:  # dklint: holds _qlock
        """Every queued handle (all tenants, queue order) under
        ``_qlock``."""
        return [h for q in self._queues.values() for h in q]

    def _q_clear_locked(self) -> List[RequestHandle]:  # dklint: holds _qlock
        out = self._q_snapshot_locked()
        self._queues.clear()
        self._qdepth = 0
        self._q_int = 0
        return out

    # ------------------------------------------------------------ admission
    def submit(self, prompt, num_steps: int, temperature: float = 0.0,
               top_k: Optional[int] = None, top_p: Optional[float] = None,
               eos_id: Optional[int] = None, pad_id: Optional[int] = None,
               seed: int = 0, rng: Optional[jax.Array] = None,
               block: bool = True, timeout: Optional[float] = None,
               deadline_s: Optional[float] = None,
               tenant: Optional[str] = None,
               priority: int = 0) -> RequestHandle:
        """Enqueue one request; returns its :class:`RequestHandle`.

        ``prompt``: (P,) int tokens.  Sampling/stopping knobs mirror
        ``generate`` exactly (that is the bit-identity contract); the
        request's rng is ``rng`` if given, else ``PRNGKey(seed)``.
        Backpressure: with the queue at ``queue_capacity``, ``block=True``
        waits (up to ``timeout``), ``block=False`` raises :class:`QueueFull`
        immediately.  ``deadline_s`` (default: the submitting tenant's
        ``TenantPolicy.deadline_s``, else the engine's
        ``default_deadline_s``) bounds the request's whole lifetime,
        queueing included: an expired request is retired with reason
        ``"deadline"`` — shed before prefill if still queued, mid-run with
        its slot freed immediately if decoding.  Raises :class:`Draining`
        while ``drain`` is in progress and :class:`EngineDead` on a dead
        engine.

        QoS: ``tenant`` names the submitting tenant (default
        ``"default"``) — admission is weighted-fair across backlogged
        tenants per their registered :class:`TenantPolicy`; a tenant over
        its token-bucket quota raises :class:`QuotaExceeded` immediately
        (even with ``block=True`` — quota is policy, not backpressure).
        ``priority`` orders requests WITHIN a tenant's queue (higher
        first); batch-tier running requests may additionally be preempted
        (swapped out, later resumed bit-identically) when the interactive
        tier is starved.
        """
        if self.role == "decode":
            raise ValueError(
                "role='decode' engines admit only shipped block sets "
                "(submit_prefilled) — route plain submissions to the "
                "prefill engine or a DisaggPair")
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1:
            raise ValueError(f"prompt must be 1-D tokens, got shape "
                             f"{prompt.shape} — submit one request per row")
        if num_steps < 0:
            raise ValueError(f"num_steps must be >= 0, got {num_steps}")
        tenant = "default" if tenant is None else str(tenant)
        priority = int(priority)
        if deadline_s is None:
            with self._qlock:  # register_tenant may race admission
                pol = self._tenants.get(tenant)
            if pol is not None and pol.deadline_s is not None:
                deadline_s = pol.deadline_s
            else:
                deadline_s = self.default_deadline_s
        elif deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        # the id is taken before the request is built, so that the span
        # carries it from its first instant; a request refused below
        # (validation, quota, a full queue) leaves a gap in the sequence
        rid = next(self._ids)
        with span("serve.submit", rid=rid, prompt=len(prompt),
                  steps=int(num_steps)):
            key = rng if rng is not None else jax.random.PRNGKey(int(seed))
            _validate_sampling(temperature, key, top_k, top_p)
            _validate_stopping(eos_id, pad_id, self._vocab)
            total = len(prompt) + int(num_steps)
            if len(prompt) < 1:
                raise ValueError("prompt must hold at least one token")
            if total > self.max_len:
                raise ValueError(
                    f"prompt ({len(prompt)}) + num_steps ({num_steps}) = "
                    f"{total} exceeds the engine's max_len {self.max_len}")
            with self._qlock:
                if self._dead is not None:
                    raise EngineDead(str(self._dead)) from self._dead
                if self._draining:
                    raise Draining("serving engine is draining; admission "
                                   "stopped")
                pol = self._tenants.get(tenant)
                if pol is not None and not pol._take(time.monotonic()):
                    # policy refusal BEFORE requests_submitted so drain()'s
                    # terminal accounting never waits on a refused request;
                    # per-tenant so one tenant's refusals don't dilute the
                    # global shed_rate (requests_rejected untouched)
                    self._tenant_stats(tenant)["quota_refused"] += 1
                    self.stats["quota_refused"] += 1
                    raise QuotaExceeded(
                        f"tenant {tenant!r} over its token-bucket quota "
                        f"({pol.rate}/s, burst {pol.burst})")
                handle = RequestHandle(rid, prompt, num_steps,
                                       temperature, top_k, top_p, eos_id,
                                       pad_id, key, deadline_s=deadline_s,
                                       tenant=tenant, priority=priority)
                self.stats["requests_submitted"] += 1
                tstats = self._tenant_stats(tenant)
                tstats["submitted"] += 1
                if num_steps == 0:  # nothing to generate: complete in place
                    handle._finish("empty")
                    self.stats["requests_completed"] += 1
                    tstats["completed"] += 1
                    return handle
                while self._qdepth >= self.queue_capacity:
                    if not block or not self._not_full.wait(timeout=timeout):
                        self.stats["requests_rejected"] += 1
                        tstats["shed"] += 1
                        raise QueueFull(
                            f"admission queue at capacity "
                            f"({self.queue_capacity}); request {handle.id} "
                            f"shed")
                    # _declare_dead / drain notify _not_full while we wait —
                    # re-check on every wake or the request lands in a queue no
                    # scheduler will ever pop (result() would hang forever).
                    # Both raises count as admission sheds (requests_rejected)
                    # so the terminal accounting drain() sums stays balanced.
                    if self._dead is not None:
                        self.stats["requests_rejected"] += 1
                        raise EngineDead(str(self._dead)) from self._dead
                    if self._draining:
                        self.stats["requests_rejected"] += 1
                        raise Draining("serving engine is draining; admission "
                                       "stopped")
                self._q_push(handle)
                self.stats["queue_peak"] = max(self.stats["queue_peak"],
                                               self._qdepth)
                self._have_work.notify()
                qd = self._qdepth
            self._publish_load(qd=qd)
            return handle

    def submit_prefilled(self, blocks, prompt, first_token: int,
                         num_steps: int, temperature: float = 0.0,
                         top_k: Optional[int] = None,
                         top_p: Optional[float] = None,
                         eos_id: Optional[int] = None,
                         pad_id: Optional[int] = None,
                         block: bool = True, timeout: Optional[float] = None,
                         deadline_s: Optional[float] = None,
                         tenant: Optional[str] = None,
                         priority: int = 0) -> RequestHandle:
        """Decode-role admission: enqueue a request whose prefill already
        ran elsewhere.  ``blocks`` is the shipped
        :class:`networking.KVBlocks` (prompt KV in logical block order +
        the request's RNG key), ``first_token`` the token the prefill
        engine sampled at the prompt boundary — pushed into the handle
        immediately, so the client-visible stream is unchanged.
        ``num_steps`` counts TOTAL generated tokens, the shipped first one
        included (the unified-engine contract).  The scheduler scatters
        the payload into this engine's OWN arena blocks
        (``_PagedKVPool.admit`` plain allocation — physical ids never
        cross engines) and the slot enters the token loop at the shipped
        position.  Geometry lies (wrong arena shape/dtype for this model)
        raise ``ValueError``; torn/hostile payloads should be rejected by
        ``blocks.validate()`` at the transport boundary BEFORE this call.
        Backpressure/death semantics mirror :meth:`submit` exactly."""
        if self.role != "decode":
            raise ValueError("submit_prefilled needs role='decode' — "
                             f"this engine is role={self.role!r}")
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or len(prompt) < 1:
            raise ValueError(f"prompt must be 1-D tokens (>= 1), got "
                             f"shape {prompt.shape}")
        if num_steps < 1:
            raise ValueError(f"num_steps must be >= 1 (it counts the "
                             f"shipped first token), got {num_steps}")
        tenant = "default" if tenant is None else str(tenant)
        priority = int(priority)
        if deadline_s is None:
            with self._qlock:  # register_tenant may race admission
                pol = self._tenants.get(tenant)
            if pol is not None and pol.deadline_s is not None:
                deadline_s = pol.deadline_s
            else:
                deadline_s = self.default_deadline_s
        elif deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        kvb = blocks
        if kvb.block_size != self.block_size:
            raise ValueError(
                f"shipped blocks are {kvb.block_size}-token, this arena "
                f"pages {self.block_size}-token blocks")
        if kvb.positions != len(prompt):
            raise ValueError(
                f"shipped positions ({kvb.positions}) disagree with the "
                f"prompt length ({len(prompt)})")
        total = len(prompt) + int(num_steps)
        if total > self.max_len:
            raise ValueError(f"prompt ({len(prompt)}) + num_steps "
                             f"({num_steps}) = {total} exceeds the engine's "
                             f"max_len {self.max_len}")
        if len(kvb.layers) != len(self.caches):
            raise ValueError(
                f"shipped payload spans {len(kvb.layers)} layers, this "
                f"model has {len(self.caches)}")
        for i, (c, mine) in enumerate(zip(kvb.layers, self.caches)):
            if (c is None) != (mine is None):
                raise ValueError(f"layer {i} cache presence disagrees "
                                 "with this model")
            if c is None:
                continue
            if ("ks" in c) != ("ks" in mine):
                raise ValueError(
                    f"layer {i} quantization disagrees: shipped "
                    f"{'int8' if 'ks' in c else 'dense'} KV, this arena is "
                    f"{'int8' if 'ks' in mine else 'dense'}")
            for name in c:  # rows of Hkv * Dh features, scales of Hkv
                if c[name].shape[1:] != mine[name].shape[1:] \
                        or c[name].dtype != mine[name].dtype:
                    raise ValueError(
                        f"layer {i} shipped {name!r} rows are "
                        f"{c[name].shape[1:]} {c[name].dtype}, this arena "
                        f"holds {mine[name].shape[1:]} {mine[name].dtype}")
        _validate_stopping(eos_id, pad_id, self._vocab)
        rid = next(self._ids)
        with span("serve.submit", rid=rid, prompt=len(prompt),
                  steps=int(num_steps)):
            key = np.asarray(kvb.key, np.uint32)
            with self._qlock:
                if self._dead is not None:
                    raise EngineDead(str(self._dead)) from self._dead
                if self._draining:
                    raise Draining("serving engine is draining; admission "
                                   "stopped")
                pol = self._tenants.get(tenant)
                if pol is not None and not pol._take(time.monotonic()):
                    self._tenant_stats(tenant)["quota_refused"] += 1
                    self.stats["quota_refused"] += 1
                    raise QuotaExceeded(
                        f"tenant {tenant!r} over its token-bucket quota "
                        f"({pol.rate}/s, burst {pol.burst})")
                handle = RequestHandle(rid, prompt, num_steps,
                                       temperature, top_k, top_p, eos_id,
                                       pad_id, key, deadline_s=deadline_s,
                                       tenant=tenant, priority=priority)
                handle.kvblocks = kvb
                self.stats["requests_submitted"] += 1
                tstats = self._tenant_stats(tenant)
                tstats["submitted"] += 1
                # the shipped first token IS this request's first generated
                # token: push it now (TTFT on this engine is the hand-off
                # instant) and complete in place when it already terminates
                handle._push(int(first_token))
                if (eos_id is not None and int(first_token) == int(eos_id)) \
                        or num_steps == 1:
                    reason = ("eos" if eos_id is not None
                              and int(first_token) == int(eos_id)
                              else "length")
                    handle._finish(reason)
                    self.stats["requests_completed"] += 1
                    tstats["completed"] += 1
                    self.stats["tokens_generated"] += 1
                    return handle
                while self._qdepth >= self.queue_capacity:
                    if not block or not self._not_full.wait(timeout=timeout):
                        self.stats["requests_rejected"] += 1
                        tstats["shed"] += 1
                        raise QueueFull(
                            f"admission queue at capacity "
                            f"({self.queue_capacity}); request {handle.id} "
                            f"shed")
                    if self._dead is not None:
                        self.stats["requests_rejected"] += 1
                        raise EngineDead(str(self._dead)) from self._dead
                    if self._draining:
                        self.stats["requests_rejected"] += 1
                        raise Draining("serving engine is draining; admission "
                                       "stopped")
                self.stats["tokens_generated"] += 1
                self._q_push(handle)
                self.stats["queue_peak"] = max(self.stats["queue_peak"],
                                               self._qdepth)
                self._have_work.notify()
                qd = self._qdepth
            self._publish_load(qd=qd)
            return handle

    @property
    def queue_depth(self) -> int:
        with self._qlock:
            return self._qdepth

    @property
    def active_requests(self) -> int:
        return int(self._active.sum())

    # --------------------------------------------------- load snapshot
    def _publish_load(self, qd: Optional[int] = None,
                      draining: Optional[bool] = None,
                      dead: Optional[bool] = None) -> None:
        """Republish the lock-free load snapshot (see ``__init__``).

        Must be called OUTSIDE any ``_qlock`` block: callers that need an
        exact queue depth capture it under the lock and pass it in; a
        ``None`` field carries the previous snapshot's value forward.
        Everything else read here is scheduler-confined (``_free``,
        ``_active``, the trie counter) or an already-synchronised stats
        counter — stale-by-one is fine for routing."""
        with span("serve.publish"):
            self._set_load(qd, draining, dead)

    def _set_load(self, qd: Optional[int] = None,
                  draining: Optional[bool] = None,
                  dead: Optional[bool] = None) -> None:
        """``_publish_load`` without its span: ``step()``'s closing publish
        is a phase of the loop and opens ``serve.publish`` through the
        account."""
        prev = self._load_snapshot
        stats = self.stats
        with self._qlock:
            qi = self._q_int
        self._load_snapshot = {
            "queue_depth": (prev["queue_depth"] if qd is None
                            else int(qd)),
            "slots_free": len(self._free),
            "slots_total": self.num_slots,
            "active": int(self._active.sum()),
            "trie_blocks": (self._pool.trie_nodes if self.paged else 0),
            "queue_capacity": self.queue_capacity,
            "max_len": self.max_len,
            "draining": (prev["draining"] if draining is None
                         else bool(draining)),
            "dead": prev["dead"] if dead is None else bool(dead),
            "prefix_hit_tokens": stats["prefix_hit_tokens"],
            "prefill_tokens": stats["prefill_tokens"],
            "tokens_generated": stats["tokens_generated"],
            "requests_completed": stats["requests_completed"],
            "requests_failed": stats["requests_failed"],
            "queued_interactive": qi,
        }

    def load(self) -> Dict[str, Any]:
        """Cheap read-only load snapshot for routing decisions: queue
        depth, free/total slots, active requests, prefix-trie cached block
        count, draining/dead flags, and a few throughput counters.  Takes
        NO locks (the snapshot dict is republished by reference from the
        scheduler/submit paths), so a router may poll it at any rate
        without perturbing the hot path.  Values may trail the engine by
        one scheduler iteration."""
        return dict(self._load_snapshot)

    def _pop_queued(self) -> Optional[RequestHandle]:
        with self._qlock:
            h = self._q_pop_locked()
            if h is None:
                return None
            self._not_full.notify()
            qd = self._qdepth
        self._publish_load(qd=qd)
        return h

    # ------------------------------------------------- cancel + deadlines
    def cancel(self, handle: RequestHandle) -> bool:
        """Request cancellation (thread-safe, any thread): the scheduler
        retires the request with reason ``"cancel"`` within ONE iteration —
        a queued request is shed before prefill, a running one frees its KV
        slot immediately (the disconnect-reclamation path the wire server
        drives).  Returns False if the request already finished."""
        with handle._cond:
            if handle.finish is not None:
                return False
            if handle.cancelled_at is None:
                handle.cancelled_at = time.perf_counter()
        with self._qlock:
            self._have_work.notify_all()  # prompt reclamation on idle loops
        return True

    def _reap(self) -> bool:
        """Retire cancelled and deadline-expired requests: queued ones are
        shed before ever taking a slot; running ones mid-run, freeing the
        slot for the next queued request.  Runs at the top of every
        scheduler iteration."""
        now = time.perf_counter()
        shed: List[RequestHandle] = []
        with self._qlock:
            for name, q in self._queues.items():
                if not any(h.cancelled_at is not None or h._expired(now)
                           for h in q):
                    continue
                keep: List[RequestHandle] = []
                for h in q:
                    if h.cancelled_at is not None or h._expired(now):
                        shed.append(h)
                        self._qdepth -= 1
                        if self._tier_of(name) == "interactive":
                            self._q_int -= 1
                    else:
                        keep.append(h)
                self._queues[name] = keep
            if shed:
                self._not_full.notify_all()
        for h in shed:
            reason = "cancel" if h.cancelled_at is not None else "deadline"
            if h._finish(reason):
                # held_slot=False: a queued shed never occupied a KV slot,
                # so it must not contribute a (near-zero) sample to the
                # slot_reclaim_ms reclamation-latency metric
                self._account_terminal(h, reason, now, held_slot=False)
                with self._qlock:  # drain()'s busy() sums this cross-thread
                    self.stats["requests_completed"] += 1
                    self._tenant_stats(h.tenant)["completed"] += 1
        did = bool(shed)
        for slot in np.flatnonzero(self._active):
            h = self._handles[slot]
            if h.cancelled_at is not None:
                self._retire(int(slot), "cancel")
                did = True
            elif h._expired(now):
                self._retire(int(slot), "deadline")
                did = True
        for slot in list(self._prefilling):
            h = self._prefilling[slot].handle
            if h.cancelled_at is not None:
                self._abort_prefill(slot, "cancel")
                did = True
            elif h._expired(now):
                self._abort_prefill(slot, "deadline")
                did = True
        # suspended (swapped-out) requests hold no slot or blocks — their
        # cancel/deadline path is pure bookkeeping: drop the host-side
        # swap record and retire the handle (held_slot=False: nothing to
        # reclaim, so no slot_reclaim_ms sample)
        with self._qlock:  # _declare_dead clears _suspended cross-thread
            susp = list(self._suspended.items())
        for rid, rec in susp:
            h = rec.handle
            if h.cancelled_at is not None:
                reason = "cancel"
            elif h._expired(now):
                reason = "deadline"
            else:
                continue
            with self._qlock:
                self._suspended.pop(rid, None)
            if h._finish(reason):
                self._account_terminal(h, reason, now, held_slot=False)
                with self._qlock:
                    self.stats["requests_completed"] += 1
                    self._tenant_stats(h.tenant)["completed"] += 1
            did = True
        return did

    def _abort_prefill(self, slot: int, reason: str) -> None:
        """Retire a request MID-chunked-prefill (cancel / deadline /
        client disconnect): the slot goes straight back to the pool — the
        chunks already written are junk the next occupant's prefill
        overwrites, exactly like a retired decode slot's cache row.
        Paged engines release the job's block plan — refcounts drop and
        its private blocks (mid-chunk contents included) go straight back
        to the allocator; the device table was never installed, so no
        junk write can reach them once reallocated."""
        h = self._prefilling.pop(slot).handle
        self._handles[slot] = None
        self._free.append(slot)
        self._release_blocks(slot)
        if h._finish(reason):
            with self._qlock:  # drain()'s busy() sums this cross-thread
                self.stats["requests_completed"] += 1
                self._tenant_stats(h.tenant)["completed"] += 1
            self._account_terminal(h, reason, time.perf_counter())

    def _release_blocks(self, slot: int) -> None:
        if self._pool is None:
            return
        plan = self._plans.pop(slot, None)
        if plan is not None:
            self._pool.release(plan)

    def _account_terminal(self, h: RequestHandle, reason: str,
                          now: float, held_slot: bool = True) -> None:
        """Reason counters, plus — for requests that actually held a KV
        slot (``held_slot``) — the slot-reclaim latency sample
        (cancel/expiry instant → slot free) for the
        ``serving_slot_reclaim_ms`` bench.  Queue sheds keep their
        cancelled/expired counters but contribute no reclaim sample."""
        if reason == "cancel":
            self.stats["requests_cancelled"] += 1
            if held_slot and h.cancelled_at is not None:
                self.stats["slot_reclaim_ms"].append(
                    round((now - h.cancelled_at) * 1e3, 3))
        elif reason == "deadline":
            self.stats["requests_expired"] += 1
            if held_slot and h.deadline is not None:
                self.stats["slot_reclaim_ms"].append(
                    round((now - h.deadline) * 1e3, 3))

    # ------------------------------------------------------------- prefill
    def _bucket_of(self, n: int) -> int:
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]

    def _schedule_prefills(self) -> bool:
        """Spend up to ``prefills_per_step`` prefill work units this
        iteration: first advance chunked prefills already holding slots
        (one chunk each — finishing started work bounds every occupant's
        TTFT), then admit queued requests: short prompts gather into
        per-bucket batches (one jitted forward each), prompts longer
        than ``prefill_chunk`` go to the chunked path.

        Paged engines additionally walk the radix index per admission:
        matched prefix blocks are shared (COW at a partial boundary), the
        block chain is reserved from the allocator, and — when blocks
        are exhausted even after evicting cached chains — the head
        request stays queued until retirements free blocks (FIFO
        head-of-line, deliberately: admission order is the fairness
        contract).  Chunked routing keys on the UNMATCHED suffix length,
        so a long shared prompt with a hot prefix admits in one bucket
        program."""
        did = False
        budget = self.prefills_per_step
        if self.paged:
            self._pool.next_epoch()
        if self.role == "decode":
            # disaggregated ingest replaces prefill entirely: each queued
            # handle carries a shipped block set; admission is one plain
            # block allocation + one jitted scatter/install dispatch.
            # Block exhaustion requeues at the FRONT and stops, exactly
            # like the paged prefill path (FIFO fairness contract).
            while budget > 0 and self._free:
                h = self._pop_queued()
                if h is None:
                    break
                with span("serve.admit", rid=h.id):
                    admitted = self._ingest(h)
                if not admitted:
                    with self._qlock:
                        self._q_push(h, front=True)
                    self._hold("no_blocks")
                    break
                budget -= 1
                did = True
            else:
                self._hold("budget" if self._free else "no_slot")
            return did
        for slot in list(self._prefilling):
            if budget <= 0:
                break
            self._advance_chunk(slot)
            budget -= 1
            did = True
        batch: List[RequestHandle] = []
        plans: Dict[int, _BlockPlan] = {}
        while budget > 0 and len(self._free) > len(batch):
            h = self._pop_queued()
            if h is None:
                break
            plan = None
            if self.paged:
                with span("serve.admit", rid=h.id):
                    plan = self._admit_blocks(h)
                if plan is None:
                    # no blocks even after eviction: requeue at the FRONT
                    # and stop admitting — retirements will free blocks.
                    # An interactive-tier request starving on BLOCKS (not
                    # slots) flags the preemption pass: next iteration a
                    # batch-tier victim is swapped out to free its chain
                    with self._qlock:
                        interactive = (self._tier_of(h.tenant)
                                       == "interactive")
                        self._q_push(h, front=True)
                    if interactive:
                        self._int_blocked = True
                    self._hold("no_blocks")
                    break
            budget -= 1
            did = True
            if (len(h.prompt) - (plan.matched if plan else 0)
                    > self.prefill_chunk):
                self._start_chunked(self._free.pop(), h, plan)
            else:
                if plan is not None:
                    plans[h.id] = plan
                    self._pool.publish(plan, h.prompt)
                batch.append(h)
        else:
            self._hold("budget" if len(self._free) > len(batch)
                       else "no_slot")
        if batch:
            self._batch_prefill(batch, plans)
        return did

    def _hold(self, reason: str) -> None:
        """``_schedule_prefills`` stopped admitting: if a request is still
        queued, say why its head waits (the span ``serve.hold`` and the
        account's ``held``).  The depth is read without the lock: a submit
        that lands in this instant is counted an iteration late."""
        queued = self._qdepth
        if queued:
            self.account.hold(reason, queued)

    # ------------------------------------------------- paged admission
    def _admit_blocks(self, h: RequestHandle) -> Optional[_BlockPlan]:
        """Reserve a request's block chain (trie walk + allocation) and
        dispatch its copy-on-write block copy, if any."""
        # a prefill-role engine writes ONLY the prompt's KV (the first
        # sampled token's write happens on the decode engine at position
        # p_len), so its chain stops at ceil(p_len / bs)
        total = len(h.prompt) + (0 if self.role == "prefill"
                                 else h.num_steps)
        if self.rolling:
            plan = self._pool.admit(None, self._blocks_per_slot)
        else:
            # target and draft pools page the same chain, and both write
            # at most up to the verify frontier — positions past `total`
            # drop into the null block via the table, so ceil(total/bs)
            # blocks cover every entry a live query can ever attend
            plan = self._pool.admit(h.prompt,
                                    -(-total // self.block_size))
        if plan is not None and plan.cow is not None:
            src, dst = plan.cow
            if self._draft_model is None:
                self.caches = self._copy_fn(self.caches, src, dst)
            else:
                self.caches, self.d_caches = self._copy_fn(
                    self.caches, self.d_caches, src, dst)
        return plan

    def _row_tables(self, plan: _BlockPlan):
        """A plan's chain as null-padded numpy block-table rows (target
        [+ draft])."""
        bt = np.full((self._t_tbl,), self.kv_blocks, np.int32)
        n = min(len(plan.blocks), self._t_tbl - 1)
        bt[:n] = plan.blocks[:n]
        if self._draft_model is None:
            return bt, None
        dbt = np.full((self._d_tbl,), self.kv_blocks, np.int32)
        n = min(len(plan.blocks), self._d_tbl - 1)
        dbt[:n] = plan.blocks[:n]
        return bt, dbt

    def _ingest(self, h: RequestHandle) -> bool:
        """Admit ONE shipped block set (decode role): allocate this
        engine's own private chain (``admit(None, ...)`` — no trie, so
        release is a plain refund and the zero-leak contract is the
        standard retirement path), scatter the payload into those blocks,
        and install the slot's device row at the shipped position.
        Returns False when blocks are unavailable (the caller requeues at
        the front and waits for retirements)."""
        kvb = h.kvblocks
        bs = self.block_size
        total = len(h.prompt) + h.num_steps
        plan = self._pool.admit(None, -(-total // bs))
        if plan is None:
            return False
        t0 = time.perf_counter()
        slot = self._free.pop()
        h.slot = slot
        h.started_at = t0
        self._handles[slot] = h
        self._plans[slot] = plan
        self.stats["slot_requests"][slot] += 1
        n_src = kvb.num_blocks
        rows = np.full((self._blocks_per_slot,), self.kv_blocks, np.int32)
        rows[:n_src] = plan.blocks[:n_src]
        phys = (rows[:, None] * bs
                + np.arange(bs, dtype=np.int32)[None, :]).reshape(-1)
        pad = (self._blocks_per_slot - n_src) * bs
        payload = []
        for c in kvb.layers:
            if c is None:
                payload.append(None)
                continue
            payload.append({
                k: self._put(np.concatenate(
                    [np.asarray(v),
                     np.zeros((pad,) + v.shape[1:], v.dtype)])
                    if pad else np.ascontiguousarray(v))
                for k, v in c.items()})
        bt, _ = self._row_tables(plan)
        (self.caches, self._dev_bt, self._dev_tok, self._dev_pos,
         self._dev_act, self._dev_temp, self._dev_topk, self._dev_topp,
         self._dev_keys) = self._ingest_fn(
            self.caches, self._dev_bt, self._dev_tok, self._dev_pos,
            self._dev_act, self._dev_temp, self._dev_topk,
            self._dev_topp, self._dev_keys,
            self._put(phys), payload, self._put(np.int32(slot)),
            self._put(bt), self._put(np.int32(h.tokens[0])),
            self._put(np.int32(len(h.prompt))),
            self._put(np.float32(h.temperature)),
            self._put(np.int32(0 if h.top_k is None else h.top_k)),
            self._put(np.float32(0.0 if h.top_p is None else h.top_p)),
            self._put(np.asarray(h.key, np.uint32)))
        self._mirror_admit(slot, h)
        self.stats["kv_blocks_ingested"] += n_src
        self.stats["kv_block_bytes_ingested"] += kvb.nbytes
        self.stats["transfer_ms"].append(
            (time.perf_counter() - t0) * 1000.0)
        return True

    def _batch_prefill(self, batch: List[RequestHandle],
                       plans: Optional[Dict[int, _BlockPlan]] = None
                       ) -> None:
        """Admit up to ``prefills_per_step`` short prompts in ONE jitted
        batched forward per length bucket.  The program batch is always
        ``prefills_per_step`` rows (one compiled shape per bucket);
        unfilled rows target slot ``num_slots``, so every write they
        produce is dropped on device.  Paged engines bucket by UNMATCHED
        suffix length and pass each row's match frontier + block-table
        row; ``prefill_tokens`` counts only what is actually prefilled
        (the hit tokens live in ``prefix_hit_tokens``)."""
        def matched(h):
            return plans[h.id].matched if (plans and h.id in plans) else 0

        groups: Dict[int, List[RequestHandle]] = {}
        for h in batch:
            groups.setdefault(self._bucket_of(len(h.prompt) - matched(h)),
                              []).append(h)
        for width, group in groups.items():
            hit = sum(matched(h) for h in group)
            with self.account.unit(
                    rid=group[0].id,
                    tokens=sum(len(h.prompt) for h in group) - hit,
                    kind="bucket", width=width, hit=hit):
                nb = self.prefills_per_step
                prompts = np.zeros((nb, width), np.int32)
                match = np.zeros((nb,), np.int32)
                p_lens = np.ones((nb,), np.int32)
                slots = np.full((nb,), self.num_slots, np.int32)
                r_temp = np.zeros((nb,), np.float32)
                r_topk = np.zeros((nb,), np.int32)
                r_topp = np.zeros((nb,), np.float32)
                r_keys = np.zeros((nb, 2), np.uint32)
                if self.paged:
                    row_bt = np.full((nb, self._t_tbl), self.kv_blocks,
                                     np.int32)
                    row_dbt = (np.full((nb, self._d_tbl), self.kv_blocks,
                                       np.int32)
                               if self._draft_model is not None else None)
                entries: List[Tuple[int, RequestHandle]] = []
                for i, h in enumerate(group):
                    slot = self._free.pop()
                    p = len(h.prompt)
                    m = 0
                    if self.paged:
                        plan = plans[h.id]
                        m = plan.matched
                        self._plans[slot] = plan
                        rb, rd = self._row_tables(plan)
                        row_bt[i] = rb
                        if rd is not None:
                            row_dbt[i] = rd
                    prompts[i, :p - m] = h.prompt[m:]
                    match[i] = m
                    p_lens[i] = p
                    slots[i] = slot
                    r_temp[i] = h.temperature
                    r_topk[i] = 0 if h.top_k is None else int(h.top_k)
                    r_topp[i] = 0.0 if h.top_p is None else float(h.top_p)
                    r_keys[i] = np.asarray(h.key, np.uint32)
                    h.slot = slot
                    h.started_at = time.perf_counter()
                    self._handles[slot] = h
                    self._mirror_admit(slot, h)
                    self.stats["prefills"] += 1
                    self.stats["slot_requests"][slot] += 1
                    self.stats["recurrent_slots_cleared"] += self._recurrent
                    self.stats["prefill_tokens"] += p - m
                    entries.append((slot, h))
                if self.paged:
                    extra = [self._put(prompts), self._put(match),
                             self._put(p_lens), self._put(slots),
                             self._put(row_bt)]
                    if row_dbt is not None:
                        extra.append(self._put(row_dbt))
                    first = self._apply_state(self._bucket_fn(width)(
                        *self._prog_args(), *extra, self._put(r_temp),
                        self._put(r_topk), self._put(r_topp),
                        self._put(r_keys)))
                else:
                    first = self._apply_state(self._bucket_fn(width)(
                        *self._prog_args(), self._put(prompts),
                        self._put(p_lens), self._put(slots), self._put(r_temp),
                        self._put(r_topk), self._put(r_topp),
                        self._put(r_keys)))
                self.stats["prefill_batches"] += 1
                self.stats["prefill_batched_requests"] += len(group)
                self.stats["prefill_batch_size_mean"] = round(
                    self.stats["prefill_batched_requests"]
                    / self.stats["prefill_batches"], 3)
                self._pending.append(("prefill", first, entries,
                                      self.stats["decode_steps"]))

    def _start_chunked(self, slot: int, h: RequestHandle,
                       plan: Optional[_BlockPlan] = None) -> None:
        """Claim ``slot`` for a long prompt and run its first chunk; the
        scheduler advances one more chunk per iteration (``_reap`` can
        retire it mid-prefill).  Paged non-rolling jobs skip the staging
        cache entirely — chunks write into the request's own blocks
        (private until the final chunk installs the device table and
        publishes the prompt chain into the trie), starting at the
        matched frontier so a hot shared prefix skips its chunks."""
        h.slot = slot
        h.started_at = time.perf_counter()
        self._handles[slot] = h
        if self.paged:
            self._plans[slot] = plan
            bt, dbt = self._row_tables(plan)
            bt_d = self._put(bt[None])
            dbt_d = self._put(dbt[None]) if dbt is not None else None
            if self.rolling:
                staging = init_cache(self.model, 1, self.max_len)
                d_staging = (init_cache(self._draft_model, 1, self.max_len)
                             if self._draft_model is not None else None)
                job = _PrefillJob(h, staging, d_staging, bt_d, dbt_d)
            else:
                job = _PrefillJob(h, bt=bt_d, dbt=dbt_d)
                job.written = plan.matched
                job.moe = self._moe_zero
        else:
            staging = init_cache(self.model, 1, self.max_len)
            d_staging = (init_cache(self._draft_model, 1, self.max_len)
                         if self._draft_model is not None else None)
            job = _PrefillJob(h, staging, d_staging)
        self._prefilling[slot] = job
        self.stats["prefills"] += 1
        self.stats["slot_requests"][slot] += 1
        self.stats["recurrent_slots_cleared"] += self._recurrent
        job.hit = job.written
        self._advance_chunk(slot)

    def _advance_chunk(self, slot: int) -> None:
        """One chunk of one prefilling slot: write ``prefill_chunk`` more
        prompt tokens into the cache (the final chunk rounds up to a
        length bucket instead, samples the first token, and activates the
        slot for decode)."""
        job = self._prefilling[slot]
        h = job.handle
        p_len = len(h.prompt)
        remaining = p_len - job.written
        offset = job.written
        if remaining > self._chunk_width:
            width, real, final = self._chunk_width, self._chunk_width, False
        else:
            width, real, final = self._bucket_of(remaining), remaining, True
        hit, job.hit = job.hit, 0
        with self.account.unit(
                rid=h.id, tokens=real, kind="final" if final else "chunk",
                width=width, hit=hit):
            toks = np.zeros((1, width), np.int32)
            toks[0, :real] = h.prompt[offset:offset + real]
            toks_d = self._put(toks)
            self.stats["prefill_chunks"] += 1
            self.stats["prefill_tokens"] += real
            paged_direct = self.paged and not self.rolling
            if paged_direct:
                off_vec = self._put(np.asarray([offset], np.int32))
                plen_vec = self._put(np.asarray([p_len], np.int32))
            if not final:
                if paged_direct:
                    if self._draft_model is not None:
                        self.caches, self.d_caches = self._stage_fn(width)(
                            self.params, self._draft_params, self.caches,
                            self.d_caches, toks_d, off_vec, plen_vec,
                            job.bt, job.dbt)
                    else:
                        self.caches, job.moe = self._stage_fn(width)(
                            self.params, self.caches, toks_d, off_vec,
                            plen_vec, job.bt, slot, job.moe)
                elif self._draft_model is not None:
                    job.staging, job.d_staging = self._stage_fn(width)(
                        self.params, self._draft_params, job.staging,
                        job.d_staging, toks_d, offset)
                else:
                    job.staging = self._stage_fn(width)(
                        self.params, job.staging, toks_d, offset)
            else:
                if paged_direct:
                    if self._draft_model is not None:
                        first = self._apply_state(self._final_fn(width)(
                            *self._prog_args(), toks_d, slot, off_vec,
                            plen_vec, real - 1, job.bt, job.dbt,
                            *self._sampling_row(h)))
                    else:
                        first = self._apply_state(self._final_fn(width)(
                            *self._prog_args(), toks_d, slot, off_vec,
                            plen_vec, real - 1, job.bt,
                            *self._sampling_row(h), job.moe))
                elif self.paged:  # rolling: staged chunks, block-table commit
                    if self._draft_model is not None:
                        first = self._apply_state(self._final_fn(width)(
                            *self._prog_args(), job.staging, job.d_staging,
                            toks_d, slot, offset, real - 1, p_len,
                            job.bt, job.dbt, *self._sampling_row(h)))
                    else:
                        first = self._apply_state(self._final_fn(width)(
                            *self._prog_args(), job.staging, toks_d, slot,
                            offset, real - 1, p_len, job.bt,
                            *self._sampling_row(h)))
                elif self._draft_model is not None:
                    first = self._apply_state(self._final_fn(width)(
                        *self._prog_args(), job.staging, job.d_staging,
                        toks_d, slot, offset, real - 1, p_len,
                        *self._sampling_row(h)))
                else:
                    first = self._apply_state(self._final_fn(width)(
                        *self._prog_args(), job.staging, toks_d,
                        slot, offset, real - 1, p_len, *self._sampling_row(h)))
                job.staging = None
                job.d_staging = None
                if self.paged:
                    # the chain's contents are now fully dispatched: publish
                    # the prompt's full blocks into the prefix trie
                    self._pool.publish(self._plans[slot], h.prompt)
            job.written += real
            if final:
                del self._prefilling[slot]
                self._mirror_admit(slot, h)
                self._pending.append(("prefill", first, [(slot, h)],
                                      self.stats["decode_steps"]))

    def _mirror_admit(self, slot: int, h: RequestHandle) -> None:
        """Host mirrors of the per-slot state the prefill program just set
        on device — the scheduler's bookkeeping view."""
        self._active[slot] = True
        self._positions[slot] = len(h.prompt)

    def _finish_prefilled(self, slot: int, token: int) -> None:
        """Prefill role's hand-off: the drained first token means this
        request's prompt KV is fully written, so gather its blocks out of
        the arena (read-only — shared prefix blocks gather safely), hang
        a :class:`networking.KVBlocks` on the handle, push the token, and
        retire ``"prefilled"`` through the STANDARD path — blocks release
        via ``_release_blocks`` exactly like any retirement, so the
        zero-leak contract holds without a special case."""
        h = self._handles[slot]
        t0 = time.perf_counter()
        plan = self._plans[slot]
        bs = self.block_size
        p_len = len(h.prompt)
        n_src = -(-p_len // bs)
        rows = np.full((self._blocks_per_slot,), self.kv_blocks, np.int32)
        rows[:n_src] = plan.blocks[:n_src]
        phys = (rows[:, None] * bs
                + np.arange(bs, dtype=np.int32)[None, :]).reshape(-1)
        dev = self._gather_fn(self.caches, self._put(phys))
        keep = n_src * bs
        layers = [None if c is None else
                  {k: np.ascontiguousarray(self._fetch(v)[:keep])
                   for k, v in c.items()}
                  for c in dev]
        h.kvblocks = networking.KVBlocks(
            layers, bs, n_src, p_len, np.asarray(h.key, np.uint32))
        h._push(token)
        self.stats["tokens_generated"] += 1
        self.stats["kv_blocks_shipped"] += n_src
        self.stats["kv_block_bytes_shipped"] += h.kvblocks.nbytes
        self.stats["transfer_ms"].append(
            (time.perf_counter() - t0) * 1000.0)
        self._retire(slot, "prefilled")

    # ---------------------------------------------------------- retirement
    def _emit(self, slot: int, token: int) -> None:
        """Record one produced token for the request in ``slot``; retire on
        eos (the eos itself is emitted, as in ``generate``) or length."""
        h = self._handles[slot]
        h._push(token)
        self.stats["tokens_generated"] += 1
        if h.eos_id is not None and token == h.eos_id:
            self._retire(slot, "eos")
        elif len(h.tokens) >= h.num_steps:
            self._retire(slot, "length")

    def _vacate(self, slot: int) -> None:
        """Free a running request's slot, host row and device row.  An
        in-flight lookahead step may compute one junk token for the row
        (drained entries skip finished handles), but from the next
        dispatch on the slot is inert until a prefill program rewrites it.
        Paged: the block-table row is re-nulled IN THE SAME program, so
        that junk (and every later idle pass) drops into the null block
        while the released blocks go back to the allocator — the one
        in-flight lookahead write ordered before any program that could
        reuse them."""
        self._handles[slot] = None
        self._active[slot] = False
        self._positions[slot] = 0
        self._free.append(slot)
        if not self.paged:
            self._dev_act = self._deact_fn(self._dev_act, slot)
            return
        if self._draft_model is None:
            self._dev_act, self._dev_bt = self._deact_fn(
                self._dev_act, self._dev_bt, slot)
        else:
            (self._dev_act, self._dev_bt, self._dev_dbt) = self._deact_fn(
                self._dev_act, self._dev_bt, self._dev_dbt, slot)
        self._release_blocks(slot)

    def _retire(self, slot: int, reason: str) -> None:
        h = self._handles[slot]
        with span("serve.retire", rid=h.id, reason=reason):
            self._vacate(slot)
            if h._finish(reason):  # no-op when _declare_dead already failed it
                with self._qlock:  # drain()'s busy() sums this cross-thread
                    self.stats["requests_completed"] += 1
                    self._tenant_stats(h.tenant)["completed"] += 1
                self._account_terminal(h, reason, time.perf_counter())

    # ----------------------------------------------- preemption (QoS swap)
    def preempt(self, handle: RequestHandle) -> bool:
        """Mark a RUNNING request for preemption (thread-safe): within one
        scheduler iteration its live KV blocks are gathered to host
        memory, its slot and blocks are freed, and it waits in the
        suspended set until capacity is free again — then resumes through
        the jitted ingest program with a bit-identical token stream.
        The deterministic-control surface tests and operators use; the
        scheduler fires the same path itself when the interactive tier is
        starved.  Returns False when the request already finished or this
        engine cannot preempt (needs ``paged=True``, ``role="unified"``,
        no rolling window, no speculation)."""
        if not self._can_preempt:
            return False
        with handle._cond:
            if handle.finish is not None:
                return False
        with self._qlock:
            self._preempt_ids.add(handle.id)
            self._have_work.notify_all()
        return True

    def _ensure_swap_fns(self) -> None:
        """Build (lazily) the swap-out gather and swap-in ingest programs.
        Both reuse the disaggregation machinery — ``gather_slot_state``
        wraps the prefill role's block gather and ``_build_ingest_fn`` is
        exactly the decode role's install program — so a preemption
        round-trips bytes through the very path PR 16 ships them over the
        wire with."""
        if self._swap_gather_fn is None:
            self._swap_gather_fn = jax.jit(_dec.gather_slot_state)
        if self._swap_ingest_fn is None:
            self._swap_ingest_fn = self._build_ingest_fn()

    def _suspend_slot(self, slot: int) -> bool:
        """Swap one running request out: flush the decode lookahead (so
        the handle's emitted tokens reach the true frontier), gather its
        live KV blocks + device frontier in one jitted dispatch, copy
        them to host memory, then free the slot and blocks through the
        standard deactivation path — WITHOUT making the handle terminal.
        The d2h fetches land in the PR 9 transfer counters like any
        extraction.  Returns False when the request retired during the
        flush (nothing left to suspend)."""
        h = self._handles[slot]
        if h is None:
            return False
        t0 = time.perf_counter()
        if self._pending:
            self._drain_pending(flush=True)
        if self._handles[slot] is not h or h.finish is not None:
            return False  # eos/length/cancel landed in the flush
        self._ensure_swap_fns()
        plan = self._plans[slot]
        bs = self.block_size
        n_src = max(-(-int(self._positions[slot]) // bs), 1)
        rows = np.full((self._blocks_per_slot,), self.kv_blocks, np.int32)
        rows[:n_src] = plan.blocks[:n_src]
        phys = (rows[:, None] * bs
                + np.arange(bs, dtype=np.int32)[None, :]).reshape(-1)
        dev, d_tok, d_pos, _ = self._swap_gather_fn(
            self.caches, self._put(phys), self._dev_tok, self._dev_pos,
            self._dev_keys, self._put(np.int32(slot)))
        keep = n_src * bs
        layers = [None if c is None else
                  {k: np.ascontiguousarray(self._fetch(v)[:keep])
                   for k, v in c.items()}
                  for c in dev]
        pos, tok = int(self._fetch(d_pos)), int(self._fetch(d_tok))
        rec = _SuspendedReq(h, layers, n_src, pos, tok)
        # free the slot + blocks exactly like _retire, minus the terminal
        # transition: the handle stays live, parked in _suspended
        self._vacate(slot)
        h.slot = None
        nbytes = sum(a.nbytes for c in layers if c is not None
                     for a in c.values())
        with self._qlock:  # _declare_dead drains _suspended cross-thread
            self._suspended[h.id] = rec
            self.stats["preemptions"] += 1
            self._tenant_stats(h.tenant)["preemptions"] += 1
        self.stats["kv_blocks_swapped_out"] += n_src
        self.stats["kv_block_bytes_swapped_out"] += nbytes
        self.stats["preempt_swap_ms"].append(
            (time.perf_counter() - t0) * 1000.0)
        return True

    def _resume_suspended(self, rec: _SuspendedReq) -> bool:
        """Swap one suspended request back in: allocate a fresh private
        chain, scatter the host payload into it, and re-install the
        slot's device row at the SUSPENDED frontier — original RNG key,
        current token, position — through the jitted ingest program.
        Sampling keys fold per (key, absolute position), so the resumed
        stream is bit-identical to the run that was never preempted.
        Returns False when blocks or slots are unavailable (the caller
        retries next iteration)."""
        h = rec.handle
        if not self._free:
            return False
        bs = self.block_size
        total = len(h.prompt) + h.num_steps
        plan = self._pool.admit(None, -(-total // bs))
        if plan is None:
            return False
        t0 = time.perf_counter()
        self._ensure_swap_fns()
        slot = self._free.pop()
        h.slot = slot
        self._handles[slot] = h
        self._plans[slot] = plan
        self.stats["slot_requests"][slot] += 1
        n_src = rec.n_blocks
        rows = np.full((self._blocks_per_slot,), self.kv_blocks, np.int32)
        rows[:n_src] = plan.blocks[:n_src]
        phys = (rows[:, None] * bs
                + np.arange(bs, dtype=np.int32)[None, :]).reshape(-1)
        pad = (self._blocks_per_slot - n_src) * bs
        payload = []
        nbytes = 0
        for c in rec.layers:
            if c is None:
                payload.append(None)
                continue
            nbytes += sum(a.nbytes for a in c.values())
            payload.append({
                k: self._put(np.concatenate(
                    [v, np.zeros((pad,) + v.shape[1:], v.dtype)])
                    if pad else v)
                for k, v in c.items()})
        bt, _ = self._row_tables(plan)
        (self.caches, self._dev_bt, self._dev_tok, self._dev_pos,
         self._dev_act, self._dev_temp, self._dev_topk, self._dev_topp,
         self._dev_keys) = self._swap_ingest_fn(
            self.caches, self._dev_bt, self._dev_tok, self._dev_pos,
            self._dev_act, self._dev_temp, self._dev_topk,
            self._dev_topp, self._dev_keys,
            self._put(phys), payload, self._put(np.int32(slot)),
            self._put(bt), self._put(np.int32(rec.tok)),
            self._put(np.int32(rec.pos)),
            self._put(np.float32(h.temperature)),
            self._put(np.int32(0 if h.top_k is None else h.top_k)),
            self._put(np.float32(0.0 if h.top_p is None else h.top_p)),
            self._put(np.asarray(h.key, np.uint32)))
        self._mirror_admit(slot, h)
        # the suspended frontier, not the prompt boundary
        self._positions[slot] = rec.pos
        with self._qlock:
            self.stats["resumes"] += 1
            self._tenant_stats(h.tenant)["resumes"] += 1
        self.stats["kv_blocks_resumed"] += n_src
        self.stats["kv_block_bytes_resumed"] += nbytes
        self.stats["preempt_resume_ms"].append(
            (time.perf_counter() - t0) * 1000.0)
        return True

    def _balance_qos(self) -> bool:
        """The preemption scheduler pass (between ``_reap`` and
        ``_schedule_prefills``): suspend explicitly-marked requests and —
        when the interactive tier is starved of slots or blocks — the
        lowest-priority, youngest batch-tier running request (one victim
        per iteration: preemption is expensive; starvation that persists
        keeps firing it); then resume suspended requests oldest-first
        whenever capacity is free and no interactive request is waiting
        (suspended requests outrank the queue — they hold paid-for
        progress)."""
        did = False
        suspended_now = set()
        with self._qlock:
            explicit = set(self._preempt_ids)
            self._preempt_ids.clear()
            starved = self._q_int > 0
        blocked = self._int_blocked
        self._int_blocked = False
        if explicit:
            for slot in np.flatnonzero(self._active):
                h = self._handles[int(slot)]
                if h is not None and h.id in explicit:
                    if self._suspend_slot(int(slot)):
                        suspended_now.add(h.id)
                        did = True
        if starved and (not self._free or blocked):
            victims = []
            for slot in np.flatnonzero(self._active):
                h = self._handles[int(slot)]
                if h is None:
                    continue
                with self._qlock:
                    tier = self._tier_of(h.tenant)
                if tier == "interactive":
                    continue
                victims.append((h.priority, -(h.started_at or 0.0),
                                int(slot)))
            if victims:
                victims.sort()
                v = self._handles[victims[0][2]]
                if self._suspend_slot(victims[0][2]):
                    suspended_now.add(v.id)
                    did = True
        with self._qlock:  # _declare_dead drains _suspended cross-thread
            waiting_int = self._q_int > 0
            susp = list(self._suspended.items())
        if susp and self._free and not waiting_int:
            for rid, rec in susp:
                if not self._free:
                    break
                if rid in suspended_now:
                    # never round-trip a request suspended THIS pass:
                    # the capacity it freed must first be offered to
                    # whatever starved it (admitted one stage later,
                    # in _schedule_prefills)
                    continue
                if rec.handle.finish is not None:
                    with self._qlock:
                        self._suspended.pop(rid, None)  # failed meanwhile
                    continue
                if not self._resume_suspended(rec):
                    break  # block-starved: wait for retirements
                with self._qlock:
                    self._suspended.pop(rid, None)
                did = True
        return did

    # ------------------------------------------------------------ schedule
    def step(self) -> bool:
        """One engine iteration: retire cancelled/expired requests
        (``_reap`` — queued ones shed before prefill, running AND
        mid-chunked-prefill ones freeing their slot mid-run), spend up to
        ``prefills_per_step`` prefill work units (chunk advances + new
        admissions), dispatch one decode step for every running request,
        then drain the pipeline's oldest in-flight step (one-step
        lookahead: the device computes step t+1 while the host emits step
        t's tokens).  Returns whether any work happened.

        Hot weight reload fires only when ``decode_steps`` actually
        ADVANCED onto a multiple of the reload cadence — a reap- or
        prefill-only iteration leaves the counter parked and must not
        re-pull on every pass."""
        self._iterations += 1
        phase = self.account.phase
        with self.account.iteration(self._iterations,
                                    int(np.count_nonzero(self._active))):
            self.last_beat = time.monotonic()
            steps_before = self.stats["decode_steps"]
            with phase("reap"):
                did = self._reap()
            if self._can_preempt:
                with self._qlock:
                    qos_work = bool(self._preempt_ids or self._suspended
                                    or self._q_int)
                if qos_work or self._int_blocked:
                    with phase("qos"):
                        did = self._balance_qos() or did
            with phase("schedule"):
                did = self._schedule_prefills() or did
            if self.role == "prefill":
                # no token loop at all: drain every dispatched prefill NOW
                # (the drained first token triggers extraction + hand-off —
                # with decode gated off, nothing else would ever push a
                # lookahead entry out of the pipeline)
                if self._pending:
                    did = self._drain_pending(flush=True) or did
                with phase("publish"):
                    self._set_load()
                return did
            if self._active.any():
                self._decode_once()
                did = True
            if self._pending:
                did = self._drain_pending(flush=not self._active.any()) or did
            if (self._reload_every
                    and self.stats["decode_steps"] > steps_before
                    and self.stats["decode_steps"] % self._reload_every == 0):
                with phase("reload"):
                    self._pull_weights()
            with phase("publish"):
                self._set_load()
            return did

    def _decode_once(self) -> None:
        # dispatch only — every argument is already a device
        # array (zero uploads), and the sampled row is fetched one
        # iteration later by _drain_pending (one-step lookahead)
        entries = [(int(s), self._handles[s])
                   for s in np.flatnonzero(self._active)]
        self.stats["decode_steps"] += 1
        step = self.stats["decode_steps"]
        self.stats["paged_kernel_steps"] += self._decode_attn == "kernel"
        sample = _sampler_work(entries)
        self.stats["sampler_draw_steps"] += sample != "greedy"
        self.stats["sampler_filter_steps"] += sample == "filter"
        self.account.decode_step(step)
        with self.account.phase("decode_dispatch", active=len(entries),
                                step=step, attn=self._decode_attn,
                                sample=sample, state=self._state_kinds):
            if self._draft_model is not None:
                # speculative round: k draft steps + one batched verify in
                # ONE program; rows commit 1..spec_len+1 tokens each, packed
                # with their per-row counts into the one drained array
                (out, self.caches, self.d_caches, self._dev_tok,
                 self._dev_pos) = self._spec_fn(
                    self.params, self._draft_params, *self._state_args())
                self.stats["verify_calls"] += 1
                self.stats["target_calls"] += 1
                self.stats["drafted"] += self.spec_len * len(entries)
                self.stats["active_slot_steps"] += len(entries)
                self._pending.append(("spec", out, entries, step))
                return
            out, self.caches, self._dev_pos, *packed = self._decode_fn(
                self.params, *self._state_args())
            self._dev_tok = out
            self.stats["active_slot_steps"] += len(entries)
            self.stats["recurrent_state_bytes_moved"] += (
                2 * len(entries) * self._recurrent_row_bytes)
            # a model with expert layers hands back the tokens with its
            # counters behind them: still one fetch a step
            self._pending.append(("decode", packed[0] if packed else out,
                                  entries, step))

    def _drain_pending(self, flush: bool = False) -> bool:
        """Emit the tokens of in-flight steps older than the lookahead
        window (``flush=True`` empties the pipeline — the no-decode-work
        tail).  Each drained entry costs exactly one device→host fetch.
        A slot whose request retired (or was recycled) after dispatch is
        skipped: the lookahead step computed one junk token for it, which
        dies here."""
        did = False
        keep = 0 if flush else 1
        while len(self._pending) > keep:
            kind, arr, entries, step = self._pending.popleft()
            with self.account.phase("fetch", step=step):
                vals = self._fetch(arr)
            with self.account.phase("emit", kind=kind, rows=len(entries),
                                    step=step) as stamp:
                if kind != "prefill":
                    self.account.step_emitted(step, len(entries), stamp.t0)
                if kind == "decode" and len(vals) > self.num_slots:
                    held, touched, fullest = vals[self.num_slots:]
                    self.stats["moe_assignments_held"] += int(held)
                    self.stats["moe_experts_touched"] += int(touched)
                    self.stats["moe_load_max"] += int(fullest)
                    self.stats["moe_layer_steps"] += self._moe_layers
                elif kind == "prefill" and self._moe_prefill:
                    held, touched, _, units = vals[-4:]
                    self.stats["moe_prefill_assignments_held"] += int(held)
                    self.stats["moe_prefill_experts_touched"] += int(touched)
                    self.stats["moe_prefill_layer_units"] += int(units)
                for i, (slot, h) in enumerate(entries):
                    if h.finish is not None or self._handles[slot] is not h:
                        continue
                    if kind == "spec":
                        # row ``slot`` committed n tokens this round (its
                        # per-row accept length + 1); emit in order,
                        # stopping the moment eos/length retires the
                        # request — the round's trailing tokens die here,
                        # like any lookahead junk
                        n = int(vals[slot, -1])
                        self.stats["accepted"] += max(n - 1, 0)
                        self._positions[slot] += n
                        for j in range(n):
                            self._emit(slot, int(vals[slot, j]))
                            if (h.finish is not None
                                    or self._handles[slot] is not h):
                                break
                        continue
                    token = int(vals[slot] if kind == "decode" else vals[i])
                    if kind == "decode":
                        self._positions[slot] += 1
                    if self.role == "prefill":
                        self._finish_prefilled(slot, token)
                    else:
                        self._emit(slot, token)
            did = True
        return did

    def run_until_idle(self, max_steps: Optional[int] = None) -> None:
        """Drive the scheduler inline until queue and slots are empty (the
        synchronous mode tests and closed-loop benches use).  A crash
        inside a step fails every in-flight handle with
        :class:`EngineDead` before re-raising — waiters on other threads
        never hang on a dead inline engine."""
        steps = 0
        while True:
            try:
                if not self.step():
                    return
            except Exception as e:
                self._declare_dead(e)
                raise
            steps += 1
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"engine still busy after {max_steps} steps "
                    f"(queue={self.queue_depth}, "
                    f"active={self.active_requests})")

    @property
    def slot_occupancy(self) -> Optional[float]:
        """Mean fraction of slots doing useful work per decode step — the
        continuous-batching health metric (1.0 = every step fully packed)."""
        if not self.stats["decode_steps"]:
            return None
        return (self.stats["active_slot_steps"]
                / (self.stats["decode_steps"] * self.num_slots))

    # ------------------------------------------------------- thread driver
    def start(self) -> "ServingEngine":
        """Run the scheduler on a background thread (the wire server's
        mode); idles on the work condition when nothing is queued/active."""
        if self._thread is not None:
            return self
        with self._qlock:
            self._running = True
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="dkt-serving-engine")
        self._thread.start()
        return self

    def stop(self, join_timeout: float = 10.0) -> None:
        """Stop the background scheduler thread.

        A decode thread that outlives ``join_timeout`` is wedged inside a
        decode step (stuck compile, hung device transfer): it is logged,
        every in-flight handle is failed with :class:`EngineDead` (so no
        ``result()`` waiter blocks on a thread that will never answer),
        and the thread is detached — the same leak contract as
        ``SocketParameterServer.stop(join_timeout)``."""
        with self._qlock:
            self._running = False
            self._have_work.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=join_timeout)
            if self._thread.is_alive():
                logger.error(
                    "serving engine decode thread still alive after "
                    "stop(join_timeout=%.1fs) — wedged in a decode step; "
                    "failing in-flight requests and detaching the thread",
                    join_timeout)
                self._declare_dead(EngineDead(
                    f"decode thread wedged: did not exit within "
                    f"stop(join_timeout={join_timeout})"))
            self._thread = None
        if self._reload_sock is not None:
            try:
                networking.send_opcode(self._reload_sock, b"q")
                self._reload_sock.close()
            except OSError:
                pass
            self._reload_sock = None
        if self._reload_client is not None:
            try:
                self._reload_client.disconnect()
            except (OSError, ConnectionError):
                pass
            self._reload_client = None

    def drain(self, timeout: Optional[float] = None,
              poll: float = 0.01) -> bool:
        """Graceful drain: stop admission (``submit`` raises
        :class:`Draining`), let every queued and running request finish,
        then stop the scheduler.  Returns True when everything finished
        within ``timeout`` (None = wait forever).  On timeout the
        remaining in-flight handles are failed with :class:`EngineDead`
        (reason ``"drain"``) so no waiter hangs, and False is returned.
        Engines never ``start()``-ed are driven to idle inline by this
        call."""
        with self._qlock:
            self._draining = True
            self._not_full.notify_all()  # blocked submitters raise Draining
        self._publish_load(draining=True)
        t0 = time.monotonic()

        def busy() -> bool:
            # terminal accounting, not queue+active snapshots: a request
            # between queue-pop and slot activation (mid-prefill) is in
            # neither, but it has not reached a terminal state either.
            # rejected requests ARE terminal (incremented before the
            # QueueFull/EngineDead/Draining raise) — without them a single
            # backpressure shed would leave busy() True forever
            with self._qlock:
                s = self.stats
                return (s["requests_submitted"]
                        > s["requests_completed"] + s["requests_failed"]
                        + s["requests_rejected"])

        def timed_out() -> bool:
            return (timeout is not None
                    and time.monotonic() - t0 > timeout)

        if self._thread is None and self._dead is None:
            while busy() and not timed_out():
                try:
                    self.step()
                except Exception as e:
                    self._declare_dead(e)
                    raise
        else:
            while busy() and self._dead is None and not timed_out():
                time.sleep(poll)
        clean = self._dead is None and not busy()
        if not clean and self._dead is None:
            # declare BEFORE stop so waiters unblock immediately with
            # reason "drain" (stop would otherwise block a full
            # join_timeout on a wedged loop first)
            self._declare_dead(
                EngineDead(f"drain timed out after {timeout}s with work "
                           f"in flight"), reason="drain")
        self.stop(join_timeout=10.0 if clean else 2.0)
        if not clean:
            self._fail_stragglers(reason="drain")
        return clean

    def _fail_stragglers(self, reason: str) -> None:
        """Post-join sweep for the declare→exit window: a request the
        scheduler popped from the queue BEFORE ``_declare_dead`` swept it
        can land in ``_handles`` (or ``_suspended``) during the loop's
        final iteration, AFTER the sweep — invisible to both.  With the
        loop joined, fail whatever it left live so no waiter hangs."""
        exc = self._dead
        if exc is None:
            return
        with self._qlock:
            suspended = [rec.handle for rec in self._suspended.values()]
            self._suspended.clear()
        for h in suspended:
            if h._fail(EngineDead(
                    f"request was swapped out (preempted) and not resumed "
                    f"before engine shutdown: {exc}"), reason=reason):
                with self._qlock:
                    self.stats["requests_failed"] += 1
                    self._tenant_stats(h.tenant)["completed"] += 1
        for h in list(self._handles):
            if h is not None and h._fail(EngineDead(str(exc)),
                                         reason=reason):
                with self._qlock:
                    self.stats["requests_failed"] += 1

    # -------------------------------------------------- failure semantics
    def declare_dead(self, reason: str) -> None:
        """Supervisor-facing: mark the engine dead and fail every in-flight
        handle with a typed :class:`EngineDead` (``EngineSupervisor`` calls
        this on a stale heartbeat — a wedged decode step — before
        restarting from ``respawn_clone``)."""
        self._declare_dead(EngineDead(reason))

    def _declare_dead(self, cause: BaseException,
                      reason: str = "error") -> None:
        """Terminal engine failure: stop the loop, shed the queue, and fail
        every queued + running handle so no ``result()``/``next_chunk``
        waiter hangs.  Idempotent (first cause wins).  Slot arrays are NOT
        recycled — a wedged decode thread may still be writing them; a
        restart goes through ``respawn_clone`` (fresh pool) instead."""
        exc = (cause if isinstance(cause, EngineDead)
               else EngineDead(f"serving engine died: {cause!r}"))
        if exc is not cause:
            exc.__cause__ = cause
        with self._qlock:
            self._running = False
            if self._dead is not None:
                return
            self._dead = exc
            queued = self._q_clear_locked()
            suspended = [rec.handle for rec in self._suspended.values()]
            self._suspended.clear()
            self._not_full.notify_all()
            self._have_work.notify_all()
        # suspended requests hold no slot and no blocks — they are invisible
        # to _handles and to busy()'s terminal accounting until failed here;
        # without this, drain()/scale_down() would hang on a swapped-out
        # request forever (its waiter never reaches a terminal state)
        for h in suspended:
            if h._fail(EngineDead(
                    f"request was swapped out (preempted) and not resumed "
                    f"before engine shutdown: {exc}"), reason=reason):
                with self._qlock:
                    self.stats["requests_failed"] += 1
                    self._tenant_stats(h.tenant)["completed"] += 1
        inflight = queued + [h for h in self._handles if h is not None]
        for h in inflight:
            # _handles is read without the scheduler's lock: a still-running
            # decode thread may retire a handle concurrently, making _fail a
            # no-op — only a true transition counts (a request must never
            # land in both requests_completed and requests_failed)
            if h._fail(EngineDead(str(exc)), reason=reason):
                with self._qlock:  # drain()'s busy() sums this cross-thread
                    self.stats["requests_failed"] += 1
        self._publish_load(qd=0, dead=True)

    @property
    def dead(self) -> Optional[BaseException]:
        """The :class:`EngineDead` that killed this engine, or None."""
        return self._dead

    @property
    def params(self):
        """The weights as the engine holds them: on the device, once (the
        decode loop ships nothing host→device per iteration), each layer's
        in the form its ``store_for_serving`` gives — an expert layer's
        up-projection transposed where the grouped matmul would otherwise
        read it through a relayout copy
        (``stats["moe_up_projections_transposed"]`` counts them).  ASSIGN
        parameters in the model's own layout (``Sequential.init``'s, what
        ``set_weights`` makes) or as read from an engine: construction, the
        parameter-server reload and ``respawn_clone`` all come through this
        one setter, and the form given in is not kept."""
        return self._params

    @params.setter
    def params(self, params):
        stored = [layer.store_for_serving(p)
                  for layer, p in zip(self.model.layers, params)]
        self._params = jax.device_put([p for p, _ in stored])
        self.stats["moe_up_projections_transposed"] = sum(
            n for _, n in stored)

    def respawn_clone(self) -> "ServingEngine":
        """A fresh engine over the same model/params and knobs — new KV
        slot pool, empty queue, fresh stats (the ``EngineSupervisor``
        restart path; mirrors ``SocketParameterServer.respawn_clone``)."""
        with self._qlock:  # register_tenant may race a supervisor respawn
            # QoS policy carries over with FRESH token buckets — banked
            # quota credit belongs to the dead engine's admission history
            tenant_pols = [p.clone() for p in self._tenants.values()]
        eng = ServingEngine(
            (self.model, self.params), num_slots=self.num_slots,
            max_len=self.max_len, queue_capacity=self.queue_capacity,
            prefills_per_step=self.prefills_per_step, rolling=self.rolling,
            default_deadline_s=self.default_deadline_s,
            prefill_chunk=self.prefill_chunk,
            spec_draft=(None if self._draft_model is None
                        else (self._draft_model, self._draft_params)),
            spec_len=self.spec_len, quantize=self.quantize,
            kv_dtype=self.kv_dtype,
            # paged knobs carry over with the SAME arena shape but a
            # FRESH trie + allocator — cached prefix chains belong to the
            # dead pool's arena contents, which the clone does not share
            paged=self.paged, block_size=self.block_size,
            kv_blocks=self.kv_blocks, role=self.role,
            tenants=tenant_pols or None)
        # quantized clones re-quantize and re-store idempotently; the
        # skeleton the hot-reload path maps pulled weights onto carries over
        # as-is (the clone's params are already quantized and stored for
        # serving, so it could not rebuild the model's own layout itself)
        eng._fp_skel = self._fp_skel
        if self._ps_addr is not None:
            eng.attach_ps(*self._ps_addr, every=self._reload_every,
                          retry_policy=self._reload_policy,
                          shard_plan=self._ps_shard_plan,
                          shard_addrs=self._ps_shard_addrs)
        # the freshness listener is engine-agnostic (a (time, clock)
        # callback) — carrying it over keeps the online deployment's
        # freshness chain intact across supervised restarts and
        # blue/green swaps without re-registration
        eng._reload_listener = self._reload_listener
        return eng

    @property
    def kv_pool_bytes(self) -> int:
        """On-device bytes of the target KV slot pool — the flat block
        arena for paged engines — (int8 codes + scales for
        ``kv_dtype="int8"`` pools, itemsize-true otherwise): the
        byte-accounting behind ``serving_quant_capacity_slots`` and
        ``serving_paged_capacity_slots``."""
        return _quant.kv_cache_bytes(self.caches)

    @property
    def kv_blocks_in_use(self) -> Optional[int]:
        """Paged engines: blocks currently HELD by live requests
        (privately-owned + trie-shared with ref > 0).  0 when idle —
        refcount-0 cached chains are reusable capacity, not leaks; the
        resilience matrix asserts this returns to 0 after every
        retirement path.  None for dense engines."""
        return None if self._pool is None else self._pool.in_use()

    def warmup(self) -> "ServingEngine":
        """Compile the engine's jitted programs before serving traffic: the
        decode step plus EVERY bucket's batched prefill program and (when
        long prompts can chunk) the chunk-step programs.  A fresh engine otherwise pays each program's jit
        trace/compile inside the first real iteration that needs it —
        under an ``EngineSupervisor`` whose ``liveness_deadline`` is
        shorter than that compile, a cold engine is indistinguishable
        from a wedged one, so the supervisor warms every respawned clone
        before it goes live (cold jit must never read as a wedge under
        live traffic).  The prefill warmups target slot ``num_slots``, so
        every write drops on device — state is untouched.  Idempotent;
        fresh/idle engines only."""
        if self._active.any() or self._prefilling:
            raise RuntimeError("warmup() on an engine with active slots "
                               "would consume a real decode step")

        def program(name):  # one span per program compiled
            return span("serve.warmup", program=name)

        # one all-slots-inactive decode step (the speculative
        # round — draft steps + verify + back-fill — when a draft is
        # attached: a respawn under live traffic must pay zero jit on its
        # first real round)...
        if self.role == "prefill":
            # the token loop never runs on a prefill-role engine: skip
            # the decode-step warmup and warm the extraction gather
            # instead (all-null rows read the null block)
            rows = jnp.full((self._blocks_per_slot * self.block_size,),
                            self.kv_blocks * self.block_size, jnp.int32)
            with program("gather"):
                jax.block_until_ready(jax.tree_util.tree_leaves(
                    self._gather_fn(self.caches, rows))[0])
        elif self._draft_model is not None:
            with program("spec"):
                (_, self.caches, self.d_caches, self._dev_tok,
                 self._dev_pos) = self._spec_fn(
                    self.params, self._draft_params, *self._state_args())
                jax.block_until_ready(self._dev_tok)
        else:
            with program("decode"):
                out, self.caches, self._dev_pos, *_ = self._decode_fn(
                    self.params, *self._state_args())
                self._dev_tok = out
                jax.block_until_ready(out)
        if self.role == "decode":
            # ingest program only: the bucket/chunk prefill programs are
            # never dispatched on a decode-role engine (admission is
            # submit_prefilled), so warming them would compile dead code.
            # Slot num_slots + mode="drop" installs nothing; the scatter
            # lands in the null block.
            n = self._blocks_per_slot * self.block_size
            rows = jnp.full((n,), self.kv_blocks * self.block_size,
                            jnp.int32)
            payload = [None if c is None else
                       {k: jnp.zeros((n,) + v.shape[1:], v.dtype)
                        for k, v in c.items()}
                       for c in self.caches]
            with program("ingest"):
                (self.caches, self._dev_bt, self._dev_tok, self._dev_pos,
                 self._dev_act, self._dev_temp, self._dev_topk,
                 self._dev_topp, self._dev_keys) = self._ingest_fn(
                    self.caches, self._dev_bt, self._dev_tok,
                    self._dev_pos, self._dev_act, self._dev_temp,
                    self._dev_topk, self._dev_topp, self._dev_keys, rows,
                    payload, jnp.int32(self.num_slots),
                    jnp.full((self._t_tbl,), self.kv_blocks, jnp.int32),
                    jnp.int32(0), jnp.int32(0), jnp.float32(0.0),
                    jnp.int32(0), jnp.float32(0.0),
                    jnp.zeros((2,), jnp.uint32))
                jax.block_until_ready(
                    jax.tree_util.tree_leaves(self.caches)[0])
            return self
        # ...every bucket's batched prefill program (all rows dropped;
        # quantized pools and draft-pool prefill compile here too — the
        # commit/quantize paths live inside these same programs; paged
        # warmups pass all-null block tables, so every cache write drops
        # into the null block)...
        nb = self.prefills_per_step
        drop = jnp.full((nb,), self.num_slots, jnp.int32)
        if self.paged:
            null_bt = jnp.full((nb, self._t_tbl), self.kv_blocks,
                               jnp.int32)
            null_dbt = (jnp.full((nb, self._d_tbl), self.kv_blocks,
                                 jnp.int32)
                        if self._draft_model is not None else None)
            # the copy-on-write block-copy program (null → null)
            with program("copy"):
                if self._draft_model is None:
                    self.caches = self._copy_fn(self.caches, self.kv_blocks,
                                                self.kv_blocks)
                else:
                    self.caches, self.d_caches = self._copy_fn(
                        self.caches, self.d_caches, self.kv_blocks,
                        self.kv_blocks)
        for width in self._buckets:
            with program(f"bucket_{width}"):
                if self.paged:
                    extra = [jnp.zeros((nb, width), jnp.int32),
                             jnp.zeros((nb,), jnp.int32),
                             jnp.ones((nb,), jnp.int32), drop, null_bt]
                    if null_dbt is not None:
                        extra.append(null_dbt)
                    self._apply_state(self._bucket_fn(width)(
                        *self._prog_args(), *extra,
                        jnp.zeros((nb,), jnp.float32),
                        jnp.zeros((nb,), jnp.int32),
                        jnp.zeros((nb,), jnp.float32),
                        jnp.zeros((nb, 2), jnp.uint32)))
                else:
                    self._apply_state(self._bucket_fn(width)(
                        *self._prog_args(),
                        jnp.zeros((nb, width), jnp.int32),
                        jnp.ones((nb,), jnp.int32), drop,
                        jnp.zeros((nb,), jnp.float32),
                        jnp.zeros((nb,), jnp.int32),
                        jnp.zeros((nb,), jnp.float32),
                        jnp.zeros((nb, 2), jnp.uint32)))
        # ...and the chunk-step programs, when a prompt can be long enough
        # to take the chunked path at all
        if self.max_len > self.prefill_chunk:
            one = (jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
                   jnp.zeros((1,), jnp.float32),
                   jnp.zeros((1, 2), jnp.uint32))
            for width in sorted({self._chunk_width, *self._buckets}):
                with program(f"chunk_{width}"):
                    toks = jnp.zeros((1, width), jnp.int32)
                    if self.paged and not self.rolling:
                        off = jnp.zeros((1,), jnp.int32)
                        plen = jnp.ones((1,), jnp.int32)
                        bt1 = null_bt[:1]
                        if self._draft_model is not None:
                            self.caches, self.d_caches = self._stage_fn(width)(
                                self.params, self._draft_params, self.caches,
                                self.d_caches, toks, off, plen, bt1,
                                null_dbt[:1])
                            self._apply_state(self._final_fn(width)(
                                *self._prog_args(), toks, self.num_slots,
                                off, plen, 0, bt1, null_dbt[:1], *one))
                        else:
                            self.caches, moe = self._stage_fn(width)(
                                self.params, self.caches, toks, off, plen,
                                bt1, self.num_slots, self._moe_zero)
                            self._apply_state(self._final_fn(width)(
                                *self._prog_args(), toks, self.num_slots,
                                off, plen, 0, bt1, *one, moe))
                        continue
                    staging = init_cache(self.model, 1, self.max_len)
                    if self._draft_model is not None:
                        d_staging = init_cache(self._draft_model, 1,
                                               self.max_len)
                        staging, d_staging = self._stage_fn(width)(
                            self.params, self._draft_params, staging,
                            d_staging, toks, 0)
                        if self.paged:  # rolling paged: block-table commit
                            self._apply_state(self._final_fn(width)(
                                *self._prog_args(), staging, d_staging, toks,
                                self.num_slots, 0, 0, 1, null_bt[:1],
                                null_dbt[:1], *one))
                        else:
                            self._apply_state(self._final_fn(width)(
                                *self._prog_args(), staging, d_staging, toks,
                                self.num_slots, 0, 0, 1, *one))
                    else:
                        staging = self._stage_fn(width)(self.params, staging,
                                                        toks, 0)
                        if self.paged:
                            self._apply_state(self._final_fn(width)(
                                *self._prog_args(), staging, toks,
                                self.num_slots, 0, 0, 1, null_bt[:1], *one))
                        else:
                            self._apply_state(self._final_fn(width)(
                                *self._prog_args(), staging, toks,
                                self.num_slots, 0, 0, 1, *one))
        # QoS engines also pre-pay the preemption swap programs: gather
        # (all-null rows read the null block) and ingest (slot num_slots
        # drops the install, the scatter lands in the null block) — a
        # first preemption under live overload must not stall the decode
        # loop a jit-compile long.
        with self._qlock:
            qos_on = bool(self._tenants)
        if self._can_preempt and qos_on:
            self._ensure_swap_fns()
            n = self._blocks_per_slot * self.block_size
            null_rows = jnp.full((n,), self.kv_blocks * self.block_size,
                                 jnp.int32)
            with program("swap_gather"):
                jax.block_until_ready(jax.tree_util.tree_leaves(
                    self._swap_gather_fn(self.caches, null_rows, self._dev_tok,
                                         self._dev_pos, self._dev_keys,
                                         jnp.int32(0))[0])[0])
            payload = [None if c is None else
                       {k: jnp.zeros((n,) + v.shape[1:], v.dtype)
                        for k, v in c.items()}
                       for c in self.caches]
            with program("swap_ingest"):
                (self.caches, self._dev_bt, self._dev_tok, self._dev_pos,
                 self._dev_act, self._dev_temp, self._dev_topk,
                 self._dev_topp, self._dev_keys) = self._swap_ingest_fn(
                    self.caches, self._dev_bt, self._dev_tok, self._dev_pos,
                    self._dev_act, self._dev_temp, self._dev_topk,
                    self._dev_topp, self._dev_keys, null_rows, payload,
                    jnp.int32(self.num_slots),
                    jnp.full((self._t_tbl,), self.kv_blocks, jnp.int32),
                    jnp.int32(0), jnp.int32(0), jnp.float32(0.0),
                    jnp.int32(0), jnp.float32(0.0),
                    jnp.zeros((2,), jnp.uint32))
        jax.block_until_ready(jax.tree_util.tree_leaves(self.caches)[0])
        return self

    def _loop(self) -> None:
        try:
            while self._running:
                if not self.step():
                    with self.account.phase("idle_wait"), self._qlock:
                        self._have_work.wait_for(
                            lambda: self._qdepth > 0 or bool(self._preempt_ids)
                            or not self._running,
                            timeout=0.05)
        except Exception as e:
            # a crashed decode loop fails loudly: every in-flight handle
            # gets a typed EngineDead instead of hanging its waiter
            logger.exception("serving engine decode loop crashed")
            self._declare_dead(e)

    def __enter__(self) -> "ServingEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------- hot reload (stretch)
    def attach_ps(self, host: str, port: int, every: int = 1,
                  retry_policy=None, shard_plan=None,
                  shard_addrs=None) -> None:
        """Hot weight reload: pull a fresh center from a live parameter
        server (the PS stack's ``'p'`` opcode — same wire the training
        workers speak) every ``every`` decode steps, so a training run and
        this engine share one deployment.  The pull happens BETWEEN decode
        steps — in-flight requests simply continue on the new weights (the
        KV cache keeps old-weight k/v until those positions roll out, the
        standard live-reload tradeoff).

        ``retry_policy`` (a ``resilience.RetryPolicy``) governs the
        RE-DIAL when the reload socket is down — a PS shard respawning on
        the same address (``ShardSupervisor``) comes back within a few
        tens of milliseconds, so a short bounded policy rides out the
        blip without abandoning the pull.  The default
        (:data:`DEFAULT_RELOAD_POLICY`) is deliberately tight: the pull
        runs on the decode thread between steps, so its worst case is a
        bounded serving stall, never an unbounded one.  A pull that fails
        past the policy counts ``stats["reload_failures"]`` and KEEPS the
        current weights — hot reload stays best-effort by design; the
        engine never dies on its PS.

        A SHARDED training PS (``ps_shards>1``) attaches by passing
        ``shard_plan`` + ``shard_addrs``: each pull gathers the center
        across every shard through a ``ps_sharding.ShardedPSClient``
        (scatter/gather over the same 'p' wire), so the engine never
        hot-reloads one shard's torn slice.  The gathered view is
        epoch-wave consistent — per-shard slices are each snapshotted
        under their own apply lock, the same consistency the sharded
        checkpoint path provides — and a pull that loses ANY shard past
        the policy keeps the current weights wholesale (all-or-nothing,
        never a partial swap).  ``(host, port)`` must be shard 0's
        address (the canonical deployment handle)."""
        if every < 1:
            raise ValueError(f"every must be >= 1, got {every}")
        if (shard_plan is None) != (shard_addrs is None):
            raise ValueError(
                "shard_plan and shard_addrs come as a pair — both for a "
                "sharded PS attachment, neither for a single server")
        if shard_addrs is not None and len(shard_addrs) < 2:
            # the N=1 plan is the identity partition: the plain single-
            # socket pull already returns the full center
            shard_plan = shard_addrs = None
        self._ps_addr = (host, int(port))
        self._reload_policy = retry_policy
        self._reload_every = int(every)
        self._ps_shard_plan = shard_plan
        self._ps_shard_addrs = (None if shard_addrs is None else
                                [(str(h), int(p)) for h, p in shard_addrs])

    def _pull_sharded(self) -> Dict[str, Any]:
        """One gathered pull over every shard (sharded attach_ps) —
        returns the same ``{"weights", "clock"}`` shape the single-socket
        'p' reply carries, with the clock summed over shards (each shard
        counts its own applies; the sum is the total-updates center
        generation, monotone across shard respawns by the client's
        per-shard monotonic clock view)."""
        if self._reload_client is None:
            from .ps_sharding import ShardedPSClient
            policy = (self._reload_policy if self._reload_policy
                      is not None else DEFAULT_RELOAD_POLICY)
            client = ShardedPSClient(self._ps_shard_plan,
                                     self._ps_shard_addrs,
                                     recovery=True, policy=policy)
            client.connect(policy=policy)
            self._reload_client = client
        weights = self._reload_client.pull()
        return {"weights": weights,
                "clock": sum(self._reload_client._clocks)}

    def _pull_weights(self) -> None:
        try:
            if self._ps_shard_addrs is not None:
                msg = self._pull_sharded()
            else:
                if self._reload_sock is None:
                    from . import resilience
                    policy = (self._reload_policy if self._reload_policy
                              is not None else DEFAULT_RELOAD_POLICY)
                    self._reload_sock = resilience.dial(*self._ps_addr,
                                                        policy=policy)
                networking.send_opcode(self._reload_sock, b"p")
                msg = networking.recv_data(self._reload_sock,
                                           pool=self._reload_pool)
            # the skeleton maps the flat wire list back onto the model's
            # own pytree; the pulled center then takes the SAME path the
            # constructor's parameters took — never raw fp32 weights into a
            # quantized engine, and the ``params`` setter keeps them in the
            # serving form and device-resident (the decode loop's
            # zero-upload contract must survive a reload)
            fresh = self.model.set_weights(self._fp_skel, msg["weights"])
            if self.quantize is not None:
                fresh = _quantize_weights(fresh, self.quantize)
            self.params = fresh
            self.stats["weight_reloads"] += 1
            self.stats["reloads"] += 1
            clock = msg.get("clock") if isinstance(msg, dict) else None
            if clock is not None:
                self.stats["center_generation"] = int(clock)
            listener = self._reload_listener
            if listener is not None:
                try:
                    listener(time.monotonic(),
                             self.stats["center_generation"])
                except Exception:   # freshness is observability, not
                    logger.exception(  # control flow — never kill decode
                        "hot-reload listener raised")
        except (ConnectionError, OSError, ValueError) as e:
            self.stats["reload_failures"] += 1
            logger.warning("serving hot-reload pull failed (%s); keeping "
                           "current weights", e)
            if self._reload_sock is not None:
                try:
                    self._reload_sock.close()
                except OSError:
                    pass
                self._reload_sock = None
            if self._reload_client is not None:
                try:
                    self._reload_client.disconnect()
                except (OSError, ConnectionError):
                    pass
                self._reload_client = None


# ---------------------------------------------------------------------------
# wire layer: the serving protocol over the shared frame codec
# ---------------------------------------------------------------------------

#: serving-protocol opcodes (this protocol's own namespace — a serving
#: server port never speaks the PS protocol): 'q' enqueue request (frame:
#: prompt + sampling params → ack/backpressure reply), 'r' stream reply
#: (frame: {"id"} → chunk frames until {"done": True}), 'x' cancel (frame:
#: {"id"} → ack; mid-stream it is unacked — the stream's final frame
#: carries finish="cancel").
OP_ENQUEUE = networking.SERVING_OP_ENQUEUE
OP_STREAM = networking.SERVING_OP_STREAM
OP_CANCEL = networking.SERVING_OP_CANCEL
OP_KVBLOCKS = networking.SERVING_OP_KVBLOCKS
OP_STATS = networking.SERVING_OP_STATS

#: the selectable serving transport cores (``server_core=`` on
#: :class:`ServingServer`): ``"threaded"`` is the seed's
#: thread-per-connection handler, ``"event"`` the one-selector I/O loop
#: (the ``parameter_servers.PS_CORES`` twin — same knob idiom)
SERVING_CORES = ("threaded", "event")

#: event-core receive chunk: big enough that a steady-state request frame
#: lands complete in ONE recv (the parser's zero-copy fast path); larger
#: frames reassemble through the parser accumulator
_EV_RECV_CHUNK = 1 << 20

#: frames coalesced per ``sendmsg`` — comfortably under IOV_MAX, and one
#: loop wake rarely owes a connection more than a few token chunks
_EV_SENDMSG_BATCH = 64


class _EvPoisoned:
    """A deferred KV-block payload that failed its transport-boundary
    ``validate()`` while being deep-copied out of the receive scratch —
    the rejection is replayed when the deferred op is dispatched, so a
    hostile pipelined ``'k'`` sheds the connection through the same
    ``ProtocolError`` path the threaded core uses."""

    __slots__ = ("error",)

    def __init__(self, error: str):
        self.error = str(error)


def _deepcopy_wire_msg(msg: Dict[str, Any]) -> Dict[str, Any]:
    """Deep-copy a parsed wire message whose ndarray leaves are zero-copy
    views into the connection's receive scratch.  Deferred (pipelined)
    ops outlive that scratch — the next ``recv_into`` overwrites it — so
    views must be promoted to owned memory at deferral time."""
    out: Dict[str, Any] = {}
    for k, v in msg.items():
        if isinstance(v, np.ndarray):
            out[k] = np.array(v, copy=True)
        elif isinstance(v, networking.KVBlocks):
            try:
                out[k] = v.validate().decoded()
            except ValueError as e:  # replayed at dispatch (see above)
                out[k] = _EvPoisoned(str(e))
        else:
            out[k] = v
    return out


class _ServingConn:
    """Per-connection state on the serving event loop: the incremental
    frame parser over a pooled receive scratch, the pending-write queue
    with its encode pool, and the streaming-relay state (the handle being
    pumped, ops the client pipelined past it, backpressure flags).

    Touched ONLY on the loop thread — no lock.  The decoded-view lifetime
    contract matches the PS event core: every parsed op is consumed (or
    deep-copied into ``deferred``) before this connection's next
    ``recv_into`` can overwrite the scratch."""

    __slots__ = ("sock", "parser", "out", "out_bytes", "recv_pool",
                 "send_pool", "want_write", "paused", "stream", "deferred",
                 "last_progress", "closed")

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self.parser = networking.FrameParser(
            frame_ops=OP_ENQUEUE + OP_STREAM + OP_CANCEL + OP_KVBLOCKS)
        self.out: List[memoryview] = []
        self.out_bytes = 0
        self.recv_pool = networking.BufferPool()
        self.send_pool = networking.BufferPool()
        self.want_write = False
        self.paused = False   # backpressure: reads masked off, pump held
        self.stream: Optional[RequestHandle] = None  # handle mid-relay
        self.deferred: List[Tuple[bytes, Dict[str, Any]]] = []
        self.last_progress = 0.0  # perf_counter of the last stream chunk
        self.closed = False


class ServingServer:
    """TCP front-end for a :class:`ServingEngine` — same accept-loop /
    frame-codec / BufferPool idiom as ``SocketParameterServer``, so serving
    clients speak the exact wire the PS stack already speaks.

    Two transport cores behind one constructor knob (``server_core``, the
    ``parameter_servers.PS_CORES`` idiom): ``"threaded"`` (default) keeps
    the seed's thread-per-connection handler bit-identical; ``"event"``
    multiplexes every connection on ONE selector I/O thread
    (``dkt-serving-io``) — per-connection read/write buffers over the
    incremental ``networking.FrameParser``, token frames flushed through
    a socketpair waker when the engine thread pushes (no per-connection
    thread), non-blocking coalesced writes so a slow client never pins
    the relay, and a per-connection outbound cap (``max_conn_buffer``)
    that stops reading from — and pumping to — a client that stops
    reading us.  Protocol, typed errors, counters, and the failure matrix
    below are identical on both cores (docs/serving.md "Event
    transport").

    Per connection: ``'q'`` + request frame → ack ``{"ok": True, "id": n}``
    or a typed rejection (``kind`` ``"backpressure"`` / ``"draining"`` /
    ``"engine_dead"`` / ``"bad_request"``); ``'r'`` + ``{"id": n}`` → a
    stream of ``{"id", "tokens", "done"}`` chunk frames, the last one
    carrying ``done=True`` + ``finish`` (eos/length/deadline/cancel/…) +
    the final padded ``row`` (or a typed error instead of a row when the
    engine died); ``'x'`` + ``{"id": n}`` → cancel ack.  EOF closes the
    connection; the engine keeps running.

    Failure semantics (this is the client-disconnect reclamation layer):

     - every empty stream-poll slice (``poll_s``) checks the client socket
       — EOF/RST cancels the streamed request, so an abandoned connection
       reclaims its KV slot within one scheduler iteration of detection
       instead of decoding to completion;
     - a request is *owned* by the connection that submitted it (ownership
       transfers to whichever connection streams it); when a connection
       dies, its unfinished owned requests are cancelled
       (``cancel_on_disconnect``, default True) and their handles
       released — a dead client leaks neither slots nor handle entries;
     - a stream that makes no progress is bounded by the request deadline
       (plus a grace period) or, for deadline-less requests, by
       ``stream_timeout_s`` — a stalled engine gets a typed ``"stall"``
       error frame instead of pinning the handler thread for a fixed
       minute;
     - a torn/corrupt frame (``protocol_errors``) or transport fault
       (``disconnects``) sheds the connection silently; its pooled
       buffers are per-handler locals so they are released with it, and
       ``live_connections`` decrements (asserted in
       tests/test_serving_resilience.py).
    """

    def __init__(self, engine: ServingEngine, host: str = "127.0.0.1",
                 port: int = 0, stream_timeout_s: float = 60.0,
                 poll_s: float = 0.02, cancel_on_disconnect: bool = True,
                 server_core: str = "threaded",
                 max_conn_buffer: int = 1 << 20):
        if server_core not in SERVING_CORES:
            raise ValueError(f"server_core must be one of "
                             f"{sorted(SERVING_CORES)}, got {server_core!r}")
        self.engine = engine
        self.host = host
        self.port = int(port)
        self.stream_timeout_s = float(stream_timeout_s)
        self.poll_s = float(poll_s)
        self.cancel_on_disconnect = bool(cancel_on_disconnect)
        self.server_core = server_core
        #: event core only: per-connection outbound-buffer cap in bytes.
        #: A client that stops reading its token stream grows the pending
        #: write queue; past this cap the loop stops reading from AND
        #: pumping to that connection until the flush drains below half
        #: the cap (the PS core's oversize-guard idiom, per connection).
        self.max_conn_buffer = int(max_conn_buffer)
        self._handles: Dict[int, RequestHandle] = {}
        #: request id → owning connection (submitting conn, re-claimed by
        #: the streaming conn) — the disconnect-reclamation bookkeeping
        self._owner: Dict[int, socket.socket] = {}
        self._hlock = threading.Lock()
        self._server: Optional[socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._conns: List[socket.socket] = []
        self._lock = threading.Lock()  # guards: _conns
        #: lock-free stop flag: written once by start()/stop(), polled by
        #: the accept path on either core — monotonic, so races are benign
        self._running = False
        #: event core: the shared I/O loop and its per-socket conn state
        #: (the latter touched ONLY on the loop thread — no lock)
        self._loop: Optional[networking.EventLoop] = None
        self._econns: Dict[socket.socket, _ServingConn] = {}
        self.disconnects = 0       # transport faults / EOF mid-frame
        self.protocol_errors = 0   # corrupt frames (bad magic, length lies)
        self.disconnect_cancels = 0  # requests reclaimed from dead clients

    @property
    def addr(self) -> Tuple[str, int]:
        return (self.host, self.port)

    @property
    def live_connections(self) -> int:
        """Open client connections with a live handler (the serving twin of
        ``SocketParameterServer.live_connections`` — shed connections must
        decrement this, pooled buffers and all)."""
        with self._lock:
            return len(self._conns)

    def start(self) -> "ServingServer":
        self.engine.start()
        self._server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._server.bind((self.host, self.port))
        self.port = self._server.getsockname()[1]
        self._server.listen(128)
        self._running = True
        if self.server_core == "event":
            self._server.setblocking(False)
            self._loop = networking.EventLoop(name="dkt-serving-io")
            self._loop.stop_hooks.append(self._ev_shutdown)
            self._loop.start()
            self._loop.call_soon(
                lambda: self._loop.add(self._server, self._ev_accept))
            # the name is load-bearing: supervisors probe server liveness
            # through ``_accept_thread.is_alive()`` on either core
            self._accept_thread = self._loop.thread
        else:
            self._accept_thread = threading.Thread(
                target=self._accept_loop, daemon=True,
                name="dkt-serving-accept")
            self._accept_thread.start()
        return self

    def stop(self, join_timeout: float = 5.0) -> None:
        self._running = False
        if self.server_core == "event":
            loop = self._loop
            if loop is not None and not loop.stop(join_timeout=join_timeout):
                # wedged inside a callback (the loop itself never blocks
                # on a socket): force-close everything from here so the
                # wedged thread fails fast on its next socket op and a
                # same-address respawn can bind
                logger.warning(
                    "serving I/O loop still alive after stop(join_timeout="
                    "%.1fs); force-closing its connections and listener",
                    join_timeout)
                with self._lock:
                    conns = list(self._conns)
                    self._conns.clear()
                for c in conns:
                    networking._hard_close(c)
                if self._server is not None:
                    try:
                        self._server.close()
                    except OSError:
                        pass
            self.engine.stop()
            return
        if self._server is not None:
            try:  # wake the blocked accept()
                socket.create_connection((self.host, self.port),
                                         timeout=1.0).close()
            except OSError:
                pass
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=join_timeout)
        if self._server is not None:
            try:
                self._server.close()
            except OSError:
                pass
        with self._lock:
            conns = list(self._conns)
            self._conns.clear()
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        self.engine.stop()

    def respawn_clone(self, engine: Optional[ServingEngine] = None
                      ) -> "ServingServer":
        """A same-core replacement server on this address with every
        transport knob carried over — ``server_core`` included, so a
        supervisor restart never silently changes the I/O architecture.
        ``engine`` defaults to this server's (the ``EngineSupervisor``
        already re-points ``.engine`` in place; this seam is for the
        whole-server restart path, mirroring
        ``SocketParameterServer.respawn_clone``)."""
        return ServingServer(
            engine if engine is not None else self.engine,
            host=self.host, port=self.port,
            stream_timeout_s=self.stream_timeout_s, poll_s=self.poll_s,
            cancel_on_disconnect=self.cancel_on_disconnect,
            server_core=self.server_core,
            max_conn_buffer=self.max_conn_buffer)

    def __enter__(self) -> "ServingServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _accept_loop(self) -> None:
        while True:
            try:
                conn, _ = self._server.accept()
            except OSError:
                return
            if not self._running:
                try:
                    conn.close()
                except OSError:
                    pass
                return
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            with self._lock:
                self._conns.append(conn)
            threading.Thread(target=self._handle, args=(conn,),
                             daemon=True, name="dkt-serving-conn").start()

    def _handle(self, conn: socket.socket) -> None:
        # per-connection pools: requests land in a reusable receive buffer,
        # replies re-serialize into a reusable send buffer.  The send pool
        # is per-connection (BufferPool is lock-protected, but a shared
        # pool would still let another connection's encode overwrite a
        # frame between encode and sendall).  Both are handler locals, so
        # every exit path — clean EOF, torn frame, transport fault —
        # releases them with the handler.
        recv_pool = networking.BufferPool()
        send_pool = networking.BufferPool()
        pending_op = b""  # opcode the client pipelined during a stream
        try:
            while True:
                if pending_op:
                    op, pending_op = pending_op, b""
                else:
                    op = networking.recv_opcode(conn)
                if op == b"":
                    return
                if op == OP_ENQUEUE:
                    msg = networking.recv_data(conn, pool=recv_pool)
                    try:
                        h = self.engine.submit(
                            np.array(msg["prompt"], np.int32, copy=True),
                            int(msg["num_steps"]),
                            temperature=float(msg.get("temperature", 0.0)),
                            top_k=msg.get("top_k"),
                            top_p=msg.get("top_p"),
                            eos_id=msg.get("eos_id"),
                            pad_id=msg.get("pad_id"),
                            seed=int(msg.get("seed", 0)),
                            deadline_s=msg.get("deadline_s"),
                            tenant=msg.get("tenant"),
                            priority=int(msg.get("priority", 0)),
                            block=False)
                    except QuotaExceeded as e:
                        networking.send_data(
                            conn, {"ok": False, "error": str(e),
                                   "kind": "quota"}, pool=send_pool)
                        continue
                    except QueueFull:
                        networking.send_data(
                            conn, {"ok": False, "error": "queue full",
                                   "kind": "backpressure"},
                            pool=send_pool)
                        continue
                    except Draining as e:
                        networking.send_data(
                            conn, {"ok": False, "error": str(e),
                                   "kind": "draining"}, pool=send_pool)
                        continue
                    except EngineDead as e:
                        networking.send_data(
                            conn, {"ok": False, "error": str(e),
                                   "kind": "engine_dead"}, pool=send_pool)
                        continue
                    except ValueError as e:
                        networking.send_data(
                            conn, {"ok": False, "error": str(e),
                                   "kind": "bad_request"}, pool=send_pool)
                        continue
                    with self._hlock:
                        self._handles[h.id] = h
                        self._owner[h.id] = conn
                    networking.send_data(conn, {"ok": True, "id": h.id},
                                         pool=send_pool)
                elif op == OP_KVBLOCKS:
                    # disaggregated hand-off: a prefill engine (via
                    # DisaggPair) ships a request's filled KV blocks.
                    # validate() runs BEFORE any engine call — a
                    # hostile/torn payload raises ProtocolError (a
                    # ValueError) out to the shed path below with the
                    # receiving pool untouched; decoded() copies the
                    # pooled recv views before they die on the next recv.
                    msg = networking.recv_data(conn, pool=recv_pool)
                    kvb = msg.get("blocks")
                    if not isinstance(kvb, networking.KVBlocks):
                        raise networking.ProtocolError(
                            "kv-block frame carries no KVBlocks payload")
                    kvb = kvb.validate().decoded()
                    try:
                        h = self.engine.submit_prefilled(
                            kvb,
                            np.array(msg["prompt"], np.int32, copy=True),
                            int(msg["first_token"]),
                            int(msg["num_steps"]),
                            temperature=float(msg.get("temperature", 0.0)),
                            top_k=msg.get("top_k"),
                            top_p=msg.get("top_p"),
                            eos_id=msg.get("eos_id"),
                            pad_id=msg.get("pad_id"),
                            deadline_s=msg.get("deadline_s"),
                            tenant=msg.get("tenant"),
                            priority=int(msg.get("priority", 0)),
                            block=False)
                    except QuotaExceeded as e:
                        networking.send_data(
                            conn, {"ok": False, "error": str(e),
                                   "kind": "quota"}, pool=send_pool)
                        continue
                    except QueueFull:
                        networking.send_data(
                            conn, {"ok": False, "error": "queue full",
                                   "kind": "backpressure"},
                            pool=send_pool)
                        continue
                    except Draining as e:
                        networking.send_data(
                            conn, {"ok": False, "error": str(e),
                                   "kind": "draining"}, pool=send_pool)
                        continue
                    except EngineDead as e:
                        networking.send_data(
                            conn, {"ok": False, "error": str(e),
                                   "kind": "engine_dead"}, pool=send_pool)
                        continue
                    except ValueError as e:
                        networking.send_data(
                            conn, {"ok": False, "error": str(e),
                                   "kind": "bad_request"}, pool=send_pool)
                        continue
                    with self._hlock:
                        self._handles[h.id] = h
                        self._owner[h.id] = conn
                    networking.send_data(conn, {"ok": True, "id": h.id},
                                         pool=send_pool)
                elif op == OP_STREAM:
                    msg = networking.recv_data(conn, pool=recv_pool)
                    rid = int(msg["id"])
                    with self._hlock:
                        h = self._handles.get(rid)
                        if h is not None:
                            self._owner[rid] = conn  # stream claims it
                    if h is None:
                        networking.send_data(
                            conn, {"ok": False, "done": True,
                                   "kind": "unknown_id",
                                   "error": f"unknown id {rid}"},
                            pool=send_pool)
                        continue
                    alive, pending_op = self._stream(conn, h, recv_pool,
                                                     send_pool)
                    if not alive:
                        return  # client gone mid-stream (finally reclaims)
                elif op == OP_CANCEL:
                    msg = networking.recv_data(conn, pool=recv_pool)
                    with self._hlock:
                        h = self._handles.get(int(msg["id"]))
                    ok = h is not None and self.engine.cancel(h)
                    networking.send_data(
                        conn, {"ok": True, "cancelled": bool(ok)},
                        pool=send_pool)
                elif op == OP_STATS:
                    # load probe (no request body): the engine's lock-free
                    # snapshot, the signal a ServingRouter dispatches on
                    networking.send_data(
                        conn, {"ok": True, "load": self.engine.load()},
                        pool=send_pool)
                else:
                    return  # protocol violation: drop the connection
        except ValueError:
            self.protocol_errors += 1  # corrupt frame: shed silently
            return
        except (ConnectionError, OSError):
            self.disconnects += 1  # incl. a half-frame EOF/RST mid-recv
            return
        finally:
            try:
                conn.close()
            except OSError:
                pass
            with self._lock:
                if conn in self._conns:
                    self._conns.remove(conn)
            self._release_owned(conn)

    def _release_owned(self, conn: socket.socket) -> None:
        """Disconnect reclamation: cancel this connection's unfinished
        requests and drop their handle entries — a dead client's KV slot is
        back in the pool within one scheduler iteration, and the handle
        table does not grow with abandoned ids."""
        with self._hlock:
            owned = [rid for rid, c in self._owner.items() if c is conn]
            handles = [self._handles.pop(rid, None) for rid in owned]
            for rid in owned:
                self._owner.pop(rid, None)
        if not self.cancel_on_disconnect:
            return
        for h in handles:
            if h is not None and self.engine.cancel(h):
                self.disconnect_cancels += 1

    def _stream(self, conn: socket.socket, h: RequestHandle,
                recv_pool: "networking.BufferPool",
                send_pool: "networking.BufferPool"
                ) -> Tuple[bool, bytes]:
        """Relay ``h``'s token chunks until its final frame.  Bounded
        waits: each empty ``poll_s`` slice checks the client socket for
        EOF/RST (→ cancel + reclaim) or a mid-stream ``'x'`` cancel
        opcode; a stream with no progress past the request deadline (+
        grace) or ``stream_timeout_s`` sends a typed ``"stall"`` error
        frame.  Returns ``(alive, pending_op)``: ``alive`` is False when
        the connection is gone; ``pending_op`` is an opcode the client
        pipelined while the stream was relaying, for ``_handle`` to
        process after the final frame."""
        grace = max(1.0, 4 * self.poll_s)
        waited = 0.0
        pending = b""
        while True:
            # check the client side EVERY iteration (not just idle slices):
            # a mid-stream cancel or disconnect must land even while chunks
            # are flowing back-to-back.  Once the client pipelines its next
            # opcode ('q'/'r'), STOP reading — the following bytes are that
            # request's frame, owned by _handle after this stream's final
            # frame (a disconnect is still caught by the send path below).
            if not pending:
                status = self._poll_client(conn, recv_pool)
                if status == "dead":
                    if self.cancel_on_disconnect:
                        self.engine.cancel(h)
                    return False, b""
                if isinstance(status, bytes):
                    pending = status
            chunk, done = h.next_chunk(timeout=self.poll_s)
            if not done and not len(chunk):
                waited += self.poll_s
                now = time.perf_counter()
                stalled = (now > h.deadline + grace
                           if h.deadline is not None
                           else waited >= self.stream_timeout_s)
                if stalled:
                    # the engine should have retired this request by now —
                    # it is wedged or dead; unblock the client with a typed
                    # error frame instead of holding the handler thread
                    with self._hlock:
                        self._handles.pop(h.id, None)
                        self._owner.pop(h.id, None)
                    try:
                        networking.send_data(
                            conn, {"id": h.id, "ok": False, "done": True,
                                   "tokens": np.zeros(0, np.int32),
                                   "finish": "error", "kind": "stall",
                                   "error": f"no progress on request "
                                            f"{h.id} (engine stalled)"},
                            pool=send_pool)
                    except (ConnectionError, OSError):
                        return False, b""
                    return True, pending
                continue
            waited = 0.0
            reply: Dict[str, Any] = {"id": h.id, "tokens": chunk,
                                     "done": done}
            if done:
                reply["finish"] = h.finish
                if h.error is not None:
                    reply["ok"] = False
                    reply["kind"] = "engine_dead"
                    reply["error"] = str(h.error)
                else:
                    reply["row"] = h.result()
            try:
                networking.send_data(conn, reply, pool=send_pool)
            except (ConnectionError, OSError):
                if self.cancel_on_disconnect:
                    self.engine.cancel(h)
                return False, b""
            if done:
                with self._hlock:
                    self._handles.pop(h.id, None)
                    self._owner.pop(h.id, None)
                return True, pending

    def _poll_client(self, conn: socket.socket,
                     recv_pool: "networking.BufferPool"
                     ) -> Union[str, bytes]:
        """Non-blocking client-socket check between stream chunks:
        ``"idle"`` (nothing to read — the normal case), ``"dead"``
        (EOF/RST/garbage — the disconnect-reclamation trigger), ``"ok"``
        after consuming a mid-stream ``'x'`` cancel (any id; unacked —
        the stream's final frame is the acknowledgement), or the opcode
        byte itself when the client pipelined its next ``'q'``/``'r'``
        request while this stream is still relaying (stashed by
        ``_stream``, processed after the final frame — pipelining is not
        a protocol violation)."""
        try:
            readable, _, _ = select.select([conn], [], [], 0)
            if not readable:
                return "idle"
            op = conn.recv(1)
            if op == OP_CANCEL:
                # the cancel payload may trail the opcode across packets:
                # bound the recv so a torn/stalled cancel frame cannot pin
                # the stream relay (timeout → OSError → "dead")
                conn.settimeout(1.0)
                try:
                    msg = networking.recv_data(conn, pool=recv_pool)
                finally:
                    conn.settimeout(None)
                with self._hlock:
                    target = self._handles.get(int(msg["id"]))
                if target is not None:
                    self.engine.cancel(target)
                return "ok"
            if op in (OP_ENQUEUE, OP_STREAM, OP_KVBLOCKS):
                return op  # pipelined next request, not a dead client
        except (ConnectionError, OSError, ValueError):
            return "dead"
        # EOF (b"") or mid-stream protocol violation: the client is gone
        return "dead"

    # -- the event core ------------------------------------------------------
    # One selector I/O thread ("dkt-serving-io") multiplexes every client
    # connection: accept, parse, dispatch, stream-relay, and flush all run
    # as EventLoop callbacks, so 64 concurrent wire streams cost 64
    # registered fds instead of 64 handler threads.  Token frames reach
    # the loop through RequestHandle.set_listener → call_soon (the
    # socketpair waker), and every method below runs ON the loop thread —
    # _econns and _ServingConn state need no lock.  Semantics (typed
    # rejections, mid-stream 'x', pipelining, stall bounds, disconnect
    # reclamation, counters) mirror the threaded handler above, clause
    # for clause.

    def _ev_accept(self, mask: int) -> None:
        while True:
            try:
                sock, _ = self._server.accept()
            except (BlockingIOError, InterruptedError):
                return
            except OSError:
                return
            if not self._running:
                try:
                    sock.close()
                except OSError:
                    pass
                return
            try:
                sock.setblocking(False)
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            except OSError:
                try:
                    sock.close()
                except OSError:
                    pass
                continue
            conn = _ServingConn(sock)
            self._econns[sock] = conn
            with self._lock:
                self._conns.append(sock)
            self._loop.add(sock, lambda m, c=conn: self._ev_io(c, m))

    def _ev_io(self, conn: _ServingConn, mask: int) -> None:
        if conn.closed:
            return
        if mask & selectors.EVENT_WRITE:
            self._ev_flush(conn)
        if conn.closed or conn.paused:
            return
        if mask & selectors.EVENT_READ:
            self._ev_read(conn)

    def _ev_read(self, conn: _ServingConn) -> None:
        # drain ops already parsed first (a mid-batch backpressure pause
        # abandons the messages() walk; the resume path re-enters here
        # with no new bytes owed by the socket)
        if self._ev_drain_parsed(conn):
            return
        while not conn.closed and not conn.paused:
            # direct-fill continuation for a frame torn across recvs,
            # else land the bytes in the pooled scratch and decode
            # zero-copy views over it (the PS event core's read path)
            target = conn.parser.writable()
            fed_scratch = target is None
            if fed_scratch:
                target = memoryview(conn.recv_pool.get(_EV_RECV_CHUNK))
            try:
                n = conn.sock.recv_into(target)
            except (BlockingIOError, InterruptedError):
                return
            except (ConnectionError, OSError):
                self._ev_conn_lost(conn, fault=conn.parser.midframe)
                return
            if not n:
                # EOF: clean at a frame boundary (no counter — the
                # threaded recv_opcode contract), a torn frame otherwise
                self._ev_conn_lost(conn, fault=conn.parser.midframe)
                return
            if fed_scratch:
                conn.parser.feed(target[:n])
            else:
                conn.parser.advance(n)
            if self._ev_drain_parsed(conn):
                return  # dispatched >= 1 op: yield the loop (fairness);
                # the level-triggered selector re-arms for the rest

    def _ev_drain_parsed(self, conn: _ServingConn) -> bool:
        """Dispatch every op the parser has buffered.  Returns True when
        at least one op was dispatched or the connection died (the read
        loop yields), False when more bytes are needed."""
        got = False
        try:
            for op, msg in conn.parser.messages():
                got = True
                self._ev_dispatch(conn, op, msg)
                if conn.closed or conn.paused:
                    return True
        except ValueError:
            if conn.stream is not None:
                # mid-stream garbage/torn frame: the threaded core's
                # _poll_client "dead" verdict — cancel + shed, no counter
                self._ev_conn_lost(conn, fault=False)
            else:
                self.protocol_errors += 1  # corrupt frame: shed silently
                self._ev_close(conn)
            return True
        except Exception:
            logger.exception(
                "serving event dispatch failed; shedding the connection "
                "(threaded-core parity: its handler thread died with it)")
            self._ev_close(conn)
            return True
        return got

    def _ev_dispatch(self, conn: _ServingConn, op: Optional[bytes],
                     msg) -> None:
        if conn.stream is not None:
            # mid-stream: the threaded core's _poll_client contract
            if op == OP_CANCEL:
                with self._hlock:
                    target = self._handles.get(int(msg["id"]))
                if target is not None:
                    self.engine.cancel(target)
                return  # unacked: the stream's final frame acknowledges
            if op in (OP_ENQUEUE, OP_STREAM, OP_KVBLOCKS):
                # pipelined next request: deferred past the final frame,
                # deep-copied out of the recv scratch its views die with
                conn.deferred.append((op, _deepcopy_wire_msg(msg)))
                return
            self._ev_conn_lost(conn, fault=False)  # protocol violation
            return
        if msg is None:
            if op == OP_STATS:
                # load probe, answered inline on the loop (no request
                # body): the engine's lock-free snapshot, piggybacked on
                # whatever flush this wake already owes the connection
                self._ev_queue(conn, {"ok": True,
                                      "load": self.engine.load()})
            else:
                self._ev_close(conn)  # protocol violation: drop silently
            return
        if op in (OP_ENQUEUE, OP_KVBLOCKS):
            self._ev_submit(conn, op, msg)
        elif op == OP_STREAM:
            rid = int(msg["id"])
            with self._hlock:
                h = self._handles.get(rid)
                if h is not None:
                    self._owner[rid] = conn.sock  # stream claims it
            if h is None:
                self._ev_queue(conn, {"ok": False, "done": True,
                                      "kind": "unknown_id",
                                      "error": f"unknown id {rid}"})
                return
            self._ev_start_stream(conn, h)
        elif op == OP_CANCEL:
            with self._hlock:
                h = self._handles.get(int(msg["id"]))
            ok = h is not None and self.engine.cancel(h)
            self._ev_queue(conn, {"ok": True, "cancelled": bool(ok)})

    def _ev_submit(self, conn: _ServingConn, op: bytes, msg) -> None:
        """``'q'``/``'k'`` admission with the threaded core's exact typed
        rejection chain.  A ``ProtocolError`` (hostile KV payload)
        re-raises past the bad_request catch so the connection is shed
        and counted as a protocol error, with no engine call made."""
        try:
            if op == OP_KVBLOCKS:
                kvb = msg.get("blocks")
                if isinstance(kvb, _EvPoisoned):
                    raise networking.ProtocolError(kvb.error)
                if not isinstance(kvb, networking.KVBlocks):
                    raise networking.ProtocolError(
                        "kv-block frame carries no KVBlocks payload")
                kvb = kvb.validate().decoded()
                h = self.engine.submit_prefilled(
                    kvb, np.array(msg["prompt"], np.int32, copy=True),
                    int(msg["first_token"]), int(msg["num_steps"]),
                    temperature=float(msg.get("temperature", 0.0)),
                    top_k=msg.get("top_k"), top_p=msg.get("top_p"),
                    eos_id=msg.get("eos_id"), pad_id=msg.get("pad_id"),
                    deadline_s=msg.get("deadline_s"),
                    tenant=msg.get("tenant"),
                    priority=int(msg.get("priority", 0)), block=False)
            else:
                h = self.engine.submit(
                    np.array(msg["prompt"], np.int32, copy=True),
                    int(msg["num_steps"]),
                    temperature=float(msg.get("temperature", 0.0)),
                    top_k=msg.get("top_k"), top_p=msg.get("top_p"),
                    eos_id=msg.get("eos_id"), pad_id=msg.get("pad_id"),
                    seed=int(msg.get("seed", 0)),
                    deadline_s=msg.get("deadline_s"),
                    tenant=msg.get("tenant"),
                    priority=int(msg.get("priority", 0)), block=False)
        except QuotaExceeded as e:
            self._ev_queue(conn, {"ok": False, "error": str(e),
                                  "kind": "quota"})
            return
        except QueueFull:
            self._ev_queue(conn, {"ok": False, "error": "queue full",
                                  "kind": "backpressure"})
            return
        except Draining as e:
            self._ev_queue(conn, {"ok": False, "error": str(e),
                                  "kind": "draining"})
            return
        except EngineDead as e:
            self._ev_queue(conn, {"ok": False, "error": str(e),
                                  "kind": "engine_dead"})
            return
        except networking.ProtocolError:
            raise  # transport-boundary rejection: shed, don't reply
        except ValueError as e:
            self._ev_queue(conn, {"ok": False, "error": str(e),
                                  "kind": "bad_request"})
            return
        with self._hlock:
            self._handles[h.id] = h
            self._owner[h.id] = conn.sock
        self._ev_queue(conn, {"ok": True, "id": h.id})

    # -- event-core stream relay --------------------------------------------
    def _ev_start_stream(self, conn: _ServingConn,
                         h: RequestHandle) -> None:
        conn.stream = h
        conn.last_progress = time.perf_counter()
        loop = self._loop

        def poke(c=conn, hh=h):
            loop.call_soon(lambda: self._ev_pump(c, hh))

        h.set_listener(poke)  # fires once now if progress predates it
        self._ev_schedule_stall(conn, h)
        self._ev_pump(conn, h)

    def _ev_pump(self, conn: _ServingConn, h: RequestHandle) -> None:
        """Relay every token chunk ``h`` has ready onto ``conn``'s write
        queue — the event twin of ``_stream``'s relay body.  Invoked via
        the handle's listener on every engine push (duplicate wakes are
        cheap no-ops) and from the backpressure resume path."""
        if conn.closed or conn.stream is not h or conn.paused:
            return
        while True:
            chunk, done = h.next_chunk(timeout=0)
            if not done and not len(chunk):
                return
            conn.last_progress = time.perf_counter()
            reply: Dict[str, Any] = {"id": h.id, "tokens": chunk,
                                     "done": done}
            if done:
                reply["finish"] = h.finish
                if h.error is not None:
                    reply["ok"] = False
                    reply["kind"] = "engine_dead"
                    reply["error"] = str(h.error)
                else:
                    reply["row"] = h.result()
            self._ev_queue(conn, reply)
            if conn.closed:
                return  # the flush tore the connection down mid-relay
            if done:
                self._ev_end_stream(conn, h)
                return
            if conn.paused:
                return  # backpressure: the flush path resumes the pump

    def _ev_end_stream(self, conn: _ServingConn,
                       h: RequestHandle) -> None:
        with self._hlock:
            self._handles.pop(h.id, None)
            self._owner.pop(h.id, None)
        h.set_listener(None)
        conn.stream = None
        self._ev_drain_deferred(conn)

    def _ev_drain_deferred(self, conn: _ServingConn) -> None:
        """Dispatch ops the client pipelined during a stream (the
        threaded core's ``pending_op``, processed after the final
        frame).  A deferred ``'r'`` re-enters streaming; anything still
        queued behind it stays deferred, in order, for that stream's
        end."""
        while (conn.deferred and not conn.closed and not conn.paused
                and conn.stream is None):
            op, msg = conn.deferred.pop(0)
            try:
                self._ev_dispatch(conn, op, msg)
            except ValueError:
                if conn.stream is not None:
                    self._ev_conn_lost(conn, fault=False)
                else:
                    self.protocol_errors += 1
                    self._ev_close(conn)
                return
            except Exception:
                logger.exception("serving event dispatch failed; "
                                 "shedding the connection")
                self._ev_close(conn)
                return

    def _ev_schedule_stall(self, conn: _ServingConn,
                           h: RequestHandle) -> None:
        grace = max(1.0, 4 * self.poll_s)
        now = time.perf_counter()
        if h.deadline is not None:
            delay = h.deadline + grace - now
        else:
            delay = conn.last_progress + self.stream_timeout_s - now
        self._loop.call_later(max(self.poll_s, delay),
                              lambda: self._ev_check_stall(conn, h))

    def _ev_check_stall(self, conn: _ServingConn,
                        h: RequestHandle) -> None:
        """Stall watchdog: a stream with no progress past the request
        deadline (+ grace) or ``stream_timeout_s`` gets the typed
        ``"stall"`` error frame instead of pinning the relay — the
        threaded core's bounded-wait contract, on a timer instead of a
        poll loop.  Stale timers (stream already retired) no-op."""
        if conn.closed or conn.stream is not h:
            return
        grace = max(1.0, 4 * self.poll_s)
        now = time.perf_counter()
        if h.deadline is not None:
            # one empty poll slice of silence required, like the threaded
            # loop which only diagnoses a stall from an empty slice
            stalled = (now > h.deadline + grace
                       and now - conn.last_progress >= self.poll_s)
        else:
            stalled = now - conn.last_progress >= self.stream_timeout_s
        if not stalled:
            self._ev_schedule_stall(conn, h)
            return
        with self._hlock:
            self._handles.pop(h.id, None)
            self._owner.pop(h.id, None)
        self._ev_queue(conn, {"id": h.id, "ok": False, "done": True,
                              "tokens": np.zeros(0, np.int32),
                              "finish": "error", "kind": "stall",
                              "error": f"no progress on request {h.id} "
                                       f"(engine stalled)"})
        if conn.closed:
            return
        h.set_listener(None)
        conn.stream = None
        self._ev_drain_deferred(conn)

    # -- event-core write path ----------------------------------------------
    def _ev_queue(self, conn: _ServingConn, obj) -> None:
        if conn.closed:
            return
        if conn.out:
            # the pooled buffer still backs an in-flight frame: encode
            # into fresh bytes (the PS _queue_reply discipline)
            data = memoryview(networking.encode_message(obj))
        else:
            data = memoryview(networking.encode_message_into(
                obj, conn.send_pool))
        conn.out.append(data)
        conn.out_bytes += len(data)
        self._ev_flush(conn)

    def _ev_flush(self, conn: _ServingConn) -> None:
        if conn.closed:
            return
        was_paused = conn.paused
        while conn.out:
            try:
                if len(conn.out) > 1:
                    # write batching: every frame owed to this connection
                    # in ONE syscall — token chunks queued by successive
                    # pumps coalesce per loop wake
                    sent = conn.sock.sendmsg(conn.out[:_EV_SENDMSG_BATCH])
                else:
                    sent = conn.sock.send(conn.out[0])
            except (BlockingIOError, InterruptedError):
                break
            except (ConnectionError, OSError):
                self._ev_conn_lost(conn, fault=True)
                return
            conn.out_bytes -= sent
            while conn.out and sent >= len(conn.out[0]):
                sent -= len(conn.out[0])
                conn.out.pop(0)
            if sent:
                conn.out[0] = conn.out[0][sent:]
                break  # partial write: the kernel buffer is full
        self._ev_update_mask(conn)
        if was_paused and not conn.paused and not conn.closed:
            self._loop.call_soon(lambda: self._ev_resume(conn))

    def _ev_update_mask(self, conn: _ServingConn) -> None:
        if conn.closed:
            return
        if conn.paused:
            if conn.out_bytes <= self.max_conn_buffer // 2:
                conn.paused = False  # drained: resume reads + pump
        elif conn.out_bytes > self.max_conn_buffer:
            conn.paused = True  # never-reading client: stop reading too
        want = bool(conn.out)
        conn.want_write = want
        mask = ((0 if conn.paused else selectors.EVENT_READ)
                | (selectors.EVENT_WRITE if want else 0))
        if not mask:  # unreachable (paused implies pending writes), but
            mask = selectors.EVENT_READ  # a 0 mask would be an error
        self._loop.set_mask(conn.sock, mask)

    def _ev_resume(self, conn: _ServingConn) -> None:
        """Backpressure release: re-pump the stream (tokens queued while
        paused sit in the handle — bounded by its ``num_steps``), then
        re-drain parsed/deferred ops before going back to the socket."""
        if conn.closed or conn.paused:
            return
        if conn.stream is not None:
            self._ev_pump(conn, conn.stream)
        if conn.closed or conn.paused:
            return
        if conn.stream is None:
            self._ev_drain_deferred(conn)
        if not conn.closed and not conn.paused:
            self._ev_read(conn)

    # -- event-core teardown -------------------------------------------------
    def _ev_conn_lost(self, conn: _ServingConn, fault: bool) -> None:
        """Transport-level death.  Counting mirrors the threaded core:
        mid-stream death is ``_poll_client``'s "dead" verdict (cancel the
        streamed request, no counter); outside a stream a torn frame or
        send fault counts ``disconnects``; a clean EOF counts nothing."""
        if conn.closed:
            return
        h = conn.stream
        if h is not None:
            if self.cancel_on_disconnect:
                self.engine.cancel(h)
        elif fault:
            self.disconnects += 1
        self._ev_close(conn)

    def _ev_close(self, conn: _ServingConn) -> None:
        if conn.closed:
            return
        conn.closed = True
        h = conn.stream
        conn.stream = None
        if h is not None:
            h.set_listener(None)
        if self._loop is not None:
            self._loop.remove(conn.sock)
        self._econns.pop(conn.sock, None)
        try:
            conn.sock.close()
        except OSError:
            pass
        with self._lock:
            if conn.sock in self._conns:
                self._conns.remove(conn.sock)
        del conn.out[:]
        conn.out_bytes = 0
        del conn.deferred[:]
        self._release_owned(conn.sock)

    def _ev_shutdown(self) -> None:
        """Loop-exit hook (runs ON the loop thread, before the selector
        and waker close): flush pending writes bounded-best-effort, close
        every registered connection, reclaim their owned requests, close
        the listener.  ``stop(join_timeout)`` drains through here — zero
        leaked fds (tests/test_serving_event.py)."""
        conns = list(self._econns.values())
        self._econns.clear()
        with self._lock:
            self._conns.clear()
        for conn in conns:
            if conn.out:
                try:
                    conn.sock.setblocking(True)
                    conn.sock.settimeout(0.5)
                    for buf in conn.out:
                        conn.sock.sendall(buf)
                except (ConnectionError, OSError, socket.timeout):
                    pass
            h = conn.stream
            conn.stream = None
            if h is not None:
                h.set_listener(None)
            conn.closed = True
            try:
                conn.sock.close()
            except OSError:
                pass
            self._release_owned(conn.sock)
        if self._server is not None:
            try:
                self._server.close()
            except OSError:
                pass


def _raise_typed(kind: Optional[str], err: str):
    """Map a typed error reply back to the exception the engine raised."""
    if kind == "quota":  # before backpressure: QuotaExceeded IS a QueueFull
        raise QuotaExceeded(err)
    if kind == "backpressure" or "queue full" in err:
        raise QueueFull(err)
    if kind == "draining":
        raise Draining(err)
    if kind in ("engine_dead", "stall"):
        raise EngineDead(err)
    raise ValueError(err)


class ServingClient:
    """Minimal client for :class:`ServingServer` — one socket, the shared
    frame codec, pooled receives.  ``generate`` is the one-call form whose
    returned row matches offline ``generate`` for the same request; with a
    ``retry_policy`` (``resilience.RetryPolicy``) it re-dials and
    resubmits across engine deaths and connection resets — requests are
    deterministic in their seed, so the retry is idempotent."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, int(port)
        self.sock = networking.connect(self.host, self.port)
        self._pool = networking.BufferPool()
        self._send_pool = networking.BufferPool()

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass

    def _redial(self) -> None:
        self.close()
        self.sock = networking.connect(self.host, self.port)

    def __enter__(self) -> "ServingClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def submit(self, prompt, num_steps: int, **kw) -> int:
        """Enqueue a request; returns the server-assigned id.  Raises the
        typed rejection: :class:`QueueFull` (backpressure),
        :class:`Draining`, :class:`EngineDead`, or ``ValueError``."""
        req = {"prompt": np.asarray(prompt, np.int32),
               "num_steps": int(num_steps), **kw}
        networking.send_opcode(self.sock, OP_ENQUEUE)
        networking.send_data(self.sock, req, pool=self._send_pool)
        ack = networking.recv_data(self.sock, pool=self._pool)
        if not ack.get("ok"):
            _raise_typed(ack.get("kind"), str(ack.get("error", "rejected")))
        return int(ack["id"])

    def submit_prefilled(self, blocks, prompt, first_token: int,
                         num_steps: int, **kw) -> int:
        """Ship a prefilled request's KV blocks to a decode-role server
        (``SERVING_OP_KVBLOCKS``) — the wire half of the disaggregated
        hand-off.  ``blocks`` is a :class:`networking.KVBlocks`; the block
        payloads ride the frame codec's zero-copy buffer path.  Returns
        the server-assigned id; raises the same typed rejections as
        :meth:`submit`."""
        req = {"blocks": blocks,
               "prompt": np.asarray(prompt, np.int32),
               "first_token": int(first_token),
               "num_steps": int(num_steps), **kw}
        networking.send_opcode(self.sock, OP_KVBLOCKS)
        networking.send_data(self.sock, req, pool=self._send_pool)
        ack = networking.recv_data(self.sock, pool=self._pool)
        if not ack.get("ok"):
            _raise_typed(ack.get("kind"), str(ack.get("error", "rejected")))
        return int(ack["id"])

    def cancel(self, rid: int, await_ack: bool = True) -> bool:
        """Cancel request ``rid``.  With ``await_ack=False`` the cancel is
        fire-and-forget — the form to use from another thread while THIS
        socket is mid-stream (the ack would interleave with chunk frames;
        the stream's final ``finish="cancel"`` frame is the
        acknowledgement there)."""
        networking.send_opcode(self.sock, OP_CANCEL)
        networking.send_data(self.sock, {"id": int(rid)},
                             pool=self._send_pool)
        if not await_ack:
            return True
        ack = networking.recv_data(self.sock, pool=self._pool)
        return bool(ack.get("cancelled"))

    def load(self) -> Dict[str, Any]:
        """Probe the server's engine load (``SERVING_OP_STATS``): the
        lock-free :meth:`ServingEngine.load` snapshot — queue depth, free
        slots, trie-cached block count, draining/dead flags.  Cheap enough
        for a router to poll per dispatch."""
        networking.send_opcode(self.sock, OP_STATS)
        reply = networking.recv_data(self.sock, pool=self._pool)
        if not reply.get("ok"):
            _raise_typed(reply.get("kind"),
                         str(reply.get("error", "stats probe rejected")))
        return dict(reply["load"])

    def stream(self, rid: int):
        """Yield ``(tokens, done_reply)`` chunk by chunk; ``done_reply`` is
        None until the final frame (which carries ``finish`` —
        eos/length/deadline/cancel — and the padded ``row``).  Typed error
        frames raise: :class:`EngineDead` for ``engine_dead``/``stall``,
        ``ValueError`` otherwise."""
        networking.send_opcode(self.sock, OP_STREAM)
        networking.send_data(self.sock, {"id": int(rid)},
                             pool=self._send_pool)
        while True:
            reply = networking.recv_data(self.sock, pool=self._pool)
            if reply.get("error"):
                _raise_typed(reply.get("kind"), str(reply["error"]))
            tokens = np.array(reply["tokens"], np.int32, copy=True)
            if reply["done"]:
                yield tokens, {"finish": reply["finish"],
                               "row": np.array(reply["row"], np.int32,
                                               copy=True)}
                return
            yield tokens, None

    def generate(self, prompt, num_steps: int, retry_policy=None,
                 **kw) -> np.ndarray:
        """Submit + stream to completion; returns the full padded row
        (prompt + tokens), exactly ``generate``-shaped.  ``retry_policy``
        (a ``resilience.RetryPolicy``) retries the whole submit+stream on
        :class:`EngineDead` or a transport fault, re-dialing first — the
        client-side half of the supervised-restart story."""
        def attempt() -> np.ndarray:
            rid = self.submit(prompt, num_steps, **kw)
            for _, done in self.stream(rid):
                if done is not None:
                    return done["row"]
            raise ConnectionError("stream ended without a done frame")

        if retry_policy is None:
            return attempt()
        return retry_policy.call_reconnecting(
            attempt, self._redial,
            retry_on=(EngineDead, ConnectionError, OSError))


# ---------------------------------------------------------------------------
# disaggregated prefill/decode (PR 16)
# ---------------------------------------------------------------------------

class _DisaggRequest:
    """One in-flight request's routing record inside a :class:`DisaggPair`:
    the client-facing proxy handle, the current upstream handle it mirrors
    (prefill first, decode after the hand-off), and a cancel relay that
    always points at whichever engine owns the upstream right now."""

    __slots__ = ("proxy", "upstream", "cancel_fn", "cancelled", "thread",
                 "kw", "attempts")

    def __init__(self, proxy: RequestHandle, kw: Optional[Dict[str, Any]]
                 = None):
        self.proxy = proxy
        self.upstream: Optional[RequestHandle] = None
        self.cancel_fn = None
        self.cancelled = False
        self.thread: Optional[threading.Thread] = None
        self.kw: Dict[str, Any] = dict(kw or {})
        self.attempts = 1  # prefill admissions so far (re-route budget)


class DisaggPair:
    """Disaggregated serving: N ``role="prefill"`` engines feeding ONE
    ``role="decode"`` engine, behind the unified engine's client surface
    (``submit`` → :class:`RequestHandle` → ``next_chunk``/``result``).

    Admissions route to a prefill engine (round-robin); when its half
    retires (``finish="prefilled"``), the request's filled KV blocks ship
    to the decode engine — in-process via ``submit_prefilled`` when
    ``decode`` is an engine, or over the serving wire
    (``SERVING_OP_KVBLOCKS`` through :class:`ServingClient`) when
    ``decode_addr`` names a remote decode-role :class:`ServingServer`.
    The client-visible stream is unchanged: tokens relay into the proxy
    handle as the decode engine emits them, and greedy output is
    token-identical to a unified engine (the decode engine resumes from
    bit-exact shipped KV at the shipped position with the same RNG key).

    Failure matrix (docs/serving.md):

     - **prefill death** mid-prefill or mid-transfer re-routes: the
       request resubmits to the next live prefill engine with its
       ORIGINAL rng key (deterministic, so the retry is idempotent),
       bounded by one attempt per engine; blocks the dead engine held are
       reclaimed by its own death path, and the decode pool never saw the
       torn transfer (``kv_blocks_in_use == 0`` on both sides).
     - **decode death** is terminal: the proxy fails with the typed
       :class:`EngineDead` (no silent re-route — the decode engine owns
       all live KV state, exactly the supervised-restart seam
       ``resilience.PairSupervisor`` covers).
     - **cancel/deadline** land on whichever engine currently owns the
       request; the proxy mirrors the upstream finish reason.
    """

    def __init__(self, prefills, decode: Optional[ServingEngine] = None,
                 decode_addr: Optional[Tuple[str, int]] = None,
                 poll_s: float = 0.02):
        if isinstance(prefills, ServingEngine):
            prefills = [prefills]
        if not prefills:
            raise ValueError("DisaggPair needs at least one prefill engine")
        for e in prefills:
            if e.role != "prefill":
                raise ValueError(f"prefill engines must be role='prefill', "
                                 f"got role={e.role!r}")
        if (decode is None) == (decode_addr is None):
            raise ValueError("pass exactly one of decode= (in-process "
                             "engine) or decode_addr= (remote server)")
        if decode is not None and decode.role != "decode":
            raise ValueError(f"decode engine must be role='decode', got "
                             f"role={decode.role!r}")
        self._prefills: List[ServingEngine] = list(prefills)
        self._decode = decode
        self._decode_addr = decode_addr
        self.poll_s = float(poll_s)
        self._lock = threading.Lock()
        self._live: Dict[int, _DisaggRequest] = {}
        self._next_id = 0
        self._rr = 0  # round-robin cursor over prefill engines
        #: shared event relay (PR 19): ONE loop watches every in-flight
        #: request across both halves — prefill completion, the KV
        #: hand-off, and the decode token relay — instead of a routing
        #: thread per request.  Lazily started on first submit.
        self._relay_loop: Optional[networking.EventLoop] = None
        # the pair's OWN terminal accounting: engine counters double-count
        # a re-routed request (every attempt is a submission somewhere), so
        # client-facing totals live here
        self.counters: Dict[str, int] = {
            "requests_submitted": 0, "requests_completed": 0,
            "requests_failed": 0, "requests_rejected": 0,
            "requests_cancelled": 0, "requests_expired": 0,
            "prefill_reroutes": 0,
        }

    # ------------------------------------------------------------ lifecycle
    def warmup(self) -> "DisaggPair":
        """Compile every engine's role-specific programs (prefill buckets
        + gather on the prefill side, decode step + ingest on the decode
        side) before traffic — the pair-level twin of
        ``ServingEngine.warmup``."""
        for e in self.engines:
            e.warmup()
        return self

    def start(self) -> "DisaggPair":
        for e in self.engines:  # prefill engines first, then decode
            e.start()
        return self

    def stop(self, join_timeout: float = 10.0) -> None:
        for e in self.engines:
            e.stop(join_timeout=join_timeout)
        self._ev_wait_idle(join_timeout)
        with self._lock:
            loop, self._relay_loop = self._relay_loop, None
        if loop is not None:
            loop.stop(join_timeout=join_timeout)

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful drain, prefill side first (no new hand-offs) then the
        decode engine; the event relay pumps the final laps out so every
        proxy reaches a terminal state before this returns."""
        with self._lock:
            pres, dec = list(self._prefills), self._decode
        clean = all([e.drain(timeout=timeout) for e in pres])
        if dec is not None:
            clean = dec.drain(timeout=timeout) and clean
        self._ev_wait_idle(5.0)
        return clean

    def __enter__(self) -> "DisaggPair":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------ admission
    def submit(self, prompt, num_steps: int, **kw) -> RequestHandle:
        """Unified-engine ``submit`` surface.  Returns a proxy handle whose
        stream spans both halves: TTFT is the prefill engine's first
        token, every later token is the decode engine's."""
        prompt = np.asarray(prompt, np.int32)
        ph, eng = self._submit_prefill(prompt, num_steps, kw, first=True)
        if num_steps == 0:
            # the prefill engine completed it in place ("empty"): nothing
            # to hand off, and the engine's own counters saw it — mirror
            # into the pair's
            with self._lock:
                self.counters["requests_submitted"] += 1
                self.counters["requests_completed"] += 1
            return ph
        with self._lock:
            self._next_id += 1
            proxy = RequestHandle(
                self._next_id, prompt, num_steps,
                float(kw.get("temperature", 0.0)), kw.get("top_k"),
                kw.get("top_p"), kw.get("eos_id"), kw.get("pad_id"),
                ph.key, deadline_s=kw.get("deadline_s"))
            rec = _DisaggRequest(proxy, kw)
            rec.upstream = ph
            rec.cancel_fn = (lambda e=eng, h=ph: e.cancel(h))
            self._live[proxy.id] = rec
            self.counters["requests_submitted"] += 1
        self._ev_watch_prefill(rec, ph)
        return proxy

    def _submit_prefill(self, prompt, num_steps, kw, first: bool,
                        rng=None):
        """Round-robin submit over LIVE prefill engines; on the first
        admission typed backpressure propagates to the caller after every
        engine refused, on a re-route the caller handles it."""
        last: Optional[BaseException] = None
        with self._lock:
            attempts_budget = len(self._prefills)
        for _ in range(attempts_budget):
            with self._lock:
                eng = self._prefills[self._rr % len(self._prefills)]
                self._rr += 1
            try:
                sub = dict(kw)
                # pair admission is non-blocking by construction: a full
                # prefill queue tries the next engine instead of parking
                sub.pop("block", None)
                sub.pop("timeout", None)
                if rng is not None:
                    sub.pop("seed", None)
                    sub["rng"] = rng
                return eng.submit(prompt, num_steps, block=False,
                                  **sub), eng
            except (EngineDead, QueueFull, Draining) as e:
                last = e
        if first:
            with self._lock:
                self.counters["requests_rejected"] += 1
        raise last if last is not None else EngineDead(
            "no live prefill engine")

    # -------------------------------------------------------------- routing
    #
    # The whole request lifecycle rides the pair's shared event loop
    # (PR 19): the prefill handle's listener wakes the loop when its half
    # retires, the KV hand-off runs as a loop callback (non-blocking
    # decode admission, with a ``call_later`` retry while the decode
    # queue is full), and the decode half relays listener-driven — no
    # per-request routing thread anywhere on the path.

    def _ev_loop(self) -> "networking.EventLoop":
        with self._lock:
            loop = self._relay_loop
            if loop is None or not loop.alive:
                loop = networking.EventLoop(name="dkt-disagg-relay")
                loop.start()
                self._relay_loop = loop
            return loop

    def _ev_watch_prefill(self, rec: _DisaggRequest,
                          ph: RequestHandle) -> None:
        loop = self._ev_loop()
        ph.set_listener(lambda: loop.call_soon(
            lambda: self._ev_prefill_done(rec, ph)))
        loop.call_soon(lambda: self._ev_prefill_done(rec, ph))

    def _ev_prefill_done(self, rec: _DisaggRequest,
                         ph: RequestHandle) -> None:
        """Loop-side prefill watcher: when the prefill half retires, hand
        off (``finish="prefilled"``), re-route a death with the ORIGINAL
        key (bit-identical retry, bounded by one attempt per engine), or
        mirror a cancel/deadline/drain finish."""
        proxy = rec.proxy
        if rec.upstream is not ph or not ph.done:
            return  # stale wake, or woken by a token push mid-prefill
        ph.set_listener(None)
        rec.upstream = None  # claim the transition exactly once
        if ph.finish == "prefilled":
            kvb = ph.kvblocks
            first_token = int(ph.tokens[0])
            with self._lock:
                dec = self._decode  # in-flight hand-offs keep their engine
            if dec is not None:
                self._ev_handoff_local(rec, kvb, first_token, dec)
            else:
                self._ev_handoff_wire(rec, kvb, first_token)
            return
        if ph.error is not None:
            # prefill engine died with the request in flight: re-route
            # with the ORIGINAL key so the retry is bit-identical
            with self._lock:
                budget = len(self._prefills) + 1
            if rec.attempts >= budget:
                self._retire(rec, error=EngineDead(
                    f"request {proxy.id}: every prefill re-route "
                    f"failed ({ph.error})"))
                return
            with self._lock:
                self.counters["prefill_reroutes"] += 1
                cancelled = rec.cancelled
            if cancelled:
                self._retire(rec, finish="cancel")
                return
            try:
                nph, eng = self._submit_prefill(
                    proxy.prompt, proxy.num_steps, rec.kw, first=False,
                    rng=proxy.key)
            except (EngineDead, QueueFull, Draining) as e:
                self._retire(rec, error=e)
                return
            with self._lock:
                rec.upstream = nph
                rec.cancel_fn = (lambda e=eng, h=nph: e.cancel(h))
                if rec.cancelled:
                    rec.cancel_fn()
            rec.attempts += 1
            self._ev_watch_prefill(rec, nph)
            return
        # cancel / deadline / drain on the prefill half: mirror it
        self._retire(rec, finish=ph.finish)

    def _ev_handoff_local(self, rec: _DisaggRequest, kvb,
                          first_token: int, dec: ServingEngine) -> None:
        """In-process hand-off on the loop: non-blocking decode admission,
        re-armed via ``call_later`` while the decode queue is full (the
        event-core analogue of the old thread's ``block=True`` park)."""
        proxy = rec.proxy
        if rec.cancelled:
            self._retire(rec, finish="cancel")
            return
        try:
            dh = dec.submit_prefilled(
                kvb, proxy.prompt, first_token, proxy.num_steps,
                temperature=proxy.temperature, top_k=proxy.top_k,
                top_p=proxy.top_p, eos_id=proxy.eos_id,
                pad_id=proxy.pad_id, deadline_s=rec.kw.get("deadline_s"),
                block=False)
        except QueueFull:
            self._relay_loop.call_later(
                self.poll_s, lambda: self._ev_handoff_local(
                    rec, kvb, first_token, dec))
            return
        except (EngineDead, Draining) as e:
            # decode death is terminal (typed), never silently re-routed:
            # the decode engine owns all live KV state
            self._retire(rec, error=e)
            return
        except ValueError as e:
            self._retire(rec, error=e)
            return
        with self._lock:
            rec.upstream = dh
            rec.cancel_fn = (lambda e=dec, h=dh: e.cancel(h))
            if rec.cancelled:
                rec.cancel_fn()
        loop = self._relay_loop
        dh.set_listener(lambda: loop.call_soon(
            lambda: self._ev_pump_decode(rec, dh)))
        self._ev_pump_decode(rec, dh)

    def _ev_pump_decode(self, rec: _DisaggRequest,
                        dh: RequestHandle) -> None:
        """Loop-side decode relay: drain ready chunks into the proxy."""
        if rec.upstream is not dh:
            return  # stale wake
        proxy = rec.proxy
        while True:
            chunk, done = dh.next_chunk(timeout=0)
            for t in chunk:
                proxy._push(int(t))
            if done:
                dh.set_listener(None)
                rec.upstream = None
                if dh.error is not None:
                    self._retire(rec, error=dh.error)
                else:
                    self._retire(rec, finish=dh.finish)
                return
            if not len(chunk):
                return  # drained; the listener wakes us on more

    def _ev_handoff_wire(self, rec: _DisaggRequest, kvb,
                         first_token: int) -> None:
        """Wire hand-off on the loop: ship the block set to the remote
        decode server (``SERVING_OP_KVBLOCKS``), then relay its reply
        stream non-blocking off a bare-frame parser."""
        proxy = rec.proxy
        client = ServingClient(*self._decode_addr)
        try:
            rid = client.submit_prefilled(
                kvb, proxy.prompt, first_token, proxy.num_steps,
                temperature=proxy.temperature, top_k=proxy.top_k,
                top_p=proxy.top_p, eos_id=proxy.eos_id,
                pad_id=proxy.pad_id, deadline_s=rec.kw.get("deadline_s"))
            networking.send_opcode(client.sock, OP_STREAM)
            networking.send_data(client.sock, {"id": int(rid)},
                                 pool=client._send_pool)
            client.sock.setblocking(False)
        except (EngineDead, ConnectionError, OSError) as e:
            client.close()
            self._retire(rec, error=e if isinstance(e, EngineDead)
                         else EngineDead(f"decode engine unreachable: "
                                         f"{e!r}"))
            return
        except ValueError as e:
            client.close()
            self._retire(rec, error=e)
            return
        with self._lock:
            rec.cancel_fn = (lambda c=client, r=rid:
                             c.cancel(r, await_ack=False))
            if rec.cancelled:
                try:
                    rec.cancel_fn()
                except (ConnectionError, OSError):
                    pass
        parser = networking.FrameParser(frame_ops=None)
        scratch = networking.BufferPool()
        loop = self._relay_loop
        if loop is None:
            client.close()
            return
        loop.add(client.sock,
                 lambda mask: self._ev_wire_read(rec, client, parser,
                                                 scratch))

    def _ev_wire_read(self, rec: _DisaggRequest, client, parser,
                      scratch) -> None:
        sock = client.sock
        while True:
            target = parser.writable()
            fed_scratch = target is None
            if fed_scratch:
                target = memoryview(scratch.get(_EV_RECV_CHUNK))
            try:
                n = sock.recv_into(target)
            except (BlockingIOError, InterruptedError):
                return
            except (ConnectionError, OSError) as e:
                self._ev_wire_lost(rec, client, e)
                return
            if not n:
                self._ev_wire_lost(rec, client,
                                   ConnectionError("stream ended without "
                                                   "a done frame"))
                return
            if fed_scratch:
                parser.feed(target[:n])
            else:
                parser.advance(n)
            try:
                for _op, msg in parser.messages():
                    if self._ev_wire_frame(rec, client, msg):
                        return  # stream finished / typed failure
            except ValueError as e:
                self._ev_wire_lost(rec, client, e)
                return

    def _ev_wire_frame(self, rec: _DisaggRequest, client, msg) -> bool:
        """One decode-server reply frame.  Returns True when the stream
        detached (done or failed) — decode death is terminal, typed."""
        if msg.get("error"):
            kind = msg.get("kind")
            err = str(msg["error"])
            self._ev_wire_detach(rec, client)
            if kind in ("engine_dead", "stall"):
                self._retire(rec, error=EngineDead(err))
            else:
                self._retire(rec, error=ValueError(err))
            return True
        for t in msg["tokens"]:
            rec.proxy._push(int(t))
        if msg["done"]:
            self._ev_wire_detach(rec, client)
            self._retire(rec, finish=msg["finish"])
            return True
        return False

    def _ev_wire_detach(self, rec: _DisaggRequest, client) -> None:
        loop = self._relay_loop
        if loop is not None:
            loop.remove(client.sock)
        client.close()

    def _ev_wire_lost(self, rec: _DisaggRequest, client,
                      err: BaseException) -> None:
        self._ev_wire_detach(rec, client)
        self._retire(rec, error=err if isinstance(err, EngineDead)
                     else EngineDead(f"decode engine unreachable: "
                                     f"{err!r}"))

    def _ev_wait_idle(self, timeout: float) -> None:
        """Bounded wait for the loop to retire the in-flight requests —
        stopping/draining the engines makes their handles terminal, and
        the loop pumps those final laps out asynchronously."""
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                busy = bool(self._live)
            if not busy or time.monotonic() >= deadline:
                return
            time.sleep(0.005)

    def _retire(self, rec: _DisaggRequest, finish: Optional[str] = None,
                error: Optional[BaseException] = None) -> None:
        """Make the proxy terminal exactly once and book the pair-level
        counter for its reason."""
        proxy = rec.proxy
        if error is not None:
            exc = (error if isinstance(error, EngineDead)
                   else EngineDead(str(error)))
            counted = proxy._fail(exc)
            key = "requests_failed"
        else:
            counted = proxy._finish(finish)
            key = {"cancel": "requests_cancelled",
                   "deadline": "requests_expired"}.get(
                       finish, "requests_completed")
        with self._lock:
            if counted:
                self.counters[key] += 1
            self._live.pop(proxy.id, None)

    # ------------------------------------------------------------- controls
    def cancel(self, handle: RequestHandle) -> bool:
        """Cancel a proxy handle wherever its request currently lives
        (queued/prefilling, mid-transfer, or decoding).  Returns False if
        it already finished."""
        with handle._cond:
            if handle.finish is not None:
                return False
        with self._lock:
            rec = self._live.get(handle.id)
            if rec is None or rec.proxy is not handle:
                return False
            rec.cancelled = True
            fn = rec.cancel_fn
        if fn is not None:
            try:
                fn()
            except (ConnectionError, OSError):
                pass  # upstream gone: its death path retires the proxy
        return True

    def replace_engine(self, old: ServingEngine,
                       new: ServingEngine) -> None:
        """Swap a respawned engine into the pair (the
        ``resilience.PairSupervisor`` restart seam).  In-flight requests
        on the old engine fail through its death path and re-route."""
        with self._lock:
            for i, e in enumerate(self._prefills):
                if e is old:
                    self._prefills[i] = new
                    return
            if self._decode is old:
                self._decode = new
                return
        raise ValueError("engine to replace is not part of this pair")

    # ------------------------------------------------------------ telemetry
    @property
    def engines(self) -> List[ServingEngine]:
        with self._lock:
            return self._prefills + ([self._decode]
                                     if self._decode is not None else [])

    @property
    def stats(self) -> Dict[str, Any]:
        """Merged engine stats (numeric counters summed, sample lists
        concatenated) with the request-level terminal counters OVERRIDDEN
        by the pair's own: a re-routed request is one client request, not
        one per attempt."""
        merged: Dict[str, Any] = {}
        for e in self.engines:
            for k, v in e.stats.items():
                if isinstance(v, bool) or not isinstance(
                        v, (int, float, list)):
                    merged.setdefault(k, v)
                elif isinstance(v, list):
                    merged.setdefault(k, [])
                    merged[k] = merged[k] + list(v)
                else:
                    merged[k] = merged.get(k, 0) + v
        with self._lock:
            merged.update(self.counters)
        return merged

    @property
    def kv_blocks_in_use(self) -> Optional[int]:
        """Sum across BOTH sides — the zero-leak assertion surface."""
        vals = [e.kv_blocks_in_use for e in self.engines]
        vals = [v for v in vals if v is not None]
        return sum(vals) if vals else None

    @property
    def slot_occupancy(self) -> Optional[float]:
        """The DECODE engine's occupancy (None for wire-mode pairs): the
        continuous-batching health metric disaggregation exists to
        protect."""
        with self._lock:
            dec = self._decode
        return dec.slot_occupancy if dec is not None else None

    @property
    def max_len(self) -> int:
        return min(e.max_len for e in self.engines)

    @property
    def queue_depth(self) -> int:
        return sum(e.queue_depth for e in self.engines)

    @property
    def dead(self) -> Optional[BaseException]:
        """The first dead engine's error, or None while every engine in
        the pair is live."""
        for e in self.engines:
            if e.dead is not None:
                return e.dead
        return None
