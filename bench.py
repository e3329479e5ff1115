"""North-star benchmark: ADAG on the MNIST ConvNet (BASELINE.json).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "examples/sec/chip",
   "vs_baseline": N, "mfu": N, "platform": "...", "device_kind": "...",
   "device_count": N, "jax": "...", "data": "real"|"synthetic",
   "flops_per_example": N}

``vs_baseline`` is the multiple over the measured reference-proxy CPU
throughput in ``BASELINE_MEASURED.json`` (the reference publishes no numbers
— see BASELINE.md; scripts/measure_cpu_baseline.py measures the proxy).
North-star target: >= 8x.  ``mfu`` = achieved trained-FLOP/s (analytic
matmul/conv FLOPs x 3 for backward) / bf16 peak of the detected chip.

This is a measurement path: it runs on a TPU or not at all.  With no TPU
visible it exits 3 and prints no result — a CPU number is never written
under a per-chip metric's name.  A sub-benchmark that raises still lets
the line print (its fields null) but makes the exit code 1.

Steady-state timing: the initial state is placed with its steady-state
shardings so ONE warmup epoch compiles the one program every later call
reuses; then full epochs are timed for ~3 s, capped by a hard wall-clock
budget (DISTKERAS_BENCH_BUDGET, default 540 s).
DISTKERAS_BENCH_DEBUG=1 streams stage timings to stderr.
"""

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))


def _host_ps_fixture():
    """Shared small workload for the PS-path microbenchmarks: a 4-class
    blob dataset and a 2-layer MLP (same shapes as tests/test_host_ps.py)."""
    import numpy as np

    from distkeras_tpu import Dataset
    from distkeras_tpu.core.layers import Dense
    from distkeras_tpu.core.model import Sequential

    rng = np.random.default_rng(0)
    n, d, classes = 4096, 16, 4
    protos = rng.uniform(-1, 1, (classes, d))
    labels = rng.integers(0, classes, n)
    x = (protos[labels] + 0.3 * rng.standard_normal((n, d))).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[labels]
    ds = Dataset({"features": x, "label": y})
    model = Sequential([Dense(32, activation="relu"),
                        Dense(classes, activation="softmax")],
                       input_shape=(d,), compute_dtype="float32")
    return ds, model, n


def host_ps_microbench(budget_s: float = 90.0):
    """PS-path microbenchmark: a small ADAG run over the live socket PS on
    loopback, measuring the transport pipelining win as data, not assertion.

    Returns ``{"host_ps_examples_per_sec": float,
    "host_ps_rtts_per_window": float}`` — RTTs/window is transport messages
    initiated per communication window, excluding each worker's initial
    pull: 2.0 on the serial 'c'+'p' path, 1.0 with ``comm_overlap`` (the
    combined 'u' opcode, reply hidden behind the next window's compute).
    Returns None values if the run exceeds sanity bounds or fails — the
    north-star artifact must exist either way.
    """
    from distkeras_tpu import ADAG

    ds, model, n = _host_ps_fixture()
    # num_workers=1 + parallelism_factor=2 → two true-async worker threads
    # against the PS without needing a multi-device mesh (the bench process
    # may see a single CPU device)
    t = ADAG(model, num_workers=1, parallelism_factor=2, batch_size=32,
             num_epoch=2, communication_window=4, learning_rate=0.05,
             execution="host_ps")
    t0 = time.perf_counter()
    t.train(ds)
    dt = time.perf_counter() - t0
    if dt > budget_s:
        return {"host_ps_examples_per_sec": None,
                "host_ps_rtts_per_window": None}
    workers = getattr(t, "_ps_workers", [])
    windows = sum(w._commits for w in workers)
    ops = sum(w.transport_ops for w in workers)
    rtts_per_window = ((ops - len(workers)) / windows) if windows else None
    return {
        "host_ps_examples_per_sec": round(n * t.num_epoch / dt, 1),
        "host_ps_rtts_per_window": (round(rtts_per_window, 3)
                                    if rtts_per_window is not None else None),
    }


def host_ps_shard_bench(budget_s: float = 120.0):
    """Shard-scaling observable: the same small ADAG host-PS run at
    ``ps_shards=1`` vs ``ps_shards=4`` (docs/host_ps.md).  At this
    loopback/toy scale the numbers mostly prove the sharded path carries
    full training throughput — the PS-CPU/NIC relief shows up at DCN scale;
    per-shard RTT accounting is asserted by tests/test_ps_sharding.py.

    Returns ``{"host_ps_shard_scaling": {"shards1_examples_per_sec": ...,
    "shards4_examples_per_sec": ...}}`` (Nones on overrun/failure — never
    fatal to the north-star artifact).
    """
    from distkeras_tpu import ADAG

    ds, model, n = _host_ps_fixture()
    out = {}
    t_start = time.perf_counter()
    # warmup: compile the shared window program once so neither measured run
    # pays the jit cost (the N=1 run would otherwise eat it and inflate the
    # apparent shard speedup)
    ADAG(model, num_workers=1, parallelism_factor=2, batch_size=32,
         num_epoch=1, communication_window=4, learning_rate=0.05,
         execution="host_ps").train(ds)
    for shards in (1, 4):
        t = ADAG(model, num_workers=1, parallelism_factor=2, batch_size=32,
                 num_epoch=2, communication_window=4, learning_rate=0.05,
                 execution="host_ps", ps_shards=shards)
        t0 = time.perf_counter()
        t.train(ds)
        dt = time.perf_counter() - t0
        over = time.perf_counter() - t_start > budget_s
        out[f"shards{shards}_examples_per_sec"] = (
            None if over else round(n * t.num_epoch / dt, 1))
    return {"host_ps_shard_scaling": out}


def host_ps_worker_scaling_bench(budget_s: float = 240.0):
    """Worker-count scaling curve: examples/sec through the PS fabric vs
    N workers (N ∈ {1, 2, 4, 8, 16}) at fixed total batch, for BOTH PS
    server cores:

      - ``threaded``: the seed thread-per-connection core (one handler
        thread per worker, one apply-lock acquisition + one O(n) center
        snapshot + one reply encode per 'u' commit);
      - ``event``: the selector event loop with commit coalescing (one
        I/O thread; commits arriving while an apply runs merge into ONE
        drain = one lock acquisition + ONE shared encoded reply).

    Each worker speaks the real wire protocol (combined 'u' commit+pull,
    pooled send/receive buffers — exactly ``PSWorker``'s transport) and
    commits windows of ``batch_size`` examples; the total example count
    is fixed, N only splits it.  No device compute runs, so the curve
    isolates the server fabric — the property the classic PS scaling
    results hinge on (Dean et al. 2012; Li et al. 2014) and the PR-7
    before/after observable for ROADMAP item 2: thread-per-connection
    flattens from GIL churn and per-commit snapshot+encode copies; the
    event core must stay flat-or-better at every N and pull ahead under
    concurrency.  ``coalesce`` reports the event core's drain counters at
    each N — the acceptance check that drains really merge ≥ 2 commits
    under load.  Each point is best-of-3 (thread-scheduling noise).
    Returns Nones on overrun — never fatal to the north-star artifact.
    """
    import threading

    import numpy as np

    from distkeras_tpu import networking, parameter_servers

    n_params = 300_000  # ~1.2 MB dense f32 commit — a small-MLP center
    batch_size = 32
    total_commits = 256  # fixed total batch: 8192 examples per point
    rng = np.random.default_rng(0)
    blob = {"model": None,
            "weights": [rng.standard_normal(n_params).astype(np.float32)]}
    delta = [rng.standard_normal(n_params).astype(np.float32) * 1e-3]
    t_start = time.perf_counter()

    def run(core, n):
        ps = parameter_servers.ADAGParameterServer(blob, num_workers=n)
        srv = parameter_servers.make_socket_server(ps, ps_core=core)
        srv.start()
        iters = total_commits // n
        failures = []

        def worker():
            try:
                sock = networking.connect("127.0.0.1", srv.port)
                pool = networking.BufferPool()
                spool = networking.BufferPool()
                for _ in range(iters):
                    sock.sendall(b"u")
                    networking.send_data(
                        sock, {"delta": delta, "worker": 0,
                               "gen": srv.generation}, pool=spool)
                    networking.recv_data(sock, pool=pool)
                sock.sendall(b"q")
                sock.close()
            except Exception as e:  # surfaced below, never hangs the bench
                failures.append(e)

        threads = [threading.Thread(target=worker) for _ in range(n)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        stats = getattr(srv, "coalesce_stats", None)
        srv.stop()
        if failures:
            raise failures[0]
        return n * iters * batch_size / wall, stats

    out = {"examples_per_sec": {"event": {}, "threaded": {}},
           "coalesce": {}}
    for n in (1, 2, 4, 8, 16):
        if time.perf_counter() - t_start > budget_s:
            out["examples_per_sec"]["threaded"][str(n)] = None
            out["examples_per_sec"]["event"][str(n)] = None
            continue
        # best-of-5 with the cores INTERLEAVED inside each repeat, so a
        # background-load burst penalizes both curves, not whichever core
        # happened to be running (scheduler noise at low N is larger than
        # the gap under test)
        best = {"threaded": 0.0, "event": 0.0}
        stats = None
        for _ in range(5):
            for core in ("threaded", "event"):
                eps, st = run(core, n)
                if eps > best[core]:
                    best[core] = eps
                    if core == "event":
                        stats = st
        for core in ("threaded", "event"):
            out["examples_per_sec"][core][str(n)] = round(best[core], 1)
        if stats is not None:
            out["coalesce"][str(n)] = {
                "mean_drain": stats.get("mean_drain"),
                "max_drain": stats.get("max_drain"),
                "coalesced_drains": stats.get("coalesced_drains")}
    return {"host_ps_worker_scaling": out}


def host_ps_wire_bytes_bench():
    """Encoded commit bytes per window for each wire mode — the observable
    for the delta-compression stack (docs/host_ps.md).  A representative
    MNIST-MLP-scale delta (784→128→10, ~101k params) is pushed through the
    exact encoders each mode uses (dense f32, bf16 cast, int8 codes +
    per-tensor scales, sparse top-k at the default density 0.01) and the
    full frame length counted.  Pure CPU, deterministic, sub-second.

    Returns ``{"host_ps_wire_bytes_per_window": {mode: bytes},
    "host_ps_commit_compression_ratio": {mode: dense/mode}}``.
    """
    import numpy as np

    import ml_dtypes
    from distkeras_tpu import networking
    from distkeras_tpu.workers import topk_select

    rng = np.random.default_rng(0)
    shapes = [(784, 128), (128,), (128, 10), (10,)]
    delta = [rng.standard_normal(s).astype(np.float32) * 0.01
             for s in shapes]
    base = {"worker_id": 0, "clock": 0}

    def nbytes(msg):
        return len(networking.encode_message(msg))

    out = {"dense": nbytes({"delta": delta, **base})}
    out["bfloat16"] = nbytes(
        {"delta": [d.astype(ml_dtypes.bfloat16) for d in delta], **base})
    scales = [float(np.max(np.abs(d)) / 127.0) or 1.0 for d in delta]
    codes = [np.clip(np.rint(d / s), -127, 127).astype(np.int8)
             for d, s in zip(delta, scales)]
    out["int8"] = nbytes({"delta": codes, "scales": scales, **base})
    flat = np.concatenate([d.reshape(-1) for d in delta])
    k = max(1, int(np.ceil(0.01 * flat.size)))
    idx, wire, _, scale, _ = topk_select(flat, k, None)
    out["topk"] = nbytes(
        {"delta": networking.SparseDelta(idx, wire, flat.size, scale),
         **base})
    ratios = {m: round(out["dense"] / b, 2)
              for m, b in out.items() if m != "dense"}
    return {"host_ps_wire_bytes_per_window": out,
            "host_ps_commit_compression_ratio": ratios}


def host_ps_embedding_commit_bytes_bench():
    """Encoded commit bytes for an embedding-heavy window under the dense
    wire vs the EXACT row-sparse profile (``row_sparse=`` —
    ``networking.RowSparseDelta``; docs/host_ps.md "Streaming + row-sparse
    embeddings").  A recommender-scale delta — a (20000, 32) embedding
    table of which one window touched 1% of rows, plus a small dense head
    — is pushed through the exact encoder the workers use and the full
    frame length counted.  Pure CPU, deterministic, sub-second.

    Returns ``{"host_ps_embedding_commit_bytes_per_window":
    {"dense": bytes, "row_sparse": bytes, "touched_rows": k,
    "table_rows": V, "compression_ratio": dense/row_sparse}}``.
    """
    import numpy as np

    from distkeras_tpu import networking

    rng = np.random.default_rng(0)
    vocab, dim = 20000, 32
    touched = np.sort(rng.choice(vocab, size=vocab // 100,
                                 replace=False)).astype(np.int32)
    table_delta = np.zeros((vocab, dim), np.float32)
    table_delta[touched] = 0.01 * rng.standard_normal(
        (len(touched), dim)).astype(np.float32)
    head = [0.01 * rng.standard_normal((dim, 4)).astype(np.float32),
            0.01 * rng.standard_normal((4,)).astype(np.float32)]
    base = {"worker_id": 0, "clock": 0}
    dense = len(networking.encode_message(
        {"delta": [table_delta] + head, **base}))
    sparse = len(networking.encode_message(
        {"delta": [networking.RowSparseDelta(
            touched, table_delta[touched], vocab)] + head, **base}))
    return {"host_ps_embedding_commit_bytes_per_window": {
        "dense": dense, "row_sparse": sparse,
        "touched_rows": int(len(touched)), "table_rows": vocab,
        "compression_ratio": round(dense / sparse, 2)}}


def host_ps_stream_bench(budget_s: float = 90.0):
    """Streaming-ingestion throughput: a small online DOWNPOUR run over a
    generator-backed ``StreamSource`` (deterministic seeds) — rows
    ingested and trained per second through the horizon-leased PS fabric
    with row-sparse embedding commits.  Returns
    ``{"host_ps_stream_examples_per_sec": float|None}`` — None on
    overrun/failure, never fatal to the north-star artifact.
    """
    import numpy as np

    from distkeras_tpu import DOWNPOUR, Sequential
    from distkeras_tpu.core.layers import Dense, Embedding, Flatten
    from distkeras_tpu.streaming import StreamSource

    vocab, dim, classes = 2048, 16, 4
    rng = np.random.default_rng(0)
    mapping = rng.integers(0, classes, vocab)

    def gen():
        for _ in range(32):
            items = rng.integers(0, vocab, 256).astype(
                np.int32).reshape(-1, 1)
            yield items, np.eye(classes, dtype=np.float32)[
                mapping[items[:, 0]]]

    model = Sequential([Embedding(vocab, dim), Flatten(),
                        Dense(classes, activation="softmax")],
                       input_shape=(1,), compute_dtype="float32")
    t = DOWNPOUR(model, num_workers=1, parallelism_factor=2, batch_size=32,
                 num_epoch=1, communication_window=4, learning_rate=0.5,
                 execution="host_ps", stream=True, row_sparse=True)
    t0 = time.perf_counter()
    t.train(StreamSource(generator=gen()))
    if time.perf_counter() - t0 > budget_s:
        return {"host_ps_stream_examples_per_sec": None}
    return {"host_ps_stream_examples_per_sec":
            t.stream_stats.get("examples_per_sec")}


def online_deployment_bench(budget_s: float = 120.0):
    """The train-while-serve loop (deployment_online.py): a drifting
    token-mapping stream trains under DOWNPOUR while an inline engine
    hot-reloads from the live PS and answers probe traffic each horizon,
    with served feedback riding the stream.  The observables are the
    freshness percentiles (stream entry → commit → served pull, row-
    weighted) and the FINAL served accuracy against the drifted world —
    accuracy-tracks-drift on the served path.  Returns
    ``{"freshness_p50_s", "freshness_p99_s", "online_served_accuracy"}``
    — None on overrun/failure, never fatal to the north-star artifact.
    """
    import numpy as np

    import jax

    from distkeras_tpu import DOWNPOUR, OnlineDeployment
    from distkeras_tpu.models import transformer_lm
    from distkeras_tpu.serving import ServingEngine
    from distkeras_tpu.streaming import StreamSource

    vocab, seq = 16, 8
    rng = np.random.default_rng(0)
    mapping = rng.permutation(vocab).astype(np.int32)
    drifted = mapping.copy()
    flip = rng.permutation(vocab)[: vocab // 2]
    drifted[flip] = np.roll(mapping[flip], 1)

    def gen():
        for i in range(6):
            m = drifted if i >= 3 else mapping
            x = rng.integers(0, vocab, (128, seq)).astype(np.int32)
            yield x, m[x]

    def make_model():
        return transformer_lm(vocab_size=vocab, seq_len=seq + 2,
                              d_model=32, num_heads=4, num_layers=1,
                              mlp_dim=64, compute_dtype="float32")

    trainer = DOWNPOUR(
        make_model(), num_workers=2, batch_size=16, num_epoch=1,
        communication_window=2, execution="host_ps",
        loss="sparse_categorical_crossentropy_from_logits",
        worker_optimizer="adam", learning_rate=3e-3, stream=True,
        horizon_windows=4, seed=0, max_horizons=12)
    serve_model = make_model()
    params = serve_model.init(jax.random.PRNGKey(1), (seq + 2,))
    engine = ServingEngine((serve_model, params), num_slots=4, max_len=4)
    dep = OnlineDeployment(trainer, StreamSource(generator=gen()),
                           engine, reload_every=1)
    probe = np.arange(vocab, dtype=np.int32).reshape(-1, 1)
    acc = {"last": None}

    def on_horizon(h, fitted):
        rows, _ = dep.serve(list(probe), num_steps=1)
        pred = np.array([r[1] for r in rows])
        acc["last"] = float(np.mean(pred == drifted[probe[:, 0]]))
        if h < 8:
            fx = np.repeat(probe, seq, axis=1)
            dep.feed(fx, (drifted if h >= 3 else mapping)[fx])

    trainer.on_horizon = on_horizon
    t0 = time.perf_counter()
    dep.start()
    dep.join(timeout=max(budget_s, 30.0))
    dep.stop()
    s = dep.stats()
    if time.perf_counter() - t0 > budget_s:
        return {"freshness_p50_s": None, "freshness_p99_s": None,
                "online_served_accuracy": None}
    return {"freshness_p50_s": s["freshness_p50_s"],
            "freshness_p99_s": s["freshness_p99_s"],
            "online_served_accuracy": acc["last"]}


def host_ps_recovery_bench(budget_s: float = 60.0):
    """Client-observed shard recovery latency: a 2-shard group under a
    ``ShardSupervisor``; one shard is crash-killed and the measured number
    is kill → the next successful client pull through reconnect-resume
    (supervisor detection + respawn-from-snapshot + worker re-dial).
    Returns ``{"host_ps_recovery_ms": float|None}`` — None on
    overrun/failure, never fatal to the north-star artifact.
    """
    import numpy as np

    from distkeras_tpu.ps_sharding import ShardedPSClient, ShardedServerGroup
    from distkeras_tpu.resilience import RetryPolicy, ShardSupervisor

    blob = {"model": "{}",
            "weights": [np.zeros((4096,), np.float32),
                        np.zeros((512,), np.float32)]}
    group = ShardedServerGroup("downpour", blob, num_workers=1, num_shards=2)
    group.start()
    sup = ShardSupervisor(group, "downpour", 1, heartbeat_interval=0.05,
                          liveness_deadline=0.25, snapshot_interval=0.05)
    sup.start()
    client = ShardedPSClient(
        group.plan, group.addrs, recovery=True,
        policy=RetryPolicy(attempts=None, backoff=0.01, max_backoff=0.1,
                           deadline=min(budget_s, 20.0), seed=0))
    t0 = time.perf_counter()
    try:
        client.connect()
        client.update({"delta": [np.ones_like(w) for w in blob["weights"]],
                       "worker_id": 0, "clock": 0})
        time.sleep(0.2)  # let a post-commit snapshot land
        t0 = time.perf_counter()
        sup.kill_shard(0)
        client.pull()  # blocks through detection + respawn + re-dial
        ms = round((time.perf_counter() - t0) * 1e3, 1)
    finally:
        client.abort()
        sup.stop()
        group.stop()
    return {"host_ps_recovery_ms": ms}


def host_ps_worker_recovery_bench(budget_s: float = 90.0):
    """Elastic-worker recovery latency (resilience.WorkerSupervisor): a
    small elastic ADAG run where one worker dies ('exit' fault) mid-epoch;
    the measured number is the supervisor's death-detection → replacement
    respawn latency (``respawn_records[0]["recovery_ms"]``) — the worker
    twin of ``host_ps_recovery_ms``.  Returns
    ``{"host_ps_worker_recovery_ms": float|None}`` — None on
    overrun/failure, never fatal to the north-star artifact.
    """
    from distkeras_tpu import ADAG

    ds, model, n = _host_ps_fixture()
    t = ADAG(model, num_workers=1, parallelism_factor=2, batch_size=32,
             num_epoch=1, communication_window=4, learning_rate=0.05,
             execution="host_ps", elastic=True, lease_timeout=2.0,
             fault_injection={0: ("exit", 2)})
    t0 = time.perf_counter()
    t.train(ds)
    if time.perf_counter() - t0 > budget_s:
        return {"host_ps_worker_recovery_ms": None}
    recs = t.elastic_stats.get("respawn_records") or []
    ms = next((r["recovery_ms"] for r in recs
               if r.get("recovery_ms") is not None), None)
    return {"host_ps_worker_recovery_ms": ms}


def host_ps_straggler_bench(budget_s: float = 120.0):
    """Straggler-mitigation overhead: the same small elastic ADAG run with
    no faults vs with one worker wedged mid-epoch ('hang' fault — its
    leases are stolen by the survivor).  Reported as the chaos/clean
    wall-clock ratio: how much one hung worker costs an epoch when lease
    stealing is doing its job (bounded by roughly one lease deadline plus
    the stolen leases' retraining, instead of a full hang).  Returns
    ``{"host_ps_straggler_overhead": float|None}``.
    """
    from distkeras_tpu import ADAG

    ds, model, n = _host_ps_fixture()
    times = {}
    t_start = time.perf_counter()
    for label, faults in (("clean", None), ("chaos", {0: ("hang", 2)})):
        t = ADAG(model, num_workers=1, parallelism_factor=2, batch_size=32,
                 num_epoch=1, communication_window=4, learning_rate=0.05,
                 execution="host_ps", elastic=True, lease_timeout=1.0,
                 fault_injection=faults)
        t0 = time.perf_counter()
        t.train(ds)
        times[label] = time.perf_counter() - t0
        if time.perf_counter() - t_start > budget_s:
            return {"host_ps_straggler_overhead": None}
    return {"host_ps_straggler_overhead":
            round(times["chaos"] / max(times["clean"], 1e-9), 2)}


def serving_bench(budget_s: float = 90.0):
    """Continuous-batching serving observables (distkeras_tpu/serving.py):
    the fixed seeded request trace from ``examples/loadgen.py`` in a
    closed loop (8 users, 4 slots) against the slot-pooled engine, vs the
    SAME trace through sequential per-request ``generate`` — the
    pre-engine serving story.  Fields: ``serving_tokens_per_sec`` (engine),
    ``serving_p50_ms``/``serving_p99_ms`` (submit→done, queueing included),
    ``serving_slot_occupancy`` (mean busy-slot fraction per decode step),
    and ``serving_sequential_tokens_per_sec`` for the comparison the
    engine must win at ≥ 4 concurrent requests.  The failure-semantics
    observables ride the same harness: ``serving_shed_rate`` (fraction of
    an overload flood shed at admission — bounded buffering),
    ``serving_slot_reclaim_ms`` (mean cancel/expiry → slot-free latency
    under the seeded ~10% client-kill chaos schedule), and
    ``serving_deadline_miss_rate`` (fraction retired ``"deadline"`` under
    a tight per-request deadline).

    Prefill fast-path observables: ``serving_ttft_p50_ms``/
    ``serving_ttft_p99_ms`` (time to first token under the main closed
    loop) and ``serving_prefill_tokens_per_sec`` (prompt tokens through
    the compiled prefill path), plus a LONG-PROMPT leg running one trace
    whose prompts exceed ``prefill_chunk`` through both prefill modes:
    ``serving_longprompt_ttft_p99_ms`` (bucketed + chunked, the fast
    path) vs ``serving_longprompt_ttft_eager_p99_ms`` (the eager
    reference) — the chunked-prefill TTFT win, recorded alongside
    throughput.

    Speculation + quantization observables (PR 11):
    ``serving_spec_tokens_per_sec`` (the same trace through a self-draft
    speculative engine — one jitted draft+verify round per iteration)
    with ``serving_spec_accept_rate`` (accepted/drafted), and
    ``serving_quant_capacity_slots`` — the byte-accounted slot count an
    int8 KV pool sustains inside the full-precision pool's HBM budget
    (>= 1.5× ``num_slots`` is the acceptance bar).

    Disaggregation observables (PR 16): a bimodal long-prompt +
    decode-heavy trace through a unified paged engine vs a ``DisaggPair``
    (prefill-role engine shipping KV blocks to a decode-role engine):
    ``serving_unified_decode_p99_ms`` vs ``serving_disagg_decode_p99_ms``
    (per-token decode latency p99 of the decode-heavy requests — the
    interference disaggregation eliminates) and
    ``serving_kv_transfer_bytes`` (byte-accounted shipped blocks).

    Multi-tenant QoS observables (PR 18): an open-loop overload burst
    over a mixed-tenant trace —
    ``serving_interactive_p99_ms_under_overload`` (the interactive
    tier's latency while weighted-fair admission + batch preemption
    shield it), ``serving_batch_completion_rate`` (the tier absorbing
    the queueing), and ``serving_preempt_resume_ms`` (mean swap-in
    cost — the TUNING.md swap-vs-recompute crossover input).

    Paged KV + prefix sharing observables (PR 12): one shared-prefix
    trace (8 users over a single 128-token prefix, steady state — the
    prefix is warmed once first) through the paged pool AND the PR 9
    bucketed path: ``serving_prefix_ttft_p99_ms`` (paged) vs
    ``serving_prefix_ttft_dense_p99_ms`` (the ≥5× acceptance
    comparison), ``serving_prefix_hit_rate`` (fraction of demanded
    prompt tokens served from the radix index — byte-accounted block
    reuse, not just speed), and ``serving_paged_capacity_slots`` — how
    many concurrent shared-prefix requests the paged pool's on-demand
    allocation sustains inside the dense pool's byte budget (shared
    blocks counted once + marginal private blocks per request).
    Returns Nones on overrun/failure — never fatal to the north-star
    artifact.
    """
    sys.path.insert(0, os.path.join(_REPO, "examples"))
    import loadgen

    none = {"serving_tokens_per_sec": None, "serving_p50_ms": None,
            "serving_p99_ms": None, "serving_slot_occupancy": None,
            "serving_sequential_tokens_per_sec": None,
            "serving_shed_rate": None, "serving_slot_reclaim_ms": None,
            "serving_deadline_miss_rate": None,
            "serving_ttft_p50_ms": None, "serving_ttft_p99_ms": None,
            "serving_prefill_tokens_per_sec": None,
            "serving_longprompt_ttft_p99_ms": None,
            "serving_longprompt_ttft_eager_p99_ms": None,
            "serving_spec_tokens_per_sec": None,
            "serving_spec_accept_rate": None,
            "serving_quant_capacity_slots": None,
            "serving_prefix_ttft_p99_ms": None,
            "serving_prefix_ttft_dense_p99_ms": None,
            "serving_prefix_hit_rate": None,
            "serving_prefix_prefill_tokens_per_sec": None,
            "serving_prefix_prefill_dense_tokens_per_sec": None,
            "serving_paged_capacity_slots": None,
            "serving_unified_decode_p99_ms": None,
            "serving_disagg_decode_p99_ms": None,
            "serving_kv_transfer_bytes": None,
            "serving_interactive_p99_ms_under_overload": None,
            "serving_batch_completion_rate": None,
            "serving_preempt_resume_ms": None}
    if budget_s < 5.0:  # not enough budget to even warm the engine up
        return none
    t0 = time.perf_counter()
    fitted, engine = loadgen.build_engine(num_slots=4)
    trace = loadgen.make_trace(24, num_steps=16, temperature=0.7)
    try:
        closed = loadgen.run_closed_loop(engine, trace, concurrency=8,
                                         timeout_s=budget_s)
    finally:
        engine.stop()
    if time.perf_counter() - t0 > budget_s:
        return none
    seq = loadgen.sequential_baseline(fitted, trace, max_len=engine.max_len)
    out = dict(none)
    out.update({
        "serving_tokens_per_sec": closed["tokens_per_sec"],
        "serving_p50_ms": closed["p50_ms"],
        "serving_p99_ms": closed["p99_ms"],
        "serving_slot_occupancy": closed["slot_occupancy"],
        "serving_sequential_tokens_per_sec": seq["tokens_per_sec"],
        "serving_ttft_p50_ms": closed["ttft_p50_ms"],
        "serving_ttft_p99_ms": closed["ttft_p99_ms"],
        "serving_prefill_tokens_per_sec": closed["prefill_tokens_per_sec"],
    })
    # quantized-capacity accounting (pure byte math, no run): slots an
    # int8 KV pool sustains inside the f32/bf16 pool's byte budget
    _, fp_eng = loadgen.build_engine(num_slots=4)
    _, q8_eng = loadgen.build_engine(num_slots=4, kv_dtype="int8")
    out["serving_quant_capacity_slots"] = int(
        fp_eng.kv_pool_bytes // (q8_eng.kv_pool_bytes // q8_eng.num_slots))
    fp_eng.stop()
    q8_eng.stop()
    if time.perf_counter() - t0 > budget_s * 0.35:
        return out
    # paged prefix-sharing leg (PR 12): 8 users over ONE 128-token shared
    # prefix (each request adds a short private suffix), the prefix warmed
    # once — steady-state multi-tenant serving — then the SAME trace
    # through the paged pool and the PR 9 bucketed path.  TTFT p99 and
    # effective prefill-tokens/sec (demanded = prefilled + trie-served)
    # are the ≥5× acceptance comparison; prefix_hit_rate byte-accounts
    # the reuse
    px_trace = loadgen.make_trace(16, num_steps=1, prompt_lengths=(4, 6, 8),
                                  prefix_groups=1, prefix_len=240)
    for paged, tf, pf in (
            (True, "serving_prefix_ttft_p99_ms",
             "serving_prefix_prefill_tokens_per_sec"),
            (False, "serving_prefix_ttft_dense_p99_ms",
             "serving_prefix_prefill_dense_tokens_per_sec")):
        _, px_eng = loadgen.build_engine(num_slots=8, max_len=256,
                                        paged=paged, block_size=16,
                                        prefill_chunk=16,
                                        prefills_per_step=4)
        try:
            px_eng.warmup()
            px_eng.submit(px_trace[0]["prompt"], 1)
            px_eng.run_until_idle()      # warm the shared prefix once
            px = loadgen.run_closed_loop(px_eng, px_trace, concurrency=8,
                                         timeout_s=budget_s)
            out[tf] = px["ttft_p99_ms"]
            eff = px["prefill_tokens_per_sec"] or 0.0
            if px["wall_s"]:
                eff += px["prefix_hit_tokens"] / px["wall_s"]
            out[pf] = round(eff, 1)
            if paged:
                out["serving_prefix_hit_rate"] = px["prefix_hit_rate"]
                # capacity: blocks the dense pool's byte budget buys,
                # minus the shared prefix chain (counted ONCE), divided
                # by the worst-case PRIVATE blocks one trace request
                # needs — concurrent shared-prefix requests at fixed HBM
                blk_bytes = px_eng.kv_pool_bytes // (px_eng.kv_blocks + 1)
                _, dn_eng = loadgen.build_engine(num_slots=8, max_len=256)
                budget_blocks = dn_eng.kv_pool_bytes // blk_bytes
                dn_eng.stop()
                bs = px_eng.block_size
                shared = 240 // bs
                marg = max(
                    -(-(len(r["prompt"]) + r["num_steps"]) // bs) - shared
                    for r in px_trace)
                out["serving_paged_capacity_slots"] = int(
                    (budget_blocks - shared) // max(marg, 1))
        finally:
            px_eng.stop()
        if time.perf_counter() - t0 > budget_s * 0.5:
            return out
    if time.perf_counter() - t0 > budget_s * 0.45:
        return out
    # speculative leg: a TRAINED (2-layer target, 1-layer draft) pair on
    # the x+1 task serving an in-distribution greedy trace — accept rate
    # ~0.8, the way production prompts are in-distribution for a real
    # draft (speculation's win is a property of the traffic).  Each
    # engine iteration is ONE jitted draft+verify round committing
    # 1..spec_len+1 tokens per row
    _, _, spec_eng = loadgen.build_spec_engine(num_slots=4, spec_len=3)
    spec_trace = loadgen.make_trace(24, num_steps=16, pattern="arith")
    try:
        spec_eng.warmup()
        spec = loadgen.run_closed_loop(spec_eng, spec_trace, concurrency=8,
                                       timeout_s=budget_s)
        out["serving_spec_tokens_per_sec"] = spec["tokens_per_sec"]
        out["serving_spec_accept_rate"] = spec["spec_accept_rate"]
    finally:
        spec_eng.stop()
    if time.perf_counter() - t0 > budget_s * 0.55:
        return out
    # long-prompt TTFT leg: prompts past prefill_chunk, same trace through
    # the bucketed+chunked fast path and the eager reference — admissions
    # must no longer stall the running batch for a whole prompt
    lp_trace = loadgen.make_trace(12, num_steps=6, temperature=0.7,
                                  prompt_lengths=(20, 28, 40))
    for mode, field in (("bucketed", "serving_longprompt_ttft_p99_ms"),
                        ("eager", "serving_longprompt_ttft_eager_p99_ms")):
        _, lp_engine = loadgen.build_engine(
            num_slots=4, max_len=64, prefill_mode=mode, prefill_chunk=8,
            prefills_per_step=2)
        try:
            lp = loadgen.run_closed_loop(lp_engine, lp_trace,
                                         concurrency=8, timeout_s=budget_s)
            out[field] = lp["ttft_p99_ms"]
        finally:
            lp_engine.stop()
        if time.perf_counter() - t0 > budget_s * 0.7:
            return out
    if time.perf_counter() - t0 > budget_s * 0.7:
        return out
    # chaos leg: ~10% seeded client kills + a deadline tight enough that
    # queue-delayed requests miss it — the reclamation observables
    _, engine = loadgen.build_engine(num_slots=2, queue_capacity=16)
    trace = loadgen.make_trace(16, num_steps=12, temperature=0.7)
    try:
        chaos = loadgen.run_closed_loop(engine, trace, concurrency=8,
                                        timeout_s=budget_s, chaos_kill=0.1,
                                        chaos_seed=0, deadline_s=2.0)
    finally:
        engine.stop()
    out["serving_slot_reclaim_ms"] = chaos["slot_reclaim_ms"]
    out["serving_deadline_miss_rate"] = chaos["deadline_miss_rate"]
    if time.perf_counter() - t0 > budget_s * 0.85:
        return out
    # overload leg: flood a tiny bounded queue — shed-not-collapse rate
    _, engine = loadgen.build_engine(num_slots=2, queue_capacity=4)
    trace = loadgen.make_trace(32, num_steps=4)
    try:
        flood = loadgen.run_open_loop(engine, trace, qps=1e6,
                                      timeout_s=budget_s)
    finally:
        engine.stop()
    out["serving_shed_rate"] = flood["shed_rate"]
    if time.perf_counter() - t0 > budget_s * 0.9:
        return out
    # disaggregation leg (PR 16): the DistServe/Splitwise interference
    # scenario — a bimodal trace (long-prompt prefill-heavy bursts mixed
    # into short-prompt decode-heavy requests) through a unified paged
    # engine and through a DisaggPair with the same knobs.  The
    # observable is per-token DECODE latency p99 of the decode-heavy
    # requests only ((latency - ttft) / (tokens - 1): prefill and
    # queueing excluded by construction) — on the unified engine the
    # long prefills stall the token loop; the pair's decode engine never
    # runs a prefill.  serving_kv_transfer_bytes byte-accounts the
    # shipped blocks (the transfer-discipline counter family)
    dg_trace = loadgen.make_trace(16, num_steps=12, seed=3,
                                  prompt_lengths=(4, 24),
                                  pattern="bimodal", long_fraction=0.3)
    short_len = 4

    def _decode_p99(eng) -> object:
        eng.warmup()  # measure scheduling interference, not jit compiles
        eng.start()
        try:
            hs = [(req, eng.submit(**req)) for req in dg_trace]
            per_tok = []
            for req, h in hs:
                if not h.wait(timeout=budget_s):
                    raise TimeoutError(f"request {h.id} incomplete")
                if (len(req["prompt"]) == short_len
                        and h.finish in ("eos", "length")
                        and len(h.tokens) >= 2 and h.ttft_s is not None):
                    per_tok.append((h.latency_s - h.ttft_s)
                                   / (len(h.tokens) - 1))
            return loadgen._percentile_ms(per_tok, 99)
        finally:
            eng.stop()

    _, uni_eng = loadgen.build_engine(num_slots=4, max_len=40, paged=True)
    out["serving_unified_decode_p99_ms"] = _decode_p99(uni_eng)
    _, pair = loadgen.build_engine(num_slots=4, max_len=40,
                                   disaggregate=True, prefill_engines=1)
    out["serving_disagg_decode_p99_ms"] = _decode_p99(pair)
    out["serving_kv_transfer_bytes"] = int(
        pair.stats["kv_block_bytes_shipped"])
    if time.perf_counter() - t0 > budget_s * 0.95:
        return out
    # multi-tenant QoS leg (PR 18): an open-loop overload burst over a
    # mixed-tenant trace on a small paged engine — weighted-fair
    # admission + batch-slot preemption must hold the interactive tier's
    # p99 while the batch tier absorbs the queueing; preempt_resume_ms
    # prices the swap-out/swap-in round-trip the TUNING.md crossover
    # guidance is about
    _, qos_eng = loadgen.build_engine(num_slots=2, max_len=32, paged=True,
                                      block_size=8, queue_capacity=32)
    for p in loadgen.qos_policies(3):
        qos_eng.register_tenant(p)
    qos_trace = loadgen.make_trace(20, num_steps=16, seed=5,
                                   tenants=3, tier_mix=0.3)
    try:
        qos = loadgen.run_overload(qos_eng, qos_trace, qps=200.0,
                                   timeout_s=budget_s)
        out["serving_interactive_p99_ms_under_overload"] = \
            qos["interactive_p99_ms"]
        out["serving_batch_completion_rate"] = qos["batch_completion_rate"]
        out["serving_preempt_resume_ms"] = qos["preempt_resume_ms"]
    finally:
        qos_eng.stop()
    return out


def serving_fleet_bench(budget_s: float = 90.0):
    """Replicated-fleet routing observables (distkeras_tpu/router.py):

     - ``serving_fleet_tokens_per_sec`` — the SAME closed-loop trace
       through a ``ServingRouter`` at N ∈ {1, 2, 4} in-process replicas
       (concurrency scaled with N so offered load tracks capacity): the
       fleet-scaling curve, keyed by replica count.
     - ``serving_fleet_prefix_hit_rate`` — a multi-tenant shared-prefix
       trace through a 2-replica PAGED fleet under ``affinity="prefix"``
       vs the seeded ``"random"`` control arm: cache-aware routing holds
       the fleet-wide radix hit rate where random scatters tenants
       across cold tries.
     - ``serving_fleet_failover_lost_requests`` — accepted requests that
       failed to complete after one of two replicas is killed under
       load.  MUST be 0: typed ``EngineDead`` + seeded resubmission is
       the zero-loss contract tests/test_router.py pins bit-exactly.

    Returns Nones on overrun/failure — never fatal to the artifact.
    """
    sys.path.insert(0, os.path.join(_REPO, "examples"))
    import loadgen

    none = {"serving_fleet_tokens_per_sec": None,
            "serving_fleet_prefix_hit_rate": None,
            "serving_fleet_failover_lost_requests": None}
    if budget_s < 10.0:
        return none
    t0 = time.perf_counter()
    out = dict(none)
    # fleet scaling: identical trace + per-replica knobs, N in {1, 2, 4}
    scaling = {}
    trace = loadgen.make_trace(24, num_steps=8, temperature=0.7)
    for n in (1, 2, 4):
        _, router = loadgen.build_fleet(replicas=n,
                                        affinity="least-loaded",
                                        num_slots=2)
        try:
            closed = loadgen.run_closed_loop(router, trace,
                                             concurrency=4 * n,
                                             timeout_s=budget_s)
        finally:
            router.stop()
        scaling[str(n)] = closed["tokens_per_sec"]
        if time.perf_counter() - t0 > budget_s * 0.5:
            break
    out["serving_fleet_tokens_per_sec"] = scaling
    if time.perf_counter() - t0 > budget_s * 0.6:
        return out
    # cache-aware routing vs the control arm: same tenanted trace, same
    # paged fleet, only the dispatch policy differs
    hit = {}
    ptrace = loadgen.make_trace(24, num_steps=4, prefix_groups=4,
                                prefix_len=12)
    for policy in ("prefix", "random"):
        _, router = loadgen.build_fleet(replicas=2, affinity=policy,
                                        paged=True, block_size=4)
        try:
            closed = loadgen.run_closed_loop(router, ptrace,
                                             concurrency=4,
                                             timeout_s=budget_s)
        finally:
            router.stop()
        hit[policy] = closed["prefix_hit_rate"]
    out["serving_fleet_prefix_hit_rate"] = hit
    if time.perf_counter() - t0 > budget_s * 0.85:
        return out
    # zero-loss failover: one of two replicas dies with requests queued
    # and mid-stream; seeded resubmission must complete every one
    _, router = loadgen.build_fleet(replicas=2, affinity="least-loaded",
                                    num_slots=2)
    ftrace = loadgen.make_trace(12, num_steps=8, seed=5, temperature=0.7)
    router.start()
    try:
        handles = [router.submit(block=True, timeout=budget_s, **req)
                   for req in ftrace]
        router.engines[0].declare_dead("bench: fleet failover leg")
        lost = 0
        for h in handles:
            if not h.wait(timeout=budget_s) or h.error is not None:
                lost += 1
        out["serving_fleet_failover_lost_requests"] = lost
    finally:
        router.stop()
    return out


def serving_wire_bench(budget_s: float = 90.0):
    """Wire-transport scaling observables (PR 19): the same seeded trace
    through a :class:`ServingServer` over loopback sockets at 8 and 64
    concurrent wire clients, once per transport core —

     - ``serving_connection_scaling`` — tokens/sec keyed by core
       (``"threaded"`` / ``"event"``) then client count (``"8"`` /
       ``"64"``), each point also recording the peak per-connection
       server thread count sampled mid-flight: the threaded core holds
       one relay thread per connection (O(N)); the event core's single
       selector thread holds ZERO (the O(1) the acceptance bar asserts).
     - ``serving_event_tokens_per_sec`` — the event core at 64 clients,
       the headline compared against the threaded core's 64-client point
       (event must not be behind: one loop thread replaces 64 without
       giving up throughput).

    Returns Nones on overrun/failure — never fatal to the artifact.
    """
    sys.path.insert(0, os.path.join(_REPO, "examples"))
    import loadgen
    from distkeras_tpu.serving import ServingServer

    none = {"serving_event_tokens_per_sec": None,
            "serving_connection_scaling": None}
    if budget_s < 10.0:
        return none
    t0 = time.perf_counter()
    trace = loadgen.make_trace(96, num_steps=8)
    scaling = {}
    for core in ("threaded", "event"):
        scaling[core] = {}
        for clients in (8, 64):
            _, engine = loadgen.build_engine(num_slots=4,
                                             queue_capacity=128)
            srv = ServingServer(engine, server_core=core,
                                poll_s=0.01).start()
            try:
                m = loadgen.run_wire_closed_loop(srv.addr, trace,
                                                 concurrency=clients,
                                                 timeout_s=budget_s)
            finally:
                srv.stop()
                engine.stop()
            scaling[core][str(clients)] = {
                "tokens_per_sec": m["tokens_per_sec"],
                "server_conn_threads": m["server_conn_threads_peak"]}
            if time.perf_counter() - t0 > budget_s:
                return {"serving_event_tokens_per_sec": None,
                        "serving_connection_scaling": scaling}
    ev64 = scaling["event"]["64"]["tokens_per_sec"]
    return {"serving_connection_scaling": scaling,
            "serving_event_tokens_per_sec": ev64}


def main():
    t_start = time.perf_counter()
    debug = os.environ.get("DISTKERAS_BENCH_DEBUG", "") == "1"

    def stage(name):
        if debug:
            print(f"[bench {time.perf_counter() - t_start:7.1f}s] {name}",
                  file=sys.stderr, flush=True)

    sys.path.insert(0, _REPO)
    import jax
    import numpy as np

    from distkeras_tpu.utils import use_compile_cache
    stage(f"compile cache: {use_compile_cache()}")
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"bench.py: no TPU (jax found {device.platform}) - this "
              "benchmark measures the chip or nothing", file=sys.stderr)
        sys.exit(3)

    from distkeras_tpu.data.datasets import has_real_data, load_mnist
    from distkeras_tpu.metrics import flops_per_example, peak_flops
    from distkeras_tpu.models.zoo import mnist_convnet
    from distkeras_tpu.parallel.mesh import get_mesh
    from distkeras_tpu.parallel.spmd import SPMDEngine, shape_epoch_data

    # batch 512 won the on-chip sweep of 2026-07-31 (docs/TUNING.md):
    # 690k ex/s vs 662k at 128 and 578k at 1024 on a v5-lite
    batch = int(os.environ.get("DISTKERAS_BENCH_BATCH", "512"))
    window = int(os.environ.get("DISTKERAS_BENCH_WINDOW", "12"))
    n_rows = int(os.environ.get("DISTKERAS_BENCH_ROWS", "60000"))
    dtype = "bfloat16"

    mesh = get_mesh()
    n = mesh.devices.size
    stage(f"mesh ready: n={n} platform={jax.devices()[0].platform}")
    model = mnist_convnet(dtype)
    engine = SPMDEngine(model, "categorical_crossentropy", "adam", mesh,
                        "adag", communication_window=window)

    data_kind = "real" if has_real_data("mnist") else "synthetic"
    train, _ = load_mnist(n_train=n_rows)
    x = np.asarray(train["features"], np.float32) / 255.0
    y = np.eye(10, dtype=np.float32)[np.asarray(train["label"])]
    xb, yb, mb, rounds = shape_epoch_data(x, y, n, window, batch)

    state = engine.init_state(jax.random.PRNGKey(0), (784,))
    # Re-place the fresh state with the exact shardings the epoch outputs
    # carry (the checkpoint-restore path): the first call then compiles for
    # the same layouts as every later call — ONE compile instead of a
    # host-committed + donated pair.
    state = engine.put_state(jax.device_get(state))
    rngs = engine.worker_rngs(0)

    # The whole epoch's data lives in HBM across epochs (188 MB at MNIST
    # scale) — place it once; steady-state training never re-transfers.
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = NamedSharding(mesh, P(None, None, "workers"))
    xb = jax.device_put(xb, sh)
    yb = jax.device_put(yb, sh)
    mb = jax.device_put(mb, sh)
    epoch_fn = engine._build_epoch_fn()

    stage("data placed; warming up")
    # one warmup compiles (state already carries the steady-state layouts);
    # a second run double-checks layout stability cheaply
    for i in range(2):
        state, losses = epoch_fn(state, xb, yb, mb, rngs)
        assert np.isfinite(np.asarray(losses)).all()
        stage(f"warmup {i} done")

    # Estimate per-epoch wall time (host fetch included) to size a ~3.5 s
    # run: min of two samples (one host stall can't collapse the rep
    # count), and a floor of 8 reps amortizes the final fetch to <= 1/8 of
    # an epoch.
    est = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        state, losses = epoch_fn(state, xb, yb, mb, rngs)
        np.asarray(losses)
        est = min(est, time.perf_counter() - t0)
        stage(f"est epoch: {time.perf_counter() - t0:.2f}s")
    reps = max(8, min(200, int(round(3.5 / est))))

    # Hard wall-clock budget (DISTKERAS_BENCH_BUDGET seconds, default 540):
    # whatever compilation already cost, cap the timed region and gate the
    # sub-benchmarks below on what is left.
    budget = float(os.environ.get("DISTKERAS_BENCH_BUDGET", "540"))
    remaining = budget - (time.perf_counter() - t_start)
    reps = max(1, min(reps, int(remaining / max(est, 1e-9))))
    stage(f"est={est:.2f}s reps={reps} (remaining budget {remaining:.0f}s)")

    # Timed region: dispatch the whole run as one donation-chained sequence
    # and materialize once at the end.  Each epoch depends on the previous
    # state, so the final device->host fetch waits for every epoch; fetching
    # losses *per* epoch would add a host round-trip to every epoch —
    # measurement overhead, not training.
    t0 = time.perf_counter()
    for _ in range(reps):
        state, losses = epoch_fn(state, xb, yb, mb, rngs)
    final_losses = np.asarray(losses)
    dt = time.perf_counter() - t0
    assert np.isfinite(final_losses).all()

    # padded tail is masked, every real row trains exactly once per epoch
    examples = reps * len(x)
    eps_per_chip = examples / dt / n

    device_kind = device.device_kind
    flops_ex = flops_per_example(model, backward=True)
    mfu = round(eps_per_chip * flops_ex / peak_flops(device_kind), 4)

    baseline_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "BASELINE_MEASURED.json")
    vs = None
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            base = json.load(f)
        if base.get("value"):
            vs = round(eps_per_chip / float(base["value"]), 2)

    result = {
        "metric": "examples_per_sec_per_chip_mnist_convnet_adag",
        "value": round(eps_per_chip, 1),
        "unit": "examples/sec/chip",
        "vs_baseline": vs,
        "mfu": mfu,
        "platform": device.platform,
        "device_kind": device_kind,
        "device_count": len(jax.devices()),
        "jax": jax.__version__,
        "data": data_kind,
        "compute_dtype": dtype,
        "batch": batch,
        "window": window,
        "rows": len(x),
        "flops_per_example": flops_ex,
    }
    # Sub-benchmarks: skipped (fields null) when the budget is spent; one
    # that RAISES still lets the line print but fails the run.
    failed = []

    def sub_failed(name, e):
        failed.append(name)
        print(f"[bench] {name} failed: {type(e).__name__}: {e}",
              file=sys.stderr)

    # PS-path microbenchmark (the observable for the overlapped 'u'
    # transport — docs/host_ps.md)
    stage("host_ps microbench")
    ps_fields = {"host_ps_examples_per_sec": None,
                 "host_ps_rtts_per_window": None}
    ps_remaining = budget - (time.perf_counter() - t_start)
    if ps_remaining > 60:
        try:
            ps_fields = host_ps_microbench(budget_s=ps_remaining)
        except Exception as e:
            sub_failed("host_ps microbench", e)
    result.update(ps_fields)
    # PS shard-scaling (ps_sharding.py): examples/sec at ps_shards=1 vs 4
    stage("host_ps shard scaling")
    shard_fields = {"host_ps_shard_scaling": None}
    shard_remaining = budget - (time.perf_counter() - t_start)
    if shard_remaining > 60:
        try:
            shard_fields = host_ps_shard_bench(budget_s=shard_remaining)
        except Exception as e:
            sub_failed("host_ps shard bench", e)
    result.update(shard_fields)
    # worker-count scaling, event core vs the retained thread-per-
    # connection core (the PR 7 before/after observable) + the coalesced-
    # drain counters proving commits really merge under load
    stage("host_ps worker scaling")
    scaling_fields = {"host_ps_worker_scaling": None}
    scaling_remaining = budget - (time.perf_counter() - t_start)
    if scaling_remaining > 90:
        try:
            scaling_fields = host_ps_worker_scaling_bench(
                budget_s=scaling_remaining)
        except Exception as e:
            sub_failed("host_ps worker scaling bench", e)
    result.update(scaling_fields)
    # wire-byte observable for the commit-compression stack (dense vs
    # bf16/int8/topk): deterministic and sub-second, so no budget gate —
    # the byte win is tracked in every BENCH_* artifact
    stage("host_ps wire bytes")
    wire_fields = {"host_ps_wire_bytes_per_window": None,
                   "host_ps_commit_compression_ratio": None}
    try:
        wire_fields = host_ps_wire_bytes_bench()
    except Exception as e:
        sub_failed("host_ps wire bytes bench", e)
    result.update(wire_fields)
    # row-sparse embedding commit bytes (the exact sparse profile):
    # deterministic and sub-second, so no budget gate — the byte win is
    # tracked in every BENCH_* artifact next to the flat top-k one
    stage("host_ps embedding commit bytes")
    emb_fields = {"host_ps_embedding_commit_bytes_per_window": None}
    try:
        emb_fields = host_ps_embedding_commit_bytes_bench()
    except Exception as e:
        sub_failed("host_ps embedding commit bytes bench", e)
    result.update(emb_fields)
    # streaming-ingestion throughput (streaming.py): a generator-backed
    # online run through the horizon-leased PS fabric
    stage("host_ps stream")
    stream_fields = {"host_ps_stream_examples_per_sec": None}
    stream_remaining = budget - (time.perf_counter() - t_start)
    if stream_remaining > 60:
        try:
            stream_fields = host_ps_stream_bench(budget_s=stream_remaining)
        except Exception as e:
            sub_failed("host_ps stream bench", e)
    result.update(stream_fields)
    # PS recovery latency (resilience.py): kill one shard under the
    # supervisor, measure client-observed time back to a successful pull
    stage("host_ps recovery")
    recovery_fields = {"host_ps_recovery_ms": None}
    recovery_remaining = budget - (time.perf_counter() - t_start)
    if recovery_remaining > 30:
        try:
            recovery_fields = host_ps_recovery_bench(
                budget_s=recovery_remaining)
        except Exception as e:
            sub_failed("host_ps recovery bench", e)
    result.update(recovery_fields)
    # elastic-worker observables (resilience.py): death→respawn latency and
    # the wall-clock cost of one hung worker under lease stealing
    stage("host_ps worker recovery + straggler")
    elastic_fields = {"host_ps_worker_recovery_ms": None,
                      "host_ps_straggler_overhead": None}
    elastic_remaining = budget - (time.perf_counter() - t_start)
    if elastic_remaining > 60:
        try:
            elastic_fields.update(host_ps_worker_recovery_bench(
                budget_s=elastic_remaining))
            elastic_fields.update(host_ps_straggler_bench(
                budget_s=budget - (time.perf_counter() - t_start)))
        except Exception as e:
            sub_failed("host_ps elastic bench", e)
    result.update(elastic_fields)
    # continuous-batching serving observables (serving.py + loadgen):
    # engine vs sequential per-request generate on the same request trace
    stage("serving loadgen")
    serving_fields = {"serving_tokens_per_sec": None,
                      "serving_p50_ms": None, "serving_p99_ms": None,
                      "serving_slot_occupancy": None,
                      "serving_sequential_tokens_per_sec": None,
                      "serving_shed_rate": None,
                      "serving_slot_reclaim_ms": None,
                      "serving_deadline_miss_rate": None,
                      "serving_ttft_p50_ms": None,
                      "serving_ttft_p99_ms": None,
                      "serving_prefill_tokens_per_sec": None,
                      "serving_longprompt_ttft_p99_ms": None,
                      "serving_longprompt_ttft_eager_p99_ms": None,
                      "serving_spec_tokens_per_sec": None,
                      "serving_spec_accept_rate": None,
                      "serving_quant_capacity_slots": None,
                      "serving_prefix_ttft_p99_ms": None,
                      "serving_prefix_ttft_dense_p99_ms": None,
                      "serving_prefix_hit_rate": None,
                      "serving_prefix_prefill_tokens_per_sec": None,
                      "serving_prefix_prefill_dense_tokens_per_sec": None,
                      "serving_paged_capacity_slots": None,
                      "serving_unified_decode_p99_ms": None,
                      "serving_disagg_decode_p99_ms": None,
                      "serving_kv_transfer_bytes": None,
                      "serving_interactive_p99_ms_under_overload": None,
                      "serving_batch_completion_rate": None,
                      "serving_preempt_resume_ms": None}
    serving_remaining = budget - (time.perf_counter() - t_start)
    if serving_remaining > 45:
        try:
            serving_fields = serving_bench(budget_s=serving_remaining)
        except Exception as e:
            sub_failed("serving bench", e)
    result.update(serving_fields)
    # replicated-fleet routing (router.py): scaling curve, cache-aware
    # routing vs the random control arm, and the zero-loss failover count
    stage("serving fleet routing")
    fleet_fields = {"serving_fleet_tokens_per_sec": None,
                    "serving_fleet_prefix_hit_rate": None,
                    "serving_fleet_failover_lost_requests": None}
    fleet_remaining = budget - (time.perf_counter() - t_start)
    if fleet_remaining > 45:
        try:
            fleet_fields = serving_fleet_bench(budget_s=fleet_remaining)
        except Exception as e:
            sub_failed("serving fleet bench", e)
    result.update(fleet_fields)
    # wire-transport scaling (PR 19): tokens/sec at 8 vs 64 concurrent
    # wire clients through both server cores + the thread-count deltas
    stage("serving wire transport")
    wire_fields = {"serving_event_tokens_per_sec": None,
                   "serving_connection_scaling": None}
    wire_remaining = budget - (time.perf_counter() - t_start)
    if wire_remaining > 45:
        try:
            wire_fields = serving_wire_bench(budget_s=wire_remaining)
        except Exception as e:
            sub_failed("serving wire bench", e)
    result.update(wire_fields)
    # the train-while-serve loop (deployment_online.py): freshness
    # percentiles + served accuracy under drift on the live deployment
    stage("online deployment")
    online_fields = {"freshness_p50_s": None, "freshness_p99_s": None,
                     "online_served_accuracy": None}
    online_remaining = budget - (time.perf_counter() - t_start)
    if online_remaining > 60:
        try:
            online_fields = online_deployment_bench(
                budget_s=online_remaining)
        except Exception as e:
            sub_failed("online deployment bench", e)
    result.update(online_fields)
    print(json.dumps(result))
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
