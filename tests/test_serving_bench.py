"""Load-generator surface (examples/loadgen.py).

The fast variants here are tier-1: a small fixed trace through the closed
loop must complete losslessly with sane metrics, and the trace itself must
be a pure function of its seed.  The full-size comparison — continuous
batching beating sequential per-request ``generate`` at ≥ 4 concurrent
requests — and the offered-QPS sweep are ``slow`` (they time real decode
work).
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "examples"))
import loadgen  # noqa: E402


def test_trace_is_deterministic():
    a = loadgen.make_trace(12, seed=3, temperature=0.7)
    b = loadgen.make_trace(12, seed=3, temperature=0.7)
    assert len(a) == len(b) == 12
    for ra, rb in zip(a, b):
        assert ra.keys() == rb.keys()
        np.testing.assert_array_equal(ra["prompt"], rb["prompt"])
        assert ra["seed"] == rb["seed"]
        assert ra.get("temperature") == rb.get("temperature")
    c = loadgen.make_trace(12, seed=4, temperature=0.7)
    assert any((len(ra["prompt"]) != len(rc["prompt"]))
               or (ra["prompt"] != rc["prompt"][:len(ra["prompt"])]).any()
               for ra, rc in zip(a, c))


def test_closed_loop_fast_trace_lossless():
    """Tier-1 deterministic variant: every traced request completes, zero
    shed, tokens accounted exactly, occupancy recorded."""
    _, engine = loadgen.build_engine(num_slots=2, queue_capacity=16)
    trace = loadgen.make_trace(6, num_steps=6, temperature=0.5)
    try:
        m = loadgen.run_closed_loop(engine, trace, concurrency=4,
                                    timeout_s=120.0)
    finally:
        engine.stop()
    assert m["completed"] == 6 and m["shed"] == 0
    assert m["tokens"] == 6 * 6
    assert m["tokens_per_sec"] > 0
    assert m["p50_ms"] is not None and m["p99_ms"] >= m["p50_ms"]
    assert 0.0 < m["slot_occupancy"] <= 1.0
    assert all(n >= 1 for n in engine.stats["slot_requests"])
    # the TTFT observables ride the same run: first token precedes the
    # end of its request, and prompt tokens flowed through the compiled
    # prefill path
    assert m["ttft_p50_ms"] is not None
    assert m["ttft_p50_ms"] <= m["p50_ms"]
    assert m["prefill_tokens_per_sec"] > 0
    assert engine.stats["prefill_batches"] >= 1
    assert engine.stats["prefill_batch_size_mean"] >= 1.0


def test_closed_loop_outputs_match_offline_generate():
    """The loadgen path changes scheduling only: each traced request's
    tokens equal offline generate's for the same seed."""
    import jax

    fitted, engine = loadgen.build_engine(num_slots=2, queue_capacity=16)
    trace = loadgen.make_trace(5, num_steps=5, temperature=0.6)
    handles = [engine.submit(**req) for req in trace]
    try:
        engine.start()
        for h in handles:
            assert h.wait(timeout=120.0)
    finally:
        engine.stop()
    for h, req in zip(handles, trace):
        temp = req.get("temperature", 0.0)
        want = np.asarray(fitted.generate(
            req["prompt"][None], req["num_steps"], temperature=temp,
            rng=jax.random.PRNGKey(req["seed"]) if temp else None,
            max_len=engine.max_len))[0]
        np.testing.assert_array_equal(h.result(), want)


def test_closed_loop_chaos_kill_schedule_no_leaks():
    """The --chaos client-kill schedule: seeded kills cancel mid-run, the
    engine reclaims every slot (zero leaks), survivors complete, and the
    new failure-semantics metrics are recorded."""
    # 24-step requests: the fast-path engine streams short requests so
    # quickly that a killer waiting for its seeded token count could lose
    # the race and cancel an already-finished request (a no-op) — the
    # longer run keeps every seeded kill landing mid-run
    _, engine = loadgen.build_engine(num_slots=2, queue_capacity=16)
    trace = loadgen.make_trace(8, num_steps=24, temperature=0.5)
    try:
        m = loadgen.run_closed_loop(engine, trace, concurrency=4,
                                    timeout_s=120.0, chaos_kill=0.4,
                                    chaos_seed=3)
    finally:
        engine.stop()
    assert m["killed"] > 0  # the seeded schedule really killed someone
    # every request reached a terminal state: zero leaks
    s = engine.stats
    assert s["requests_submitted"] == 8
    assert m["completed"] == 8  # completed counts every retirement
    assert s["requests_cancelled"] + s["requests_expired"] >= 1
    assert not engine._active.any()
    assert sorted(engine._free) == list(range(engine.num_slots))
    # metric fields recorded (killed requests excluded from latencies)
    assert m["slot_reclaim_ms"] is None or m["slot_reclaim_ms"] >= 0
    assert 0.0 <= m["deadline_miss_rate"] <= 1.0
    assert 0.0 <= m["shed_rate"] <= 1.0
    # determinism: the kill schedule is a pure function of the seed
    _, engine2 = loadgen.build_engine(num_slots=2, queue_capacity=16)
    try:
        m2 = loadgen.run_closed_loop(engine2, trace, concurrency=4,
                                     timeout_s=120.0, chaos_kill=0.4,
                                     chaos_seed=3)
    finally:
        engine2.stop()
    assert m2["killed"] == m["killed"]


@pytest.mark.slow
def test_continuous_batching_beats_sequential_at_4_concurrent():
    """The acceptance comparison: the engine's closed-loop tokens/sec beats
    sequential per-request generate on the same trace at ≥ 4 concurrent
    requests (4 slots, 8 users)."""
    fitted, engine = loadgen.build_engine(num_slots=4)
    trace = loadgen.make_trace(24, num_steps=16, temperature=0.7)
    try:
        closed = loadgen.run_closed_loop(engine, trace, concurrency=8,
                                         timeout_s=300.0)
    finally:
        engine.stop()
    seq = loadgen.sequential_baseline(fitted, trace, max_len=engine.max_len)
    assert closed["completed"] == 24
    assert closed["tokens_per_sec"] > seq["tokens_per_sec"], (closed, seq)


@pytest.mark.slow
def test_open_loop_qps_sweep_sheds_under_overload():
    """Offered-QPS sweep: a modest rate completes everything; an absurd
    rate against a tiny queue sheds (bounded buffering, not collapse)."""
    _, engine = loadgen.build_engine(num_slots=2, queue_capacity=4)
    trace = loadgen.make_trace(16, num_steps=8)
    try:
        calm = loadgen.run_open_loop(engine, trace, qps=2.0,
                                     timeout_s=300.0)
    finally:
        engine.stop()
    assert calm["shed"] == 0 and calm["completed"] == 16
    _, engine = loadgen.build_engine(num_slots=2, queue_capacity=4)
    # saturate admission before the engine thread can drain: floods the
    # bounded queue at effectively infinite rate
    trace = loadgen.make_trace(64, num_steps=8)
    try:
        flood = loadgen.run_open_loop(engine, trace, qps=1e6,
                                      timeout_s=300.0)
    finally:
        engine.stop()
    assert flood["shed"] > 0
    assert flood["completed"] == 64 - flood["shed"]  # shed, never lost


# ---------------------------------------------------------------------------
# paged loadgen (PR 12): the fast leg is tier-1 (seeded trace, no sleeps);
# the timing comparison is slow
# ---------------------------------------------------------------------------

@pytest.mark.paged
def test_paged_loadgen_shared_prefix_fast_leg():
    """Tier-1 deterministic paged leg: a shared-prefix trace through a
    paged engine completes losslessly, records prefix hits with
    byte-accounted block reuse, and the trace generator is a pure
    function of its seed."""
    a = loadgen.make_trace(8, seed=3, prefix_groups=2, prefix_len=8)
    b = loadgen.make_trace(8, seed=3, prefix_groups=2, prefix_len=8)
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra["prompt"], rb["prompt"])
    # round-robin groups: requests 0 and 2 share a prefix, 0 and 1 don't
    np.testing.assert_array_equal(a[0]["prompt"][:8], a[2]["prompt"][:8])
    assert (a[0]["prompt"][:8] != a[1]["prompt"][:8]).any()
    _, engine = loadgen.build_engine(num_slots=2, max_len=32, paged=True,
                                     block_size=4, queue_capacity=16)
    trace = loadgen.make_trace(6, num_steps=6, temperature=0.5,
                               prefix_groups=1, prefix_len=8)
    try:
        m = loadgen.run_closed_loop(engine, trace, concurrency=4,
                                    timeout_s=120.0)
    finally:
        engine.stop()
    assert m["completed"] == 6 and m["shed"] == 0
    assert m["prefix_hits"] >= 1
    assert m["prefix_hit_tokens"] >= 8
    assert m["prefix_hit_rate"] > 0
    assert m["blocks_reused"] >= 1
    assert m["kv_pool_bytes"] == engine.kv_pool_bytes
    assert engine.kv_blocks_in_use == 0


# ---------------------------------------------------------------------------
# wire transport scaling (PR 19): the fast legs are tier-1 (small trace over
# loopback, bounded waits); the 64-client scaling comparison is slow
# ---------------------------------------------------------------------------

def test_wire_closed_loop_lossless_both_cores():
    """Tier-1 deterministic wire leg: a small trace through a
    ServingServer over loopback completes losslessly on BOTH transport
    cores, and the event core's mid-flight per-connection server thread
    count is ZERO while the threaded core's is positive."""
    from distkeras_tpu.serving import ServingServer

    trace = loadgen.make_trace(6, num_steps=6, temperature=0.5)
    conn_threads = {}
    for core in ("threaded", "event"):
        _, engine = loadgen.build_engine(num_slots=2, queue_capacity=16)
        srv = ServingServer(engine, server_core=core, poll_s=0.01).start()
        try:
            m = loadgen.run_wire_closed_loop(srv.addr, trace,
                                             concurrency=4,
                                             timeout_s=120.0)
        finally:
            srv.stop()
            engine.stop()
        assert m["completed"] == 6, (core, m)
        assert m["tokens"] == 6 * 6
        assert m["tokens_per_sec"] > 0
        assert m["p50_ms"] is not None and m["p99_ms"] >= m["p50_ms"]
        conn_threads[core] = m["server_conn_threads_peak"]
    assert conn_threads["event"] == 0, conn_threads
    assert conn_threads["threaded"] >= 1, conn_threads


@pytest.mark.slow
def test_wire_event_core_holds_throughput_at_64_clients():
    """The PR 19 acceptance comparison: at 64 concurrent wire clients the
    event core's ONE selector thread sustains at least the threaded
    core's tokens/sec (64 relay threads), with zero per-connection
    server threads."""
    from distkeras_tpu.serving import ServingServer

    trace = loadgen.make_trace(96, num_steps=8)
    tps = {}
    for core in ("threaded", "event"):
        _, engine = loadgen.build_engine(num_slots=4, queue_capacity=128)
        srv = ServingServer(engine, server_core=core, poll_s=0.01).start()
        try:
            m = loadgen.run_wire_closed_loop(srv.addr, trace,
                                             concurrency=64,
                                             timeout_s=300.0)
        finally:
            srv.stop()
            engine.stop()
        assert m["completed"] == 96, (core, m)
        tps[core] = m["tokens_per_sec"]
        if core == "event":
            assert m["server_conn_threads_peak"] == 0, m
        else:
            assert m["server_conn_threads_peak"] >= 32, m
    # one loop thread replaces 64 relay threads without losing
    # throughput (10% guard band: both cores are engine-bound here,
    # the margin absorbs scheduler noise on a loaded CI host)
    assert tps["event"] >= tps["threaded"] * 0.9, tps


# ---------------------------------------------------------------------------
# fleet routing (PR 17): seeded trace, bounded waits
# ---------------------------------------------------------------------------

@pytest.mark.router
def test_closed_loop_router_fleet_lossless():
    """Tier-1 deterministic fleet leg: the closed loop drives a 2-replica
    router exactly like a bare engine (duck-typed submit/cancel/stats),
    every request completes, and the per-replica skew report accounts
    for the whole trace."""
    _, router = loadgen.build_fleet(replicas=2, affinity="least-loaded",
                                    num_slots=2)
    trace = loadgen.make_trace(6, num_steps=6, temperature=0.5)
    try:
        m = loadgen.run_closed_loop(router, trace, concurrency=4,
                                    timeout_s=120.0)
        report = loadgen.fleet_report(router, m)
    finally:
        router.stop()
    assert m["completed"] == 6 and m["shed"] == 0
    assert m["tokens"] == 6 * 6
    assert m["tokens_per_sec"] > 0
    assert report["replicas"] == 2
    assert sum(p["routed"] for p in report["per_replica"]) == 6
    assert report["requests_failed"] == 0
    assert report["routed_skew"] is not None and report["routed_skew"] >= 1


@pytest.mark.paged
@pytest.mark.slow
def test_paged_shared_prefix_ttft_beats_dense_5x():
    """The PR 12 acceptance bar: ≥8 users sharing a ≥128-token prefix see
    ≥5× better TTFT p99 AND effective prefill-tokens/sec through the
    paged pool than through the PR 9 bucketed path (prefix warmed once on
    both sides — steady state), with prefix_hit_tokens byte-accounting
    proving the win is block reuse."""
    # prefill-heavy trace (one continuation token): the measured quantity
    # IS the prefill path — TTFT is the time to that token, and wall time
    # is prefill-dominated so tokens/sec measures cache fill, not decode
    trace = loadgen.make_trace(24, num_steps=1, prompt_lengths=(4, 6, 8),
                               prefix_groups=1, prefix_len=240)
    results = {}
    for paged in (True, False):
        _, eng = loadgen.build_engine(num_slots=8, max_len=256,
                                      paged=paged, block_size=16,
                                      prefill_chunk=16,
                                      prefills_per_step=4)
        try:
            eng.warmup()
            eng.submit(trace[0]["prompt"], 1)
            eng.run_until_idle()          # warm the shared prefix once
            m = loadgen.run_closed_loop(eng, trace, concurrency=8,
                                        timeout_s=300.0)
            eff = (m["prefill_tokens_per_sec"] or 0.0)
            if m["wall_s"]:
                eff += m["prefix_hit_tokens"] / m["wall_s"]
            results[paged] = (m["ttft_p99_ms"], eff, m)
        finally:
            eng.stop()
    ttft_paged, eff_paged, m_paged = results[True]
    ttft_dense, eff_dense, _ = results[False]
    assert m_paged["prefix_hit_tokens"] >= 224 * 23  # every later request
    # hit rate over the ENGINE lifetime includes the one warm prefill
    assert m_paged["prefix_hit_rate"] > 0.85
    assert ttft_dense >= 5 * ttft_paged, (ttft_dense, ttft_paged)
    assert eff_paged >= 5 * eff_dense, (eff_paged, eff_dense)
