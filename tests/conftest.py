"""Test environment: force an 8-device virtual CPU platform *before* JAX
initializes, so distributed-trainer tests exercise real mesh sharding +
collectives without TPU hardware (SURVEY.md §4's multi-device simulation —
the idiomatic analogue of the reference's Spark ``local[*]`` fake cluster).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import numpy as np
import pytest

# Cheap-first file ordering.  The tier-1 gate (ROADMAP.md) runs the whole
# suite under one wall-clock budget; in alphabetical order a handful of
# compile-heavy files (serving fastpath/resilience soaks, attention
# kernels) sit mid-alphabet and a budget overrun truncates hundreds of
# sub-second tests queued behind them.  Order files by measured mean
# seconds/test instead — fast feedback first, the soaks last, in-file
# order untouched (the sort is stable and keys are per-file, so files stay
# contiguous and module-scoped fixtures still build once).
_FILE_COST = {  # mean s/test on the CPU gate machine; unlisted -> 3.0
    "test_applykernel.py": 0.01, "test_wirecodec.py": 0.01,
    "test_evaluators.py": 0.01, "test_update_rules.py": 0.02,
    "test_data.py": 0.02, "test_analysis.py": 0.11,
    "test_losses_keras1.py": 0.22, "test_ps_sharding.py": 0.30,
    "test_dcn_chaos.py": 0.37,
    "test_event_ps.py": 0.30, "test_job_deployment.py": 0.34,
    "test_host_ps_overlap.py": 0.34, "test_host_ps.py": 0.41,
    "test_core.py": 0.42, "test_fault_tolerance.py": 0.56,
    "test_streaming.py": 0.63, "test_elastic_workers.py": 0.63,
    "test_schedules.py": 0.66, "test_topk_wire.py": 0.75,
    "test_chip_compile.py": 0.7,
    "test_keras_adapter.py": 0.76, "test_determinism_faults.py": 0.78,
    "test_quant.py": 1.07, "test_checkpoint_metrics.py": 1.10,
    "test_online_deployment.py": 1.40, "test_fused_ce.py": 1.51,
    "test_flash_attention.py": 1.52, "test_rope.py": 1.56,
    "test_resilience.py": 1.58, "test_trainers.py": 1.66,
    "test_batchnorm.py": 1.82, "test_beam_search.py": 2.37,
    "test_serving.py": 2.51, "test_pipeline.py": 2.60,
    "test_decode.py": 2.76, "test_router.py": 3.55,
    "test_serving_disagg.py": 3.82, "test_serving_bench.py": 3.85,
    "test_serving_qos.py": 4.0,
    "test_speculative.py": 4.44, "test_ulysses.py": 4.50,
    "test_parallelism.py": 4.69, "test_attention.py": 4.91,
    "test_packing.py": 5.10, "test_parallel_transformer.py": 5.47,
    "test_serving_event.py": 5.1,
    "test_serving_resilience.py": 5.49, "test_zero.py": 5.55,
    "test_serving_fastpath.py": 6.12,
    "test_fsdp.py": 7.41,
}


def pytest_collection_modifyitems(config, items):
    items.sort(key=lambda it: (
        _FILE_COST.get(os.path.basename(str(it.fspath)), 3.0),
        str(it.fspath)))


@pytest.fixture(scope="session")
def eight_devices():
    import jax
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {devs}"
    return devs


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


@pytest.fixture(params=["threaded", "event"])
def server_core(request, monkeypatch):
    """Parametrize ``ServingServer``'s transport core (PR 19): a
    wire-touching test that pulls this fixture runs once per core —
    thread-per-connection and one-selector event loop — with no edits at
    its construction sites; the fixture rebinds the constructor's
    DEFAULT, so explicit ``server_core=`` arguments still win."""
    from distkeras_tpu import serving
    core = request.param
    orig = serving.ServingServer.__init__

    def _init(self, *args, **kw):
        kw.setdefault("server_core", core)
        orig(self, *args, **kw)

    monkeypatch.setattr(serving.ServingServer, "__init__", _init)
    return core


def pytest_configure(config):
    # before any worker imports distkeras_tpu (which binds the native
    # extensions, or their fallbacks, at import): see native_build.py
    from native_build import ensure_built
    ensure_built()
    config.addinivalue_line(
        "markers",
        "slow: multi-process end-to-end tests (worker subprocesses each "
        "import jax and compile)")
    config.addinivalue_line(
        "markers",
        "stream: streaming-ingestion / online-learning contract tests "
        "(tier-1 ones are generator-backed — no live sockets or sleeps on "
        "the fast path; socket-feed coverage uses socketpair only)")
    config.addinivalue_line(
        "markers",
        "paged: paged-KV-pool / radix-prefix-sharing serving tests "
        "(tier-1 ones run small seeded traces inline — no sleeps; the "
        "arena-pressure soaks and timing comparisons are additionally "
        "marked slow, mirroring the stream marker's tiering)")
    config.addinivalue_line(
        "markers",
        "analysis: dklint static-analysis contract tests (pure-ast over "
        "fixture strings plus the tier-1 zero-unbaselined gate over the "
        "package — no JAX imports of checked code, no sleeps)")
    config.addinivalue_line(
        "markers",
        "online: train-while-serve deployment tests (tier-1 ones are "
        "generator-backed and seeded with inline-pumped engines — no "
        "sleeps on the fast path; the chaos soak with live engine kills "
        "and supervised restarts is additionally marked slow)")
    config.addinivalue_line(
        "markers",
        "disagg: disaggregated prefill/decode serving tests (tier-1 legs "
        "are in-process or socketpair/loopback-only, seeded, and "
        "sleep-free; unified-vs-disagg timing comparisons are "
        "additionally marked slow)")
    config.addinivalue_line(
        "markers",
        "router: replicated-fleet routing tests (tier-1 legs are "
        "in-process or loopback-only, seeded, and bounded-wait — "
        "condition-variable waits with deadlines, no fixed sleeps on "
        "the fast path; fleet-scaling timing comparisons are "
        "additionally marked slow)")
    config.addinivalue_line(
        "markers",
        "dcn: cross-process/WAN-grade chaos and partition-tolerance tests "
        "(tier-1 legs are sleep-free and at most two-process-local — "
        "ChaosProxy/ProcessChaos schedules are seeded-deterministic; the "
        "multi-process DCN soaks with SIGSTOP legs and journal respawns "
        "are additionally marked slow)")
    config.addinivalue_line(
        "markers",
        "qos: multi-tenant QoS tests — quotas, weighted-fair admission, "
        "SLO tiers, and paged-KV preemption with bit-identical resume "
        "(tier-1 legs run seeded traces on inline-stepped engines — no "
        "sleeps on the fast path; the overload soak is additionally "
        "marked slow)")


@pytest.fixture()
def lock_order_audit():
    """Opt-in runtime lock-order auditing: locks created inside the test
    body (engine/supervisor construction included) are instrumented, and
    teardown asserts the acquisition-order graph stayed acyclic.  See
    distkeras_tpu/analysis/runtime.py."""
    from distkeras_tpu.analysis.runtime import audit_locks
    with audit_locks() as auditor:
        yield auditor
    assert auditor.violations == [], \
        "runtime lock-order violations:\n" + "\n".join(auditor.violations)
