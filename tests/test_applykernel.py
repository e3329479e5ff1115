"""Native apply kernel (csrc/applykernel.cpp) vs the pure-NumPy reference.

The kernel's contract is BIT-equality: ``axpy_f32`` reproduces numpy's
``dst += scale * src`` (two roundings — the extension compiles with
``-ffp-contract=off`` so no FMA collapses them) and ``scatter_add_f32``
reproduces ``np.add.at``'s sequential array-order accumulation.  Fuzzed
over dense/bf16/int8/SparseDelta apply paths and over BOTH buffer
alignments (numpy-aligned arrays and byte-offset unaligned views).

Mirrors the wirecodec test guard: builds the extension in place (the
shared, locked ``tests/native_build.py``) when a toolchain exists, skips
otherwise.  The fallback smoke test is tier-1 safe — it monkeypatches the
native module away and proves the numpy path serves every apply.
"""

import numpy as np
import pytest

from native_build import ensure_built

from distkeras_tpu import applykernel, networking
from distkeras_tpu.networking import SparseDelta
from distkeras_tpu.parameter_servers import (ADAGParameterServer,
                                             DeltaParameterServer,
                                             DynSGDParameterServer,
                                             _scatter_add)


def _ensure_native():
    if applykernel._native is None:
        error = ensure_built()
        if error is not None:
            pytest.skip(f"no native toolchain: {error}")
        import distkeras_tpu._applykernel as native
        applykernel._native = native
    return applykernel._native


@pytest.fixture()
def native():
    old = applykernel._native
    yield _ensure_native()
    applykernel._native = old


def _unaligned_f32(n, rng=None):
    """A writable float32 array at a 1-byte offset — deliberately
    unaligned (flags.aligned is False), the pooled-view worst case."""
    raw = bytearray(4 * n + 1)
    arr = np.frombuffer(raw, dtype=np.float32, count=n, offset=1)
    if rng is not None:
        arr[:] = rng.standard_normal(n).astype(np.float32)
    return arr


# ---------------------------------------------------------------------------
# primitive bit-equality, fuzzed, both alignments
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("alignment", ["aligned", "unaligned"])
@pytest.mark.parametrize("scale", [1.0, 0.25, 1.0 / 3.0, -2.7183, 0.0])
def test_axpy_bit_equal_fuzz(native, alignment, scale):
    rng = np.random.default_rng(hash((alignment, scale)) % (2 ** 31))
    for n in (0, 1, 7, 128, 1023):
        if alignment == "aligned":
            dst_n = rng.standard_normal(n).astype(np.float32)
            src = rng.standard_normal(n).astype(np.float32)
        else:
            dst_n = _unaligned_f32(n, rng)
            src = _unaligned_f32(n, rng)
        dst_k = dst_n.copy()
        # numpy reference — exactly what ParameterServer._apply_scaled does
        if scale == 1.0:
            dst_n += src
        else:
            dst_n += scale * src
        native.axpy_f32(dst_k, np.ascontiguousarray(src), scale)
        np.testing.assert_array_equal(dst_k, dst_n)


@pytest.mark.parametrize("alignment", ["aligned", "unaligned"])
def test_scatter_add_bit_equal_fuzz(native, alignment):
    rng = np.random.default_rng(5 if alignment == "aligned" else 6)
    for n, k in ((1, 1), (64, 7), (512, 200), (300, 900)):
        if alignment == "aligned":
            dst_n = rng.standard_normal(n).astype(np.float32)
        else:
            dst_n = _unaligned_f32(n, rng)
        dst_k = dst_n.copy()
        # duplicates on purpose: per-coordinate accumulation ORDER is part
        # of the bit-equality contract
        idx = rng.integers(0, n, size=k).astype(np.int64)
        vals = (rng.standard_normal(k)
                * 10.0 ** rng.integers(-6, 6, k)).astype(np.float32)
        np.add.at(dst_n, idx, vals)
        native.scatter_add_f32(dst_k, idx, vals)
        np.testing.assert_array_equal(dst_k, dst_n)


def test_scatter_add_out_of_range_raises(native):
    dst = np.zeros(4, np.float32)
    with pytest.raises(IndexError):
        native.scatter_add_f32(dst, np.array([4], np.int64),
                               np.array([1.0], np.float32))
    with pytest.raises(IndexError):
        native.scatter_add_f32(dst, np.array([-1], np.int64),
                               np.array([1.0], np.float32))


def test_axpy_shape_mismatch_raises(native):
    with pytest.raises(ValueError):
        native.axpy_f32(np.zeros(4, np.float32),
                        np.zeros(5, np.float32), 1.0)


# ---------------------------------------------------------------------------
# the full apply path: dense / bf16 / int8 / SparseDelta, kernel vs numpy
# ---------------------------------------------------------------------------

SHAPES = [(33,), (8, 5), (), (64,)]
TOTAL = sum(int(np.prod(s, dtype=np.int64)) for s in SHAPES)


def _blob():
    return {"model": "{}",
            "weights": [np.zeros(s, np.float32) for s in SHAPES]}


def _wire_msgs(rng):
    """One commit per wire form, decoded exactly as the transport boundary
    decodes them before the apply rule sees the message."""
    import ml_dtypes
    dense = [rng.standard_normal(s).astype(np.float32) * 0.1
             for s in SHAPES]
    bf16 = [d.astype(ml_dtypes.bfloat16) for d in dense]
    scales = [float(np.max(np.abs(d)) / 127.0) or 1.0 for d in dense]
    int8_decoded = [np.asarray(np.clip(np.rint(d / s), -127, 127)
                               .astype(np.int8), np.float32) * s
                    for d, s in zip(dense, scales)]
    k = 17
    idx = np.sort(rng.choice(TOTAL, k, replace=False)).astype(np.int32)
    vals = rng.standard_normal(k).astype(np.float32)
    sp_scale = float(np.max(np.abs(vals)) / 127.0) or 1.0
    sp_int8 = SparseDelta(idx, np.clip(np.rint(vals / sp_scale), -127, 127)
                          .astype(np.int8), TOTAL, sp_scale)
    return [
        {"delta": dense, "clock": 0},
        {"delta": bf16, "clock": 0},
        {"delta": int8_decoded, "clock": 0},
        {"delta": SparseDelta(idx, vals, TOTAL), "clock": 0},
        {"delta": sp_int8.decoded(), "clock": 0},
    ]


@pytest.mark.parametrize("make_ps", [
    lambda kern: DeltaParameterServer(_blob(), apply_kernel=kern),
    lambda kern: ADAGParameterServer(_blob(), 3, apply_kernel=kern),
    lambda kern: DynSGDParameterServer(_blob(), apply_kernel=kern),
], ids=["delta", "adag", "dynsgd"])
def test_apply_path_bit_equal_native_vs_numpy(native, make_ps):
    rng = np.random.default_rng(9)
    msgs = _wire_msgs(rng)
    ps_numpy, ps_native = make_ps(None), make_ps("native")
    for m in msgs:
        ps_numpy.handle_commit(dict(m))
        ps_native.handle_commit(dict(m))
    # sequential applies agree bit for bit...
    for a, b in zip(ps_numpy.center, ps_native.center):
        np.testing.assert_array_equal(a, b)
    # ...and a coalesced drain of the same mixed forms does too
    ps_numpy2, ps_native2 = make_ps(None), make_ps("native")
    ps_numpy2.apply_drain([dict(m) for m in msgs])
    ps_native2.apply_drain([dict(m) for m in msgs])
    for a, b in zip(ps_numpy2.center, ps_native2.center):
        np.testing.assert_array_equal(a, b)


def test_scatter_add_helper_native_matches_numpy(native):
    rng = np.random.default_rng(11)
    center_a = [rng.standard_normal(s).astype(np.float32) for s in SHAPES]
    center_b = [c.copy() for c in center_a]
    idx = np.sort(rng.choice(TOTAL, 29, replace=False)).astype(np.int32)
    vals = rng.standard_normal(29).astype(np.float32)
    sp = SparseDelta(idx, vals, TOTAL)
    _scatter_add(center_a, sp, 0.5, kernel=None)
    _scatter_add(center_b, sp, 0.5, kernel=native)
    for a, b in zip(center_a, center_b):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# fallback + resolution (tier-1 safe: no native module required)
# ---------------------------------------------------------------------------

def test_python_fallback_serves_applies_when_native_absent(monkeypatch):
    """The satellite's smoke test: with the native module monkeypatched
    away, 'auto' resolves to the numpy path and the apply still works —
    the fallback can't rot unexercised on machines where the extension is
    always importable."""
    monkeypatch.setattr(applykernel, "_native", None)
    assert applykernel.resolve("auto") is None
    assert applykernel.resolve(None) is None
    assert applykernel.resolve("numpy") is None
    with pytest.raises(RuntimeError, match="not.*built|build_ext"):
        applykernel.resolve("native")
    ps = DeltaParameterServer(_blob(), apply_kernel="auto")
    assert ps._kernel is None  # the numpy path is live
    d = [np.full(s, 2.0, np.float32) for s in SHAPES]
    ps.handle_commit({"delta": d, "clock": 0})
    idx = np.array([0, 1], np.int32)
    ps.handle_commit({"delta": SparseDelta(idx, np.ones(2, np.float32),
                                           TOTAL), "clock": 0})
    assert ps.num_updates == 2
    np.testing.assert_array_equal(ps.center[0][:2], np.full(2, 3.0))
    np.testing.assert_array_equal(ps.center[0][2:], np.full(31, 2.0))


def test_resolve_rejects_unknown_names():
    with pytest.raises(ValueError, match="apply_kernel"):
        applykernel.resolve("cuda")
