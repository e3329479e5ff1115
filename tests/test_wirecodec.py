"""Native C++ wire codec (csrc/wirecodec.cpp) vs the pure-Python codec.

The two implementations must be byte-identical on the wire (either end of a
host-PS connection may run either one).  Builds the extension in place if it
isn't already built (the shared, locked ``tests/native_build.py``); skips
where no toolchain exists.

The ``codec`` fixture parametrizes the shared contract tests over BOTH
implementations — forcing ``networking._native = None`` routes every encode,
decode, and pooled-payload split through the pure-Python fallback
(``_decode_payload_py`` included), so the fallback can't rot unexercised on
machines where the native extension is always importable.
"""

import socket
import threading

import numpy as np
import pytest

from native_build import ensure_built

from distkeras_tpu import networking


def _ensure_native():
    if networking._native is None:
        error = ensure_built()
        if error is not None:
            pytest.skip(f"no native toolchain: {error}")
        import distkeras_tpu._wirecodec as native
        networking._native = native
    return networking._native


@pytest.fixture()
def native():
    old = networking._native
    yield _ensure_native()
    networking._native = old


@pytest.fixture(params=["python", "native"])
def codec(request):
    """Force one codec implementation for the duration of a test: 'python'
    nulls the native module (every path falls back to the pure-Python twin,
    ``_decode_payload_py`` included); 'native' requires/builds the
    extension."""
    old = networking._native
    networking._native = None if request.param == "python" \
        else _ensure_native()
    yield request.param
    networking._native = old


MESSAGE = {
    "weights": [np.arange(12, dtype=np.float32).reshape(3, 4),
                np.ones((5,), np.float64)],
    "clock": 7,
    "tag": "commit",
    "nested": {"t": (1, 2.5, None), "flag": True},
}


def test_native_and_python_bytes_identical(native):
    networking._native = native
    enc_native = networking.encode_message(MESSAGE)
    networking._native = None
    enc_python = networking.encode_message(MESSAGE)
    assert enc_native == enc_python


def test_cross_decoding(native):
    """Python-encoded → native-decoded and vice versa."""
    networking._native = None
    blob_py = networking.encode_message(MESSAGE)
    networking._native = native
    out = networking.decode_message(blob_py)
    np.testing.assert_array_equal(out["weights"][0], MESSAGE["weights"][0])
    assert out["nested"]["t"] == (1, 2.5, None)

    blob_nat = networking.encode_message(MESSAGE)
    networking._native = None
    out2 = networking.decode_message(blob_nat)
    np.testing.assert_array_equal(out2["weights"][1], MESSAGE["weights"][1])
    assert out2["clock"] == 7 and out2["tag"] == "commit"


def test_native_rejects_corrupt_frames(native):
    networking._native = native
    blob = bytearray(networking.encode_message(MESSAGE))
    with pytest.raises(ValueError, match="magic"):
        networking.decode_message(b"XXXX" + bytes(blob[4:]))
    with pytest.raises(ValueError):
        networking.decode_message(bytes(blob[:len(blob) - 3]))  # truncated


def test_native_decode_zero_copy(native):
    header, views = native.decode_frames(
        networking.encode_message(MESSAGE))
    assert all(isinstance(v, memoryview) for v in views)
    assert views[0].nbytes == 12 * 4


def test_roundtrip_large_delta(native):
    """Weight-delta-shaped message (the PS hot path) round-trips exactly."""
    networking._native = native
    rng = np.random.default_rng(0)
    delta = [rng.standard_normal((500, 500)).astype(np.float32),
             rng.standard_normal((500,)).astype(np.float32)]
    out = networking.decode_message(
        networking.encode_message({"delta": delta, "worker": 3}))
    for a, b in zip(out["delta"], delta):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# contract tests, parametrized over BOTH codec implementations
# ---------------------------------------------------------------------------

def test_roundtrip_either_codec(codec):
    out = networking.decode_message(networking.encode_message(MESSAGE))
    np.testing.assert_array_equal(out["weights"][0], MESSAGE["weights"][0])
    np.testing.assert_array_equal(out["weights"][1], MESSAGE["weights"][1])
    assert out["clock"] == 7 and out["nested"]["t"] == (1, 2.5, None)


def test_payload_decode_either_codec(codec):
    """decode_payload (the pooled-receive frame splitter) splits and
    truncation-checks identically on both implementations."""
    payload = b"".join(len(x).to_bytes(8, "little") + x
                       for x in (b"abc", b"", b"0123456789"))
    assert [bytes(v) for v in networking.decode_payload(payload)] == \
        [b"abc", b"", b"0123456789"]
    with pytest.raises(ValueError, match="Truncated"):
        networking.decode_payload(payload[:-3])


def test_pooled_recv_either_codec(codec):
    """The zero-copy pooled receive path (recv_data(pool=...) →
    decode_payload) works — and reuses its buffer — on both codecs."""
    pool = networking.BufferPool()
    a, b = socket.socketpair()
    msg = {"weights": [np.arange(24, dtype=np.float32).reshape(4, 6)],
           "clock": 5}
    try:
        for _ in range(2):
            t = threading.Thread(target=networking.send_data, args=(a, msg))
            t.start()
            out = networking.recv_data(b, pool=pool)
            t.join()
            np.testing.assert_array_equal(out["weights"][0],
                                          msg["weights"][0])
            assert out["clock"] == 5
        assert pool.misses == 1 and pool.hits == 1
        assert not out["weights"][0].flags["OWNDATA"]  # view into the pool
    finally:
        a.close()
        b.close()


def test_rejects_corrupt_frames_either_codec(codec):
    blob = networking.encode_message(MESSAGE)
    with pytest.raises(ValueError, match="magic"):
        networking.decode_message(b"XXXX" + blob[4:])
    with pytest.raises(ValueError):
        networking.decode_message(blob[:len(blob) - 3])  # truncated


SPARSE_MESSAGE = {
    "delta": networking.SparseDelta(
        np.array([0, 3, 7, 12], np.int32),
        np.array([0.5, -1.25, 2.0, -3.5], np.float32), 20),
    "coded": networking.SparseDelta(
        np.array([1, 2], np.int32), np.array([10, -20], np.int8), 6,
        scale=0.25),
    "worker_id": 1,
    "clock": 4,
}


def test_sparse_node_roundtrip_either_codec(codec):
    """The sparse payload node (indices + values + dense length, optional
    value scale) survives both codec implementations bit for bit."""
    out = networking.decode_message(networking.encode_message(SPARSE_MESSAGE))
    sp = out["delta"]
    assert isinstance(sp, networking.SparseDelta)
    np.testing.assert_array_equal(sp.indices,
                                  SPARSE_MESSAGE["delta"].indices)
    np.testing.assert_array_equal(sp.values, SPARSE_MESSAGE["delta"].values)
    assert sp.length == 20 and sp.scale is None
    coded = out["coded"]
    assert coded.values.dtype == np.int8 and coded.scale == 0.25
    np.testing.assert_allclose(coded.f32_values(), [2.5, -5.0])


def test_sparse_node_pooled_recv_either_codec(codec):
    """A sparse commit received through the zero-copy pooled path decodes to
    views over the pool; .decoded() detaches them for use past the next
    receive."""
    pool = networking.BufferPool()
    a, b = socket.socketpair()
    try:
        for _ in range(2):
            t = threading.Thread(target=networking.send_data,
                                 args=(a, SPARSE_MESSAGE))
            t.start()
            out = networking.recv_data(b, pool=pool)
            t.join()
            sp = out["delta"]
            np.testing.assert_array_equal(
                sp.indices, SPARSE_MESSAGE["delta"].indices)
            assert not sp.values.flags["OWNDATA"]  # view into the pool
            detached = sp.decoded()
            assert detached.values.flags["OWNDATA"]
        assert pool.misses == 1 and pool.hits == 1
    finally:
        a.close()
        b.close()


def test_encode_pool_bytes_identical_either_codec(codec):
    """The encode-side scratch pool (send-path satellite) produces byte-
    identical frames to the plain encoder, and reuses its buffer."""
    pool = networking.BufferPool()
    for msg in (MESSAGE, SPARSE_MESSAGE):
        plain = networking.encode_message(msg)
        assert bytes(networking.encode_message_into(msg, pool)) == plain
        assert bytes(networking.encode_message_into(msg, pool)) == plain
    assert pool.hits == 2  # one reuse per message size


def test_sparse_dense_equivalence_fuzz(codec):
    """Randomized dense↔sparse equivalence (fixed seed): for random tensor
    lists, densities, and value codings, selecting with topk_select,
    shipping through the codec, and scatter-adding on the far side equals
    the dense apply of the densified delta — and the EF invariant
    eff == applied + residual holds to coding precision."""
    from distkeras_tpu.parameter_servers import _scatter_add
    from distkeras_tpu.workers import topk_select

    rng = np.random.default_rng(1234)
    for trial in range(10):
        nt = rng.integers(1, 5)
        shapes = [tuple(rng.integers(1, 9, rng.integers(0, 3)))
                  for _ in range(nt)]
        total = sum(int(np.prod(s)) for s in shapes)
        eff = (rng.standard_normal(total) * 10.0 ** rng.integers(-3, 2)
               ).astype(np.float32)
        k = int(rng.integers(1, total + 1))
        code = [None, "bfloat16", "int8"][trial % 3]
        idx, wire, applied, scale, res = topk_select(eff, k, code)
        dense = np.zeros(total, np.float32)
        dense[idx] = applied
        np.testing.assert_allclose(eff, dense + res, atol=1e-6)
        sp = networking.decode_message(networking.encode_message(
            {"d": networking.SparseDelta(idx, wire, total, scale)}))["d"]
        center = [rng.standard_normal(s).astype(np.float32) for s in shapes]
        expect = [c.copy() for c in center]
        scale_f = float(rng.uniform(0.25, 2.0))
        _scatter_add(center, sp, scale_f)
        off = 0
        for c in expect:
            c += scale_f * dense[off:off + c.size].reshape(c.shape)
            off += c.size
        for got, want in zip(center, expect):
            np.testing.assert_allclose(got, want, atol=1e-5)


ROW_SPARSE_MESSAGE = {
    "delta": [np.ones((3,), np.float32),
              networking.RowSparseDelta(
                  np.array([0, 4, 9], np.int32),
                  np.arange(12, dtype=np.float32).reshape(3, 4), 16)],
    "worker_id": 2,
    "clock": 5,
}


def test_row_sparse_node_roundtrip_either_codec(codec):
    """The row-sparse payload node (rows + (k, dim) value block + dense row
    count) survives both codec implementations bit for bit, embedded in a
    mixed dense+row-sparse delta list (the wire form of a row_sparse
    commit)."""
    out = networking.decode_message(
        networking.encode_message(ROW_SPARSE_MESSAGE))
    dense, rsp = out["delta"]
    np.testing.assert_array_equal(dense, ROW_SPARSE_MESSAGE["delta"][0])
    assert isinstance(rsp, networking.RowSparseDelta)
    want = ROW_SPARSE_MESSAGE["delta"][1]
    np.testing.assert_array_equal(rsp.rows, want.rows)
    np.testing.assert_array_equal(rsp.values, want.values)
    assert rsp.num_rows == 16 and rsp.row_shape == (4,)
    np.testing.assert_array_equal(rsp.to_dense()[want.rows], want.values)


def test_row_sparse_node_pooled_recv_either_codec(codec):
    """A row-sparse commit through the zero-copy pooled path decodes to
    views over the pool; .decoded() detaches them."""
    pool = networking.BufferPool()
    a, b = socket.socketpair()
    try:
        for _ in range(2):
            t = threading.Thread(target=networking.send_data,
                                 args=(a, ROW_SPARSE_MESSAGE))
            t.start()
            out = networking.recv_data(b, pool=pool)
            t.join()
            rsp = out["delta"][1]
            assert not rsp.values.flags["OWNDATA"]  # view into the pool
            detached = rsp.decoded()
            assert detached.values.flags["OWNDATA"]
            np.testing.assert_array_equal(
                detached.values, ROW_SPARSE_MESSAGE["delta"][1].values)
        assert pool.misses == 1 and pool.hits == 1
    finally:
        a.close()
        b.close()


def test_row_sparse_slice_rows():
    """Shard splitting by row range: local re-indexing, empty middles,
    boundary rows land exactly once."""
    rsp = networking.RowSparseDelta(
        np.array([0, 4, 9, 10], np.int32),
        np.arange(8, dtype=np.float32).reshape(4, 2), 12)
    lo = rsp.slice_rows(0, 5)
    np.testing.assert_array_equal(lo.rows, [0, 4])
    hi = rsp.slice_rows(5, 12)
    np.testing.assert_array_equal(hi.rows, [4, 5])
    assert lo.num_rows == 5 and hi.num_rows == 7
    full = np.zeros((12, 2), np.float32)
    full[:5] += lo.to_dense()
    full[5:] += hi.to_dense()
    np.testing.assert_array_equal(full, rsp.to_dense())
    empty = rsp.slice_rows(5, 9)
    assert empty.nnz == 0 and empty.num_rows == 4


# --- decode guards: duplicate/negative/out-of-range/unsorted indices must
# --- reject with the typed ProtocolError, never corrupt the center

def _sp(idx, length=16):
    return networking.SparseDelta(np.asarray(idx, np.int32),
                                  np.ones(len(idx), np.float32), length)


def _rsp(rows, num_rows=16):
    return networking.RowSparseDelta(
        np.asarray(rows, np.int32),
        np.ones((len(rows), 3), np.float32), num_rows)


@pytest.mark.parametrize("make,label", [
    (lambda: _sp([3, 3, 7]), "duplicate"),
    (lambda: _sp([-1, 2, 7]), "negative"),
    (lambda: _sp([2, 7, 16]), "out-of-range"),
    (lambda: _sp([7, 2, 3]), "unsorted"),
    (lambda: _rsp([3, 3, 7]), "row-duplicate"),
    (lambda: _rsp([-1, 2, 7]), "row-negative"),
    (lambda: _rsp([2, 7, 16]), "row-out-of-range"),
    (lambda: _rsp([7, 2, 3]), "row-unsorted"),
])
def test_sparse_guard_rejects_bad_indices_either_codec(make, label, codec):
    """Hostile/corrupt index vectors survive the codec (the codec frames
    buffers, it doesn't interpret them) but validate() rejects them with
    the typed ProtocolError — a ValueError subclass, so every server
    handler's torn-frame path drops the connection."""
    node = make()
    out = networking.decode_message(
        networking.encode_message({"delta": node}))["delta"]
    with pytest.raises(networking.ProtocolError):
        out.validate()
    assert isinstance(networking.ProtocolError("x"), ValueError)


def test_sparse_guard_fuzz_valid_commits_pass(codec):
    """Randomized valid commits (sorted unique in-range indices) always
    pass validation after a codec round trip — the guard rejects only
    contract violations."""
    rng = np.random.default_rng(7)
    for _ in range(20):
        length = int(rng.integers(4, 200))
        k = int(rng.integers(0, min(length, 32) + 1))
        idx = np.sort(rng.choice(length, size=k, replace=False)).astype(
            np.int32)
        sp = networking.SparseDelta(idx, rng.standard_normal(k).astype(
            np.float32), length)
        networking.decode_message(networking.encode_message(
            {"d": sp}))["d"].validate()
        rows = int(rng.integers(2, 50))
        kk = int(rng.integers(0, rows + 1))
        rr = np.sort(rng.choice(rows, size=kk, replace=False)).astype(
            np.int32)
        rsp = networking.RowSparseDelta(
            rr, rng.standard_normal((kk, 3)).astype(np.float32), rows)
        networking.decode_message(networking.encode_message(
            {"d": rsp}))["d"].validate()


def test_sparse_guard_fuzz_corrupted_commits_reject(codec):
    """Fuzz: valid commits corrupted at a random index position (dup /
    negate / overflow) must reject after the round trip."""
    rng = np.random.default_rng(13)
    for trial in range(30):
        length = int(rng.integers(8, 100))
        k = int(rng.integers(2, min(length, 16) + 1))
        idx = np.sort(rng.choice(length, size=k, replace=False)).astype(
            np.int64)
        pos = int(rng.integers(0, k))
        kind = trial % 3
        if kind == 0:
            idx[pos] = idx[(pos + 1) % k]  # duplicate
        elif kind == 1:
            idx[pos] = -1 - idx[pos]  # negative
        else:
            idx[pos] = length + int(rng.integers(0, 5))  # out of range
        row_form = trial % 2 == 0
        if row_form:
            node = networking.RowSparseDelta(
                idx, np.ones((k, 2), np.float32), length)
        else:
            node = networking.SparseDelta(
                idx, np.ones(k, np.float32), length)
        out = networking.decode_message(
            networking.encode_message({"d": node}))["d"]
        with pytest.raises(networking.ProtocolError):
            out.validate()


# serving-protocol messages ('q' enqueue / 'r' stream reply —
# networking.SERVING_OP_ENQUEUE / SERVING_OP_STREAM): the request, ack,
# backpressure, chunk, and final frames the serving server exchanges must
# round-trip BOTH codec implementations unchanged (either end of a serving
# connection may run either one).

SERVING_FRAMES = [
    {"prompt": np.array([3, 4, 5, 6], np.int32), "num_steps": 16,
     "temperature": 0.7, "top_k": 5, "top_p": 0.9, "eos_id": 2,
     "pad_id": 0, "seed": 11},
    {"prompt": np.array([1], np.int32), "num_steps": 1},  # minimal request
    {"ok": True, "id": 7},
    {"ok": False, "error": "queue full"},                 # backpressure
    {"id": 7, "tokens": np.array([9, 4, 1], np.int32), "done": False},
    {"id": 7, "tokens": np.array([], np.int32), "done": True,
     "finish": "eos", "row": np.array([3, 4, 5, 6, 9, 4, 1, 2], np.int32)},
]


def test_serving_frames_roundtrip_either_codec(codec):
    assert len(networking.SERVING_OP_ENQUEUE) == 1
    assert len(networking.SERVING_OP_STREAM) == 1
    for frame in SERVING_FRAMES:
        out = networking.decode_message(networking.encode_message(frame))
        assert out.keys() == frame.keys()
        for key, want in frame.items():
            if isinstance(want, np.ndarray):
                np.testing.assert_array_equal(out[key], want)
                assert out[key].dtype == want.dtype
            else:
                assert out[key] == want and type(out[key]) is type(want)


def test_serving_frames_pooled_socket_roundtrip_either_codec(codec):
    """The serving wire pattern end to end: every frame kind through a
    socket with pooled receive AND pooled send, twice (buffer reuse)."""
    recv_pool = networking.BufferPool()
    send_pool = networking.BufferPool()
    a, b = socket.socketpair()
    try:
        for _ in range(2):
            for frame in SERVING_FRAMES:
                t = threading.Thread(target=networking.send_data,
                                     args=(a, frame),
                                     kwargs={"pool": send_pool})
                t.start()
                out = networking.recv_data(b, pool=recv_pool)
                t.join()
                assert out.keys() == frame.keys()
    finally:
        a.close()
        b.close()
    assert recv_pool.hits > 0 and send_pool.hits > 0


def test_buffer_pool_concurrent_get_safe():
    """BufferPool.get is thread-safe (the serving server's per-connection
    reuse pattern has several threads alive against pools): concurrent
    distinct-size acquisitions under an eviction-prone max_idle must not
    corrupt the bookkeeping dicts or lose buffers."""
    pool = networking.BufferPool(max_idle=4)
    errors = []

    def worker(wid):
        try:
            for i in range(300):
                buf = pool.get(64 + (wid * 7 + i) % 16)
                buf[0:1] = b"x"  # touch the buffer we were handed
        except Exception as e:  # pragma: no cover - the failure under test
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(w,)) for w in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert pool.hits + pool.misses == 8 * 300


def test_native_rejects_u64_overflow_lengths(native):
    """Hostile u64 lengths that would wrap `off + blen` must terminate with
    'Truncated', not loop or return empty buffers."""
    good = networking.encode_message({"w": np.zeros((4,), np.float32)})
    for evil in ((1 << 64) - 8, (1 << 64) - 1, (1 << 63)):
        tampered = bytearray(good)
        off = len(good) - 16 - 8
        tampered[off:off + 8] = evil.to_bytes(8, "little")
        with pytest.raises(ValueError, match="Truncated"):
            native.decode_frames(bytes(tampered))


# KV-block transfer node ('k' SERVING_OP_KVBLOCKS / __kvb__ — PR 16):
# a prefill engine ships a request's filled paged-KV blocks (plus int8
# scales, positions, RNG key) to a decode engine.  Like the sparse nodes,
# the codecs frame the buffers and validate() is the transport-boundary
# guard: hostile geometry must raise the typed ProtocolError before the
# receiving pool allocates anything.

def _kvb(int8=False, bs=4, nb=2, hkv=2, dh=3, seed=0):
    """A 3-layer KVBlocks (layer 0 cache-less, like an embedding layer)."""
    rng = np.random.default_rng(seed)
    rows = nb * bs
    layers = [None]
    for _ in range(2):
        if int8:
            c = {"k": rng.integers(-127, 128, (rows, hkv * dh)).astype(
                     np.int8),
                 "v": rng.integers(-127, 128, (rows, hkv * dh)).astype(
                     np.int8),
                 "ks": rng.random((rows, hkv)).astype(np.float32),
                 "vs": rng.random((rows, hkv)).astype(np.float32)}
        else:
            c = {"k": rng.standard_normal((rows, hkv * dh)).astype(
                     np.float32),
                 "v": rng.standard_normal((rows, hkv * dh)).astype(
                     np.float32)}
        layers.append(c)
    return networking.KVBlocks(layers, bs, nb, positions=rows - 1,
                               key=np.array([0, 11], np.uint32))


def test_kvblocks_opcode_distinct():
    ops = (networking.SERVING_OP_ENQUEUE, networking.SERVING_OP_STREAM,
           networking.SERVING_OP_CANCEL, networking.SERVING_OP_KVBLOCKS)
    assert len(networking.SERVING_OP_KVBLOCKS) == 1
    assert len(set(ops)) == len(ops)


@pytest.mark.parametrize("int8", [False, True],
                         ids=["dense", "int8-scales"])
def test_kvblocks_roundtrip_either_codec(codec, int8):
    """__kvb__ survives both codecs bit for bit: block geometry, positions,
    RNG key, per-layer k/v payloads (and int8 codes + per-entry scales),
    None layers preserved positionally."""
    kvb = _kvb(int8=int8)
    frame = {"blocks": kvb, "prompt": np.array([1, 2, 3], np.int32),
             "first_token": 9, "num_steps": 4}
    out = networking.decode_message(networking.encode_message(frame))
    got = out["blocks"]
    assert isinstance(got, networking.KVBlocks)
    assert got.block_size == kvb.block_size
    assert got.num_blocks == kvb.num_blocks
    assert got.positions == kvb.positions
    np.testing.assert_array_equal(got.key, kvb.key)
    assert got.key.dtype == np.uint32
    assert len(got.layers) == len(kvb.layers)
    assert got.layers[0] is None
    for mine, want in zip(got.layers[1:], kvb.layers[1:]):
        assert sorted(mine) == sorted(want)
        for k in want:
            np.testing.assert_array_equal(mine[k], want[k])
            assert mine[k].dtype == want[k].dtype
    assert got.nbytes == kvb.nbytes
    got.validate()  # a clean round trip must stay admissible


def test_kvblocks_pooled_recv_decoded_either_codec(codec):
    """Through the zero-copy pooled path the payloads are views into the
    reusable recv buffer; decoded() detaches them (what ServingServer
    must do before queueing past the next recv)."""
    pool = networking.BufferPool()
    kvb = _kvb(int8=True)
    a, b = socket.socketpair()
    try:
        for _ in range(2):
            t = threading.Thread(target=networking.send_data,
                                 args=(a, {"blocks": kvb}))
            t.start()
            out = networking.recv_data(b, pool=pool)
            t.join()
            got = out["blocks"]
            assert not got.layers[1]["k"].flags["OWNDATA"]
            det = got.validate().decoded()
            assert det.layers[1]["k"].flags["OWNDATA"]
            np.testing.assert_array_equal(det.layers[1]["k"],
                                          kvb.layers[1]["k"])
            np.testing.assert_array_equal(det.layers[2]["vs"],
                                          kvb.layers[2]["vs"])
        assert pool.misses == 1 and pool.hits == 1
    finally:
        a.close()
        b.close()


def _corrupt(kvb, how):
    if how == "zero-blocks":
        kvb.num_blocks = 0
    elif how == "positions-zero":
        kvb.positions = 0
    elif how == "positions-overflow":
        kvb.positions = kvb.num_blocks * kvb.block_size + 1
    elif how == "missing-v":
        del kvb.layers[1]["v"]
    elif how == "unknown-payload":
        kvb.layers[1]["evil"] = kvb.layers[1]["k"]
    elif how == "row-count-lie":
        kvb.layers[1]["k"] = kvb.layers[1]["k"][:-1]
        kvb.layers[1]["v"] = kvb.layers[1]["v"][:-1]
    elif how == "kv-dtype-split":
        kvb.layers[1]["v"] = kvb.layers[1]["v"].astype(np.float64)
    elif how == "half-scales":
        del kvb.layers[1]["vs"]
    elif how == "scales-on-dense":
        kvb.layers[1]["ks"] = np.ones(kvb.layers[1]["k"].shape[:2],
                                      np.float32)
        kvb.layers[1]["vs"] = kvb.layers[1]["ks"]
    elif how == "scale-shape-lie":
        kvb.layers[1]["ks"] = kvb.layers[1]["ks"][:, :1]
    elif how == "no-layers":
        kvb.layers = [None, None, None]
    elif how == "signed-key":
        kvb.key = np.array([-1, 2], np.int64)
    return kvb


@pytest.mark.parametrize("how", [
    "zero-blocks", "positions-zero", "positions-overflow", "missing-v",
    "unknown-payload", "row-count-lie", "kv-dtype-split", "no-layers",
    "signed-key"])
def test_kvblocks_hostile_rejects_either_codec(codec, how):
    """Hostile/torn block frames survive the codec (it frames buffers,
    it doesn't interpret them) but validate() rejects with the typed
    ProtocolError — the serving server's ValueError shed path."""
    kvb = _corrupt(_kvb(), how)
    out = networking.decode_message(
        networking.encode_message({"blocks": kvb}))["blocks"]
    with pytest.raises(networking.ProtocolError):
        out.validate()


@pytest.mark.parametrize("how", ["half-scales", "scales-on-dense",
                                 "scale-shape-lie"])
def test_kvblocks_hostile_scale_rejects_either_codec(codec, how):
    kvb = _corrupt(_kvb(int8=(how != "scales-on-dense")), how)
    out = networking.decode_message(
        networking.encode_message({"blocks": kvb}))["blocks"]
    with pytest.raises(networking.ProtocolError):
        out.validate()
