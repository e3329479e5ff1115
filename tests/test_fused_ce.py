"""Fused softmax-cross-entropy kernel vs the XLA oracle.

The oracle is plain ``log_softmax`` + gather (what
``core.losses.sparse_categorical_crossentropy`` computes); the kernel must
match it in value and logits-gradient, including ragged (non-block-multiple)
shapes, bf16 inputs, and use inside the parallel LM's loss.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.ops.fused_ce import fused_softmax_cross_entropy


def oracle(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[:, None], axis=-1)[:, 0]


def rand(t, v, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=(t, v)) * 3.0, dtype)
    labels = jnp.asarray(rng.integers(0, v, size=(t,)), jnp.int32)
    return logits, labels


@pytest.mark.parametrize("t,v", [(8, 16), (256, 512), (300, 1000),
                                 (7, 130), (64, 50257 % 2048)])
def test_value_matches_oracle(t, v):
    logits, labels = rand(t, v, seed=t + v)
    got = fused_softmax_cross_entropy(logits, labels,
                                      block_t=64, block_v=128)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(oracle(logits, labels)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t,v", [(32, 64), (100, 300)])
def test_grad_matches_oracle(t, v):
    logits, labels = rand(t, v, seed=3)
    w = jnp.asarray(np.random.default_rng(1).normal(size=(t,)), jnp.float32)

    # weighted sum exercises a non-uniform cotangent
    g_fused = jax.grad(lambda lg: jnp.sum(
        w * fused_softmax_cross_entropy(lg, labels, block_t=32,
                                        block_v=64)))(logits)
    g_ref = jax.grad(lambda lg: jnp.sum(w * oracle(lg, labels)))(logits)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-5)


def test_bf16_logits_grad_dtype_and_value():
    logits, labels = rand(64, 128, seed=5, dtype=jnp.bfloat16)
    loss = fused_softmax_cross_entropy(logits, labels)
    assert loss.dtype == jnp.float32
    g = jax.grad(lambda lg: jnp.sum(
        fused_softmax_cross_entropy(lg, labels)))(logits)
    assert g.dtype == jnp.bfloat16
    g_ref = jax.grad(lambda lg: jnp.sum(oracle(lg, labels)))(
        logits.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(g, np.float32),
                               np.asarray(g_ref), rtol=0.05, atol=0.02)


def test_extreme_logits_stable():
    """Online-softmax must survive ±1e4 logits without overflow."""
    logits = jnp.array([[1e4, 0.0, -1e4, 5.0] * 32] * 8, jnp.float32)
    labels = jnp.zeros((8,), jnp.int32)
    got = fused_softmax_cross_entropy(logits, labels, block_v=32)
    assert np.isfinite(np.asarray(got)).all()
    # blockwise vs whole-row summation order differs at ~1e-5 relative
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(oracle(logits, labels)),
                               rtol=1e-4, atol=1e-5)


def test_jit_and_vocab_one_block():
    logits, labels = rand(16, 32, seed=9)
    f = jax.jit(lambda lg, lb: fused_softmax_cross_entropy(lg, lb))
    np.testing.assert_allclose(np.asarray(f(logits, labels)),
                               np.asarray(oracle(logits, labels)),
                               rtol=1e-5, atol=1e-5)


def test_inside_parallel_lm_loss(eight_devices):
    """ParallelTransformerLM(fused_ce=True) trains to the same losses as
    the XLA loss path on a dp×tp mesh."""
    import optax
    from jax.sharding import Mesh
    from distkeras_tpu.parallel.transformer import ParallelTransformerLM

    devs = np.array(jax.devices()[:4]).reshape(2, 1, 2)
    mesh = Mesh(devs, ("data", "seq", "model"))

    def run(fused):
        lm = ParallelTransformerLM(
            vocab_size=48, seq_len=16, d_model=16, num_heads=2,
            num_layers=2, mlp_dim=32, mesh=mesh,
            compute_dtype=jnp.float32, fused_ce=fused)
        params = lm.init(jax.random.PRNGKey(11))
        opt_state, step = lm.compile_train_step(optax.adam(1e-2), params)
        rng = np.random.default_rng(2)
        toks = rng.integers(0, 48, (8, 16)).astype(np.int32)
        labels = (toks + 1) % 48
        sh = lm.batch_sharding()
        toks, labels = jax.device_put(toks, sh), jax.device_put(labels, sh)
        losses = []
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, toks, labels)
            losses.append(float(loss))
        return losses

    np.testing.assert_allclose(run(True), run(False), rtol=1e-5)


# -- the kernel behind core.losses (PR 33) -----------------------------------
#
# ``core.losses._sparse_nll`` takes the kernel by ``fused_ce_applies`` (a TPU,
# f32/bf16, a vocabulary of a lane block).  Off the TPU the rule
# says XLA, so these tests steer it as ``tests/test_paged_attention.py``
# steers the paged kernel: ``losses._on_tpu`` patched to True, the kernel
# itself in interpret mode (``interpret=None`` asks the real backend).

from distkeras_tpu.core import losses
from distkeras_tpu.core.losses import fused_ce_applies, get_loss, per_example

SPARSE = "sparse_categorical_crossentropy_from_logits"
MASKED = "sparse_categorical_crossentropy_masked_from_logits"


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(losses, "_on_tpu", lambda: True)


def kernels_in(fn, *args):
    """Names of the Pallas kernels in ``fn``'s jaxpr (traced through a
    fresh wrapper: jax keeps traces by function and shapes, and the patched
    rule is no part of that key)."""
    text = str(jax.make_jaxpr(lambda *a: fn(*a))(*args))
    return sorted({k for k in ("fused_ce_fwd", "fused_ce_bwd") if k in text})


RULE = [  # logits' shape, dtype, a TPU underneath -> the kernel?
    ((8, 1024, 50257), jnp.float32, True, True),  # train-adag-gpt2s's own
    ((8, 1024, 50257), jnp.bfloat16, True, True),
    ((2048, 8192), jnp.float32, True, True),
    ((8, 128, 1000), jnp.float32, True, True),    # a small LM: no size asked
    ((1, 50257), jnp.float32, True, True),        # one token
    ((1, 128), jnp.float32, True, True),          # ... of one lane block
    ((131072, 128), jnp.float32, True, True),     # AT the vocabulary's edge
    ((262144, 127), jnp.float32, True, False),    # under a lane block
    ((1 << 24, 10), jnp.float32, True, False),    # MNIST's classes, any size
    ((1 << 24, 2), jnp.float32, True, False),     # the Higgs job's
    ((8, 1024, 50257), jnp.float16, True, False),
    ((1 << 24,), jnp.float32, True, False),       # no token axis
    ((8, 1024, 50257), jnp.float32, False, False),  # off the TPU
]


@pytest.mark.parametrize("shape,dtype,tpu,want", RULE, ids=lambda v: (
    "x".join(map(str, v)) if isinstance(v, tuple) else
    str(v) if isinstance(v, bool) else np.dtype(v).name))
def test_rule_by_shape_dtype_and_backend(monkeypatch, shape, dtype, tpu,
                                         want):
    monkeypatch.setattr(losses, "_on_tpu", lambda: tpu)
    assert fused_ce_applies(shape, dtype) is want


@pytest.mark.parametrize("t,v,want", [
    (8192, 50257, (256, 2048)),   # train-adag-gpt2s's own
    (8192, 1000, (256, 896)),     # whole lane tiles under the width
    (100, 50304, (100, 2048)),    # a short operand: one block of rows
    (64, 100, (64, 100)),         # narrower than a lane tile: one block
])
def test_tiles_follow_the_shape(t, v, want):
    from distkeras_tpu.ops.fused_ce import _tiles
    assert _tiles(t, v) == want


def xla_nll(labels, logits):
    """The parent's per-token form: log_softmax + take_along_axis."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1)[..., 0]


def rand_nd(shape, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=shape) * 3.0, dtype),
            jnp.asarray(rng.integers(0, shape[-1], shape[:-1]), jnp.int32))


HELPER = [  # shape, dtype, on the kernel's side of the rule?
    ((300, 128), jnp.float32, True),       # AT the rule's edge
    ((300, 127), jnp.float32, False),      # one column under it
    ((520, 1000), jnp.float32, True),      # no multiple of 128
    ((334, 50257), jnp.float32, True),     # the cell's ragged vocabulary
    ((334, 50257), jnp.bfloat16, True),
    ((2, 256, 2048), jnp.float32, True),   # (batch, seq, vocab)
    ((64, 10), jnp.float32, False),
]


@pytest.mark.parametrize("shape,dtype,kernel", HELPER, ids=lambda v: (
    "x".join(map(str, v)) if isinstance(v, tuple) else
    str(v) if isinstance(v, bool) else np.dtype(v).name))
def test_per_token_form_matches_xla(on_tpu, shape, dtype, kernel):
    """Value and gradient of the one per-token helper against XLA's
    log_softmax + take_along_axis, on both sides of the rule and at its
    edge; the kernel is in the program exactly where the rule says."""
    logits, labels = rand_nd(shape, seed=sum(shape), dtype=dtype)
    w = jnp.asarray(np.random.default_rng(1).normal(size=shape[:-1]),
                    jnp.float32)
    loss = lambda nll: lambda lg: jnp.sum(w * nll(labels, lg))
    helper = lambda lb, lg: losses._sparse_nll(lb, lg, True)

    grad = jax.grad(loss(helper))
    assert kernels_in(grad, logits) == (
        ["fused_ce_bwd", "fused_ce_fwd"] if kernel else [])
    got, want = helper(labels, logits), xla_nll(labels, logits)
    assert got.dtype == jnp.float32 and got.shape == labels.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    g, g_ref = grad(logits), jax.grad(loss(xla_nll))(logits)
    assert g.dtype == logits.dtype
    bf16 = dtype == jnp.bfloat16  # one rounding of the gradient's values
    np.testing.assert_allclose(np.asarray(g, np.float32),
                               np.asarray(g_ref, np.float32),
                               rtol=0.02 if bf16 else 1e-4,
                               atol=0.02 if bf16 else 1e-5)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_masked_loss_skips_negative_labels(monkeypatch, kernel):
    """Labels -1 (packing) through the masked loss: the valid positions'
    mean, and an exactly zero row of the logits' gradient."""
    monkeypatch.setattr(losses, "_on_tpu", lambda: kernel)
    logits, labels = rand_nd((4, 128, 1024), seed=11)
    labels = labels.at[1].set(-1).at[2, 100:].set(-1)
    fn = get_loss(MASKED)
    assert bool(kernels_in(fn, labels, logits)) is kernel
    valid = np.asarray(labels) >= 0
    want = np.asarray(xla_nll(jnp.maximum(labels, 0), logits))[valid].mean()
    np.testing.assert_allclose(float(fn(labels, logits)), want, rtol=1e-5)
    g = np.asarray(jax.grad(lambda lg: fn(labels, lg))(logits))
    assert not g[~valid].any() and g[valid].any()
    rows = np.asarray(per_example(fn)(labels, logits))
    assert rows[1] == 0.0  # a row with no valid position
    nll = np.asarray(xla_nll(jnp.maximum(labels, 0), logits))
    np.testing.assert_allclose(rows[2], nll[2, :100].mean(), rtol=1e-5)


@pytest.mark.parametrize("name", [SPARSE, MASKED,
                                  "sparse_categorical_crossentropy",
                                  "sparse_categorical_crossentropy_masked"])
def test_per_example_is_the_native_form_for_the_sparse_losses(on_tpu, name):
    """One call on the whole batch — the kernel sees (tokens, vocab), no
    ``pallas_call`` under ``vmap`` — and each row reads what the
    mean-reducing loss gives on that row alone."""
    logits, labels = rand_nd((4, 128, 1024), seed=5)
    pred = logits if "logits" in name else jax.nn.softmax(logits)
    fn = get_loss(name)
    rows = per_example(fn)
    text = str(jax.make_jaxpr(lambda *a: rows(*a))(labels, pred))
    assert ("fused_ce_fwd" in text) is ("logits" in name)
    if "logits" in name:
        assert "f32[512,1024]" in text and "f32[1,128,1024]" not in text
    want = [float(fn(labels[i:i + 1], pred[i:i + 1])) for i in range(4)]
    np.testing.assert_allclose(np.asarray(rows(labels, pred)), want,
                               rtol=1e-5)


def test_per_example_vmaps_a_custom_callable(on_tpu):
    """Anything but the resolved sparse losses keeps the vmap of singleton
    batches: a custom callable (here one that WRAPS the sparse loss, so it
    is no key of the table) and a name without a per-row form."""
    logits, labels = rand_nd((4, 64, 256), seed=6)
    custom = lambda yt, yp: get_loss(SPARSE)(yt, yp) * 2.0
    got = per_example(custom)(labels, logits)
    want = [2.0 * float(get_loss(SPARSE)(labels[i:i + 1], logits[i:i + 1]))
            for i in range(4)]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    for fn in (custom, get_loss("mse")):
        assert per_example(fn) not in losses._PER_ROW.values()
    assert per_example(get_loss(SPARSE)) in losses._PER_ROW.values()


def tiny_lm(vocab=1024, seq=128):
    from distkeras_tpu.models import transformer_lm
    return transformer_lm(vocab_size=vocab, seq_len=seq, d_model=32,
                          num_heads=2, num_layers=1, mlp_dim=64,
                          compute_dtype="float32")


def lm_batch(rows, vocab=1024, seq=128, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, vocab, (rows, seq)).astype(np.int32)
    return x, (x + 1) % vocab


@pytest.mark.parametrize("weights", [(1, 0, 1, 0), (0, 0, 0, 0)],
                         ids=["half-padding", "all-padding"])
def test_masked_step_weight_zero_rows(monkeypatch, weights):
    """Weight-0 rows through ``make_masked_loss_fn`` on the kernel's side:
    the gradient is that of the live rows alone (XLA's side agrees), and
    the all-padding step is still a true no-op."""
    import optax
    from distkeras_tpu.core.train import (make_masked_loss_fn,
                                          make_masked_step)
    model = tiny_lm()
    params = model.init(jax.random.PRNGKey(0))
    x, y = lm_batch(4)
    w = jnp.asarray(weights, jnp.float32)
    key = jax.random.PRNGKey(1)

    def grads(tpu):
        monkeypatch.setattr(losses, "_on_tpu", lambda: tpu)
        compute = make_masked_loss_fn(model, SPARSE)
        assert bool(kernels_in(compute, params, x, y, w, key)) is tpu
        return jax.value_and_grad(compute, has_aux=True)(params, x, y, w,
                                                         key)

    (l_k, _), g_k = grads(True)
    (l_x, _), g_x = grads(False)
    np.testing.assert_allclose(float(l_k), float(l_x), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_k),
                    jax.tree_util.tree_leaves(g_x)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-6)
    live = np.asarray(weights, bool)
    if not live.any():
        assert all(not np.asarray(g).any()
                   for g in jax.tree_util.tree_leaves(g_k))
        monkeypatch.setattr(losses, "_on_tpu", lambda: True)
        tx = optax.adam(1e-3)
        opt = tx.init(params)
        new_p, new_opt, _, wsum = make_masked_step(model, SPARSE, tx)(
            params, opt, x, y, w, key)
        assert float(wsum) == 0.0
        same = lambda a, b: all(
            np.array_equal(np.asarray(u), np.asarray(v)) for u, v in zip(
                jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
        assert same(new_p, params) and same(new_opt, opt)


def _train_lm(trainer):
    from distkeras_tpu import ADAG, Dataset, SingleTrainer
    x, y = lm_batch(16, seed=3)
    kw = dict(batch_size=4, num_epoch=2, loss=SPARSE,
              worker_optimizer="adam", learning_rate=1e-3)
    if trainer == "adag":
        t = ADAG(tiny_lm(), num_workers=2, communication_window=2, **kw)
    else:
        t = SingleTrainer(tiny_lm(), **kw)
    t.train(Dataset({"features": x, "label": y}))
    return np.asarray(t.history, np.float32)


@pytest.mark.parametrize("trainer", ["single", "adag"])
def test_trainers_read_the_same_losses_on_either_side_of_the_rule(
        monkeypatch, trainer):
    """Two epochs on a tiny ``transformer_lm`` (batches of 4 x 128 x
    1,024 logits): the same losses to f32 rounding
    with the kernel (``SingleTrainer``: interpret mode inside the epoch
    scan; ADAG: ``shard_map`` off the TPU takes the kernel's own XLA
    fall-back, so its run holds the flatten and the per-row reduction) and
    with XLA's fused form."""
    monkeypatch.setattr(losses, "_on_tpu", lambda: True)
    kernel = _train_lm(trainer)
    monkeypatch.setattr(losses, "_on_tpu", lambda: False)
    xla = _train_lm(trainer)
    assert kernel.shape == xla.shape and np.isfinite(kernel).all()
    np.testing.assert_allclose(kernel, xla, rtol=2e-5)


def parent_sparse_ce(y_true, y_pred):
    """``sparse_categorical_crossentropy`` as the parent of PR 33 had it, a
    custom callable to the trainer: it goes through ``per_example``'s vmap
    of singleton batches, which is the parent's whole loss program."""
    logp = jnp.log(jnp.clip(y_pred.astype(jnp.float32), 1e-7, 1.0))
    idx = y_true.astype(jnp.int32)
    picked = jnp.take_along_axis(logp, idx[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)


@pytest.mark.parametrize("tpu", [False, True], ids=["cpu", "tpu-rule"])
def test_narrow_model_trains_bit_identically_to_the_parent(monkeypatch, tpu):
    """MNIST's 10 classes never see the kernel, whatever the backend: the
    MLP's losses under the sparse name are bit for bit those of the
    parent's loss program."""
    from distkeras_tpu import ADAG, Dataset
    from distkeras_tpu.models import mnist_mlp
    monkeypatch.setattr(losses, "_on_tpu", lambda: tpu)
    rng = np.random.default_rng(0)
    data = Dataset({"features": rng.normal(size=(64, 784)).astype(np.float32),
                    "label": rng.integers(0, 10, 64).astype(np.int32)})

    def run(loss):
        t = ADAG(mnist_mlp("float32"), num_workers=2, batch_size=8,
                 num_epoch=2, communication_window=2, loss=loss,
                 worker_optimizer="adam", learning_rate=1e-3)
        t.train(data)
        return list(t.history)

    assert run("sparse_categorical_crossentropy") == run(parent_sparse_ce)
