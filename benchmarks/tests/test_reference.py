"""The plain reference against the system at tiny widths, the lower-precision
controls failing, and a run with the timed path broken underneath coming out
not correct."""

import numpy as np
import pytest

from benchmarks.lib import manifest as mf, program, reference
from benchmarks.lib.weights import make_weights

import helpers


@pytest.fixture(scope="module")
def tiny():
    man = mf.Manifest()
    return mf.resolve_sizes(man.config("gpt2-small"), True)


def test_reference_logits_match_the_systems_forward(tiny):
    import jax
    model = program.build_model(tiny)
    params = program.program_params(tiny, 5)
    toks = np.random.default_rng(0).integers(
        0, tiny["vocab_size"], (2, tiny["n_positions"])).astype(np.int32)
    got = np.asarray(jax.jit(model.apply)(params, toks), np.float32)
    want = np.asarray(reference.logits_fn(make_weights(tiny, 5), toks,
                                          tiny["n_head"]))
    # bf16 compute against f32 HIGHEST: 8 bits of mantissa on logits of
    # scale ~0.5
    assert np.abs(got - want).max() < 0.02
    assert np.abs(got - want).max() > 0       # not the same arithmetic


def test_reference_imports_nothing_of_the_program():
    import ast
    import inspect
    for mod in (reference, __import__("benchmarks.lib.weights",
                                      fromlist=["x"])):
        tree = ast.parse(inspect.getsource(mod))
        names = [n.module or "" for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)]
        names += [a.name for n in ast.walk(tree)
                  if isinstance(n, ast.Import) for a in n.names]
        assert not any("distkeras" in n or "program" in n for n in names)


def test_int8_matmul_is_lower_precision_with_an_unquantised_gradient():
    import jax
    import jax.numpy as jnp
    a = jnp.asarray(np.random.default_rng(0).normal(size=(4, 16)),
                    jnp.float32)
    b = jnp.asarray(np.random.default_rng(1).normal(size=(16, 8)),
                    jnp.float32)
    exact, low = reference.f32_matmul(a, b), reference.int8_matmul(a, b)
    err = float(jnp.abs(exact - low).max())
    assert 1e-4 < err < 0.5
    g = jax.grad(lambda x: reference.int8_matmul(x, b).sum())(a)
    # straight-through: the derivative is taken at the quantised weights,
    # which lie within half a step (absmax / 254) of the true ones
    assert float(jnp.abs(g - b.sum(1)).max()) < 8 * float(
        jnp.abs(b).max()) / 254 + 1e-6


def test_adag_rounds_with_one_worker_is_plain_adam(tiny):
    w = make_weights(tiny, 2)
    toks = np.random.default_rng(0).integers(0, 64, (2, 32)).astype(np.int32)
    batch = (toks, (toks + 1) % 64)
    losses, grads, center = reference.adag_rounds(
        w, [[[batch, batch]], [[batch]]], tiny["n_head"], lr=1e-3)
    assert losses[0][0][0] > losses[0][0][1] > losses[1][0][0]
    # Adam's first step moves every live coordinate by lr
    import jax
    step = jax.tree_util.tree_map(lambda a, b: np.abs(np.asarray(a - b)),
                                  center, w)
    assert 2.5e-3 < float(np.median(step["w1"])) < 3.1e-3
    assert len(grads) == 1


def test_adag_rounds_center_moves_by_the_mean_of_the_workers(tiny):
    import jax
    w = make_weights(tiny, 2)
    rng = np.random.default_rng(0)
    a = rng.integers(0, 64, (2, 32)).astype(np.int32)
    b = rng.integers(0, 64, (2, 32)).astype(np.int32)
    one = lambda t: [[[(t, (t + 1) % 64)]]]
    both = [[[(a, (a + 1) % 64)], [(b, (b + 1) % 64)]]]
    _, _, ca = reference.adag_rounds(w, one(a), tiny["n_head"], lr=1e-3)
    _, _, cb = reference.adag_rounds(w, one(b), tiny["n_head"], lr=1e-3)
    _, grads, cab = reference.adag_rounds(w, both, tiny["n_head"], lr=1e-3)
    want = jax.tree_util.tree_map(lambda x, y: (x + y) / 2, ca, cb)
    for k in ("w1", "wte", "head_w"):
        assert np.allclose(np.asarray(cab[k]), np.asarray(want[k]),
                           atol=1e-7)
    assert len(grads) == 2


# -- whole runs, without the look for a chip ----------------------------------

def test_training_cell_end_to_end_is_correct(tmp_path):
    res = helpers.run(helpers.context("train-adag-gpt2s", seed=2 ** 31 + 9,
                                      seconds=2, tmp=tmp_path))
    assert res.correct, [c.line() for c in res.compared]
    assert {c.name for c in res.compared} >= {
        "loss_gap_step1", "loss_gap_step2", "grad_norm_gap",
        "grad_diff_gap", "update_norm_gap"}
    assert res.attempted >= 2 and res.failed == 0
    assert res.end_to_end["train_tokens_per_s"] > 0
    assert res.end_to_end["setup_s"] > 0


def test_training_control_in_int8_is_not_correct(tmp_path):
    """The reference with int8 matmuls in the program's place, at the test's
    size: it fails the number that separates (the first gradient's
    difference), as it does at the cell's own size on the chip."""
    from benchmarks.lib.weights import fold_seed
    ctx = helpers.context("train-adag-gpt2s", seed=3, seconds=1, tmp=tmp_path)
    drv, job = mf.load_driver("train_adag"), ctx.traffic
    rows = int(job["steps_per_epoch"]) * int(job["trainer"]["batch_size"])
    toks, labels = drv.corpus(fold_seed(3), rows, int(job["seq_len"]),
                              int(job["token_range"]))
    by = {c.name: c for c in drv.check(
        ctx, None, toks, labels,
        in_place=job["controls"]["int8"]["matmul"])}
    assert not by["grad_diff_gap"].ok
    assert by["loss_gap_step1"].ok      # a number it hardly moves


def test_training_check_names_what_a_refactored_trainer_no_longer_has():
    class Refactored:           # a trainer without the private names
        seed = 0
    with pytest.raises(RuntimeError, match=r"_engine.*README"):
        mf.load_driver("train_adag").window_program(Refactored())


def test_training_cell_with_the_step_broken_is_not_correct(tmp_path,
                                                           monkeypatch):
    """The epoch program returns its state unchanged: losses still come out,
    nothing is learnt, and the center never moves."""
    from distkeras_tpu.parallel import spmd
    real = spmd.SPMDEngine.run_epoch

    def unchanged(self, state, xb, yb, mb, rngs, sb=None):
        import jax
        keep = jax.tree_util.tree_map(lambda a: a + 0, state)
        _, losses = real(self, state, xb, yb, mb, rngs, sb=sb)
        return keep, losses
    monkeypatch.setattr(spmd.SPMDEngine, "run_epoch", unchanged)
    res = helpers.run(helpers.context("train-adag-gpt2s", seed=3, seconds=2,
                                      tmp=tmp_path))
    assert not res.correct
    by = {c.name: c for c in res.compared}
    assert not by["update_norm_gap"].ok and by["update_norm_gap"].value > 0.9


def test_training_cell_with_part_of_the_batch_left_out_is_not_correct(
        tmp_path, monkeypatch):
    """Half of every batch masked out inside the engine: the loss of a step
    is then the mean over other rows than the reference's."""
    from distkeras_tpu.parallel import spmd
    real = spmd.SPMDEngine.run_epoch

    def half(self, state, xb, yb, mb, rngs, sb=None):
        mb = np.array(mb)
        mb[..., ::2] = 0.0
        return real(self, state, xb, yb, mb, rngs, sb=sb)
    monkeypatch.setattr(spmd.SPMDEngine, "run_epoch", half)
    res = helpers.run(helpers.context("train-adag-gpt2s", seed=3, seconds=2,
                                      tmp=tmp_path))
    by = {c.name: c for c in res.compared}
    assert not res.correct and not by["loss_gap_step1"].ok


def test_serving_cell_end_to_end_is_correct(tmp_path):
    res = helpers.run(helpers.context("serve-chat-gpt2m", seed=2 ** 31 + 9,
                                      seconds=3, tmp=tmp_path))
    assert res.correct, [c.line() for c in res.compared]
    assert res.attempted == 18 and res.failed == 0
    for k in ("ttft_p95_ms", "itl_p95_ms", "serve_tokens_per_s", "setup_s"):
        assert res.end_to_end[k] > 0
    assert res.records["decode_steps"] > 0
    assert 0 < res.records["active_slot_steps"] <= (
        res.records["decode_steps"] * res.records["num_slots"])


def test_serving_control_reference_in_int8_is_not_correct(tmp_path,
                                                          monkeypatch):
    """The int8 reference's own tokens in the program's place: they lie as
    far below the float32 reference's best as the yardstick itself, so the
    number reads 1 against the cell's limit."""
    ctx = helpers.context("serve-chat-gpt2m", seed=1, seconds=4, tmp=tmp_path)
    # the cell's own limits (the tiny block holds only the widest gap)
    ctx.traffic["correct"]["limits"] = dict(
        mf.Manifest().traffic("chat-open-poisson")["correct"]["limits"],
        served_token_gap=0.05)
    drv = mf.load_driver("serve_engine")
    real = drv.check
    monkeypatch.setattr(drv, "check", lambda c, served, below: real(
        c, served, below, low_in_place=True))
    res = drv.run(ctx)          # this module object: the one patched
    by = {c.name: c for c in res.compared}
    assert by["served_mean_gap_vs_int8"].value == 1.0
    assert not res.correct and not by["served_mean_gap_vs_int8"].ok
    assert by["served_token_gap"].ok and by["precision_below_stated"].ok


@pytest.mark.parametrize("control", ["int8_kv", "int8_weights"])
def test_serving_engines_own_lower_precision_path_is_not_correct(
        control, tmp_path):
    """The program's own path switched on (the configuration's ``controls``
    laid over its deployment): keys and values, or weights, held in int8
    where the configuration states bf16 and f32.  Its greedy tokens are as
    close to the reference as the bf16 path's, here and at the cell's size on
    the chip (PERF.md), so what fails is the exact comparison of the types
    held with the types stated."""
    ctx = helpers.context("serve-chat-gpt2m", seed=1, seconds=3, tmp=tmp_path)
    ctx.cfg = mf.deep_merge(ctx.cfg, {"deployment": {
        "engine": ctx.cfg["controls"][control]["engine"]}})
    res = helpers.run(ctx)
    by = {c.name: c for c in res.compared}
    assert not res.correct and res.failed == 0
    assert by["precision_below_stated"].value > 0
    assert by["served_token_gap"].ok    # the tokens alone do not show it


def test_serving_cell_with_a_token_altered_is_not_correct(tmp_path,
                                                          monkeypatch):
    """Every tenth token replaced where the engine hands it out."""
    from distkeras_tpu import serving
    real = serving.RequestHandle._push
    count = [0]

    def altered(self, token):
        count[0] += 1
        return real(self, (token + 1) % 512 if count[0] % 10 == 0 else token)
    monkeypatch.setattr(serving.RequestHandle, "_push", altered)
    res = helpers.run(helpers.context("serve-chat-gpt2m", seed=5, seconds=3,
                                      tmp=tmp_path))
    assert not res.correct


def test_closed_loop_shared_prefix_cell_is_data_only(tmp_path):
    cell = dict(name="serve-agent-fixture", config="gpt2-medium",
                traffic="agent-closed-prefix", chips=1, why="fixture")
    res = helpers.run(helpers.context(cell, "agent-closed-prefix.json",
                                      seed=4, seconds=2, tmp=tmp_path))
    assert res.correct, [c.line() for c in res.compared]
    assert res.attempted > 6 and res.failed == 0


def test_four_worker_training_cell_is_data_only(tmp_path):
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual CPU devices")
    man = mf.Manifest()
    cell = dict(name="train-adag-fixture-x4", config="gpt2-small",
                traffic="adag-lm-8x1024", chips=4, why="fixture")
    res = helpers.run(helpers.context(cell, man.traffic("adag-lm-8x1024"),
                                      seed=6, seconds=1, tmp=tmp_path))
    assert res.correct, [c.line() for c in res.compared]
    assert res.records["tokens_per_epoch"] == 4 * 6 * 2 * 128
