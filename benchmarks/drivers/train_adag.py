"""Driver of kind ``train_adag``: a distributed trainer of the paper's family
through its normal entry point, ``trainer.train(dataset)``, on the SPMD engine,
one worker per chip.

One trainer object, one ``train()`` call.  Its first ``setup_epochs`` epochs
trace, load and lay out the two epoch programs (a fresh state and a donated
one are different inputs to jax) and belong to set-up; the epochs after them
are the window.  The number of window epochs is fixed by the job file
(``ceil(seconds / epoch_seconds_hint)``): a fixed amount of work, whatever the
program's speed, and the rate is taken over the time it really took.

``correct`` is decided after the window on that same trainer's engine and
compiled epoch program: the first steps from the seeded weights, every other
step masked out, against the plain reference (see ``check``).
"""

from __future__ import annotations

import math
import threading
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmarks.lib import harness, program, reference
from benchmarks.lib.weights import fold_seed, make_weights


def corpus(seed: int, rows: int, seq_len: int, token_range: int
           ) -> Tuple[np.ndarray, np.ndarray]:
    """The x+1 rule (the label of a token is the token plus one) over the
    first ``token_range`` ids, so that a few steps can learn it at a 50k
    vocabulary.  Every row differs."""
    toks = np.random.default_rng(seed).integers(
        0, token_range, (rows, seq_len)).astype(np.int32)
    return toks, (toks + 1) % token_range


def build_trainer(ctx: harness.RunContext, num_epoch: int):
    program.import_program()
    import distkeras_tpu
    from distkeras_tpu.core.model import FittedModel
    spec = dict(ctx.traffic["trainer"])
    cls = getattr(distkeras_tpu, spec.pop("class"))
    fitted = FittedModel(program.build_model(ctx.cfg),
                         program.program_params(ctx.cfg, ctx.seed))
    return cls(fitted, num_workers=ctx.chips, label_col="label",
               seed=fold_seed(ctx.seed), num_epoch=num_epoch, **spec)


class EpochWatcher(threading.Thread):
    """Starts the profiler once ``first`` epochs are done and stops it
    ``count + 1`` epochs later, so that ``count`` whole epochs lie inside the
    traced window.  Reads only the length of ``trainer.metrics``."""

    def __init__(self, trainer, first: int, count: int,
                 profiler: harness.Profiler):
        super().__init__(name="bench-epoch-watcher", daemon=True)
        self.trainer, self.first, self.count = trainer, first, count
        self.profiler, self.done = profiler, threading.Event()
        self.error = None

    def _epochs(self) -> int:
        return sum(1 for e in list(self.trainer.metrics)
                   if e.get("kind") == "epoch")

    def run(self) -> None:
        try:
            while self._epochs() < self.first and not self.done.is_set():
                time.sleep(0.002)
            if self.done.is_set():
                return
            self.profiler.start()
            while (self._epochs() < self.first + self.count + 1
                   and not self.done.is_set()):
                time.sleep(0.002)
            self.profiler.stop()
        except Exception as e:  # reported by the caller, on its thread
            self.error = e


def run(ctx: harness.RunContext) -> harness.RunResult:
    job = ctx.traffic
    setup_epochs = int(job["setup_epochs"])
    window_epochs = max(int(math.ceil(
        ctx.seconds / float(job["epoch_seconds_hint"]))), 1)
    trace_epochs = int(job["trace_epochs"])
    if ctx.trace:
        window_epochs = max(window_epochs, trace_epochs + 2)
    seed = fold_seed(ctx.seed)
    batch = int(job["trainer"]["batch_size"])
    seq = int(job["seq_len"])
    rows = int(job["steps_per_epoch"]) * batch * ctx.chips
    toks, labels = corpus(seed, rows, seq, int(job["token_range"]))
    tokens_per_epoch = rows * seq

    from distkeras_tpu import Dataset
    trainer = build_trainer(ctx, setup_epochs + window_epochs)
    data = Dataset({"features": toks, "label": labels})
    watcher = profiler = None
    if ctx.trace:
        profiler = harness.Profiler(ctx.out_dir, ctx.cell["name"])
        watcher = EpochWatcher(trainer, setup_epochs + 1, trace_epochs,
                               profiler)
        watcher.start()
    try:
        trainer.train(data, shuffle=bool(job["shuffle"]))
    finally:
        if watcher is not None:
            watcher.done.set()
            watcher.join(timeout=120)
    if watcher is not None and watcher.error is not None:
        raise watcher.error
    peak = harness.memory_peak_bytes()

    events = [e for e in trainer.metrics if e.get("kind") == "epoch"]
    warm = events[setup_epochs:]
    # the window opens when the last set-up epoch has been logged and closes
    # when the last epoch has: every host gap between epochs lies inside
    t_open, t_close = events[setup_epochs - 1]["t"], events[-1]["t"]
    window_s = t_close - t_open
    setup_s = t_open - ctx.wall_start
    failed = sum(1 for e in warm if not math.isfinite(e["loss"]))
    e2e = {"train_tokens_per_s": len(warm) * tokens_per_epoch / window_s,
           "setup_s": setup_s}
    ctx.log(driver="train_adag", epochs=len(events),
            window_epochs=len(warm), window_s=window_s,
            epoch_seconds=[e["seconds"] for e in events],
            epoch_loss=[e["loss"] for e in events],
            tokens_per_epoch=tokens_per_epoch)

    compared = check(ctx, trainer, toks, labels)
    losses = [e["loss"] for e in events]
    compared.append(harness.Compared(
        "window_loss_not_finite_or_rising",
        float(not (all(math.isfinite(v) for v in losses)
                   and losses[-1] < losses[0])), 0.0, exact=True))
    records = dict(kind="train", epoch_events=warm,
                   tokens_per_epoch=tokens_per_epoch, seq_len=seq,
                   batch=batch, steps_per_epoch=int(job["steps_per_epoch"]),
                   epoch_programs=list(job["epoch_programs"]))
    return harness.RunResult(
        compared=compared, attempted=len(warm), failed=failed,
        end_to_end=e2e, records=records, memory_peak_bytes=peak,
        trace_path=profiler.dir if profiler else None)


# -- correct --------------------------------------------------------------------

CHECK_STEPS = ((0, 0), (0, 1), (1, 0))     # (round, step) of the steps kept
DEAD_LEAF = 1e-3    # of the median leaf's gradient norm: no gradient at all

# What the check takes from the program beyond ``trainer.train``: the window's
# own engine and compiled epoch program can only be driven again through these
# names, which no public entry offers.  README.md ("What is taken from the
# program") lists them as the interface a PR that refactors the trainer keeps
# (or re-points in a ``benchmark`` PR); a missing one stops the run with its
# name rather than with a traceback from the middle of the check.
TRAINER_NAMES = ("_engine", "_initial_params", "_input_shape", "_state",
                 "seed")
ENGINE_NAMES = ("init_state", "run_epoch", "worker_rngs")


def window_program(trainer):
    """The trainer's engine, with the window's state freed; every name of the
    lists above looked for first."""
    missing = [n for n in TRAINER_NAMES if not hasattr(trainer, n)]
    engine = getattr(trainer, "_engine", None)
    missing += [f"_engine.{n}" for n in ENGINE_NAMES
                if not hasattr(engine, n)]
    if missing:
        raise RuntimeError(
            "the training check drives the window's own epoch program "
            f"through names the program no longer has: {missing} "
            "(benchmarks/README.md, 'What is taken from the program')")
    trainer._state = None       # the window's state, freed before the check
    return engine


def _leaf_norms(tree) -> np.ndarray:
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree_util.tree_leaves(t)])(tree)
    return np.asarray([float(n) for n in norms], np.float64)


def _worst_gap(got: np.ndarray, want: np.ndarray) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    floor = float(np.median(want))
    return float(np.max(np.abs(got - want) / np.maximum(want, floor)))


def _adam_moment(opt_state):
    """The first-moment tree inside an optax state (``ScaleByAdamState.mu``
    under the trainer's masking wrapper)."""
    import jax
    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu") and hasattr(s, "nu"))
        if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


def check_epoch(ctx: harness.RunContext, toks, labels):
    """The epoch's data as ``train()`` lays it out (unshuffled), and the
    batches of ``CHECK_STEPS`` in it, one per worker."""
    program.import_program()
    from distkeras_tpu.parallel.spmd import shape_epoch_data
    spec = ctx.traffic["trainer"]
    xb, yb, mb, _ = shape_epoch_data(toks, labels, ctx.chips,
                                     int(spec["communication_window"]),
                                     int(spec["batch_size"]))
    if not mb.all():
        raise RuntimeError("the job's epoch is padded: steps_per_epoch must "
                           "fill whole rounds")
    batches = [[(xb[r, s, k], yb[r, s, k]) for k in range(ctx.chips)]
               for r, s in CHECK_STEPS]
    return xb, yb, mb, batches


def program_first_steps(ctx: harness.RunContext, trainer, toks, labels
                        ) -> Dict[str, Any]:
    """Drive the window's own compiled epoch program, on the trainer's own
    engine, from the seeded weights through the steps of ``CHECK_STEPS`` and
    no others: the epoch's data as ``train()`` lays it out, with the mask of
    every other step at zero (the program's own padding convention, under
    which a step is a true no-op).  Twice: once with only the first step (its
    loss alone, and Adam's first moment, which is 0.1 x the gradient as the
    optimizer got it), once with all three."""
    import jax
    import jax.numpy as jnp
    job = ctx.traffic
    engine = window_program(trainer)
    xb, yb, mb, batches = check_epoch(ctx, toks, labels)
    rngs = engine.worker_rngs(fold_seed(ctx.seed) + 17)
    b1 = float(job["adam"]["b1"])

    def masked(steps, keep_init=False):
        mask = np.zeros_like(mb)
        for r, s in steps:
            mask[r, s] = 1.0
        init = trainer._initial_params(trainer._input_shape)
        # a copy: the epoch program donates its state, the center with it
        kept = (jax.tree_util.tree_map(jnp.copy, init) if keep_init
                else None)
        state = engine.init_state(jax.random.PRNGKey(trainer.seed),
                                  trainer._input_shape, initial_params=init)
        state, losses = engine.run_epoch(state, xb, yb, mask, rngs)
        return state, np.asarray(losses, np.float64), kept

    state, losses_a, _ = masked(CHECK_STEPS[:1])
    mu = _adam_moment(state.opt_state)
    grads = [jax.tree_util.tree_map(lambda m: m[k] / (1.0 - b1), mu)
             for k in range(ctx.chips)]
    grad_norms = [_leaf_norms(g) for g in grads]
    del state, mu
    state, losses_b, init = masked(CHECK_STEPS, keep_init=True)
    update = jax.tree_util.tree_map(lambda a, b: a - b, state.center, init)
    update_norms = _leaf_norms(update)
    del state, update, init
    l1 = float(losses_a[0])
    return dict(losses=[l1, 2.0 * float(losses_b[0]) - l1,
                        float(losses_b[1])],
                grads=grads, grad_norms=grad_norms,
                update_norms=update_norms, batches=batches)


def reference_first_steps(ctx: harness.RunContext, batches, mm
                          ) -> Dict[str, Any]:
    """The plain reference over the same steps, from the same seed's weights
    made again: mean loss of each kept step over the workers, every worker's
    first gradient, the center's change, by leaf in the program's order."""
    import jax
    job, cfg = ctx.traffic, ctx.cfg
    w = make_weights(cfg, ctx.seed, "float32")
    schedule = [
        [[batches[0][k], batches[1][k]] for k in range(ctx.chips)],
        [[batches[2][k]] for k in range(ctx.chips)],
    ]
    losses, grads, center = reference.adag_rounds(
        w, schedule, int(cfg["n_head"]),
        lr=float(job["trainer"]["learning_rate"]), b1=job["adam"]["b1"],
        b2=job["adam"]["b2"], adam_eps=job["adam"]["eps"],
        eps=float(cfg["layer_norm_epsilon"]), mm=mm)
    per_step = [np.mean([losses[0][k][0] for k in range(ctx.chips)]),
                np.mean([losses[0][k][1] for k in range(ctx.chips)]),
                np.mean([losses[1][k][0] for k in range(ctx.chips)])]
    update = jax.tree_util.tree_map(lambda a, b: a - b, center, w)
    grads = [program.to_program_layout(g) for g in grads]
    return dict(
        losses=[float(v) for v in per_step], grads=grads,
        grad_norms=[_leaf_norms(g) for g in grads],
        update_norms=_leaf_norms(program.to_program_layout(update)))


def check(ctx: harness.RunContext, trainer, toks, labels,
          in_place: str = "") -> List[harness.Compared]:
    """The numbers read: the gap of each step's loss; the widest gap of a
    leaf's first-gradient norm (the gradient as the optimizer got it) and the
    widest norm of a leaf's first-gradient difference; the widest gap of a
    leaf's norm of the center's change after the three steps.  Those that the
    job file gives a limit are compared, each beside its limit.  ``in_place`` (the
    tools and the tests, never a run) names a matmul of the reference,
    ``int8``, and puts the reference computed with it in the program's
    place."""
    import jax
    import jax.numpy as jnp
    limits = ctx.traffic["correct"]["limits"]
    t0 = time.perf_counter()
    if in_place:
        mm = {"int8": reference.int8_matmul}[in_place]
        batches = check_epoch(ctx, toks, labels)[3]
        got = reference_first_steps(ctx, batches, mm)
    else:
        got = program_first_steps(ctx, trainer, toks, labels)
        batches = got["batches"]
    t1 = time.perf_counter()
    want = reference_first_steps(ctx, batches, reference.f32_matmul)
    loss_gaps = [abs(a - b) for a, b in zip(got["losses"], want["losses"])]
    grad_gap = max(_worst_gap(g, w) for g, w in
                   zip(got["grad_norms"], want["grad_norms"]))
    # the norm of the difference, leaf by leaf, beside the difference of the
    # norms: rounding that a norm averages away stays in it
    diff_gap = 0.0
    for g, w, wn in zip(got["grads"], want["grads"], want["grad_norms"]):
        diff = _leaf_norms(jax.tree_util.tree_map(jnp.subtract, g, w))
        diff_gap = max(diff_gap, float(np.max(
            diff / np.maximum(wn, float(np.median(wn))))))
    # Adam turns a gradient that is exactly zero by algebra (a key bias:
    # softmax does not see a shift of every score of a query) into steps of
    # the size of its rounding noise, so such a leaf's change says nothing:
    # leaves whose reference gradient is all but zero are left out of it
    ref_grad = np.max(np.stack(want["grad_norms"]), axis=0)
    live = ref_grad >= DEAD_LEAF * np.median(ref_grad)
    update_gap = _worst_gap(got["update_norms"][live],
                            want["update_norms"][live])
    numbers = {f"loss_gap_step{i + 1}": g for i, g in enumerate(loss_gaps)}
    numbers.update(grad_norm_gap=grad_gap, grad_diff_gap=diff_gap,
                   update_norm_gap=update_gap)
    ctx.log(check="first_steps", program_s=t1 - t0,
            reference_s=time.perf_counter() - t1,
            program_losses=got["losses"], reference_losses=want["losses"],
            leaves=len(live), leaves_without_gradient=int((~live).sum()),
            in_place=in_place, **numbers)
    # the numbers that the job file gives a limit are the ones compared
    return [harness.Compared(k, numbers[k], float(v))
            for k, v in limits.items() if v is not None]
