#!/usr/bin/env python3
"""The quickest proof that distkeras_tpu still starts on the chip.

One process, one TPU chip, the entry points a user calls: the paper's
flagship trainer (ADAG on the MNIST ConvNet), the transformer LM at
GPT-2-small widths under ``SingleTrainer`` and ``ParallelTransformerLM``
(with the HLO checked for the flash and fused-CE Pallas kernels), the same
model behind ``ServingEngine(paged=True)`` + ``ServingServer`` +
``ServingClient`` on loopback, the asynchronous host-PS path (DOWNPOUR
against the socket parameter server), and the kernel checks the old
subprocess smoke tests carried.  Weights and data come from ``--seed``;
nothing outside the checkout is read.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # only the cross-chip paths + their
                                      # one-device comparisons
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --rehearse [--chips 4]   # tiny widths, CPU

Every phase prints one JSON line (seconds, compile seconds, what it
checked); a failed phase makes the exit code non-zero.  Without a TPU (and
without ``--rehearse``) it exits 3 before any phase and prints no result.
The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device exactly as jax reports it.  Every rate printed here is a
sanity figure, not a measurement.
"""

from __future__ import annotations

import argparse
import collections
import concurrent.futures
import importlib.metadata
import json
import os
import re
import subprocess
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

#: GPT-2 small (Radford et al. 2019; 124M): the widths the repo's block
#: matches — LayerNorm, GELU MLP, biases, learned positions.
FULL = dict(
    lm=dict(vocab_size=50257, seq_len=1024, d_model=768, num_heads=12,
            num_layers=12, mlp_dim=3072),
    lm_batch=8, lm_steps=12, plm_steps=3, token_range=512,
    mnist_train=61440, mnist_test=2048, adag_batch=512, adag_window=12,
    adag_epochs=2,
    slots=8, new_tokens=32, prefix_len=128, group_suffix=64,
    lone_prompts=(64, 128, 320, 512), quant_prompt=64,
    ps_rows=8192, ps_batch=64, ps_window=4,
    long_seq=8192, long_window=512,
)
#: the CPU rehearsal (--rehearse): same control flow, toy widths
TINY = dict(
    lm=dict(vocab_size=512, seq_len=128, d_model=64, num_heads=2,
            num_layers=2, mlp_dim=128),
    lm_batch=2, lm_steps=6, plm_steps=3, token_range=64,
    mnist_train=2048, mnist_test=256, adag_batch=32, adag_window=4,
    adag_epochs=3,
    slots=8, new_tokens=8, prefix_len=32, group_suffix=16,
    lone_prompts=(16, 32, 48, 64), quant_prompt=16,
    ps_rows=1024, ps_batch=32, ps_window=4,
    long_seq=256, long_window=32,
)

#: the Pallas kernels each check looks for in its program's HLO.  Every
#: name is one of ``distkeras_tpu.metrics.KERNEL_NAMES``: ``require_kernels``
#: holds them to it here, ``tests/test_tracing.py`` in tier 1 (the package
#: itself is imported only after ``build_native``, so not up here).
REQUIRED_KERNELS = {
    # 8 x 1,024 x 50,257 logits: the kernel's side of the loss's rule
    # (``core.losses.fused_ce_applies``)
    "SingleTrainer step": ("flash_fwd", "flash_bwd",
                           "fused_ce_fwd", "fused_ce_bwd"),
    "ParallelTransformerLM step": ("fused_ce_fwd", "fused_ce_bwd"),
    "long-context forward": ("flash_fwd",),
    "2x1x2 step": ("fused_ce_fwd", "fused_ce_bwd"),
    # a Mamba-2 layer's state in the paged pool's decode step
    "state-space decode step": ("ssd_decode",),
}


def emit(**line) -> None:
    print(json.dumps(line), flush=True)


def build_native() -> dict:
    """Build csrc/ with the documented command in a child that never
    imports jax (the chip belongs to this process), then import the
    package and report which of the three extensions it picked up."""
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "setup.py", "build_ext", "--inplace"], cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        timeout=600)
    from distkeras_tpu import applykernel, networking
    from distkeras_tpu.data import datasets
    active = {"_wirecodec": networking._native is not None,
              "_applykernel": applykernel.have_native(),
              "_csvloader": datasets._native_csv is not None}
    line = dict(phase="native_build", seconds=round(
        time.perf_counter() - t0, 1), build_rc=proc.returncode,
        active=active)
    missing = [name for name, on in active.items() if not on]
    if missing:
        line["going_on_without"] = missing
        line["build_tail"] = proc.stdout[-600:]
    emit(**line)
    return active


class CompileMeter:
    """Backend-compile seconds and persistent-cache hits/misses, read from
    jax's own monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.seconds, self.programs, self.hits, self.misses = 0.0, 0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return (self.seconds, self.programs, self.hits, self.misses)


class Runner:
    def __init__(self, meter: CompileMeter):
        self.meter = meter
        self.failed = []

    def phase(self, name, fn, *args):
        before, t0 = self.meter.snapshot(), time.perf_counter()
        try:
            checked, ok = fn(*args), True
        except Exception as e:  # reported, and the run exits non-zero
            traceback.print_exc()
            checked, ok = {"error": f"{type(e).__name__}: {e}"[:400]}, False
            self.failed.append(name)
        after = self.meter.snapshot()
        emit(phase=name, ok=ok,
             seconds=round(time.perf_counter() - t0, 2),
             compile_seconds=round(after[0] - before[0], 2),
             programs_compiled=after[1] - before[1],
             cache_hits=after[2] - before[2],
             cache_misses=after[3] - before[3], **checked)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def kernel_names(lowered_text: str) -> dict:
    """Pallas kernels in a lowered program, by the ``name=`` of their
    ``pallas_call`` (``distkeras_tpu/metrics.py`` lists them)."""
    return dict(collections.Counter(
        re.findall(r'kernel_name = "(\w+)"', lowered_text)))


def require_kernels(names: dict, what: str, on_tpu: bool) -> str:
    """On the chip the kernels ``REQUIRED_KERNELS[what]`` must be in the
    program; on the CPU rehearsal the dispatch takes its interpret/XLA
    branch by design."""
    from distkeras_tpu.metrics import KERNEL_NAMES
    wanted = REQUIRED_KERNELS[what]
    unknown = [k for k in wanted if k not in KERNEL_NAMES]
    check(not unknown, f"{what}: {unknown} are not kernels the package "
          f"names ({KERNEL_NAMES})")
    if not on_tpu:
        return "not checked (cpu rehearsal)"
    missing = [k for k in wanted if not names.get(k)]
    check(not missing, f"{what}: no tpu_custom_call for {missing} — the "
          f"dispatch dropped to XLA (found {names})")
    return "found"


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def _mnist(cfg, seed):
    from distkeras_tpu import MinMaxTransformer, OneHotTransformer
    from distkeras_tpu.data import load_mnist
    train, test = load_mnist(n_train=cfg["mnist_train"],
                             n_test=cfg["mnist_test"], seed=seed)
    scale = MinMaxTransformer(0, 1, 0, 255)
    train = OneHotTransformer(10).transform(scale.transform(train))
    return train, scale.transform(test)


def _accuracy(fitted, test) -> float:
    from distkeras_tpu import (AccuracyEvaluator, LabelIndexTransformer,
                               ModelPredictor)
    pred = LabelIndexTransformer().transform(
        ModelPredictor(fitted).predict(test))
    return float(AccuracyEvaluator().evaluate(pred))


def adag_convnet(cfg, seed, num_workers=1):
    """README quickstart: ADAG on the MNIST ConvNet, one worker per chip."""
    import jax
    import numpy as np
    from distkeras_tpu import ADAG
    from distkeras_tpu.models import mnist_convnet

    train, test = _mnist(cfg, seed)
    trainer = ADAG(mnist_convnet("bfloat16"), num_workers=num_workers,
                   batch_size=cfg["adag_batch"],
                   num_epoch=cfg["adag_epochs"],
                   communication_window=cfg["adag_window"],
                   label_col="label_encoded", worker_optimizer="adam",
                   learning_rate=1e-3, seed=seed)
    fitted = trainer.train(train, shuffle=True)
    hist = np.asarray(trainer.history, np.float64)
    check(hist.size > 0 and np.isfinite(hist).all(),
          f"non-finite ADAG loss history: {hist}")
    acc = _accuracy(fitted, test)
    check(acc > 0.8, f"ADAG accuracy {acc} <= 0.8")
    out = dict(workers=trainer.num_workers, rounds=int(hist.size),
               loss_first=float(hist[0]), loss_last=float(hist[-1]),
               accuracy=acc,
               examples_per_s_sanity=round(
                   len(train) * cfg["adag_epochs"]
                   / trainer.get_training_time(), 1))
    if num_workers > 1:
        # one worker per chip, really: every state leaf lives on all n
        # devices, and the epoch program reduces across them
        leaves = jax.tree_util.tree_leaves(trainer._state)
        ndev = {len({s.device for s in leaf.addressable_shards})
                for leaf in leaves}
        check(ndev == {num_workers},
              f"state leaves span {ndev} devices, want {num_workers}")
        from distkeras_tpu.parallel.spmd import shape_epoch_data
        x = np.asarray(train["features"])
        y = np.asarray(train["label_encoded"])
        xb, yb, mb, _ = shape_epoch_data(x, y, num_workers,
                                         cfg["adag_window"],
                                         cfg["adag_batch"])
        eng = trainer._engine
        text = eng._epoch_fn.lower(
            trainer._state, xb, yb, mb,
            eng.worker_rngs(seed + 17)).compile().as_text()
        check("all-reduce" in text, "no all-reduce in the epoch program")
        out.update(distinct_devices_per_leaf=num_workers,
                   state_leaves=len(leaves),
                   all_reduce_ops=text.count(" all-reduce("))
    return out


def _x_plus_1_corpus(cfg, n_rows, seed):
    """The x+1 rule of tests/test_decode.py, tokens drawn from the first
    ``token_range`` ids so a few steps can learn it at a 50k vocabulary."""
    import numpy as np
    toks = np.random.default_rng(seed).integers(
        0, cfg["token_range"], (n_rows, cfg["lm"]["seq_len"])
    ).astype(np.int32)
    return toks, (toks + 1) % cfg["token_range"]


def lm_train(cfg, seed, on_tpu):
    """GPT-2-small widths under SingleTrainer (flash attention must be in
    the step) and under ParallelTransformerLM(fused_ce=True) on a 1x1x1
    mesh (the fused-CE kernels must be in the step, under shard_map)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    import distkeras_tpu.trainers as trainers_mod
    from distkeras_tpu import Dataset, SingleTrainer
    from distkeras_tpu.models import transformer_lm
    from distkeras_tpu.parallel.transformer import ParallelTransformerLM

    lm = cfg["lm"]
    toks, labels = _x_plus_1_corpus(cfg, cfg["lm_batch"] * cfg["lm_steps"],
                                    seed)
    # reach for the trainer's own jitted epoch program to lower it: the
    # program gets no option for this
    lowered = []
    make = trainers_mod.make_epoch_runner

    def capturing(*a, **kw):
        runner = make(*a, **kw)

        def run(*args):
            if not lowered:
                lowered.append(runner.lower(*args).as_text())
            return runner(*args)
        return run

    trainers_mod.make_epoch_runner = capturing
    try:
        trainer = SingleTrainer(
            transformer_lm(compute_dtype="bfloat16", **lm),
            batch_size=cfg["lm_batch"], num_epoch=1,
            loss="sparse_categorical_crossentropy_from_logits",
            worker_optimizer="adam", learning_rate=1e-3, seed=seed)
        t0 = time.perf_counter()
        trainer.train(Dataset({"features": toks, "label": labels}))
        single_s = time.perf_counter() - t0
    finally:
        trainers_mod.make_epoch_runner = make
    hist = np.asarray(trainer.history, np.float64)
    check(np.isfinite(hist).all(), f"non-finite LM losses: {hist}")
    check(hist[-1] < hist[0] - 0.5,
          f"LM loss did not fall on the x+1 corpus: {hist}")
    names = kernel_names(lowered[0])
    flash = require_kernels(names, "SingleTrainer step", on_tpu)

    # -- the parallel LM: fused CE (and flash) inside shard_map --------------
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("data", "seq", "model"))
    plm = ParallelTransformerLM(mesh=mesh, fused_ce=True, **lm)
    params = plm.init(jax.random.PRNGKey(seed))
    opt_state, step = plm.compile_train_step(optax.adam(1e-3), params)
    bt = jax.device_put(jnp.asarray(toks[:cfg["lm_batch"]]),
                        plm.batch_sharding())
    bl = jax.device_put(jnp.asarray(labels[:cfg["lm_batch"]]),
                        plm.batch_sharding())
    pnames = kernel_names(step.lower(params, opt_state, bt, bl).as_text())
    # (its default sp_impl="ring" attends through parallel/ring.py, plain
    # XLA by design — only the fused-CE kernels belong in this program)
    fused = require_kernels(pnames, "ParallelTransformerLM step", on_tpu)
    plosses, psecs = [], []
    for _ in range(cfg["plm_steps"]):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, bt, bl)
        plosses.append(float(loss))  # waits for the device
        psecs.append(round(time.perf_counter() - t0, 4))
    check(np.isfinite(plosses).all(), f"non-finite parallel-LM losses: "
          f"{plosses}")
    check(plosses[-1] < plosses[0], f"parallel-LM loss did not fall on a "
          f"repeated batch: {plosses}")
    return dict(
        widths=lm, batch_tokens=cfg["lm_batch"] * lm["seq_len"],
        steps=int(hist.size), single_trainer_seconds=round(single_s, 2),
        losses=[round(float(v), 4) for v in hist],
        flash_kernels_in_single_trainer_step=flash, kernels_single=names,
        parallel_lm_losses=[round(v, 4) for v in plosses],
        parallel_lm_step_seconds_sanity=psecs,  # the first one compiles
        fused_ce_kernels_in_parallel_step=fused, kernels_parallel=pnames)


def _seeded_lm(cfg, seed):
    import jax
    from distkeras_tpu.core.model import FittedModel
    from distkeras_tpu.models import transformer_lm
    model = transformer_lm(compute_dtype="bfloat16", **cfg["lm"])
    params = model.init(jax.random.PRNGKey(seed), (cfg["lm"]["seq_len"],))
    return FittedModel(model, params)


def _request(addr, prompt, steps):
    """One client, one request: submit + stream to the done frame."""
    from distkeras_tpu.serving import ServingClient
    with ServingClient(*addr) as client:
        rid = client.submit(prompt, steps)
        n = 0
        for tokens, done in client.stream(rid):
            n += len(tokens)
            if done is not None:
                return dict(row=done["row"], finish=done["finish"],
                            streamed=n)
    raise ConnectionError("stream ended without a done frame")


def _engine(fitted, cfg, **kw):
    from distkeras_tpu.serving import ServingEngine
    return ServingEngine(fitted, num_slots=cfg["slots"],
                         max_len=cfg["lm"]["seq_len"], paged=True,
                         block_size=16, **kw)


def _serve(eng, prompts_by_pass, steps, cfg):
    """Serve ``prompts_by_pass`` (a list of lists: each inner list is sent
    concurrently, one client per request) through the wire server."""
    from distkeras_tpu.serving import ServingServer
    replies = []
    with ServingServer(eng) as srv:
        with concurrent.futures.ThreadPoolExecutor(cfg["slots"]) as pool:
            for batch in prompts_by_pass:
                futs = [pool.submit(_request, srv.addr, p, steps)
                        for p in batch]
                replies += [f.result(timeout=600) for f in futs]
    return replies


def _first_divergence(forward, params, got, want, seq_len):
    """Where two greedy rows part: the reference logits' top-2 there
    (``forward``: the jitted full forward) and how far below their maximum
    each of the two tokens sits (a tie broken two ways: both near 0)."""
    import numpy as np
    t = int(np.argmax(got != want))
    x = np.zeros((1, seq_len), np.int32)
    x[0, :t] = want[:t]
    logits = np.asarray(forward(params, x), np.float32)[0, t - 1]
    check(np.isfinite(logits).all(), "non-finite reference logits")
    top2 = np.argsort(logits)[-2:][::-1]
    best = float(logits[top2[0]])
    return dict(position=t, engine_token=int(got[t]),
                offline_token=int(want[t]),
                top2=[int(i) for i in top2],
                top2_gap=best - float(logits[top2[1]]),
                engine_below_max=best - float(logits[got[t]]),
                offline_below_max=best - float(logits[want[t]]),
                logit_absmax=float(np.abs(logits).max()))


def serve(cfg, seed):
    """The LM behind the paged engine + wire server + clients, against
    offline ``fitted.generate`` on the same device."""
    import jax
    import numpy as np
    lm, steps = cfg["lm"], cfg["new_tokens"]
    seq = lm["seq_len"]
    fitted = _seeded_lm(cfg, seed)
    rng = np.random.default_rng(seed + 1)
    draw = lambda n: rng.integers(0, lm["vocab_size"], n).astype(np.int32)
    prefix = draw(cfg["prefix_len"])
    group = [np.concatenate([prefix, draw(cfg["group_suffix"])])
             for _ in range(4)]
    lone = [draw(n) for n in cfg["lone_prompts"]]
    # warm up before going live, as the engine's supervisor does: at these
    # widths one cold program outlasts the client's 60 s socket timeout and
    # the server's stall bound, and a cold engine reads as a wedged one
    eng = _engine(fitted, cfg)
    t0 = time.perf_counter()
    eng.warmup()
    warmup_s = time.perf_counter() - t0
    # the group's first request goes a pass ahead: same-pass admissions do
    # not cross-match in the radix index
    t0 = time.perf_counter()
    replies = _serve(eng, [group[:1], group[1:] + lone], steps, cfg)
    serve_s = time.perf_counter() - t0
    prompts = group[:1] + group[1:] + lone
    for p, r in zip(prompts, replies):
        check(r["finish"] == "length", f"finish={r['finish']!r}")
        check(len(r["row"]) == len(p) + steps and r["streamed"] == steps,
              f"token count: row {len(r['row'])}, streamed "
              f"{r['streamed']}, want {len(p)} + {steps}")
        check((r["row"][:len(p)] == p).all(), "prompt not echoed")
        check(((0 <= r["row"]) & (r["row"] < lm["vocab_size"])).all(),
              "token out of vocabulary")
    st = eng.stats
    check(st["requests_failed"] == 0 and st["requests_rejected"] == 0
          and st["requests_expired"] == 0 and st["requests_cancelled"] == 0,
          f"failed/shed requests: {st}")
    check(st["requests_completed"] == len(prompts), f"completed: {st}")
    check(st["prefix_hits"] > 0, "no prefix hit for the shared group")
    check(eng.kv_blocks_in_use == 0 and eng._pool.check_conservation(),
          "leaked KV blocks")

    # the full forward the engine's programs derive from: finite logits
    forward = jax.jit(fitted.model.apply)
    x = np.zeros((1, seq), np.int32)
    x[0, :len(lone[-1])] = lone[-1]
    check(np.isfinite(np.asarray(forward(fitted.params, x),
                                 np.float32)).all(), "non-finite logits")

    # offline reference on the same device, same cache length
    agree, divergences = 0, []
    for p, r in zip(prompts, replies):
        want = np.asarray(fitted.generate(p[None], steps, max_len=seq))[0]
        if (want == r["row"]).all():
            agree += 1
        else:
            divergences.append(_first_divergence(
                forward, fitted.params, r["row"], want, seq))
    # a tie at rounding size broken two ways is a finding; anything larger
    # is a bug.  Both tokens must sit within rounding of the reference
    # maximum (a third program, with a rounding of its own).  bf16 keeps 8
    # bits: allow 2^-6 of the logit scale.
    for d in divergences:
        tol = 2.0 ** -6 * max(1.0, d["logit_absmax"])
        d["tolerance"] = tol
        check(d["engine_below_max"] <= tol and d["offline_below_max"] <= tol,
              f"engine and offline generate diverge beyond rounding: {d}")

    # the lossy pools: one request each, in process, sanity only
    qp = draw(cfg["quant_prompt"])
    extra = {}
    for name, model, kw in (("kv_int8", fitted, dict(kv_dtype="int8")),
                            ("weights_int8", fitted.quantize(), {})):
        e2 = _engine(model, cfg, **kw)
        h = e2.submit(qp, steps)
        e2.run_until_idle()
        row = h.result()
        check(h.finish == "length" and len(row) == len(qp) + steps,
              f"{name}: {h.finish}, {len(row)} tokens")
        check(((0 <= row) & (row < lm["vocab_size"])).all(),
              f"{name}: token out of vocabulary")
        check(e2.stats["requests_failed"] == 0, f"{name}: failed request")
        check(e2.kv_blocks_in_use == 0, f"{name}: leaked KV blocks")
        extra[name] = "1 request, finish=length"
    return dict(
        widths=lm, requests=len(prompts),
        prompt_lengths=[len(p) for p in prompts], new_tokens=steps,
        warmup_seconds=round(warmup_s, 2),
        serve_seconds=round(serve_s, 2),
        tokens_per_s_sanity=round(len(prompts) * steps / serve_s, 1),
        prefix_hits=int(st["prefix_hits"]),
        prefix_hit_tokens=int(st["prefix_hit_tokens"]),
        prefill_batches=int(st["prefill_batches"]),
        decode_steps=int(st["decode_steps"]),
        agree_with_offline_generate=agree / len(prompts),
        divergences=divergences, **extra)


def host_ps(cfg, seed, native):
    """DOWNPOUR, execution='host_ps': two worker threads on one device
    against the socket parameter server on the host."""
    import numpy as np
    from distkeras_tpu import DOWNPOUR
    from distkeras_tpu.models import mnist_convnet

    train, test = _mnist(dict(cfg, mnist_train=cfg["ps_rows"]), seed)
    # num_workers counts mesh devices even for host_ps (README, host-PS
    # section): two THREADS on one chip are parallelism_factor=2
    trainer = DOWNPOUR(mnist_convnet("bfloat16"), num_workers=1,
                       parallelism_factor=2, execution="host_ps",
                       batch_size=cfg["ps_batch"], num_epoch=1,
                       communication_window=cfg["ps_window"],
                       label_col="label_encoded", worker_optimizer="adam",
                       learning_rate=1e-3, seed=seed, apply_kernel="auto")
    fitted = trainer.train(train)
    hist = np.asarray(trainer.history, np.float64)
    check(hist.size > 0 and np.isfinite(hist).all(),
          f"non-finite host-PS losses: {hist}")
    workers = trainer._ps_workers
    check(len(workers) == 2, f"{len(workers)} worker threads, want 2")
    ops = sum(w.transport_ops for w in workers)
    commits = sum(w._commits for w in workers)
    # comm_overlap (DOWNPOUR's default): one pull per worker, then one
    # combined commit+pull round trip per window
    check(ops == len(workers) + commits,
          f"transport_ops {ops} != workers {len(workers)} + windows "
          f"{commits}")
    weights = fitted.get_weights()
    check(all(np.isfinite(w).all() for w in weights), "non-finite center")
    return dict(worker_threads=len(workers), windows=commits,
                transport_ops=ops, loss_first=float(hist[0]),
                loss_last=float(hist[-1]),
                accuracy_sanity=_accuracy(fitted, test),
                wire_codec="native" if native["_wirecodec"] else "python",
                apply_kernel=("native" if native["_applykernel"]
                              else "numpy"))


def kernels(cfg, seed, on_tpu):
    """What the old subprocess smoke tests carried and the phases above do
    not: kernel numerics against the XLA reference (windowed and GQA flash,
    the paged decode kernel, ragged fused CE), flash inside shard_map, the
    long-context stack, and the 1F1B pipeline schedule."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from distkeras_tpu.core.decode import generate
    from distkeras_tpu.core.optimizers import get_schedule
    from distkeras_tpu.models import transformer_lm
    from distkeras_tpu.ops import flash_attention as flash_ops
    from distkeras_tpu.ops.attention import attention
    from distkeras_tpu.ops.fused_ce import fused_softmax_cross_entropy
    from distkeras_tpu.parallel.pp_transformer import PipelineTransformerLM
    from distkeras_tpu.parallel.ulysses import ulysses_self_attention

    rng = np.random.default_rng(seed)
    f32 = lambda t: np.asarray(t, np.float32)
    out = {}

    def qkv(shape, kv_heads=None):
        kshape = shape if kv_heads is None else (
            shape[0], shape[1], kv_heads, shape[3])
        return [jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
                for s in (shape, kshape, kshape)]

    # flash vs XLA: forward + fused backward over the eligibility envelope
    # (lane-aligned, small head_dim, one sub-128 block), GQA, window
    worst = 0.0
    for shape, kvh, window in (((2, 256, 4, 128), None, None),
                               ((2, 256, 4, 64), None, None),
                               ((2, 112, 4, 64), None, None),
                               ((2, 256, 4, 64), 2, None),
                               ((2, 256, 4, 64), 2, 96)):
        q, k, v = qkv(shape, kvh)

        def run(impl):
            def loss(q, k, v):
                o = attention(q, k, v, causal=True, impl=impl,
                              window=window)
                return o.astype(jnp.float32).sum(), o
            (_, o), grads = jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
            return o, grads

        (op, gp), (ox, gx) = run("pallas"), run("xla")
        fwd = float(np.max(np.abs(f32(op) - f32(ox))))
        bwd = max(float(np.max(np.abs(f32(a) - f32(b))))
                  for a, b in zip(gp, gx))
        check(fwd < 0.05 and bwd < 0.125,
              f"flash vs xla at {shape} kv={kvh} window={window}: "
              f"fwd {fwd}, bwd {bwd}")
        worst = max(worst, fwd)
    out["flash_vs_xla_max_abs_err"] = worst

    # every shape above takes the one-kernel backward (its dq accumulator
    # fits); the two-pass pair, which longer sequences fall back to, on the
    # same residuals: the same three gradients
    q, k, v = qkv((2, 256, 4, 64))
    g = qkv((2, 256, 4, 64))[0]
    static, tiles, kv_tiles = flash_ops._resolve(q, True, None, None, None,
                                                 not on_tpu, None)
    check(flash_ops._backward_plan(256, 64, 2, True, None)
          == flash_ops.ONE_KERNEL, "S=256 does not take the one kernel")
    o, lse = flash_ops._flash_forward(q, k, v, tiles=tiles, **static)
    one, two = (flash_ops._flash_backward(
        q, k, v, o, lse, g, tiles=tiles, kv_tiles=kv_tiles, plan=plan,
        **static) for plan in (flash_ops.ONE_KERNEL, flash_ops.TWO_PASS))
    err = max(float(np.max(np.abs(f32(a) - f32(b))))
              for a, b in zip(one, two))
    check(err < 0.05, f"one-kernel flash backward vs the two-pass pair: "
          f"{err}")
    out["flash_bwd_vs_two_pass_max_abs_err"] = err

    # paged decode kernel vs the gather path it stands in for, on one arena:
    # ragged rows (one position, a page, a page + 1, the whole view), a dead
    # row, grouped queries, head_dim 64, pages of 16 and 32
    from distkeras_tpu.ops.attention import paged_attention
    from distkeras_tpu.ops.paged_attention import paged_decode_attention
    view = 1024 if on_tpu else 128
    worst = 0.0
    for h, hkv, page in ((16, 16, 16), (8, 2, 32)):
        lens = np.array([1, page, page + 1, view, 0, view // 2 + 1, 3 * page,
                         view - 1], np.int32)
        b, f, cols = len(lens), hkv * 64, view // page + 1
        blocks = b * (cols - 1)
        tables = np.full((b, cols), blocks, np.int32)   # null = `blocks`
        ids = iter(rng.permutation(blocks))
        for r, n in enumerate(lens):
            tables[r, :-(-n // page)] = [next(ids)
                                         for _ in range(-(-n // page))]
        ka, va = (jnp.asarray(rng.standard_normal(((blocks + 1) * page, f)),
                              jnp.bfloat16) for _ in range(2))
        q = jnp.asarray(rng.standard_normal((b, h, 64)), jnp.bfloat16)
        got = jax.jit(lambda *a: paged_decode_attention(*a, page))(
            q, ka, va, tables, lens)

        want = jax.jit(lambda q, ka, va, tables, lens: paged_attention(
            q[:, None], ka, va, tables, page, view, kv_length=lens,
            q_positions=jnp.maximum(lens - 1, 0)[:, None])[:, 0])(
                q, ka, va, tables, lens)
        live = (lens > 0)[:, None, None]
        err = float(np.max(np.abs(np.where(live, f32(got) - f32(want), 0))))
        check(err < 0.03 and not f32(got)[lens == 0].any(),
              f"paged decode vs gather at H={h} Hkv={hkv} page={page}: "
              f"{err}")
        worst = max(worst, err)
    out["paged_decode_vs_gather_max_abs_err"] = worst

    # fused CE vs the log_softmax oracle, ragged vocab and rows included
    def oracle(lg, lb):
        logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]

    for t, v, dtype in ((256, 1024, jnp.float32), (192, 1000, jnp.float32),
                        (256, 2048, jnp.bfloat16)):
        lg = jnp.asarray(rng.standard_normal((t, v)) * 3, dtype)
        lb = jnp.asarray(rng.integers(0, v, t), jnp.int32)
        tol = 0.05 if dtype == jnp.bfloat16 else 1e-4
        err = float(jnp.max(jnp.abs(
            jax.jit(fused_softmax_cross_entropy)(lg, lb) - oracle(lg, lb))))
        g = jax.jit(jax.grad(lambda a: fused_softmax_cross_entropy(
            a, lb).sum()))(lg)
        gref = jax.grad(lambda a: oracle(a, lb).sum())(
            lg.astype(jnp.float32))
        gerr = float(jnp.max(jnp.abs(f32(g) - f32(gref))))
        check(err < tol and gerr < tol,
              f"fused CE vs oracle at ({t},{v}) {dtype}: {err}, {gerr}")
    out["fused_ce_vs_oracle"] = "3 shapes within tolerance"

    # flash from INSIDE shard_map (the ulysses attend): outputs must
    # declare their varying mesh axes or the compiled kernel is refused
    mesh = Mesh(np.array(jax.devices()[:1]), ("seq",))
    q, k, v = qkv((2, 256, 4, 64))
    err = float(np.max(np.abs(
        f32(ulysses_self_attention(q, k, v, mesh, "seq", causal=True))
        - f32(attention(q, k, v, causal=True, impl="xla")))))
    check(err < 0.05, f"ulysses flash-in-shard_map vs xla: {err}")
    out["flash_in_shard_map_max_abs_err"] = err

    # long context: RoPE + GQA + sliding window forwards at long_seq with
    # the flash kernel in the program, and the rolling O(window) cache
    # generates the same tokens as the full cache once the ring has wrapped
    s, w = cfg["long_seq"], cfg["long_window"]
    model = transformer_lm(vocab_size=256, seq_len=s, d_model=256,
                           num_heads=4, num_kv_heads=2, num_layers=2,
                           mlp_dim=512, positional="rope",
                           attention_window=w)
    params = model.init(jax.random.PRNGKey(seed))
    toks = rng.integers(0, 256, (1, s)).astype(np.int32)
    fwd = jax.jit(model.apply)
    out["long_context_flash"] = require_kernels(
        kernel_names(fwd.lower(params, toks).as_text()),
        "long-context forward", on_tpu)
    check(np.isfinite(f32(fwd(params, toks))).all(),
          f"non-finite logits at seq_len {s}")
    prompt = toks[:, :16]
    n = w + 16  # prompt 16 + n steps > window: slots evict
    full = np.asarray(generate(model, params, prompt, n))
    rolled = np.asarray(generate(model, params, prompt, n, rolling=True))
    check((full == rolled).all(), "rolling cache != full cache")
    out["long_context"] = dict(seq_len=s, window=w, rolling_equals_full=True)

    # 1F1B on a 1-device 'stage' ring with a warmup+cosine schedule
    pmesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                 ("data", "stage"))
    plm = PipelineTransformerLM(vocab_size=64, seq_len=64, d_model=64,
                                num_heads=2, num_layers=2, mlp_dim=128,
                                mesh=pmesh, num_microbatches=2,
                                schedule="1f1b")
    pp = plm.init(jax.random.PRNGKey(seed))
    opt_state, step = plm.compile_train_step(
        optax.adam(get_schedule("warmup_cosine", 1e-2, total_steps=4)), pp)
    ptoks = jnp.asarray(rng.integers(0, 64, (4, 64)), jnp.int32)
    losses = []
    for _ in range(4):
        pp, opt_state, loss = step(pp, opt_state, ptoks, (ptoks + 1) % 64)
        losses.append(float(loss))
    check(np.isfinite(losses).all(), f"non-finite 1F1B losses: {losses}")
    out["pipeline_1f1b_losses"] = [round(v, 4) for v in losses]
    return out


def parallel_lm_4(cfg, seed, on_tpu):
    """dp x sp x tp = 2x1x2 with fused CE against the same seed and batch
    on a 1x1x1 mesh on device 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from distkeras_tpu.parallel.transformer import ParallelTransformerLM

    lm = cfg["lm"]
    toks, labels = _x_plus_1_corpus(cfg, cfg["lm_batch"], seed)
    devs = jax.devices()

    def run(shape):
        n = int(np.prod(shape))
        mesh = Mesh(np.array(devs[:n]).reshape(shape),
                    ("data", "seq", "model"))
        plm = ParallelTransformerLM(mesh=mesh, fused_ce=True, **lm)
        params = plm.init(jax.random.PRNGKey(seed))
        w1 = params["layers"][0]["w1"]
        shard = tuple(w1.addressable_shards[0].data.shape)
        opt_state, step = plm.compile_train_step(optax.adam(1e-3), params)
        bt = jax.device_put(jnp.asarray(toks), plm.batch_sharding())
        bl = jax.device_put(jnp.asarray(labels), plm.batch_sharding())
        lowered = step.lower(params, opt_state, bt, bl)
        names = kernel_names(lowered.as_text())
        losses, secs = [], []
        for _ in range(cfg["plm_steps"]):
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, bt, bl)
            losses.append(float(loss))  # waits for the device
            secs.append(round(time.perf_counter() - t0, 4))
        return losses, tuple(w1.shape), shard, names, secs

    l4, full, shard4, names4, secs4 = run((2, 1, 2))
    l1, _, shard1, _, secs1 = run((1, 1, 1))
    check(np.isfinite(l4).all() and np.isfinite(l1).all(),
          f"non-finite losses: {l4} {l1}")
    check(shard1 == full and shard4 == (full[0], full[1] // 2),
          f"w1 {full}: shard on 2x1x2 is {shard4}, on 1x1x1 {shard1}")
    kern = require_kernels(names4, "2x1x2 step", on_tpu)
    # bf16 compute, and tp changes the matmul reduction order; Adam's
    # first updates (sign-like steps) amplify that rounding a little
    tol = 0.01
    diffs = [abs(a - b) for a, b in zip(l4, l1)]
    check(all(d <= tol * max(1.0, abs(b)) for d, b in zip(diffs, l1)),
          f"2x1x2 vs 1x1x1 losses differ: {l4} vs {l1}")
    return dict(widths=lm, mesh="data=2 x seq=1 x model=2",
                losses_2x1x2=l4, losses_1x1x1=l1, abs_diff=diffs,
                step_seconds_sanity_2x1x2=secs4,  # the first one compiles
                step_seconds_sanity_1x1x1=secs1,
                tolerance_relative=tol, w1_shape=full,
                w1_shard_2x1x2=shard4, kernels_in_step=kern,
                kernels=names4)


# ---------------------------------------------------------------------------

def state_space(cfg, seed, on_tpu):
    """A stack of one-part layers (Mamba-2, experts, attention) through the
    paged engine: the recurrence's three forms agree on the device, the
    grouped matmul over a weight stored transposed is the dense product, the
    decode program holds the ``ssd_decode`` kernel, and what the engine
    serves (a bucket prompt and a chunked one, slots reused; its expert
    up-projections held transposed) is what the model's own full forward
    puts first."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distkeras_tpu.core.model import FittedModel
    from distkeras_tpu.models import hybrid_lm
    from distkeras_tpu.ops import ssd
    from distkeras_tpu.ops.experts import grouped_matmul
    from distkeras_tpu.serving import ServingEngine

    rng = np.random.default_rng(seed)
    nrm = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    b, length, h, p, g, n = 4, 200, 8, 64, 2, 128
    x, bm, cm = nrm(b, length, h, p), nrm(b, length, g, n), nrm(b, length,
                                                                g, n)
    dt = jax.nn.softplus(nrm(b, length, h) - 2.0)
    a = -jnp.exp(jnp.asarray(rng.uniform(0.0, 2.5, h), jnp.float32))
    s0 = nrm(b, h, p, n)

    def token(state, i):
        y, state = ssd.ssd_step(x[:, i], dt[:, i], a, bm[:, i], cm[:, i],
                                state)
        return state, y
    s_ref, y_ref = jax.lax.scan(token, s0, jnp.arange(length))
    y, s = ssd.ssd_chunk(x, dt, a, bm, cm, s0)
    scale = float(jnp.abs(y_ref).max())
    err = float(jnp.abs(y - jnp.moveaxis(y_ref, 0, 1)).max()) / scale
    check(err < 1e-3, f"ssd_chunk vs the recurrence: {err} of the largest")
    live = jnp.asarray([True, False, True, True])
    yk, sk = ssd.ssd_decode(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
                            s0 + 0, live)
    ys, ss = ssd.ssd_step(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], s0)
    kerr = float(jnp.abs(jnp.where(live[:, None, None], yk - ys, yk)).max())
    check(kerr < 1e-3 * float(jnp.abs(ys).max())
          and bool((sk[1] == s0[1]).all()),
          f"ssd_decode vs ssd_step: {kerr}; dead slot touched: "
          f"{not bool((sk[1] == s0[1]).all())}")

    # an expert width of 1.5 lane tiles under a hidden size of two: the
    # engine holds such an up-projection (4, 256, 192) transposed, and the
    # grouped matmul reads it so (on the chip: the kernel's transpose_rhs)
    hidden, width = 256, 192
    sizes = [40, 0, 31, 9]
    rows, w_up = nrm(sum(sizes) + 16, hidden), nrm(4, hidden, width)
    got = grouped_matmul(rows, jnp.swapaxes(w_up, 1, 2),
                         jnp.asarray(sizes, jnp.int32), transpose_rhs=True)
    ends = np.cumsum([0] + sizes)
    want = jnp.concatenate(
        [rows[lo:hi] @ w_up[e] for e, (lo, hi) in enumerate(
            zip(ends[:-1], ends[1:]))] + [jnp.zeros((16, width))])
    gerr = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    check(gerr < 1e-2, f"grouped matmul over a transposed weight vs the "
          f"dense products: {gerr} of the largest")

    config = dict(
        hybrid_override_pattern="ME*ME", num_hidden_layers=5,
        hidden_size=hidden, vocab_size=512, layer_norm_epsilon=1e-5,
        mamba_num_heads=8, mamba_head_dim=64, ssm_state_size=128,
        n_groups=2, conv_kernel=4, chunk_size=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=64,
        n_routed_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=width,
        n_shared_experts=1, moe_shared_expert_intermediate_size=256,
        routed_scaling_factor=2.5)
    model = hybrid_lm(config, compute_dtype="float32", held=(0, 4))
    params = model.init(jax.random.PRNGKey(seed), (8,))
    eng = ServingEngine(FittedModel(model, params), num_slots=2, max_len=256,
                        paged=True, block_size=16, kv_blocks=64,
                        prefill_chunk=64)
    held_t = eng.stats["moe_up_projections_transposed"]
    check(held_t == 2, f"{held_t} of 2 up-projections held transposed")
    found = require_kernels(
        kernel_names(eng._decode_fn.lower(
            eng.params, *eng._state_args()).as_text()),
        "state-space decode step", on_tpu)
    prompts = [rng.integers(0, 512, k).astype(np.int32)
               for k in (20, 150, 64, 7)]
    handles = [eng.submit(q, cfg["new_tokens"]) for q in prompts]
    eng.run_until_idle()
    worst = 0.0
    for q, hd in zip(prompts, handles):
        check(hd.finish == "length", f"request ended {hd.finish}")
        toks = np.asarray(hd.tokens, np.int32)
        row = np.concatenate([q, toks[:-1]])
        logits = np.asarray(model.apply(params, jnp.asarray(row)[None])[0],
                            np.float32)[len(q) - 1:]
        gap = logits.max(-1) - logits[np.arange(len(toks)), toks]
        worst = max(worst, float(gap.max()))
    # the chip's default float32 matmuls round as bfloat16 passes do: a
    # near-tie may flip between two programs; a lost state is off by tenths
    check(worst < 0.05, f"a served token lies {worst} below the full "
          "forward's best logit")
    return dict(ssd_chunk_vs_recurrence_rel_err=err,
                ssd_decode_vs_step_abs_err=kerr, ssd_decode_kernel=found,
                gmm_transposed_vs_dense_rel_err=gerr,
                up_projections_transposed=held_t,
                state_kinds=eng._state_kinds, served_token_worst_gap=worst,
                slot_requests=eng.stats["slot_requests"],
                prefill_chunks=eng.stats["prefill_chunks"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the cross-chip paths and what they "
                         "are compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on whatever backend jax finds (the "
                         "CPU sandbox); reports that backend, never a TPU")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(REPO, "distkeras_tpu")):
        print("chip_smoke.py: no distkeras_tpu package beside this script",
              file=sys.stderr)
        return 2
    import jax
    import jaxlib
    devs = jax.devices()
    dev = devs[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke.py: no TPU (jax found {dev.platform}); "
              "--rehearse runs the tiny CPU rehearsal", file=sys.stderr)
        return 3
    if len(devs) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but jax sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 3
    # before the package is imported: it binds the extensions (or their
    # fallbacks) at import.  The child never touches jax, so it does not
    # matter that this process already holds the chip.
    native = build_native()
    from distkeras_tpu.utils import use_compile_cache
    cache_dir = use_compile_cache()
    meter = CompileMeter()
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    emit(phase="environment", jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu, devices=[str(d) for d in devs],
         device_kind=dev.device_kind, compile_cache_dir=cache_dir,
         compile_cache_entries_at_start=(
             len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0),
         rehearsal=args.rehearse, seed=args.seed, chips=args.chips)

    cfg = TINY if args.rehearse else FULL
    run = Runner(meter)
    if args.chips == 4:
        run.phase("adag_convnet_4", adag_convnet, cfg, args.seed, 4)
        run.phase("parallel_lm_4", parallel_lm_4, cfg, args.seed, on_tpu)
    else:
        run.phase("adag_convnet", adag_convnet, cfg, args.seed)
        run.phase("lm_train", lm_train, cfg, args.seed, on_tpu)
        run.phase("serve", serve, cfg, args.seed)
        run.phase("host_ps", host_ps, cfg, args.seed, native)
        run.phase("kernels", kernels, cfg, args.seed, on_tpu)
        run.phase("state_space", state_space, cfg, args.seed, on_tpu)
    if run.failed:
        print(f"chip_smoke.py: failed phases: {run.failed}", file=sys.stderr)
        return 1
    result = {"ok": True, "device": {"platform": dev.platform,
                                     "kind": dev.device_kind,
                                     "count": len(devs)}}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
