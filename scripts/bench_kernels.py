"""Kernel-level TPU microbenchmarks: flash attention and KV-cache decode.

The north-star bench (bench.py) measures the end-to-end ADAG ConvNet; this
script measures the two long-context hot paths the framework adds beyond
reference parity (SURVEY.md §2.3 marks sequence models "absent upstream"):

  * ``ops.flash_attention`` (Pallas, online-softmax, O(S·W) windowed) vs the
    XLA ``dot_product_attention`` fallback — forward and forward+backward —
    across sequence lengths, in bf16.
  * ``core.decode.jit_decode_step`` autoregressive throughput (tokens/sec)
    with a full KV cache and with the O(window) rolling ring cache.

Prints one JSON line per measurement; when the default backend is an
accelerator the results are also written to ``KERNELS_TPU.json`` (same
preserve-the-hardware-signal policy as bench.py / BENCH_TPU.json).

Run:  python scripts/bench_kernels.py [--quick] [--seqs 512,2048,8192]
``--quick`` shrinks shapes/reps for a CPU smoke run (XLA path only — the
Pallas kernel in interpret mode would dominate the wall clock for nothing).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, reps: int = 20, warmup: int = 2) -> float:
    """Median wall-clock seconds of ``fn(*args)`` (jitted, blocked)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def attention_flops(b, s, h, dh, causal=True, window=None):
    """Analytic matmul FLOPs of one attention forward: QK^T + PV."""
    if window is not None:
        kv_per_q = min(window, s)  # O(S·W) with the windowed kernel
        pairs = b * h * s * kv_per_q
    elif causal:
        pairs = b * h * s * (s + 1) // 2
    else:
        pairs = b * h * s * s
    return 2 * 2 * pairs * dh  # two matmuls, 2 FLOPs per MAC


def bench_attention(seqs, b, h, dh, window, reps, impls, emit):
    from distkeras_tpu.ops.attention import dot_product_attention
    from distkeras_tpu.ops.flash_attention import flash_attention

    key = jax.random.PRNGKey(0)
    for s in seqs:
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, s, h, dh), jnp.bfloat16)
        k = jax.random.normal(kk, (b, s, h, dh), jnp.bfloat16)
        v = jax.random.normal(kv, (b, s, h, dh), jnp.bfloat16)
        for impl in impls:
            for w in ([None] if impl == "xla" else [None, window]):
                if w is not None and w >= s:
                    continue
                if impl == "pallas":
                    fwd = jax.jit(lambda q, k, v, w=w: flash_attention(
                        q, k, v, causal=True, window=w))
                else:
                    fwd = jax.jit(lambda q, k, v: dot_product_attention(
                        q, k, v, causal=True))
                # grad w.r.t. ALL of q/k/v: with argnums=0 alone, jit
                # dead-code-eliminates the XLA path's dk/dv work while the
                # Pallas custom_vjp still computes all three, skewing the
                # comparison
                loss = jax.jit(jax.grad(
                    lambda q, k, v, f=fwd: jnp.sum(
                        f(q, k, v).astype(jnp.float32)),
                    argnums=(0, 1, 2)))
                try:
                    t_f = _time(fwd, q, k, v, reps=reps)
                    t_b = _time(loss, q, k, v, reps=reps)
                except Exception as e:  # OOM at large S on the XLA path
                    emit({"bench": "attention", "impl": impl, "seq": s,
                          "window": w, "error": str(e)[:160]})
                    continue
                fl = attention_flops(b, s, h, dh, window=w)
                emit({"bench": "attention", "impl": impl, "seq": s,
                      "window": w, "batch": b, "heads": h, "head_dim": dh,
                      "fwd_ms": round(t_f * 1e3, 3),
                      "fwd_bwd_ms": round(t_b * 1e3, 3),
                      "fwd_tflops": round(fl / t_f / 1e12, 3)})


def bench_decode(reps, quick, emit):
    from distkeras_tpu.core.decode import init_cache, jit_decode_step
    from distkeras_tpu.models.zoo import transformer_lm

    from distkeras_tpu.core.quant import quantize_params

    batch = 8
    # int8 flavors measure the weight-only-quantization serving win (same
    # jitted program; XLA fuses the dequant into each matmul's operand read)
    cfgs = [("full", dict(), False, False),
            ("full_int8", dict(), False, True),
            ("rolling_window", dict(
                attention_window=256, positional="rope"), True, False),
            ("rolling_window_int8", dict(
                attention_window=256, positional="rope"), True, True)]
    seq_len = 512 if quick else 2048
    for name, extra, rolling, int8 in cfgs:
        model = transformer_lm(
            vocab_size=512, seq_len=seq_len, d_model=256, num_heads=8,
            num_layers=4, mlp_dim=1024, num_kv_heads=2, **extra)
        params = model.init(jax.random.PRNGKey(0))
        if int8:
            params = quantize_params(params)
        caches = init_cache(model, batch=batch,
                            max_len=extra.get("attention_window", seq_len)
                            if rolling else seq_len, rolling=rolling)
        step = jit_decode_step(model, rolling=rolling)
        tok = jnp.zeros((batch,), jnp.int32)

        def run(params, caches, tok, n=64 if quick else 256):
            # n sequential steps through one jitted program: the measured
            # unit is the serving inner loop, python dispatch included
            pos = seq_len - 1 if rolling else 0
            for i in range(n):
                logits, caches = step(params, caches, tok, pos + (
                    0 if rolling else i))
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
            return tok

        n = 64 if quick else 256
        t = _time(run, params, caches, tok, reps=max(3, reps // 4),
                  warmup=1)
        emit({"bench": "decode", "cache": name, "batch": batch,
              "steps": n, "d_model": 256, "layers": 4,
              "tokens_per_sec": round(batch * n / t, 1),
              "ms_per_step": round(t / n * 1e3, 3)})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--seqs", default=None)
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--window", type=int, default=1024)
    args = ap.parse_args()

    platform = jax.default_backend()
    quick = args.quick or platform != "tpu"
    seqs = ([int(x) for x in args.seqs.split(",")] if args.seqs
            else ([256, 512] if quick else [512, 2048, 8192]))
    reps = args.reps or (5 if quick else 20)
    impls = ["xla"] if platform != "tpu" else ["xla", "pallas"]
    b, h, dh = (2, 4, 64) if quick else (4, 8, 128)

    results = []

    def emit(rec):
        rec = {"platform": platform,
               "device_kind": jax.devices()[0].device_kind, **rec}
        print(json.dumps(rec), flush=True)
        results.append(rec)

    bench_attention(seqs, b, h, dh, args.window, reps, impls, emit)
    bench_decode(reps, quick, emit)

    if platform != "cpu":
        out = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "KERNELS_TPU.json")
        with open(out, "w") as f:
            json.dump({"captured_unix": round(time.time(), 1),
                       "results": results}, f, indent=1)
        print(f"# wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
