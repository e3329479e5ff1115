"""The full long-context stack on one small LM, end to end.

Trains a causal transformer with every long-context feature the framework
provides composed at once —

  - rotary position embeddings (``positional="rope"``: extrapolates past
    the training length),
  - grouped-query attention (``num_kv_heads``: H/Hkv smaller kv
    projections and KV cache),
  - sliding-window attention (``attention_window``: causal-local masking;
    O(S·W) compute through the flash kernel on TPU),

then generates a continuation several times longer than the training
sequences with the ROLLING KV cache (``generate(..., rolling=True)``):
per-block cache memory stays at O(window) no matter how far generation
runs.  The task is next-token = (token + 1) mod V, so correctness of the
long continuation is checkable by eye (and asserted).

No reference counterpart (SURVEY.md §2.3: sequence models absent
upstream) — this demonstrates the beyond-parity long-context layer.

Run:  python examples/longcontext_generate.py [--steps 48]
(On CPU: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
         JAX_PLATFORMS=cpu python examples/longcontext_generate.py)
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # run without installing


def main():
    import jax
    import numpy as np

    from distkeras_tpu import ADAG, Dataset
    from distkeras_tpu.models import transformer_lm

    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=16)
    ap.add_argument("--window", type=int, default=6)
    ap.add_argument("--epochs", type=int, default=40)
    ap.add_argument("--steps", type=int, default=48,
                    help="tokens to generate (3x the training length)")
    ap.add_argument("--int8", action="store_true",
                    help="serve from weight-only int8 quantized params "
                         "(FittedModel.quantize(); decode code unchanged)")
    args = ap.parse_args()

    model = transformer_lm(
        vocab_size=args.vocab, seq_len=args.seq_len, d_model=32,
        num_heads=4, num_kv_heads=2, num_layers=2, mlp_dim=64,
        compute_dtype="float32", positional="rope",
        attention_window=args.window)

    rng = np.random.default_rng(0)
    x = rng.integers(0, args.vocab, (512, args.seq_len)).astype(np.int32)
    y = (x + 1) % args.vocab

    trainer = ADAG(model, num_workers=len(jax.devices()), batch_size=8,
                   num_epoch=args.epochs, communication_window=2,
                   loss="sparse_categorical_crossentropy_from_logits",
                   worker_optimizer="adam", learning_rate=3e-3)
    fitted = trainer.train(Dataset({"features": x, "label": y}),
                           shuffle=True)
    print(f"trained {trainer.get_training_time():.1f}s "
          f"({len(jax.devices())} workers)")

    if args.int8:
        fitted = fitted.quantize()
        print("serving int8 (weight-only, per-channel scales)")

    prompt = np.array([[2, 3, 4]], np.int32)
    out = np.asarray(fitted.generate(prompt, num_steps=args.steps,
                                     rolling=True))
    print("prompt:      ", prompt[0].tolist())
    print("continuation:", out[0, prompt.shape[1]:].tolist())

    want = (prompt[:, -1:] + 1 + np.arange(args.steps)) % args.vocab
    ok = np.array_equal(out[:, prompt.shape[1]:], want)
    print(f"rule held for all {args.steps} generated tokens "
          f"({args.steps / args.seq_len:.1f}x the training length, "
          f"cache memory O({args.window})): {ok}")
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
