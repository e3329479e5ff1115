"""Every serving surface on one trained model, end to end.

Trains the x+1 toy LM once (so outputs are predictable by eye), then runs
the full inference stack on it:

  greedy / sampled ``generate`` (KV cache) → ``beam_search`` →
  ``speculative_generate`` (1-layer draft) → int8 ``quantize`` serving →
  the continuous-batching ``ServingEngine`` (slot pool + wire server)

and checks the invariants the test suite pins: beam-0 == greedy, the
speculative output == greedy bit-for-bit, int8 greedy == full-precision
greedy, and the engine's lone-request row == offline ``generate``.  No
reference counterpart (SURVEY.md §2.3: no sequence models upstream) —
this is the beyond-parity serving layer in one script.

Run:  python examples/serving_tour.py [--steps 16]
(CPU: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      JAX_PLATFORMS=cpu python examples/serving_tour.py)
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

    from distkeras_tpu import Dataset
    from distkeras_tpu.models import transformer_lm
    from distkeras_tpu.trainers import SingleTrainer

    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=16)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--epochs", type=int, default=25)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    x = rng.integers(0, args.vocab, (256, 12)).astype(np.int32)
    y = (x + 1) % args.vocab

    def train(layers):
        m = transformer_lm(vocab_size=args.vocab, seq_len=64, d_model=32,
                           num_heads=4, num_layers=layers, mlp_dim=64,
                           compute_dtype="float32")
        t = SingleTrainer(m, batch_size=32, num_epoch=args.epochs,
                          loss="sparse_categorical_crossentropy_from_logits",
                          worker_optimizer="adam", learning_rate=3e-3)
        return t.train(Dataset({"features": x, "label": y}))

    print("training target (2 layers) and draft (1 layer)...")
    target, draft = train(2), train(1)
    prompt = np.array([[3, 4, 5, 6]], np.int32)
    want = (prompt[:, -1:] + 1 + np.arange(args.steps)) % args.vocab

    greedy = np.asarray(target.generate(prompt, args.steps))
    assert (greedy[:, 4:] == want).all(), "greedy lost the rule"
    print("greedy:      ", greedy[0, 4:].tolist())

    sampled = np.asarray(target.generate(
        prompt, args.steps, temperature=0.7, rng=jax.random.PRNGKey(1),
        top_k=4, top_p=0.95))
    print("top-k/top-p: ", sampled[0, 4:].tolist())

    beams, scores = target.beam_search(prompt, args.steps, num_beams=3)
    assert (np.asarray(beams)[:, 0] == greedy).all(), "beam-0 != greedy"
    print(f"beam-0 == greedy; beam scores "
          f"{[round(float(s), 2) for s in np.asarray(scores)[0]]}")

    spec, stats = target.speculative_generate(draft, prompt, args.steps,
                                              draft_len=4,
                                              return_stats=True)
    assert (np.asarray(spec) == greedy).all(), "speculative != greedy"
    rate = stats["accepted"] / max(stats["drafted"], 1)
    print(f"speculative == greedy; draft accept {rate:.0%}, "
          f"{stats['target_calls']} verify calls for {args.steps} tokens")

    # speculative SAMPLING (rejection rule): same warped-target statistics
    # as plain sampled generate, the draft only changes wall-clock.  On the
    # trained x+1 model the warped distribution is near-deterministic, so
    # the sampled run still recovers the rule
    sspec, sstats = target.speculative_generate(
        draft, prompt, args.steps, draft_len=4, temperature=0.5, top_k=4,
        rng=jax.random.PRNGKey(2), return_stats=True)
    srate = sstats["accepted"] / max(sstats["drafted"], 1)
    print(f"speculative sampling (T=0.5, top-4): "
          f"{np.asarray(sspec)[0, 4:].tolist()}, draft accept {srate:.0%}")

    # eos stopping composes with speculation: same semantics as generate,
    # and a fully-finished batch stops issuing verify calls early
    eos = int(greedy[0, 4 + args.steps // 2])  # a token greedy will emit
    espec, estats = target.speculative_generate(
        draft, prompt, args.steps, draft_len=4, eos_id=eos, pad_id=0,
        return_stats=True)
    want_eos = np.asarray(target.generate(prompt, args.steps, eos_id=eos,
                                          pad_id=0))
    assert (np.asarray(espec) == want_eos).all(), "spec eos != generate eos"
    assert estats["target_calls"] < stats["target_calls"], \
        "eos stopping did not save verify calls"
    print(f"speculative + eos_id={eos}: "
          f"{np.asarray(espec)[0, 4:].tolist()} "
          f"({estats['target_calls']} verify calls, stopped early)")

    q = target.quantize()
    q_greedy = np.asarray(q.generate(prompt, args.steps))
    assert (q_greedy == greedy).all(), "int8 changed greedy decode"
    print("int8 quantized greedy == full precision")

    # the continuous-batching engine: a mixed batch of concurrent requests
    # through one slot-pooled decode program, then the same thing over the
    # wire server.  The lone greedy request must equal offline generate
    # bit-for-bit — the engine is scheduling, never different numerics.
    from distkeras_tpu.serving import ServingClient, ServingEngine, \
        ServingServer

    eng = ServingEngine(target, num_slots=3, max_len=4 + args.steps)
    lone = eng.submit(prompt[0], args.steps)
    mixed = [eng.submit(np.array([2, 3], np.int32), args.steps // 2),
             eng.submit(np.array([7, 8, 9], np.int32), args.steps,
                        temperature=0.7, top_k=4, seed=5),
             eng.submit(np.array([1], np.int32), 3)]
    eng.run_until_idle()
    assert (lone.result() == greedy[0]).all(), "engine != offline generate"
    occ = eng.slot_occupancy
    print(f"engine: {1 + len(mixed)} concurrent requests, "
          f"{eng.stats['tokens_generated']} tokens, "
          f"slot occupancy {occ:.0%}, "
          f"slots reused {eng.stats['slot_requests']}")

    with ServingServer(ServingEngine(target, num_slots=2,
                                     max_len=4 + args.steps)) as srv:
        with ServingClient(*srv.addr) as client:
            row = client.generate(prompt[0], args.steps)
            assert (row == greedy[0]).all(), "wire row != offline generate"
    print("wire server round trip == offline generate")
    print("SERVING-TOUR-OK")


if __name__ == "__main__":
    main()
