"""The one traffic generator: a data file of parameters in, a list of
requests out.

Every seed replays the SAME schedule (prompt length, output length and gap to
the next arrival of every request, drawn once from the file's own
``shape_seed``) with other tokens: the seed changes what is sent, never how
much or when, so runs with different seeds differ no more than runs of one.
(Letting the seed shuffle, or even rotate, the sequence was tried: with the
48 requests a window of the first serving cell holds, a rotation moved the
95th percentile of time to first token, and the tokens that finish inside the
window, by 8 %: their order IS the work.  PERF.md.)  Arrivals are an open loop on a schedule (``poisson``: exponential gaps;
``gamma``: gaps with a stated coefficient of variation) or a closed loop of
``clients``.  Requests may share one of ``prefix_groups`` system prompts of
``prefix_len`` tokens ahead of their own part.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    due_s: Optional[float]        # from the schedule's start; None = closed
    prompt: np.ndarray            # int32 tokens
    output_len: int
    phase: str                    # "lead_in" or "window"
    group: int = -1               # shared-prefix group, -1 = none


def _lengths(spec: Dict[str, Any], n: int, rng: np.random.Generator
             ) -> np.ndarray:
    dist = spec["dist"]
    if dist == "lognormal":
        raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif dist == "uniform":
        raw = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    out = np.floor(raw).astype(np.int64)
    return np.clip(out, spec["min"], spec["max"])


def _gaps(arrival: Dict[str, Any], n: int, span_s: float,
          rng: np.random.Generator) -> np.ndarray:
    """``n`` gaps that sum to ``span_s`` exactly: the first arrival falls
    inside the span's first gap, the last before its end."""
    proc = arrival["process"]
    if proc == "poisson":
        raw = rng.exponential(1.0, n)
    elif proc == "gamma":
        cv = float(arrival["cv"])
        raw = rng.gamma(1.0 / cv ** 2, cv ** 2, n)
    else:
        raise ValueError(f"no schedule for arrival process {proc!r}")
    return raw * (span_s / raw.sum())


def count_for(rate: float, span_s: float) -> int:
    return max(int(round(rate * span_s)), 1)


def generate(traffic: Dict[str, Any], seed: int, seconds: float,
             vocab_size: int) -> List[Request]:
    """The requests of one run: ``lead_in_s`` of arrivals, then ``seconds``.
    Open loop: each phase holds ``round(rate x span)`` requests whose gaps
    sum to the span.  Closed loop: ``requests`` requests without due times,
    all of phase "window" (the driver starts the window once the lead-in
    has passed)."""
    arrival = traffic["arrival"]
    shape_rng = np.random.default_rng(int(traffic.get("shape_seed", 0)))
    rng = np.random.default_rng(int(seed))
    lead_in = float(traffic.get("lead_in_s", 0.0))
    closed = arrival["process"] == "closed"
    if closed:
        phases = [("window", int(arrival["requests"]), None, 0.0)]
    else:
        rate = float(arrival["rate"])
        phases = []
        if lead_in > 0:
            phases.append(("lead_in", count_for(rate, lead_in), lead_in, 0.0))
        phases.append(("window", count_for(rate, seconds), float(seconds),
                       lead_in))
    groups = int(traffic.get("prefix_groups", 0))
    prefix_len = int(traffic.get("prefix_len", 0))
    prefixes = [rng.integers(0, vocab_size, prefix_len).astype(np.int32)
                for _ in range(groups)]
    out: List[Request] = []
    for phase, n, span, offset in phases:
        # the schedule: the same for every seed
        p_len = _lengths(traffic["prompt_len"], n, shape_rng)
        o_len = _lengths(traffic["output_len"], n, shape_rng)
        gaps = None if closed else _gaps(arrival, n, span, shape_rng)
        if gaps is not None:
            # the first arrival half a gap into the span, the last half a
            # gap before its end
            due = offset + np.cumsum(gaps) - gaps[0] / 2
        group_of = np.arange(n) % groups if groups else np.full(n, -1)
        for i in range(n):
            own = rng.integers(0, vocab_size, int(p_len[i])).astype(np.int32)
            g = int(group_of[i])
            prompt = np.concatenate([prefixes[g], own]) if g >= 0 else own
            out.append(Request(len(out), None if closed else float(due[i]),
                               prompt, int(o_len[i]), phase, g))
    return out
