"""The one generator: same seed, same trace; another seed, the same work in
another order; rates, clips, due times; shared prefixes and closed loops from
data alone."""

import json
import os

import numpy as np
import pytest

from benchmarks.lib import manifest as mf
from benchmarks.lib.traffic import generate

from helpers import FIXTURES

CHAT = mf.load_json(os.path.join(mf.BENCH_DIR, "traffic",
                                 "chat-open-poisson.json"))
BIG = 2 ** 31 + 12345       # the driver's seeds exceed 32 signed bits


def lens(reqs, phase="window"):
    return (sorted(len(r.prompt) for r in reqs if r.phase == phase),
            sorted(r.output_len for r in reqs if r.phase == phase))


def test_same_seed_same_trace():
    a, b = generate(CHAT, BIG, 30, 50257), generate(CHAT, BIG, 30, 50257)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.output_len == y.output_len
        assert np.array_equal(x.prompt, y.prompt)


def test_another_seed_same_schedule_other_tokens():
    """48 requests a window: their order is the work, so the seed draws the
    tokens and nothing else."""
    a, b = generate(CHAT, BIG, 30, 50257), generate(CHAT, 5, 30, 50257)
    assert [(r.due_s, len(r.prompt), r.output_len, r.phase) for r in a] == \
        [(r.due_s, len(r.prompt), r.output_len, r.phase) for r in b]
    assert not np.array_equal(a[0].prompt[:16], b[0].prompt[:16])
    other = generate(mf.deep_merge(CHAT, {"shape_seed": 1}), 5, 30, 50257)
    assert [len(r.prompt) for r in other] != [len(r.prompt) for r in b]


def test_rate_clips_and_due_times():
    rate, lead, secs = CHAT["arrival"]["rate"], CHAT["lead_in_s"], 30
    reqs = generate(CHAT, 11, secs, 50257)
    window = [r for r in reqs if r.phase == "window"]
    lead_in = [r for r in reqs if r.phase == "lead_in"]
    assert len(window) == round(rate * secs)
    assert len(lead_in) == round(rate * lead)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[0] >= 0
    assert all(r.due_s < lead for r in lead_in)
    assert all(lead <= r.due_s < lead + secs for r in window)
    p, o = CHAT["prompt_len"], CHAT["output_len"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(o["min"] <= r.output_len <= o["max"] for r in reqs)
    assert max(len(r.prompt) + r.output_len for r in reqs) <= 1024
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 50257
               and r.prompt.min() >= 0 for r in reqs)
    # heavy tail, prompts longer than answers
    assert np.median([len(r.prompt) for r in window]) > \
        np.median([r.output_len for r in window])


def test_poisson_mean_rate_and_spread_of_gaps():
    mix = mf.deep_merge(CHAT, {"arrival": {"rate": 50.0}, "lead_in_s": 0})
    reqs = generate(mix, 3, 40, 50257)
    gaps = np.diff([r.due_s for r in reqs])
    assert len(reqs) == 2000
    assert abs(gaps.mean() - 1 / 50.0) < 0.05 / 50.0
    # exponential gaps: coefficient of variation about 1, not a metronome
    assert 0.85 < gaps.std() / gaps.mean() < 1.15


def test_gamma_arrivals_are_burstier_from_data_alone():
    mix = mf.deep_merge(CHAT, {"arrival": {"process": "gamma", "rate": 50.0,
                                           "cv": 3.0}, "lead_in_s": 0})
    gaps = np.diff([r.due_s for r in generate(mix, 3, 40, 50257)])
    assert gaps.std() / gaps.mean() > 2.0


def test_shared_prefix_closed_loop_fixture_is_data_only():
    with open(os.path.join(FIXTURES, "agent-closed-prefix.json")) as f:
        mix = json.load(f)
    reqs = generate(mix, 9, 5, 512)
    assert len(reqs) == mix["arrival"]["requests"]
    assert all(r.due_s is None and r.phase == "window" for r in reqs)
    groups = {r.group for r in reqs}
    assert groups == {0, 1}
    for g in groups:
        heads = {tuple(r.prompt[:mix["prefix_len"]]) for r in reqs
                 if r.group == g}
        assert len(heads) == 1          # one system prompt a group
    assert all(mix["prefix_len"] + 4 <= len(r.prompt)
               <= mix["prefix_len"] + 12 for r in reqs)


def test_unknown_process_or_distribution_is_an_error():
    with pytest.raises(ValueError):
        generate(mf.deep_merge(CHAT, {"arrival": {"process": "weibull"}}),
                 1, 5, 100)
    with pytest.raises(ValueError):
        generate(mf.deep_merge(CHAT, {"prompt_len": {"dist": "zipf"}}),
                 1, 5, 100)
