"""The command itself, as the driver starts it (a new process each time)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import manifest as mf

RUN = os.path.join(mf.BENCH_DIR, "run.py")


def start(*args, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    e.pop("XLA_FLAGS", None)
    e.update(env or {})
    return subprocess.run([sys.executable, RUN, *args], cwd=mf.ROOT, env=e,
                          capture_output=True, text=True, timeout=600)


def test_without_a_tpu_it_exits_non_zero_and_prints_no_result():
    p = start("--workload", "train-adag-gpt2s", "--seed", "1",
              "--seconds", "1", "--trace", "0")
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_an_unknown_cell_is_an_error():
    p = start("--workload", "no-such-cell", "--seed", "1", "--rehearse")
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("cell,trace", [("train-adag-gpt2s", "0"),
                                        ("train-adag-gpt2s", "1"),
                                        ("serve-chat-gpt2m", "0"),
                                        ("serve-chat-gpt2m", "1")])
def test_rehearsal_prints_the_contracts_last_line(cell, trace):
    p = start("--workload", cell, "--seed", str(2 ** 31 + 17),
              "--seconds", "2", "--trace", trace, "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"     # named for what it is
    assert "busy_s" not in out["device"]          # no device metric
    man = mf.Manifest()
    declared = (man.end_to_end(cell) if trace == "0"
                else man.per_layer(cell))
    by_name = {m["name"]: m for m in declared}
    assert out["metrics"] and set(out["metrics"]) <= set(by_name)
    for name, m in out["metrics"].items():
        assert m["unit"] == by_name[name]["unit"] and m["value"] > 0
        if trace == "1":
            assert by_name[name]["source"] != "device_trace"
    if trace == "0":
        assert set(out["metrics"]) == set(by_name)
    # every number compared is printed beside its limit on an earlier line
    compared = [json.loads(l) for l in lines[:-1] if '"compared"' in l]
    assert compared and all({"value", "limit", "ok"} <= set(c)
                            for c in compared)
