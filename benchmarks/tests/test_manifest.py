import json
import os

import pytest

from benchmarks.lib import manifest as mf


@pytest.fixture(scope="module")
def man():
    return mf.Manifest()


def test_manifest_meets_the_contract(man):
    assert mf.validate(man.data) == []


def test_validate_finds_breaches(man):
    bad = json.loads(json.dumps(man.data))
    bad["workloads"][0]["name"] = "has space"
    bad["end_to_end"][0]["unit"] = "tokens per second"
    bad["per_layer"][0]["why"] = "not allowed"
    found = "\n".join(mf.validate(bad))
    assert "bad name" in found and "unit" in found and "keys" in found


def test_every_cell_resolves_to_files_by_name(man):
    for cell in man.data["workloads"]:
        cfg = man.config(cell["config"])
        traffic = man.traffic(cell["traffic"])
        assert cfg["name"] == cell["config"]
        assert hasattr(mf.load_driver(traffic["kind"]), "run")
        assert "tiny" in cfg and man.end_to_end(cell["name"])
        assert man.per_layer(cell["name"])


def test_every_per_layer_metric_has_its_own_reader_that_agrees(man):
    for m in man.data["per_layer"]:
        mod = mf.load_layer_metric(m["name"])
        assert callable(mod.read)
        assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["better"], m["source"], m["moves"])
    files = {f[:-3] for f in os.listdir(os.path.join(mf.BENCH_DIR,
                                                     "layer_metrics"))
             if f.endswith(".py")}
    assert files == {m["name"] for m in man.data["per_layer"]}


def test_a_reader_with_nothing_to_read_returns_nothing(man):
    for m in man.data["per_layer"]:
        mod = mf.load_layer_metric(m["name"])
        assert mod.read({"kind": "other"}, None, {}) is None


def test_a_cell_reports_only_its_own_metrics(man):
    train = {m["name"] for m in man.per_layer("train-adag-gpt2s")}
    assert train and all(n.endswith(".train") for n in train)
    e2e = {m["name"] for m in man.end_to_end("train-adag-gpt2s")}
    assert e2e == {"train_tokens_per_s", "setup_s"}


def test_peaks_known_device_and_unknown_device():
    from benchmarks.lib.peaks import peaks_for
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks_for("cpu")


def test_tiny_block_lays_over_the_config(man):
    cfg = man.config("gpt2-small")
    tiny = mf.resolve_sizes(cfg, True)
    assert tiny["n_embd"] == cfg["tiny"]["n_embd"] != cfg["n_embd"]
    assert tiny["precision"] == cfg["precision"]
    assert mf.resolve_sizes(cfg, False) is cfg
