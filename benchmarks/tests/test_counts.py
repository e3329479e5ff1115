"""The FLOP and byte counts on shapes small enough to count by hand, so that
no share of a peak can read above 100 % by arithmetic."""

import os

import pytest

from benchmarks.lib import counts, manifest as mf

CFG = dict(n_embd=4, n_head=2, n_layer=3, n_inner=8, vocab_size=10,
           n_positions=6,
           precision=dict(compute="bfloat16", kv_cache="bfloat16"))


def test_matmul_and_total_params_by_hand():
    # a block: q, k, v, o = 4 x (4 x 4) = 64; MLP = 2 x (4 x 8) = 64
    assert counts.matmul_params(CFG) == 3 * 128 + 4 * 10
    # plus biases 4 x 4 + 8 + 4 = 28, norms 4 x 4 = 16 a block; tables
    # (10 + 6) x 4; final norm 8; head bias 10
    assert counts.total_params(CFG) == (3 * (128 + 28 + 16) + 64 + 8
                                        + 40 + 10)


def test_total_params_equals_the_weights_made():
    import jax
    from benchmarks.lib.weights import make_weights
    cfg = mf.load_json(os.path.join(mf.BENCH_DIR, "configs",
                                    "gpt2-small.json"))
    tiny = mf.resolve_sizes(cfg, True)
    w = make_weights(tiny, 3)
    assert sum(x.size for x in jax.tree_util.tree_leaves(w)) == \
        counts.total_params(tiny)
    # the published widths, without making them: 163.0 M and 406.3 M
    assert counts.total_params(cfg) == 163_087_441
    med = mf.load_json(os.path.join(mf.BENCH_DIR, "configs",
                                    "gpt2-medium.json"))
    assert round(counts.total_params(med) / 1e6, 1) == 406.3


def test_train_flops_per_token_by_hand():
    # 6 x 424 matmul parameters + 6 x 3 layers x 5 positions x 4 wide
    assert counts.train_flops_per_token(CFG, 5) == 6 * 424 + 6 * 3 * 5 * 4
    cfg = mf.load_json(os.path.join(mf.BENCH_DIR, "configs",
                                    "gpt2-small.json"))
    per_token = counts.train_flops_per_token(cfg, 1024)
    assert per_token == 6 * 123_532_032 + 6 * 12 * 1024 * 768
    assert round(per_token / 1e9, 3) == 0.798   # the ISSUE's 0.80 GFLOP


def test_flash_attention_call_by_hand():
    c = counts.flash_attention_call(batch=2, heads=3, seq=8, head_dim=4)
    one_matmul = 2 * (2 * 3) * 8 * 8 * 4 / 2     # causal half
    assert c["fwd_flops"] == 2 * one_matmul
    assert c["bwd_flops"] == 4 * one_matmul
    tensor = 2 * 3 * 8 * 4 * 2                   # bytes of q in bf16
    assert c["fwd_bytes"] == 4 * tensor and c["bwd_bytes"] == 8 * tensor


def test_decode_least_seconds_by_hand():
    assert counts.kv_bytes_per_token(CFG) == 2 * 3 * 4 * 2
    assert counts.weight_bytes(CFG) == 424 * 2
    t = counts.decode_least_seconds(CFG, decode_steps=5,
                                    context_positions=100,
                                    hbm_bytes_per_s=1000.0)
    assert t == (5 * 848 + 100 * 48) / 1000.0
    med = mf.load_json(os.path.join(mf.BENCH_DIR, "configs",
                                    "gpt2-medium.json"))
    assert counts.kv_bytes_per_token(med) == 98_304


def test_roofline_picks_the_larger_bound():
    assert counts.roofline_seconds(100.0, 10.0, 10.0, 10.0) == (10.0, "flops")
    assert counts.roofline_seconds(10.0, 100.0, 10.0, 10.0) == (10.0, "bytes")


@pytest.mark.parametrize("efficiency", [1.0, 0.5, 0.028])
def test_a_share_of_the_roofline_cannot_pass_100_by_arithmetic(efficiency):
    """A kernel that ran exactly at the peak reads 100 %; any real kernel is
    slower than the least time, so reads below."""
    c = counts.flash_attention_call(8, 12, 1024, 64)
    least, bound = counts.roofline_seconds(
        c["fwd_flops"] + c["bwd_flops"], c["fwd_bytes"] + c["bwd_bytes"],
        197e12, 819e9)
    assert bound == "flops" and round(least * 1e3, 3) == 0.196
    measured = least / efficiency
    assert 100.0 * least / measured <= 100.0 + 1e-9
