"""Model FLOP/s utilization of the traced epochs: the benchmark's own count of
operations per token (``counts.train_flops_per_token``: 6 x matmul parameters
plus causal attention, nothing recomputed) times the tokens of the whole epoch
programs inside the traced window, over the time from the first one's start to
the last one's end (host gaps between them included) and the chips' bf16 peak."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(records, trace, env):
    if records.get("kind") != "train" or trace is None or not trace.devices:
        return None
    from benchmarks.lib import counts
    from benchmarks.lib.trace import module_runs
    runs = module_runs(trace.devices[0], trace.window,
                       records["epoch_programs"])
    if not runs:
        return None
    span_s = (max(e for _, e in runs) - min(s for s, _ in runs)) / 1e9
    tokens = len(runs) * records["tokens_per_epoch"]
    flops = tokens * counts.train_flops_per_token(env["cfg"],
                                                  records["seq_len"])
    peak = env["chips"] * env["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / span_s / peak
