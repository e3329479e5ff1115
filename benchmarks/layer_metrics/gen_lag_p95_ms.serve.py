"""95th percentile of how late the benchmark's own load generator submitted a
request after it was due: a starved generator must not read as a fast server."""

LAYER = "load generator"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "ttft_p95_ms"


def read(records, trace, env):
    if records.get("kind") != "serve" or not records["gen_lag_s"]:
        return None
    from benchmarks.lib.stats import percentile
    # never 0 on a real clock; floored so that a rehearsal prints a number
    return max(1000.0 * percentile(records["gen_lag_s"], 95), 1e-6)
