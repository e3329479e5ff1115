"""95th percentile of ``started_at - submitted_at`` over the window's requests:
the wait in the engine's admission queue, by the engine's own stamps."""

LAYER = "serving engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "ttft_p95_ms"


def read(records, trace, env):
    if records.get("kind") != "serve" or not records["queue_wait_s"]:
        return None
    from benchmarks.lib.stats import percentile
    return 1000.0 * percentile(records["queue_wait_s"], 95)
