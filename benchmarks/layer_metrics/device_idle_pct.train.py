"""Share of the traced window (a few warm epochs of the one ``train()`` call)
in which no operation ran on the chip, averaged over the chips."""

LAYER = "SPMD engine and device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(records, trace, env):
    if records.get("kind") != "train" or trace is None:
        return None
    from benchmarks.lib.trace import busy_and_window_s
    busy, window = busy_and_window_s(trace)
    return 100.0 * (1.0 - busy / window) if window > 0 else None
