"""Share of the traced span (a few steady seconds of the window) in which no
operation ran on the chip: the host between steps."""

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    if records.get("kind") != "serve" or trace is None:
        return None
    from benchmarks.lib.trace import busy_and_window_s
    busy, window = busy_and_window_s(trace)
    return 100.0 * (1.0 - busy / window) if window > 0 else None
