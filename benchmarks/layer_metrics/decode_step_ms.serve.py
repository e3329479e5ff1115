"""Device time of one decode step: the leaf operations that ran inside the
runs of the decode program (``decode_programs`` of the traffic file) in the
traced span, over the number of those runs, on the first chip."""

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    if records.get("kind") != "serve" or trace is None or not trace.devices:
        return None
    from benchmarks.lib import trace as T
    plane = trace.devices[0]
    runs = T.module_runs(plane, trace.window, records["decode_programs"])
    if not runs:
        return None
    return 1000.0 * T.ops_inside(plane, runs) / len(runs)
